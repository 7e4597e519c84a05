"""BCPNN core math: units, learning rule, plasticity (paper Alg. 1)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    StructuralPlasticityLayer,
    UnitLayout,
    batch_means,
    complementary_layout,
    hcu_softmax,
    init_marginals,
    learning_cycle,
    onehot_layout,
    update_marginals,
    weights_from_marginals,
)
from repro.core import plasticity
from repro.core.learning import forward


class TestUnitLayout:
    def test_blocked_flat_roundtrip(self):
        lo = UnitLayout(6, 5)
        x = jnp.arange(2 * 30, dtype=jnp.float32).reshape(2, 30)
        assert jnp.array_equal(lo.flat(lo.blocked(x)), x)

    def test_bad_sizes(self):
        with pytest.raises(ValueError):
            UnitLayout(0, 4)
        lo = UnitLayout(6, 5)
        with pytest.raises(ValueError):
            lo.blocked(jnp.zeros((2, 31)))

    def test_hcu_index(self):
        lo = UnitLayout(3, 2)
        assert list(np.asarray(lo.hcu_index())) == [0, 0, 1, 1, 2, 2]

    def test_shard_divisibility(self):
        UnitLayout(16, 4).validate_divisible_by(8)
        with pytest.raises(ValueError):
            UnitLayout(6, 4).validate_divisible_by(4)

    def test_named_layouts(self):
        assert complementary_layout(10).shape == (10, 2)
        assert onehot_layout(7).shape == (1, 7)


class TestLearning:
    def test_uniform_init_gives_zero_weights(self):
        pre, post = UnitLayout(4, 2), UnitLayout(3, 5)
        marg = init_marginals(8, 15, pre, post)
        w, b = weights_from_marginals(marg)
        np.testing.assert_allclose(np.asarray(w), 0.0, atol=1e-6)
        np.testing.assert_allclose(np.asarray(b), np.log(1 / 5), rtol=1e-5)

    def test_jitter_breaks_symmetry(self):
        pre, post = UnitLayout(4, 2), UnitLayout(3, 5)
        marg = init_marginals(8, 15, pre, post, key=jax.random.PRNGKey(0), jitter=1.0)
        w, _ = weights_from_marginals(marg)
        assert float(jnp.std(w)) > 0.1

    def test_ewma_fixed_point(self):
        # Repeatedly feeding the same batch must converge C to batch means.
        rng = np.random.default_rng(0)
        pre, post = UnitLayout(4, 2), UnitLayout(2, 4)
        ai = jnp.asarray(rng.dirichlet(np.ones(2), (16, 4)).reshape(16, 8), jnp.float32)
        aj = jnp.asarray(rng.dirichlet(np.ones(4), (16, 2)).reshape(16, 8), jnp.float32)
        marg = init_marginals(8, 8, pre, post)
        mi, mj, mij = batch_means(ai, aj)
        for _ in range(2000):
            marg = update_marginals(marg, mi, mj, mij, lam=0.05)
        np.testing.assert_allclose(np.asarray(marg.ci), np.asarray(mi), rtol=1e-4)
        np.testing.assert_allclose(np.asarray(marg.cij), np.asarray(mij), rtol=1e-4, atol=1e-6)

    def test_hcu_softmax_is_simplex(self):
        lo = UnitLayout(5, 7)
        s = jnp.asarray(np.random.default_rng(1).standard_normal((3, 35)), jnp.float32)
        a = hcu_softmax(s, lo)
        sums = lo.blocked(a).sum(-1)
        np.testing.assert_allclose(np.asarray(sums), 1.0, rtol=1e-5)
        assert float(a.min()) >= 0.0

    def test_forward_gain_sharpens(self):
        lo = UnitLayout(2, 8)
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.random((4, 6)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((6, 16)), jnp.float32)
        b = jnp.zeros((16,))
        a1 = forward(x, w, b, lo, gain=1.0)
        a4 = forward(x, w, b, lo, gain=4.0)
        ent = lambda a: float((-lo.blocked(a) * jnp.log(lo.blocked(a) + 1e-9)).sum(-1).mean())
        assert ent(a4) < ent(a1)

    def test_learning_cycle_mask_applied(self):
        pre, post = UnitLayout(4, 2), UnitLayout(2, 4)
        rng = np.random.default_rng(3)
        ai = jnp.asarray(rng.random((8, 8)), jnp.float32)
        aj = jnp.asarray(rng.random((8, 8)), jnp.float32)
        marg = init_marginals(8, 8, pre, post, key=jax.random.PRNGKey(0), jitter=0.5)
        mask = jnp.zeros((8, 8)).at[:, :4].set(1.0)
        _, w, _ = learning_cycle(marg, ai, aj, 0.1, mask=mask)
        assert float(jnp.abs(w[:, 4:]).max()) == 0.0
        assert float(jnp.abs(w[:, :4]).max()) > 0.0


class TestPlasticity:
    def _random_marginals(self, pre, post, seed=0):
        return init_marginals(
            pre.n_units, post.n_units, pre, post,
            key=jax.random.PRNGKey(seed), jitter=1.0,
        )

    def test_random_mask_fan_in(self):
        pre, post = UnitLayout(10, 2), UnitLayout(6, 3)
        st = plasticity.init_random_mask(jax.random.PRNGKey(0), pre, post, fan_in=4)
        np.testing.assert_array_equal(np.asarray(plasticity.fan_in(st)), 4.0)

    def test_update_preserves_fan_in(self):
        pre, post = UnitLayout(10, 2), UnitLayout(6, 3)
        st = plasticity.init_random_mask(jax.random.PRNGKey(0), pre, post, fan_in=4)
        marg = self._random_marginals(pre, post)
        for i in range(5):
            st = plasticity.update_mask(st, marg, pre, post)
            np.testing.assert_array_equal(np.asarray(plasticity.fan_in(st)), 4.0)
            assert set(np.unique(np.asarray(st.hcu_mask))) <= {0.0, 1.0}

    def test_swap_improves_or_keeps_score(self):
        pre, post = UnitLayout(8, 2), UnitLayout(4, 3)
        st = plasticity.init_random_mask(jax.random.PRNGKey(1), pre, post, fan_in=3)
        marg = self._random_marginals(pre, post, seed=2)
        scores = plasticity.mi_scores(marg, pre, post)
        before = (np.asarray(st.hcu_mask) * np.asarray(scores)).sum(0)
        st2 = plasticity.update_mask(st, marg, pre, post)
        after = (np.asarray(st2.hcu_mask) * np.asarray(scores)).sum(0)
        assert (after >= before - 1e-6).all()

    def test_unit_mask_expansion(self):
        pre, post = UnitLayout(2, 3), UnitLayout(2, 2)
        st = plasticity.PlasticityState(hcu_mask=jnp.asarray([[1.0, 0.0], [0.0, 1.0]]))
        m = st.unit_mask(pre, post)
        assert m.shape == (6, 4)
        np.testing.assert_array_equal(np.asarray(m[:3, :2]), 1.0)
        np.testing.assert_array_equal(np.asarray(m[:3, 2:]), 0.0)

    @pytest.mark.parametrize(
        "n_pre_hcu,n_pre_mcu,n_post_hcu,n_post_mcu,shard",
        [
            (6, 2, 3, 4, None),
            (5, 3, 40, 2, None),  # two words of receptive-field bits
            (4, 2, 70, 3, (10, 45)),  # a hidden shard of whole HCUs
        ],
    )
    def test_mask_weights_equals_unit_mask_product(
        self, n_pre_hcu, n_pre_mcu, n_post_hcu, n_post_mcu, shard
    ):
        """`mask_weights` is `w * unit_mask` to the bit, signed zeros too."""
        pre, post = UnitLayout(n_pre_hcu, n_pre_mcu), UnitLayout(n_post_hcu, n_post_mcu)
        rng = np.random.default_rng(n_post_hcu)
        hcu = (rng.random((n_pre_hcu, n_post_hcu)) < 0.5).astype(np.float32)
        st = plasticity.PlasticityState(hcu_mask=jnp.asarray(hcu))
        w = jnp.asarray(rng.standard_normal((pre.n_units, post.n_units)), jnp.float32)
        want = w * st.unit_mask(pre, post)
        if shard is not None:
            lo, hi = shard
            cols = slice(lo * n_post_mcu, hi * n_post_mcu)
            local = plasticity.PlasticityState(hcu_mask=st.hcu_mask[:, lo:hi])
            got, want = local.mask_weights(w[:, cols], pre, post), want[:, cols]
        else:
            got = jax.jit(st.mask_weights, static_argnums=(1, 2))(w, pre, post)
        np.testing.assert_array_equal(
            np.asarray(got).view(np.uint32), np.asarray(want).view(np.uint32)
        )


def _plasticity_layer(pre, post):
    return StructuralPlasticityLayer(
        pre, post, fan_in=pre.n_hcu // 2, lam=0.2, mask_update_every=3,
        init_jitter=1.0, gain=4.0,
    )


def _inputs(pre, batch, seed):
    """Complementary-coded rows: each input HCU's two units sum to one."""
    p = np.random.default_rng(seed).random((batch, pre.n_hcu)).astype(np.float32)
    return jnp.asarray(np.stack([p, 1.0 - p], -1).reshape(batch, pre.n_units))


def _blocks(state, pre, post):
    """w as (input HCU, hidden HCU, input MCU, hidden MCU) blocks."""
    w = np.asarray(state.w).reshape(pre.n_hcu, pre.n_mcu, post.n_hcu, post.n_mcu)
    return w.transpose(0, 2, 1, 3)


def _silent_blocks(state, pre, post):
    """w's entries on silent (input HCU, hidden HCU) blocks, and on active."""
    silent = np.asarray(state.plast.hcu_mask) == 0.0
    blocks = _blocks(state, pre, post)
    return blocks[silent], blocks[~silent]


class TestMaskedWeightsInvariant:
    """`w` is zero on every silent (input HCU, hidden HCU) block after each
    writer: init, a learning cycle, and a rewire that swaps a connection."""

    pre, post = UnitLayout(12, 2), UnitLayout(4, 5)

    def _layer(self):
        return _plasticity_layer(self.pre, self.post)

    def test_after_init_and_learning_cycles(self):
        layer = self._layer()
        state = layer.init(jax.random.PRNGKey(0))
        silent, active = _silent_blocks(state, self.pre, self.post)
        assert silent.size and not silent.any() and active.any()
        x = _inputs(self.pre, 8, seed=1)
        step = jax.jit(layer.train_batch)
        for _ in range(5):  # crosses the rewire at step 3
            state, _ = step(state, x)
            silent, active = _silent_blocks(state, self.pre, self.post)
            assert not silent.any() and active.any()

    def test_after_a_rewire_that_swaps(self):
        layer = self._layer()
        state = layer.init(jax.random.PRNGKey(0))
        step = jax.jit(layer.train_batch)
        rewire = jax.jit(layer.maybe_update_mask)
        for i in range(60):
            state, _ = step(state, _inputs(self.pre, 8, seed=i))
            if int(state.step) % layer.mask_update_every:
                continue
            rewired = rewire(state)
            old = np.asarray(state.plast.hcu_mask)
            new = np.asarray(rewired.plast.hcu_mask)
            if (old != new).any():
                break
        else:
            pytest.fail("no rewire swapped a connection")
        silenced, activated = (old > new), (new > old)

        def blocks(s):
            return _blocks(s, self.pre, self.post)

        # The silenced block held weights and is now zero; the activated
        # block was silent, so it is zero until the next learning cycle.
        assert blocks(state)[silenced].any()
        assert not blocks(rewired)[silenced].any()
        assert not blocks(rewired)[activated].any()
        assert not _silent_blocks(rewired, self.pre, self.post)[0].any()
        # train_batch rewires the same way, then the learning cycle writes
        # the activated block and keeps every silent block zero.
        learned, _ = step(state, _inputs(self.pre, 8, seed=99))
        np.testing.assert_array_equal(np.asarray(learned.plast.hcu_mask), new)
        assert blocks(learned)[activated].any()
        assert not blocks(learned)[silenced].any()
        assert not _silent_blocks(learned, self.pre, self.post)[0].any()
