"""The compile-step DSL: ExecutionConfig/CompiledNetwork parity with the
legacy Network.fit shim, compile-time precision binding, cached predict,
whole-network save/load, streaming via the compiled object, partial_fit."""
import tempfile

import jax
import numpy as np
import pytest

from repro.core import (
    DenseLayer,
    ExecutionConfig,
    Network,
    StructuralPlasticityLayer,
    UnitLayout,
    onehot_layout,
)
from repro.data import complementary_code, mnist_like
from repro.precision import PrecisionPolicy


@pytest.fixture(scope="module")
def dataset():
    ds = mnist_like(n_train=512, n_test=128, n_features=32, seed=0)
    x, layout = complementary_code(ds.x_train)
    x_te, _ = complementary_code(ds.x_test)
    return ds, x, x_te, layout


def _build(layout, seed=0, precision=None):
    hidden = UnitLayout(4, 8)
    net = Network(seed=seed)
    net.add(
        StructuralPlasticityLayer(
            layout, hidden, fan_in=16, lam=0.05, init_jitter=1.0, gain=4.0,
            precision=precision,
        )
    )
    net.add(DenseLayer(hidden, onehot_layout(10), lam=0.05, precision=precision))
    return net


def _assert_layer_states_equal(states_a, states_b, exact=True):
    for sa, sb in zip(states_a, states_b):
        cmp = (
            np.testing.assert_array_equal
            if exact
            else lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
        )
        cmp(np.asarray(sa.w), np.asarray(sb.w))
        cmp(np.asarray(sa.b), np.asarray(sb.b))
        cmp(np.asarray(sa.marginals.cij), np.asarray(sb.marginals.cij))
        assert int(sa.step) == int(sb.step)


KW = dict(epochs_hidden=2, epochs_readout=2, batch_size=64)


class TestDeprecationShim:
    """fit(engine=..., trainer=..., readout=...) must warn and produce state
    identical to the equivalent compile()+fit() path, for both readouts."""

    @pytest.mark.parametrize("readout", ["bcpnn", "sgd"])
    @pytest.mark.parametrize("engine", ["scan", "batch"])
    def test_shim_warns_and_matches_compile(self, dataset, engine, readout):
        ds, x, _, layout = dataset

        legacy = _build(layout)
        with pytest.warns(DeprecationWarning, match="compile"):
            legacy.fit((x, ds.y_train), engine=engine, readout=readout, **KW)

        compiled = _build(layout).compile(ExecutionConfig(engine=engine))
        compiled.fit((x, ds.y_train), readout=readout, **KW)

        _assert_layer_states_equal(legacy.states, compiled.state.layers)
        if readout == "sgd":
            np.testing.assert_array_equal(
                np.asarray(legacy._sgd_readout["w"]),
                np.asarray(compiled.state.readout["w"]),
            )
            np.testing.assert_array_equal(
                np.asarray(legacy._sgd_readout["b"]),
                np.asarray(compiled.state.readout["b"]),
            )
        # The legacy predict/evaluate surface matches the compiled one.
        np.testing.assert_array_equal(
            np.asarray(legacy.predict(x[:64])),
            np.asarray(compiled.predict(x[:64])),
        )

    def test_unknown_engine_rejected_at_config(self):
        with pytest.raises(ValueError, match="engine"):
            ExecutionConfig(engine="warp")

    def test_unknown_readout_rejected(self, dataset):
        ds, x, _, layout = dataset
        compiled = _build(layout).compile(ExecutionConfig())
        with pytest.raises(ValueError, match="readout"):
            compiled.fit((x, ds.y_train), readout="psychic", **KW)


class TestCompileTimeBinding:
    def test_precision_binds_at_compile(self, dataset):
        """ExecutionConfig(precision=...) on a precision-free declaration
        must equal declaring the policy per layer (the legacy style)."""
        ds, x, _, layout = dataset
        pol = PrecisionPolicy.named("bf20")

        per_layer = _build(layout, precision=pol).compile(ExecutionConfig())
        per_layer.fit((x, ds.y_train), **KW)

        bound = _build(layout).compile(ExecutionConfig(precision="bf20"))
        bound.fit((x, ds.y_train), **KW)

        _assert_layer_states_equal(per_layer.state.layers, bound.state.layers)

    def test_compile_does_not_mutate_declaration(self, dataset):
        _, _, _, layout = dataset
        net = _build(layout)
        net.compile(ExecutionConfig(precision="bf16", use_kernels=True))
        assert net.layers[0].spec.precision is None
        assert net.layers[0].spec.use_kernels is False

    def test_initial_states_are_copied(self, dataset):
        """Compile must not alias the declarative Network's state buffers:
        the scan plan donates its carry on accelerators, so aliasing would
        invalidate network.states after the first fit (breaking the
        declare-once / compile-per-config pattern)."""
        ds, x, _, layout = dataset
        net = _build(layout)
        compiled = net.compile(ExecutionConfig())
        assert compiled.state.layers[0].w is not net.states[0].w
        compiled.fit((x, ds.y_train), **KW)
        assert int(net.states[0].step) == 0  # declaration untouched

    def test_bcpnn_refit_clears_stale_sgd_head(self, dataset):
        """A full fit(readout='bcpnn') supersedes a previously trained SGD
        head — predict must use the fresh DenseLayer readout."""
        ds, x, x_te, layout = dataset
        compiled = _build(layout).compile(ExecutionConfig())
        compiled.fit((x, ds.y_train), readout="sgd", **KW)
        assert compiled.state.readout is not None
        compiled.fit((x, ds.y_train), readout="bcpnn", **KW)
        assert compiled.state.readout is None
        ref = _build(layout).compile(ExecutionConfig())
        ref.fit((x, ds.y_train), **KW)
        # two bcpnn epochs on top of the earlier run differ, but the readout
        # now really is the DenseLayer: scores match its forward shape/kind
        assert compiled.predict(x_te[:8]).shape == ref.predict(x_te[:8]).shape

    def test_one_declaration_many_configs(self, dataset):
        """The same Network object can be compiled repeatedly; each
        CompiledNetwork starts from the same initial states."""
        ds, x, _, layout = dataset
        net = _build(layout)
        a = net.compile(ExecutionConfig(engine="scan"))
        b = net.compile(ExecutionConfig(engine="batch"))
        a.fit((x, ds.y_train), **KW)
        b.fit((x, ds.y_train), **KW)
        _assert_layer_states_equal(a.state.layers, b.state.layers, exact=False)


class TestPredictCache:
    def test_forward_built_once(self, dataset):
        """predict's jitted level-H head is built once per compile and
        never rebuilt across calls."""
        ds, x, x_te, layout = dataset
        compiled = _build(layout).compile(ExecutionConfig())
        compiled.fit((x, ds.y_train), **KW)
        compiled.predict(x_te[:32])
        head = compiled._head
        assert head is not None
        compiled.predict(x_te[:64])
        compiled.evaluate((x_te, ds.y_test))
        assert compiled._head is head  # no rebuild across calls

    def test_sgd_head_on_headless_network(self, dataset):
        """A network with no DenseLayer readout + SGD head: the head was
        trained on the FULL hidden stack, so predict must run every hidden
        layer before applying it."""
        ds, x, x_te, layout = dataset
        net = Network(seed=0).add(
            StructuralPlasticityLayer(
                layout, UnitLayout(4, 8), fan_in=16, lam=0.05, init_jitter=1.0
            )
        )
        compiled = net.compile(ExecutionConfig())
        compiled.fit((x, ds.y_train), readout="sgd", **KW)
        scores = compiled.predict(x_te[:16])
        assert scores.shape == (16, 10)
        # A later bcpnn fit has no DenseLayer to train here — it must NOT
        # drop the SGD head without a replacement.
        compiled.fit((x, ds.y_train), **KW)
        assert compiled.state.readout is not None
        assert compiled.predict(x_te[:4]).shape == (4, 10)

    def test_readout_switch_reuses_callable(self, dataset):
        """bcpnn -> sgd readout changes the state *schema*; the cached jit
        handles it via its own trace cache without a Python-level rebuild."""
        ds, x, x_te, layout = dataset
        compiled = _build(layout).compile(ExecutionConfig())
        compiled.fit((x, ds.y_train), **KW)
        s1 = compiled.predict(x_te[:16])
        compiled.fit((x, ds.y_train), readout="sgd", **KW)
        s2 = compiled.predict(x_te[:16])
        assert s1.shape == s2.shape


class TestSaveLoad:
    def test_roundtrip_bitexact(self, dataset):
        """evaluate() after load matches before save bit-for-bit, for both
        readout kinds; the shuffle RNG stream also resumes identically."""
        ds, x, x_te, layout = dataset
        for readout in ("bcpnn", "sgd"):
            src = _build(layout).compile(ExecutionConfig())
            src.fit((x, ds.y_train), readout=readout, **KW)
            with tempfile.TemporaryDirectory() as d:
                path = src.save(d, step=7)
                dst = _build(layout).compile(ExecutionConfig())
                dst.load(path)
                np.testing.assert_array_equal(
                    np.asarray(src.predict(x_te)), np.asarray(dst.predict(x_te))
                )
                assert src.evaluate((x_te, ds.y_test)) == dst.evaluate(
                    (x_te, ds.y_test)
                )
                np.testing.assert_array_equal(
                    src._epoch_indices(64, 512, True),
                    dst._epoch_indices(64, 512, True),
                )

    def test_load_rejects_wrong_architecture(self, dataset):
        ds, x, _, layout = dataset
        src = _build(layout).compile(ExecutionConfig())
        src.fit((x, ds.y_train), **KW)
        with tempfile.TemporaryDirectory() as d:
            path = src.save(d)
            other = Network(seed=0)
            other.add(
                StructuralPlasticityLayer(
                    layout, UnitLayout(2, 4), fan_in=16, init_jitter=1.0
                )
            )
            wrong = other.compile(ExecutionConfig())
            with pytest.raises(ValueError):
                wrong.load(path)

    def test_load_rejects_non_network_checkpoint(self, dataset):
        from repro.checkpoint import save_checkpoint

        ds, x, _, layout = dataset
        compiled = _build(layout).compile(ExecutionConfig())
        with tempfile.TemporaryDirectory() as d:
            path = save_checkpoint(d, 0, {"w": np.zeros(3)})
            with pytest.raises(ValueError, match="network checkpoint"):
                compiled.load(path)


class TestStreamingViaCompile:
    def test_sessions_share_cells_and_adopt_state(self, dataset):
        ds, x, _, layout = dataset
        compiled = _build(layout).compile(ExecutionConfig())
        s1 = compiled.streaming(max_batch=16)
        s2 = compiled.streaming(max_batch=8)
        for row in x[:32]:
            s1.feed(row)
        for row in x[32:48]:
            s2.feed(row)
        # Both sessions draw from the compiled network's one cell cache.
        assert compiled._stream_train_cells  # populated by the sessions
        st = s1.close()
        assert compiled.state.layers[0] is st  # adopted on close

    def test_compiled_cell_cache_is_shape_bounded(self, dataset):
        """The compiled-level cell cache is per-shape and LRU-bounded: many
        distinct micro-batch sizes cannot grow it past cache_size, and the
        same size re-uses the same jit wrapper across sessions."""
        _, x, _, layout = dataset
        compiled = _build(layout).compile(ExecutionConfig())
        sess = compiled.streaming(max_batch=64, cache_size=3)
        for b in (1, 2, 3, 4, 5):
            for row in x[:b]:
                sess.feed(row)
            sess.flush()
        lru = compiled._stream_train_cells[0]
        assert len(lru) <= 3 and lru.evictions >= 2
        # A second session with a seen size gets the SAME cell object.
        sess2 = compiled.streaming(max_batch=64, cache_size=3)
        for row in x[:5]:
            sess2.feed(row)
        sess2.flush()
        assert sess2._train_cells.get(5) is lru.get(5)

    def test_lru_bounds_cell_cache(self, dataset):
        """An adversarial burst pattern (many distinct micro-batch sizes)
        cannot grow the jit cache without limit."""
        from repro.core.streaming import StreamingSession

        _, x, _, layout = dataset
        layer = StructuralPlasticityLayer(
            layout, UnitLayout(4, 8), fan_in=16, init_jitter=1.0
        )
        sess = StreamingSession(
            layer, layer.init(jax.random.PRNGKey(0)), max_batch=64,
            cache_size=3,
        )
        for b in (1, 2, 3, 4, 5, 6, 1, 2):  # 6 distinct shapes, cap 3
            for row in x[:b]:
                sess.feed(row)
            sess.flush()
        stats = sess.stats
        assert stats["train_cache_size"] <= 3
        assert stats["cache_capacity"] == 3
        assert stats["cache_evictions"] >= 3
        assert stats["flushes"] == 8
        assert stats["samples_seen"] == 1 + 2 + 3 + 4 + 5 + 6 + 1 + 2

    def test_streaming_still_matches_batched(self, dataset):
        """The LRU refactor must not change EWMA semantics."""
        import jax.numpy as jnp

        _, x, _, layout = dataset
        layer = StructuralPlasticityLayer(
            layout, UnitLayout(4, 8), fan_in=16, lam=0.05, init_jitter=1.0
        )
        net = Network(seed=0).add(layer)
        compiled = net.compile(ExecutionConfig())
        st_b = compiled.state.layers[0]  # same init as the session's
        for i in range(0, 64, 16):
            st_b, _ = jax.jit(layer.train_batch)(st_b, jnp.asarray(x[i : i + 16]))
        sess = compiled.streaming(max_batch=16)
        for row in x[:64]:
            sess.feed(row)
        st_s = sess.close()
        np.testing.assert_allclose(
            np.asarray(st_s.w), np.asarray(st_b.w), rtol=1e-5, atol=1e-6
        )


class TestPartialFit:
    def test_incremental_chunks_advance_state(self, dataset):
        ds, x, _, layout = dataset
        compiled = _build(layout).compile(ExecutionConfig())
        for i in range(0, 256, 128):
            compiled.partial_fit(
                (x[i : i + 128], ds.y_train[i : i + 128]), batch_size=64,
                readout="bcpnn",
            )
        # 2 chunks x 2 batches each.
        assert int(compiled.state.layers[0].step) == 4
        assert int(compiled.state.layers[1].step) == 4

    def test_sgd_readout_persists_across_calls(self, dataset):
        ds, x, _, layout = dataset
        compiled = _build(layout).compile(ExecutionConfig())
        compiled.partial_fit((x[:128], ds.y_train[:128]), batch_size=64,
                             readout="sgd")
        w1 = np.asarray(compiled.state.readout["w"]).copy()
        compiled.partial_fit((x[:128], ds.y_train[:128]), batch_size=64,
                             readout="sgd")
        w2 = np.asarray(compiled.state.readout["w"])
        assert not np.array_equal(w1, w2)  # continued, not re-initialized

    def test_sgd_head_sized_from_declared_layout(self, dataset):
        """A first chunk missing the high classes must not lock the SGD head
        too narrow — jit would silently clamp later labels into the last
        class instead of erroring."""
        ds, x, _, layout = dataset
        low = ds.y_train < 5
        compiled = _build(layout).compile(ExecutionConfig())
        compiled.partial_fit((x[low][:64], ds.y_train[low][:64]),
                             batch_size=32, readout="sgd")
        assert compiled.state.readout["w"].shape[1] == 10  # declared width
        compiled.partial_fit((x[:64], ds.y_train[:64]), batch_size=32,
                             readout="sgd")
        assert compiled.predict(x[:8]).shape == (8, 10)

    def test_bcpnn_partial_fit_supersedes_sgd_head(self, dataset):
        """Incrementally training the BCPNN readout after an SGD fit must
        make the DenseLayer authoritative — not leave its work shadowed by
        the stale SGD head."""
        ds, x, _, layout = dataset
        compiled = _build(layout).compile(ExecutionConfig())
        compiled.fit((x, ds.y_train), readout="sgd", **KW)
        assert compiled.state.readout is not None
        compiled.partial_fit((x[:128], ds.y_train[:128]), batch_size=64,
                             readout="bcpnn")
        assert compiled.state.readout is None
        assert int(compiled.state.layers[1].step) == 2  # readout trained

    def test_hidden_only_when_no_readout_requested(self, dataset):
        ds, x, _, layout = dataset
        compiled = _build(layout).compile(ExecutionConfig())
        res = compiled.partial_fit((x[:128], ds.y_train[:128]), batch_size=64)
        assert res.epochs_readout == 0
        assert int(compiled.state.layers[1].step) == 0  # readout untouched
