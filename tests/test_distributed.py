"""Distributed semantics on 8 fake devices (subprocess: jax locks device
count at first init, so multi-device tests spawn a fresh interpreter)."""
import os
import subprocess
import sys
import textwrap

import pytest


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=560,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


def test_bcpnn_data_parallel_matches_single_device():
    """The paper's MPI scheme: shard_map/pjit DP training must be numerically
    identical to the single-device reference given the same global batch."""
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import StructuralPlasticityLayer, UnitLayout
        from repro.core.distributed import DataParallelTrainer

        pre, post = UnitLayout(8, 2), UnitLayout(4, 8)
        layer = StructuralPlasticityLayer(pre, post, fan_in=8, lam=0.05,
                                          init_jitter=1.0)
        st0 = layer.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.random((64, 16)), jnp.float32)

        # single-device reference
        st_ref = st0
        for _ in range(4):
            st_ref, _ = jax.jit(layer.train_batch)(st_ref, x)

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        for mode in ("shard_map", "pjit"):
            tr = DataParallelTrainer(mesh, mode=mode)
            step = tr.hidden_step(layer)
            st = tr.place_state(layer, st0)
            xg = jax.device_put(x, tr.batch_sharding())
            for _ in range(4):
                st = step(st, xg)
            np.testing.assert_allclose(
                np.asarray(jax.device_get(st.w)), np.asarray(st_ref.w),
                rtol=2e-4, atol=2e-5,
            )
            np.testing.assert_allclose(
                np.asarray(jax.device_get(st.marginals.cij)),
                np.asarray(st_ref.marginals.cij), rtol=2e-4, atol=1e-7,
            )
            print(mode, "OK")
    """)


def test_moe_psum_and_a2a_match_local():
    """The three MoE dispatch schemes agree (same routing, no drops)."""
    run_with_devices("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.models.moe import moe_init, moe_apply
        from repro.sharding.rules import ShardCtx

        cfg = get_smoke_config("moonshot-v1-16b-a3b")
        cfg = dataclasses.replace(cfg, d_model=32, n_experts=8, top_k=2,
                                  moe_d_ff=16, capacity_factor=8.0,
                                  n_shared_experts=1)
        params = moe_init(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((8, 16, 32)), jnp.float32)

        out_local, aux_local = moe_apply(
            params, x, dataclasses.replace(cfg, moe_impl="local"), ShardCtx())

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        ctx = ShardCtx(mesh=mesh)
        from jax.sharding import NamedSharding, PartitionSpec as P
        xg = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
        pg = jax.device_put(params, NamedSharding(mesh, P()))
        for impl in ("psum", "a2a"):
            cfg_i = dataclasses.replace(cfg, moe_impl=impl)
            with mesh:
                out, aux = jax.jit(
                    lambda p, x: moe_apply(p, x, cfg_i, ctx)
                )(pg, xg)
            np.testing.assert_allclose(
                np.asarray(jax.device_get(out)), np.asarray(out_local),
                rtol=2e-3, atol=2e-4,
            )
            # aux is computed from per-shard routing statistics (standard in
            # DP MoE): smaller per-shard token pools bias the f_e*P_e
            # estimator upward, so allow O(E/n_local) slack; the OUTPUT
            # equality above is the semantic check.
            np.testing.assert_allclose(float(aux), float(aux_local), rtol=1e-1)
            print(impl, "OK")
    """)


def test_sharded_train_step_matches_unsharded():
    """One LM train step under production-style shardings == unsharded."""
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.optim import AdamW
        from repro.sharding.rules import ShardCtx
        from jax.sharding import NamedSharding

        cfg = get_smoke_config("yi-9b")
        rng = np.random.default_rng(0)
        batch = {
            "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)), jnp.int32),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)), jnp.int32),
        }
        opt = AdamW(learning_rate=1e-2)

        def run(mesh):
            ctx = ShardCtx(mesh=mesh)
            m = build_model(cfg, ctx)
            params = m.init(jax.random.PRNGKey(0))
            ost = opt.init(params)
            step = m.make_train_step(opt, n_micro=2)
            if mesh is not None:
                from repro.sharding.rules import param_shardings
                ps = param_shardings(ctx, params, m.logical())
                params = jax.tree_util.tree_map(jax.device_put, params, ps)
                with mesh:
                    p2, _, metrics = jax.jit(step)(params, ost, batch)
            else:
                p2, _, metrics = jax.jit(step)(params, ost, batch)
            return jax.device_get(p2), float(metrics["loss"])

        p_ref, l_ref = run(None)
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        p_sh, l_sh = run(mesh)
        assert abs(l_ref - l_sh) < 1e-4, (l_ref, l_sh)
        for a, b in zip(jax.tree_util.tree_leaves(p_ref),
                        jax.tree_util.tree_leaves(p_sh)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-3, atol=5e-4)
        print("sharded == unsharded OK", l_ref)
    """)


def test_elastic_checkpoint_restore_across_meshes():
    """Save on a (4,2) mesh, restore on (2,4) and on 1 device — elastic."""
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np, tempfile, os
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import save_checkpoint, restore_checkpoint

        tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        mesh_a = jax.make_mesh((4, 2), ("data", "model"))
        sharded = jax.device_put(
            tree, {"w": NamedSharding(mesh_a, P("data", "model"))})
        with tempfile.TemporaryDirectory() as d:
            path = save_checkpoint(d, 1, sharded)
            mesh_b = jax.make_mesh((2, 4), ("data", "model"))
            out = restore_checkpoint(
                path, tree, {"w": NamedSharding(mesh_b, P("model", None))})
            np.testing.assert_array_equal(
                np.asarray(jax.device_get(out["w"])), np.asarray(tree["w"]))
            out1 = restore_checkpoint(path, tree)
            np.testing.assert_array_equal(
                np.asarray(out1["w"]), np.asarray(tree["w"]))
        print("elastic restore OK")
    """)


def test_scan_engine_data_parallel_matches_single_device():
    """The trainer decorates the compiled execution plan: a sharded scan
    epoch must match the single-device scan epoch, for one declarative model
    compiled under three ExecutionConfigs."""
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import (DenseLayer, ExecutionConfig, Network,
                                StructuralPlasticityLayer, UnitLayout,
                                onehot_layout)
        from repro.core.distributed import DataParallelTrainer
        from repro.data import complementary_code, mnist_like

        ds = mnist_like(n_train=256, n_test=32, n_features=16, seed=0)
        x, layout = complementary_code(ds.x_train)

        def build():
            hidden = UnitLayout(4, 8)
            net = Network(seed=0)
            net.add(StructuralPlasticityLayer(layout, hidden, fan_in=8,
                                              lam=0.05, init_jitter=1.0))
            net.add(DenseLayer(hidden, onehot_layout(10), lam=0.05))
            return net

        kw = dict(epochs_hidden=2, epochs_readout=2, batch_size=64)
        ref = build().compile(ExecutionConfig(engine="scan"))
        ref.fit((x, ds.y_train), **kw)

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        for mode in ("shard_map", "pjit"):
            tr = DataParallelTrainer(mesh, mode=mode)
            compiled = build().compile(ExecutionConfig(engine="scan", trainer=tr))
            compiled.fit((x, ds.y_train), **kw)
            for sr, st in zip(ref.state.layers, compiled.state.layers):
                np.testing.assert_allclose(
                    np.asarray(jax.device_get(st.w)), np.asarray(sr.w),
                    rtol=2e-4, atol=2e-5,
                )
                np.testing.assert_allclose(
                    np.asarray(jax.device_get(st.marginals.cij)),
                    np.asarray(sr.marginals.cij), rtol=2e-4, atol=1e-7,
                )
            print(mode, "OK")
    """)


# The parent formulation of a hidden batch: a unit-granular F×H mask built
# from hcu_mask every batch, multiplied into w by the rewire, the forward and
# the learning cycle.  The program keeps w masked instead and applies the
# receptive fields at HCU granularity; the two must agree bit for bit.
PARITY = """
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import StructuralPlasticityLayer, UnitLayout, learning, plasticity
    from repro.core.distributed import DataParallelTrainer, dp_learning_cycle, _local_post
    from repro.core.layers import LayerState
    from repro.runtime import hidden_epoch_fn
    from repro.runtime.epoch_engine import epoch_sharding

    MODE, MESH = {mode!r}, {mesh!r}
    pre, post = UnitLayout(12, 2), UnitLayout(4, 6)
    layer = StructuralPlasticityLayer(pre, post, fan_in=5, lam=0.2,
                                      mask_update_every=3, init_jitter=1.0,
                                      gain=4.0)
    st0 = layer.init(jax.random.PRNGKey(0))
    p = np.random.default_rng(0).random((10, 16, pre.n_hcu)).astype(np.float32)
    xs = jnp.asarray(np.stack([p, 1 - p], -1).reshape(10, 16, pre.n_units))

    def parent_rewire(s):
        def rewire(s):
            new = plasticity.update_mask(s.plast, s.marginals, pre, post)
            return LayerState(s.marginals, s.w * new.unit_mask(pre, post),
                              s.b, new, s.step)
        do = (s.step % layer.mask_update_every) == 0
        return jax.lax.cond(do, rewire, lambda s: s, s)

    def parent_step(s, xb):
        s = parent_rewire(s)
        mask = s.plast.unit_mask(pre, post)
        aj = learning.forward(xb, s.w, s.b, post, mask=mask, gain=4.0)
        marg, w, b = learning.learning_cycle(s.marginals, xb, aj, 0.2, 1.0,
                                             mask=mask)
        return LayerState(marg, w, b, s.plast, s.step + 1)

    if MODE == "one_device":
        new_step, ref_step, state, xs_in = None, parent_step, st0, xs
    else:
        mesh = jax.make_mesh(MESH, ("data", "model"))
        tr = DataParallelTrainer(mesh, mode=MODE)
        spec = tr._state_spec(layer, tr._can_shard_hidden(layer))
        sh = jax.tree_util.tree_map(lambda q: NamedSharding(tr.mesh, q), spec,
                                    is_leaf=lambda q: isinstance(q, P))
        new_step = tr.hidden_step(layer)
        if MODE == "pjit":
            ref_step = jax.jit(parent_step, in_shardings=(sh, tr.batch_sharding()),
                               out_shardings=sh)
        else:
            def local(s, xb):
                lpost = _local_post(post, s.w)
                mask = s.plast.unit_mask(pre, lpost)
                sup = xb @ (s.w * mask) + s.b
                aj = learning.hcu_softmax(sup * 4.0, lpost)
                marg, w, b = dp_learning_cycle(s.marginals, xb, aj, 0.2, 1.0,
                                               tr.baxes)
                return LayerState(marg, w * mask, b, s.plast, s.step + 1)
            body = jax.shard_map(local, mesh=tr.mesh,
                                 in_specs=(spec, P(tr.baxes, None)),
                                 out_specs=spec, check_vma=False)
            ref_step = lambda s, xb: body(jax.jit(parent_rewire)(s), xb)
        state = tr.place_state(layer, st0)
        xs_in = jax.device_put(xs, epoch_sharding(tr, 3))

    run = lambda step: hidden_epoch_fn(layer, step_fn=step,
                                       donate=False)(state, xs_in)
    got, want = run(new_step), run(ref_step)
    assert int(got.step) == 10  # rewires at steps 0, 3, 6 and 9
    assert (np.asarray(got.plast.hcu_mask) != np.asarray(st0.plast.hcu_mask)).any()
    names = ("ci", "cj", "cij", "w", "b", "hcu_mask")
    leaves = lambda s: (*s.marginals, s.w, s.b, s.plast.hcu_mask)
    for name, a, b in zip(names, leaves(got), leaves(want)):
        a, b = np.asarray(jax.device_get(a)), np.asarray(jax.device_get(b))
        assert a.shape == b.shape, name
        assert (a.view(np.uint32) == b.view(np.uint32)).all(), name
    print(MODE, MESH, "bit-identical")
"""


@pytest.mark.parametrize(
    "mode,mesh",
    [
        ("one_device", None),
        ("shard_map", (4, 1)),
        ("shard_map", (2, 2)),  # hidden axis sharded: local masks
        ("pjit", (4, 1)),
    ],
)
def test_hcu_masked_update_matches_unit_mask_formulation(mode, mesh):
    """An epoch crossing rewires that swap connections: the masked-w path
    equals the unit-mask formulation to the bit, on one device and under
    both trainers on four forced CPU devices."""
    run_with_devices(PARITY.format(mode=mode, mesh=mesh), n=4)
