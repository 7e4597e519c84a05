"""Fused one-kernel BCPNN phase: reference agreement, dispatch counts,
bf-state tier.

The contract under test: ``bcpnn_phase`` — forward + HCU softmax + EWMA
marginals + weight/bias epilogue in ONE Pallas dispatch — and the unfused
kernel composition (``masked_matmul`` -> ``hcu_softmax`` -> ``bcpnn_update``)
each agree with the float32 jnp reference (``kernels/ref.py``) in interpret
mode, across tile-divisible and non-divisible shapes, with and without the
quantized bf-state tier.  The two kernel paths tile their sums differently,
so they are held to the reference under written tolerances, not to each
other's bits.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (
    DenseLayer,
    Network,
    StructuralPlasticityLayer,
    UnitLayout,
)
from repro.core.compiled import ExecutionConfig
from repro.core.learning import MarginalState
from repro.kernels import ops, ref
from repro.precision import PrecisionPolicy

RNG = np.random.default_rng(7)

# (B, F, n_hcu, n_mcu): tile-aligned, everything-prime, H-tile-splitting
# (n_mcu > 128 lanes), multi-tile on every axis, and batch > one chunk.
SHAPES = [
    (32, 64, 4, 16),
    (13, 17, 3, 7),
    (64, 200, 2, 129),
    (130, 300, 20, 16),
    (257, 140, 2, 70),
]

# Tolerances against the float32 reference.  The kernels sum the forward
# GEMM and the batch outer product in F- and batch-tile order, XLA in its
# own; with F, B <= 300 and O(1) terms the reassociation error is a few
# hundred float32 ulps of the sums at most.  a_j and w pass that through a
# softmax and a log, so they get 1e-4 relative; C_ij is an EWMA whose
# batch term is scaled by lam, so it keeps 1e-5; the bias is k_b*log(c_j)
# and passes through 0, so it needs an absolute floor.
TOL = {
    "aj": dict(rtol=1e-4, atol=1e-6),
    "ci": dict(rtol=1e-5, atol=1e-7),
    "cj": dict(rtol=1e-5, atol=1e-7),
    "cij": dict(rtol=1e-5, atol=1e-7),
    "w": dict(rtol=1e-4, atol=1e-5),
    "bias": dict(rtol=1e-4, atol=1e-5),
}
# bf16 state: the traces are rounded to 8 significant bits, and a trace
# that lands within reassociation error of a rounding boundary may round
# the other way: one bf16 ulp (2^-8 relative).  w = log C_ij - log c_i -
# log c_j then moves by at most three such ulps in absolute terms.
BF16_TOL = {
    "cij": dict(rtol=2.0 ** -8, atol=0.0),
    "w": dict(rtol=0.0, atol=3 * 2.0 ** -8),
    "bias": dict(rtol=0.0, atol=2.0 ** -8),
}


def _problem(B, F, n_hcu, n_mcu, use_mask=True):
    H = n_hcu * n_mcu
    x = jnp.asarray(RNG.random((B, F)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((F, H)) * 0.1, jnp.float32)
    b = jnp.asarray(RNG.standard_normal(H) * 0.1, jnp.float32)
    marg = MarginalState(
        ci=jnp.asarray(RNG.random(F) * 0.5 + 0.25, jnp.float32),
        cj=jnp.asarray(RNG.random(H) * 0.5 + 0.25, jnp.float32),
        cij=jnp.asarray(RNG.random((F, H)) * 0.25 + 0.1, jnp.float32),
    )
    mask = (
        jnp.asarray(RNG.random((F, H)) > 0.3, jnp.float32) if use_mask else None
    )
    return x, w, b, marg, mask, UnitLayout(n_hcu=n_hcu, n_mcu=n_mcu)


def _unfused(x, w, b, marg, mask, layout, lam, k_b, gain, state_format=None):
    """The unfused kernel composition layers.py runs."""
    s = ops.masked_matmul(x, w, b, mask=mask)
    if gain != 1.0:
        s = s * gain
    aj = ops.hcu_softmax(s, layout.n_hcu, layout.n_mcu)
    st, w_n, b_n = ops.bcpnn_update(
        marg, x, aj, lam, k_b=k_b, mask=mask, state_format=state_format,
    )
    return st, w_n, b_n, aj


def _assert_paths_match_ref(x, w, b, marg, mask, layout, lam, k_b, gain,
                            names=("aj", "ci", "cj", "cij", "w", "bias")):
    """Fused and unfused kernel paths, each against the f32 reference."""
    r = ref.bcpnn_phase(
        x, w, b, marg.ci, marg.cj, marg.cij, lam, layout.n_hcu, layout.n_mcu,
        k_b=k_b, gain=gain, mask=mask,
    )
    want = dict(zip(("aj", "ci", "cj", "cij", "w", "bias"), r))
    paths = {
        "fused": ops.bcpnn_phase(
            marg, x, w, b, layout, lam, k_b=k_b, gain=gain, mask=mask
        ),
        "unfused": _unfused(x, w, b, marg, mask, layout, lam, k_b, gain),
    }
    for path, (st, w_n, b_n, aj) in paths.items():
        got = dict(aj=aj, ci=st.ci, cj=st.cj, cij=st.cij, w=w_n, bias=b_n)
        for name in names:
            np.testing.assert_allclose(
                np.asarray(got[name]), np.asarray(want[name]), **TOL[name],
                err_msg=f"{path} {name} vs f32 reference",
            )


class TestFusedBitParity:
    @pytest.mark.parametrize("B,F,n_hcu,n_mcu", SHAPES)
    def test_bitwise_vs_unfused(self, B, F, n_hcu, n_mcu):
        x, w, b, marg, mask, layout = _problem(B, F, n_hcu, n_mcu)
        _assert_paths_match_ref(x, w, b, marg, mask, layout, 0.01, 0.9, 1.3)

    def test_bitwise_no_mask(self):
        x, w, b, marg, mask, layout = _problem(13, 17, 3, 7, use_mask=False)
        _assert_paths_match_ref(
            x, w, b, marg, None, layout, 0.05, 1.0, 1.0,
            names=("w", "aj", "cij"),
        )

    @pytest.mark.parametrize("B,F,n_hcu,n_mcu", SHAPES[:3])
    def test_matches_ref(self, B, F, n_hcu, n_mcu):
        x, w, b, marg, mask, layout = _problem(B, F, n_hcu, n_mcu)
        lam, k_b, gain = 0.01, 0.9, 1.3
        st_f, w_f, b_f, aj_f = ops.bcpnn_phase(
            marg, x, w, b, layout, lam, k_b=k_b, gain=gain, mask=mask
        )
        aj_r, ci_r, cj_r, cij_r, w_r, b_r = ref.bcpnn_phase(
            x, w, b, marg.ci, marg.cj, marg.cij, lam, n_hcu, n_mcu,
            k_b=k_b, gain=gain, mask=mask,
        )
        np.testing.assert_allclose(np.asarray(aj_f), np.asarray(aj_r), **TOL["aj"])
        np.testing.assert_allclose(np.asarray(st_f.cij), np.asarray(cij_r), **TOL["cij"])
        np.testing.assert_allclose(np.asarray(w_f), np.asarray(w_r), **TOL["w"])
        np.testing.assert_allclose(np.asarray(b_f), np.asarray(b_r), **TOL["bias"])

    def test_bf16_state_bitwise_vs_unfused(self):
        """The quantized-state epilogue of both kernel paths must agree with
        the reference's rounded traces, and both must return the storage
        dtype."""
        x, w, b, marg, mask, layout = _problem(13, 17, 3, 7)
        lam, k_b, gain = 0.02, 0.8, 1.1
        _, _, _, cij_r, w_r, b_r = ref.bcpnn_phase(
            x, w, b, marg.ci, marg.cj, marg.cij, lam, 3, 7,
            k_b=k_b, gain=gain, mask=mask, state_mantissa=7,
        )
        paths = {
            "fused": ops.bcpnn_phase(
                marg, x, w, b, layout, lam, k_b=k_b, gain=gain, mask=mask,
                state_format="bf16",
            ),
            "unfused": _unfused(
                x, w, b, marg, mask, layout, lam, k_b, gain,
                state_format="bf16",
            ),
        }
        for path, (st, w_n, b_n, _) in paths.items():
            assert st.cij.dtype == jnp.bfloat16, path
            for name, got, want in [
                ("cij", st.cij.astype(jnp.float32), cij_r),
                ("w", w_n, w_r), ("bias", b_n, b_r),
            ]:
                np.testing.assert_allclose(
                    np.asarray(got), np.asarray(want), **BF16_TOL[name],
                    err_msg=f"{path} {name} vs rounded f32 reference",
                )


def _build():
    net = Network(seed=0)
    net.add(
        StructuralPlasticityLayer(
            UnitLayout(12, 2), UnitLayout(5, 6), fan_in=8, lam=0.05
        )
    )
    net.add(DenseLayer(UnitLayout(5, 6), UnitLayout(1, 3), lam=0.05))
    return net


_X = RNG.random((96, 24)).astype(np.float32)
_Y = RNG.integers(0, 3, 96)


class TestFusedFit:
    @pytest.mark.parametrize("engine", ["scan", "batch"])
    def test_whole_fit_bitwise_parity(self, engine):
        """fused_phase=True and the unfused kernels through
        CompiledNetwork.fit: learned state and predictions must match the
        float32 jnp path (use_kernels=False).  Six hidden batches compound
        the per-batch reassociation error, so w and C_ij get the per-call
        TOL; the readout sees it only through a_j."""
        def run(**kw):
            c = _build().compile(ExecutionConfig(engine=engine, **kw))
            c.fit((_X, _Y), epochs_hidden=2, epochs_readout=2, batch_size=32,
                  shuffle=False)
            return {
                "w": np.asarray(c.state.layers[0].w),
                "cij": np.asarray(c.state.layers[0].marginals.cij),
                "scores": np.asarray(c.predict(_X)),
            }

        want = run(use_kernels=False)
        tol = dict(TOL, scores=dict(rtol=1e-5, atol=1e-6))
        for fused in (False, True):
            got = run(use_kernels=True, fused_phase=fused)
            for name in ("w", "cij", "scores"):
                np.testing.assert_allclose(
                    got[name], want[name], **tol[name],
                    err_msg=f"{engine} fused={fused}: {name} vs jnp path",
                )

    def test_single_dispatch(self):
        """The fused hidden train step lowers exactly ONE pallas_call; the
        unfused kernel path needs three."""
        c = _build().compile(ExecutionConfig(fused_phase=True))
        lyr, st = c.hidden_layers[0], c.state.layers[0]
        xb = jnp.asarray(_X[:32])
        assert ops.count_pallas_calls(lyr.train_batch, st, xb) == 1
        c0 = _build().compile(ExecutionConfig(use_kernels=True))
        l0 = c0.hidden_layers[0]
        assert ops.count_pallas_calls(
            l0.train_batch, c0.state.layers[0], xb
        ) == 3

    def test_config_validation(self):
        with pytest.raises(ValueError, match="use_kernels"):
            ExecutionConfig(fused_phase=True, use_kernels=False)
        with pytest.raises(ValueError, match="datapath"):
            ExecutionConfig(fused_phase=True, precision="bf20")
        # fused_phase auto-enables the kernels.
        assert ExecutionConfig(fused_phase=True).use_kernels is True
        # Spec-level guard (direct layer construction).
        from repro.core.layers import BCPNNLayerSpec

        with pytest.raises(ValueError, match="use_kernels"):
            BCPNNLayerSpec(
                pre=UnitLayout(2, 2), post=UnitLayout(2, 2), fused_phase=True
            )


class TestQuantizedStateTier:
    POLICY = PrecisionPolicy.named("fp32", state_format="bf16")

    def test_compile_casts_and_fit_keeps_bf16(self):
        c = _build().compile(
            ExecutionConfig(fused_phase=True, precision=self.POLICY)
        )
        assert c.state.layers[0].marginals.ci.dtype == jnp.bfloat16
        c.fit((_X, _Y), epochs_hidden=1, epochs_readout=1, batch_size=32,
              shuffle=False)
        assert c.state.layers[0].marginals.cij.dtype == jnp.bfloat16
        # Weights stay full precision (derived, not stored state).
        assert c.state.layers[0].w.dtype == jnp.float32

    def test_save_load_roundtrip(self, tmp_path):
        cfg = ExecutionConfig(fused_phase=True, precision=self.POLICY)
        c = _build().compile(cfg)
        c.fit((_X, _Y), epochs_hidden=1, epochs_readout=1, batch_size=32,
              shuffle=False)
        # fit cached _X's hidden projection in 32-row chunks; predict
        # projects in 1024-row chunks, and XLA's float32 GEMM may round
        # differently per chunk height.  Drop the cache so both networks
        # project _X the same way from their states.
        c.activations.invalidate()
        before = np.asarray(c.predict(_X))
        path = c.save(str(tmp_path))
        c2 = _build().compile(cfg)
        c2.load(path)
        assert c2.state.layers[0].marginals.cij.dtype == jnp.bfloat16
        np.testing.assert_array_equal(before, np.asarray(c2.predict(_X)))
