"""Kernel microbenchmarks (beyond-paper): Pallas interpret-mode correctness
cost + the jnp reference path timings at paper-scale shapes, analytic TPU
roofline projections, and the fused-phase vs separate-ops comparison
(per-batch dispatch counts + interpret-mode step timings on CPU).

``--smoke`` runs the cheap structural rows only (dispatch counts + a tiny
interpret-mode fused/unfused step) — the CI guard that the fused path stays
a single pallas_call.  Agreement with the float32 reference is the tests'
job (tests/test_fused_phase.py).
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.bench_common import emit, time_fn
from repro.core import StructuralPlasticityLayer, UnitLayout, init_marginals
from repro.kernels import ops, ref
from repro.launch.mesh import V5E, peaks

# The roofline rows are projections for one v5e chip.
CHIP = peaks(V5E)


def _dispatch_rows(smoke: bool):
    """Per-batch kernel-dispatch counts of the hidden train step: the fused
    phase must lower exactly ONE pallas_call, the separate-ops path three."""
    pre, post = UnitLayout(12, 2), UnitLayout(4, 8)
    x = jnp.asarray(np.random.default_rng(0).random((32, 24)), jnp.float32)
    counts = {}
    for fused in (False, True):
        layer = StructuralPlasticityLayer(
            pre, post, fan_in=8, lam=0.05, use_kernels=True, fused_phase=fused
        )
        st = layer.init(jax.random.PRNGKey(0))
        counts[fused] = ops.count_pallas_calls(layer.train_batch, st, x)
    emit("phase_dispatches_separate", counts[False], "pallas calls/batch")
    emit("phase_dispatches_fused", counts[True], "pallas calls/batch",
         "forward+softmax+EWMA+weights in one kernel")
    assert counts[True] == 1, f"fused phase lowered {counts[True]} kernels"
    return counts


def _fused_step_rows(smoke: bool):
    """Interpret-mode wall time of one fused phase vs the separate-ops
    composition (correctness-path cost on CPU; the HBM-traffic model below
    is the TPU story)."""
    b, f, n_hcu, n_mcu = (16, 32, 4, 8) if smoke else (64, 128, 16, 16)
    h = n_hcu * n_mcu
    layout = UnitLayout(n_hcu, n_mcu)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.random((b, f)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((f, h)) * 0.1, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(h) * 0.1, jnp.float32)
    marg = init_marginals(f, h, key=jax.random.PRNGKey(1), jitter=0.5)

    def fused_step(m, xb, wb, bb):
        return ops.bcpnn_phase(m, xb, wb, bb, layout, 0.01, gain=2.0)

    def separate_step(m, xb, wb, bb):
        s = ops.masked_matmul(xb, wb, bb) * 2.0
        aj = ops.hcu_softmax(s, n_hcu, n_mcu)
        return ops.bcpnn_update(m, xb, aj, 0.01)

    iters = 1 if smoke else 3
    t_f = time_fn(fused_step, marg, x, w, bias, warmup=1, iters=iters)
    t_s = time_fn(separate_step, marg, x, w, bias, warmup=1, iters=iters)
    emit("phase_interpret_fused_s", t_f, "s", f"B={b} F={f} H={h}")
    emit("phase_interpret_separate_s", t_s, "s", "matmul+softmax+update")


def _traffic_rows(b: int, f: int, h: int):
    """Analytic HBM-traffic model: what the fused phase saves on a real TPU
    (the interpret-mode timings above measure emulation, not the target)."""
    flops = 2.0 * b * f * h * 2 + 8.0 * f * h  # fwd + outer product + epilogue
    # Separate ops: s and aj make full HBM round-trips between kernels, and
    # cij/w move once per kernel that touches them.
    sep = (
        (b * f + f * h + b * h) * 4       # matmul: x, w, s out
        + (b * h * 2) * 4                 # softmax: s in, aj out
        + (b * (f + h) + f * h * 3) * 4   # update: acts, cij r/w, w out
    )
    # Fused: x/w/cij in, aj/cij/w out — s never leaves VMEM, aj written once.
    fus = (b * f + f * h * 2) * 4 + (b * h + f * h * 2) * 4
    emit("phase_hbm_bytes_separate", sep, "B", f"B={b} F={f} H={h}")
    emit("phase_hbm_bytes_fused", fus, "B", "s stays in VMEM")
    emit("phase_fusion_saving", sep / fus, "x HBM traffic")
    emit("phase_tpu_mem_bound_s", fus / CHIP.hbm_bw, "s")
    emit("phase_tpu_cmp_bound_s", flops / CHIP.bf16_flops, "s")


def main(smoke: bool = False):
    _dispatch_rows(smoke)
    _fused_step_rows(smoke)

    # Paper MNIST scale: N_F=1568 (complementary 784), N_H=3000, B=256.
    b, f, h = 256, 1568, 3000
    _traffic_rows(b, f, h)
    if smoke:
        return

    rng = np.random.default_rng(0)
    ai = jnp.asarray(rng.random((b, f)), jnp.float32)
    aj = jnp.asarray(rng.random((b, h)), jnp.float32)
    marg = init_marginals(f, h, key=jax.random.PRNGKey(0), jitter=0.5)

    fused = jax.jit(
        lambda m, x, y: ref.bcpnn_update(x, y, m.ci, m.cj, m.cij, 0.01)
    )
    t = time_fn(fused, marg, ai, aj)
    flops = 2.0 * b * f * h + 8.0 * f * h  # outer product + EWMA/log epilogue
    emit("kernel_bcpnn_update_cpu_ref", flops / t / 1e9, "GFLOP/s", f"t={t:.4g}s")

    # Analytic TPU projection for the fused update kernel (per step, one chip):
    hbm_bytes = (f * h * 4) * 3 + (b * (f + h) * 4)  # cij r/w + w write + acts
    t_mem = hbm_bytes / CHIP.hbm_bw
    t_cmp = flops / CHIP.bf16_flops
    emit("kernel_bcpnn_update_tpu_mem_bound_s", t_mem, "s",
         "fused: 3x f*h HBM moves")
    emit("kernel_bcpnn_update_tpu_cmp_bound_s", t_cmp, "s")
    unfused = hbm_bytes + 2 * (f * h * 4)  # extra cij round-trip when unfused
    emit("kernel_fusion_saving", unfused / hbm_bytes, "x HBM traffic",
         "FPGA-style fusion benefit")


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true",
                   help="cheap CI rows: dispatch counts + tiny interpret step")
    main(smoke=p.parse_args().smoke)
