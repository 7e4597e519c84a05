"""Pallas TPU kernel: fused BCPNN marginal + weight update (Alg.1 L11-16).

This is the TPU re-design of the paper's FPGA accelerator, whose pipeline
keeps a C_ij tile resident in BRAM while the matrix engine accumulates the
batched outer product and a "network probability unit" applies the
EWMA + log-ratio epilogue.  Here the same fusion maps to the TPU memory
hierarchy:

  HBM -> VMEM : a_i/a_j batch tiles stream in; the (F_tile, H_tile) C_ij
                block is read once and stays in VMEM across all batch steps
                (output-block revisiting);
  MXU         : acc += a_i_tile^T @ a_j_tile   (the dominant GEMM);
  VPU epilogue: C_ij' = (1-λ)C_ij + (λ/B)acc,
                w = [log C_ij' - log c_i' - log c_j'] * mask   (masked Bayes),
                both written back exactly once.

Compared to the unfused jnp path this saves one full HBM round-trip of the
(N_F x N_H) C_ij and w tensors per cycle.

The c_i'/c_j' vector EWMAs and the bias also run *inside* the kernel now:
each batch tile contributes its row-sum to the resident (1, F_tile)/(1,
H_tile) output rows while it is in VMEM for the GEMM, so the activations are
read from HBM exactly once for both the outer product and the means.  With
``state_mantissa`` set (the quantized bf-state tier), the marginal traces
are RNE-rounded in the epilogue — fused `bf_round`, not a separate op — and
w/bias are derived from the rounded traces.  λ, B, k_B are compile-time
constants (λ changes never inside a run).

Grid layout: ``(H_tiles, F_tiles * B_chunks)`` with the step counter t
innermost; step t processes batch chunk c of F tile i, (i, c) =
divmod(t, nb).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bf_round import rne_round

EPS = 1e-8


def _kernel(
    nb: int,
    b_real: int,
    lam: float,
    inv_b: float,
    k_b: float,
    state_mantissa: Optional[int],
    ai_ref, aj_ref, cij_ref, ci_ref, cj_ref, mask_ref,
    cij_out_ref, w_ref, ci_out_ref, cj_out_ref, bias_ref,
):
    t = pl.program_id(1)
    one_m = 1.0 - lam
    i = t // nb   # F tile
    c = t % nb    # batch chunk

    ai = ai_ref[...].astype(jnp.float32)  # (bt, ft)
    aj = aj_ref[...].astype(jnp.float32)  # (bt, ht)

    # Chunk 0: seed the accumulators with the decayed old marginals.
    # cij/ci blocks are revisited per j (recomputed identically); the
    # cj/bias blocks stay resident for the whole j sweep, so cj is
    # seeded/accumulated only during F tile 0's chunk sweep.
    @pl.when(c == 0)
    def _():
        cij_out_ref[...] = one_m * cij_ref[...].astype(jnp.float32)
        ci_out_ref[...] = one_m * ci_ref[...].astype(jnp.float32)

    @pl.when((c == 0) & (i == 0))
    def _():
        cj_out_ref[...] = one_m * cj_ref[...].astype(jnp.float32)

    # MXU: contraction over the batch chunk; VPU: batch-mean row-sums.
    cij_out_ref[...] += (lam * inv_b) * jax.lax.dot_general(
        ai, aj, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    ci_out_ref[...] += lam * (jnp.sum(ai, axis=0, keepdims=True) / b_real)

    @pl.when(i == 0)
    def _():
        cj_out_ref[...] += lam * (
            jnp.sum(aj, axis=0, keepdims=True) / b_real
        )

    # Last chunk: (optional) state rounding + Bayes weight epilogue on
    # the resident tiles.
    @pl.when(c == nb - 1)
    def _():
        ci = ci_out_ref[...]
        cj = cj_out_ref[...]
        cij_new = cij_out_ref[...]
        if state_mantissa is not None:
            ci = rne_round(ci, state_mantissa)
            cj = rne_round(cj, state_mantissa)  # idempotent for i > 0
            cij_new = rne_round(cij_new, state_mantissa)
            cij_out_ref[...] = cij_new
            ci_out_ref[...] = ci

            @pl.when(i == 0)
            def _():
                cj_out_ref[...] = cj

        @pl.when(i == 0)
        def _():
            bias_ref[...] = k_b * jnp.log(jnp.maximum(cj, EPS))

        log_ci = jnp.log(jnp.maximum(ci, EPS)).reshape(ci.shape[1], 1)
        log_cj = jnp.log(jnp.maximum(cj, EPS))  # (1, ht)
        w = jnp.log(jnp.maximum(cij_new, EPS)) - log_ci - log_cj
        w_ref[...] = (w * mask_ref[...].astype(jnp.float32)).astype(w_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "lam", "k_b", "state_mantissa",
        "block_b", "block_f", "block_h", "interpret",
    ),
)
def bcpnn_update_fused(
    ai: jnp.ndarray,
    aj: jnp.ndarray,
    cij: jnp.ndarray,
    ci: jnp.ndarray,
    cj: jnp.ndarray,
    mask: jnp.ndarray,
    lam: float,
    k_b: float = 1.0,
    state_mantissa: Optional[int] = None,
    block_b: int = 128,
    block_f: int = 128,
    block_h: int = 128,
    interpret: bool = False,
):
    """Fused EWMA marginal update + masked weight/bias computation.

    ai (B, F), aj (B, H), cij (F, H), ci (F,), cj (H,), mask (F, H).
    Returns (ci', cj', cij', w, bias), all f32 — storage-dtype casts for the
    quantized-state tier are the wrapper's (ops.py) job.  Padding: batch with
    zeros (outer-product and row-sum contributions vanish), F/H to tile
    multiples with marginals at 1.0 (finite logs; sliced off).
    """
    b, f = ai.shape
    h = aj.shape[1]
    bt = min(block_b, b)
    ft = min(block_f, f)
    ht = min(block_h, h)
    bp = -(-b // bt) * bt
    fp = -(-f // ft) * ft
    hp = -(-h // ht) * ht

    ai_p = jnp.pad(ai, ((0, bp - b), (0, fp - f)))
    aj_p = jnp.pad(aj, ((0, bp - b), (0, hp - h)))
    cij_p = jnp.pad(cij, ((0, fp - f), (0, hp - h)), constant_values=1.0)
    ci_p = jnp.pad(ci, (0, fp - f), constant_values=1.0).reshape(1, fp)
    cj_p = jnp.pad(cj, (0, hp - h), constant_values=1.0).reshape(1, hp)
    mask_p = jnp.pad(mask.astype(jnp.float32), ((0, fp - f), (0, hp - h)))

    nb = bp // bt
    nf = fp // ft
    grid = (hp // ht, nf * nb)  # one step per (F tile, batch chunk)

    def upd_i(t):
        return t // nb

    def upd_c(t):
        return t % nb

    # jaxlint: allow[JL001] reason=lam/k_b are in static_argnames — Python floats at trace time, not device values
    lam_f, kb_f = float(lam), float(k_b)
    kernel = functools.partial(
        _kernel, nb, b, lam_f, 1.0 / b, kb_f, state_mantissa
    )
    cij_n, w, ci_n, cj_n, bias = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((fp, hp), jnp.float32),
            jax.ShapeDtypeStruct((fp, hp), jnp.float32),
            jax.ShapeDtypeStruct((1, fp), jnp.float32),
            jax.ShapeDtypeStruct((1, hp), jnp.float32),
            jax.ShapeDtypeStruct((1, hp), jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, ft), lambda j, t: (upd_c(t), upd_i(t))),  # ai
            pl.BlockSpec((bt, ht), lambda j, t: (upd_c(t), j)),         # aj
            pl.BlockSpec((ft, ht), lambda j, t: (upd_i(t), j)),  # cij (old)
            pl.BlockSpec((1, ft), lambda j, t: (0, upd_i(t))),   # ci (old)
            pl.BlockSpec((1, ht), lambda j, t: (0, j)),          # cj (old)
            pl.BlockSpec((ft, ht), lambda j, t: (upd_i(t), j)),  # mask
        ],
        out_specs=(
            pl.BlockSpec((ft, ht), lambda j, t: (upd_i(t), j)),  # cij' (acc)
            pl.BlockSpec((ft, ht), lambda j, t: (upd_i(t), j)),  # w
            pl.BlockSpec((1, ft), lambda j, t: (0, upd_i(t))),   # ci'
            pl.BlockSpec((1, ht), lambda j, t: (0, j)),          # cj'
            pl.BlockSpec((1, ht), lambda j, t: (0, j)),          # bias
        ),
        interpret=interpret,
    )(ai_p, aj_p, cij_p, ci_p, cj_p, mask_p)
    return ci_n[0, :f], cj_n[0, :h], cij_n[:f, :h], w[:f, :h], bias[0, :h]
