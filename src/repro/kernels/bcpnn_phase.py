"""Pallas TPU mega-kernel: one full BCPNN training phase per batch.

This is the one-kernel training pipeline of the stream-based FPGA
accelerator (arXiv 2503.01561) mapped onto the TPU memory hierarchy: the
forward support GEMM, per-HCU softmax, batch means, EWMA marginal updates
(c_i / c_j / C_ij) and the Bayesian weight/bias epilogue all run in a single
grid pass, with the (F_tile, H_tile) C_ij block resident in VMEM.  Compared
to the three-dispatch composition (`masked_matmul` -> gain -> `hcu_softmax`
-> `bcpnn_update`) this eliminates the HBM round-trips of the support matrix
s and the activations a_j, and fuses the optional `bf_round` state
quantization into the epilogue instead of running it as a separate op.

Hypercolumn lane layout: inside the kernel every HCU owns whole 128-lane
groups — its n_mcu units followed by lane padding up to
``hcu_lanes(n_mcu)``.  The wrapper moves the H axis of w / C_ij / mask /
b / c_j into that layout and back.  So every H tile is a multiple of 128
lanes, an HCU never spans two tiles, and the softmax reads each HCU as an
aligned lane slice (no lane-splitting reshape, which Mosaic refuses).

Grid layout: ``(H_tiles, T)`` with the phase counter ``t`` innermost and
``T = F_tiles + 1 + F_tiles * B_chunks``.  For a fixed output tile column j:

  t in [0, nf)      forward: s_acc (scratch, full padded batch resident)
                    accumulates x_tile @ (w_tile * mask_tile) over F tiles;
  t == nf           softmax: bias add + gain, per-HCU softmax over its lane
                    group with the pad lanes at -inf, padded batch rows
                    zeroed; writes the a_j block (which stays resident for
                    the update steps);
  t > nf            update: step (i, c) = divmod(t - nf - 1, nb) processes
                    batch chunk c of F tile i; the epilogue at c == nb-1
                    applies state rounding and the masked Bayes weights.

λ, B, k_B, gain and the state mantissa width are compile-time constants.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bf_round import rne_round

EPS = 1e-8
LANES = 128
# Widest H tile: at B=256, F tile 128 the double-buffered w / C_ij / mask
# inputs, the C_ij' / w' outputs, the a_j block and the s scratch take
# about 5 MiB, well inside v5e's 16 MiB of scoped VMEM.
MAX_TILE_LANES = 512


def hcu_lanes(n_mcu: int) -> int:
    """Lanes one HCU occupies in the kernel layout: n_mcu rounded up to
    whole 128-lane groups."""
    return -(-n_mcu // LANES) * LANES


def hcus_per_tile(n_hcu: int, n_mcu: int) -> int:
    """HCUs per H tile: the largest divisor of n_hcu whose tile stays within
    MAX_TILE_LANES (one HCU when a single HCU is already wider)."""
    mp = hcu_lanes(n_mcu)
    return max(
        d for d in range(1, n_hcu + 1)
        if n_hcu % d == 0 and (d == 1 or d * mp <= MAX_TILE_LANES)
    )


def to_hcu_lanes(a, n_hcu: int, n_mcu: int, fill: float = 0.0):
    """(..., n_hcu*n_mcu) -> (..., n_hcu*hcu_lanes(n_mcu)), pad lanes at
    ``fill``."""
    mp = hcu_lanes(n_mcu)
    if mp == n_mcu:
        return a
    lead = a.shape[:-1]
    a = a.reshape(*lead, n_hcu, n_mcu)
    a = jnp.pad(
        a, [(0, 0)] * (len(lead) + 1) + [(0, mp - n_mcu)], constant_values=fill
    )
    return a.reshape(*lead, n_hcu * mp)


def from_hcu_lanes(a, n_hcu: int, n_mcu: int):
    """Inverse of :func:`to_hcu_lanes`: drop the pad lanes."""
    mp = hcu_lanes(n_mcu)
    if mp == n_mcu:
        return a
    lead = a.shape[:-1]
    return a.reshape(*lead, n_hcu, mp)[..., :n_mcu].reshape(
        *lead, n_hcu * n_mcu
    )


def _kernel(
    nf: int,
    nb: int,
    bt: int,
    b_real: int,
    lam: float,
    inv_b: float,
    k_b: float,
    gain: float,
    n_mcu: int,
    mp: int,
    has_mask: bool,
    state_mantissa: Optional[int],
    ai_full_ref, ai_ref, w_ref, bias_ref, cij_ref, ci_ref, cj_ref, mask_ref,
    aj_ref, cij_out_ref, w_out_ref, ci_out_ref, cj_out_ref, bias_out_ref,
    s_acc,
):
    t = pl.program_id(1)
    one_m = 1.0 - lam
    upd = t - (nf + 1)
    i = upd // nb   # F tile of the update step (valid when t > nf)
    c = upd % nb    # batch chunk of the update step (floor-mod, ditto)

    # ---- forward phase (t < nf): accumulate s = x @ (w * mask) ----
    @pl.when(t == 0)
    def _():
        s_acc[...] = jnp.zeros_like(s_acc)

    @pl.when(t < nf)
    def _():
        w = w_ref[...].astype(jnp.float32)
        if has_mask:
            w = w * mask_ref[...].astype(jnp.float32)
        s_acc[...] += jax.lax.dot_general(
            ai_full_ref[...].astype(jnp.float32),
            w,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    # ---- softmax phase (t == nf): a_j, kept resident for the update ----
    # Each HCU is one aligned group of mp lanes (see hcu_lanes); its pad
    # lanes go to -inf, so exp() gives them zero mass.
    @pl.when(t == nf)
    def _():
        bp, ht = s_acc.shape
        lane = jax.lax.broadcasted_iota(jnp.int32, (bp, mp), 1)
        # Padded batch rows went through the softmax as garbage; zero them so
        # they vanish from the means and the outer products below.
        row = jax.lax.broadcasted_iota(jnp.int32, (bp, mp), 0)
        for g in range(ht // mp):
            cols = slice(g * mp, (g + 1) * mp)
            s = s_acc[:, cols] + bias_ref[:, cols].astype(jnp.float32)
            if gain != 1.0:
                s = s * gain
            s = jnp.where(lane < n_mcu, s, -jnp.inf)
            e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
            a = e / jnp.sum(e, axis=-1, keepdims=True)
            aj_ref[:, cols] = jnp.where(row < b_real, a, 0.0)

    # ---- update phase (t > nf): EWMA marginals + weight epilogue ----
    @pl.when(t > nf)
    def _():
        ai = ai_ref[...].astype(jnp.float32)            # (bt, ft)
        if nb == 1:
            aj = aj_ref[...]                            # (bt, ht) f32
        else:
            aj = aj_ref[pl.ds(pl.multiple_of(c * bt, bt), bt), :]

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
                bias_out_ref[...] = k_b * jnp.log(jnp.maximum(cj, EPS))

            log_ci = jnp.log(jnp.maximum(ci, EPS)).reshape(ci.shape[1], 1)
            log_cj = jnp.log(jnp.maximum(cj, EPS))  # (1, ht)
            w = jnp.log(jnp.maximum(cij_new, EPS)) - log_ci - log_cj
            if has_mask:
                w = w * mask_ref[...].astype(jnp.float32)
            w_out_ref[...] = w


@functools.partial(
    jax.jit,
    static_argnames=(
        "lam", "k_b", "gain", "n_hcu", "n_mcu", "state_mantissa", "interpret",
    ),
)
def bcpnn_phase_fused(
    x: jnp.ndarray,
    w: jnp.ndarray,
    b: jnp.ndarray,
    cij: jnp.ndarray,
    ci: jnp.ndarray,
    cj: jnp.ndarray,
    mask: Optional[jnp.ndarray],
    lam: float,
    k_b: float,
    gain: float,
    n_hcu: int,
    n_mcu: int,
    state_mantissa: Optional[int] = None,
    interpret: bool = False,
):
    """One fused BCPNN training phase.

    x (B, F), w (F, H), b (H,), cij (F, H), ci (F,), cj (H,), mask (F, H) or
    None, with H = n_hcu * n_mcu.  Returns
    (aj (B, H), ci', cj', cij', w', bias') — all f32; state rounding (if
    ``state_mantissa``) is applied in the epilogue, storage-dtype casts are
    the wrapper's (ops.py) job.

    Padding: batch and F with zeros; H into the hypercolumn lane layout
    (:func:`to_hcu_lanes`) with w/bias/mask zero and marginals 1.0 on the
    pad lanes, so the logs stay finite.  The softmax gives pad lanes zero
    activation, and they are dropped on the way out.
    """
    bsz, f = x.shape
    ft = min(LANES, f)
    fp = -(-f // ft) * ft
    nf = fp // ft
    mp = hcu_lanes(n_mcu)
    hp = n_hcu * mp
    ht = hcus_per_tile(n_hcu, n_mcu) * mp
    bt = min(128, bsz)
    bp = -(-bsz // bt) * bt
    nb = bp // bt

    def lanes(a, fill=0.0):
        return to_hcu_lanes(a, n_hcu, n_mcu, fill)

    def pad_f(a, fill=0.0):
        return jnp.pad(a, ((0, fp - f), (0, 0)), constant_values=fill)

    x_p = jnp.pad(x, ((0, bp - bsz), (0, fp - f)))
    w_p = pad_f(lanes(w))
    b_p = lanes(b).reshape(1, hp)
    cij_p = pad_f(lanes(cij, 1.0), 1.0)
    ci_p = jnp.pad(ci, (0, fp - f), constant_values=1.0).reshape(1, fp)
    cj_p = lanes(cj, 1.0).reshape(1, hp)
    has_mask = mask is not None
    mask_p = (
        pad_f(lanes(mask.astype(jnp.float32)))
        if has_mask
        else jnp.ones((1, 1), jnp.float32)  # dummy operand, never read
    )

    # Phase counter t: F tiles of the forward sweep, the softmax step, then
    # one step per (F tile, batch chunk) of the update sweep.
    def fwd_f(t):
        return jnp.where(t < nf, t, 0)

    def upd_i(t):
        return jnp.clip((t - nf - 1) // nb, 0, nf - 1)

    def upd_c(t):
        return jnp.where(t > nf, (t - nf - 1) % nb, 0)

    def midx(t):
        return jnp.where(t < nf, t, upd_i(t))

    grid = (hp // ht, nf + 1 + nf * nb)
    # jaxlint: allow[JL001] reason=lam/k_b/gain are in static_argnames — Python floats at trace time, not device values
    lam_f, kb_f, gain_f = float(lam), float(k_b), float(gain)
    kernel = functools.partial(
        _kernel, nf, nb, bt, bsz, lam_f, 1.0 / bsz, kb_f,
        gain_f, n_mcu, mp, has_mask, state_mantissa,
    )
    aj, cij_n, w_n, ci_n, cj_n, bias_n = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((bp, hp), jnp.float32),  # aj
            jax.ShapeDtypeStruct((fp, hp), jnp.float32),  # cij'
            jax.ShapeDtypeStruct((fp, hp), jnp.float32),  # w'
            jax.ShapeDtypeStruct((1, fp), jnp.float32),   # ci'
            jax.ShapeDtypeStruct((1, hp), jnp.float32),   # cj'
            jax.ShapeDtypeStruct((1, hp), jnp.float32),   # bias'
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bp, ft), lambda j, t: (0, fwd_f(t))),   # x (fwd)
            pl.BlockSpec((bt, ft), lambda j, t: (upd_c(t), upd_i(t))),  # x (upd)
            pl.BlockSpec((ft, ht), lambda j, t: (fwd_f(t), j)),   # w
            pl.BlockSpec((1, ht), lambda j, t: (0, j)),           # bias
            pl.BlockSpec((ft, ht), lambda j, t: (upd_i(t), j)),   # cij
            pl.BlockSpec((1, ft), lambda j, t: (0, upd_i(t))),    # ci
            pl.BlockSpec((1, ht), lambda j, t: (0, j)),           # cj
            pl.BlockSpec((ft, ht), lambda j, t: (midx(t), j))
            if has_mask
            else pl.BlockSpec((1, 1), lambda j, t: (0, 0)),       # mask
        ],
        out_specs=(
            pl.BlockSpec((bp, ht), lambda j, t: (0, j)),          # aj
            pl.BlockSpec((ft, ht), lambda j, t: (upd_i(t), j)),   # cij'
            pl.BlockSpec((ft, ht), lambda j, t: (upd_i(t), j)),   # w'
            pl.BlockSpec((1, ft), lambda j, t: (0, upd_i(t))),    # ci'
            pl.BlockSpec((1, ht), lambda j, t: (0, j)),           # cj'
            pl.BlockSpec((1, ht), lambda j, t: (0, j)),           # bias'
        ),
        scratch_shapes=[pltpu.VMEM((bp, ht), jnp.float32)],
        interpret=interpret,
    )(x_p, x_p, w_p, b_p, cij_p, ci_p, cj_p, mask_p)
    return (
        from_hcu_lanes(aj[:bsz], n_hcu, n_mcu),
        ci_n[0, :f],
        from_hcu_lanes(cj_n[0], n_hcu, n_mcu),
        from_hcu_lanes(cij_n[:f], n_hcu, n_mcu),
        from_hcu_lanes(w_n[:f], n_hcu, n_mcu),
        from_hcu_lanes(bias_n[0], n_hcu, n_mcu),
    )
