"""jit'd public wrappers around the Pallas kernels.

This is the layer the rest of the framework imports (``repro.core.layers``
routes here when ``use_kernels=True``).  Responsibilities:

* backend dispatch: ``interpret=True`` when not running on a real TPU, so
  the kernels run (slowly, for tests) on the CPU and compile natively on
  the TPU target;
* shape plumbing between the framework's (MarginalState, UnitLayout) level
  and the kernels' raw-array level;
* the quantized-state tier: resolving ``state_format`` into the kernels'
  static mantissa width and casting the returned traces into the storage
  dtype (bf16 for mantissa <= 7, f32 otherwise).

``bcpnn_phase`` is the one-dispatch training path: forward, HCU softmax,
EWMA marginals and the weight/bias epilogue in a single kernel — the three
separate ops (``masked_matmul`` / ``hcu_softmax`` / ``bcpnn_update``) remain
as the unfused path.  Both are checked against the float32 jnp reference
(``ref.py``) under written tolerances, not against each other's bits.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import bcpnn_phase as _pk
from repro.kernels import bcpnn_update as _bk
from repro.kernels import bf_round as _bfk
from repro.kernels import hcu_softmax as _sk
from repro.kernels import masked_matmul as _mk


def _interpret() -> bool:
    # Deliberately uncached: caching the first answer would pin interpret
    # mode across a later jax.config platform change (e.g. a test forcing
    # cpu after a tpu init), silently running Pallas in the wrong mode.
    # jax caches the backend lookup itself, so this is cheap.
    return jax.default_backend() != "tpu"


def _state_spec(state_format) -> Tuple[Optional[int], Optional[jnp.dtype]]:
    """Resolve a ``state_format`` (None | name | BFFormat) into the kernels'
    static (mantissa_bits, storage_dtype) pair."""
    if state_format is None:
        return None, None
    from repro.precision.formats import get_format, state_spec

    fmt = (
        get_format(state_format)
        if isinstance(state_format, str)
        else state_format
    )
    return state_spec(fmt)


def hcu_softmax(s: jnp.ndarray, n_hcu: int, n_mcu: int) -> jnp.ndarray:
    return _sk.hcu_softmax(s, n_hcu=n_hcu, n_mcu=n_mcu, interpret=_interpret())


def masked_matmul(
    x: jnp.ndarray,
    w: jnp.ndarray,
    b: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    return _mk.masked_matmul(x, w, b, mask=mask, interpret=_interpret())


def bf_round(x: jnp.ndarray, mantissa_bits: int) -> jnp.ndarray:
    return _bfk.bf_round(x, mantissa_bits, interpret=_interpret())


def bcpnn_update(
    marginals,
    ai: jnp.ndarray,
    aj: jnp.ndarray,
    lam: float,
    k_b: float = 1.0,
    mask: Optional[jnp.ndarray] = None,
    state_format=None,
):
    """Full Alg.1 L11-16 cycle with the fused Pallas GEMM+epilogue kernel.

    marginals: repro.core.learning.MarginalState.  The vector EWMAs
    (c_i'/c_j') and the bias run inside the kernel alongside the C_ij GEMM;
    with ``state_format`` the traces come back rounded (and bf16-cast when
    the format fits).  Returns (new MarginalState, w, b) matching
    learning.learning_cycle.
    """
    from repro.core.learning import MarginalState

    mant, sdtype = _state_spec(state_format)
    m = (
        mask
        if mask is not None
        else jnp.ones((ai.shape[1], aj.shape[1]), jnp.float32)
    )
    ci, cj, cij, w, bias = _bk.bcpnn_update_fused(
        ai, aj, marginals.cij, marginals.ci, marginals.cj, m,
        lam=float(lam), k_b=float(k_b), state_mantissa=mant,
        interpret=_interpret(),
    )
    if sdtype is not None:
        ci, cj, cij = ci.astype(sdtype), cj.astype(sdtype), cij.astype(sdtype)
    return MarginalState(ci=ci, cj=cj, cij=cij), w, bias


def bcpnn_phase(
    marginals,
    x: jnp.ndarray,
    w: jnp.ndarray,
    b: jnp.ndarray,
    layout,
    lam: float,
    k_b: float = 1.0,
    gain: float = 1.0,
    mask: Optional[jnp.ndarray] = None,
    n_cycles: int = 1,
    state_format=None,
):
    """One whole BCPNN training phase (Alg.1 L8-16) in a single Pallas
    dispatch: forward support, per-HCU softmax, batch means, EWMA marginals
    and the weight/bias epilogue, with the C_ij tile resident in VMEM.

    marginals: MarginalState; x (B, F); w/b the layer's cached weights/bias;
    layout: the post UnitLayout.  Extra learning cycles (n_cycles > 1) reuse
    the first cycle's activations through the unfused update kernel, like
    the unfused path.  Returns (new MarginalState, w', b', aj).
    """
    from repro.core.learning import MarginalState

    mant, sdtype = _state_spec(state_format)
    aj, ci, cj, cij, w_n, bias = _pk.bcpnn_phase_fused(
        x, w, b, marginals.cij, marginals.ci, marginals.cj, mask,
        lam=float(lam), k_b=float(k_b), gain=float(gain),
        n_hcu=layout.n_hcu, n_mcu=layout.n_mcu,
        state_mantissa=mant, interpret=_interpret(),
    )
    if sdtype is not None:
        ci, cj, cij = ci.astype(sdtype), cj.astype(sdtype), cij.astype(sdtype)
    state = MarginalState(ci=ci, cj=cj, cij=cij)
    for _ in range(n_cycles - 1):
        state, w_n, bias = bcpnn_update(
            state, x, aj, lam, k_b=k_b, mask=mask, state_format=state_format,
        )
    return state, w_n, bias, aj


def count_pallas_calls(fn, *args, **kwargs) -> int:
    """Number of ``pallas_call`` equations in ``fn``'s jaxpr, recursing into
    sub-jaxprs (jit/scan/cond bodies).  This is the per-batch kernel-dispatch
    metric bench_kernels reports and tests assert on (fused phase == 1)."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return _count_pallas(closed.jaxpr)


def _count_pallas(jaxpr) -> int:
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            total += 1
        for val in eqn.params.values():
            total += sum(_count_pallas(j) for j in _subjaxprs(val))
    return total


def _subjaxprs(val):
    if hasattr(val, "jaxpr"):  # ClosedJaxpr
        yield val.jaxpr
    elif hasattr(val, "eqns"):  # raw Jaxpr
        yield val
    elif isinstance(val, (list, tuple)):
        for v in val:
            yield from _subjaxprs(v)
