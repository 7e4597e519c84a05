"""The comparison that decides ``correct`` for a training cell.

The program's hidden-layer state after its first epoch (``prog``) is set
beside the reference's state before (``init``) and after (``ref``) the same
epoch from the same seed.  A gain-4 softmax turns a rounding difference in
the support into a different winning unit now and then, and Hebbian learning
then follows the tipped winner, so per-unit values may not be compared
element by element.  Three numbers are compared instead, each relative to
how far the reference moved:

``ci_dev``
    ``|prog.ci - ref.ci| / |ref.ci - init.ci|``: the input marginal depends on
    the inputs alone, so no tip reaches it.  Catches wrong or missing rows,
    a wrong mean, a state left unchanged, and storage in a lower precision.
``cij_hcu_dev``
    The same for C_ij summed over each hidden hypercolumn's units, a
    ``(F, n_hcu)`` array.  The softmax makes each hypercolumn's activities
    sum to one, so these sums move like c_i whatever unit wins; they check
    the outer product, its EWMA and the softmax's normalisation.
``leaf_norm_gap``
    Over the leaves c_i, c_j, C_ij, w, b and the receptive-field mask, the
    worst ``| |prog - init| - |ref - init| | / |ref - init|``: the gap
    between how far each leaf moved in the program and in the reference.
    Which unit wins does not change how far a leaf moves, so this covers
    the forward, the softmax and the rewire as a whole.  A leaf the
    reference leaves unmoved is left out.

Norms are Frobenius norms over the whole leaf.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

NUMBERS = ("ci_dev", "cij_hcu_dev", "leaf_norm_gap")
LEAVES = ("ci", "cj", "cij", "w", "b", "mask")


def _norm(a) -> float:
    return float(jnp.linalg.norm(jnp.ravel(a).astype(jnp.float32)))


def _hcu_sums(cij, n_hcu: int):
    return cij.astype(jnp.float32).reshape(cij.shape[0], n_hcu, -1).sum(-1)


def numbers(init: dict, ref: dict, prog: dict, n_hcu: int) -> dict:
    """The compared numbers; every argument maps leaf names to arrays."""
    f32 = {k: jnp.asarray(v, jnp.float32) for k, v in init.items() if k in LEAVES}
    r32 = {k: jnp.asarray(v, jnp.float32) for k, v in ref.items() if k in LEAVES}
    p32 = {k: jnp.asarray(v, jnp.float32) for k, v in prog.items() if k in LEAVES}

    def dev(p, r, i):
        return _norm(p - r) / _norm(r - i)

    out = {
        "ci_dev": dev(p32["ci"], r32["ci"], f32["ci"]),
        "cij_hcu_dev": dev(_hcu_sums(p32["cij"], n_hcu),
                           _hcu_sums(r32["cij"], n_hcu),
                           _hcu_sums(f32["cij"], n_hcu)),
    }
    out["leaf_norm_gap"] = max(leaf_gaps(f32, r32, p32).values())
    return out


def leaf_gaps(init: dict, ref: dict, prog: dict) -> dict:
    """Per leaf the reference moves, ``| |prog - init| - |ref - init| |``
    over ``|ref - init|``."""
    gaps = {}
    for k in LEAVES:
        moved = _norm(ref[k] - init[k])
        if moved > 0.0:
            gaps[k] = abs(_norm(prog[k] - init[k]) - moved) / moved
    return gaps


def judge(values: dict, limits: dict) -> bool:
    """True when every number is finite and at or under its limit."""
    return all(np.isfinite(values[k]) and values[k] <= limits[k]
               for k in limits)
