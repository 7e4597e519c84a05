"""Structural (dynamic) plasticity: mutual-information-driven rewiring.

The paper's third innovation: the input->hidden connectivity is sparse and
*evolves*.  Connections are at (input-HCU, hidden-HCU) granularity — an
input HCU is either part of a hidden HCU's receptive field or silenced.
Every N_HCU batches each hidden HCU:

  1. scores every input HCU by the mutual information its units carry about
     the hidden HCU's units,   MI(I,H) = sum_{i in I, j in H} cij log(cij/(ci cj))
  2. finds its weakest *active* input and strongest *silent* input,
  3. swaps them if the silent one scores strictly higher (greedy,
     fixed fan-in — "the total number of active incoming connections is
     fixed", Sec.2).

The mask is stored and updated at HCU granularity (n_pre_hcu, n_post_hcu) —
exactly the receptive-field semantics of [26] — and applied to w (Alg.1 L16)
by `PlasticityState.mask_weights`, whose unit-granular factor the compiler
fuses into the pass that writes w.  `unit_mask` materializes the
(n_pre_units, n_post_units) array for the kernel and reduced-precision paths,
which take it as an operand.

Everything is vmapped/argmax-based so it jits cleanly; the update runs
infrequently (the paper notes it is "not the primary candidate for
performance optimization") so clarity wins over micro-optimization here.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.learning import EPS, MarginalState
from repro.core.units import UnitLayout


class PlasticityState(NamedTuple):
    """hcu_mask: (n_pre_hcu, n_post_hcu) float {0,1} — receptive fields."""

    hcu_mask: jnp.ndarray

    def unit_mask(self, pre: UnitLayout, post: UnitLayout) -> jnp.ndarray:
        """Expand the HCU-granular mask to unit granularity for w."""
        m = jnp.repeat(self.hcu_mask, pre.n_mcu, axis=0)
        return jnp.repeat(m, post.n_mcu, axis=1)

    def mask_weights(
        self, w: jnp.ndarray, pre: UnitLayout, post: UnitLayout
    ) -> jnp.ndarray:
        """`w * unit_mask(pre, post)`, bit for bit, with no unit-granular
        mask built.

        Each input unit's row of receptive fields is packed into bits, 32
        hidden HCUs a word, and each element of `w` reads its hidden HCU's
        bit: broadcasts, shifts and selects that the compiler fuses into the
        pass that reads or writes `w`.  `unit_mask`'s column repeat is
        instead a relayout of a whole F×H array.  `w` may be a hidden shard
        (whole HCUs) with this state's matching shard of the mask.
        """
        n_pre_hcu, n_post_hcu = self.hcu_mask.shape
        n_words = -(-n_post_hcu // 32)
        lane = jnp.arange(n_words * 32, dtype=jnp.uint32) % 32
        active = jnp.pad(self.hcu_mask > 0.5, ((0, 0), (0, n_words * 32 - n_post_hcu)))
        words = jnp.where(active, jnp.left_shift(jnp.uint32(1), lane), jnp.uint32(0))
        words = words.reshape(n_pre_hcu, n_words, 32).sum(-1, dtype=jnp.uint32)
        words = jnp.repeat(words, pre.n_mcu, axis=0)  # (n_pre_units, n_words)
        col = jnp.arange(w.shape[1], dtype=jnp.uint32) // post.n_mcu
        word = words[:, :1]
        for i in range(1, n_words):
            word = jnp.where(col // 32 == i, words[:, i : i + 1], word)
        on = jnp.right_shift(word, col % 32) & jnp.uint32(1)
        return w * on.astype(w.dtype)


def init_random_mask(
    key: jax.Array, pre: UnitLayout, post: UnitLayout, fan_in: int
) -> PlasticityState:
    """Random initial receptive fields: each hidden HCU gets `fan_in`
    distinct active input HCUs ("Initially, we randomly set the plasticity")."""
    if not (0 < fan_in <= pre.n_hcu):
        raise ValueError(f"fan_in={fan_in} out of range (1..{pre.n_hcu})")

    def one_column(k):
        perm = jax.random.permutation(k, pre.n_hcu)
        active = perm < fan_in  # fan_in random positions
        return active.astype(jnp.float32)

    keys = jax.random.split(key, post.n_hcu)
    cols = jax.vmap(one_column)(keys)  # (n_post_hcu, n_pre_hcu)
    return PlasticityState(hcu_mask=cols.T)


def mi_scores(
    state: MarginalState, pre: UnitLayout, post: UnitLayout
) -> jnp.ndarray:
    """Mutual information between each (input HCU, hidden HCU) pair.

    MI(I,H) = sum_{i in I, j in H} cij * log( cij / (ci * cj) ), computed
    from the running marginal estimates.  Shape (n_pre_hcu, n_post_hcu).
    """
    ci = jnp.maximum(state.ci, EPS)
    cj = jnp.maximum(state.cj, EPS)
    cij = jnp.maximum(state.cij, EPS)
    pointwise = cij * (jnp.log(cij) - jnp.log(ci)[:, None] - jnp.log(cj)[None, :])
    blocked = pointwise.reshape(pre.n_hcu, pre.n_mcu, post.n_hcu, post.n_mcu)
    return blocked.sum(axis=(1, 3))


def update_mask(
    plast: PlasticityState,
    marginals: MarginalState,
    pre: UnitLayout,
    post: UnitLayout,
    n_swaps: int = 1,
) -> PlasticityState:
    """Greedy rewiring step (Alg.1 L4-6).

    For each hidden HCU: silence the active connection with the lowest MI and
    activate the silent connection with the highest MI, iff the silent one
    scores strictly higher.  `n_swaps` repeats the greedy step (paper uses 1).
    Fan-in is preserved exactly.
    """
    scores = mi_scores(marginals, pre, post)  # (n_pre_hcu, n_post_hcu)

    def swap_once(mask_col: jnp.ndarray, score_col: jnp.ndarray) -> jnp.ndarray:
        # mask_col/score_col: (n_pre_hcu,) for one hidden HCU.
        neg_inf = jnp.asarray(-jnp.inf, score_col.dtype)
        pos_inf = jnp.asarray(jnp.inf, score_col.dtype)
        active = mask_col > 0.5
        worst_active = jnp.argmin(jnp.where(active, score_col, pos_inf))
        best_silent = jnp.argmax(jnp.where(active, neg_inf, score_col))
        do_swap = (
            (score_col[best_silent] > score_col[worst_active])
            & active.any()
            & (~active).any()
        )
        new_col = mask_col.at[worst_active].set(
            jnp.where(do_swap, 0.0, mask_col[worst_active])
        )
        new_col = new_col.at[best_silent].set(
            jnp.where(do_swap, 1.0, new_col[best_silent])
        )
        return new_col

    mask = plast.hcu_mask
    swap_cols = jax.vmap(swap_once, in_axes=(1, 1), out_axes=1)
    for _ in range(n_swaps):
        mask = swap_cols(mask, scores)
    return PlasticityState(hcu_mask=mask)


def fan_in(plast: PlasticityState) -> jnp.ndarray:
    """Active incoming connections per hidden HCU (invariant under updates)."""
    return plast.hcu_mask.sum(axis=0)


def full_mask(pre: UnitLayout, post: UnitLayout) -> PlasticityState:
    """All-active mask (a plain dense BCPNN layer)."""
    return PlasticityState(hcu_mask=jnp.ones((pre.n_hcu, post.n_hcu), jnp.float32))
