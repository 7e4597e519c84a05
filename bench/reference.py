"""Plain Alg. 1 reference for the hidden layer, written from the paper.

Independent of the program: it imports nothing of ``repro`` and takes nothing
the program made.  It builds its own initial state from the seed, by the
convention the program's ``Network(seed)`` documents (``PRNGKey(seed)`` split
once per layer; the hidden layer splits its key into the C_ij jitter key and
the receptive-field key), and its own shuffle from the same seed.

One batch iteration of the hidden layer (Alg. 1):
1. every ``every`` batches (step 0 first) each hidden HCU swaps its weakest
   active input HCU for its strongest silent one by mutual information, if
   the silent one scores strictly higher; ``w`` is re-masked;
2. support ``s = gain * (x @ (w * mask) + b)``, softmax within each HCU;
3. batch means of ``x``, ``a_j`` and ``x^T a_j``; EWMA of c_i, c_j, C_ij;
4. ``w = (log C_ij - log c_i - log c_j) * mask``, ``b = log c_j``, all
   probabilities clamped at ``EPS``.

Float32 runs under ``default_matmul_precision("highest")``.  ``dtype=
bfloat16`` runs every array and operation in bfloat16: that is the control.
``fault`` plants one of the faults the comparison has to catch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-8
LEAVES = ("ci", "cj", "cij", "w", "b", "mask")

# Faults planted in the reference put in the program's place:
#   "half":  each batch's means are taken over its first half of rows only;
#   "token": one input row of every batch is altered (x -> 1 - x);
#   "local": each batch's means are taken over the first chip's rows only,
#            as when the exchange between chips is left out.
FAULTS = ("half", "token", "local")


def _mask_units(hcu_mask, n_mcu_in, n_mcu):
    return jnp.repeat(jnp.repeat(hcu_mask, n_mcu_in, axis=0), n_mcu, axis=1)


def _weights(ci, cj, cij):
    lci = jnp.log(jnp.maximum(ci, EPS))
    lcj = jnp.log(jnp.maximum(cj, EPS))
    return jnp.log(jnp.maximum(cij, EPS)) - lci[:, None] - lcj[None, :], lcj


def init_state(cfg: dict, seed: int, dtype=jnp.float32) -> dict:
    """The hidden layer's initial state from the seed, on the device."""
    return _init(cfg["n_features"], cfg["n_mcu_in"], cfg["n_hcu"], cfg["n_mcu"],
                 cfg["fan_in"], float(cfg["init_jitter"]), jnp.dtype(dtype),
                 jnp.asarray(seed % 2**32, jnp.uint32))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 6))
def _init(n_in_hcu, n_mcu_in, n_hcu, n_mcu, fan_in, jitter, dtype, seed):
    f, h = n_in_hcu * n_mcu_in, n_hcu * n_mcu
    hidden_key = jax.random.split(jax.random.PRNGKey(seed), 2)[0]
    k_cij, k_mask = jax.random.split(hidden_key)
    pi, pj = 1.0 / n_mcu_in, 1.0 / n_mcu
    ci = jnp.full((f,), pi, jnp.float32)
    cj = jnp.full((h,), pj, jnp.float32)
    cij = pi * pj * jnp.exp(jitter * jax.random.normal(k_cij, (f, h), jnp.float32))
    if fan_in < n_in_hcu:
        cols = jax.vmap(lambda k: jax.random.permutation(k, n_in_hcu) < fan_in)(
            jax.random.split(k_mask, n_hcu))
        hcu_mask = cols.T.astype(jnp.float32)
    else:
        hcu_mask = jnp.ones((n_in_hcu, n_hcu), jnp.float32)
    w, b = _weights(ci, cj, cij)
    w = w * _mask_units(hcu_mask, n_mcu_in, n_mcu)
    state = dict(ci=ci, cj=cj, cij=cij, w=w, b=b, mask=hcu_mask)
    state = {k: v.astype(dtype) for k, v in state.items()}
    state["step"] = jnp.zeros((), jnp.int32)
    return state


def _rewire(st, n_in_hcu, n_mcu_in, n_hcu, n_mcu):
    ci = jnp.maximum(st["ci"], EPS)
    cj = jnp.maximum(st["cj"], EPS)
    cij = jnp.maximum(st["cij"], EPS)
    point = cij * (jnp.log(cij) - jnp.log(ci)[:, None] - jnp.log(cj)[None, :])
    score = point.reshape(n_in_hcu, n_mcu_in, n_hcu, n_mcu).sum(axis=(1, 3))
    mask = st["mask"]
    active = mask > 0.5
    cols = jnp.arange(n_hcu)
    worst = jnp.argmin(jnp.where(active, score, jnp.inf), axis=0)
    best = jnp.argmax(jnp.where(active, -jnp.inf, score), axis=0)
    swap = ((score[best, cols] > score[worst, cols])
            & active.any(axis=0) & (~active).any(axis=0))
    one = jnp.ones((), mask.dtype)
    mask = mask.at[worst, cols].set(jnp.where(swap, 0 * one, mask[worst, cols]))
    mask = mask.at[best, cols].set(jnp.where(swap, one, mask[best, cols]))
    w = st["w"] * _mask_units(mask, n_mcu_in, n_mcu)
    return {**st, "mask": mask, "w": w}


def _step(st, x, *, cfg, fault, n_shards):
    n_in_hcu, n_mcu_in = cfg["n_features"], cfg["n_mcu_in"]
    n_hcu, n_mcu = cfg["n_hcu"], cfg["n_mcu"]
    lam, gain = cfg["lam"], cfg["gain"]
    if cfg["fan_in"] < n_in_hcu:
        st = jax.lax.cond(
            st["step"] % n_hcu == 0,
            lambda s: _rewire(s, n_in_hcu, n_mcu_in, n_hcu, n_mcu),
            lambda s: s, st)
    if fault == "half":
        x = x[: x.shape[0] // 2]
    elif fault == "local":
        x = x[: x.shape[0] // n_shards]
    elif fault == "token":
        x = x.at[0].set(1 - x[0])
    mask = _mask_units(st["mask"], n_mcu_in, n_mcu)
    s = (x @ (st["w"] * mask) + st["b"]) * gain
    aj = jax.nn.softmax(s.reshape(-1, n_hcu, n_mcu), axis=-1).reshape(s.shape)
    n = x.shape[0]
    mi, mj = x.mean(axis=0), aj.mean(axis=0)
    mij = (x.T @ aj) / n
    ci = (1 - lam) * st["ci"] + lam * mi
    cj = (1 - lam) * st["cj"] + lam * mj
    cij = (1 - lam) * st["cij"] + lam * mij
    w, b = _weights(ci, cj, cij)
    dt = st["ci"].dtype
    return dict(ci=ci.astype(dt), cj=cj.astype(dt), cij=cij.astype(dt),
                w=(w * mask).astype(dt), b=b.astype(dt), mask=st["mask"],
                step=st["step"] + 1)


@functools.partial(jax.jit, static_argnames=("cfg_items", "fault", "n_shards"))
def _epoch(st, xs, *, cfg_items, fault, n_shards):
    cfg = dict(cfg_items)
    dt = st["ci"].dtype

    def body(s, x):
        return _step(s, x.astype(dt), cfg=cfg, fault=fault,
                     n_shards=n_shards), None

    return jax.lax.scan(body, st, xs)[0]


def shuffle(seed: int, n_total: int, batch: int) -> np.ndarray:
    """The first epoch's sample order under ``fit(shuffle=True)`` for a
    network built with ``seed``: a permutation of the whole split, trimmed
    to whole batches."""
    n = (n_total // batch) * batch
    return np.random.default_rng(seed).permutation(n_total)[:n]


_HYPER = ("n_features", "n_mcu_in", "n_hcu", "n_mcu", "fan_in", "lam", "gain")


def run(cfg: dict, seed: int, x: np.ndarray, batch: int, dtype=jnp.float32,
        fault: str | None = None, n_shards: int = 1):
    """``(initial, after)``: the reference's state before and after one
    shuffled epoch over host inputs ``x`` in global batches of ``batch``."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r} (want one of {FAULTS})")
    idx = shuffle(seed, x.shape[0], batch)
    xs = x[idx].reshape(-1, batch, x.shape[1])
    items = tuple((k, cfg[k]) for k in _HYPER)
    init = init_state(cfg, seed, dtype)
    with jax.default_matmul_precision("highest"):
        after = _epoch(dict(init), jnp.asarray(xs), cfg_items=items,
                       fault=fault, n_shards=n_shards)
    return init, after
