"""Mixture-of-Experts with expert parallelism (DeepSeek-V2 / Moonlight style).

Routing is top-k softmax with capacity-based token dropping (GShard); the
*dispatch* is sort-free scatter/gather (one-hot cumsum slot assignment), so
the compiled HLO contains only the real expert GEMMs + data movement — no
GShard dense dispatch-einsum FLOP pollution (that formulation inflates
HLO_FLOPs by O(E*C/k) and would corrupt the roofline's useful-FLOP ratio).

Three execution schemes (cfg.moe_impl):

* ``local`` — single-shard dispatch (CPU smoke tests, and the E_loc == E case);
* ``psum``  — activations replicated over the model axis; each model shard
  computes only its E/TP experts and the partial outputs are psum-ed.
  Simple and robust; collective volume = tokens x d per layer.  This is the
  *baseline* scheme (paper-era MoE-as-allreduce).
* ``a2a``   — tokens sequence-sharded over the model axis inside the block;
  capacity buffers are exchanged with ``lax.all_to_all`` to the owning
  expert shard and back.  Collective volume ~ 2 x tokens x k/E_shards x d x
  capacity_factor — the production dispatch at pod scale (beyond-paper
  optimization).

All schemes share ``_dispatch_compute`` so they are numerically identical
(up to token-drop tie-breaking) and are cross-validated in tests.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import Params, dense_init
from repro.sharding.rules import L, ShardCtx


# ------------------------------------------------------------------ params
def moe_init(key, cfg) -> Params:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, e)),
        "gate": dense_init(ks[1], (e, d, f)) ,
        "up": dense_init(ks[2], (e, d, f)),
        "down": dense_init(ks[3], (e, f, d), in_axis=1),
    }
    if cfg.n_shared_experts > 0:
        fs = cfg.n_shared_experts * f
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "gate": dense_init(k1, (d, fs)),
            "up": dense_init(k2, (d, fs)),
            "down": dense_init(k3, (fs, d)),
        }
    return p


def moe_logical(cfg) -> Params:
    p = {
        "router": L("d_fsdp", None),
        "gate": L("expert", "d_fsdp", None),
        "up": L("expert", "d_fsdp", None),
        "down": L("expert", None, "d_fsdp"),
    }
    if cfg.n_shared_experts > 0:
        p["shared"] = {
            "gate": L("d_fsdp", "mlp"),
            "up": L("d_fsdp", "mlp"),
            "down": L("mlp", "d_fsdp"),
        }
    return p


# ------------------------------------------------------------------ router
def router_topk(
    logits: jnp.ndarray, k: int
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(T, E) -> probs (T, k), idx (T, k) int32, aux load-balance loss."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    # Renormalize selected probabilities (DeepSeek convention).
    top_p = top_p / jnp.maximum(jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)
    # Load-balance aux (Switch): E * sum_e f_e * P_e.
    e = logits.shape[-1]
    me = jnp.mean(probs, axis=0)
    fe = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_i, e, dtype=jnp.float32), axis=1), axis=0
    )
    aux = e * jnp.sum(me * fe)
    return top_p, top_i, aux


def _slots(e_flat: jnp.ndarray, n_experts: int, capacity: int):
    """Slot index of each assignment within its expert's capacity buffer."""
    oh = jax.nn.one_hot(e_flat, n_experts, dtype=jnp.int32)  # (A, E)
    slot = jnp.take_along_axis(
        jnp.cumsum(oh, axis=0) - 1, e_flat[:, None], axis=1
    )[:, 0]
    keep = slot < capacity
    return slot, keep


def _dispatch_compute(
    x: jnp.ndarray,  # (T, d)
    probs: jnp.ndarray,  # (T, k)
    idx: jnp.ndarray,  # (T, k) global expert ids in [e_lo, e_lo+E_loc)
    gate_w: jnp.ndarray,  # (E_loc, d, f)
    up_w: jnp.ndarray,
    down_w: jnp.ndarray,  # (E_loc, f, d)
    e_lo: int | jnp.ndarray,
    capacity: int,
) -> jnp.ndarray:
    """Capacity-buffer dispatch -> batched expert GEMM -> weighted combine.

    Assignments routed outside [e_lo, e_lo + E_loc) are dropped by this
    shard (they belong to another shard in the psum scheme).
    """
    t, k = idx.shape
    e_loc = gate_w.shape[0]
    d = x.shape[-1]
    tok = jnp.repeat(jnp.arange(t), k)  # (A,)
    e_local = idx.reshape(-1) - e_lo
    in_range = (e_local >= 0) & (e_local < e_loc)
    e_clip = jnp.clip(e_local, 0, e_loc - 1)
    # Out-of-range assignments go to a fake overflow bucket (id e_loc) so
    # they don't consume real experts' capacity, and are masked from scatter.
    slot, fits = _slots(
        jnp.where(in_range, e_clip, e_loc), e_loc + 1, capacity
    )
    keep = (fits & in_range).astype(x.dtype)
    slot = jnp.clip(slot, 0, capacity - 1)

    buf = jnp.zeros((e_loc, capacity, d), x.dtype)
    buf = buf.at[e_clip, slot].add(x[tok] * keep[:, None])

    h_g = jnp.einsum("ecd,edf->ecf", buf, gate_w.astype(x.dtype))
    h_u = jnp.einsum("ecd,edf->ecf", buf, up_w.astype(x.dtype))
    h = jax.nn.silu(h_g) * h_u
    out_buf = jnp.einsum("ecf,efd->ecd", h, down_w.astype(x.dtype))

    gathered = out_buf[e_clip, slot] * keep[:, None]  # (A, d)
    weighted = gathered * probs.reshape(-1)[:, None].astype(x.dtype)
    return jnp.sum(weighted.reshape(t, k, d), axis=1)


def _capacity(tokens: int, k: int, n_experts: int, cf: float) -> int:
    c = int(math.ceil(tokens * k / n_experts * cf))
    return max(8, -(-c // 8) * 8)  # round up to 8 for TPU-friendly tiling


def _shared_expert(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    g = jax.nn.silu(jnp.einsum("...d,df->...f", x, p["gate"].astype(x.dtype)))
    u = jnp.einsum("...d,df->...f", x, p["up"].astype(x.dtype))
    return jnp.einsum("...f,fd->...d", g * u, p["down"].astype(x.dtype))


# ------------------------------------------------------------------- apply
def moe_apply(
    params: Params, x: jnp.ndarray, cfg, ctx: ShardCtx
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B,S,d) -> (out (B,S,d), aux loss scalar)."""
    b, s, d = x.shape
    impl = cfg.moe_impl
    tp = ctx.axis_size("model")
    if ctx.mesh is None or tp == 1 or cfg.n_experts % tp != 0:
        impl = "local"

    shared = (
        _shared_expert(params["shared"], x) if "shared" in params else 0.0
    )

    if impl == "local":
        xt = x.reshape(-1, d)
        logits = jnp.einsum("td,de->te", xt, params["router"].astype(x.dtype))
        probs, idx, aux = router_topk(logits, cfg.top_k)
        cap = _capacity(xt.shape[0], cfg.top_k, cfg.n_experts, cfg.capacity_factor)
        out = _dispatch_compute(
            xt, probs, idx, params["gate"], params["up"], params["down"], 0, cap
        )
        return out.reshape(b, s, d) + shared, aux

    if impl == "psum":
        out, aux = _moe_psum(params, x, cfg, ctx)
    elif impl == "a2a":
        out, aux = _moe_a2a(params, x, cfg, ctx)
    else:
        raise ValueError(f"unknown moe_impl {impl}")
    return out + shared, aux


def _moe_psum(params, x, cfg, ctx: ShardCtx):
    """Replicated activations, sharded experts, psum combine (baseline)."""
    b, s, d = x.shape
    tp = ctx.axis_size("model")
    e_loc = cfg.n_experts // tp
    baxes = ctx.batch_axes()
    dp = 1
    for a in baxes:
        dp *= ctx.axis_size(a)
    t_loc = (b // dp) * s
    cap = _capacity(t_loc, cfg.top_k, cfg.n_experts, cfg.capacity_factor)

    def local(x_l, router, gate, up, down):
        xt = x_l.reshape(-1, d)
        logits = jnp.einsum("td,de->te", xt, router.astype(xt.dtype))
        probs, idx, aux = router_topk(logits, cfg.top_k)
        shard = jax.lax.axis_index("model")
        out = _dispatch_compute(
            xt, probs, idx, gate, up, down, shard * e_loc, cap
        )
        out = jax.lax.psum(out, "model")
        if baxes:
            aux = jax.lax.pmean(aux, baxes)
        return out.reshape(x_l.shape), aux

    fn = jax.shard_map(
        local,
        mesh=ctx.mesh,
        in_specs=(
            P(baxes, None, None),
            P(None, None),
            P("model", None, None),
            P("model", None, None),
            P("model", None, None),
        ),
        out_specs=(P(baxes, None, None), P()),
        check_vma=False,
    )
    return fn(x, params["router"], params["gate"], params["up"], params["down"])


def _moe_a2a(params, x, cfg, ctx: ShardCtx):
    """Sequence-sharded tokens + all_to_all expert exchange (production)."""
    b, s, d = x.shape
    tp = ctx.axis_size("model")
    e_loc = cfg.n_experts // tp
    baxes = ctx.batch_axes()
    dp = 1
    for a in baxes:
        dp *= ctx.axis_size(a)
    t_loc = (b // dp) * (s // tp)  # tokens per (data, model) shard
    # Per-source-shard, per-expert capacity.
    cap = _capacity(t_loc, cfg.top_k, cfg.n_experts, cfg.capacity_factor)

    def local(x_l, router, gate, up, down):
        # x_l: (B_loc, S_loc, d) — sequence-sharded over the model axis.
        xt = x_l.reshape(-1, d)
        t = xt.shape[0]
        logits = jnp.einsum("td,de->te", xt, router.astype(xt.dtype))
        probs, idx, aux = router_topk(logits, cfg.top_k)
        k = cfg.top_k
        tok = jnp.repeat(jnp.arange(t), k)
        e_flat = idx.reshape(-1)
        # Slot within the destination expert's buffer (global expert id).
        slot, fits = _slots(e_flat, cfg.n_experts, cap)
        keep = fits.astype(xt.dtype)
        slot = jnp.clip(slot, 0, cap - 1)
        buf = jnp.zeros((cfg.n_experts, cap, d), xt.dtype)
        buf = buf.at[e_flat, slot].add(xt[tok] * keep[:, None])
        # (E, cap, d) -> (tp, E_loc, cap, d): slab j goes to shard j.
        buf = buf.reshape(tp, e_loc, cap, d)
        recv = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=0)
        # recv: (tp_src, E_loc, cap, d) — tokens from all source shards.
        rb = jnp.swapaxes(recv, 0, 1).reshape(e_loc, tp * cap, d)
        h_g = jnp.einsum("ecd,edf->ecf", rb, gate.astype(xt.dtype))
        h_u = jnp.einsum("ecd,edf->ecf", rb, up.astype(xt.dtype))
        h = jax.nn.silu(h_g) * h_u
        ob = jnp.einsum("ecf,efd->ecd", h, down.astype(xt.dtype))
        # Back to (tp_src, E_loc, cap, d) and inverse exchange.
        ob = jnp.swapaxes(ob.reshape(e_loc, tp, cap, d), 0, 1)
        back = jax.lax.all_to_all(ob, "model", split_axis=0, concat_axis=0)
        # back: (tp_dst=E-shard, E_loc, cap, d) == original buf layout.
        out_buf = back.reshape(cfg.n_experts, cap, d)
        gathered = out_buf[e_flat, slot] * keep[:, None]
        weighted = gathered * probs.reshape(-1)[:, None].astype(xt.dtype)
        out = jnp.sum(weighted.reshape(t, k, d), axis=1)
        aux = jax.lax.pmean(aux, baxes + ("model",) if baxes else "model")
        return out.reshape(x_l.shape), aux

    fn = jax.shard_map(
        local,
        mesh=ctx.mesh,
        in_specs=(
            P(baxes, "model", None),
            P(None, None),
            P("model", None, None),
            P("model", None, None),
            P("model", None, None),
        ),
        out_specs=(P(baxes, "model", None), P()),
        check_vma=False,
    )
    x_sp = ctx.cs(x, "batch", "sp_seq", None)  # reshard: seq over model
    out, aux = fn(x_sp, params["router"], params["gate"], params["up"], params["down"])
    return ctx.cs(out, "batch", "seq", None), aux
