"""Production training launcher.

    python -m repro.launch.train --arch yi-9b --steps 100 [--smoke]

Language models only; BCPNN networks train through
``Network.compile(...).fit`` (see examples/ and chip_smoke.py).

On the container this runs the reduced (smoke) configs on CPU; on a real
pod the same entry point runs the full config with the production mesh
(``--mesh pod`` requires the device count to match).  Wires together:
configs -> model zoo -> sharding rules -> optimizer -> data pipeline ->
fault-tolerant train loop -> checkpointing.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.configs import ARCH_NAMES, get_config, get_smoke_config
from repro.data import lm_batches, token_stream
from repro.models import build_model
from repro.optim import AdamW, warmup_cosine
from repro.runtime import TrainLoopConfig, train_loop
from repro.sharding.rules import ShardCtx, param_shardings


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_NAMES), required=True)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--mesh", choices=("none", "host", "pod"), default="none")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument(
        "--profile-dir", default=None,
        help="run the train loop under jax.profiler.trace(DIR) — a "
             "device-level profile viewable in TensorBoard/Perfetto",
    )
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = None
    if args.mesh == "host":
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh()
    elif args.mesh == "pod":
        from repro.launch.mesh import make_production_mesh

        mesh = make_production_mesh()
    ctx = ShardCtx(mesh=mesh)
    model = build_model(cfg, ctx)
    params = model.init(jax.random.PRNGKey(0))
    if mesh is not None:
        ps = param_shardings(ctx, params, model.logical())
        params = jax.tree_util.tree_map(jax.device_put, params, ps)
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"[train] {args.arch} ({cfg.family}): {n/1e6:.1f}M params, mesh={args.mesh}")

    opt = AdamW(learning_rate=warmup_cosine(args.lr, 10, args.steps), weight_decay=0.1)
    opt_state = opt.init(params)
    step_fn = jax.jit(model.make_train_step(opt, n_micro=1))

    tokens = token_stream(1_000_000, vocab_size=cfg.vocab_size, seed=0)
    batches = list(lm_batches(tokens, args.batch, args.seq, epoch=0))
    rng = np.random.default_rng(0)

    def batch_fn(step):
        b = dict(batches[step % len(batches)])
        batch = {k: jnp.asarray(v) for k, v in b.items()}
        if cfg.family == "encdec":
            s = args.seq
            batch = {
                "enc_embeds": jnp.asarray(
                    rng.standard_normal((args.batch, s, cfg.d_model)), jnp.float32
                ),
                "tokens": batch["tokens"][:, : s // cfg.dec_ratio],
                "labels": batch["labels"][:, : s // cfg.dec_ratio],
            }
        elif cfg.family == "vlm":
            p = min(cfg.n_patches, args.seq // 4)
            batch["embeds"] = jnp.asarray(
                rng.standard_normal((args.batch, p, cfg.d_model)), jnp.float32
            )
        return batch

    import contextlib

    profile = (
        jax.profiler.trace(args.profile_dir)
        if args.profile_dir is not None
        else contextlib.nullcontext()
    )
    with profile:
        res = train_loop(
            step_fn, params, opt_state, batch_fn,
            TrainLoopConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir),
        )
    losses = [m["loss"] for m in res.metrics]
    print(
        f"[train] done: loss {losses[0]:.3f} -> {losses[-1]:.3f}, "
        f"mean step {res.mean_step_s*1e3:.0f}ms"
    )


if __name__ == "__main__":
    main()
