"""End-to-end LM training driver: model zoo + optimizer + data pipeline +
fault-tolerant loop + checkpointing, on synthetic token streams.

    # ~100M-parameter model, a few hundred steps (the full deliverable run):
    PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 300

    # quick CPU sanity (default):
    PYTHONPATH=src python examples/train_lm.py

Loss should visibly decrease (the synthetic stream has planted bigram
structure).  Checkpoints land in --ckpt-dir; rerunning resumes.
"""
import argparse
import dataclasses

import jax

from repro.launch.compile_cache import enable_compile_cache
from repro.configs import get_smoke_config
from repro.data import lm_batches, token_stream
from repro.models import build_model
from repro.optim import AdamW, warmup_cosine
from repro.runtime import TrainLoopConfig, train_loop

PRESETS = {
    # name: (d_model, n_layers, n_heads, kv, d_ff, vocab) — ~params
    "tiny": (128, 4, 4, 2, 512, 2048),      # ~2M
    "20m": (384, 6, 6, 2, 1536, 8192),      # ~20M
    "100m": (640, 12, 10, 2, 2560, 32768),  # ~100M
}


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=PRESETS, default="tiny")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_lm_ckpt")
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()

    d, nl, h, kv, ff, v = PRESETS[args.preset]
    cfg = dataclasses.replace(
        get_smoke_config("yi-9b"),
        d_model=d, n_layers=nl, n_heads=h, n_kv_heads=kv,
        d_head=d // h, d_ff=ff, vocab_size=v, n_micro=1,
        q_chunk=128, kv_chunk=256,
    )
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"preset={args.preset}: {n_params/1e6:.1f}M params")

    opt = AdamW(
        learning_rate=warmup_cosine(args.lr, 20, args.steps),
        weight_decay=0.1,
    )
    opt_state = opt.init(params)
    step_fn = jax.jit(model.make_train_step(opt, n_micro=1))

    tokens = token_stream(2_000_000, vocab_size=v, seed=0)
    batches = list(
        lm_batches(tokens, args.batch, args.seq, epoch=0, seed=0)
    )

    def batch_fn(step):
        b = batches[step % len(batches)]
        return {k: jax.numpy.asarray(v) for k, v in b.items()}

    res = train_loop(
        step_fn, params, opt_state, batch_fn,
        TrainLoopConfig(
            total_steps=args.steps, ckpt_dir=args.ckpt_dir, ckpt_every=50,
        ),
    )
    losses = [m["loss"] for m in res.metrics]
    print(
        f"steps={res.steps_done} loss {losses[0]:.3f} -> {losses[-1]:.3f} "
        f"(mean step {res.mean_step_s*1e3:.0f} ms, restarts={res.restarts})"
    )
    assert losses[-1] < losses[0], "loss did not decrease"


if __name__ == "__main__":
    main()
