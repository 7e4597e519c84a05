"""Batched LM serving: prefill + continuous-batching fused decode.

    PYTHONPATH=src python examples/serve_batched.py --arch yi-9b --requests 6

Uses the reduced (smoke) config of any assigned architecture and generates
greedy completions for a queue of prompts through the unified serving API:
``ServiceConfig`` binds the model to an ``InferenceService`` whose
DecodePlan advances every decode slot in ONE jitted step over a fused slot
axis (the legacy ``ServeSession`` paid one dispatch per slot per token).
Prompt-length buckets bound the number of compiled prefill shapes.
"""
import argparse
import time

import jax
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.configs import ARCH_NAMES, get_smoke_config
from repro.models import build_model
from repro.runtime import Request, ServiceConfig, serve_model


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=[a for a in ARCH_NAMES], default="yi-9b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-batch", type=int, default=3)
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch)
    if cfg.family == "encdec":
        raise SystemExit("serve_batched targets decoder-only archs")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    service = serve_model(
        model, params,
        ServiceConfig(max_batch=args.max_batch, max_seq=128, buckets=(8, 24)),
    )

    rng = np.random.default_rng(0)
    for i in range(args.requests):
        service.submit(
            Request(
                rid=i,
                prompt=rng.integers(
                    0, cfg.vocab_size, rng.integers(4, 24)
                ).astype(np.int32),
                max_new_tokens=args.max_new,
            )
        )
    t0 = time.perf_counter()
    done = service.drain()
    dt = time.perf_counter() - t0
    total_new = sum(len(c.tokens) for c in done)
    for c in sorted(done, key=lambda c: c.rid):
        print(f"req {c.rid}: prefill={c.prefill_len:3d} -> {c.tokens.tolist()}")
    st = service.stats
    print(
        f"\n{len(done)} requests, {total_new} tokens in {dt:.1f}s "
        f"({total_new/dt:.1f} tok/s on CPU, arch={args.arch}, "
        f"{st['fused_steps']} fused steps at mean occupancy "
        f"{st['mean_occupancy']:.2f})"
    )
    from repro.runtime import format_latency_line

    print(
        "telemetry: "
        + format_latency_line(
            st["telemetry"], "queue_wait_s", "prefill_s", "decode_step_s"
        )
    )


if __name__ == "__main__":
    main()
