"""Async LM serving: futures, mid-flight admission, latency telemetry.

    PYTHONPATH=src python examples/serve_async.py --arch yi-9b --requests 8

Demonstrates the AsyncEngine surface of the unified serving API.
``ServiceConfig(async_mode=True)`` starts a dedicated executor thread at
bind time; ``submit()`` then returns a ``concurrent.futures.Future`` and
the engine admits each request into the next free fused-decode slot
*between* jitted steps — requests arriving while others are mid-generation
do not wait for the whole queue to drain (continuous batching).  Latency
telemetry (queue-wait / prefill / per-token decode percentile histograms)
records throughout and is printed at the end.
"""
import argparse
import time

import jax
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.configs import ARCH_NAMES, get_smoke_config
from repro.models import build_model
from repro.runtime import (
    Request,
    ServiceConfig,
    format_latency_line,
    serve_model,
)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=[a for a in ARCH_NAMES], default="yi-9b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=10)
    ap.add_argument("--max-batch", type=int, default=3)
    ap.add_argument(
        "--arrival-ms", type=float, default=30.0,
        help="mean inter-arrival gap (requests trickle in mid-flight)",
    )
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch)
    if cfg.family == "encdec":
        raise SystemExit("serve_async targets decoder-only archs")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    service = serve_model(
        model, params,
        ServiceConfig(
            max_batch=args.max_batch, max_seq=128, buckets=(8, 24),
            async_mode=True,
        ),
    )

    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    futures = []
    for i in range(args.requests):
        # Requests arrive over time, not as one pre-collected queue: the
        # engine admits each into the next freed slot mid-flight.
        futures.append(
            service.submit(
                Request(
                    rid=i,
                    prompt=rng.integers(
                        0, cfg.vocab_size, rng.integers(4, 24)
                    ).astype(np.int32),
                    max_new_tokens=args.max_new,
                )
            )
        )
        time.sleep(rng.exponential(args.arrival_ms / 1e3))
    done = [f.result() for f in futures]  # block only at the very end
    service.drain_and_stop()
    dt = time.perf_counter() - t0

    total_new = sum(len(c.tokens) for c in done)
    for c in sorted(done, key=lambda c: c.rid):
        print(f"req {c.rid}: prefill={c.prefill_len:3d} -> {c.tokens.tolist()}")
    st = service.stats
    print(
        f"\n{len(done)} requests, {total_new} tokens in {dt:.1f}s "
        f"({total_new/dt:.1f} tok/s on CPU, arch={args.arch}, "
        f"{st['fused_steps']} fused steps at mean occupancy "
        f"{st['mean_occupancy']:.2f}, {st['engine']['admitted']} engine "
        "admissions)"
    )
    print(
        "telemetry: "
        + format_latency_line(
            st["telemetry"], "queue_wait_s", "prefill_s", "decode_step_s",
            "e2e_s",
        )
    )


if __name__ == "__main__":
    main()
