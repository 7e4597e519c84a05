"""Serving fabric demo: a Router fronting a fleet of decode engines.

    PYTHONPATH=src python examples/serve_fleet.py --fleet 2 --requests 12
    PYTHONPATH=src python examples/serve_fleet.py --smoke --fleet 2 --strict

One set of weights, N independent decode engines, one ``submit()`` front
door.  Two tenants share the fleet — ``paid`` at 4x the DRR weight of
``free`` — and every request carries a deadline: work that misses its SLO
while queued is shed with a typed ``DeadlineExceeded`` on its future
instead of wasting a decode slot.  The Router routes each dispatch to the
engine with the lowest p95 queue-wait read from the telemetry histograms.

``--smoke`` shrinks the workload to a CI-sized check and asserts the
invariants (every future resolves; both engines served; tenants isolated)
instead of just printing them.  ``--strict`` runs the engines' fused
decode steps under the PR 6 runtime verification (transfer guard +
recompile sentinels) — the fabric on top adds no jitted callables.
"""
import argparse
import sys
import time

import jax
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.configs import ARCH_NAMES, get_smoke_config
from repro.models import build_model
from repro.runtime import (
    DeadlineExceeded,
    Request,
    RouterConfig,
    ServiceConfig,
    TenantConfig,
    format_latency_line,
    serve_fleet,
)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_NAMES), default="gemma3-1b")
    ap.add_argument("--fleet", type=int, default=2)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument(
        "--deadline-s", type=float, default=30.0,
        help="per-request SLO budget (queued work past it is shed)",
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help="CI-sized run: fewer tokens, assert the fabric invariants",
    )
    ap.add_argument(
        "--strict", action="store_true",
        help="run engines under strict runtime verification",
    )
    args = ap.parse_args()
    if args.smoke:
        args.requests = min(args.requests, 6)
        args.max_new = min(args.max_new, 4)

    cfg = get_smoke_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    router = serve_fleet(
        model, params,
        ServiceConfig(
            max_batch=2, max_seq=96, buckets=(8,), strict=args.strict,
            router=RouterConfig(
                tenants={
                    "free": TenantConfig(weight=1.0),
                    "paid": TenantConfig(weight=4.0),
                },
            ),
        ),
        fleet=args.fleet,
    )

    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    futures = []
    for i in range(args.requests):
        futures.append(
            router.submit(
                Request(
                    rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                    max_new_tokens=args.max_new,
                ),
                tenant="paid" if i % 3 else "free",
                priority=float(i % 2),
                deadline_s=args.deadline_s,
            )
        )
    done, shed = [], 0
    for f in futures:
        try:
            done.append(f.result())
        except DeadlineExceeded:
            shed += 1
    router.drain_and_stop()
    dt = time.perf_counter() - t0

    tot = sum(len(c.tokens) for c in done)
    snap = router.metrics.snapshot()
    print(
        f"[fleet] {args.fleet} engines, {len(done)} done + {shed} shed of "
        f"{args.requests} in {dt:.1f}s ({tot/dt:.1f} tok/s, "
        f"{snap['restarts']} restarts)"
    )
    for name, tm in sorted(snap["tenants"].items()):
        print(
            f"[tenant {name}] completed={tm['completed']} "
            f"shed_deadline={tm['shed_deadline']} | "
            + format_latency_line(tm, "sched_wait_s", "e2e_s")
        )
    served = {
        name: eng["completed"] for name, eng in snap["engines"].items()
    }
    print(f"[engines] completed per engine: {served}")

    if args.smoke:
        assert len(done) + shed == args.requests, "a future was dropped"
        assert router.state == "stopped"
        assert snap["dispatched"] == len(done), (
            "dispatch count must match completions in a crash-free run"
        )
        assert all(n >= 0 for n in served.values()) and sum(
            served.values()
        ) == len(done), f"engine roll-up mismatch: {served}"
        print("[smoke] fleet invariants hold")
    return 0


if __name__ == "__main__":
    sys.exit(main())
