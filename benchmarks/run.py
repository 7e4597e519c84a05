"""Benchmark harness: one module per paper table/figure.

  fig2a  bench_train_batchsize   training throughput vs batch size
  fig2b  bench_inference         inference throughput + streaming row
  fig2c  bench_accuracy          MNIST-proxy accuracy (BCPNN + hybrid)
  fig2d  bench_scaling           strong scaling (meshes over this process's devices)
  fig3   bench_precision         BF14..BF28 accuracy cliff
  sec4.3 bench_stl10             STL-10-scale run
  issue4 bench_deep              depth sweep: project-once vs fused phases
  issue5 bench_serving_async     async engine vs whole-queue drain (Poisson)
  issue7 bench_router            Router fabric: multi-tenant p99, crash/restart
  issue8 bench_continual         online-learning recovery under label shift
  extra  bench_kernels           kernel-level roofline projections

Prints ``name,value,unit,derived`` CSV rows; `python -m benchmarks.run`.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

MODULES = [
    "bench_accuracy",
    "bench_train_batchsize",
    "bench_inference",
    "bench_precision",
    "bench_stl10",
    "bench_deep",
    "bench_serving_async",
    "bench_router",
    "bench_continual",
    "bench_kernels",
    "bench_scaling",
]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated module names")
    args = ap.parse_args()
    mods = args.only.split(",") if args.only else MODULES

    print("name,value,unit,derived")
    failures = 0
    for name in mods:
        print(f"# --- {name} ---", flush=True)
        t0 = time.perf_counter()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["main"])
            mod.main()
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name},-1,error,{type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
        print(f"# {name} took {time.perf_counter() - t0:.1f}s", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
