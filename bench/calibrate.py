"""Readings that the check's limits are set from, at a cell's own size.

    python bench/calibrate.py --workload <cell> --seeds <n> ... \\
        [--control-seeds <n> ...]

For each of ``--seeds`` the program runs its checked epoch as a benchmark
run's set-up does, and the compared numbers are read against the float32
reference (sound runs: the lower readings).  For each of
``--control-seeds`` the stand-ins are read in the program's place: the
reference in bfloat16 (the control), a state left unchanged, and the faults
of ``bench.reference`` (the upper readings).  One JSON line per reading;
the last line sums them up per number.  Runs on the chip only.
"""
import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import check, reference, spec  # noqa: E402
from bench.run import enable_cache, require_chips  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    cell = spec.load_cell(ROOT, args.workload)
    devices = require_chips(cell.chips)
    enable_cache(ROOT)
    readings = []

    def emit(kind, seed, values):
        readings.append((kind, values))
        leaves = check.leaf_gaps(*({k: jnp.asarray(v, jnp.float32)
                                    for k, v in st.items() if k in check.LEAVES}
                                   for st in drv.last))
        print(json.dumps({"kind": kind, "seed": seed, **values,
                          "leaf_gaps": leaves}), flush=True)

    for seed in dict.fromkeys(args.seeds + args.control_seeds):
        t0 = time.perf_counter()
        drv = cell.runner().Runner(cell, seed, devices)
        drv.setup()
        drv.release()
        if seed in args.seeds:
            emit("sound", seed, drv.reading())
        if seed in args.control_seeds:
            emit("control", seed, drv.reading(dtype=jnp.bfloat16))
            emit("frozen", seed, drv.reading(prog="init"))
            faults = [f for f in reference.FAULTS
                      if f != "local" or drv.n_shards() > 1]
            for f in faults:
                emit(f, seed, drv.reading(fault=f))
        print(f"seed {seed}: {time.perf_counter() - t0:.1f} s", file=sys.stderr)
        del drv
    summary = {}
    for k in check.NUMBERS:
        sound = [v[k] for kind, v in readings if kind == "sound"]
        summary[k] = {"lower": max(sound) if sound else None}
        for kind in {kind for kind, _ in readings if kind != "sound"}:
            summary[k][kind] = min(v[k] for kd, v in readings if kd == kind)
    print(json.dumps({"summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
