"""Runs one benchmark cell on the chips of this machine and prints one result.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (``setup_s``: process start to window start) makes the inputs and the
network from ``--seed``, compiles or loads every program from the persistent
cache in ``<checkout>/.jax_cache`` and drives the cell's first step.  With
``--trace 0`` the window then repeats the traffic mix's step in a closed
loop for ``--seconds`` and reports the cell's end-to-end metrics; with
``--trace 1`` it profiles a short span of whole steps and reports the
per-layer metrics.  Afterwards the program's state is freed and the float32
reference decides ``correct``.

The last line of standard output is one JSON object; the numbers compared
and their limits close standard error and the object.  Off a TPU, or with
fewer chips than the cell asks for, the run exits non-zero and prints no
result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402

from bench import check, devtrace, spec  # noqa: E402
from bench.clock import CompileClock  # noqa: E402

CALL = "bench.call"
TRACE_MIN_CALLS = 2
TRACE_MIN_SECONDS = 3.0


def require_chips(n: int):
    """The first ``n`` TPU devices; exits when JAX finds fewer."""
    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(f"bench: JAX found no TPU (backend {backend!r}); "
                 "the benchmark runs on the chip only")
    devices = jax.devices()
    if len(devices) < n:
        sys.exit(f"bench: the cell needs {n} chips, JAX found {len(devices)}")
    return devices[:n]


def enable_cache(root: pathlib.Path) -> None:
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def closed_loop(drv, more) -> dict:
    """Steps while ``more(steps_done, seconds_elapsed)``; every step started
    is counted, and the loop ends when the last one returns.  A step that
    raises ends it, its batches counted as failed."""
    out = dict(attempted=0, failed=0, samples=0, host_s=0.0, calls=0,
               step_s=[])
    t0 = time.perf_counter()
    with CompileClock() as clock:
        while more(out["calls"], time.perf_counter() - t0):
            out["attempted"] += drv.batches_per_step()
            t_step = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation(CALL):
                    r = drv.call()
            except Exception as e:
                print(f"bench: step {out['calls']} raised {e!r}", file=sys.stderr)
                out["failed"] += drv.batches_per_step()
                break
            out["step_s"].append(time.perf_counter() - t_step)
            out["calls"] += 1
            out["samples"] += r["samples"]
            out["host_s"] += r["host_s"]
    out["seconds"] = time.perf_counter() - t0
    out["compiles"] = clock.compiles
    return out


def traced_span(drv, cell, devices, seconds: float):
    """Profiles whole steps (at least two, and at least a few seconds) and
    returns the loop's counts, the per-layer metrics, the breakdown and the
    device's busy and window seconds."""
    span_s = min(seconds, TRACE_MIN_SECONDS)
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        with jax.profiler.trace(tmp):
            out = closed_loop(
                drv, lambda n, t: n < TRACE_MIN_CALLS or t < span_s)
        trace = devtrace.load(tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    lo, hi = devtrace.annotated_window(trace["host"], CALL)
    span = devtrace.Span(trace, lo, hi, chips=len(devices),
                         batches=out["attempted"] - out["failed"],
                         samples=out["samples"], host_s=out["host_s"],
                         work=drv.work(), device_kind=devices[0].device_kind)
    metrics = {}
    for m in cell.per_layer:
        value = cell.reader(m["name"]).read(span)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev0 = span.devices.get(0, [])
    breakdown = {"device_ops": devtrace.top_ops(dev0),
                 "idle_gaps": devtrace.idle_gaps(dev0, span.host, lo, hi)}
    extra = {"busy_s": span.mean_busy_s, "window_s": span.window_s}
    return out, metrics, breakdown, extra


def main(argv=None, root=ROOT, chips=require_chips, cache=enable_cache) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    root = pathlib.Path(root)
    cell = spec.load_cell(root, args.workload)
    devices = chips(cell.chips)
    cache(root)
    drv = cell.runner().Runner(cell, args.seed, devices)

    with CompileClock() as setup_clock:
        check_s = drv.setup()
    setup_s = time.perf_counter() - T_START - check_s
    print(f"bench: set-up {setup_s:.3f} s, {setup_clock.compiles} programs "
          f"compiled or loaded ({setup_clock.seconds:.3f} s), "
          f"{setup_clock.cache_misses} not in the persistent cache",
          file=sys.stderr)

    if args.trace:
        win, metrics, breakdown, extra = traced_span(drv, cell, devices,
                                                     args.seconds)
    else:
        win = closed_loop(drv, lambda n, t: n == 0 or t < args.seconds)
        rates = drv.end_to_end(win["samples"], win["seconds"])
        metrics = {m["name"]: {"value": rates[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in rates}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        breakdown = extra = None
    print(f"bench: window {win['seconds']:.3f} s, {win['calls']} steps "
          f"({', '.join(f'{s:.3f}' for s in win['step_s'])} s), "
          f"{win['compiles']} programs compiled inside it", file=sys.stderr)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes":
              memory_peak(devices)}
    if extra:
        device.update(extra)
    finite = win["failed"] == 0 and drv.finite()
    drv.release()
    t_ref = time.perf_counter()
    values = drv.reading()
    print(f"bench: reference and check {time.perf_counter() - t_ref:.3f} s",
          file=sys.stderr)
    limits = drv.cfg["check"]["limits"]
    checks = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    correct = bool(finite and check.judge(values, limits))
    if not finite:
        win["failed"] = win["attempted"]

    result = {"correct": correct, "attempted": win["attempted"],
              "failed": win["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for k, c in checks.items():
        print(f"bench: check {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
