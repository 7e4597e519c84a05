"""Reduction of a JAX profiler trace to device busy time, idle gaps and ops.

``load`` reads the ``.xplane.pb`` that ``jax.profiler.trace`` writes and
keeps two things, on the profiler's one clock in nanoseconds: each TPU's
operations (the ``XLA Ops`` line of its plane) as ``[start, end, name,
opcode]``, and the host's events (the benchmark's own ``TraceAnnotation``s
and the Python functions among them) as ``[start, end, name]``.  The
functions below work on that plain structure, so a CPU test can check them
on a small recorded trace.

A loop or conditional op spans the ops of its body, which the line lists
too: it counts towards busy time but is left out of the top ops, where it
would count its body twice.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute")
CONTAINERS = ("while", "conditional", "call")
# "%name = <shape> opcode(operands), attributes"; a shape holds no "word(".
HLO = re.compile(r"^%?([^\s=]+) = .*?\s([a-z][a-z0-9-]*)\(")


def op_name(text: str):
    """``(name, opcode)`` of an ``XLA Ops`` event's HLO text."""
    m = HLO.match(text)
    return (m.group(1), m.group(2)) if m else (text, "")


def load(profile_dir: str) -> dict:
    """``{"devices": {id: [[start, end, name], ...]}, "host": [[start, end,
    name], ...]}`` from the newest trace under ``profile_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend([e.start_ns, e.start_ns + e.duration_ns,
                                *op_name(e.name)] for e in line.events)
            devices[int(m.group(1))] = sorted(ops)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend([e.start_ns, e.start_ns + e.duration_ns, e.name]
                            for e in line.events)
    return {"devices": devices, "host": sorted(host)}


def clip(events, lo: float, hi: float):
    """Events cut to the window ``[lo, hi]``; those outside it dropped."""
    return [[max(ev[0], lo), min(ev[1], hi), *ev[2:]] for ev in events
            if ev[1] > lo and ev[0] < hi]


def union(events):
    """Merged ``[start, end]`` intervals covered by ``events``."""
    merged = []
    for s, e, *_ in sorted(events):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_ns(events) -> float:
    return float(sum(e - s for s, e in union(events)))


def collective_ns(events) -> float:
    """Time covered by collective operations (their own union), found by
    the op's name or opcode, never by its operands."""
    return busy_ns([ev for ev in events
                    if COLLECTIVE.search(" ".join(ev[2:]))])


def top_ops(events, n: int = 10):
    """The ``n`` operation names that took most device time, in seconds;
    loops and conditionals left out."""
    total = {}
    for s, e, name, *kind in events:
        if kind and kind[0] in CONTAINERS:
            continue
        total[name] = total.get(name, 0) + (e - s)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def gaps(events, lo: float, hi: float):
    """Idle ``[start, end]`` intervals of the device inside ``[lo, hi]``."""
    out, t = [], lo
    for s, e in union(clip(events, lo, hi)):
        if s > t:
            out.append([t, s])
        t = max(t, e)
    if hi > t:
        out.append([t, hi])
    return out


def label(host, t0: float, t1: float) -> str:
    """What the host was doing in ``[t0, t1]``: the shortest host event that
    covers the interval's midpoint, or "host idle"."""
    mid = (t0 + t1) / 2
    best = None
    for s, e, name in host:
        if s > mid:
            break
        if e >= mid and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "host idle"


def idle_gaps(events, host, lo: float, hi: float, n: int = 10):
    """The ``n`` longest idle gaps in ``[lo, hi]``, labelled by the host."""
    longest = sorted(gaps(events, lo, hi), key=lambda g: g[0] - g[1])[:n]
    return [[label(host, s, e), (e - s) / 1e9] for s, e in longest]


def annotated_window(host, name: str):
    """``(start, end)`` spanned by the host events called ``name``."""
    spans = [(s, e) for s, e, n in host if n == name]
    if not spans:
        raise ValueError(f"no host event {name!r} in the trace")
    return min(s for s, _ in spans), max(e for _, e in spans)


class Span:
    """A traced span of whole calls, as the per-layer metrics read it.

    ``devices`` and ``host`` are ``load``'s events cut to ``[lo, hi]`` (ns);
    ``batches``, ``samples`` and ``host_s`` are what the calls in the span
    reported; ``work`` is the work of one batch, ``peak`` the chip's peaks.
    """

    def __init__(self, trace: dict, lo: float, hi: float, *, chips: int,
                 batches: int, samples: int, host_s: float, work,
                 device_kind: str):
        self.lo, self.hi = lo, hi
        self.devices = {d: clip(ev, lo, hi) for d, ev in trace["devices"].items()
                        if d < chips}
        self.host = clip(trace["host"], lo, hi)
        self.chips = chips
        self.batches = batches
        self.samples = samples
        self.host_s = host_s
        self.work = work
        self.device_kind = device_kind

    @property
    def peak(self):
        from bench.peaks import peaks

        return peaks(self.device_kind)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def busy_s(self, device: int = 0) -> float:
        return busy_ns(self.devices.get(device, [])) / 1e9

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s(d) for d in range(self.chips)) / self.chips
