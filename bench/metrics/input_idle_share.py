"""Share of the traced span in which device 0 ran no operation while the
host gathered or uploaded an epoch: device 0's idle time inside the union
of the program's ``train.gather`` and ``train.upload`` spans.  A gather that
overlaps device work counts here only where the device is idle.  None where
device 0 ran nothing or the program names no such span."""
from bench import devtrace

SPANS = ("train.gather", "train.upload")


def read(span):
    events = span.devices.get(0)
    inputs = devtrace.union(ev for ev in span.host if ev[2] in SPANS)
    if not events or not inputs:
        return None
    idle = sum(e - s for lo, hi in inputs
               for s, e in devtrace.gaps(events, lo, hi))
    return 100.0 * idle / 1e9 / span.window_s
