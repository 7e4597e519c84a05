"""Share of the traced span in which the host gathered epochs: the union of
the program's ``train.gather`` spans (``ScanPlan``'s shuffled epoch stack,
before its upload).  None where the program names no such span, or where
device 0 ran nothing (a trace without a chip, as on the CPU)."""
from bench import devtrace

SPAN = "train.gather"


def read(span):
    events = [ev for ev in span.host if ev[2] == SPAN]
    if not events or not span.devices.get(0):
        return None
    return 100.0 * devtrace.busy_ns(events) / 1e9 / span.window_s
