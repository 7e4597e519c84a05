"""Device 0's time in collective operations (all-reduce, all-gather,
reduce-scatter, all-to-all, collective-permute) over its busy time."""
from bench import devtrace


def read(span):
    events = span.devices.get(0)
    if span.chips < 2 or not events:
        return None
    coll = devtrace.collective_ns(events)
    if coll == 0.0:
        return None
    return 100.0 * coll / devtrace.busy_ns(events)
