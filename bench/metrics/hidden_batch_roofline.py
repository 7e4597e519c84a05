"""The least time one hidden-layer batch update needs on this chip (larger of
``bench.work`` operations over the bf16 peak and bytes over the HBM peak),
over device 0's busy time per batch in the traced span.  Busy time counts
everything the device ran, so the share survives renamed kernels."""


def read(span):
    busy = span.busy_s(0)
    if busy <= 0.0 or span.batches == 0:
        return None
    return 100.0 * span.work.least_seconds(span.peak) / (busy / span.batches)
