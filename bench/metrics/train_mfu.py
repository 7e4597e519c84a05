"""Required operations per sample (``bench.work``) times the traced span's
samples per second, over the chips' summed bf16 peak."""


def read(span):
    if not span.devices.get(0):
        return None
    rate = span.samples / span.window_s
    return (100.0 * span.work.flops_per_sample * rate
            / (span.chips * span.peak.bf16_flops))
