"""Share of the traced span in which device 0 ran no operation."""


def read(span):
    if not span.devices.get(0):
        return None
    return 100.0 * (1.0 - span.busy_s(0) / span.window_s)
