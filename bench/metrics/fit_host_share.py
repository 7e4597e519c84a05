"""Share of the traced span that ``fit`` spent on the host before its epoch
fences: the sum of ``FitResult.history[*].host_s`` over the span's calls."""


def read(span):
    return 100.0 * span.host_s / span.window_s
