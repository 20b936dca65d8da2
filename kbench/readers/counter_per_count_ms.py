"""A /metrics counter of seconds over the ``_count`` of a histogram,
each as its delta over the window, in ms: what one observed event cost
in a currency the histogram does not hold (a handler thread's CPU
seconds over the chunks it streamed)."""


def read(ctx, *, seconds, count):
    b, a = ctx["before"], ctx["after"]
    if seconds not in a:
        return None
    n = a.get(count, 0.0) - b.get(count, 0.0)
    if n <= 0:
        return None
    return (a[seconds] - b.get(seconds, 0.0)) / n * 1e3
