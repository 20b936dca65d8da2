"""Mean of a /metrics histogram over the window, in ms: the delta of
its ``_sum`` over the delta of its ``_count`` between the two scrapes."""


def read(ctx, *, name):
    b, a = ctx["before"], ctx["after"]
    n = a.get(name + "_count", 0.0) - b.get(name + "_count", 0.0)
    if n <= 0:
        return None
    return (a[name + "_sum"] - b.get(name + "_sum", 0.0)) / n * 1e3
