"""``part`` over ``whole`` as a percentage, each the delta of a /metrics
counter over the window (``counter_share_pct`` takes a part and the
rest; here the whole is a counter of its own)."""


def read(ctx, *, part, whole):
    def delta(n):
        return ctx["after"].get(n, 0.0) - ctx["before"].get(n, 0.0)

    if whole not in ctx["after"] or delta(whole) <= 0:
        return None
    return 100.0 * delta(part) / delta(whole)
