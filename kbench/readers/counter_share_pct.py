"""``part`` over ``part`` + ``rest`` as a percentage, each the delta of
a /metrics counter over the window."""


def read(ctx, *, part, rest):
    def delta(n):
        return ctx["after"].get(n, 0.0) - ctx["before"].get(n, 0.0)

    total = delta(part) + delta(rest)
    return None if total <= 0 else 100.0 * delta(part) / total
