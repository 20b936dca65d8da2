"""Mean of a /metrics gauge polled at a fixed period during the window
(a polled mean, not an integral: what happens between polls is unseen)."""


def read(ctx, *, name, scale=1.0):
    vals = [p[name] for p in ctx["polls"] if name in p]
    return None if not vals else scale * sum(vals) / len(vals)
