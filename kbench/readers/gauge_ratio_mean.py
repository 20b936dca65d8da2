"""Mean over the window's polls of one /metrics gauge over another, at
the polls where the second is above zero (a polled mean, not an
integral: what happens between polls is unseen)."""


def read(ctx, *, name, over):
    vals = [p[name] / p[over] for p in ctx["polls"]
            if name in p and p.get(over, 0) > 0]
    return None if not vals else sum(vals) / len(vals)
