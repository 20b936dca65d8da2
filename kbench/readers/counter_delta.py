"""How far a /metrics counter moved over the window."""


def read(ctx, *, name):
    if name not in ctx["after"]:
        return None
    return ctx["after"][name] - ctx["before"].get(name, 0.0)
