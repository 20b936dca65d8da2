"""A client-side time less the mean of a server-side histogram over
the window, in ms: what the path outside the histogram's span adds."""

from . import hist_mean_ms


def read(ctx, *, client, name):
    inner = hist_mean_ms.read(ctx, name=name)
    outer = ctx["client"].get(client)
    return None if inner is None or outer is None else outer - inner
