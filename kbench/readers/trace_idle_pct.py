"""Share of the traced window in which no operation ran on the device
(averaged over the devices used)."""


def read(ctx):
    t = ctx["trace"]
    if not t or t["window_s"] <= 0 or t["devices"] == 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
