"""Share of the device's active extent — from its first to its last
operation in the trace — in which no operation ran on it (averaged
over the devices used).  The extent, not the traced window: that one
holds the profiler's own start and stop, and moves with the tracer."""

import trace_spans


def read(ctx):
    t = trace_spans.reduced_newest(ctx)
    if not t or t["devices"] == 0 or t["active_s"] <= 0:
        return None
    return 100.0 * t["idle_s"] / t["active_s"]
