"""Share of the device-idle time, inside the device's active extent
and between the engine thread's first and last recorded span, that
fell while that thread was in a phase of this ``kind``
(``trace_spans.KIND``; ``unattributed`` is the rest).  Nothing to read
from a program that does not mark its phases."""

import trace_spans


def read(ctx, *, kind):
    t = trace_spans.reduced_newest(ctx)
    parts = t.get("idle_in_s") if t else None
    if not parts or sum(parts.values()) <= 0:
        return None
    return 100.0 * parts[kind] / sum(parts.values())
