"""Share of the device-idle time that fell while the engine thread was
in this ``part`` of a phase (``trace_parts.PART``: ``args``,
``launch``, ``plan``, ``resolve``), of the same total that
``trace_idle_in_pct`` divides by: the idle time inside the device's
active extent and between the engine thread's first and last recorded
span.  Nothing to read from a program that opens no ``host.*`` span."""

import trace_parts


def read(ctx, *, part):
    t = trace_parts.reduced_newest(ctx)
    parts = t.get("idle_part_s") if t else None
    if not parts or sum(parts.values()) <= 0:
        return None
    return 100.0 * parts[part] / sum(parts.values())
