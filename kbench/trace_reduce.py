#!/usr/bin/env python3
"""From a profiler trace (``.xplane.pb``) to device numbers.

Reads the file with ``jax.profiler.ProfileData`` and nothing else.
Device planes are the ones named ``/device:TPU:<n>``; the line
``XLA Ops`` of each holds one event per executed operation, nested
where an operation contains others (a ``while`` contains its body).

The line ``XLA Modules`` holds one event per executed program
(``jit_<name>(<hash>)``).  Host and device events share one clock.

- ``busy_s``: the union of the op intervals of a device, averaged over
  the devices that ran anything; ``window_s``: from the first to the
  last event on any plane, host threads included, so that a device
  that sat idle at either end of the trace counts as idle.  The
  profiler's own ``start_trace``/``stop_trace`` calls are left out.
- ``ops``: per op, named ``<program>/<op>`` (the HLO name up to its
  ``=``, the program without its hash), the *self* time (its duration
  less the operations nested inside it), summed over devices and
  divided by their number, so that a loop is not counted on top of its
  body.  ``op_counts``: how often each ran, per device.  ``modules``:
  the self times summed per program.

The idle intervals, and what the host did in them, are
``trace_spans.py``'s.

    trace_reduce.py <file.xplane.pb> [--dump]     # prints JSON

Run it in a process of its own with ``JAX_PLATFORMS=cpu``: importing
``jax.profiler`` must never touch the chip the server holds.
"""

import bisect
import json
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
PROFILER_OWN = re.compile(r"start_trace|stop_trace")


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _union(intervals: list) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _self_times(events: list) -> dict:
    """{name: self seconds} for one line's (start, end, name) events."""
    out, stack = {}, []      # stack of [end, name, self]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, self_s = stack.pop()
            out[name] = out.get(name, 0.0) + self_s

    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    close(float("inf"))
    return out


def _short(name: str) -> str:
    return name.split(" = ", 1)[0].strip()


def _in_modules(ops: list, modules: list) -> list:
    """Each op renamed ``<program>/<op>`` by the program event that
    contains its start."""
    modules = sorted(modules)
    starts = [m[0] for m in modules]
    out = []
    for s, e, name in ops:
        i = bisect.bisect_right(starts, s) - 1
        inside = i >= 0 and s < modules[i][1]
        prog = re.sub(r"\(\d+\)$", "", modules[i][2]) if inside else "?"
        out.append((s, e, f"{prog}/{_short(name)}"))
    return out


def reduce(path: str) -> dict:
    data = load(path)
    lo, hi = float("inf"), float("-inf")
    ops, modules = {}, {}
    for plane in data.planes:
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            keep = {OPS_LINE: ops, MODULES_LINE: modules}.get(
                line.name) if is_dev else None
            for ev in line.events:
                if PROFILER_OWN.search(ev.name):
                    continue
                # one rounding each, so that an operation that ends where
                # the next begins still does so in seconds: a sum of two
                # roundings put 0.3 s of 3 s into the wrong parent
                s = ev.start_ns * 1e-9
                e = (ev.start_ns + ev.duration_ns) * 1e-9
                lo, hi = min(lo, s), max(hi, e)
                if keep is not None:
                    keep.setdefault(plane.name, []).append((s, e, ev.name))
    window = hi - lo if hi > lo else 0.0
    if not ops:
        return {"devices": 0, "busy_s": 0.0, "window_s": window,
                "ops": {}, "op_counts": {}, "modules": {}}
    busy = [_union([(s, e) for s, e, _ in evs]) for evs in ops.values()]
    by_op, by_module, counts = {}, {}, {}
    for dev, evs in ops.items():
        named = _in_modules(evs, modules.get(dev, []))
        for _, _, name in named:
            counts[name] = counts.get(name, 0.0) + 1.0 / len(ops)
        for name, sec in _self_times(named).items():
            by_op[name] = by_op.get(name, 0.0) + sec / len(ops)
            prog = name.split("/", 1)[0]
            by_module[prog] = by_module.get(prog, 0.0) + sec / len(ops)
    return {"devices": len(ops), "busy_s": sum(busy) / len(busy),
            "window_s": window, "ops": by_op, "op_counts": counts,
            "modules": by_module}


def dump(path: str) -> dict:
    """What is in the file: planes, lines, counts and the commonest
    names.  For looking at one trace by hand before trusting a reader."""
    out = []
    for plane in load(path).planes:
        for line in plane.lines:
            names, n, lo, hi = {}, 0, float("inf"), float("-inf")
            for ev in line.events:
                n += 1
                names[ev.name] = names.get(ev.name, 0.0) + ev.duration_ns * 1e-9
                lo = min(lo, ev.start_ns)
                hi = max(hi, ev.start_ns + ev.duration_ns)
            out.append({"plane": plane.name, "line": line.name, "events": n,
                        "span_s": (hi - lo) * 1e-9 if n else 0.0,
                        "top": sorted(names.items(), key=lambda x: -x[1])[:12]})
    return {"lines": out}


if __name__ == "__main__":
    fn = dump if "--dump" in sys.argv[2:] else reduce
    json.dump(fn(sys.argv[1]), sys.stdout)
    print()
