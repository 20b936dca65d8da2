#!/usr/bin/env python3
"""Which part of a phase the device sat idle in.

``trace_spans.py`` names every instant of the engine thread by its
innermost ``engine.*`` span and sums the device's idle time by the
kind of that span.  Since PR 40 the program opens further spans inside
three of them, under another prefix so that that reduction does not
see them (``docs/observability.md``): ``host.args`` and ``host.launch``
inside each dispatch span (everything before the jitted call, and the
call itself), ``host.plan`` inside ``engine.decode`` (the planning
before a launch).  This reduction takes the same trace the same way —
the same engine thread, device extent, clock shift and
first-to-last-span window, all through ``trace_spans``' own functions,
so that the two agree to the microsecond — with the ``engine.*`` and
the ``host.*`` spans together, the innermost naming the instant:

- ``idle_part_s``: the device-idle seconds by part: ``args``,
  ``launch``, ``plan``, ``resolve`` (``engine.prefill.resolve``, which
  has no kind in ``trace_spans.KIND`` and so counts as unattributed
  there) and ``rest``.  Their sum is the sum of ``trace_spans``'
  ``idle_in_s``: ``args`` and ``launch`` are taken out of its
  ``dispatch``, ``plan`` and ``resolve`` out of its ``unattributed``
  (a launch outside a dispatch span, ``_patch_carry_row``'s, and the
  synchronous loop's plan, which lies in ``engine.schedule``, are the
  exceptions; no cell's traffic runs either).
- ``part_s``: the engine thread's own seconds in each part inside that
  window, idle device or not.

    trace_parts.py <file.xplane.pb>     # prints JSON

A trace without ``host.*`` spans (the parent of the PR that added
them) gives null for both.  ``reduced_newest`` is the harness's side,
as ``trace_spans.reduced_newest`` is.
"""

import functools
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import trace_spans                                         # noqa: E402
from kserver import BenchError, child_env, log            # noqa: E402
from paths import KBENCH, OUT, ROOT                        # noqa: E402
from trace_reduce import (DEVICE_PLANE, MODULES_LINE,      # noqa: E402
                          OPS_LINE, load)

PART = {"host.args": "args", "host.launch": "launch", "host.plan": "plan",
        "engine.prefill.resolve": "resolve"}
PARTS = ("args", "launch", "plan", "resolve", "rest")


def split(interval: tuple, pieces: list) -> dict:
    """Seconds of ``interval`` by the part of the piece that covers
    them; what no piece covers, or a piece of no part, is ``rest``."""
    lo, hi = interval
    out = dict.fromkeys(PARTS, 0.0)
    for s, e, name in pieces:
        if e <= lo:
            continue
        if s >= hi:
            break
        if name in PART:
            out[PART[name]] += min(e, hi) - max(s, lo)
    out["rest"] = (hi - lo) - sum(out.values())
    return out


def thread_lines(data) -> tuple:
    """(device ops by plane, programs, the engine thread's ``engine.*``
    spans, its ``host.*`` spans, its jitted calls): the engine thread
    is the host line that holds the most ``engine.step`` spans, as in
    ``trace_spans.reduce``."""
    ops, programs, engine, host, calls, most = {}, [], [], [], [], 0
    for plane in data.planes:
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            evs = [(ev.start_ns * 1e-9,
                    (ev.start_ns + ev.duration_ns) * 1e-9, ev.name)
                   for ev in line.events]
            if is_dev and line.name == OPS_LINE:
                ops[plane.name] = [(s, e) for s, e, _ in evs]
            elif is_dev and line.name == MODULES_LINE:
                programs += evs
            elif not is_dev:
                steps = sum(1 for ev in evs if ev[2] == trace_spans.STEP)
                if steps > most:
                    most = steps
                    calls = [ev for ev in evs if trace_spans.CALL.match(ev[2])]
                    engine = [ev for ev in evs if ev[2].startswith("engine.")]
                    host = [ev for ev in evs if ev[2].startswith("host.")]
    return ({dev: evs for dev, evs in ops.items() if evs}, programs, engine,
            host, calls)


def reduce(path: str) -> dict:
    ops, programs, engine, host, calls = thread_lines(load(path))
    out = {"devices": len(ops), "host_spans": len(host),
           "idle_part_s": None, "part_s": None}
    if not ops or not host:
        return out
    offset, _ = trace_spans.clock_offset(programs, trace_spans.launches(
        [ev for ev in engine if ev[2] in trace_spans.DISPATCH], calls))
    shift = -offset if offset is not None and offset < 0 else 0.0
    # the window is the engine.* spans' alone, as trace_spans takes it
    named = trace_spans.innermost(engine)
    seen = (named[0][0], named[-1][1])
    pieces = trace_spans.innermost(engine + host)
    idle = dict.fromkeys(PARTS, 0.0)
    for dev in sorted(ops):
        evs = [(s + shift, e + shift) for s, e in ops[dev]]
        for gap in trace_spans.idle_intervals(evs, *seen):
            for part, sec in split(gap, pieces).items():
                idle[part] += sec
    out["idle_part_s"] = {k: v / len(ops) for k, v in idle.items()}
    out["part_s"] = split(seen, pieces)
    return out


@functools.lru_cache(maxsize=4)
def _reduced(path: str, mtime: float) -> dict:
    res = subprocess.run(
        [sys.executable, os.path.join(KBENCH, "trace_parts.py"), path],
        capture_output=True, text=True, cwd=ROOT,
        env=child_env({"JAX_PLATFORMS": "cpu"}))
    if res.returncode != 0:
        raise BenchError(f"part reduction of {path} failed:\n"
                         + res.stderr[-2000:])
    out = json.loads(res.stdout)
    log(f"parts: {out.get('host_spans')} host.* spans; device idle by the "
        f"engine thread's part {json.dumps(out.get('idle_part_s'))}; the "
        f"thread's own seconds {json.dumps(out.get('part_s'))}")
    with open(os.path.join(os.path.dirname(path), "trace_parts.json"),
              "w") as f:
        json.dump(out, f)
    return out


def reduced_newest(ctx: dict):
    """The reduction of the run's trace, or None when the run took
    none: the newest file under ``kbench/out``, as
    ``trace_spans.reduced_newest`` finds it."""
    if not ctx.get("trace"):
        return None
    path = trace_spans.newest_trace(OUT)
    return _reduced(path, os.path.getmtime(path)) if path else None


if __name__ == "__main__":
    json.dump(reduce(sys.argv[1]), sys.stdout)
    print()
