#!/usr/bin/env python3
"""What the host was doing while the device sat idle.

The program marks the phases of its serving loop with
``jax.profiler.TraceAnnotation`` spans (``engine.step`` and its
children, ``docs/observability.md``), so they land in the profiler's
own trace, nested by thread, beside the device's operations.  This
reduction overlaps the two:

- the device's *active extent* runs from its first to its last
  operation (``XLA Ops`` of each ``/device:TPU:<n>`` plane);
  ``busy_s`` is the union of the operations inside it and ``idle_s``
  the rest.  The profiler's own start and stop, and host threads that
  outlive the device's work, are outside the extent.
- the *engine thread* is the host line that holds ``engine.step``.
  Every instant of it belongs to its innermost ``engine.*`` span, and
  each span name to one kind (``KIND``); an instant in no span, in
  ``engine.idle`` or in a span's own time that names no work
  (``engine.step``, ``engine.decode``) is ``unattributed``.
  ``idle_in_s`` is the device-idle time by the kind the engine thread
  was in, ``gaps`` the longest idle intervals with the same split.
  ``idle_by_name`` is every idle interval under a name that says what
  the engine thread was doing in it (``gap_name``: phases, no time of
  day, so that one kind of gap has one name in every run), the
  seconds of one name summed, the largest first: the ``idle_gaps`` of
  a run's ``breakdown``.  All three are taken between the engine
  thread's first and last recorded span: a span still open when the
  trace stops is not recorded, so what the thread did at the trace's
  edges cannot be told.
- host and device clocks may differ by a small offset (in
  ``testdata/tiny.xplane.pb`` a program starts 1.2 ms before the call
  that launched it).  The program an ``engine.*.dispatch`` span
  launched is the one its last jitted call names (the profiler's
  ``PjitFunction(<name>)`` event on the engine thread; the device's
  ``XLA Modules`` event is ``jit_<name>(<hash>)``): the calls before
  it build arguments.  A program cannot start before the call that
  launched it, so the smallest (program start - call start) bounds
  the offset from above: when it is negative the device's events are
  moved later by it.  ``clock_offset_ms`` is that smallest difference,
  ``clock_shift_ms`` what was applied, ``early_programs`` how many
  programs still start before their dispatch span opens afterwards.

    trace_spans.py <file.xplane.pb>     # prints JSON

A trace of a program without the spans (the parent of the PR that
added them) gives the extent, ``busy_s`` and ``idle_s``, every idle
interval under the one name ``unattributed``, and null for the rest.
``reduced_newest`` is the harness's side: it runs this file in a
child held to the CPU, as ``run.py`` runs ``trace_reduce.py``.
"""

import functools
import json
import os
import re
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from kserver import BenchError, child_env, log            # noqa: E402
from paths import KBENCH, OUT, ROOT                        # noqa: E402
from trace_reduce import (DEVICE_PLANE, MODULES_LINE,      # noqa: E402
                          OPS_LINE, load)

STEP = "engine.step"
DISPATCH = ("engine.decode.dispatch", "engine.prefill.dispatch")
KIND = {"engine.decode.replay": "replay",
        "engine.decode.dispatch": "dispatch",
        "engine.prefill.dispatch": "dispatch",
        "engine.schedule": "schedule",
        "engine.prefill": "prefill",
        "engine.decode.wait": "wait",
        "engine.prefill.wait": "wait"}
KINDS = ("replay", "dispatch", "schedule", "prefill", "wait", "unattributed")
CALL = re.compile(r"^PjitFunction\((.+)\)$")
# a phase is part of a gap's name when it covers this share of the gap
NAMED_SHARE = 0.25
# a program of the same name that starts this long before a call was
# launched by an earlier call: far above any clock offset, far below
# the time between two dispatches of one program
SLACK_S = 0.005


def merge(intervals: list) -> list:
    """The union of (start, end) intervals as a sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def idle_intervals(ops: list, lo=float("-inf"), hi=float("inf")) -> list:
    """The gaps between the first and the last of ``ops``, cut to
    ``lo``..``hi``."""
    busy = merge(ops)
    cut = [(max(a[1], lo), min(b[0], hi)) for a, b in zip(busy, busy[1:])]
    return [(s, e) for s, e in cut if e > s]


def innermost(spans: list) -> list:
    """Properly nested (start, end, name) spans of one thread as
    disjoint (start, end, name) pieces, each named by the innermost
    span that covers it."""
    pieces, stack, at = [], [], float("-inf")     # stack of (end, name)

    def emit(upto):
        nonlocal at
        if stack and upto > at:
            pieces.append((at, upto, stack[-1][1]))
        at = max(at, upto)

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            emit(stack[-1][0])
            stack.pop()
        emit(s)
        if stack:
            e = min(e, stack[-1][0])       # clock jitter: keep it nested
        stack.append((e, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return pieces


def split(interval: tuple, pieces: list) -> dict:
    """Seconds of ``interval`` by the kind of the piece that covers
    them; what no piece covers is ``unattributed``."""
    lo, hi = interval
    out = dict.fromkeys(KINDS, 0.0)
    covered = 0.0
    for s, e, name in pieces:
        if e <= lo:
            continue
        if s >= hi:
            break
        sec = min(e, hi) - max(s, lo)
        out[KIND.get(name, "unattributed")] += sec
        covered += sec
    out["unattributed"] += (hi - lo) - covered
    return out


def _phase(span: str) -> str:
    """``engine.decode.wait`` as ``decode.wait``: the ledger keeps 64
    characters of a name.  ``engine.idle`` stays whole."""
    return span if span == "engine.idle" else span[len("engine."):]


def gap_name(interval: tuple, pieces: list) -> str:
    """What the engine thread was doing in a device-idle interval:
    ``idle in <phases> after <phase>``.  The phases are the innermost
    ``engine.*`` spans that each cover ``NAMED_SHARE`` of it or more
    (the largest alone when none does), largest first; ``after`` is the
    one the thread was in when the device ran dry, where that is none
    of them.  Time of the thread in no span counts as ``no-span``; an
    interval that meets no span at all is ``unattributed``."""
    lo, hi = interval
    sec, first = {}, None
    for s, e, name in pieces:
        if e <= lo:
            continue
        if s >= hi:
            break
        if s <= lo:
            first = name
        sec[name] = sec.get(name, 0.0) + min(e, hi) - max(s, lo)
    if not sec:
        return "unattributed"
    rest = (hi - lo) - sum(sec.values())
    if rest > 0:
        sec["engine.no-span"] = rest
    by_size = sorted(sec, key=lambda n: (-sec[n], n))
    named = [n for n in by_size if sec[n] >= NAMED_SHARE * (hi - lo)]
    named = named or by_size[:1]
    after = (f" after {_phase(first)}"
             if first is not None and first not in named else "")
    return "idle in " + "+".join(_phase(n) for n in named) + after


def launches(dispatches: list, calls: list) -> list:
    """Per dispatch span that holds a jitted call, (span start, call
    start, program name) of its last one."""
    out = []
    for s, e, _ in sorted(dispatches):
        inside = [(cs, CALL.match(name).group(1)) for cs, _, name in calls
                  if s <= cs < e]
        if inside:
            out.append((s,) + max(inside))
    return out


def clock_offset(programs: list, launched: list):
    """The smallest (program start - call start) in seconds over
    ``launched`` (from ``launches``), and the (span start, call start,
    program start) triples it was taken over; None with no pair.  A
    call's program is the first of its name on the device, not yet
    taken, from ``SLACK_S`` before the call."""
    by_name = {}
    for s, _, name in sorted(programs):
        by_name.setdefault(re.sub(r"\(\d+\)$", "", name), []).append(s)
    pairs = []
    for span_s, call_s, name in launched:
        starts = by_name.get("jit_" + name, [])
        while starts and starts[0] < call_s - SLACK_S:
            starts.pop(0)
        if starts:
            pairs.append((span_s, call_s, starts.pop(0)))
    if not pairs:
        return None, []
    return min(p - c for _, c, p in pairs), pairs


def reduce(path: str, top: int = 10) -> dict:
    data = load(path)
    ops, programs, engine, calls, most = {}, [], [], [], 0
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
                steps = sum(1 for ev in evs if ev[2] == STEP)
                if steps > most:        # the engine thread's line
                    most = steps
                    calls = [ev for ev in evs if CALL.match(ev[2])]
                    engine = [ev for ev in evs
                              if ev[2].startswith("engine.")]
    ops = {dev: evs for dev, evs in ops.items() if evs}
    if not ops:
        return {"devices": 0, "active_s": 0.0, "busy_s": 0.0, "idle_s": 0.0}
    offset, pairs = clock_offset(programs, launches(
        [ev for ev in engine if ev[2] in DISPATCH], calls))
    shift = -offset if offset is not None and offset < 0 else 0.0
    pieces = innermost(engine)
    seen = ((pieces[0][0], pieces[-1][1]) if pieces
            else (float("-inf"), float("inf")))
    active = busy = 0.0
    idle_in = dict.fromkeys(KINDS, 0.0)
    gaps, by_name = [], {}
    for dev in sorted(ops):
        evs = [(s + shift, e + shift) for s, e in ops[dev]]
        lo = min(s for s, _ in evs)
        active += max(e for _, e in evs) - lo
        busy += sum(e - s for s, e in merge(evs))
        for gap in idle_intervals(evs, *seen):
            parts = split(gap, pieces)
            for kind, sec in parts.items():
                idle_in[kind] += sec
            gaps.append((gap[1] - gap[0], gap[0] - lo, parts))
            name = gap_name(gap, pieces)
            by_name[name] = (by_name.get(name, 0.0)
                             + (gap[1] - gap[0]) / len(ops))
    out = {"devices": len(ops), "active_s": active / len(ops),
           "busy_s": busy / len(ops), "idle_s": (active - busy) / len(ops),
           "engine_spans": len(engine), "clock_offset_ms": None,
           "clock_shift_ms": shift * 1e3, "early_programs": None,
           "idle_in_s": None, "gaps": None,
           "idle_by_name": [[name, sec] for name, sec in sorted(
               by_name.items(), key=lambda x: (-x[1], x[0]))[:top]]}
    if engine:
        out["idle_in_s"] = {k: v / len(ops) for k, v in idle_in.items()}
        out["gaps"] = [[at, sec, parts] for sec, at, parts
                       in sorted(gaps, key=lambda g: -g[0])[:top]]
    if offset is not None:
        out["clock_offset_ms"] = offset * 1e3
        out["early_programs"] = sum(1 for s, _, p in pairs if p + shift < s)
    return out


@functools.lru_cache(maxsize=4)
def _reduced(path: str, mtime: float) -> dict:
    res = subprocess.run(
        [sys.executable, os.path.join(KBENCH, "trace_spans.py"), path],
        capture_output=True, text=True, cwd=ROOT,
        env=child_env({"JAX_PLATFORMS": "cpu"}))
    if res.returncode != 0:
        raise BenchError(f"span reduction of {path} failed:\n"
                         + res.stderr[-2000:])
    out = json.loads(res.stdout)
    log(f"spans: clock offset {out.get('clock_offset_ms')} ms, shifted by "
        f"{out.get('clock_shift_ms')} ms, early programs "
        f"{out.get('early_programs')}; device idle by the engine thread's "
        f"phase {json.dumps(out.get('idle_in_s'))}; longest gaps "
        f"{json.dumps(out.get('gaps'))}")
    with open(os.path.join(os.path.dirname(path), "trace_spans.json"),
              "w") as f:
        json.dump(out, f)
    return out


def newest_trace(root: str):
    """The newest ``.xplane.pb`` under ``root``, or None."""
    found = [os.path.join(base, f) for base, _, files in os.walk(root)
             for f in files if f.endswith(".xplane.pb")]
    return max(found, key=os.path.getmtime) if found else None


def reduced_newest(ctx: dict):
    """The reduction of the run's trace, or None when the run took
    none.  ``ctx`` carries no path: ``run.py`` wipes the cell's
    directory and writes one trace a run, so the newest file under
    ``kbench/out`` is this run's."""
    if not ctx.get("trace"):
        return None
    path = newest_trace(OUT)
    return _reduced(path, os.path.getmtime(path)) if path else None


if __name__ == "__main__":
    json.dump(reduce(sys.argv[1]), sys.stdout)
    print()
