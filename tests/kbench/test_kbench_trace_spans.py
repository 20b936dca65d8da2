"""The reduction that overlaps the program's phase spans with the
device's idle time: on synthetic intervals, and on a small annotated
trace recorded on the chip."""

import os

import pytest

import trace_spans
from paths import KBENCH

PLAIN = os.path.join(KBENCH, "testdata", "tiny.xplane.pb")
SPANS = os.path.join(KBENCH, "testdata", "tiny_spans.xplane.pb")

LOOP = [(0, 10, "engine.step"), (1, 3, "engine.schedule"),
        (3, 9, "engine.decode"), (3.5, 4, "engine.decode.dispatch"),
        (4, 7, "engine.decode.wait"), (7, 9, "engine.decode.replay"),
        (12, 13, "engine.idle"),
        (20, 30, "engine.step"), (21, 29, "engine.prefill"),
        (22, 24, "engine.prefill.dispatch"), (24, 28, "engine.prefill.wait")]


def test_idle_is_what_lies_between_the_merged_operations():
    ops = [(0, 1), (0.5, 2), (4, 5), (4.2, 4.4), (7, 8)]
    assert trace_spans.merge(ops) == [(0, 2), (4, 5), (7, 8)]
    assert trace_spans.idle_intervals(ops) == [(2, 4), (5, 7)]
    assert trace_spans.idle_intervals([(1, 2)]) == []
    # cut to what the engine thread's recorded spans cover
    assert trace_spans.idle_intervals(ops, 3, 6) == [(3, 4), (5, 6)]
    assert trace_spans.idle_intervals(ops, 4.5, 5) == []


def test_every_instant_belongs_to_its_innermost_span():
    pieces = trace_spans.innermost(LOOP)
    assert pieces[:5] == [(0, 1, "engine.step"), (1, 3, "engine.schedule"),
                          (3, 3.5, "engine.decode"),
                          (3.5, 4, "engine.decode.dispatch"),
                          (4, 7, "engine.decode.wait")]
    assert (21, 22, "engine.prefill") in pieces       # outside its children
    assert (28, 29, "engine.prefill") in pieces
    # disjoint, in order, and as long as the outermost spans together
    assert all(a[1] <= b[0] for a, b in zip(pieces, pieces[1:]))
    assert sum(e - s for s, e, _ in pieces) == 10 + 1 + 10


def test_an_idle_interval_splits_by_kind_and_adds_up():
    pieces = trace_spans.innermost(LOOP)
    got = trace_spans.split((2.5, 12.5), pieces)
    assert got == {"replay": 2.0, "dispatch": 0.5, "schedule": 0.5,
                   "prefill": 0.0, "wait": 3.0,
                   # decode's own 0.5, step's own 1, no span 2, idle 0.5
                   "unattributed": 4.0}
    got = trace_spans.split((20.5, 28.5), pieces)
    assert got["dispatch"] == 2.0 and got["wait"] == 4.0
    assert got["prefill"] == 1.5 and got["unattributed"] == 0.5
    assert sum(got.values()) == pytest.approx(8.0)
    assert sum(trace_spans.split((40, 41), pieces).values()) == 1.0


@pytest.mark.parametrize("interval,name", [
    # one phase holds it all
    ((4.5, 6.5), "idle in decode.wait"),
    # the device ran dry in the wait; replay took the time
    ((6.8, 9), "idle in decode.replay after decode.wait"),
    # two phases of a quarter or more, largest first; decode's own 0.3
    # is too small to be named, but it is where the gap began
    ((3.2, 5), "idle in decode.wait+decode.dispatch after decode"),
    # the thread in no span for most of it, engine.idle whole
    ((9.5, 13), "idle in no-span+engine.idle after step"),
    # no phase reaches a quarter: the largest alone
    ((0, 30), "idle in no-span after step"),
    # before the thread's first span and after its last
    ((-5, -1), "unattributed"), ((40, 41), "unattributed")])
def test_a_gap_is_named_by_the_phases_that_cover_it(interval, name):
    pieces = trace_spans.innermost(LOOP)
    assert trace_spans.gap_name(interval, pieces) == name
    # no time of day in a name: the same gap one iteration later
    later = [(s + 100, e + 100, n) for s, e, n in pieces]
    moved = (interval[0] + 100, interval[1] + 100)
    assert trace_spans.gap_name(moved, later) == name
    assert len(name) <= 64                  # what the ledger keeps


def test_the_clock_offset_is_the_earliest_program_against_its_call():
    dispatches = [(1.0, 1.006, "engine.decode.dispatch"),
                  (1.2, 1.202, "engine.prefill.dispatch"),
                  (2.0, 2.006, "engine.decode.dispatch")]
    # argument-building calls first, the launch last; one call outside
    calls = [(1.001, 1.0012, "PjitFunction(broadcast_in_dim)"),
             (1.005, 1.0054, "PjitFunction(decode_multi)"),
             (1.1, 1.1002, "PjitFunction(scatter)"),
             (1.201, 1.2014, "PjitFunction(prefill_step)"),
             (2.005, 2.0054, "PjitFunction(decode_multi)")]
    launched = trace_spans.launches(dispatches, calls)
    assert launched == [(1.0, 1.005, "decode_multi"),
                        (1.2, 1.201, "prefill_step"),
                        (2.0, 2.005, "decode_multi")]
    # the device's clock runs 1.5 ms early; launches take 0.3-2 ms; the
    # small programs of argument building run just before, and are
    # nobody's launch
    early = 0.0015
    programs = [(1.0012 - early, 1.00121 - early, "jit_broadcast_in_dim(7)"),
                (1.005 + 0.0003 - early, 1.07, "jit_decode_multi(123)"),
                (1.1004 - early, 1.10041 - early, "jit_scatter(9)"),
                (1.201 + 0.002 - early, 1.23, "jit_prefill_step(45)"),
                (2.005 + 0.0005 - early, 2.07, "jit_decode_multi(123)")]
    offset, pairs = trace_spans.clock_offset(programs, launched)
    assert offset == pytest.approx(0.0003 - early)
    assert [(s, c) for s, c, _ in pairs] == [(1.0, 1.005), (1.2, 1.201),
                                             (2.0, 2.005)]
    # shifted by it, no program starts before the call that launched it
    assert all(p - offset >= c - 1e-12 for _, c, p in pairs)
    # a program from before the trace's first call is nobody's
    stale = [(0.9, 0.95, "jit_decode_multi(123)")] + programs
    assert trace_spans.clock_offset(stale, launched)[0] == pytest.approx(
        offset)
    assert trace_spans.clock_offset([], launched) == (None, [])
    assert trace_spans.clock_offset(programs, []) == (None, [])
    # a positive smallest difference is launch latency, not an offset
    offset, _ = trace_spans.clock_offset(
        [(1.0054, 1.07, "jit_decode_multi(1)")], launched[:1])
    assert offset == pytest.approx(0.0004)


@pytest.fixture(scope="module")
def recorded():
    """Three iterations shaped like the engine loop's, on a thread that
    existed before the trace started, recorded on one v5e chip with the
    Python tracer off (PERF.md section 3 describes the file)."""
    return trace_spans.reduce(SPANS)


def test_recorded_spans_attribute_the_idle_time(recorded):
    t = recorded
    assert t["devices"] == 1 and t["engine_spans"] == 30
    assert 0 < t["busy_s"] < t["active_s"]
    assert t["idle_s"] == pytest.approx(t["active_s"] - t["busy_s"])
    parts = t["idle_in_s"]
    assert set(parts) == set(trace_spans.KINDS)
    # the idle time between the thread's first and last recorded span:
    # all of it but the edges of the trace
    assert 0.9 * t["idle_s"] < sum(parts.values()) <= t["idle_s"]
    # what the loop slept in each phase, three times over: 2 ms of
    # replay, 0.5 ms of schedule, 0.3 ms of prefill outside its
    # children, 1 ms of engine.idle (unattributed)
    assert parts["replay"] >= 3 * 0.002
    assert parts["schedule"] >= 3 * 0.0005
    assert parts["prefill"] >= 3 * 0.0003
    assert parts["unattributed"] >= 2 * 0.001
    assert parts["dispatch"] > 0 and parts["wait"] > 0
    # the ten longest gaps, each split the same way
    assert len(t["gaps"]) == 10
    assert [g[1] for g in t["gaps"]] == sorted(
        (g[1] for g in t["gaps"]), reverse=True)
    for at, sec, split in t["gaps"]:
        assert sum(split.values()) == pytest.approx(sec, abs=1e-12)


def test_recorded_gaps_are_summed_under_their_names(recorded):
    named = recorded["idle_by_name"]
    assert 1 <= len(named) <= 10
    assert [sec for _, sec in named] == sorted(
        (sec for _, sec in named), reverse=True)
    names = [n for n, _ in named]
    assert len(set(names)) == len(names)
    # every idle interval is under some name: nothing lost in the summing
    assert sum(sec for _, sec in named) == pytest.approx(
        sum(recorded["idle_in_s"].values()))
    # the loop slept 2 ms in its replay after each launch, three times,
    # and that is the largest name; none carries a time or a digit
    assert named[0][0] == "idle in decode.replay after decode.dispatch"
    assert named[0][1] >= 3 * 0.002
    assert all(n.startswith("idle in ") and not any(c.isdigit() for c in n)
               for n in names)


def test_recorded_clock_offset_is_applied(recorded):
    """That process's device clock ran about a millisecond early: the
    reduction finds it and moves the device's events."""
    t = recorded
    assert -3.0 < t["clock_offset_ms"] < 0
    assert t["clock_shift_ms"] == pytest.approx(-t["clock_offset_ms"])
    assert t["early_programs"] == 0


def test_readers_on_the_recorded_spans(recorded, monkeypatch):
    from readers import trace_active_idle_pct, trace_idle_in_pct

    monkeypatch.setattr(trace_spans, "reduced_newest", lambda ctx: recorded)
    ctx = {"trace": {"busy_s": 1.0}}
    idle = trace_active_idle_pct.read(ctx)
    assert idle == pytest.approx(
        100.0 * recorded["idle_s"] / recorded["active_s"])
    shares = {k: trace_idle_in_pct.read(ctx, kind=k)
              for k in trace_spans.KINDS}
    assert sum(shares.values()) == pytest.approx(100.0)
    assert shares["replay"] > shares["dispatch"]


def test_a_trace_without_spans_gives_the_extent_and_nothing_else():
    """What the parent of the PR that added the spans gives."""
    from readers import trace_active_idle_pct, trace_idle_in_pct

    t = trace_spans.reduce(PLAIN)
    assert t["devices"] == 1 and 0 < t["busy_s"] < t["active_s"]
    assert t["idle_s"] == pytest.approx(t["active_s"] - t["busy_s"])
    assert t["idle_in_s"] is None and t["gaps"] is None
    # one name for all of its idle time, and it does not say why
    assert t["idle_by_name"] == [["unattributed", pytest.approx(t["idle_s"])]]
    assert t["clock_offset_ms"] is None and t["clock_shift_ms"] == 0.0
    assert trace_active_idle_pct.read({"trace": None}) is None
    assert trace_idle_in_pct.read({"trace": None}, kind="replay") is None


def test_the_harness_side_reduces_the_newest_trace_in_a_child(
        recorded, tmp_path, monkeypatch):
    import shutil
    import time

    monkeypatch.setattr(trace_spans, "OUT", str(tmp_path))
    assert trace_spans.reduced_newest({"trace": {"busy_s": 1.0}}) is None
    old = tmp_path / "other-cell" / "profile" / "a"
    new = tmp_path / "this-cell" / "profile" / "b"
    old.mkdir(parents=True)
    new.mkdir(parents=True)
    shutil.copy(PLAIN, old / "x.xplane.pb")
    time.sleep(0.02)
    shutil.copy(SPANS, new / "y.xplane.pb")
    # a run that took no trace reads none, whatever lies around
    assert trace_spans.reduced_newest({"trace": None}) is None
    got = trace_spans.reduced_newest({"trace": {"busy_s": 1.0}})
    assert got["idle_in_s"] == pytest.approx(recorded["idle_in_s"])
    assert (new / "trace_spans.json").exists()
    assert trace_spans.reduced_newest({"trace": {"busy_s": 1.0}}) is got
