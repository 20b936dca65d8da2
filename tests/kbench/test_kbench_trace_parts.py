"""The reduction that splits the device's idle time by the part of a
phase the engine thread was in (``host.args``, ``host.launch``,
``host.plan``, ``engine.prefill.resolve``): on synthetic intervals, on
a small annotated trace recorded on the chip, and against
``trace_spans``, which must not see the new spans at all."""

import os

import pytest

import trace_parts
import trace_spans
from paths import KBENCH

PARTS = os.path.join(KBENCH, "testdata", "tiny_parts.xplane.pb")
OLD = os.path.join(KBENCH, "testdata", "tiny_spans.xplane.pb")

ENGINE = [(0, 10, "engine.step"), (1, 3, "engine.schedule"),
          (3, 9, "engine.decode"), (4, 6, "engine.decode.dispatch"),
          (6, 7, "engine.decode.wait"), (7, 8, "engine.decode.replay"),
          (8, 9, "engine.prefill.resolve"),
          (20, 30, "engine.step"), (21, 29, "engine.prefill"),
          (22, 26, "engine.prefill.dispatch"),
          (26, 28, "engine.prefill.wait")]
HOST = [(3.2, 4, "host.plan"), (4, 4.5, "host.args"),
        (4.5, 5.75, "host.launch"),
        (22, 23, "host.args"), (23, 25.5, "host.launch")]
# a drain inside the plan: its own spans are innermost there
DRAIN = [(40, 50, "engine.step"), (41, 49, "engine.decode"),
         (42, 47, "host.plan"), (43, 45, "engine.decode.wait"),
         (45, 46, "engine.decode.replay")]


def test_the_innermost_of_both_kinds_of_span_names_the_instant():
    pieces = trace_spans.innermost(ENGINE + HOST + DRAIN)
    assert (3, 3.2, "engine.decode") in pieces
    assert (3.2, 4, "host.plan") in pieces
    assert (4, 4.5, "host.args") in pieces
    assert (4.5, 5.75, "host.launch") in pieces
    assert (5.75, 6, "engine.decode.dispatch") in pieces     # its own time
    assert (8, 9, "engine.prefill.resolve") in pieces
    assert (42, 43, "host.plan") in pieces and (46, 47, "host.plan") in pieces
    assert (43, 45, "engine.decode.wait") in pieces
    assert (45, 46, "engine.decode.replay") in pieces
    assert all(a[1] <= b[0] for a, b in zip(pieces, pieces[1:]))


@pytest.mark.parametrize("interval", [(2.5, 12.5), (20.5, 28.5), (3.1, 5.0),
                                      (40, 52), (-5, -1), (0, 60)])
def test_the_parts_add_up_to_what_the_phases_add_up_to(interval):
    """The same idle interval, split by part here and by kind there:
    one total; args and launch come out of dispatch, plan and resolve
    out of unattributed, and nothing else moves."""
    both = trace_spans.innermost(ENGINE + HOST + DRAIN)
    alone = trace_spans.innermost(ENGINE + DRAIN)
    parts = trace_parts.split(interval, both)
    kinds = trace_spans.split(interval, alone)
    assert set(parts) == set(trace_parts.PARTS)
    assert sum(parts.values()) == pytest.approx(sum(kinds.values()))
    assert sum(parts.values()) == pytest.approx(interval[1] - interval[0])
    assert parts["args"] + parts["launch"] <= kinds["dispatch"] + 1e-12
    assert parts["plan"] + parts["resolve"] <= kinds["unattributed"] + 1e-12
    assert all(v >= -1e-12 for v in parts.values())


def test_a_split_by_part_on_known_numbers():
    both = trace_spans.innermost(ENGINE + HOST + DRAIN)
    got = trace_parts.split((2.5, 12.5), both)
    assert got == pytest.approx({"args": 0.5, "launch": 1.25, "plan": 0.8,
                                 "resolve": 1.0, "rest": 6.45})
    # the drain's wait and replay are not the plan's
    got = trace_parts.split((40, 50), both)
    assert got["plan"] == pytest.approx(2.0) and got["rest"] == \
        pytest.approx(8.0)


class _Line:
    def __init__(self, line, keep):
        self.name = line.name
        self.events = [ev for ev in line.events if keep(ev.name)]


class _Plane:
    def __init__(self, plane, keep):
        self.name = plane.name
        self.lines = [_Line(line, keep) for line in plane.lines]


class _Stripped:
    """A loaded trace without the events ``keep`` refuses."""

    def __init__(self, data, keep):
        self.planes = [_Plane(plane, keep) for plane in data.planes]


@pytest.fixture(scope="module")
def recorded():
    """Three iterations shaped like the two-deep loop's, through the
    program's PhaseClock, recorded on one v5e chip with the Python
    tracer off (PERF.md section 3 describes the file; that directory's
    README is an older PR's and not this one's to edit)."""
    return trace_parts.reduce(PARTS)


def test_the_recorded_trace_splits_by_part(recorded):
    t = recorded
    assert t["devices"] == 1 and t["host_spans"] == 16
    idle = t["idle_part_s"]
    assert set(idle) == set(trace_parts.PARTS)
    # what the loop slept in each part, three times over: 0.3 ms of
    # argument build in each of two dispatches, 0.4 ms of planning,
    # 0.6 ms of resolve twice; the jitted calls take what they take
    assert idle["args"] >= 3 * 2 * 0.0003
    assert idle["plan"] >= 3 * 0.0004
    assert idle["resolve"] >= 2 * 0.0006
    assert idle["launch"] > 0 and idle["rest"] > 0
    # the thread's own seconds hold the idle ones
    assert all(t["part_s"][k] >= idle[k] - 1e-9 for k in ("args", "launch",
                                                          "plan", "resolve"))


def test_both_reductions_agree_on_the_recorded_trace(recorded):
    """The same engine thread, extent, clock shift and window: one
    total, to the microsecond, and each part inside the kind it was
    taken out of."""
    spans = trace_spans.reduce(PARTS)
    kinds, parts = spans["idle_in_s"], recorded["idle_part_s"]
    assert sum(parts.values()) == pytest.approx(sum(kinds.values()),
                                                abs=1e-6)
    assert parts["args"] + parts["launch"] <= kinds["dispatch"] + 1e-9
    assert parts["plan"] + parts["resolve"] <= kinds["unattributed"] + 1e-9
    assert spans["clock_shift_ms"] > 0 and spans["early_programs"] == 0


def test_trace_spans_does_not_see_the_new_spans(monkeypatch):
    """``trace_spans.reduce`` of a trace with ``host.*`` spans is what
    it is with them stripped: every number of it."""
    data = trace_spans.load(PARTS)
    whole = trace_spans.reduce(PARTS)
    assert whole["engine_spans"] == 34
    monkeypatch.setattr(trace_spans, "load", lambda path: _Stripped(
        data, lambda name: not name.startswith("host.")))
    stripped = trace_spans.reduce(PARTS)
    assert stripped["idle_in_s"] == whole["idle_in_s"]
    assert stripped == whole
    # and the new reduction has nothing to say of the stripped one
    monkeypatch.setattr(trace_parts, "load", trace_spans.load)
    assert trace_parts.reduce(PARTS)["idle_part_s"] is None


@pytest.mark.parametrize("path", [OLD, os.path.join(
    KBENCH, "testdata", "tiny.xplane.pb")])
def test_a_parents_trace_gives_none_for_the_parts(path, monkeypatch):
    """A program that opens no ``host.*`` span (the parent of PR 40,
    ``engine.prefill.resolve`` or not): null, and the reader reports
    nothing."""
    from readers import trace_idle_part_pct

    t = trace_parts.reduce(path)
    assert t["host_spans"] == 0
    assert t["idle_part_s"] is None and t["part_s"] is None
    monkeypatch.setattr(trace_parts, "reduced_newest", lambda ctx: t)
    assert trace_idle_part_pct.read({"trace": {"busy_s": 1.0}},
                                    part="launch") is None


def test_the_reader_divides_by_the_total_the_old_one_divides_by(
        recorded, monkeypatch):
    from readers import trace_idle_in_pct, trace_idle_part_pct

    spans = trace_spans.reduce(PARTS)
    monkeypatch.setattr(trace_parts, "reduced_newest", lambda ctx: recorded)
    monkeypatch.setattr(trace_spans, "reduced_newest", lambda ctx: spans)
    ctx = {"trace": {"busy_s": 1.0}}
    share = {p: trace_idle_part_pct.read(ctx, part=p)
             for p in trace_parts.PARTS}
    assert sum(share.values()) == pytest.approx(100.0)
    old = {k: trace_idle_in_pct.read(ctx, kind=k) for k in trace_spans.KINDS}
    assert share["args"] + share["launch"] <= old["dispatch"] + 1e-6
    assert share["plan"] + share["resolve"] <= old["unattributed"] + 1e-6
    assert share["launch"] == pytest.approx(
        100.0 * recorded["idle_part_s"]["launch"]
        / sum(spans["idle_in_s"].values()), abs=1e-4)
    # no trace taken: nothing to read
    monkeypatch.undo()
    assert trace_parts.reduced_newest({"trace": None}) is None


def test_the_harness_reduces_in_a_child_and_remembers(tmp_path, monkeypatch):
    import shutil

    path = str(tmp_path / "a.xplane.pb")
    shutil.copy(PARTS, path)
    monkeypatch.setattr(trace_parts, "OUT", str(tmp_path))
    ctx = {"trace": {"busy_s": 1.0}}
    got = trace_parts.reduced_newest(ctx)
    assert got["host_spans"] == 16 and got["idle_part_s"]["launch"] > 0
    assert os.path.exists(str(tmp_path / "trace_parts.json"))
    assert trace_parts.reduced_newest(ctx) is got           # memoised
