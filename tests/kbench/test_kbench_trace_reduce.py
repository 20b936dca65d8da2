"""The reduction from a profiler trace to device numbers."""

import os
from types import SimpleNamespace

import pytest

import trace_reduce
from paths import KBENCH

TRACE = os.path.join(KBENCH, "testdata", "tiny.xplane.pb")


def test_union_of_overlapping_intervals():
    assert trace_reduce._union([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace_reduce._union([]) == 0
    assert trace_reduce._union([(0, 10), (2, 3)]) == 10


def test_self_time_takes_nested_operations_out():
    events = [(0.0, 10.0, "while"), (1.0, 4.0, "fusion"), (4.0, 9.0, "fusion"),
              (5.0, 6.0, "all-reduce"), (12.0, 13.0, "copy")]
    got = trace_reduce._self_times(events)
    assert got == {"while": 2.0, "fusion": 7.0, "all-reduce": 1.0, "copy": 1.0}
    assert sum(got.values()) == trace_reduce._union(
        [(s, e) for s, e, _ in events])


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(TRACE)


def test_recorded_tpu_trace_reduces(reduced):
    # three calls of a jitted scan of four tanh(x @ w) steps, recorded
    # on one v5e chip (kbench/testdata/README.md)
    assert reduced["devices"] == 1
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    assert abs(sum(reduced["ops"].values()) - reduced["busy_s"]) \
        < 1e-6 * reduced["busy_s"] + 1e-9
    # each call is one program of a scan and its body's operations
    assert reduced["modules"] and set(reduced["op_counts"]) == set(reduced["ops"])
    assert all(n.split("/", 1)[0] in reduced["modules"] for n in reduced["ops"])


def test_back_to_back_operations_stay_siblings_in_seconds(monkeypatch):
    """Nanoseconds become seconds with one rounding each: an end worked
    out as start + duration, both rounded, can pass the next start, the
    next operation then counts as a child, and its time is never taken
    out of the loop that holds both (0.30 s of a 3 s trace, PR 26)."""
    import random

    rng = random.Random(26)
    at = 375904377.0                 # ns, as a real trace has them
    inside, events = at + 1.0, []
    for _ in range(4000):
        dur = float(rng.randrange(200, 90000))
        events.append(SimpleNamespace(name=f"%fusion.{len(events) % 7} = f32[] fusion()",
                            start_ns=inside, duration_ns=dur))
        inside += dur
    events.append(SimpleNamespace(name="%while.1 = () while()", start_ns=at,
                        duration_ns=inside + 1.0 - at))
    module = SimpleNamespace(name="jit_step(123)", start_ns=at - 5.0,
                   duration_ns=inside + 10.0 - at)
    plane = SimpleNamespace(name="/device:TPU:0", lines=[
        SimpleNamespace(name="XLA Modules", events=[module]),
        SimpleNamespace(name="XLA Ops", events=events)])
    monkeypatch.setattr(trace_reduce, "load", lambda path: SimpleNamespace(planes=[plane]))
    got = trace_reduce.reduce("unused")
    assert got["busy_s"] == pytest.approx((inside + 1.0 - at) * 1e-9)
    assert sum(got["ops"].values()) == pytest.approx(got["busy_s"], rel=1e-9)
    # the loop's own time is the two nanoseconds round its body
    assert got["ops"]["jit_step/%while.1"] == pytest.approx(2e-9, abs=1e-12)
    assert got["op_counts"]["jit_step/%while.1"] == 1
