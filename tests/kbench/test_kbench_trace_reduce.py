"""The reduction from a profiler trace to device numbers."""

import os

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


def test_longest_gaps_first():
    gaps = trace_reduce._gaps([(0, 1), (2, 3), (7, 8), (8.5, 9)], top=2)
    assert gaps == [(4, 3), (1, 1)]


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
    assert len(reduced["gaps"]) >= 2            # the sleeps between calls
    assert all(gap > 0 for _, gap in reduced["gaps"])


def test_readers_on_the_recorded_trace(reduced):
    from readers import trace_idle_pct

    ctx = {"trace": reduced}
    idle = trace_idle_pct.read(ctx)
    assert 0 < idle < 100
    assert abs(idle - 100.0 * (1 - reduced["busy_s"] / reduced["window_s"])) \
        < 1e-6
    assert trace_idle_pct.read({"trace": None}) is None
