"""The bracket a traced run puts round its trace: the stop is counted
from the return of ``/start_profile``, not from the moment it was sent,
against a fake server on a fake clock; and what the stop may cost: every
mix's span against the limit of the call, and the line a run logs when a
stop came near it."""

import glob
import os
import sys

import pytest

import run as bench
from kserver import BenchError
from manifest import Manifest
from paths import KBENCH

MIXES = sorted(os.path.splitext(os.path.basename(p))[0]
               for p in glob.glob(os.path.join(KBENCH, "traffic", "*.json")))


class FakeServer:
    """Its profiler takes ``start_takes`` seconds to come up and
    ``stop_takes`` to write the trace; time passes only in ``sleep``
    and in its handlers."""

    def __init__(self, start_takes=2.0, stop_takes=1.5, start_status=200):
        self.t = 1000.0
        self.start_takes, self.stop_takes = start_takes, stop_takes
        self.start_status = start_status
        self.got = []           # (path, arrived, returned)

    def now(self):
        return self.t

    def sleep(self, s):
        self.t += max(0.0, s)

    def post(self, path):
        if path == "/stop_profile" \
                and self.stop_takes > bench.PROFILER_TIMEOUT_S:
            self.t += bench.PROFILER_TIMEOUT_S    # urlopen gives up
            raise TimeoutError("timed out")
        arrived = self.t
        self.t += self.start_takes if path == "/start_profile" \
            else self.stop_takes
        self.got.append((path, arrived - 1000.0, self.t - 1000.0))
        return self.start_status if path == "/start_profile" else 200


def _bracket(srv, begin=18.5, span=14.0, window=51.0):
    return bench.trace_window(srv.post, begin, span, window,
                              now=srv.now, sleep=srv.sleep)


def test_the_stop_lands_trace_seconds_after_the_starts_return():
    srv = FakeServer(start_takes=2.0)
    got = _bracket(srv)
    assert [p for p, _, _ in srv.got] == ["/start_profile", "/stop_profile"]
    (_, asked, started), (_, stopping, stopped) = srv.got
    assert asked == pytest.approx(18.5) and started == pytest.approx(20.5)
    # 14 s from the return: a stop at start + 14 would have cut 2 s off
    assert stopping - started == pytest.approx(14.0)
    assert got["start_s"] == pytest.approx(20.5)
    assert got["stop_s"] == pytest.approx(34.5)
    assert got["stop_s"] - got["start_s"] == pytest.approx(14.0)
    assert got["start_took_s"] == pytest.approx(2.0)
    assert got["stop_took_s"] == pytest.approx(1.5)


def test_a_start_that_returns_too_late_is_a_named_error_and_is_stopped():
    # 18.5 + 20 + 14 is past the 51 s of the window
    srv = FakeServer(start_takes=20.0)
    with pytest.raises(BenchError) as e:
        _bracket(srv)
    said = str(e.value)
    assert "/start_profile" in said and "returned at 38.5s" in said
    assert "1.5s after the 51s window" in said
    # the profiler is not left running, and no short trace is taken for one
    (_, _, started), (path, stopping, _) = srv.got
    assert path == "/stop_profile" and stopping == pytest.approx(started)


@pytest.mark.parametrize("status", [409, 500])
def test_a_refused_start_is_an_error_and_sends_no_stop(status):
    srv = FakeServer(start_status=status)
    with pytest.raises(BenchError, match=f"/start_profile answered {status}"):
        _bracket(srv)
    assert [p for p, _, _ in srv.got] == ["/start_profile"]


def budget_s(mix: dict) -> float:
    """What the mix's span costs its largest cell's ``/stop_profile`` on
    the slowest host on record."""
    return mix["trace_seconds"] * mix["trace_mb_per_s"] * bench.EXPORT_S_PER_MB


@pytest.mark.parametrize("name", MIXES)
def test_a_mixs_span_fits_the_profilers_limit_on_the_slowest_host(name):
    """A cell that gets faster writes more events a traced second: the
    recorded MB a second is read again when it does, and this fails before
    a run on a slow host does (PRs 53 and 54 were refused at such a run)."""
    mix = Manifest().traffic(name)
    assert mix["trace_mb_per_s"] > 0 and len(mix["trace_mb_per_s_from"]) > 40
    assert budget_s(mix) <= bench.TRACE_BUDGET_SHARE * bench.PROFILER_TIMEOUT_S


def test_the_five_second_span_that_failed_is_over_the_budget():
    """``batch-long-out`` traced 5 s until PR 55, and its stop read 259,
    280 and 298 s of the 300; 3 s, which ISSUE 55 asked for, read 94.7 MB
    and 188.5 s on the chip and is over the budget too."""
    assert "batch-long-out" in MIXES
    mix = Manifest().traffic("batch-long-out")
    limit = bench.TRACE_BUDGET_SHARE * bench.PROFILER_TIMEOUT_S
    assert limit == 225.0 and bench.STOP_WARN_SHARE < bench.TRACE_BUDGET_SHARE
    assert budget_s(dict(mix, trace_seconds=5)) > bench.PROFILER_TIMEOUT_S
    assert budget_s(dict(mix, trace_seconds=3)) > limit >= budget_s(mix)


COST = {"xplane_bytes": 141778124, "trace_reduce_s": 29.8,
        "trace_spans_s": 13.7, "start_profile_s": 0.05}


@pytest.mark.parametrize("stop_s, said", [(199.9, False), (200.0, False),
                                          (200.1, True), (258.8, True)])
def test_a_stop_over_two_thirds_of_the_limit_is_said(stop_s, said):
    line = bench.stop_warning("joyai-flash-ep16-long-out",
                              dict(COST, stop_profile_s=stop_s), 5.0)
    assert bool(line) == said
    if said:
        assert line.startswith("WARNING: joyai-flash-ep16-long-out: ")
        assert f"{stop_s:.1f}s" in line and "141.8 MB" in line
        assert "trace_seconds 5:" in line and "benchmark PR" in line
        assert f"{stop_s / 3:.0f}% of the 300s" in line


def test_a_stop_that_outlasts_the_limit_is_the_brackets_failure(monkeypatch,
                                                                 capsys):
    """``Server.request`` raises ``TimeoutError`` there; the tracer thread
    leaves the bracket's failure for ``run`` to raise, and ``main`` exits
    1 with no result line."""
    srv = FakeServer(stop_takes=bench.PROFILER_TIMEOUT_S + 1.0)
    traced = {}
    bench.trace_middle(srv.post, 51.0, 5.0, traced,
                       now=srv.now, sleep=srv.sleep)
    assert traced["error"] == "the profiler's bracket failed: timed out"
    assert "stop_took_s" not in traced and traced["t0_unix"] > 0
    # the span lay in the middle of the window before the stop was lost
    assert srv.got == [("/start_profile", 23.0, 25.0)]

    def failing(args, t_start):
        raise BenchError(traced["error"])

    monkeypatch.setattr(bench, "run", failing)
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", "joyai-flash-ep16-long-out", "--seed", "1",
        "--seconds", "51", "--trace", "1"])
    assert bench.main() == 1
    out, err = capsys.readouterr()
    assert out == "" and "FAILED: the profiler's bracket failed" in err


def test_a_bracket_that_holds_leaves_its_times_and_no_error():
    srv = FakeServer(start_takes=0.05, stop_takes=150.0)
    traced = {}
    bench.trace_middle(srv.post, 51.0, 3.0, traced,
                       now=srv.now, sleep=srv.sleep)
    assert "error" not in traced
    assert traced["start_s"] == pytest.approx(24.05)
    assert traced["stop_s"] - traced["start_s"] == pytest.approx(3.0)
    assert traced["stop_took_s"] == pytest.approx(150.0)
    assert not bench.stop_warning("c", dict(COST, stop_profile_s=150.0), 3.0)


def test_both_reductions_of_the_runs_trace(tmp_path, monkeypatch):
    """What the thread beside the reference child leaves behind: the
    device numbers, the spans (which the readers then find reduced),
    what the reductions cost; and a named error where no trace is."""
    import os
    import shutil

    import trace_spans
    from paths import KBENCH

    monkeypatch.setattr(trace_spans, "OUT", str(tmp_path))
    profile = tmp_path / "cell" / "profile"
    profile.mkdir(parents=True)
    into = {}
    bench.reduce_trace(str(profile), str(tmp_path), into)
    assert into == {"error": "the profiler wrote no trace"}

    shutil.copy(os.path.join(KBENCH, "testdata", "tiny_spans.xplane.pb"),
                profile / "host.xplane.pb")
    into = {}
    bench.reduce_trace(str(profile), str(tmp_path), into)
    assert "error" not in into
    assert into["trace"]["devices"] == 1 and into["trace"]["busy_s"] > 0
    assert into["spans"]["idle_by_name"][0][0].startswith("idle in ")
    assert into["cost"]["xplane_bytes"] == os.path.getsize(
        profile / "host.xplane.pb")
    assert into["cost"]["trace_reduce_s"] > 0 < into["cost"]["trace_spans_s"]
    # a reader that asks afterwards gets the same object, from no new child
    assert trace_spans.reduced_newest({"trace": into["trace"]}) is into["spans"]
