"""The bracket a traced run puts round its trace: the stop is counted
from the return of ``/start_profile``, not from the moment it was sent,
against a fake server on a fake clock."""

import pytest

import run as bench
from kserver import BenchError


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
