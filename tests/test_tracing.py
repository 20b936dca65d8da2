"""End-to-end request tracing: trace-id plumbing, the span ring and
step flight recorder, the router's own metrics, and the /debug export
surface (docs/observability.md).

Pure tracing-unit tests plus router tests against cheap in-process
stub backends (no engine, no XLA); the tests at the end of the file
boot real engines and prove the acceptance path: a request
through dp_router -> engine comes back with an ``X-Request-Id`` whose
span tree covers queue -> admission -> prefill -> decode, PD handoff
spans share one id across both roles, and /debug/timeline is valid
Chrome trace JSON.
"""

import json
import logging
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from kaito_tpu.utils.tracing import (PhaseClock, RingTracer, Span,
                                     StepTimeline, chrome_trace,
                                     format_span_tree,
                                     make_request_id, parse_traceparent,
                                     sanitize_request_id, timeline_trace)

# ---------------------------------------------------------------------------
# tracing units (fast)
# ---------------------------------------------------------------------------


def test_parse_traceparent():
    tid = "a" * 32
    assert parse_traceparent(f"00-{tid}-{'b' * 16}-01") == tid
    # case-insensitive per spec; normalized to lowercase
    assert parse_traceparent(f"00-{'A' * 32}-{'B' * 16}-01") == "a" * 32
    for bad in (None, "", "garbage", f"00-{'0' * 32}-{'b' * 16}-01",
                f"00-{tid}-{'b' * 15}-01", f"00-{tid[:-1]}-{'b' * 16}-01",
                f"zz{tid}"):
        assert parse_traceparent(bad) is None


def test_sanitize_request_id():
    assert sanitize_request_id("req-1.2:a_B") == "req-1.2:a_B"
    assert sanitize_request_id("  spaced id\n") == "spacedid"
    assert sanitize_request_id("x" * 500) == "x" * 128
    assert sanitize_request_id("\n\t ") is None
    assert sanitize_request_id(None) is None
    assert sanitize_request_id("") is None


def test_make_request_id_is_sanitary_and_unique():
    a, b = make_request_id(), make_request_id()
    assert a != b
    assert sanitize_request_id(a) == a


def test_ring_tracer_capacity_and_filter():
    tr = RingTracer(capacity=3)
    for i in range(5):
        tr.record(f"s{i}", "t1" if i % 2 else "t2", float(i), 0.1)
    assert len(tr) == 3                        # oldest two fell off
    assert [s.name for s in tr.spans()] == ["s2", "s3", "s4"]
    assert [s.name for s in tr.spans("t1")] == ["s3"]
    tr.clear()
    assert len(tr) == 0


def test_ring_tracer_span_context_records_errors():
    tr = RingTracer()
    with tr.span("ok", "t", k=1):
        pass
    with pytest.raises(ValueError):
        with tr.span("boom", "t"):
            raise ValueError("x")
    ok, boom = tr.spans("t")
    assert ok.name == "ok" and ok.attrs["k"] == 1 and ok.dur >= 0
    assert boom.attrs["error"] == "ValueError"


def test_chrome_trace_export_shape():
    tr = RingTracer()
    tr.record("a", "t1", 1.0, 0.5, slot=3)
    tr.record("b", "t2", 1.2, 0.1)
    doc = tr.chrome_trace()
    json.loads(json.dumps(doc))               # JSON-serializable
    evs = doc["traceEvents"]
    names = {e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert names == {"t1", "t2"}              # one named track per trace
    xs = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"a", "b"}
    a = next(e for e in xs if e["name"] == "a")
    assert a["ts"] == 1_000_000 and a["dur"] == 500_000   # us
    assert a["args"]["slot"] == 3 and a["args"]["trace_id"] == "t1"
    # filtered export keeps only the requested trace
    only = tr.chrome_trace("t1")["traceEvents"]
    assert all(e["ph"] == "M" or e["args"]["trace_id"] == "t1"
               for e in only)
    assert chrome_trace([]) == {"traceEvents": [],
                                "displayTimeUnit": "ms"}


def test_format_span_tree_nests_by_containment():
    spans = [Span("request", "t", 0.0, 1.0),
             Span("queue.wait", "t", 0.0, 0.2),
             Span("prefill.chunk", "t", 0.2, 0.3),
             Span("decode", "t", 0.5, 0.5)]
    out = format_span_tree(spans)
    lines = out.splitlines()
    assert lines[0].startswith("request")
    for inner in lines[1:]:
        assert inner.startswith("  ")         # children indent under it
    assert format_span_tree([]) == "(no spans)"


def test_step_timeline_and_trace():
    tl = StepTimeline(capacity=2)
    tl.add(1.0, 0.01, running=2, waiting=1, kv_pages_used=7)
    tl.add(1.1, 0.02, running=3, waiting=0, kv_pages_used=9)
    tl.add(1.2, 0.03, running=1, waiting=0, kv_pages_used=4)
    assert len(tl) == 2                       # bounded
    doc = tl.chrome_trace()
    json.loads(json.dumps(doc))
    evs = doc["traceEvents"]
    steps = [e for e in evs if e["ph"] == "X"]
    assert len(steps) == 2
    assert steps[0]["args"]["running"] == 3
    counters = [e for e in evs if e["ph"] == "C"]
    assert {e["name"] for e in counters} == {"batch", "kv_pages_used"}
    assert timeline_trace([])["traceEvents"][0]["ph"] == "M"


def test_ring_overflow_surfaces_dropped_in_trace_metadata():
    """Evicted records are counted and ride the Chrome-export
    ``metadata`` key, so a missing span in /debug/trace or
    /debug/timeline reads as ring overflow, not as missing
    instrumentation."""
    tr = RingTracer(capacity=3)
    for i in range(5):
        tr.record(f"s{i}", "t", float(i), 0.1)
    assert tr.dropped == 2
    assert tr.chrome_trace()["metadata"] == {"dropped": 2}
    tr.clear()
    assert tr.dropped == 0

    tl = StepTimeline(capacity=2)
    for i in range(5):
        tl.add(float(i), 0.01, running=1)
    assert tl.dropped == 3
    assert tl.chrome_trace()["metadata"] == {"dropped": 3}
    tl.clear()
    assert tl.dropped == 0
    # explicit dropped=None keeps the export shape unchanged
    assert "metadata" not in chrome_trace([])
    assert "metadata" not in timeline_trace([])


# ---------------------------------------------------------------------------
# router observability against stub backends (fast; no engine)
# ---------------------------------------------------------------------------


def _stub_backend():
    """Minimal backend: 200s everything, echoes the X-Request-Id it was
    forwarded (header + body) and records what it saw."""
    seen = []

    class H(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):
            pass

        def _reply(self):
            rid = self.headers.get("X-Request-Id", "")
            seen.append({"path": self.path, "rid": rid})
            body = json.dumps({"rid": rid}).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            if rid:
                self.send_header("X-Request-Id", rid)
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            self._reply()

        def do_POST(self):
            self.rfile.read(int(self.headers.get("Content-Length") or 0))
            self._reply()

    srv = ThreadingHTTPServer(("127.0.0.1", 0), H)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, f"http://127.0.0.1:{srv.server_address[1]}", seen


@pytest.fixture()
def routed_stub():
    from kaito_tpu.runtime.dp_router import DPRouter, make_router_server

    srv, url, seen = _stub_backend()
    router = DPRouter([url])
    rsrv = make_router_server(router, host="127.0.0.1", port=0)
    threading.Thread(target=rsrv.serve_forever, daemon=True).start()
    yield f"http://127.0.0.1:{rsrv.server_address[1]}", router, seen
    rsrv.shutdown()
    srv.shutdown()


def test_router_generates_and_forwards_request_id(routed_stub):
    router_url, router, seen = routed_stub
    with urllib.request.urlopen(router_url + "/health", timeout=10) as r:
        rid = r.headers.get("X-Request-Id")
    assert rid and sanitize_request_id(rid) == rid
    assert seen[-1]["rid"] == rid             # backend saw the same id


def test_router_preserves_client_request_id(routed_stub):
    router_url, router, seen = routed_stub
    req = urllib.request.Request(router_url + "/health",
                                 headers={"X-Request-Id": "client-id-7"})
    with urllib.request.urlopen(req, timeout=10) as r:
        assert r.headers.get("X-Request-Id") == "client-id-7"
    assert seen[-1]["rid"] == "client-id-7"


def test_router_accepts_traceparent(routed_stub):
    router_url, router, seen = routed_stub
    tid = "ab" * 16
    req = urllib.request.Request(
        router_url + "/health",
        headers={"traceparent": f"00-{tid}-{'cd' * 8}-01"})
    with urllib.request.urlopen(req, timeout=10):
        pass
    assert seen[-1]["rid"] == tid


def test_router_metrics_endpoint(routed_stub):
    router_url, router, seen = routed_stub
    for _ in range(3):
        urllib.request.urlopen(router_url + "/v1/models", timeout=10).read()
    with urllib.request.urlopen(router_url + "/metrics", timeout=10) as r:
        assert r.headers["Content-Type"].startswith("text/plain")
        body = r.read().decode()
    (backend_url,) = [b.url for b in router.backends]
    assert (f'kaito:router_requests_forwarded_total'
            f'{{backend="{backend_url}"}}') in body
    assert (f'kaito:router_backend_breaker_state'
            f'{{backend="{backend_url}"}} 0') in body
    assert (f'kaito:router_upstream_latency_seconds_bucket'
            f'{{backend="{backend_url}",le="+Inf"}}') in body
    # /metrics and /router/stats are answered locally, never relayed
    assert all(s["path"] not in ("/metrics", "/router/stats")
               for s in seen)


def test_router_counts_failures_and_retries():
    from kaito_tpu.runtime.dp_router import DPRouter, make_router_server

    srv, live_url, seen = _stub_backend()
    dead_url = "http://127.0.0.1:9"            # discard port: refuses
    router = DPRouter([dead_url, live_url])
    rsrv = make_router_server(router, host="127.0.0.1", port=0)
    threading.Thread(target=rsrv.serve_forever, daemon=True).start()
    try:
        router_url = f"http://127.0.0.1:{rsrv.server_address[1]}"
        out = json.loads(urllib.request.urlopen(
            router_url + "/health", timeout=10).read())
        assert out["rid"]                      # relayed via the live one
        body = router.registry.expose()
        assert router.m_failures.value(backend=dead_url) >= 1
        assert router.m_forwarded.value(backend=live_url) >= 1
        assert router.m_retries.value(backend=live_url) >= 1
        # one connect failure opens the cooldown => breaker reads open
        assert (f'kaito:router_backend_breaker_state'
                f'{{backend="{dead_url}"}} 2') in body
    finally:
        rsrv.shutdown()
        srv.shutdown()


# ---------------------------------------------------------------------------
# phase spans: the instrument, the engine loop, the SSE path, the
# profiler endpoints (fast; the profiler itself is never started)
# ---------------------------------------------------------------------------


class SpanRecorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: the same
    signature, and a log of (thread, depth, name, attrs, t0, t1)."""

    def __init__(self):
        self.log = []
        self._depth = {}

    def __call__(self, name, **attrs):
        return _Recorded(self, name, attrs)

    def of(self, name):
        return [r for r in self.log if r["name"] == name]


class _Recorded:
    def __init__(self, rec, name, attrs):
        self.rec = rec
        self.row = {"name": name, "attrs": attrs,
                    "thread": threading.get_ident()}

    def __enter__(self):
        tid = self.row["thread"]
        self.row["depth"] = self.rec._depth.get(tid, 0)
        self.rec._depth[tid] = self.row["depth"] + 1
        self.rec.log.append(self.row)
        self.row["t0"] = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.row["t1"] = time.perf_counter()
        self.rec._depth[self.row["thread"]] -= 1
        return False


def test_phase_clock_sums_the_owner_thread_and_only_it():
    rec = SpanRecorder()
    clock = PhaseClock(rec)
    with clock.phase("engine.schedule"):
        time.sleep(0.02)            # blocked, not computing: a stall
    with clock.phase("engine.decode", k=4, rows=2):
        with clock.phase("engine.decode.wait"):
            time.sleep(0.01)
    with clock.phase("engine.schedule"):
        pass
    with clock.annotate("http.stream.chunk", rid="r1"):
        time.sleep(0.005)
    seconds, stall, stalled = clock.flush()
    assert set(seconds) == {"engine.schedule", "engine.decode",
                            "engine.decode.wait"}   # annotate adds nothing
    assert stalled == {"engine.schedule": stall}
    assert seconds["engine.schedule"] >= 0.02
    assert seconds["engine.decode"] >= seconds["engine.decode.wait"] >= 0.01
    # schedule never blocks on the device, so its sleep is a stall; the
    # wait phase blocks by design and is left out
    assert 0.015 <= stall <= seconds["engine.schedule"]
    assert clock.flush() == ({}, 0, {})
    assert [r["name"] for r in rec.log] == [
        "engine.schedule", "engine.decode", "engine.decode.wait",
        "engine.schedule", "http.stream.chunk"]
    assert rec.of("engine.decode")[0]["attrs"] == {"k": 4, "rows": 2}
    assert rec.of("engine.decode.wait")[0]["depth"] == 1


def test_a_part_has_seconds_and_a_stall_of_its_own():
    """A part of a phase: a span, wall seconds and stalled seconds
    under its own name, and nothing added twice to the loop's stall."""
    rec = SpanRecorder()
    clock = PhaseClock(rec)
    with clock.phase("engine.decode", rows=1):
        with clock.part("host.plan"):
            time.sleep(0.01)
        with clock.phase("engine.decode.dispatch"):
            with clock.part("host.args"):
                sum(range(20000))                   # computing: no stall
            with clock.part("host.launch"):
                time.sleep(0.02)                    # held: a stall
    with clock.phase("engine.decode.replay"):
        time.sleep(0.005)
    seconds, stall, stalled = clock.flush()
    assert seconds["host.launch"] >= 0.02 and seconds["host.plan"] >= 0.01
    assert seconds["host.args"] + seconds["host.launch"] \
        <= seconds["engine.decode.dispatch"]
    assert 0.015 <= stalled["host.launch"] <= seconds["host.launch"]
    assert stalled["host.args"] < 0.005 <= 0.008 <= stalled["host.plan"]
    # the loop's stall is its phases': dispatch (which holds the
    # launch's) and replay, the parts' not added again
    assert stall == pytest.approx(stalled["engine.decode.dispatch"]
                                  + stalled["engine.decode.replay"])
    assert stalled["engine.decode.replay"] >= 0.004
    assert set(stalled) == {"host.plan", "host.args", "host.launch",
                            "engine.decode.dispatch",
                            "engine.decode.replay"}
    assert [(r["name"], r["depth"]) for r in rec.log] == [
        ("engine.decode", 0), ("host.plan", 1),
        ("engine.decode.dispatch", 1), ("host.args", 2), ("host.launch", 2),
        ("engine.decode.replay", 0)]
    assert all(r["attrs"] == {} for r in rec.log[1:])
    assert clock.flush() == ({}, 0.0, {})


PHASE_FAMILIES = {
    "engine.schedule": "kaito:engine_schedule_seconds",
    "engine.decode": "kaito:engine_decode_step_seconds",
    "engine.decode.dispatch": "kaito:engine_decode_dispatch_seconds",
    "engine.decode.wait": "kaito:engine_decode_wait_seconds",
    "engine.decode.replay": "kaito:engine_decode_replay_seconds",
    "engine.prefill": "kaito:engine_prefill_step_seconds",
    "loop_stall": "kaito:engine_loop_stall_seconds",
    # parts of a phase and the two stalls told apart
    "host.args": "kaito:engine_dispatch_args_seconds",
    "host.launch": "kaito:engine_launch_seconds",
    "launch_stall": "kaito:engine_launch_stall_seconds",
    "host.plan": "kaito:engine_decode_plan_seconds",
    "replay_stall": "kaito:engine_replay_stall_seconds",
}
# every engine.* span there is (docs/observability.md's table): the
# benchmark's reduction maps these names to kinds through a fixed table
# (kbench/trace_spans.KIND) and calls any other name unattributed
ENGINE_SPANS = {"engine.step", "engine.schedule", "engine.decode",
                "engine.decode.dispatch", "engine.decode.wait",
                "engine.decode.replay", "engine.prefill",
                "engine.prefill.dispatch", "engine.prefill.wait",
                "engine.prefill.resolve", "engine.idle"}


@pytest.fixture(scope="module")
def phased():
    """(engine, recorder): a tiny engine that is stepped by the test
    or, once started, by its own thread; its spans go to the recorder."""
    from kaito_tpu.engine.config import EngineConfig
    from kaito_tpu.engine.engine import InferenceEngine

    # prefill every iteration, so that one step can hold every phase
    engine = InferenceEngine(EngineConfig(**E2E_CFG, prefill_interleave=1))
    rec = engine.phases.annotate = SpanRecorder()
    yield engine, rec
    engine.stop()


def _contains(outer, inner):
    return (outer["t0"] <= inner["t0"] and inner["t1"] <= outer["t1"]
            and inner["depth"] == outer["depth"] + 1)


def test_one_step_emits_each_phase_once_and_they_add_up(phased):
    from kaito_tpu.engine.engine import SamplingParams

    engine, rec = phased
    assert set(engine.phase_hists) == set(PHASE_FAMILIES)
    for phase, family in PHASE_FAMILIES.items():
        assert engine.phase_hists[phase].name == family
    params = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)
    engine.submit(list(range(5, 25)), params)
    assert engine.step()                     # admits and prefills the first
    engine.submit(list(range(7, 60)), params)
    rec.log.clear()
    steps0 = engine.step_hist._total
    assert engine.step()      # admits the second, decodes the first, prefills
    names = [r["name"] for r in rec.log if r["name"].startswith("engine.")]
    want = ["engine.step", "engine.schedule", "engine.decode",
            "engine.decode.dispatch", "engine.decode.wait",
            "engine.decode.replay", "engine.prefill",
            "engine.prefill.dispatch", "engine.prefill.wait"]
    assert names == want, names             # each once, in the loop's order
    by = {r["name"]: r for r in rec.log}
    for parent, children in (
            ("engine.step", ("engine.schedule", "engine.decode",
                             "engine.prefill")),
            ("engine.decode", ("engine.decode.dispatch",
                               "engine.decode.wait", "engine.decode.replay")),
            ("engine.prefill", ("engine.prefill.dispatch",
                                "engine.prefill.wait"))):
        for child in children:
            assert _contains(by[parent], by[child]), (parent, child)
    assert by["engine.step"]["attrs"].keys() == {"n", "rows"}
    assert by["engine.decode"]["attrs"] == {"k": 1, "rows": 1}

    rec_ = engine.timeline.records()[-1]
    step_s = rec_["dur"]
    parts = rec_["decode.dispatch"] + rec_["decode.wait"] + rec_["decode.replay"]
    assert parts == pytest.approx(rec_["decode"], rel=0.03)
    assert (rec_["schedule"] + rec_["decode"] + rec_["prefill"]
            == pytest.approx(step_s, rel=0.03))
    assert rec_["prefill.dispatch"] + rec_["prefill.wait"] <= rec_["prefill"]
    assert 0.0 <= rec_["loop_stall"] <= step_s
    # one observation per family per non-idle step, zero included
    assert engine.step_hist._total == steps0 + 1
    for hist in engine.phase_hists.values():
        assert hist._total == engine.step_hist._total
    assert engine.phase_hists["engine.decode"]._sum == pytest.approx(
        sum(r.get("decode", 0.0) for r in engine.timeline.records()),
        abs=1e-4)


def test_each_dispatch_opens_args_then_launch(phased):
    """One step of the synchronous loop: inside the decode dispatch,
    inside the prefill dispatch and inside the blocking first-token
    program's span, ``host.args`` then ``host.launch``, each nested in
    it; the planning is a part of its own; their seconds reach the
    record."""
    from kaito_tpu.engine.engine import SamplingParams

    engine, rec = phased
    while engine.step():
        pass
    params = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)
    engine.submit(list(range(5, 25)), params)
    assert engine.step()
    engine.submit(list(range(7, 60)), params)
    rec.log.clear()
    assert engine.step()        # decodes the first, prefills the second
    log = rec.log
    assert {r["name"] for r in log if r["name"].startswith("engine.")} \
        <= ENGINE_SPANS
    assert {r["name"] for r in log if not r["name"].startswith("engine.")} \
        == {"host.plan", "host.args", "host.launch"}
    held = ("engine.decode.dispatch", "engine.prefill.dispatch",
            "engine.prefill.wait")
    for outer in (r for r in log if r["name"] in held):
        inside = [r for r in log if _contains(outer, r)]
        assert [r["name"] for r in inside] == ["host.args", "host.launch"], \
            outer["name"]
        assert inside[0]["t1"] <= inside[1]["t0"]
    assert len([r for r in log if r["name"] in held]) == 3
    assert len(rec.of("host.launch")) == len(rec.of("host.args")) == 3
    plan, = rec.of("host.plan")
    assert _contains(rec.of("engine.schedule")[0], plan)   # this loop's
    rec_ = engine.timeline.records()[-1]
    assert 0 < rec_["launch"] and 0 < rec_["args"] and 0 < rec_["plan"]
    assert rec_["args"] + rec_["launch"] <= (
        rec_["decode.dispatch"] + rec_["prefill.dispatch"]
        + rec_["prefill.wait"])
    # the decode dispatch's own two parts against its own seconds
    d = rec.of("engine.decode.dispatch")[0]
    mine = [r for r in log if _contains(d, r)]
    assert sum(r["t1"] - r["t0"] for r in mine) <= d["t1"] - d["t0"]
    assert rec_.get("launch_stall", 0.0) <= rec_["launch"]
    assert rec_.get("replay_stall", 0.0) <= rec_["decode.replay"]
    assert "dispatch_gap" not in rec_
    for key in ("host.launch", "launch_stall", "host.args", "host.plan",
                "replay_stall"):
        assert engine.phase_hists[key]._total == engine.step_hist._total


def test_a_step_that_compiles_says_so(phased, caplog):
    """A program first met after the warm-up: the process-wide count
    moves, the step's record carries it, and once the heap has settled
    the log names the step."""
    from kaito_tpu.engine.engine import SamplingParams
    from kaito_tpu.engine.metrics import EngineMetrics

    engine, _ = phased
    while engine.step():
        pass
    assert all("compiles" not in r for r in engine.timeline.records()[-2:])
    engine._settle_heap()                   # as the loop does when idle
    n0, s0 = engine.compile_totals()
    tick = engine._tick
    # a prompt of a bucket no earlier test of this engine has used
    engine.submit(list(range(3, 103)),
                  SamplingParams(max_tokens=2, temperature=0.0,
                                 ignore_eos=True))
    with caplog.at_level(logging.WARNING, logger="kaito_tpu.engine.engine"):
        assert engine.step()
    n1, s1 = engine.compile_totals()
    rec_ = engine.timeline.records()[-1]
    assert n1 > n0 and s1 > s0
    assert rec_["compiles"] == n1 - n0
    assert rec_["compile_s"] == pytest.approx(s1 - s0, abs=1e-5)
    assert rec_["compile_s"] <= rec_["launch"] + rec_["args"] + 1e-3
    said = [r.getMessage() for r in caplog.records
            if "program(s)" in r.getMessage()]
    assert said == [f"compiled {n1 - n0} program(s) in "
                    f"{rec_['compile_s']:.2f} s inside step {tick}"]
    text = EngineMetrics(engine=engine).registry.expose()
    assert f"kaito:engine_compiles_total {n1}" in text
    while engine.step():
        pass
    assert "compiles" not in engine.timeline.records()[-1]


def test_an_idle_poll_observes_nothing(phased):
    engine, rec = phased
    while engine.step():
        pass
    counts = {p: h._total for p, h in engine.phase_hists.items()}
    records = len(engine.timeline)
    rec.log.clear()
    assert not engine.step()
    assert {r["name"] for r in rec.log} <= {"engine.step", "engine.schedule",
                                            "engine.prefill"}
    assert {p: h._total for p, h in engine.phase_hists.items()} == counts
    assert len(engine.timeline) == records


@pytest.fixture(scope="module")
def phased_async():
    """(engine, recorder): the two-deep loop (what a chip runs), stepped
    by the test."""
    from kaito_tpu.engine.config import EngineConfig
    from kaito_tpu.engine.engine import InferenceEngine

    engine = InferenceEngine(EngineConfig(
        **E2E_CFG, prefill_interleave=1, decode_run_ahead=1,
        async_dispatch=True))
    rec = engine.phases.annotate = SpanRecorder()
    yield engine, rec
    engine.stop()


def test_the_two_deep_loop_plans_and_launches_in_parts(phased_async):
    """The loop a chip runs: ``host.plan`` inside ``engine.decode``
    before the dispatch, ``host.args`` then ``host.launch`` inside the
    decode dispatch and inside both prefill dispatches (the chunk's and
    the first-token program's), and no ``engine.*`` span that the
    table does not list."""
    from kaito_tpu.engine.engine import SamplingParams

    engine, rec = phased_async
    params = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True)
    first = engine.submit(list(range(5, 25)), params)
    for _ in range(50):
        engine.step()
        if engine._inflight is not None:
            break
    assert engine._inflight is not None
    engine.submit(list(range(7, 60)), params)
    rec.log.clear()
    assert engine.step()    # a primed window, the second prompt's prefill
    log = rec.log
    assert {r["name"] for r in log if r["name"].startswith("engine.")} \
        <= ENGINE_SPANS
    decode, = rec.of("engine.decode")
    plans = rec.of("host.plan")
    assert len(plans) == 2 and all(_contains(decode, p) for p in plans)
    dispatch, = rec.of("engine.decode.dispatch")
    assert plans[1]["t1"] <= dispatch["t0"]
    spans = [dispatch] + rec.of("engine.prefill.dispatch")
    assert len(spans) == 3
    for outer in spans:
        inside = [r for r in log if _contains(outer, r)]
        assert [r["name"] for r in inside] == ["host.args", "host.launch"], \
            outer["name"]
    rec_ = engine.timeline.records()[-1]
    assert "drain" not in rec_ and "dispatch_gap" not in rec_
    decode_parts = [r for r in log if _contains(dispatch, r)]
    assert sum(r["t1"] - r["t0"] for r in decode_parts) \
        <= rec_["decode.dispatch"] + 1e-6
    assert rec_["args"] + rec_["launch"] \
        <= rec_["decode.dispatch"] + rec_["prefill.dispatch"]
    assert rec_["plan"] == pytest.approx(
        sum(p["t1"] - p["t0"] for p in plans), abs=1e-4)
    while not first.finish_reason:
        engine.step()


def test_a_drain_inside_the_plan_keeps_its_own_spans(phased_async,
                                                     monkeypatch):
    """Page pressure found while planning retires the window in flight
    there: its wait and replay are spans of their own inside
    ``host.plan``, innermost, as a drain inside ``engine.schedule`` is."""
    from kaito_tpu.engine.engine import SamplingParams

    engine, rec = phased_async
    while engine.step():
        pass
    req = engine.submit(list(range(9, 40)), SamplingParams(
        max_tokens=12, temperature=0.0, ignore_eos=True))
    for _ in range(50):
        engine.step()
        if engine._inflight is not None:
            break
    assert engine._inflight is not None
    # the schedule's question is answered yes, the plan's no
    asked = []
    monkeypatch.setattr(engine, "_lookahead_fits",
                        lambda k: (asked.append(k), len(asked) != 2)[1])
    rec.log.clear()
    assert engine.step()
    monkeypatch.undo()
    assert len(asked) >= 2
    plan = rec.of("host.plan")[-1]
    wait = [r for r in rec.of("engine.decode.wait")
            if r["attrs"] == {"drain": "page_pressure"}]
    assert len(wait) == 1 and _contains(plan, wait[0])
    replay = [r for r in rec.of("engine.decode.replay")
              if _contains(plan, r)]
    assert len(replay) == 1 and wait[0]["t1"] <= replay[0]["t0"]
    rec_ = engine.timeline.records()[-1]
    assert rec_["drain"] == "page_pressure"
    assert rec_["plan"] >= rec_["decode.wait"]
    # the window launched after it went into an idle device
    assert _contains(rec.of("engine.decode")[0],
                     rec.of("engine.decode.dispatch")[0])
    while not req.finish_reason:
        engine.step()
    assert {r["name"] for r in rec.log if r["name"].startswith("engine.")} \
        <= ENGINE_SPANS


@pytest.fixture(scope="module")
def phased_server(phased):
    from kaito_tpu.engine.server import make_server

    engine, rec = phased
    engine.start()
    server = make_server(engine, engine.cfg, host="127.0.0.1", port=0)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    yield server, f"http://127.0.0.1:{server.server_address[1]}", rec
    server.shutdown()
    server.server_close()


def test_streaming_emits_request_and_chunk_spans(phased_server, monkeypatch):
    server, url, rec = phased_server
    rec.log.clear()
    metrics = server.state.metrics
    chunks0 = metrics.stream_chunk._total
    cpu0, added = metrics.stream_cpu.value(), []
    real_inc = metrics.stream_cpu.inc
    monkeypatch.setattr(metrics.stream_cpu, "inc",
                        lambda s: (added.append(s), real_inc(s)))
    t0 = time.perf_counter()
    with _post(url, "/v1/completions",
               {"prompt": "stream me", "max_tokens": 6, "temperature": 0.0,
                "ignore_eos": True, "stream": True},
               headers={"X-Request-Id": "phase-rid-1"}) as r:
        body = r.read().decode()
    assert body.rstrip().endswith("data: [DONE]")
    request = rec.of("http.request")
    chunks = rec.of("http.stream.chunk")
    assert len(request) == 1 and request[0]["attrs"] == {"rid": "phase-rid-1"}
    assert len(chunks) == 6                      # one per generated token
    assert all(c["attrs"] == {"rid": "phase-rid-1"} for c in chunks)
    # intake ends at submit: no chunk is inside it, and it is shorter
    # than the request
    assert all(c["t0"] >= request[0]["t1"] for c in chunks)
    assert all(c["thread"] == request[0]["thread"] for c in chunks)
    wall = time.perf_counter() - t0
    assert metrics.stream_chunk._total == chunks0 + 6
    # what the six tokens cost this handler's thread in CPU: handed
    # over once, with the chunk seconds, and no more than the clock saw
    assert len(added) == 1 and 0 < added[0] <= wall
    assert metrics.stream_cpu.value() == pytest.approx(cpu0 + added[0])
    # the engine's own thread idles in a span of its own
    assert rec.of("engine.idle") or rec.of("engine.step")


def test_new_families_are_unlabelled_on_metrics(phased_server):
    """The benchmark's scrape keeps only unlabelled samples."""
    _, url, _ = phased_server
    with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
        lines = r.read().decode().splitlines()
    for family in list(PHASE_FAMILIES.values()) + [
            "kaito:http_stream_chunk_seconds"]:
        assert f"# TYPE {family} histogram" in lines, family
        for suffix in ("_sum", "_count"):
            mine = [ln for ln in lines if ln.startswith(family + suffix)]
            assert len(mine) == 1 and "{" not in mine[0], (family, mine)
    for name in ("kaito:http_stream_cpu_seconds_total",
                 "kaito:engine_compiles_total",
                 "kaito:engine_compile_seconds_total"):
        mine = [ln for ln in lines if ln.startswith(name)]
        assert len(mine) == 1 and "{" not in mine[0], (name, mine)
        assert float(mine[0].split()[1]) > 0
    assert not [ln for ln in lines if "dispatch_gap" in ln]
    count = {ln.split()[0]: float(ln.split()[1]) for ln in lines
             if ln.startswith("kaito:engine_") and "_count" in ln}
    assert count["kaito:engine_schedule_seconds_count"] \
        == count["kaito:engine_step_seconds_count"] > 0


def test_profile_endpoints_sync_the_clock_and_gate_the_python_tracer(
        phased_server, monkeypatch, tmp_path):
    """No trace is taken: start_trace and stop_trace are recorded."""
    import jax
    import urllib.error

    server, url, rec = phased_server
    monkeypatch.setenv("KAITO_PROFILE_DIR", str(tmp_path))
    calls = []
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda d, profiler_options=None: calls.append(
            ("start", d, profiler_options.python_tracer_level,
             len(rec.of("clock.sync")))))
    monkeypatch.setattr(
        jax.profiler, "stop_trace",
        lambda: calls.append(("stop", len(rec.of("clock.sync")))))
    rec.log.clear()
    for body, level in (({}, 0), ({"python_tracer": True}, 1),
                        ({"python_tracer": False, "seconds": 0}, 0)):
        calls.clear()
        before = time.monotonic_ns()
        _post(url, "/start_profile", body).read()
        n = len(rec.of("clock.sync"))
        # the mark comes right after start_trace returns ...
        assert calls == [("start", str(tmp_path), level, n - 1)]
        _post(url, "/stop_profile", {}).read()
        # ... and right before stop_trace
        assert calls[-1] == ("stop", n + 1)
        marks = rec.of("clock.sync")[-2:]
        assert before <= marks[0]["attrs"]["mono_ns"] \
            <= marks[1]["attrs"]["mono_ns"] <= time.monotonic_ns()
    with pytest.raises(urllib.error.HTTPError) as exc:
        _post(url, "/start_profile", {"python_tracer": "yes"})
    assert exc.value.code == 400
    assert not server.state._profiling


# ---------------------------------------------------------------------------
# e2e against real engines (slow tier)
# ---------------------------------------------------------------------------

E2E_CFG = dict(model="tiny-llama-test", max_model_len=256, page_size=16,
               max_num_seqs=2, dtype="float32", kv_dtype="float32",
               prefill_buckets=(32, 64, 128), seed=0,
               # every request trips the slow-request span dump, so the
               # caplog test below needs no extra engine boot
               slow_request_threshold_s=1e-4)


def _boot_engine(**overrides):
    from kaito_tpu.engine.config import EngineConfig
    from kaito_tpu.engine.engine import InferenceEngine
    from kaito_tpu.engine.server import make_server

    cfg = EngineConfig(**{**E2E_CFG, **overrides})
    engine = InferenceEngine(cfg)
    engine.start()
    server = make_server(engine, cfg, host="127.0.0.1", port=0)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    return engine, server, f"http://127.0.0.1:{server.server_address[1]}"


def _post(url, path, body, headers=None):
    req = urllib.request.Request(
        url + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    return urllib.request.urlopen(req, timeout=120)


def _get_json(url, path):
    with urllib.request.urlopen(url + path, timeout=30) as r:
        return json.loads(r.read())


@pytest.fixture(scope="module")
def traced_stack():
    """One engine server behind the DP router (the sim-mode data
    plane): (router_url, engine_url, engine, router)."""
    from kaito_tpu.runtime.dp_router import DPRouter, make_router_server

    engine, srv, engine_url = _boot_engine()
    router = DPRouter([engine_url])
    rsrv = make_router_server(router, host="127.0.0.1", port=0)
    threading.Thread(target=rsrv.serve_forever, daemon=True).start()
    yield (f"http://127.0.0.1:{rsrv.server_address[1]}", engine_url,
           engine, router)
    rsrv.shutdown()
    srv.shutdown()
    engine.stop()


def test_request_id_spans_router_to_engine(traced_stack):
    """Acceptance: a completion through dp_router -> engine returns an
    X-Request-Id whose /debug/trace span tree covers queue ->
    admission -> prefill -> decode."""
    router_url, engine_url, engine, _ = traced_stack
    with _post(router_url, "/v1/completions",
               {"prompt": "trace me end to end", "max_tokens": 4,
                "temperature": 0.0}) as r:
        rid = r.headers.get("X-Request-Id")
        out = json.loads(r.read())
    assert rid, "router->engine response must carry X-Request-Id"
    assert out["usage"]["completion_tokens"] >= 1
    doc = _get_json(engine_url, f"/debug/trace?trace_id={rid}")
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert {"queue.wait", "admit", "prefill.chunk",
            "decode", "request"} <= names, names
    # every event in the filtered export belongs to this request
    assert all(e["args"]["trace_id"] == rid
               for e in doc["traceEvents"] if e["ph"] == "X")


def test_client_request_id_echoed_in_errors(traced_stack):
    router_url, _, _, _ = traced_stack
    import urllib.error

    req = urllib.request.Request(
        router_url + "/v1/completions", data=b"{not json",
        headers={"Content-Type": "application/json",
                 "X-Request-Id": "err-trace-1"})
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req, timeout=30)
    assert ei.value.headers.get("X-Request-Id") == "err-trace-1"
    err = json.loads(ei.value.read())
    assert err["error"]["request_id"] == "err-trace-1"


def test_debug_timeline_is_valid_chrome_trace(traced_stack):
    router_url, engine_url, engine, _ = traced_stack
    _post(router_url, "/v1/completions",
          {"prompt": "fill the flight recorder", "max_tokens": 3,
           "temperature": 0.0}).read()
    doc = _get_json(engine_url, "/debug/timeline")
    steps = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert steps, "engine must have recorded non-idle steps"
    for e in steps:
        assert e["name"] == "engine.step"
        assert e["dur"] >= 0 and {"running", "waiting"} <= set(e["args"])
    assert any(e["ph"] == "C" and e["name"] == "kv_pages_used"
               for e in doc["traceEvents"])
    # the recorder counted real work: some step decoded tokens
    assert any(e["args"].get("decode_tokens", 0) > 0 for e in steps)


def test_engine_metrics_gain_step_and_queue_series(traced_stack):
    router_url, engine_url, engine, _ = traced_stack
    _post(router_url, "/v1/completions",
          {"prompt": "observe me", "max_tokens": 2,
           "temperature": 0.0}).read()
    with urllib.request.urlopen(engine_url + "/metrics", timeout=30) as r:
        body = r.read().decode()
    assert 'kaito:engine_step_seconds_bucket{le="+Inf"}' in body
    assert 'kaito:queue_wait_seconds_bucket{le="+Inf"}' in body
    assert "kaito:batch_occupancy" in body
    assert engine.step_hist.percentile(0.5) > 0.0


def test_slow_request_logs_span_tree(traced_stack, caplog):
    router_url, _, engine, _ = traced_stack
    with caplog.at_level(logging.WARNING, logger="kaito_tpu.engine.engine"):
        with _post(router_url, "/v1/completions",
                   {"prompt": "log my span tree", "max_tokens": 2,
                    "temperature": 0.0}) as r:
            rid = r.headers.get("X-Request-Id")
            r.read()
        # the warning fires on the engine thread just before the
        # response completes; allow a beat for the record to land
        for _ in range(50):
            if any("slow request" in m for m in caplog.messages):
                break
            time.sleep(0.02)
    slow = [m for m in caplog.messages if "slow request" in m
            and rid in m]
    assert slow, caplog.messages
    assert "request" in slow[-1] and "decode" in slow[-1]


def test_pd_handoff_shares_trace_id():
    """Acceptance: prefill and decode roles record spans under ONE
    trace id — carried by the staged-export meta — and the decode
    response echoes it even though the decode client sent no header."""
    pre_eng, pre_srv, pre_url = _boot_engine(pd_enabled=True,
                                             prefill_buckets=(64, 128))
    dec_eng, dec_srv, dec_url = _boot_engine(pd_enabled=True,
                                             prefill_buckets=(64, 128))
    try:
        tid = "pd-shared-trace-1"
        prompt = "hello disaggregated tracing"
        with _post(pre_url, "/pd/prefill",
                   {"prompt": prompt, "temperature": 0.0},
                   headers={"X-Request-Id": tid}) as r:
            assert r.headers.get("X-Request-Id") == tid
            pre = json.loads(r.read())
        assert pre["request_id"] == tid
        # decode pod: NO client header — the id must ride the handoff
        with _post(dec_url, "/v1/completions",
                   {"prompt": prompt, "max_tokens": 4, "temperature": 0.0,
                    "kv_transfer": {"source_url": pre_url,
                                    "req_id": pre["req_id"],
                                    "prompt_tokens": pre["prompt_tokens"],
                                    "first_token": pre["first_token"],
                                    "force": True, "wire": "http"}}) as r:
            assert r.headers.get("X-Request-Id") == tid
            out = json.loads(r.read())
        assert out["usage"]["completion_tokens"] >= 1
        for url, role in ((pre_url, "prefill"), (dec_url, "decode")):
            doc = _get_json(url, f"/debug/trace?trace_id={tid}")
            xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
            assert xs, f"{role} role recorded no spans under {tid}"
        dec_names = {e["name"] for e in _get_json(
            dec_url, f"/debug/trace?trace_id={tid}")["traceEvents"]
            if e["ph"] == "X"}
        assert "kv.import.chunked" in dec_names, dec_names
    finally:
        for s in (pre_srv, dec_srv):
            s.shutdown()
        pre_eng.stop()
        dec_eng.stop()
