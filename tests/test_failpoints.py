"""Chaos suite: every injected fault stays inside its failure domain.

The failpoint registry (kaito_tpu/utils/failpoints.py) arms named
failure sites across the engine, PD hand-off and DP router; these tests
prove the isolation contracts of docs/failure-domains.md:

- a KV-import fault kills ONE request (structured error) while its
  neighbours on the same engine finish normally — no ``_fail_all``;
- a transient transfer fault consumes the retry budget and falls back
  to local recompute (the request still SUCCEEDS);
- an engine-step fault is engine-fatal: everything in flight fails
  loudly, and the engine serves new work afterwards;
- a failpoint-killed DP backend trips its circuit breaker and traffic
  fails over with a 100% success rate for idempotent requests.

Registry/router/satellite tests come first, the engine-driven chaos
(compile-heavy) after them; the ``make chaos`` target runs the whole
module.
"""

import http.client
import json
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from kaito_tpu.utils.failpoints import (FAILPOINTS, FailpointError,
                                        FailpointRegistry, failpoint)


@pytest.fixture(autouse=True)
def _clean_failpoints():
    FAILPOINTS.clear()
    yield
    FAILPOINTS.clear()


# ---------------------------------------------------------------------------
# registry semantics (fast)
# ---------------------------------------------------------------------------

def test_failpoint_raise_and_deactivate():
    FAILPOINTS.activate("t.raise", "raise", arg="boom")
    with pytest.raises(FailpointError, match="boom"):
        FAILPOINTS.fire("t.raise")
    assert FAILPOINTS.hits("t.raise") == 1
    FAILPOINTS.deactivate("t.raise")
    FAILPOINTS.fire("t.raise")          # inactive: no-op
    assert FAILPOINTS.hits("t.raise") == 1


def test_failpoint_count_limits_fires():
    FAILPOINTS.activate("t.count", count=2)
    for _ in range(2):
        with pytest.raises(FailpointError):
            FAILPOINTS.fire("t.count")
    FAILPOINTS.fire("t.count")          # budget exhausted: no-op
    assert FAILPOINTS.hits("t.count") == 2
    assert not FAILPOINTS.is_active("t.count")


def test_failpoint_delay_sleeps():
    FAILPOINTS.activate("t.delay", "delay", arg=0.05)
    t0 = time.monotonic()
    FAILPOINTS.fire("t.delay")
    assert time.monotonic() - t0 >= 0.04


def test_failpoint_context_match_scopes_to_one_request():
    FAILPOINTS.activate("t.match", req_id="r1")
    FAILPOINTS.fire("t.match", req_id="r2")     # other request: untouched
    FAILPOINTS.fire("t.match")                   # no ctx: no match
    with pytest.raises(FailpointError):
        FAILPOINTS.fire("t.match", req_id="r1")
    assert FAILPOINTS.hits("t.match") == 1


def test_failpoint_corrupt_flips_bytes_same_length():
    data = b"abcdefgh"
    assert FAILPOINTS.corrupt("t.corrupt", data) == data     # inactive
    with failpoint("t.corrupt", "corrupt"):
        out = FAILPOINTS.corrupt("t.corrupt", data)
    assert out != data and len(out) == len(data)
    assert FAILPOINTS.corrupt("t.corrupt", data) == data


def test_failpoint_env_spec_parsing():
    reg = FailpointRegistry()
    reg.load_env("a=raise*2; b=delay:0.01 ;c=corrupt;;d")
    assert reg.is_active("a") and reg.is_active("b")
    assert reg.is_active("c") and reg.is_active("d")
    with pytest.raises(FailpointError):
        reg.fire("a")
    with pytest.raises(FailpointError):
        reg.fire("a")
    reg.fire("a")                        # *2 exhausted
    t0 = time.monotonic()
    reg.fire("b")
    assert time.monotonic() - t0 >= 0.005
    assert reg.corrupt("c", b"xy") != b"xy"
    with pytest.raises(ValueError):
        reg.activate("bad", "explode")


def test_failpoint_context_manager_disarms():
    with failpoint("t.cm"):
        assert FAILPOINTS.is_active("t.cm")
        with pytest.raises(FailpointError):
            FAILPOINTS.fire("t.cm")
    assert not FAILPOINTS.is_active("t.cm")


# ---------------------------------------------------------------------------
# admission control / shedding (fast)
# ---------------------------------------------------------------------------

class _StubAllocator:
    def __init__(self, available, num_pages):
        self.available = available
        self.num_pages = num_pages


class _StubEngine:
    def __init__(self, num_waiting=0, available=90, num_pages=101):
        self.num_waiting = num_waiting
        self.allocator = _StubAllocator(available, num_pages)


def test_shed_reason_queue_and_kv_pressure():
    from kaito_tpu.engine.rate_limit import RateLimiter

    lim = RateLimiter(4, kv_shed_threshold=0.9)
    assert lim.shed_reason(_StubEngine(num_waiting=0)) is None
    assert lim.shed_reason(
        _StubEngine(num_waiting=4))["reason"] == "queue_full"
    # 95% of pages used while a queue exists -> kv_pressure
    assert lim.shed_reason(
        _StubEngine(num_waiting=2, available=5))["reason"] == "kv_pressure"
    # same pressure with an empty queue: admit (work may drain)
    assert lim.shed_reason(
        _StubEngine(num_waiting=0, available=5)) is None
    # threshold off: only queue depth sheds
    assert RateLimiter(4).shed_reason(
        _StubEngine(num_waiting=2, available=5)) is None
    # disabled limiter never sheds
    assert RateLimiter(0, disabled=True).shed_reason(
        _StubEngine(num_waiting=999, available=0)) is None
    # legacy contract stays
    assert lim.admit(3) and not lim.admit(4)


def test_retry_after_scales_with_backlog():
    from kaito_tpu.engine.rate_limit import RateLimiter

    lim = RateLimiter(4)
    assert lim.retry_after_s(_StubEngine(num_waiting=0)) == 1
    assert lim.retry_after_s(_StubEngine(num_waiting=1000)) == 30


# ---------------------------------------------------------------------------
# satellite: mistral trailing system message (fast)
# ---------------------------------------------------------------------------

def test_mistral_trailing_system_message_not_dropped():
    from kaito_tpu.engine.chat import _mistral

    out = _mistral([{"role": "user", "content": "hi"},
                    {"role": "assistant", "content": "yo"},
                    {"role": "system", "content": "answer briefly"}])
    assert out.endswith("[INST] answer briefly [/INST]")
    # non-trailing system still folds into the NEXT user turn
    out2 = _mistral([{"role": "user", "content": "a"},
                     {"role": "assistant", "content": "b"},
                     {"role": "system", "content": "sys"},
                     {"role": "user", "content": "c"}])
    assert "[INST] sys\n\nc [/INST]" in out2
    assert "[/INST][INST]" not in out2.replace(" ", "")


# ---------------------------------------------------------------------------
# satellite: export-registry grace drain + periodic GC (fast)
# ---------------------------------------------------------------------------

class _FakeExport:
    def __init__(self, age_s=0.0):
        self.created = time.monotonic() - age_s
        self.draining = False
        self.fully_served = False

    def ensure_draining(self):
        self.draining = True


def test_export_registry_tick_starts_overdue_drains():
    from kaito_tpu.engine.pd import KVExportRegistry

    reg = KVExportRegistry()
    fresh, stale = _FakeExport(age_s=0.0), _FakeExport(age_s=60.0)
    reg.put("fresh", fresh)
    reg.put("stale", stale)
    reg.tick(grace_s=5.0)
    assert stale.draining            # unpulled past the grace: HBM unpinned
    assert not fresh.draining        # inside the grace: colocated pull may come


def test_export_registry_tick_gcs_expired_entries():
    from kaito_tpu.engine.pd import KVExportRegistry

    reg = KVExportRegistry(ttl_s=0.01)
    reg.put("old", _FakeExport())
    time.sleep(0.03)
    reg.tick()                       # GC no longer depends on a new put()
    assert reg.get("old") is None


# ---------------------------------------------------------------------------
# DP router: breaker, failover, drain, framing (fast — fake backends)
# ---------------------------------------------------------------------------

def _fake_backend(tag: str) -> ThreadingHTTPServer:
    class H(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):
            pass

        def _json(self, code, obj):
            body = json.dumps(obj).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            if self.path == "/health":
                self._json(200, {"ok": True})
            elif self.path == "/nobody":
                self.send_response(204)
                self.end_headers()
            elif self.path == "/busy":
                self._json(503, {"error": "loading"})
            else:
                self._json(404, {"error": "nope"})

        def do_POST(self):
            n = int(self.headers.get("Content-Length") or 0)
            body = self.rfile.read(n) if n else b""
            if self.path == "/echo":
                self._json(200, {"len": len(body),
                                 "body": body.decode("utf-8", "replace")})
            else:
                self._json(200, {"backend": tag, "len": len(body)})

    return ThreadingHTTPServer(("127.0.0.1", 0), H)


@pytest.fixture()
def router_pair():
    from kaito_tpu.runtime.dp_router import DPRouter, make_router_server

    b0, b1 = _fake_backend("b0"), _fake_backend("b1")
    for b in (b0, b1):
        threading.Thread(target=b.serve_forever, daemon=True).start()
    urls = [f"http://127.0.0.1:{b.server_address[1]}" for b in (b0, b1)]
    router = DPRouter(urls)
    srv = make_router_server(router, "127.0.0.1", 0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        yield router, f"http://127.0.0.1:{srv.server_address[1]}", urls
    finally:
        srv.shutdown()
        b0.shutdown()
        b1.shutdown()


def _post(url, obj, timeout=10.0):
    req = urllib.request.Request(url, json.dumps(obj).encode(),
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def test_router_breaker_opens_and_traffic_fails_over(router_pair):
    """Acceptance: one backend failpoint-killed -> breaker opens, every
    idempotent request still succeeds via the surviving replica."""
    router, rurl, urls = router_pair
    with failpoint("router.forward", backend=urls[0]):
        served = []
        for i in range(6):
            # fast-forward the cooldown so each round actually probes
            # the dead backend again (breaker accrues failures)
            router.backends[0].down_until = 0.0
            status, out = _post(rurl + "/v1/completions", {"i": i})
            assert status == 200          # 100% success under the fault
            served.append(out["backend"])
        assert set(served) == {"b1"}      # every reply from the live replica
        assert router.backends[0].failures >= 3
        assert router.backends[0].state == "open"
    # cooldown lapses -> half-open: the next request is the probe
    router.backends[0].down_until = 0.0
    assert router.backends[0].state == "half-open"
    for i in range(4):
        status, _ = _post(rurl + "/v1/completions", {"i": i})
        assert status == 200
    # a success closed the breaker again
    assert router.backends[0].state == "closed"
    assert router.backends[0].failures == 0
    stats = json.loads(urllib.request.urlopen(
        rurl + "/router/stats", timeout=5).read())
    assert all(("state" in s and "served" in s and "alive" in s)
               for s in stats.values())


def test_router_health_probe_closes_breaker():
    from kaito_tpu.runtime.dp_router import DPRouter, HealthProber

    b0 = _fake_backend("b0")
    threading.Thread(target=b0.serve_forever, daemon=True).start()
    try:
        router = DPRouter([f"http://127.0.0.1:{b0.server_address[1]}"])
        for _ in range(3):
            router.backends[0].mark_down()
        assert router.backends[0].state == "open"
        prober = HealthProber(router, interval_s=0.05)
        prober.start()
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline \
                and router.backends[0].state != "closed":
            time.sleep(0.02)
        prober.stop()
        assert router.backends[0].state == "closed"
    finally:
        b0.shutdown()


def test_router_504_on_backend_503_falls_back_to_peer(router_pair):
    """A replica answering 503 (loading stub/drain) is routed AROUND
    without tripping its breaker — the process is alive."""
    router, rurl, urls = router_pair
    status, out = _post(rurl + "/v1/completions", {"x": 1})
    assert status == 200
    assert router.backends[0].failures == 0


def test_router_no_chunked_framing_on_204(router_pair):
    router, rurl, urls = router_pair
    host, port = rurl[len("http://"):].split(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=10)
    try:
        conn.request("GET", "/nobody")
        resp = conn.getresponse()
        assert resp.status == 204
        assert resp.getheader("Transfer-Encoding") is None
        assert resp.read() == b""
        # the connection must still be usable (no stray terminator)
        conn.request("GET", "/health")
        resp2 = conn.getresponse()
        assert resp2.status == 200
        assert json.loads(resp2.read()) == {"ok": True}
    finally:
        conn.close()


def test_router_dechunks_chunked_client_body(router_pair):
    router, rurl, urls = router_pair
    host, port = rurl[len("http://"):].split(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=10)
    try:
        conn.request("POST", "/echo", body=iter([b"hello ", b"world"]),
                     headers={"Transfer-Encoding": "chunked"},
                     encode_chunked=True)
        resp = conn.getresponse()
        assert resp.status == 200
        out = json.loads(resp.read())
        # previously: chunked bodies were silently dropped (len 0)
        assert out == {"len": 11, "body": "hello world"}
    finally:
        conn.close()


def test_router_graceful_drain_rejects_new_work(router_pair):
    router, rurl, urls = router_pair
    assert router.drain(timeout_s=1.0)       # idle: quiesces immediately
    req = urllib.request.Request(rurl + "/v1/completions",
                                 json.dumps({}).encode(),
                                 headers={"Content-Type": "application/json"})
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req, timeout=5)
    assert ei.value.code == 503
    assert ei.value.headers.get("Retry-After") is not None
    assert json.loads(ei.value.read())["error"] == "router draining"
    router.draining = False                  # heal for fixture teardown
    status, _ = _post(rurl + "/v1/completions", {})
    assert status == 200


def test_router_retryable_classification():
    from kaito_tpu.runtime.dp_router import _retryable

    assert _retryable("GET", "/anything")
    assert _retryable("DELETE", "/pd/kv/x")
    assert _retryable("POST", "/v1/completions")
    assert _retryable("POST", "/v1/chat/completions")
    assert not _retryable("POST", "/pd/prefill")     # mutates replica state


# ---------------------------------------------------------------------------
# engine chaos (compile-heavy; `make chaos` runs them)
# ---------------------------------------------------------------------------

BASE = dict(model="tiny-llama-test", max_model_len=256, page_size=16,
            max_num_seqs=4, dtype="float32", kv_dtype="float32",
            prefill_buckets=(32, 64, 128), seed=0,
            enable_prefix_caching=False, kv_import_retries=1)


def _greedy(n):
    from kaito_tpu.engine.engine import SamplingParams

    return SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True)


@pytest.fixture(scope="module")
def eng():
    from kaito_tpu.engine.config import EngineConfig
    from kaito_tpu.engine.engine import InferenceEngine

    return InferenceEngine(EngineConfig(**BASE))


def _drive(eng, until, max_steps=400):
    for _ in range(max_steps):
        eng.step()
        if until():
            return
    raise AssertionError("condition not reached while driving the engine")


def _chunked_meta(eng, n_tokens):
    """A wire meta/plans pair matching this engine's pool layout."""
    from kaito_tpu.engine.pd import ChunkPlan

    n_pages = -(-n_tokens // eng.cfg.page_size)
    k, v = eng.cache.k, eng.cache.v
    meta = {"shape": [int(k.shape[0]), n_pages] + [int(s) for s in k.shape[2:]],
            "v_shape": [int(v.shape[0]), n_pages]
            + [int(s) for s in v.shape[2:]],
            "dtype": str(k.dtype), "model": "", "n_tokens": n_tokens}
    plans = [ChunkPlan(0, int(k.shape[0]), 0, n_pages)]
    return meta, plans


def test_kv_import_fault_is_request_scoped(eng):
    """Acceptance: one request's KV import failpoint fires -> THAT
    request gets a structured error; a concurrent decode on the same
    engine finishes; the engine serves new work; no _fail_all."""
    fatal0 = eng.counters["engine_fatal_total"]
    a = eng.submit(list(range(1, 17)), _greedy(8))
    _drive(eng, lambda: any(s.request is a for s in eng.slots))
    meta, plans = _chunked_meta(eng, 16)
    b = eng.submit_with_kv_chunked(list(range(20, 36)), 5, meta, plans,
                                   _greedy(4))
    b.kv_retries = 0                      # isolate the scoping (no retry)
    with failpoint("engine.kv_import", req_id=b.req_id):
        _drive(eng, lambda: b.finish_reason != "")
    assert b.finish_reason == "error"
    assert b.error["type"] == "kv_transfer_failed"
    assert b.error["status"] == 502
    # the neighbour decodes to completion, untouched
    _drive(eng, lambda: a.finish_reason != "")
    assert a.finish_reason == "length"
    assert len(a.output_tokens) == 8
    # and the engine is healthy for NEW work
    c = eng.submit(list(range(40, 50)), _greedy(3))
    _drive(eng, lambda: c.finish_reason != "")
    assert c.finish_reason == "length"
    assert eng.counters["engine_fatal_total"] == fatal0


def test_transient_kv_fault_retries_as_local_recompute(eng):
    """A transient transfer failure consumes the retry budget and the
    request still SUCCEEDS via local prefill."""
    retries0 = eng.counters["kv_import_retries_total"]
    meta, plans = _chunked_meta(eng, 16)
    b = eng.submit_with_kv_chunked(list(range(50, 66)), 5, meta, plans,
                                   _greedy(4))
    assert b.kv_retries == 1              # from cfg.kv_import_retries
    _drive(eng, lambda: any(s.request is b and s.importing
                            for s in eng.slots))
    b.kv_chunked.set_error("chunk pull failed: connection reset",
                           transient=True)
    _drive(eng, lambda: b.finish_reason != "")
    assert b.finish_reason == "length"    # SUCCESS, not an error
    assert len(b.output_tokens) == 4
    assert b.kv_chunked is None           # fell back to local recompute
    assert eng.counters["kv_import_retries_total"] == retries0 + 1


def test_permanent_kv_fault_exhausts_no_budget_and_fails(eng):
    """A corrupt/mis-shaped transfer is NOT retried: the bytes would be
    wrong again."""
    meta, plans = _chunked_meta(eng, 16)
    b = eng.submit_with_kv_chunked(list(range(70, 86)), 5, meta, plans,
                                   _greedy(4))
    _drive(eng, lambda: any(s.request is b and s.importing
                            for s in eng.slots))
    b.kv_chunked.set_error("chunk 0 shape mismatch", transient=False)
    _drive(eng, lambda: b.finish_reason != "")
    assert b.finish_reason == "error"
    assert b.error["type"] == "kv_transfer_failed"
    assert b.kv_retries == 1              # budget untouched


def test_deadline_expires_in_queue_before_tpu_time(eng):
    expired0 = eng.counters["requests_expired_total"]
    prompts0 = eng.counters["prompt_tokens_total"]
    r = eng.submit(list(range(1, 9)), _greedy(4), timeout_s=0.01)
    time.sleep(0.08)
    _drive(eng, lambda: r.finish_reason != "", max_steps=10)
    assert r.finish_reason == "deadline"
    assert r.error["status"] == 408
    assert r.error["type"] == "deadline_exceeded"
    assert eng.counters["requests_expired_total"] == expired0 + 1
    # never prefilled: no prompt tokens were burned on an expired request
    assert eng.counters["prompt_tokens_total"] == prompts0


def test_deadline_aborts_active_decode_and_frees_pages(eng):
    free0 = eng.allocator.available
    r = eng.submit(list(range(1, 17)), _greedy(200), timeout_s=0.25)
    _drive(eng, lambda: any(s.request is r for s in eng.slots))
    time.sleep(0.3)
    _drive(eng, lambda: r.finish_reason != "", max_steps=20)
    assert r.finish_reason == "deadline"
    assert r.error["status"] == 408
    assert 0 < len(r.output_tokens) < 200     # some tokens, then the cut
    assert eng.allocator.available == free0   # pages all returned


def test_submit_with_kv_device_rejects_shape_mismatch(eng):
    """Satellite: incompatible slab layout fails in the REQUEST thread
    with ValueError (-> clean 4xx), never inside the scheduler."""
    meta, _ = _chunked_meta(eng, 16)
    meta["shape"][2] += 1                 # wrong page_size dimension
    with pytest.raises(ValueError, match="incompatible"):
        eng.submit_with_kv_device(list(range(1, 17)), 5, meta, None,
                                  _greedy(2))
    bad_heads = _chunked_meta(eng, 16)[0]
    bad_heads["shape"][3] *= 2            # wrong KV head count
    with pytest.raises(ValueError, match="incompatible"):
        eng.submit_with_kv_device(list(range(1, 17)), 5, bad_heads, None,
                                  _greedy(2))
    wrong_tokens = _chunked_meta(eng, 16)[0]
    wrong_tokens["n_tokens"] = 99
    with pytest.raises(ValueError, match="token mismatch"):
        eng.submit_with_kv_device(list(range(1, 17)), 5, wrong_tokens, None,
                                  _greedy(2))


def test_engine_step_wires_export_registry_tick(eng):
    stale = _FakeExport(age_s=60.0)
    eng.kv_exports.put("tick-test", stale)
    eng._last_export_tick = 0.0
    eng.step()
    assert stale.draining
    eng.kv_exports.pop("tick-test")


def test_step_failpoint_is_engine_fatal_then_recovers():
    """The engine-fatal domain: a fault at the top of step() fails
    EVERYTHING in flight (no stranded clients), and the engine serves
    new work on the next iteration."""
    from kaito_tpu.engine.config import EngineConfig
    from kaito_tpu.engine.engine import InferenceEngine

    e = InferenceEngine(EngineConfig(**BASE))
    e.start()
    try:
        a = e.submit(list(range(1, 9)), _greedy(500))
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and not a.output_tokens:
            time.sleep(0.01)
        assert a.output_tokens, "request never started decoding"
        FAILPOINTS.activate("engine.step", count=1, arg="injected step fault")
        while time.monotonic() < deadline and a.finish_reason == "":
            time.sleep(0.01)
        assert a.finish_reason == "error"
        assert e.counters["engine_fatal_total"] == 1
        # recovery: a fresh request completes
        b = e.submit(list(range(30, 38)), _greedy(3))
        while time.monotonic() < deadline and b.finish_reason == "":
            time.sleep(0.01)
        assert b.finish_reason == "length"
        assert len(b.output_tokens) == 3
    finally:
        e.stop()


def test_request_scoped_error_contained_by_loop():
    """RequestScopedError raised out of step() fails ONE request and
    the loop keeps serving (the scoped half of the classification)."""
    from kaito_tpu.engine.config import EngineConfig
    from kaito_tpu.engine.engine import InferenceEngine, RequestScopedError

    e = InferenceEngine(EngineConfig(**BASE))
    victim = e.submit(list(range(1, 9)), _greedy(4))
    armed = threading.Event()
    armed.set()
    orig_step = e.step

    def step_with_injection():
        if armed.is_set():
            armed.clear()
            got = e._pop_waiting()
            assert got is victim
            raise RequestScopedError(got, "injected scoped fault")
        return orig_step()

    e.step = step_with_injection
    e.start()
    try:
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and victim.finish_reason == "":
            time.sleep(0.01)
        assert victim.finish_reason == "error"
        assert victim.error["message"] == "injected scoped fault"
        assert e.counters["engine_fatal_total"] == 0
        survivor = e.submit(list(range(20, 28)), _greedy(3))
        while time.monotonic() < deadline and survivor.finish_reason == "":
            time.sleep(0.01)
        assert survivor.finish_reason == "length"
    finally:
        e.stop()


def test_prefill_failpoint_scoped_to_one_request(eng):
    failed0 = eng.counters["requests_failed_total"]
    a = eng.submit(list(range(1, 9)), _greedy(3))
    b = eng.submit(list(range(10, 18)), _greedy(3))
    with failpoint("engine.prefill", req_id=a.req_id):
        _drive(eng, lambda: a.finish_reason != "" and b.finish_reason != "")
    assert a.finish_reason == "error"
    assert a.error["type"] == "prefill_failed"
    assert b.finish_reason == "length"        # neighbour unharmed
    assert eng.counters["requests_failed_total"] == failed0 + 1


def test_guaranteed_tenant_completes_under_flood_and_chaos():
    """Tenant-starvation chaos (docs/qos.md, `make chaos`): a
    best-effort flood oversubscribes a 2-slot engine while a prefill
    failpoint kills one flood member mid-overload; the guaranteed
    tenant — submitted LAST — still completes 100% of its work."""
    import json

    from kaito_tpu.engine.config import EngineConfig
    from kaito_tpu.engine.engine import InferenceEngine

    qos = json.dumps({
        "classes": {"guaranteed": {"priority": 100, "weight": 8},
                    "best-effort": {"priority": 0, "weight": 1}},
        "tenants": {"acme": "guaranteed"},
        "default_class": "best-effort"})
    e = InferenceEngine(EngineConfig(**{**BASE, "max_num_seqs": 2,
                                        "max_pages": 10,
                                        "qos_config": qos}))
    flood = [e.submit([7 + i, 8, 9] * 9, _greedy(16), tenant="be",
                      req_id=f"be{i}") for i in range(6)]
    gold = [e.submit([40 + i, 41, 42] * 9, _greedy(24), tenant="acme",
                     req_id=f"g{i}") for i in range(3)]
    FAILPOINTS.activate("engine.prefill", count=1, req_id="be1")
    e.start()
    try:
        gold_out = [list(g.stream()) for g in gold]
        for r in flood:
            list(r.stream())        # drain; chaos victim errors out
    finally:
        e.stop()
    # the guaranteed tenant completes 100%, despite submitting last,
    # despite the flood, despite the chaos
    for g, out in zip(gold, gold_out):
        assert g.finish_reason == "length"
        assert len(out) == 24
    # the chaos actually fired, scoped to its one flood victim...
    victims = [r for r in flood if r.finish_reason == "error"]
    assert [r.req_id for r in victims] == ["be1"]
    # ...and the surviving best-effort requests were degraded (shed is
    # the HTTP layer's job; in-engine the ladder shows as preemption),
    # not lost: every survivor still finished
    assert all(r.finish_reason == "length"
               for r in flood if r is not victims[0])
