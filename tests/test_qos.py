"""Multi-tenant QoS: priority classes, weighted-fair admission, and
per-tenant graceful degradation under overload (docs/qos.md).

Fast tier (`make qos`): config parsing, DRR admission order, the
preemption-ordering ladder (the legacy newest-preempts-first pin plus
its priority-aware extension), per-tenant rate-limit budgets,
per-tenant metric/SLO slices, fleet aggregation, EPP scorers and the
429-aware routing fail-over.  The two-tenant overload e2e over real
engine processes is the slow leg.
"""

import json

import numpy as np
import pytest

from kaito_tpu.engine.config import EngineConfig
from kaito_tpu.engine.engine import InferenceEngine, SamplingParams
from kaito_tpu.engine.qos import parse_qos_config, priority_rank

BASE = dict(model="tiny-llama-test", max_model_len=256, page_size=16,
            max_num_seqs=4, dtype="float32", kv_dtype="float32",
            prefill_buckets=(32, 64, 128), seed=0,
            enable_prefix_caching=False)

# two classes + a tenant map: "acme" is guaranteed, everyone else
# best-effort.  Used by most QoS-on tests below.
QOS = json.dumps({
    "classes": {
        "guaranteed": {"priority": 100, "weight": 8},
        "best-effort": {"priority": 0, "weight": 1,
                        "max_queue_len": 4, "tokens_per_s": 0},
    },
    "tenants": {"acme": "guaranteed"},
    "default_class": "best-effort",
})


def _greedy(n):
    return SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True)


# ---------------------------------------------------------------------------
# preemption ordering: pin the LEGACY invariant first (QoS absent)
# ---------------------------------------------------------------------------

def test_pin_newest_preempts_first_without_qos():
    """With no QoS config the scheduler must keep today's contract
    exactly: when the page pool runs dry, the newest-admitted sequence
    yields — the older request is never preempted while a newer one
    holds pages."""
    cfg = EngineConfig(**{**BASE, "max_num_seqs": 2, "max_pages": 10})
    eng = InferenceEngine(cfg)
    eng.start()
    try:
        ra = eng.submit([40, 41, 42] * 11, _greedy(100))   # oldest
        rb = eng.submit([50, 51, 52] * 11, _greedy(40))    # newest
        a_out = list(ra.stream())
        b_out = list(rb.stream())
    finally:
        eng.stop()
    assert len(a_out) == 100 and len(b_out) == 40
    assert eng.counters["preemptions_total"] >= 1
    assert rb.preemptions >= 1      # the newest yielded
    assert ra.preemptions == 0      # the oldest never did
    assert eng.allocator.available == eng.allocator.num_pages - 1


# ---------------------------------------------------------------------------
# QoS on: priority-aware preemption ordering + restore
# ---------------------------------------------------------------------------

def test_lowest_priority_preempted_first_with_qos():
    """Same geometry as the pin test but with QoS and the SUBMIT ORDER
    REVERSED: the best-effort sequence is the oldest, the guaranteed
    one the newest.  Legacy would evict the guaranteed request
    (newest); the QoS scheduler must evict the best-effort one and
    restore it to completion afterwards."""
    cfg = EngineConfig(**{**BASE, "max_num_seqs": 2, "max_pages": 10,
                          "qos_config": QOS})
    eng = InferenceEngine(cfg)
    eng.start()
    try:
        rb = eng.submit([50, 51, 52] * 11, _greedy(40),
                        tenant="free")                   # oldest, prio 0
        ra = eng.submit([40, 41, 42] * 11, _greedy(100),
                        tenant="acme")                   # newest, prio 100
        a_out = list(ra.stream())
        b_out = list(rb.stream())
    finally:
        eng.stop()
    assert len(a_out) == 100 and len(b_out) == 40        # restore works
    assert eng.counters["preemptions_total"] >= 1
    assert rb.preemptions >= 1      # best-effort yielded despite age
    assert ra.preemptions == 0      # guaranteed never did
    assert eng.allocator.available == eng.allocator.num_pages - 1


def test_best_effort_admission_never_evicts_guaranteed():
    """A best-effort admission may not preempt a running guaranteed
    sequence to make room — it waits its turn instead."""
    cfg = EngineConfig(**{**BASE, "max_num_seqs": 2, "max_pages": 12,
                          "qos_config": QOS})
    eng = InferenceEngine(cfg)
    eng.start()
    try:
        g1 = eng.submit([10, 11] * 8, _greedy(30), tenant="acme")
        g2 = eng.submit([12, 13] * 8, _greedy(30), tenant="acme")
        be = eng.submit([60, 61] * 8, _greedy(10), tenant="free")
        assert len(list(g1.stream())) == 30
        assert len(list(g2.stream())) == 30
        assert len(list(be.stream())) == 10
    finally:
        eng.stop()
    assert g1.preemptions == 0 and g2.preemptions == 0


def test_guaranteed_claims_slot_from_running_best_effort():
    """Slot-level preemption: with every slot held by a lower class, a
    queued guaranteed request evicts one instead of waiting out its
    decode — and the evicted best-effort request still completes."""
    import time

    cfg = EngineConfig(**{**BASE, "max_num_seqs": 1, "qos_config": QOS})
    eng = InferenceEngine(cfg)
    eng.start()
    try:
        be = eng.submit([50, 51, 52] * 4, _greedy(60), tenant="free")
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and not be.output_tokens:
            time.sleep(0.01)
        assert be.output_tokens, "best-effort never started decoding"
        g = eng.submit([40, 41, 42] * 4, _greedy(10), tenant="acme")
        assert len(list(g.stream())) == 10
        assert len(list(be.stream())) == 60      # restored + finished
    finally:
        eng.stop()
    assert be.preemptions >= 1
    assert g.preemptions == 0


# ---------------------------------------------------------------------------
# QoS admission order: strict priority, weighted DRR within a class
# ---------------------------------------------------------------------------

def _mk_queued_engine(qos_doc):
    """An engine that is NEVER started: submits enqueue, _pop_waiting
    exposes the admission order without running any model steps."""
    cfg = EngineConfig(**{**BASE, "qos_config": json.dumps(qos_doc)})
    return InferenceEngine(cfg)


def test_admission_strict_priority_then_weighted_drr():
    doc = {
        "classes": {
            "gold": {"priority": 10, "weight": 1},
            "a": {"priority": 0, "weight": 4},
            "b": {"priority": 0, "weight": 1},
        },
        "tenants": {"gold": "gold", "a": "a", "b": "b"},
        "default_class": "b",
    }
    eng = _mk_queued_engine(doc)
    ids = {}
    for t in ("a", "b"):
        for i in range(5):
            h = eng.submit([1, 2, 3], _greedy(4), tenant=t,
                           req_id=f"{t}{i}")
            ids[h.req_id] = h
    eng.submit([1, 2, 3], _greedy(4), tenant="gold", req_id="g0")
    order = []
    while True:
        req = eng._pop_waiting()
        if req is None:
            break
        order.append(req.req_id)
    # gold admitted first despite being submitted LAST (strict
    # priority); then a:b interleave at the 4:1 DRR weight
    assert order[0] == "g0"
    assert order[1:] == ["a0", "a1", "a2", "a3", "b0",
                         "a4", "b1", "b2", "b3", "b4"]
    assert eng.num_waiting == 0


def test_requeue_front_is_served_next_within_class():
    doc = {"classes": {"only": {"priority": 0, "weight": 1}},
           "tenants": {}, "default_class": "only"}
    eng = _mk_queued_engine(doc)
    r1 = eng.submit([1], _greedy(2), tenant="t1", req_id="r1")
    eng.submit([1], _greedy(2), tenant="t2", req_id="r2")
    first = eng._pop_waiting()
    assert first.req_id == "r1"
    eng._requeue_front(first)        # a preemption puts it back in front
    assert eng.num_waiting_for("t1") == 1
    assert eng._pop_waiting().req_id == "r1"
    assert eng._pop_waiting().req_id == "r2"
    del r1


# ---------------------------------------------------------------------------
# config parsing
# ---------------------------------------------------------------------------

def test_parse_qos_config_empty_is_off():
    assert parse_qos_config("") is None
    assert parse_qos_config("   ") is None


def test_parse_qos_config_file(tmp_path):
    p = tmp_path / "qos.json"
    p.write_text(QOS)
    q = parse_qos_config(f"@{p}")
    assert q.class_of("acme").priority == 100
    assert q.class_of("someone-else").name == "best-effort"
    # an explicit priority header names a class directly
    assert q.class_of("someone-else", "guaranteed").priority == 100
    assert q.weight_of("acme") == 8
    assert q.to_dict()["default_class"] == "best-effort"


@pytest.mark.parametrize("doc, msg", [
    ("{not json", "not valid JSON"),
    ("[]", "JSON object"),
    ('{"classes": {}}', "non-empty 'classes'"),
    ('{"classes": {"bad name!": {}}}', "label-safe"),
    ('{"classes": {"a": {"weight": 0}}}', "weight must be >= 1"),
    ('{"classes": {"a": {"burst": 2}}}', "unknown"),
    ('{"classes": {"a": {"tokens_per_s": -1}}}', "budgets must be >= 0"),
    ('{"classes": {"a": {}}, "tenants": {"t": "nope"}}', "unknown class"),
    ('{"classes": {"a": {}, "b": {}}}', "default_class"),
    ('{"classes": {"a": {}}, "default_class": "zz"}', "not a defined"),
])
def test_parse_qos_config_rejects(doc, msg):
    with pytest.raises(ValueError, match=msg):
        parse_qos_config(doc)


def test_priority_rank():
    assert priority_rank("") == 0.0
    assert priority_rank("guaranteed") == 1.0
    assert priority_rank("best-effort") == 0.0
    assert priority_rank("75") == 0.75
    assert priority_rank("5000") == 1.0          # numeric clamps
    assert priority_rank("my-custom-class") == 0.5   # neutral


# ---------------------------------------------------------------------------
# rate limiter: per-tenant budgets, deterministic jitter, probe counter
# ---------------------------------------------------------------------------

class _Clock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


class _StubEngine:
    def __init__(self, num_waiting=0, per_tenant=None):
        self.num_waiting = num_waiting
        self._per = per_tenant or {}

    def num_waiting_for(self, tenant):
        return self._per.get(tenant, 0)


def test_tenant_queue_budget_sheds_before_global():
    from kaito_tpu.engine.rate_limit import RateLimiter

    lim = RateLimiter(max_queue_len=100, qos=parse_qos_config(QOS))
    eng = _StubEngine(num_waiting=8, per_tenant={"free": 4, "acme": 4})
    assert lim.shed_reason(eng, tenant="free") \
        == {"reason": "tenant_queue_full", "tenant": "free"}
    # the guaranteed class has no queue cap: same depth admits
    assert lim.shed_reason(eng, tenant="acme") is None
    # anonymous traffic only sees the global cap
    assert lim.shed_reason(eng) is None


def test_tenant_token_bucket_is_post_paid():
    from kaito_tpu.engine.rate_limit import RateLimiter

    doc = json.dumps({"classes": {"metered": {"tokens_per_s": 10}},
                      "default_class": "metered"})
    clock = _Clock()
    lim = RateLimiter(max_queue_len=100, qos=parse_qos_config(doc),
                      time_fn=clock)
    eng = _StubEngine()
    # a fresh bucket holds the burst headroom: admitted
    assert lim.shed_reason(eng, tenant="t") is None
    lim.note_tokens("t", 100)       # actual usage, debited at completion
    assert lim.shed_reason(eng, tenant="t")["reason"] == "tenant_rate"
    clock.t += 9.0                  # refills at the sustained 10 tok/s
    assert lim.shed_reason(eng, tenant="t") is None


def test_retry_after_jitter_is_deterministic_per_request():
    from kaito_tpu.engine.rate_limit import RateLimiter

    lim = RateLimiter(max_queue_len=200)
    eng = _StubEngine(num_waiting=80)
    base = lim.retry_after_s(eng)
    assert base == 11                      # min(30, 1 + 80 // 8), no jitter
    a = lim.retry_after_s(eng, key="req-1")
    assert a == lim.retry_after_s(eng, key="req-1")     # hash, not random
    assert base <= a <= 30
    spread = {lim.retry_after_s(eng, key=f"req-{i}") for i in range(32)}
    assert len(spread) > 1    # shed cohorts don't retry on the same tick


def test_probe_error_counter_on_broken_pressure_probe():
    from kaito_tpu.engine.rate_limit import RateLimiter

    class _NoAllocator:
        num_waiting = 2

    lim = RateLimiter(max_queue_len=100, kv_shed_threshold=0.9)
    assert lim.shed_reason(_NoAllocator()) is None
    assert lim.probe_errors.value() == 1.0


# ---------------------------------------------------------------------------
# per-tenant observability: engine metrics + SLO watchdog slices
# ---------------------------------------------------------------------------

def test_engine_metrics_tenant_families_gated_on_qos():
    from kaito_tpu.engine.metrics import EngineMetrics

    # QoS off: the per-tenant families must not even emit HELP/TYPE,
    # or the exposition stops being byte-identical to the seed
    off = EngineMetrics()
    assert "kaito:requests_shed_total" not in off.registry.expose()
    assert "kaito:requests_served_total" not in off.registry.expose()

    on = EngineMetrics(qos=parse_qos_config(QOS))
    on.tenant_shed.inc(tenant="free")
    on.tenant_served.inc(tenant="acme")
    text = on.registry.expose()
    assert 'kaito:requests_shed_total{tenant="free"} 1' in text
    assert 'kaito:requests_served_total{tenant="acme"} 1' in text


def test_slo_watchdog_tenant_slices_and_gauges():
    from kaito_tpu.engine.metrics import Registry
    from kaito_tpu.runtime.slo import SLOWatchdog

    clock = _Clock()
    slo = SLOWatchdog(time_fn=clock, per_tenant=True)
    for _ in range(5):
        slo.observe_ttft(0.1, tenant="acme")
        slo.observe_ttft(2.0, tenant="free")
    slo.note_shed(3, tenant="free")
    snap = slo.tenant_snapshot()
    assert snap["acme"]["ttft_p50_s"] == pytest.approx(0.1)
    assert snap["free"]["ttft_p50_s"] == pytest.approx(2.0)
    assert snap["free"]["shed"] == 3.0
    assert snap["acme"]["shed"] == 0.0
    assert slo.snapshot()["tenants"] == snap

    reg = Registry()
    slo.register_metrics(reg)
    text = reg.expose()
    assert 'kaito:slo_tenant_ttft_p50_seconds{tenant="acme"}' in text
    assert 'kaito:slo_tenant_shed{tenant="free"} 3' in text

    # per_tenant off: no tenant families, no "tenants" snapshot key
    off = SLOWatchdog(time_fn=clock)
    off.observe_ttft(0.1, tenant="acme")    # tenant arg is a no-op
    assert "tenants" not in off.snapshot()
    reg2 = Registry()
    off.register_metrics(reg2)
    assert "slo_tenant" not in reg2.expose()


# ---------------------------------------------------------------------------
# routing: 429 Retry-After demotion (no breaker trip)
# ---------------------------------------------------------------------------

def test_429_demotion_prefers_other_backends_without_breaker_trip():
    from kaito_tpu.runtime.routing import RoutingCore

    core = RoutingCore(["http://a:1", "http://b:1"])
    a, b = core.backends
    a.demote(30.0)
    assert a.demoted and a.state == "closed"    # breaker untouched
    assert {core.next_backend().url for _ in range(4)} == {"http://b:1"}
    # every backend inside an advisory window: still serves (a refused
    # retry beats a guaranteed 503)
    b.demote(30.0)
    assert core.next_backend() is not None
    # the window is advisory and expires on its own
    a.avoid_until = 0.0
    assert not a.demoted
    urls = {core.next_backend().url for _ in range(4)}
    assert urls == {"http://a:1"}


# ---------------------------------------------------------------------------
# EPP: tenant stickiness + priority scorers (inert without headers)
# ---------------------------------------------------------------------------

def _epp_body(prompt, **extra):
    return json.dumps({"prompt": prompt, **extra}).encode()


def test_epp_tenant_stickiness_is_consistent_and_header_driven():
    from kaito_tpu.runtime.epp import EndpointPicker

    p = EndpointPicker(["http://a:1", "http://b:1"], block_chars=8)
    hdrs = {"X-Kaito-Tenant": "acme"}
    ctx = p.make_ctx("POST", "/v1/completions", _epp_body("x"), headers=hdrs)
    assert ctx.tenant == "acme"
    first = next(iter(p.candidates("POST", "/v1/completions", ctx))).url
    for i in range(3):      # same tenant, different prompts: same home
        c = p.make_ctx("POST", "/v1/completions",
                       _epp_body(f"prompt {i}"), headers=hdrs)
        assert next(iter(p.candidates("POST", "/v1/completions", c))).url == first
    # body fields are the no-gateway fallback for the same routing
    c = p.make_ctx("POST", "/v1/completions", _epp_body("y", tenant="acme"))
    assert c.tenant == "acme"
    assert next(iter(p.candidates("POST", "/v1/completions", c))).url == first
    # headerless traffic scores identically on both backends (inert)
    plain = p.make_ctx("POST", "/v1/completions", _epp_body("x"))
    assert plain.tenant == "" and plain.priority == ""
    assert p._score(p.backends[0], plain) \
        == pytest.approx(p._score(p.backends[1], plain))


def test_epp_priority_scorer_widens_the_headroom_gap():
    from kaito_tpu.runtime.epp import EndpointPicker

    p = EndpointPicker(["http://a:1", "http://b:1"], block_chars=8)
    a, b = p.backends
    b.load.occupancy = 0.8
    plain = p.make_ctx("POST", "/v1/completions", _epp_body("x"))
    prio = p.make_ctx("POST", "/v1/completions", _epp_body("x"),
                      headers={"X-Kaito-Priority": "guaranteed"})
    assert prio.priority == "guaranteed"
    gap_plain = p._score(a, plain) - p._score(b, plain)
    gap_prio = p._score(a, prio) - p._score(b, prio)
    # high-priority work is steered toward headroom HARDER than default
    assert gap_prio > gap_plain
    assert next(iter(p.candidates("POST", "/v1/completions",
                              prio))).url == "http://a:1"


# ---------------------------------------------------------------------------
# controller + manifests: the kaito-tpu.io/qos annotation
# ---------------------------------------------------------------------------

def test_qos_annotation_renders_engine_flag():
    from kaito_tpu.api import InferenceSpec, ObjectMeta, ResourceSpec, Workspace
    from kaito_tpu.manifests.inference import build_engine_command
    from kaito_tpu.models.registry import get_model_by_name
    from kaito_tpu.parallel.plan import plan_parallelism
    from kaito_tpu.sku.catalog import CHIP_CATALOG

    md = get_model_by_name("llama-3.1-8b-instruct")
    plan = plan_parallelism(md, CHIP_CATALOG["v5e"], workload="serve",
                            max_model_len=2048)
    ws = Workspace(
        ObjectMeta(name="qos", annotations={"kaito-tpu.io/qos": QOS}),
        resource=ResourceSpec(instance_type="ct5lp-hightpu-4t"),
        inference=InferenceSpec(preset="llama-3.1-8b-instruct"))
    cmd = build_engine_command(ws, md, plan)
    assert cmd[cmd.index("--qos-config") + 1] == QOS
    # no annotation -> no flag
    ws.metadata.annotations = {}
    assert "--qos-config" not in build_engine_command(ws, md, plan)


def test_workspace_plan_fails_on_bad_qos_annotation():
    from kaito_tpu.api import InferenceSpec, ObjectMeta, ResourceSpec, Workspace
    from kaito_tpu.api.workspace import COND_RESOURCE_READY
    from kaito_tpu.controllers.runtime import Store
    from kaito_tpu.controllers.workspace import WorkspaceReconciler
    from kaito_tpu.provision import FakeCloud, KarpenterTPUProvisioner

    store = Store()
    cloud = FakeCloud(store)
    rec = WorkspaceReconciler(store, KarpenterTPUProvisioner(store))
    store.create(Workspace(
        ObjectMeta(name="bad-qos", annotations={
            "kaito-tpu.io/qos": '{"classes": {}}'}),    # empty class map
        resource=ResourceSpec(instance_type="ct5lp-hightpu-1t"),
        inference=InferenceSpec(preset="llama-3.1-8b-instruct")))
    for _ in range(3):
        rec.reconcile_key("default", "bad-qos")
        cloud.tick()
    ws = store.get("Workspace", "default", "bad-qos")
    cond = next((c for c in ws.status.conditions
                 if c.type == COND_RESOURCE_READY), None)
    assert cond is not None and cond.status == "False"
    assert cond.reason == "PlanFailed"
    assert "kaito-tpu.io/qos" in cond.message


# ---------------------------------------------------------------------------
# acceptance e2e (slow): two tenants flood a REAL engine server process
# ---------------------------------------------------------------------------

def _qos_post(url, obj, tenant, timeout=120.0):
    import urllib.request

    req = urllib.request.Request(
        url + "/v1/completions", data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json",
                 "X-Kaito-Tenant": tenant})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _stream_ttft(url, tenant, prompt, max_tokens=8, timeout=120.0):
    """POST a streamed completion; return (seconds to the first SSE
    data event, completed) — completed means the stream reached
    ``[DONE]`` (the request was served end to end, never shed)."""
    import time as _time
    import urllib.request

    body = json.dumps({"prompt": prompt, "max_tokens": max_tokens,
                       "temperature": 0.0, "stream": True}).encode()
    req = urllib.request.Request(
        url + "/v1/completions", data=body,
        headers={"Content-Type": "application/json",
                 "X-Kaito-Tenant": tenant})
    t0 = _time.monotonic()
    first, completed = None, False
    with urllib.request.urlopen(req, timeout=timeout) as r:
        for line in r:
            if not line.startswith(b"data:"):
                continue
            if first is None:
                first = _time.monotonic() - t0
            if b"[DONE]" in line:
                completed = True
                break
    return first, completed


# slow: 35 s, and it holds a latency to 2x its unloaded baseline: a
# wall-clock threshold on a shared box
@pytest.mark.slow
def test_two_tenant_overload_guaranteed_holds_best_effort_sheds():
    """The degradation ladder end to end over a real engine-server
    process: a best-effort tenant floods past its queue budget while a
    guaranteed tenant keeps submitting.  Best-effort absorbs every 429;
    the guaranteed tenant completes 100% with a loaded TTFT p50 within
    2x its unloaded baseline, and the per-tenant
    ``kaito:requests_shed_total{tenant=...}`` exposition proves the
    split landed on the right tenant."""
    import threading
    import time
    import urllib.error
    import urllib.request

    from tests.helpers.dp_cluster import boot_backends

    prompt = "qos overload probe " * 3
    with boot_backends(1, extra_args=["--qos-config", QOS,
                                      "--max-queue-len", "64"]) as urls:
        url = urls[0]
        # warm the compile caches so the loaded phase measures
        # scheduling, not XLA compilation
        for _ in range(2):
            _stream_ttft(url, "acme", prompt)
        baseline = sorted(_stream_ttft(url, "acme", prompt)[0]
                          for _ in range(5))
        baseline_p50 = baseline[len(baseline) // 2]

        stop = threading.Event()
        sheds = []          # 429s the best-effort flood absorbed
        served = []

        def flood():
            while not stop.is_set():
                try:
                    _qos_post(url, {"prompt": prompt, "max_tokens": 24,
                                    "temperature": 0.0}, tenant="free")
                    served.append(1)
                except urllib.error.HTTPError as e:
                    assert e.code == 429
                    assert e.headers.get("Retry-After")
                    sheds.append(1)
                    time.sleep(0.05)

        threads = [threading.Thread(target=flood, daemon=True)
                   for _ in range(10)]
        for t in threads:
            t.start()
        time.sleep(1.0)     # let the flood saturate the queue
        try:
            loaded = []
            for _ in range(6):
                ttft, completed = _stream_ttft(url, "acme", prompt)
                assert completed            # 100%: never shed, never cut
                loaded.append(ttft)
        finally:
            stop.set()
        for t in threads:
            t.join(timeout=240)
        loaded_p50 = sorted(loaded)[len(loaded) // 2]
        assert sheds, "the flood never outran the best-effort budget"
        assert loaded_p50 <= max(2 * baseline_p50, baseline_p50 + 0.25), \
            (baseline, loaded)

        # the per-tenant exposition proves WHO paid for the overload
        with urllib.request.urlopen(url + "/metrics", timeout=10) as r:
            text = r.read().decode()
        shed_by = {}
        served_by = {}
        from kaito_tpu.utils.promtext import parse_exposition, parse_labels
        for name, labels, value in parse_exposition(text):
            if name == "kaito:requests_shed_total":
                shed_by[parse_labels(labels).get("tenant")] = value
            elif name == "kaito:requests_served_total":
                served_by[parse_labels(labels).get("tenant")] = value
        assert shed_by.get("free", 0) >= len(sheds) > 0
        assert shed_by.get("acme", 0.0) == 0.0      # never shed
        assert served_by.get("acme", 0) >= 13       # warmup+baseline+loaded
