"""Fleet telemetry plane (kaito_tpu/runtime/fleet.py).

Fast tier: the pure evaluator (hysteresis, sustain, saturation, idle),
payload folding, counter-delta rates with restart detection, store
discovery, ingest→fold→gauge round-trips through the shared exposition
parser, ScalingSignal conditions + deduped Events, the concurrent
scraper against a hung-but-listening target, and the manager's
``/debug/fleet`` route.

Slow tier: the acceptance e2e — two REAL engine-server processes plus
a deliberately hung third target behind one InferenceSet, load driven
against one replica, asserting cross-replica sums, ``replicas_reporting
== 2``, and a nominal → pressure → nominal transition with no flap.
"""

import json
import socket
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from kaito_tpu.api import InferenceSet, InferenceSetSpec, ObjectMeta, Workspace
from kaito_tpu.api.meta import get_condition
from kaito_tpu.api.workspace import COND_SCALING_SIGNAL, LABEL_CREATED_BY_INFERENCESET
from kaito_tpu.controllers.objects import Unstructured
from kaito_tpu.controllers.runtime import Store
from kaito_tpu.engine.metrics import Registry
from kaito_tpu.runtime.fleet import (
    ANNOTATION_SCRAPE_URL,
    EVENT_PRESSURE_DETECTED,
    EVENT_PRESSURE_RESOLVED,
    FleetPolicy,
    FleetTelemetry,
    ReplicaSample,
    SIGNAL_IDLE,
    SIGNAL_NOMINAL,
    SIGNAL_PRESSURE,
    SIGNAL_SATURATED,
    evaluate_signal,
    parse_replica_metrics,
    recommend_replicas,
)
from kaito_tpu.utils.promtext import parse_exposition, parse_labels


class Clock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def tick(self, dt):
        self.t += dt


# ---------------------------------------------------------------------------
# payload folding + rates
# ---------------------------------------------------------------------------

ENGINE_PAYLOAD = """\
# HELP kaito:batch_occupancy occ
# TYPE kaito:batch_occupancy gauge
kaito:batch_occupancy 0.5
# TYPE kaito:num_requests_waiting gauge
kaito:num_requests_waiting 3
# TYPE kaito:kv_cache_usage_perc gauge
kaito:kv_cache_usage_perc 0.25
# TYPE kaito:active_slots gauge
kaito:active_slots 1
# TYPE kaito:slots_total gauge
kaito:slots_total 2
# TYPE kaito:process_uptime_seconds gauge
kaito:process_uptime_seconds 120
# TYPE kaito:request_success_total counter
kaito:request_success_total{finished_reason="stop"} 7
kaito:request_success_total{finished_reason="length"} 3
# TYPE kaito:request_shed_total counter
kaito:request_shed_total{reason="queue_full"} 2
# TYPE kaito:prefix_cache_hits_total counter
kaito:prefix_cache_hits_total 30
# TYPE kaito:prefix_cache_misses_total counter
kaito:prefix_cache_misses_total 10
"""


def test_parse_replica_metrics_folds_sums_and_means():
    vals = parse_replica_metrics(ENGINE_PAYLOAD)
    assert vals["occupancy"] == 0.5
    assert vals["waiting"] == 3.0
    assert vals["kv_usage"] == 0.25
    assert vals["slots_total"] == 2.0
    # labelled counter series sum into one fleet key
    assert vals["requests_total"] == 10.0
    assert vals["shed_total"] == 2.0
    assert vals["uptime_s"] == 120.0
    # unknown families are ignored, not errors
    assert "burn_max" not in vals


def test_counter_deltas_become_rates_with_restart_detection():
    clock = Clock()
    ft = FleetTelemetry(Store(), time_fn=clock)
    prev = ReplicaSample(ts=clock() - 10.0,
                         values={"requests_total": 100.0, "uptime_s": 50.0})
    rates = ft._rates(prev, {"requests_total": 130.0, "uptime_s": 60.0},
                      clock())
    assert rates["requests_rate"] == pytest.approx(3.0)
    # counter went backwards AND uptime < dt: replica restarted — the
    # round rates as 0 instead of hugely negative
    rates = ft._rates(prev, {"requests_total": 4.0, "uptime_s": 2.0},
                      clock())
    assert rates["requests_rate"] == 0.0
    # no previous scrape -> no rates at all
    assert ft._rates(ReplicaSample(), {"requests_total": 4.0}, clock()) == {}


TENANT_PAYLOAD = ENGINE_PAYLOAD + """\
# TYPE kaito:requests_shed_total counter
kaito:requests_shed_total{tenant="free"} 8
kaito:requests_shed_total{tenant="acme"} 0
# TYPE kaito:requests_served_total counter
kaito:requests_served_total{tenant="acme"} 12
"""


PREFILL_PAYLOAD = ENGINE_PAYLOAD + """\
# TYPE kaito:prompt_tokens_total counter
kaito:prompt_tokens_total 4096
# TYPE kaito:engine_prefill_pack_size histogram
kaito:engine_prefill_pack_size_bucket{le="1"} 2
kaito:engine_prefill_pack_size_bucket{le="+Inf"} 10
kaito:engine_prefill_pack_size_sum 30
kaito:engine_prefill_pack_size_count 10
# TYPE kaito:prefill_queue_wait_seconds histogram
kaito:prefill_queue_wait_seconds_bucket{le="+Inf"} 8
kaito:prefill_queue_wait_seconds_sum 0.4
kaito:prefill_queue_wait_seconds_count 8
"""


def test_prefill_pack_series_parse_rate_and_aggregate():
    """Prefill-turn telemetry (docs/prefill.md): the histogram's
    _sum/_count fold as counters, rate like any other, and aggregate
    into the fleet pack-mean / queue-wait-mean gauge fields."""
    vals = parse_replica_metrics(PREFILL_PAYLOAD)
    assert vals["prompt_tokens_total"] == 4096.0
    assert vals["prefill_turn_prompts_total"] == 30.0
    assert vals["prefill_dispatches_total"] == 10.0
    assert vals["prefill_wait_seconds_total"] == pytest.approx(0.4)
    assert vals["prefill_waits_total"] == 8.0
    # bucket lines never alias into the fold
    assert all("bucket" not in k for k in vals)

    clock = Clock()
    ft = FleetTelemetry(Store(), time_fn=clock)
    prev = ReplicaSample(ts=clock() - 10.0,
                         values={"prefill_turn_prompts_total": 0.0,
                                 "prefill_dispatches_total": 0.0,
                                 "prefill_wait_seconds_total": 0.0,
                                 "prefill_waits_total": 0.0,
                                 "prompt_tokens_total": 0.0,
                                 "uptime_s": 50.0})
    rates = ft._rates(prev, vals, clock())
    assert rates["prompt_tokens_rate"] == pytest.approx(409.6)
    assert rates["prefill_turn_prompts_rate"] == pytest.approx(3.0)
    assert rates["prefill_dispatches_rate"] == pytest.approx(1.0)

    key = ("InferenceSet", "default", "pack")
    ft.ingest(key, "http://r0:5000", vals, rates=rates)
    ft.fold()
    agg = ft._last_agg[key]
    assert agg["prefill_tokens_rate"] == pytest.approx(409.6)
    assert agg["prefill_dispatch_rate"] == pytest.approx(1.0)
    assert agg["prefill_pack_mean"] == pytest.approx(3.0)
    assert agg["prefill_queue_wait_mean"] == pytest.approx(0.05)


def test_per_tenant_counters_parse_rate_and_aggregate():
    vals = parse_replica_metrics(TENANT_PAYLOAD)
    assert vals["tenant_shed_total:free"] == 8.0
    assert vals["tenant_shed_total:acme"] == 0.0
    assert vals["tenant_served_total:acme"] == 12.0
    # a payload without the QoS families produces no tenant keys
    assert not any(k.startswith("tenant_")
                   for k in parse_replica_metrics(ENGINE_PAYLOAD))

    clock = Clock()
    ft = FleetTelemetry(Store(), time_fn=clock)
    prev = ReplicaSample(ts=clock() - 10.0,
                         values={"tenant_shed_total:free": 3.0,
                                 "tenant_served_total:acme": 2.0,
                                 "uptime_s": 50.0})
    rates = ft._rates(prev, {"tenant_shed_total:free": 8.0,
                             "tenant_served_total:acme": 12.0,
                             "uptime_s": 60.0}, clock())
    assert rates["tenant_shed_rate:free"] == pytest.approx(0.5)
    assert rates["tenant_served_rate:acme"] == pytest.approx(1.0)

    key = ("InferenceSet", "default", "qos")
    ft.ingest(key, "http://r0:5000", {"waiting": 0.0},
              rates={"tenant_shed_rate:free": 0.5,
                     "tenant_served_rate:acme": 1.0}, replica="r0")
    ft.ingest(key, "http://r1:5000", {"waiting": 0.0},
              rates={"tenant_shed_rate:free": 1.5}, replica="r1")
    ft.fold()
    agg = ft._last_agg[key]
    assert agg["tenant_shed_rate:free"] == pytest.approx(2.0)
    assert agg["tenant_served_rate:acme"] == pytest.approx(1.0)

    registry = Registry()
    ft.register_metrics(registry)
    by = {}
    for name, labels, value in parse_exposition(registry.expose()):
        by[(name, tuple(sorted(parse_labels(labels).items())))] = value
    base = (("kind", "InferenceSet"), ("name", "qos"))
    assert by[("kaito:fleet_tenant_shed_per_s",
               tuple(sorted(base + (("tenant", "free"),))))] \
        == pytest.approx(2.0)
    assert by[("kaito:fleet_tenant_served_per_s",
               tuple(sorted(base + (("tenant", "acme"),))))] \
        == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# pure evaluator: hysteresis + sustain
# ---------------------------------------------------------------------------

def _policy(**kw):
    base = dict(sustain_s=10.0, idle_sustain_s=30.0, min_samples=2,
                min_window_coverage=0.8)
    base.update(kw)
    return FleetPolicy(**base)


def _series(now, spec):
    """[(age_s, sample), ...] -> evaluator input."""
    return [(now - age, s) for age, s in spec]


HIGH = {"occupancy_mean": 0.95, "replicas_reporting": 2.0}
MID = {"occupancy_mean": 0.70, "replicas_reporting": 2.0}   # lo < x < hi
LOW = {"occupancy_mean": 0.10, "queue_sum": 0.5, "replicas_reporting": 2.0,
       "requests_rate": 1.0}


def test_signal_needs_sustained_high_before_pressure():
    p, now = _policy(), 100.0
    # one fresh spike: not sustained (coverage too thin)
    d = evaluate_signal(SIGNAL_NOMINAL, _series(now, [(1.0, HIGH)]), p, now)
    assert d.state == SIGNAL_NOMINAL
    # high across the whole window: pressure, with the driver named
    d = evaluate_signal(SIGNAL_NOMINAL,
                        _series(now, [(9.0, HIGH), (5.0, HIGH), (1.0, HIGH)]),
                        p, now)
    assert d.state == SIGNAL_PRESSURE
    assert "occupancy" in d.drivers
    assert d.reason == "FleetPressure"


def test_signal_hysteresis_band_does_not_flap():
    p, now = _policy(), 100.0
    # inside the hysteresis band (above exit-low, below enter-high):
    # nominal stays nominal AND pressure stays pressure
    band = _series(now, [(9.0, MID), (5.0, MID), (1.0, MID)])
    assert evaluate_signal(SIGNAL_NOMINAL, band, p, now).state \
        == SIGNAL_NOMINAL
    assert evaluate_signal(SIGNAL_PRESSURE, band, p, now).state \
        == SIGNAL_PRESSURE
    # sustained below EVERY low watermark: pressure resolves
    calm = _series(now, [(9.0, LOW), (5.0, LOW), (1.0, LOW)])
    d = evaluate_signal(SIGNAL_PRESSURE, calm, p, now)
    assert d.state == SIGNAL_NOMINAL and d.reason == "FleetNominal"


def test_signal_saturation_and_stepdown():
    p, now = _policy(), 100.0
    deep = {"kv_mean": 0.99, "replicas_reporting": 2.0}
    hot = _series(now, [(9.0, deep), (5.0, deep), (1.0, deep)])
    d = evaluate_signal(SIGNAL_NOMINAL, hot, p, now)
    assert d.state == SIGNAL_SATURATED and d.reason == "FleetSaturated"
    # leaving saturation lands on pressure first (not straight nominal)
    # when still inside the pressure band
    band = _series(now, [(9.0, MID), (5.0, MID), (1.0, MID)])
    assert evaluate_signal(SIGNAL_SATURATED, band, p, now).state \
        == SIGNAL_PRESSURE
    calm = _series(now, [(9.0, LOW), (5.0, LOW), (1.0, LOW)])
    assert evaluate_signal(SIGNAL_SATURATED, calm, p, now).state \
        == SIGNAL_NOMINAL


def test_signal_idle_and_wake():
    p, now = _policy(), 100.0
    quiet = {"requests_rate": 0.0, "queue_sum": 0.0, "active_slots": 0.0,
             "replicas_reporting": 1.0}
    dead = _series(now, [(29.0, quiet), (15.0, quiet), (1.0, quiet)])
    d = evaluate_signal(SIGNAL_NOMINAL, dead, p, now)
    assert d.state == SIGNAL_IDLE and d.reason == "FleetIdle"
    # first non-idle sample wakes immediately (no sustain on the way up)
    awake = dead[:-1] + [(now - 0.5, dict(quiet, requests_rate=2.0))]
    assert evaluate_signal(SIGNAL_IDLE, awake, p, now).state \
        == SIGNAL_NOMINAL


def test_recommended_replicas_hints():
    p = _policy()
    assert recommend_replicas(SIGNAL_NOMINAL, 3, p) == 3
    assert recommend_replicas(SIGNAL_PRESSURE, 3, p) == 4
    assert recommend_replicas(SIGNAL_SATURATED, 4, p) == 6
    assert recommend_replicas(SIGNAL_IDLE, 3, p) == 1
    assert recommend_replicas(SIGNAL_IDLE, 3,
                              _policy(scale_to_zero_hint=True)) == 0
    assert recommend_replicas(SIGNAL_SATURATED, 4,
                              _policy(max_replicas_hint=5)) == 5


# ---------------------------------------------------------------------------
# discovery from the store
# ---------------------------------------------------------------------------

def _service(name, port=5000, annotations=None):
    return Unstructured(
        "Service", ObjectMeta(name=name, annotations=annotations or {}),
        spec={"ports": [{"port": port}]})


def test_refresh_targets_discovers_sets_and_standalones():
    store = Store()
    store.create(InferenceSet(ObjectMeta(name="fleet"),
                              InferenceSetSpec(replicas=2)))
    for i in range(2):
        store.create(Workspace(ObjectMeta(
            name=f"fleet-{i}",
            labels={LABEL_CREATED_BY_INFERENCESET: "fleet"})))
        store.create(_service(f"fleet-{i}", port=5000 + i))
    store.create(_service("fleet-epp"))
    # a standalone Workspace with an annotation override, no Service
    store.create(Workspace(ObjectMeta(
        name="solo",
        annotations={ANNOTATION_SCRAPE_URL: "http://127.0.0.1:7777/"})))
    # a Workspace with neither Service nor annotation: not scrapable yet
    store.create(Workspace(ObjectMeta(name="bare")))

    ft = FleetTelemetry(store)
    ft.refresh_targets()
    iset = ft._targets[("InferenceSet", "default", "fleet")]
    assert set(iset) == {"http://fleet-0:5000", "http://fleet-1:5001",
                         "http://fleet-epp:5000"}
    assert iset["http://fleet-epp:5000"].role == "epp"
    solo = ft._targets[("Workspace", "default", "solo")]
    assert set(solo) == {"http://127.0.0.1:7777"}   # trailing / stripped
    assert ("Workspace", "default", "bare") not in ft._targets

    # a deleted CR drops its series and targets on the next refresh
    store.delete("Workspace", "default", "solo")
    ft.refresh_targets()
    assert ("Workspace", "default", "solo") not in ft._targets


# ---------------------------------------------------------------------------
# ingest -> fold -> gauges (round-tripped through the shared parser)
# ---------------------------------------------------------------------------

def test_fold_aggregates_and_fleet_gauges_round_trip():
    clock = Clock()
    store = Store()
    ft = FleetTelemetry(store, time_fn=clock)
    key = ("InferenceSet", "default", "fleet")
    ft.ingest(key, "http://r0:5000",
              {"occupancy": 1.0, "waiting": 4.0, "kv_usage": 0.5,
               "requests_total": 100.0},
              rates={"requests_rate": 2.0, "prefix_hits_rate": 3.0,
                     "prefix_misses_rate": 1.0}, replica="r0")
    ft.ingest(key, "http://r1:5000",
              {"occupancy": 0.5, "waiting": 1.0, "kv_usage": 0.3,
               "requests_total": 40.0},
              rates={"requests_rate": 1.0}, replica="r1")
    ft.fold()
    agg = ft._last_agg[key]
    assert agg["replicas_reporting"] == 2.0
    assert agg["queue_sum"] == 5.0
    assert agg["occupancy_mean"] == pytest.approx(0.75)
    assert agg["requests_total"] == 140.0
    assert agg["requests_rate"] == pytest.approx(3.0)
    assert agg["prefix_hit_rate"] == pytest.approx(0.75)

    registry = Registry()
    ft.register_metrics(registry)
    samples = parse_exposition(registry.expose())
    by = {}
    for name, labels, value in samples:
        by[(name, tuple(sorted(parse_labels(labels).items())))] = value
    base = (("kind", "InferenceSet"), ("name", "fleet"))
    assert by[("kaito:fleet_replicas_reporting", base)] == 2.0
    assert by[("kaito:fleet_requests_total", base)] == 140.0
    assert by[("kaito:fleet_queue_depth",
               tuple(sorted(base + (("agg", "sum"),))))] == 5.0
    assert by[("kaito:fleet_batch_occupancy",
               tuple(sorted(base + (("agg", "mean"),))))] \
        == pytest.approx(0.75)
    assert by[("kaito:fleet_signal_state", base)] == 1.0   # nominal

    # a replica going stale drops out of the NEXT fold
    clock.tick(ft.freshness_s + 1.0)
    ft.ingest(key, "http://r1:5000", {"occupancy": 0.5, "waiting": 1.0},
              replica="r1")
    ft.fold()
    assert ft._last_agg[key]["replicas_reporting"] == 1.0
    assert ft._last_agg[key]["queue_sum"] == 1.0


def test_cr_ring_prunes_to_max_window():
    clock = Clock()
    ft = FleetTelemetry(Store(), max_window_s=30.0, time_fn=clock)
    key = ("Workspace", "default", "solo")
    for _ in range(10):
        ft.ingest(key, "http://r0:5000", {"waiting": 1.0}, replica="r0")
        ft.fold()
        clock.tick(10.0)
    cr = ft._crs[key]
    # only samples inside the 30 s horizon survive (boundary inclusive,
    # same as WindowSeries)
    assert len(cr.samples) == 4
    assert cr.samples[0][0] == clock() - 40.0   # pruned at the last fold
    assert cr.window_stats(30.0)["queue_sum"]["last"] == 1.0
    assert cr.window_stats(5.0) == {}      # nothing that fresh


# ---------------------------------------------------------------------------
# conditions + events
# ---------------------------------------------------------------------------

def _drive_fold(ft, clock, key, values, rounds, dt=4.0):
    for _ in range(rounds):
        clock.tick(dt)
        ft.ingest(key, "http://r0:5000", values,
                  rates={"requests_rate": values.get("_rps", 1.0)},
                  replica="r0")
        ft.fold()
        ft.apply_signals()


def test_scaling_signal_condition_and_event_dedupe():
    clock = Clock()
    store = Store()
    store.create(InferenceSet(ObjectMeta(name="fleet"),
                              InferenceSetSpec(replicas=1)))
    ft = FleetTelemetry(store, policy=_policy(), time_fn=clock)
    key = ("InferenceSet", "default", "fleet")

    hot = {"occupancy": 0.95, "waiting": 9.0, "kv_usage": 0.2}
    _drive_fold(ft, clock, key, hot, rounds=5)
    live = store.get("InferenceSet", "default", "fleet")
    cond = get_condition(live.status.conditions, COND_SCALING_SIGNAL)
    assert cond is not None and cond.status == "True"
    assert cond.reason == "FleetPressure"
    assert live.status.scaling_signal == SIGNAL_PRESSURE
    assert live.status.recommended_replicas == 2
    rv = live.metadata.resource_version

    # steady pressure: no further writes, no resourceVersion churn
    _drive_fold(ft, clock, key, hot, rounds=3)
    assert store.get("InferenceSet", "default", "fleet") \
        .metadata.resource_version == rv
    detected = store.events.events(reason=EVENT_PRESSURE_DETECTED)
    assert len(detected) == 1 and detected[0].count == 1

    # recovery: condition flips once, resolved event once — no flap
    calm = {"occupancy": 0.05, "waiting": 0.0, "kv_usage": 0.1}
    _drive_fold(ft, clock, key, calm, rounds=6)
    live = store.get("InferenceSet", "default", "fleet")
    cond = get_condition(live.status.conditions, COND_SCALING_SIGNAL)
    assert cond.status == "False" and cond.reason == "FleetNominal"
    assert live.status.scaling_signal == SIGNAL_NOMINAL
    assert live.status.recommended_replicas == 1
    assert len(store.events.events(reason=EVENT_PRESSURE_RESOLVED)) == 1
    assert len(store.events.events(reason=EVENT_PRESSURE_DETECTED)) == 1
    assert ft._crs[key].transitions == 2


def test_no_telemetry_reports_unknown_condition():
    clock = Clock()
    store = Store()
    store.create(InferenceSet(ObjectMeta(name="fleet"),
                              InferenceSetSpec(replicas=1)))
    ft = FleetTelemetry(store, time_fn=clock)
    key = ("InferenceSet", "default", "fleet")
    ft.ingest(key, "http://r0:5000", {"occupancy": 0.2}, replica="r0")
    clock.tick(ft.freshness_s + 1.0)   # the only sample goes stale
    ft.fold()
    ft.apply_signals()
    cond = get_condition(
        store.get("InferenceSet", "default", "fleet").status.conditions,
        COND_SCALING_SIGNAL)
    assert cond.status == "Unknown" and cond.reason == "NoTelemetry"


# ---------------------------------------------------------------------------
# concurrent scraping: a hung target degrades only itself
# ---------------------------------------------------------------------------

class _FakeEngine(BaseHTTPRequestHandler):
    payload = ENGINE_PAYLOAD

    def log_message(self, *a):
        pass

    def do_GET(self):
        if self.path == "/metrics":
            body = self.payload.encode()
        elif self.path == "/debug/slo":
            body = json.dumps({"burn_max": 0.5}).encode()
        else:
            self.send_response(404)
            self.end_headers()
            return
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)


def test_scraper_hung_target_degrades_only_its_own_freshness():
    srv = ThreadingHTTPServer(("127.0.0.1", 0), _FakeEngine)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    hung = socket.socket()
    hung.bind(("127.0.0.1", 0))
    hung.listen(1)            # accepts the connect, never answers
    store = Store()
    store.create(InferenceSet(ObjectMeta(name="fleet"),
                              InferenceSetSpec(replicas=2)))
    for i, port in enumerate([srv.server_address[1],
                              hung.getsockname()[1]]):
        store.create(Workspace(ObjectMeta(
            name=f"fleet-{i}",
            labels={LABEL_CREATED_BY_INFERENCESET: "fleet"},
            annotations={ANNOTATION_SCRAPE_URL:
                         f"http://127.0.0.1:{port}"})))
    try:
        ft = FleetTelemetry(store, interval_s=0.2, timeout_s=0.5)
        ft.refresh_targets()
        t0 = time.monotonic()
        ft.scrape_once(force=True, wait=True)
        assert time.monotonic() - t0 < 5.0
        key = ("InferenceSet", "default", "fleet")
        snap = ft.snapshot()["fleet"]["InferenceSet/default/fleet"]
        assert snap["replicas_reporting"] == 1
        healthy = snap["replicas"]["fleet-0"]
        assert healthy["fresh"] and healthy["consecutive_failures"] == 0
        assert healthy["values"]["waiting"] == 3.0
        assert healthy["values"]["burn_max"] == 0.5   # /debug/slo fold-in
        sick = snap["replicas"]["fleet-1"]
        assert not sick["fresh"]
        assert sick["consecutive_failures"] >= 1 and sick["last_error"]
        # second forced round still scrapes the healthy one even if the
        # hung one were somehow still in flight
        ft.scrape_once(force=True, wait=True)
        assert ft._last_agg[key]["replicas_reporting"] == 1.0
    finally:
        srv.shutdown()
        hung.close()


def test_manager_debug_fleet_route():
    from kaito_tpu.controllers.manager import Manager
    from kaito_tpu.controllers.metrics import make_manager_server

    mgr = Manager()
    mgr.store.create(InferenceSet(ObjectMeta(name="fleet"),
                                  InferenceSetSpec(replicas=1)))
    mgr.resync()
    srv = make_manager_server(mgr.metrics, host="127.0.0.1", port=0,
                              fleet=mgr.fleet)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    try:
        with urllib.request.urlopen(base + "/debug/fleet", timeout=5) as r:
            snap = json.loads(r.read())
        assert "policy" in snap and "fleet" in snap
        with urllib.request.urlopen(base + "/metrics", timeout=5) as r:
            text = r.read().decode()
        assert "kaito:fleet_signal_state" in text
        parse_exposition(text)     # manager registry stays well-formed
        # without a fleet plane the route 404s instead of crashing
        bare = make_manager_server(mgr.metrics, host="127.0.0.1", port=0)
        threading.Thread(target=bare.serve_forever, daemon=True).start()
        try:
            urllib.request.urlopen(
                f"http://127.0.0.1:{bare.server_address[1]}/debug/fleet",
                timeout=5)
            assert False, "expected 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404
        finally:
            bare.shutdown()
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# acceptance e2e: real engines + hung third target behind one CR
# ---------------------------------------------------------------------------

def _post(url, path, body):
    req = urllib.request.Request(
        url + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=240) as r:
        return json.loads(r.read())


def _direct(url, key):
    with urllib.request.urlopen(url + "/metrics", timeout=10) as r:
        return parse_replica_metrics(r.read().decode()).get(key, 0.0)


# slow: 32 s, two real replicas and scrape deadlines on the wall clock
@pytest.mark.slow
def test_fleet_e2e_two_real_replicas_plus_hung_third():
    from tests.helpers.dp_cluster import boot_backends

    with boot_backends(2) as urls:
        hung = socket.socket()
        hung.bind(("127.0.0.1", 0))
        hung.listen(1)
        store = Store()
        store.create(InferenceSet(ObjectMeta(name="demo"),
                                  InferenceSetSpec(replicas=3)))
        targets = urls + [f"http://127.0.0.1:{hung.getsockname()[1]}"]
        for i, u in enumerate(targets):
            store.create(Workspace(ObjectMeta(
                name=f"demo-{i}",
                labels={LABEL_CREATED_BY_INFERENCESET: "demo"},
                annotations={ANNOTATION_SCRAPE_URL: u})))
        # queue depth is the one driver (2-slot CPU engines cannot hold
        # occupancy across a whole fleet); burn/kv/occupancy watermarks
        # are parked out of reach
        policy = FleetPolicy(
            occupancy_hi=10.0, occupancy_lo=10.0, queue_hi=1.0,
            queue_lo=0.4, kv_hi=10.0, kv_lo=10.0, burn_hi=1e9,
            burn_lo=1e9, shed_hi=1e9, shed_lo=1e9, sat_kv=10.0,
            sat_shed=1e9, sat_queue=1e9, sat_occupancy=10.0,
            sustain_s=2.0, idle_sustain_s=1e6, min_samples=3,
            min_window_coverage=0.6, freshness_s=4.0)
        ft = FleetTelemetry(store, policy=policy, interval_s=0.5,
                            timeout_s=2.0)
        ft.refresh_targets()
        key = ("InferenceSet", "default", "demo")

        def states():
            return [e.count for e in
                    store.events.events(reason=EVENT_PRESSURE_DETECTED)]

        stop_load = threading.Event()

        def pound(target_url):
            # keep ~8 requests in flight against ONE replica so its
            # waiting gauge stays well above queue_hi * replicas
            def one():
                while not stop_load.is_set():
                    try:
                        _post(target_url, "/v1/completions",
                              {"prompt": "fleet pressure probe " * 4,
                               "max_tokens": 24, "temperature": 0.0})
                    except Exception:
                        # 429 shed under full queue is part of the
                        # pressure being measured — keep pounding
                        time.sleep(0.2)
            ts = [threading.Thread(target=one, daemon=True)
                  for _ in range(8)]
            for t in ts:
                t.start()
            return ts

        def drive(seconds):
            deadline = time.monotonic() + seconds
            while time.monotonic() < deadline:
                ft.scrape_once(force=True, wait=False)
                ft.apply_signals()
                time.sleep(0.35)

        def wait_state(want, seconds):
            deadline = time.monotonic() + seconds
            while time.monotonic() < deadline:
                ft.scrape_once(force=True, wait=False)
                ft.apply_signals()
                if ft._crs[key].state == want:
                    return True
                time.sleep(0.35)
            return False

        # settle at nominal with both real replicas reporting
        drive(2.5)
        assert ft._crs[key].state == SIGNAL_NOMINAL
        snap = ft.snapshot()["fleet"]["InferenceSet/default/demo"]
        assert snap["replicas_reporting"] == 2
        assert snap["replicas_desired"] == 3

        # load ONE replica -> sustained queue -> pressure
        loaders = pound(urls[0])
        try:
            assert wait_state(SIGNAL_PRESSURE, 120.0), \
                ft.snapshot()["fleet"]["InferenceSet/default/demo"]
        finally:
            stop_load.set()
        for t in loaders:
            t.join(timeout=240)
        live = store.get("InferenceSet", "default", "demo")
        cond = get_condition(live.status.conditions, COND_SCALING_SIGNAL)
        assert cond.status == "True" and cond.reason == "FleetPressure"
        assert live.status.recommended_replicas == 4      # 3 + 1

        # drain -> sustained calm -> back to nominal, exactly one
        # detect/resolve pair (hysteresis: no flap)
        assert wait_state(SIGNAL_NOMINAL, 120.0), \
            ft.snapshot()["fleet"]["InferenceSet/default/demo"]
        assert ft._crs[key].transitions == 2
        detected = store.events.events(reason=EVENT_PRESSURE_DETECTED)
        resolved = store.events.events(reason=EVENT_PRESSURE_RESOLVED)
        assert len(detected) == 1 and detected[0].count == 1
        assert len(resolved) == 1 and resolved[0].count == 1
        live = store.get("InferenceSet", "default", "demo")
        cond = get_condition(live.status.conditions, COND_SCALING_SIGNAL)
        assert cond.status == "False" and cond.reason == "FleetNominal"

        # after the drain, one clean synchronous round: the fleet sums
        # must match direct per-replica scrapes exactly
        ft.scrape_once(force=True, wait=True)
        registry = Registry()
        ft.register_metrics(registry)
        samples = parse_exposition(registry.expose())
        got = {}
        for name, labels, value in samples:
            lb = parse_labels(labels)
            if lb.get("name") == "demo":
                got[(name, lb.get("agg", ""))] = value
        want_total = sum(_direct(u, "requests_total") for u in urls)
        assert want_total > 0
        assert got[("kaito:fleet_requests_total", "")] == want_total
        assert got[("kaito:fleet_replicas_reporting", "")] == 2.0
        direct_waiting = sum(_direct(u, "waiting") for u in urls)
        assert got[("kaito:fleet_queue_depth", "sum")] == direct_waiting

        # the hung third target degraded only its own freshness
        snap = ft.snapshot()["fleet"]["InferenceSet/default/demo"]
        assert snap["replicas_reporting"] == 2
        sick = snap["replicas"]["demo-2"]
        assert not sick["fresh"] and sick["consecutive_failures"] >= 1
        for r in ("demo-0", "demo-1"):
            assert snap["replicas"][r]["fresh"]
        hung.close()
