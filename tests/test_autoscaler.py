"""Closed-loop autoscaler (kaito_tpu/controllers/autoscaler.py).

Fast tier: policy defaulting/validation, stabilization + cooldown +
flap suppression on a deterministic clock, warm NodePool render-ahead
and GC, drain-before-delete ordering through the EPP manifests, the
scale-to-zero park + received-rate wake, the node-count guard planning
the template (multi-host presets), the unbounded child name probe, and
the spec.autoscale -> SignalPolicy hint wiring.

Slow tier: the acceptance e2e — real engine-server processes behind a
real EndpointPicker front, fleet telemetry scraping over real sockets,
and the autoscaler driving idle -> pressure -> scale-up (warm pool
BEFORE the Workspace) -> scale-down (drain, zero dropped in-flight) ->
zero -> wake.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from kaito_tpu.api import (
    InferenceSet,
    InferenceSetSpec,
    InferenceSpec,
    ObjectMeta,
    ResourceSpec,
    Workspace,
)
from kaito_tpu.api.inferenceset import AutoscalePolicy, WorkspaceTemplate
from kaito_tpu.api.meta import get_condition
from kaito_tpu.api.workspace import (
    ANNOTATION_DRAINING,
    LABEL_CREATED_BY_INFERENCESET,
)
from kaito_tpu.controllers.autoscaler import (
    AutoscalerController,
    COND_AUTOSCALER_ACTIVE,
    LABEL_WARM_FOR,
)
from kaito_tpu.controllers.inferenceset import InferenceSetReconciler
from kaito_tpu.controllers.runtime import Store, update_with_retry
from kaito_tpu.engine.metrics import Registry
from kaito_tpu.manifests.epp import EPP_PORT, build_epp_command
from kaito_tpu.provision.karpenter import KarpenterTPUProvisioner, LABEL_OWNER
from kaito_tpu.runtime.fleet import FleetPolicy, FleetTelemetry


class Clock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def tick(self, dt):
        self.t += dt


def _policy(**kw):
    base = dict(sustain_s=10.0, idle_sustain_s=10.0, min_samples=2,
                min_window_coverage=0.8)
    base.update(kw)
    return FleetPolicy(**base)


HIGH = {"occupancy": 0.95, "waiting": 8.0, "kv_usage": 0.5,
        "active_slots": 2.0}
LOW = {"occupancy": 0.10, "waiting": 0.0, "kv_usage": 0.10,
       "active_slots": 1.0}
QUIET = {"occupancy": 0.0, "waiting": 0.0, "kv_usage": 0.0,
         "active_slots": 0.0}


def _template(instance="ct5lp-hightpu-1t", preset="phi-4-mini-instruct"):
    return WorkspaceTemplate(resource=ResourceSpec(instance_type=instance),
                             inference=InferenceSpec(preset=preset))


def _iset(name="fleet", replicas=1, autoscale=None, **spec_kw):
    return InferenceSet(
        ObjectMeta(name=name),
        InferenceSetSpec(replicas=replicas, template=_template(),
                         autoscale=autoscale or AutoscalePolicy(),
                         **spec_kw))


def _rig(iset, clock=None, provision=False, fleet_policy=None):
    """Store + fleet + autoscaler on one injected clock."""
    clock = clock or Clock()
    store = Store()
    store.create(iset)
    ft = FleetTelemetry(store, policy=fleet_policy or _policy(),
                        time_fn=clock)
    prov = KarpenterTPUProvisioner(store) if provision else None
    asc = AutoscalerController(store, ft, provisioner=prov, time_fn=clock)
    return store, ft, asc, clock


def _drive(ft, clock, key, values, rounds, dt=4.0, rps=1.0, epp_rps=None):
    """Ingest -> fold -> apply_signals, like a manager resync."""
    for _ in range(rounds):
        clock.tick(dt)
        ft.ingest(key, "http://r0:5000", values,
                  rates={"requests_rate": rps}, replica="r0")
        if epp_rps is not None:
            ft.ingest(key, "http://epp:5000", {},
                      rates={"received_rate": epp_rps}, role="epp",
                      replica="epp")
        ft.fold()
        ft.apply_signals()


KEY = ("InferenceSet", "default", "fleet")


def _live(store):
    return store.get("InferenceSet", "default", "fleet")


def _reasons(store, reason):
    return store.events.events(kind="InferenceSet", name="fleet",
                               reason=reason)


# ---------------------------------------------------------------------------
# policy surface
# ---------------------------------------------------------------------------

def test_autoscale_policy_defaulting_and_validation():
    p = AutoscalePolicy(enabled=True, min_replicas=-2, warm_pool=-1,
                        idle_grace_s=-5.0)
    p.default()
    assert p.min_replicas == 0 and p.warm_pool == 0 and p.idle_grace_s == 0.0
    # min 0 without scale-to-zero is a hole, not a valid floor
    assert AutoscalePolicy(enabled=True, min_replicas=0).validate()
    assert not AutoscalePolicy(enabled=True, min_replicas=0,
                               scale_to_zero=True).validate()
    assert AutoscalePolicy(enabled=True, min_replicas=3,
                           max_replicas=2).validate()
    # disabled specs validate vacuously (the block is inert)
    assert not AutoscalePolicy(min_replicas=9, max_replicas=2).validate()
    # floor: scale-to-zero parks at 0, else minReplicas >= 1
    assert AutoscalePolicy(scale_to_zero=True).floor() == 0
    assert AutoscalePolicy(min_replicas=3).floor() == 3
    assert AutoscalePolicy().floor() == 1


def test_iset_defaulting_validates_autoscale_block():
    iset = _iset(autoscale=AutoscalePolicy(enabled=True, min_replicas=0))
    iset.default()
    assert any("scaleToZero" in e for e in iset.validate())


# ---------------------------------------------------------------------------
# scale-up: stabilization + cooldown
# ---------------------------------------------------------------------------

def test_scale_up_waits_for_stabilization_then_respects_cooldown():
    pol = AutoscalePolicy(enabled=True, max_replicas=4,
                          scale_up_stabilization_s=20.0,
                          scale_up_cooldown_s=120.0, warm_pool=0)
    store, ft, asc, clock = _rig(_iset(autoscale=pol))

    _drive(ft, clock, KEY, HIGH, rounds=4)         # -> pressure
    st, _, dec = ft.signal(KEY)
    assert st == "pressure" and dec.recommended_replicas >= 2
    asc.tick()                                     # dwell < stabilization
    live = _live(store)
    assert live.spec.replicas == 1
    cond = get_condition(live.status.conditions, COND_AUTOSCALER_ACTIVE)
    assert cond.reason == "Stabilizing"

    _drive(ft, clock, KEY, HIGH, rounds=5)         # dwell past 20 s
    asc.tick()
    live = _live(store)
    assert live.spec.replicas == 2
    assert _reasons(store, "ScalingUp")
    assert get_condition(live.status.conditions,
                         COND_AUTOSCALER_ACTIVE).reason == "ScalingUp"
    assert asc.m_scale_events.value(name="fleet", direction="up") == 1.0

    _drive(ft, clock, KEY, HIGH, rounds=3)         # still hot, too soon
    asc.tick()
    live = _live(store)
    assert live.spec.replicas == 2
    assert get_condition(live.status.conditions,
                         COND_AUTOSCALER_ACTIVE).reason == "CoolingDown"

    _drive(ft, clock, KEY, HIGH, rounds=30)        # past the cooldown
    asc.tick()
    assert _live(store).spec.replicas == 3


def test_scale_up_capped_by_max_replicas():
    pol = AutoscalePolicy(enabled=True, max_replicas=1,
                          scale_up_stabilization_s=0.0,
                          scale_up_cooldown_s=0.0, warm_pool=0)
    store, ft, asc, clock = _rig(_iset(autoscale=pol))
    _drive(ft, clock, KEY, HIGH, rounds=6)
    asc.tick()
    live = _live(store)
    assert live.spec.replicas == 1
    assert get_condition(live.status.conditions,
                         COND_AUTOSCALER_ACTIVE).reason == "AtCapacity"


def test_min_replicas_enforced_and_disabled_writes_condition_once():
    pol = AutoscalePolicy(enabled=True, min_replicas=2)
    store, ft, asc, clock = _rig(_iset(replicas=0, autoscale=pol))
    asc.tick()
    assert _live(store).spec.replicas == 2

    def off(o):
        o.spec.autoscale.enabled = False
    update_with_retry(store, "InferenceSet", "default", "fleet", off)
    asc.tick()
    live = _live(store)
    cond = get_condition(live.status.conditions, COND_AUTOSCALER_ACTIVE)
    assert cond.status == "False" and cond.reason == "Disabled"
    rv = live.metadata.resource_version
    asc.tick()                                     # dedupe: no rewrite
    assert _live(store).metadata.resource_version == rv


# ---------------------------------------------------------------------------
# scale-down: drain grace, flap suppression, scale-to-zero + wake
# ---------------------------------------------------------------------------

def _idle_policy(**kw):
    base = dict(enabled=True, min_replicas=1, idle_grace_s=12.0,
                scale_down_stabilization_s=0.0, scale_down_cooldown_s=0.0,
                drain_grace_s=15.0, warm_pool=0)
    base.update(kw)
    return AutoscalePolicy(**base)


def _with_children(store, n, ready=()):
    from kaito_tpu.api.meta import Condition, set_condition
    from kaito_tpu.api.workspace import COND_INFERENCE_READY

    for i in range(n):
        ws = Workspace(ObjectMeta(
            name=f"fleet-{i}",
            labels={LABEL_CREATED_BY_INFERENCESET: "fleet"}))
        if i in ready:
            set_condition(ws.status.conditions, Condition(
                type=COND_INFERENCE_READY, status="True", reason="Ready",
                message=""))
        store.create(ws)


def test_scale_down_drains_then_commits_after_grace():
    store, ft, asc, clock = _rig(_iset(replicas=2,
                                       autoscale=_idle_policy()))
    _with_children(store, 2, ready=(0, 1))
    _drive(ft, clock, KEY, QUIET, rounds=4, rps=0.0)   # -> idle
    asc.tick()                                     # dwell < idle grace
    assert _live(store).spec.replicas == 2
    _drive(ft, clock, KEY, QUIET, rounds=3, rps=0.0)
    asc.tick()                                     # begins the drain
    live = _live(store)
    assert live.spec.replicas == 2                 # NOT lowered yet
    victim = store.get("Workspace", "default", "fleet-1")
    assert victim.metadata.annotations.get(ANNOTATION_DRAINING) == "true"
    assert not store.get("Workspace", "default", "fleet-0") \
        .metadata.annotations.get(ANNOTATION_DRAINING)
    assert _reasons(store, "ScalingDown")
    assert get_condition(live.status.conditions,
                         COND_AUTOSCALER_ACTIVE).reason == "Draining"

    _drive(ft, clock, KEY, QUIET, rounds=1, rps=0.0)   # 4 s: grace not up
    asc.tick()
    assert _live(store).spec.replicas == 2
    _drive(ft, clock, KEY, QUIET, rounds=4, rps=0.0)   # past 15 s grace
    asc.tick()
    assert _live(store).spec.replicas == 1
    assert asc.m_scale_events.value(name="fleet", direction="down") == 1.0


def test_pressure_flap_cancels_pending_drain():
    store, ft, asc, clock = _rig(_iset(replicas=2,
                                       autoscale=_idle_policy()))
    _with_children(store, 2, ready=(0, 1))
    _drive(ft, clock, KEY, QUIET, rounds=7, rps=0.0)
    asc.tick()
    assert store.get("Workspace", "default", "fleet-1") \
        .metadata.annotations.get(ANNOTATION_DRAINING)
    # load returns before the grace elapses: drain is cancelled, the
    # victim is unmarked, replicas never moved
    _drive(ft, clock, KEY, HIGH, rounds=1)
    asc.tick()
    live = _live(store)
    assert live.spec.replicas == 2
    assert not store.get("Workspace", "default", "fleet-1") \
        .metadata.annotations.get(ANNOTATION_DRAINING)
    # the cancelled drain never commits, even once idle returns briefly
    assert asc.m_scale_events.value(name="fleet", direction="down") == 0.0


def test_scale_to_zero_parks_and_received_rate_wakes():
    pol = _idle_policy(min_replicas=0, scale_to_zero=True,
                       idle_grace_s=10.0, drain_grace_s=5.0)
    store, ft, asc, clock = _rig(_iset(replicas=1, autoscale=pol))
    _with_children(store, 1, ready=(0,))
    _drive(ft, clock, KEY, QUIET, rounds=7, rps=0.0, epp_rps=0.0)
    asc.tick()                                     # drain begins
    _drive(ft, clock, KEY, QUIET, rounds=2, rps=0.0, epp_rps=0.0)
    asc.tick()                                     # commits to zero
    live = _live(store)
    assert live.spec.replicas == 0
    assert _reasons(store, "ScaleToZero")
    assert get_condition(live.status.conditions,
                         COND_AUTOSCALER_ACTIVE).reason == "ScaledToZero"

    # parked: quiet EPP keeps it at zero
    _drive(ft, clock, KEY, QUIET, rounds=2, rps=0.0, epp_rps=0.0)
    asc.tick()
    assert _live(store).spec.replicas == 0
    # first queued request at the EPP wakes it, no stabilization wait
    clock.tick(4.0)
    ft.ingest(KEY, "http://epp:5000", {}, rates={"received_rate": 2.0},
              role="epp", replica="epp")
    ft.fold()
    ft.apply_signals()
    asc.tick()
    assert _live(store).spec.replicas == 1
    assert asc.m_scale_events.value(name="fleet", direction="wake") == 1.0
    assert asc.m_scale_events.value(name="fleet", direction="zero") == 1.0


# ---------------------------------------------------------------------------
# warm pools: render-ahead + GC
# ---------------------------------------------------------------------------

def test_warm_pool_rendered_on_pressure_before_workspace_then_gcd():
    pol = AutoscalePolicy(enabled=True, max_replicas=3, warm_pool=1,
                          warm_pool_gc_s=30.0,
                          scale_up_stabilization_s=3600.0)  # never commits
    store, ft, asc, clock = _rig(_iset(autoscale=pol), provision=True)
    _with_children(store, 1, ready=(0,))
    _drive(ft, clock, KEY, HIGH, rounds=4)
    asc.tick()
    # the NEXT replica's NodePool exists while its Workspace does not
    pool = store.get("NodePool", "", "fleet-1-slice-0")
    assert pool.metadata.labels[LABEL_OWNER] == "fleet-1"
    assert pool.metadata.labels[LABEL_WARM_FOR] == "fleet"
    assert store.try_get("Workspace", "default", "fleet-1") is None
    assert _reasons(store, "WarmPoolProvisioned")
    # idempotent: a second pressure tick neither duplicates the pool
    # nor re-fires the event
    _drive(ft, clock, KEY, HIGH, rounds=1)
    asc.tick()
    assert len(_reasons(store, "WarmPoolProvisioned")) == 1

    # pressure resolves without the scale-up committing: sustained
    # nominal reclaims the orphaned warm pool
    _drive(ft, clock, KEY, LOW, rounds=4)
    st, _, _ = ft.signal(KEY)
    assert st == "nominal"
    asc.tick()                                     # dwell < gc window
    assert store.try_get("NodePool", "", "fleet-1-slice-0") is not None
    _drive(ft, clock, KEY, LOW, rounds=8)
    asc.tick()
    assert store.try_get("NodePool", "", "fleet-1-slice-0") is None
    assert _reasons(store, "WarmPoolReclaimed")


def test_warm_pool_adopted_when_replica_materializes():
    pol = AutoscalePolicy(enabled=True, max_replicas=3, warm_pool=1,
                          warm_pool_gc_s=0.0,
                          scale_up_stabilization_s=3600.0)
    store, ft, asc, clock = _rig(_iset(autoscale=pol), provision=True)
    _with_children(store, 1, ready=(0,))
    _drive(ft, clock, KEY, HIGH, rounds=4)
    asc.tick()
    assert store.get("NodePool", "", "fleet-1-slice-0")
    # the replica lands: the pool is owned for real — the warm label is
    # stripped and even an instant GC window must NOT reclaim it
    store.create(Workspace(ObjectMeta(
        name="fleet-1", labels={LABEL_CREATED_BY_INFERENCESET: "fleet"})))
    _drive(ft, clock, KEY, LOW, rounds=4)
    asc.tick()
    pool = store.get("NodePool", "", "fleet-1-slice-0")
    assert LABEL_WARM_FOR not in pool.metadata.labels


# ---------------------------------------------------------------------------
# drain-before-delete ordering through the rendered EPP
# ---------------------------------------------------------------------------

def _epp_command(store):
    dep = store.get("Deployment", "default", "fleet-epp")
    return dep.spec["template"]["spec"]["containers"][0]["command"]


def test_drain_flows_through_epp_manifest_then_victim_deleted_first():
    store, ft, asc, clock = _rig(_iset(replicas=2,
                                       autoscale=_idle_policy()))
    rec = InferenceSetReconciler(store, gateway_api_enabled=True)
    rec.reconcile(_live(store))                    # creates fleet-0/1 + epp
    assert len(store.list("Workspace", "default")) == 2
    assert "--drain-backend" not in _epp_command(store)

    _drive(ft, clock, KEY, QUIET, rounds=7, rps=0.0)
    asc.tick()                                     # marks fleet-1 draining
    rec.reconcile(_live(store))                    # re-renders the EPP
    cmd = _epp_command(store)
    i = cmd.index("--drain-backend")
    assert cmd[i + 1] == f"http://fleet-1:{EPP_PORT}"
    assert len(store.list("Workspace", "default")) == 2  # not deleted yet

    _drive(ft, clock, KEY, QUIET, rounds=5, rps=0.0)
    asc.tick()                                     # commits replicas -> 1
    rec.reconcile(_live(store))
    names = [w.metadata.name for w in store.list("Workspace", "default")]
    assert names == ["fleet-0"]                    # draining victim went


def test_build_epp_command_emits_drain_args():
    cmd = build_epp_command(["http://a:5000", "http://b:5000"],
                            draining=["http://b:5000"])
    assert cmd.count("--backend") == 2
    i = cmd.index("--drain-backend")
    assert cmd[i + 1] == "http://b:5000"


# ---------------------------------------------------------------------------
# routing tier: draining ordering + arrival counter with empty pool
# ---------------------------------------------------------------------------

def test_picker_deprioritizes_draining_and_drops_affinity():
    from kaito_tpu.runtime.epp import EndpointPicker

    picker = EndpointPicker(["http://a:1", "http://b:2"],
                            draining=["http://b:2"])
    a, b = picker.backends
    assert b.draining and not a.draining
    body = json.dumps({"prompt": "x" * 4096}).encode()
    ctx = picker.make_ctx("POST", "/v1/completions", body)
    order = list(picker.candidates("POST", "/v1/completions", ctx))
    # alive-and-not-draining first; the draining backend is the
    # 503-free last resort, after every non-draining live one
    assert order[0] is a and order[-1] is b
    # a draining replica never earns fresh affinity (its KV is about
    # to be torn down); a live one still does
    picker.note_response(b, ctx, 200)
    assert not picker.make_ctx("POST", "/v1/completions",
                               body).matched.get(b.url)
    picker.note_response(a, ctx, 200)
    assert picker.make_ctx("POST", "/v1/completions",
                           body).matched.get(a.url)
    # with the live backend dead (breaker open), the draining one
    # still serves
    a.down_until = time.monotonic() + 60.0
    order = list(picker.candidates("POST", "/v1/completions", ctx))
    assert order[0] is b


def test_empty_pool_counts_arrivals_and_returns_503():
    from tests.helpers.dp_cluster import serve_front
    from kaito_tpu.runtime.epp import EndpointPicker

    registry = Registry()
    picker = EndpointPicker([], registry=registry)
    with serve_front(picker) as url:
        req = urllib.request.Request(
            url + "/v1/completions", method="POST",
            data=json.dumps({"prompt": "hi"}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        assert ei.value.code == 503
        assert ei.value.headers.get("Retry-After")
    # the arrival was COUNTED before backend selection failed — this
    # counter is what wakes a scaled-to-zero set
    assert picker.m_received.value() == 1.0


# ---------------------------------------------------------------------------
# satellites: name probe, node-count guard, hint wiring
# ---------------------------------------------------------------------------

def test_child_probe_fills_sparse_index_holes():
    store = Store()
    store.create(_iset(replicas=4))
    for i in (0, 3):
        store.create(Workspace(ObjectMeta(
            name=f"fleet-{i}",
            labels={LABEL_CREATED_BY_INFERENCESET: "fleet"})))
    rec = InferenceSetReconciler(store)
    rec.reconcile(store.get("InferenceSet", "default", "fleet"))
    names = sorted(w.metadata.name
                   for w in store.list("Workspace", "default"))
    assert names == ["fleet-0", "fleet-1", "fleet-2", "fleet-3"]


def test_node_count_guard_plans_multihost_template_with_zero_children():
    # falcon-40b on 4-chip v5e hosts plans 2 hosts/replica: a 5-node
    # limit admits 2 replicas, not 5 (the old 1-node default)
    store = Store()
    iset = InferenceSet(
        ObjectMeta(name="fleet"),
        InferenceSetSpec(
            replicas=5, node_count_limit=5,
            template=WorkspaceTemplate(
                resource=ResourceSpec(instance_type="ct5lp-hightpu-4t"),
                inference=InferenceSpec(preset="falcon-40b"))))
    store.create(iset)
    rec = InferenceSetReconciler(store)
    rec.reconcile(store.get("InferenceSet", "default", "fleet"))
    assert len(store.list("Workspace", "default")) == 2


def test_autoscaler_cap_combines_max_replicas_and_node_limit():
    pol = AutoscalePolicy(enabled=True, max_replicas=8, warm_pool=0)
    iset = InferenceSet(
        ObjectMeta(name="fleet"),
        InferenceSetSpec(
            replicas=1, node_count_limit=5, autoscale=pol,
            template=WorkspaceTemplate(
                resource=ResourceSpec(instance_type="ct5lp-hightpu-4t"),
                inference=InferenceSpec(preset="falcon-40b"))))
    store = Store()
    store.create(iset)
    ft = FleetTelemetry(store, policy=_policy(), time_fn=Clock())
    asc = AutoscalerController(store, ft)
    assert asc._replica_cap(iset, pol, []) == 2    # min(8, 5 // 2)


def test_spec_autoscale_shapes_recommended_replicas_hint():
    pol = AutoscalePolicy(enabled=True, min_replicas=0, scale_to_zero=True,
                          max_replicas=5)
    clock = Clock()
    store = Store()
    store.create(_iset(replicas=2, autoscale=pol))
    # a scrapable child so refresh_targets keeps the CR series (and
    # picks the hints off spec.autoscale)
    from kaito_tpu.runtime.fleet import ANNOTATION_SCRAPE_URL

    store.create(Workspace(ObjectMeta(
        name="fleet-0", labels={LABEL_CREATED_BY_INFERENCESET: "fleet"},
        annotations={ANNOTATION_SCRAPE_URL: "http://r0:5000"})))
    ft = FleetTelemetry(store, policy=_policy(), time_fn=clock)
    ft.refresh_targets()
    _drive(ft, clock, KEY, QUIET, rounds=7, rps=0.0)
    st, _, dec = ft.signal(KEY)
    assert st == "idle"
    # scale_to_zero=True flowed into the hint: idle recommends 0, not 1
    assert dec.recommended_replicas == 0
    assert _live(store).status.recommended_replicas == 0


def test_manager_gates_autoscaler_off_by_default():
    from kaito_tpu.controllers.manager import Manager

    assert Manager().autoscaler is None
    mgr = Manager(feature_gates="autoscaler=true,"
                                "enableInferenceSetController=true")
    assert mgr.autoscaler is not None
    mgr.resync()                                   # tick runs instrumented
    assert "kaito:autoscaler_desired_replicas" in mgr.metrics.registry.expose()


# ---------------------------------------------------------------------------
# slow tier: the closed loop over real engine processes
# ---------------------------------------------------------------------------

# slow: 39 s, and it waits on wall-clock stabilization windows over real
# engine processes: a loaded box can miss them
@pytest.mark.slow
def test_autoscaler_closed_loop_e2e():
    """idle -> pressure -> scale-up (warm NodePool before the
    Workspace) -> scale-down (drain through the EPP, zero dropped
    in-flight) -> zero -> wake, over REAL engine processes and real
    scrapes."""
    from tests.helpers.dp_cluster import boot_backends, serve_front
    from kaito_tpu.runtime.epp import EndpointPicker
    from kaito_tpu.runtime.fleet import ANNOTATION_SCRAPE_URL
    from kaito_tpu.runtime.routing import Backend
    from kaito_tpu.controllers.objects import Unstructured

    pol = AutoscalePolicy(
        enabled=True, min_replicas=0, scale_to_zero=True, max_replicas=2,
        idle_grace_s=2.0, scale_up_stabilization_s=1.0,
        scale_down_stabilization_s=1.0, scale_up_cooldown_s=0.5,
        scale_down_cooldown_s=0.5, drain_grace_s=2.0, warm_pool=1,
        warm_pool_gc_s=3600.0)
    # the engines' SLO burn gauge rolls over a fixed 300 s fast window
    # (runtime/slo.WINDOW_FAST_S) — on this test's compressed timescale
    # residual burn from the blast phase would pin the signal in
    # pressure long after traffic stops, so the burn watermark is
    # neutralized here (its gating has pure-function coverage in the
    # fleet tier)
    fleet_policy = _policy(sustain_s=1.0, idle_sustain_s=1.5,
                           min_samples=2, min_window_coverage=0.5,
                           burn_hi=1e9, burn_lo=1e9)

    store = Store()
    iset = InferenceSet(
        ObjectMeta(name="fleet"),
        InferenceSetSpec(replicas=1, autoscale=pol,
                         template=_template(preset="tiny-llama-test")))
    store.create(iset)
    ft = FleetTelemetry(store, policy=fleet_policy, interval_s=0.2)
    prov = KarpenterTPUProvisioner(store)
    asc = AutoscalerController(store, ft, provisioner=prov)
    rec = InferenceSetReconciler(store, gateway_api_enabled=True)

    errors_5xx = []
    stop_load = threading.Event()

    def completion(url, timeout=30):
        req = urllib.request.Request(
            url + "/v1/completions", method="POST",
            data=json.dumps({"model": "tiny-llama-test", "prompt": "hi",
                             "max_tokens": 8}).encode(),
            headers={"Content-Type": "application/json"})
        return urllib.request.urlopen(req, timeout=timeout)

    with boot_backends(2) as urls:
        registry = Registry()
        picker = EndpointPicker([urls[0]], registry=registry)
        with serve_front(picker) as front:
            # wire the store to the real data plane: child fleet-0
            # scrapes backend 0; the set's EPP Service scrapes the
            # picker front
            def sync_plane():
                """One control-plane turn: reconcile, map any new
                child onto a real backend url, mirror the rendered
                --drain-backend args into the live picker (the test's
                stand-in for the Deployment restart), scrape, tick."""
                rec.reconcile(store.get("InferenceSet", "default", "fleet"))
                kids = store.list(
                    "Workspace", "default",
                    labels={LABEL_CREATED_BY_INFERENCESET: "fleet"})
                live_urls = set()
                for ws in kids:
                    idx = int(ws.metadata.name.rsplit("-", 1)[1])
                    if idx < len(urls):
                        live_urls.add(urls[idx])
                        if ANNOTATION_SCRAPE_URL \
                                not in ws.metadata.annotations:
                            def ann(o, u=urls[idx]):
                                o.metadata.annotations[
                                    ANNOTATION_SCRAPE_URL] = u
                            update_with_retry(store, "Workspace", "default",
                                              ws.metadata.name, ann)
                for u in live_urls - {b.url for b in picker.backends}:
                    picker.backends.append(Backend(u))
                picker.backends[:] = [b for b in picker.backends
                                      if b.url in live_urls]
                dep = store.try_get("Deployment", "default", "fleet-epp")
                drains = set()
                if dep is not None:
                    cmd = dep.spec["template"]["spec"]["containers"][0][
                        "command"]
                    drains = {cmd[i + 1] for i, a in enumerate(cmd)
                              if a == "--drain-backend"}
                drain_names = {d.split("//")[1].split(":")[0]
                               for d in drains}
                for b in picker.backends:
                    name = f"fleet-{urls.index(b.url)}"
                    b.draining = name in drain_names
                ft.refresh_targets()
                ft.scrape_once(force=True)
                ft.fold()
                ft.apply_signals()
                asc.tick()

            if store.try_get("Service", "default", "fleet-epp") is None:
                store.create(Unstructured(
                    "Service",
                    ObjectMeta(name="fleet-epp", annotations={
                        ANNOTATION_SCRAPE_URL: front}),
                    spec={"ports": [{"port": 80}]}))
            sync_plane()

            def until(pred, timeout, what):
                deadline = time.monotonic() + timeout
                while time.monotonic() < deadline:
                    sync_plane()
                    if pred():
                        return
                    time.sleep(0.3)
                raise AssertionError(f"timed out waiting for {what}")

            # phase 0: light trickle keeps it nominal/idle at 1 replica
            until(lambda: store.get("InferenceSet", "default",
                                    "fleet").status.replicas == 1,
                  30, "initial replica")

            # phase 1: saturate the single replica -> pressure ->
            # warm pool -> scale-up
            def blast():
                while not stop_load.is_set():
                    try:
                        with completion(front) as r:
                            r.read()
                    except urllib.error.HTTPError as e:
                        # 503 is explicit backpressure (engine shed /
                        # router draining), not a dropped request
                        if e.code >= 500 and e.code != 503:
                            errors_5xx.append(e.code)
                    except Exception:
                        pass
            threads = [threading.Thread(target=blast) for _ in range(6)]
            for t in threads:
                t.start()

            saw_warm_before_ws = []

            def scaled_up():
                pool = store.try_get("NodePool", "", "fleet-1-slice-0")
                ws1 = store.try_get("Workspace", "default", "fleet-1")
                if pool is not None and ws1 is None:
                    saw_warm_before_ws.append(True)
                return ws1 is not None
            until(scaled_up, 120, "pressure-driven scale-up")
            # provision-ahead: the N+1 NodePool was rendered while the
            # N+1 Workspace did not exist yet
            assert saw_warm_before_ws
            assert store.get("InferenceSet", "default",
                             "fleet").spec.replicas == 2

            # phase 2: stop the load -> idle -> drain -> scale down to
            # zero; a slow trickle keeps probing the front meanwhile
            stop_load.set()
            for t in threads:
                t.join(timeout=30)

            drain_probes = []

            def at_zero():
                kids = store.list(
                    "Workspace", "default",
                    labels={LABEL_CREATED_BY_INFERENCESET: "fleet"})
                if not drain_probes and any(
                        w.metadata.annotations.get(ANNOTATION_DRAINING)
                        for w in kids):
                    # one in-flight request while the victims drain:
                    # draining backends are alive-but-last-resort, so
                    # the front must answer 200, never 503.  The probe
                    # itself resets the idle signal — flap suppression
                    # cancels THIS drain and the loop re-enters idle
                    # and drains again, which is exactly the contract.
                    with completion(front, timeout=60) as r:
                        assert r.status == 200
                        r.read()
                    drain_probes.append(True)
                return store.get("InferenceSet", "default",
                                 "fleet").spec.replicas == 0 and not kids
            until(at_zero, 180, "idle-driven scale to zero")
            assert drain_probes       # scale-down went THROUGH a drain
            assert not errors_5xx     # zero dropped in-flight requests

            # phase 3: one queued request at the empty front wakes it
            try:
                completion(front, timeout=10)
            except urllib.error.HTTPError as e:
                assert e.code == 503 and e.headers.get("Retry-After")
            until(lambda: store.get("InferenceSet", "default",
                                    "fleet").spec.replicas >= 1,
                  60, "received-rate wake from zero")
            evts = store.events.events(kind="InferenceSet", name="fleet")
            reasons = {e.reason for e in evts}
            assert {"ScalingUp", "ScalingDown", "ScaleToZero",
                    "WarmPoolProvisioned"} <= reasons
