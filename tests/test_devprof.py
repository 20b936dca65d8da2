"""Device-time attribution (engine/devprof.py, docs/observability.md).

Fast tests cover the classifier rule table, both trace parsers (a
hand-encoded XPlane protobuf and the chrome-trace JSON fixture format),
the window-summary math (the buckets+idle==100 invariant, cross-track
overlap, phase attribution), the gated-off byte-identical exposition
pin, the fleet fold, and the manifest annotation plumbing.

The slow test runs the real thing: a live CPU engine with devprof on,
one synchronous sampled window around real decode steps, and the
/debug/device vs /metrics agreement the ISSUE acceptance gate names.
"""
import json
import struct  # noqa: F401  (kept: wire-format tests read raw bytes)

import pytest

from kaito_tpu.engine.devprof import (
    BUCKETS,
    PHASES,
    DeviceProfiler,
    Slice,
    classify,
    parse_trace_events,
    parse_xplane,
    phase_of,
    summarize_window,
)
from kaito_tpu.utils.promtext import parse_exposition, parse_labels

# ---------------------------------------------------------------------------
# classifier
# ---------------------------------------------------------------------------


def test_classify_rule_table():
    assert classify("", "fusion.3.dot_general") == "matmul"
    assert classify("jit(f)/transformer/einsum") == "matmul"
    assert classify("", "all-reduce.17") == "collective"
    assert classify("", "reduce-scatter.2") == "collective"
    assert classify("", "collective-permute.1") == "collective"
    assert classify("", "copy.4") == "copy"
    assert classify("", "infeed.0") == "copy"
    assert classify("jit(step)/attention/mul", "fusion.9") == "attention"
    assert classify("", "flash_decode_kernel") == "attention"
    assert classify("", "broadcast.1") == "other"
    # ordering: a fused all-reduce+dot must count as comm, not matmul
    assert classify("", "fused-all-reduce-dot.1") == "collective"
    # copy outranks matmul (DMA slices often mention the producer op)
    assert classify("", "dot.1 copy-start") == "copy"
    # case-insensitive
    assert classify("", "ALL-REDUCE.9") == "collective"


def test_classify_fusion_names_embedding_collectives():
    """Rule-order pin for the comm-overlap ring (docs/multichip.md):
    XLA fuses the ring's ppermute hops with the neighbouring partial
    matmuls/updates, emitting fusion names that embed BOTH a collective
    and a matmul substring — the collective rule must stay first so
    those slices land in comm_pct, never matmul/other."""
    assert classify("", "fusion.all-reduce.3") == "collective"
    assert classify("", "fusion.reduce-scatter.dot.1") == "collective"
    assert classify("", "loop_all-gather_fusion.7") == "collective"
    assert classify("", "fusion.collective-permute.2") == "collective"
    assert classify("", "ppermute_dynamic-update-slice_fusion") \
        == "collective"
    # scoped form: the ring body's named_scope + a fused dot
    assert classify("jit(step)/comm_overlap_ring/fusion",
                    "all-reduce.dot.4") == "collective"
    # a fusion with NO collective substring still classifies by its
    # other needles — the pin is on ordering, not a catch-all
    assert classify("", "fusion.dot.5") == "matmul"
    assert classify("", "fusion.8") == "other"


def test_phase_of():
    assert phase_of("jit(step)/kaito/decode/dot_general") == "decode"
    assert phase_of("a/kaito/prefill/b") == "prefill"
    assert phase_of("kaito/kv_import") == "kv_import"
    assert phase_of("jit(step)/decode/dot") is None      # no kaito/ scope
    assert phase_of("kaito/unknown_phase") is None
    assert phase_of("") is None


# ---------------------------------------------------------------------------
# window summary math
# ---------------------------------------------------------------------------


def test_bucket_sum_invariant_with_nested_and_overlapping_slices():
    # one track: an enclosing fusion, a fully nested event (claims its
    # extent FROM the envelope — child wins, no double count), and a
    # partially overlapping one (the envelope keeps only [4, 8])
    slices = [
        Slice("fusion.1", "", 0.0, 10.0, "t0"),
        Slice("dot.2", "", 2.0, 2.0, "t0"),      # nested -> counts [2, 4]
        Slice("dot.3", "", 8.0, 4.0, "t0"),      # overlap -> [8, 12]
    ]
    s = summarize_window(slices)
    assert s["n_tracks"] == 1
    assert s["wall_us"] == pytest.approx(12.0)
    assert s["busy_us"] == pytest.approx(12.0)
    assert s["bucket_pct"]["other"] == pytest.approx(100.0 * 6 / 12,
                                                    abs=0.01)
    assert s["bucket_pct"]["matmul"] == pytest.approx(100.0 * 6 / 12,
                                                     abs=0.01)
    assert s["bucket_pct"]["idle"] == 0.0
    assert sum(s["bucket_pct"].values()) == pytest.approx(100.0, abs=0.01)


def test_control_flow_envelope_yields_to_scoped_children():
    # the live-dump shape that motivated _leaf_pieces: XLA emits the
    # fused-decode scan as one giant metadata-less `while` event with
    # the scoped body ops nested inside it on the same line.  The body
    # ops must be bucketed/attributed; the envelope keeps only the gaps.
    env = Slice("while.12", "", 0.0, 100.0, "t0")
    kids = [
        Slice("fusion.3", "jit(decode_multi)/kaito/decode/while/body/dot",
              10.0, 30.0, "t0"),
        Slice("fusion.4", "jit(decode_multi)/kaito/decode/while/body/dot",
              50.0, 40.0, "t0"),
    ]
    s = summarize_window([env] + kids)
    assert s["busy_us"] == pytest.approx(100.0)
    assert s["bucket_pct"]["matmul"] == pytest.approx(70.0, abs=0.01)
    assert s["bucket_pct"]["other"] == pytest.approx(30.0, abs=0.01)
    assert s["phase_pct"]["decode"] == pytest.approx(70.0, abs=0.01)
    # attribution is measured against non-idle time only
    assert s["phase_attributed_pct"] == pytest.approx(70.0, abs=0.01)


def test_cross_track_overlap_and_idle():
    slices = [
        Slice("all-reduce.1", "", 0.0, 10.0, "A"),
        Slice("dot.1", "", 0.0, 5.0, "B"),
        Slice("copy.1", "", 2.0, 2.0, "C"),
    ]
    s = summarize_window(slices)
    assert s["n_tracks"] == 3
    # span 10us x 3 tracks; busy 10+5+2
    assert s["wall_us"] == pytest.approx(30.0)
    assert s["bucket_pct"]["idle"] == pytest.approx(100.0 * 13 / 30,
                                                   abs=0.01)
    assert sum(s["bucket_pct"].values()) == pytest.approx(100.0, abs=0.01)
    assert s["comm_pct"] == pytest.approx(100.0 * 10 / 30, abs=0.01)
    # the collective is hidden behind B's dot for 5 of its 10us
    assert s["comm_compute_overlap_pct"] == pytest.approx(50.0)
    # the copy is fully covered by A's collective (busy, another track)
    assert s["copy_overlap_pct"] == pytest.approx(100.0)


def test_single_track_overlap_is_structurally_zero():
    slices = [
        Slice("all-reduce.1", "", 0.0, 4.0, "t0"),
        Slice("dot.1", "", 4.0, 4.0, "t0"),
    ]
    s = summarize_window(slices)
    assert s["comm_compute_overlap_pct"] == 0.0
    assert s["copy_overlap_pct"] == 0.0


def test_phase_attribution():
    slices = [
        Slice("dot.1", "jit(f)/kaito/decode/dot_general", 0.0, 6.0, "t0"),
        Slice("dot.2", "jit(f)/kaito/prefill/dot_general", 6.0, 2.0, "t0"),
        Slice("fusion.1", "", 8.0, 2.0, "t0"),   # unattributed
    ]
    s = summarize_window(slices)
    assert s["phase_pct"]["decode"] == pytest.approx(60.0)
    assert s["phase_pct"]["prefill"] == pytest.approx(20.0)
    assert s["phase_attributed_pct"] == pytest.approx(80.0)


def test_empty_window_summary_is_schema_stable():
    s = summarize_window([])
    assert set(s["bucket_pct"]) == set(BUCKETS)
    assert set(s["phase_pct"]) == set(PHASES)
    assert s["comm_pct"] == 0.0 and s["n_slices"] == 0


def test_roofline_rates():
    slices = [Slice("dot.1", "", 0.0, 10.0, "t0")]
    roof = {"params": 1e6, "bytes_per_tok": 2e6,
            "peak_flops": 1e12, "peak_bytes_s": 1e11}
    s = summarize_window(slices, roofline=roof, window_tokens=1000.0,
                         capture_s=0.5)
    tok_s = 1000.0 / 0.5
    assert s["matmul_pct_of_peak_flops"] == pytest.approx(
        100.0 * tok_s * 2.0 * 1e6 / 1e12, abs=0.01)
    assert s["hbm_pct_of_peak"] == pytest.approx(
        100.0 * tok_s * 2e6 / 1e11, abs=0.01)
    # no roofline config -> rates pinned at 0.0, keys still present
    s2 = summarize_window(slices)
    assert s2["matmul_pct_of_peak_flops"] == 0.0
    assert s2["hbm_pct_of_peak"] == 0.0


# ---------------------------------------------------------------------------
# chrome trace-event parser (CPU fallback + fixture format)
# ---------------------------------------------------------------------------


def _meta(name, pid, tid=None, label=""):
    ev = {"ph": "M", "name": name, "pid": pid, "args": {"name": label}}
    if tid is not None:
        ev["tid"] = tid
    return ev


def test_parse_trace_events_device_planes():
    doc = {"traceEvents": [
        _meta("process_name", 1, label="/device:TPU:0"),
        _meta("thread_name", 1, 1, label="XLA Ops"),
        _meta("process_name", 2, label="python"),
        {"ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": 4,
         "name": "dot.1", "args": {"op_name": "jit(f)/kaito/decode/dot"}},
        {"ph": "X", "pid": 1, "tid": 1, "ts": 4, "dur": 4,
         "name": "all-reduce.1", "args": {}},
        # host process events must not count as device time
        {"ph": "X", "pid": 2, "tid": 7, "ts": 0, "dur": 100,
         "name": "HostWork", "args": {}},
        # zero-duration and infra events are skipped
        {"ph": "X", "pid": 1, "tid": 1, "ts": 8, "dur": 0,
         "name": "marker"},
        {"ph": "X", "pid": 1, "tid": 1, "ts": 8, "dur": 2,
         "name": "ThunkExecutor::run"},
    ]}
    slices = parse_trace_events(doc)
    assert len(slices) == 2
    assert all(s.device for s in slices)
    assert {s.name for s in slices} == {"dot.1", "all-reduce.1"}
    s = summarize_window(slices)
    assert s["bucket_pct"]["matmul"] == pytest.approx(50.0)
    assert s["comm_pct"] == pytest.approx(50.0)
    assert s["phase_pct"]["decode"] == pytest.approx(50.0)


def test_parse_trace_events_host_fallback_and_phase_arg():
    doc = {"traceEvents": [
        _meta("process_name", 1, label="kaito host"),
        _meta("thread_name", 1, 3, label="tf_XLATfrtCpuClient/271"),
        _meta("thread_name", 1, 4, label="MainThread"),
        {"ph": "X", "pid": 1, "tid": 3, "ts": 0, "dur": 6,
         "name": "fusion.1", "args": {"phase": "prefill"}},
        {"ph": "X", "pid": 1, "tid": 3, "ts": 6, "dur": 2,
         "name": "$traced_fn"},                     # python frame
        {"ph": "X", "pid": 1, "tid": 4, "ts": 0, "dur": 50,
         "name": "dispatch"},                       # non-XLA thread
    ]}
    slices = parse_trace_events(doc)
    assert len(slices) == 1
    assert not slices[0].device                     # host stand-in
    assert phase_of(slices[0].op_name) == "prefill"


# ---------------------------------------------------------------------------
# XPlane protobuf wire parser
# ---------------------------------------------------------------------------


def _vint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _vf(fno, val):
    return _vint((fno << 3) | 0) + _vint(val)


def _ld(fno, payload):
    return _vint((fno << 3) | 2) + _vint(len(payload)) + payload


def _hlo_proto():
    # HloProto.hlo_module=1 > computations=3 > instructions=2
    #   > {name=1, metadata=7 > op_name=2}
    instr = (_ld(1, b"dot.1")
             + _ld(7, _ld(2, b"jit(step)/kaito/decode/dot_general")))
    comp = _ld(2, instr)
    module = _ld(3, comp)
    return _ld(1, module)


def _xspace(plane_name=b"/device:TPU:0", line_name=b"XLA Ops"):
    hlo = _hlo_proto()
    # XEventMetadata{id=1,name=2,stats=5>XStat{metadata_id=1,
    #   bytes_value=6}} — the HloProto blob rides a stat of entry 1
    md1 = (_vf(1, 1) + _ld(2, b"dot.1")
           + _ld(5, _vf(1, 99) + _ld(6, hlo)))
    md2 = _vf(1, 2) + _ld(2, b"all-reduce.2")
    md3 = _vf(1, 3) + _ld(2, b"ThunkExecutor::run")
    entries = b"".join(_ld(4, _vf(1, i) + _ld(2, m))
                       for i, m in ((1, md1), (2, md2), (3, md3)))
    # XEvent{metadata_id=1,offset_ps=2,duration_ps=3}; ps -> us = /1e6
    ev1 = _vf(1, 1) + _vf(2, 0) + _vf(3, 1_000_000)
    ev2 = _vf(1, 2) + _vf(2, 1_000_000) + _vf(3, 1_000_000)
    ev3 = _vf(1, 3) + _vf(2, 2_000_000) + _vf(3, 1_000_000)  # infra
    # XLine{id=1,name=2,timestamp_ns=3,events=4}
    line = (_vf(1, 7) + _ld(2, line_name) + _vf(3, 1000)
            + _ld(4, ev1) + _ld(4, ev2) + _ld(4, ev3))
    # XPlane{id=1,name=2,lines=3,event_metadata=4}
    plane = _vf(1, 1) + _ld(2, plane_name) + entries + _ld(3, line)
    return _ld(1, plane)        # XSpace.planes=1


def test_parse_xplane_device_plane_with_hlo_op_names():
    slices = parse_xplane(_xspace())
    assert len(slices) == 2                         # infra event dropped
    by_name = {s.name: s for s in slices}
    dot = by_name["dot.1"]
    # scoped op_name resolved through the embedded HloProto
    assert dot.op_name == "jit(step)/kaito/decode/dot_general"
    assert dot.device and dot.track == "/device:TPU:0/XLA Ops"
    # timestamp_ns=1000 -> 1us base; offsets/durations in ps
    assert dot.t0_us == pytest.approx(1.0)
    assert dot.dur_us == pytest.approx(1.0)
    assert by_name["all-reduce.2"].t0_us == pytest.approx(2.0)
    s = summarize_window(slices)
    assert s["bucket_pct"]["matmul"] == pytest.approx(50.0)
    assert s["comm_pct"] == pytest.approx(50.0)
    assert s["phase_pct"]["decode"] == pytest.approx(50.0)
    assert sum(s["bucket_pct"].values()) == pytest.approx(100.0, abs=0.01)


def test_parse_xplane_host_fallback_requires_xla_line():
    raw = (_xspace(plane_name=b"/host:CPU",
                   line_name=b"tf_XLATfrtCpuClient/271")
           + _xspace(plane_name=b"/host:CPU", line_name=b"MainThread"))
    slices = parse_xplane(raw)
    # only the XLA executor line counts; same 2 non-infra events
    assert len(slices) == 2
    assert all(not s.device for s in slices)
    assert all("XLATfrtCpuClient" in s.track for s in slices)


def test_parse_xplane_garbage_raises_not_crashes_profiler(tmp_path):
    with pytest.raises((ValueError, IndexError)):
        parse_xplane(b"\xff\xff\xff\xff not a protobuf")
    # the sampler counts it instead of dying
    prof = DeviceProfiler(interval_s=60.0)
    dump = tmp_path / "plugins" / "profile" / "1"
    dump.mkdir(parents=True)
    (dump / "host.xplane.pb").write_bytes(b"\xff\xff\xff\xff junk")
    with pytest.raises(Exception):
        prof._parse_dump(str(tmp_path))


def test_parse_dump_prefers_xplane_then_json(tmp_path):
    import gzip
    prof = DeviceProfiler(interval_s=60.0)
    doc = {"traceEvents": [
        _meta("process_name", 1, label="/device:TPU:0"),
        {"ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": 5,
         "name": "dot.9"},
    ]}
    with gzip.open(tmp_path / "host.trace.json.gz", "wt") as f:
        json.dump(doc, f)
    slices = prof._parse_dump(str(tmp_path))
    assert [s.name for s in slices] == ["dot.9"]
    # an xplane.pb sibling wins over the JSON
    (tmp_path / "host.xplane.pb").write_bytes(_xspace())
    slices = prof._parse_dump(str(tmp_path))
    assert {s.name for s in slices} == {"dot.1", "all-reduce.2"}
    with pytest.raises(FileNotFoundError):
        prof._parse_dump(str(tmp_path / "nope"))


# ---------------------------------------------------------------------------
# gauge accessors + gated-off exposition pin
# ---------------------------------------------------------------------------


def test_profiler_gauge_accessors_before_and_after_first_window():
    prof = DeviceProfiler(interval_s=60.0)
    # schema-stable zeros before the first capture
    assert prof.comm_pct() == 0.0 and prof.idle_pct() == 0.0
    assert prof.bucket_pct() == {(b,): 0.0 for b in BUCKETS}
    assert prof.phase_pct() == {(p,): 0.0 for p in PHASES}
    summary = summarize_window([
        Slice("all-reduce.1", "", 0.0, 1.0, "A"),
        Slice("dot.1", "jit(f)/kaito/decode/dot", 0.0, 1.0, "B"),
    ])
    prof.windows.append(summary)
    assert prof.comm_pct() == pytest.approx(50.0)
    assert prof.overlap_pct() == pytest.approx(100.0)
    assert prof.bucket_pct()[("collective",)] == pytest.approx(50.0)
    assert prof.phase_pct()[("decode",)] == pytest.approx(50.0)
    snap = prof.snapshot()
    assert snap["last"] == summary and snap["ring"] == [summary]


def test_devprof_off_exposition_has_no_device_families():
    """The gate the ISSUE pins: with devprof off (the default) the
    /metrics surface gains NO new families — byte-identical to the
    pre-PR exposition."""
    from kaito_tpu.engine.metrics import EngineMetrics
    text = EngineMetrics().registry.expose()
    assert "kaito:device_" not in text
    assert "devprof" not in text


# ---------------------------------------------------------------------------
# fleet fold
# ---------------------------------------------------------------------------

DEVICE_PAYLOAD = """\
# TYPE kaito:num_requests_waiting gauge
kaito:num_requests_waiting 0
# TYPE kaito:device_comm_pct gauge
kaito:device_comm_pct 12.5
# TYPE kaito:device_comm_compute_overlap_pct gauge
kaito:device_comm_compute_overlap_pct 80.0
# TYPE kaito:device_idle_pct gauge
kaito:device_idle_pct 30.0
"""


def test_fleet_parses_and_folds_device_families():
    from kaito_tpu.controllers.runtime import Store
    from kaito_tpu.engine.metrics import Registry
    from kaito_tpu.runtime.fleet import FleetTelemetry, parse_replica_metrics

    vals = parse_replica_metrics(DEVICE_PAYLOAD)
    assert vals["device_comm_pct"] == 12.5
    assert vals["device_overlap_pct"] == 80.0
    assert vals["device_idle_pct"] == 30.0

    ft = FleetTelemetry(Store())
    key = ("Workspace", "default", "ws")
    ft.ingest(key, "http://r0:5000",
              {"device_comm_pct": 10.0, "device_overlap_pct": 80.0,
               "device_idle_pct": 20.0}, replica="r0")
    ft.ingest(key, "http://r1:5000",
              {"device_comm_pct": 30.0, "device_overlap_pct": 40.0,
               "device_idle_pct": 40.0}, replica="r1")
    ft.fold()
    agg = ft._last_agg[key]
    assert agg["device_comm_pct"] == pytest.approx(20.0)
    assert agg["device_overlap_pct"] == pytest.approx(60.0)
    assert agg["device_idle_pct"] == pytest.approx(30.0)

    registry = Registry()
    ft.register_metrics(registry)
    by = {}
    for name, labels, value in parse_exposition(registry.expose()):
        by[(name, tuple(sorted(parse_labels(labels).items())))] = value
    base = (("kind", "Workspace"), ("name", "ws"))
    assert by[("kaito:fleet_device_comm_pct", base)] == pytest.approx(20.0)
    assert by[("kaito:fleet_device_overlap_pct", base)] \
        == pytest.approx(60.0)
    assert by[("kaito:fleet_device_idle_pct", base)] == pytest.approx(30.0)


# ---------------------------------------------------------------------------
# manifest annotation + plan-time validation
# ---------------------------------------------------------------------------


def test_parse_devprof_annotation():
    from kaito_tpu.manifests.inference import parse_devprof_annotation

    assert parse_devprof_annotation("") is None
    assert parse_devprof_annotation("  ") is None
    assert parse_devprof_annotation("off") is None
    assert parse_devprof_annotation("false") is None
    assert parse_devprof_annotation("0") is None
    assert parse_devprof_annotation("60") == 60.0
    assert parse_devprof_annotation("1.5") == 1.5
    for bad in ("abc", "-5", "0.25", "nan", "inf-ish"):
        with pytest.raises(ValueError):
            parse_devprof_annotation(bad)


def test_devprof_annotation_renders_flag_only_when_present():
    from kaito_tpu.api import (InferenceSpec, ObjectMeta, ResourceSpec,
                               Workspace)
    from kaito_tpu.controllers.runtime import Store
    from kaito_tpu.controllers.workspace import plan_workspace
    from kaito_tpu.manifests.inference import build_engine_command

    store = Store()
    ws = Workspace(
        ObjectMeta(name="dp"),
        resource=ResourceSpec(instance_type="ct5lp-hightpu-1t"),
        inference=InferenceSpec(preset="phi-4-mini-instruct"))
    md, plan, _ = plan_workspace(store, ws)
    cmd = build_engine_command(ws, md, plan)
    assert "--devprof-interval-s" not in cmd

    ws.metadata.annotations["kaito-tpu.io/devprof"] = "60"
    cmd = build_engine_command(ws, md, plan)
    i = cmd.index("--devprof-interval-s")
    assert cmd[i + 1] == "60.0"

    # plan-time validation: a bad annotation fails the plan with the
    # PlanFailed-shaped message, before any capacity is asked for
    ws.metadata.annotations["kaito-tpu.io/devprof"] = "bogus"
    with pytest.raises(ValueError, match="kaito-tpu.io/devprof"):
        plan_workspace(store, ws)


# ---------------------------------------------------------------------------
# live CPU smoke (slow): real engine, real jax.profiler window
# ---------------------------------------------------------------------------


# slow: takes a jax.profiler trace on the CPU backend, where stop_trace has
# killed the process in traced rehearsals (PERF.md section 7)
@pytest.mark.slow
def test_live_window_buckets_debug_device_and_metrics_agree():
    import threading
    import urllib.error
    import urllib.request

    from kaito_tpu.engine.config import EngineConfig
    from kaito_tpu.engine.engine import InferenceEngine, SamplingParams
    from kaito_tpu.engine.server import make_server

    cfg = EngineConfig(model="tiny-llama-test", max_model_len=128,
                       page_size=16, max_num_seqs=2, dtype="float32",
                       kv_dtype="float32", prefill_buckets=(32, 64),
                       devprof_interval_s=3600.0,   # sampled manually
                       devprof_window_s=0.5)
    engine = InferenceEngine(cfg)
    engine.start()
    assert engine.devprof is not None
    server = make_server(engine, cfg, host="127.0.0.1", port=0)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{server.server_address[1]}"
    try:
        # warm the compile cache so the sampled window sees steady-state
        # decode (and the named_scope markers are baked into the jit)
        req = engine.submit([1, 2, 3],
                            SamplingParams(max_tokens=8, temperature=0.0,
                                           ignore_eos=True))
        for _ in req.stream():
            pass

        # decode in the background while one window samples around it
        def _burn():
            r = engine.submit([4, 5, 6],
                              SamplingParams(max_tokens=256,
                                             temperature=0.0,
                                             ignore_eos=True))
            for _ in r.stream():
                pass

        t = threading.Thread(target=_burn)
        t.start()
        summary = engine.devprof.sample_window()
        t.join()
        assert summary is not None, "window skipped/failed on CPU CI"
        assert summary["n_slices"] > 0
        # the acceptance invariant: buckets + idle account for the wall
        assert sum(summary["bucket_pct"].values()) \
            == pytest.approx(100.0, abs=1.0)
        # named_scope phase markers survive into the dump: decode was
        # the only work running, so attribution must land on it (the
        # acceptance gate: >90% of non-idle device time carries a
        # kaito/<phase> scope)
        assert summary["phase_attributed_pct"] > 90.0
        assert summary["phase_pct"]["decode"] > 0.0

        # /debug/device and /metrics agree on comm_pct
        with urllib.request.urlopen(url + "/debug/device") as r:
            dbg = json.loads(r.read())
        assert dbg["windows_total"] >= 1
        assert dbg["last"]["bucket_pct"] == summary["bucket_pct"]
        with urllib.request.urlopen(url + "/metrics") as r:
            samples = parse_exposition(r.read().decode())
        vals = {n: v for n, labels, v in samples if not labels}
        assert vals["kaito:device_comm_pct"] \
            == pytest.approx(dbg["last"]["comm_pct"])
        assert vals["kaito:device_windows_total"] >= 1.0
        buckets = {parse_labels(labels)["bucket"]: v
                   for n, labels, v in samples
                   if n == "kaito:device_bucket_pct"}
        assert set(buckets) == set(BUCKETS)
        assert sum(buckets.values()) == pytest.approx(100.0, abs=1.0)

        # the 403 gate: no devprof -> /debug/device refuses
        prof, engine.devprof = engine.devprof, None
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(url + "/debug/device")
            assert ei.value.code == 403
        finally:
            engine.devprof = prof

        # satellite: /start_profile arms and reports its auto-stop
        # deadline; manual capture wins over the sampler (skip counted)
        req = urllib.request.Request(
            url + "/start_profile",
            data=json.dumps({"seconds": 30}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req) as r:
            body = json.loads(r.read())
        try:
            assert body["auto_stop_seconds"] == 30
            assert body["auto_stop_deadline"] > 0
            skipped0 = engine.devprof.windows_skipped
            assert engine.devprof.sample_window() is None
            assert engine.devprof.windows_skipped == skipped0 + 1
        finally:
            urllib.request.urlopen(urllib.request.Request(
                url + "/stop_profile", data=b""))
    finally:
        server.shutdown()
        server.server_close()
        engine.stop()
