"""Prometheus text exposition correctness for the in-repo metrics
toolkit (kaito_tpu/engine/metrics.py): bucket monotonicity, +Inf ==
_count, percentile edge cases, labelled-series semantics, and label
escaping — parsed with the promoted library parser
(kaito_tpu/utils/promtext.py) and round-tripped against every registry
in the codebase, plus a real sim engine's /metrics payload."""

import math
import threading

import pytest

from kaito_tpu.engine.metrics import Counter, Gauge, Histogram, Registry
from kaito_tpu.utils.promtext import (check_histograms, parse_exposition,
                                      parse_labels)

# kept under the historical names: other suites (tests/test_epp.py)
# import the parser from here
_parse = parse_exposition
_check_histograms = check_histograms


def test_unlabelled_histogram_buckets_cumulative():
    r = Registry()
    h = Histogram("t:lat", "help", r, buckets=(0.1, 0.5, 1.0))
    for v in (0.05, 0.05, 0.3, 0.7, 42.0):
        h.observe(v)
    samples = _parse(r.expose())
    _check_histograms(samples)
    by_line = {(n, lbl): v for n, lbl, v in samples}
    assert by_line[("t:lat_bucket", '{le="0.1"}')] == 2
    assert by_line[("t:lat_bucket", '{le="0.5"}')] == 3
    assert by_line[("t:lat_bucket", '{le="+Inf"}')] == 5
    assert by_line[("t:lat_count", "")] == 5
    assert by_line[("t:lat_sum", "")] == pytest.approx(43.1)


def test_labelled_histogram_per_series():
    r = Registry()
    h = Histogram("t:lat", "help", r, buckets=(0.1, 1.0),
                  labels=("backend",))
    h.observe(0.05, backend="a")
    h.observe(0.5, backend="a")
    h.observe(2.0, backend="b")
    samples = _parse(r.expose())
    series = _check_histograms(samples)
    assert (("t:lat", '{backend="a"}') in series
            and ("t:lat", '{backend="b"}') in series)
    by_line = {(n, lbl): v for n, lbl, v in samples}
    assert by_line[("t:lat_count", '{backend="a"}')] == 2
    # _fmt renders whole floats without the trailing .0 (le="1")
    assert by_line[("t:lat_bucket", '{backend="b",le="1"}')] == 0
    # the aggregate percentile still sees every observation
    assert h.percentile(1.0) >= 1.0


def test_percentile_edges():
    h = Histogram("t:p", "help", None, buckets=(0.1, 1.0))
    assert h.percentile(0.5) == 0.0            # empty -> 0.0
    h.observe(0.05)
    assert 0.0 < h.percentile(0.0) <= 0.1
    assert 0.0 < h.percentile(1.0) <= 0.1
    only_inf = Histogram("t:q", "help", None, buckets=(0.1,))
    only_inf.observe(5.0)                      # lands past every edge
    assert only_inf.percentile(0.99) == math.inf


def test_labelled_counter_empty_emits_no_samples():
    r = Registry()
    Counter("t:labelled", "help", r, labels=("route",))
    Counter("t:plain", "help", r)
    samples = _parse(r.expose())
    names = [n for n, _, _ in samples]
    # no placeholder series for the labelled family; the unlabelled
    # one still advertises its zero
    assert "t:labelled" not in names
    assert ("t:plain", "", 0.0) in samples


def test_label_escaping_round_trip():
    r = Registry()
    c = Counter("t:esc", "help", r, labels=("path",))
    hairy = 'a\\b"c\nd'
    c.inc(path=hairy)
    out = r.expose()
    assert 't:esc{path="a\\\\b\\"c\\nd"} 1' in out
    _parse(out)                                # still one line, parseable
    assert c.value(path=hairy) == 1


def test_counter_and_gauge_basics():
    r = Registry()
    c = Counter("t:c", "help", r, labels=("k",))
    c.inc(k="x")
    c.inc(2, k="x")
    c.inc(k=7)                                 # values stringify
    assert c.value(k="x") == 3
    assert c.value(k="7") == 1
    g = Gauge("t:g", "help", r, fn=lambda: 0.25)
    assert "t:g 0.25" in r.expose()
    assert ('t:c{k="x"} 3' in r.expose())


def test_histogram_thread_safety_smoke():
    """Concurrent observes across labelled series must never lose the
    +Inf == _count invariant (collect snapshots under the lock)."""
    r = Registry()
    h = Histogram("t:mt", "help", r, buckets=(0.5,), labels=("w",))

    def work(tag):
        for i in range(500):
            h.observe((i % 2) * 1.0, w=tag)

    threads = [threading.Thread(target=work, args=(str(t),))
               for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    samples = _parse(r.expose())
    _check_histograms(samples)
    by_line = {(n, lbl): v for n, lbl, v in samples}
    for tag in range(4):
        assert by_line[("t:mt_count", f'{{w="{tag}"}}')] == 500


def test_parse_labels_unescapes():
    assert parse_labels('{path="a\\\\b\\"c\\nd",le="+Inf"}') == \
        {"path": 'a\\b"c\nd', "le": "+Inf"}
    assert parse_labels("") == {}


def test_every_registry_round_trips():
    """One strict parse + histogram-invariant pass over every metrics
    registry in the codebase, so a label-escaping or exposition
    regression in ANY producer fails here (docs/observability.md)."""
    from kaito_tpu.controllers.metrics import ManagerMetrics
    from kaito_tpu.engine.metrics import EngineMetrics
    from kaito_tpu.runtime.epp import EndpointPicker
    from kaito_tpu.runtime.routing import RoutingCore

    url = "http://127.0.0.1:9"
    em = EngineMetrics()
    em.ttft.observe(0.05)
    em.request_success.inc(finished_reason="stop")

    core = RoutingCore([url])
    core.m_forwarded.inc(backend=url)
    core.upstream_latency.observe(0.01, backend=url)

    epp = EndpointPicker([url])
    epp.m_forwarded.inc(backend=url)
    epp.upstream_latency.observe(0.02, backend=url)

    mm = ManagerMetrics()
    mm.observe_reconcile("WorkspaceReconciler", "ok", 0.001)
    mm.workspace_condition.set(1.0, name='ws"hairy\nname', type="Ready")

    for tag, registry in (("engine", em.registry), ("router", core.registry),
                          ("epp", epp.registry), ("manager", mm.registry)):
        samples = parse_exposition(registry.expose())
        assert samples, f"{tag}: empty payload"
        check_histograms(samples)

    # the tuning sidecar renders its exposition by hand — same parser
    from kaito_tpu.tuning.metrics_server import render_metrics

    samples = parse_exposition(render_metrics(
        {"step": 3, "loss": 1.5, "tokens_per_second": 10.0}, done=True))
    names = {n for n, _, _ in samples}
    assert {"kaito:tuning_step", "kaito:tuning_loss",
            "kaito:tuning_completed"} <= names


def test_sim_engine_metrics_payload_parses():
    """The real engine server's /metrics payload passes the parser and
    the histogram invariants end to end."""
    import json
    import urllib.request

    from kaito_tpu.engine.config import EngineConfig
    from kaito_tpu.engine.engine import InferenceEngine
    from kaito_tpu.engine.server import make_server

    cfg = EngineConfig(model="tiny-llama-test", max_model_len=128,
                       page_size=16, max_num_seqs=2, dtype="float32",
                       kv_dtype="float32", prefill_buckets=(32, 64))
    engine = InferenceEngine(cfg)
    engine.start()
    server = make_server(engine, cfg, host="127.0.0.1", port=0)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{server.server_address[1]}"
    try:
        req = urllib.request.Request(
            url + "/v1/completions",
            data=json.dumps({"prompt": "metrics probe", "max_tokens": 3,
                             "temperature": 0.0}).encode(),
            headers={"Content-Type": "application/json"})
        urllib.request.urlopen(req, timeout=120).read()
        body = urllib.request.urlopen(url + "/metrics",
                                      timeout=30).read().decode()
        samples = _parse(body)
        series = _check_histograms(samples)
        fams = {fam for fam, _ in series}
        assert {"kaito:time_to_first_token_seconds",
                "kaito:e2e_request_latency_seconds",
                "kaito:engine_step_seconds",
                "kaito:queue_wait_seconds"} <= fams, fams
        names = {n for n, _, _ in samples}
        assert "kaito:batch_occupancy" in names
    finally:
        server.shutdown()
        engine.stop()
