import json
import threading
import urllib.error
import urllib.request

import pytest

from kaito_tpu.engine.config import EngineConfig
from kaito_tpu.engine.engine import InferenceEngine
from kaito_tpu.engine.server import make_server


@pytest.fixture(scope="module")
def served():
    cfg = EngineConfig(
        model="tiny-llama-test", max_model_len=256, page_size=16,
        max_num_seqs=4, dtype="float32", kv_dtype="float32",
        prefill_buckets=(32, 64, 128), served_model_name="tiny")
    engine = InferenceEngine(cfg)
    engine.start()
    server = make_server(engine, cfg, host="127.0.0.1", port=0)
    port = server.server_address[1]
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{port}", engine
    server.shutdown()
    engine.stop()


def _post(url, path, body, raw=False):
    req = urllib.request.Request(
        url + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    resp = urllib.request.urlopen(req, timeout=120)
    if raw:
        return resp
    return json.loads(resp.read())


def _get(url, path):
    return urllib.request.urlopen(url + path, timeout=30)


def test_health_and_models(served):
    url, _ = served
    health = json.loads(_get(url, "/health").read())
    assert health["status"] == "ok"
    # the engine always publishes its HBM sizing decision ("measured"
    # on an accelerator, "seq-cap" on the CPU, "configured" with
    # max_pages) and says where and how it runs
    assert health["hbm_sizing"]["source"] in ("measured", "seq-cap",
                                              "configured")
    assert health["hbm_sizing"]["pages"] >= 2
    assert health["platform"] == "cpu" and health["device_count"] >= 1
    assert health["attention"] == "jax"
    assert "moe_combine" not in health      # no expert layer
    assert health["prefix_cache"] in ("native", "off")
    assert {"id", "bytes_in_use", "peak_bytes_in_use",
            "bytes_limit"} <= set(health["devices"][0])
    models = json.loads(_get(url, "/v1/models").read())
    assert models["data"][0]["id"] == "tiny"


def test_completions_sync(served):
    url, _ = served
    out = _post(url, "/v1/completions", {
        "prompt": "hello world", "max_tokens": 8, "temperature": 0.0,
    })
    assert out["object"] == "text_completion"
    assert out["usage"]["completion_tokens"] >= 1
    assert out["choices"][0]["finish_reason"] in ("stop", "length")
    assert isinstance(out["choices"][0]["text"], str)


def test_chat_completions_sync(served):
    url, _ = served
    out = _post(url, "/v1/chat/completions", {
        "messages": [{"role": "user", "content": "hi"}],
        "max_tokens": 6, "temperature": 0.0,
    })
    assert out["choices"][0]["message"]["role"] == "assistant"
    assert out["usage"]["total_tokens"] > 0


def test_chat_stream_sse(served):
    url, _ = served
    resp = _post(url, "/v1/chat/completions", {
        "messages": [{"role": "user", "content": "hi"}],
        "max_tokens": 6, "temperature": 0.0, "stream": True,
    }, raw=True)
    assert resp.headers["Content-Type"].startswith("text/event-stream")
    events = []
    for line in resp:
        line = line.strip()
        if line.startswith(b"data: "):
            events.append(line[6:])
    assert events[-1] == b"[DONE]"
    first = json.loads(events[0])
    assert first["choices"][0]["delta"].get("role") == "assistant"
    fin = json.loads(events[-2])
    assert fin["choices"][0]["finish_reason"] in ("stop", "length")


def test_bad_requests(served):
    url, _ = served
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(url, "/v1/completions", {"prompt": ""})
    assert e.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(url, "/v1/chat/completions", {"messages": []})
    assert e.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(url, "/v1/completions", {"prompt": "x" * 100000, "max_tokens": 1})
    assert e.value.code == 400  # prompt exceeds max_model_len
    with pytest.raises(urllib.error.HTTPError) as e:
        _get(url, "/nope")
    assert e.value.code == 404


def test_metrics_exposition(served):
    url, _ = served
    body = _get(url, "/metrics").read().decode()
    assert "kaito:generation_tokens_total" in body
    assert "kaito:num_requests_running" in body
    assert "kaito:kv_cache_usage_perc" in body
    assert "kaito:time_to_first_token_seconds_bucket" in body


def test_rate_limit_429():
    from kaito_tpu.engine.rate_limit import RateLimiter

    lim = RateLimiter(max_queue_len=2)
    assert lim.admit(0) and lim.admit(1)
    assert not lim.admit(2)
    assert RateLimiter(0, disabled=True).admit(100)


def test_stop_string(served):
    url, _ = served
    full = _post(url, "/v1/completions", {
        "prompt": "abc", "max_tokens": 10, "temperature": 0.0})
    text = full["choices"][0]["text"]
    if len(text) >= 3:
        stop = text[1]
        out = _post(url, "/v1/completions", {
            "prompt": "abc", "max_tokens": 10, "temperature": 0.0,
            "stop": [stop]})
        assert stop not in out["choices"][0]["text"]


def test_config_file_merge(tmp_path):
    from kaito_tpu.engine.server import load_config_file

    p = tmp_path / "cfg.yaml"
    p.write_text("max-model-len: 512\nmax_num_seqs: 16\nserved-model-name: foo\n")
    cfg = load_config_file(EngineConfig(), str(p))
    assert cfg.max_model_len == 512
    assert cfg.max_num_seqs == 16
    assert cfg.served_model_name == "foo"


def _config_built(argv, monkeypatch):
    """The EngineConfig ``main`` hands the engine it starts."""
    from kaito_tpu.engine import server
    from kaito_tpu.utils import platform

    class Built(Exception):
        pass

    def engine(cfg):
        raise Built(cfg)

    monkeypatch.setattr(platform, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(server, "start_loading_stub", lambda host, port: None)
    monkeypatch.setattr(server, "InferenceEngine", engine)
    with pytest.raises(Built) as e:
        server.main(["--model", "tiny-llama-test"] + argv)
    return e.value.args[0]


@pytest.mark.parametrize("argv,warns", [([], False),
                                        (["--prefill-pack", "1"], False),
                                        (["--prefill-pack", "0"], True)])
def test_prefill_pack_is_parsed_and_ignored(argv, warns, monkeypatch, caplog):
    """The benchmark's configurations still pass the flag (ROADMAP
    D16): whatever it says, the engine is the one no flag starts, and
    a value that used to ask for packing is told so once."""
    with caplog.at_level("WARNING", logger="kaito_tpu.engine.server"):
        cfg = _config_built(argv, monkeypatch)
    assert cfg == _config_built([], monkeypatch)
    assert not hasattr(cfg, "prefill_pack")
    said = [r for r in caplog.records if "prefill packing was removed"
            in r.getMessage()]
    assert len(said) == int(warns)


def test_a_prefill_pack_annotation_renders_no_flag():
    """A Workspace that still carries ``kaito-tpu.io/prefill-pack`` is
    served as if it did not: the pod's command is that of no
    annotation."""
    from kaito_tpu.api import (InferenceSpec, ObjectMeta, ResourceSpec,
                               Workspace)
    from kaito_tpu.manifests.inference import build_engine_command
    from kaito_tpu.models.registry import get_model_by_name
    from kaito_tpu.parallel.plan import plan_parallelism
    from kaito_tpu.sku.catalog import CHIP_CATALOG

    md = get_model_by_name("llama-3.1-8b-instruct")
    plan = plan_parallelism(md, CHIP_CATALOG["v5e"], workload="serve",
                            max_model_len=2048)
    ws = Workspace(
        ObjectMeta(name="packed",
                   annotations={"kaito-tpu.io/prefill-pack": "4"}),
        resource=ResourceSpec(instance_type="ct5lp-hightpu-4t"),
        inference=InferenceSpec(preset="llama-3.1-8b-instruct"))
    cmd = build_engine_command(ws, md, plan)
    assert "--prefill-pack" not in cmd
    ws.metadata.annotations = {}
    assert cmd == build_engine_command(ws, md, plan)


def test_adapter_discovery(tmp_path):
    from kaito_tpu.engine.server import discover_adapters

    (tmp_path / "style-a").mkdir()
    (tmp_path / "style-a" / "adapter_config.json").write_text("{}")
    (tmp_path / "not-adapter").mkdir()
    found = discover_adapters(str(tmp_path))
    assert list(found) == ["style-a"]


def test_loading_stub_answers_probes_then_hands_over():
    """Before the engine exists, the stub answers /health 503-loading
    and /metrics with a loading gauge (reference: the pre-download
    metrics stub, inference_api.py:265-415); the real server then binds
    the same port."""
    from kaito_tpu.engine.server import start_loading_stub

    stub = start_loading_stub("127.0.0.1", 0)
    port = stub.server_address[1]
    url = f"http://127.0.0.1:{port}"
    try:
        try:
            _get(url, "/health")
            assert False, "expected 503"
        except urllib.error.HTTPError as e:
            assert e.code == 503
            assert json.loads(e.read())["status"] == "loading"
        metrics = _get(url, "/metrics").read().decode()
        assert "kaito:engine_loading 1" in metrics
        try:
            _post(url, "/v1/completions", {"prompt": "x"})
            assert False, "expected 503"
        except urllib.error.HTTPError as e:
            assert e.code == 503
    finally:
        stub.shutdown()
        stub.server_close()

    # the real server binds the same port immediately after
    cfg = EngineConfig(model="tiny-llama-test", max_model_len=128,
                       page_size=16, max_num_seqs=2, dtype="float32",
                       kv_dtype="float32", prefill_buckets=(32,))
    engine = InferenceEngine(cfg)
    engine.start()
    server = make_server(engine, cfg, host="127.0.0.1", port=port)
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    try:
        assert json.loads(_get(url, "/health").read())["status"] == "ok"
    finally:
        server.shutdown()
        engine.stop()


def test_n_choices(served):
    url, _ = served
    out = _post(url, "/v1/completions",
                {"prompt": "count with me", "max_tokens": 5, "n": 3,
                 "temperature": 0.8, "seed": 7})
    assert [c["index"] for c in out["choices"]] == [0, 1, 2]
    assert out["usage"]["completion_tokens"] == 15
    try:
        _post(url, "/v1/completions",
              {"prompt": "x", "max_tokens": 2, "n": 2, "stream": True})
        assert False, "expected 400"
    except urllib.error.HTTPError as e:
        assert e.code == 400


def test_completions_logprobs(served):
    url, _ = served
    out = _post(url, "/v1/completions",
                {"prompt": "hello logprobs", "max_tokens": 6,
                 "temperature": 0, "logprobs": 1})
    lp = out["choices"][0]["logprobs"]
    assert len(lp["token_logprobs"]) == 6
    assert len(lp["tokens"]) == 6 and len(lp["text_offset"]) == 6
    assert all(isinstance(v, float) and v <= 0.0
               for v in lp["token_logprobs"])
    # alternatives are not implemented and must fail loudly
    try:
        _post(url, "/v1/completions",
              {"prompt": "x", "max_tokens": 2, "logprobs": 5})
        assert False, "expected 400"
    except urllib.error.HTTPError as e:
        assert e.code == 400


def test_chat_logprobs(served):
    url, _ = served
    out = _post(url, "/v1/chat/completions",
                {"messages": [{"role": "user", "content": "hi"}],
                 "max_tokens": 4, "temperature": 0, "logprobs": True})
    content = out["choices"][0]["logprobs"]["content"]
    assert len(content) == 4
    assert all(e["logprob"] <= 0.0 and isinstance(e["bytes"], list)
               for e in content)
    try:
        _post(url, "/v1/chat/completions",
              {"messages": [{"role": "user", "content": "x"}],
               "max_tokens": 2, "logprobs": True, "top_logprobs": 3})
        assert False, "expected 400"
    except urllib.error.HTTPError as e:
        assert e.code == 400


def test_echo_prompt_scoring(served):
    url, _ = served
    out = _post(url, "/v1/completions",
                {"prompt": "score this prompt", "max_tokens": 0,
                 "echo": True, "logprobs": 1})
    ch = out["choices"][0]
    assert ch["text"] == "score this prompt"
    lp = ch["logprobs"]
    assert lp["token_logprobs"][0] is None
    assert len(lp["token_logprobs"]) == out["usage"]["prompt_tokens"]
    assert all(v is None or v <= 0.0 for v in lp["token_logprobs"])
    assert "".join(lp["tokens"]) == ch["text"]
    assert out["usage"]["completion_tokens"] == 0
    try:
        _post(url, "/v1/completions",
              {"prompt": "x", "max_tokens": 4, "echo": True, "logprobs": 1})
        assert False, "expected 400"
    except urllib.error.HTTPError as e:
        assert e.code == 400


def test_profiler_endpoints(served, tmp_path, monkeypatch):
    monkeypatch.setenv("KAITO_PROFILE_DIR", str(tmp_path / "prof"))
    url, _ = served
    out = _post(url, "/start_profile", {})
    assert out["status"] == "started"
    try:
        _post(url, "/start_profile", {})
        assert False, "expected 409"
    except urllib.error.HTTPError as e:
        assert e.code == 409
    _post(url, "/v1/completions",
          {"prompt": "profile me", "max_tokens": 3, "temperature": 0})
    out = _post(url, "/stop_profile", {})
    assert out["status"] == "stopped"
    import os as _os

    assert _os.path.isdir(out["dir"])       # trace artifacts written
    try:
        _post(url, "/stop_profile", {})
        assert False, "expected 409"
    except urllib.error.HTTPError as e:
        assert e.code == 409
