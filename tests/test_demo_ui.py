"""Demo chat UI: page serving + OpenAI proxy against a live engine
(the reference's DemoUI chart rebuilt dependency-free,
charts/DemoUI/inference)."""

import json
import threading
import urllib.request

import pytest

from kaito_tpu.engine.config import EngineConfig
from kaito_tpu.engine.engine import InferenceEngine
from kaito_tpu.engine.server import make_server as make_engine_server
from kaito_tpu.ui import make_server as make_ui_server



@pytest.fixture(scope="module")
def stack():
    cfg = EngineConfig(model="tiny-llama-test", max_model_len=256,
                       page_size=16, max_num_seqs=2, dtype="float32",
                       kv_dtype="float32", prefill_buckets=(32, 64))
    eng = InferenceEngine(cfg)
    eng.start()
    backend = make_engine_server(eng, cfg, host="127.0.0.1", port=0)
    bport = backend.server_address[1]
    threading.Thread(target=backend.serve_forever, daemon=True).start()
    ui = make_ui_server(f"http://127.0.0.1:{bport}", host="127.0.0.1",
                       port=0)
    uport = ui.server_address[1]
    threading.Thread(target=ui.serve_forever, daemon=True).start()
    yield f"http://127.0.0.1:{uport}", f"http://127.0.0.1:{bport}"
    ui.shutdown()
    backend.shutdown()
    eng.stop()


def test_ui_serves_chat_page(stack):
    ui_url, backend_url = stack
    with urllib.request.urlopen(ui_url + "/", timeout=30) as r:
        page = r.read().decode()
    assert "chat demo" in page and "v1/chat/completions" in page
    # the engine serves the same page at /ui for single-pod demos
    with urllib.request.urlopen(backend_url + "/ui", timeout=30) as r:
        assert "chat demo" in r.read().decode()


def test_ui_proxies_completions(stack):
    ui_url, _ = stack
    req = urllib.request.Request(
        ui_url + "/v1/chat/completions",
        data=json.dumps({"messages": [{"role": "user", "content": "hi"}],
                         "max_tokens": 4}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        out = json.loads(r.read())
    assert out["usage"]["completion_tokens"] == 4


def test_ui_proxies_streaming(stack):
    ui_url, _ = stack
    req = urllib.request.Request(
        ui_url + "/v1/chat/completions",
        data=json.dumps({"messages": [{"role": "user", "content": "hi"}],
                         "max_tokens": 4, "stream": True}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        body = r.read().decode()
    assert "data: " in body and "[DONE]" in body
