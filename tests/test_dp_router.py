"""Data parallelism over REAL process boundaries: independent engine
replicas (one OS process each) behind the in-repo round-robin router —
the data plane the InferenceSet/EPP tier renders in production
(reference: vLLM --data-parallel-size over Ray,
``pkg/model/interface.go:500-512``)."""

import json
import urllib.request

import pytest

# slow: 41 s for four tests: two engine servers behind the router (23 s of
# set-up), HTTP on the wall clock; left out for the check's budget
pytestmark = pytest.mark.slow


def _post(url: str, body: dict, timeout: float = 240.0) -> dict:
    req = urllib.request.Request(
        url, json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


@pytest.fixture(scope="module")
def dp():
    from tests.helpers.dp_cluster import boot_dp

    try:
        with boot_dp(2) as (router_url, backend_urls, router):
            yield router_url, backend_urls, router
    except RuntimeError as e:
        pytest.fail(str(e))


def test_dp_round_robin_spreads_requests(dp):
    router_url, backend_urls, router = dp
    outs = [_post(router_url + "/v1/completions",
                  {"prompt": f"dp req {i}", "max_tokens": 4,
                   "temperature": 0}) for i in range(4)]
    assert all(o["usage"]["completion_tokens"] == 4 for o in outs)
    # both replicas actually served (round robin, 4 reqs over 2)
    stats = json.loads(urllib.request.urlopen(
        router_url + "/router/stats", timeout=10).read())
    assert all(stats[u]["served"] >= 2 for u in backend_urls), stats


def test_dp_greedy_determinism_across_replicas(dp):
    """Same seed on every replica => identical greedy output whichever
    backend answers."""
    router_url, _, _ = dp
    body = {"prompt": "deterministic across replicas", "max_tokens": 6,
            "temperature": 0}
    a = _post(router_url + "/v1/completions", body)
    b = _post(router_url + "/v1/completions", body)
    assert a["choices"][0]["text"] == b["choices"][0]["text"]


def test_dp_streaming_relays_through_router(dp):
    """SSE tokens stream through the relay (chunked passthrough)."""
    router_url, _, _ = dp
    req = urllib.request.Request(
        router_url + "/v1/completions",
        json.dumps({"prompt": "stream me", "max_tokens": 4,
                    "temperature": 0, "stream": True}).encode(),
        headers={"Content-Type": "application/json"})
    events = []
    saw_done = False
    with urllib.request.urlopen(req, timeout=240) as r:
        for line in r:
            line = line.decode().strip()
            if line == "data: [DONE]":
                saw_done = True
            elif line.startswith("data: "):
                events.append(json.loads(line[len("data: "):]))
    # the model may hit EOS early; the relay contract is that the SSE
    # event stream passes through intact (events + terminal sentinel)
    assert len(events) >= 2
    assert any(e["choices"][0].get("finish_reason") for e in events)
    assert saw_done


def test_dp_survives_replica_death(dp):
    """A dead replica costs a skipped turn, not failed requests."""
    router_url, backend_urls, router = dp
    # mark one backend down the way a connect failure would
    router.backends[0].mark_down()
    outs = [_post(router_url + "/v1/completions",
                  {"prompt": f"failover {i}", "max_tokens": 3,
                   "temperature": 0}) for i in range(2)]
    assert all(o["usage"]["completion_tokens"] == 3 for o in outs)
    router.backends[0].down_until = 0.0   # heal for later tests
