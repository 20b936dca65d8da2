"""Multi-host serving bootstrap: a 2-process jax.distributed CPU
cluster (leader HTTP + headless worker in lockstep) serves one model.

The CPU twin of a multi-host v5e slice: the manifests inject
TPU_WORKER_ID / KAITO_COORDINATOR (kaito_tpu/manifests/inference.py)
and server.main() calls initialize_distributed() — this test exercises
that exact contract end to end (reference analogue: Ray leader/worker
command, pkg/model/interface.go:534-560).
"""

import json
import urllib.request

import pytest


def _post(url: str, body: dict, timeout: float = 240.0) -> dict:
    # generous timeout: under concurrent pytest on a loaded 1-core box
    # the lockstep broadcast can stall for minutes without being wrong
    # (round-2 verdict reproduced a 60 s socket timeout under 4-way
    # parallel runs)
    req = urllib.request.Request(
        url, json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _boot_cluster(extra_args):
    from tests.helpers.mh_cluster import boot_cluster

    try:
        with boot_cluster(extra_args) as base:
            yield base
    except RuntimeError as e:
        pytest.fail(str(e))


@pytest.fixture(scope="module")
def cluster():
    yield from _boot_cluster(["--tensor-parallel-size", "4"])


@pytest.fixture(scope="module")
def cluster_pp():
    """The north-star tier-3 serving shape over REAL process
    boundaries: pipeline across the 2 processes (the DCN tier), TP
    inside each process's 2 local devices (reference:
    interface.go:514-560, multi-node PP tier)."""
    yield from _boot_cluster(["--pipeline-parallel-size", "2",
                              "--tensor-parallel-size", "2"])


@pytest.fixture(scope="module")
def cluster_ep():
    """EP over 2 processes: experts split across the process boundary
    (expert axis spans both hosts' devices), TP inside each process —
    the MoE serving tier the planner's expert carve-out targets."""
    yield from _boot_cluster(["--model", "tiny-moe-real",
                              "--expert-parallel-size", "2",
                              "--tensor-parallel-size", "2"])


# slow: 35 s: a multi-process cluster of its own (25 s to boot) for one test
@pytest.mark.slow
def test_multihost_ep_serves_completions(cluster_ep):
    body = {"model": "tiny-moe-real", "prompt": "experts across processes",
            "max_tokens": 8, "temperature": 0}
    out = _post(cluster_ep + "/v1/completions", body)
    assert out["usage"]["completion_tokens"] == 8
    out2 = _post(cluster_ep + "/v1/completions", body)
    assert out2["choices"][0]["text"] == out["choices"][0]["text"]


def test_multihost_serves_completions(cluster):
    body = {"model": "tiny-llama-test", "prompt": "multi host hello",
            "max_tokens": 8, "temperature": 0}
    out = _post(cluster + "/v1/completions", body)
    assert out["usage"]["completion_tokens"] == 8
    # greedy determinism across the 2-process lockstep
    out2 = _post(cluster + "/v1/completions", body)
    assert out2["choices"][0]["text"] == out["choices"][0]["text"]


def test_multihost_concurrent_requests(cluster):
    import concurrent.futures as cf

    def one(i):
        return _post(cluster + "/v1/completions", {
            "model": "tiny-llama-test", "prompt": f"worker req {i}",
            "max_tokens": 6, "temperature": 0})

    with cf.ThreadPoolExecutor(4) as ex:
        outs = list(ex.map(one, range(4)))
    assert all(o["usage"]["completion_tokens"] == 6 for o in outs)


# slow: 50 s with its twin below: a second multi-process cluster (29 s to boot)
@pytest.mark.slow
def test_multihost_pp_serves_completions(cluster_pp):
    """PP over 2 processes: stages live in different OS processes and
    activations cross the process boundary via the jitted ppermute
    ring; greedy decode must be deterministic across the lockstep."""
    body = {"model": "tiny-llama-test", "prompt": "pp across processes",
            "max_tokens": 8, "temperature": 0}
    out = _post(cluster_pp + "/v1/completions", body)
    assert out["usage"]["completion_tokens"] == 8
    out2 = _post(cluster_pp + "/v1/completions", body)
    assert out2["choices"][0]["text"] == out["choices"][0]["text"]


# slow: shares cluster_pp with the test above; alone it would pay the boot
@pytest.mark.slow
def test_multihost_pp_concurrent_requests(cluster_pp):
    import concurrent.futures as cf

    def one(i):
        return _post(cluster_pp + "/v1/completions", {
            "model": "tiny-llama-test", "prompt": f"pp req {i}",
            "max_tokens": 6, "temperature": 0})

    with cf.ThreadPoolExecutor(3) as ex:
        outs = list(ex.map(one, range(3)))
    assert all(o["usage"]["completion_tokens"] == 6 for o in outs)


@pytest.fixture(scope="module")
def cluster_pp_spill(tmp_path_factory):
    """A 2-process pipeline cluster with a TINY page pool and the host
    KV offload tier on: preemption under page pressure must spill
    per-host shards and restore them instead of recomputing (the last
    parallelism tier that used to fall back to recompute)."""
    cfg = tmp_path_factory.mktemp("ppspill") / "engine.yaml"
    cfg.write_text("engine:\n  page-size: 16\n")
    yield from _boot_cluster([
        "--pipeline-parallel-size", "2", "--tensor-parallel-size", "2",
        "--max-pages", "4", "--max-num-seqs", "2",
        "--kaito-config-file", str(cfg),
        "--kaito-kv-cache-cpu-memory-utilization", "0.02"])


# slow: 81 s: a third multi-process cluster (37 s to boot) and a spill cycle
@pytest.mark.slow
def test_multihost_pp_preempt_restores_from_host(cluster_pp_spill):
    """Two concurrent generations overflow the tiny page pool, so the
    newest preempts mid-decode; with the offload tier it must resume
    from restored host pages — greedy output identical to running the
    same request uncontended — and the restore counter must move."""
    import concurrent.futures as cf
    import urllib.request as _ur

    base = cluster_pp_spill

    def gen(prompt):
        return _post(base + "/v1/completions", {
            "model": "tiny-llama-test", "prompt": prompt,
            "max_tokens": 42, "temperature": 0, "ignore_eos": True},
            timeout=600)

    # uncontended references (greedy => deterministic)
    solo_a = gen("spill victim alpha")
    solo_b = gen("spill victim beta")

    with cf.ThreadPoolExecutor(2) as ex:
        fa = ex.submit(gen, "spill victim alpha")
        fb = ex.submit(gen, "spill victim beta")
        got_a, got_b = fa.result(), fb.result()
    assert got_a["choices"][0]["text"] == solo_a["choices"][0]["text"]
    assert got_b["choices"][0]["text"] == solo_b["choices"][0]["text"]

    metrics = _ur.urlopen(base + "/metrics", timeout=30).read().decode()
    vals = {l.split()[0]: float(l.split()[1]) for l in metrics.splitlines()
            if l and not l.startswith("#")}
    assert vals.get("kaito:num_preemptions_total", 0) >= 1, \
        "pool pressure never forced a preemption — test shape is wrong"
    assert vals.get("kaito:host_kv_restored_pages_total", 0) >= 1, \
        "preemption recomputed instead of restoring from host shards"


def test_multihost_health_contract(cluster):
    """The worker health probe contract: coordinator reachable."""
    from kaito_tpu.runtime.health import coordinator_reachable, \
        leader_http_healthy

    assert leader_http_healthy(cluster)
    # the coordinator port is embedded in the cluster fixture env of the
    # child processes; probe the leader HTTP instead for the worker path
    host = cluster.split("//")[1]
    assert coordinator_reachable(host)
