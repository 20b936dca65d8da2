"""Dynamic multi-LoRA serving (docs/multi-lora.md): the bounded
two-tier adapter cache (hot-load into fixed HBM slots, LRU demotion to
the host tier, fault-back-in), its no-retrace pin, the /v1/adapters
admin surface, QoS tenant->adapter mapping, adapter-seeded prefix
hashing, the EPP adapter-affinity scorer, annotation->flag rendering +
plan-time rejection, gating invisibility (no adapter config =>
byte-identical engine surface), and the hot-load-then-route e2e over
two real engine processes behind the EPP (slow tier)."""

import json
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from kaito_tpu.engine.config import EngineConfig
from kaito_tpu.engine.engine import InferenceEngine, SamplingParams
from kaito_tpu.engine.model import TransformerLM
from kaito_tpu.models import get_model_by_name
from kaito_tpu.tuning.lora import LoraConfig, add_lora_params, save_adapter

TINY = get_model_by_name("tiny-llama-test").arch


def _make_adapter(path, seed, scale=0.5, r=4, base="tiny-llama-test"):
    model = TransformerLM(TINY, dtype=jnp.float32)
    params = add_lora_params(model, model.init_params(jax.random.PRNGKey(0)),
                             LoraConfig(r=r), jax.random.PRNGKey(seed))
    params["dense"]["q_lora_b"] = scale * jax.random.normal(
        jax.random.PRNGKey(seed + 100),
        params["dense"]["q_lora_b"].shape, jnp.float32)
    save_adapter(str(path), params, LoraConfig(r=r), base)


@pytest.fixture(scope="module")
def adapters_dir(tmp_path_factory):
    root = tmp_path_factory.mktemp("lora")
    _make_adapter(root / "style-a", seed=1)
    _make_adapter(root / "style-b", seed=7, scale=0.8, r=8)
    _make_adapter(root / "style-c", seed=3, scale=0.3, r=2)
    return root


def _greedy(n=6):
    return SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True)


CFG = dict(model="tiny-llama-test", max_model_len=128, page_size=16,
           max_num_seqs=4, dtype="float32", kv_dtype="float32",
           prefill_buckets=(32,), enable_prefix_caching=False, seed=0)


# ---------------------------------------------------------------------------
# cache unit tier: refusals, pinning, two-tier residency
# ---------------------------------------------------------------------------

def _raw_factors(seed=11, r=4):
    model = TransformerLM(TINY, dtype=jnp.float32)
    lora = add_lora_params(model, model.init_params(jax.random.PRNGKey(0)),
                           LoraConfig(r=r), jax.random.PRNGKey(seed))
    flat = {}
    for g, stack in lora.items():
        if not isinstance(stack, dict):
            continue
        for k, v in stack.items():
            if "_lora_" in k:
                flat[f"{g}/{k}"] = v
    return flat


def test_cache_refusals_are_counted_and_typed():
    from kaito_tpu.engine.adapter_cache import AdapterCache, AdapterLoadError

    model = TransformerLM(TINY, dtype=jnp.float32)
    cache = AdapterCache(model, slots=1, rmax=4,
                         base_model="tiny-llama-test")
    flat = _raw_factors()
    # satellite #1: base-model mismatch is a load REFUSAL with a
    # counted reason, not a silent merge
    with pytest.raises(AdapterLoadError) as ei:
        cache.install("wrong-base", flat, r=4, scaling=1.0,
                      base="other-model")
    assert ei.value.reason == "base_mismatch"
    assert cache.load_failures == {"base_mismatch": 1}
    # rank beyond the pre-allocated rmax can never fit the slot table
    with pytest.raises(AdapterLoadError) as ei:
        cache.install("too-wide", flat, r=9, scaling=1.0)
    assert ei.value.reason == "rank_overflow"
    with pytest.raises(AdapterLoadError) as ei:
        cache.install("empty", {"dense/nope": jnp.zeros(3)}, r=2,
                      scaling=1.0)
    assert ei.value.reason == "no_targets"
    # the escape hatch serves the mismatched base anyway
    permissive = AdapterCache(model, slots=1, rmax=4,
                              base_model="tiny-llama-test",
                              allow_base_mismatch=True)
    assert permissive.install("wrong-base", flat, r=4, scaling=1.0,
                              base="other-model") == 1


def test_cache_eviction_pinning_and_host_tier():
    from kaito_tpu.engine.adapter_cache import (AdapterBusyError,
                                                AdapterCache,
                                                AdapterLoadError)

    model = TransformerLM(TINY, dtype=jnp.float32)
    cache = AdapterCache(model, slots=2, rmax=4, host_bytes=64 << 20)
    s1 = cache.install("one", _raw_factors(1), r=4, scaling=1.0)
    s2 = cache.install("two", _raw_factors(2), r=4, scaling=1.0)
    assert {s1, s2} == {1, 2} and len(cache) == 2
    # LRU order is touch order: ensure() refreshes "one", so filling
    # the table evicts "two" — into the host tier, not oblivion
    assert cache.ensure("one") == s1
    assert cache.hits_total == 1
    s3 = cache.install("three", _raw_factors(3), r=4, scaling=1.0)
    assert s3 == s2 and cache.evictions_total == 1
    assert not cache.name_to_slot.get("two")
    assert cache.host.has("two") and cache.has("two")
    # fault-back-in reclaims a slot (evicting the LRU resident, "one",
    # to the host tier) and round-trips the factors
    slot = cache.ensure("two")
    assert cache.faults_total == 1 and cache.name_to_slot["two"] == slot
    assert cache.host.has("one")
    # a pinned adapter is never evicted; with every slot pinned the
    # load is refused with reason "capacity"
    cache.busy_fn = lambda name: True
    with pytest.raises(AdapterLoadError) as ei:
        cache.install("four", _raw_factors(4), r=4, scaling=1.0)
    assert ei.value.reason == "capacity"
    with pytest.raises(AdapterBusyError):
        cache.remove("two")
    cache.busy_fn = lambda name: False
    # remove drops BOTH tiers: no fault-back-in afterwards
    assert cache.remove("two")
    assert not cache.has("two")
    with pytest.raises(KeyError):
        cache.ensure("two")
    snap = cache.snapshot()
    assert snap["enabled"] and snap["slots"] == 2
    assert {e["name"] for e in snap["resident"]} == {"three"}
    assert snap["host_tier"] == ["one"]


# ---------------------------------------------------------------------------
# engine tier: heterogeneous batches, no-retrace hot-load, re-fault parity
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cache_engine(adapters_dir):
    cfg = EngineConfig(**CFG, adapters_dir=str(adapters_dir),
                       adapter_slots=3, adapter_rmax=8,
                       adapter_host_bytes=64 << 20)
    eng = InferenceEngine(cfg)
    eng.start()
    yield eng
    eng.stop()


def test_heterogeneous_batch_matches_solo_goldens(cache_engine):
    """Base + two adapters decoding in the SAME batch must reproduce
    their solo greedy streams exactly (the batched-LoRA property, now
    through the dynamic slot table instead of the boot-time stacks)."""
    eng = cache_engine
    assert sorted(eng.adapter_cache.resident()) == \
        ["style-a", "style-b", "style-c"]
    prompt = [9, 10, 11]
    solo = {name: list(eng.submit(prompt, _greedy(8),
                                  adapter=name).stream())
            for name in ("", "style-a", "style-b", "style-c")}
    assert len({tuple(v) for v in solo.values()}) == 4   # four real deltas
    reqs = [eng.submit(prompt, _greedy(8), adapter=n)
            for n in ("style-b", "", "style-c", "style-a")]
    outs = [list(r.stream()) for r in reqs]
    assert outs[0] == solo["style-b"]
    assert outs[1] == solo[""]
    assert outs[2] == solo["style-c"]
    assert outs[3] == solo["style-a"]


def test_evict_fault_roundtrip_is_exact_and_never_retraces(adapters_dir,
                                                           tmp_path):
    """The tentpole pin: hot-load, LRU-evict to host, fault back in —
    greedy output identical before and after the round trip, and the
    jitted decode program NEVER retraces (every slot write is a
    same-shape donation into the pre-allocated buffers)."""
    cfg = EngineConfig(**CFG, adapter_slots=1, adapter_rmax=8,
                       adapter_host_bytes=64 << 20)
    eng = InferenceEngine(cfg)
    eng.start()
    try:
        assert eng.adapter_cache is not None and len(eng.adapter_cache) == 0
        base = list(eng.submit([5, 6, 7], _greedy()).stream())
        traced = eng._decode_fn._cache_size()
        slot = eng.load_adapter_dynamic("style-a",
                                        str(adapters_dir / "style-a"))
        assert slot == 1
        golden_a = list(eng.submit([5, 6, 7], _greedy(),
                                   adapter="style-a").stream())
        assert golden_a != base
        # one slot: loading style-b demotes style-a to the host tier
        eng.load_adapter_dynamic("style-b", str(adapters_dir / "style-b"))
        snap = eng.adapter_snapshot()
        assert [e["name"] for e in snap["resident"]] == ["style-b"]
        assert snap["host_tier"] == ["style-a"]
        assert snap["evictions_total"] == 1
        golden_b = list(eng.submit([5, 6, 7], _greedy(),
                                   adapter="style-b").stream())
        # submitting the evicted name faults it back in (evicting b)
        got_a = list(eng.submit([5, 6, 7], _greedy(),
                                adapter="style-a").stream())
        assert got_a == golden_a
        assert eng.adapter_cache.faults_total == 1
        # ...and back the other way
        assert list(eng.submit([5, 6, 7], _greedy(),
                               adapter="style-b").stream()) == golden_b
        assert list(eng.submit([5, 6, 7], _greedy()).stream()) == base
        # the whole churn ran on the ORIGINAL traced program
        assert eng._decode_fn._cache_size() == traced
        # a name neither tier holds is an unknown adapter
        with pytest.raises(ValueError, match="unknown adapter"):
            eng.submit([1, 2], _greedy(), adapter="ghost")
    finally:
        eng.stop()


def _agreeing_drafts(stream, n_prompt, k):
    """How often the n-gram drafter's proposal is what the model went
    on to emit: output positions whose preceding ``k``-gram occurred
    earlier in ``stream`` followed by the same token."""
    follower, hits = {}, 0
    for end in range(k, len(stream)):
        gram = tuple(stream[end - k:end])
        if end >= n_prompt and follower.get(gram) == stream[end]:
            hits += 1
        follower[gram] = stream[end]
    return hits


def test_adapter_compose_int8kv_and_ngram_spec(adapters_dir):
    """Compose leg: per-request LoRA x int8 KV cache x n-gram
    speculative decoding in ONE engine.  (Exact parity with a non-spec
    engine is deliberately not pinned: the verify path requantizes
    accepted-token KV in page-batched absmax groups, which is allowed
    to round differently from one-token-at-a-time decode.)  What must
    hold: adapters stay isolated, replays are deterministic, and
    speculation actually engages through the adapter slot table.
    (In-engine replays are NOT pinned either: acceptance patterns — and
    with them the requant grouping — are history-dependent.
    Determinism is pinned at the process level instead: an identical
    engine fed the identical request sequence must reproduce
    byte-for-byte.)"""
    cfg = dict(CFG, kv_dtype="int8", adapters_dir=str(adapters_dir),
               adapter_slots=3, adapter_rmax=8)
    # tests/test_speculative.py's REPEAT_PROMPT: the synthetic model's
    # greedy continuation of it loops, asserted below, not hoped for
    prompt = [7, 11, 13, 7, 11, 13, 7, 11, 13, 7, 11]
    names = ("", "style-a", "style-b")

    def run_sequence(names, **kw):
        eng = InferenceEngine(EngineConfig(**dict(cfg, **kw)))
        eng.start()
        try:
            outs = {n: list(eng.submit(prompt, _greedy(24),
                                       adapter=n).stream())
                    for n in names}
            return outs, dict(eng.counters)
        finally:
            eng.stop()

    # precondition: without speculation this engine's own greedy stream
    # revisits an n-gram with the same follower, so the drafter has
    # something to be right about (a prompt whose continuation never
    # loops proposes and is refused, and proves nothing about the path)
    plain, _ = run_sequence(("",))
    assert _agreeing_drafts(prompt + plain[""], len(prompt),
                            EngineConfig.speculative_min_match) > 0

    outs, counters = run_sequence(names, speculative_ngram=4)
    # three real deltas: quantized KV never blurs adapters together
    assert len({tuple(v) for v in outs.values()}) == 3
    # the speculator engaged (proposed AND accepted drafted tokens)
    assert counters["spec_proposed_tokens_total"] > 0
    assert counters["spec_accepted_tokens_total"] > 0
    # identical engine + identical request sequence => identical bytes
    outs2, _ = run_sequence(names, speculative_ngram=4)
    assert outs2 == outs


# ---------------------------------------------------------------------------
# adapter-seeded prefix hashing: KV never cross-matches between adapters
# ---------------------------------------------------------------------------

def test_adapter_seed_isolates_hash_chains():
    from kaito_tpu.engine.kv_pool import prompt_pool_blocks
    from kaito_tpu.runtime.routing import adapter_seed, prefix_blocks

    text = "the quick brown fox jumps over the lazy dog " * 8
    assert adapter_seed("") == 0          # base chains stay byte-identical
    assert adapter_seed("style-a") != 0
    assert adapter_seed("style-a") != adapter_seed("style-b")
    base = prefix_blocks(text, 64)
    assert base == prefix_blocks(text, 64, seed=0)
    a = prefix_blocks(text, 64, seed=adapter_seed("style-a"))
    b = prefix_blocks(text, 64, seed=adapter_seed("style-b"))
    # same lengths, zero collisions anywhere in the chains
    assert len(a) == len(b) == len(base)
    assert not set(a) & set(base) and not set(a) & set(b)
    # the engine-side pool publisher seeds the exact same way the EPP
    # does — hash parity per adapter, or the affinity index is useless
    assert prompt_pool_blocks(text, 16, adapter="style-a") == a
    assert prompt_pool_blocks(text, 16) == base


# ---------------------------------------------------------------------------
# server tier: gating invisibility, admin lifecycle, tenant mapping
# ---------------------------------------------------------------------------

def _boot(**over):
    from kaito_tpu.engine.server import make_server

    cfg = EngineConfig(**{**CFG, **over})
    eng = InferenceEngine(cfg)
    eng.start()
    srv = make_server(eng, cfg, host="127.0.0.1", port=0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return eng, srv, f"http://127.0.0.1:{srv.server_address[1]}"


def _post(url, path, body, headers=None):
    req = urllib.request.Request(
        url + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    return json.loads(urllib.request.urlopen(req, timeout=120).read())


def test_adapter_plane_disabled_is_invisible():
    """Default-off gate: no cache, /v1/adapters 403s, and the /metrics
    exposition carries NO kaito:adapter_ family (byte-identical — a
    family would change the payload even at zero)."""
    eng, srv, url = _boot()
    try:
        assert eng.adapter_cache is None
        _post(url, "/v1/completions",
              {"prompt": "gate probe", "max_tokens": 2,
               "temperature": 0.0})
        body = urllib.request.urlopen(url + "/metrics",
                                      timeout=30).read().decode()
        assert "kaito:adapter_" not in body
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(url + "/v1/adapters", timeout=10)
        assert ei.value.code == 403
        for method, path in (("POST", "/v1/adapters"),
                             ("DELETE", "/v1/adapters/x")):
            req = urllib.request.Request(
                url + path, data=b'{"name":"x","source":"/tmp"}',
                method=method,
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=10)
            assert ei.value.code == 403
    finally:
        srv.shutdown()
        eng.stop()


def test_adapters_admin_lifecycle_over_http(adapters_dir, tmp_path):
    eng, srv, url = _boot(adapter_slots=2, adapter_rmax=8)
    try:
        # enabled engine exposes the gated metric families
        body = urllib.request.urlopen(url + "/metrics",
                                      timeout=30).read().decode()
        for fam in ("kaito:adapter_resident", "kaito:adapter_slots_total",
                    "kaito:adapter_loads_total",
                    "kaito:adapter_evictions_total",
                    "kaito:adapter_hits_total"):
            assert fam in body
        out = _post(url, "/v1/adapters",
                    {"name": "style-a",
                     "source": f"path://{adapters_dir / 'style-a'}"})
        assert out == {"loaded": "style-a", "slot": 1}
        snap = json.loads(urllib.request.urlopen(
            url + "/v1/adapters", timeout=10).read())
        assert [e["name"] for e in snap["resident"]] == ["style-a"]
        # satellite #2: /v1/models lists runtime-resident adapters
        ids = {m["id"] for m in json.loads(urllib.request.urlopen(
            url + "/v1/models", timeout=10).read())["data"]}
        assert {"tiny-llama-test", "style-a"} <= ids
        # ...and the model field routes through the dynamic cache
        _post(url, "/v1/completions",
              {"model": "style-a", "prompt": "hi", "max_tokens": 2,
               "temperature": 0.0})
        # trust model: remote schemes need the allowlist (403), unknown
        # schemes and bad names are 400s, missing dirs are 400s
        cases = [
            ({"name": "x", "source": "oras://ghcr.io/evil/a:1"}, 403),
            ({"name": "x", "source": "s3://bucket/a"}, 400),
            ({"name": "bad name!", "source": "/tmp"}, 400),
            ({"name": "x", "source": f"{adapters_dir}/nope"}, 400),
            ({"name": "x"}, 400),
        ]
        for body_, code in cases:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(url, "/v1/adapters", body_)
            assert ei.value.code == code, body_
        # base-mismatch refusal surfaces as 422 + counted reason
        _make_adapter(tmp_path / "alien", seed=9, base="other-model")
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(url, "/v1/adapters",
                  {"name": "alien",
                   "source": str(tmp_path / "alien")})
        assert ei.value.code == 422
        body = urllib.request.urlopen(url + "/metrics",
                                      timeout=30).read().decode()
        assert 'kaito:adapter_load_failures_total{reason="base_mismatch"} 1' \
            in body
        # DELETE drops it; a second DELETE 404s
        req = urllib.request.Request(url + "/v1/adapters/style-a",
                                     method="DELETE")
        assert json.loads(urllib.request.urlopen(req, timeout=10).read()) \
            == {"deleted": "style-a"}
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                urllib.request.Request(url + "/v1/adapters/style-a",
                                       method="DELETE"), timeout=10)
        assert ei.value.code == 404
    finally:
        srv.shutdown()
        eng.stop()


def test_tenant_header_selects_adapter(adapters_dir):
    """QoS mapping: when the model field doesn't name an adapter, the
    X-Kaito-Tenant header does (docs/multi-lora.md)."""
    qos = json.dumps({
        "classes": {"standard": {"priority": 50}},
        "tenants": {"acme": "standard"},
        "default_class": "standard",
        "adapters": {"acme": "style-a", "ghost-corp": "never-loaded"},
    })
    eng, srv, url = _boot(adapter_slots=2, adapter_rmax=8,
                          adapters_dir=str(adapters_dir), qos_config=qos)
    try:
        routed = []
        orig = eng.submit

        def spy(tokens, params, **kw):
            routed.append(kw.get("adapter", ""))
            return orig(tokens, params, **kw)

        eng.submit = spy
        body = {"prompt": "hello", "max_tokens": 2, "temperature": 0.0}
        _post(url, "/v1/completions", body)
        _post(url, "/v1/completions", body,
              headers={"X-Kaito-Tenant": "acme"})
        # an explicit model field beats the tenant mapping
        _post(url, "/v1/completions", {**body, "model": "style-b"},
              headers={"X-Kaito-Tenant": "acme"})
        assert routed == ["", "style-a", "style-b"]
        # a tenant mapped to an adapter the engine doesn't hold is a
        # 503 (retryable capacity condition), not a silent base answer
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(url, "/v1/completions", body,
                  headers={"X-Kaito-Tenant": "ghost-corp"})
        assert ei.value.code == 503
    finally:
        srv.shutdown()
        eng.stop()


def test_qos_adapters_doc_roundtrip_and_validation():
    from kaito_tpu.engine.qos import parse_qos_config

    doc = {"classes": {"standard": {"priority": 50}},
           "default_class": "standard"}
    # pre-adapter documents round-trip byte-identically (no new key)
    assert "adapters" not in parse_qos_config(json.dumps(doc)).to_dict()
    cfg = parse_qos_config(json.dumps(
        {**doc, "adapters": {"acme": "style-a"}}))
    assert cfg.adapter_of("acme") == "style-a"
    assert cfg.adapter_of("other") == ""
    assert cfg.to_dict()["adapters"] == {"acme": "style-a"}
    for bad in ({"adapters": ["x"]}, {"adapters": {"acme": 7}},
                {"adapters": {"bad name!": "a"}},
                {"adapters": {"acme": "bad name!"}}):
        with pytest.raises(ValueError):
            parse_qos_config(json.dumps({**doc, **bad}))


# ---------------------------------------------------------------------------
# EPP tier: residency index, adapter-seeded ctx, affinity scoring
# ---------------------------------------------------------------------------

def test_epp_adapter_affinity_scoring_and_gating():
    from kaito_tpu.runtime.epp import ADAPTER_WEIGHT, EndpointPicker
    from kaito_tpu.runtime.routing import Backend

    a, b = Backend("http://a:1"), Backend("http://b:1")
    # off: no index, no scorer, no metric families (byte-identical)
    plain = EndpointPicker([a, b])
    assert plain.adapter_index is None
    assert not any(t == "adapter-affinity-scorer"
                   for t, _ in plain.plugins)
    assert "adapter" not in plain.registry.expose()

    picker = EndpointPicker([Backend("http://a:1"), Backend("http://b:1")],
                            adapter_affinity=True, block_chars=8)
    assert any(t == "adapter-affinity-scorer" and w == ADAPTER_WEIGHT
               for t, w in picker.plugins)
    picker.adapter_index.update("http://a:1", {
        "enabled": True,
        "resident": [{"name": "style-a", "slot": 1, "r": 4, "base": ""}],
        "host_tier": ["style-b"]})
    assert picker.adapter_index.known("style-a")
    assert picker.adapter_index.residency("style-a") == {"http://a:1": 1.0}
    # host-tier residency scores HALF: fault-in beats a cold hot-load
    # but loses to a replica serving from an HBM slot
    assert picker.adapter_index.residency("style-b") == {"http://a:1": 0.5}

    body = json.dumps({"model": "style-a",
                       "prompt": "a prompt long enough for blocks"}).encode()
    ctx = picker.make_ctx("POST", "/v1/completions", body, {})
    assert ctx.adapter == "style-a"
    # an unknown model field never becomes an adapter (scrape-race
    # safety: degrade to unseeded blocks, not a poisoned chain)
    cold = picker.make_ctx("POST", "/v1/completions", json.dumps(
        {"model": "unscraped", "prompt": "a prompt long enough for blocks"}
    ).encode(), {})
    assert cold.adapter == ""
    assert ctx.blocks != cold.blocks and len(ctx.blocks) == len(cold.blocks)
    # the explicit header wins without any advert
    hdr = picker.make_ctx("POST", "/v1/completions", b'{"prompt":"x"}',
                          {"X-Kaito-Adapter": "style-b"})
    assert hdr.adapter == "style-b"

    ba, bb = picker.backends
    assert picker._score(ba, ctx) > picker._score(bb, ctx)
    assert next(iter(picker.candidates(
        "POST", "/v1/completions", ctx))).url == "http://a:1"
    # saturated residents earn nothing (affinity never beats overload)
    ba.saturated = True
    assert picker._score(ba, ctx) == pytest.approx(picker._score(bb, ctx))
    ba.saturated = False
    picker.note_response(ba, ctx, 200)
    assert picker.m_adapter_hits.value() == 1.0
    picker.adapter_index.update("http://a:1", None)   # advert cleared
    ctx2 = picker.make_ctx("POST", "/v1/completions", body, {})
    assert ctx2.adapter == ""                          # name forgotten
    assert len(picker.adapter_index) == 0


# ---------------------------------------------------------------------------
# controller + manifests: the kaito-tpu.io/adapters annotation
# ---------------------------------------------------------------------------

ADAPTERS_ANN = json.dumps({"slots": 4, "rmax": 8,
                           "host_bytes": 128 << 20,
                           "allow_base_mismatch": True,
                           "allowlist": ["oras://ghcr.io/acme/"]})


def test_adapters_annotation_renders_engine_flags():
    from kaito_tpu.api import (InferenceSpec, ObjectMeta, ResourceSpec,
                               Workspace)
    from kaito_tpu.manifests.inference import (build_engine_command,
                                               parse_adapters_annotation)
    from kaito_tpu.models.registry import get_model_by_name
    from kaito_tpu.parallel.plan import plan_parallelism
    from kaito_tpu.sku.catalog import CHIP_CATALOG

    md = get_model_by_name("llama-3.1-8b-instruct")
    plan = plan_parallelism(md, CHIP_CATALOG["v5e"], workload="serve",
                            max_model_len=2048)
    ws = Workspace(
        ObjectMeta(name="lora", annotations={
            "kaito-tpu.io/adapters": ADAPTERS_ANN}),
        resource=ResourceSpec(instance_type="ct5lp-hightpu-4t"),
        inference=InferenceSpec(preset="llama-3.1-8b-instruct"))
    cmd = build_engine_command(ws, md, plan)
    assert cmd[cmd.index("--adapter-slots") + 1] == "4"
    assert cmd[cmd.index("--adapter-rmax") + 1] == "8"
    assert cmd[cmd.index("--adapter-host-bytes") + 1] == str(128 << 20)
    assert "--adapter-allow-base-mismatch" in cmd
    assert cmd[cmd.index("--adapter-source-allowlist") + 1] == \
        "oras://ghcr.io/acme/"
    # no annotation -> no flag (the off path renders byte-identically)
    ws.metadata.annotations = {}
    assert "--adapter-slots" not in build_engine_command(ws, md, plan)
    # defaults fill in; malformed documents raise
    assert parse_adapters_annotation('{"slots": 2}') == {
        "slots": 2, "rmax": 16, "host_bytes": 256 << 20,
        "allow_base_mismatch": False, "allowlist": []}
    assert parse_adapters_annotation("") is None
    for bad in ("not json", '["x"]', '{"slots": 0}', '{"rmax": 4}',
                '{"slots": 2, "bogus": 1}',
                '{"slots": 2, "allowlist": "oras://x"}',
                '{"slots": 2, "allowlist": ["s3://bucket/"]}',
                '{"slots": 2, "allowlist": ["oras://a,b"]}',
                '{"slots": 2, "allow_base_mismatch": "yes"}'):
        with pytest.raises(ValueError):
            parse_adapters_annotation(bad)


def test_workspace_plan_fails_on_bad_adapters_annotation():
    from kaito_tpu.api import (InferenceSpec, ObjectMeta, ResourceSpec,
                               Workspace)
    from kaito_tpu.api.workspace import COND_RESOURCE_READY
    from kaito_tpu.controllers.runtime import Store
    from kaito_tpu.controllers.workspace import WorkspaceReconciler
    from kaito_tpu.provision import FakeCloud, KarpenterTPUProvisioner

    store = Store()
    cloud = FakeCloud(store)
    rec = WorkspaceReconciler(store, KarpenterTPUProvisioner(store))
    store.create(Workspace(
        ObjectMeta(name="bad-lora", annotations={
            "kaito-tpu.io/adapters": '{"slots": 0}'}),
        resource=ResourceSpec(instance_type="ct5lp-hightpu-1t"),
        inference=InferenceSpec(preset="llama-3.1-8b-instruct")))
    for _ in range(3):
        rec.reconcile_key("default", "bad-lora")
        cloud.tick()
    ws = store.get("Workspace", "default", "bad-lora")
    cond = next((c for c in ws.status.conditions
                 if c.type == COND_RESOURCE_READY), None)
    assert cond is not None and cond.status == "False"
    assert cond.reason == "PlanFailed"
    assert "kaito-tpu.io/adapters" in cond.message


def test_epp_command_mirrors_adapter_affinity():
    from kaito_tpu.manifests.epp import build_epp_command

    cmd = build_epp_command(["http://a:1"], adapter_affinity=True)
    assert "--adapter-affinity" in cmd
    assert "--adapter-affinity" not in build_epp_command(["http://a:1"])


# ---------------------------------------------------------------------------
# acceptance e2e (slow): hot-load on one of two REAL engine processes
# behind the EPP; the scraper learns residency and affinity routes to it
# ---------------------------------------------------------------------------

# slow: 30 s: two real engines behind the picker
@pytest.mark.slow
def test_e2e_hot_load_then_affinity_routes_to_holder(tmp_path):
    from tests.helpers.dp_cluster import boot_epp

    _make_adapter(tmp_path / "hot-style", seed=5, r=4)
    extra = ["--adapter-slots", "2", "--adapter-rmax", "8",
             "--dtype", "float32"]
    with boot_epp(2, extra_args=extra, adapter_affinity=True,
                  block_chars=32) as (front, urls, picker):
        from kaito_tpu.runtime.epp import AdapterScraper

        scraper = AdapterScraper(picker, interval_s=0.5)
        scraper.start()
        try:
            # hot-load onto replica 0 ONLY — no restart anywhere
            out = _post(urls[0], "/v1/adapters",
                        {"name": "hot-style",
                         "source": f"path://{tmp_path / 'hot-style'}"})
            assert out["loaded"] == "hot-style"
            deadline = time.monotonic() + 30
            while not picker.adapter_index.known("hot-style"):
                assert time.monotonic() < deadline, "scrape never landed"
                time.sleep(0.2)
            assert picker.adapter_index.residency("hot-style") == \
                {urls[0]: 1.0}
            # adapter traffic through the front lands on the holder
            # (and actually serves — the engine resolves the adapter)
            for _ in range(3):
                _post(front, "/v1/completions",
                      {"model": "hot-style", "prompt": "adapter hello",
                       "max_tokens": 3, "temperature": 0.0})
            assert picker.m_adapter_hits.value() >= 3
            assert picker.m_picks.value(backend=urls[0]) >= 3
            assert picker.m_picks.value(backend=urls[1]) == 0
            # base traffic is untouched by the adapter plane
            _post(front, "/v1/completions",
                  {"prompt": "base hello", "max_tokens": 3,
                   "temperature": 0.0})
        finally:
            scraper.stop()
