"""Cluster-wide KV pool (docs/kv-pool.md): hash parity between the EPP
and the engine-side publisher, the replica-local prefix store, the
EPP's cluster prefix->holder index + route-vs-fetch steering, the
staged-export TTL regression, metric gating (pool off => byte-identical
exposition), and the warm-TTFT-survives-scale-out e2e (slow tier)."""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from kaito_tpu.engine.kv_pool import (HostExport, PoolEntry,
                                      PrefixPageStore, common_prefix_pages,
                                      meta_nbytes, pool_block_chars,
                                      pool_key, prompt_pool_blocks)
from kaito_tpu.runtime.routing import extract_prompt_text, prefix_blocks

# ---------------------------------------------------------------------------
# hash parity: the EPP and the engine-side publisher MUST produce the
# same chain for the same prompt, or the global index is useless
# ---------------------------------------------------------------------------

PROMPTS = [
    "short",
    "the quick brown fox jumps over the lazy dog " * 8,
    "unicode préfixe éléphant " * 20,
]


@pytest.mark.parametrize("page_size", [8, 16, 64, 128])
def test_publisher_blocks_match_epp_blocks(page_size):
    """Satellite pin: the engine publisher hashes at page_size*4 chars
    and the EPP's block_chars derives from the scraped page size the
    same way — identical prompts must chain to identical hashes at
    every block-size config."""
    for text in PROMPTS:
        assert prompt_pool_blocks(text, page_size) == \
            prefix_blocks(text, page_size * 4)
    assert pool_block_chars(page_size) == page_size * 4


def test_extraction_agreement_prompt_and_messages():
    """Both sides hash ``extract_prompt_text`` output, for both body
    shapes — a divergence silently zeroes the cross-replica hit rate."""
    p_body = {"prompt": "hello pool", "max_tokens": 4}
    m_body = {"messages": [{"role": "system", "content": "be brief"},
                           {"role": "user", "content": "hello pool"}]}
    assert extract_prompt_text(p_body) == "hello pool"
    assert extract_prompt_text(m_body) == \
        "<system>be brief<user>hello pool"
    assert extract_prompt_text({"prompt": 42}) == ""
    assert extract_prompt_text("not a dict") == ""
    # the engine-side publisher consumes the SAME extraction output
    for body in (p_body, m_body):
        text = extract_prompt_text(body)
        assert prompt_pool_blocks(text, 16) == prefix_blocks(text, 64)


def test_pool_key_is_chained_over_whole_prefix():
    """The store key is the LAST chained hash: any change in an earlier
    block must change it (the key names the whole prefix)."""
    a = prompt_pool_blocks("a" * 256, 16)
    b = prompt_pool_blocks("b" + "a" * 255, 16)
    assert len(a) == len(b) == 4
    assert pool_key(a) != pool_key(b)
    assert pool_key(a) == f"{a[-1]:016x}"


# ---------------------------------------------------------------------------
# token-level import authority
# ---------------------------------------------------------------------------

def test_common_prefix_pages_caps_and_trims():
    ps = 4
    entry = list(range(100, 112))                       # 12 tokens, 3 pages
    # full match, capped below the request so one token remains
    assert common_prefix_pages(list(range(100, 120)), entry, ps) == 3
    # request == entry: cap at len-1 => 11 tokens => 2 whole pages
    assert common_prefix_pages(list(range(100, 112)), entry, ps) == 2
    # divergence mid-page trims to whole pages below it
    req = list(range(100, 106)) + [999] * 10
    assert common_prefix_pages(req, entry, ps) == 1
    # divergence in the first page -> nothing importable
    assert common_prefix_pages([999] * 16, entry, ps) == 0
    assert common_prefix_pages([], entry, ps) == 0


# ---------------------------------------------------------------------------
# replica-local prefix store
# ---------------------------------------------------------------------------

def _entry(key, nbytes, n_pages=2, page_size=4):
    return PoolEntry(key=key, blocks=list(range(n_pages)),
                     n_tokens=n_pages * page_size, n_pages=n_pages,
                     export=None, nbytes=nbytes)


def test_prefix_store_lru_eviction_and_accounting():
    store = PrefixPageStore(max_bytes=100)
    assert store.put(_entry("a", 40))
    assert store.put(_entry("b", 40))
    assert store.get("a") is not None          # a is now most-recent
    assert store.put(_entry("c", 40))          # evicts b (LRU)
    assert store.has("a") and store.has("c") and not store.has("b")
    assert store.evictions_total == 1
    assert store.used_bytes == 80
    # oversized entry is refused outright, store untouched
    assert not store.put(_entry("huge", 101))
    assert len(store) == 2
    # miss/hit accounting happens in get(), never in peek()
    hits, misses = store.hits_total, store.misses_total
    assert store.get("b") is None
    assert store.misses_total == misses + 1
    assert store.peek("a") is not None
    assert store.peek("nope") is None
    assert store.hits_total == hits            # peek() counted nothing
    # same-key republish replaces bytes, not duplicates
    assert store.put(_entry("a", 60))
    assert store.used_bytes == 100
    adv = store.advert()
    assert [e["key"] for e in adv] == ["a", "c"]   # freshest first
    assert all(isinstance(b, str) and len(b) == 16
               for e in adv for b in e["blocks"])


def test_host_export_chunk_roundtrip():
    """HostExport serves the same wire format StagedExport does: every
    chunk deserializes and the reassembled slabs equal the originals
    (int8 + fp32 scale slabs included)."""
    from kaito_tpu.engine.pd import deserialize_chunk

    rng = np.random.default_rng(0)
    L, P, ps, H, D = 3, 4, 4, 2, 8
    k = rng.integers(-128, 127, (L, P, ps, H, D)).astype(np.int8)
    v = rng.integers(-128, 127, (L, P, ps, H, D)).astype(np.int8)
    ks = rng.random((L, P, H), np.float32)
    vs = rng.random((L, P, H), np.float32)
    exp = HostExport(k, v, ks, vs, n_tokens=P * ps, model="m",
                     prompt_tokens=list(range(P * ps)))
    assert exp.n_chunks == len(exp.meta["chunks"]) >= 1
    got_k = np.zeros_like(k)
    got_v = np.zeros_like(v)
    got_ks = np.zeros_like(ks)
    got_vs = np.zeros_like(vs)
    for i, plan in enumerate(exp.plans):
        ck, cv, cks, cvs = deserialize_chunk(exp.get_chunk(i))
        sl = (slice(plan.layer_lo, plan.layer_hi),
              slice(plan.page_lo, plan.page_hi))
        got_k[sl], got_v[sl] = ck, cv
        got_ks[sl], got_vs[sl] = cks, cvs
    np.testing.assert_array_equal(got_k, k)
    np.testing.assert_array_equal(got_v, v)
    np.testing.assert_array_equal(got_ks, ks)
    np.testing.assert_array_equal(got_vs, vs)
    assert meta_nbytes(exp.meta) == (k.nbytes + v.nbytes
                                     + ks.nbytes + vs.nbytes)
    with pytest.raises(IndexError):
        exp.get_chunk(exp.n_chunks)


# ---------------------------------------------------------------------------
# satellite regression: export-registry TTL ages on last_access
# ---------------------------------------------------------------------------

class _FakeExport:
    fully_served = False
    draining = True

    def __init__(self, now):
        self.created = now
        self.last_access = now


def test_export_ttl_ages_on_last_access_not_creation(monkeypatch):
    """A chunk pull AFTER ttl_s from creation but WITHIN ttl_s of the
    last access must still find the entry: get() bumps last_access and
    the GC ages on it, so a slow multi-chunk pull can't lose its export
    mid-transfer (the old behavior aged on ``created``)."""
    import kaito_tpu.engine.pd as pd

    now = [1000.0]
    monkeypatch.setattr(pd.time, "monotonic", lambda: now[0])
    reg = pd.KVExportRegistry(ttl_s=10.0)
    reg.put("r1", _FakeExport(now[0]))
    now[0] += 8.0                   # t=8: mid-pull chunk access
    assert reg.get("r1") is not None
    now[0] += 7.0                   # t=15 > ttl from CREATION, but only
    reg.tick()                      # 7s since last access: GC runs
    assert reg.get("r1") is not None   # between chunks, entry survives
    now[0] += 11.0                  # t=26: abandoned past ttl -> GC'd
    reg.tick()
    assert reg.get("r1") is None


# ---------------------------------------------------------------------------
# EPP cluster index + steering (no engines needed)
# ---------------------------------------------------------------------------

def _advert(entries, block_chars=64):
    return {"enabled": True, "page_size": block_chars // 4,
            "block_chars": block_chars,
            "entries": [{"key": pool_key(b), "n_tokens": len(b) * 16,
                         "blocks": [f"{h:016x}" for h in b]}
                        for b in entries]}


def test_kv_pool_index_longest_prefix_wins():
    from kaito_tpu.runtime.epp import KVPoolIndex

    idx = KVPoolIndex()
    text = "z" * 64 * 6
    blocks = prefix_blocks(text, 64)
    idx.update("http://a:1", _advert([blocks[:4]]))
    idx.update("http://b:1", _advert([blocks[:2]]))
    # match returns holders at the LONGEST matching position only: a
    # serves 4 pages, so the 2-page holder b is not nominated
    m = idx.match(blocks, 64)
    assert m == {"http://a:1": (pool_key(blocks[:4]), 4, 4 * 16)}
    # a shorter request still finds holders through mid-chain rows, and
    # at b's depth both holders surface
    m = idx.match(blocks[:3], 64)
    assert m["http://a:1"][1] == 3 and "http://b:1" not in m
    m = idx.match(blocks[:2], 64)
    assert m["http://a:1"][1] == 2 and m["http://b:1"][1] == 2
    assert m["http://b:1"][0] == pool_key(blocks[:2])
    # wrong block size never cross-matches
    assert idx.match(blocks, 128) == {}
    # unrelated prompt: no match
    assert idx.match(prefix_blocks("y" * 300, 64), 64) == {}
    # a replica that stops advertising (rollout restart) drops out
    idx.update("http://a:1", None)
    assert "http://a:1" not in idx.match(blocks, 64)
    idx.update("http://b:1", {"enabled": False})
    assert len(idx) == 0


def test_epp_pool_scoring_and_fetch_headers():
    from kaito_tpu.runtime.epp import EndpointPicker, RequestCtx

    a, b = "http://a:1", "http://b:1"
    picker = EndpointPicker([a, b], kv_pool=True)
    assert any(t == "kv-pool-scorer" for t, _ in picker.plugins)
    text = "steering prompt " * 32
    blocks = prefix_blocks(text, picker.block_chars)
    picker.pool_index.update(a, _advert([blocks], picker.block_chars))
    body = json.dumps({"prompt": text}).encode()
    ctx = picker.make_ctx("POST", "/v1/completions", body)
    assert a in ctx.pool_match and b not in ctx.pool_match
    ba = next(x for x in picker.backends if x.url == a)
    bb = next(x for x in picker.backends if x.url == b)
    # the holder outscores the non-holder (route-to-holder)
    assert picker._score(ba, ctx) > picker._score(bb, ctx)
    # picked the holder: no fetch hint
    assert picker.request_headers(ctx, ba) == {}
    # picked the non-holder: hint names the holder + entry key
    hdrs = picker.request_headers(ctx, bb)
    assert hdrs == {"X-Kaito-KV-Fetch": a,
                    "X-Kaito-KV-Fetch-Key": pool_key(blocks)}
    # a saturated holder earns no pool score -> load steers away, and
    # the pick then carries the fetch hint
    ba.saturated = True
    assert picker._score(ba, ctx) == pytest.approx(
        picker._score(bb, ctx))
    picker.note_response(bb, ctx, 200)
    assert picker.m_pool_fetch.value() == 1.0
    picker.note_response(ba, ctx, 200)
    assert picker.m_pool_route.value() == 1.0
    # dead holder: advert is stale, no hint (fall back to recompute)
    ba.mark_down()
    assert picker.request_headers(ctx, bb) == {}
    # pool off: no index, no scorer, no pool metric families
    plain = EndpointPicker([a, b])
    assert plain.pool_index is None
    assert not any(t == "kv-pool-scorer" for t, _ in plain.plugins)
    assert "kv_pool" not in plain.registry.expose()
    cold = plain.make_ctx("POST", "/v1/completions", body)
    assert isinstance(cold, RequestCtx) and cold.pool_match == {}


def test_epp_pool_registry_round_trips():
    """Promtext round-trip for the new EPP families (the pool-off
    exposition is covered by the equality check above)."""
    from kaito_tpu.runtime.epp import EndpointPicker
    from kaito_tpu.utils.promtext import check_histograms, parse_exposition

    picker = EndpointPicker(["http://a:1"], kv_pool=True)
    picker.m_pool_route.inc()
    picker.m_pool_fetch.inc()
    # check_histograms needs at least one observed bucket series
    picker.upstream_latency.observe(0.02, backend="http://a:1")
    samples = parse_exposition(picker.registry.expose())
    check_histograms(samples)
    names = {n for n, _, _ in samples}
    assert {"kaito:epp_kv_pool_holder_routed_total",
            "kaito:epp_kv_pool_fetch_hints_total",
            "kaito:epp_kv_pool_index_size"} <= names


# ---------------------------------------------------------------------------
# engine integration: gating + publish/fetch over the real wire
# ---------------------------------------------------------------------------

CFG = dict(model="tiny-llama-test", max_model_len=256, page_size=16,
           max_num_seqs=2, dtype="float32", kv_dtype="float32",
           prefill_buckets=(64, 128), seed=0)


def _boot(**over):
    from kaito_tpu.engine.config import EngineConfig
    from kaito_tpu.engine.engine import InferenceEngine
    from kaito_tpu.engine.server import make_server

    cfg = EngineConfig(**{**CFG, **over})
    eng = InferenceEngine(cfg)
    eng.start()
    srv = make_server(eng, cfg, host="127.0.0.1", port=0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return eng, srv, f"http://127.0.0.1:{srv.server_address[1]}"


def _post(url, body, headers=None):
    req = urllib.request.Request(
        url + "/v1/completions", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    return json.loads(urllib.request.urlopen(req, timeout=120).read())


def test_pool_disabled_is_invisible():
    """Default-off gate: no pool store, pool routes 403, and the
    /metrics exposition carries NO kv_pool family (the byte-identical
    guarantee — a family would change the payload even at zero)."""
    eng, srv, url = _boot()
    try:
        assert eng.kv_pool is None
        _post(url, {"prompt": "gate probe", "max_tokens": 2,
                    "temperature": 0.0})
        body = urllib.request.urlopen(url + "/metrics",
                                      timeout=30).read().decode()
        assert "kv_pool" not in body
        # host-tier families are unconditional (offload satellite)
        for fam in ("kaito:host_kv_entries", "kaito:host_kv_hits_total",
                    "kaito:host_kv_misses_total",
                    "kaito:host_kv_evictions_total"):
            assert fam in body
        for path in ("/debug/kv_pool", "/kv_pool/abc/meta",
                     "/kv_pool/abc/chunk/0"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(url + path, timeout=10)
            assert ei.value.code == 403
    finally:
        srv.shutdown()
        eng.stop()


def test_publish_fetch_import_greedy_parity():
    """Two live engine servers: A publishes a finished prompt's prefix,
    B is handed the EPP-style fetch headers and imports it over the
    chunked wire — and B's output must match A's local compute exactly
    (same seed => same weights; the pool can remove work, never change
    results).  B never sees the prompt before the fetch, so the
    replication check below proves the import path populated B's own
    store."""
    a_eng, a_srv, a_url = _boot(kv_pool_enabled=True)
    b_eng, b_srv, b_url = _boot(kv_pool_enabled=True)
    try:
        prompt = "cluster pool parity check " * 8
        a_out = _post(a_url, {"prompt": prompt, "max_tokens": 6,
                              "temperature": 0.0})
        assert a_eng.counters["kv_pool_published_total"] == 1
        adv = json.loads(urllib.request.urlopen(
            a_url + "/debug/kv_pool", timeout=10).read())
        assert adv["enabled"] and len(adv["entries"]) == 1
        key = adv["entries"][0]["key"]
        # meta handshake counts ONE hit; chunk pulls must not inflate it
        out = _post(b_url, {"prompt": prompt, "max_tokens": 6,
                            "temperature": 0.0},
                    headers={"X-Kaito-KV-Fetch": a_url,
                             "X-Kaito-KV-Fetch-Key": key})
        assert out["choices"][0]["text"] == a_out["choices"][0]["text"]
        assert b_eng.counters["kv_pool_fetches_total"] == 1
        assert b_eng.counters["kv_pool_fetched_tokens_total"] > 0
        assert b_eng.counters["kv_pool_fetch_failures_total"] == 0
        assert a_eng.kv_pool.hits_total == 1
        # B replicated the fetched prefix into its OWN store (the pool
        # heals toward N holders, so A can scale down safely)
        assert b_eng.kv_pool.has(key)
        # pool metric families exist on an enabled engine
        body = urllib.request.urlopen(b_url + "/metrics",
                                      timeout=30).read().decode()
        for fam in ("kaito:kv_pool_entries", "kaito:kv_pool_bytes_used",
                    "kaito:kv_pool_fetches_total",
                    "kaito:kv_pool_published_total"):
            assert fam in body
        # promtext round-trip over the enabled exposition
        from kaito_tpu.utils.promtext import (check_histograms,
                                              parse_exposition)
        check_histograms(parse_exposition(body))
        # a bogus key 404s the handshake (fetch degrades to recompute)
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                a_url + "/kv_pool/0123456789abcdef/meta", timeout=10)
        assert ei.value.code == 404
    finally:
        for s in (a_srv, b_srv):
            s.shutdown()
        a_eng.stop()
        b_eng.stop()


def test_fetch_failure_falls_back_to_local_recompute():
    """A fetch hint naming a DEAD holder must not fail or corrupt the
    request: the handshake fails, the submit falls back to a plain
    local prefill, and the output is unchanged."""
    b_eng, b_srv, b_url = _boot(kv_pool_enabled=True)
    try:
        prompt = "failover pool prompt " * 8
        ref = _post(b_url, {"prompt": prompt, "max_tokens": 5,
                            "temperature": 0.0})
        out = _post(b_url, {"prompt": prompt, "max_tokens": 5,
                            "temperature": 0.0},
                    headers={"X-Kaito-KV-Fetch": "http://127.0.0.1:9",
                             "X-Kaito-KV-Fetch-Key": "feedfacefeedface"})
        assert out["choices"][0]["text"] == ref["choices"][0]["text"]
        assert b_eng.counters["kv_pool_fetches_total"] == 0
    finally:
        b_srv.shutdown()
        b_eng.stop()


# ---------------------------------------------------------------------------
# e2e: warm TTFT survives scale-out (slow tier)
# ---------------------------------------------------------------------------

# slow: compares two wall-clock TTFTs of one process (`warm_ttft <
# cold_ttft`); its twin in test_kv_tier.py fails on a loaded box
@pytest.mark.slow
def test_warm_ttft_survives_scaleout():
    """The headline: replica A holds a warm prefix and is draining
    (rollout/scale-down); replica B just scaled up cold.  The EPP
    orders draining replicas last, picks B, and stamps the fetch hint —
    B pulls A's prefix over the wire and its first warm hit beats its
    own cold TTFT on an equal-length prompt, with the cross-replica
    fetch visible in the EPP's and B's counters."""
    from kaito_tpu.runtime.epp import EndpointPicker, KVPoolScraper
    from tests.helpers.dp_cluster import serve_front

    over = dict(max_model_len=1024, prefill_buckets=(128, 512, 1024),
                kv_pool_enabled=True)
    a_eng, a_srv, a_url = _boot(**over)
    b_eng, b_srv, b_url = _boot(**over)
    try:
        # equal char length -> near-equal token counts, so the two TTFT
        # measurements prefill the same bucket
        # byte-level tokenizer: ~1 token/char, so 28*30 ≈ 841 tokens —
        # inside max_model_len=1024 and prefilling the 1024 bucket.
        # All four are EXACTLY 28 chars/unit: compiled programs are
        # keyed on the request's token-length class, so the warmups
        # must share the class the measurements run in
        warm_prompt = "warm shared prefix abcdefgh " * 30
        cold_prompt = "cold unlike prefix abcdefgh " * 30
        compile_prompt = "xla compiling prefix watchy " * 30
        pull_prompt = "pull path compile prefix ab " * 30
        # compile B's big prefill bucket AND the small one the warm
        # path's remainder-prefill uses, so neither measurement pays XLA
        _post(b_url, {"prompt": compile_prompt, "max_tokens": 2,
                      "temperature": 0.0})
        _post(b_url, {"prompt": "short warmup", "max_tokens": 2,
                      "temperature": 0.0})
        # A computes + publishes the warm prefix, plus a sacrificial
        # prefix used only to pre-compile B's fetch/import path
        _post(a_url, {"prompt": pull_prompt, "max_tokens": 2,
                      "temperature": 0.0})
        _post(a_url, {"prompt": warm_prompt, "max_tokens": 2,
                      "temperature": 0.0})
        assert a_eng.counters["kv_pool_published_total"] >= 2

        picker = EndpointPicker([a_url, b_url], kv_pool=True,
                                block_chars=16 * 4)
        picker.set_draining(a_url)
        scraper = KVPoolScraper(picker, interval_s=3600.0)
        scraper.poll_pass()
        for _ in range(100):
            if len(picker.pool_index):
                break
            time.sleep(0.05)
        assert len(picker.pool_index) > 0

        with serve_front(picker) as front:
            # one throwaway fetch first: B compiles the prefix-import +
            # remainder-prefill programs so the measured warm request
            # pays only the transfer, not XLA compilation
            _post(front, {"prompt": pull_prompt, "max_tokens": 1,
                          "temperature": 0.0})
            assert b_eng.counters["kv_pool_fetches_total"] == 1
            t0 = time.monotonic()
            _post(front, {"prompt": cold_prompt, "max_tokens": 1,
                          "temperature": 0.0})
            cold_ttft = time.monotonic() - t0
            t0 = time.monotonic()
            _post(front, {"prompt": warm_prompt, "max_tokens": 1,
                          "temperature": 0.0})
            warm_ttft = time.monotonic() - t0
        # all requests landed on B (A is draining)
        assert b_eng.counters["kv_pool_fetches_total"] == 2
        assert b_eng.counters["kv_pool_fetched_tokens_total"] > 0
        # the EPP recorded the cross-replica fetch it brokered
        assert picker.m_pool_fetch.value() >= 1.0
        # the warm hit beat the cold prefill
        assert warm_ttft < cold_ttft, (warm_ttft, cold_ttft)
    finally:
        for s in (a_srv, b_srv):
            s.shutdown()
        a_eng.stop()
        b_eng.stop()
