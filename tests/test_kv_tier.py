"""Session-scale KV tier 3 (docs/kv-pool.md "Tier 3: SSD"): the disk
slab store under the cluster pool's host LRU, the spill-on-evict wiring,
the local host/SSD probe ahead of remote fetch, the break-even veto, the
capped advert + EPP merge, the conversation session pin, and the
annotation plumbing.  The fast live-engine tests replay a multi-turn
conversation through a forced eviction and prove the turn-N import is
bit-equal to recompute; the slow e2e proves the EPP session pin turns
into a real TTFT win."""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from kaito_tpu.engine.kv_pool import (DiskPageStore, HostExport, PoolEntry,
                                      PrefixPageStore, pool_key,
                                      prompt_pool_blocks)

# ---------------------------------------------------------------------------
# DiskPageStore units
# ---------------------------------------------------------------------------

def _export(seed=0, n_pages=4, page_size=4, layers=2, heads=2, dim=8,
            tok0=100):
    rng = np.random.default_rng(seed)
    shape = (layers, n_pages, page_size, heads, dim)
    k = rng.integers(-128, 127, shape).astype(np.int8)
    v = rng.integers(-128, 127, shape).astype(np.int8)
    ks = rng.random((layers, n_pages, heads), np.float32)
    vs = rng.random((layers, n_pages, heads), np.float32)
    return HostExport(k, v, ks, vs, n_tokens=n_pages * page_size, model="m",
                      prompt_tokens=list(range(tok0,
                                               tok0 + n_pages * page_size)))


def _disk_entry(blocks, seed=0, **kw):
    exp = _export(seed=seed, **kw)
    nbytes = sum(len(exp.get_chunk(i)) for i in range(len(exp.plans)))
    return PoolEntry(key=pool_key(blocks), blocks=list(blocks),
                     n_tokens=exp.meta["n_tokens"],
                     n_pages=len(blocks), export=exp, nbytes=nbytes)


def test_disk_store_spill_lookup_read_roundtrip(tmp_path):
    """The slab on disk is the WIRE format: a spilled entry reads back
    chunk-for-chunk byte-identical to what the export would have served
    over /kv_pool/<key>/chunk/<i> (int8 scale slabs included), and
    ``lookup_longest`` walks the block chain deepest-first exactly like
    the host-store probe."""
    store = DiskPageStore(str(tmp_path), max_bytes=1 << 20)
    blocks = [0x1111, 0x2222, 0x3333]
    entry = _disk_entry(blocks)
    assert store.spill(entry)
    assert store.spills_total == 1 and len(store) == 1
    assert store.used_bytes > 0
    # spilling the same key again is a no-op, not a double-count
    assert store.spill(entry)
    assert store.spills_total == 1

    # longest-prefix lookup: the full chain hits; an extended chain
    # (deeper request) still finds the stored prefix underneath it
    hit = store.lookup_longest(blocks + [0x4444])
    assert hit is not None
    key, meta = hit
    assert key == pool_key(blocks)
    assert store.hits_total == 1
    assert meta["n_tokens"] == entry.n_tokens
    assert meta["prompt_tokens"] == entry.export.prompt_tokens
    assert meta["blocks"] == [f"{b:016x}" for b in blocks]
    # chunk reads are byte-identical to the live export's wire chunks
    exp = entry.export
    for i in range(len(exp.plans)):
        assert store.read_chunk(key, i, meta) == exp.get_chunk(i)
    with pytest.raises(IndexError):
        store.read_chunk(key, len(exp.plans), meta)
    # an unrelated chain misses (and counts ONE miss for the walk)
    assert store.lookup_longest([0xdead, 0xbeef]) is None
    assert store.misses_total == 1


def test_disk_store_restart_scan_and_orphan_cleanup(tmp_path):
    """Restart survival: a fresh store over the same root re-indexes
    complete entries (meta+slab) and deletes the debris an interrupted
    spill can leave — an orphan slab without meta, and tmp files."""
    store = DiskPageStore(str(tmp_path), max_bytes=1 << 20)
    blocks = [0xaaaa, 0xbbbb]
    entry = _disk_entry(blocks, seed=1)
    assert store.spill(entry)
    # debris: slab-without-meta (crash between the two renames) + tmps
    (tmp_path / ("f" * 16 + ".slab")).write_bytes(b"orphan")
    (tmp_path / ("e" * 16 + ".slab.tmp")).write_bytes(b"partial")
    store2 = DiskPageStore(str(tmp_path), max_bytes=1 << 20)
    assert len(store2) == 1
    assert store2.used_bytes == store.used_bytes
    hit = store2.lookup_longest(blocks)
    assert hit is not None and hit[0] == pool_key(blocks)
    assert not (tmp_path / ("f" * 16 + ".slab")).exists()
    assert not (tmp_path / ("e" * 16 + ".slab.tmp")).exists()


def test_disk_store_budget_prune_lru(tmp_path):
    """mtime-LRU prune: over budget, the oldest-touched entry goes
    first; a read refreshes (touch) so live conversations survive."""
    store = DiskPageStore(str(tmp_path), max_bytes=1 << 20)
    a, b = [0x0a0a], [0x0b0b]
    assert store.spill(_disk_entry(a, seed=2, n_pages=2))
    one = store.used_bytes
    assert store.spill(_disk_entry(b, seed=3, n_pages=2))
    # age BOTH metas way back, then touch a via a read: the touch must
    # protect it when the third spill overflows the budget
    import os
    meta_a = tmp_path / (pool_key(a) + ".json")
    os.utime(meta_a, (1.0, 1.0))
    meta_b = tmp_path / (pool_key(b) + ".json")
    os.utime(meta_b, (2.0, 2.0))
    assert store.lookup_longest(a) is not None      # touches a
    store.max_bytes = 2 * one + 1                   # room for two entries
    assert store.spill(_disk_entry([0x0c0c], seed=4, n_pages=2))
    # b (oldest mtime now) was evicted; a survived its touch
    assert store.lookup_longest(a) is not None
    assert store.lookup_longest(b) is None
    assert store.evictions_total >= 1
    # an entry bigger than the whole budget is refused outright
    store.max_bytes = 8
    assert not store.spill(_disk_entry([0x0d0d], seed=5))


def test_disk_store_rejects_hostile_keys(tmp_path):
    """Keys are our own 16-hex pool_key strings; anything else (path
    traversal, wrong width) is refused before touching the fs."""
    store = DiskPageStore(str(tmp_path), max_bytes=1 << 20)
    for bad in ("../../etc/passwd", "ABCDEF0123456789",  # upper hex
                "0123", "z" * 16, "0123456789abcdef0"):
        with pytest.raises(ValueError):
            store._paths(bad)
    store._paths("0123456789abcdef")                # canonical ok


def test_disk_store_corruption_drops_cleanly(tmp_path):
    """Corrupt meta -> load_meta returns None and the entry is gone;
    truncated slab -> read_chunk raises (the import machinery turns
    that into a clean recompute) and the entry is dropped."""
    store = DiskPageStore(str(tmp_path), max_bytes=1 << 20)
    blocks = [0x5a5a, 0x6b6b]
    assert store.spill(_disk_entry(blocks, seed=6))
    key = pool_key(blocks)
    # corrupt the meta json
    (tmp_path / (key + ".json")).write_bytes(b"{not json")
    assert store.lookup_longest(blocks) is None
    assert store.errors_total == 1 and len(store) == 0
    assert not (tmp_path / (key + ".slab")).exists()
    # re-spill, then truncate the slab under intact meta
    entry = _disk_entry(blocks, seed=6)
    assert store.spill(entry)
    hit = store.lookup_longest(blocks)
    assert hit is not None
    key, meta = hit
    (tmp_path / (key + ".slab")).write_bytes(b"x")
    with pytest.raises(ValueError, match="truncated"):
        store.read_chunk(key, 0, meta)
    assert len(store) == 0                          # dropped on detect
    assert store.errors_total == 2


# ---------------------------------------------------------------------------
# break-even veto
# ---------------------------------------------------------------------------

def test_should_import_from_disk_measured_rates_only():
    """Priors never veto (same discipline as the remote-fetch path):
    the veto fires only when BOTH the SSD read rate and the prefill
    rate have real samples and the read loses."""
    from kaito_tpu.engine.pd import TransferCostModel, \
        should_import_from_disk

    assert should_import_from_disk(1 << 30, 16, None)
    m = TransferCostModel()
    assert should_import_from_disk(1 << 30, 16, m)         # no samples
    m.note_disk_read(100 * 1024 * 1024, 1.0)               # 100 MB/s
    assert should_import_from_disk(1 << 30, 16, m)         # prefill unknown
    m.note_prefill(1000, 1.0)                              # 1000 tok/s
    # 1 GiB read at 100 MB/s ~ 10.7 s vs 16 tokens ~ 16 ms: veto
    assert not should_import_from_disk(1 << 30, 16, m)
    # 1 MB read ~ 10 ms vs 1000 tokens ~ 1 s: import wins
    assert should_import_from_disk(1 << 20, 1000, m)
    snap = m.snapshot()
    assert snap["disk_samples"] == 1 and snap["disk_bytes_s"] > 0


# ---------------------------------------------------------------------------
# capped advert + EPP merge (satellite)
# ---------------------------------------------------------------------------

def _entry(key, nbytes=10):
    return PoolEntry(key=key, blocks=[1, 2], n_tokens=8, n_pages=2,
                     export=None, nbytes=nbytes)


def test_advert_cap_keeps_freshest_n():
    store = PrefixPageStore(max_bytes=1000)
    for k in ("a" * 16, "b" * 16, "c" * 16, "d" * 16):
        store.put(_entry(k))
    store.get("b" * 16)                         # b is now freshest
    adv = store.advert(max_entries=2)
    assert [e["key"] for e in adv] == ["b" * 16, "d" * 16]
    # 0 = uncapped, freshest first (existing contract)
    assert len(store.advert()) == 4
    assert store.advert()[0]["key"] == "b" * 16


def test_kv_pool_index_capped_merge():
    """A capped advert is authoritative only for the rows it lists:
    previously-advertised entries stay in the index (bounded), while a
    FULL advert wholesale-replaces — and the per-URL bound holds."""
    from kaito_tpu.runtime.epp import KVPoolIndex
    from kaito_tpu.runtime.routing import prefix_blocks

    idx = KVPoolIndex()
    url = "http://a:1"
    chains = [prefix_blocks(f"prompt {i} " + "x" * 200, 64)
              for i in range(4)]

    def adv(cs, capped):
        return {"enabled": True, "page_size": 16, "block_chars": 64,
                "capped": capped,
                "entries": [{"key": pool_key(b), "n_tokens": len(b) * 16,
                             "blocks": [f"{h:016x}" for h in b]}
                            for b in cs]}

    idx.update(url, adv(chains[:2], capped=False))
    assert idx.match(chains[0], 64) and idx.match(chains[1], 64)
    # capped advert listing only chain 2: 0 and 1 must SURVIVE
    idx.update(url, adv([chains[2]], capped=True))
    for c in chains[:3]:
        assert url in idx.match(c, 64), "capped merge lost a row"
    # full advert listing only chain 3: everything else drops
    idx.update(url, adv([chains[3]], capped=False))
    assert url in idx.match(chains[3], 64)
    for c in chains[:3]:
        assert idx.match(c, 64) == {}
    # the per-URL bound actually bounds a capped-merge accumulation
    idx.update(url, adv(chains[:2], capped=True))
    with idx._lock:
        assert len(idx._adverts[url]["entries"]) <= \
            KVPoolIndex.MAX_ENTRIES_PER_URL


# ---------------------------------------------------------------------------
# session pin (routing index + EPP)
# ---------------------------------------------------------------------------

def test_session_pin_index_units():
    from kaito_tpu.runtime.routing import PrefixAffinityIndex

    idx = PrefixAffinityIndex(session_capacity=3)
    assert idx.session_holder("conv") is None
    idx.record_session("conv", "http://a:1")
    assert idx.session_holder("conv") == "http://a:1"
    assert idx.session_count() == 1
    # re-pin moves the conversation (failover)
    idx.record_session("conv", "http://b:1")
    assert idx.session_holder("conv") == "http://b:1"
    # capacity bound evicts the least-recently-used conversation
    for i in range(3):
        idx.record_session(f"s{i}", "http://a:1")
    assert idx.session_count() == 3
    assert idx.session_holder("conv") is None
    # a dead backend takes its pins down with it
    assert idx.session_holder("s2") == "http://a:1"
    idx.drop_backend("http://a:1")
    assert idx.session_holder("s2") is None


def test_epp_session_pin_routes_and_counts():
    """Turn N goes to turn N-1's holder ahead of score order; a
    saturated holder forfeits the pin; counters prove the routing."""
    from kaito_tpu.runtime.epp import EndpointPicker

    a, b = "http://a:1", "http://b:1"
    picker = EndpointPicker([a, b], kv_pool=True)
    body = json.dumps({"prompt": "session turn " * 8}).encode()
    ctx = picker.make_ctx("POST", "/v1/completions", body,
                          headers={"X-Kaito-Session": "conv-7"})
    assert ctx.session == "conv-7"
    bb = next(x for x in picker.backends if x.url == b)
    # turn 1: no pin yet -> scored order; serving records the pin
    picker.note_response(bb, ctx, 200)
    assert picker.index.session_holder("conv-7") == b
    # turn 2: pinned backend jumps the queue regardless of score
    ctx2 = picker.make_ctx("POST", "/v1/completions", body,
                           headers={"X-Kaito-Session": "conv-7"})
    first = next(iter(picker.candidates(
        "POST", "/v1/completions", ctx2)))
    assert first.url == b
    picker.note_response(first, ctx2, 200)
    assert picker.m_session_pin_routed.value() == 1.0
    # a saturated holder forfeits the pin (request would just queue)
    bb.saturated = True
    ctx3 = picker.make_ctx("POST", "/v1/completions", body,
                           headers={"X-Kaito-Session": "conv-7"})
    first = next(iter(picker.candidates(
        "POST", "/v1/completions", ctx3)))
    assert first.url == a
    picker.note_response(first, ctx3, 200)
    assert picker.m_session_pin_misses.value() == 1.0
    # ...and serving on A moved the pin there
    assert picker.index.session_holder("conv-7") == a
    # 5xx must NOT re-pin (the turn didn't land)
    bb.saturated = False
    ctx4 = picker.make_ctx("POST", "/v1/completions", body,
                           headers={"X-Kaito-Session": "conv-7"})
    picker.note_response(bb, ctx4, 503)
    assert picker.index.session_holder("conv-7") == a
    # exposition carries the families (pool on)
    body_m = picker.registry.expose()
    for fam in ("kaito:epp_session_pin_routed_total",
                "kaito:epp_session_pin_misses_total",
                "kaito:epp_session_pins"):
        assert fam in body_m


def test_epp_session_pin_gated_by_kv_pool():
    """Pool off: the session header is still parsed (tracing parity)
    but pins neither route nor register, and the exposition carries no
    session family — byte-identical to pre-PR."""
    from kaito_tpu.runtime.epp import EndpointPicker

    plain = EndpointPicker(["http://a:1", "http://b:1"])
    body = json.dumps({"prompt": "x"}).encode()
    ctx = plain.make_ctx("POST", "/v1/completions", body,
                         headers={"X-Kaito-Session": "conv"})
    bb = plain.backends[1]
    plain.note_response(bb, ctx, 200)
    assert plain.index.session_count() == 0
    assert "session" not in plain.registry.expose()


# ---------------------------------------------------------------------------
# annotation plumbing
# ---------------------------------------------------------------------------

def test_parse_kv_pool_disk_annotation():
    from kaito_tpu.manifests.inference import parse_kv_pool_disk_annotation

    on = "true"
    assert parse_kv_pool_disk_annotation("", on) is None
    for text in ("off", "false", "0", "  "):
        assert parse_kv_pool_disk_annotation(text, on) is None
    assert parse_kv_pool_disk_annotation("20Gi", on) == 20 * (1 << 30)
    assert parse_kv_pool_disk_annotation("500M", on) == 500 * 10 ** 6
    assert parse_kv_pool_disk_annotation("1048576", on) == 1 << 20
    with pytest.raises(ValueError, match="byte quantity"):
        parse_kv_pool_disk_annotation("lots", on)
    # a disk budget without the pool is a plan-time error, not a pod
    # that boots with a dead flag
    with pytest.raises(ValueError, match="requires"):
        parse_kv_pool_disk_annotation("20Gi", "")
    with pytest.raises(ValueError, match="requires"):
        parse_kv_pool_disk_annotation("20Gi", "false")


# ---------------------------------------------------------------------------
# live engine: multi-turn replay through a forced eviction
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


def _force_spill(eng, url, prompt, evict_prompt):
    """Publish ``prompt``, shrink the host store so publishing
    ``evict_prompt`` evicts it, and wait for the spill worker to land
    it on SSD.  Returns the reference completion text."""
    ref = _post(url, {"prompt": prompt, "max_tokens": 6,
                      "temperature": 0.0})
    assert eng.kv_pool.used_bytes > 0
    # room for ~1.5 entries: the next equal-sized publish must evict
    eng.kv_pool.max_bytes = eng.kv_pool.used_bytes * 3 // 2
    _post(url, {"prompt": evict_prompt, "max_tokens": 6,
                "temperature": 0.0})
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if eng.kv_tier.spills_total >= 1:
            break
        time.sleep(0.05)
    assert eng.kv_tier.spills_total >= 1, "spill worker never landed"
    return ref["choices"][0]["text"]


def test_multiturn_replay_imports_from_disk(tmp_path):
    """The headline smoke: turn 1 publishes, a later conversation
    evicts it from host RAM, the spill worker lands it on SSD, and the
    replayed turn imports from the disk tier — bit-equal greedy output
    vs the original recompute, with the hit visible in the counters
    and the labeled metric family."""
    # both prompts are exactly 36 chars/unit so their pool entries are
    # the same size (the shrunken budget must ADMIT the evictor)
    prompt = "conversation turn one about tensors " * 6
    evictor = "unrelated second conversation filler " * 6
    eng, srv, url = _boot(kv_pool_enabled=True,
                          kv_pool_disk_bytes=1 << 30,
                          kv_pool_disk_dir=str(tmp_path))
    try:
        assert eng.kv_tier is not None
        ref = _force_spill(eng, url, prompt, evictor)
        key = pool_key(prompt_pool_blocks(prompt, CFG["page_size"]))
        assert not eng.kv_pool.has(key), "eviction never happened"
        assert eng.kv_tier.has(key)
        out = _post(url, {"prompt": prompt, "max_tokens": 6,
                          "temperature": 0.0})
        assert out["choices"][0]["text"] == ref
        assert eng.counters["kv_tier_disk_hits_total"] == 1
        assert eng.counters["kv_tier_import_tokens_total"] > 0
        assert eng.counters["kv_pool_fetch_failures_total"] == 0
        body = urllib.request.urlopen(url + "/metrics",
                                      timeout=30).read().decode()
        assert 'kaito:kv_tier_hits_total{tier="disk"} 1' in body
        assert "kaito:kv_tier_spills_total" in body
        from kaito_tpu.utils.promtext import (check_histograms,
                                              parse_exposition)
        check_histograms(parse_exposition(body))
        # the timed slab read calibrated the break-even EWMA
        assert eng.pd_costs.snapshot()["disk_samples"] >= 1
    finally:
        srv.shutdown()
        eng.stop()


def test_corrupt_slab_falls_back_to_recompute(tmp_path):
    """A truncated slab under intact meta must not fail the request:
    the feeder errors, the engine's prefix-import error path ticks
    kv_pool_fetch_failures_total and requeues a clean full local
    prefill — same greedy output, no crash."""
    import os
    prompt = "replayed conversation with a damaged " * 6
    evictor = "other talk pushing the first one out " * 6
    eng, srv, url = _boot(kv_pool_enabled=True,
                          kv_pool_disk_bytes=1 << 30,
                          kv_pool_disk_dir=str(tmp_path))
    try:
        ref = _force_spill(eng, url, prompt, evictor)
        key = pool_key(prompt_pool_blocks(prompt, CFG["page_size"]))
        slab = os.path.join(str(tmp_path), key + ".slab")
        with open(slab, "wb") as f:
            f.write(b"x")                       # truncate to 1 byte
        out = _post(url, {"prompt": prompt, "max_tokens": 6,
                          "temperature": 0.0})
        assert out["choices"][0]["text"] == ref
        assert eng.counters["kv_tier_disk_hits_total"] == 1
        assert eng.counters["kv_pool_fetch_failures_total"] == 1
        assert eng.kv_tier.errors_total >= 1
        assert not eng.kv_tier.has(key)         # dropped on detect
    finally:
        srv.shutdown()
        eng.stop()


def test_disk_tier_off_is_invisible():
    """Gate: pool on but disk budget 0 -> no tier store, no spill
    thread, and the /metrics exposition carries NO kv_tier family (the
    byte-identical guarantee)."""
    eng, srv, url = _boot(kv_pool_enabled=True)
    try:
        assert eng.kv_tier is None
        assert eng._spill_thread is None
        assert eng.kv_pool.on_evict is None
        _post(url, {"prompt": "gate probe", "max_tokens": 2,
                    "temperature": 0.0})
        body = urllib.request.urlopen(url + "/metrics",
                                      timeout=30).read().decode()
        assert "kv_tier" not in body
    finally:
        srv.shutdown()
        eng.stop()


# ---------------------------------------------------------------------------
# e2e: session pin turns into a TTFT win (slow tier)
# ---------------------------------------------------------------------------

# slow: fails under the check's command: `assert ttft2 < ttft1` compares two
# wall-clock times of one loaded process (1.084 against 0.837 s)
@pytest.mark.slow
def test_session_pin_ttft_beats_turn_one(tmp_path):
    """The conversation headline: turn 1 lands somewhere and pins the
    session; turn 2 (history + new user message) is routed BY THE PIN
    to the same replica, whose host tier imports the turn-1 prefix —
    so turn 2's TTFT beats turn 1's cold full prefill even though its
    prompt is longer, with the pin proven from the EPP counters."""
    from kaito_tpu.runtime.epp import EndpointPicker
    from tests.helpers.dp_cluster import serve_front

    over = dict(max_model_len=1024, prefill_buckets=(128, 512, 1024),
                kv_pool_enabled=True, kv_pool_disk_bytes=1 << 30)
    a_eng, a_srv, a_url = _boot(kv_pool_disk_dir=str(tmp_path / "a"),
                                **over)
    b_eng, b_srv, b_url = _boot(kv_pool_disk_dir=str(tmp_path / "b"),
                                **over)
    try:
        # byte-level tokenizer ~1 token/char; every unit is EXACTLY 28
        # chars.  turn1 ~ 840 tokens (1024 bucket); turn2 adds a short
        # suffix so its remainder-prefill lands in the 128 bucket.
        turn1 = "conversation system history  " * 30
        suffix = "and the new user question ab "
        compile1 = "xla compile long bucket fill " * 30
        # pre-compile BOTH replicas directly (no front): the long
        # bucket, then the host-tier import + short-remainder program
        # via a sacrificial two-turn conversation
        for u in (a_url, b_url):
            _post(u, {"prompt": compile1, "max_tokens": 1,
                      "temperature": 0.0})
            _post(u, {"prompt": compile1 + suffix, "max_tokens": 1,
                      "temperature": 0.0})
        for eng in (a_eng, b_eng):
            assert eng.counters["kv_tier_host_hits_total"] >= 1, \
                "import path never compiled"

        picker = EndpointPicker([a_url, b_url], kv_pool=True,
                                block_chars=16 * 4)
        with serve_front(picker) as front:
            hdr = {"X-Kaito-Session": "conv-e2e"}
            t0 = time.monotonic()
            _post(front, {"prompt": turn1, "max_tokens": 1,
                          "temperature": 0.0}, headers=hdr)
            ttft1 = time.monotonic() - t0
            t0 = time.monotonic()
            _post(front, {"prompt": turn1 + suffix, "max_tokens": 1,
                          "temperature": 0.0}, headers=hdr)
            ttft2 = time.monotonic() - t0
        # the pin routed turn 2 to turn 1's holder...
        assert picker.m_session_pin_routed.value() >= 1.0
        holder = picker.index.session_holder("conv-e2e")
        eng = a_eng if holder == a_url else b_eng
        # ...whose host tier served the history instead of recompute
        assert eng.counters["kv_tier_host_hits_total"] >= 2
        # and the warm turn beat the cold one despite the longer prompt
        assert ttft2 < ttft1, (ttft1, ttft2)
    finally:
        for s in (a_srv, b_srv):
            s.shutdown()
        a_eng.stop()
        b_eng.stop()
