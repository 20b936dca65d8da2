"""int8 KV-cache quantization: pure-function parity and error bounds.

Fast tier (no engine boots): exercises the quantizing page writes and
dequant reads in kaito_tpu.engine.kv_cache, the in-kernel dequant of
the Pallas decode kernel (interpreter mode), the P/D wire format with
page scales, and the capacity / transfer-cost arithmetic the estimator
and router build on.  End-to-end int8 serving is pinned separately by
the golden tests in test_real_checkpoint.py.
"""

from datetime import datetime, timezone

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kaito_tpu.engine.kv_cache import (
    KVCache, create_kv_cache, dequantize_pages, kv_cache_is_quantized,
    scale_bytes_per_page, write_decode_tokens_q, write_prefill_tokens_q)
from kaito_tpu.models.registry import get_model_by_name
from tests.helpers.decode_kernel_cases import CASES as DECODE_CASES
from tests.helpers.decode_kernel_cases import (check_decode_case,
                                               quantize_pages)

PS = 16  # page size used throughout


def _arch():
    return get_model_by_name("tiny-llama-test").arch


def _quant_bound(x: np.ndarray) -> float:
    """Worst-case absolute error of absmax int8: sigma/2 per element."""
    return float(np.max(np.abs(x))) / 127.0 / 2.0 + 1e-6


# ---------------------------------------------------------------------------
# page-write round trips
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hkv,d", [(4, 32), (1, 32), (1, 48)],
                         ids=["gqa", "mqa", "mla-latent"])
def test_prefill_write_round_trip_bound(hkv, d):
    """write_prefill_tokens_q then dequantize_pages reproduces the
    chunk within the absmax-int8 bound, for the GQA / MQA / MLA-latent
    page shapes (MLA caches one latent head, same code path)."""
    rng = np.random.default_rng(0)
    B, T, P = 2, 24, 8
    new = rng.standard_normal((B, T, hkv, d)).astype(np.float32)
    cache = jnp.zeros((P, PS, hkv, d), jnp.int8)
    scales = jnp.zeros((P, hkv), jnp.float32)
    pt = jnp.asarray([[1, 2, 0, 0], [3, 4, 0, 0]], jnp.int32)
    start = jnp.zeros((2,), jnp.int32)
    true_lens = jnp.asarray([T, T - 5], jnp.int32)

    cache, scales = write_prefill_tokens_q(
        cache, scales, jnp.asarray(new), pt, start, true_lens, PS)
    deq = np.asarray(dequantize_pages(cache, scales))
    for b in range(B):
        for t in range(int(true_lens[b])):
            page, off = int(pt[b, t // PS]), t % PS
            got, want = deq[page, off], new[b, t]
            # per-head scale: bound by that head's absmax in the page
            for h in range(hkv):
                assert np.max(np.abs(got[h] - want[h])) <= _quant_bound(
                    new[b, :, h])


def test_decode_write_rescale_on_grow():
    """A later, larger token grows the page scale; earlier codes are
    re-expressed at the new scale and stay within the NEW bound.  Equal
    writes are drift-free (ratio exactly 1.0 in _requantize)."""
    hkv, d, P = 2, 16, 4
    cache = jnp.zeros((P, PS, hkv, d), jnp.int8)
    scales = jnp.zeros((P, hkv), jnp.float32)
    pt = jnp.asarray([[2, 0]], jnp.int32)
    rng = np.random.default_rng(1)
    small = rng.standard_normal((1, hkv, d)).astype(np.float32) * 0.1
    big = rng.standard_normal((1, hkv, d)).astype(np.float32) * 10.0

    cache, scales = write_decode_tokens_q(
        cache, scales, jnp.asarray(small), pt, jnp.asarray([0]), PS)
    s0 = np.asarray(scales[2]).copy()
    code0 = np.asarray(cache[2, 0]).copy()
    # re-writing the same token must not move codes or scales
    cache, scales = write_decode_tokens_q(
        cache, scales, jnp.asarray(small), pt, jnp.asarray([0]), PS)
    np.testing.assert_array_equal(np.asarray(cache[2, 0]), code0)
    np.testing.assert_array_equal(np.asarray(scales[2]), s0)

    cache, scales = write_decode_tokens_q(
        cache, scales, jnp.asarray(big), pt, jnp.asarray([1]), PS)
    s1 = np.asarray(scales[2])
    assert np.all(s1 >= s0) and np.any(s1 > s0)
    deq = np.asarray(dequantize_pages(cache, scales))
    assert np.max(np.abs(deq[2, 1] - big[0])) <= _quant_bound(big)
    # the earlier small token survives the rescale at the grown bound
    assert np.max(np.abs(deq[2, 0] - small[0])) <= _quant_bound(big)


def test_inactive_rows_hit_null_page_only():
    hkv, d, P = 2, 16, 4
    cache = jnp.zeros((P, PS, hkv, d), jnp.int8)
    scales = jnp.zeros((P, hkv), jnp.float32)
    pt = jnp.asarray([[3, 0]], jnp.int32)
    tok = jnp.ones((1, hkv, d), jnp.float32)
    cache, scales = write_decode_tokens_q(
        cache, scales, tok, pt, jnp.asarray([0]), PS,
        active=jnp.asarray([False]))
    assert int(jnp.sum(jnp.abs(cache[1:]))) == 0
    assert float(jnp.sum(scales[1:])) == 0.0


# ---------------------------------------------------------------------------
# kernel parity: pallas interpreter vs jax dequant fallback
# ---------------------------------------------------------------------------

def test_pallas_int8_decode_matches_jax():
    from kaito_tpu.engine.attention import paged_decode_attention
    from kaito_tpu.engine.ops.decode_attention import (
        paged_decode_attention_pallas)

    B, H, Hkv, D, P, pmax = 2, 4, 2, 32, 8, 4
    key = jax.random.PRNGKey(0)
    kq, kk, kv, kt, kl = jax.random.split(key, 5)
    q = jax.random.normal(kq, (B, H, D), jnp.float32)
    ck = jax.random.normal(kk, (P, PS, Hkv, D), jnp.float32)
    cv = jax.random.normal(kv, (P, PS, Hkv, D), jnp.float32)
    pt = jax.random.randint(kt, (B, pmax), 1, P, jnp.int32)
    lens = jax.random.randint(kl, (B,), PS, pmax * PS, jnp.int32)
    scale = D ** -0.5

    k8, ks = quantize_pages(ck)
    v8, vs = quantize_pages(cv)
    o_jax = paged_decode_attention(q, k8, v8, pt, lens, scale=scale,
                                   k_scale=ks, v_scale=vs)
    o_pl = paged_decode_attention_pallas(
        q, k8, v8, pt, lens, jnp.asarray(1 << 30, jnp.int32), scale=scale,
        k_scale=ks, v_scale=vs, interpret=True)
    np.testing.assert_allclose(np.asarray(o_pl), np.asarray(o_jax),
                               rtol=0, atol=2e-5)
    # and the whole quantized path stays close to full precision
    o_ref = paged_decode_attention(q, ck, cv, pt, lens, scale=scale)
    assert float(jnp.max(jnp.abs(o_pl - o_ref))) < 0.05


@pytest.mark.parametrize("name", list(DECODE_CASES))
def test_pallas_int8_decode_rows(name):
    """The decode kernel's row cases over int8 pages: the two scale
    rings ride the carried page ring slot for slot."""
    check_decode_case(DECODE_CASES[name], int8_kv=True)


# ---------------------------------------------------------------------------
# P/D wire format
# ---------------------------------------------------------------------------

def test_pd_chunk_round_trip_with_scales():
    from kaito_tpu.engine.pd import deserialize_chunk, serialize_chunk

    rng = np.random.default_rng(2)
    k = rng.integers(-127, 128, (2, 3, PS, 2, 8)).astype(np.int8)
    v = rng.integers(-127, 128, (2, 3, PS, 2, 8)).astype(np.int8)
    ks = rng.random((2, 3, 2)).astype(np.float32)
    vs = rng.random((2, 3, 2)).astype(np.float32)
    k2, v2, ks2, vs2 = deserialize_chunk(serialize_chunk(k, v, ks, vs))
    np.testing.assert_array_equal(k2, k)
    np.testing.assert_array_equal(v2, v)
    np.testing.assert_array_equal(ks2, ks)
    np.testing.assert_array_equal(vs2, vs)
    # unquantized chunks keep the legacy 2-ary wire shape
    kb, vb, ksb, vsb = deserialize_chunk(serialize_chunk(
        k.astype(np.float32), v.astype(np.float32)))
    assert ksb is None and vsb is None


def test_import_arrays_rejects_dtype_mismatch():
    """A bf16-pool prefill node cannot hand off to an int8-pool decode
    node (and vice versa): import_arrays refuses rather than writing
    codes it cannot dequantize."""
    from kaito_tpu.engine.pd import export_kv, import_arrays, import_kv

    arch = _arch()
    pages = [1, 2]
    c_bf = create_kv_cache(arch, 4, PS, jnp.bfloat16)
    c_q = create_kv_cache(arch, 4, PS, jnp.int8)
    assert not c_bf.quantized and c_q.quantized

    meta_q, blob_q = export_kv(c_q, pages)
    meta_b, blob_b = export_kv(c_bf, pages)
    with pytest.raises(ValueError):
        import_kv(c_bf, pages, blob_q, meta_q)
    with pytest.raises(ValueError):
        import_kv(c_q, pages, blob_b, meta_b)
    # matched dtypes round-trip, scales included
    k, v, ks, vs = (np.asarray(x) if x is not None else None
                    for x in _export_arrays(c_q, pages))
    c_q2 = import_arrays(c_q, pages, k, v, ks, vs)
    assert c_q2.quantized


def _export_arrays(cache, pages):
    from kaito_tpu.engine.pd import _gather_canonical
    return _gather_canonical(cache, pages)


def test_pd_handoff_preserves_scales():
    from kaito_tpu.engine.pd import export_kv, import_kv

    arch = _arch()
    src = create_kv_cache(arch, 4, PS, jnp.int8)
    # land real tokens so pages 1..2 carry non-trivial codes + scales
    rng = np.random.default_rng(3)
    new = jnp.asarray(rng.standard_normal(
        (1, PS * 2, arch.kv_cache_heads, arch.kv_cache_dim)), jnp.float32)
    pt = jnp.asarray([[1, 2]], jnp.int32)
    k, ksc = write_prefill_tokens_q(
        src.k[0], src.k_scale[0], new, pt, jnp.asarray([0]),
        jnp.asarray([PS * 2]), PS)
    src = KVCache(k=src.k.at[0].set(k), v=src.v,
                  k_scale=src.k_scale.at[0].set(ksc), v_scale=src.v_scale)

    meta, blob = export_kv(src, [1, 2])
    dst = import_kv(create_kv_cache(arch, 4, PS, jnp.int8), [1, 2], blob,
                    meta)
    np.testing.assert_array_equal(np.asarray(dst.k[:, 1:3]),
                                  np.asarray(src.k[:, 1:3]))
    np.testing.assert_array_equal(np.asarray(dst.k_scale[:, 1:3]),
                                  np.asarray(src.k_scale[:, 1:3]))


# ---------------------------------------------------------------------------
# capacity + transfer-cost arithmetic
# ---------------------------------------------------------------------------

def test_int8_capacity_ratio_vs_bf16():
    """At an equal HBM budget the int8 pool holds >= 1.8x the pages of
    the bf16 pool — the fp32 scale rows cost 2*L*Hkv*4 bytes per page,
    a few percent of the page at real head dims."""
    arch = _arch()
    per_tok = arch.kv_cache_heads * arch.kv_cache_dim
    bf16_page = 2 * PS * per_tok * 2
    int8_page = 2 * PS * per_tok * 1 + scale_bytes_per_page(arch) \
        / arch.num_layers
    assert bf16_page / int8_page >= 1.8


def test_kv_cache_is_quantized_and_alloc():
    assert kv_cache_is_quantized("int8")
    assert not kv_cache_is_quantized("bfloat16")
    assert not kv_cache_is_quantized(jnp.float32)
    arch = _arch()
    c = create_kv_cache(arch, 4, PS, jnp.int8)
    assert c.k.dtype == jnp.int8 and c.quantized
    assert c.k_scale.shape == (arch.num_layers, 4, arch.kv_cache_heads)
    # zero scales dequantize the fresh pool to exact zeros
    assert float(jnp.max(jnp.abs(dequantize_pages(c.k, c.k_scale)))) == 0.0


def test_transfer_cost_counts_scale_bytes():
    from kaito_tpu.engine.pd import transfer_cost

    arch = _arch()
    base = transfer_cost(1024, arch, 1)
    spt = 8.0 * arch.num_layers * arch.kv_cache_heads / PS
    with_scales = transfer_cost(1024, arch, 1, scale_bytes_per_token=spt)
    assert with_scales["kv_bytes"] == base["kv_bytes"] + int(spt * 1024)
    assert with_scales["transfer_s"] > base["transfer_s"]


# ---------------------------------------------------------------------------
# int8 target KV x draft-model speculation (docs/speculative.md): the
# draft keeps a private FP pool while the target pool is quantized,
# and greedy output must match the pinned int8 goldens exactly
# ---------------------------------------------------------------------------

def test_int8_kv_composes_with_draft_speculation():
    import json
    import os

    repo = __file__.rsplit("/tests/", 1)[0]
    ckpt = os.path.join(repo, "checkpoints", "tiny-llama-real")
    goldens = os.path.join(os.path.dirname(__file__), "testdata",
                           "goldens_tiny-llama-real.json")
    if not (os.path.exists(os.path.join(ckpt, "model.safetensors"))
            and os.path.exists(goldens)):
        pytest.skip("no committed real checkpoint")
    from kaito_tpu.engine.config import EngineConfig
    from kaito_tpu.engine.engine import InferenceEngine, SamplingParams

    golden = json.load(open(goldens))
    cfg = EngineConfig(model="tiny-llama-real", weights_dir=ckpt,
                       dtype="float32", kv_dtype="int8",
                       max_model_len=512, max_num_seqs=2,
                       prefill_buckets=(64, 128),
                       enable_prefix_caching=False, seed=0,
                       speculative_draft="tiny-llama-real",
                       speculative_draft_k=4,
                       speculative_draft_weights_dir=ckpt)
    eng = InferenceEngine(cfg)
    eng.start()
    try:
        assert eng.cache.quantized
        assert not eng.spec_draft.cache.quantized  # draft pool stays fp
        p = golden["prompts"][0]
        want = p["kv_int8"]["greedy_tokens"]
        req = eng.submit(list(p["prompt_tokens"]), SamplingParams(
            max_tokens=len(want), temperature=0.0, ignore_eos=True))
        got = [t for t in req.stream()]
        assert got == want
        assert eng.counters["spec_draft_steps_total"] >= 1
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# maintenance-window cron (satellite: direct last-fire computation)
# ---------------------------------------------------------------------------

def test_last_fire_and_window():
    from kaito_tpu.controllers.autoupgrade import last_fire

    utc = timezone.utc
    # daily 03:00: fired today if past 3am, else yesterday
    assert last_fire("0 3 * * *", datetime(2026, 7, 28, 4, 30, tzinfo=utc)) \
        == datetime(2026, 7, 28, 3, 0, tzinfo=utc)
    assert last_fire("0 3 * * *", datetime(2026, 7, 28, 2, 0, tzinfo=utc)) \
        == datetime(2026, 7, 27, 3, 0, tzinfo=utc)
    # exact fire minute counts as fired
    assert last_fire("30 2 * * *", datetime(2026, 7, 28, 2, 30, tzinfo=utc)) \
        == datetime(2026, 7, 28, 2, 30, tzinfo=utc)
    # step minutes pick the latest matching step
    assert last_fire("*/15 * * * *", datetime(2026, 7, 28, 9, 44, tzinfo=utc)) \
        == datetime(2026, 7, 28, 9, 30, tzinfo=utc)
    # weekly window (Sunday=0): walks back across days
    assert last_fire("0 5 * * 0", datetime(2026, 8, 5, 12, 0, tzinfo=utc)) \
        == datetime(2026, 8, 2, 5, 0, tzinfo=utc)
    # Feb 30 never fires
    assert last_fire("0 0 30 2 *", datetime(2026, 3, 1, tzinfo=utc)) is None
