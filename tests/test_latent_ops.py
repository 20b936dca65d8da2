"""The latent decode kernel and flash prefill on the expanded heads
(docs/kv-cache.md, "Latent pages"): interpreted against the XLA paths
over ragged lengths, the whole layer through both kernels, and compiled
for a described v5e at JoyAI-LLM-Flash's published widths.  (An
interpreted Pallas kernel is called inside ONE ``jax.jit``: operation by
operation its callbacks deadlock against what is dispatched behind.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from kaito_tpu.engine import attention as attn
from kaito_tpu.engine.kv_cache import create_kv_cache
from kaito_tpu.engine.model import TransformerLM
from kaito_tpu.engine.ops.flash_prefill import flash_prefill_attention
from kaito_tpu.engine.ops.mla_decode_attention import (
    GANG_TOKENS, mla_paged_decode_attention_pallas, pages_per_gang)
from kaito_tpu.models.autogen import arch_from_hf_config

H, DN, DR, DL, DV, LANES = 4, 32, 64, 128, 32, 256
SCALE = 1.0 / np.sqrt(DN + DR)


def _pool(rng, layers, pages, ps, dtype):
    pool = np.zeros((layers, pages, ps, LANES), np.float32)
    pool[..., :DL + DR] = rng.standard_normal((layers, pages, ps, DL + DR))
    return jnp.asarray(pool, dtype)


def _weights(rng, dtype, heads=H):
    return (jnp.asarray(rng.standard_normal((DL, heads * DN)) / np.sqrt(DL),
                        dtype),
            jnp.asarray(rng.standard_normal((DL, heads * DV)) / np.sqrt(DL),
                        dtype))


def _absorbed(q_nope, q_rope, wk, dtype):
    B, heads, _ = q_nope.shape
    q_lat = jnp.einsum("bhd,lhd->bhl", q_nope, wk.reshape(DL, heads, DN),
                       preferred_element_type=jnp.float32)
    return jnp.concatenate(
        [q_lat * SCALE, q_rope.astype(jnp.float32) * SCALE,
         jnp.zeros((B, heads, LANES - DL - DR), jnp.float32)], -1).astype(dtype)


@pytest.mark.parametrize("heads,ps,pmax,lengths", [
    # ragged rows, a row of length 1, one that ends on a page boundary,
    # one that fills its table, and a slot that decodes nothing; the
    # table is narrower than a gang, so a row is one gang
    (4, 16, 6, [1, 16, 0, 37, 96]),
    # gangs of 512 tokens (8 pages of 64): rows of less than one gang,
    # and of one and a part (the last gang's pages past the row's are
    # stale)
    (4, 64, 12, [300, 256, 0, 700, 64]),
    # every row idle but the last: the ring starts cold at row 3
    (4, 16, 4, [0, 0, 0, 50]),
    # fewer gangs in all (two) than the ring has slots: the cold start
    # runs out of rows and the spare slots start nothing
    (4, 64, 8, [100, 0, 30]),
    # gangs of 32 pages of 16: a row that ends exactly on a gang's last
    # token, on the first token of the next, one short of it, on two
    # gangs exactly, and an odd number of gangs (three) in a row
    (4, 16, 72, [512, 513, 511, 1024, 1030]),
    # idle rows between live ones with the four-slot ring running two
    # and three rows ahead, and a table the last row fills
    (4, 16, 40, [600, 0, 0, 40, 0, 530, 0, 640]),
    # the head counts of the presets (deepseek-v2-lite 16, JoyAI 32,
    # deepseek-v3 128) over one, two and three gangs
    *[(heads, 16, 72, [520, 0, 1100, 33]) for heads in (16, 32, 128)],
])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)])
def test_decode_kernel_equals_the_xla_path(heads, ps, pmax, lengths, dtype,
                                           tol):
    rng = np.random.default_rng(len(lengths) + ps)
    B, L = len(lengths), 2
    pages = B * pmax + 1
    pool = _pool(rng, L, pages, ps, dtype)
    pt = jnp.asarray(rng.permutation(np.arange(1, pages))[:B * pmax]
                     .reshape(B, pmax), jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32)
    q_nope = jnp.asarray(rng.standard_normal((B, heads, DN)), dtype)
    q_rope = jnp.asarray(rng.standard_normal((B, heads, DR)), dtype)
    wk, wv = _weights(rng, dtype, heads)
    layer = jnp.int32(1)

    @jax.jit
    def kernel(q_nope, q_rope, pool, pt, lens):
        out_lat = mla_paged_decode_attention_pallas(
            _absorbed(q_nope, q_rope, wk, dtype), pool, pt, lens, layer,
            value_lanes=DL, interpret=True)
        return jnp.einsum("bhl,lhd->bhd", out_lat, wv.reshape(DL, heads, DV),
                          preferred_element_type=jnp.float32)

    got = np.asarray(kernel(q_nope, q_rope, pool, pt, lens), np.float32)
    want = np.asarray(attn.mla_paged_decode_attention(
        q_nope, q_rope, pool, pt, lens, wk, wv, scale=SCALE,
        kv_lora_rank=DL, layer=layer), np.float32)
    live = np.asarray(lengths) > 0
    assert np.abs(got[live] - want[live]).max() < tol * max(
        1.0, np.abs(want[live]).max())
    # a slot that decodes nothing is written as zeros
    assert not got[~live].any()
    assert pages_per_gang(ps, pmax) == min(GANG_TOKENS // ps, pmax)


def test_decode_kernel_refuses_a_query_of_other_lanes():
    pool = jnp.zeros((1, 4, 16, LANES), jnp.bfloat16)
    q = jnp.zeros((2, H, DL + DR), jnp.bfloat16)
    with pytest.raises(ValueError, match="lanes"):
        mla_paged_decode_attention_pallas(
            q, pool, jnp.zeros((2, 2), jnp.int32), jnp.ones((2,), jnp.int32),
            jnp.int32(0), value_lanes=DL, interpret=True)


@pytest.mark.parametrize("true_len", [128, 77])
def test_flash_prefill_on_the_expanded_heads_equals_the_latent_path(true_len):
    """Keys of [k_nope | k_rope | 0] at their stored lanes, values of
    dv, one KV head a query head: the flash kernel gives what
    ``mla_prefill_attention`` gives on the latent."""
    rng = np.random.default_rng(7)
    T, dn, dr, dv, dl = 128, 24, 16, 24, 32
    f32 = jnp.float32
    q_nope = jnp.asarray(rng.standard_normal((1, T, H, dn)), f32)
    q_rope = jnp.asarray(rng.standard_normal((1, T, H, dr)), f32)
    c_kv = jnp.asarray(rng.standard_normal((1, T, dl)), f32)
    k_rope = jnp.asarray(rng.standard_normal((1, T, dr)), f32)
    wk = jnp.asarray(rng.standard_normal((dl, H * dn)) / np.sqrt(dl), f32)
    wv = jnp.asarray(rng.standard_normal((dl, H * dv)) / np.sqrt(dl), f32)
    tl = jnp.asarray([true_len], jnp.int32)
    scale = 1.0 / np.sqrt(dn + dr)
    want = attn.mla_prefill_attention(q_nope, q_rope, c_kv, k_rope, wk, wv,
                                      scale=scale, true_len=tl)
    pad = 64 - (dn + dr)

    @jax.jit
    def flash(q_nope, q_rope, c_kv, k_rope):
        k = jnp.concatenate(
            [(c_kv @ wk).reshape(1, T, H, dn),
             jnp.broadcast_to(k_rope[:, :, None, :], (1, T, H, dr)),
             jnp.zeros((1, T, H, pad), f32)], -1)
        q = jnp.concatenate([q_nope, q_rope, jnp.zeros((1, T, H, pad), f32)],
                            -1)
        v = (c_kv @ wv).reshape(1, T, H, dv)
        return flash_prefill_attention(q, k, v, tl, jnp.int32(1 << 30),
                                       scale=scale, interpret=True)

    got = flash(q_nope, q_rope, c_kv, k_rope)
    assert np.abs(np.asarray(got - want)[:, :true_len]).max() < 2e-5


def test_context_attention_by_query_blocks_equals_one_pass(monkeypatch):
    """A wide chunk's queries go a block at a time (the float32 scores
    of a 4,096-token chunk over a 5,120-position table are 2.5 GiB):
    the same numbers as one pass."""
    rng = np.random.default_rng(11)
    B, T, ps, pmax = 2, 64, 16, 8
    pool = _pool(rng, 1, B * pmax + 1, ps, jnp.float32)
    pt = jnp.asarray(np.arange(1, B * pmax + 1).reshape(B, pmax), jnp.int32)
    q_nope = jnp.asarray(rng.standard_normal((B, T, H, DN)), jnp.float32)
    q_rope = jnp.asarray(rng.standard_normal((B, T, H, DR)), jnp.float32)
    wk, wv = _weights(rng, jnp.float32)
    args = (q_nope, q_rope, pool, pt, jnp.asarray([40, 17], jnp.int32),
            jnp.asarray([64, 50], jnp.int32), wk, wv)
    kw = dict(scale=SCALE, kv_lora_rank=DL, layer=jnp.int32(0))
    whole = attn.mla_paged_context_attention(*args, **kw)
    monkeypatch.setattr(attn, "_CONTEXT_QUERY_BLOCK", 16)
    blocked = attn.mla_paged_context_attention(*args, **kw)
    assert np.abs(np.asarray(whole - blocked)).max() < 1e-5
    assert float(jnp.abs(whole).max()) > 0.1


TINY = dict(
    architectures=["JoyAILLMFlashForCausalLM"], model_type="joyai_llm_flash",
    vocab_size=512, hidden_size=64, intermediate_size=128,
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
    head_dim=16, kv_lora_rank=128, q_lora_rank=48, qk_nope_head_dim=24,
    qk_rope_head_dim=16, v_head_dim=24, rope_theta=32000000,
    rope_interleave=True, rms_norm_eps=1e-6, max_position_embeddings=2048,
    first_k_dense_replace=1, moe_intermediate_size=32, n_routed_experts=4,
    expert_shards=4, n_shared_experts=1, num_experts_per_tok=4,
    scoring_func="sigmoid", topk_method="noaux_tc", routed_scaling_factor=2.5)


def test_the_layers_through_both_kernels_equal_the_xla_paths():
    """Prefill (flash on the expanded heads), a chunk with earlier
    context (the XLA path over the kernel-read pool) and two decode
    steps (the latent kernel), interpreted, against the same programs
    with XLA attention over the five-dimensional pool."""
    from jax.experimental.pallas import tpu as pltpu

    arch = arch_from_hf_config(TINY)
    ps = 16
    pt = jnp.asarray(np.arange(1, 25).reshape(2, 12), jnp.int32)
    toks = jnp.asarray(np.random.default_rng(1).integers(1, 500, (2, 160)),
                       jnp.int32)
    lens = jnp.asarray([128, 90], jnp.int32)
    more = jnp.asarray([32, 20], jnp.int32)

    def run(impl, flat):
        model = TransformerLM(arch, dtype=jnp.float32, attn_impl=impl)
        model.moe_impl = "ragged"
        params = model.init_params(jax.random.PRNGKey(0))
        cache = create_kv_cache(arch, 26, ps, jnp.float32, latent_kernel=flat)

        @jax.jit
        def all_of_it(params, cache):
            cache, l0, _ = model.prefill(params, cache, toks[:, :128], lens, pt)
            cache, l1, _ = model.prefill(params, cache, toks[:, 128:], more,
                                         pt, start_pos=lens)
            pos = lens + more
            cache, l2 = model.decode(params, cache, toks[:, 0], pos, pt)
            cache, l3 = model.decode(params, cache, toks[:, 1], pos + 1, pt)
            return l0, l1, l2, l3

        return all_of_it(params, cache)

    want = run("jax", False)
    with pltpu.force_tpu_interpret_mode():
        got = run("pallas", True)
    for a, b in zip(got, want):
        assert np.abs(np.asarray(a) - np.asarray(b)).max() < 5e-5


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def sds(one_chip):
    def make(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compile_decode(sds, heads):
    B, lanes, ps, pmax = 24, 640, 64, 80
    return jax.jit(lambda q, pool, pt, ln, li:
                   mla_paged_decode_attention_pallas(
                       q, pool, pt, ln, li, value_lanes=512)).lower(
        sds((B, heads, lanes)), sds((39, 1600, ps, lanes)),
        sds((B, pmax), jnp.int32), sds((B,), jnp.int32),
        sds((), jnp.int32)).compile().as_text()


def test_kernels_compile_for_v5e_at_the_published_widths(sds):
    """24 rows of 32 heads against a [39, P, 64, 640] pool, and flash
    prefill at every bucket on 32 heads of 256 | 128."""
    heads = 32
    assert "tpu_custom_call" in _compile_decode(sds, heads)
    for T in (128, 1024, 4096):
        jax.jit(lambda q, k, v, tl: flash_prefill_attention(
            q, k, v, tl, jnp.int32(1 << 30), scale=0.0722)).lower(
            sds((1, T, heads, 256)), sds((1, T, heads, 256)),
            sds((1, T, heads, 128)), sds((1,), jnp.int32)).compile()


@pytest.mark.parametrize("heads", [16, 128])
def test_decode_kernel_compiles_for_v5e_at_the_presets_head_counts(sds, heads):
    """The same gang and ring at deepseek-v2-lite's 16 heads and
    deepseek-v3's 128: a panel's scores are [heads, 512] float32."""
    assert "tpu_custom_call" in _compile_decode(sds, heads)
