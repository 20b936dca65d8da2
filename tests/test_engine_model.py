import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kaito_tpu.engine.kv_cache import create_kv_cache
from kaito_tpu.engine.model import TransformerLM
from kaito_tpu.engine.sampler import SamplingState, sample
from kaito_tpu.models import get_model_by_name
from kaito_tpu.models.autogen import arch_from_hf_config

TINY = get_model_by_name("tiny-llama-test").arch
PS = 16  # page size


def _setup(arch, batch=2, pages_per_seq=8, num_pages=64, dtype=jnp.float32):
    model = TransformerLM(arch, dtype=dtype)
    params = model.init_params(jax.random.PRNGKey(0))
    cache = create_kv_cache(arch, num_pages, PS, dtype)
    # page tables: disjoint pages per sequence, skipping null page 0
    pt = np.zeros((batch, pages_per_seq), np.int32)
    for b in range(batch):
        pt[b] = np.arange(1 + b * pages_per_seq, 1 + (b + 1) * pages_per_seq)
    return model, params, cache, jnp.asarray(pt)


def _greedy_reference(model, params, tokens):
    """Decode-free reference: run prefill over successively longer
    prefixes; the last-token logits of each prefix are what decode
    should produce."""
    raise NotImplementedError


def test_prefill_then_decode_matches_full_prefill():
    """Decoding token-by-token through the paged cache must produce the
    same logits as prefilling the whole sequence at once."""
    arch = TINY
    model, params, cache, pt = _setup(arch)
    rng = np.random.RandomState(0)
    full = jnp.asarray(rng.randint(0, arch.vocab_size, size=(2, 12)), jnp.int32)

    # full prefill of 12 tokens
    cache_a = create_kv_cache(arch, 64, PS, jnp.float32)
    _, logits_full, _ = model.prefill(
        params, cache_a, full, jnp.asarray([12, 12], jnp.int32), pt)

    # prefill 8, then decode tokens 8..11
    cache_b = create_kv_cache(arch, 64, PS, jnp.float32)
    cache_b, logits_8, _ = model.prefill(
        params, cache_b, full[:, :8], jnp.asarray([8, 8], jnp.int32), pt)
    logits_step = logits_8
    for t in range(8, 12):
        cache_b, logits_step = model.decode(
            params, cache_b, full[:, t], jnp.asarray([t, t], jnp.int32), pt)

    np.testing.assert_allclose(
        np.asarray(logits_step), np.asarray(logits_full), rtol=2e-4, atol=2e-4)


def test_prefill_padding_invariant():
    """Padded prompt rows must not change real rows' logits."""
    arch = TINY
    model, params, cache, pt = _setup(arch)
    rng = np.random.RandomState(1)
    toks = rng.randint(0, arch.vocab_size, size=(2, 10)).astype(np.int32)
    toks_padded = np.concatenate([toks, rng.randint(0, arch.vocab_size, size=(2, 6))], axis=1).astype(np.int32)

    _, logits_a, _ = model.prefill(
        params, cache, jnp.asarray(toks), jnp.asarray([10, 10], jnp.int32), pt)
    cache2 = create_kv_cache(arch, 64, PS, jnp.float32)
    _, logits_b, _ = model.prefill(
        params, cache2, jnp.asarray(toks_padded), jnp.asarray([10, 10], jnp.int32), pt)
    np.testing.assert_allclose(np.asarray(logits_a), np.asarray(logits_b), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("preset_cfg", [
    # phi-2 style: layernorm + parallel residual + partial rotary + bias
    {"architectures": ["PhiForCausalLM"], "model_type": "phi",
     "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 2,
     "num_attention_heads": 4, "intermediate_size": 128,
     "partial_rotary_factor": 0.5, "hidden_act": "gelu_new",
     "max_position_embeddings": 256},
    # gemma-3 style: qk-norm, sliding pattern, geglu, softcap-free
    {"architectures": ["Gemma3ForCausalLM"], "model_type": "gemma3_text",
     "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 4,
     "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
     "intermediate_size": 128, "sliding_window": 8, "sliding_window_pattern": 2,
     "query_pre_attn_scalar": 16, "hidden_activation": "gelu_pytorch_tanh",
     "tie_word_embeddings": True, "max_position_embeddings": 256},
    # qwen2 style: qkv bias
    {"architectures": ["Qwen2ForCausalLM"], "model_type": "qwen2",
     "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 2,
     "num_attention_heads": 4, "num_key_value_heads": 2,
     "intermediate_size": 128, "max_position_embeddings": 256},
    # falcon style: MQA, ungated gelu, parallel residual, layernorm
    {"architectures": ["FalconForCausalLM"], "model_type": "falcon",
     "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 2,
     "num_attention_heads": 4, "multi_query": True,
     "intermediate_size": 128, "hidden_act": "gelu",
     "max_position_embeddings": 256},
    # MoE (mixtral style)
    {"architectures": ["MixtralForCausalLM"], "model_type": "mixtral",
     "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 2,
     "num_attention_heads": 4, "num_key_value_heads": 2,
     "intermediate_size": 128, "num_local_experts": 4,
     "num_experts_per_tok": 2, "max_position_embeddings": 256},
])
def test_families_prefill_decode_consistency(preset_cfg):
    arch = arch_from_hf_config(preset_cfg)
    model, params, cache, pt = _setup(arch, batch=1)
    rng = np.random.RandomState(2)
    full = jnp.asarray(rng.randint(0, arch.vocab_size, size=(1, 9)), jnp.int32)

    _, logits_full, _ = model.prefill(
        params, cache, full, jnp.asarray([9], jnp.int32), pt)

    cache_b = create_kv_cache(arch, 64, PS, jnp.float32)
    cache_b, _, _ = model.prefill(
        params, cache_b, full[:, :6], jnp.asarray([6], jnp.int32), pt)
    logits_step = None
    for t in range(6, 9):
        cache_b, logits_step = model.decode(
            params, cache_b, full[:, t], jnp.asarray([t], jnp.int32), pt)
    np.testing.assert_allclose(
        np.asarray(logits_step), np.asarray(logits_full), rtol=3e-4, atol=3e-4)


def test_param_axes_match_params():
    model = TransformerLM(TINY, dtype=jnp.float32)
    params = model.init_params(jax.random.PRNGKey(0))
    axes = model.param_logical_axes()
    flat_p = jax.tree.leaves_with_path(params)
    flat_a = {jax.tree_util.keystr(k): v for k, v in jax.tree.leaves_with_path(axes, is_leaf=lambda x: isinstance(x, tuple))}
    for path, leaf in flat_p:
        key = jax.tree_util.keystr(path)
        assert key in flat_a, key
        assert len(flat_a[key]) == leaf.ndim, (key, flat_a[key], leaf.shape)


def test_sampler_greedy_and_topk():
    logits = jnp.asarray([[0.0, 5.0, 1.0, -2.0]] * 3, jnp.float32)
    st = SamplingState.create(3)
    st = st.set_slot(0, temperature=0.0, top_k=0, top_p=1.0, seed=0)   # greedy
    st = st.set_slot(1, temperature=1.0, top_k=1, top_p=1.0, seed=1)   # top-1 == greedy
    st = st.set_slot(2, temperature=0.5, top_k=0, top_p=0.05, seed=2)  # tight nucleus
    toks, st2 = sample(logits, st)
    assert toks[0] == 1
    assert toks[1] == 1
    assert toks[2] == 1
    # keys advanced for stochastic rows
    assert not np.array_equal(np.asarray(st.key[1]), np.asarray(st2.key[1]))


# slow: 272 s alone under the check's command: it draws its samples one
# dispatch at a time
@pytest.mark.slow
def test_sampler_distribution_sanity():
    logits = jnp.asarray(np.log([[0.7, 0.2, 0.1, 1e-9]]), jnp.float32)
    counts = np.zeros(4)
    st = SamplingState.create(1)
    st = st.set_slot(0, temperature=1.0, top_k=0, top_p=1.0, seed=7)
    for _ in range(200):
        tok, st = sample(logits, st)
        counts[int(tok[0])] += 1
    assert counts[0] > counts[1] > 0
    assert counts[3] == 0
