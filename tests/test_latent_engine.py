"""Latent attention through the engine (docs/kv-cache.md, "Latent
pages"): joyai_llm_flash's shape at a tiny size, served in float32 on
the CPU against the plain reference (kbench/reference/
joyai_llm_flash.py), fresh, chunked and through the paged latent cache;
the kernel-read pool's layout through the same programs; the mapping,
the router, the shares that add up, and every refusal by name."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kaito_tpu.engine import nn
from kaito_tpu.engine.config import EngineConfig
from kaito_tpu.engine.engine import InferenceEngine, SamplingParams
from kaito_tpu.engine.kv_cache import create_kv_cache
from kaito_tpu.engine.model import TransformerLM
from kaito_tpu.models.autogen import (SUPPORTED_ARCHITECTURES,
                                      metadata_from_hf_config)
from kaito_tpu.models.registry import get_model_by_name

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# joyai_llm_flash's shape at a tiny size: one dense layer and three
# expert layers, 4 heads of [24 | 16], a latent of 128 (144 with the
# rotated part: stored at 256 lanes), a quarter of 16 experts held and
# one shared expert
TINY_JOYAI = dict(
    architectures=["JoyAILLMFlashForCausalLM"], model_type="joyai_llm_flash",
    vocab_size=512, hidden_size=64, intermediate_size=128,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=4,
    head_dim=16, kv_lora_rank=128, q_lora_rank=48, qk_head_dim=40,
    qk_nope_head_dim=24, qk_rope_head_dim=16, v_head_dim=24,
    rope_theta=32000000, rope_interleave=True, rope_scaling=None,
    attention_bias=False, rms_norm_eps=1e-6, hidden_act="silu",
    max_position_embeddings=2048, tie_word_embeddings=False,
    first_k_dense_replace=1, moe_layer_freq=1, moe_intermediate_size=32,
    n_routed_experts=4, expert_shards=4, expert_shard=0,
    n_shared_experts=1, num_experts_per_tok=4, norm_topk_prob=True,
    scoring_func="sigmoid", n_group=1, topk_group=1,
    topk_method="noaux_tc", routed_scaling_factor=2.5, ep_size=1,
    num_nextn_predict_layers=1)

MD = metadata_from_hf_config("kaito-tpu/tiny-joyai-test", TINY_JOYAI,
                             name="tiny-joyai-test")
PAGE = 16


def _reference():
    spec = importlib.util.spec_from_file_location(
        "joyai_reference",
        os.path.join(ROOT, "kbench", "reference", "joyai_llm_flash.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _mk(async_on=False, md=MD, **kw):
    base = dict(model=md.name, max_model_len=256, page_size=PAGE,
                max_num_seqs=4, dtype="float32", kv_dtype="float32",
                prefill_buckets=(32, 64, 128), max_prefill_tokens=64,
                decode_run_ahead=4, async_dispatch=async_on,
                seed=5)
    base.update(kw)
    return InferenceEngine(EngineConfig(**base), metadata=md)


def _run(eng, prompts, n_out, steps=600):
    reqs = [eng.submit(list(p), SamplingParams(max_tokens=n_out,
                                               temperature=0.0,
                                               ignore_eos=True, logprobs=1))
            for p in prompts]
    for _ in range(steps):
        if all(r.finish_reason for r in reqs):
            break
        eng.step()
    assert all(r.finish_reason for r in reqs)
    return reqs


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(1, 500, size=n).tolist()


def test_autogen_maps_the_latent_layers_the_router_and_the_share():
    assert "JoyAILLMFlashForCausalLM" in SUPPORTED_ARCHITECTURES
    a = MD.arch
    assert MD.runtime == "engine"
    assert a.mla_dims == (24, 16, 128, 24) and a.q_lora_rank == 48
    assert (a.kv_cache_dim, a.latent_lanes, a.kv_cache_heads) == (144, 256, 1)
    assert a.rope_interleave and a.moe_layer_start == 1
    assert (a.num_experts, a.experts_held, a.num_experts_per_tok) == (16, 4, 4)
    assert (a.router_scoring, a.router_bias, a.routed_scaling_factor,
            a.num_shared_experts) == ("sigmoid", True, 2.5, 1)
    assert a.kv_bytes_per_token(2) == 4 * 144 * 2
    assert a.kv_bytes_per_token(2, stored=True) == 4 * 256 * 2


def test_the_deepseek_v3_preset_carries_its_familys_router():
    a = get_model_by_name("deepseek-v3-0324").arch
    assert (a.router_scoring, a.router_bias, a.routed_scaling_factor) == (
        "sigmoid", True, 2.5)
    assert a.rope_interleave and a.num_shared_experts == 1
    assert (a.expert_shards, a.experts_held) == (1, 256)
    # a config of the family that carries none of the router's keys
    # keeps deepseek-v2's softmax router
    plain = {k: v for k, v in TINY_JOYAI.items() if k not in (
        "scoring_func", "topk_method", "routed_scaling_factor",
        "rope_interleave", "expert_shards")}
    b = metadata_from_hf_config("x/y", dict(plain, model_type="deepseek_v3",
                                            architectures=[
                                                "DeepseekV3ForCausalLM"])).arch
    assert (b.router_scoring, b.router_bias, b.routed_scaling_factor,
            b.rope_interleave) == ("softmax", False, 1.0, False)


@pytest.mark.parametrize("key,value,word", [
    ("n_group", 4, "group-limited"),
    ("scoring_func", "tanh", "scoring_func"),
    ("topk_method", "group_limited_greedy", "topk_method"),
    ("norm_topk_prob", False, "norm_topk_prob"),
    ("moe_layer_freq", 2, "moe_layer_freq"),
    ("rope_scaling", {"rope_type": "longrope", "factor": 4.0},
     "rope_scaling"),
    ("expert_shard", 4, "expert_shard"),
])
def test_autogen_refuses_by_name_what_it_does_not_implement(key, value, word):
    cfg = dict(TINY_JOYAI, **{key: value})
    if key == "n_group":
        cfg["topk_group"] = 2
    with pytest.raises(ValueError, match=word):
        metadata_from_hf_config("x/y", cfg)


def test_published_config_counts_the_cut():
    """The catalog's JoyAI-LLM-Flash config cut as the benchmark's file
    cuts it (all 40 layers, 16 of 256 experts held): 4,776.4M
    parameters, and a cached token holds 1,152 B a layer, 1,280 as the
    kernel-read pool stores it."""
    import json

    with open(os.path.join(ROOT, "kbench", "configs",
                           "joyai-llm-flash-ep16.json")) as f:
        cfg = json.load(f)["config"]
    arch = metadata_from_hf_config("jdopensource/JoyAI-LLM-Flash", cfg).arch
    assert round(arch.param_count() / 1e6, 1) == 4776.4
    assert arch.kv_bytes_per_token(2) == 40 * 1152
    assert arch.kv_bytes_per_token(2, stored=True) == 40 * 1280
    assert (arch.num_experts, arch.experts_held) == (256, 16)
    model = TransformerLM(arch)
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    held = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    # (the program pads the vocabulary's 129,280 rows to whole tiles)
    pad = 2 * (model.vocab_padded - arch.vocab_size) * arch.hidden_size
    # (param_count leaves out the correction biases and the two latent
    # norms' gains)
    assert held - pad == arch.param_count() + 39 * 256 + 40 * (1536 + 512)


def test_interleaved_rotary_is_a_complex_rotation_of_adjacent_pairs():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((1, 5, 3, 16)).astype(np.float32)
    pos = np.asarray([[0, 1, 7, 100, 4000]], np.int32)
    inv = (1.0 / 32e6 ** (np.arange(0, 16, 2) / 16)).astype(np.float32)
    got = np.asarray(nn.apply_rope(jnp.asarray(x), jnp.asarray(pos),
                                   jnp.asarray(inv), 16, interleave=True))
    z = (x[..., 0::2] + 1j * x[..., 1::2]) * np.exp(
        1j * pos[..., None, None] * inv)
    want = np.stack([z.real, z.imag], axis=-1).reshape(x.shape)
    assert np.abs(got - want).max() < 2e-5
    half = np.asarray(nn.apply_rope(jnp.asarray(x), jnp.asarray(pos),
                                    jnp.asarray(inv), 16))
    assert np.abs(half - got).max() > 0.1
    # position 0 rotates nothing under either pairing
    assert np.abs(half[:, 0] - x[:, 0]).max() < 1e-6


def test_router_is_a_top_k_by_score_plus_bias_weighed_by_score_alone():
    a = MD.arch
    rng = np.random.default_rng(3)
    logits = rng.standard_normal((9, 16)).astype(np.float32)
    bias = rng.standard_normal((16,)).astype(np.float32)
    idx, w = nn.route_tokens(jnp.asarray(logits), a, jnp.asarray(bias))
    s = 1.0 / (1.0 + np.exp(-logits))
    for t in range(9):
        want = np.argsort(-(s[t] + bias))[:4]
        assert sorted(np.asarray(idx[t]).tolist()) == sorted(want.tolist())
        g = s[t][np.asarray(idx[t])]
        np.testing.assert_allclose(np.asarray(w[t]), 2.5 * g / g.sum(),
                                   rtol=1e-5)


def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_uncut_layer():
    """Each share's routed part (its layer's output less the shared
    expert's) summed over the shares, plus the shared expert once, is
    the reference's expert layer with every expert held."""
    shards = 4
    whole_cfg = dict(TINY_JOYAI, n_routed_experts=16, expert_shards=1)
    whole = TransformerLM(metadata_from_hf_config("x/y", whole_cfg).arch,
                          dtype=jnp.float32)
    stack = whole.init_params(jax.random.PRNGKey(2))["moe"]
    layer = {k: v[1] for k, v in stack.items()}
    x = jax.random.normal(jax.random.PRNGKey(3), (256, 64), jnp.float32)
    # the reference's block with attention's output projection zeroed
    # and unit norms: x + its expert layer of RMSNorm(x)
    block = _reference()._make_layer(whole_cfg, True, "")
    want = block(x, {**layer, "o": jnp.zeros_like(layer["o"]),
                     "attn_norm": jnp.ones((64,)),
                     "mlp_norm": jnp.ones((64,))}) - x
    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    shared = nn.mlp(h, {"gate": layer["shared_gate"],
                        "up": layer["shared_up"],
                        "down": layer["shared_down"]}, whole.arch)
    routed = jnp.zeros_like(x)
    for shard in range(shards):
        arch = dataclasses.replace(whole.arch, expert_shards=shards,
                                   expert_shard=shard)
        lo = shard * arch.experts_held
        mine = {k: (v[lo:lo + arch.experts_held]
                    if k.startswith("experts_") else v)
                for k, v in layer.items()}
        part = nn.moe_mlp_ragged(h, mine, arch) - shared
        assert float(jnp.abs(part).max()) > 1e-3
        routed = routed + part
    assert np.abs(np.asarray(routed + shared - want)).max() < 2e-4
    assert np.abs(np.asarray(routed + shards * shared - want)).max() > 1e-2


@pytest.mark.parametrize("async_on", [False, True])
@pytest.mark.parametrize("n_prompt", [20, 150])
def test_served_path_equals_the_plain_reference(async_on, n_prompt):
    """One fresh chunk (20 tokens) and three (150 at a budget of 64: the
    first fresh, two with earlier context in the paged latent cache),
    then decode through fused windows: every emitted logprob is the
    plain reference's, and the expert layer's counters came back."""
    eng = _mk(async_on)
    prompt = _prompt(n_prompt, 1)
    (req,) = _run(eng, [prompt], 40)
    seq = prompt + req.output_tokens
    out = _reference().forward(TINY_JOYAI, eng.params, seq, n_prompt - 1)
    want = np.asarray(out["target"])[:-1]
    got = np.asarray(req.output_logprobs)
    assert np.abs(got - want[:len(got)]).max() < 3e-4
    c = eng.counters
    assert c["moe_expert_calls_total"] > 0
    assert c["moe_experts_touched_total"] <= c["moe_expert_calls_total"]
    assert 0 < c["moe_pairs_held_total"] <= c["moe_pairs_routed_total"]
    assert eng.attention_path == "jax" and not eng.latent_kernel
    assert eng.cache.k.shape[2:] == (PAGE, 1, 144)


def test_prompt_scoring_equals_the_plain_reference():
    eng = _mk()
    prompt = _prompt(70, 4)
    got = np.asarray(eng.score_prompt(prompt))
    out = _reference().forward(TINY_JOYAI, eng.params, prompt, 0)
    want = np.asarray(out["target"])[:-1]
    assert np.abs(got[-len(want):] - want).max() < 3e-4


def test_each_perturbation_moves_the_reference():
    ref = _reference()
    eng = _mk()
    prompt = _prompt(200, 6)
    clean = np.asarray(ref.forward(TINY_JOYAI, eng.params, prompt, 0)
                       ["target"])[:-1]
    assert len(ref.PERTURBATIONS) == 12
    for name in ref.PERTURBATIONS:
        got = np.asarray(ref.forward(TINY_JOYAI, eng.params, prompt, 0,
                                     perturb=name)["target"])[:-1]
        assert np.abs(got - clean).max() > 1e-3, name


@pytest.mark.parametrize("key,value,word", [
    ("model_type", "deepseek_v3", "joyai_llm_flash"),
    ("n_group", 2, "group-limited"),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}, "rope_scaling"),
    ("kv_lora_rank", None, "latent"),
    ("scoring_func", "softmax", "sigmoid"),
    ("moe_layer_freq", 2, "expert layer"),
    ("attention_bias", True, "attention_bias"),
])
def test_reference_refuses_what_it_does_not_implement(key, value, word):
    with pytest.raises(ValueError, match=word):
        _reference().forward(dict(TINY_JOYAI, **{key: value}), {}, [1, 2], 0)
    with pytest.raises(ValueError, match="no perturbation"):
        _reference().forward(TINY_JOYAI, {}, [1, 2], 0, perturb="nothing")


def test_reference_without_a_query_latent_is_implemented():
    """``q_lora_rank`` null: one query matrix, in the program and the
    reference alike."""
    cfg = dict(TINY_JOYAI, q_lora_rank=None)
    md = metadata_from_hf_config("x/y", cfg, name="tiny-joyai-noq")
    eng = _mk(md=md)
    assert "q" in eng.params["moe"] and "q_a" not in eng.params["moe"]
    prompt = _prompt(40, 8)
    got = np.asarray(eng.score_prompt(prompt))
    want = np.asarray(_reference().forward(cfg, eng.params, prompt, 0)
                      ["target"])[:-1]
    assert np.abs(got[-len(want):] - want).max() < 3e-4


def test_kernel_read_pool_serves_the_same_logits():
    """The token-flat pool at its stored lanes through the same
    programs (the XLA paths read both layouts): prefill, a chunk with
    earlier context and decode give the five-dimensional pool's
    logits, and the lanes past the latent stay zero."""
    arch = MD.arch
    model = TransformerLM(arch, dtype=jnp.float32)
    model.moe_impl = "ragged"
    params = model.init_params(jax.random.PRNGKey(0))
    pt = jnp.asarray(np.arange(1, 17).reshape(2, 8), jnp.int32)
    toks = jnp.asarray(np.random.default_rng(0).integers(1, 500, (2, 48)),
                       jnp.int32)
    outs = []
    for flat in (False, True):
        cache = create_kv_cache(arch, 20, PAGE, jnp.float32,
                                latent_kernel=flat)
        lens = jnp.asarray([32, 20], jnp.int32)
        cache, l0, _ = model.prefill(params, cache, toks[:, :32], lens, pt)
        cache, l1, _ = model.prefill(
            params, cache, toks[:, 32:], jnp.asarray([16, 9], jnp.int32), pt,
            start_pos=lens)
        cache, l2 = model.decode(params, cache, toks[:, 0],
                                 jnp.asarray([48, 29], jnp.int32), pt)
        outs.append((l0, l1, l2))
        if flat:
            assert cache.k.shape == (4, 20, PAGE, 256)
            assert float(jnp.abs(cache.k[..., 144:]).max()) == 0.0
            assert float(jnp.abs(cache.k[:, 1:3, :, :144]).min()) > 0.0
    for a, b in zip(*outs):
        assert np.abs(np.asarray(a) - np.asarray(b)).max() < 2e-5


@pytest.mark.parametrize("kw,word", [
    (dict(tensor_parallel=2), "tensor parallelism"),
    (dict(pipeline_parallel=2), "pipeline parallelism"),
    (dict(sequence_parallel=2), "context-parallel prefill"),
    (dict(expert_parallel=2), "expert parallelism"),
    (dict(host_kv_offload_bytes=1 << 20), "host KV offload"),
    (dict(pd_enabled=True), "disaggregation"),
    (dict(kv_pool_enabled=True), "cluster KV pool"),
    (dict(speculative_ngram=3), "n-gram speculation"),
    (dict(speculative_draft="tiny-llama-test"), "draft-model speculation"),
    (dict(adapter_slots=2), "adapter cache"),
])
def test_refuses_by_name_what_a_latent_share_cannot_serve(kw, word):
    with pytest.raises(ValueError, match=word):
        _mk(**kw)


def test_prefix_caching_is_off_and_health_and_metrics_say_so():
    import json
    import threading
    import urllib.request

    from kaito_tpu.engine.server import make_server

    eng = _mk(enable_prefix_caching=True)
    assert eng.prefix_cache is None
    _run(eng, [_prompt(30, 2)], 6)
    server = make_server(eng, eng.cfg, host="127.0.0.1", port=0)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        base = f"http://127.0.0.1:{server.server_address[1]}"
        health = json.loads(urllib.request.urlopen(base + "/health",
                                                   timeout=30).read())
        metrics = urllib.request.urlopen(base + "/metrics",
                                         timeout=30).read().decode()
    finally:
        server.shutdown()
        server.server_close()
    assert health["attention"] == "jax" and health["prefix_cache"] == "off"
    sizing = health["hbm_sizing"]
    # float32 here: 4 layers x 144 numbers x 4 B, no lanes added on the
    # XLA path
    assert sizing["latent_bytes_per_token"] == 4 * 144 * 4
    assert sizing["latent_pool_bytes"] == eng.cache.k.nbytes
    for name in ("kaito:engine_latent_pool_bytes",
                 "kaito:engine_latent_bytes_per_token",
                 "kaito:engine_moe_pairs_held_total"):
        assert f"\n{name} " in metrics, name
