"""Two kinds of page through the engine (docs/kv-cache.md, "Two kinds of
page"): window and full attention layers with their own head counts, a
page pool and a page table a kind, window pages freed behind the
window, and a sigmoid-routed expert layer that holds a share of the
experts.  The served float32 path against the plain reference
(kbench/reference/mimo_v2.py), the allocator's bounds, and every
refusal by name."""

import importlib.util
import os

import numpy as np
import pytest

from kaito_tpu.engine.config import EngineConfig
from kaito_tpu.engine.engine import InferenceEngine, SamplingParams
from kaito_tpu.models.autogen import (SUPPORTED_ARCHITECTURES,
                                      metadata_from_hf_config)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# mimo_v2's shape at a tiny size: one period of the layer pattern after
# the leading dense layer, 8 query heads over 2 (full) and 4 (window)
# KV heads, keys of 24 and values of 16, a window of 32 positions (two
# pages of 16), and a quarter of 16 experts held
TINY_MIMO = dict(
    architectures=["MiMoV2ForCausalLM"], model_type="mimo_v2",
    vocab_size=512, hidden_size=64, intermediate_size=128,
    num_hidden_layers=7, num_attention_heads=8, num_key_value_heads=2,
    head_dim=24, v_head_dim=16, swa_num_attention_heads=8,
    swa_num_key_value_heads=4, swa_head_dim=24, swa_v_head_dim=16,
    hybrid_layer_pattern=[0, 1, 1, 1, 1, 0, 1],
    moe_layer_freq=[0, 1, 1, 1, 1, 1, 1], sliding_window=32,
    sliding_window_size=32, rope_theta=10000000, swa_rope_theta=10000,
    partial_rotary_factor=0.334, attention_value_scale=0.707,
    add_swa_attention_sink_bias=True, add_full_attention_sink_bias=False,
    attention_bias=False, layernorm_epsilon=1e-5, hidden_act="silu",
    max_position_embeddings=2048, tie_word_embeddings=False,
    moe_intermediate_size=32, n_routed_experts=4, expert_shards=4,
    expert_shard=0, n_shared_experts=None, num_experts_per_tok=4,
    norm_topk_prob=True, scoring_func="sigmoid", n_group=1, topk_group=1,
    topk_method="noaux_tc", routed_scaling_factor=None,
    rope_scaling={"rope_type": "default", "type": "default"})

MD = metadata_from_hf_config("kaito-tpu/tiny-mimo-v2-test", TINY_MIMO,
                             name="tiny-mimo-v2-test")
WINDOW, PAGE = 32, 16
MOST_WINDOW_PAGES = WINDOW // PAGE + 2


def _reference():
    spec = importlib.util.spec_from_file_location(
        "mimo_v2_reference",
        os.path.join(ROOT, "kbench", "reference", "mimo_v2.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _mk(async_on=False, **kw):
    base = dict(model="tiny-mimo-v2-test", max_model_len=256, page_size=PAGE,
                max_num_seqs=4, dtype="float32", kv_dtype="float32",
                prefill_buckets=(32, 64, 128), max_prefill_tokens=64,
                decode_run_ahead=4, async_dispatch=async_on,
                seed=5)
    base.update(kw)
    return InferenceEngine(EngineConfig(**base), metadata=MD)


def _run(eng, prompts, n_out, steps=600, watch=None):
    reqs = [eng.submit(list(p), SamplingParams(max_tokens=n_out,
                                               temperature=0.0,
                                               ignore_eos=True, logprobs=1))
            for p in prompts]
    for _ in range(steps):
        if all(r.finish_reason for r in reqs):
            break
        eng.step()
        if watch is not None:
            watch(eng)
    assert all(r.finish_reason for r in reqs)
    return reqs


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(1, 500, size=n).tolist()


def test_autogen_maps_the_two_kinds_and_the_share():
    assert "MiMoV2ForCausalLM" in SUPPORTED_ARCHITECTURES
    a = MD.arch
    assert MD.runtime == "engine"
    assert a.layer_attention == (0, 1, 1, 1, 1, 0, 1)
    assert a.layer_experts == (0, 1, 1, 1, 1, 1, 1)
    assert (a.num_experts, a.experts_held, a.num_experts_per_tok) == (16, 4, 4)
    assert a.two_kind_cache and a.router_scoring == "sigmoid"
    assert a.kv_page_geometry(0) == (2, 2, 24, 16)
    assert a.kv_page_geometry(1) == (5, 4, 24, 16)


@pytest.mark.parametrize("key,value,word", [
    ("n_group", 2, "group-limited"), ("n_shared_experts", 1, "shared"),
    ("scoring_func", "softmax", "scoring_func"),
    ("attention_bias", True, "attention_bias"),
    ("hybrid_block_size", 4, "hybrid_block_size"),
    ("norm_topk_prob", False, "norm_topk_prob"),
    ("topk_method", "greedy", "topk_method"),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}, "rope_scaling"),
    ("hybrid_layer_pattern", [0, 1], "hybrid_layer_pattern"),
])
def test_autogen_refuses_by_name_what_it_does_not_implement(key, value, word):
    with pytest.raises(ValueError, match=word):
        metadata_from_hf_config("x/y", dict(TINY_MIMO, **{key: value}))


def test_published_config_counts_the_cut():
    """The catalog's MiMo-V2.5 config cut as the benchmark's file cuts
    it (7 layers, 16 of 256 experts held): 4,523.6M parameters, and per
    token 2,560 B in a full layer and 5,120 B in a window layer."""
    import json

    with open(os.path.join(ROOT, "kbench", "configs",
                           "mimo-v2.5-d7-ep16.json")) as f:
        cfg = json.load(f)["config"]
    arch = metadata_from_hf_config("XiaomiMiMo/MiMo-V2.5", cfg).arch
    assert round(arch.param_count() / 1e6, 1) == 4523.6
    assert arch.kv_bytes_per_token_kind(0) == 2 * 2560
    assert arch.kv_bytes_per_token_kind(1) == 5 * 5120
    # as stored: a 192-wide key lies in 256 lanes
    assert arch.kv_bytes_per_token_kind(0, stored=True) == 2 * 3072
    assert (arch.num_experts, arch.experts_held) == (256, 16)


@pytest.mark.parametrize("async_on", [False, True])
@pytest.mark.parametrize("n_prompt", [20, 150])
def test_served_path_equals_the_plain_reference(async_on, n_prompt):
    """One fresh chunk (20 tokens) and three (150 at a budget of 64: the
    first fresh, two down context prefill across freed window pages),
    then decode far past the window through fused windows: every emitted
    logprob is the plain reference's, and window pages were freed."""
    eng = _mk(async_on)
    prompt = _prompt(n_prompt, 1)
    (req,) = _run(eng, [prompt], 60)
    seq = prompt + req.output_tokens
    out = _reference().forward(TINY_MIMO, eng.params, seq, n_prompt - 1)
    want = np.asarray(out["target"])[:-1]
    got = np.asarray(req.output_logprobs)
    assert np.abs(got - want[:len(got)]).max() < 3e-4
    # every page the sequence wrote but the few it held at the end and
    # the fresh chunk's pages behind its own tail, which were never taken
    assert eng.counters["window_pages_freed_total"] >= max(
        1, (n_prompt + 60) // PAGE - MOST_WINDOW_PAGES - 2)
    assert eng.window_pages_in_use == 0
    # the expert layer's counters came back with the windows
    c = eng.counters
    assert c["moe_expert_calls_total"] > 0
    assert c["moe_experts_touched_total"] <= c["moe_expert_calls_total"]
    assert c["moe_pairs_held_total"] <= c["moe_pairs_routed_total"]


def test_a_chunk_wider_than_a_pass_equals_the_plain_reference():
    """A fresh chunk of 128 rows routes 512 pairs where a pass of this
    share holds 256: the widths at which a chip's kernel takes only the
    rows that hold a pair back to their tokens (nn._combine_held), and
    ``/health`` says whether it does (on a CPU the gathers run)."""
    import json
    import threading
    import urllib.request

    from kaito_tpu.engine.server import make_server

    eng = _mk(max_prefill_tokens=128)
    prompt = _prompt(150, 2)
    (req,) = _run(eng, [prompt], 8)
    seq = prompt + req.output_tokens
    out = _reference().forward(TINY_MIMO, eng.params, seq, len(prompt) - 1)
    want = np.asarray(out["target"])[:-1]
    got = np.asarray(req.output_logprobs)
    assert np.abs(got - want[:len(got)]).max() < 3e-4
    assert eng.model.moe_combine == "xla"
    eng.model.moe_kernel = True
    assert eng.model.moe_combine == "pallas"
    eng.model.moe_kernel = False
    server = make_server(eng, eng.cfg, host="127.0.0.1", port=0)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        url = f"http://127.0.0.1:{server.server_address[1]}/health"
        health = json.loads(urllib.request.urlopen(url, timeout=30).read())
    finally:
        server.shutdown()
        server.server_close()
    assert health["moe_combine"] == "xla" and health["attention"] == "jax"


def test_prompt_scoring_equals_the_plain_reference():
    eng = _mk()
    prompt = _prompt(70, 4)
    got = np.asarray(eng.score_prompt(prompt))
    out = _reference().forward(TINY_MIMO, eng.params, prompt, 0)
    want = np.asarray(out["target"])[:-1]
    assert np.abs(got[-len(want):] - want).max() < 3e-4


def test_window_table_never_holds_more_than_its_bound():
    """Four sequences of different lengths decode 80 tokens each: while
    a slot decodes its window table holds window/page_size + 2 pages at
    most, freed pages are reused (the pool is smaller than what the
    sequences write), and at the end both pools are whole again."""
    eng = _mk()
    seen = {"most": 0, "pages": set()}

    def watch(eng):
        for i, s in enumerate(eng.slots):
            if s.request is not None and not s.prefilling:
                seen["most"] = max(seen["most"], len(s.wpages))
                table = eng.page_tables[i, 1]
                assert sorted(table[table > 0]) == sorted(s.wpages.values())
            seen["pages"].update(s.wpages.values())

    _run(eng, [_prompt(n, n) for n in (20, 45, 64, 150)], 80, watch=watch)
    assert 0 < seen["most"] <= MOST_WINDOW_PAGES
    written = sum(-(-(n + 80) // PAGE) for n in (20, 45, 64, 150))
    assert len(seen["pages"]) < eng._num_window_pages < written
    assert eng.window_pages_in_use == 0
    assert eng.allocator.available == eng.allocator.num_pages - 1


def test_preemption_returns_both_tables_pages():
    """A full pool too small for three sequences: the newest yields,
    both its tables' pages go back, and every request still finishes
    with the tokens it gets alone."""
    alone = _run(_mk(), [_prompt(40, 7)], 80)[0].output_tokens
    eng = _mk(max_pages=14)
    reqs = _run(eng, [_prompt(40, 7), _prompt(50, 8), _prompt(60, 9)], 80,
                steps=3000)
    assert eng.counters["preemptions_total"] > 0
    assert reqs[0].output_tokens == alone
    assert eng.window_pages_in_use == 0
    assert eng.allocator.available == eng.allocator.num_pages - 1
    assert not eng.page_tables.any() or all(
        s.request is None for s in eng.slots)


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
    (dict(kv_dtype="int8"), "int8 KV"),
])
def test_refuses_by_name_what_two_kinds_of_page_cannot_serve(kw, word):
    with pytest.raises(ValueError, match=word):
        _mk(**kw)


def test_prefix_caching_is_refused_and_said():
    eng = _mk(enable_prefix_caching=True)
    assert eng.prefix_cache is None
    with pytest.raises(ValueError, match="second page pool"):
        eng._refuse_kv_import()


def test_a_kinds_model_draws_in_float32_and_rounds():
    """JAX's bfloat16 normal sampler has 128 values and a mean of
    -0.012; drawn with it, every matrix maps the all-ones direction
    onto itself and greedy decoding falls onto a few attractor tokens
    (model._normal).  A model whose layers name their kinds draws in
    float32 and rounds; every other model keeps the draw it had."""
    import jax
    import jax.numpy as jnp

    from kaito_tpu.engine.model import TransformerLM
    from kaito_tpu.models.registry import get_model_by_name

    key = jax.random.PRNGKey(7)
    shape = (1 << 20,)
    plain = jax.random.normal(key, shape, jnp.bfloat16)
    assert float(jnp.mean(plain.astype(jnp.float32))) < -0.008
    kinds = TransformerLM(MD.arch, jnp.bfloat16)
    z = kinds._normal(key, shape)
    assert z.dtype == jnp.bfloat16
    z = np.asarray(z.astype(jnp.float32))
    assert abs(z.mean()) < 0.004 and abs(z.std() - 1.0) < 0.01
    assert np.unique(z).size > 1000 and np.abs(z).max() > 4.0
    other = TransformerLM(get_model_by_name("tiny-llama-test").arch,
                          jnp.bfloat16)
    assert np.array_equal(np.asarray(other._normal(key, shape)),
                          np.asarray(plain))
    w = kinds.init_params(jax.random.PRNGKey(3))["lm_head"]
    assert w.dtype == jnp.bfloat16
