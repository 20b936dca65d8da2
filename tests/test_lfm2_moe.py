"""LFM2-MoE through the engine (docs/kv-cache.md, "A row of conv
state"): layers whose mixer is a gated short convolution beside GQA
layers of 64-wide heads with a QK norm, pages for the attention layers
alone, a row of conv state a slot, and a sigmoid-routed expert layer
held whole.  The served float32 path against the plain reference
(kbench/reference/lfm2_moe.py), the operator's three forms, the pools'
geometry, the loader's names and every refusal by name."""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kaito_tpu.engine import attention as A
from kaito_tpu.engine import nn
from kaito_tpu.engine.config import EngineConfig
from kaito_tpu.engine.engine import InferenceEngine, SamplingParams
from kaito_tpu.engine.model import TransformerLM
from kaito_tpu.models.autogen import (SUPPORTED_ARCHITECTURES,
                                      metadata_from_hf_config)
from kaito_tpu.models.metadata import heads_per_lane_row

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# lfm2_moe's shape at a tiny size: two dense conv layers, then one
# period (attention, three conv layers) and the next one's attention;
# 4 query heads over 2 KV heads of 64 (two share a 128-lane row of the
# pools, as on the chip), 8 experts of 32 with 2 a token, all held
TINY_LFM2 = dict(
    architectures=["Lfm2MoeForCausalLM"], model_type="lfm2_moe",
    vocab_size=512, hidden_size=256, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=7, num_attention_heads=4,
    num_key_value_heads=2,
    layer_types=["conv", "conv", "full_attention", "conv", "conv", "conv",
                 "full_attention"],
    conv_L_cache=3, conv_bias=False, num_dense_layers=2, num_experts=8,
    num_experts_per_tok=2, use_expert_bias=True, norm_topk_prob=True,
    routed_scaling_factor=1, norm_eps=1e-5, rope_theta=1000000,
    max_position_embeddings=2048)

MD = metadata_from_hf_config("kaito-tpu/tiny-lfm2-test", TINY_LFM2,
                             name="tiny-lfm2-test")
PAGE = 16


def _reference():
    spec = importlib.util.spec_from_file_location(
        "lfm2_moe_reference",
        os.path.join(ROOT, "kbench", "reference", "lfm2_moe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _mk(async_on=False, **kw):
    base = dict(model="tiny-lfm2-test", max_model_len=256, page_size=PAGE,
                max_num_seqs=4, dtype="float32", kv_dtype="float32",
                prefill_buckets=(32, 64, 128), max_prefill_tokens=64,
                decode_run_ahead=4, async_dispatch=async_on,
                seed=5)
    base.update(kw)
    return InferenceEngine(EngineConfig(**base), metadata=MD)


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


def _against_reference(eng, prompt, req):
    seq = list(prompt) + list(req.output_tokens)
    out = _reference().forward(TINY_LFM2, eng.params, seq, len(prompt) - 1)
    want = np.asarray(out["target"])[:-1]
    got = np.asarray(req.output_logprobs)
    return np.abs(got - want[:len(got)]).max()


def test_autogen_maps_the_family():
    a = MD.arch
    assert {"Lfm2MoeForCausalLM", "Lfm2ForCausalLM"} <= SUPPORTED_ARCHITECTURES
    assert MD.runtime == "engine"
    assert a.layer_attention == (2, 2, 0, 2, 2, 2, 0)
    assert a.layer_experts == (0, 0, 1, 1, 1, 1, 1)
    assert (a.conv_kernel, a.conv_layers, a.attention_layers(0)) == (3, 5, 2)
    assert (a.head_dim, a.qk_norm, a.tie_word_embeddings) == (64, True, True)
    assert (a.num_experts, a.experts_held, a.num_experts_per_tok,
            a.router_scoring, a.router_bias, a.routed_scaling_factor) == (
        8, 8, 2, "sigmoid", True, 1.0)
    assert not a.two_kind_cache and a.kv_heads_per_row(0) == 2
    # the dense sibling by the same branch: no expert layer, the FFN's
    # width from its block_* keys
    dense = dict(TINY_LFM2, model_type="lfm2", block_ff_dim=384,
                 block_auto_adjust_ff_dim=True, block_multiple_of=64,
                 block_ffn_dim_multiplier=1.0,
                 architectures=["Lfm2ForCausalLM"])
    d = metadata_from_hf_config("x/y", dense).arch
    assert (d.num_experts, d.layer_experts, d.intermediate_size) == (
        0, (0,) * 7, 256)
    assert d.layer_attention == a.layer_attention


@pytest.mark.parametrize("key,value,word", [
    ("conv_bias", True, "conv_bias true"),
    ("layer_types", ["conv"] * 6 + ["sliding_attention"],
     "layer_types entry 'sliding_attention'"),
    ("layer_types", ["conv"] * 3, "must name each of the 7 layers"),
    ("norm_topk_prob", False, "norm_topk_prob false"),
    ("use_expert_bias", False, "use_expert_bias false"),
    ("conv_L_cache", 1, "conv_L_cache 1"),
    ("rope_scaling", {"rope_type": "yarn"}, "rope_scaling"),
])
def test_autogen_refuses_by_name_what_it_does_not_implement(key, value, word):
    with pytest.raises(ValueError, match=word):
        metadata_from_hf_config("x/y", dict(TINY_LFM2, **{key: value}))


def test_published_config_counts_the_cut_and_the_whole():
    """The benchmark's file (the first 14 of 24 layers): 4,667M
    parameters; a cached token holds 3 attention layers x 2,048 B and
    no more, a slot's row of conv state 11 x 2 x 2,048 bf16.  The whole
    model: 8,340M."""
    with open(os.path.join(ROOT, "kbench", "configs",
                           "lfm2-8b-a1b-d14.json")) as f:
        cfg = json.load(f)
    arch = metadata_from_hf_config("LiquidAI/LFM2-8B-A1B", cfg["config"]).arch
    assert abs(arch.param_count() / 4667e6 - 1) < 1e-3
    assert arch.kv_bytes_per_token() == 3 * 2048
    assert arch.kv_bytes_per_token(stored=True) == 3 * 2048
    assert arch.state_bytes_per_seq() == 11 * 2 * 2048 * 2
    assert (arch.conv_layers, arch.attention_layers(0),
            arch.attention_layers(1)) == (11, 3, 0)
    # 8 KV heads of 64 are 4 rows of 128 lanes
    assert arch.kv_heads_per_row(0) == 2
    assert sum(arch.layer_experts) == 12
    whole = dict(cfg["config"], num_hidden_layers=24,
                 layer_types=cfg["published"]["layer_types"])
    arch = metadata_from_hf_config("LiquidAI/LFM2-8B-A1B", whole).arch
    assert abs(arch.param_count() / 8340e6 - 1) < 1e-3
    assert (arch.conv_layers, arch.attention_layers(0)) == (18, 6)
    assert arch.state_bytes_per_seq() == 18 * 2 * 2048 * 2
    # what init_params makes is what param_count counts
    tiny = TransformerLM(MD.arch, jnp.float32)
    assert tiny.param_count(jax.eval_shape(
        tiny.init_params, jax.random.PRNGKey(0))) == MD.arch.param_count()


def test_heads_share_a_lane_row_only_where_they_fit():
    assert heads_per_lane_row(64, 64, 8) == 2
    assert heads_per_lane_row(32, 32, 8) == 4
    assert heads_per_lane_row(64, 64, 1) == 1        # no pair to make
    assert heads_per_lane_row(128, 128, 8) == 1
    assert heads_per_lane_row(192, 128, 4) == 1      # MiMo-V2.5
    assert heads_per_lane_row(24, 16, 4) == 1        # keys and values differ
    assert heads_per_lane_row(48, 48, 8) == 1        # 128 is no multiple


def test_the_three_forms_of_the_operator_agree():
    """One sequence whole, in two chunks (the second from what the first
    carried, the first padded past its true length) and as a prompt plus
    decode steps of one token: the same outputs to float32 rounding,
    and the carried state is the last two inputs."""
    rng = np.random.default_rng(0)
    T, C, K = 23, 40, 3
    v = jnp.asarray(rng.standard_normal((2, T, C)), jnp.float32)
    taps = jnp.asarray(rng.standard_normal((K, C)), jnp.float32)
    zeros = jnp.zeros((2, K - 1, C), jnp.float32)
    whole, seen = nn.short_conv(v, zeros, taps)
    want = sum(np.asarray(taps)[k] * np.pad(
        np.asarray(v), ((0, 0), (k, 0), (0, 0)))[:, :T] for k in range(K))
    assert np.abs(np.asarray(whole) - want).max() < 1e-5
    full = jnp.full((2,), T, jnp.int32)
    assert (np.asarray(nn.short_conv_carry(seen, full, K))
            == np.asarray(v[:, -2:])).all()
    # two chunks: 9 tokens in a bucket of 12, then the other 14
    first = jnp.pad(v[:, :9], ((0, 0), (0, 3), (0, 0)))
    c1, seen1 = nn.short_conv(first, zeros, taps)
    carried = nn.short_conv_carry(seen1, jnp.full((2,), 9, jnp.int32), K)
    assert (np.asarray(carried) == np.asarray(v[:, 7:9])).all()
    c2, _ = nn.short_conv(v[:, 9:], carried, taps)
    got = jnp.concatenate([c1[:, :9], c2], axis=1)
    assert np.abs(np.asarray(got - whole)).max() < 1e-5
    # a prompt of 9, then decode: a chunk of one, the state shifted
    outs = []
    for t in range(9, T):
        c, seen_t = nn.short_conv(v[:, t:t + 1], carried, taps)
        carried = seen_t[:, 1:]
        outs.append(c)
    assert np.abs(np.asarray(jnp.concatenate(outs, axis=1)
                             - whole[:, 9:])).max() < 1e-5
    # a chunk of no valid token leaves what was carried
    kept = nn.short_conv_carry(seen1, jnp.zeros((2,), jnp.int32), K)
    assert (np.asarray(kept) == 0).all()


@pytest.mark.parametrize("H,Hkv,D", [(8, 4, 64), (8, 8, 32), (4, 2, 64)])
def test_lane_packed_attention_is_the_plain_attention(H, Hkv, D):
    """Rows that hold ``pack`` KV heads side by side, read as one head
    of 128 lanes under queries laid into their own head's lanes: the
    JAX decode path over token-flat pools gives what it gives over the
    plain five-dimensional ones."""
    rng = np.random.default_rng(1)
    pack = heads_per_lane_row(D, D, Hkv)
    assert pack == 128 // D
    B, ps, P, pmax = 3, 16, 20, 5

    def t(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    q, ck, cv = t(B, H, D), t(2, P, ps, Hkv, D), t(2, P, ps, Hkv, D)
    table = jnp.asarray(rng.permutation(np.arange(1, P))[:B * pmax]
                        .reshape(B, pmax), jnp.int32)
    lens = jnp.asarray([70, 0, 41], jnp.int32)
    kw = dict(scale=0.2, layer=jnp.int32(1))
    want = A.paged_decode_attention(q, ck, cv, table, lens, **kw)
    flat = (2, P, ps * Hkv // pack, pack * D)
    got = A.paged_decode_attention(
        A.lane_pack_queries(q, Hkv, pack), ck.reshape(flat),
        cv.reshape(flat), table, lens, kv_heads=Hkv // pack, **kw)
    got = A.lane_unpack_outputs(got, Hkv, pack)
    live = (np.asarray(lens) > 0)[:, None, None]
    assert got.shape == want.shape
    assert np.abs(np.where(live, got - want, 0.0)).max() < 2e-5


def test_the_pools_hold_attention_layers_and_a_row_of_conv_state():
    eng = _mk()
    c = eng.cache
    # 2 attention layers; 2 KV heads of 64 in one 128-lane row a token
    assert c.k.shape == c.v.shape == (2, eng._num_pages, PAGE, 128)
    assert c.wk is None and c.ssm_state is None
    assert c.conv_state.shape == (5, 4, 2, 256)
    assert c.state_pool_bytes == 4 * MD.arch.state_bytes_per_seq(4)
    assert eng.page_tables.shape == (4, eng.pages_per_seq)
    assert [g.name for g in eng.model.groups] == [
        "conv_dense", "full_moe", "conv_moe"]
    assert [(r.stack, r.stack_start, r.count, r.cache_start)
            for r in eng.model.runs] == [
        ("conv_dense", 0, 2, 0), ("full_moe", 0, 1, 0),
        ("conv_moe", 0, 3, 2), ("full_moe", 1, 1, 1)]


@pytest.mark.parametrize("async_on", [False, True])
@pytest.mark.parametrize("n_prompt", [20, 150])
def test_served_path_equals_the_plain_reference(async_on, n_prompt):
    """One fresh chunk (20 tokens) and three (150 at a budget of 64: the
    conv state carried from chunk to chunk through the slot's row,
    context attention over lane-packed pages), then decode through the
    row in fused windows: every emitted logprob is the plain
    reference's."""
    eng = _mk(async_on)
    prompt = _prompt(n_prompt, 1)
    (req,) = _run(eng, [prompt], 40)
    assert _against_reference(eng, prompt, req) < 3e-4
    c = eng.counters
    assert c["moe_expert_calls_total"] > 0
    # every expert is held: every routed pair lands here
    assert c["moe_pairs_held_total"] == c["moe_pairs_routed_total"] > 0
    assert c["moe_experts_touched_total"] <= c["moe_expert_calls_total"]


def test_rows_side_by_side_and_a_reused_slot():
    """Four sequences of different lengths decode side by side, each
    through its own row; then the same prompt through a slot another
    sequence has just left gives the same logprobs (the row is zeroed
    at position 0 inside the prefill program)."""
    eng = _mk()
    prompts = [_prompt(n, 10 + n) for n in (20, 33, 70, 150)]
    reqs = _run(eng, prompts, 12)
    for p, r in zip(prompts, reqs):
        assert _against_reference(eng, p, r) < 3e-4
    one = _mk(max_num_seqs=1)
    first = _run(one, [prompts[1]], 6)[0]
    _run(one, [prompts[2]], 9)
    again = _run(one, [prompts[1]], 6)[0]
    assert again.output_tokens == first.output_tokens
    np.testing.assert_allclose(again.output_logprobs, first.output_logprobs,
                               atol=1e-6)
    assert one.counters["state_resets_total"] == 3


@pytest.mark.parametrize("async_on", [False, True])
def test_idle_rows_keep_their_bits_across_a_window(async_on):
    eng = _mk(async_on)
    req = eng.submit(_prompt(20, 4), SamplingParams(
        max_tokens=40, temperature=0.0, ignore_eos=True))
    for _ in range(6):
        eng.step()
    busy = next(i for i, s in enumerate(eng.slots) if s.request is req)
    idle = [i for i in range(4) if i != busy]
    if async_on:
        eng._drain_pipeline("idle")
    mark = jnp.asarray(np.random.default_rng(0).normal(
        size=eng.cache.conv_state[:, idle].shape), jnp.float32)
    eng.cache = dataclasses.replace(
        eng.cache, conv_state=eng.cache.conv_state.at[:, idle].set(mark))
    before = np.asarray(eng.cache.conv_state)
    for _ in range(4):
        eng.step()
    if async_on:
        eng._drain_pipeline("idle")
    after = np.asarray(eng.cache.conv_state)
    assert (after[:, idle] == before[:, idle]).all()
    assert (after[:, busy] != before[:, busy]).any()


def test_a_preempted_row_is_rebuilt_by_recompute():
    eng = _mk()
    prompt = _prompt(30, 6)
    whole = _run(eng, [prompt], 16)[0]
    req = eng.submit(list(prompt), SamplingParams(
        max_tokens=16, temperature=0.0, ignore_eos=True, logprobs=1))
    while len(req.output_tokens) < 7:
        eng.step()
    victim = next(i for i, s in enumerate(eng.slots) if s.request is req)
    eng._preempt_slot(victim)
    for _ in range(200):
        if req.finish_reason:
            break
        eng.step()
    assert req.output_tokens == whole.output_tokens
    np.testing.assert_allclose(req.output_logprobs, whole.output_logprobs,
                               atol=2e-4)
    assert eng.counters["state_recomputes_total"] == 1
    assert _against_reference(eng, prompt, req) < 3e-4


def test_a_dropped_state_or_norm_would_fail_the_check():
    """The seeded draws let the check see each new part: against the
    served logprobs, a reference whose conv layers see their newest
    input alone, whose gate is left out or whose QK norm is dropped
    reads far outside what the clean one does."""
    eng = _mk()
    prompt = _prompt(60, 3)
    (req,) = _run(eng, [prompt], 12)
    seq = prompt + req.output_tokens
    got = np.asarray(req.output_logprobs)
    ref = _reference()
    for perturb in ("conv_state_dropped", "conv_gate_dropped", "no_qk_norm",
                    "no_expert_bias", "one_expert_dropped"):
        out = ref.forward(TINY_LFM2, eng.params, seq, len(prompt) - 1,
                          perturb=perturb)
        err = np.abs(got - np.asarray(out["target"])[:-1][:len(got)]).max()
        assert err > 0.02, (perturb, err)
    stack = eng.params["conv_moe"]
    taps = np.asarray(stack["conv_w"], np.float32)
    assert taps.shape == (3, 3, 256)
    assert 0.45 < taps[:, 0].std() < 0.7 and 0.45 < taps[:, 2].std() < 0.7
    gains = np.asarray(eng.params["full_moe"]["q_norm"], np.float32)
    assert gains.shape == (2, 64) and 0.05 < gains.std() < 0.15


def test_health_surface_and_metrics():
    import threading
    import urllib.request

    from kaito_tpu.engine.metrics import EngineMetrics
    from kaito_tpu.engine.server import make_server

    eng = _mk(enable_prefix_caching=True)
    assert eng.prefix_cache is None          # requested, refused and said
    pool = eng.cache.state_pool_bytes
    report = eng.sizing_report
    assert report["state_pool_bytes"] == pool == 5 * 4 * 2 * 256 * 4
    assert report["kv_bytes_per_token"] == 2 * 2 * 2 * 64 * 4
    assert report["state_bytes_per_row"] == 5 * 2 * 256 * 4
    _run(eng, [_prompt(20, 9)], 4)
    text = EngineMetrics(eng).registry.expose()
    assert f"kaito:engine_conv_state_pool_bytes {pool}" in text
    assert f"kaito:engine_state_pool_bytes {pool}" in text
    assert "kaito:engine_state_rows_in_use 0" in text
    assert "kaito:engine_state_resets_total 1" in text
    assert "kaito:engine_moe_expert_calls_total" in text
    assert "state_rows" in eng.timeline.records()[-1]
    server = make_server(eng, eng.cfg, host="127.0.0.1", port=0)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.server_port}/health") as r:
            health = json.loads(r.read())
    finally:
        server.shutdown()
    assert health["attention"] == "jax+conv"
    assert health["prefix_cache"] == "off"
    assert health["mixers"] == {"conv": 5, "full_attention": 2}
    assert health["hbm_sizing"]["state_bytes_per_row"] == 5 * 2 * 256 * 4
    assert health["moe_combine"] == "xla"
    assert "moe_tiles" not in health    # XLA's ragged dot has no tiles


def test_a_model_with_no_conv_layer_has_no_such_family():
    from kaito_tpu.engine.metrics import EngineMetrics

    eng = InferenceEngine(EngineConfig(
        model="tiny-llama-test", max_model_len=128, page_size=16,
        max_num_seqs=2, dtype="float32", kv_dtype="float32"))
    assert eng.cache.conv_state is None and eng.attention_path == "jax"
    assert "conv_state" not in EngineMetrics(eng).registry.expose()
    assert "state_bytes_per_row" not in eng.sizing_report


@pytest.mark.parametrize("kw,names", [
    (dict(tensor_parallel=2), "tensor parallelism"),
    (dict(pipeline_parallel=2), "pipeline parallelism"),
    (dict(sequence_parallel=2), "context-parallel prefill"),
    (dict(expert_parallel=2), "expert parallelism"),
    (dict(host_kv_offload_bytes=1 << 20), "host KV offload"),
    (dict(pd_enabled=True), "prefill/decode disaggregation"),
    (dict(kv_pool_enabled=True), "the cluster KV pool"),
    (dict(speculative_ngram=3), "n-gram speculation"),
    (dict(speculative_draft="tiny-llama-test"), "draft-model speculation"),
    (dict(kv_dtype="int8"), "int8 KV cache"),
])
def test_refusals_at_start_by_name(kw, names):
    with pytest.raises(ValueError, match="recurrent state") as e:
        _mk(**kw)
    assert names in str(e.value) and next(iter(kw)) in str(e.value)


def test_a_mesh_and_imported_pages_are_refused_by_name():
    eng = _mk()
    with pytest.raises(ValueError, match="imported KV pages carry none"):
        eng.submit_with_kv(_prompt(20, 1), 3, {}, b"",
                           SamplingParams(max_tokens=2))
    with pytest.raises(ValueError, match="state pool"):
        eng.model.prefill(eng.params, eng.cache, jnp.zeros((1, 32), jnp.int32),
                          jnp.asarray([3]), jnp.zeros((1, 16), jnp.int32))
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("tensor",))
    with pytest.raises(ValueError, match="one device: no mesh"):
        InferenceEngine(eng.cfg, metadata=MD, mesh=mesh)


def test_the_loader_maps_the_familys_tensor_names():
    """A seeded state dict under the names the family publishes
    (``Lfm2Moe*``: Linear weights [out, in], the depthwise Conv1d's
    [channels, 1, taps] with its last tap on the newest input) comes
    back as the stacks it was written from."""
    from kaito_tpu.engine.weights import assemble_params

    model = TransformerLM(MD.arch, jnp.float32)
    params = model.init_params(jax.random.PRNGKey(11))
    sd = {"model.embed_tokens.weight": np.asarray(params["embed"]),
          "model.embedding_norm.weight": np.asarray(params["final_norm"])}
    plain = {"attn_norm": "operator_norm.weight", "mlp_norm": "ffn_norm.weight",
             "q_norm": "self_attn.q_layernorm.weight",
             "k_norm": "self_attn.k_layernorm.weight",
             "router_bias": "feed_forward.expert_bias"}
    linear = {"conv_in": "conv.in_proj", "conv_out": "conv.out_proj",
              "q": "self_attn.q_proj", "k": "self_attn.k_proj",
              "v": "self_attn.v_proj", "o": "self_attn.out_proj",
              "gate": "feed_forward.w1", "up": "feed_forward.w3",
              "down": "feed_forward.w2", "router": "feed_forward.gate"}
    experts = {"experts_gate": "w1", "experts_up": "w3", "experts_down": "w2"}
    for g in model.groups:
        for at, layer in enumerate(model.stack_layers(g)):
            pre = f"model.layers.{layer}."
            for key, stack in params[g.name].items():
                w = np.asarray(stack[at])
                if key in plain:
                    sd[pre + plain[key]] = w
                elif key in linear:
                    sd[pre + linear[key] + ".weight"] = w.T.copy()
                elif key == "conv_w":
                    sd[pre + "conv.conv.weight"] = \
                        w[::-1].T[:, None, :].copy()
                else:
                    for e in range(w.shape[0]):
                        sd[f"{pre}feed_forward.experts.{e}."
                           f"{experts[key]}.weight"] = w[e].T.copy()
    assert [model.stack_layers(g) for g in model.groups] == [
        [0, 1], [2, 6], [3, 4, 5]]
    assert sd["model.layers.3.conv.conv.weight"].shape == (256, 1, 3)
    back = assemble_params(model, sd.get, sorted(sd))
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        assert a.shape == b.shape and (np.asarray(a) == np.asarray(b)).all()


def test_the_estimator_counts_pages_for_attention_layers_only():
    """A sequence's bytes are its KV at full context in the attention
    layers and its row of conv state: a pool sized from every layer
    would hold a fifth of the tokens the memory has room for."""
    from kaito_tpu.estimator.estimator import (estimate_slice,
                                               max_kv_tokens)
    from kaito_tpu.sku.catalog import CHIP_CATALOG

    with open(os.path.join(ROOT, "kbench", "configs",
                           "lfm2-8b-a1b-d14.json")) as f:
        cfg = json.load(f)["config"]
    md = metadata_from_hf_config("LiquidAI/LFM2-8B-A1B", cfg)
    assert md.kv_bytes_per_token() == 6144
    by_depth = 2 * md.arch.num_layers * md.arch.num_kv_heads \
        * md.arch.head_dim * 2
    assert by_depth == 28672 and by_depth > 4.6 * md.kv_bytes_per_token()
    chip = CHIP_CATALOG["v5e"]
    est = estimate_slice(md, chip, max_model_len=5120)
    assert (est.num_chips, est.kv_bytes_per_token) == (1, 6144)
    assert est.max_kv_tokens == max_kv_tokens(md, chip, 1) > 500_000
