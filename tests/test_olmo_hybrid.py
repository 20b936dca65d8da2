"""Olmo-Hybrid through the engine (docs/kv-cache.md, "A row of matrix
state"): layers whose mixer is a gated delta rule beside MHA layers
with no rotary embedding, a QK norm over the whole projection and the
block's norms after the operators; pages for the attention layers
alone, a row of matrix state and the convolutions' tail a slot.  The
served float32 path against the plain reference
(kbench/reference/olmo_hybrid.py), the layer's three forms, the pools'
geometry, the loader's names and every refusal by name.  ONE engine a
dispatch loop for the module's tests (the refusals fail before anything
compiles)."""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kaito_tpu.engine.config import EngineConfig
from kaito_tpu.engine.engine import InferenceEngine, SamplingParams
from kaito_tpu.engine.model import TransformerLM
from kaito_tpu.engine.ops import gdn as G
from kaito_tpu.models.autogen import (SUPPORTED_ARCHITECTURES,
                                      metadata_from_hf_config)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# olmo_hybrid's shape at a tiny size: both periods of the pattern; 2
# attention heads of 128, one query head a KV head; 4 delta-rule heads
# with keys of 16 and values of 64 (two pairs of 128 lanes in the pool)
TINY_OLMO = dict(
    architectures=["OlmoHybridForCausalLM"], model_type="olmo_hybrid",
    vocab_size=512, hidden_size=256, intermediate_size=128,
    num_hidden_layers=8, num_attention_heads=2, num_key_value_heads=2,
    hidden_act="silu", max_position_embeddings=2048, attention_bias=False,
    rms_norm_eps=1e-6, tie_word_embeddings=False,
    layer_types=(["linear_attention"] * 3 + ["full_attention"]) * 2,
    linear_num_key_heads=4, linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=64, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, rope_parameters={"rope_theta": None})

MD = metadata_from_hf_config("kaito-tpu/tiny-olmo-hybrid-test", TINY_OLMO,
                             name="tiny-olmo-hybrid-test")
PAGE = 16


def _reference():
    spec = importlib.util.spec_from_file_location(
        "olmo_hybrid_reference",
        os.path.join(ROOT, "kbench", "reference", "olmo_hybrid.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _mk(async_on=False, **kw):
    base = dict(model="tiny-olmo-hybrid-test", max_model_len=256,
                page_size=PAGE, max_num_seqs=4, dtype="float32",
                kv_dtype="float32", prefill_buckets=(32, 64, 128),
                max_prefill_tokens=64, decode_run_ahead=4,
                async_dispatch=async_on, seed=5)
    base.update(kw)
    return InferenceEngine(EngineConfig(**base), metadata=MD)


@pytest.fixture(scope="module")
def eng():
    return _mk(enable_prefix_caching=True)


@pytest.fixture(scope="module")
def eng_async():
    return _mk(True)


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


def _against_reference(eng, prompt, req, perturb=""):
    seq = list(prompt) + list(req.output_tokens)
    out = _reference().forward(TINY_OLMO, eng.params, seq, len(prompt) - 1,
                               perturb=perturb)
    want = np.asarray(out["target"])[:-1]
    got = np.asarray(req.output_logprobs)
    return np.abs(got - want[:len(got)]).max()


def test_autogen_maps_the_family():
    a = MD.arch
    assert "OlmoHybridForCausalLM" in SUPPORTED_ARCHITECTURES
    assert MD.runtime == "engine"
    assert a.layer_attention == (3, 3, 3, 0) * 2
    assert a.layer_experts == (0,) * 8 and a.num_experts == 0
    assert (a.gdn_layers, a.attention_layers(0), a.conv_layers) == (6, 2, 0)
    assert (a.gdn_heads, a.gdn_key_dim, a.gdn_value_dim, a.gdn_conv,
            a.gdn_beta_scale, a.gdn_conv_dim) == (4, 16, 64, 4, 2.0, 384)
    assert (a.head_dim, a.num_kv_heads, a.qk_norm, a.qk_norm_whole,
            a.norm_after, a.rotary, a.tie_word_embeddings) == (
        128, 2, True, True, True, False, False)
    assert not a.two_kind_cache and a.kv_heads_per_row(0) == 1
    assert a.rms_norm_eps == 1e-6
    plain = metadata_from_hf_config(
        "x/y", dict(TINY_OLMO, linear_allow_neg_eigval=False)).arch
    assert plain.gdn_beta_scale == 1.0


@pytest.mark.parametrize("key,value,word", [
    ("layer_types", ["linear_attention"] * 7 + ["sliding_attention"],
     "layer_types entry 'sliding_attention'"),
    ("layer_types", ["linear_attention"] * 3,
     "must name each of the 8 layers"),
    ("linear_num_key_heads", 2, "linear_num_key_heads 2 != "
     "linear_num_value_heads 4"),
    ("rope_parameters", {"rope_theta": 500000.0}, "a rotary embedding"),
    ("attention_bias", True, "attention_bias true"),
    ("linear_conv_kernel_dim", 1, "linear_conv_kernel_dim 1"),
])
def test_autogen_refuses_by_name_what_it_does_not_implement(key, value, word):
    with pytest.raises(ValueError, match=word):
        metadata_from_hf_config("x/y", dict(TINY_OLMO, **{key: value}))


def test_published_config_counts_the_cut_and_the_whole():
    """The benchmark's file (the first 8 of 32 layers): 2,435.7M
    parameters; a cached token holds 2 attention layers x 15,360 B and
    no more, a slot's row 6 x (552,960 + 34,560) numbers.  The whole
    model: 7,430.9M."""
    with open(os.path.join(ROOT, "kbench", "configs",
                           "olmo-hybrid-7b-d8.json")) as f:
        cfg = json.load(f)
    arch = metadata_from_hf_config("allenai/Olmo-Hybrid-7B",
                                   cfg["config"]).arch
    assert abs(arch.param_count() / 2435.7e6 - 1) < 1e-3
    assert arch.param_count() == 2_435_748_072
    assert arch.kv_bytes_per_token() == 2 * 15360
    assert arch.kv_bytes_per_token(stored=True) == 2 * 15360
    assert arch.state_bytes_per_seq() == 6 * (552960 + 34560) * 2
    assert arch.state_bytes_per_seq(4) == 6 * (552960 + 34560) * 4
    # the matrix state held wider than the rest
    assert arch.state_bytes_per_seq(2, state_bytes=4) == \
        6 * (552960 * 4 + 34560 * 2)
    assert arch.gdn_state_bytes() == (552960 * 2, 34560 * 2)
    assert (arch.gdn_layers, arch.attention_layers(0),
            arch.attention_layers(1)) == (6, 2, 0)
    whole = dict(cfg["config"], num_hidden_layers=32,
                 layer_types=cfg["published"]["layer_types"])
    arch = metadata_from_hf_config("allenai/Olmo-Hybrid-7B", whole).arch
    assert abs(arch.param_count() / 7430.9e6 - 1) < 1e-3
    assert (arch.gdn_layers, arch.attention_layers(0)) == (24, 8)
    assert arch.kv_bytes_per_token() == 8 * 15360
    # what init_params makes is what param_count counts
    tiny = TransformerLM(MD.arch, jnp.float32)
    assert tiny.param_count(jax.eval_shape(
        tiny.init_params, jax.random.PRNGKey(0))) == MD.arch.param_count()


def test_the_three_forms_of_the_layer_agree():
    """One delta-rule block over one sequence whole (no cache), in two
    chunks through a row of the pools (the first padded past its true
    length, the boundary at 37: no multiple of the scan's chunk of 64)
    and as a prompt plus decode steps of one token: the same outputs
    and the same final state and tail, to float32 rounding."""
    model = TransformerLM(MD.arch, jnp.float32)
    params = model.init_params(jax.random.PRNGKey(2))
    p = jax.tree.map(lambda w: w[1], params["gdn_dense"])
    rng = np.random.default_rng(0)
    T, cut = 90, 37
    x = jnp.asarray(rng.standard_normal((1, T, 256)), jnp.float32)
    lens = lambda n: jnp.asarray([n], jnp.int32)
    kw = dict(active=None, rows=jnp.asarray([2], jnp.int32))
    whole, _, _ = model._gdn_layer(x, p, None, None, False, "train",
                                   true_lens=lens(T), start_pos=None, **kw)
    a = MD.arch
    pools = (jnp.ones((2, 4, 16, 256), jnp.float32),      # stale rows
             jnp.ones((2, 4, 3, a.gdn_conv_dim), jnp.float32))
    first = jnp.pad(x[:, :cut], ((0, 0), (0, 64 - cut), (0, 0)))
    y1, pools, _ = model._gdn_layer(
        first, p, pools, 1, False, "prefill", true_lens=lens(cut),
        start_pos=jnp.asarray([0], jnp.int32), **kw)
    after_prompt = pools
    y2, pools, _ = model._gdn_layer(
        x[:, cut:], p, pools, 1, False, "prefill", true_lens=lens(T - cut),
        start_pos=jnp.asarray([cut], jnp.int32), **kw)
    got = jnp.concatenate([y1[:, :cut], y2], axis=1)
    assert float(jnp.abs(got - whole).max()) < 2e-4
    # a chunk at position 0 starts from zeros whatever the row held,
    # and only layer 1's row 2 is written
    assert (np.asarray(pools[0][0]) == 1).all()
    assert (np.asarray(pools[0][1, :2]) == 1).all()
    # the tail is the last three inputs of the convolution
    proj = x[0, -3:] @ p["gdn_in"][:, :a.gdn_conv_dim]
    assert np.abs(np.asarray(pools[1][1, 2] - proj)).max() < 1e-5
    # a prompt of 37, then decode: every slot steps, slot 2 is the row
    st, cv = after_prompt
    outs = []
    for t in range(cut, T):
        xt = jnp.zeros((4, 1, 256), jnp.float32).at[2].set(x[0, t])
        y, (st, cv), _ = model._gdn_layer(
            xt, p, (st, cv), 1, False, "decode", true_lens=None,
            start_pos=None, rows=None,
            active=jnp.asarray([False, False, True, False]))
        outs.append(y[2])
    dec = jnp.stack(outs, axis=1)
    assert float(jnp.abs(dec - whole[:, cut:]).max()) < 2e-4
    assert float(jnp.abs(st[1, 2] - pools[0][1, 2]).max()) < 2e-5
    assert float(jnp.abs(cv[1, 2] - pools[1][1, 2]).max()) < 1e-5
    # rows that do not decode keep their bits
    assert (np.asarray(st[1, :2]) == 1).all()
    assert (np.asarray(cv[1, 3]) == 1).all()


def test_the_pools_hold_attention_layers_and_a_row_of_matrix_state(eng):
    c = eng.cache
    # 2 attention layers; 2 KV heads of 128, a lane tile each
    assert c.k.shape == c.v.shape == (2, eng._num_pages, PAGE * 2, 128)
    assert c.wk is None and c.ssm_state is None
    assert c.delta_state.shape == (6, 4, 16, 4 * 64)
    assert c.conv_state.shape == (6, 4, 3, 384)
    assert c.state_pool_bytes == 4 * MD.arch.state_bytes_per_seq(4)
    assert eng.page_tables.shape == (4, eng.pages_per_seq)
    assert [g.name for g in eng.model.groups] == ["gdn_dense", "full_dense"]
    assert [(r.stack, r.stack_start, r.count, r.cache_start)
            for r in eng.model.runs] == [
        ("gdn_dense", 0, 3, 0), ("full_dense", 0, 1, 0),
        ("gdn_dense", 3, 3, 3), ("full_dense", 1, 1, 1)]
    # no rotary embedding: no table is built
    assert eng.model._inv_freq_global is None
    assert not hasattr(eng.model, "_kind_inv_freq")
    full = eng.params["full_dense"]
    assert full["q_norm"].shape == full["k_norm"].shape == (2, 256)
    assert "attn_norm" in full and "mlp_norm" in eng.params["gdn_dense"]


@pytest.mark.parametrize("n_prompt", [20, 150])
def test_served_path_equals_the_plain_reference(eng, n_prompt):
    """One fresh chunk (20 tokens) and three (150 at a budget of 64: the
    matrix state and the convolutions' tail carried from chunk to chunk
    through the slot's row, context attention at one query head a KV
    head), then decode through the row in fused windows: every emitted
    logprob is the plain reference's token-by-token recurrence."""
    prompt = _prompt(n_prompt, 1)
    (req,) = _run(eng, [prompt], 40)
    assert _against_reference(eng, prompt, req) < 3e-4


def test_the_two_deep_loop_serves_the_same(eng_async):
    prompt = _prompt(150, 1)
    (req,) = _run(eng_async, [prompt], 40)
    assert _against_reference(eng_async, prompt, req) < 3e-4


def test_rows_side_by_side_and_a_reused_slot(eng):
    """Four sequences of different lengths decode side by side, each
    through its own row; then a prompt through a slot another sequence
    has just left gives what it gave before (the row is zeroed at
    position 0 inside the prefill program)."""
    prompts = [_prompt(n, 10 + n) for n in (20, 33, 70, 150)]
    reqs = _run(eng, prompts, 12)
    for p, r in zip(prompts, reqs):
        assert _against_reference(eng, p, r) < 3e-4
    resets = eng.counters["state_resets_total"]
    again = _run(eng, [prompts[1]], 12)[0]
    assert again.output_tokens == reqs[1].output_tokens
    np.testing.assert_allclose(again.output_logprobs,
                               reqs[1].output_logprobs, atol=2e-5)
    assert eng.counters["state_resets_total"] == resets + 1


def test_idle_rows_keep_their_bits_across_a_window(eng_async):
    eng = eng_async
    req = eng.submit(_prompt(20, 4), SamplingParams(
        max_tokens=40, temperature=0.0, ignore_eos=True))
    for _ in range(6):
        eng.step()
    busy = next(i for i, s in enumerate(eng.slots) if s.request is req)
    idle = [i for i in range(4) if i != busy]
    eng._drain_pipeline("idle")
    rng = np.random.default_rng(0)
    marked = {}
    for name in ("delta_state", "conv_state"):
        pool = getattr(eng.cache, name)
        mark = jnp.asarray(rng.normal(size=pool[:, idle].shape), jnp.float32)
        marked[name] = pool.at[:, idle].set(mark)
    eng.cache = dataclasses.replace(eng.cache, **marked)
    before = {k: np.asarray(v) for k, v in marked.items()}
    for _ in range(4):
        eng.step()
    eng._drain_pipeline("idle")
    for name, was in before.items():
        after = np.asarray(getattr(eng.cache, name))
        assert (after[:, idle] == was[:, idle]).all()
        assert (after[:, busy] != was[:, busy]).any()
    while not req.finish_reason:
        eng.step()


def test_a_preempted_row_is_rebuilt_by_recompute(eng):
    prompt = _prompt(30, 6)
    whole = _run(eng, [prompt], 16)[0]
    before = eng.counters["state_recomputes_total"]
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
    assert eng.counters["state_recomputes_total"] == before + 1
    assert _against_reference(eng, prompt, req) < 3e-4


def test_a_dropped_part_would_fail_the_check(eng):
    """The seeded draws let the check see each new part: against the
    served logprobs, a reference with the delta term, the decay, the
    doubled beta, the carried tail, the output gate, the QK norm or the
    norms' place changed, or a rotary embedding added, reads far
    outside what the clean one does."""
    prompt = _prompt(60, 3)
    (req,) = _run(eng, [prompt], 12)
    for perturb in ("delta_term_dropped", "decay_dropped",
                    "beta_not_doubled", "conv_state_dropped",
                    "out_gate_dropped", "no_qk_norm", "rope_added",
                    "pre_norm", "last_layer_dropped"):
        err = _against_reference(eng, prompt, req, perturb)
        assert err > 0.05, (perturb, err)
    stack = eng.params["gdn_dense"]
    taps = np.asarray(stack["gdn_conv_w"], np.float32)
    assert taps.shape == (6, 4, 384)
    assert 0.4 < taps[:, 0].std() < 0.6 and 0.4 < taps[:, 3].std() < 0.6
    for gains in (stack["gdn_norm"], eng.params["full_dense"]["q_norm"]):
        assert 0.05 < np.asarray(gains, np.float32).std() < 0.15
    A = np.exp(np.asarray(stack["gdn_a_log"], np.float32))
    assert 1.0 <= A.min() and A.max() <= 16.0
    dt = np.log1p(np.exp(np.asarray(stack["gdn_dt_bias"], np.float32)))
    assert 1e-3 * 0.99 <= dt.min() and dt.max() <= 1e-1 * 1.01
    assert (np.asarray(stack["attn_norm"]) == 1).all()


def test_health_surface_and_metrics(eng):
    import threading
    import urllib.request

    from kaito_tpu.engine.metrics import EngineMetrics
    from kaito_tpu.engine.server import make_server

    assert eng.prefix_cache is None          # requested, refused and said
    pool = eng.cache.state_pool_bytes
    row = 6 * (16 * 256 + 3 * 384) * 4
    report = eng.sizing_report
    assert report["state_pool_bytes"] == pool == 4 * row
    assert report["kv_bytes_per_token"] == 2 * 2 * 2 * 128 * 4
    assert report["state_bytes_per_row"] == row
    _run(eng, [_prompt(20, 9)], 4)
    text = EngineMetrics(eng).registry.expose()
    assert f"kaito:engine_state_pool_bytes {pool}" in text
    assert "kaito:engine_conv_state_pool_bytes" not in text
    assert "kaito:engine_state_rows_in_use 0" in text
    assert "kaito:engine_state_resets_total" in text
    assert "kaito:engine_state_recomputes_total" in text
    assert "state_rows" in eng.timeline.records()[-1]
    server = make_server(eng, eng.cfg, host="127.0.0.1", port=0)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.server_port}/health") as r:
            health = json.loads(r.read())
    finally:
        server.shutdown()
    assert health["attention"] == "jax+delta"
    assert health["prefix_cache"] == "off"
    assert health["mixers"] == {"gated_delta_rule": 6, "full_attention": 2}
    assert health["hbm_sizing"]["state_bytes_per_row"] == row
    assert health["hbm_sizing"]["kv_bytes_per_token"] == 4096


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


def test_a_mesh_and_imported_pages_are_refused_by_name(eng):
    with pytest.raises(ValueError, match="imported KV pages carry none"):
        eng.submit_with_kv(_prompt(20, 1), 3, {}, b"",
                           SamplingParams(max_tokens=2))
    with pytest.raises(ValueError, match="state pool"):
        eng.model.prefill(eng.params, eng.cache, jnp.zeros((1, 32), jnp.int32),
                          jnp.asarray([3]), jnp.zeros((1, 16), jnp.int32))
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("tensor",))
    with pytest.raises(ValueError, match="one device: no mesh"):
        InferenceEngine(eng.cfg, metadata=MD, mesh=mesh)
    # what the new fields need is refused where layers name no kinds
    from kaito_tpu.models.metadata import ModelArch
    with pytest.raises(NotImplementedError, match="layers that name"):
        TransformerLM(ModelArch(vocab_size=64, hidden_size=32, num_layers=1,
                                num_heads=2, num_kv_heads=2, head_dim=16,
                                intermediate_size=64, norm_after=True))


def test_the_loader_maps_the_familys_tensor_names():
    """A seeded state dict under the names the family publishes (Linear
    weights [out, in]; a depthwise Conv1d's [channels, 1, taps] with its
    last tap on the newest input; the block's norms named for where they
    stand) comes back as the stacks it was written from."""
    from kaito_tpu.engine.weights import assemble_params

    model = TransformerLM(MD.arch, jnp.float32)
    params = model.init_params(jax.random.PRNGKey(11))
    sd = {"model.embed_tokens.weight": np.asarray(params["embed"]),
          "lm_head.weight": np.asarray(params["lm_head"]),
          "model.norm.weight": np.asarray(params["final_norm"])}
    plain = {"attn_norm": "post_attention_layernorm.weight",
             "mlp_norm": "post_feedforward_layernorm.weight",
             "q_norm": "self_attn.q_norm.weight",
             "k_norm": "self_attn.k_norm.weight",
             "gdn_a_log": "linear_attn.A_log",
             "gdn_dt_bias": "linear_attn.dt_bias",
             "gdn_norm": "linear_attn.o_norm.weight"}
    linear = {"q": "self_attn.q_proj", "k": "self_attn.k_proj",
              "v": "self_attn.v_proj", "o": "self_attn.o_proj",
              "gate": "mlp.gate_proj", "up": "mlp.up_proj",
              "down": "mlp.down_proj", "gdn_out": "linear_attn.o_proj"}
    a = MD.arch
    H, dk, dv = a.gdn_heads, a.gdn_key_dim, a.gdn_value_dim
    widths = {"q": H * dk, "k": H * dk, "v": H * dv, "g": H * dv,
              "a": H, "b": H}
    fused = {"gdn_in": "qkvg", "gdn_gates": "ab"}
    for g in model.groups:
        for at, layer in enumerate(model.stack_layers(g)):
            pre = f"model.layers.{layer}."
            for key, stack in params[g.name].items():
                w = np.asarray(stack[at])
                if key in plain:
                    sd[pre + plain[key]] = w
                elif key in linear:
                    sd[pre + linear[key] + ".weight"] = w.T.copy()
                elif key in fused:
                    lo = 0
                    for n in fused[key]:
                        sd[f"{pre}linear_attn.{n}_proj.weight"] = \
                            w[:, lo:lo + widths[n]].T.copy()
                        lo += widths[n]
                    assert lo == w.shape[1]
                else:
                    assert key == "gdn_conv_w"
                    lo = 0
                    for n in "qkv":
                        sd[f"{pre}linear_attn.{n}_conv1d.weight"] = \
                            w[:, lo:lo + widths[n]].T[:, None, :].copy()
                        lo += widths[n]
    assert [model.stack_layers(g) for g in model.groups] == [
        [0, 1, 2, 4, 5, 6], [3, 7]]
    assert sd["model.layers.4.linear_attn.v_conv1d.weight"].shape == (
        256, 1, 4)
    sd = {k.removeprefix("model."): v for k, v in sd.items()}
    back = assemble_params(model, sd.get, sorted(sd))
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for x, y in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        assert x.shape == y.shape and (np.asarray(x) == np.asarray(y)).all()


def test_the_estimator_counts_pages_for_attention_layers_only():
    """A sequence's bytes are its KV at full context in the attention
    layers and its row of matrix state: at the whole model's 65,536
    positions the pages of 8 layers are 8.05 GB, a quarter of what 32
    layers of pages would be."""
    from kaito_tpu.estimator.estimator import estimate_slice
    from kaito_tpu.sku.catalog import CHIP_CATALOG

    with open(os.path.join(ROOT, "kbench", "configs",
                           "olmo-hybrid-7b-d8.json")) as f:
        cfg = json.load(f)
    md = metadata_from_hf_config("allenai/Olmo-Hybrid-7B", cfg["config"])
    assert md.kv_bytes_per_token() == 30720
    est = estimate_slice(md, CHIP_CATALOG["v5e"], max_model_len=5120)
    assert (est.num_chips, est.kv_bytes_per_token) == (1, 30720)
    whole = metadata_from_hf_config("allenai/Olmo-Hybrid-7B", dict(
        cfg["config"], num_hidden_layers=32,
        layer_types=cfg["published"]["layer_types"]))
    assert whole.kv_bytes_per_token() * 65536 == 8 * 15360 * 65536
    assert whole.arch.state_bytes_per_seq() == 24 * (552960 + 34560) * 2
