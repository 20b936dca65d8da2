"""MLA (DeepSeek-style latent attention) engine support."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kaito_tpu.engine.kv_cache import create_kv_cache
from kaito_tpu.engine.model import TransformerLM
from kaito_tpu.models.autogen import arch_from_hf_config

MLA_CFG = {
    "architectures": ["DeepseekV3ForCausalLM"],
    "model_type": "deepseek_v3",
    "vocab_size": 512,
    "hidden_size": 64,
    "num_hidden_layers": 3,
    "num_attention_heads": 4,
    "num_key_value_heads": 4,
    "intermediate_size": 128,
    "moe_intermediate_size": 32,
    "n_routed_experts": 4,
    "num_experts_per_tok": 2,
    "n_shared_experts": 1,
    "first_k_dense_replace": 1,
    "kv_lora_rank": 32,
    "q_lora_rank": 48,
    "qk_rope_head_dim": 16,
    "qk_nope_head_dim": 24,
    "v_head_dim": 24,
    "max_position_embeddings": 256,
}
PS = 16


def _setup(batch=1):
    arch = arch_from_hf_config(MLA_CFG)
    model = TransformerLM(arch, dtype=jnp.float32)
    params = model.init_params(jax.random.PRNGKey(0))
    cache = create_kv_cache(arch, 64, PS, jnp.float32)
    pt = np.zeros((batch, 8), np.int32)
    for b in range(batch):
        pt[b] = np.arange(1 + b * 8, 9 + b * 8)
    return arch, model, params, cache, jnp.asarray(pt)


def test_mla_cache_holds_latent_only():
    arch, model, params, cache, pt = _setup()
    # cache "k" is the latent stream: 1 head, kv_lora+rope wide
    assert cache.k.shape == (3, 64, PS, 1, 32 + 16)
    assert cache.v.shape[-1] == 0
    assert arch.kv_bytes_per_token(4) == 3 * (32 + 16) * 4


def test_mla_prefill_decode_consistency():
    arch, model, params, cache, pt = _setup()
    rng = np.random.RandomState(0)
    full = jnp.asarray(rng.randint(0, arch.vocab_size, (1, 10)), jnp.int32)

    _, logits_full, _ = model.prefill(
        params, cache, full, jnp.asarray([10], jnp.int32), pt)

    cache_b = create_kv_cache(arch, 64, PS, jnp.float32)
    cache_b, _, _ = model.prefill(
        params, cache_b, full[:, :7], jnp.asarray([7], jnp.int32), pt)
    logits_step = None
    for t in range(7, 10):
        cache_b, logits_step = model.decode(
            params, cache_b, full[:, t], jnp.asarray([t], jnp.int32), pt)
    np.testing.assert_allclose(
        np.asarray(logits_step), np.asarray(logits_full), rtol=3e-4, atol=3e-4)


def test_mla_chunked_prefill_matches_full():
    """Chunked MLA prefill threads start_pos: later chunks write at the
    right pages and attend over the paged latent history (ADVICE r1:
    start was hardcoded to 0, silently corrupting long MLA prompts)."""
    arch, model, params, cache, pt = _setup()
    rng = np.random.RandomState(2)
    full = jnp.asarray(rng.randint(0, arch.vocab_size, (1, 24)), jnp.int32)

    _, logits_full, _ = model.prefill(
        params, cache, full, jnp.asarray([24], jnp.int32), pt)

    cache_b = create_kv_cache(arch, 64, PS, jnp.float32)
    cache_b, _, _ = model.prefill(
        params, cache_b, full[:, :16], jnp.asarray([16], jnp.int32), pt)
    cache_b, logits_chunk, _ = model.prefill(
        params, cache_b, full[:, 16:], jnp.asarray([8], jnp.int32), pt,
        start_pos=jnp.asarray([16], jnp.int32))
    np.testing.assert_allclose(
        np.asarray(logits_chunk), np.asarray(logits_full),
        rtol=3e-4, atol=3e-4)


def test_mla_engine_long_prompt_chunked():
    """Engine-level: an MLA prompt longer than max_prefill_tokens decodes
    identically to one prefilled in a single chunk."""
    from kaito_tpu.engine.config import EngineConfig
    from kaito_tpu.engine.engine import InferenceEngine, SamplingParams
    from kaito_tpu.models.autogen import metadata_from_hf_config

    md = metadata_from_hf_config("test/tiny-mla", MLA_CFG, name="tiny-mla-test")
    common = dict(model="tiny-mla-test", max_model_len=128, page_size=16,
                  max_num_seqs=2, dtype="float32", kv_dtype="float32",
                  prefill_buckets=(16, 32, 64))
    chunked = InferenceEngine(
        EngineConfig(**common, max_prefill_tokens=16), metadata=md)
    whole = InferenceEngine(
        EngineConfig(**common, max_prefill_tokens=1024), metadata=md)
    rng = np.random.RandomState(3)
    prompt = [int(t) for t in rng.randint(0, 500, 40)]
    p = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)
    chunked.start(); whole.start()
    try:
        ref = list(whole.submit(prompt, p).stream())
        got = list(chunked.submit(prompt, p).stream())
        assert got == ref
    finally:
        chunked.stop(); whole.stop()


def test_mla_train_matches_prefill_logits():
    arch, model, params, cache, pt = _setup()
    rng = np.random.RandomState(1)
    toks = jnp.asarray(rng.randint(0, arch.vocab_size, (1, 8)), jnp.int32)
    _, logits_prefill, _ = model.prefill(
        params, cache, toks, jnp.asarray([8], jnp.int32), pt)
    logits_train = model.forward_train(params, toks, remat=False)
    np.testing.assert_allclose(
        np.asarray(logits_train[:, -1]), np.asarray(logits_prefill),
        rtol=2e-4, atol=2e-4)


def test_mla_engine_end_to_end():
    """Full engine round trip with a tiny MLA+MoE preset."""
    from kaito_tpu.engine.config import EngineConfig
    from kaito_tpu.engine.engine import InferenceEngine, SamplingParams
    from kaito_tpu.models.autogen import metadata_from_hf_config

    md = metadata_from_hf_config("test/tiny-mla", MLA_CFG, name="tiny-mla-test")
    cfg = EngineConfig(model="tiny-mla-test", max_model_len=128, page_size=16,
                       max_num_seqs=2, dtype="float32", kv_dtype="float32",
                       prefill_buckets=(32,))
    eng = InferenceEngine(cfg, metadata=md)
    eng.start()
    try:
        p = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)
        a = list(eng.submit([3, 4, 5], p).stream())
        b = list(eng.submit([3, 4, 5], p).stream())
        assert len(a) == 6 and a == b
    finally:
        eng.stop()


def test_deepseek_v3_full_arch_constructs():
    """The real DeepSeek-V3 geometry (61 layers, 256 experts) builds its
    spec tree without materializing weights."""
    from kaito_tpu.models import get_model_by_name

    md = get_model_by_name("deepseek-v3-0324")
    model = TransformerLM(md.arch, dtype=jnp.bfloat16)
    specs = model._layer_specs(True)
    assert specs["kv_b_k"][0] == (512, 128 * 128)
    assert specs["router"][0] == (7168, 256)
    axes = model.param_logical_axes()
    assert "moe" in axes and "dense" in axes


# ----------------------------------------------------------------------
# The attention weights are multiplied where they lie (PR 47): a query
# head of 192 = 128 | 64 lanes takes a barrier on its activations, the
# absorbed products read kv_b_k / kv_b_v head-major, and the tree that
# init_params draws stays as it is.
# ----------------------------------------------------------------------

def _latent_cfg(width: int) -> dict:
    """joyai_llm_flash's shape at a tiny size with its published head
    widths: q heads of [128 | width - 128], values of 128."""
    return dict(
        architectures=["JoyAILLMFlashForCausalLM"],
        model_type="joyai_llm_flash", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_hidden_layers=3, num_attention_heads=2,
        num_key_value_heads=2, head_dim=width - 128, kv_lora_rank=128,
        q_lora_rank=48, qk_head_dim=width, qk_nope_head_dim=128,
        qk_rope_head_dim=width - 128, v_head_dim=128, rope_theta=32000000,
        rope_interleave=True, rope_scaling=None, attention_bias=False,
        rms_norm_eps=1e-6, hidden_act="silu", max_position_embeddings=2048,
        tie_word_embeddings=False, first_k_dense_replace=1, moe_layer_freq=1,
        moe_intermediate_size=32, n_routed_experts=4, expert_shards=4,
        expert_shard=0, n_shared_experts=1, num_experts_per_tok=4,
        norm_topk_prob=True, scoring_func="sigmoid", n_group=1, topk_group=1,
        topk_method="noaux_tc", routed_scaling_factor=2.5, ep_size=1,
        num_nextn_predict_layers=1)


@pytest.mark.parametrize("path", ["xla", "kernel"])
@pytest.mark.parametrize("width", [192, 256])
def test_both_head_widths_equal_a_float32_evaluation_of_the_tree(width, path):
    """Prefill and two decode steps, through the XLA paths over the
    five-dimensional pool and through both kernels (interpreted) over
    the kernel-read pool with the head-major weights beside the drawn
    ones, against the plain reference's float32 evaluation of the tree
    as ``init_params`` draws it."""
    from jax.experimental.pallas import tpu as pltpu

    cfg = _latent_cfg(width)
    arch = arch_from_hf_config(cfg)
    kernel = path == "kernel"
    model = TransformerLM(arch, dtype=jnp.float32,
                          attn_impl="pallas" if kernel else "jax")
    model.moe_impl = "ragged"
    drawn = model.init_params(jax.random.PRNGKey(3))
    params = model.latent_head_major(drawn) if kernel else drawn
    cache = create_kv_cache(arch, 12, PS, jnp.float32, latent_kernel=kernel)
    n = 100
    seq = np.random.default_rng(width).integers(1, 500, n + 2)
    toks = jnp.asarray(np.concatenate([seq[:n], np.zeros(128 - n, int)])[None],
                       jnp.int32)
    pt = jnp.asarray(np.arange(1, 10)[None], jnp.int32)

    @jax.jit
    def served(params, cache):
        cache, l0, _ = model.prefill(params, cache, toks,
                                     jnp.asarray([n], jnp.int32), pt)
        step = []
        for i in (n, n + 1):
            cache, l = model.decode(params, cache,
                                    jnp.asarray(seq[i:i + 1], jnp.int32),
                                    jnp.asarray([i], jnp.int32), pt)
            step.append(l)
        return jax.nn.log_softmax(jnp.concatenate([l0, *step]), axis=-1)

    if kernel:
        with pltpu.force_tpu_interpret_mode():
            got = np.asarray(served(params, cache))
    else:
        got = np.asarray(served(params, cache))
    from test_latent_engine import _reference

    want = _reference().forward(cfg, drawn, [int(t) for t in seq], n - 1)
    target = np.asarray(want["target"])
    assert np.abs(got[0, seq[n]] - target[0]) < 3e-4
    assert np.abs(got[1, seq[n + 1]] - target[1]) < 3e-4
    assert np.abs(got.max(axis=-1) - np.asarray(want["top"])).max() < 3e-4


def _latent_shapes(width: int):
    arch = arch_from_hf_config(_latent_cfg(width))
    model = TransformerLM(arch, dtype=jnp.float32)
    model.moe_impl = "ragged"
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: create_kv_cache(arch, 8, PS, jnp.float32))
    return model, params, cache


def _decode_jaxpr(width: int, rows: int) -> str:
    model, params, cache = _latent_shapes(width)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    return str(jax.make_jaxpr(model.decode)(params, cache, i32(rows),
                                            i32(rows), i32(rows, 4)))


def _prefill_jaxpr(width: int, tokens: int) -> str:
    model, params, cache = _latent_shapes(width)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    return str(jax.make_jaxpr(model.prefill)(params, cache, i32(1, tokens),
                                             i32(1), i32(1, 4)))


def _attn_qkv_jaxpr(arch, batch: int, tokens: int) -> str:
    """``_attn_qkv`` of the first group's layer (under its kind, where
    the layers name theirs) at ``batch * tokens`` rows."""
    model = TransformerLM(arch, dtype=jnp.float32)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    g = model.groups[0]
    p = {k: jax.ShapeDtypeStruct(v.shape[1:], v.dtype)
         for k, v in params[g.name].items()}
    kind = model.kinds[g.kind] if arch.layer_attention is not None else None
    return str(jax.make_jaxpr(
        lambda x, p, pos: model._attn_qkv(x, p, pos, None, kind=kind))(
        jax.ShapeDtypeStruct((batch, tokens, arch.hidden_size), jnp.float32),
        p, jax.ShapeDtypeStruct((batch, tokens), jnp.int32)))


def _qkv_jaxpr(head_dim: int) -> str:
    """``_attn_qkv`` of a model whose layers name their kinds, at keys
    of ``head_dim`` (MiMo's are 192)."""
    from test_two_kind_engine import TINY_MIMO

    return _attn_qkv_jaxpr(
        arch_from_hf_config(dict(TINY_MIMO, head_dim=head_dim,
                                 swa_head_dim=head_dim)), 2, 1)


def _plain_arch(rotary: float):
    """A tiny decoder whose layers name no kind (``_attn_qkv``'s plain
    branch), with biases on q, k and v: phi-like where three quarters
    of a head are rotated, and with the whole head rotated."""
    return arch_from_hf_config({
        "architectures": ["LlamaForCausalLM"], "model_type": "llama",
        "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "intermediate_size": 128, "max_position_embeddings": 256,
        "attention_bias": True, "partial_rotary_factor": rotary})


def _plain_qkv_jaxpr(rotary: float, batch: int, tokens: int) -> str:
    """The plain branch at ``batch * tokens`` rows against a hidden
    size of 64."""
    return _attn_qkv_jaxpr(_plain_arch(rotary), batch, tokens)


@pytest.mark.parametrize("jaxpr,args,barrier", [
    # a 192-wide latent query head at decode rows: the activations pay
    (_decode_jaxpr, (192, 4), True),
    # whole tiles: nothing to re-lay, the program is what it was
    (_decode_jaxpr, (256, 4), False),
    # a short chunk, fewer rows than q_b has (48 here): still the
    # activations
    (_prefill_jaxpr, (192, 32), True),
    # a chunk with more rows than q_b: the weight is the smaller of
    # the two, and its re-lay is left to the compiler
    (_prefill_jaxpr, (192, 64), False),
    (_prefill_jaxpr, (256, 32), False),
    # the same pin for ``_attn_qkv``: MiMo's 192-wide keys, and heads
    # of at most one tile
    (_qkv_jaxpr, (192,), True),
    (_qkv_jaxpr, (24,), False),
    # the plain branch (no kinds: phi-4-mini's 96 rotated lanes of 128,
    # falcon-h1's whole head) goes by rows alone: decode rows and a
    # chunk shorter than the hidden size (64 here) re-lay the
    # activations, rows equal to it or past it are the compiler's
    (_plain_qkv_jaxpr, (0.75, 8, 1), True),
    (_plain_qkv_jaxpr, (1.0, 8, 1), True),
    (_plain_qkv_jaxpr, (0.75, 1, 32), True),
    (_plain_qkv_jaxpr, (1.0, 1, 48), True),
    (_plain_qkv_jaxpr, (0.75, 1, 64), False),
    (_plain_qkv_jaxpr, (1.0, 1, 64), False),
    (_plain_qkv_jaxpr, (0.75, 2, 64), False),
    (_plain_qkv_jaxpr, (1.0, 64, 1), False),
])
def test_a_barrier_stands_where_a_head_is_no_whole_number_of_tiles(
        jaxpr, args, barrier):
    assert ("optimization_barrier" in jaxpr(*args)) == barrier


@pytest.mark.parametrize("rotary,batch,tokens,overlap", [
    (0.75, 8, 1, False),
    (1.0, 8, 1, False),
    (0.75, 1, 32, False),
    (1.0, 2, 24, False),
    # the column-parallel q through the all-gather ring feeds the same
    # sum (two virtual devices)
    (0.75, 8, 1, True),
])
def test_the_plain_branchs_barrier_changes_no_value(
        rotary, batch, tokens, overlap, monkeypatch):
    """q, k and v with a LoRA delta and a bias in the sum: the jitted
    call with the barrier and the jitted call with the barrier taken
    out agree to the last bit; the eager call, whose sums no compiler
    fuses, to a rounding of either."""
    model = TransformerLM(_plain_arch(rotary), dtype=jnp.float32)
    model.lora_scaling = 0.5
    stack = model.init_params(jax.random.PRNGKey(1))[model.groups[0].name]
    p = {k: v[1] for k, v in stack.items()}
    rng = np.random.default_rng(7)
    normal = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    for name in ("q", "k", "v"):
        p[name + "_bias"] = normal(*p[name + "_bias"].shape)
        p[name + "_lora_a"] = normal(64, 4)
        p[name + "_lora_b"] = normal(4, p[name].shape[1])
    x = normal(batch, tokens, 64)
    pos = jnp.broadcast_to(jnp.arange(tokens, dtype=jnp.int32) + 3,
                           (batch, tokens))
    handle = None
    if overlap:
        from jax.sharding import Mesh

        handle = (Mesh(np.array(jax.devices()[:2]), ("tensor",)), "tensor")

    def qkv():
        # a new function a trace: jit keeps its traces by function
        return lambda x, p, pos: model._attn_qkv(x, p, pos, None,
                                                 overlap=handle)

    assert "optimization_barrier" in str(jax.make_jaxpr(qkv())(x, p, pos))
    held = jax.jit(qkv())(x, p, pos)
    eager = qkv()(x, p, pos)
    monkeypatch.setattr(jax.lax, "optimization_barrier", lambda t: t)
    assert "optimization_barrier" not in str(jax.make_jaxpr(qkv())(x, p, pos))
    free = jax.jit(qkv())(x, p, pos)
    for a, b, c in zip(held, free, eager):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-5, atol=1e-5)


def test_head_major_weights_are_derived_and_the_drawn_tree_stands():
    """``init_params`` gives the tree the benchmark's reference reads by
    name; the head-major pair is added beside it, from it."""
    arch = arch_from_hf_config(_latent_cfg(192))
    model = TransformerLM(arch, dtype=jnp.float32)
    drawn = model.init_params(jax.random.PRNGKey(0))
    assert sorted(drawn) == ["dense", "embed", "final_norm", "lm_head", "moe"]
    latent = {"attn_norm": (64,), "kv_a": (64, 192), "kv_a_norm": (128,),
              "kv_b_k": (128, 256), "kv_b_v": (128, 256), "o": (256, 64),
              "q_a": (64, 48), "q_a_norm": (48,), "q_b": (48, 384)}
    for name, count in (("dense", 1), ("moe", 2)):
        assert {k: v.shape[1:] for k, v in drawn[name].items()
                if k in latent} == latent
        assert all(v.shape[0] == count for v in drawn[name].values())
    held = model.latent_head_major(drawn)
    for name in ("dense", "moe"):
        assert set(held[name]) - set(drawn[name]) == {"kv_b_k_hm",
                                                      "kv_b_v_hm"}
        assert all(held[name][k] is v for k, v in drawn[name].items())
        for flat, hm in (("kv_b_k", "kv_b_k_hm"), ("kv_b_v", "kv_b_v_hm")):
            w = np.asarray(drawn[name][flat])            # [n, dl, H*d]
            assert held[name][hm].shape == (w.shape[0], 2, 128, 128)
            np.testing.assert_array_equal(
                np.asarray(held[name][hm])[:, 1, 5, :], w[:, :, 128 + 5])
    assert all(held[k] is drawn[k] for k in ("embed", "final_norm", "lm_head"))


@pytest.mark.parametrize("kw,form", [
    (dict(), "as_drawn"),
    # the kernel-read pool (a TPU's path, asked for by hand here):
    # decode would read the pair head-major
    (dict(use_pallas=True, dtype="bfloat16", kv_dtype="bfloat16"),
     "head_major"),
])
def test_health_names_the_form_of_the_latent_weights(kw, form):
    import json
    import threading
    import urllib.request

    from kaito_tpu.engine.config import EngineConfig
    from kaito_tpu.engine.engine import InferenceEngine
    from kaito_tpu.engine.server import make_server
    from kaito_tpu.models.autogen import metadata_from_hf_config

    md = metadata_from_hf_config("kaito-tpu/tiny-latent-192-test",
                                 _latent_cfg(192), name="tiny-latent-192-test")
    eng = InferenceEngine(EngineConfig(**{**dict(
        model=md.name, max_model_len=256, page_size=PS, max_num_seqs=2,
        dtype="float32", kv_dtype="float32", prefill_buckets=(32, 64),
        max_prefill_tokens=64, seed=5), **kw}), metadata=md)
    assert eng.latent_weights == form
    assert ("kv_b_k_hm" in eng.params["moe"]) == (form == "head_major")
    # what the pool is sized after: every resident leaf, the pair too
    drawn = jax.eval_shape(eng.model.init_params, jax.random.PRNGKey(0))
    assert set(drawn["moe"]) <= set(eng.params["moe"])
    server = make_server(eng, eng.cfg, host="127.0.0.1", port=0)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        health = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{server.server_address[1]}/health",
            timeout=30).read())
    finally:
        server.shutdown()
        server.server_close()
    assert health["latent_weights"] == form


def test_a_model_without_latent_attention_names_no_such_form():
    from kaito_tpu.engine.config import EngineConfig
    from kaito_tpu.engine.engine import InferenceEngine

    eng = InferenceEngine(EngineConfig(model="tiny-llama-test",
                                       max_model_len=64, max_num_seqs=2))
    assert eng.latent_weights is None
