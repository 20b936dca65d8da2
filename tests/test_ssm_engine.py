"""A state-space mixer beside attention, through the engine
(docs/kv-cache.md, "Two kinds of state"): the per-slot recurrent-state
pool rides the same cache object as the KV pages through prefill (one
chunk and three), the fused decode windows and the two-deep loop, and
what cannot serve such a model refuses by name."""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kaito_tpu.engine.config import EngineConfig
from kaito_tpu.engine.engine import InferenceEngine, SamplingParams
from kaito_tpu.models.autogen import (SUPPORTED_ARCHITECTURES,
                                      metadata_from_hf_config)

# falcon-h1's shape at a tiny size: every published multiplier, GQA, a
# mixer of 8 heads in 2 groups, chunks of 16
TINY_H1 = dict(
    architectures=["FalconH1ForCausalLM"], model_type="falcon_h1",
    vocab_size=512, hidden_size=64, num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=128, max_position_embeddings=2048,
    mamba_d_ssm=64, mamba_n_heads=8, mamba_d_head=8, mamba_n_groups=2,
    mamba_d_state=16, mamba_d_conv=4, mamba_chunk_size=16,
    mamba_conv_bias=True, mamba_rms_norm=True, mamba_norm_before_gate=False,
    embedding_multiplier=5.656854249492381, lm_head_multiplier=0.0078125,
    attention_in_multiplier=1.0, attention_out_multiplier=0.0375,
    key_multiplier=0.011048543456039804, ssm_in_multiplier=0.25,
    ssm_out_multiplier=0.08838834764831845,
    ssm_multipliers=[0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738],
    mlp_multipliers=[0.1767766952966369, 0.011160714285714284],
    rope_theta=1e11, rms_norm_eps=1e-5, tie_word_embeddings=False,
    hidden_act="silu")

MD = metadata_from_hf_config("kaito-tpu/tiny-falcon-h1-test", TINY_H1,
                             name="tiny-falcon-h1-test")


def _mk(async_on=False, **kw):
    base = dict(model="tiny-falcon-h1-test", max_model_len=256, page_size=16,
                max_num_seqs=4, dtype="float32", kv_dtype="float32",
                prefill_buckets=(32, 64, 128), max_prefill_tokens=32,
                decode_run_ahead=4, async_dispatch=async_on, seed=5)
    base.update(kw)
    return InferenceEngine(EngineConfig(**base), metadata=MD)


def _run(eng, prompts, n_out, steps=400):
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


def _teacher(eng, tokens):
    """Greedy continuation's logprobs from a full forward with no cache."""
    with jax.default_matmul_precision("highest"):
        logits = eng.model.forward_train(
            eng.params, jnp.asarray([tokens], jnp.int32), remat=False)[0]
    return np.asarray(jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1))


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(1, 500, size=n).tolist()


def test_autogen_maps_the_mixer():
    assert "FalconH1ForCausalLM" in SUPPORTED_ARCHITECTURES
    a = MD.arch
    assert MD.runtime == "engine"
    assert (a.ssm_state, a.ssm_heads, a.ssm_head_dim, a.ssm_groups,
            a.ssm_conv, a.ssm_chunk) == (16, 8, 8, 2, 4, 16)
    assert a.ssm_proj_dim == 64 + 64 + 2 * 2 * 16 + 8
    assert a.state_bytes_per_seq(4) == 3 * (64 * 16 + 3 * 128) * 4


def test_published_config_counts_430m_a_layer():
    """The catalog's Falcon-H1-34B-Instruct config: 430.1M a layer,
    1,336.9M in the embedding and as much in the untied head."""
    from dataclasses import replace

    cfg = dict(TINY_H1, vocab_size=261120, hidden_size=5120,
               num_hidden_layers=72, num_attention_heads=20,
               num_key_value_heads=4, head_dim=128, intermediate_size=21504,
               mamba_d_ssm=4096, mamba_n_heads=32, mamba_d_head=128,
               mamba_d_state=256, mamba_chunk_size=128)
    arch = metadata_from_hf_config("tiiuae/Falcon-H1-34B-Instruct",
                                   cfg).arch
    zero = replace(arch, num_layers=0).param_count()
    one = replace(arch, num_layers=1).param_count()
    assert round((one - zero) / 1e6, 1) == 430.1
    assert round((zero - arch.hidden_size) / 2e6, 1) == 1336.9
    assert arch.ssm_proj_dim == 9248 and arch.ssm_conv_dim == 5120
    # a slot's row, in bfloat16 as it is served: 2 MiB of state and
    # 30 KB of convolution tail a layer
    assert arch.state_bytes_per_seq() // 72 == 2 * 2**20 + 3 * 5120 * 2


@pytest.mark.parametrize("async_on", [False, True])
@pytest.mark.parametrize("n_prompt", [20, 90])
def test_prefill_then_decode_equals_full_forward(async_on, n_prompt):
    """One chunk (20 tokens) and three (90 at a budget of 32, the state
    carried from chunk to chunk), then decode through fused windows:
    every emitted logprob is the teacher-forced full forward's."""
    eng = _mk(async_on)
    prompt = _prompt(n_prompt, 1)
    (req,) = _run(eng, [prompt], 12)
    lp = _teacher(eng, prompt + req.output_tokens)
    for j, tok in enumerate(req.output_tokens):
        want = lp[n_prompt - 1 + j]
        assert tok == int(np.argmax(want))
        assert abs(req.output_logprobs[j] - want[tok]) < 2e-4
    assert eng.counters["state_resets_total"] == 1
    assert eng.counters["state_recomputes_total"] == 0


def test_reused_slot_starts_from_zero():
    """The same prompt through a slot another sequence has just left
    gives the same logprobs: the row was reset, in one chunk and in
    three."""
    eng = _mk(max_num_seqs=1)
    for n in (20, 90):
        first = _run(eng, [_prompt(n, 2)], 6)[0]
        _run(eng, [_prompt(70, 3)], 9)
        again = _run(eng, [_prompt(n, 2)], 6)[0]
        assert again.output_tokens == first.output_tokens
        np.testing.assert_allclose(again.output_logprobs,
                                   first.output_logprobs, atol=1e-6)
    assert eng.counters["state_resets_total"] == 6


@pytest.mark.parametrize("async_on", [False, True])
def test_idle_rows_state_untouched_across_a_window(async_on):
    eng = _mk(async_on)
    req = eng.submit(_prompt(20, 4), SamplingParams(
        max_tokens=40, temperature=0.0, ignore_eos=True))
    for _ in range(6):
        eng.step()
    busy = next(i for i, s in enumerate(eng.slots) if s.request is req)
    idle = [i for i in range(4) if i != busy]
    mark = jnp.asarray(np.random.default_rng(0).normal(
        size=eng.cache.ssm_state[:, idle].shape), jnp.float32)
    if async_on:
        eng._drain_pipeline("idle")
    eng.cache = dataclasses.replace(
        eng.cache, ssm_state=eng.cache.ssm_state.at[:, idle].set(mark))
    before = np.asarray(eng.cache.ssm_state)
    tail_before = np.asarray(eng.cache.ssm_conv)
    n0 = len(req.output_tokens)
    for _ in range(4):
        eng.step()
    if async_on:
        eng._drain_pipeline("idle")
    assert len(req.output_tokens) > n0
    after = np.asarray(eng.cache.ssm_state)
    assert (after[:, idle] == before[:, idle]).all()
    assert (np.asarray(eng.cache.ssm_conv)[:, idle]
            == tail_before[:, idle]).all()
    assert (after[:, busy] != before[:, busy]).any()


def test_preempted_sequence_resumes_to_the_same_logits():
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


def test_two_prompts_of_one_turn_equal_each_alone():
    """Two fresh prompts prefilled in one turn, each on its own row of
    the state pool, decode what each decodes alone."""
    eng = _mk(max_prefill_tokens=128)
    prompts = [_prompt(20, 7), _prompt(27, 8)]
    both = _run(eng, prompts, 8)
    assert eng.counters["prefill_turns_multi_total"] == 1
    serial = _mk()
    for p, r in zip(prompts, both):
        assert r.output_tokens == _run(serial, [p], 8)[0].output_tokens


@pytest.mark.parametrize("async_on", [False, True])
def test_a_serial_turn_prefills_every_staged_prompt_that_fits(async_on):
    """A prefill turn (docs/prefill.md) with a mixer: three staged
    fresh prompts within one chunk of 128 are prefilled by one step(),
    each a one-row call on its own row of the state pool; every emitted
    logprob is the full forward's, and the tokens are those of turns
    held to one prompt."""
    prompts = [_prompt(20, 31), _prompt(27, 32), _prompt(33, 33)]
    eng = _mk(async_on, max_prefill_tokens=128)
    reqs = [eng.submit(list(p), SamplingParams(
        max_tokens=10, temperature=0.0, ignore_eos=True, logprobs=1))
        for p in prompts]
    eng.step()
    assert eng.counters["prefill_steps_total"] == 3
    assert eng.counters["state_resets_total"] == 3
    assert (eng.counters["prefill_turns_multi_total"],
            eng.counters["prefill_turns_single_total"]) == (1, 0)
    assert (eng.prefill_pack_hist._total, eng.prefill_pack_hist._sum) \
        == (1, 3.0)
    for _ in range(200):
        if all(r.finish_reason for r in reqs):
            break
        eng.step()
    one = _mk(async_on, max_prefill_tokens=128)
    one._prefill_turn_budget = lambda: 0     # the first pick, no more
    ref = _run(one, prompts, 10)
    assert one.counters["prefill_turns_multi_total"] == 0
    for p, r, want in zip(prompts, reqs, ref):
        assert r.output_tokens == want.output_tokens
        lp = _teacher(eng, p + r.output_tokens)
        for j, tok in enumerate(r.output_tokens):
            assert abs(r.output_logprobs[j] - lp[len(p) - 1 + j][tok]) < 2e-4


@pytest.mark.parametrize("async_on", [False, True])
def test_a_serial_turn_never_splits_a_prompt_and_chunks_go_alone(async_on):
    """At a chunk of 32: a prompt of 90 tokens goes in three chunks, a
    turn each, its state carried between them through the pool, while
    prompts of 12 and 14 wait whole and share the turn between its
    first chunk and its second."""
    prompts = [_prompt(90, 41), _prompt(12, 42), _prompt(14, 43)]
    eng = _mk(async_on)
    reqs = _run(eng, prompts, 8)
    chunks = [(s.attrs["slot"], s.attrs["pos"], s.attrs["tokens"],
               s.attrs["pack"]) for s in eng.tracer.spans()
              if s.name == "prefill.chunk"]
    assert chunks == [(0, 0, 32, 1), (1, 0, 12, 2), (2, 0, 14, 2),
                      (0, 32, 32, 1), (0, 64, 26, 1)]
    assert eng.counters["state_recomputes_total"] == 0
    for p, r in zip(prompts, reqs):
        lp = _teacher(eng, p + r.output_tokens)
        for j, tok in enumerate(r.output_tokens):
            assert tok == int(np.argmax(lp[len(p) - 1 + j]))
            assert abs(r.output_logprobs[j] - lp[len(p) - 1 + j][tok]) < 2e-4


def test_health_surface_and_metrics():
    from kaito_tpu.engine.metrics import EngineMetrics

    eng = _mk(enable_prefix_caching=True)
    assert eng.prefix_cache is None          # /health: prefix_cache "off"
    pool = eng.cache.state_pool_bytes
    assert pool == 4 * MD.arch.state_bytes_per_seq(4)     # float32 engine
    assert eng.sizing_report["state_pool_bytes"] == pool
    _run(eng, [_prompt(20, 9)], 4)
    text = EngineMetrics(eng).registry.expose()
    assert f"kaito:engine_state_pool_bytes {pool}" in text
    assert "kaito:engine_state_rows_in_use 0" in text
    assert "kaito:engine_state_resets_total 1" in text
    assert "kaito:engine_state_recomputes_total 0" in text
    assert "state_rows" in eng.timeline.records()[-1]


def _state_after(eng, tokens, n_out, idle=None):
    """Serve one request; (its output, its row of the state pool)."""
    req = eng.submit(list(tokens), SamplingParams(
        max_tokens=n_out, temperature=0.0, ignore_eos=True))
    slot = None
    for _ in range(400):
        eng.step()
        if slot is None:
            slot = next((i for i, s in enumerate(eng.slots)
                         if s.request is req), None)
        if req.finish_reason:
            break
    assert req.finish_reason and slot not in (None, idle)
    if eng.cfg.async_dispatch:
        eng._drain_pipeline("idle")
    return (list(req.output_tokens),
            np.asarray(eng.cache.ssm_state[:, slot], np.float32))


@pytest.mark.parametrize("async_on", [False, True])
def test_the_state_pool_is_held_in_the_models_type(async_on):
    """A bfloat16 engine, as the chip serves: the pool is bfloat16 and
    half the float32 engine's bytes; prefill in three chunks (the state
    carried through the pool between them) and five decode steps leave
    a state within a few bfloat16 roundings of what a float32 engine on
    the same weights holds after the same tokens, and an idle row keeps
    its bits."""
    prompt = _prompt(90, 21)
    f32 = _mk(async_on)
    b16 = InferenceEngine(
        dataclasses.replace(f32.cfg, dtype="bfloat16", kv_dtype="bfloat16"),
        metadata=MD,
        params=jax.tree.map(lambda x: x.astype(jnp.bfloat16), f32.params))
    assert b16.cache.ssm_state.dtype == b16.cache.ssm_conv.dtype \
        == jnp.bfloat16
    assert 2 * b16.cache.state_pool_bytes == f32.cache.state_pool_bytes
    assert b16.sizing_report["state_pool_bytes"] \
        == 4 * MD.arch.state_bytes_per_seq()
    idle = 3
    planted = jnp.asarray(np.random.default_rng(2).normal(
        size=b16.cache.ssm_state[:, idle].shape), jnp.bfloat16)
    b16.cache = dataclasses.replace(
        b16.cache, ssm_state=b16.cache.ssm_state.at[:, idle].set(planted))
    out, served = _state_after(b16, prompt, 6, idle)
    # the last sampled token is never fed: the state has seen five
    _, exact = _state_after(f32, prompt + out[:5], 1)
    assert np.abs(served - exact).max() < 0.05 * np.abs(exact).max()
    assert (np.asarray(b16.cache.ssm_state[:, idle], np.float32)
            == np.asarray(planted, np.float32)).all()


def test_a_model_with_no_mixer_has_no_state_families():
    from kaito_tpu.engine.metrics import EngineMetrics

    eng = InferenceEngine(EngineConfig(
        model="tiny-llama-test", max_model_len=128, page_size=16,
        max_num_seqs=2, dtype="float32", kv_dtype="float32"))
    assert eng.cache.ssm_state is None and eng.cache.state_pool_bytes == 0
    assert "engine_state_" not in EngineMetrics(eng).registry.expose()
    assert "state_pool_bytes" not in eng.sizing_report


@pytest.mark.parametrize("kw,names", [
    (dict(tensor_parallel=2), "tensor parallelism"),
    (dict(pipeline_parallel=2), "pipeline parallelism"),
    (dict(sequence_parallel=2), "context-parallel prefill"),
    (dict(host_kv_offload_bytes=1 << 20), "host KV offload"),
    (dict(pd_enabled=True), "prefill/decode disaggregation"),
    (dict(kv_pool_enabled=True), "the cluster KV pool"),
    (dict(speculative_ngram=3), "n-gram speculation"),
    (dict(speculative_draft="tiny-llama-test"), "draft-model speculation"),
])
def test_refusals_at_start_by_name(kw, names):
    with pytest.raises(ValueError, match="recurrent state") as e:
        _mk(**kw)
    assert names in str(e.value) and next(iter(kw)) in str(e.value)


def test_kv_import_refused_at_the_request():
    eng = _mk()
    with pytest.raises(ValueError, match="imported KV pages carry none"):
        eng.submit_with_kv(_prompt(20, 1), 3, {}, b"",
                           SamplingParams(max_tokens=2))
    with pytest.raises(ValueError, match="imported KV pages carry none"):
        eng.submit_with_kv_prefix(_prompt(20, 1), {}, [], 16,
                                  SamplingParams(max_tokens=2))


def test_estimator_counts_a_sequences_state_row():
    """A sequence's bytes are its KV at full context and its row of the
    state pool: a model with a mixer needs a chip where the same model
    without one would not."""
    from dataclasses import replace

    from kaito_tpu.estimator.estimator import (_per_chip_budget,
                                               estimate_chip_count,
                                               weight_bytes)
    from kaito_tpu.sku.catalog import CHIP_CATALOG

    chip = CHIP_CATALOG["v5e"]
    arch = replace(MD.arch, ssm_state=12288, ssm_heads=64, ssm_head_dim=256,
                   num_layers=48)
    md = replace(MD, arch=arch)
    room = _per_chip_budget(chip) - weight_bytes(md) \
        - 64 * md.kv_bytes_per_token()
    assert 0 < room < arch.state_bytes_per_seq() < 2 * room
    assert estimate_chip_count(md, chip, max_model_len=64) == 2
    bare = replace(md, arch=replace(arch, ssm_state=0))
    assert estimate_chip_count(bare, chip, max_model_len=64) == 1


def test_the_loop_freezes_the_heap_when_it_goes_idle_after_compiles(caplog):
    """Tracing the step programs leaves the heap some 10^5 objects that
    live as long as the process; the loop moves them out of the
    collector's full passes the first time it is idle after a compile,
    once, and gives them back when it stops."""
    import gc
    import logging

    from kaito_tpu.engine import engine as E

    eng = _mk(True)
    base = gc.get_freeze_count()
    with caplog.at_level(logging.INFO, logger=E.logger.name):
        eng.start()
        try:
            req = eng.submit(_prompt(20, 3), SamplingParams(
                max_tokens=6, temperature=0.0, ignore_eos=True))
            assert len(list(req.stream())) == 6
            for _ in range(100):
                if eng._heap_settled_at == E._COMPILES[0]:
                    break
                time.sleep(0.05)
            frozen = gc.get_freeze_count()
            assert frozen > base + 10_000
            assert E._watch_gc in gc.callbacks
            settles = [r for r in caplog.records if "heap settled" in r.message]
            # idle again with nothing compiled since: nothing to do
            time.sleep(0.3)
            assert [r for r in caplog.records
                    if "heap settled" in r.message] == settles
            assert gc.get_freeze_count() == frozen
        finally:
            eng.stop()
    assert gc.get_freeze_count() == 0 and E._watch_gc not in gc.callbacks
    # the watch names a pass that holds the lock for long, and no other
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger=E.logger.name):
        E._watch_gc("start", {})
        E._watch_gc("stop", {"generation": 0})
        assert not caplog.records
        E._watch_gc("start", {})
        E._GC_STARTED[0] -= 1.5
        E._watch_gc("stop", {"generation": 2})
    assert "generation-2 pass held the interpreter lock 1.5" in caplog.text
