"""Chunked prefill: long prompts processed in bounded chunks must
decode identically to single-shot prefill."""

import pytest

from kaito_tpu.engine.config import EngineConfig
from kaito_tpu.engine.engine import InferenceEngine, SamplingParams

BASE = dict(model="tiny-llama-test", max_model_len=512, page_size=16,
            max_num_seqs=2, dtype="float32", kv_dtype="float32",
            prefill_buckets=(32, 64, 128, 256), seed=0,
            enable_prefix_caching=False)


def _run(engine, prompt, n=6):
    engine.start()
    try:
        p = SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True)
        return list(engine.submit(prompt, p).stream())
    finally:
        engine.stop()


def test_chunked_prefill_matches_single_shot():
    prompt = [(7 * i) % 1800 + 2 for i in range(200)]
    big = InferenceEngine(EngineConfig(**BASE, max_prefill_tokens=1024))
    ref = _run(big, prompt)

    small = InferenceEngine(EngineConfig(**BASE, max_prefill_tokens=48))
    out = _run(small, prompt)
    assert out == ref
    # really chunked: ceil(200/48) = 5 prefill steps for one request
    assert small.counters["prefill_steps_total"] >= 5


def test_chunked_prefill_with_prefix_cache():
    from kaito_tpu.native import load_native

    if load_native() is None:
        pytest.skip("native toolchain unavailable")
    prompt = [(11 * i) % 1700 + 2 for i in range(150)]
    plain = InferenceEngine(EngineConfig(**BASE, max_prefill_tokens=1024))
    ref = _run(plain, prompt)

    cfg = EngineConfig(**{**BASE, "enable_prefix_caching": True},
                       max_prefill_tokens=64)
    eng = InferenceEngine(cfg)
    eng.start()
    try:
        p = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)
        first = list(eng.submit(prompt, p).stream())
        second = list(eng.submit(prompt, p).stream())
    finally:
        eng.stop()
    assert first == ref and second == ref
    assert eng.counters["prefix_cached_tokens_total"] > 0


@pytest.mark.parametrize("async_on", [False, True])
def test_a_chunked_prompt_takes_one_chunk_a_turn_beside_short_ones(async_on):
    """The serial scheduler (prefill_pack=1) takes several whole short
    prompts in one turn (docs/prefill.md); a prompt longer than a chunk
    still goes one chunk a turn, alone, through the context program,
    and decodes what it decodes when served alone."""
    long_p = [(7 * i) % 1800 + 2 for i in range(200)]
    shorts = [[(m * i) % 1800 + 2 for i in range(n)]
              for m, n in ((3, 11), (5, 19), (11, 14))]
    p = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)

    def serve(prompts):
        eng = InferenceEngine(EngineConfig(
            **{**BASE, "max_num_seqs": 4}, max_prefill_tokens=48,
            prefill_pack=1, async_dispatch=async_on))
        reqs = [eng.submit(list(q), p) for q in prompts]
        for _ in range(400):
            if all(r.finish_reason for r in reqs):
                break
            eng.step()
        return eng, [list(r.output_tokens) for r in reqs]

    alone = [serve([q])[1][0] for q in [long_p] + shorts]
    eng, together = serve([long_p] + shorts)
    assert together == alone
    chunks = [(s.attrs["slot"], s.attrs["pos"], s.attrs["tokens"],
               s.attrs["pack"]) for s in eng.tracer.spans()
              if s.name == "prefill.chunk"]
    assert [c for c in chunks if c[0] == 0] == [
        (0, 0, 48, 1), (0, 48, 48, 1), (0, 96, 48, 1), (0, 144, 48, 1),
        (0, 192, 8, 1)]
    # the three short prompts (44 tokens) shared the turn after the
    # long prompt's first chunk
    assert [c for c in chunks if c[0] != 0] == [
        (1, 0, 11, 3), (2, 0, 19, 3), (3, 0, 14, 3)]
    assert eng.counters["prefill_turns_multi_total"] == 1
    assert eng.counters["prefill_turns_single_total"] == 5
