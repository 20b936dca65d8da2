"""Chunked prefill: long prompts processed in bounded chunks must
decode identically to single-shot prefill."""

import json

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


def _engine(async_on, **kw):
    return InferenceEngine(EngineConfig(
        **{**BASE, "max_num_seqs": 4, "async_dispatch": async_on, **kw}))


def _drive(eng, reqs):
    for _ in range(3000):
        if all(r.finish_reason for r in reqs):
            return
        eng.step()
    raise AssertionError("requests did not finish")


def _serve(eng, prompts, n=8):
    p = SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True)
    reqs = [eng.submit(list(q), p) for q in prompts]
    _drive(eng, reqs)
    return [list(r.output_tokens) for r in reqs]


def _chunk_spans(eng):
    """(slot, pos, tokens, turn size) of every prefill chunk so far."""
    return [(s.attrs["slot"], s.attrs["pos"], s.attrs["tokens"],
             s.attrs["pack"]) for s in eng.tracer.spans()
            if s.name == "prefill.chunk"]


@pytest.mark.parametrize("async_on", [False, True])
def test_a_chunked_prompt_takes_one_chunk_a_turn_beside_short_ones(async_on):
    """A prefill turn takes several whole short prompts
    (docs/prefill.md); a prompt longer than a chunk still goes one
    chunk a turn, alone, through the context program, and decodes what
    it decodes when served alone."""
    long_p = [(7 * i) % 1800 + 2 for i in range(200)]
    shorts = [[(m * i) % 1800 + 2 for i in range(n)]
              for m, n in ((3, 11), (5, 19), (11, 14))]

    def serve(prompts):
        eng = _engine(async_on, max_prefill_tokens=48)
        return eng, _serve(eng, prompts, n=6)

    alone = [serve([q])[1][0] for q in [long_p] + shorts]
    eng, together = serve([long_p] + shorts)
    assert together == alone
    chunks = _chunk_spans(eng)
    assert [c for c in chunks if c[0] == 0] == [
        (0, 0, 48, 1), (0, 48, 48, 1), (0, 96, 48, 1), (0, 144, 48, 1),
        (0, 192, 8, 1)]
    # the three short prompts (44 tokens) shared the turn after the
    # long prompt's first chunk
    assert [c for c in chunks if c[0] != 0] == [
        (1, 0, 11, 3), (2, 0, 19, 3), (3, 0, 14, 3)]
    assert eng.counters["prefill_turns_multi_total"] == 1
    assert eng.counters["prefill_turns_single_total"] == 5


@pytest.mark.parametrize("async_on", [False, True])
def test_a_chunked_prompt_behind_a_short_one_ends_the_turn(async_on):
    """A prompt of 200 tokens staged between two short ones at a chunk
    of 48: a turn that has taken a prompt does not start a chunked one,
    so each short prompt goes alone and the long one straddles five
    turns of its own; all three decode what each decodes alone."""
    prompts = [[(3 * i) % 1800 + 2 for i in range(9)],
               [(13 * i) % 1800 + 2 for i in range(200)],
               [(5 * i) % 1800 + 2 for i in range(21)]]
    solo = _engine(async_on, max_prefill_tokens=48)
    alone = [_serve(solo, [q])[0] for q in prompts]
    eng = _engine(async_on, max_prefill_tokens=48)
    assert _serve(eng, prompts) == alone
    assert _chunk_spans(eng) == [
        (0, 0, 9, 1), (2, 0, 21, 1), (1, 0, 48, 1), (1, 48, 48, 1),
        (1, 96, 48, 1), (1, 144, 48, 1), (1, 192, 8, 1)]
    assert eng.counters["prefill_turns_multi_total"] == 0


@pytest.mark.parametrize("async_on", [False, True])
def test_a_multi_prompt_turn_over_int8_pages_equals_each_prompt_alone(
        async_on):
    """Four prompts of one turn written to int8 pages (the
    rescale-on-grow fold a page) decode what each decodes alone."""
    prompts = [[(m * i) % 1900 + 2 for i in range(n)]
               for m, n in ((3, 9), (5, 21), (7, 34), (11, 65))]
    solo = _engine(async_on, kv_dtype="int8")
    alone = [_serve(solo, [q])[0] for q in prompts]
    eng = _engine(async_on, kv_dtype="int8")
    assert _serve(eng, prompts) == alone
    assert eng.counters["prefill_turns_multi_total"] == 1
    assert eng.counters["prefill_steps_total"] == 4


@pytest.mark.parametrize("async_on", [False, True])
def test_a_grammar_slot_inside_a_multi_prompt_turn(async_on):
    """A grammar-constrained request prefilled in one turn with two
    unconstrained ones: its first token is masked and read back at
    once, the others' are not, the constrained stream is valid JSON,
    and all three are what turns of one prompt give."""
    from kaito_tpu.engine.grammar import GrammarSpec, canonical_schema

    schema = {"type": "object",
              "properties": {"ok": {"type": "boolean"},
                             "tag": {"type": "string", "maxLength": 4}},
              "required": ["ok", "tag"],
              "additionalProperties": False}
    others = [[(m * i) % 1900 + 2 for i in range(n)]
              for m, n in ((3, 9), (5, 21))]

    def run(one_a_turn):
        eng = _engine(async_on)
        if one_a_turn:
            eng._prefill_turn_budget = lambda: 0    # the first pick alone
        g = eng.grammar_cache.get(
            GrammarSpec("json_schema", canonical_schema(schema)),
            eng.tokenizer)
        reqs = [eng.submit([10, 20, 30], SamplingParams(
            max_tokens=60, temperature=0.0, grammar=g))]
        p = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)
        reqs += [eng.submit(list(q), p) for q in others]
        _drive(eng, reqs)
        outs = [list(r.output_tokens) for r in reqs]
        assert set(json.loads(eng.tokenizer.decode(outs[0]))) \
            == {"ok", "tag"}
        return eng, outs

    eng, outs = run(False)
    assert outs == run(True)[1]
    assert _chunk_spans(eng) == [(0, 0, 3, 3), (1, 0, 9, 3), (2, 0, 21, 3)]
    if async_on:
        assert eng.first_token_blocking["grammar"] == 1
        assert eng.counters["first_tokens_deferred_total"] == 2
