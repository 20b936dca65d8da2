"""Scheduler throughput behaviors: multi-admission, decode-priority
prefill interleave, reserve-on-demand paging with preemption.

These drive engine.step() directly (no loop thread) where determinism
matters, mirroring how the reference's vLLM scheduler is unit-tested at
the step level rather than by wall-clock.
"""

import dataclasses
import functools
import importlib
import json

import numpy as np
import pytest

from kaito_tpu.engine.config import EngineConfig
from kaito_tpu.engine.engine import InferenceEngine, SamplingParams

BASE = dict(model="tiny-llama-test", max_model_len=256, page_size=16,
            max_num_seqs=4, dtype="float32", kv_dtype="float32",
            prefill_buckets=(32, 64, 128), seed=0,
            enable_prefix_caching=False)


def _greedy(n):
    return SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True)


def test_multi_admission_fills_all_slots_in_one_step():
    eng = InferenceEngine(EngineConfig(**BASE))
    for i in range(4):
        eng.submit([10 + i, 20 + i, 30 + i], _greedy(4))
    eng.step()
    staged = sum(1 for s in eng.slots if s.request is not None)
    assert staged == 4          # one step stages every free slot
    assert eng.num_waiting == 0


def test_decode_never_starved_by_prefill():
    """With an active decode batch, every scheduler iteration runs a
    decode step; prefill chunks ride the configured interleave — the
    decode-priority contract (decode cadence within the interleave
    overhead bound while prompts stream in)."""
    eng = InferenceEngine(EngineConfig(**BASE, max_prefill_tokens=32,
                                       prefill_interleave=4))
    a = eng.submit([1, 2, 3], _greedy(60))
    # admit + prefill + first decode steps for A
    for _ in range(4):
        eng.step()
    assert eng.num_running == 1
    # stream in a long prompt (4 chunks of 32) while A decodes
    eng.submit([(7 * i) % 1800 + 2 for i in range(128)], _greedy(4))
    d0 = eng.counters["decode_steps_total"]
    p0 = eng.counters["prefill_steps_total"]
    iters = 12
    for _ in range(iters):
        eng.step()
    # decode ran EVERY iteration; prefill advanced at the 1/4 cadence
    assert eng.counters["decode_steps_total"] - d0 == iters
    assert 0 < eng.counters["prefill_steps_total"] - p0 <= iters // 4 + 1


def test_admission_is_bookkeeping_only():
    """Admission must not run prefill compute (prefill cadence is owned
    by _advance_prefills)."""
    eng = InferenceEngine(EngineConfig(**BASE))
    eng.submit([5, 6, 7], _greedy(4))
    before = eng.counters["prefill_steps_total"]
    assert eng._admit_new()
    assert eng.counters["prefill_steps_total"] == before
    assert eng.slots[0].prefilling


def test_preemption_requeues_and_resumes_seamlessly():
    """When the page pool runs dry mid-decode, the newest sequence is
    preempted to the queue and later resumed by recompute; the client
    stream sees the full, correct token sequence."""
    cfg = EngineConfig(**{**BASE, "max_num_seqs": 2, "max_pages": 10})
    solo = InferenceEngine(cfg)
    solo.start()
    try:
        b_ref = list(solo.submit([50, 51, 52] * 11, _greedy(40)).stream())
    finally:
        solo.stop()

    eng = InferenceEngine(cfg)
    eng.start()
    try:
        ra = eng.submit([40, 41, 42] * 11, _greedy(100))   # grows to 9 pages
        rb = eng.submit([50, 51, 52] * 11, _greedy(40))    # grows to 5 pages
        a_out = list(ra.stream())
        b_out = list(rb.stream())
    finally:
        eng.stop()
    assert len(a_out) == 100
    assert len(b_out) == 40
    assert b_out == b_ref                  # greedy survives preemption
    assert eng.counters["preemptions_total"] >= 1
    assert rb.preemptions >= 1
    # all pages are back after the dust settles
    assert eng.allocator.available == eng.allocator.num_pages - 1


def test_preemption_with_prefix_cache_reuses_committed_pages():
    from kaito_tpu.native import load_native

    if load_native() is None:
        return
    cfg = EngineConfig(**{**BASE, "enable_prefix_caching": True,
                      "max_num_seqs": 2, "max_pages": 10})
    eng = InferenceEngine(cfg)
    eng.start()
    try:
        ra = eng.submit([60, 61, 62] * 11, _greedy(100))
        rb = eng.submit([70, 71, 72] * 11, _greedy(40))
        a_out = list(ra.stream())
        b_out = list(rb.stream())
    finally:
        eng.stop()
    assert len(a_out) == 100 and len(b_out) == 40
    # every page is free or evictable once the dust settles (the
    # committed prefixes of preempted sequences may legitimately have
    # been evicted to feed the survivor's growth)
    assert eng.allocator.available == eng.allocator.num_pages - 1


# ---------------------------------------------------------------------------
# the prefill turn (docs/prefill.md): whole staged prompts while they
# fit the turn's budget, each through the one-row programs
# ---------------------------------------------------------------------------

def _serial(async_on=False, **kw):
    cfg = dict(BASE, async_dispatch=async_on,
               decode_run_ahead=4, fused_under_load=4)
    cfg.update(kw)
    return InferenceEngine(EngineConfig(**cfg))


def _tokens(n, mul=7):
    return [(mul * i) % 1800 + 2 for i in range(n)]


def _chunks(eng):
    """(slot, pos, tokens, turn size) of every prefill chunk so far."""
    return [(s.attrs["slot"], s.attrs["pos"], s.attrs["tokens"],
             s.attrs["pack"]) for s in eng.tracer.spans()
            if s.name == "prefill.chunk"]


def _turns(eng):
    c = eng.counters
    return (c["prefill_turns_multi_total"], c["prefill_turns_single_total"])


def _finish(eng, reqs, limit=2000):
    for _ in range(limit):
        if all(r.finish_reason for r in reqs):
            return [list(r.output_tokens) for r in reqs]
        eng.step()
    raise AssertionError("requests did not finish")


@pytest.mark.parametrize("async_on", [False, True])
def test_a_turn_takes_every_staged_prompt_that_fits(async_on):
    """Four staged fresh prompts of 72 tokens together, a budget of one
    chunk of 128 (nothing decodes): one step() prefills all four, each
    a one-row call, and the counters and the histogram say so."""
    eng = _serial(async_on, max_prefill_tokens=128)
    lens = (9, 21, 30, 12)
    for n in lens:
        eng.submit(_tokens(n), _greedy(4))
    eng.step()
    assert eng.counters["prefill_steps_total"] == 4
    assert _chunks(eng) == [(i, 0, n, 4) for i, n in enumerate(lens)]
    assert not any(s.prefilling for s in eng.slots)
    assert _turns(eng) == (1, 0)
    h = eng.prefill_pack_hist
    assert (h._total, h._sum) == (1, 4.0)
    assert eng.timeline.records()[-1]["prefill_pack"] == 4


@pytest.mark.parametrize("async_on", [False, True])
def test_a_prompt_that_does_not_fit_whole_waits_and_is_never_split(
        async_on):
    """Three prompts of 30 against a budget of 64: two go, the third
    waits with nothing of it written, and goes whole in the next turn.
    The context-prefill program is never asked for."""
    eng = _serial(async_on, max_prefill_tokens=64)
    eng._prefill_ctx_fn = lambda bucket: pytest.fail(
        "a fresh prompt went down the context-prefill program")
    reqs = [eng.submit(_tokens(30, m), _greedy(6)) for m in (3, 5, 7)]
    eng.step()
    assert _chunks(eng) == [(0, 0, 30, 2), (1, 0, 30, 2)]
    assert eng.slots[2].prefilling and eng.slots[2].prefill_pos == 0
    _finish(eng, reqs)
    assert _chunks(eng)[2] == (2, 0, 30, 1)
    assert _turns(eng) == (1, 1)
    assert eng.prefill_pack_hist._sum == 3.0


@pytest.mark.parametrize("async_on", [False, True])
def test_a_chunked_prompt_takes_its_turns_alone_in_round_robin(async_on):
    """The first pick is taken whatever its size: a prompt of 100
    tokens at a chunk of 32 runs one chunk a turn, alone, as it always
    did, and the two short prompts staged behind it share the turn
    between its first chunk and its second."""
    eng = _serial(async_on, max_prefill_tokens=32)
    reqs = [eng.submit(_tokens(n, m), _greedy(6))
            for n, m in ((100, 3), (12, 5), (14, 7))]
    _finish(eng, reqs)
    assert _chunks(eng) == [
        (0, 0, 32, 1), (1, 0, 12, 2), (2, 0, 14, 2),
        (0, 32, 32, 1), (0, 64, 32, 1), (0, 96, 4, 1)]
    assert _turns(eng) == (1, 4)


def test_a_turn_starts_where_the_pointer_stands_and_wraps():
    """Round-robin as before: the pointer counts prompts served and
    indexes the slots still staged; a turn starts there and goes on
    cyclically.  Slot 0 alone (a chunk of 32 holds one prompt of 20);
    then the pointer, 1, names the second of the staged slots 1, 2, 3,
    and the window of 4 steps has earned two chunks, which hold three
    such prompts: slots 2, 3 and, wrapping, 1."""
    eng = _serial(max_prefill_tokens=32)
    for m in (3, 5, 7, 11):
        eng.submit(_tokens(20, m), _greedy(30))
    eng.step()
    assert _chunks(eng) == [(0, 0, 20, 1)]
    eng.step()
    assert _chunks(eng)[1:] == [(2, 0, 20, 3), (3, 0, 20, 3), (1, 0, 20, 3)]


@pytest.mark.parametrize("steps,every,under_load,chunks", [
    (0, 2, 4, 1),       # nothing decodes: one chunk, as before
    (1, 2, 4, 1),       # a single step: never less than one chunk
    (2, 2, 4, 1),
    (3, 2, 4, 1),
    (4, 2, 4, 2),       # a window of 4 steps has earned two
    (1000, 2, 4, 2),    # steps run with nothing staged earn nothing
    (4, 1, 4, 4),
    (4, 0, 4, 4),
    (8, 4, 8, 2),
    (8, 4, 0, 1),       # no fusing under load: a chunk an interleave
])
def test_the_turns_budget_is_a_chunk_for_every_interleave_of_steps(
        steps, every, under_load, chunks):
    eng = _serial(max_prefill_tokens=48, prefill_interleave=every,
                  fused_under_load=under_load)
    eng._decode_since_prefill = steps
    assert eng._prefill_turn_budget() == 48 * chunks


@pytest.mark.parametrize("async_on", [False, True])
def test_a_window_of_four_steps_earns_two_chunks(async_on):
    """Behind a decoding batch the turn spends what the window earned:
    of three prompts of 30 staged during a window of 4 steps (chunk 32,
    interleave 2) two go in the turn after it."""
    eng = _serial(async_on, max_prefill_tokens=32)
    first = eng.submit(_tokens(8), _greedy(200))
    for _ in range(6):
        eng.step()
    assert eng.num_running == 1 and _turns(eng) == (0, 1)
    late = [eng.submit(_tokens(30, m), _greedy(6)) for m in (3, 5, 7)]
    d0 = eng.counters["decode_steps_total"]
    eng.step()
    assert eng.counters["decode_steps_total"] - d0 == 4
    assert [c[3] for c in _chunks(eng)] == [1, 2, 2]
    assert _turns(eng) == (1, 1)
    _finish(eng, late)
    assert not first.finish_reason


@pytest.mark.parametrize("async_on", [False, True])
def test_greedy_outputs_are_those_of_one_prompt_turns(async_on):
    """Token for token what the engine gives when every turn takes one
    prompt (a budget of nothing: the first pick is always taken), under
    both loops."""
    prompts = [_tokens(n, m) for n, m in
               ((9, 3), (21, 5), (30, 7), (12, 11), (17, 13), (25, 17),
                (11, 19))]
    one = _serial(async_on, max_prefill_tokens=128)
    one._prefill_turn_budget = lambda: 0     # the first pick, no more
    ref = _finish(one, [one.submit(p, _greedy(10 + 2 * i))
                        for i, p in enumerate(prompts)])
    assert _turns(one) == (0, len(prompts))
    many = _serial(async_on, max_prefill_tokens=128)
    out = _finish(many, [many.submit(p, _greedy(10 + 2 * i))
                         for i, p in enumerate(prompts)])
    assert out == ref
    assert _turns(many)[0] >= 1
    assert (many.counters["prefill_steps_total"]
            == one.counters["prefill_steps_total"] == len(prompts))


def test_the_turn_counters_are_exposed():
    from kaito_tpu.engine.metrics import EngineMetrics

    eng = _serial(max_prefill_tokens=128)
    for n in (9, 21):
        eng.submit(_tokens(n), _greedy(2))
    eng.step()
    text = EngineMetrics(engine=eng).registry.expose()
    assert "kaito:engine_prefill_turns_multi_total 1" in text
    assert "kaito:engine_prefill_turns_single_total 0" in text
    assert "kaito:engine_prefill_pack_size_sum 2" in text


# ---------------------------------------------------------------------------
# what sharing a turn must not change: every prompt decodes what it
# decodes when it is served alone, whatever shares its turn
# ---------------------------------------------------------------------------

# two short prompts, one past the 32 bucket, one just past the 64
MIXED = [_tokens(n, m) for n, m in ((9, 3), (21, 5), (34, 7), (65, 11))]


def _together(eng, prompts, n=8):
    return _finish(eng, [eng.submit(list(p), _greedy(n)) for p in prompts])


@functools.lru_cache(maxsize=None)
def _mixed_alone(async_on):
    """What each of MIXED decodes on an engine that serves nothing
    else: one after the other, no prefix cache."""
    eng = _serial(async_on)
    return [_together(eng, [p])[0] for p in MIXED]


@pytest.mark.parametrize("async_on", [False, True])
def test_mixed_lengths_in_one_turn_decode_what_each_decodes_alone(async_on):
    """Prompts of 9, 21, 34 and 65 tokens, three buckets, one turn:
    four one-row calls, the prompts' own tokens and no more, and the
    greedy streams of each served alone."""
    eng = _serial(async_on)
    assert _together(eng, MIXED) == _mixed_alone(async_on)
    assert _turns(eng) == (1, 0)
    assert eng.counters["prefill_steps_total"] == len(MIXED)
    assert eng.counters["prefill_tokens_total"] == sum(map(len, MIXED))
    h = eng.prefill_pack_hist
    assert (h._total, h._sum) == (1, float(len(MIXED)))


@pytest.mark.parametrize("async_on", [False, True])
@pytest.mark.parametrize("chunk,steps,turns", [(512, 2, (1, 0)),
                                               (16, 3, (0, 3))])
def test_a_turn_takes_the_prompts_its_chunk_holds_whole(chunk, steps, turns,
                                                        async_on):
    """One one-row dispatch a prompt or chunk, and as many prompts a
    turn as its chunk budget holds whole (docs/prefill.md): both at
    512, one at 16, where the second prompt (21 tokens) is chunked.
    The streams are the same either way."""
    eng = _serial(async_on, max_prefill_tokens=chunk)
    assert _together(eng, MIXED[:2]) == _mixed_alone(async_on)[:2]
    assert eng.counters["prefill_steps_total"] == steps
    assert _turns(eng) == turns
    assert eng.prefill_pack_hist._sum == steps
    assert eng.prefill_pack_hist._total == sum(turns)


@pytest.mark.parametrize("async_on", [False, True])
def test_an_abort_between_two_prompts_of_a_turn_leaves_the_rest(async_on):
    """The second of three prompts of one turn is aborted after the
    first is prefilled and before its own call: the turn goes on, the
    aborted request retires at its first emit, and the other two
    decode what they decode alone."""
    eng = _serial(async_on)
    reqs = [eng.submit(list(p), _greedy(8)) for p in MIXED[:3]]
    chunk = eng._prefill_serial_chunk

    def abort_after_the_first(i, turn):
        ok = chunk(i, turn)
        if i == 0:
            eng.abort(reqs[1])
        return ok

    eng._prefill_serial_chunk = abort_after_the_first
    eng.step()
    assert _chunks(eng) == [(i, 0, len(p), 3)
                            for i, p in enumerate(MIXED[:3])]
    out = _finish(eng, reqs)
    alone = _mixed_alone(async_on)
    assert [out[0], out[2]] == [alone[0], alone[2]]
    assert reqs[1].finish_reason is not None
    assert len(out[1]) < 8 and out[1] == alone[1][:len(out[1])]


@pytest.mark.parametrize("async_on", [False, True])
def test_admission_by_priority_decides_who_a_turn_serves_first(async_on):
    """A turn serves the staged slots round-robin; the order of
    admission into the slots is the QoS classes' (engine/qos.py).  At
    a budget of one prompt a turn the guaranteed tenant's prompt is
    prefilled first even when it was submitted last."""
    qos = json.dumps({
        "classes": {"guaranteed": {"priority": 100, "weight": 8},
                    "best-effort": {"priority": 0, "weight": 1}},
        "tenants": {"acme": "guaranteed"},
        "default_class": "best-effort",
    })
    eng = _serial(async_on, qos_config=qos, max_prefill_tokens=32)
    be = eng.submit(_tokens(30, 3), _greedy(4), tenant="free")
    gt = eng.submit(_tokens(30, 5), _greedy(4), tenant="acme")
    _finish(eng, [be, gt])
    assert [c[3] for c in _chunks(eng)] == [1, 1]
    assert gt.first_token_time <= be.first_token_time


@pytest.mark.parametrize("async_on", [False, True])
def test_the_turn_histograms_round_trip_through_the_exposition(async_on):
    eng = _serial(async_on)
    _together(eng, MIXED[:3], n=4)
    for hist, name in ((eng.prefill_pack_hist,
                        "kaito:engine_prefill_pack_size"),
                       (eng.prefill_wait_hist,
                        "kaito:prefill_queue_wait_seconds")):
        lines = list(hist.collect())
        assert f"# TYPE {name} histogram" in lines
        count = sum_ = None
        for ln in lines:
            if ln.startswith(f"{name}_count"):
                count = float(ln.split()[-1])
            elif ln.startswith(f"{name}_sum"):
                sum_ = float(ln.split()[-1])
        assert count is not None and count > 0
        assert sum_ is not None and sum_ >= 0.0
    assert "# HELP kaito:engine_prefill_pack_size Prompts a prefill turn" \
        in list(eng.prefill_pack_hist.collect())
    # the step timeline names the turn that took several prompts
    packs = [e for e in eng.timeline.records() if e.get("prefill_pack")]
    assert packs and max(e["prefill_pack"] for e in packs) == 3


@pytest.mark.parametrize("module", [
    "test_latent_engine",       # a latent stream, a share of the experts
    "test_two_kind_engine",     # a page pool a kind of attention layer
    "test_lfm2_moe",            # a row of conv state a slot
    "test_ssm_engine",          # a state-space mixer beside attention
])
def test_every_kind_of_model_starts_with_no_prefill_setting(module):
    """There is one prefill scheduler and no setting that selects it:
    the tiny engine of every kind of layer starts from a configuration
    that names none, and a turn takes two staged prompts."""
    assert not [f.name for f in dataclasses.fields(EngineConfig)
                if "pack" in f.name]
    md = importlib.import_module(f"tests.{module}").MD
    eng = InferenceEngine(EngineConfig(
        model=md.name, max_model_len=256, page_size=16, max_num_seqs=4,
        dtype="float32", kv_dtype="float32", prefill_buckets=(32, 64, 128),
        seed=5), metadata=md)
    for m in (3, 5):
        eng.submit([(m * i) % 500 + 2 for i in range(12)], _greedy(3))
    eng.step()
    assert _turns(eng) == (1, 0)
    assert [c[1:] for c in _chunks(eng)] == [(0, 12, 2), (0, 12, 2)]
