"""The two-deep decode dispatch loop under the traffic users send
(docs/decode-loop.md): more requests than slots, so every few windows
a request finishes and another takes its slot.

The loop must stay primed across a finish the scan applied itself and
across an admission into a free slot, give bit-identical tokens and
logprobs to the synchronous loop, and still go back to depth 1 where
only the host knows what happened (abort, deadline, preemption).
Engines are stepped by hand, so what is in flight at each event is
deterministic.  tests/test_async_dispatch.py (slow tier) holds the
one-request-at-a-time parity checks.
"""

import json
import os
import time

import numpy as np
import pytest

from kaito_tpu.engine.config import EngineConfig
from kaito_tpu.engine.engine import InferenceEngine, SamplingParams

BASE = dict(model="tiny-llama-test", max_model_len=256, page_size=16,
            max_num_seqs=4, dtype="float32", kv_dtype="float32",
            prefill_buckets=(32, 64, 128), decode_run_ahead=4,
            fused_under_load=4, prefill_pack=1)
N_REQUESTS = 14
STOPPED = 5                    # the request that gets a stop id
SAMPLED = (1, 2)               # temperature 0.8, top-k 40, seeded


def _mk(async_on, **kw):
    return InferenceEngine(EngineConfig(**{**BASE, **kw,
                                           "async_dispatch": async_on}))


def _greedy(n, **kw):
    return SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True,
                          **kw)


def _run(eng, reqs, limit=5000):
    """Step until every request has finished."""
    for _ in range(limit):
        if all(r.finish_reason for r in reqs):
            return
        eng.step()
    raise AssertionError("requests did not finish")


def _submit_mix(eng, stop_tok=None):
    """14 requests on 4 slots: prompts of 3-39 tokens, budgets of 5-40,
    one with a stop id, two sampled from a seed.

    The sampled ones are in the first batch with the longest budget, so
    they draw while their neighbours finish and are replaced: a seeded
    stream is folded with its slot's index (SamplingState.set_slot),
    and which slot a later arrival gets depends on which frees first,
    which the two loops may see in a different order."""
    rng = np.random.default_rng(7)
    reqs = []
    for i in range(N_REQUESTS):
        prompt = [int(x) for x in rng.integers(1, 250, rng.integers(3, 40))]
        budget = int(rng.integers(5, 41))
        if i in SAMPLED:
            p = SamplingParams(max_tokens=40, temperature=0.8, top_k=40,
                               seed=100 + i, ignore_eos=True, logprobs=True)
        else:
            stop = (stop_tok,) if i == STOPPED and stop_tok is not None \
                else ()
            p = _greedy(budget, logprobs=True, stop_token_ids=stop)
        reqs.append(eng.submit(prompt, p))
    return reqs


@pytest.fixture(scope="module")
def sustained():
    """The mix through both loops; the stop id is a token the stopped
    request emits mid-window when nothing stops it."""
    probe = _mk(False)
    free_run = _submit_mix(probe)
    _run(probe, free_run)
    stop_tok = free_run[STOPPED].output_tokens[6]

    sync = _mk(False)
    sync_reqs = _submit_mix(sync, stop_tok)
    _run(sync, sync_reqs)

    eng = _mk(True)
    in_flight_at_admission = []
    admit = eng._admit

    def spy(req, slot):
        in_flight_at_admission.append(eng._inflight is not None)
        return admit(req, slot)

    eng._admit = spy
    reqs = _submit_mix(eng, stop_tok)
    _run(eng, reqs)
    return dict(sync=sync_reqs, eng=eng, reqs=reqs, stop_tok=stop_tok,
                free_run=free_run,
                in_flight_at_admission=in_flight_at_admission)


@pytest.mark.skipif(os.environ.get("KAITO_ASYNC_DISPATCH", "") != "",
                    reason="the environment pins the loop")
def test_none_resolves_off_on_the_cpu_backend():
    """Unset, the engine decides from the backend: tier 1 runs on the
    CPU and keeps the synchronous loop it has always tested."""
    eng = _mk(None)
    assert eng.async_dispatch is False
    assert eng.dispatch_gap_hist is None
    assert "decode_windows_primed_total" not in eng.counters


@pytest.mark.parametrize("env,want", [("1", True), ("true", True),
                                      ("0", False), ("false", False)])
def test_environment_pins_an_unset_field(monkeypatch, env, want):
    monkeypatch.setenv("KAITO_ASYNC_DISPATCH", env)
    assert _mk(None).async_dispatch is want
    # the field beats the environment
    assert _mk(not want).async_dispatch is (not want)


@pytest.mark.parametrize("what", ["tokens", "logprobs", "finish_reason"])
def test_sustained_admission_is_bit_identical(sustained, what):
    for a, b in zip(sustained["sync"], sustained["reqs"]):
        if what == "tokens":
            assert a.output_tokens == b.output_tokens
            assert len(b.output_tokens) > 0
        elif what == "logprobs":
            assert a.output_logprobs == b.output_logprobs
            assert len(b.output_logprobs) == len(b.output_tokens)
        else:
            assert a.finish_reason == b.finish_reason


def test_the_stop_id_fired_mid_window(sustained):
    """The stopped request ended early, on the device's own check, and
    at the token the synchronous loop ended it."""
    r = sustained["reqs"][STOPPED]
    full = sustained["free_run"][STOPPED].output_tokens
    assert r.finish_reason == "stop"
    cut = full.index(sustained["stop_tok"])
    assert r.output_tokens[:cut] == full[:cut]
    assert 0 < cut < len(full) - 1 and cut % BASE["decode_run_ahead"] != 0


def test_slots_were_refilled_with_a_window_in_flight(sustained):
    """After the first batch every admission met a launched window: the
    slot's first tenant was finished by the scan, not by a drain."""
    seen = sustained["in_flight_at_admission"]
    assert len(seen) == N_REQUESTS
    assert seen[:4] == [False] * 4
    assert all(seen[4:])


def test_primed_and_unprimed_add_up_to_the_windows_launched(sustained):
    eng = sustained["eng"]
    primed = eng.counters["decode_windows_primed_total"]
    unprimed = eng.counters["decode_windows_unprimed_total"]
    launched = sum(1 for r in eng.timeline.records()
                   if r["decode_steps"] > 0)
    assert primed + unprimed == launched
    assert primed / launched > 0.5
    # no finish and no admission of this mix took the loop to depth 1
    assert not any(eng.drain_counts.values())


def test_the_families_exist_where_the_loop_runs(sustained):
    from kaito_tpu.engine.metrics import EngineMetrics

    eng = sustained["eng"]
    eng.drain_counts["finish"] = 2          # as two drains would leave it
    try:
        text = EngineMetrics(engine=eng).registry.expose()
    finally:
        eng.drain_counts["finish"] = 0
    primed = eng.counters["decode_windows_primed_total"]
    assert f"kaito:engine_decode_windows_primed_total {primed}" in text
    assert "kaito:engine_decode_windows_unprimed_total 1" in text
    assert 'kaito:engine_decode_drains_total{reason="finish"} 2' in text
    assert 'kaito:engine_decode_drains_total{reason="deadline"} 0' in text
    assert "kaito:engine_dispatch_gap_seconds_count" in text
    off = EngineMetrics(engine=_mk(False)).registry.expose()
    assert "decode_windows" not in off and "decode_drains" not in off


def _decoding_with_a_window_in_flight(eng, reqs):
    """Step until every request has rows in the window in flight."""
    for _ in range(200):
        eng.step()
        if eng._inflight is not None and all(
                len(r.output_tokens) > 2 * BASE["decode_run_ahead"]
                for r in reqs):
            return
    raise AssertionError("no window in flight")


def test_a_window_replays_only_into_the_slots_owner_at_its_launch():
    """The invariant admission without a drain rests on.  A slot whose
    request the host retires, and that another request takes while the
    old tenant's window is still in flight, gets none of that window's
    tokens; its neighbour gets all of its own."""
    ref = _mk(False)
    want = ref.submit([2, 4, 6], _greedy(40))
    _run(ref, [want])

    eng = _mk(True)
    old = eng.submit([9, 8, 7], _greedy(40))
    keeper = eng.submit([2, 4, 6], _greedy(40))
    _decoding_with_a_window_in_flight(eng, [old, keeper])
    slot = next(i for i, s in enumerate(eng.slots) if s.request is old)
    launched_for = eng._inflight[4][slot]
    assert launched_for == eng.slots[slot].seq
    # what only the host knows: the old tenant goes, a new one comes,
    # and nothing has drained in between
    eng._evict_slot(slot, commit=False)
    new = eng.submit([5, 5, 5], _greedy(8))
    assert eng._admit(eng._pop_waiting(), slot)
    assert eng.slots[slot].seq != launched_for
    kept = len(keeper.output_tokens)
    eng._drain_pipeline("finish")
    assert new.output_tokens == []
    assert len(keeper.output_tokens) == kept + BASE["decode_run_ahead"]
    # the eviction dirtied the carry: the next launch uploads the
    # mirrors, and both survivors decode on as if nothing had happened
    _run(eng, [keeper, new])
    assert keeper.output_tokens == want.output_tokens
    assert len(new.output_tokens) == 8


def _drains_against_sync(cfg_kw, submit, disturb):
    """Run ``submit``'s requests through both loops; ``disturb(eng,
    reqs)`` acts once a window is in flight.  Returns the async engine
    and both sets of requests."""
    out = []
    for async_on in (False, True):
        eng = _mk(async_on, **cfg_kw)
        reqs = submit(eng)
        if async_on:
            _decoding_with_a_window_in_flight(eng, reqs[:1])
        else:
            for _ in range(6):
                eng.step()
        disturb(eng, reqs)
        out.append((eng, reqs))
    return out


def test_an_abort_still_drains():
    """What a stop string is to the engine: the server matches the
    text and aborts.  The scan cannot see it, so the loop goes to depth
    1 and the single-step path retires the request; the survivor's
    stream is the synchronous loop's."""
    def submit(eng):
        return [eng.submit([9, 8, 7], _greedy(40)),
                eng.submit([2, 4, 6], _greedy(40))]

    def disturb(eng, reqs):
        eng.abort(reqs[0])
        _run(eng, reqs)

    (_, ref), (eng, reqs) = _drains_against_sync({}, submit, disturb)
    assert reqs[0].aborted and reqs[0].finish_reason
    assert reqs[1].output_tokens == ref[1].output_tokens
    assert eng.drain_counts.get("sync_decode", 0) >= 1
    drains = [r["drain"] for r in eng.timeline.records() if "drain" in r]
    assert "sync_decode" in ",".join(drains)


def test_a_deadline_still_drains_and_only_when_it_has_passed():
    def submit(eng):
        reqs = [eng.submit([9, 8, 7], _greedy(60)),
                eng.submit([2, 4, 6], _greedy(40))]
        reqs[0].deadline = time.monotonic() + 3600
        return reqs

    def disturb(eng, reqs):
        for _ in range(3):
            eng._last_deadline_sweep = 0.0
            eng.step()
        # a deadline that is set and not yet due costs nothing
        assert not eng.drain_counts.get("deadline")
        reqs[0].deadline = time.monotonic() - 1
        eng._last_deadline_sweep = 0.0
        _run(eng, reqs)

    (_, ref), (eng, reqs) = _drains_against_sync({}, submit, disturb)
    assert reqs[0].finish_reason == "deadline"
    assert reqs[1].output_tokens == ref[1].output_tokens
    assert eng.drain_counts.get("deadline") == 1


def test_page_pressure_still_drains_before_it_preempts():
    def submit(eng):
        return [eng.submit([10 + i, 20 + i, 30 + i], _greedy(60))
                for i in range(4)]

    def disturb(eng, reqs):
        _run(eng, reqs)

    kw = dict(max_model_len=128, max_pages=14, prefill_buckets=(32, 64),
              enable_prefix_caching=False)
    (_, ref), (eng, reqs) = _drains_against_sync(kw, submit, disturb)
    assert eng.counters["preemptions_total"] >= 1
    assert eng.drain_counts.get("page_pressure", 0) >= 1
    for a, b in zip(ref, reqs):
        assert len(b.output_tokens) == 60
        assert a.output_tokens == b.output_tokens


def test_an_admission_that_preempts_still_drains():
    """QoS: a guaranteed request claims the one slot from a running
    best-effort request.  The victim resumes from its own tokens, so
    every token the device produced for it is replayed first."""
    qos = json.dumps({
        "classes": {"guaranteed": {"priority": 100, "weight": 8},
                    "best-effort": {"priority": 0, "weight": 1}},
        "tenants": {"acme": "guaranteed"},
        "default_class": "best-effort"})

    def submit(eng):
        return [eng.submit([50, 51, 52] * 4, _greedy(40), tenant="free")]

    def disturb(eng, reqs):
        reqs.append(eng.submit([40, 41, 42] * 4, _greedy(10),
                               tenant="acme"))
        _run(eng, reqs)

    kw = dict(max_num_seqs=1, qos_config=qos, enable_prefix_caching=False)
    (_, ref), (eng, reqs) = _drains_against_sync(kw, submit, disturb)
    assert reqs[0].preemptions >= 1 and reqs[1].preemptions == 0
    assert eng.drain_counts.get("admission", 0) >= 1
    assert [len(r.output_tokens) for r in reqs] == [40, 10]
    assert reqs[1].output_tokens == ref[1].output_tokens
    assert reqs[0].output_tokens == ref[0].output_tokens


def test_a_request_that_ends_on_its_first_token_never_reaches_the_device():
    """max_tokens=1 finishes inside _begin_decode: the carry is neither
    patched nor dirtied, and the neighbour's window stays primed."""
    eng = _mk(True)
    long = eng.submit([2, 4, 6], _greedy(40))
    _decoding_with_a_window_in_flight(eng, [long])
    uploads = eng.counters["h2d_uploads_total"]
    one = eng.submit([7, 7, 7], _greedy(1))
    _run(eng, [one])
    assert len(one.output_tokens) == 1
    assert not any(eng.drain_counts.values())
    assert not eng._state_dirty & eng._DEVICE_ADVANCED
    # page table and adapter row of the slot it passed through, twice
    assert eng.counters["h2d_uploads_total"] - uploads <= 4
    _run(eng, [long])
    assert len(long.output_tokens) == 40
