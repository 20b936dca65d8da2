"""The two-deep decode dispatch loop under the traffic users send
(docs/decode-loop.md): more requests than slots, so every few windows
a request finishes and another takes its slot.

The loop must stay primed across a finish the scan applied itself and
across an admission into a free slot, give bit-identical tokens and
logprobs to the synchronous loop, and still go back to depth 1 where
only the host knows what happened (abort, deadline, preemption).
Engines are stepped by hand, so what is in flight at each event is
deterministic.  tests/test_async_dispatch.py holds the
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
            fused_under_load=4)
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

    # the same loop with every first token read back at once: the
    # path a grammar or a penalty takes, here for the whole mix
    blocking = _mk(True)
    blocking._first_token_blocks = lambda idxs: "stop_set"
    blocking_reqs = _submit_mix(blocking, stop_tok)
    _run(blocking, blocking_reqs)
    return dict(sync=sync_reqs, eng=eng, reqs=reqs, stop_tok=stop_tok,
                free_run=free_run, blocking=blocking,
                blocking_reqs=blocking_reqs,
                in_flight_at_admission=in_flight_at_admission)


@pytest.mark.skipif(os.environ.get("KAITO_ASYNC_DISPATCH", "") != "",
                    reason="the environment pins the loop")
def test_none_resolves_off_on_the_cpu_backend():
    """Unset, the engine decides from the backend: tier 1 runs on the
    CPU and keeps the synchronous loop it has always tested."""
    eng = _mk(None)
    assert eng.async_dispatch is False
    assert not hasattr(eng, "dispatch_gap_hist") and not eng.drain_counts
    assert "decode_windows_primed_total" not in eng.counters


@pytest.mark.parametrize("env,want", [("1", True), ("true", True),
                                      ("0", False), ("false", False)])
def test_environment_pins_an_unset_field(monkeypatch, env, want):
    monkeypatch.setenv("KAITO_ASYNC_DISPATCH", env)
    assert _mk(None).async_dispatch is want
    # the field beats the environment
    assert _mk(not want).async_dispatch is (not want)


@pytest.mark.parametrize("path", ["reqs", "blocking_reqs"],
                         ids=["deferred", "blocking"])
@pytest.mark.parametrize("what", ["tokens", "logprobs", "finish_reason"])
def test_sustained_admission_is_bit_identical(sustained, what, path):
    """The synchronous loop against the two-deep loop, its first
    tokens left on the device until the loop next waits (deferred) or
    read back at once (blocking): one program samples them in all
    three."""
    for a, b in zip(sustained["sync"], sustained[path]):
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
    assert "dispatch_gap" not in text
    assert all("dispatch_gap" not in r for r in eng.timeline.records())
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


def _spy_on_windows(eng):
    """Record every replayed window's [K, S] ``active`` trace."""
    seen = []
    replay = eng._replay_window

    def spy(K, toks, acts, lps, owners):
        seen.append(np.array(acts))
        return replay(K, toks, acts, lps, owners)

    eng._replay_window = spy
    return seen


def _first_token_of(prompt):
    probe = _mk(False)
    r = probe.submit(prompt, _greedy(2))
    _run(probe, [r])
    return r.output_tokens[0]


@pytest.mark.parametrize("how", ["budget", "stop_id"])
def test_a_request_that_ends_on_its_first_token_never_reaches_a_window(how):
    """max_tokens=1, or a stop id first: the carry's program runs for
    the row and leaves it inactive on the device, as _emit decides on
    the host; no window ever holds the row, nothing drains, and the
    neighbour's windows stay primed."""
    prompt = [7, 7, 7]
    if how == "budget":
        params, want = _greedy(1), "length"
    else:
        params = _greedy(30, stop_token_ids=(_first_token_of(prompt),))
        want = "stop"
    eng = _mk(True)
    long = eng.submit([2, 4, 6], _greedy(40))
    _decoding_with_a_window_in_flight(eng, [long])
    windows = _spy_on_windows(eng)
    uploads = eng.counters["h2d_uploads_total"]
    unprimed = eng.counters["decode_windows_unprimed_total"]
    one = eng.submit(prompt, params)
    slots = set()
    for _ in range(200):
        if one.finish_reason:
            break
        eng.step()
        slots |= {i for i, s in enumerate(eng.slots) if s.request is one}
    assert one.finish_reason == want
    assert len(one.output_tokens) == 1
    assert eng.counters["first_tokens_deferred_total"] == 2
    (slot,) = slots
    # the device retired the row in the program that sampled its token
    assert not bool(np.asarray(eng._dev_state["active"])[slot])
    assert windows and not any(w[:, slot].any() for w in windows)
    assert not any(eng.drain_counts.values())
    assert not eng._state_dirty & eng._DEVICE_ADVANCED
    assert eng.counters["decode_windows_unprimed_total"] == unprimed
    # page table and adapter row of the slot it passed through, twice,
    # and the row the program joined
    assert eng.counters["h2d_uploads_total"] - uploads <= 5
    _run(eng, [long])
    assert len(long.output_tokens) == 40


def _arrival_with_a_window_in_flight():
    """Two requests decoding, a window in flight, and a third whose
    prefill has just been dispatched: its first token is on the device
    and the host has not seen it.  Returns the engine, the arrival and
    its reference from the synchronous loop."""
    ref = _mk(False)
    want = ref.submit([5, 6, 7, 8], _greedy(20, logprobs=True))
    _run(ref, [want])
    eng = _mk(True)
    olds = [eng.submit([9, 8, 7], _greedy(60)),
            eng.submit([2, 4, 6], _greedy(60))]
    _decoding_with_a_window_in_flight(eng, olds)
    new = eng.submit([5, 6, 7, 8], _greedy(20, logprobs=True))
    for _ in range(20):
        if eng._first_pending:
            break
        eng.step()
    assert eng._first_pending and eng._inflight is not None
    assert new.output_tokens == []
    return eng, new, want, olds


def _prefill_turns(eng):
    return (eng.counters["prefill_turns_multi_total"]
            + eng.counters["prefill_turns_single_total"])


def test_the_host_does_not_wait_for_a_first_token_at_the_prefill(sustained):
    """No step of the deferred run spent time in prefill.wait, the
    blocking run's steps did, and resolution has its own span."""
    deferred = sustained["eng"].timeline.records()
    assert not any("prefill.wait" in r for r in deferred)
    # a prefill turn takes every staged prompt its budget holds, and
    # the turn's first tokens are resolved where the loop next waits:
    # a step with a resolution for each turn, not for each request
    turns = _prefill_turns(sustained["eng"])
    assert 1 < turns < N_REQUESTS
    assert turns - 1 <= sum(1 for r in deferred
                            if "prefill.resolve" in r) <= turns
    blocking = sustained["blocking"].timeline.records()
    assert sum(1 for r in blocking if "prefill.wait" in r) \
        == _prefill_turns(sustained["blocking"])
    assert not any("prefill.resolve" in r for r in blocking)


def test_a_pending_first_token_holds_its_slot():
    """Between the dispatch and the resolution the slot is neither
    free nor prefilling; the host plans it as a decoding row (pages,
    stop ids) and the device has it in the carry with its token."""
    eng, new, want, _ = _arrival_with_a_window_in_flight()
    (staged, tok, lp, _t0), = eng._first_pending
    (slot, seq, n), = staged
    assert eng.slots[slot].request is new and eng.slots[slot].seq == seq
    assert not eng.slots[slot].prefilling and n == 4
    assert eng.active[slot] and eng.positions[slot] == 4
    assert bool(np.asarray(eng._dev_state["active"])[slot])
    assert int(np.asarray(eng._dev_state["last_tokens"])[slot]) \
        == int(np.asarray(tok)[0]) == want.output_tokens[0]
    assert int(np.asarray(eng._dev_state["left"])[slot]) == 19
    assert not eng._state_dirty & eng._DEVICE_ADVANCED
    assert new.first_token_time is None
    _run(eng, [new])
    assert new.output_tokens == want.output_tokens
    assert new.output_logprobs == want.output_logprobs
    assert eng.first_token_resolve_hist._total == 3


def _emissions(eng, req):
    """Spy on _emit: for ``req``, whether each token came from a
    window's replay."""
    order, state = [], {"replaying": False}
    emit, replay = eng._emit, eng._replay_window

    def spy_emit(slot_idx, token, logprob=None):
        if eng.slots[slot_idx].request is req:
            order.append(state["replaying"])
        return emit(slot_idx, token, logprob=logprob)

    def spy_replay(*a):
        state["replaying"] = True
        try:
            return replay(*a)
        finally:
            state["replaying"] = False

    eng._emit, eng._replay_window = spy_emit, spy_replay
    return order


def test_a_first_token_is_emitted_before_any_replayed_token_of_its_row():
    eng, new, want, _ = _arrival_with_a_window_in_flight()
    order = _emissions(eng, new)
    _run(eng, [new])
    assert new.output_tokens == want.output_tokens
    assert order[0] is False and all(order[1:])


def test_a_replay_that_meets_an_unresolved_first_token_resolves_it_first():
    """The guard behind the order: a window that holds the row's later
    tokens, retired with the first still on the device (the loop never
    lets it come to that), emits the first before them."""
    eng, new, want, _ = _arrival_with_a_window_in_flight()
    order = _emissions(eng, new)
    resolve = eng._resolve_first_tokens
    eng._resolve_first_tokens = lambda ready_only=False: False
    eng._drain_pipeline("idle")       # the window launched before the row
    assert eng._first_pending and new.output_tokens == []
    eng._decode_async(4)              # a window that holds the row
    assert eng._first_pending
    win, eng._inflight = eng._inflight, None
    eng._resolve_first_tokens = \
        lambda ready_only=False: False if ready_only else resolve()
    eng._retire_window(win)
    assert not eng._first_pending
    # all five inside the replay, the guard's first
    assert new.output_tokens == want.output_tokens[:5]
    assert order == [True] * 5
    eng._resolve_first_tokens = resolve
    _run(eng, [new])
    assert new.output_tokens == want.output_tokens


def test_a_drain_with_a_first_token_pending_resolves_it_before_the_upload():
    """Something only the host knows dirties the carry while a first
    token is on the device: the drain reads it back, the mirrors hold
    the row, and the upload from them rolls nothing back."""
    ref = _mk(False)
    wants = [ref.submit(p, _greedy(60)) for p in ([9, 8, 7], [2, 4, 6])]
    _run(ref, wants)
    eng, new, want, olds = _arrival_with_a_window_in_flight()
    (staged, *_), = eng._first_pending
    slot = staged[0][0]
    eng._mark_state_dirty("active", "left", why="finish")
    eng._drain_pipeline("finish")
    assert not eng._first_pending and eng._inflight is None
    assert new.output_tokens == want.output_tokens[:1]
    assert eng.last_tokens[slot] == want.output_tokens[0]
    assert eng.active[slot] and eng.positions[slot] == 4
    uploads = eng.counters["h2d_uploads_total"]
    eng.step()
    assert eng.counters["h2d_uploads_total"] - uploads >= 2
    _run(eng, [new] + olds)
    assert new.output_tokens == want.output_tokens
    assert [r.output_tokens for r in olds] == [w.output_tokens for w in wants]
    assert eng.drain_counts["finish"] == 1


def _grammar_of(eng):
    from kaito_tpu.engine.grammar import GrammarSpec, canonical_schema

    schema = {"type": "object", "properties": {"ok": {"type": "boolean"}},
              "required": ["ok"]}
    return eng.grammar_cache.get(
        GrammarSpec("json_schema", canonical_schema(schema)), eng.tokenizer)


@pytest.mark.parametrize("reason", ["grammar", "penalties", "stop_set",
                                    "speculation"])
def test_what_the_host_must_see_first_takes_the_blocking_path(reason):
    """A request whose first token the host needs before the next
    launch can be built is read back at once and counted by reason;
    its neighbour's is deferred; both streams are the synchronous
    loop's."""
    cfg = dict(speculative_ngram=3) if reason == "speculation" else {}

    def special(eng):
        if reason == "grammar":
            return SamplingParams(max_tokens=30, temperature=0.0,
                                  grammar=_grammar_of(eng))
        if reason == "penalties":
            return _greedy(20, presence_penalty=0.7, frequency_penalty=0.3)
        if reason == "stop_set":
            return _greedy(20, stop_token_ids=tuple(range(300, 309)))
        return _greedy(20)

    out = []
    for async_on in (False, True):
        eng = _mk(async_on, **cfg)
        plain = eng.submit([2, 4, 6], _greedy(24))
        for _ in range(8):
            eng.step()
        marked = eng.submit([10, 20, 30], special(eng))
        _run(eng, [plain, marked])
        out.append((eng, plain, marked))
    (_, ref_plain, ref_marked), (eng, plain, marked) = out
    assert marked.output_tokens == ref_marked.output_tokens
    assert plain.output_tokens == ref_plain.output_tokens
    both = 2 if reason == "speculation" else 1
    assert eng.first_token_blocking[reason] == both
    assert sum(eng.first_token_blocking.values()) == both
    assert eng.counters["first_tokens_deferred_total"] == 2 - both
    assert not eng._first_pending


def test_deferred_and_blocking_add_up_to_the_prompts_completed(sustained):
    for name, deferred in (("eng", N_REQUESTS), ("blocking", 0)):
        eng = sustained[name]
        assert eng.counters["first_tokens_deferred_total"] == deferred
        assert eng.counters["first_tokens_deferred_total"] \
            + sum(eng.first_token_blocking.values()) \
            == eng.counters["prefill_steps_total"] == N_REQUESTS
        assert eng.first_token_resolve_hist._total == N_REQUESTS
        # one first-token program a prompt, however many its turn took
        assert eng.prefill_pack_hist._sum == N_REQUESTS
        assert eng.prefill_pack_hist._total == _prefill_turns(eng)
    # the fixture forced the path: the reason it gave is counted
    assert sustained["blocking"].first_token_blocking["stop_set"] \
        == N_REQUESTS
    assert "first_tokens_deferred_total" not in _mk(False).counters


def test_the_first_token_families_exist_where_the_loop_runs(sustained):
    from kaito_tpu.engine.metrics import EngineMetrics

    text = EngineMetrics(engine=sustained["blocking"]).registry.expose()
    assert "kaito:engine_first_tokens_deferred_total 0" in text
    assert f"kaito:engine_first_tokens_blocking_total {N_REQUESTS}" in text
    assert ('kaito:engine_first_tokens_blocking_by_reason_total'
            f'{{reason="stop_set"}} {N_REQUESTS}') in text
    assert ('kaito:engine_first_tokens_blocking_by_reason_total'
            '{reason="grammar"} 0') in text
    assert ("kaito:engine_first_token_resolve_seconds_count "
            f"{N_REQUESTS}") in text
    off = EngineMetrics(engine=_mk(False)).registry.expose()
    assert "engine_first_token" not in off


def test_every_prompt_of_a_multi_prompt_turn_is_deferred():
    """Four staged prompts go in one turn: a first-token program a
    prompt, queued behind its prefill, none read back inside the turn,
    every one deferred."""
    prompts = [[3 + i, 5 + i, 7 + i, 9 + i] for i in range(4)]
    out = []
    for async_on in (False, True):
        eng = _mk(async_on)
        reqs = [eng.submit(p, _greedy(12 + 3 * i, logprobs=True))
                for i, p in enumerate(prompts)]
        if async_on:
            eng.step()
            assert (eng.prefill_pack_hist._total,
                    eng.prefill_pack_hist._sum) == (1, 4.0)
            staged = [i for rows, *_ in eng._first_pending
                      for i, _, _ in rows]
            assert sorted(staged) == [0, 1, 2, 3]
            assert all(eng.active[i] for i in staged)
        _run(eng, reqs)
        out.append((eng, reqs))
    (_, ref), (eng, reqs) = out
    for a, b in zip(ref, reqs):
        assert a.output_tokens == b.output_tokens
        assert a.output_logprobs == b.output_logprobs
    assert eng.counters["first_tokens_deferred_total"] == 4
    assert not any(eng.first_token_blocking.values())
    assert eng.first_token_resolve_hist._total == 4
