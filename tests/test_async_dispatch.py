"""Zero-bubble async decode loop (docs/decode-loop.md).

The two-deep dispatch pipeline with device-resident loop state must be
observationally identical to the synchronous loop: same tokens, same
stop/abort/preempt behavior, same /metrics when the loop is off.  What
changes is WHERE the host does its postprocess (overlapped with window
N+1's device compute) and how often loop state crosses PCIe (~never in
steady state).
"""

import os
import time

import pytest

from kaito_tpu.engine.config import EngineConfig
from kaito_tpu.engine.engine import InferenceEngine, SamplingParams

_ENV_FORCED = os.environ.get("KAITO_ASYNC_DISPATCH", "") in ("1", "true")


def _mk(async_on, run_ahead=4, **kw):
    cfg = EngineConfig(
        model="tiny-llama-test",
        max_model_len=256,
        page_size=16,
        max_num_seqs=4,
        dtype="float32",
        kv_dtype="float32",
        prefill_buckets=(32, 64, 128),
        decode_run_ahead=run_ahead,
        async_dispatch=async_on,
        **kw)
    return InferenceEngine(cfg)


@pytest.fixture(scope="module")
def engines():
    sync = _mk(False)
    async_ = _mk(True)
    sync.start()
    async_.start()
    yield sync, async_
    sync.stop()
    async_.stop()


def test_flag_resolution():
    """config beats env; None follows KAITO_ASYNC_DISPATCH and, with
    that unset, the backend: off on the CPU these tests run on (on
    where it is an accelerator; tests/test_decode_pipeline.py pins the
    environment's both values)."""
    assert _mk(True).async_dispatch is True
    assert _mk(False).async_dispatch is False
    assert _mk(None).async_dispatch is _ENV_FORCED


def test_greedy_parity_plain(engines):
    """run_ahead exercised at K>1 AND K=1 (budget shrink near the end
    clamps the window): async must be bit-identical either way."""
    sync, async_ = engines
    p = SamplingParams(max_tokens=24, temperature=0.0, ignore_eos=True)
    prompts = [[1, 2, 3, 4, 5], [7, 8, 9], [11], list(range(20, 45))]
    outs_s = [list(sync.submit(pr, p).stream()) for pr in prompts]
    outs_a = [list(async_.submit(pr, p).stream()) for pr in prompts]
    assert outs_s == outs_a
    for o in outs_a:
        assert len(o) == 24


def test_greedy_parity_single_step():
    """run_ahead=1: the pipeline carries K=1 windows (the CPU default);
    state residency must not perturb the plain path."""
    sync, async_ = _mk(False, run_ahead=1), _mk(True, run_ahead=1)
    sync.start()
    async_.start()
    try:
        p = SamplingParams(max_tokens=16, temperature=0.0, ignore_eos=True)
        for pr in ([2, 4, 6], [9, 9, 1, 1]):
            assert list(sync.submit(pr, p).stream()) \
                == list(async_.submit(pr, p).stream())
    finally:
        sync.stop()
        async_.stop()


def test_greedy_parity_ngram_spec():
    """The ngram-speculative path under the async flag: it drains to
    depth 1 per window (acceptance decides the next window) but must
    stay bit-identical to the sync engine's speculative path."""
    sync = _mk(False, speculative_ngram=3, speculative_min_match=2)
    async_ = _mk(True, speculative_ngram=3, speculative_min_match=2)
    sync.start()
    async_.start()
    try:
        p = SamplingParams(max_tokens=32, temperature=0.0, ignore_eos=True)
        # repetitive prompts give the prompt-lookup proposer real hits
        prompts = [[5, 6, 7, 5, 6, 7, 5, 6], [1, 2, 1, 2, 1, 2, 1]]
        outs_s = [list(sync.submit(pr, p).stream()) for pr in prompts]
        outs_a = [list(async_.submit(pr, p).stream()) for pr in prompts]
        assert outs_s == outs_a
        assert async_.counters["spec_steps_total"] > 0
    finally:
        sync.stop()
        async_.stop()


def test_sampled_parity(engines):
    """Seeded stochastic sampling: PRNG rows advance once per decode
    step in both loops, so same seed => same stream."""
    sync, async_ = engines
    p = SamplingParams(max_tokens=16, temperature=0.8, top_k=40,
                       seed=1234, ignore_eos=True)
    assert list(sync.submit([5, 10, 15], p).stream()) \
        == list(async_.submit([5, 10, 15], p).stream())


def test_stop_token_mid_window(engines):
    """A stop token landing mid-window while the NEXT window is already
    in flight: the in-scan deactivation plus host replay must end the
    stream at exactly the sync loop's token, and the slot must free."""
    sync, async_ = engines
    p0 = SamplingParams(max_tokens=32, temperature=0.0, ignore_eos=True)
    ref = list(sync.submit([3, 1, 4, 1, 5], p0).stream())
    stop_tok = ref[7]
    first_hit = ref.index(stop_tok)
    p_stop = SamplingParams(max_tokens=32, temperature=0.0,
                            ignore_eos=True, stop_token_ids=(stop_tok,))
    out_s = list(sync.submit([3, 1, 4, 1, 5], p_stop).stream())
    out_a = list(async_.submit([3, 1, 4, 1, 5], p_stop).stream())
    assert out_s == out_a == ref[:first_hit]
    deadline = time.monotonic() + 5
    while async_.num_running and time.monotonic() < deadline:
        time.sleep(0.05)
    assert async_.num_running == 0


def test_abort_with_window_in_flight():
    """Abort while a dispatch is in flight: the pipeline must drain,
    the abort must retire the request promptly, and the surviving
    request's stream must be unperturbed.  Driven step-by-step so the
    in-flight state is deterministic."""
    ref = _mk(False)
    ref.start()
    p = SamplingParams(max_tokens=40, temperature=0.0, ignore_eos=True)
    ref_out = list(ref.submit([2, 4, 6], p).stream())
    ref.stop()

    eng = _mk(True)
    victim = eng.submit([9, 8, 7], p)
    keeper = eng.submit([2, 4, 6], p)
    for _ in range(60):
        eng.step()
        if eng._inflight is not None:
            break
    assert eng._inflight is not None
    eng.abort(victim)
    for _ in range(400):
        eng.step()
        if victim.finish_reason and keeper.finish_reason:
            break
    assert victim.aborted and victim.finish_reason
    assert keeper.output_tokens == ref_out


def test_preempt_with_window_in_flight():
    """Page pressure forcing a preemption while the pipeline is primed:
    the drain-to-depth-1 rule must reconcile every in-flight token into
    resume_tokens before the victim is requeued — all requests finish
    with exactly their budget."""
    def mk(async_on):
        cfg = EngineConfig(
            model="tiny-llama-test", max_model_len=128, page_size=16,
            max_num_seqs=4, max_pages=14, dtype="float32",
            kv_dtype="float32", prefill_buckets=(32, 64),
            decode_run_ahead=4, enable_prefix_caching=False,
            async_dispatch=async_on)
        return InferenceEngine(cfg)

    eng = mk(True)
    eng.start()
    try:
        p = SamplingParams(max_tokens=30, temperature=0.0, ignore_eos=True)
        reqs = [eng.submit([10 + i, 20 + i, 30 + i], p) for i in range(4)]
        outs = [list(r.stream()) for r in reqs]
        for o in outs:
            assert len(o) == 30
    finally:
        eng.stop()


def test_no_retrace_and_h2d_flat_steady_state():
    """The acceptance criteria, pinned: across >= 100 steady-state
    dispatches the with-state program never retraces (state residency
    adds no new shapes) and kaito:engine_h2d_uploads_total stays flat
    (nothing crosses PCIe once the pipeline is warm)."""
    cfg = EngineConfig(
        model="tiny-llama-test", max_model_len=4096, page_size=1024,
        max_num_seqs=2, dtype="float32", kv_dtype="float32",
        prefill_buckets=(32,), decode_run_ahead=1, async_dispatch=True)
    eng = InferenceEngine(cfg)
    # page_size 1024: no page growth for thousands of steps, so the
    # steady state really is steady (no page_tables dirtying)
    eng.submit([1, 2, 3], SamplingParams(max_tokens=3000, temperature=0.0,
                                         ignore_eos=True))
    for _ in range(40):
        eng.step()
        if eng._inflight is not None:
            break
    assert eng._inflight is not None
    fn = eng._decode_multi_state_fns[1]
    traced = fn._cache_size()
    before = eng.counters["h2d_uploads_total"]
    for _ in range(120):
        eng.step()
    assert eng.counters["h2d_uploads_total"] == before
    assert fn._cache_size() == traced
    # every one of those windows was launched behind another: what
    # the dispatch_gap field said, the counters and the records say
    assert eng.counters["decode_windows_unprimed_total"] == 1
    assert eng.counters["decode_windows_primed_total"] >= 120
    steady = eng.timeline.records()[-100:]
    assert all("dispatch_gap" not in r and "drain" not in r
               and r["decode.dispatch"] > 0 for r in steady)


@pytest.mark.skipif(_ENV_FORCED, reason="KAITO_ASYNC_DISPATCH forces the "
                    "async loop on; the flag-off exposition check needs "
                    "a true sync engine")
def test_flag_off_byte_identical_exposition():
    """The synchronous loop (what an unset field resolves to on the CPU
    backend): no async metric families, no async counters, no
    drain timeline field (and, on either loop, no dispatch_gap: the
    family is gone) — the exposition and the
    flight recorder are byte-identical to before the feature existed."""
    from kaito_tpu.engine.metrics import EngineMetrics

    eng = _mk(None)
    assert eng.async_dispatch is False
    assert not hasattr(eng, "dispatch_gap_hist")
    assert not eng.drain_counts
    assert "h2d_uploads_total" not in eng.counters
    assert "decode_windows_primed_total" not in eng.counters
    text = EngineMetrics(engine=eng).registry.expose()
    assert "dispatch_gap" not in text
    assert "h2d_uploads" not in text
    assert "decode_windows" not in text and "decode_drains" not in text
    eng.submit([1, 2, 3], SamplingParams(max_tokens=4, temperature=0.0,
                                         ignore_eos=True))
    for _ in range(200):
        eng.step()
        if not eng.num_running and not eng.num_waiting:
            break
    assert all("dispatch_gap" not in r and "drain" not in r
               for r in eng.timeline.records())


def test_flag_on_exposes_gap_and_h2d_families():
    """Wherever the two-deep loop runs its families exist, from the
    first scrape: the upload count, the primed and unprimed window
    counts, the drains by reason.  The gap histogram is gone (it
    observed 0 for every primed launch)."""
    from kaito_tpu.engine.metrics import EngineMetrics

    eng = _mk(True)
    text = EngineMetrics(engine=eng).registry.expose()
    assert "dispatch_gap" not in text
    assert "kaito:engine_h2d_uploads_total" in text
    assert "kaito:engine_decode_windows_primed_total 0" in text
    assert "kaito:engine_decode_windows_unprimed_total 0" in text
    assert 'kaito:engine_decode_drains_total{reason="finish"} 0' in text
