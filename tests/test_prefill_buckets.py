"""The ladder of prefill row counts (docs/prefill.md, "The ladder"): a
chunk runs alone in the smallest one-row program of
``EngineConfig.prefill_buckets`` that holds it, and from 1,024 rows up
the ladder has the step halfway to the next power of two.  What a prompt
is served does not depend on which program ran it; what the device is
billed does, and ``prefill_rows_total`` beside ``prefill_tokens_total``
says how much."""

import numpy as np
import pytest

from kaito_tpu.engine.config import EngineConfig
from kaito_tpu.engine.engine import InferenceEngine
from kaito_tpu.engine.metrics import EngineMetrics

import test_latent_engine
import test_lfm2_moe
import test_olmo_hybrid
import test_ssm_engine
import test_two_kind_engine
from test_two_kind_engine import _run

DEFAULT = (128, 256, 512, 1024, 1536, 2048, 3072, 4096)


def _dense(**kw):
    base = dict(model="tiny-llama-test", max_model_len=256, page_size=16,
                max_num_seqs=4, dtype="float32", kv_dtype="float32",
                max_prefill_tokens=64, decode_run_ahead=4, seed=5)
    base.update(kw)
    return InferenceEngine(EngineConfig(**base))


# a dense, a two-kind, a conv, a delta-rule, a latent and a state-space
# model: every way a prefill program treats its rows
PRESETS = {"dense": _dense, "two_kind": test_two_kind_engine._mk,
           "conv": test_lfm2_moe._mk, "delta_rule": test_olmo_hybrid._mk,
           "latent": test_latent_engine._mk, "ssm": test_ssm_engine._mk}


@pytest.fixture(scope="module")
def long_engine():
    """The default ladder at the benchmark's long cells' length."""
    return _dense(max_model_len=5120, page_size=64, max_num_seqs=1)


def test_the_default_ladder_has_its_half_steps_from_1024_up():
    assert EngineConfig().prefill_buckets == DEFAULT


@pytest.mark.parametrize("max_model_len,want", [
    (5120, DEFAULT + (5120,)),
    (3072, (128, 256, 512, 1024, 1536, 2048, 3072)),
    (1024, (128, 256, 512, 1024)),
    (256, (128, 256))])
def test_the_ladder_stops_at_max_model_len(max_model_len, want):
    eng = _dense(max_model_len=max_model_len, page_size=64, max_num_seqs=1)
    assert eng.buckets == want


@pytest.mark.parametrize("n,want", [
    (1024, 1024), (1025, 1536), (1536, 1536), (1537, 2048), (2489, 3072),
    (3072, 3072), (3073, 4096), (4096, 4096)])
def test_a_chunk_gets_the_smallest_program_that_holds_it(long_engine, n,
                                                         want):
    assert long_engine._bucket(n) == want


@pytest.fixture(scope="module", params=sorted(PRESETS))
def ladders(request):
    """One model behind a ladder with a half step and behind the powers
    of two alone, the benchmark's ladders scaled to the tiny models."""
    mk = PRESETS[request.param]
    return (mk(prefill_buckets=(32, 48, 64), max_prefill_tokens=64),
            mk(prefill_buckets=(32, 64), max_prefill_tokens=64))


@pytest.mark.parametrize("n_prompt,rows", [
    (40, ((40, 48, 64),)),                   # one fresh chunk
    (100, ((64, 64, 64), (36, 48, 64)))])    # and one down context prefill
def test_a_half_step_serves_what_the_next_power_of_two_serves(
        ladders, n_prompt, rows):
    """Tokens and log-probabilities are the same through the 48-row
    program and through the 64-row one, for the fresh chunk and for the
    context chunk, and the counters say which ran."""
    half, pow2 = ladders
    prompt = np.random.default_rng(n_prompt).integers(
        1, 250, size=n_prompt).tolist()
    got = []
    for eng, col in ((half, 1), (pow2, 2)):
        before = dict(eng.counters)
        (req,) = _run(eng, [prompt], 6)
        got.append(req)
        assert eng.counters["prefill_tokens_total"] \
            - before["prefill_tokens_total"] == sum(r[0] for r in rows)
        assert eng.counters["prefill_rows_total"] \
            - before["prefill_rows_total"] == sum(r[col] for r in rows)
    a, b = got
    assert a.output_tokens == b.output_tokens
    np.testing.assert_allclose(a.output_logprobs, b.output_logprobs,
                               atol=2e-5, rtol=0)


def test_metrics_expose_rows_beside_tokens():
    eng = _dense(prefill_buckets=(32, 48, 64))
    _run(eng, [range(1, 41)], 2)
    text = EngineMetrics(engine=eng).registry.expose()
    assert "kaito:engine_prefill_tokens_total 40" in text
    assert "kaito:engine_prefill_rows_total 48" in text
