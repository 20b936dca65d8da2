"""The full pallas attention path (flash prefill + paged decode) under
interpreter mode must match the pure-JAX model path end to end."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from kaito_tpu.engine.kv_cache import create_kv_cache
from kaito_tpu.engine.model import TransformerLM
from kaito_tpu.models import get_model_by_name

TINY = get_model_by_name("tiny-llama-test").arch
PS = 16


def test_pallas_path_matches_jax_path():
    jax_model = TransformerLM(TINY, dtype=jnp.float32, attn_impl="jax")
    pl_model = TransformerLM(TINY, dtype=jnp.float32, attn_impl="pallas")
    params = jax_model.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    T = 32  # block-aligned chunk
    toks = jnp.asarray(rng.randint(0, TINY.vocab_size, (2, T)), jnp.int32)
    tl = jnp.asarray([T, 21], jnp.int32)
    pt = np.zeros((2, 8), np.int32)
    for b in range(2):
        pt[b] = np.arange(1 + b * 8, 9 + b * 8)
    pt = jnp.asarray(pt)

    cache_a = create_kv_cache(TINY, 32, PS, jnp.float32)
    cache_a, ref_logits, _ = jax_model.prefill(params, cache_a, toks, tl, pt)

    with pltpu.force_tpu_interpret_mode():
        cache_b = create_kv_cache(TINY, 32, PS, jnp.float32)
        cache_b, pl_logits, _ = pl_model.prefill(params, cache_b, toks, tl, pt)
        np.testing.assert_allclose(np.asarray(pl_logits),
                                   np.asarray(ref_logits),
                                   rtol=3e-4, atol=3e-4)

        # continue decoding on both paths
        positions = tl
        cache_a2, ref_d = jax_model.decode(
            params, cache_a, jnp.asarray([5, 6], jnp.int32), positions, pt)
        cache_b2, pl_d = pl_model.decode(
            params, cache_b, jnp.asarray([5, 6], jnp.int32), positions, pt)
        np.testing.assert_allclose(np.asarray(pl_d), np.asarray(ref_d),
                                   rtol=3e-4, atol=3e-4)


def _greedy_tp2(use_pallas, kv_dtype, prompts):
    """Greedy tokens from a TP=2 engine stepped on THIS thread (the
    interpret-mode switch is thread-local, so no engine.start())."""
    from kaito_tpu.engine.config import EngineConfig
    from kaito_tpu.engine.engine import InferenceEngine, SamplingParams

    eng = InferenceEngine(EngineConfig(
        model="tiny-llama-test", max_model_len=256, page_size=PS,
        max_num_seqs=4, dtype="float32", kv_dtype=kv_dtype,
        prefill_buckets=(32, 64, 128), max_prefill_tokens=64,
        tensor_parallel=2, use_pallas=use_pallas,
        enable_prefix_caching=False, seed=0))
    assert (eng.model.head_shard is not None) == use_pallas
    p = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)
    reqs = [eng.submit(list(t), p) for t in prompts]
    for _ in range(400):
        if all(r.finish_reason for r in reqs):
            break
        eng.step()
    assert all(r.finish_reason == "length" for r in reqs)
    return [list(r.output_tokens) for r in reqs]


@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
def test_pallas_path_under_tp_mesh_matches_jax_path(cpu_devices, kv_dtype):
    """On a mesh the kernels run per head shard under shard_map (a
    Mosaic call is never auto-partitioned): packed prefill (20+33
    tokens in one 64-token round), chunked context prefill (100 tokens)
    and batched decode must all match the pure-JAX engine."""
    rng = np.random.RandomState(0)
    prompts = [rng.randint(3, 2000, size=n).tolist() for n in (20, 33, 100)]
    want = _greedy_tp2(False, kv_dtype, prompts)
    with pltpu.force_tpu_interpret_mode():
        got = _greedy_tp2(True, kv_dtype, prompts)
    assert got == want


def _greedy_ragged(use_pallas, budgets):
    """Greedy tokens and the decode-row counters of a one-chip engine
    whose batch never fills (one slot more than requests) and whose
    rows end at different steps, under the two-deep loop a chip runs."""
    from kaito_tpu.engine.config import EngineConfig
    from kaito_tpu.engine.engine import InferenceEngine, SamplingParams

    eng = InferenceEngine(EngineConfig(
        model="tiny-llama-test", max_model_len=256, page_size=PS,
        max_num_seqs=len(budgets) + 1, dtype="float32", kv_dtype="float32",
        prefill_buckets=(32, 64), max_prefill_tokens=64,
        use_pallas=use_pallas, decode_run_ahead=8, async_dispatch=True,
        enable_prefix_caching=False, seed=0))
    assert eng.model.attn_impl == ("pallas" if use_pallas else "jax")
    rng = np.random.RandomState(1)
    reqs = [eng.submit(rng.randint(3, 2000, size=n).tolist(),
                       SamplingParams(max_tokens=m, temperature=0.0,
                                      ignore_eos=True))
            for n, m in budgets]
    for _ in range(400):
        if all(r.finish_reason for r in reqs):
            break
        eng.step()
    # the window launched behind the last one is retired by the idle
    # step, as the serving loop's next iteration would
    while eng.step():
        pass
    eng.stop()
    assert all(r.finish_reason == "length" for r in reqs)
    return [list(r.output_tokens) for r in reqs], dict(eng.counters)


def test_rows_that_decode_nothing_leave_the_others_exact():
    """A free slot all along and a row that finishes in the middle of a
    fused window pass the kernel a length of 0: the rows still decoding
    give the pure-JAX path's greedy ids, and the counters hold every
    slot-step the programs ran and those that decoded nothing."""
    budgets = [(20, 3), (37, 14), (9, 11)]     # (prompt, max_tokens)
    want, _ = _greedy_ragged(False, budgets)
    with pltpu.force_tpu_interpret_mode():
        got, counters = _greedy_ragged(True, budgets)
    assert got == want
    rows = counters["decode_rows_total"]
    # every step ran every slot; a request's first token is its
    # prefill's, each later one a row that decoded
    assert rows == (len(budgets) + 1) * counters["decode_steps_total"]
    decoded = sum(m - 1 for _, m in budgets)
    assert counters["decode_rows_idle_total"] == rows - decoded
    assert 0 < decoded < rows
