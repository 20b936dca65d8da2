"""Serving-side context parallelism: ring-attention single-shot prefill
over the mesh ``sequence`` axis, greedy-parity-checked against the
chunked baseline engine.

The capability SURVEY §7(e) flags as the part the reference never built
(its long-context story is vLLM's ``--max-model-len`` KV budget,
``pkg/model/interface.go:308-312``): here a long prompt prefills in ONE
sharded dispatch, so TTFT scales with the sequence-axis size while
decode stays tensor-parallel.
"""

import numpy as np
import pytest

from kaito_tpu.engine.config import EngineConfig
from kaito_tpu.engine.engine import InferenceEngine, SamplingParams

BASE = dict(model="tiny-llama-test", max_model_len=512, page_size=16,
            max_num_seqs=2, dtype="float32", kv_dtype="float32",
            prefill_buckets=(64, 128, 256), seed=0,
            max_prefill_tokens=64, cp_min_tokens=128)

PROMPT = list(range(3, 200))   # long enough to cross cp_min_tokens
P = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True)


def _run(**kw):
    eng = InferenceEngine(EngineConfig(**{**BASE, **kw}))
    eng.start()
    try:
        out = list(eng.submit(list(PROMPT), P).stream())
        steps = eng.counters["prefill_steps_total"]
    finally:
        eng.stop()
    return out, steps


@pytest.fixture(scope="module")
def baseline():
    """Chunked single-device reference continuation."""
    return _run()


def test_cp_prefill_greedy_parity(baseline):
    ref, ref_steps = baseline
    assert ref_steps > 1          # the baseline really chunked
    out, steps = _run(sequence_parallel=2)
    assert steps == 1             # CP ran the whole prompt in one dispatch
    assert out == ref


def test_cp_prefill_parity_seq4(baseline):
    ref, _ = baseline
    out, steps = _run(sequence_parallel=4)
    assert steps == 1
    assert out == ref


def test_cp_composes_with_tp(baseline):
    ref, _ = baseline
    out, steps = _run(sequence_parallel=2, tensor_parallel=2)
    assert steps == 1
    assert out == ref


def test_cp_short_prompts_keep_chunked_path():
    """Below cp_min_tokens the ordinary prefill runs (still correct)."""
    eng = InferenceEngine(EngineConfig(**{**BASE, "sequence_parallel": 2}))
    eng.start()
    try:
        short = list(range(3, 40))
        ref = list(eng.submit(list(short), P).stream())
        assert len(ref) == P.max_tokens
        assert ("cp", 64) not in eng._prefill_fns
    finally:
        eng.stop()


def test_cp_q_tile_parity(baseline):
    """Tiled ring queries (the long-context memory bound) are exact."""
    ref, _ = baseline
    out, steps = _run(sequence_parallel=2, cp_q_tile=32)
    assert steps == 1
    assert out == ref


def test_cp_q_tile_unaligned_parity(baseline):
    """A tile that does not divide the local shard still runs tiled
    (main tiles + one remainder ring), never one giant score block."""
    ref, _ = baseline
    # bucket 256, sp=2 -> T_loc=128; 48 leaves a 32-row remainder
    out, steps = _run(sequence_parallel=2, cp_q_tile=48)
    assert steps == 1
    assert out == ref


def test_cp_composes_with_dp(baseline):
    """DP groups each get their own sequence axis: dp=2 x sp=2 on 8
    devices, CP engages inside every group."""
    from kaito_tpu.engine.dp import DataParallelEngine

    ref, _ = baseline
    eng = DataParallelEngine(EngineConfig(**{**BASE, "data_parallel": 2,
                                             "sequence_parallel": 2}))
    eng.start()
    try:
        out = list(eng.submit(list(PROMPT), P).stream())
        assert out == ref
        assert eng.counters["prefill_steps_total"] == 1
    finally:
        eng.stop()


def test_sequence_parallel_plumbs_to_pod_env():
    """The planner's sequence axis reaches the pod: engine_env exports
    KAITO_SEQUENCE_PARALLEL and the server flag default reads it, so a
    CP plan never silently idles the chips it reserved."""
    from kaito_tpu.api import (InferenceSpec, ObjectMeta, ResourceSpec,
                               Workspace)
    from kaito_tpu.manifests.inference import engine_env
    from kaito_tpu.models import get_model_by_name
    from kaito_tpu.parallel.plan import plan_parallelism
    from kaito_tpu.sku.catalog import CHIP_CATALOG

    md = get_model_by_name("llama-3.3-70b-instruct")
    plan = plan_parallelism(md, CHIP_CATALOG["v5p"], workload="serve",
                            max_model_len=131072, target_chips=32,
                            cp_autocarve=True)
    ws = Workspace(ObjectMeta(name="cp"),
                   resource=ResourceSpec(instance_type="ct5p-hightpu-4t"),
                   inference=InferenceSpec(preset=md.name))
    env = {e["name"]: e.get("value", "") for e in engine_env(ws, md, plan)}
    assert int(env["KAITO_SEQUENCE_PARALLEL"]) == plan.mesh.size("sequence")
    assert int(env["KAITO_SEQUENCE_PARALLEL"]) >= 2

    # the server wires the flag through to EngineConfig
    import kaito_tpu.engine.server as server_mod
    src = open(server_mod.__file__).read()
    assert "KAITO_SEQUENCE_PARALLEL" in src
    assert "sequence_parallel=args.sequence_parallel_size" in src


def test_serve_plan_carves_sequence_axis():
    """The planner gives long-context SERVE plans a sequence axis when
    the user OPTS IN (cp_autocarve) — the carve is off by default
    (plan_parallelism's docstring says on what evidence: round 5's
    BENCH_r05, a file not in the tree)."""
    from kaito_tpu.models import get_model_by_name
    from kaito_tpu.parallel.plan import plan_parallelism
    from kaito_tpu.sku.catalog import CHIP_CATALOG

    md = get_model_by_name("llama-3.3-70b-instruct")
    plan = plan_parallelism(md, CHIP_CATALOG["v5p"], workload="serve",
                            max_model_len=131072, target_chips=32,
                            cp_autocarve=True)
    assert plan.mesh.size("sequence") >= 2
    assert any("context-parallel" in n for n in plan.notes)
    # short-context plans stay CP-free even when opted in
    plan_s = plan_parallelism(md, CHIP_CATALOG["v5p"], workload="serve",
                              max_model_len=8192, cp_autocarve=True)
    assert plan_s.mesh.size("sequence") == 1


def test_serve_cp_carve_gated_off_by_default():
    """Without the opt-in, long-context serve plans must NOT spend
    chips on a sequence axis (leftover becomes DP instead); the train
    carve stays unconditional."""
    from kaito_tpu.models import get_model_by_name
    from kaito_tpu.parallel.plan import plan_parallelism
    from kaito_tpu.sku.catalog import CHIP_CATALOG

    md = get_model_by_name("llama-3.3-70b-instruct")
    plan = plan_parallelism(md, CHIP_CATALOG["v5p"], workload="serve",
                            max_model_len=131072, target_chips=32)
    assert plan.mesh.size("sequence") == 1
    assert not any("context-parallel" in n for n in plan.notes)
    # evidence requirement is recorded where planner users will see it
    assert "cp_speedup" in (plan_parallelism.__doc__ or "")
    train = plan_parallelism(md, CHIP_CATALOG["v5p"], workload="train",
                             max_model_len=131072, target_chips=64)
    assert train.mesh.size("sequence") >= 2
