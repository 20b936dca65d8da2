"""Pipeline-parallel serving: a 2-stage engine on the CPU mesh must
decode greedily identically to a single-device engine.

Covers the serving side of the planner's tier 3 (reference:
pkg/model/interface.go:519-530 --pipeline-parallel-size over Ray; here
a stage-sharded shard_map program over the ``pipeline`` mesh axis).
"""

import jax
import numpy as np
import pytest

from kaito_tpu.engine.config import EngineConfig
from kaito_tpu.engine.engine import InferenceEngine, SamplingParams

BASE = dict(model="tiny-llama-test", max_model_len=256, page_size=16,
            max_num_seqs=4, dtype="float32", kv_dtype="float32",
            prefill_buckets=(32, 64, 128), seed=0,
            enable_prefix_caching=False)

pytestmark = pytest.mark.skipif(len(jax.devices()) < 2,
                                reason="needs >=2 devices")


def _greedy(n):
    return SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True)


def test_pp_decode_greedy_parity():
    ref_eng = InferenceEngine(EngineConfig(**BASE))
    pp_eng = InferenceEngine(
        EngineConfig(**{**BASE, "pipeline_parallel": 2,
                        "pp_microbatches": 2}))
    assert pp_eng.pp_exec is not None
    prompts = [[7, 8, 9], [11, 12, 13, 14], [21, 22], [5, 6, 7, 8, 9]]
    ref_eng.start(); pp_eng.start()
    try:
        refs = [list(ref_eng.submit(p, _greedy(8)).stream()) for p in prompts]
        # submit concurrently so microbatched decode really interleaves
        reqs = [pp_eng.submit(p, _greedy(8)) for p in prompts]
        outs = [list(r.stream()) for r in reqs]
    finally:
        ref_eng.stop(); pp_eng.stop()
    assert outs == refs


def test_pp_chunked_prefill_parity():
    """Long prompts through the staged chunked-prefill (context) path."""
    ref_eng = InferenceEngine(EngineConfig(**BASE, max_prefill_tokens=32))
    pp_eng = InferenceEngine(
        EngineConfig(**{**BASE, "pipeline_parallel": 2, "pp_microbatches": 2},
                     max_prefill_tokens=32))
    prompt = [(13 * i) % 1800 + 2 for i in range(100)]
    ref_eng.start(); pp_eng.start()
    try:
        ref = list(ref_eng.submit(prompt, _greedy(6)).stream())
        out = list(pp_eng.submit(prompt, _greedy(6)).stream())
    finally:
        ref_eng.stop(); pp_eng.stop()
    assert out == ref


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs >=4 devices")
def test_pp_tp_decode_greedy_parity():
    """The north-star serving shape: TP inside each pipeline stage
    (reference tier 3, interface.go:514-530).  pp=2 x tp=2 over 4 CPU
    devices must decode greedily identically to a single device."""
    ref_eng = InferenceEngine(EngineConfig(**BASE))
    eng = InferenceEngine(
        EngineConfig(**{**BASE, "pipeline_parallel": 2,
                        "tensor_parallel": 2, "pp_microbatches": 2}))
    assert eng.pp_exec is not None and eng.pp_exec.tp == 2
    prompts = [[7, 8, 9], [11, 12, 13, 14], [21, 22], [5, 6, 7, 8, 9]]
    ref_eng.start(); eng.start()
    try:
        refs = [list(ref_eng.submit(p, _greedy(8)).stream()) for p in prompts]
        reqs = [eng.submit(p, _greedy(8)) for p in prompts]
        outs = [list(r.stream()) for r in reqs]
    finally:
        ref_eng.stop(); eng.stop()
    assert outs == refs


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs >=4 devices")
def test_pp_tp_chunked_prefill_parity():
    """Long prompt through the staged chunked-prefill path at pp=2xtp=2."""
    ref_eng = InferenceEngine(EngineConfig(**BASE, max_prefill_tokens=32))
    eng = InferenceEngine(
        EngineConfig(**{**BASE, "pipeline_parallel": 2, "tensor_parallel": 2,
                        "pp_microbatches": 2}, max_prefill_tokens=32))
    prompt = [(13 * i) % 1800 + 2 for i in range(100)]
    ref_eng.start(); eng.start()
    try:
        ref = list(ref_eng.submit(prompt, _greedy(6)).stream())
        out = list(eng.submit(prompt, _greedy(6)).stream())
    finally:
        ref_eng.stop(); eng.stop()
    assert out == ref


def test_pp_guards():
    # ep must divide the expert count (0 experts on a dense model)
    with pytest.raises(ValueError, match="expert"):
        InferenceEngine(EngineConfig(**{**BASE, "pipeline_parallel": 2,
                                        "expert_parallel": 2}))


# slow: 26 s alone under the check's command
@pytest.mark.slow
def test_pd_handoff_across_layouts():
    """Round-4: the KV wire layout is canonical (layer-major), so a
    pipeline-staged prefill engine hands KV to a FLAT decode engine —
    and the reverse — with exact greedy parity (beyond the reference,
    whose NIXL hand-off requires matching worker layouts)."""
    prompt = list(range(3, 40))
    p = SamplingParams(max_tokens=10, temperature=0.0, ignore_eos=True)
    pd_base = dict(BASE, pd_enabled=True)

    ref = InferenceEngine(EngineConfig(**pd_base))
    ref.start()
    ref_out = list(ref.submit(prompt, p).stream())
    ref.stop()

    def handoff(prod_cfg, cons_cfg):
        prod = InferenceEngine(EngineConfig(**prod_cfg))
        prod.start()
        try:
            pre = prod.submit(prompt, SamplingParams(
                max_tokens=1, temperature=0.0, ignore_eos=True),
                export_kv=True)
            first = list(pre.stream())[0]
            staged = prod.kv_exports.pop(pre.req_id)
            staged.wait_all()
            blob = staged.whole_blob()
            meta = staged.meta
        finally:
            prod.stop()
        cons = InferenceEngine(EngineConfig(**cons_cfg))
        cons.start()
        try:
            req = cons.submit_with_kv(prompt, first, meta, blob, p)
            list(req.stream())
            assert req.finish_reason != "error"
            return list(req.output_tokens)
        finally:
            cons.stop()

    pp_cfg = dict(pd_base, pipeline_parallel=2, pp_microbatches=2)
    # pp prefill -> flat decode
    assert handoff(pp_cfg, pd_base) == ref_out
    # flat prefill -> pp decode
    assert handoff(pd_base, pp_cfg) == ref_out


def test_planner_pp_wiring():
    """plan_parallelism tier 3 emits a pipeline axis the engine config
    can consume directly."""
    from kaito_tpu.models import get_model_by_name
    from kaito_tpu.parallel.plan import plan_parallelism
    from kaito_tpu.sku.catalog import CHIP_CATALOG

    md = get_model_by_name("llama-3.3-70b-instruct")
    chip = CHIP_CATALOG["v5e"]
    plan = plan_parallelism(md, chip, workload="serve", max_model_len=8192)
    # 70B on v5e: either a wide-TP single slice or PP stages; both are
    # valid plans — the engine accepts whatever the mesh says
    assert plan.mesh.size("pipeline") >= 1
    cfg = EngineConfig(model=md.name,
                       tensor_parallel=plan.mesh.size("tensor"),
                       pipeline_parallel=plan.mesh.size("pipeline"))
    assert cfg.pipeline_parallel == plan.num_slices
