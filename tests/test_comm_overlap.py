"""Collective-compute overlap for multi-chip decode: the pipelined
ring (ops/overlap_collectives.py) must match the monolithic collective
it replaces, the layer-ahead prefetch must be a pure bandwidth hint
(bitwise no-op on the output), and the engine gate must be exactly
that — gate on: TP>=2 greedy decode is token-identical to gate off;
gate off: the decode program and exposition are byte-identical to
before the feature existed."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from kaito_tpu.engine.config import EngineConfig
from kaito_tpu.engine.engine import InferenceEngine, SamplingParams
from kaito_tpu.engine.ops.overlap_collectives import (
    all_gather_matmul, overlap_linear, resolve_mode)

_ENV_FORCED = (os.environ.get("KAITO_COMM_OVERLAP", "").strip().lower()
               not in ("", "0", "false", "off"))

BASE = dict(model="tiny-llama-test", max_model_len=128, page_size=16,
            max_num_seqs=2, dtype="float32", kv_dtype="float32",
            prefill_buckets=(32,), seed=0)


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("tensor",))


def _run(engine, prompt, n=8):
    engine.start()
    try:
        p = SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True)
        return list(engine.submit(prompt, p).stream())
    finally:
        engine.stop()


# ---------------------------------------------------------------------------
# ring primitives: parity against the dense/unoverlapped reference
# ---------------------------------------------------------------------------


def test_resolve_mode_env_override(monkeypatch):
    for val, want in (("", "ring"), ("1", "ring"), ("true", "ring"),
                      ("auto", "ring"), ("ring", "ring"),
                      ("jax", "jax"), ("JAX", "jax"), (" jax ", "jax")):
        monkeypatch.setenv("KAITO_COMM_OVERLAP", val)
        assert resolve_mode() == want, val


@pytest.mark.parametrize("n", [2, 4])
def test_ring_linear_matches_dense(cpu_devices, n):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 8 * n)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((8 * n, 12 * n)), jnp.float32)
    out = overlap_linear(x, w, _mesh(n))
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                               rtol=2e-4, atol=2e-4)


def test_jax_reference_mode_matches_dense(cpu_devices, monkeypatch):
    monkeypatch.setenv("KAITO_COMM_OVERLAP", "jax")
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 32)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((32, 48)), jnp.float32)
    out = overlap_linear(x, w, _mesh(4))
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                               rtol=2e-4, atol=2e-4)


def test_ring_out_dim_not_divisible_raises(cpu_devices):
    x = jnp.ones((2, 16), jnp.float32)
    w = jnp.ones((16, 13), jnp.float32)   # 13 % 4 != 0
    with pytest.raises(ValueError, match="divisible"):
        overlap_linear(x, w, _mesh(4))


def test_all_gather_matmul_matches_dense(cpu_devices):
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((2, 32)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((32, 48)), jnp.float32)
    out = all_gather_matmul(x, w, _mesh(4))
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                               rtol=2e-4, atol=2e-4)


def test_all_gather_matmul_jax_reference_mode(cpu_devices, monkeypatch):
    """KAITO_COMM_OVERLAP=jax swaps the hand-rolled ring for the
    framework all-gather in the COLUMN-parallel primitive too — same
    numbers, different schedule (the A/B lever works on both ends)."""
    monkeypatch.setenv("KAITO_COMM_OVERLAP", "jax")
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, 32)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((32, 48)), jnp.float32)
    out = all_gather_matmul(x, w, _mesh(4))
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                               rtol=2e-4, atol=2e-4)


def test_ag_matmul_eligible_gating(cpu_devices):
    """The q/gate/up wiring keys off ``ag_matmul_eligible``: plain 2-D
    weights with both dims divisible by the mesh only — QTensor dicts
    (int4/int8) and LoRA-delta shapes stay on the unoverlapped path."""
    from kaito_tpu.engine.ops.overlap_collectives import ag_matmul_eligible

    x = jnp.ones((2, 32), jnp.float32)
    w = jnp.ones((32, 48), jnp.float32)
    assert ag_matmul_eligible(x, w, 4)
    assert not ag_matmul_eligible(x, w, 1)            # no TP axis
    assert not ag_matmul_eligible(x, {"q8": w}, 4)    # quantized dict
    assert not ag_matmul_eligible(x, jnp.ones((32, 50)), 4)  # N % n
    assert not ag_matmul_eligible(x, jnp.ones((30, 48)), 4)  # K mismatch
    assert not ag_matmul_eligible(jnp.ones((2, 30)), jnp.ones((30, 48)),
                                  4)                  # K % n
    assert not ag_matmul_eligible(x, jnp.ones((32,)), 4)     # not 2-D


def test_quantized_ring_parity(cpu_devices):
    """QTensor weights ride the ring: int8 (per-out-channel scale) and
    int4 (per-group scale, groups along K so each shard owns whole
    groups) must match the unsharded dequant reference."""
    from kaito_tpu.engine.quant import (quantize_weight_int4,
                                        quantize_weight_int8)
    from kaito_tpu.engine.ops.quant_matmul import dequant_matmul_jax

    rng = np.random.default_rng(3)
    mesh = _mesh(4)
    x = jnp.asarray(rng.standard_normal((2, 512)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((512, 64)), jnp.float32)

    w8 = quantize_weight_int8(w)
    out8 = overlap_linear(x, w8, mesh)
    np.testing.assert_allclose(np.asarray(out8),
                               np.asarray(dequant_matmul_jax(x, w8)),
                               rtol=2e-4, atol=2e-4)

    w4 = quantize_weight_int4(w)   # group=128 -> one group per shard
    out4 = overlap_linear(x, w4, mesh)
    np.testing.assert_allclose(np.asarray(out4),
                               np.asarray(dequant_matmul_jax(x, w4)),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# layer-ahead prefetch: a bandwidth hint, never a numerics change
# ---------------------------------------------------------------------------


def test_prefetch_is_bitwise_noop(monkeypatch):
    """The prefetch streams are guarded by a runtime-false predicate:
    the kernel's output with the next layer's slab threaded through is
    BITWISE identical to the kernel without it."""
    from kaito_tpu.engine.quant import (quantize_weight_int4,
                                        quantize_weight_int8)
    from kaito_tpu.engine.ops.quant_matmul import quant_linear

    monkeypatch.setenv("KAITO_QUANT_MATMUL", "interpret")
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((4, 256)), jnp.float32)
    for quantize in (quantize_weight_int8, quantize_weight_int4):
        w = quantize(jnp.asarray(rng.standard_normal((256, 256)),
                                 jnp.float32))
        w_next = quantize(jnp.asarray(rng.standard_normal((256, 256)),
                                      jnp.float32))
        base = np.asarray(quant_linear(x, w))
        pf = np.asarray(quant_linear(x, w, prefetch=w_next))
        assert (base == pf).all()


def test_prefetch_ok_gating():
    """Shape/kind mismatches and slabs over the VMEM budget are
    dropped, not errors."""
    from kaito_tpu.engine.quant import (quantize_weight_int4,
                                        quantize_weight_int8)
    from kaito_tpu.engine.ops.quant_matmul import kernel_plan, prefetch_ok

    w8 = quantize_weight_int8(jnp.ones((256, 256), jnp.float32))
    w4 = quantize_weight_int4(jnp.ones((256, 256), jnp.float32))
    plan = kernel_plan(4, w8)
    assert plan is not None
    assert prefetch_ok(plan, w8)
    assert not prefetch_ok(plan, None)
    assert not prefetch_ok(plan, w4)          # kind mismatch
    other = quantize_weight_int8(jnp.ones((256, 128), jnp.float32))
    assert not prefetch_ok(plan, other)       # shape mismatch


def test_ring_axis_resolution():
    from kaito_tpu.parallel.sharding import (PartitionRules, SERVE_RULES,
                                             ring_axis)

    assert ring_axis(SERVE_RULES) == "tensor"
    assert ring_axis(PartitionRules({})) is None
    # axes disagreeing between the row-parallel contractions -> no ring
    assert ring_axis(PartitionRules(
        {"heads": "tensor", "intermediate": "expert"})) is None


# ---------------------------------------------------------------------------
# manifest annotation + plan-time validation
# ---------------------------------------------------------------------------


def test_parse_comm_overlap_annotation():
    from kaito_tpu.manifests.inference import parse_comm_overlap_annotation

    assert parse_comm_overlap_annotation("") is None
    assert parse_comm_overlap_annotation("  ") is None
    for text in ("true", "1", "on", "enabled", " True "):
        assert parse_comm_overlap_annotation(text) is True
    for text in ("false", "0", "off", "disabled"):
        assert parse_comm_overlap_annotation(text) is False
    for bad in ("yes-ish", "2", "ring", "bogus"):
        with pytest.raises(ValueError):
            parse_comm_overlap_annotation(bad)


def test_comm_overlap_annotation_renders_flag_only_when_true():
    from kaito_tpu.api import (InferenceSpec, ObjectMeta, ResourceSpec,
                               Workspace)
    from kaito_tpu.controllers.runtime import Store
    from kaito_tpu.controllers.workspace import plan_workspace
    from kaito_tpu.manifests.inference import build_engine_command

    store = Store()
    ws = Workspace(
        ObjectMeta(name="ov"),
        resource=ResourceSpec(instance_type="ct5lp-hightpu-1t"),
        inference=InferenceSpec(preset="phi-4-mini-instruct"))
    md, plan, _ = plan_workspace(store, ws)
    cmd = build_engine_command(ws, md, plan)
    assert "--comm-overlap" not in cmd

    ws.metadata.annotations["kaito-tpu.io/comm-overlap"] = "true"
    assert "--comm-overlap" in build_engine_command(ws, md, plan)

    ws.metadata.annotations["kaito-tpu.io/comm-overlap"] = "false"
    assert "--comm-overlap" not in build_engine_command(ws, md, plan)

    # plan-time validation: a malformed gate fails the plan with the
    # PlanFailed-shaped message, before any capacity is asked for
    ws.metadata.annotations["kaito-tpu.io/comm-overlap"] = "bogus"
    with pytest.raises(ValueError, match="kaito-tpu.io/comm-overlap"):
        plan_workspace(store, ws)


# ---------------------------------------------------------------------------
# engine gate + greedy bit-equivalence (full engines on the mesh)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tp", [2, 4])
def test_tp_greedy_bit_equivalent_on_vs_off(cpu_devices, tp):
    """The acceptance bar: overlap on under TP>=2 produces the exact
    greedy token stream of overlap off."""
    prompt = [5, 6, 7, 8]
    off = InferenceEngine(EngineConfig(**BASE, tensor_parallel=tp,
                                       comm_overlap=False))
    assert off.comm_overlap is False
    ref = _run(off, prompt)

    on = InferenceEngine(EngineConfig(**BASE, tensor_parallel=tp,
                                      comm_overlap=True))
    assert on.comm_overlap is True
    assert on.model.overlap is not None
    assert on.model.overlap[1] == "tensor"
    assert _run(on, prompt) == ref


def test_compose_int4_int8kv_async_overlap(cpu_devices):
    """The full compose leg: int4 weights x int8 KV x async dispatch x
    overlap must still be token-identical to the same stack with the
    overlap gate off (the prefetch threads the quantized slab through
    the ring here)."""
    base = dict(BASE, kv_dtype="int8", quantization="int4",
                tensor_parallel=2, async_dispatch=True)
    prompt = [9, 10, 11]
    off = InferenceEngine(EngineConfig(**base, comm_overlap=False))
    ref = _run(off, prompt)
    on = InferenceEngine(EngineConfig(**base, comm_overlap=True))
    assert on.comm_overlap is True
    assert _run(on, prompt) == ref


def test_no_retrace_steady_state(cpu_devices):
    """The ring path bakes into the one decode program: after warmup
    the jit cache never grows (no per-step retraces)."""
    eng = InferenceEngine(EngineConfig(**BASE, tensor_parallel=2,
                                       comm_overlap=True))
    assert eng.comm_overlap is True
    eng.submit([1, 2, 3], SamplingParams(max_tokens=64, temperature=0.0,
                                         ignore_eos=True))
    for _ in range(8):
        eng.step()
    traced = eng._decode_fn._cache_size()
    assert traced >= 1
    for _ in range(40):
        eng.step()
    assert eng._decode_fn._cache_size() == traced


@pytest.mark.skipif(_ENV_FORCED, reason="KAITO_COMM_OVERLAP forces the "
                    "gate on; the gate-off exposition check needs a "
                    "true baseline engine")
def test_gate_off_byte_identical_exposition(cpu_devices):
    """Gate off: no overlap wiring anywhere — the model never sees a
    mesh handle and the decode program is the pre-feature program."""
    eng = InferenceEngine(EngineConfig(**BASE, tensor_parallel=2))
    assert eng.comm_overlap is False
    assert eng.model.overlap is None
    out = _run(eng, [5, 6, 7, 8], n=4)
    assert len(out) == 4


@pytest.mark.skipif(_ENV_FORCED, reason="env forces the gate on")
def test_gate_requires_tp_mesh(cpu_devices):
    """comm_overlap=True on a single-chip engine degrades to off with
    a warning — never an error, never a silent behavior change."""
    eng = InferenceEngine(EngineConfig(**BASE, comm_overlap=True))
    assert eng.comm_overlap is False
    assert eng.model.overlap is None
