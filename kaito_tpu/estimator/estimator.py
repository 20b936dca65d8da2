"""HBM capacity estimator: how many TPU chips does a model need?

TPU-native analogue of the reference node estimator
(``pkg/workspace/estimator/nodesestimator/estimator.go:70``
EstimateNodeCount and the formula doc
``presets/workspace/generator/model-sku-calculation.md``).  The
reference computes a per-GPU memory budget
``gpuMem*0.84 - (2.3GiB + maxModelLen*bytesPerToken/gpuCount)`` and
divides expanded weights by it; we do the same accounting against a
chip's HBM, with TPU-appropriate constants, and round the answer up to
a *valid slice topology* instead of a VM count.

Differences from the reference, by design:

- XLA preallocates and manages HBM without torch/CUDA fragmentation, so
  the utilization cap is higher (0.92 vs 0.84).
- The fixed overhead covers the XLA runtime + compiled executables +
  collective scratch, not CUDA context + torch allocator slack.
- The answer is a topology (``"4x4"``) because TPUs provision in slice
  shapes, not node counts.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

from kaito_tpu.models.metadata import ModelMetadata
from kaito_tpu.sku.catalog import TPUChipSpec, topology_chips

GiB = 2**30

# TPU estimator constants (counterparts of estimator.go:34-59).
HBM_UTILIZATION = 0.92          # fraction of HBM the engine may plan for
WEIGHT_EXPANSION = 1.02         # loaded weights vs on-disk size
PER_CHIP_OVERHEAD_BYTES = int(1.25 * GiB)  # XLA runtime + programs + scratch
WEIGHT_OVERHEAD_FACTOR = 0.03   # proportional slack (buffers, donation gaps)

# Bytes per weight for supported quantization schemes.  Served int4
# (engine/quant.py) is packed nibbles + fp32 per-group scales at
# g=128: 0.5 + 4/128 = 0.53125 — same density as mxfp4's 4.25
# bits/weight, by coincidence of constants.
_QUANT_BYTES = {"": 2.0, "bf16": 2.0, "fp16": 2.0, "int8": 1.0, "fp8": 1.0,
                "mxfp4": 0.53125, "int4": 0.53125}


def weight_bytes(md: ModelMetadata, quantization: Optional[str] = None) -> int:
    """Loaded-weight bytes including expansion factor."""
    quant = md.quantization if quantization is None else quantization
    per_weight = _QUANT_BYTES.get(quant.lower(), 2.0)
    params = md.arch.param_count()
    return int(params * per_weight * WEIGHT_EXPANSION * (1 + WEIGHT_OVERHEAD_FACTOR))


@dataclass(frozen=True)
class SliceEstimate:
    """Result of sizing a model onto a chip generation."""

    chip: TPUChipSpec
    topology: str
    num_chips: int
    weights_bytes: int            # total, all chips
    kv_bytes_per_token: int       # all layers, un-sharded
    per_chip_budget: int          # usable HBM per chip
    kv_budget_bytes: int          # slice-wide bytes left for KV cache
    max_kv_tokens: int            # total KV tokens the slice can hold

    @property
    def per_chip_weights(self) -> int:
        return self.weights_bytes // max(self.num_chips, 1)


def _per_chip_budget(chip: TPUChipSpec) -> int:
    return int(chip.hbm_bytes * HBM_UTILIZATION) - PER_CHIP_OVERHEAD_BYTES


def estimate_chip_count(
    md: ModelMetadata,
    chip: TPUChipSpec,
    *,
    max_model_len: Optional[int] = None,
    kv_dtype_bytes: int = 2,
    quantization: Optional[str] = None,
) -> int:
    """Minimum chips such that weights (sharded) plus the KV cache of at
    least one max-length sequence fit (reference requirement:
    ``estimator.go:153`` — a GPU must hold its weight shard AND its share
    of one full-context KV)."""
    budget = _per_chip_budget(chip)
    if budget <= 0:
        raise ValueError(f"chip {chip.generation} has no usable HBM budget")
    w = weight_bytes(md, quantization)
    ctx = max_model_len or md.max_model_len
    # a sequence's bytes: its KV at full context and, for a model with a
    # state-space mixer, its row of the recurrent-state pool
    kv_one_seq = (ctx * md.kv_bytes_per_token(kv_dtype_bytes)
                  + md.arch.state_bytes_per_seq())
    chips = math.ceil((w + kv_one_seq) / budget)
    return max(chips, 1)


def estimate_slice(
    md: ModelMetadata,
    chip: TPUChipSpec,
    *,
    max_model_len: Optional[int] = None,
    kv_dtype_bytes: int = 2,
    quantization: Optional[str] = None,
    min_chips: int = 1,
) -> SliceEstimate:
    """Size the model onto the smallest valid slice topology of ``chip``.

    Raises if no topology of this generation can hold the model (the
    reference errors when a model cannot distribute; we do the same
    rather than silently spilling to host memory).
    """
    need = max(min_chips, estimate_chip_count(
        md, chip, max_model_len=max_model_len,
        kv_dtype_bytes=kv_dtype_bytes, quantization=quantization))
    topology = chip.topology_for_chips(need)
    if topology is None:
        raise ValueError(
            f"model {md.name!r} needs {need} {chip.generation} chips; largest "
            f"valid slice is {chip.valid_topologies[-1]} "
            f"({topology_chips(chip.valid_topologies[-1])} chips)"
        )
    n = topology_chips(topology)
    budget = _per_chip_budget(chip)
    w = weight_bytes(md, quantization)
    kv_budget = n * budget - w
    bpt = md.kv_bytes_per_token(kv_dtype_bytes)
    return SliceEstimate(
        chip=chip,
        topology=topology,
        num_chips=n,
        weights_bytes=w,
        kv_bytes_per_token=bpt,
        per_chip_budget=budget,
        kv_budget_bytes=max(kv_budget, 0),
        max_kv_tokens=max(kv_budget, 0) // bpt if bpt else 0,
    )


def max_kv_tokens(
    md: ModelMetadata,
    chip: TPUChipSpec,
    num_chips: int,
    *,
    kv_dtype_bytes: int = 2,
    quantization: Optional[str] = None,
) -> int:
    """KV token capacity of a given chip count (drives the engine's page
    pool size and the benchmark probe's concurrency derivation, the way
    the reference reads vLLM's KV-capacity gauges)."""
    budget = num_chips * _per_chip_budget(chip) - weight_bytes(md, quantization)
    bpt = md.kv_bytes_per_token(kv_dtype_bytes)
    return max(budget, 0) // bpt if bpt else 0
