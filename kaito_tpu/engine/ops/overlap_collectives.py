"""Pipelined TP collectives: collective-compute overlap for decode.

Under GSPMD tensor parallelism the row-parallel decode linears (the
attention-out and MLP-down projections) produce PARTIAL sums that XLA
finishes with one monolithic all-reduce — at TP>=4 that all-reduce is
the decode step's critical path and nothing hides it.  This module is
the standard Megatron-style latency-hiding decomposition ("Overlap
Communication with Dependent Computation", Wang et al.): split the
output collective into reduce-scatter + all-gather and pipeline both as
N-1 ``ppermute`` ring hops, each hop overlapped with the NEXT output
chunk's partial matmul, so the ICI transfer drains behind the MXU
instead of after it.

Two ring primitives (both run INSIDE ``shard_map`` per-device bodies):

``ring_matmul_reduce_scatter``
    x_local [.., K/n] @ w_local [K/n, N] -> owned chunk [.., N/n].
    Step s computes ONE output-column chunk and accumulates it into the
    rotating partial that just arrived, then forwards it — by the last
    hop each device holds the fully-reduced chunk it owns.  The next
    chunk's matmul issues while the previous hop's ``ppermute`` is in
    flight, which is the whole point.

``ring_all_gather_matmul``
    The dual pair for a column-parallel linear: x chunks rotate around
    the ring while each device matmuls the chunk it currently holds
    against the matching row block of its out-sharded weight — the
    all-gather hides behind the partial dots.  (The wired decode path
    uses rs+ag; this pair is the building block for fusing the gather
    into the NEXT projection and is exercised by tests.)

``overlap_linear`` is the model-facing entry: a ``shard_map`` over the
mesh's tensor axis wrapping ring reduce-scatter + ring all-gather, with
a pure-``jax.lax`` reference body (``psum`` of the local partial — the
exact unoverlapped collective) selected by KAITO_COMM_OVERLAP=jax.
The override is read at TRACE time, same contract as
KAITO_QUANT_MATMUL: ``auto`` (and the bare gate values ``1``/``true``)
resolve to ``ring``; CPU CI runs the ring path itself — ``ppermute``
lowers to collective-permute on the host backend too, so the hop
structure the TPU will execute is what the tests pin.

QTensor weights (engine/quant.py) ride the ring natively: the local
shard's quantized planes are column-sliced per chunk (int8 scale rows
follow their out channels, int4 per-group scale columns follow their
groups — groups run along the contraction dim, so chunking the OUT dim
never splits a group) and each chunk's partial dot goes through
``quant_linear``, i.e. the fused dequant kernel on TPU with the
layer-ahead slab prefetch (``prefetch=``) threading straight through.
Numerics: the ring accumulates chunk contributions in a fixed
device-order, which differs from XLA's psum tree at n>2 — greedy decode
output is token-identical (the engine's acceptance bar), logits agree
to float tolerance.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = [
    "overlap_linear", "all_gather_matmul", "ag_matmul_eligible",
    "resolve_mode",
    "ring_matmul_reduce_scatter", "ring_all_gather",
    "ring_all_gather_matmul",
]

_OFF = ("", "0", "false", "off")


def _impl_mode() -> str:
    """Raw KAITO_COMM_OVERLAP value (the engine gate doubles as the
    trace-time implementation override)."""
    return os.environ.get("KAITO_COMM_OVERLAP", "").strip().lower()


def resolve_mode() -> str:
    """ring | jax for the next trace.  ``jax`` is the pure-lax psum
    reference (the exact unoverlapped collective); everything else that
    turns the gate on resolves to the pipelined ring."""
    return "jax" if _impl_mode() == "jax" else "ring"


def _out_dim(w) -> int:
    if isinstance(w, dict):
        return int(w["scale"].shape[-1])
    return int(w.shape[-1])


def _slice_out(w, start, size: int):
    """Column chunk [start, start+size) of a plain weight or QTensor.

    Every QTensor plane ends in the out dim (q8/q4 [K(,q), N], int8
    scale [N], int4 scale [G, N]), so one last-axis dynamic slice per
    leaf keeps the chunk a well-formed QTensor."""
    if isinstance(w, dict):
        return {k: jax.lax.dynamic_slice_in_dim(v, start, size,
                                                axis=v.ndim - 1)
                for k, v in w.items()}
    return jax.lax.dynamic_slice_in_dim(w, start, size, axis=w.ndim - 1)


def _local_matmul(x, w, prefetch=None):
    """Per-shard partial product: fused dequant path for QTensors
    (threading the layer-ahead slab), plain dot otherwise."""
    if isinstance(w, dict):
        from kaito_tpu.engine.ops.quant_matmul import quant_linear

        return quant_linear(x, w, prefetch=prefetch)
    return x @ w


def ring_matmul_reduce_scatter(x, w, *, axis_name: str, axis_size: int,
                               prefetch=None):
    """Pipelined matmul + reduce-scatter (per-device shard_map body).

    x: [.., K_local]; w: [K_local, N] (full out dim).  Returns the
    fully-reduced chunk this device owns: [.., N/axis_size].  At step s
    device d computes chunk ``(d - s - 1) mod n`` into the accumulator
    that just arrived and forwards it — the accumulator that lands on d
    after the last hop has visited every device exactly once, so it is
    chunk d complete.  Each hop's ``ppermute`` overlaps the next
    chunk's partial matmul.
    """
    n = axis_size
    N = _out_dim(w)
    if N % n:
        raise ValueError(f"out dim {N} not divisible by ring size {n}")
    nc = N // n
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    acc = None
    for s in range(n):
        c = jax.lax.rem(idx - s - 1 + 2 * n, n)
        wc = _slice_out(w, c * nc, nc)
        pfc = (_slice_out(prefetch, c * nc, nc)
               if prefetch is not None else None)
        part = _local_matmul(x, wc, prefetch=pfc)
        acc = part if acc is None else acc + part
        if s != n - 1:
            acc = jax.lax.ppermute(acc, axis_name, perm)
    return acc


def ring_all_gather(y, *, axis_name: str, axis_size: int):
    """Ring all-gather of owned chunks (per-device shard_map body):
    y [.., N/n] -> [.., N] via n-1 ``ppermute`` hops, each landing its
    chunk with a dynamic-update while the next hop is in flight."""
    n = axis_size
    nc = y.shape[-1]
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    out = jnp.zeros((*y.shape[:-1], n * nc), y.dtype)
    cur, src = y, idx
    for s in range(n):
        out = jax.lax.dynamic_update_slice_in_dim(out, cur, src * nc,
                                                  axis=out.ndim - 1)
        if s != n - 1:
            cur = jax.lax.ppermute(cur, axis_name, perm)
            src = jax.lax.rem(src - 1 + n, n)
    return out


def ring_all_gather_matmul(x, w, *, axis_name: str, axis_size: int):
    """Pipelined all-gather + matmul (per-device shard_map body).

    The column-parallel dual: x [.., K/n] is the chunk this device
    owns, w [K, N_local] is out-sharded with ALL contraction rows
    present.  x chunks rotate around the ring; each arrival matmuls
    against its matching row block, so the gather hides behind the
    partial dots.  Returns the local out shard [.., N_local].  Plain
    weights only — int4 packing ties row slicing to nibble pairs, and
    the wired decode path needs rs+ag anyway.
    """
    n = axis_size
    kc = x.shape[-1]
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    acc = None
    cur, src = x, idx
    for s in range(n):
        wrows = jax.lax.dynamic_slice_in_dim(w, src * kc, kc,
                                             axis=w.ndim - 2)
        part = cur @ wrows
        acc = part if acc is None else acc + part
        if s != n - 1:
            cur = jax.lax.ppermute(cur, axis_name, perm)
            src = jax.lax.rem(src - 1 + n, n)
    return acc


def _weight_specs(w, axis_name: str):
    """shard_map in_specs for a row-parallel weight: contraction dim on
    the ring axis, out dim (and int8's per-out-channel scale row)
    replicated; int4's group dim follows its groups' shards."""
    if isinstance(w, dict):
        return {k: (P(axis_name, None) if v.ndim == 2 else P(None))
                for k, v in w.items()}
    return P(axis_name, None)


def overlap_linear(x: jax.Array, w, mesh, *, axis_name: str = "tensor",
                   prefetch: Optional[dict] = None) -> jax.Array:
    """Row-parallel TP linear with the output collective decomposed
    into pipelined ring hops: x [.., K] @ w [K, N] -> [.., N]
    replicated, numerically a psum of local partials with ring
    accumulation order.

    ``prefetch`` is the NEXT layer's quantized slab (same QTensor
    layout as ``w``): it rides the same shard_map/ring slicing and
    lands in ``quant_linear`` so its HBM->VMEM DMA streams behind the
    hop drain (ops/quant_matmul.py).  The implementation body —
    pipelined ring vs the pure-lax psum reference — is picked by
    KAITO_COMM_OVERLAP at trace time (``resolve_mode``).
    """
    mode = resolve_mode()
    n = int(mesh.shape[axis_name])
    lead = x.ndim - 1
    x_spec = P(*([None] * lead + [axis_name]))
    out_spec = P(*([None] * (lead + 1)))
    w_spec = _weight_specs(w, axis_name)
    operands = (x, w)
    in_specs = (x_spec, w_spec)
    if prefetch is not None:
        operands += (prefetch,)
        in_specs += (_weight_specs(prefetch, axis_name),)

    def body(xl, wl, *rest):
        pfl = rest[0] if rest else None
        if mode == "jax":
            return jax.lax.psum(_local_matmul(xl, wl), axis_name)
        yc = ring_matmul_reduce_scatter(
            xl, wl, axis_name=axis_name, axis_size=n, prefetch=pfl)
        return ring_all_gather(yc, axis_name=axis_name, axis_size=n)

    with jax.named_scope(f"comm_overlap_{mode}"):
        return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                             out_specs=out_spec, check_vma=False)(*operands)


def ag_matmul_eligible(x: jax.Array, w, n: int) -> bool:
    """Can this column-parallel projection route through
    :func:`all_gather_matmul`?  Plain 2-D weights only — int4 packing
    ties row slicing to nibble pairs and int8 QTensors carry a scale
    dict — with the contraction dim K (gathered around the ring) and
    the out dim N (sharded) both dividing the ring size."""
    if n <= 1 or isinstance(w, dict) or getattr(w, "ndim", 0) != 2:
        return False
    K, N = int(w.shape[0]), int(w.shape[1])
    return int(x.shape[-1]) == K and K % n == 0 and N % n == 0


def all_gather_matmul(x: jax.Array, w: jax.Array, mesh, *,
                      axis_name: str = "tensor") -> jax.Array:
    """Column-parallel pair entry: x [.., K] (sharded on K over the
    ring) @ w [K, N] (sharded on N) -> [.., N] with the x all-gather
    hidden behind the partial dots.  Output stays out-sharded under
    GSPMD (the caller's next op decides whether it ever materializes
    replicated).  Like ``overlap_linear``, KAITO_COMM_OVERLAP=jax
    swaps the body for the pure-lax reference (gather, then one dense
    matmul) at trace time."""
    mode = resolve_mode()
    n = int(mesh.shape[axis_name])
    lead = x.ndim - 1
    x_spec = P(*([None] * lead + [axis_name]))
    w_spec = P(None, axis_name)
    out_spec = P(*([None] * lead + [axis_name]))

    def body(xl, wl):
        if mode == "jax":
            xg = jax.lax.all_gather(xl, axis_name, axis=xl.ndim - 1,
                                    tiled=True)
            return xg @ wl
        return ring_all_gather_matmul(xl, wl, axis_name=axis_name,
                                      axis_size=n)

    with jax.named_scope(f"comm_overlap_ag_matmul_{mode}"):
        return jax.shard_map(body, mesh=mesh, in_specs=(x_spec, w_spec),
                             out_specs=out_spec, check_vma=False)(x, w)
