"""Pallas TPU kernel: flash attention for prefill chunks.

Causal self-attention over a fresh chunk without materializing the
[T, T] score matrix: the grid tiles (batch, q-head, q-block); K/V for
the whole chunk sit in VMEM (chunks are bounded by the engine's
prefill budget; ``_check_kv_fits_vmem`` refuses what cannot fit) and the
kernel walks K blocks with online softmax, skipping blocks entirely
above the causal diagonal.

Same contract as engine.attention.prefill_attention (GQA, true_len,
sliding window, softcap, values narrower than keys, a sink bias a
head); tests compare the two in interpreter mode.  With a window the
walk starts at the K block that holds the first position any query of
the block can see: blocks wholly behind the window are not computed.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128

# Both kernels hold one KV head's whole K and V in VMEM, and the grid
# pipeline double-buffers each: 4 * T * D * itemsize bytes, 1 KiB per
# token at D=128 in bf16.  A v5e's default scoped-VMEM limit is 16 MiB:
# 12 MiB of K/V (T=12,288) compiles there and 16 MiB is refused by
# Mosaic ("Ran out of memory in memory space vmem"), so longer chunks
# are refused here, by name.  The engine's fresh-prefill chunks are
# bounded by max_prefill_tokens (512) — far below.
_KV_VMEM_BUDGET = 12 << 20


def _check_kv_fits_vmem(T: int, D: int, dtype, Dv: Optional[int] = None) -> None:
    need = 2 * T * (D + (D if Dv is None else Dv)) * jnp.dtype(dtype).itemsize
    if need > _KV_VMEM_BUDGET:
        raise ValueError(
            f"flash prefill keeps a head's K and V in VMEM: a {T}-token "
            f"chunk needs {need >> 20} MiB of the {_KV_VMEM_BUDGET >> 20} "
            f"MiB budget; prefill it in smaller chunks")


def _flash_kernel(
    true_len_ref,      # [B] SMEM (scalar prefetch)
    window_ref,        # [1] SMEM
    # with a sink only: [H] fp32 SMEM (scalar prefetch); then
    # q_ref [1, 1, Bq, D] VMEM (pre-scaled), k_ref [1, 1, T, D],
    # v_ref [1, 1, T, Dv], o_ref [1, 1, Bq, Dv]
    *rest,
    block_k: int,
    softcap: Optional[float],
    has_sink: bool,
):
    sink_ref = None
    if has_sink:
        sink_ref, *rest = rest
    q_ref, k_ref, v_ref, o_ref = rest
    b = pl.program_id(0)
    qi = pl.program_id(2)
    true_len = true_len_ref[b]
    window = window_ref[0]

    q = q_ref[0, 0]                          # [Bq, D]
    Bq, D = q.shape
    Dv = v_ref.shape[3]
    q_start = qi * Bq
    num_k_blocks = pl.cdiv(jnp.minimum(q_start + Bq, true_len), block_k)
    # the block's first query sees no position before q_start - window + 1
    first_k_block = jnp.maximum(q_start - window + 1, 0) // block_k

    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (Bq, 1), 0)

    def body(ki, carry):
        m, l, acc = carry
        k = k_ref[0, 0, pl.ds(ki * block_k, block_k), :]   # [Bk, D]
        v = v_ref[0, 0, pl.ds(ki * block_k, block_k), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [Bq, Bk]
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        valid = (k_pos <= q_pos) & (k_pos < true_len) \
            & (k_pos > q_pos - window)
        s = jnp.where(valid, s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        return m_new, l_new, acc * alpha + pv

    m0 = jnp.full((Bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((Bq, 1), jnp.float32)
    acc0 = jnp.zeros((Bq, Dv), jnp.float32)
    m, l, acc = jax.lax.fori_loop(first_k_block, num_k_blocks, body,
                                  (m0, l0, acc0))
    if has_sink:
        # the sink's column: probability and no value
        sink = sink_ref[pl.program_id(1)]
        m_new = jnp.maximum(m, sink)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.exp(sink - m_new)
        acc = acc * alpha
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _flash_packed_kernel(
    window_ref,        # [1] SMEM (scalar prefetch)
    seg_ref,           # [1, T] VMEM int32 segment ids (-1 = pad)
    pos_ref,           # [1, T] VMEM int32 within-segment positions
    q_ref,             # [1, 1, Bq, D] VMEM (pre-scaled)
    k_ref,             # [1, 1, T, D] VMEM
    v_ref,             # [1, 1, T, D] VMEM
    o_ref,             # [1, 1, Bq, D] VMEM
    *,
    block_k: int,
    softcap: Optional[float],
):
    qi = pl.program_id(2)
    window = window_ref[0]

    q = q_ref[0, 0]                          # [Bq, D]
    Bq, D = q.shape
    q_start = qi * Bq
    # Segments are contiguous and ordered within the packed row, so no
    # key past the current q block's end can be a same-segment-earlier
    # token: the causal block skip survives packing unchanged.
    num_k_blocks = pl.cdiv(q_start + Bq, block_k)

    seg_q = seg_ref[0, pl.ds(q_start, Bq)].reshape(Bq, 1)
    pos_q = pos_ref[0, pl.ds(q_start, Bq)].reshape(Bq, 1)

    def body(ki, carry):
        m, l, acc = carry
        k = k_ref[0, 0, pl.ds(ki * block_k, block_k), :]   # [Bk, D]
        v = v_ref[0, 0, pl.ds(ki * block_k, block_k), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [Bq, Bk]
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        seg_k = seg_ref[0, pl.ds(ki * block_k, block_k)].reshape(1, block_k)
        pos_k = pos_ref[0, pl.ds(ki * block_k, block_k)].reshape(1, block_k)
        # same segment + within-segment causal + sliding window; pads
        # carry seg -1 and never match a valid query's segment.  Fully
        # masked leading blocks self-heal: once the first valid entry
        # lands, alpha = exp(-inf - m_new) zeroes the garbage partials.
        valid = (seg_k == seg_q) & (seg_q >= 0) & (pos_k <= pos_q) \
            & (pos_k > pos_q - window)
        s = jnp.where(valid, s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        return m_new, l_new, acc * alpha + pv

    m0 = jnp.full((Bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((Bq, 1), jnp.float32)
    acc0 = jnp.zeros((Bq, D), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, num_k_blocks, body, (m0, l0, acc0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _scoped(fn):
    # trace-time marker for the device profiler's bucket classifier
    # (engine/devprof.py): every HLO op emitted here carries
    # ".../attention/..." in its metadata op_name
    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        with jax.named_scope("attention"):
            return fn(*args, **kwargs)
    return wrapper


@functools.partial(
    jax.jit,
    static_argnames=("scale", "softcap", "block_q", "block_k", "interpret"))
@_scoped
def flash_prefill_packed(
    q: jax.Array,            # [B, T, H, D] segment-packed row(s)
    k: jax.Array,            # [B, T, Hkv, D]
    v: jax.Array,
    seg_ids: jax.Array,      # [B, T] int32 (-1 = pad)
    positions: jax.Array,    # [B, T] int32 within-segment positions
    window: jax.Array,       # [] int32 (huge == global)
    *,
    scale: float,
    softcap: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """Segment-packed variant of :func:`flash_prefill_attention`: many
    fresh prompts share one padded row, masked to attend only within
    their own segment (same contract as
    engine.attention.packed_prefill_attention)."""
    B, T, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    bq = min(block_q, T)
    bk = min(block_k, T)
    if T % bq or T % bk:
        raise ValueError(f"chunk length {T} must be a multiple of the "
                         f"block sizes ({bq}, {bk})")
    _check_kv_fits_vmem(T, D, k.dtype)
    grid = (B, H, T // bq)

    qt = (q * scale).astype(q.dtype).transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, T), lambda b, h, t, *_: (b, 0)),
            pl.BlockSpec((1, T), lambda b, h, t, *_: (b, 0)),
            pl.BlockSpec((1, 1, bq, D), lambda b, h, t, *_: (b, h, t, 0)),
            pl.BlockSpec((1, 1, T, D), lambda b, h, t, *_: (b, h // G, 0, 0)),
            pl.BlockSpec((1, 1, T, D), lambda b, h, t, *_: (b, h // G, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, t, *_: (b, h, t, 0)),
    )
    kernel = functools.partial(_flash_packed_kernel, block_k=bk,
                               softcap=softcap)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.reshape(window, (1,)), seg_ids.astype(jnp.int32),
      positions.astype(jnp.int32), qt, kt, vt)
    return out.transpose(0, 2, 1, 3)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "softcap", "block_q", "block_k", "interpret"))
@_scoped
def flash_prefill_attention(
    q: jax.Array,            # [B, T, H, D]
    k: jax.Array,            # [B, T, Hkv, D]
    v: jax.Array,
    true_len: jax.Array,     # [B] int32
    window: jax.Array,       # [] int32 (huge == global)
    *,
    scale: float,
    softcap: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
    sink: Optional[jax.Array] = None,      # [H] fp32 sink bias a head
) -> jax.Array:
    B, T, H, D = q.shape
    Hkv = k.shape[2]
    Dv = v.shape[3]
    G = H // Hkv
    has_sink = sink is not None
    bq = min(block_q, T)
    bk = min(block_k, T)
    if T % bq or T % bk:
        raise ValueError(f"chunk length {T} must be a multiple of the "
                         f"block sizes ({bq}, {bk})")
    _check_kv_fits_vmem(T, D, k.dtype, Dv)
    grid = (B, H, T // bq)

    # Head-major [B, H, T, D] layout so every block's trailing two dims
    # are (seq, head_dim) — real-TPU lowering requires the last two
    # block dims be (8, 128)-tileable or span the full array dim.
    qt = (q * scale).astype(q.dtype).transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    prefetch = [true_len, jnp.reshape(window, (1,))]
    if has_sink:
        prefetch.append(sink.astype(jnp.float32).reshape(H))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, t, *_: (b, h, t, 0)),
            pl.BlockSpec((1, 1, T, D), lambda b, h, t, *_: (b, h // G, 0, 0)),
            pl.BlockSpec((1, 1, T, Dv), lambda b, h, t, *_: (b, h // G, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dv), lambda b, h, t, *_: (b, h, t, 0)),
    )
    kernel = functools.partial(_flash_kernel, block_k=bk, softcap=softcap,
                               has_sink=has_sink)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, T, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*prefetch, qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
