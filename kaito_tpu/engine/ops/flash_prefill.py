"""Pallas TPU kernels: flash attention for prefill chunks.

Causal self-attention over a fresh chunk without materializing the
[T, T] score matrix.  ``flash_prefill_attention`` walks it so:

- **The grid goes over KV heads**: ``(batch, KV head, query block)``.
  One grid step holds the ``G = H // Hkv`` query heads that share a KV
  head, for one block of ``Bq`` query positions, stacked as
  ``[G * Bq, D]`` rows (row ``r`` is head ``r // Bq`` at position
  ``q_start + r % Bq``).  Each key block is loaded once for all of
  them: one ``[G * Bq, D] x [D, Bk]`` product, one online-softmax
  update on ``[G * Bq, Bk]`` scores, one ``[G * Bq, Bk] x [Bk, Dv]``
  product.  A KV head's whole K and V sit in VMEM and are fetched once
  a head (their block index does not move along the innermost axis).
- **Only live query blocks walk.**  A block that starts at or past
  ``true_len`` computes nothing and is written as zeros: nothing
  uninitialised leaves the kernel (a NaN in a padding row would reach
  live rows of the next layer through ``0 x NaN``).  A live block walks
  the key blocks from the first its window can see to the last at or
  under its causal diagonal (and under ``true_len``).  Padding rows
  *inside* a live block are computed like live ones: finite, and
  otherwise undefined.
- **The tile follows the operands** (``_pick_tile``): the largest
  ``Bq`` that keeps ``G * Bq`` rows under a cap, the widest ``Bk``
  under another, both shrunk until K, V, the q and output tiles and
  the float32 scores, accumulator and softmax columns fit the scoped
  VMEM budget (``_vmem_need``); a chunk that fits at no tile is refused
  by name (``_check_fits_vmem``).

Scores, the running maximum, sum and accumulator are float32;
probabilities are cast to the values' type for the second product.
Same contract as engine.attention.prefill_attention (GQA, true_len,
sliding window, softcap, values narrower than keys, a sink bias a
head); tests compare the two in interpreter mode.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# The kernel holds one KV head's whole K and V in VMEM, and the grid
# pipeline double-buffers each: 4 * T * D * itemsize bytes, 1 KiB per
# token at D=128 in bf16.  A v5e's default scoped-VMEM limit is 16 MiB:
# 12 MiB of K/V (T=12,288) compiles there and 16 MiB is refused by
# Mosaic ("Ran out of memory in memory space vmem").  What the kernel
# may plan for of those 16 MiB, K and V, tiles and float32 temporaries
# together (_vmem_need); the rest is the compiler's own scratch.  A
# chunk that needs more is refused by name (_check_fits_vmem).
_VMEM_BUDGET = 14 << 20
# Rows (query heads of a KV head x query positions) and keys a step:
# rows enough to stream through each K tile the MXU loads, keys enough
# that the softmax's row maximum and sum (one cross-lane reduction a
# row whatever the block's width) are a small part of a step.  On a
# v5e MiMo-V2.5's full layers (G 16, T 4,096) read 1.62 ms a call at
# 512 rows x 512 keys and 1.82 at 1,024 x 256; its window layers (G 8)
# 0.76-0.90 anywhere in 512-1,024 x 128-512 (PERF.md section 6, PR 39).
_MAX_ROWS = 512
_MAX_BLOCK_K = 512


def _vmem_need(T: int, D: int, Dv: int, dtype, rows: int, bk: int) -> int:
    """Bytes of VMEM one grid step of ``_flash_kernel`` plans for."""
    item = jnp.dtype(dtype).itemsize
    kv = 2 * T * (D + Dv) * item          # K and V, double-buffered
    tiles = 2 * rows * (D + Dv) * item    # the q and output tiles, likewise
    scores = 3 * rows * bk * 4            # scores, probabilities, a temporary
    acc = 2 * rows * Dv * 4               # accumulator and the second product
    cols = 4 * rows * 128 * 4             # m and l, old and new, a lane tile each
    return kv + tiles + scores + acc + cols


def _pick_tile(G: int, D: int, Dv: int, T: int, dtype) -> tuple[int, int]:
    """(Bq, Bk) for a chunk of T tokens, from what the operands show."""
    sublane = 32 // jnp.dtype(dtype).itemsize     # rows of one packed tile
    bqs = [b for b in (512, 256, 128, 64, 32, 16, 8)
           if b >= sublane and T % b == 0] or [T]
    bks = [b for b in (512, 256, 128)
           if b <= _MAX_BLOCK_K and T % b == 0] or [T]
    bq = next((b for b in bqs if G * b <= _MAX_ROWS), bqs[-1])
    bk = bks[0]
    # what does not fit gives up rows first, then keys
    while _vmem_need(T, D, Dv, dtype, G * bq, bk) > _VMEM_BUDGET:
        if bq > bqs[-1]:
            bq = bqs[bqs.index(bq) + 1]
        elif bk > bks[-1]:
            bk = bks[bks.index(bk) + 1]
        else:
            break
    return bq, bk


def _check_fits_vmem(T: int, D: int, Dv: int, dtype, G: int, bq: int,
                     bk: int) -> None:
    need = _vmem_need(T, D, Dv, dtype, G * bq, bk)
    if need > _VMEM_BUDGET:
        kv = 2 * T * (D + Dv) * jnp.dtype(dtype).itemsize
        raise ValueError(
            f"flash prefill keeps a head's K and V in VMEM beside its "
            f"tile: a {T}-token chunk needs {need >> 20} MiB ({kv >> 20} "
            f"of K and V, the rest a tile of {G * bq} rows x {bk} keys) "
            f"of the {_VMEM_BUDGET >> 20} MiB budget; prefill it in "
            f"smaller chunks")


def _flash_kernel(
    true_len_ref,      # [B] SMEM (scalar prefetch)
    window_ref,        # [1] SMEM
    # with a sink only: [H] fp32 SMEM (scalar prefetch); then
    # q_ref [1, G, Bq, D] VMEM (pre-scaled), k_ref [1, 1, T, D],
    # v_ref [1, 1, T, Dv], o_ref [1, G, Bq, Dv]
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
    hk = pl.program_id(1)
    qi = pl.program_id(2)
    true_len = true_len_ref[b]
    window = window_ref[0]
    G, Bq, D = q_ref.shape[1:]
    Dv = v_ref.shape[3]
    R = G * Bq
    q_start = qi * Bq

    @pl.when(q_start >= true_len)
    def _padding():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(q_start < true_len)
    def _live():
        q = q_ref[0].reshape(R, D)               # head-major rows
        row = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)
        q_pos = q_start + jax.lax.rem(row, Bq)

        def body(ki, carry):
            m, l, acc = carry
            k_start = pl.multiple_of(ki * block_k, block_k)
            k = k_ref[0, 0, pl.ds(k_start, block_k), :]        # [Bk, D]
            v = v_ref[0, 0, pl.ds(k_start, block_k), :]        # [Bk, Dv]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)  # [R, Bk]
            if softcap:
                s = jnp.tanh(s / softcap) * softcap
            k_pos = k_start + jax.lax.broadcasted_iota(
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

        # the block's first query sees no position before
        # q_start - window + 1, its last none past q_start + Bq - 1
        first = jnp.maximum(q_start - window + 1, 0) // block_k
        last = pl.cdiv(jnp.minimum(q_start + Bq, true_len), block_k)
        m0 = jnp.full((R, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((R, 1), jnp.float32)
        acc0 = jnp.zeros((R, Dv), jnp.float32)
        m, l, acc = jax.lax.fori_loop(first, last, body, (m0, l0, acc0))
        if has_sink:
            # the sink's column: probability and no value, a head's
            # scalar down that head's Bq rows
            sink = jnp.concatenate(
                [jnp.full((Bq, 1), sink_ref[hk * G + g], jnp.float32)
                 for g in range(G)], axis=0)
            m_new = jnp.maximum(m, sink)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.exp(sink - m_new)
            acc = acc * alpha
        # (a column's reciprocal: a divide a row, not a row's Dv)
        out = acc * (1.0 / jnp.maximum(l, 1e-30))
        o_ref[0] = out.reshape(G, Bq, Dv).astype(o_ref.dtype)


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
def flash_prefill_attention(
    q: jax.Array,            # [B, T, H, D]
    k: jax.Array,            # [B, T, Hkv, D]
    v: jax.Array,            # [B, T, Hkv, Dv]
    true_len: jax.Array,     # [B] int32
    window: jax.Array,       # [] int32 (huge == global)
    *,
    scale: float,
    softcap: Optional[float] = None,
    block_q: Optional[int] = None,     # None: _pick_tile's
    block_k: Optional[int] = None,
    interpret: bool = False,
    sink: Optional[jax.Array] = None,      # [H] fp32 sink bias a head
) -> jax.Array:
    """Causal attention of a fresh chunk, [B, T, H, Dv].  Rows of a
    query block wholly past ``true_len`` are zeros; padding rows inside
    a live block are finite and otherwise undefined."""
    B, T, H, D = q.shape
    Hkv = k.shape[2]
    Dv = v.shape[3]
    G = H // Hkv
    has_sink = sink is not None
    bq, bk = _pick_tile(G, D, Dv, T, k.dtype)
    bq = bq if block_q is None else min(block_q, T)
    bk = bk if block_k is None else min(block_k, T)
    if T % bq or T % bk:
        raise ValueError(f"chunk length {T} must be a multiple of the "
                         f"block sizes ({bq}, {bk})")
    _check_fits_vmem(T, D, Dv, k.dtype, G, bq, bk)
    grid = (B, Hkv, T // bq)

    # Head-major [B, H, T, D] layout so every block's trailing two dims
    # are (seq, head_dim) — real-TPU lowering requires the last two
    # block dims be (8, 128)-tileable or span the full array dim.  A KV
    # head's G query heads lie side by side there: one block of G.
    qt = (q * scale).astype(q.dtype).transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    prefetch = [true_len, jnp.reshape(window, (1,))]
    if has_sink:
        prefetch.append(sink.astype(jnp.float32).reshape(H))

    def q_block(b, h, t, true_len_ref, *_):
        # a padding block names the last live one: a block index that
        # does not move is not fetched again
        live = jnp.maximum(pl.cdiv(true_len_ref[b], bq), 1)
        return b, h, jnp.minimum(t, live - 1), 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, G, bq, D), q_block),
            pl.BlockSpec((1, 1, T, D), lambda b, h, t, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, T, Dv), lambda b, h, t, *_: (b, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, bq, Dv), lambda b, h, t, *_: (b, h, t, 0)),
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
