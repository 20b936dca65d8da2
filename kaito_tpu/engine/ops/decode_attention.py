"""Pallas TPU kernel: paged decode attention.

One grid program per sequence.  Each loop iteration DMAs one page of K
and V for *all* KV heads (the token-major cache layout makes a page one
contiguous ``[page_size * Hkv, D]`` panel) into a 4-deep VMEM ring
while the previous page's flash-attention block computes.  HBM traffic
is exactly one read of the live KV — the decode roofline.

Compute is the *flat cross-head* formulation: scores for ALL query
heads against ALL of the page's rows in one MXU matmul
``[H, D] @ [ps*Hkv, D]^T -> [H, ps*Hkv]``, with GQA head-matching
applied as a -inf mask so mismatched (query-head, kv-head) entries drop
out of the online softmax exactly (exp(-inf) = 0 contributes nothing to
the running sum, and the PV pass ``[H, ps*Hkv] @ [ps*Hkv, D]`` sees
zeros there).  This wastes Hkv× MXU FLOPs — which are free at decode
sizes — to buy a kernel with NO transposes, reshapes, or batched dots:
Mosaic compiles only leading-batch/2-D dots well, and an in-kernel
``[ps, Hkv, D] -> [Hkv, ps, D]`` transpose doubled the kernel's cost.

The cache layout is token-major within a page (see engine.kv_cache):
each decode-step KV write is then a scatter whose update window is one
minor-contiguous ``[Hkv, D]`` tile, which XLA keeps in the default
layout — the same layout this kernel pins for its operands.  (With the
head-major order the scatter preferred a transposed layout and XLA
reconciled the two with a full-cache copy per layer: 64 GiB/step of
pure layout conversion at phi-4-mini bench shapes.)

With ``layer`` the caches are the FULL stacked layer group and the
kernel DMAs pages of that layer straight out of the big buffer — no
per-layer slice is ever materialized (feeding per-layer slices through
the scan cost more than the kernel itself).

Supports GQA (grouped queries), sliding windows (traced per-layer
window sizes from the model's scan flags), and gemma-2 logit softcap.
The pure-JAX fallback in kaito_tpu.engine.attention implements the same
contract; tests compare the two in interpreter mode and on-chip.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
N_BUF = 4


def _decode_kernel(
    # scalar prefetch
    page_tables_ref,   # [B, pmax] SMEM
    lengths_ref,       # [B] SMEM
    window_ref,        # [1] SMEM
    layer_ref,         # [1] SMEM layer index into the stacked cache
    # inputs
    q_ref,             # [1, H, D] VMEM (pre-scaled)
    k_hbm,             # [Lg, P, ps*Hkv, D] ANY/HBM (full group stack)
    v_hbm,
    # quantized mode only: [Lg, P, 1, ps*Hkv] fp32 dequant rows, then
    # outputs + scratch (+[N_BUF, 1, ps*Hkv] scale ring / extra sems)
    *rest,
    page_size: int,
    num_kv: int,
    softcap: Optional[float],
    quantized: bool,
):
    if quantized:
        (ks_hbm, vs_hbm, o_ref,
         k_buf, v_buf, sems, ks_buf, vs_buf, ssems) = rest
    else:
        o_ref, k_buf, v_buf, sems = rest
        ks_hbm = vs_hbm = ks_buf = vs_buf = ssems = None

    b = pl.program_id(0)
    length = lengths_ref[b]
    window = window_ref[0]
    li = layer_ref[0]
    n_pages = pl.cdiv(length, page_size)
    H = q_ref.shape[1]
    G = H // num_kv
    cols = page_size * num_kv

    def k_dma(slot, p):
        return pltpu.make_async_copy(
            k_hbm.at[li, page_tables_ref[b, p]], k_buf.at[slot],
            sems.at[slot, 0])

    def v_dma(slot, p):
        return pltpu.make_async_copy(
            v_hbm.at[li, page_tables_ref[b, p]], v_buf.at[slot],
            sems.at[slot, 1])

    def ks_dma(slot, p):
        return pltpu.make_async_copy(
            ks_hbm.at[li, page_tables_ref[b, p]], ks_buf.at[slot],
            ssems.at[slot, 0])

    def vs_dma(slot, p):
        return pltpu.make_async_copy(
            vs_hbm.at[li, page_tables_ref[b, p]], vs_buf.at[slot],
            ssems.at[slot, 1])

    def start_page(slot, p):
        k_dma(slot, p).start()
        v_dma(slot, p).start()
        if quantized:
            ks_dma(slot, p).start()
            vs_dma(slot, p).start()

    for i in range(N_BUF):
        @pl.when(i < n_pages)
        def _(i=i):
            start_page(i, i)

    q2 = q_ref[0]                                  # [H, D]
    # score-panel coordinates: column t*Hkv + h' is page row t, kv head
    # h'; query row h*G+g matches kv head h
    row_kv = jax.lax.broadcasted_iota(jnp.int32, (H, cols), 0) // G
    col_kv = jax.lax.broadcasted_iota(jnp.int32, (H, cols), 1) % num_kv
    col_t = jax.lax.broadcasted_iota(jnp.int32, (H, cols), 1) // num_kv
    head_ok = row_kv == col_kv

    def body(p, carry):
        m, l, acc = carry
        slot = jax.lax.rem(p, N_BUF)

        k_dma(slot, p).wait()
        v_dma(slot, p).wait()
        k2 = k_buf[slot]                           # [ps*Hkv, D]
        v2 = v_buf[slot]
        if quantized:
            ks_dma(slot, p).wait()
            vs_dma(slot, p).wait()
            # Per-column scales factor out of the D-contraction exactly:
            # fold sigma_k into the scores and sigma_v into the probs, so
            # the int8 dots match the dequantize-then-dot fallback.
            k2 = k2.astype(q2.dtype)

        s = jax.lax.dot_general(
            q2, k2, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)    # [H, ps*Hkv]
        if quantized:
            s = s * ks_buf[slot]                   # [1, ps*Hkv] broadcast
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        pos = p * page_size + col_t
        valid = head_ok & (pos < length) & (pos >= length - window)
        s = jnp.where(valid, s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p_ij = jnp.exp(s - m_new)
        l_new = l * alpha + jnp.sum(p_ij, axis=1, keepdims=True)
        if quantized:
            p_ij = p_ij * vs_buf[slot]
            v2 = v2.astype(jnp.float32)
        pv = jax.lax.dot_general(
            p_ij.astype(v2.dtype), v2, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # [H, D]

        # refill the slot we just consumed
        @pl.when(p + N_BUF < n_pages)
        def _():
            start_page(slot, p + N_BUF)
        return m_new, l_new, acc * alpha + pv

    D = q_ref.shape[2]
    m0 = jnp.full((H, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((H, 1), jnp.float32)
    acc0 = jnp.zeros((H, D), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_pages, body, (m0, l0, acc0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


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
    static_argnames=("scale", "softcap", "interpret"))
@_scoped
def paged_decode_attention_pallas(
    q: jax.Array,            # [B, H, D]
    cache_k: jax.Array,      # [P, ps, Hkv, D] or [Lg, P, ps, Hkv, D] w/ layer
    cache_v: jax.Array,
    page_tables: jax.Array,  # [B, pmax] int32
    lengths: jax.Array,      # [B] int32
    window: jax.Array,       # [] int32 (huge == global attention)
    *,
    scale: float,
    softcap: Optional[float] = None,
    interpret: bool = False,
    layer: Optional[jax.Array] = None,
    k_scale: Optional[jax.Array] = None,   # [P, Hkv] / [Lg, P, Hkv] fp32
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    B, H, D = q.shape
    quantized = k_scale is not None
    if layer is None:
        cache_k = cache_k[None]
        cache_v = cache_v[None]
        if quantized:
            k_scale = k_scale[None]
            v_scale = v_scale[None]
        layer = jnp.zeros((), jnp.int32)
    Lg, P, ps, Hkv, _ = cache_k.shape
    # token-flat page view [Lg, P, ps*Hkv, D]: free reshape, and the
    # page DMA plus both kernel dots run on it without any relayout
    ck_flat = cache_k.reshape(Lg, P, ps * Hkv, D)
    cv_flat = cache_v.reshape(Lg, P, ps * Hkv, D)
    q_scaled = q * scale

    operands = [q_scaled, ck_flat, cv_flat]
    cache_specs = [
        pl.BlockSpec(memory_space=pltpu.ANY),
        pl.BlockSpec(memory_space=pltpu.ANY),
    ]
    scratch = [
        pltpu.VMEM((N_BUF, ps * Hkv, D), cache_k.dtype),
        pltpu.VMEM((N_BUF, ps * Hkv, D), cache_v.dtype),
        pltpu.SemaphoreType.DMA((N_BUF, 2)),
    ]
    if quantized:
        # Pre-expand the per-page scales to per-COLUMN dequant rows
        # [Lg, P, 1, ps*Hkv]: column t*Hkv+h' holds sigma[h'] (tile
        # repeats the head axis ps times, matching the token-major
        # column order), so one extra [1, ps*Hkv] row rides each page's
        # DMA ring — ~3% of the page's int8 bytes.
        ks_rows = jnp.tile(k_scale.astype(jnp.float32),
                           (1, 1, ps)).reshape(Lg, P, 1, ps * Hkv)
        vs_rows = jnp.tile(v_scale.astype(jnp.float32),
                           (1, 1, ps)).reshape(Lg, P, 1, ps * Hkv)
        operands += [ks_rows, vs_rows]
        cache_specs += [
            pl.BlockSpec(memory_space=pltpu.ANY),
            pl.BlockSpec(memory_space=pltpu.ANY),
        ]
        scratch += [
            pltpu.VMEM((N_BUF, 1, ps * Hkv), jnp.float32),
            pltpu.VMEM((N_BUF, 1, ps * Hkv), jnp.float32),
            pltpu.SemaphoreType.DMA((N_BUF, 2)),
        ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, H, D), lambda b, *_: (b, 0, 0))]
        + cache_specs,
        out_specs=pl.BlockSpec((1, H, D), lambda b, *_: (b, 0, 0)),
        scratch_shapes=scratch,
    )

    kernel = functools.partial(_decode_kernel, page_size=ps, num_kv=Hkv,
                               softcap=softcap, quantized=quantized)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page_tables, lengths, jnp.reshape(window, (1,)),
      jnp.reshape(layer, (1,)).astype(jnp.int32),
      *operands)
    return out
