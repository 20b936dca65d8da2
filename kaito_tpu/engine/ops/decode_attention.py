"""Pallas TPU kernel: paged decode attention.

One call is one pass over the batch's live KV pages.  The grid walks
the rows (sequences) in order; the pages of all rows form one stream,
row after row, and a ring of ``N_BUF`` VMEM slots runs ``N_BUF`` pages
ahead of the page being computed.  A page is K and V for *all* KV heads
(the token-major cache layout makes it one contiguous
``[page_size * Hkv, D]`` panel each), and HBM traffic is one read of the
pages that hold live KV — the decode roofline.

The ring is carried from one grid step to the next (the grid dimension
is sequential, so VMEM/SMEM scratch and DMA semaphores outlive a step):
while a row's last pages compute, the first pages of the next row that
has any are already in flight, and that row starts by waiting for
copies issued a row ago.  Only the first live row of a call starts
cold, and the last issues nothing it does not wait for.  What a row
needs that is not the row's own is made once a call: the pages each row
holds and the next row that holds any (SMEM, first grid step), and the
head-match mask with the page-row index, which depend on shapes alone
and come in as one constant operand whose block never changes
(``_score_columns``).  A row of length 0 — a slot that is not decoding —
copies nothing, takes no slot of the ring and writes zeros.

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
window sizes from the model's scan flags), gemma-2 logit softcap,
values narrower than keys (the output has the values' size) and a sink
bias a query head (``sink``: one more column of the softmax that takes
probability and carries no value).  With a window a row starts at the
page that holds the first position inside it: pages wholly behind the
window are neither copied nor computed, and their table entries are
never read, so a window kind's freed pages may be the null page
(docs/kv-cache.md).
The pure-JAX fallback in kaito_tpu.engine.attention implements the same
contract; tests compare the two in interpreter mode and on-chip.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# score-panel columns of another kv head than the query row's: no page
# position ever compares below a row's length
_NO_COLUMN = np.iinfo(np.int32).max
# slots of the K/V page ring, a power of two: 4 pages of K and V in
# flight hide a copy's latency; 8 and 16 were no faster at 128 KB pages
# nor at 32 KB ones (PERF.md, PR 32)
N_BUF = 4


def _score_columns(num_heads: int, num_kv: int, page_size: int) -> np.ndarray:
    """[H, ps*Hkv] int32: column t*Hkv + h' of a page's score panel is
    page row t of kv head h'; query row h*G + g matches kv head h.  The
    entry is t where the heads match and ``_NO_COLUMN`` where they do
    not, so one compare against the row's length masks both."""
    group = num_heads // num_kv
    col = np.arange(page_size * num_kv)
    row_kv = np.arange(num_heads)[:, None] // group
    return np.where(row_kv == (col % num_kv)[None, :],
                    (col // num_kv)[None, :], _NO_COLUMN).astype(np.int32)


def _decode_kernel(
    # scalar prefetch
    page_tables_ref,   # [B, pmax] SMEM
    lengths_ref,       # [B] SMEM
    window_ref,        # [1] SMEM
    layer_ref,         # [1] SMEM layer index into the stacked cache
    # inputs
    q_ref,             # [1, H, D] VMEM (pre-scaled)
    cols_ref,          # [H, ps*Hkv] VMEM (_score_columns), fetched once
    # with a sink only: [H, 1] fp32 VMEM, fetched once; then
    # k_hbm [Lg, P, ps*Hkv, D] and v_hbm [Lg, P, ps*Hkv, Dv] ANY/HBM
    # (full group stack); quantized mode only: [Lg, P, 1, ps*Hkv] fp32
    # dequant rows; then outputs + scratch (+[N_BUF, 1, ps*Hkv] scale
    # ring / extra sems)
    *rest,
    page_size: int,
    softcap: Optional[float],
    quantized: bool,
    has_sink: bool,
):
    sink_ref = None
    if has_sink:
        sink_ref, *rest = rest
    k_hbm, v_hbm, *rest = rest
    if quantized:
        (ks_hbm, vs_hbm, o_ref, k_buf, v_buf, sems, ring, row_pages,
         row_next, row_first, ks_buf, vs_buf, ssems) = rest
    else:
        (o_ref, k_buf, v_buf, sems, ring, row_pages, row_next,
         row_first) = rest
        ks_hbm = vs_hbm = ks_buf = vs_buf = ssems = None

    b = pl.program_id(0)
    B = pl.num_programs(0)
    length = lengths_ref[b]
    window = window_ref[0]
    li = layer_ref[0]

    def page_copies(slot, page):
        copies = [
            pltpu.make_async_copy(k_hbm.at[li, page], k_buf.at[slot],
                                  sems.at[slot, 0]),
            pltpu.make_async_copy(v_hbm.at[li, page], v_buf.at[slot],
                                  sems.at[slot, 1])]
        if quantized:
            copies += [
                pltpu.make_async_copy(ks_hbm.at[li, page], ks_buf.at[slot],
                                      ssems.at[slot, 0]),
                pltpu.make_async_copy(vs_hbm.at[li, page], vs_buf.at[slot],
                                      ssems.at[slot, 1])]
        return copies

    def issue(slot, row, page):
        # start the copies of the cursor's page, if rows are left, and
        # move the cursor on: to the row's next page, or to page 0 of
        # the next row that has any (B when none has)
        r = jnp.minimum(row, B - 1)

        @pl.when(row < B)
        def _():
            for c in page_copies(slot,
                                 page_tables_ref[row, row_first[r] + page]):
                c.start()
        done = page + 1 >= row_pages[r]
        return (jnp.where(done, row_next[r], row),
                jnp.where(done, 0, page + 1))

    @pl.when(b == 0)
    def _cold_start():
        # the pages a row reads (from the one that holds the first
        # position inside the window) and the next row that reads any,
        # once a call: the page loop then never divides or searches
        def fill(i, live):
            r = B - 1 - i
            first = jnp.maximum(lengths_ref[r] - window, 0) // page_size
            n = pl.cdiv(lengths_ref[r], page_size) - first
            row_first[r] = first
            row_pages[r] = n
            row_next[r] = live
            return jnp.where(n > 0, r, live)
        row = jax.lax.fori_loop(0, B, fill, B)
        page = jnp.int32(0)
        for slot in range(N_BUF):
            row, page = issue(slot, row, page)
        ring[0] = 0
        ring[1] = row
        ring[2] = page

    # the ring as the rows before left it: the slot of this row's first
    # page, whose copy (and the next N_BUF - 1) is already in flight,
    # and the cursor of the next page to ask for
    head = ring[0]
    n_pages = row_pages[b]
    first = row_first[b]
    q2 = q_ref[0]                                  # [H, D]
    H, D = q2.shape
    Dv = v_buf.shape[-1]

    def body(p, carry):
        m, l, acc, row, page = carry
        slot = (head + p) & (N_BUF - 1)

        for c in page_copies(slot, 0):
            c.wait()
        k2 = k_buf[slot]                           # [ps*Hkv, D]
        v2 = v_buf[slot]
        if quantized:
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
        # a column's position is (first + p)*ps + t: compare t against
        # the row's bounds moved by the page's start (two scalar
        # subtractions)
        t = cols_ref[...]
        end = length - (first + p) * page_size
        valid = (t < end) & (t >= end - window)
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

        # refill the slot just consumed with the page N_BUF ahead, which
        # may be a later row's
        row, page = issue(slot, row, page)
        return m_new, l_new, acc * alpha + pv, row, page

    m0 = jnp.full((H, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((H, 1), jnp.float32)
    acc0 = jnp.zeros((H, Dv), jnp.float32)
    m, l, acc, row, page = jax.lax.fori_loop(
        0, n_pages, body, (m0, l0, acc0, ring[1], ring[2]))
    ring[0] = (head + n_pages) & (N_BUF - 1)
    ring[1] = row
    ring[2] = page
    if has_sink:
        # the sink's column: probability and no value
        sink = sink_ref[...]                       # [H, 1]
        m_new = jnp.maximum(m, sink)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.exp(sink - m_new)
        acc = acc * alpha
    # a row of length 0 ran no page: acc 0 over the floor is 0
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
    static_argnames=("scale", "softcap", "interpret", "kv_heads"))
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
    sink: Optional[jax.Array] = None,      # [H] fp32 sink bias a head
    kv_heads: Optional[int] = None,        # the pools are token-flat,
                                           # [(Lg,) P, ps*kv_heads, D]
) -> jax.Array:
    B, H, D = q.shape
    quantized = k_scale is not None
    has_sink = sink is not None
    if layer is None:
        cache_k = cache_k[None]
        cache_v = cache_v[None]
        if quantized:
            k_scale = k_scale[None]
            v_scale = v_scale[None]
        layer = jnp.zeros((), jnp.int32)
    Dv = cache_v.shape[-1]
    if kv_heads is not None:
        # stored as the kernel reads them: no view to take
        Lg, P, rows, _ = cache_k.shape
        ps, Hkv = rows // kv_heads, kv_heads
        ck_flat, cv_flat = cache_k, cache_v
    else:
        Lg, P, ps, Hkv, _ = cache_k.shape
        # token-flat page view [Lg, P, ps*Hkv, D]: free reshape, and the
        # page DMA plus both kernel dots run on it without any relayout
        ck_flat = cache_k.reshape(Lg, P, ps * Hkv, D)
        cv_flat = cache_v.reshape(Lg, P, ps * Hkv, Dv)
    q_scaled = q * scale

    # the head-match mask and page-row index depend on shapes alone: a
    # constant of the program, one block for every grid step
    operands = [q_scaled, _score_columns(H, Hkv, ps)]
    const_specs = [pl.BlockSpec((H, ps * Hkv), lambda b, *_: (0, 0))]
    if has_sink:
        operands.append(sink.astype(jnp.float32).reshape(H, 1))
        const_specs.append(pl.BlockSpec((H, 1), lambda b, *_: (0, 0)))
    operands += [ck_flat, cv_flat]
    cache_specs = [
        pl.BlockSpec(memory_space=pltpu.ANY),
        pl.BlockSpec(memory_space=pltpu.ANY),
    ]
    scratch = [
        pltpu.VMEM((N_BUF, ps * Hkv, D), cache_k.dtype),
        pltpu.VMEM((N_BUF, ps * Hkv, Dv), cache_v.dtype),
        pltpu.SemaphoreType.DMA((N_BUF, 2)),
        # carried from one grid step (row) to the next: the ring's head
        # slot and the (row, page) cursor of the next page to copy; the
        # pages each row reads; the next row that reads any; the first
        # page each row reads
        pltpu.SMEM((3,), jnp.int32),
        pltpu.SMEM((B,), jnp.int32),
        pltpu.SMEM((B,), jnp.int32),
        pltpu.SMEM((B,), jnp.int32),
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
        + const_specs + cache_specs,
        out_specs=pl.BlockSpec((1, H, Dv), lambda b, *_: (b, 0, 0)),
        scratch_shapes=scratch,
    )

    kernel = functools.partial(_decode_kernel, page_size=ps,
                               softcap=softcap, quantized=quantized,
                               has_sink=has_sink)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page_tables, lengths, jnp.reshape(window, (1,)),
      jnp.reshape(layer, (1,)).astype(jnp.int32),
      *operands)
    return out
