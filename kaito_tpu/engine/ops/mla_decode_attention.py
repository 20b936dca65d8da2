"""Pallas TPU kernel: paged decode attention over a latent cache.

Latent attention (the deepseek-v2/v3 family's) caches one stream a
layer: a token's normed latent ``c_kv`` and the one rotated key part
all heads share, ``[c_kv | k_r]`` (512 | 64).  In the absorbed form a
query head's ``q_nope`` is carried into latent space (``q_lat = q_nope
W_uk``, the caller's ``mla_absorb``) and every head attends the same
stream: scores ``[q_lat | q_r] . [c_kv | k_r]``, and the values are the
keys' first ``kv_lora_rank`` lanes, so the output is a latent again
(``W_uv`` expands it, the caller's ``mla_expand``).  All query heads
against one KV stream, each live page read once, as keys and as values.

The pool is token-flat as the kernel reads a page, ``[layers, pages,
page_size, lanes]``: a 576-wide row lies in 640 lanes of HBM under the
TPU's (8, 128) tiling whatever its logical shape, so the pool says what
it holds (``metadata.stored_key_dim``); the last lanes are zero and the
query is padded alike, so no score changes.

One call is one pass over the batch's live pages, in the walk of
``decode_attention.py``: the grid goes over the rows in order and a ring
of VMEM slots, carried from one grid step to the next, runs ahead of the
page being computed, so a row starts by waiting for copies issued a row
ago.  A page of a latent is small (80 KB at 64 tokens, a tenth of a
microsecond of HBM), so a slot of the ring is a GANG of pages copied
side by side and computed as one ``[gang * page_size, lanes]`` panel:
one score product, one online-softmax update and one value product a
gang, not a page.  A gang's pages past the row's last hold what an
earlier gang left (zeros at first: nothing uninitialised is ever
multiplied) and are masked by position.  A row of length 0, a slot that
decodes nothing, copies nothing and writes zeros.  Pages beyond a row's
length are neither copied nor computed; the cache is never cast.

What a turn overlaps, and what the two sizes are chosen from.  A turn of
the loop is serial in itself (wait for the gang's copies, scores, mask
and softmax, values, refill the slot), so what it overlaps is the copy
engine against all of that: while one gang computes, the other
``N_BUF - 1`` slots' copies are in flight.  Both sizes were read off the
chip (a v5e; PERF.md section 6, PR 43), on 24 rows of 2,816 tokens at
640 stored lanes:

- ``GANG_TOKENS``: a turn's fixed cost (the fill and drain of two
  dependent products, two cross-lane reductions between them, the
  loop's branch) is a quarter of a microsecond, and a panel has to
  stream for several times that to hide it: 512 tokens are 0.8 us of
  HBM.  At 256 a turn took 0.61 us where its bytes need 0.40;
  at 384 to 1,024 a call runs at nine tenths of the HBM stream and a
  larger panel only computes more masked lanes in a row's last gang.
  The head count does not move the choice: at 16 and 32 heads the call
  is bound by its copies from 384 tokens on, at 128 heads (where the
  products, not the copies, bound it) 512 beat 256 by a sixth and 128
  lost a half.  ``pages_per_gang`` turns the tokens into pages of the
  pool's page size, and a table narrower than a gang is one gang.
- ``N_BUF``: with two slots ONE gang's copy is in flight while a gang
  computes and the copy engine idles between a slot's last read and its
  refill; with four, three gangs (2.4 us of stream) are queued behind
  the one computing, which covers a copy's latency.  Eight slots read
  what four do.  Four slots of 512 tokens are 2.6 MB of VMEM.

Two sub-panels a turn as independent chains, one semaphore and one wait
a gang, and a refill issued before the fold into ``acc`` were each
measured at these sizes and bought nothing or lost 3-13%: a call bound
by its copies has no use for a shorter turn.

``attention.mla_paged_decode_attention`` is the same contract in plain
JAX; tests compare the two in interpreter mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# slots of the ring, a power of two, each a gang of pages: one computes
# while the copies of the others are in flight
N_BUF = 4
# tokens a gang holds: its panel is [GANG_TOKENS, lanes], several times
# a turn's fixed cost in HBM time
GANG_TOKENS = 512


def _mla_decode_kernel(
    # scalar prefetch
    page_tables_ref,   # [B, pmax] SMEM
    lengths_ref,       # [B] SMEM
    layer_ref,         # [1] SMEM layer index into the stacked pool
    # inputs
    q_ref,             # [1, H, lanes] VMEM (absorbed, pre-scaled)
    lat_hbm,           # [Lg, P, ps, lanes] ANY/HBM (the whole stack)
    # outputs, scratch
    o_ref,             # [1, H, dl]
    buf,               # [N_BUF, gang * ps, lanes] VMEM
    sems,              # DMA [N_BUF, gang]
    ring,              # SMEM [3]: head slot, (row, gang) cursor
    row_pages,         # SMEM [B]: pages a row reads
    row_next,          # SMEM [B]: the next row that reads any
    *,
    page_size: int,
    gang: int,
    value_lanes: int,
):
    b = pl.program_id(0)
    B = pl.num_programs(0)
    length = lengths_ref[b]
    li = layer_ref[0]
    ps = page_size

    def page_copy(slot, j, page):
        return pltpu.make_async_copy(
            lat_hbm.at[li, page], buf.at[slot, pl.ds(j * ps, ps)],
            sems.at[slot, j])

    def issue(slot, row, g):
        # start the copies of the cursor's gang, if rows are left, and
        # move the cursor on: to the row's next gang, or to gang 0 of
        # the next row that has any (B when none has)
        r = jnp.minimum(row, B - 1)
        n = row_pages[r]
        for j in range(gang):
            @pl.when((row < B) & (g * gang + j < n))
            def _(j=j):
                page_copy(slot, j, page_tables_ref[r, g * gang + j]).start()
        done = (g + 1) * gang >= n
        return jnp.where(done, row_next[r], row), jnp.where(done, 0, g + 1)

    @pl.when(b == 0)
    def _cold_start():
        def fill(i, live):
            r = B - 1 - i
            n = pl.cdiv(lengths_ref[r], ps)
            row_pages[r] = n
            row_next[r] = live
            return jnp.where(n > 0, r, live)
        row = jax.lax.fori_loop(0, B, fill, B)
        # a gang's unfilled pages are multiplied by zero weights: they
        # must hold numbers
        buf[...] = jnp.zeros_like(buf)
        g = jnp.int32(0)
        for slot in range(N_BUF):
            row, g = issue(slot, row, g)
        ring[0] = 0
        ring[1] = row
        ring[2] = g

    head = ring[0]
    n_pages = row_pages[b]
    n_gangs = pl.cdiv(n_pages, gang)
    q2 = q_ref[0]                                   # [H, lanes]
    H = q2.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (H, gang * ps), 1)

    def body(p, carry):
        m, l, acc, row, g = carry
        slot = (head + p) & (N_BUF - 1)
        for j in range(gang):
            @pl.when(p * gang + j < n_pages)
            def _(j=j):
                page_copy(slot, j, 0).wait()
        k2 = buf[slot]                              # [gang*ps, lanes]
        s = jax.lax.dot_general(
            q2, k2, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)     # [H, gang*ps]
        s = jnp.where(col < length - p * (gang * ps), s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p_ij = jnp.exp(s - m_new)
        l_new = l * alpha + jnp.sum(p_ij, axis=1, keepdims=True)
        # the values are the keys' latent lanes
        pv = jax.lax.dot_general(
            p_ij.astype(k2.dtype), k2[:, :value_lanes],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)     # [H, dl]
        row, g = issue(slot, row, g)
        return m_new, l_new, acc * alpha + pv, row, g

    m0 = jnp.full((H, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((H, 1), jnp.float32)
    acc0 = jnp.zeros((H, value_lanes), jnp.float32)
    m, l, acc, row, g = jax.lax.fori_loop(
        0, n_gangs, body, (m0, l0, acc0, ring[1], ring[2]))
    ring[0] = (head + n_gangs) & (N_BUF - 1)
    ring[1] = row
    ring[2] = g
    # a row of length 0 ran no gang: acc 0 over the floor is 0
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def pages_per_gang(page_size: int, pmax: int) -> int:
    return max(1, min(GANG_TOKENS // page_size, pmax))


@functools.partial(jax.jit, static_argnames=("value_lanes", "interpret"))
def mla_paged_decode_attention_pallas(
    q: jax.Array,            # [B, H, lanes]: [q_lat | q_rope | 0], scaled
    pool: jax.Array,         # [Lg, P, ps, lanes]: [c_kv | k_rope | 0]
    page_tables: jax.Array,  # [B, pmax] int32
    lengths: jax.Array,      # [B] int32, the new token included
    layer: jax.Array,        # [] int32 index into the stack
    *,
    value_lanes: int,        # kv_lora_rank: the value is lanes [0, dl)
    interpret: bool = False,
) -> jax.Array:
    """The attended latent of each row and head, [B, H, value_lanes]."""
    B, H, lanes = q.shape
    Lg, P, ps, pool_lanes = pool.shape
    if pool_lanes != lanes:
        raise ValueError(f"query of {lanes} lanes against a pool of "
                         f"{pool_lanes}")
    gang = pages_per_gang(ps, page_tables.shape[1])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, H, lanes), lambda b, *_: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pltpu.ANY)],
        out_specs=pl.BlockSpec((1, H, value_lanes),
                               lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((N_BUF, gang * ps, lanes), pool.dtype),
            pltpu.SemaphoreType.DMA((N_BUF, gang)),
            pltpu.SMEM((3,), jnp.int32),
            pltpu.SMEM((B,), jnp.int32),
            pltpu.SMEM((B,), jnp.int32),
        ],
    )
    kernel = functools.partial(_mla_decode_kernel, page_size=ps, gang=gang,
                               value_lanes=value_lanes)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, value_lanes), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mla_attention",     # the op's name in a trace
    )(page_tables, lengths, jnp.reshape(layer, (1,)).astype(jnp.int32),
      q, pool)
