"""Pallas TPU kernel: an expert layer's rows back to their tokens.

A chip that holds a share of an expert layer computes, a pass, ``cap``
sorted (token, expert) pairs of the ``T * k`` the router chose
(``nn.moe_mlp_ragged``): one pair in sixteen at MiMo-V2.5's widths.
This kernel adds each computed row of ``out`` [rows, E] float32 to its
token's row of ``y`` [T, E] float32 and moves nothing else: a token
with no pair here, and every padding row, reads nothing.

The pass's live pairs come as one stream in token order, a token's
pairs in the order of its ``k`` slots (``tok`` and ``pos``, scalar
prefetched, with ``tile_first[i]``: where the stream reaches token
tile ``i``).  The grid walks the token tiles; ``out`` stays where XLA put it
and a ring of ``N_BUF`` row copies runs ahead of the row being added,
across tile boundaries (the grid is sequential, so the copies in flight
outlive a step: the decode-attention kernel's page ring).  A tile's
block of ``y`` is the accumulator: float32, each token's rows added
one after the other in stream order, which is the order the loop over
all ``k`` slots adds them in (``nn._combine_slots``), so the two agree
bit for bit.  ``fresh`` says ``y`` is all zeros (a layer's first pass):
its blocks are then not read (the block index stays where it is).

On a v5e at [4,096, 4,096], 1,282 live pairs, the call with its sort
takes 0.48 ms where the eight gathers with the scatter that builds
their places take 1.96, and a compact form in plain XLA (a gather of
[T, E] for each pair the fullest token holds) 2.07, no better than the
gathers it would replace: there is no such form (PERF.md, PR 41).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# row copies in flight, a power of two: 128 KB each at E 4,096; 8, 16
# and 32 were no faster (0.45-0.48 ms a call against 0.44)
N_BUF = 4
# tokens a grid step: a [128, 4096] float32 block is 2 MiB, four of
# them (in and out, double-buffered) half the scoped VMEM; 64 was no
# faster and 256 does not fit
TILE = 128


def token_tile(tokens: int) -> int:
    """Tokens a grid step; the kernel takes whole tiles only."""
    return min(TILE, tokens)


def _combine_kernel(tile_first, tok, pos, fresh,      # SMEM
                    y_ref,      # [tile, E] VMEM
                    out_hbm,    # [rows, E] ANY: where XLA put it
                    o_ref,      # [tile, E] VMEM
                    buf, sems, *, tile: int):
    i = pl.program_id(0)
    n_live = tile_first[pl.num_programs(0)]

    def row_copy(n):
        # float32 is tiled in (8, 128): a row comes with the eight of
        # its tile row, one contiguous read
        slot = n & (N_BUF - 1)
        first = pl.multiple_of(pos[n] & -8, 8)
        return pltpu.make_async_copy(out_hbm.at[pl.ds(first, 8)],
                                     buf.at[slot], sems.at[slot])

    @pl.when(i == 0)
    def _cold_start():
        for n in range(N_BUF):
            @pl.when(n < n_live)
            def _():
                row_copy(n).start()

    o_ref[...] = jnp.where(fresh[0] == 1, 0.0, y_ref[...])

    def add_row(n, carry):
        row_copy(n).wait()
        at = pl.ds(tok[n] - i * tile, 1)
        o_ref[at, :] = o_ref[at, :] + buf[n & (N_BUF - 1),
                                          pl.ds(pos[n] & 7, 1), :]

        @pl.when(n + N_BUF < n_live)
        def _():
            row_copy(n + N_BUF).start()
        return carry

    jax.lax.fori_loop(tile_first[i], tile_first[i + 1], add_row, 0)


@jax.jit
def moe_combine_pallas(y: jax.Array,           # [T, E] float32
                       out: jax.Array,         # [rows, E] float32
                       tok: jax.Array,         # [rows] int32, sorted
                       pos: jax.Array,         # [rows] int32
                       fresh: jax.Array,       # [] bool: y is zeros
                       ) -> jax.Array:
    """``y`` with row ``pos[n]`` of ``out`` added to row ``tok[n]`` for
    every ``n`` whose ``tok[n]`` is a token (below T), in the order of
    ``n``.  ``y`` is updated in place (the call aliases it to its
    result)."""
    T, E = y.shape
    tile = token_tile(T)
    assert T % tile == 0, (T, tile)
    # how many of the stream's entries lie before each tile of tokens
    tile_first = jnp.searchsorted(tok, jnp.arange(0, T + 1, tile),
                                  method="compare_all").astype(jnp.int32)

    def y_block(i, tile_first, tok, pos, fresh):
        # zeros need no reading: the block stays put and is fetched once
        return (jnp.where(fresh[0] == 1, 0, i), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(T // tile,),
        in_specs=[pl.BlockSpec((tile, E), y_block),
                  pl.BlockSpec(memory_space=pltpu.ANY)],
        out_specs=pl.BlockSpec((tile, E), lambda i, *_: (i, 0)),
        scratch_shapes=[pltpu.VMEM((N_BUF, 8, E), out.dtype),
                        pltpu.SemaphoreType.DMA((N_BUF,))],
    )
    return pl.pallas_call(
        functools.partial(_combine_kernel, tile=tile),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
        # operand 4 (behind the four prefetched scalars) is y
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="moe_combine",     # the op's name in a trace
    )(tile_first, tok, pos, jnp.reshape(fresh, (1,)).astype(jnp.int32),
      y, out)
