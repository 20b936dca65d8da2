"""The state-space mixer's recurrence (Mamba-2's selective state update).

Per head ``i`` of ``H`` (its group ``g = i // (H // G)``), with state
``h`` of shape ``[P, N]`` (head dim x state dim)::

    a   = exp(A_i * dt)                 A_i = -exp(A_log_i) < 0, dt > 0
    h  <- a * h + dt * x_i (outer) B_g
    y_i = h @ C_g                       (the caller adds D_i * x_i)

Two forms of it serve, and tests/test_ssm_ops.py holds both to the
definition, token by token:

- ``ssm_chunked_scan``: prefill.  The sequence in chunks of
  ``chunk`` tokens: inside a chunk the quadratic (attention-like) form,
  between chunks the state carried by a scan, from an initial state to
  a final one.  XLA einsums in float32; a padded position has ``dt`` 0,
  which leaves the state as it was.
- ``ssm_state_update``: decode, one token for every slot.  A Pallas
  TPU kernel over the per-slot state pool ``[L, S, H, P, N]``: the
  pool is aliased in and out, only rows that decode are read or
  written (2 MiB of bfloat16 state each way a row a layer at
  falcon-h1's sizes: the step's largest stream), and decay, update and
  read-out are fused, so HBM traffic is one read and one write of the
  live rows.
  ``ssm_state_update_jax`` is the same contract in ``jax.numpy``: what
  a CPU serves and what the kernel is tested against in interpret
  mode.

The pool is held in the type the model is served in (bfloat16 on the
chip, docs/kv-cache.md): every form computes in float32 and rounds once,
where a state is written to the pool.

The depthwise causal convolution in front of the recurrence
(``causal_conv``, ``conv_tail``, ``conv_step``) is ``jax.numpy`` in
both phases: 30 KB a row against the state's 2 MiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST


# ----------------------------------------------------------------------
# the convolution in front of the recurrence
# ----------------------------------------------------------------------

def causal_conv(x: jax.Array, tail: jax.Array, w: jax.Array,
                b: jax.Array = None) -> jax.Array:
    """Depthwise causal convolution.  x: [B, T, C]; tail: [B, K-1, C],
    the K-1 inputs before x[:, 0] (zeros at a sequence's start);
    w: [K, C], w[K-1] on the current input; b: [C] or None (no bias).
    Returns [B, T, C]."""
    K = w.shape[0]
    T = x.shape[1]
    full = jnp.concatenate([tail, x], axis=1)
    out = 0.0 if b is None else b[None, None, :]
    for k in range(K):
        out = out + w[k][None, None, :] * full[:, k:k + T]
    return out


def conv_tail(x: jax.Array, tail: jax.Array, true_lens: jax.Array
              ) -> jax.Array:
    """The K-1 inputs that precede position ``true_len`` of each row:
    what the next chunk, or the first decode step, convolves over."""
    K1 = tail.shape[1]
    full = jnp.concatenate([tail, x], axis=1)           # [B, K-1+T, C]
    idx = true_lens[:, None] + jnp.arange(K1, dtype=jnp.int32)[None, :]
    return jnp.take_along_axis(full, idx[:, :, None], axis=1)


def conv_step(x: jax.Array, tail: jax.Array, w: jax.Array,
              b: jax.Array = None):
    """One token.  x: [S, C]; tail: [S, K-1, C]; b: [C] or None.
    Returns the convolution's output [S, C] and the tail after this
    token."""
    window = jnp.concatenate([tail, x[:, None, :]], axis=1)   # [S, K, C]
    out = jnp.sum(w[None] * window, axis=1)
    if b is not None:
        out = b[None, :] + out
    return out, window[:, 1:]


# ----------------------------------------------------------------------
# the recurrence: chunked scan, one decode step
# ----------------------------------------------------------------------

def ssm_chunked_scan(x, dt, A, B, C, h0, chunk: int):
    """Prefill.  x: [b, T, H, P]; dt: [b, T, H]; A: [H]; B, C:
    [b, T, G, N]; h0: [b, H, P, N]; everything float32; ``dt`` is 0 at
    padded positions.  Returns (y [b, T, H, P], final state)."""
    b, T, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    Hg = H // G
    Q = min(chunk, T)
    pad = -T % Q
    if pad:
        x, dt, B, C = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, B, C))
    nc = (T + pad) // Q
    x = x.reshape(b, nc, Q, G, Hg, P)
    dt = dt.reshape(b, nc, Q, G, Hg)
    B = B.reshape(b, nc, Q, G, N)
    C = C.reshape(b, nc, Q, G, N)
    cs = jnp.cumsum(dt * A.reshape(1, 1, 1, G, Hg), axis=2)  # [b,nc,Q,G,Hg]

    # inside a chunk: y_i += sum_{j<=i} exp(cs_i - cs_j) dt_j (C_i.B_j) x_j
    qi = jnp.arange(Q)
    causal = (qi[:, None] >= qi[None, :])[None, None, :, :, None, None]
    seg = cs[:, :, :, None] - cs[:, :, None, :]              # [b,nc,i,j,G,Hg]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("bcign,bcjgn->bcijg", C, B, precision=_HI)
    m = cb[..., None] * decay * dt[:, :, None]               # [b,nc,i,j,G,Hg]
    y = jnp.einsum("bcijgh,bcjghp->bcighp", m, x, precision=_HI)

    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(cs[:, :, -1:] - cs) * dt                # [b,nc,Q,G,Hg]
    s = jnp.einsum("bcqghp,bcqgn->bcghpn", x * to_end[..., None], B,
                   precision=_HI)
    chunk_decay = jnp.exp(cs[:, :, -1])                      # [b,nc,G,Hg]

    def carry(h, inp):
        s_c, d_c = inp
        return h * d_c[..., None, None] + s_c, h             # ys: state BEFORE

    h_last, h_in = jax.lax.scan(
        carry, h0.reshape(b, G, Hg, P, N),
        (jnp.moveaxis(s, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    h_in = jnp.moveaxis(h_in, 0, 1)                          # [b,nc,G,Hg,P,N]
    y = y + jnp.einsum("bcqgn,bcghpn->bcqghp", C, h_in,
                       precision=_HI) * jnp.exp(cs)[..., None]
    y = y.reshape(b, nc * Q, H, P)[:, :T]
    return y, h_last.reshape(b, H, P, N)


def ssm_state_update_jax(pool, layer, x, dt, A, B, C, active):
    """One decode step in ``jax.numpy``.  pool: [L, S, H, P, N];
    layer: scalar index; x: [S, H, P]; dt: [S, H]; A: [H];
    B, C: [S, G, N]; active: [S] bool or None.  Returns (pool, y
    [S, H, P] float32); a row that is not active keeps its state, bit
    for bit, and reads y = 0."""
    H, G = x.shape[1], B.shape[1]
    kept = pool[layer]
    h = kept.astype(jnp.float32)
    Bh = jnp.repeat(B, H // G, axis=1)
    Ch = jnp.repeat(C, H // G, axis=1)
    a = jnp.exp(dt * A[None, :])
    new = h * a[..., None, None] \
        + (dt[..., None] * x)[..., None] * Bh[:, :, None, :]
    y = jnp.einsum("shpn,shn->shp", new, Ch, precision=_HI)
    new = new.astype(pool.dtype)
    if active is not None:
        new = jnp.where(active[:, None, None, None], new, kept)
        y = jnp.where(active[:, None, None], y, 0.0)
    return pool.at[layer].set(new), y


# ----------------------------------------------------------------------
# the decode kernel
# ----------------------------------------------------------------------

def live_rows(active: jax.Array):
    """(rows [S] int32, n_live [1] int32) for :func:`ssm_state_update`:
    the rows that decode, in order, then the last of them repeated.  A
    grid step past ``n_live`` then names the block the step before it
    held, so nothing is copied for it."""
    S = active.shape[0]
    order = jnp.argsort(~active, stable=True).astype(jnp.int32)
    n = jnp.sum(active).astype(jnp.int32)
    last = order[jnp.maximum(n - 1, 0)]
    rows = jnp.where(jnp.arange(S) < n, order, last)
    return rows, n.reshape(1)


def _update_kernel(layer_ref, rows_ref, nlive_ref,      # scalar prefetch
                   s_ref,      # [hb, P, N] the row's state, heads of this block
                   dtx_ref,    # [P, hb]    dt * x, head dim on sublanes
                   a_ref,      # [hb, N]    exp(A dt), one value a head
                   b_ref,      # [G, N]
                   c_ref,      # [G, N]
                   o_ref,      # [hb, P, N] aliased with the pool
                   y_ref,      # [P, hb]
                   *, hb: int, heads_per_group: int):
    j = pl.program_id(0)
    hblk = pl.program_id(1)
    live = j < nlive_ref[0]

    @pl.when(live)
    def _():
        P = dtx_ref.shape[0]
        lane = jax.lax.broadcasted_iota(jnp.int32, (P, hb), 1)
        y = jnp.zeros((P, hb), jnp.float32)
        for k in range(hb):
            g = (hblk * hb + k) // heads_per_group
            new = s_ref[k].astype(jnp.float32) * a_ref[k:k + 1, :] \
                + dtx_ref[:, k:k + 1] * b_ref[pl.ds(g, 1), :]
            o_ref[k] = new.astype(o_ref.dtype)
            ycol = jnp.sum(new * c_ref[pl.ds(g, 1), :], axis=-1,
                           keepdims=True)                    # [P, 1]
            y = jnp.where(lane == k, ycol, y)
        y_ref[...] = y

    # no row decodes: every step names block (row 0, last head block);
    # hand it back as it came, or the write-back would be whatever the
    # output buffer held
    @pl.when((nlive_ref[0] == 0) & (j == 0) & (hblk == 0))
    def _():
        o_ref[...] = s_ref[...]


# heads a grid step holds: 16 x [128, 256] of bfloat16 is 1 MiB in and
# 1 MiB out, double-buffered
HEADS_PER_BLOCK = 16


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_state_update(pool, layer, rows, n_live, x, dt, A, B, C, *,
                     interpret: bool = False):
    """One decode step over the state pool, in place.

    pool: [L, S, H, P, N], aliased to the output (in place
    where the caller's program donates it, as the step programs do);
    layer: int32 scalar; rows, n_live: :func:`live_rows` of the step's
    ``active``; x: [S, H, P]; dt: [S, H]; A: [H]; B, C: [S, G, N]
    (float32).  Returns (pool, y [S, H, P] float32); y of a row that
    does not decode is not written: the caller masks it."""
    L, S, H, P, N = pool.shape
    G = B.shape[1]
    hb = min(HEADS_PER_BLOCK, H)
    if H % hb:
        raise ValueError(f"{H} heads are not whole blocks of {hb}")
    nhb = H // hb
    f32 = jnp.float32
    # head dim onto sublanes, the block's heads onto lanes: the kernel
    # needs dt*x as a column to broadcast along the state dim
    dtx = (dt[..., None] * x).astype(f32).reshape(S, nhb, hb, P)
    dtx = jnp.swapaxes(dtx, 2, 3)                            # [S, nhb, P, hb]
    a = jnp.broadcast_to(jnp.exp(dt * A[None, :]).astype(f32)[..., None],
                         (S, H, N))
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def hsel(j, h, nl):
        return jnp.where(j < nl[0], h, nhb - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, nhb),
        in_specs=[
            pl.BlockSpec((None, None, hb, P, N),
                         lambda j, h, li, r, nl: (li[0], r[j], hsel(j, h, nl),
                                                  0, 0)),
            pl.BlockSpec((None, None, P, hb),
                         lambda j, h, li, r, nl: (r[j], hsel(j, h, nl), 0, 0)),
            pl.BlockSpec((None, hb, N),
                         lambda j, h, li, r, nl: (r[j], hsel(j, h, nl), 0)),
            pl.BlockSpec((None, G, N), lambda j, h, li, r, nl: (r[j], 0, 0)),
            pl.BlockSpec((None, G, N), lambda j, h, li, r, nl: (r[j], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, hb, P, N),
                         lambda j, h, li, r, nl: (li[0], r[j], hsel(j, h, nl),
                                                  0, 0)),
            pl.BlockSpec((None, None, P, hb),
                         lambda j, h, li, r, nl: (r[j], hsel(j, h, nl), 0, 0)),
        ],
    )
    pool, y = pl.pallas_call(
        functools.partial(_update_kernel, hb=hb, heads_per_group=H // G),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((S, nhb, P, hb), f32)],
        # operand 3 = pool, after the three scalar-prefetch operands
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="ssm_state_update",
    )(layer, rows, n_live, pool, dtx, a, B.astype(f32), C.astype(f32))
    return pool, jnp.swapaxes(y, 2, 3).reshape(S, H, P)
