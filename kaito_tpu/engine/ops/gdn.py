"""The gated delta rule (Gated DeltaNet's recurrence; olmo_hybrid's
``linear_attention`` layers).

Per head, with a state ``S`` of shape ``[dk, dv]`` (keys x values),
zero at a sequence's start, a log decay ``g_t <= 0`` and a step
``beta_t`` in (0, 2)::

    S'  = exp(g_t) * S_{t-1}
    u_t = beta_t * (v_t - S'^T k_t)        the delta: what the state does
                                           not yet say of v_t along k_t
    S_t = S' + k_t u_t^T
    o_t = S_t^T q_t

(``q`` and ``k`` come in L2-normalised, ``q`` scaled by ``dk ** -0.5``.)
Unlike a state-space mixer's, the update reads the state back: a token
costs a read AND a write of the head's whole matrix.

Two forms of it serve, and tests/test_gdn_ops.py holds both to the
definition (``gdn_recurrence``), token by token, at beta up to 2:

- ``gdn_chunked_scan``: prefill.  Chunks of ``chunk`` tokens; with
  ``gamma_i`` the sum of ``g`` up to ``i`` inside a chunk and ``S_0``
  the state at its start::

      A_ij = beta_i (k_i.k_j) e^{gamma_i - gamma_j}    i > j, else 0
      T    = (I + A)^-1                                forward substitution
      W    = T diag(beta) (K * e^gamma)      U = T diag(beta) V
      V'   = U - W S_0
      O    = (Q * e^gamma) S_0 + ((Q K^T) * D) V'      D_ij = e^{gamma_i - gamma_j}, i >= j
      S_C  = e^{gamma_C} S_0 + sum_j e^{gamma_C - gamma_j} k_j v'_j^T
           = M S_0 + N      M = diag(e^{gamma_C}) - K_end^T W    N = K_end^T U
                            K_end = K * e^{gamma_C - gamma}

  Only two things in this are serial, and only they sit in a loop.
  ``T`` is unit lower triangular and is had in blocks of ``GDN_SUB``
  rows: a diagonal block's inverse a row at a time (row ``i`` is
  ``e_i - A[i, :i] T[:i]``; ``GDN_SUB - 1`` steps, for every block of
  every chunk and head at once), then the blocks under the diagonal a
  block row at a time, ``T[r, :r] = -T_rr (A[r, :r] T[:r, :r])``
  (``chunk / GDN_SUB - 1`` pairs of products).  And a chunk's state
  follows from the chunk before it: ``M`` and ``N`` are computed for
  every chunk at once, the loop over the chunks carries
  ``S <- M_c S + N_c``, one product a step, and hands out ``S`` at each
  chunk's start; ``V'`` and ``O`` then follow for every chunk at once.
  At the default chunk that is 31 + 3 steps, then one product a chunk
  (benchmarks/gdn_scan.py times the forms against each other).

  XLA einsums in float32 under the scope ``gdn_scan``, from an initial
  state to a final one; a padded position has ``g`` 0 and ``beta`` 0
  and leaves the state as it was.
- ``gdn_state_update``: decode, one token for every slot.  A Pallas TPU
  kernel over the per-slot state pool ``[L, S, dk, H * dv]``: keys on
  sublanes, the heads' values side by side on lanes (a 192-wide value
  is no lane tile; 30 of them are 45 whole tiles, and two are three, so
  the kernel takes the heads in pairs and nothing is padded).  The pool
  is aliased in and out, only rows that decode are read or written, and
  decay, correction, update and read-out are fused, so HBM sees one
  read and one write of a live row's state.
  ``gdn_state_update_jax`` is the same contract in ``jax.numpy``: what
  a CPU serves and what the kernel is tested against in interpret mode.

Every form computes in float32 and rounds once, where a state is
written to the pool.  The depthwise causal convolutions in front of
the recurrence are ``ops/ssm.py``'s (``causal_conv``, ``conv_tail``,
``conv_step``), with no bias.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST

# tokens a chunk of the prefill scan holds: its products put a chunk's
# tokens on the rows of the matrix unit, which 128 fill
GDN_CHUNK = 128
# rows a diagonal block of the chunk's triangular inverse holds: at 16
# the scan alone is 4% faster and a prefill program costs a start 0.5 s
# more to meet (seven block rows of operations to trace and load, not
# three; PERF.md section 6, PR 56)
GDN_SUB = 32


def gdn_recurrence(q, k, v, g, beta, s0):
    """The definition, token by token (what the other forms are held
    to).  q, k: [b, T, H, dk]; v: [b, T, H, dv]; g, beta: [b, T, H];
    s0: [b, H, dk, dv]; float32.  Returns (o [b, T, H, dv], final
    state)."""
    def step(S, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        S = S * jnp.exp(g_t)[..., None, None]
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t,
                                               precision=_HI))
        S = S + k_t[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t, precision=_HI)

    s_last, o = jax.lax.scan(
        step, s0, tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s_last


def gdn_chunked_scan(q, k, v, g, beta, s0, chunk: int = GDN_CHUNK):
    """Prefill.  Shapes as ``gdn_recurrence``; ``g`` and ``beta`` are 0
    at padded positions.  Returns (o [b, T, H, dv], final state)."""
    with jax.named_scope("gdn_scan"):
        return _chunked_scan(q, k, v, g, beta, s0, chunk)


def _lane_product(X, Y):
    """[i, j, n] x [j, k, n] -> [i, k, n]: a matrix product a lane."""
    return jnp.sum(X[:, :, None, :] * Y[None, :, :, :], axis=1)


def _unit_lower_inverse(A):
    """``(I + A)^-1`` for ``A`` [..., C, C] strictly lower triangular,
    in blocks of ``GDN_SUB`` rows (a ``C`` that is no whole number of
    them is one block of its own size: a sequence shorter than a chunk,
    which no prefill program is).

    Laid out [row, column, batch] with every matrix of the batch on a
    lane, so that a step is arithmetic on whole registers where a
    product of 1 x 32 by 32 x 32 a matrix would leave the matrix unit
    idle.  The diagonal blocks by forward substitution, all at once:
    row ``i`` of a block's inverse is ``e_i - A[i, :i] T[:i]`` (rows
    ``i`` and on of ``T`` still hold the identity, and ``A[i, j]`` is 0
    there).  Then the blocks under them, a block row at a time:
    ``T[r, :r] = -T_rr (A[r, :r] T[:r, :r])``."""
    *lead, C, _ = A.shape
    sub = GDN_SUB if C % GDN_SUB == 0 else C
    nb = C // sub
    At = jnp.moveaxis(A.reshape(-1, C, C), 0, -1)            # [C, C, n]
    n = At.shape[-1]
    Ad = jnp.concatenate([At[r * sub:(r + 1) * sub, r * sub:(r + 1) * sub]
                          for r in range(nb)], axis=-1)      # [sub,sub,nb*n]
    eye = jnp.broadcast_to(jnp.eye(sub, dtype=A.dtype)[:, :, None], Ad.shape)

    def row(i, Td):
        a_i = jax.lax.dynamic_index_in_dim(Ad, i, axis=0, keepdims=False)
        e_i = jax.lax.dynamic_index_in_dim(eye, i, axis=0, keepdims=False)
        new = e_i - jnp.sum(a_i[:, None, :] * Td, axis=0)
        return jax.lax.dynamic_update_index_in_dim(Td, new, i, axis=0)

    Td = jax.lax.fori_loop(1, sub, row, eye)
    Td = [Td[..., r * n:(r + 1) * n] for r in range(nb)]
    Tm = Td[0]
    for r in range(1, nb):
        below = -_lane_product(
            Td[r], _lane_product(At[r * sub:(r + 1) * sub, :r * sub], Tm))
        Tm = jnp.concatenate([
            jnp.pad(Tm, [(0, 0), (0, sub), (0, 0)]),
            jnp.concatenate([below, Td[r]], axis=1)], axis=0)
    return jnp.moveaxis(Tm, -1, 0).reshape(*lead, C, C)


def _chunked_scan(q, k, v, g, beta, s0, chunk):
    b, T, H, dk = q.shape
    dv = v.shape[-1]
    C = min(chunk, T)
    pad = -T % C
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    nc = (T + pad) // C
    # [b, nc, H, C, ...]: a chunk's tokens on the row axis of its products
    q, k, v = (jnp.moveaxis(x.reshape(b, nc, C, H, x.shape[-1]), 3, 2)
               for x in (q, k, v))
    g, beta = (jnp.moveaxis(x.reshape(b, nc, C, H), 3, 2) for x in (g, beta))
    gamma = jnp.cumsum(g, axis=-1)                           # [b,nc,H,C]
    ci = jnp.arange(C)
    seg = gamma[..., :, None] - gamma[..., None, :]          # gamma_i - gamma_j
    decay = jnp.exp(jnp.where(ci[:, None] >= ci[None, :], seg, -jnp.inf))
    kk = jnp.einsum("bchid,bchjd->bchij", k, k, precision=_HI)
    A = jnp.where(ci[:, None] > ci[None, :],
                  beta[..., :, None] * kk * decay, 0.0)
    Tm = _unit_lower_inverse(A)
    eg = jnp.exp(gamma)[..., None]
    W = jnp.einsum("bchij,bchjd->bchid", Tm, beta[..., None] * k * eg,
                   precision=_HI)
    U = jnp.einsum("bchij,bchjd->bchid", Tm, beta[..., None] * v,
                   precision=_HI)
    qk = jnp.einsum("bchid,bchjd->bchij", q, k, precision=_HI) * decay
    q_in = q * eg                                            # reads S_0
    to_end = jnp.exp(gamma[..., -1:] - gamma)[..., None]     # [b,nc,H,C,1]
    k_end = k * to_end
    chunk_decay = jnp.exp(gamma[..., -1])                    # [b,nc,H]
    # a chunk takes the state at its start to the one at its end
    # linearly, S_C = M S_0 + N: both for every chunk at once, so that
    # the loop is left one product a chunk
    M = jnp.eye(dk, dtype=jnp.float32) * chunk_decay[..., None, None] \
        - jnp.einsum("bchjk,bchjd->bchkd", k_end, W, precision=_HI)
    N = jnp.einsum("bchjk,bchjv->bchkv", k_end, U, precision=_HI)

    def carry(S, inp):
        M_c, N_c = inp
        return jnp.einsum("bhkj,bhjv->bhkv", M_c, S, precision=_HI) + N_c, S

    s_last, starts = jax.lax.scan(
        carry, s0, (jnp.moveaxis(M, 1, 0), jnp.moveaxis(N, 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1)                      # [b,nc,H,dk,dv]
    v_new = U - jnp.einsum("bchik,bchkv->bchiv", W, starts, precision=_HI)
    o = jnp.einsum("bchik,bchkv->bchiv", q_in, starts, precision=_HI) \
        + jnp.einsum("bchij,bchjv->bchiv", qk, v_new, precision=_HI)
    o = jnp.moveaxis(o, 2, 3)                                # [b,nc,C,H,dv]
    return o.reshape(b, nc * C, H, dv)[:, :T], s_last


# ----------------------------------------------------------------------
# one decode step
# ----------------------------------------------------------------------

def pool_layout(state: jax.Array) -> jax.Array:
    """A state [..., H, dk, dv] as the pool lays it out: [..., dk, H * dv]
    (keys on sublanes, the heads' values side by side on lanes)."""
    *lead, H, dk, dv = state.shape
    return jnp.moveaxis(state, -3, -2).reshape(*lead, dk, H * dv)


def from_pool_layout(rows: jax.Array, heads: int) -> jax.Array:
    """The other way: [..., dk, H * dv] -> [..., H, dk, dv]."""
    *lead, dk, hdv = rows.shape
    return jnp.moveaxis(rows.reshape(*lead, dk, heads, hdv // heads), -2, -3)


def gdn_state_update_jax(pool, layer, q, k, v, g, beta, active):
    """One decode step in ``jax.numpy``.  pool: [L, S, dk, H * dv];
    layer: scalar index; q, k: [S, H, dk]; v: [S, H, dv]; g, beta:
    [S, H]; active: [S] bool or None.  Returns (pool, o [S, H, dv]
    float32); a row that is not active keeps its state, bit for bit,
    and reads o = 0."""
    H = q.shape[1]
    kept = pool[layer]
    S = from_pool_layout(kept.astype(jnp.float32), H) \
        * jnp.exp(g)[..., None, None]
    u = beta[..., None] * (v - jnp.einsum("shkv,shk->shv", S, k,
                                          precision=_HI))
    S = S + k[..., :, None] * u[..., None, :]
    o = jnp.einsum("shkv,shk->shv", S, q, precision=_HI)
    new = pool_layout(S).astype(pool.dtype)
    if active is not None:
        new = jnp.where(active[:, None, None], new, kept)
        o = jnp.where(active[:, None, None], o, 0.0)
    return pool.at[layer].set(new), o


def _update_kernel(layer_ref, rows_ref, nlive_ref,      # scalar prefetch
                   s_ref,      # [dk, H*dv]  the row's state
                   kt_ref,     # [dk, H]     keys, a head a lane
                   qt_ref,     # [dk, H]     queries, a head a lane
                   lane_ref,   # [4, H*dv]   alpha, alpha*beta, beta*v, k.q
                   o_ref,      # [dk, H*dv]  aliased with the pool
                   y_ref,      # [1, H*dv]
                   *, heads: int, dv: int):
    j = pl.program_id(0)

    @pl.when(j < nlive_ref[0])
    def _():
        dk = s_ref.shape[0]
        # a step of the loop holds a pair of heads: two of 192 values
        # are three whole lane tiles
        w = 2 * dv
        first = jax.lax.broadcasted_iota(jnp.int32, (dk, w), 1) < dv
        for p in range(heads // 2):
            at = pl.ds(p * w, w)
            S = s_ref[:, at].astype(jnp.float32)             # [dk, 2*dv]
            # a head's key (query) down its own lanes of the pair
            kb = jnp.where(first, kt_ref[:, 2 * p:2 * p + 1],
                           kt_ref[:, 2 * p + 1:2 * p + 2])
            qb = jnp.where(first, qt_ref[:, 2 * p:2 * p + 1],
                           qt_ref[:, 2 * p + 1:2 * p + 2])
            alpha = lane_ref[0:1, at]
            # S'^T k = alpha S^T k, so both read-outs come off the row
            # as it was read
            pred = jnp.sum(S * kb, axis=0, keepdims=True)    # [1, 2*dv]
            read = jnp.sum(S * qb, axis=0, keepdims=True)
            u = lane_ref[2:3, at] - lane_ref[1:2, at] * pred
            o_ref[:, at] = (alpha * S + kb * u).astype(o_ref.dtype)
            # o = S_t^T q = alpha S^T q + u (k.q)
            y_ref[:, at] = alpha * read + u * lane_ref[3:4, at]

    # no row decodes: every step names row 0's block; hand it back as it
    # came, or the write-back would be whatever the output buffer held
    @pl.when((nlive_ref[0] == 0) & (j == 0))
    def _():
        o_ref[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gdn_state_update(pool, layer, rows, n_live, q, k, v, g, beta, *,
                     interpret: bool = False):
    """One decode step over the state pool, in place.

    pool: [L, S, dk, H * dv], aliased to the output (in place where the
    caller's program donates it, as the step programs do); layer: int32
    scalar; rows, n_live: ``ssm.live_rows`` of the step's ``active``;
    q, k: [S, H, dk]; v: [S, H, dv]; g, beta: [S, H] (float32).
    Returns (pool, o [S, H, dv] float32); o of a row that does not
    decode is not written: the caller masks it."""
    L, S, dk, hdv = pool.shape
    H = q.shape[1]
    dv = hdv // H
    if H % 2 or (2 * dv) % 128:
        raise ValueError(f"{H} heads of {dv} values are not whole pairs of "
                         f"whole lane tiles")
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    alpha = jnp.exp(g)
    # what a head's lanes of the row need, a value a lane
    lanes = jnp.stack([
        jnp.repeat(alpha, dv, axis=-1),
        jnp.repeat(alpha * beta, dv, axis=-1),
        (beta[..., None] * v).reshape(S, hdv),
        jnp.repeat(jnp.sum(q * k, axis=-1), dv, axis=-1)], axis=1)
    kt, qt = jnp.swapaxes(k, 1, 2), jnp.swapaxes(q, 1, 2)    # [S, dk, H]
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def row(j, li, r, nl):
        return (li[0], r[j], 0, 0)

    def operand(j, li, r, nl):
        return (r[j], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((None, None, dk, hdv), row),
            pl.BlockSpec((None, dk, H), operand),
            pl.BlockSpec((None, dk, H), operand),
            pl.BlockSpec((None, 4, hdv), operand),
        ],
        out_specs=[
            pl.BlockSpec((None, None, dk, hdv), row),
            pl.BlockSpec((None, 1, hdv), operand),
        ],
    )
    pool, y = pl.pallas_call(
        functools.partial(_update_kernel, heads=H, dv=dv),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((S, 1, hdv), f32)],
        # operand 3 = pool, after the three scalar-prefetch operands
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="gdn_state_update",
    )(layer, rows, n_live, pool, kt, qt, lanes)
    return pool, y.reshape(S, H, dv)
