"""The gated delta rule's three forms (kaito_tpu/engine/ops/gdn.py)
against the definition, token by token, at beta up to 2: the chunked
scan that prefill runs (its blocked triangular inverse and its two
loops too), the ``jax.numpy`` decode step a CPU serves, and
the Pallas decode kernel in interpret mode at the smallest shape that
crosses a lane tile (tests/test_two_kind_ops.py compiles it for a
described v5e at the published widths)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from kaito_tpu.engine.ops import gdn as G
from kaito_tpu.engine.ops.ssm import live_rows


def _inputs(b, T, H, dk, dv, seed=0):
    rng = np.random.default_rng(seed)

    def n(*s):
        return jnp.asarray(rng.standard_normal(s), jnp.float32)

    q, k, v = n(b, T, H, dk), n(b, T, H, dk), n(b, T, H, dv)
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jnp.asarray(rng.uniform(1e-3, 1.6, (b, T, H)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.0, 2.0, (b, T, H)), jnp.float32)
    # the edge of the range: an eigenvalue of -1 along k
    beta = beta.at[:, ::7].set(2.0)
    return q, k, v, g, beta, n(b, H, dk, dv)


@pytest.mark.parametrize("T,chunk", [(150, 64), (64, 64), (37, 16), (5, 64)])
def test_chunked_scan_is_the_recurrence(T, chunk):
    """From an initial state that is not zero to a final one, a length
    that is no whole number of chunks included."""
    q, k, v, g, beta, s0 = _inputs(2, T, 3, 8, 16)
    want_o, want_s = G.gdn_recurrence(q, k, v, g, beta, s0)
    got_o, got_s = jax.jit(G.gdn_chunked_scan, static_argnames="chunk")(
        q, k, v, g, beta, s0, chunk=chunk)
    assert float(jnp.abs(want_o).max()) > 0.1
    assert float(jnp.abs(got_o - want_o).max()) < 2e-5
    assert float(jnp.abs(got_s - want_s).max()) < 2e-5


def test_a_padded_position_leaves_the_state_as_it_was():
    """g = 0 and beta = 0 past a row's true length: the final state is
    the state after the last valid token, whatever the padding holds."""
    q, k, v, g, beta, s0 = _inputs(2, 50, 2, 8, 16, seed=1)
    lens = jnp.asarray([50, 23])
    valid = (jnp.arange(50)[None, :] < lens[:, None])[..., None]
    _, got = G.gdn_chunked_scan(q, k, v, jnp.where(valid, g, 0.0),
                                jnp.where(valid, beta, 0.0), s0, chunk=16)
    _, want = G.gdn_recurrence(q[1:, :23], k[1:, :23], v[1:, :23],
                               g[1:, :23], beta[1:, :23], s0[1:])
    assert float(jnp.abs(got[1:] - want).max()) < 2e-5


def test_two_chunks_carry_the_state():
    """The second call from what the first left, the boundary on no
    multiple of the scan's chunk: what one call over the whole gives."""
    q, k, v, g, beta, s0 = _inputs(1, 100, 2, 8, 16, seed=2)
    whole_o, whole_s = G.gdn_chunked_scan(q, k, v, g, beta, s0, chunk=16)
    cut = 37
    o1, s1 = G.gdn_chunked_scan(q[:, :cut], k[:, :cut], v[:, :cut],
                                g[:, :cut], beta[:, :cut], s0, chunk=16)
    o2, s2 = G.gdn_chunked_scan(q[:, cut:], k[:, cut:], v[:, cut:],
                                g[:, cut:], beta[:, cut:], s1, chunk=16)
    got = jnp.concatenate([o1, o2], axis=1)
    assert float(jnp.abs(got - whole_o).max()) < 2e-5
    assert float(jnp.abs(s2 - whole_s).max()) < 2e-5


def _lower_A(C, dk=8, seed=5, n=6):
    """``n`` matrices ``A`` as a chunk of ``C`` tokens makes them
    (float64): normalised keys, decays between a thousandth and 1.6 a
    token, as many under 0.04 as over it, beta up to 2 and 2 itself at
    every seventh token."""
    rng = np.random.default_rng(seed)
    k = rng.standard_normal((n, C, dk))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    gamma = np.cumsum(-np.exp(rng.uniform(np.log(1e-3), np.log(1.6),
                                          (n, C))), axis=-1)
    beta = rng.uniform(0.0, 2.0, (n, C))
    beta[:, ::7] = 2.0
    A = beta[..., None] * (k @ np.swapaxes(k, -1, -2)) \
        * np.exp(gamma[..., :, None] - gamma[..., None, :])
    return np.tril(A, -1)


def _row_recurrence(A):
    """Forward substitution over the whole matrix, in float64: row i of
    ``(I + A)^-1`` is ``e_i - A[i, :i] T[:i]``."""
    T = np.broadcast_to(np.eye(A.shape[-1]), A.shape).copy()
    for i in range(1, A.shape[-1]):
        T[..., i, :] -= np.einsum("nj,njk->nk", A[..., i, :i], T[..., :i, :])
    return T


@pytest.mark.parametrize("C", [128, 64, 32, 16, 24])
def test_the_blocked_inverse_is_the_inverse(C):
    """Blocks of 32 rows (24 and 16 are no whole number of them and go
    as one block) against ``inv(I + A)`` and against the row recurrence over
    the whole matrix.  float32 against float64: forward substitution
    loses a rounding (6e-8) a term of a row's sum, on entries of order
    1; this reads 1.1e-7 to 1.6e-7 of the largest entry at every
    size."""
    A = _lower_A(C)
    got = np.asarray(jax.jit(G._unit_lower_inverse)(
        jnp.asarray(A, jnp.float32).reshape(2, 3, C, C))).reshape(A.shape)
    inv = np.linalg.inv(np.eye(C) + A)
    rows = _row_recurrence(A)
    top = np.abs(inv).max()
    assert top >= 1.0 and np.abs(A).max() > 0.5
    assert np.abs(rows - inv).max() < 1e-12 * top
    assert np.abs(got - inv).max() < 1e-6 * top
    assert np.abs(got - rows).max() < 1e-6 * top
    # unit lower triangular, exactly
    assert (np.triu(got, 1) == 0).all()
    assert (np.diagonal(got, axis1=-2, axis2=-1) == 1).all()


@pytest.mark.parametrize("T,chunk", [(2048, 128), (1024, 64)])
def test_sixteen_chunks_at_the_published_head_shape(T, chunk):
    """Heads of 96 keys and 192 values from a state that is not zero,
    16 chunks: the carry multiplies 16 transition matrices ``M`` in a
    row.  A token's transition ``e^g (I - beta k k^T)`` has no
    eigenvalue outside [-1, 1] at beta <= 2, so ``M`` stretches
    nothing and the roundings add and do not compound: one product of
    96 terms is off by about ``sqrt(96) * 6e-8 = 6e-7`` of the state's
    largest entry (4-5 here), 16 of them in a row by at most 5e-5 and,
    adding as a random walk, by about 1e-5: the limit is the other
    cases' 2e-5 (a CPU reads 1.7e-6 and 5.0e-6 on the state and under
    1e-6 on the output; the chip, whose float32 products are six
    bfloat16 passes, 0.9e-5 to 4.3e-5 of 2.1-2.8 over 12 to 32 chunks
    of 30 heads, benchmarks/gdn_scan.py).  A dropped term is of the
    order of the state itself."""
    q, k, v, g, beta, s0 = _inputs(1, T, 3, 96, 192, seed=6)
    want_o, want_s = jax.jit(G.gdn_recurrence)(q, k, v, g, beta, s0)
    got_o, got_s = jax.jit(G.gdn_chunked_scan, static_argnames="chunk")(
        q, k, v, g, beta, s0, chunk=chunk)
    assert float(jnp.abs(want_s).max()) > 1.0
    assert float(jnp.abs(got_o - want_o).max()) < 2e-5
    assert float(jnp.abs(got_s - want_s).max()) < 2e-5


def _equations(jaxpr):
    """Every equation of a jaxpr, those of nested jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for x in param if isinstance(param, (list, tuple)) else [param]:
                inner = getattr(x, "jaxpr", x)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def test_only_what_is_serial_is_in_a_loop():
    """The scan's two loops at 256 tokens: the inverse's row loop makes
    31 trips (blocks of 32 rows, not the chunk's 127) with no matrix
    product in it, and the loop over the chunks holds exactly one (the
    state's own recurrence), so work put back into a serial loop fails
    here and not only in a benchmark."""
    q, k, v, g, beta, s0 = _inputs(1, 256, 2, 8, 16)
    jaxpr = jax.make_jaxpr(G.gdn_chunked_scan)(q, k, v, g, beta, s0).jaxpr
    names = [e.primitive.name for e in _equations(jaxpr)]
    assert "while" not in names
    loops = {e.params["length"]: [x.primitive.name
                                  for x in _equations(e.params["jaxpr"].jaxpr)]
             for e in _equations(jaxpr) if e.primitive.name == "scan"}
    assert sorted(loops) == [256 // G.GDN_CHUNK, G.GDN_SUB - 1] == [2, 31]
    assert loops[31].count("dot_general") == 0
    assert loops[2].count("dot_general") == 1


def _decode_case(seed=3):
    S, H, dk, dv = 5, 4, 16, 64
    q, k, v, g, beta, _ = _inputs(1, S, H, dk, dv, seed=seed)
    rng = np.random.default_rng(seed)
    pool = jnp.asarray(rng.standard_normal((3, S, dk, H * dv)), jnp.float32)
    active = jnp.asarray([True, False, True, True, False])
    return pool, (q[0], k[0], v[0], g[0], beta[0]), active, H


def test_the_jax_decode_step_is_one_token_of_the_recurrence():
    pool, (q, k, v, g, beta), active, H = _decode_case()
    new, o = G.gdn_state_update_jax(pool, 1, q, k, v, g, beta, active)
    want_o, want_s = G.gdn_recurrence(
        q[:, None], k[:, None], v[:, None], g[:, None], beta[:, None],
        G.from_pool_layout(pool[1], H))
    live = np.asarray(active)
    assert float(jnp.abs(o[live] - want_o[live, 0]).max()) < 1e-5
    assert float(jnp.abs(new[1][live]
                         - G.pool_layout(want_s)[live]).max()) < 1e-5
    # a row that does not decode keeps its bits and reads zero; the
    # other layers are not touched
    assert (np.asarray(new[1])[~live] == np.asarray(pool[1])[~live]).all()
    assert not np.asarray(o)[~live].any()
    assert (np.asarray(new[0]) == np.asarray(pool[0])).all()
    assert (np.asarray(new[2]) == np.asarray(pool[2])).all()
    back = G.from_pool_layout(G.pool_layout(want_s), H)
    assert (np.asarray(back) == np.asarray(want_s)).all()


def test_the_kernel_is_the_jax_decode_step():
    """Interpret mode, 4 heads of 64 values: two pairs of 128 lanes, so
    the loop over pairs crosses a lane tile; the pool in bfloat16 as on
    the chip (both forms compute in float32 and round once)."""
    pool, (q, k, v, g, beta), active, H = _decode_case(seed=4)
    pool = pool.astype(jnp.bfloat16)
    want_pool, want_o = G.gdn_state_update_jax(pool, 1, q, k, v, g, beta,
                                               active)
    rows, n_live = live_rows(active)

    def call(pool, rows, n_live):
        return G.gdn_state_update(pool, jnp.int32(1), rows, n_live, q, k, v,
                                  g, beta)

    with pltpu.force_tpu_interpret_mode():
        got_pool, got_o = jax.jit(call)(pool, rows, n_live)
        # no row decodes: the pool comes back as it went in
        idle, _ = jax.jit(call)(pool, *live_rows(jnp.zeros((5,), bool)))
    got_o = jnp.where(active[:, None, None], got_o, 0.0)
    assert float(jnp.abs(got_o - want_o).max()) < 1e-5
    got, want = (np.asarray(x.astype(jnp.float32))
                 for x in (got_pool, want_pool))
    # (the kernel reads both products off the row as it came and
    # decays after: a float32 rounding apart, at most one bfloat16 step)
    assert np.abs(got - want).max() <= 2.0 ** -7 * np.abs(want).max()
    assert (got != want).mean() < 0.02
    live = np.asarray(active)
    assert (got[1][~live] == want[1][~live]).all()
    assert (got[0] == want[0]).all() and (got[2] == want[2]).all()
    assert (np.asarray(idle.astype(jnp.float32))
            == np.asarray(pool.astype(jnp.float32))).all()


def test_heads_that_are_no_whole_pairs_of_lane_tiles_are_refused():
    pool = jnp.zeros((1, 2, 8, 3 * 64), jnp.float32)
    z = jnp.zeros((2, 3, 8), jnp.float32)
    with pytest.raises(ValueError, match="whole pairs of whole lane tiles"):
        G.gdn_state_update(pool, jnp.int32(0), jnp.zeros((2,), jnp.int32),
                           jnp.ones((1,), jnp.int32), z, z,
                           jnp.zeros((2, 3, 64), jnp.float32),
                           jnp.zeros((2, 3), jnp.float32),
                           jnp.zeros((2, 3), jnp.float32))
