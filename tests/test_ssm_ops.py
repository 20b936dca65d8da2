"""The state-space mixer's recurrence (kaito_tpu/engine/ops/ssm.py): the
chunked scan that prefill runs and the Pallas decode kernel (interpret
mode) against the token-by-token definition, which lives here, at chunk
boundaries, with a non-zero initial state, with padded positions, with
rows that decode nothing, and with the pool in bfloat16 as it is
served."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kaito_tpu.engine.ops import ssm

H, P, G, N = 16, 8, 2, 16
_HI = jax.lax.Precision.HIGHEST


def ssm_recurrence(x, dt, A, B, C, h0):
    """The definition, token by token.  Per head ``i`` of group ``g``:
    ``a = exp(A_i dt)``, ``h <- a h + dt x_i (outer) B_g``,
    ``y_i = h @ C_g``.  x: [b, T, H, P]; dt: [b, T, H]; A: [H];
    B, C: [b, T, G, N]; h0: [b, H, P, N].  Returns (y [b, T, H, P],
    final state)."""
    H, G = x.shape[2], B.shape[2]

    def step(h, inp):
        xt, dtt, Bt, Ct = inp
        Bh = jnp.repeat(Bt, H // G, axis=1)                 # [b, H, N]
        Ch = jnp.repeat(Ct, H // G, axis=1)
        a = jnp.exp(dtt * A[None, :])
        h = h * a[..., None, None] \
            + (dtt[..., None] * xt)[..., None] * Bh[:, :, None, :]
        return h, jnp.einsum("bhpn,bhn->bhp", h, Ch, precision=_HI)

    xs = tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, B, C))
    h, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1), h


# one program a shape, not one dispatch an einsum
_scan = jax.jit(ssm.ssm_chunked_scan, static_argnums=6)
_recur = jax.jit(ssm_recurrence)


def _inputs(b, T, seed=0, H=H):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    x, B, C = f(b, T, H, P), f(b, T, G, N), f(b, T, G, N)
    dt = jnp.asarray(rng.uniform(1e-3, 1e-1, size=(b, T, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(1, 16, size=(H,)), jnp.float32)
    return x, dt, A, B, C, f(b, H, P, N)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


# a chunk of 32: shorter than one, exactly one, one token over, several
# and a ragged end
@pytest.mark.parametrize("T", [5, 32, 33, 64, 100])
@pytest.mark.parametrize("zero_state", [True, False])
def test_chunked_scan_equals_the_recurrence(T, zero_state):
    x, dt, A, B, C, h0 = _inputs(2, T, seed=T)
    if zero_state:
        h0 = jnp.zeros_like(h0)
    y0, hf0 = _recur(x, dt, A, B, C, h0)
    y1, hf1 = _scan(x, dt, A, B, C, h0, 32)
    np.testing.assert_allclose(y1, y0, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(hf1, hf0, atol=2e-6, rtol=1e-5)


def test_state_carried_across_calls_equals_one_call():
    """Three prefill chunks (the context-prefill program's case): the
    final state of one is the next one's initial state."""
    x, dt, A, B, C, h0 = _inputs(1, 96, seed=7)
    y_all, h_all = _scan(x, dt, A, B, C, h0, 32)
    h, ys = h0, []
    for lo, hi in ((0, 40), (40, 72), (72, 96)):
        y, h = _scan(x[:, lo:hi], dt[:, lo:hi], A, B[:, lo:hi], C[:, lo:hi],
                     h, 32)
        ys.append(y)
    np.testing.assert_allclose(jnp.concatenate(ys, axis=1), y_all,
                               atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(h, h_all, atol=2e-6, rtol=1e-5)


def test_a_padded_position_leaves_the_state_as_it_was():
    x, dt, A, B, C, h0 = _inputs(1, 50, seed=9)
    dt_pad = dt.at[:, 37:].set(0.0)
    _, h_pad = _scan(x, dt_pad, A, B, C, h0, 32)
    _, h_cut = _scan(x[:, :37], dt[:, :37], A, B[:, :37], C[:, :37], h0, 32)
    np.testing.assert_allclose(h_pad, h_cut, atol=2e-6, rtol=1e-5)


def test_convolution_with_a_tail_equals_the_whole_sequence():
    rng = np.random.default_rng(3)
    K, Cd, T = 4, 24, 20
    x = jnp.asarray(rng.normal(size=(2, T, Cd)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, Cd)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(Cd,)), jnp.float32)
    zeros = jnp.zeros((2, K - 1, Cd), jnp.float32)
    whole = ssm.causal_conv(x, zeros, w, b)
    # by definition: tap K-1 on the current input
    want = b + sum(w[k] * jnp.pad(x, ((0, 0), (K - 1 - k, 0), (0, 0)))[:, :T]
                   for k in range(K))
    np.testing.assert_allclose(whole, want, atol=1e-6)
    lens = jnp.asarray([11, 2], jnp.int32)   # the second shorter than a tail
    tail = ssm.conv_tail(x, zeros, lens)
    for r, n in enumerate((11, 2)):
        rest = ssm.causal_conv(x[r:r + 1, n:], tail[r:r + 1], w, b)
        np.testing.assert_allclose(rest, whole[r:r + 1, n:], atol=1e-6)
        out, new_tail = ssm.conv_step(x[r, n][None], tail[r:r + 1], w, b)
        np.testing.assert_allclose(out[0], whole[r, n], atol=1e-6)
        np.testing.assert_allclose(
            new_tail, ssm.conv_tail(x, zeros, lens + 1)[r:r + 1], atol=0)


@pytest.mark.parametrize("active", [
    [1, 0, 1, 1, 0, 0, 1, 0], [0] * 8, [1] * 8, [0, 0, 0, 0, 0, 0, 0, 1]])
@pytest.mark.parametrize("heads", [16, 32])
def test_state_update_kernel_in_interpret_mode(active, heads):
    """The Pallas kernel (one block of heads a row, and two) against the
    jax.numpy step and the definition: live rows updated in place from a
    non-zero state, rows that decode nothing (empty rows included: none,
    all, the last alone) left bit for bit, other layers untouched."""
    L, S, H = 3, 8, heads
    assert H // ssm.HEADS_PER_BLOCK == heads // 16
    x, dt, A, B, C, _ = _inputs(1, S, seed=11, H=H)
    xs, dts, Bs, Cs = x[0], dt[0], B[0], C[0]
    pool = jnp.asarray(np.random.default_rng(5).normal(
        size=(L, S, H, P, N)), jnp.float32)
    act = jnp.asarray(active, bool)
    want_pool, want_y = ssm.ssm_state_update_jax(pool, 1, xs, dts, A, Bs, Cs,
                                                 act)
    # the jax.numpy step is one step of the definition
    y_def, h_def = ssm_recurrence(xs[:, None], dts[:, None], A,
                                  Bs[:, None], Cs[:, None], pool[1])
    live = np.asarray(active, bool)
    np.testing.assert_allclose(want_pool[1][live], h_def[live], atol=1e-6)
    np.testing.assert_allclose(want_y[live], y_def[live, 0], atol=1e-5)
    rows, n_live = ssm.live_rows(act)
    assert int(n_live[0]) == sum(active)
    got_pool, got_y = ssm.ssm_state_update(
        pool + 0, jnp.int32(1), rows, n_live, xs, dts, A, Bs, Cs,
        interpret=True)
    np.testing.assert_allclose(got_pool[1][live], want_pool[1][live],
                               atol=1e-6)
    np.testing.assert_allclose(got_y[live], want_y[live], atol=1e-5)
    assert (np.asarray(got_pool[1])[~live] == np.asarray(pool[1])[~live]).all()
    assert (np.asarray(got_pool[0]) == np.asarray(pool[0])).all()
    assert (np.asarray(got_pool[2]) == np.asarray(pool[2])).all()


@pytest.mark.parametrize("active", [[1, 0, 1, 1, 0, 0, 1, 0], [0] * 8])
def test_a_bfloat16_pool_is_read_updated_in_float32_and_rounded_once(active):
    """The pool as the chip serves it: both forms read the bfloat16
    state, compute in float32, return y unrounded and write the new
    state rounded once; a row that decodes nothing keeps its bits."""
    L, S = 2, 8
    x, dt, A, B, C, _ = _inputs(1, S, seed=13)
    xs, dts, Bs, Cs = x[0], dt[0], B[0], C[0]
    pool = jnp.asarray(np.random.default_rng(6).normal(
        size=(L, S, H, P, N)), jnp.bfloat16)
    act = jnp.asarray(active, bool)
    live = np.asarray(active, bool)
    exact_pool, exact_y = ssm.ssm_state_update_jax(
        pool.astype(jnp.float32), 1, xs, dts, A, Bs, Cs, act)
    rows, n_live = ssm.live_rows(act)
    for got_pool, got_y in (
            ssm.ssm_state_update_jax(pool, 1, xs, dts, A, Bs, Cs, act),
            ssm.ssm_state_update(pool + 0, jnp.int32(1), rows, n_live, xs,
                                 dts, A, Bs, Cs, interpret=True)):
        assert got_pool.dtype == jnp.bfloat16 and got_y.dtype == jnp.float32
        np.testing.assert_allclose(got_y[live], exact_y[live], atol=1e-5)
        want = np.asarray(exact_pool[1].astype(jnp.bfloat16), np.float32)
        got = np.asarray(got_pool[1], np.float32)
        # one rounding of the same float32 value: the same bits, or a
        # neighbour where the two sums differ in their last place
        np.testing.assert_allclose(got[live], want[live], rtol=2 ** -7)
        assert (got[~live] == np.asarray(pool[1], np.float32)[~live]).all()
        assert (np.asarray(got_pool[0], np.float32)
                == np.asarray(pool[0], np.float32)).all()


def test_live_rows_names_the_rows_that_decode_then_repeats_the_last():
    rows, n = ssm.live_rows(jnp.asarray([0, 1, 0, 1, 1, 0], bool))
    assert rows.tolist() == [1, 3, 4, 4, 4, 4] and n.tolist() == [3]
    rows, n = ssm.live_rows(jnp.zeros((4,), bool))
    assert rows.tolist() == [0, 0, 0, 0] and n.tolist() == [0]


# ----------------------------------------------------------------------
# the kernel at the benchmark's widths, compiled for the chip it serves
# on (described, not attached: nothing runs).  The topology is described
# inside a fixture, never while a module is imported: one process at a
# time may load the TPU's library.
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_state_update_kernel_compiles_for_v5e_at_the_published_widths(
        one_chip):
    """Falcon-H1-34B's mixer at the cell's 96 slots and 6 layers, the
    pool in bfloat16 as it is served: Mosaic takes the kernel, the pool
    is aliased (no second 1.2 GB buffer) and the program needs no
    temporary of the pool's size."""
    L, S, Hm, Pm, Nm, Gm = 6, 96, 32, 128, 256, 2

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(ssm.ssm_state_update.__wrapped__,
                       donate_argnums=0).lower(
        sd((L, S, Hm, Pm, Nm), jnp.bfloat16), sd((), jnp.int32),
        sd((S,), jnp.int32),
        sd((1,), jnp.int32), sd((S, Hm, Pm)), sd((S, Hm)), sd((Hm,)),
        sd((S, Gm, Nm)), sd((S, Gm, Nm))).compile()
    assert "ssm_state_update" in compiled.as_text()
    mem = compiled.memory_analysis()
    pool_bytes = L * S * Hm * Pm * Nm * 2
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 8
