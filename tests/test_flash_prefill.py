"""Flash prefill kernel vs the pure-JAX reference (interpreter mode)."""

import jax.numpy as jnp
import numpy as np
import pytest

from kaito_tpu.engine.attention import prefill_attention
from kaito_tpu.engine.ops.flash_prefill import flash_prefill_attention

from tests.helpers.flash_geometries import SERVED

BIG = 1 << 30


def _setup(B=2, T=64, Hkv=2, G=2, D=32, seed=0):
    rng = np.random.RandomState(seed)
    H = Hkv * G
    q = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, T, Hkv, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, T, Hkv, D), jnp.float32)
    return q, k, v


def _case(id, **kw):
    """One comparison: batch rows of ``true_lens`` tokens in a chunk of
    ``T``, ``G`` query heads a KV head, keys of ``D`` and values of
    ``Dv``, blocks of ``bq`` queries and ``bk`` keys."""
    case = dict(T=64, Hkv=2, G=2, D=32, Dv=32, true_lens=(64, 64),
                window=None, softcap=None, sink=False, bq=16, bk=16,
                scale=0.17, seed=0)
    case.update(kw)
    return pytest.param(case, id=id)


# what the kernel was held to before its grid went over KV heads
_OLD_CASES = [
    _case("whole"),
    _case("ragged", true_lens=(50, 23)),
    _case("window", window=9),
    _case("softcap", softcap=25.0, true_lens=(64, 40)),
    _case("mqa-one-block", T=32, Hkv=1, G=4, true_lens=(32,), bq=32, bk=32,
          scale=0.3, seed=3),
]

# The stacked group: every G a served model has (1; phi's 3; falcon's
# 5; MiMo's 8 and 16), keys as wide as values and wider, window and
# sink on and off.  Chunks of 128 in query blocks of 16: a row that
# ends inside a block with no whole block of padding behind it (125),
# with one (100) and with three (70); key blocks of 32, so the walk
# ends inside a key block too.
_GROUP_CASES = [
    _case(f"G{G}-D{D}-{'win' if window else 'full'}"
          f"{'-sink' if sink else ''}",
          T=128, Hkv=1 if G > 5 else 2, G=G, D=D, Dv=128,
          true_lens=(125, 100, 70), window=window, sink=sink, bk=32,
          seed=G)
    for G in (1, 3, 5, 8, 16)
    for D in (128, 256)
    for window, sink in ((None, False), (None, True), (24, False),
                         (24, True))
]


@pytest.mark.parametrize("case", _OLD_CASES + _GROUP_CASES)
def test_flash_matches_reference(case):
    c = case
    rng = np.random.RandomState(c["seed"])
    B, T, H = len(c["true_lens"]), c["T"], c["Hkv"] * c["G"]
    q = jnp.asarray(rng.randn(B, T, H, c["D"]), jnp.float32)
    k = jnp.asarray(rng.randn(B, T, c["Hkv"], c["D"]), jnp.float32)
    v = jnp.asarray(rng.randn(B, T, c["Hkv"], c["Dv"]), jnp.float32)
    sink = jnp.asarray(rng.randn(H), jnp.float32) if c["sink"] else None
    true_len = jnp.asarray(c["true_lens"], jnp.int32)
    ref = prefill_attention(
        q, k, v, scale=c["scale"], sliding_window=c["window"],
        logit_softcap=c["softcap"], true_len=true_len, sink=sink)
    out = flash_prefill_attention(
        q, k, v, true_len,
        jnp.asarray(c["window"] if c["window"] else BIG, jnp.int32),
        scale=c["scale"], softcap=c["softcap"], block_q=c["bq"],
        block_k=c["bk"], interpret=True, sink=sink)
    assert out.shape == (B, T, H, c["Dv"])
    out = np.asarray(out)
    assert np.isfinite(out).all()
    for b, tl in enumerate(c["true_lens"]):
        # live rows against the reference; padding rows inside a live
        # block are undefined in both
        np.testing.assert_allclose(out[b, :tl], np.asarray(ref[b, :tl]),
                                   rtol=2e-5, atol=2e-5)
        # a query block wholly past true_len: zeros
        dead = -(-tl // c["bq"]) * c["bq"]
        assert not out[b, dead:].any()


@pytest.mark.parametrize("G,sink_on", [(1, False), (3, True), (16, True)])
def test_padding_blocks_are_written_as_zeros(G, sink_on):
    """The TPU interpreter hands the kernel an output buffer of NaNs
    (``uninitialized_memory='nan'``): a query block the kernel walked
    past and did not write would come back NaN, and ``0 x NaN`` in the
    next layer's second product would reach live rows.  Rows of 37, 16
    and 0 tokens in a chunk of 64: three, three and four whole blocks
    of padding."""
    from jax.experimental.pallas import tpu as pltpu

    rng = np.random.RandomState(G)
    B, T, D, Dv, bq = 3, 64, 32, 16, 16
    q = jnp.asarray(rng.randn(B, T, G, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, T, 1, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, T, 1, Dv), jnp.float32)
    sink = jnp.asarray(rng.randn(G), jnp.float32) if sink_on else None
    true_lens = (37, 16, 0)
    with pltpu.force_tpu_interpret_mode():
        out = np.asarray(flash_prefill_attention(
            q, k, v, jnp.asarray(true_lens, jnp.int32),
            jnp.asarray(BIG, jnp.int32), scale=0.2, block_q=bq, block_k=16,
            sink=sink))
    assert np.isfinite(out).all()
    ref = np.asarray(prefill_attention(
        q, k, v, scale=0.2, true_len=jnp.asarray(true_lens, jnp.int32),
        sink=sink))
    for b, tl in enumerate(true_lens):
        dead = -(-tl // bq) * bq
        assert np.abs(out[b, :tl] - ref[b, :tl]).max(initial=0.0) < 2e-5
        assert out[b, dead:].shape[0] >= bq and not out[b, dead:].any()


@pytest.mark.parametrize("name", SERVED)
def test_picked_tile_fits_vmem_at_every_bucket(name):
    """The tile ``flash_prefill_attention`` picks from its operands'
    shapes divides every prefill bucket, is whole sublane and lane
    tiles, and fits the VMEM arithmetic with the head's K and V
    (tests/test_two_kind_ops.py hands the same tiles to Mosaic)."""
    from kaito_tpu.engine.config import EngineConfig
    from kaito_tpu.engine.ops import flash_prefill as fp

    H, Hkv, D, Dv, _ = SERVED[name]
    G = H // Hkv
    for T in EngineConfig.prefill_buckets:
        bq, bk = fp._pick_tile(G, D, Dv, T, jnp.bfloat16)
        assert T % bq == 0 and T % bk == 0
        assert bq % 16 == 0 and bk % 128 == 0
        assert G * bq <= fp._MAX_ROWS and bk <= fp._MAX_BLOCK_K
        need = fp._vmem_need(T, D, Dv, jnp.bfloat16, G * bq, bk)
        assert need <= fp._VMEM_BUDGET < 16 << 20
        fp._check_fits_vmem(T, D, Dv, jnp.bfloat16, G, bq, bk)
    # the longest chunk the parent's budget took (12 MiB of K and V at
    # one lane tile) still finds a tile; its rows and keys give way
    bq, bk = fp._pick_tile(3, 128, 128, 12288, jnp.bfloat16)
    fp._check_fits_vmem(12288, 128, 128, jnp.bfloat16, 3, bq, bk)
    assert 3 * bq * bk < fp._MAX_ROWS * fp._MAX_BLOCK_K


def test_tile_that_cannot_fit_is_refused_by_name():
    """Asked for by hand (``block_q``/``block_k``), a tile whose float32
    scores and accumulator do not fit beside K and V is a ValueError
    that names the rows and keys, not a Mosaic allocation failure."""
    q = jnp.zeros((1, 4096, 64, 256), jnp.bfloat16)
    k = jnp.zeros((1, 4096, 4, 256), jnp.bfloat16)
    v = jnp.zeros((1, 4096, 4, 128), jnp.bfloat16)
    with pytest.raises(ValueError, match="2048 rows x 512 keys"):
        flash_prefill_attention(
            q, k, v, jnp.asarray([4096], jnp.int32),
            jnp.asarray(BIG, jnp.int32), scale=1.0, block_q=128,
            block_k=512, interpret=True)


def test_flash_rejects_misaligned_chunk():
    q, k, v = _setup(T=48)
    with pytest.raises(ValueError, match="multiple"):
        flash_prefill_attention(
            q, k, v, jnp.asarray([48, 48], jnp.int32),
            jnp.asarray(BIG, jnp.int32), scale=1.0,
            block_q=32, block_k=32, interpret=True)


def test_chunk_too_long_for_vmem_is_refused_by_name():
    """The kernel keeps a head's whole K and V in VMEM; a chunk past
    the budget is a ValueError here, not a Mosaic allocation failure."""
    T = 16384                      # 16 MiB of bf16 K/V at D=128
    q = jnp.zeros((1, T, 2, 128), jnp.bfloat16)
    k = jnp.zeros((1, T, 1, 128), jnp.bfloat16)
    win = jnp.asarray(1 << 30, jnp.int32)
    with pytest.raises(ValueError, match="VMEM"):
        flash_prefill_attention(q, k, k, jnp.asarray([T], jnp.int32), win,
                                scale=1.0, interpret=True)
