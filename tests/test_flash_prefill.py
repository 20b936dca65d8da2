"""Flash prefill kernel vs the pure-JAX reference (interpreter mode)."""

import jax.numpy as jnp
import numpy as np
import pytest

from kaito_tpu.engine.attention import (packed_prefill_attention,
                                        prefill_attention)
from kaito_tpu.engine.ops.flash_prefill import (flash_prefill_attention,
                                                flash_prefill_packed)

BIG = 1 << 30


def _setup(B=2, T=64, Hkv=2, G=2, D=32, seed=0):
    rng = np.random.RandomState(seed)
    H = Hkv * G
    q = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, T, Hkv, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, T, Hkv, D), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("window,softcap,true_lens", [
    (None, None, (64, 64)),
    (None, None, (50, 23)),        # ragged
    (9, None, (64, 64)),           # sliding window
    (None, 25.0, (64, 40)),        # softcap
])
def test_flash_matches_reference(window, softcap, true_lens):
    q, k, v = _setup()
    scale = 0.17
    ref = prefill_attention(
        q, k, v, scale=scale, sliding_window=window, logit_softcap=softcap,
        true_len=jnp.asarray(true_lens, jnp.int32))
    out = flash_prefill_attention(
        q, k, v, jnp.asarray(true_lens, jnp.int32),
        jnp.asarray(window if window else BIG, jnp.int32),
        scale=scale, softcap=softcap, block_q=16, block_k=16, interpret=True)
    # compare only valid rows (padding rows are undefined in both)
    for b, tl in enumerate(true_lens):
        np.testing.assert_allclose(
            np.asarray(out[b, :tl]), np.asarray(ref[b, :tl]),
            rtol=2e-5, atol=2e-5)


def test_flash_mqa_single_block():
    q, k, v = _setup(B=1, T=32, Hkv=1, G=4, seed=3)
    ref = prefill_attention(q, k, v, scale=0.3,
                            true_len=jnp.asarray([32], jnp.int32))
    out = flash_prefill_attention(
        q, k, v, jnp.asarray([32], jnp.int32), jnp.asarray(BIG, jnp.int32),
        scale=0.3, block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def _packed_layout(T, seg_lens):
    """Segment ids / within-segment positions for prompts packed back
    to back into one row of length T (pads: seg -1, pos 0)."""
    segs = np.full((1, T), -1, np.int32)
    poss = np.zeros((1, T), np.int32)
    off = 0
    for si, ln in enumerate(seg_lens):
        segs[0, off:off + ln] = si
        poss[0, off:off + ln] = np.arange(ln)
        off += ln
    return jnp.asarray(segs), jnp.asarray(poss)


@pytest.mark.parametrize("window,softcap,seg_lens", [
    (None, None, (20, 30, 14)),    # three packed segments + no pad
    (None, None, (25, 17)),        # trailing pad
    (7, None, (20, 30, 14)),       # sliding window inside segments
    (None, 25.0, (40, 10)),        # softcap
    (None, None, (64,)),           # degenerate: one segment == serial
])
def test_flash_packed_matches_reference(window, softcap, seg_lens):
    q, k, v = _setup(B=1)
    T = q.shape[1]
    segs, poss = _packed_layout(T, seg_lens)
    scale = 0.17
    ref = packed_prefill_attention(
        q, k, v, segs, poss, scale=scale, sliding_window=window,
        logit_softcap=softcap)
    out = flash_prefill_packed(
        q, k, v, segs, poss,
        jnp.asarray(window if window else BIG, jnp.int32),
        scale=scale, softcap=softcap, block_q=16, block_k=16,
        interpret=True)
    valid = sum(seg_lens)
    np.testing.assert_allclose(
        np.asarray(out[0, :valid]), np.asarray(ref[0, :valid]),
        rtol=2e-5, atol=2e-5)


def test_flash_packed_segments_do_not_leak():
    """Token j of segment B must see nothing of segment A: its output
    equals running segment B alone at batch 1."""
    q, k, v = _setup(B=1, T=64)
    segs, poss = _packed_layout(64, (24, 40))
    out = flash_prefill_packed(
        q, k, v, segs, poss, jnp.asarray(BIG, jnp.int32),
        scale=0.17, block_q=16, block_k=16, interpret=True)
    solo = flash_prefill_attention(
        q[:, 24:], k[:, 24:], v[:, 24:], jnp.asarray([40], jnp.int32),
        jnp.asarray(BIG, jnp.int32), scale=0.17, block_q=8, block_k=8,
        interpret=True)
    np.testing.assert_allclose(np.asarray(out[0, 24:]),
                               np.asarray(solo[0]),
                               rtol=2e-5, atol=2e-5)


def test_flash_rejects_misaligned_chunk():
    q, k, v = _setup(T=48)
    with pytest.raises(ValueError, match="multiple"):
        flash_prefill_attention(
            q, k, v, jnp.asarray([48, 48], jnp.int32),
            jnp.asarray(BIG, jnp.int32), scale=1.0,
            block_q=32, block_k=32, interpret=True)


def test_chunk_too_long_for_vmem_is_refused_by_name():
    """Both kernels keep a head's whole K and V in VMEM; a chunk past
    the budget is a ValueError here, not a Mosaic allocation failure."""
    T = 16384                      # 16 MiB of bf16 K/V at D=128
    q = jnp.zeros((1, T, 2, 128), jnp.bfloat16)
    k = jnp.zeros((1, T, 1, 128), jnp.bfloat16)
    ids = jnp.zeros((1, T), jnp.int32)
    win = jnp.asarray(1 << 30, jnp.int32)
    with pytest.raises(ValueError, match="VMEM"):
        flash_prefill_attention(q, k, k, jnp.asarray([T], jnp.int32), win,
                                scale=1.0, interpret=True)
    with pytest.raises(ValueError, match="VMEM"):
        flash_prefill_packed(q, k, k, ids, ids, win, scale=1.0,
                             interpret=True)
