"""Kernel vs pure-JAX reference comparisons (interpreter mode on CPU)."""

import jax.numpy as jnp
import numpy as np
import pytest

from kaito_tpu.engine.attention import paged_decode_attention
from kaito_tpu.engine.ops.decode_attention import (
    _score_columns, paged_decode_attention_pallas)
from tests.helpers.decode_kernel_cases import CASES, check_decode_case

BIG = 1 << 30


def _setup(B=3, Hkv=2, G=2, D=64, ps=16, pmax=6, P=32, seed=0):
    rng = np.random.RandomState(seed)
    H = Hkv * G
    q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
    ck = jnp.asarray(rng.randn(P, ps, Hkv, D), jnp.float32)
    cv = jnp.asarray(rng.randn(P, ps, Hkv, D), jnp.float32)
    pt = np.zeros((B, pmax), np.int32)
    for b in range(B):
        pt[b] = rng.permutation(np.arange(1, P))[:pmax]
    lengths = jnp.asarray(rng.randint(1, pmax * ps, size=(B,)), jnp.int32)
    return q, ck, cv, jnp.asarray(pt), lengths


@pytest.mark.parametrize("window,softcap", [
    (None, None),
    (7, None),
    (None, 30.0),
])
def test_pallas_decode_matches_reference(window, softcap):
    q, ck, cv, pt, lengths = _setup()
    scale = 0.125
    ref = paged_decode_attention(
        q, ck, cv, pt, lengths, scale=scale,
        sliding_window=window, logit_softcap=softcap)
    win = jnp.asarray(window if window else BIG, jnp.int32)
    out = paged_decode_attention_pallas(
        q, ck, cv, pt, lengths, win, scale=scale, softcap=softcap,
        interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_pallas_decode_single_token_length():
    q, ck, cv, pt, _ = _setup(seed=3)
    lengths = jnp.ones((3,), jnp.int32)
    ref = paged_decode_attention(q, ck, cv, pt, lengths, scale=1.0)
    out = paged_decode_attention_pallas(
        q, ck, cv, pt, lengths, jnp.asarray(BIG, jnp.int32), scale=1.0,
        interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_pallas_decode_mqa():
    # Hkv=1 (falcon-style MQA), G=4
    q, ck, cv, pt, lengths = _setup(Hkv=1, G=4, seed=5)
    ref = paged_decode_attention(q, ck, cv, pt, lengths, scale=0.25)
    out = paged_decode_attention_pallas(
        q, ck, cv, pt, lengths, jnp.asarray(BIG, jnp.int32), scale=0.25,
        interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("name", list(CASES))
def test_pallas_decode_rows(name):
    """Ragged rows, rows that decode nothing (zeros out, neighbours
    exact), head layouts, masks and the stacked pool: the ring carried
    from row to row must hand every row its own pages."""
    check_decode_case(CASES[name])


@pytest.mark.parametrize("heads,num_kv,page", [(24, 8, 64), (4, 1, 16),
                                                (6, 2, 16)])
def test_score_columns_are_the_head_mask_and_page_row(heads, num_kv, page):
    """The kernel's constant operand, column by column: page row t where
    the query row's kv head is the column's, out of reach elsewhere."""
    t = _score_columns(heads, num_kv, page)
    row = np.arange(heads)[:, None] // (heads // num_kv)
    col = np.arange(page * num_kv)[None, :]
    match = row == col % num_kv
    assert t.shape == (heads, page * num_kv) and t.dtype == np.int32
    np.testing.assert_array_equal(
        t[match], np.broadcast_to(col // num_kv, t.shape)[match])
    # a mismatched column sits at or past every length a row can have
    assert (t[~match] == np.iinfo(np.int32).max).all()
