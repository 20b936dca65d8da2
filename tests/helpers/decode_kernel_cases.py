"""The paged decode kernel's cases, shared by its bf16/fp32 and int8-KV
tests: ragged rows, rows that decode nothing, head layouts, masks and
the layer-stacked pool, each against the pure-JAX attention.

The kernel runs in Pallas's interpreter, where a copy lands when it is
started: a ring slot handed to the wrong row, consumed before its copy
or overwritten before its turn shows up as a wrong number here.  What
it cannot show (a copy never waited for, a semaphore left signalled
across rows or calls) is the chip smoke's ``kernels`` leg
(benchmarks/kernel_bench.py); the engine-level case in
tests/test_pallas_model_path.py runs the TPU interpreter, whose copies
land at their wait.
"""

import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np

from kaito_tpu.engine.attention import paged_decode_attention
from kaito_tpu.engine.ops.decode_attention import (
    N_BUF, paged_decode_attention_pallas)

PS = 16
D = 64
BIG = 1 << 30


@dataclasses.dataclass(frozen=True)
class Case:
    lengths: tuple                 # tokens a row holds; 0: decodes nothing
    hkv: int = 2
    group: int = 2
    window: Optional[int] = None
    softcap: Optional[float] = None
    layers: int = 0                # > 0: the stacked pool, with ``layer``
    layer: int = 0


CASES = {
    "one_token": Case((1, 5, 1)),
    "exactly_one_page": Case((PS, 3, PS)),
    "page_boundary": Case((PS + 1, 2 * PS, 2 * PS - 1)),
    "deeper_than_ring": Case((PS * (N_BUF + 3) - 5, 2, PS * (2 * N_BUF + 1))),
    "one_page_beside_fourteen": Case((7, 14 * PS - 3, 9)),
    "empty_first": Case((0, 40, 17)),
    "empty_last": Case((40, 17, 0)),
    "empty_between": Case((40, 0, 0, 5 * PS + 1)),
    "all_empty": Case((0, 0, 0)),
    "batch_of_one": Case((3 * PS + 2,)),
    "mqa": Case((33, 70, 5), hkv=1, group=4),
    "gqa_group_of_three": Case((33, 0, 70), hkv=2, group=3),
    "window": Case((50, 7, 0, 90), window=20),
    "softcap": Case((50, 0, 90), softcap=30.0),
    "stacked_pool_layer": Case((6 * PS, 0, 19), layers=3, layer=2),
}


def quantize_pages(pages):
    """absmax int8 per page per kv head, the granularity the engine
    writes ([..., P, ps, Hkv, D] -> codes, scales [..., P, Hkv])"""
    s = jnp.max(jnp.abs(pages), axis=(-3, -1)) / 127.0
    codes = jnp.clip(jnp.round(
        pages / jnp.maximum(s, 1e-30)[..., None, :, None]), -127, 127)
    return codes.astype(jnp.int8), s


def check_decode_case(case: Case, *, int8_kv: bool = False, seed: int = 0):
    rng = np.random.RandomState(seed)
    B = len(case.lengths)
    H = case.hkv * case.group
    pmax = max(2, -(-max(case.lengths) // PS))
    P = B * pmax + 1
    pool = (case.layers,) if case.layers else ()
    q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
    ck = jnp.asarray(rng.randn(*pool, P, PS, case.hkv, D), jnp.float32)
    cv = jnp.asarray(rng.randn(*pool, P, PS, case.hkv, D), jnp.float32)
    # every row its own pages, in no order; page 0 is the null page
    pt = jnp.asarray(rng.permutation(np.arange(1, P)).reshape(B, pmax)
                     .astype(np.int32))
    lengths = jnp.asarray(case.lengths, jnp.int32)
    layer = jnp.asarray(case.layer, jnp.int32) if case.layers else None
    scales = {}
    if int8_kv:
        ck, ks = quantize_pages(ck)
        cv, vs = quantize_pages(cv)
        scales = dict(k_scale=ks, v_scale=vs)
    scale = D ** -0.5

    out = paged_decode_attention_pallas(
        q, ck, cv, pt, lengths,
        jnp.asarray(case.window or BIG, jnp.int32), scale=scale,
        softcap=case.softcap, layer=layer,
        interpret=True, **scales)
    ref = paged_decode_attention(
        q, ck, cv, pt, lengths, scale=scale, sliding_window=case.window,
        logit_softcap=case.softcap, layer=layer, **scales)

    out, ref = np.asarray(out), np.asarray(ref)
    live = np.asarray(case.lengths) > 0
    np.testing.assert_allclose(out[live], ref[live], rtol=2e-5, atol=2e-5)
    # a row that decodes nothing ran no page: exact zeros, not the
    # reference's mean over masked columns
    assert not out[~live].any()
