"""The chip smoke's parity leg for the engine's Pallas kernels.

Every kernel is built as a ``KernelCase`` at phi-4-mini shapes (H=24,
Hkv=8, D=128, page 64, bf16) next to the pure-JAX function it must
match.  The kernels are compiled for the chip (never interpreted), so
this needs a TPU; the interpreter is covered by
tests/test_pallas_ops.py.  All test data is generated ON DEVICE with
jax.random.  A kernel's time in a served step and its roofline share
come from the benchmark's trace (kbench/, PERF.md section 3); each row
here carries ``call_ms``, the host's clock round the case called alone
(the wrapper's transposes included), which is what a kernel change is
measured by before a cell is run and is no benchmark metric.

Usage:  python benchmarks/kernel_bench.py --parity [--only name,name]

This is ``chip_smoke.py``'s ``kernels`` leg: one JSON line per case,
then one naming the device; exit 1 if any case fails to compile or to
match.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time
from typing import Callable, Optional

# make `python benchmarks/kernel_bench.py` work from anywhere (the
# script dir, not the repo root, is what python puts on sys.path)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

# phi-4-mini attention geometry
H, HKV, D, PS = 24, 8, 128, 64
BIG_WINDOW = 1 << 30

# Attention outputs are bf16 and reach |x| ~ 4 where a row attends to a
# single token (out == v): one bf16 ulp there is 2**-5 = 0.031, and the
# kernels round probabilities to bf16 before the PV matmul (rel 2**-9).
ATTN_TOL = 0.05
ATTN_WHY = "bf16 output, 1 ulp at |x|~4 is 0.031; probs rounded to bf16"
# The matmul kernels widen the integer codes exactly and accumulate in
# fp32, so against an fp32 reference only the bf16 output rounding
# (half-ulp relative 2**-9) and the accumulation order remain.
GEMV_TOL = 2.0 ** -7
GEMV_WHY = "bf16 output rounding 2**-9 relative, 4x margin for fp32 order"


@dataclasses.dataclass
class KernelCase:
    name: str
    kernel: Callable           # *args -> array, the Pallas path
    reference: Callable        # *args -> array, pure JAX
    args: tuple
    tol: float
    why: str                   # where the tolerance comes from
    relative: bool = False     # tol bounds err / max|reference|
    mask: Optional[jax.Array] = None   # entries with a defined result
    zero_where_masked: bool = False    # ... and the rest must be zeros
    # bytes or FLOPs one call must move/do, and which: read by nothing
    # here; kept for the move into kbench/rooflines.py (ROADMAP D5)
    unit: str = ""
    work: float = 0.0
    # other forms of the same result, timed as the kernel is:
    # {name: *args -> array}
    others: dict = dataclasses.field(default_factory=dict)


def _f32(x):
    return x.astype(jnp.float32)


def _call_ms(compiled, args) -> float:
    """The host's clock round five calls, whatever the wrapper does
    around the kernel (a transpose, a gather) included: what a change
    to a kernel is first measured by, alone, before any cell is run;
    no benchmark metric."""
    jax.block_until_ready(compiled(*args))
    t0 = time.perf_counter()
    for _ in range(5):
        out = compiled(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / 5 * 1e3


def parity(case: KernelCase) -> dict:
    """Compile the kernel for the chip, run it and the reference, and
    compare.  Raises if the kernel was not lowered through Mosaic."""
    lowered = jax.jit(case.kernel).lower(*case.args)
    if "tpu_custom_call" not in lowered.as_text():
        raise AssertionError(
            f"{case.name}: no Mosaic custom call in the lowering "
            f"(interpret mode or a JAX path was taken)")
    compiled = lowered.compile()
    out = compiled(*case.args)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(case.reference)(*case.args)
    # twice: state a call leaves behind (a semaphore, a ring slot) would
    # show in the next call of the same executable
    again = compiled(*case.args)
    repeats = bool(jnp.array_equal(out, again))
    call_ms = _call_ms(compiled, case.args)
    others = {
        f"call_ms.{name}": round(_call_ms(
            jax.jit(fn).lower(*case.args).compile(), case.args), 4)
        for name, fn in case.others.items()}
    diff = jnp.abs(_f32(out) - _f32(ref))
    if case.mask is not None:
        diff = diff * case.mask
    err = float(jnp.max(diff))
    if case.relative:
        err /= float(jnp.max(jnp.abs(_f32(ref)))) or 1.0
    finite = bool(jnp.all(jnp.isfinite(_f32(out))))
    zeros = (not case.zero_where_masked
             or not bool(jnp.any(_f32(out) * (1.0 - case.mask))))
    return {"name": case.name,
            "ok": finite and repeats and zeros and err <= case.tol,
            "repeats": repeats, "call_ms": round(call_ms, 4),
            "max_err": round(err, 6), "tol": case.tol,
            "relative": case.relative, "finite": finite,
            "shape": list(out.shape), "why": case.why, **others}


# ---------------------------------------------------------------------
# cases
# ---------------------------------------------------------------------

def _quantize_pages(pages):
    """absmax per page per kv head — the granularity the engine writes"""
    p32 = _f32(pages)
    s = jnp.max(jnp.abs(p32), axis=(1, 3)) / 127.0      # [P, Hkv]
    codes = jnp.clip(jnp.round(
        p32 / jnp.maximum(s, 1e-30)[:, None, :, None]), -127, 127)
    return codes.astype(jnp.int8), s


def decode_case(int8_kv: bool = False) -> KernelCase:
    from kaito_tpu.engine.attention import paged_decode_attention
    from kaito_tpu.engine.ops.decode_attention import (
        paged_decode_attention_pallas)

    B, P, pmax = 32, 2048, 32
    scale = D ** -0.5
    kq, kk, kv, kt, kl = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(kq, (B, H, D), jnp.bfloat16)
    ck = jax.random.normal(kk, (P, PS, HKV, D), jnp.bfloat16)
    cv = jax.random.normal(kv, (P, PS, HKV, D), jnp.bfloat16)
    pt = jax.random.randint(kt, (B, pmax), 0, P, jnp.int32)
    lens = jax.random.randint(kl, (B,), 1, pmax * PS, jnp.int32)
    # ragged on purpose: the kernel carries its page ring from row to
    # row, so a row that decodes nothing (first, between, last), one
    # token, one page exactly and a page boundary sit among long rows.
    # Only the chip shows a copy that was never waited for or a
    # semaphore left signalled for the next row or call.
    lens = lens.at[jnp.asarray([0, 1, 2, 3, 9, 10, B - 1])].set(
        jnp.asarray([0, 1, PS, PS + 1, 0, 0, 0], jnp.int32))
    # a row of length 0 is defined as zeros; the reference's mean over
    # masked columns there is not compared
    live = (lens > 0).astype(jnp.float32)[:, None, None]
    win = jnp.asarray(BIG_WINDOW, jnp.int32)
    live_rows = float(jnp.sum(lens)) * HKV * D
    if not int8_kv:
        # caches are ARGUMENTS, not closure captures: a captured device
        # array becomes a 268 MiB compile-time constant
        return KernelCase(
            "decode_bf16",
            lambda q, ck, cv, pt, lens: paged_decode_attention_pallas(
                q, ck, cv, pt, lens, win, scale=scale),
            lambda q, ck, cv, pt, lens: paged_decode_attention(
                q, ck, cv, pt, lens, scale=scale),
            (q, ck, cv, pt, lens), ATTN_TOL, ATTN_WHY, mask=live,
            zero_where_masked=True,
            unit="live-KV bytes", work=live_rows * 2 * 2)
    k8, ks = _quantize_pages(ck)
    v8, vs = _quantize_pages(cv)
    live_pages = float(jnp.sum(-(-lens // PS)))
    # the reference takes q in fp32 so its dequantized pages are exact:
    # the comparison sees the kernel's arithmetic, not a second rounding
    return KernelCase(
        "decode_int8kv",
        lambda q, k8, v8, ks, vs, pt, lens: paged_decode_attention_pallas(
            q, k8, v8, pt, lens, win, scale=scale, k_scale=ks, v_scale=vs),
        lambda q, k8, v8, ks, vs, pt, lens: paged_decode_attention(
            _f32(q), k8, v8, pt, lens, scale=scale, k_scale=ks, v_scale=vs),
        (q, k8, v8, ks, vs, pt, lens), ATTN_TOL, ATTN_WHY, mask=live,
        zero_where_masked=True,
        unit="live-KV bytes",
        work=live_rows * 2 + live_pages * 2 * HKV * 4)


def mla_decode_case(name: str, rows: int, context: int,
                    ragged: float = 0.25) -> KernelCase:
    """The latent decode kernel at JoyAI-LLM-Flash's widths (32 heads
    against one stream of 512 + 64 at 640 stored lanes, pages of 64):
    ``rows`` rows of ``context`` tokens give or take ``ragged`` of it,
    one idle, against the XLA path over the same token-flat pool.
    ``call_ms`` takes in the two einsums around the kernel.  One row of
    16 gangs gives the cost a gang, the cell's 24 rows what rows add to
    it, and 24 rows near ``max_model_len`` the long end."""
    from kaito_tpu.engine.attention import mla_paged_decode_attention
    from kaito_tpu.engine.ops.mla_decode_attention import (
        mla_paged_decode_attention_pallas)

    heads, dn, dr, dl, dv, lanes, pmax = 32, 128, 64, 512, 128, 640, 80
    P = rows * pmax + 1
    scale = (dn + dr) ** -0.5
    keys = jax.random.split(jax.random.PRNGKey(4), 7)
    q_nope = jax.random.normal(keys[0], (rows, heads, dn), jnp.bfloat16)
    q_rope = jax.random.normal(keys[1], (rows, heads, dr), jnp.bfloat16)
    pool = jnp.pad(jax.random.normal(keys[2], (1, P, PS, dl + dr),
                                     jnp.bfloat16),
                   ((0, 0), (0, 0), (0, 0), (0, lanes - dl - dr)))
    wk = (jax.random.normal(keys[3], (dl, heads * dn), jnp.float32)
          / math.sqrt(dl)).astype(jnp.bfloat16)
    wv = (jax.random.normal(keys[4], (dl, heads * dv), jnp.float32)
          / math.sqrt(dl)).astype(jnp.bfloat16)
    pt = jax.random.permutation(keys[5], jnp.arange(1, P, dtype=jnp.int32)
                                ).reshape(rows, pmax)
    lens = jax.random.randint(
        keys[6], (rows,), int(context * (1 - ragged)),
        min(int(context * (1 + ragged)), pmax * PS), jnp.int32)
    if rows > 2:
        lens = lens.at[jnp.asarray([1, rows - 1])].set(
            jnp.asarray([0, PS], jnp.int32))
    live = (lens > 0).astype(jnp.float32)[:, None, None]
    layer = jnp.int32(0)

    def kernel(q_nope, q_rope, pool, pt, lens):
        q_lat = jnp.einsum("bhd,lhd->bhl", q_nope, wk.reshape(dl, heads, dn),
                           preferred_element_type=jnp.float32)
        q = jnp.concatenate(
            [q_lat * scale, _f32(q_rope) * scale,
             jnp.zeros((rows, heads, lanes - dl - dr), jnp.float32)],
            -1).astype(jnp.bfloat16)
        out = mla_paged_decode_attention_pallas(q, pool, pt, lens, layer,
                                                value_lanes=dl)
        return jnp.einsum("bhl,lhd->bhd", out, wv.reshape(dl, heads, dv),
                          preferred_element_type=jnp.float32
                          ).astype(jnp.bfloat16) * live.astype(jnp.bfloat16)

    def reference(q_nope, q_rope, pool, pt, lens):
        return mla_paged_decode_attention(
            q_nope, q_rope, pool, pt, lens, wk, wv, scale=scale,
            kv_lora_rank=dl, layer=layer)

    return KernelCase(
        name, kernel, reference, (q_nope, q_rope, pool, pt, lens),
        ATTN_TOL, ATTN_WHY + "; the absorbed query is rounded to bf16 too",
        mask=live, zero_where_masked=True, unit="live-latent bytes",
        work=float(jnp.sum(lens)) * (dl + dr) * 2,
        others={"xla": reference})


# flash prefill's geometries: (name, B, T, H, Hkv, D, Dv, window, sink,
# true_len).  phi-4-mini's, ragged; then MiMo-V2.5's two kinds at its
# longest bucket (64 query heads on 4 and on 8 KV heads, keys stored at
# 256 lanes, values of 128; the window kind with its window of 128 and
# a sink bias a head): 16 and 8 query heads stacked on each key block,
# and 1,536 rows of padding whose query blocks the kernel walks past
# and writes as zeros.
PREFILL_GEOMETRIES = {
    "flash_prefill": (4, 1024, H, HKV, D, D, None, False,
                      (1024, 768, 127, 1)),
    "flash_prefill_gqa16": (1, 4096, 64, 4, 256, 128, None, False, (2560,)),
    "flash_prefill_window_sink": (1, 4096, 64, 8, 256, 128, 128, True,
                                  (2560,)),
    # JoyAI-LLM-Flash's expanded heads: one KV head a query head
    "flash_prefill_mla32": (1, 4096, 32, 32, 256, 128, None, False,
                            (3000,)),
}


def prefill_case(name: str = "flash_prefill") -> KernelCase:
    from kaito_tpu.engine.attention import prefill_attention
    from kaito_tpu.engine.ops.flash_prefill import flash_prefill_attention

    B, T, Hq, Hkv, Dk, Dv, window, sink_on, lens = PREFILL_GEOMETRIES[name]
    G = Hq // Hkv
    scale = Dk ** -0.5
    kq, kk, kv, ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(kq, (B, T, Hq, Dk), jnp.bfloat16)
    k = jax.random.normal(kk, (B, T, Hkv, Dk), jnp.bfloat16)
    v = jax.random.normal(kv, (B, T, Hkv, Dv), jnp.bfloat16)
    tl = jnp.asarray(lens, jnp.int32)
    win = jnp.asarray(window or BIG_WINDOW, jnp.int32)
    # near log(window), where a sink takes a real share of a row
    sink = (math.log(128.0) + jax.random.normal(ks, (Hq,), jnp.float32)
            if sink_on else None)
    # rows past true_len are padding the engine never reads; where they
    # fill whole query blocks (true_len a multiple of every tile) the
    # kernel owes zeros
    mask = (jnp.arange(T)[None, :, None, None]
            < tl[:, None, None, None]).astype(jnp.float32)

    def reference(q, k, v, tl):
        # a KV head at a time: the [G, T, T] float32 scores of all 64
        # heads at once are 4 GiB
        def one(h):
            qh = jax.lax.dynamic_slice_in_dim(q, h * G, G, axis=2)
            kh = jax.lax.dynamic_slice_in_dim(k, h, 1, axis=2)
            vh = jax.lax.dynamic_slice_in_dim(v, h, 1, axis=2)
            sh = (jax.lax.dynamic_slice_in_dim(sink, h * G, G)
                  if sink_on else None)
            return prefill_attention(qh, kh, vh, scale=scale, true_len=tl,
                                     sliding_window=window, sink=sh)
        out = jax.lax.map(one, jnp.arange(Hkv))      # [Hkv, B, T, G, Dv]
        return out.transpose(1, 2, 0, 3, 4).reshape(B, T, Hq, Dv)

    live = float(sum(n * (n + 1) // 2 if window is None else
                     sum(min(p + 1, window) for p in range(n))
                     for n in lens))
    return KernelCase(
        name,
        lambda q, k, v, tl: flash_prefill_attention(
            q, k, v, tl, win, scale=scale, sink=sink),
        reference,
        (q, k, v, tl), ATTN_TOL, ATTN_WHY, mask=mask,
        zero_where_masked=all(n % 512 == 0 for n in lens),
        unit="live causal FLOPs", work=2.0 * Hq * (Dk + Dv) * live)


def gemv_case(scheme: str, prefetch: bool = False) -> KernelCase:
    from kaito_tpu.engine.ops.quant_matmul import (dequant_matmul_jax,
                                                   kernel_plan, prefetch_ok,
                                                   quant_matmul)
    from kaito_tpu.engine.quant import quantize_weight

    # phi-4-mini's MLP-down projection at a decode batch of 8
    rows, K, N = 8, 8192, 3072
    kx, kw, kn = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(kx, (rows, K), jnp.bfloat16)
    quantize = jax.jit(lambda w: quantize_weight(w, scheme))
    qw = quantize(jax.random.normal(kw, (K, N), jnp.float32))
    if scheme == "int4":
        w_bytes = K * N / 2 + 4 * qw["scale"].shape[-2] * N
    else:
        w_bytes = K * N + 4 * N
    args = (x, qw)
    kernel = quant_matmul
    if prefetch:
        # the next layer's slab rides the grid under a runtime-false
        # predicate: same output, two more input streams to compile
        nxt = quantize(jax.random.normal(kn, (K, N), jnp.float32))
        if not prefetch_ok(kernel_plan(rows, qw), nxt):
            raise AssertionError(f"gemv_{scheme}: prefetch stream does "
                                 f"not fit the kernel plan")
        args = (x, qw, nxt)
    return KernelCase(
        f"gemv_{scheme}" + ("_prefetch" if prefetch else ""),
        kernel,
        lambda x, qw, *_: dequant_matmul_jax(_f32(x), qw),
        args, GEMV_TOL, GEMV_WHY, relative=True,
        unit="weight bytes", work=w_bytes)


def ssm_case() -> KernelCase:
    """The selective state update at Falcon-H1-34B's mixer widths, on
    layer 1 of a two-layer bfloat16 pool (the type it is served in),
    from a non-zero state.  Ragged on
    purpose: rows that decode nothing first, between and last.  The
    result is the layer's new state less what an idle row held, and y:
    an idle row then reads exact zeros if and only if the kernel left
    its state bit for bit and wrote it no y."""
    from kaito_tpu.engine.ops import ssm

    L, S, Hm, Pm, Gm, Nm = 2, 32, 32, 128, 2, 256
    kp, kx, kd, kb, kc, ka = jax.random.split(jax.random.PRNGKey(4), 6)
    pool = jax.random.normal(kp, (L, S, Hm, Pm, Nm), jnp.bfloat16)
    x = jax.random.normal(kx, (S, Hm, Pm), jnp.float32)
    dt = jnp.exp(jax.random.uniform(kd, (S, Hm), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    B = jax.random.normal(kb, (S, Gm, Nm), jnp.float32)
    C = jax.random.normal(kc, (S, Gm, Nm), jnp.float32)
    A = -jax.random.uniform(ka, (Hm,), jnp.float32, 1.0, 16.0)
    active = jnp.ones((S,), bool).at[
        jnp.asarray([0, 1, 9, 10, 17, S - 1])].set(False)
    live = active.astype(jnp.float32)[:, None]

    def pack(pool_in, new_pool, y):
        idle = jnp.where(active[:, None, None, None], 0.0, _f32(pool_in[1]))
        y = jnp.where(active[:, None, None], y, 0.0)
        return jnp.concatenate([(_f32(new_pool[1]) - idle).reshape(S, -1),
                                y.reshape(S, -1)], axis=1)

    def kernel(pool, x, dt, B, C):
        rows, n_live = ssm.live_rows(active)
        new_pool, y = ssm.ssm_state_update(pool, jnp.int32(1), rows, n_live,
                                           x, dt, A, B, C)
        return pack(pool, new_pool, y)

    def reference(pool, x, dt, B, C):
        new_pool, y = ssm.ssm_state_update_jax(pool, 1, x, dt, A, B, C,
                                               active)
        return pack(pool, new_pool, y)

    return KernelCase(
        "ssm_state_update", kernel, reference, (pool, x, dt, B, C), 0.04,
        "both sides round one float32 state to bfloat16: a last place can "
        "move one step, 0.031 at |h| in 4..8; y float32, a 256-term sum",
        mask=live, zero_where_masked=True, unit="live-state bytes",
        work=float(jnp.sum(active)) * 2 * Hm * Pm * Nm * 2)


def combine_case() -> KernelCase:
    """An expert layer's rows back to their tokens at MiMo-V2.5's
    longest prefill bucket on one of sixteen chips: 4,096 rows of which
    2,489 are a prompt, 8 of 256 experts a token, 16 held, so one pass
    of 4,096 sorted rows of which about 1,250 hold a pair.  The kernel
    with its sort against the gathers of all eight slots with the
    scatter that builds their places, bit for bit; ``call_ms.loop`` is
    the gathers' time."""
    from kaito_tpu.engine import nn

    T, E, k, X, held, n_valid = 4096, 4096, 8, 256, 16, 2489
    cap = T                                  # nn.moe_mlp_ragged's, 16 shards
    kr, ko = jax.random.split(jax.random.PRNGKey(5))
    _, idx = jax.lax.top_k(jax.random.uniform(kr, (T, X)), k)
    here = (idx < held) & (jnp.arange(T) < n_valid)[:, None]
    order = jnp.argsort(jnp.where(here, idx, held).reshape(-1))
    live = jnp.arange(cap) < jnp.sum(here)
    out = jnp.where(live[:, None],
                    jax.random.normal(ko, (cap, E), jnp.float32), 0.0)

    def loop(out, order, live):
        place = jnp.zeros((T * k,), jnp.int32).at[order].set(
            jnp.arange(T * k, dtype=jnp.int32))
        return nn._combine_slots(jnp.zeros((T, E), jnp.float32), out,
                                 place.reshape(T, k), cap)

    def kernel(out, order, live):
        return nn._combine_held(jnp.zeros((T, E), jnp.float32), out,
                                order[:cap].astype(jnp.int32), live, k,
                                fresh=jnp.bool_(True))

    return KernelCase(
        "moe_combine_ep16", kernel, loop, (out, order, live), 0.0,
        "the same float32 sums in the same order, less exact zeros",
        unit="live-row bytes", work=float(jnp.sum(live)) * E * 4 + T * E * 4,
        others={"loop": loop})


CASES: dict[str, Callable[[], KernelCase]] = {
    "ssm_state_update": ssm_case,
    "decode_bf16": decode_case,
    "decode_int8kv": lambda: decode_case(int8_kv=True),
    "flash_prefill": prefill_case,
    "flash_prefill_gqa16": lambda: prefill_case("flash_prefill_gqa16"),
    "flash_prefill_window_sink":
        lambda: prefill_case("flash_prefill_window_sink"),
    "flash_prefill_mla32": lambda: prefill_case("flash_prefill_mla32"),
    "mla_decode_24x3k": lambda: mla_decode_case("mla_decode_24x3k", 24, 3072),
    "mla_decode_1x4k": lambda: mla_decode_case("mla_decode_1x4k", 1, 4096),
    "mla_decode_24x5k": lambda: mla_decode_case("mla_decode_24x5k", 24, 4864,
                                                ragged=0.05),
    "gemv_int8": lambda: gemv_case("int8"),
    "gemv_int8_prefetch": lambda: gemv_case("int8", prefetch=True),
    "gemv_int4": lambda: gemv_case("int4"),
    "gemv_int4_prefetch": lambda: gemv_case("int4", prefetch=True),
    "moe_combine_ep16": combine_case,
}


def run_parity(only: Optional[list] = None) -> int:
    """The chip smoke's kernels leg (``only``: just those cases).  A
    case that does not compile is a failed case with the compiler's
    message, not a skipped one."""
    dev = jax.devices()[0]
    failed = 0
    for name in only or CASES:
        build = CASES[name]
        try:
            res = parity(build())
        except Exception as e:   # report every kernel, then fail the run
            res = {"name": name, "ok": False,
                   "error": f"{type(e).__name__}: {e}"[:2000]}
        failed += not res["ok"]
        print(json.dumps(res), flush=True)
    print(json.dumps({"kernels_ok": failed == 0, "failed": failed,
                      "platform": dev.platform,
                      "device_kind": dev.device_kind,
                      "device_count": jax.device_count()}), flush=True)
    return 1 if failed else 0


def main() -> None:
    from kaito_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--parity", action="store_true", required=True,
                    help="compile and check every kernel; JSON lines")
    ap.add_argument("--only", default="",
                    help="comma-separated case names; default every case")
    args = ap.parse_args()
    sys.exit(run_parity([n for n in args.only.split(",") if n] or None))


if __name__ == "__main__":
    main()
