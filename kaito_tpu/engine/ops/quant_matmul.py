"""Pallas TPU kernel: fused dequant matmul for quantized weights.

The decode-step GEMV/skinny-GEMM against an int8 or packed-int4
QTensor (engine/quant.py), with the same dequant-after-DMA discipline
as the int8 KV decode kernel (ops/decode_attention.py): the grid
pipelines the QUANTIZED weight blocks and their scale rows into VMEM
(pallas double-buffers each input stream on its own ring), the kernel
unpacks/dequants in-register, and partial products accumulate in an
fp32 VMEM scratch — so the HBM stream is the quantized bytes by
construction, never a materialized bf16 copy of the weight.

Layout contract (engine/quant.py): int4 packs ADJACENT in-row pairs
(row 2i low nibble, row 2i+1 high nibble) and every weight chunk the
kernel sees is a run of WHOLE scale groups, so each group's scale folds
POST-dot:

    acc += (x_even_g @ lo_nibbles_g + x_odd_g @ hi_nibbles_g) * s_g

The even/odd x columns are two cheap strided slices of the (tiny)
activation taken once outside the kernel and laid out group-major
``[G, rows, g/2]`` — no in-kernel interleave, transpose or sub-tile
lane slice, which Mosaic would serialize or refuse.

``quant_linear`` is the nn.linear entry point: it picks the kernel for
decode-shaped calls (rows <= MAX_ROWS, tileable shapes) on TPU and the
pure-JAX unpack-then-dot fallback everywhere else (CPU tests, prefill,
odd shapes).  KAITO_QUANT_MATMUL=auto|pallas|interpret|jax overrides
the choice (read at trace time; 'interpret' runs the kernel in
interpreter mode so CPU tests cover the kernel path end-to-end, and is
the only way to get the interpreter: 'pallas' off a TPU is an error).
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kaito_tpu.engine.quant import dequant_weight, int4_group_size

# decode/verify batches are skinny (max_num_seqs, or batch * spec
# window); anything wider is prefill-shaped and belongs on the MXU via
# the plain dot with XLA-fused dequant
MAX_ROWS = 256

# int8 chunk: in-rows per inner grid step
_INT8_CHUNK = 512

# int4 chunk: scale groups per inner grid step.  The chunk's scale block
# is [groups, tn], and Mosaic tiles the second-minor block dim by 8
# unless it spans the array — so a chunk is 8 groups, or all of them
# when the group count is not a multiple of 8 (bounded: the per-group
# dots unroll)
_INT4_GROUPS = 8
_INT4_MAX_GROUPS = 32

# layer-ahead weight prefetch (docs/multichip.md): the L+1 slab rides
# the same grid as two extra double-buffered input streams, so its
# HBM->VMEM DMA issues while layer L's ring hops drain.  Bounded VMEM
# budget: the prefetch streams' double-buffered blocks must fit under
# this cap or the call silently drops back to the plain (no-prefetch)
# grid — never a compile failure, never a numerics change.
_PREFETCH_VMEM_BUDGET = 4 << 20


def _pick_tn(N: int):
    """Out-tile width: lane-dim friendly when possible."""
    for cand in (512, 256, 128):
        if N % cand == 0:
            return cand
    return N if N <= 1024 else None


def _pick_int8_chunk(K: int):
    for cand in (_INT8_CHUNK, 256, 128, 64):
        if K % cand == 0:
            return cand
    return K if K <= _INT8_CHUNK else None


def kernel_plan(rows: int, w: dict):
    """(grid, tiles) for the fused kernel, or None when the shape
    doesn't tile (the caller falls back to pure JAX).  w is a PER-LAYER
    QTensor (2-D planes) — the scan body has already sliced the stack.
    """
    if rows > MAX_ROWS:
        return None
    if "q8" in w:
        if w["q8"].ndim != 2:
            return None
        K, N = w["q8"].shape
        tk = _pick_int8_chunk(K)
        tn = _pick_tn(N)
        if tk is None or tn is None:
            return None
        return {"kind": "int8", "K": K, "N": N, "tk": tk, "tn": tn}
    if w["q4"].ndim != 2:
        return None
    Kq, N = w["q4"].shape
    K = 2 * Kq
    g = int4_group_size(w)
    tn = _pick_tn(N)
    if tn is None or g % 2 or K % g:
        return None
    groups = K // g
    ng = _INT4_GROUPS if groups % _INT4_GROUPS == 0 else groups
    if ng > _INT4_MAX_GROUPS:
        return None
    return {"kind": "int4", "K": K, "N": N, "tk": ng * g, "tn": tn, "g": g}


def _int8_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, n_chunks):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _zero():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # dequant-after-DMA: the block arrived int8; widen in-register and
    # fold the per-out-channel scale after the dot (exact: one scale
    # row covers the whole contraction)
    part = jax.lax.dot_general(
        x_ref[:], w_ref[:].astype(x_ref.dtype),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    acc_ref[:] += part * s_ref[0].astype(jnp.float32)

    @pl.when(c == n_chunks - 1)
    def _flush():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def _int4_kernel(xe_ref, xo_ref, w_ref, s_ref, o_ref, acc_ref, *,
                 n_chunks):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _zero():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    ng, _, gq = xe_ref.shape          # groups in this chunk, packed rows each
    acc = jnp.zeros(acc_ref.shape, jnp.float32)
    for gi in range(ng):
        # unpack both nibble planes in-register ( & 0xFF kills the int8
        # sign extension from the widening)
        p = w_ref[gi * gq:(gi + 1) * gq, :].astype(jnp.int32) & 0xFF
        lo = ((p & 0xF) - 8).astype(xe_ref.dtype)
        hi = (((p >> 4) & 0xF) - 8).astype(xe_ref.dtype)
        part = jax.lax.dot_general(
            xe_ref[gi], lo, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        part += jax.lax.dot_general(
            xo_ref[gi], hi, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # one scale group per dot, so the group scale folds post-dot
        acc += part * s_ref[gi:gi + 1, :].astype(jnp.float32)
    acc_ref[:] += acc

    @pl.when(c == n_chunks - 1)
    def _flush():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def prefetch_ok(plan: dict, w_next: Optional[dict]) -> bool:
    """Whether the L+1 slab can ride this plan's grid: same kind and
    plane shapes (one scan body serves every layer, so the stacked
    slabs always match), and the two extra double-buffered streams fit
    the VMEM budget."""
    if w_next is None or plan is None:
        return False
    kind = "q8" if "q8" in w_next else "q4"
    if kind != ("q8" if plan["kind"] == "int8" else "q4"):
        return False
    tk, tn = plan["tk"], plan["tn"]
    if plan["kind"] == "int8":
        if w_next["q8"].shape != (plan["K"], plan["N"]):
            return False
        block = tk * tn + 4 * tn            # int8 slab + f32 scale row
    else:
        if w_next["q4"].shape != (plan["K"] // 2, plan["N"]):
            return False
        # packed slab + one f32 scale row per group
        block = (tk // 2) * tn + 4 * (tk // plan["g"]) * tn
    return 2 * block <= _PREFETCH_VMEM_BUDGET


def _prefetch_touch(flag_ref, nw_ref, ns_ref, acc_ref, *, n_chunks):
    """DCE-proof liveness anchor for the L+1 streams: the runtime flag
    is the constant 0, so the body NEVER executes (numerics stay
    bit-identical to the plain grid) — but the compiler can't prove a
    runtime scalar false, so the blocks keep their places on the
    pipeline's input rings and their HBM->VMEM DMA issues a block
    ahead, exactly like the live streams."""
    c = pl.program_id(1)

    @pl.when((c == n_chunks - 1) & (flag_ref[0, 0] != 0))
    def _touch():
        acc_ref[:] += (nw_ref[:].astype(jnp.float32).sum()
                       + ns_ref[:].astype(jnp.float32).sum())


def _int8_kernel_pf(x_ref, w_ref, s_ref, flag_ref, nw_ref, ns_ref,
                    o_ref, acc_ref, *, n_chunks):
    _int8_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, n_chunks=n_chunks)
    _prefetch_touch(flag_ref, nw_ref, ns_ref, acc_ref, n_chunks=n_chunks)


def _int4_kernel_pf(xe_ref, xo_ref, w_ref, s_ref, flag_ref, nw_ref,
                    ns_ref, o_ref, acc_ref, *, n_chunks):
    _int4_kernel(xe_ref, xo_ref, w_ref, s_ref, o_ref, acc_ref,
                 n_chunks=n_chunks)
    _prefetch_touch(flag_ref, nw_ref, ns_ref, acc_ref, n_chunks=n_chunks)


@functools.partial(jax.jit, static_argnames=("interpret",))
def quant_matmul(x: jax.Array, w: dict, w_next: Optional[dict] = None,
                 *, interpret: bool = False) -> jax.Array:
    """x: [rows, K] (rows <= MAX_ROWS) @ QTensor w -> [rows, N].

    Caller must have checked kernel_plan(rows, w) is not None.

    ``w_next`` is the NEXT layer's slab (same QTensor layout): its
    quantized blocks + scale rows join the grid as two more
    double-buffered input streams, so the L+1 HBM->VMEM DMA starts
    while this layer's output collective drains (docs/multichip.md).
    The streams are read only under a runtime-false predicate — output
    is bit-identical with or without them.  Caller gates on
    ``prefetch_ok``.
    """
    rows = x.shape[0]
    plan = kernel_plan(rows, w)
    if plan is None:
        raise ValueError(
            f"no kernel plan for rows={rows}, w shapes "
            f"{jax.tree.map(jnp.shape, w)}")
    K, N, tk, tn = plan["K"], plan["N"], plan["tk"], plan["tn"]
    n_chunks = K // tk
    grid = (N // tn, n_chunks)
    scale = w["scale"]
    pf = w_next is not None
    flag = jnp.zeros((1, 1), jnp.int32)     # runtime-false; see _prefetch_touch
    pf_specs = [
        pl.BlockSpec((1, 1), lambda j, c: (0, 0),
                     memory_space=pltpu.SMEM),
    ]

    if plan["kind"] == "int8":
        kernel = functools.partial(
            _int8_kernel_pf if pf else _int8_kernel, n_chunks=n_chunks)
        in_specs = [
            pl.BlockSpec((rows, tk), lambda j, c: (0, c)),
            pl.BlockSpec((tk, tn), lambda j, c: (c, j)),
            pl.BlockSpec((1, tn), lambda j, c: (0, j)),
        ]
        operands = (x, w["q8"], scale.reshape(1, N))
        if pf:
            in_specs += pf_specs + [
                pl.BlockSpec((tk, tn), lambda j, c: (c, j)),
                pl.BlockSpec((1, tn), lambda j, c: (0, j)),
            ]
            operands += (flag, w_next["q8"],
                         w_next["scale"].reshape(1, N))
    else:
        kernel = functools.partial(
            _int4_kernel_pf if pf else _int4_kernel, n_chunks=n_chunks)
        # the two nibble-plane activations: even/odd in-rows of x
        # (packed byte row i holds original rows 2i and 2i+1), laid out
        # group-major [G, rows, gq] so the kernel picks a group's
        # columns by leading index
        gq = plan["g"] // 2              # packed rows per scale group
        ng = tk // plan["g"]             # scale groups per chunk
        tkq = tk // 2                    # packed rows per chunk

        def plane(xs):
            return xs.reshape(rows, K // plan["g"], gq).transpose(1, 0, 2)

        in_specs = [
            pl.BlockSpec((ng, rows, gq), lambda j, c: (c, 0, 0)),
            pl.BlockSpec((ng, rows, gq), lambda j, c: (c, 0, 0)),
            pl.BlockSpec((tkq, tn), lambda j, c: (c, j)),
            pl.BlockSpec((ng, tn), lambda j, c: (c, j)),
        ]
        operands = (plane(x[:, 0::2]), plane(x[:, 1::2]), w["q4"], scale)
        if pf:
            in_specs += pf_specs + [
                pl.BlockSpec((tkq, tn), lambda j, c: (c, j)),
                pl.BlockSpec((ng, tn), lambda j, c: (c, j)),
            ]
            operands += (flag, w_next["q4"], w_next["scale"])

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((rows, tn), lambda j, c: (0, j)),
        out_shape=jax.ShapeDtypeStruct((rows, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((rows, tn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)


def dequant_matmul_jax(x: jax.Array, w: dict) -> jax.Array:
    """Pure-JAX fallback: int8 keeps the fused dequant-into-dot form
    (XLA reads the int8 bytes and fuses the convert); int4 unpacks then
    dots (the unpack is elementwise, so XLA can still fuse it — the
    guarantee of reading only quantized bytes is the kernel's job)."""
    if "q8" in w:
        return (x @ w["q8"].astype(x.dtype)) * w["scale"].astype(x.dtype)
    return x @ dequant_weight(w, x.dtype)


def _impl_mode() -> str:
    """auto | pallas | interpret | jax (trace-time escape hatch)."""
    return os.environ.get("KAITO_QUANT_MATMUL", "auto")


def quant_linear(x: jax.Array, w: dict,
                 prefetch: Optional[dict] = None) -> jax.Array:
    """nn.linear entry point for QTensor weights: fused Pallas kernel
    for decode-shaped calls on TPU, pure-JAX fallback otherwise.

    The branch is trace-time static (shapes + backend + env), so each
    jitted program bakes in exactly one path.  ``prefetch`` (the next
    layer's slab, threaded by the comm-overlap decode path) only
    engages on the kernel path and only when it fits the VMEM budget —
    everywhere else it is dropped, never a behavior change.
    """
    with jax.named_scope("quant_matmul"):
        return _quant_linear(x, w, prefetch)


def _quant_linear(x: jax.Array, w: dict,
                  prefetch: Optional[dict] = None) -> jax.Array:
    mode = _impl_mode()
    lead, K = x.shape[:-1], x.shape[-1]
    rows = 1
    for d in lead:
        rows *= d
    use_kernel = False
    if mode in ("pallas", "interpret"):
        use_kernel = True
    elif mode == "auto":
        use_kernel = jax.default_backend() == "tpu"
    plan = kernel_plan(rows, w) if use_kernel and rows > 0 else None
    if plan is not None:
        w_next = prefetch if prefetch_ok(plan, prefetch) else None
        out = quant_matmul(x.reshape(rows, K), w, w_next,
                           interpret=mode == "interpret")
        return out.reshape(*lead, out.shape[-1])
    return dequant_matmul_jax(x, w)
