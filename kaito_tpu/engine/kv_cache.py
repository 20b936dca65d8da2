"""Paged KV cache.

The engine's KV memory is a global page pool per layer —
``[num_layers, num_pages, page_size, kv_heads, head_dim]`` — addressed
through per-sequence page tables, vLLM-style but with static shapes
throughout so XLA compiles one program per (bucket, batch) shape.  The
reference delegates this entirely to vLLM's PagedAttention
(SURVEY.md §2.3); on TPU we own it.

The layout is page-major and TOKEN-major within a page: each page is
one contiguous ``[page_size, kv_heads, head_dim]`` block in HBM (a
single clean leading-index DMA per page in the Pallas decode kernel)
and each token's row is one ``[kv_heads, head_dim]`` tile.  That tile
is exactly what a decode step writes, so the write is a scatter whose
update window is minor-dim-contiguous — XLA keeps the default layout
for it.  (With the head-major order the scatter preferred a transposed
layout while the Mosaic custom call pinned the default one, and XLA
reconciled them with a full-cache copy per layer: 64 GiB/step of pure
layout conversion at phi-4-mini bench shapes.)

Page 0 is reserved as the null page: unused page-table slots point at
it, so gathers are always in-bounds and masking is done by length, not
by index validity.

Quantized mode (``kv_dtype="int8"``): the pools store int8 codes plus a
per-page-per-head fp32 scale tensor ``[L, num_pages, kv_heads]`` carried
in the same pytree.  Writes quantize with a *rescale-on-grow* fold: the
written tile's absmax is folded into the page scale
(sigma_new = max(sigma_old, absmax/127)) and, when the scale grows, the
page's existing codes are re-quantized at the new scale in the same
scatter — so dequantization ``code * sigma`` stays correct for every
token a page holds, not just the last-written one.  Reads dequantize
either inside the Pallas decode kernel (scales ride the page DMA) or
after the gather on the pure-JAX paths.  The null page accumulates
garbage codes AND garbage scales by design; length masking hides both.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from kaito_tpu.models.metadata import ModelArch, stored_key_dim

NULL_PAGE = 0


@jax.tree_util.register_dataclass
@dataclass
class KVCache:
    """Stacked per-layer page pools (a pytree; donate on every step)."""

    k: jax.Array  # [L, num_pages, page_size, kv_heads, head_dim]
    v: jax.Array
    # Per-page-per-head dequantization scales, fp32 [L, num_pages, kv_heads];
    # None for non-quantized pools (None is a valid empty pytree leaf, so
    # the bf16 mode's scan carries and donation are untouched).
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None
    # The second kind of state (docs/kv-cache.md): a model with a
    # state-space mixer beside attention keeps, per decode slot and not
    # per page, the mixer's recurrent state
    # [L, slots, ssm_heads, ssm_head_dim, ssm_state] and the last
    # ssm_conv-1 inputs of its convolution
    # [L, slots, ssm_conv-1, conv channels], both in the type the model
    # is served in.  A slot's row costs the same at token 1 and token
    # 1,000; no page carries any of it.  None for every other model.
    ssm_state: Optional[jax.Array] = None
    ssm_conv: Optional[jax.Array] = None
    # A row of conv state (docs/kv-cache.md): a model some of whose
    # layers mix tokens by a short convolution and not by attention
    # (lfm2) keeps for those layers, per decode slot, the last
    # conv_kernel-1 inputs of the convolution, [conv layers, slots,
    # conv_kernel-1, hidden], in the type the model is served in; those
    # layers have no page, and the page pools hold the attention layers
    # alone.  None for every other model.
    conv_state: Optional[jax.Array] = None
    # A row of matrix state (docs/kv-cache.md): a model some of whose
    # layers mix tokens by a gated delta rule (olmo_hybrid) keeps for
    # those layers, per decode slot, a key x value matrix a head,
    # [delta layers, slots, key dim, heads * value dim] (keys on
    # sublanes, the heads' values side by side on lanes: whole lane
    # tiles with no padding), and in ``conv_state`` the last inputs of
    # the convolutions in front of it, [delta layers, slots, taps - 1,
    # channels]; those layers have no page.  None for every other model.
    delta_state: Optional[jax.Array] = None
    # Two kinds of page (docs/kv-cache.md): a model whose window layers
    # have a geometry of their own (mimo_v2) keeps those layers' keys
    # and values in a second pair of pools, addressed through a second
    # page table a sequence; ``k`` and ``v`` then hold the full layers
    # alone.  All four pools of such a model are TOKEN-FLAT, as the
    # decode kernel reads a page: [layers, pages, page_size * kv heads,
    # dim], token t of a page in rows t * kv heads and on (a key of
    # two lane tiles under four KV heads made the five-dimensional
    # form's merge of its two middle axes a copy of the pool a step).
    # A window page goes back to its pool once every position in it is
    # a window behind the sequence's next token.  None for every other
    # model.
    wk: Optional[jax.Array] = None
    wv: Optional[jax.Array] = None
    # An expert layer's counters over the decode steps of one program:
    # int32 [held experts (one call each), held experts that got a
    # pair, pairs held, pairs routed].  The decode programs zero it,
    # the layers add to it and the program returns it with its tokens.
    # None for a model with no expert layer that is shared.
    moe_stats: Optional[jax.Array] = None

    @property
    def num_pages(self) -> int:
        return self.k.shape[1]

    @property
    def page_size(self) -> int:
        return self.k.shape[2]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def window_pool_bytes(self) -> int:
        """Bytes of the window kind's pools (0: one kind of page)."""
        return 0 if self.wk is None else int(self.wk.nbytes + self.wv.nbytes)

    @property
    def state_pool_bytes(self) -> int:
        """Bytes of the per-slot state pool (0: a model with none)."""
        return sum(int(pool.nbytes) for pool in (
            self.ssm_state, self.ssm_conv, self.conv_state,
            self.delta_state) if pool is not None)


def kv_cache_is_quantized(dtype) -> bool:
    return jnp.dtype(dtype) == jnp.int8


def scale_bytes_per_page(arch: ModelArch) -> int:
    """HBM overhead of the two fp32 scale rows one page carries."""
    return 2 * arch.num_layers * arch.kv_cache_heads * 4


def create_state_pool(arch: ModelArch, slots: int, dtype: jnp.dtype):
    """Zeroed (state, convolution tail) pools for ``slots`` decode
    slots, or (None, None) for a model with no state-space mixer.
    ``dtype`` is the model's: the step programs compute the recurrence
    in float32 and round once, where a state is written back."""
    if not arch.ssm_state:
        return None, None
    L = arch.num_layers
    return (jnp.zeros((L, slots, arch.ssm_heads, arch.ssm_head_dim,
                       arch.ssm_state), dtype),
            jnp.zeros((L, slots, arch.ssm_conv - 1, arch.ssm_conv_dim),
                      dtype))


def create_conv_state_pool(arch: ModelArch, slots: int, dtype: jnp.dtype):
    """Zeroed rows of conv state for ``slots`` decode slots, or None
    for a model with no short-convolution layer."""
    if not arch.conv_layers:
        return None
    return jnp.zeros((arch.conv_layers, slots, arch.conv_kernel - 1,
                      arch.hidden_size), dtype)


def create_delta_state_pool(arch: ModelArch, slots: int, dtype: jnp.dtype):
    """Zeroed (matrix state, convolution tail) pools of a model with
    delta-rule layers for ``slots`` decode slots, in the model's type
    (the step programs compute in float32 and round once, where a state
    is written back)."""
    L = arch.gdn_layers
    return (jnp.zeros((L, slots, arch.gdn_key_dim,
                       arch.gdn_heads * arch.gdn_value_dim), dtype),
            jnp.zeros((L, slots, arch.gdn_conv - 1, arch.gdn_conv_dim),
                      dtype))


def create_kv_cache(
    arch: ModelArch,
    num_pages: int,
    page_size: int,
    dtype: jnp.dtype = jnp.bfloat16,
    window_pages: int = 0,
    latent_kernel: bool = False,
) -> KVCache:
    """``latent_kernel``: a latent-attention model's pool is read by the
    Pallas decode kernel (``ops/mla_decode_attention.py``) and laid out
    as that reads a page, token-flat at the stored lanes: [layers,
    pages, page_size, ``arch.latent_lanes``], the lanes past the latent
    zero (docs/kv-cache.md, "Latent pages")."""
    if arch.layer_attention is not None:
        # a pair of pools an attention kind, each with its own geometry
        if kv_cache_is_quantized(dtype):
            raise ValueError("an int8 KV cache is not implemented for a "
                             "model with two kinds of page")

        def pools(kind, pages):
            layers, heads, dk, dv = arch.kv_page_geometry(kind)
            # (heads narrower than a lane tile lie ``n`` to a row)
            n = arch.kv_heads_per_row(kind)
            rows = page_size * heads // n
            return (jnp.zeros((layers, pages, rows, n * stored_key_dim(dk)),
                              dtype),
                    jnp.zeros((layers, pages, rows, n * dv), dtype))

        k, v = pools(0, num_pages)
        wk, wv = pools(1, window_pages) if arch.two_kind_cache \
            else (None, None)
        return KVCache(
            k=k, v=v, wk=wk, wv=wv,
            moe_stats=(jnp.zeros((4,), jnp.int32)
                       if arch.num_experts and arch.layer_experts
                       and any(arch.layer_experts) else None))
    shape = (arch.num_layers, num_pages, page_size, arch.kv_cache_heads,
             arch.kv_cache_dim)
    k_scale = v_scale = None
    if kv_cache_is_quantized(dtype):
        # Zero scales dequantize the zeroed pool to exact zeros; scales
        # only grow as real tokens land in a page.
        sshape = (arch.num_layers, num_pages, arch.kv_cache_heads)
        k_scale = jnp.zeros(sshape, jnp.float32)
        v_scale = jnp.zeros(sshape, jnp.float32)
    if arch.attention_kind.value == "MLA":
        # MLA caches one latent stream; `k` holds it, `v` is a
        # zero-size placeholder keeping the pytree uniform
        if latent_kernel:
            if k_scale is not None:
                raise ValueError("the latent decode kernel reads a bf16 "
                                 "pool; an int8 latent pool keeps the "
                                 "[L, P, ps, 1, dl+dr] layout")
            shape = shape[:3] + (arch.latent_lanes,)
        # a shared expert layer's counters ride the cache (moe_stats)
        stats = (jnp.zeros((4,), jnp.int32)
                 if arch.num_experts and arch.expert_shards > 1 else None)
        return KVCache(k=jnp.zeros(shape, dtype),
                       v=jnp.zeros(shape[:-1] + (0,), dtype),
                       k_scale=k_scale, v_scale=v_scale, moe_stats=stats)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   k_scale=k_scale, v_scale=v_scale)


def _safe(s: jax.Array) -> jax.Array:
    """Guard divisions by a not-yet-grown (zero) page scale."""
    return jnp.where(s > 0, s, 1.0)


def dequantize_pages(pages: jax.Array, scale: jax.Array) -> jax.Array:
    """[..., ps, Hkv, D] int8 codes x [..., Hkv] scales -> fp32."""
    return pages.astype(jnp.float32) * scale[..., None, :, None]


def _is_token_flat(cache_layer: jax.Array, layer) -> bool:
    """A pool of token-flat pages, ([Lg,] P, ps*Hkv, D): one axis fewer
    than ([Lg,] P, ps, Hkv, D)."""
    return cache_layer.ndim == (3 if layer is None else 4)


def _set_decode_rows(pool: jax.Array, layer, page_idx: jax.Array,
                     offset: jax.Array, new: jax.Array) -> jax.Array:
    """Token-flat pages ([Lg,] P, ps*Hkv, D): token ``offset[i]`` of
    page ``page_idx[i]`` takes ``new[i]`` [Hkv, D] as rows ``offset *
    Hkv`` and on.  Whole pages are read, changed and written back: a
    scatter of [Hkv, D] windows into rows that are no whole tile runs
    index by index (4,096 of them a prefill chunk halved the cell's
    rate, PERF.md section 6, PR 38); a page is whole tiles."""
    hkv = new.shape[-2]
    lidx = (layer,) if layer is not None else ()
    pages = pool[lidx + (page_idx,)]                  # [B, ps*Hkv, D]
    row = jnp.arange(pages.shape[-2], dtype=jnp.int32)[None, :]
    mine = row // hkv == offset.astype(jnp.int32)[:, None]  # [B, ps*Hkv]
    # row r of a page is head r % Hkv of its token: the new token's
    # heads, repeated down the page
    rows = jnp.tile(new.astype(pool.dtype), (1, pages.shape[-2] // hkv, 1))
    return pool.at[lidx + (page_idx,)].set(
        jnp.where(mine[..., None], rows, pages))


def _set_prefill_rows(pool: jax.Array, layer, new: jax.Array,
                      page_tables: jax.Array, start_pos: jax.Array,
                      true_lens: jax.Array, page_size: int) -> jax.Array:
    """Token-flat pages: a batch of prefill chunks ``new`` [B, T, Hkv,
    D] into the pages their tables name, page-wise, as the quantizing
    writes do: the pages a chunk spans are gathered, the chunk's valid
    tokens laid over their rows, and whole pages scattered back (what a
    page held before the chunk's first token, and after its last valid
    one, stays).  A slot past the table and a table's null entries go
    to the null page."""
    B, T, hkv, _ = new.shape
    ps = page_size
    n_pg = (T + ps - 1) // ps + 1
    first_slot = (start_pos // ps).astype(jnp.int32)              # [B]
    pmax = page_tables.shape[1]
    slot_ids = first_slot[:, None] + jnp.arange(n_pg, dtype=jnp.int32)[None]
    span_pages = jnp.where(
        slot_ids < pmax,
        jnp.take_along_axis(page_tables, jnp.clip(slot_ids, 0, pmax - 1),
                            axis=1), NULL_PAGE)                    # [B, n_pg]
    lidx = (layer,) if layer is not None else ()
    pages = pool[lidx + (span_pages,)]             # [B, n_pg, ps*Hkv, D]
    span = pages.reshape(B, n_pg * ps * hkv, pages.shape[-1])
    # span row r holds token r // Hkv of the span, whose place in the
    # chunk is that less the chunk's offset into its first page
    off = (start_pos % ps).astype(jnp.int32)                      # [B]
    tok = jnp.arange(n_pg * ps, dtype=jnp.int32)[None, :] - off[:, None]
    mine = (tok >= 0) & (tok < true_lens[:, None])                # [B, n_pg*ps]
    rows = new.astype(pool.dtype).reshape(B, T * hkv, new.shape[-1])
    laid = jnp.stack([                 # each chunk moved to its offset
        jax.lax.dynamic_update_slice(jnp.zeros_like(span[b]), rows[b],
                                     (off[b] * hkv, 0))
        for b in range(B)])
    merged = jnp.where(jnp.repeat(mine, hkv, axis=1)[..., None], laid, span)
    return pool.at[lidx + (span_pages.reshape(-1),)].set(
        merged.reshape((B * n_pg,) + pages.shape[2:]))


def write_prefill_tokens(
    cache_layer: jax.Array,       # [num_pages, ps, Hkv, D] or, with
                                  # ``layer``, the stacked group [Lg, P, ps, Hkv, D];
                                  # or token-flat, [(Lg,) P, ps*Hkv, D]
    new: jax.Array,               # [B, T, Hkv, D]
    page_tables: jax.Array,       # [B, pages_per_seq] int32
    start_pos: jax.Array,         # [B] sequence position of new[:, 0]
    true_lens: jax.Array,         # [B] valid tokens per row; pad -> null page
    page_size: int,
    layer: Optional[jax.Array] = None,   # scalar layer index into the stack
) -> jax.Array:
    """Scatter a batch of prefill chunks into their pages in one flat
    scatter (a vmap would fork the shared pool buffer per row).

    With ``layer``, the stacked group cache is updated in place at that
    layer — the form the serve path uses so the cache can ride the layer
    scan as a *carry* (in-place scatter) instead of as stacked ys, which
    copied the full pool every step (round-2 perf finding: 13.9 ms of a
    31 ms decode step was cache copies)."""
    B, T = new.shape[:2]
    t = jnp.arange(T, dtype=jnp.int32)[None, :]
    pos = start_pos[:, None] + t                                  # [B, T]
    page_idx = jnp.take_along_axis(page_tables, pos // page_size, axis=1)
    valid = t < true_lens[:, None]
    page_idx = jnp.where(valid, page_idx, NULL_PAGE)
    offset = pos % page_size
    flat = new.reshape(B * T, *new.shape[2:])                      # [B*T, Hkv, D]
    if _is_token_flat(cache_layer, layer):
        return _set_prefill_rows(cache_layer, layer, new, page_tables,
                                 start_pos, true_lens, page_size)
    if layer is None:
        return cache_layer.at[page_idx.reshape(-1), offset.reshape(-1)].set(flat)
    return cache_layer.at[layer, page_idx.reshape(-1), offset.reshape(-1)].set(flat)


def write_decode_tokens(
    cache_layer: jax.Array,       # [num_pages, ps, Hkv, D] or, with
                                  # ``layer``, the stacked group [Lg, P, ps, Hkv, D];
                                  # or token-flat, [(Lg,) P, ps*Hkv, D]
    new: jax.Array,               # [B, Hkv, D] one token per sequence
    page_tables: jax.Array,       # [B, pages_per_seq]
    positions: jax.Array,         # [B] current position of each new token
    page_size: int,
    active: Optional[jax.Array] = None,  # [B] bool; inactive rows hit page 0
    layer: Optional[jax.Array] = None,   # scalar layer index into the stack
) -> jax.Array:
    page_idx = jnp.take_along_axis(
        page_tables, (positions // page_size)[:, None], axis=1)[:, 0]
    if active is not None:
        # inactive rows target the null page (harmless scratch writes)
        page_idx = jnp.where(active, page_idx, NULL_PAGE)
    offset = positions % page_size
    if _is_token_flat(cache_layer, layer):
        return _set_decode_rows(cache_layer, layer, page_idx, offset, new)
    if layer is None:
        return cache_layer.at[page_idx, offset].set(new)
    return cache_layer.at[layer, page_idx, offset].set(new)


def _requantize(pages: jax.Array, old: jax.Array, s_new: jax.Array) -> jax.Array:
    """Re-express existing int8 codes at a grown page scale.

    ``ratio = old/new <= 1`` so the rescaled codes stay in [-127, 127];
    when the scale didn't grow ratio is exactly 1.0 and the round-trip
    is the identity (no drift on repeated writes to the same page)."""
    ratio = jnp.where(s_new > 0, old / _safe(s_new), 1.0)
    scaled = pages.astype(jnp.float32) * ratio[..., None, :, None]
    return jnp.clip(jnp.round(scaled), -127, 127)


def write_decode_tokens_q(
    cache_layer: jax.Array,       # int8 [Lg, P, ps, Hkv, D] (or unstacked)
    scale_layer: jax.Array,       # fp32 [Lg, P, Hkv] (or [P, Hkv])
    new: jax.Array,               # [B, Hkv, D] one token per sequence
    page_tables: jax.Array,       # [B, pages_per_seq]
    positions: jax.Array,         # [B]
    page_size: int,
    active: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array]:
    """Quantizing counterpart of :func:`write_decode_tokens`.

    Gathers each target page + its scale, folds the new token's absmax
    into the scale (rescaling the page's existing codes if it grew),
    inserts the quantized token row, and scatters both back.  Inactive
    rows hit the null page — its codes and scale become garbage, which
    is fine: reads mask by length and scales stay finite."""
    page_idx = jnp.take_along_axis(
        page_tables, (positions // page_size)[:, None], axis=1)[:, 0]
    if active is not None:
        page_idx = jnp.where(active, page_idx, NULL_PAGE)
    offset = positions % page_size

    lidx = (layer,) if layer is not None else ()
    pages = cache_layer[lidx + (page_idx,)]        # [B, ps, Hkv, D]
    old = scale_layer[lidx + (page_idx,)]          # [B, Hkv]

    new32 = new.astype(jnp.float32)
    cand = jnp.max(jnp.abs(new32), axis=-1) / 127.0          # [B, Hkv]
    s_new = jnp.maximum(old, cand)
    merged = _requantize(pages, old, s_new)
    q_new = jnp.clip(jnp.round(new32 / _safe(s_new)[..., None]), -127, 127)

    ps = cache_layer.shape[-3]
    at_row = jnp.arange(ps, dtype=jnp.int32)[None, :] == offset[:, None]
    merged = jnp.where(at_row[..., None, None], q_new[:, None], merged)
    merged = merged.astype(cache_layer.dtype)

    cache_layer = cache_layer.at[lidx + (page_idx,)].set(merged)
    scale_layer = scale_layer.at[lidx + (page_idx,)].set(s_new)
    return cache_layer, scale_layer


def write_prefill_tokens_q(
    cache_layer: jax.Array,       # int8 [Lg, P, ps, Hkv, D] (or unstacked)
    scale_layer: jax.Array,       # fp32 [Lg, P, Hkv] (or [P, Hkv])
    new: jax.Array,               # [B, T, Hkv, D]
    page_tables: jax.Array,       # [B, pages_per_seq]
    start_pos: jax.Array,         # [B]
    true_lens: jax.Array,         # [B]
    page_size: int,
    layer: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array]:
    """Quantizing counterpart of :func:`write_prefill_tokens`.

    A T-token chunk starting mid-page spans at most ceil(T/ps)+1 page
    slots, so the update is reformulated page-wise: gather that span,
    fold per-segment absmaxes into the span's scales, requantize what
    the pages already held, insert the new tokens at the grown scales,
    and scatter the span back.  Invalid (padding) tokens are routed to
    an out-of-bounds segment — JAX drops OOB scatter indices — and are
    excluded from the absmax fold."""
    B, T = new.shape[:2]
    ps = page_size
    n_pg = (T + ps - 1) // ps + 1

    t = jnp.arange(T, dtype=jnp.int32)[None, :]
    pos = start_pos[:, None] + t                                  # [B, T]
    valid = t < true_lens[:, None]
    first_slot = (start_pos // ps).astype(jnp.int32)              # [B]
    seg = pos // ps - first_slot[:, None]                         # [B, T] in [0, n_pg)

    pmax = page_tables.shape[1]
    slot_ids = first_slot[:, None] + jnp.arange(n_pg, dtype=jnp.int32)[None, :]
    in_range = slot_ids < pmax
    span_pages = jnp.take_along_axis(
        page_tables, jnp.clip(slot_ids, 0, pmax - 1), axis=1)     # [B, n_pg]
    span_pages = jnp.where(in_range, span_pages, NULL_PAGE)

    lidx = (layer,) if layer is not None else ()
    pages = cache_layer[lidx + (span_pages,)]      # [B, n_pg, ps, Hkv, D]
    old = scale_layer[lidx + (span_pages,)]        # [B, n_pg, Hkv]

    new32 = new.astype(jnp.float32)
    tokmax = jnp.max(jnp.abs(new32), axis=-1)                     # [B, T, Hkv]
    seg_onehot = (seg[:, :, None] == jnp.arange(n_pg)[None, None, :]) \
        & valid[:, :, None]                                        # [B, T, n_pg]
    cand = jnp.max(
        jnp.where(seg_onehot[..., None], tokmax[:, :, None, :], 0.0),
        axis=1) / 127.0                                            # [B, n_pg, Hkv]
    s_new = jnp.maximum(old, cand)
    merged = _requantize(pages, old, s_new)

    s_tok = jnp.take_along_axis(
        s_new, jnp.clip(seg, 0, n_pg - 1)[..., None], axis=1)     # [B, T, Hkv]
    q_tok = jnp.clip(jnp.round(new32 / _safe(s_tok)[..., None]), -127, 127)

    # Insert each token into its page-span slot; invalid tokens get
    # segment n_pg, which is out of bounds for axis 1 -> dropped.
    b_idx = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[:, None], (B, T))
    seg_i = jnp.where(valid, seg, n_pg)
    offset = pos % ps
    merged = merged.at[
        b_idx.reshape(-1), seg_i.reshape(-1), offset.reshape(-1)
    ].set(q_tok.reshape(B * T, *q_tok.shape[2:]))
    merged = merged.astype(cache_layer.dtype)

    flat_pages = span_pages.reshape(-1)
    cache_layer = cache_layer.at[lidx + (flat_pages,)].set(
        merged.reshape(B * n_pg, *merged.shape[2:]))
    scale_layer = scale_layer.at[lidx + (flat_pages,)].set(
        s_new.reshape(B * n_pg, s_new.shape[-1]))
    return cache_layer, scale_layer
