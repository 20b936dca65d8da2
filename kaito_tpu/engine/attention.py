"""Attention: chunked-causal prefill and paged decode.

Pure-JAX reference implementations with static shapes.  The Pallas
kernels (kaito_tpu.engine.ops) implement the same signatures and are
selected by ``EngineConfig.use_pallas``; tests compare the two.  All
softmax math is fp32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _scoped(fn):
    """Trace this attention entry point under named_scope("attention")
    so its HLO ops carry the marker the device profiler's classifier
    buckets on (engine/devprof.py) — scopes bind at trace time, so the
    wrapper costs nothing per executed step."""
    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        with jax.named_scope("attention"):
            return fn(*args, **kwargs)
    return wrapper


def _gqa_expand(x: jax.Array, groups: int) -> jax.Array:
    """[..., Hkv, D] -> [..., Hkv*groups, D]."""
    if groups == 1:
        return x
    return jnp.repeat(x, groups, axis=-2)


def _softmax_with_sink(scores: jax.Array, sink: Optional[jax.Array],
                       head_axes: tuple) -> jax.Array:
    """Softmax over the last axis with, given ``sink`` (one float a
    query head), one more column a head that takes probability and
    carries no value: ``exp(a - m) / (exp(sink - m) + sum exp(a - m))``.
    ``head_axes`` are the axes of ``scores`` the heads lie on, in the
    order that flattens to the head index."""
    if sink is None:
        return jax.nn.softmax(scores, axis=-1)
    shape = [1] * scores.ndim
    for ax in head_axes:
        shape[ax] = scores.shape[ax]
    col = jnp.broadcast_to(sink.astype(jnp.float32).reshape(shape),
                           scores.shape[:-1] + (1,))
    probs = jax.nn.softmax(jnp.concatenate([scores, col], axis=-1), axis=-1)
    return probs[..., :-1]


def _layer_view(cache: jax.Array, layer):
    """Resolve the optional stacked-group form of a paged cache.

    Returns ``(flat_cache [Lg*P, ...], page_base)`` where a page index p
    of the selected layer lives at row ``page_base + p``.  With
    ``layer=None`` the cache is a single layer ``[P, ...]`` (the round-1
    contract kept for tests/benchmarks); with ``layer`` given it is the
    stacked group ``[Lg, P, ...]`` and the flatten-plus-offset gather
    avoids materializing a 30+ MiB per-layer slice inside the scan."""
    if layer is None:
        return cache, 0
    Lg, P = cache.shape[:2]
    return cache.reshape(Lg * P, *cache.shape[2:]), layer * P


def _dequant_gathered(pages, scale_pool, page_tables, base, layer, out_dtype):
    """Dequantize gathered int8 pages with their per-page-per-head scales.

    ``pages`` is [B, pmax, ps, Hkv, D] straight from the page gather;
    ``scale_pool`` is the [P, Hkv] / [Lg, P, Hkv] scale tensor, gathered
    through the same page tables.  Null/garbage pages dequantize to
    finite junk that the length mask drops, same as the bf16 path."""
    s_flat, _ = _layer_view(scale_pool, layer)
    s = s_flat[base + page_tables]                 # [B, pmax, Hkv]
    return (pages.astype(jnp.float32) * s[:, :, None, :, None]).astype(out_dtype)


@_scoped
def prefill_attention(
    q: jax.Array,            # [B, T, H, D]
    k: jax.Array,            # [B, T, Hkv, D]
    v: jax.Array,            # [B, T, Hkv, D]
    *,
    scale: float,
    sliding_window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    true_len: Optional[jax.Array] = None,   # [B]
    sink: Optional[jax.Array] = None,       # [H] fp32 sink bias a head
) -> jax.Array:
    """Causal self-attention over a freshly prefillled chunk.  ``v``'s
    heads may be narrower than ``k``'s: the output has ``v``'s size.

    Positions are 0..T-1 within the chunk (round-1 engine prefills a
    request in one padded chunk; the chunked long-prompt path arrives
    with the Pallas flash kernel).
    """
    B, T, H, D = q.shape
    groups = H // k.shape[2]
    k = _gqa_expand(k, groups)
    v = _gqa_expand(v, groups)
    scores = jnp.einsum("bthd,bshd->bhts", q, k, preferred_element_type=jnp.float32)
    scores = scores * scale
    if logit_softcap:
        scores = jnp.tanh(scores / logit_softcap) * logit_softcap
    t_pos = jnp.arange(T)[:, None]
    s_pos = jnp.arange(T)[None, :]
    mask = s_pos <= t_pos
    # sliding_window may be a traced per-layer scalar (scan flag); global
    # layers pass a huge window, so the mask stays branch-free.
    if sliding_window is not None:
        mask &= s_pos > t_pos - sliding_window
    if true_len is not None:
        mask = mask[None, :, :] & (s_pos[None] < true_len[:, None, None])
        mask = mask[:, None]  # [B, 1, T, S]
    scores = jnp.where(mask, scores, NEG_INF)
    probs = _softmax_with_sink(scores, sink, (1,))
    return jnp.einsum("bhts,bshd->bthd", probs.astype(v.dtype), v)


@_scoped
def paged_context_attention(
    q: jax.Array,            # [B, T, H, D] chunk queries
    cache_k: jax.Array,      # [P, ps, Hkv, D] (chunk KV already written)
    cache_v: jax.Array,
    page_tables: jax.Array,  # [B, pmax]
    start_pos: jax.Array,    # [B] absolute position of q[:, 0]
    true_lens: jax.Array,    # [B] valid NEW tokens in the chunk
    *,
    scale: float,
    sliding_window: Optional[jax.Array] = None,
    logit_softcap: Optional[float] = None,
    layer: Optional[jax.Array] = None,
    k_scale: Optional[jax.Array] = None,   # [P, Hkv] / [Lg, P, Hkv] int8 pools
    v_scale: Optional[jax.Array] = None,
    sink: Optional[jax.Array] = None,      # [H] fp32 sink bias a head
    kv_heads: Optional[int] = None,        # pools are token-flat:
                                           # [(Lg,) P, ps*kv_heads, D]
) -> jax.Array:
    """Chunked prefill WITH prior context: queries attend over the whole
    paged history (cached prefix + the freshly-written chunk) with
    absolute-position causal masking.  Backs prefix-cache reuse and
    long-prompt chunked prefill.  A table entry behind the window may
    be the null page (a window kind's freed page): the mask hides it."""
    B, T, H, D = q.shape
    if kv_heads is None:
        ps, Hkv, _ = cache_k.shape[-3:]
    else:
        ps, Hkv = cache_k.shape[-2] // kv_heads, kv_heads
    Dv = cache_v.shape[-1]
    pmax = page_tables.shape[1]
    S = pmax * ps
    groups = H // Hkv

    full_k, base = _layer_view(cache_k, layer)
    full_v, _ = _layer_view(cache_v, layer)
    k = full_k[base + page_tables]                # [B, pmax, ps, Hkv, D]
    v = full_v[base + page_tables]
    if k_scale is not None:
        k = _dequant_gathered(k, k_scale, page_tables, base, layer, q.dtype)
        v = _dequant_gathered(v, v_scale, page_tables, base, layer, q.dtype)
    k = k.reshape(B, S, Hkv, D)
    v = v.reshape(B, S, Hkv, Dv)
    k = _gqa_expand(k, groups)
    v = _gqa_expand(v, groups)

    scores = jnp.einsum("bthd,bshd->bhts", q, k,
                        preferred_element_type=jnp.float32) * scale
    if logit_softcap:
        scores = jnp.tanh(scores / logit_softcap) * logit_softcap
    q_pos = start_pos[:, None] + jnp.arange(T)[None, :]       # [B, T]
    k_pos = jnp.arange(S)[None, :]                            # [1, S]
    mask = k_pos[:, None, :] <= q_pos[:, :, None]             # [B, T, S]
    mask &= (k_pos < (start_pos + true_lens)[:, None])[:, None, :]
    if sliding_window is not None:
        mask &= k_pos[:, None, :] > q_pos[:, :, None] - sliding_window
    scores = jnp.where(mask[:, None], scores, NEG_INF)
    probs = _softmax_with_sink(scores, sink, (1,))
    return jnp.einsum("bhts,bshd->bthd", probs.astype(v.dtype), v)


@_scoped
def mla_prefill_attention(
    q_nope: jax.Array,       # [B, T, H, dn]
    q_rope: jax.Array,       # [B, T, H, dr] (roped)
    c_kv: jax.Array,         # [B, T, dl]  normalized latent
    k_rope: jax.Array,       # [B, T, dr]  (roped, shared across heads)
    kv_b_k: jax.Array,       # [dl, H*dn]
    kv_b_v: jax.Array,       # [dl, H*dv]
    *,
    scale: float,
    true_len: Optional[jax.Array] = None,
) -> jax.Array:
    """DeepSeek-style latent attention over a fresh chunk.

    Scores = q_nope . (c_kv @ W_uk) + q_rope . k_rope, softmax over the
    causal window, value = c_kv @ W_uv.  Returns [B, T, H, dv].
    """
    B, T, H, dn = q_nope.shape
    dv = kv_b_v.shape[1] // H
    k_nope = (c_kv @ kv_b_k).reshape(B, T, H, dn)
    v = (c_kv @ kv_b_v).reshape(B, T, H, dv)
    s = jnp.einsum("bthd,bshd->bhts", q_nope, k_nope,
                   preferred_element_type=jnp.float32)
    s = s + jnp.einsum("bthd,bsd->bhts", q_rope, k_rope,
                       preferred_element_type=jnp.float32)
    s = s * scale
    t_pos = jnp.arange(T)[:, None]
    s_pos = jnp.arange(T)[None, :]
    mask = s_pos <= t_pos
    if true_len is not None:
        mask = mask[None, :, :] & (s_pos[None] < true_len[:, None, None])
        mask = mask[:, None]
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p.astype(v.dtype), v)


# queries a pass of latent context attention takes (its scores are
# [B, heads, queries, table positions] in float32)
_CONTEXT_QUERY_BLOCK = 512


def _gather_latent(cache_latent, page_tables, layer, latent_scale,
                   kv_lora_rank: int, rope_dim: int):
    """A batch's cached latents through its page tables: (c_kv [B, S,
    dl], k_rope [B, S, dr]) over the ``S = pmax * ps`` positions the
    tables name.  ``cache_latent`` is [(Lg,) P, ps, 1, dl+dr], or
    token-flat as the decode kernel reads a page, [(Lg,) P, ps, lanes]
    with the lanes past ``dl + dr`` zero (kv_cache.create_kv_cache);
    int8 pools dequantize by ``latent_scale`` [(Lg,) P, 1]."""
    flat = cache_latent.ndim == (3 if layer is None else 4)
    cache_latent, base = _layer_view(cache_latent, layer)
    lat = cache_latent[base + page_tables]          # [B, pmax, ps, ...]
    if not flat:
        lat = lat[:, :, :, 0]
    if latent_scale is not None:
        s_flat, _ = _layer_view(latent_scale, layer)
        sl = s_flat[base + page_tables]                 # [B, pmax, 1]
        lat = lat.astype(jnp.float32) * sl[..., None]
    B, pmax, ps, _ = lat.shape
    lat = lat.reshape(B, pmax * ps, lat.shape[-1])
    dl = kv_lora_rank
    return lat[..., :dl], lat[..., dl:dl + rope_dim]


@_scoped
def mla_paged_context_attention(
    q_nope: jax.Array,        # [B, T, H, dn] chunk queries
    q_rope: jax.Array,        # [B, T, H, dr] (roped)
    cache_latent: jax.Array,  # [P, ps, 1, dl+dr] or token-flat [P, ps, lanes]
                              # (chunk latent already written)
    page_tables: jax.Array,   # [B, pmax]
    start_pos: jax.Array,     # [B] absolute position of q[:, 0]
    true_lens: jax.Array,     # [B] valid NEW tokens in the chunk
    kv_b_k: jax.Array,        # [dl, H*dn]
    kv_b_v: jax.Array,        # [dl, H*dv]
    *,
    scale: float,
    kv_lora_rank: int,
    layer: Optional[jax.Array] = None,
    latent_scale: Optional[jax.Array] = None,   # [P, 1] / [Lg, P, 1]
) -> jax.Array:
    """Chunked MLA prefill WITH prior context: chunk queries attend over
    the whole paged latent history (earlier chunks + this one) with
    absolute-position causal masking — the latent analogue of
    paged_context_attention.  Uses the absorption form so per-token K/V
    are never materialized."""
    B, T, H, dn = q_nope.shape
    dl = kv_lora_rank
    dv = kv_b_v.shape[1] // H
    c_kv, k_rope = _gather_latent(cache_latent, page_tables, layer,
                                  latent_scale, dl, q_rope.shape[-1])
    S = c_kv.shape[1]
    c_kv, k_rope = c_kv.astype(jnp.float32), k_rope.astype(jnp.float32)
    wk = kv_b_k.reshape(dl, H, dn)
    wv = kv_b_v.reshape(dl, H, dv).astype(jnp.float32)
    k_pos = jnp.arange(S)[None, :]                            # [1, S]
    end = (start_pos + true_lens)[:, None]                    # [B, 1]

    def attend(qn, qr, q_pos):
        """A block of the chunk's queries ([B, t, H, ...], absolute
        positions [B, t]) against the whole table."""
        q_lat = jnp.einsum("bthd,lhd->bthl", qn, wk,
                           preferred_element_type=jnp.float32)
        s = jnp.einsum("bthl,bsl->bhts", q_lat, c_kv)
        s = s + jnp.einsum("bthd,bsd->bhts", qr.astype(jnp.float32), k_rope)
        mask = (k_pos[:, None, :] <= q_pos[:, :, None]) \
            & (k_pos < end)[:, None, :]                       # [B, t, S]
        s = jnp.where(mask[:, None], s * scale, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out_lat = jnp.einsum("bhts,bsl->bthl", p, c_kv)
        return jnp.einsum("bthl,lhd->bthd", out_lat, wv).astype(qn.dtype)

    q_pos = start_pos[:, None] + jnp.arange(T)[None, :]       # [B, T]
    if T <= _CONTEXT_QUERY_BLOCK or T % _CONTEXT_QUERY_BLOCK:
        return attend(q_nope, q_rope, q_pos)
    # a block of queries at a time: the float32 scores of every head
    # over a 4,096-token chunk and a 5,120-position table are 2.5 GiB
    n = T // _CONTEXT_QUERY_BLOCK

    def blocks(x):
        return jnp.moveaxis(
            x.reshape((B, n, _CONTEXT_QUERY_BLOCK) + x.shape[2:]), 1, 0)

    out = jax.lax.map(lambda a: attend(*a),
                      (blocks(q_nope), blocks(q_rope), blocks(q_pos)))
    return jnp.moveaxis(out, 0, 1).reshape(B, T, H, dv)


@_scoped
def mla_paged_decode_attention(
    q_nope: jax.Array,       # [B, H, dn]
    q_rope: jax.Array,       # [B, H, dr]
    cache_latent: jax.Array,  # [P, ps, 1, dl+dr] or token-flat [P, ps, lanes]
    page_tables: jax.Array,  # [B, pmax]
    lengths: jax.Array,      # [B]
    kv_b_k: jax.Array,       # [dl, H*dn]
    kv_b_v: jax.Array,       # [dl, H*dv]
    *,
    scale: float,
    kv_lora_rank: int,
    layer: Optional[jax.Array] = None,
    latent_scale: Optional[jax.Array] = None,   # [P, 1] / [Lg, P, 1]
) -> jax.Array:
    """Decode attention over the paged latent cache.

    Absorption form: q_nope is projected INTO latent space
    (q_lat = q_nope @ W_uk^T-per-head) so scores are latent dot
    products; the output is computed in latent space then expanded by
    W_uv — per-token K/V are never materialized (the MLA decode
    memory win).
    """
    B, H, dn = q_nope.shape
    dl = kv_lora_rank
    dv = kv_b_v.shape[1] // H
    c_kv, k_rope = _gather_latent(cache_latent, page_tables, layer,
                                  latent_scale, dl, q_rope.shape[-1])
    S = c_kv.shape[1]

    wk = kv_b_k.reshape(dl, H, dn)
    q_lat = jnp.einsum("bhd,lhd->bhl", q_nope, wk,
                       preferred_element_type=jnp.float32)   # [B, H, dl]
    s = jnp.einsum("bhl,bsl->bhs", q_lat, c_kv.astype(jnp.float32))
    s = s + jnp.einsum("bhd,bsd->bhs", q_rope.astype(jnp.float32),
                       k_rope.astype(jnp.float32))
    s = s * scale
    s_pos = jnp.arange(S)[None, :]
    s = jnp.where((s_pos < lengths[:, None])[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out_lat = jnp.einsum("bhs,bsl->bhl", p, c_kv.astype(jnp.float32))
    wv = kv_b_v.reshape(dl, H, dv)
    out = jnp.einsum("bhl,lhd->bhd", out_lat, wv.astype(jnp.float32))
    return out.astype(q_nope.dtype)


@_scoped
def paged_decode_attention(
    q: jax.Array,            # [B, H, D] (one new token per sequence)
    cache_k: jax.Array,      # [num_pages, page_size, Hkv, D]
    cache_v: jax.Array,
    page_tables: jax.Array,  # [B, pages_per_seq]
    lengths: jax.Array,      # [B] tokens in cache INCLUDING the new one
    *,
    scale: float,
    sliding_window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    layer: Optional[jax.Array] = None,
    k_scale: Optional[jax.Array] = None,   # [P, Hkv] / [Lg, P, Hkv] int8 pools
    v_scale: Optional[jax.Array] = None,
    sink: Optional[jax.Array] = None,      # [H] fp32 sink bias a head
    kv_heads: Optional[int] = None,        # pools are token-flat:
                                           # [(Lg,) P, ps*kv_heads, D]
) -> jax.Array:
    """Attend one query token per sequence over its paged KV history
    (pure-JAX reference; the Pallas kernel in engine.ops implements the
    same contract)."""
    B, H, D = q.shape
    if kv_heads is None:
        ps, Hkv, _ = cache_k.shape[-3:]
    else:
        ps, Hkv = cache_k.shape[-2] // kv_heads, kv_heads
    Dv = cache_v.shape[-1]
    pmax = page_tables.shape[1]
    S = pmax * ps
    groups = H // Hkv

    full_k, base = _layer_view(cache_k, layer)
    full_v, _ = _layer_view(cache_v, layer)
    k = full_k[base + page_tables]                # [B, pmax, ps, Hkv, D]
    v = full_v[base + page_tables]
    if k_scale is not None:
        k = _dequant_gathered(k, k_scale, page_tables, base, layer, q.dtype)
        v = _dequant_gathered(v, v_scale, page_tables, base, layer, q.dtype)
    k = k.reshape(B, S, Hkv, D)
    v = v.reshape(B, S, Hkv, Dv)

    qg = q.reshape(B, Hkv, groups, D)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, k, preferred_element_type=jnp.float32)
    scores = scores * scale
    if logit_softcap:
        scores = jnp.tanh(scores / logit_softcap) * logit_softcap
    s_pos = jnp.arange(S)[None, :]
    mask = s_pos < lengths[:, None]
    if sliding_window is not None:
        mask &= s_pos >= lengths[:, None] - sliding_window
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    probs = _softmax_with_sink(scores, sink, (1, 2))
    out = jnp.einsum("bkgs,bskd->bkgd", probs.astype(v.dtype), v)
    return out.reshape(B, H, Dv)


def _lane_slot(num_heads: int, kv_heads: int, pack: int) -> jax.Array:
    """[H] int32: which of a row's ``pack`` head-wide lane groups holds
    the KV head of each query head."""
    return (jnp.arange(num_heads) // (num_heads // kv_heads)) % pack


def lane_pack_queries(q: jax.Array, kv_heads: int, pack: int) -> jax.Array:
    """Queries laid out against pools whose rows hold ``pack`` KV heads
    side by side (metadata.heads_per_lane_row): ``q`` [..., H, D] ->
    [..., H, pack * D], a query head's D numbers in the lane group of
    its KV head and zeros in the others.  Against such a row, read as
    one KV head of ``pack * D`` lanes shared by ``pack`` times the
    query heads, the score is the score against the head's own key
    exactly (the other heads' keys meet zeros)."""
    H, D = q.shape[-2:]
    onehot = jax.nn.one_hot(_lane_slot(H, kv_heads, pack), pack,
                            dtype=q.dtype)                       # [H, pack]
    return (q[..., :, None, :] * onehot[:, :, None]).reshape(
        q.shape[:-1] + (pack * D,))


def lane_unpack_outputs(out: jax.Array, kv_heads: int,
                        pack: int) -> jax.Array:
    """The other half of ``lane_pack_queries``: attention over packed
    rows gives every query head the weighted sum of whole rows, [..., H,
    pack * Dv]; its own KV head's values are one lane group of that."""
    H = out.shape[-2]
    Dv = out.shape[-1] // pack
    o = out.reshape(out.shape[:-1] + (pack, Dv))
    slot = _lane_slot(H, kv_heads, pack).reshape(
        (1,) * (o.ndim - 3) + (H, 1, 1))
    return jnp.take_along_axis(o, slot, axis=-2)[..., 0, :]
