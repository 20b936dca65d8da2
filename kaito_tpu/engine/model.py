"""Config-driven transformer LM.

One implementation covers the dense families (llama / mistral / qwen2 /
phi-3 / phi-4 / gemma-3 / falcon / phi-2) and token-choice MoE
(mixtral / gpt-oss style); layers run under ``lax.scan`` over stacked
parameters so an 80-layer model compiles as one layer, and per-layer
heterogeneity (sliding vs global attention, local vs global RoPE) rides
along as scanned flag arrays.  Dense-prefix MoE models (DeepSeek-style
``first_k_dense_replace``) split into two scans.  A state-space mixer
beside attention in every block (falcon-h1: ``ModelArch.ssm_state``)
reads the block's normed input as attention does, adds its output to
the same residual, and keeps a per-slot recurrent state that rides the
layer scan with the page pools (``_ssm_mixer``, engine/ops/ssm.py).
Layers of more than one kind in one model (mimo_v2:
``ModelArch.layer_attention`` / ``layer_experts``: window and full
attention layers with their own head counts, dense and expert FFNs)
differ in parameter SHAPES, so they are stacked by kind and run as a
schedule of scans over those stacks (``_layer_schedule``,
``_run_layers_kinds``); each attention kind has a page pool and a page
table of its own (docs/kv-cache.md).  A layer whose mixer is no
attention has no page and keeps a row of the state pool a slot: a gated
short convolution its last inputs (lfm2: ``_conv_layer``), a gated
delta rule a matrix a head and its convolutions' last inputs
(olmo_hybrid: ``_gdn_layer``, engine/ops/gdn.py).

This replaces the model zoo the reference gets for free from vLLM
(SURVEY.md §2.2, §7 step 3); parameters are plain pytrees whose logical
axes map onto the planner's mesh via kaito_tpu.parallel.sharding.
"""

from __future__ import annotations

import dataclasses
import math
import zlib
from dataclasses import dataclass
from functools import cached_property, partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from kaito_tpu.engine import attention as attn
from kaito_tpu.engine import nn
from kaito_tpu.engine.kv_cache import (KVCache, write_decode_tokens,
                                       write_decode_tokens_q,
                                       write_prefill_tokens,
                                       write_prefill_tokens_q)
from kaito_tpu.models.metadata import (MIXER_CONV, MIXER_GDN, AttentionKind,
                                       ModelArch, heads_per_lane_row,
                                       stored_key_dim)

VOCAB_ALIGN = 128
_BIG_WINDOW = 1 << 30


def _name_salt(name: str) -> int:
    """Stable per-parameter PRNG salt.  Python's hash() is salted per
    process, which made synthetic weights differ across processes — a
    correctness hazard for multi-host lockstep serving (each process
    traces its own init program) and a source of cross-run test flakes
    (per-process weight draws occasionally produce argmax near-ties)."""
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


@dataclass(frozen=True)
class LayerGroup:
    name: str          # "dense" | "moe"; "<full|window|conv|gdn>_<dense|moe>" by kind
    start: int
    count: int
    moe: bool
    kind: int = 0      # the stack's mixer (metadata.MIXER_*)


@dataclass(frozen=True)
class AttnKind:
    """One attention kind's sizes (ModelArch.layer_attention).  ``k_dim``
    is what a key's head is stored and multiplied at: ``head_dim``
    zero-padded to whole 128-lane tiles (metadata.stored_key_dim)."""
    index: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    v_head_dim: int
    window: Optional[int]
    sink: bool

    @property
    def k_dim(self) -> int:
        return stored_key_dim(self.head_dim)

    @property
    def pack(self) -> int:
        """KV heads that share a 128-lane row of the kind's pools
        (metadata.heads_per_lane_row; 1: a head is a row)."""
        return heads_per_lane_row(self.head_dim, self.v_head_dim,
                                  self.num_kv_heads)


@dataclass(frozen=True)
class LayerRun:
    """Consecutive layers of one stack: ``count`` layers from
    ``stack_start`` of ``params[stack]``, whose attention kind's page
    pool (a short-convolution or delta-rule layer's state pool) holds
    them from ``cache_start``."""
    stack: str
    stack_start: int
    count: int
    moe: bool
    kind: int
    cache_start: int


def attention_kinds(arch: ModelArch) -> tuple:
    """The attention kinds of a model whose layers name theirs: the
    full kind, and the window kind where some layer is of it."""
    full = AttnKind(0, arch.num_heads, arch.num_kv_heads, arch.head_dim,
                    arch.v_head_dim or arch.head_dim, None, arch.full_sink)
    if not arch.two_kind_cache:
        return (full,)
    return (full,
            AttnKind(1, arch.swa_num_heads, arch.swa_num_kv_heads,
                     arch.swa_head_dim,
                     arch.swa_v_head_dim or arch.swa_head_dim,
                     arch.sliding_window, arch.swa_sink))


def _layer_schedule(arch: ModelArch):
    """(stacks, runs) of a model whose layers name their kinds: layers
    of one mixer and one FFN kind share a stack, in layer order; a run
    is a stretch of consecutive layers of one stack."""
    experts = arch.layer_experts or (0,) * arch.num_layers
    members: dict = {}
    runs: list = []
    seen = [0, 0, 0, 0]
    for kind, moe in zip(arch.layer_attention, experts):
        name = ("full", "window", "conv", "gdn")[kind] + ("_moe" if moe
                                                          else "_dense")
        at = len(members.setdefault(name, []))
        members[name].append((kind, bool(moe)))
        last = runs[-1] if runs else None
        if last is not None and last.stack == name:
            runs[-1] = LayerRun(name, last.stack_start, last.count + 1,
                                last.moe, kind, last.cache_start)
        else:
            runs.append(LayerRun(name, at, 1, bool(moe), kind, seen[kind]))
        seen[kind] += 1
    stacks = tuple(LayerGroup(name, 0, len(ls), ls[0][1], ls[0][0])
                   for name, ls in members.items())
    return stacks, tuple(runs)


def _layer_groups(arch: ModelArch) -> tuple[LayerGroup, ...]:
    if arch.num_experts > 0 and arch.moe_layer_start > 0:
        k = arch.moe_layer_start
        return (
            LayerGroup("dense", 0, k, False),
            LayerGroup("moe", k, arch.num_layers - k, True),
        )
    if arch.num_experts > 0:
        return (LayerGroup("moe", 0, arch.num_layers, True),)
    return (LayerGroup("dense", 0, arch.num_layers, False),)


def _head_major(w: jax.Array, heads: int) -> jax.Array:
    """A latent layer's ``kv_b_k`` or ``kv_b_v``, [..., dl, H*d] as
    ``init_params`` draws it, in the form the absorbed products batch
    over: [..., H, d, dl]."""
    *lead, dl, hd = w.shape
    return jnp.moveaxis(w.reshape(*lead, dl, heads, hd // heads), -3, -1)


def _prefetch_stack(stack: dict):
    """Layer-ahead slabs for the comm-overlap decode scan
    (docs/multichip.md): the QUANTIZED o/down planes rolled one layer
    forward on the stack axis, so the scan body at layer L slices layer
    L+1's slab and hands it to the fused kernel's prefetch stream.  The
    roll wraps the last layer to layer 0 — which is exactly the slab
    the NEXT decode step reads first.  bf16 stacks (no q planes) add
    nothing: prefetch is a quantized-weights optimization and the plain
    path stays untouched."""
    out = {}
    for name in ("o", "down"):
        w = stack.get(name)
        if isinstance(w, dict) and ("q8" in w or "q4" in w):
            out[name] = {k: jnp.roll(v, -1, axis=0) for k, v in w.items()}
    return out or None


class TransformerLM:
    """Functional model: all state lives in explicit params/cache trees."""

    def __init__(self, arch: ModelArch, dtype=jnp.bfloat16,
                 attn_impl: str = "jax"):
        self.is_mla = arch.attention_kind == AttentionKind.MLA
        self.arch = arch
        self.dtype = dtype
        self.attn_impl = attn_impl  # "jax" | "pallas" (paged decode)
        self.lora_scaling = 0.0     # set by the tuner when lora keys exist
        self.ring = None            # (Mesh, axis) => sequence-parallel training
        # (Mesh, axis, head_axis|None, q_tile) => context-parallel
        # serving prefill (mode "prefill_cp"); set by the engine
        self.cp = None
        # (Mesh, axis) => collective-compute overlap for TP decode
        # (docs/multichip.md); set by the engine when the comm-overlap
        # gate resolves on.  Only the DECODE mode's row-parallel
        # projections (attention-out, MLP-down) route through the
        # pipelined ring — prefill/CP/PP paths never read this.
        self.overlap = None
        # (Mesh, head axis|None) => the Pallas attention kernels run
        # under shard_map, one call per head shard.  JAX refuses to
        # partition a Mosaic call by itself ("Mosaic kernels cannot be
        # automatically partitioned"), so the engine sets this on every
        # mesh; heads are independent, so the per-shard
        # call is the same kernel at H/tp and Hkv/tp heads.  A None
        # axis (heads do not divide) runs every head on every device.
        self.head_shard = None
        self.moe_impl = "dense"     # "dense" | "ragged" (grouped matmul)
        # a state-space mixer beside attention in every block: the
        # cache then holds a per-slot state pool beside the pages
        # (docs/kv-cache.md), and prefix reuse, PD and speculation
        # are refused by the engine
        self.has_ssm = arch.ssm_state > 0
        # short-convolution layers (lfm2): their last inputs a slot are
        # the state pool's rows, and no page (docs/kv-cache.md, "A row
        # of conv state"); ``has_state``: the cache holds a state pool
        self.has_conv = arch.conv_layers > 0
        # delta-rule layers (olmo_hybrid): a matrix a head and the
        # convolutions' last inputs a slot, and no page ("A row of
        # matrix state")
        self.has_gdn = arch.gdn_layers > 0
        self.has_state = self.has_ssm or self.has_conv or self.has_gdn
        # Pallas grouped matmul, and the Pallas un-sort of a shared
        # expert layer's prefill (set by the engine)
        self.moe_kernel = False
        # layers that name their kinds: stacks by kind, a schedule of
        # runs, one page pool and table an attention kind
        self.kinds = None
        self.runs = ()
        if arch.layer_attention is not None:
            if self.is_mla or self.has_ssm:
                raise NotImplementedError(
                    "per-layer attention kinds with latent attention or "
                    "a state-space mixer are not implemented")
            self.kinds = attention_kinds(arch)
            self.groups, self.runs = _layer_schedule(arch)
        else:
            if arch.norm_after or arch.qk_norm_whole or not arch.rotary:
                raise NotImplementedError(
                    "norms after the operators, a QK norm over the whole "
                    "projection and attention with no rotary embedding "
                    "are implemented for layers that name their kinds")
            self.groups = _layer_groups(arch)
        self.vocab_padded = -(-arch.vocab_size // VOCAB_ALIGN) * VOCAB_ALIGN
        # rope tables are concrete constants; computing them lazily inside
        # a traced scan body would cache tracers
        if self.is_mla:
            from dataclasses import replace

            rope_arch = replace(arch, head_dim=arch.mla_dims[1],
                                partial_rotary_factor=1.0)
            self._inv_freq_global = nn.rope_frequencies(rope_arch)
        elif not arch.rotary:
            # no rotary embedding: no table
            self._inv_freq_global = None
        else:
            self._inv_freq_global = nn.rope_frequencies(arch)
        # attention_factor only reads rope_scaling/max_pos, which the
        # MLA rope_arch replace() leaves untouched
        self._rope_mscale = nn.rope_attention_factor(arch)
        # longrope (phi-3 family): per-position short/long table switch
        self._longrope = None if self.is_mla else nn.longrope_tables(arch)
        self._inv_freq_local = self._make_inv_freq_local()
        if self.kinds is not None and arch.rotary:
            from dataclasses import replace

            # one table a kind: the window kind has its own theta
            self._kind_inv_freq = (self._inv_freq_global,) + tuple(
                nn.rope_frequencies(replace(
                    arch, head_dim=k.head_dim,
                    rope_theta=arch.swa_rope_theta, rope_scaling=None))
                for k in self.kinds[1:])

    def _rope_select(self, positions):
        """(inv_freq, mscale) for the global table — per-position
        short/long selection when the arch is longrope (positions past
        the original trained length use the long factors)."""
        if self._longrope is None:
            return self._inv_freq_global, self._rope_mscale
        short, long, orig, short_m, long_m = self._longrope
        mask = positions >= orig                       # [..., seq]
        inv = jnp.where(mask[..., None], long, short)  # [..., seq, half]
        ms = jnp.where(mask[..., None, None], long_m, short_m)
        return inv, ms

    # ------------------------------------------------------------------
    # Parameter construction
    # ------------------------------------------------------------------

    def _layer_specs(self, moe: bool, kind: int = 0
                     ) -> dict[str, tuple[tuple[int, ...], tuple]]:
        a = self.arch
        E, H, Hkv, D, I = (a.hidden_size, a.num_heads, a.num_kv_heads,
                           a.head_dim, a.intermediate_size)
        Dv = D
        gdn = self.kinds is not None and kind == MIXER_GDN
        # (a short convolution or a delta rule: none of attention's
        # projections, biases or QK norm)
        conv = gdn or (self.kinds is not None and kind == MIXER_CONV)
        if self.kinds is not None and not conv:
            ak = self.kinds[kind]
            H, Hkv, D, Dv = (ak.num_heads, ak.num_kv_heads, ak.head_dim,
                             ak.v_head_dim)
        if gdn:
            # a gated delta rule: [q | k | v | out gate] in (whole lane
            # tiles: with the two gates' 2 x 30 columns beside them the
            # compiler re-laid the stack out once a program, 762 MiB),
            # the gates a head [a | b], the taps over [q | k | v]
            # (``gdn_conv_w[K-1]`` on the newest input), the decay's A
            # and step bias a head, the gated norm's gain a value lane,
            # out
            Hd, C, inner = a.gdn_heads, a.gdn_conv_dim, \
                a.gdn_heads * a.gdn_value_dim
            specs: dict[str, tuple[tuple[int, ...], tuple]] = {
                "attn_norm": ((E,), ("embed",)),
                "gdn_in": ((E, C + inner), ("embed", None)),
                "gdn_gates": ((E, 2 * Hd), ("embed", None)),
                "gdn_conv_w": ((a.gdn_conv, C), (None, None)),
                "gdn_a_log": ((Hd,), (None,)),
                "gdn_dt_bias": ((Hd,), (None,)),
                "gdn_norm": ((a.gdn_value_dim,), (None,)),
                "gdn_out": ((inner, E), (None, "embed")),
            }
        elif conv:
            # a gated short convolution: [B | C | u] in, the taps
            # (``conv_w[k]`` weighs the input k tokens back), out
            specs = {
                "attn_norm": ((E,), ("embed",)),
                "conv_in": ((E, 3 * E), ("embed", None)),
                "conv_w": ((a.conv_kernel, E), (None, None)),
                "conv_out": ((E, E), (None, "embed")),
            }
        elif self.is_mla:
            dn, dr, dl, dv = a.mla_dims
            specs = {
                "attn_norm": ((E,), ("embed",)),
                "kv_a": ((E, dl + dr), ("embed", None)),
                "kv_a_norm": ((dl,), (None,)),
                "kv_b_k": ((dl, H * dn), (None, "heads")),
                "kv_b_v": ((dl, H * dv), (None, "heads")),
                "o": ((H * dv, E), ("heads", "embed")),
            }
            if a.q_lora_rank:
                specs.update({
                    "q_a": ((E, a.q_lora_rank), ("embed", None)),
                    "q_a_norm": ((a.q_lora_rank,), (None,)),
                    "q_b": ((a.q_lora_rank, H * (dn + dr)), (None, "heads")),
                })
            else:
                specs["q"] = ((E, H * (dn + dr)), ("embed", "heads"))
        else:
            specs = {
                "attn_norm": ((E,), ("embed",)),
                "q": ((E, H * D), ("embed", "heads")),
                "k": ((E, Hkv * D), ("embed", "kv_heads")),
                "v": ((E, Hkv * Dv), ("embed", "kv_heads")),
                "o": ((H * Dv, E), ("heads", "embed")),
            }
            if self.kinds is not None and self.kinds[kind].sink:
                specs["sink"] = ((H,), ("heads",))
        if (a.qkv_bias or a.linear_bias) and not conv:
            specs.update({
                "q_bias": ((H * D,), ("heads",)),
                "k_bias": ((Hkv * D,), ("kv_heads",)),
                "v_bias": ((Hkv * D,), ("kv_heads",)),
            })
        if a.linear_bias:
            specs["o_bias"] = ((E,), ("embed",))
        if a.qk_norm and not conv:
            # (one gain a head's lane, or one a lane of the projection)
            whole = a.qk_norm_whole
            specs["q_norm"] = ((H * D if whole else D,), (None,))
            specs["k_norm"] = ((Hkv * D if whole else D,), (None,))
        if a.norm_type == "layernorm":
            specs["attn_norm_bias"] = ((E,), ("embed",))
        if not a.parallel_residual:
            specs["mlp_norm"] = ((E,), ("embed",))
            if a.norm_type == "layernorm":
                specs["mlp_norm_bias"] = ((E,), ("embed",))
        if a.pre_post_norm:
            specs["post_attn_norm"] = ((E,), ("embed",))
            specs["post_mlp_norm"] = ((E,), ("embed",))
        if a.ssm_state:
            Hm, Cd = a.ssm_heads, a.ssm_conv_dim
            specs.update({
                "ssm_in": ((E, a.ssm_proj_dim), ("embed", None)),
                "ssm_conv": ((a.ssm_conv, Cd), (None, None)),
                "ssm_conv_bias": ((Cd,), (None,)),
                "ssm_dt_bias": ((Hm,), (None,)),
                "ssm_a_log": ((Hm,), (None,)),
                "ssm_d": ((Hm,), (None,)),
                "ssm_norm": ((a.ssm_inner,), (None,)),
                "ssm_out": ((a.ssm_inner, E), (None, "embed")),
            })
        if moe:
            # the router scores every expert; the stacks hold this
            # chip's share of them (all, unless the layer is shared)
            X, Xh = a.num_experts, a.experts_held
            Im = a.moe_intermediate_size or I
            specs.update({
                "router": ((E, X), ("embed", "expert")),
                "experts_gate": ((Xh, E, Im), ("expert", "embed", "intermediate")),
                "experts_up": ((Xh, E, Im), ("expert", "embed", "intermediate")),
                "experts_down": ((Xh, Im, E), ("expert", "intermediate", "embed")),
            })
            if a.router_bias:
                specs["router_bias"] = ((X,), ("expert",))
            if a.num_shared_experts:
                Is = Im * a.num_shared_experts
                specs.update({
                    "shared_gate": ((E, Is), ("embed", "intermediate")),
                    "shared_up": ((E, Is), ("embed", "intermediate")),
                    "shared_down": ((Is, E), ("intermediate", "embed")),
                })
        else:
            if a.gated_mlp:
                specs["gate"] = ((E, I), ("embed", "intermediate"))
            specs["up"] = ((E, I), ("embed", "intermediate"))
            specs["down"] = ((I, E), ("intermediate", "embed"))
            if a.linear_bias:
                specs["up_bias"] = ((I,), ("intermediate",))
                specs["down_bias"] = ((E,), ("embed",))
        return specs

    def _top_specs(self) -> dict[str, tuple[tuple[int, ...], tuple]]:
        a = self.arch
        E = a.hidden_size
        specs = {
            "embed": ((self.vocab_padded, E), ("vocab", "embed")),
            "final_norm": ((E,), ("embed",)),
        }
        if a.norm_type == "layernorm":
            specs["final_norm_bias"] = ((E,), ("embed",))
        if not a.tie_word_embeddings:
            specs["lm_head"] = ((self.vocab_padded, E), ("vocab", "embed"))
        return specs

    def init_params(self, key: jax.Array) -> dict:
        """Random (synthetic) weights with sane init scales."""
        params: dict = {}
        keys = jax.random.split(key, len(self.groups) + 1)
        follow = self._draw_multipliers()
        for spec_key, (shape, _) in self._top_specs().items():
            if "norm" in spec_key:
                params[spec_key] = jnp.zeros(shape, self.dtype) if "bias" in spec_key or self.arch.norm_offset else jnp.ones(shape, self.dtype)
            else:
                params[spec_key] = (0.02 / follow.get(spec_key, 1.0)) * self._normal(
                    jax.random.fold_in(keys[0], _name_salt(spec_key)), shape)
        for gi, g in enumerate(self.groups):
            layer: dict = {}
            for name, (shape, _) in self._layer_specs(g.moe, g.kind).items():
                full = (g.count,) + shape
                if name in ("sink", "router_bias", "conv_w", "gdn_conv_w",
                            "gdn_norm") or (
                        name in ("q_norm", "k_norm")
                        and self.kinds is not None):
                    init = self._kind_draw(
                        name, jax.random.fold_in(keys[1 + gi],
                                                 _name_salt(name)), full)
                elif name in ("ssm_dt_bias", "ssm_a_log", "ssm_d", "ssm_conv",
                            "ssm_conv_bias", "gdn_a_log", "gdn_dt_bias"):
                    init = self._ssm_draw(
                        name, jax.random.fold_in(keys[1 + gi],
                                                 _name_salt(name)), full)
                elif "norm" in name and "bias" not in name:
                    init = jnp.zeros(full, self.dtype) if self.arch.norm_offset else jnp.ones(full, self.dtype)
                elif name.endswith("_bias") or "bias" in name:
                    init = jnp.zeros(full, self.dtype)
                else:
                    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
                    std = 1.0 / math.sqrt(fan_in)
                    if name in follow:
                        init = ((std / follow[name]) * jax.random.normal(
                            jax.random.fold_in(keys[1 + gi], _name_salt(name)),
                            full, jnp.float32)).astype(self.dtype)
                    else:
                        init = std * self._normal(
                            jax.random.fold_in(keys[1 + gi], _name_salt(name)), full)
                layer[name] = init
            if "router_bias" in layer:
                layer["router"], layer["router_bias"] = self._balanced_router(
                    jax.random.fold_in(keys[1 + gi], _name_salt("skew")),
                    layer["router"])
            params[g.name] = layer
        return params

    def _normal(self, key: jax.Array, shape: tuple) -> jax.Array:
        """A standard-normal draw in the model's type.  A model whose
        router carries a fitted correction bias (mimo_v2, the
        deepseek-v3 family) draws in float32 and rounds: JAX's
        bfloat16 sampler gives 128 distinct values with a mean of
        -0.012 (measured over 16.8M draws), every matrix then carries
        a rank-one part that maps the all-ones direction onto itself
        with a gain near one (sqrt(fan-in) x 0.012), and after seven
        layers 58-70% of the residual's energy is one direction common
        to every token: greedy decoding falls onto a handful of
        attractor tokens whatever the prompt, every row routes to the
        same experts, and whether a chip's share holds them is the
        seed's luck (PERF.md section 6, PR 38).  A model with
        delta-rule layers draws so too.  The other models keep the draw
        their tolerances were read with."""
        if not (self.arch.router_bias or self.arch.gdn_layers) \
                or self.dtype == jnp.float32:
            return jax.random.normal(key, shape, self.dtype)
        return jax.random.normal(key, shape, jnp.float32).astype(self.dtype)

    @cached_property
    def _ssm_mup(self) -> np.ndarray:
        """falcon-h1's ``mup_vector``: ``ssm_multipliers`` over the five
        segments [z | x | B | C | dt] of the mixer's input projection."""
        a = self.arch
        gn = a.ssm_groups * a.ssm_state
        widths = (a.ssm_inner, a.ssm_inner, gn, gn, a.ssm_heads)
        return np.concatenate([np.full((w,), m, np.float32) for w, m in
                               zip(widths, a.ssm_multipliers or (1.0,) * 5)])

    def _draw_multipliers(self) -> dict:
        """Synthetic weights of an architecture that carries its muP
        multipliers in the forward pass (falcon-h1): each matrix that a
        multiplier follows is drawn at the multiplier's inverse, so that
        every branch moves the logits as a trained model's does.  Drawn
        at the plain scales, the published multipliers shrink attention,
        mixer and MLP to a hundredth of the residual, and a check
        against the reference passes a model with a branch deleted.
        The forward pass keeps every multiplier as published."""
        a = self.arch
        gate_m, down_m = a.mlp_multipliers or (None, None)
        follow = {"lm_head": a.lm_head_multiplier, "k": a.key_multiplier,
                  "o": a.attention_out_multiplier, "gate": gate_m,
                  "down": down_m, "ssm_out": a.ssm_out_multiplier}
        follow = {k: float(v) for k, v in follow.items() if v}
        if a.ssm_in_multiplier or a.ssm_multipliers:
            follow["ssm_in"] = float(a.ssm_in_multiplier or 1.0) \
                * self._ssm_mup
        # the embedding's scale alone is no muP multiplier (gemma's
        # sqrt(hidden)): it is compensated only beside the others
        if follow and a.embedding_multiplier:
            follow["embed"] = float(a.embedding_multiplier)
        return follow

    def _kind_draw(self, name: str, key: jax.Array, shape: tuple):
        """Synthetic draws of the small parameters that a plain normal
        draw would leave without effect.  ``sink``: the log of the
        window plus a standard normal, so the sink's column takes tenths
        of a window layer's probability (scores are of order one, and a
        sink at that scale against 128 of them would take a hundredth).
        ``router_bias``: zero here, fitted by ``_balanced_router``.
        ``conv_w``: every tap N(0, 1/sqrt(taps)), so the carried inputs
        weigh as much as the newest and a dropped state moves the
        logits (``gdn_conv_w``: the same).  ``q_norm``/``k_norm`` of a
        model whose layers name their kinds and ``gdn_norm``: 1 + N(0,
        0.1), so a dropped norm moves them."""
        z = jax.random.normal(key, shape, jnp.float32)
        if name in ("conv_w", "gdn_conv_w"):
            return (z / math.sqrt(shape[-2])).astype(self.dtype)
        if name in ("q_norm", "k_norm", "gdn_norm"):
            return (1.0 + 0.1 * z).astype(self.dtype)
        if name != "sink":
            return jnp.zeros(shape, self.dtype)
        return (math.log(max(self.arch.sliding_window or 2, 2))
                + z).astype(self.dtype)

    # the fit of a correction bias: samples, steps, first step
    _BALANCE = (4096, 200, 0.02)

    def _balanced_router(self, key: jax.Array, router: jax.Array):
        """A synthetic router as a trained one is: experts of unequal
        pull, and a correction bias that evens their load.  ``router``
        [layers, E, X] drawn plain gets a log-normal (0.25) scale an
        expert, so that by their scores alone a sixteenth of the experts
        would take a fifth of the pairs and some none; the bias is then
        fitted to the router as drawn by the rule that trains it
        (``noaux_tc``: the bias of an expert over its even share goes
        down, under it up), on seeded standard-normal inputs (a router
        reads an RMS-normed stream), until every expert is chosen about
        equally often.  So the bias decides two choices in five, as it
        must for a check to see it, and every chip's share of the
        experts gets its share of the pairs, whatever the seed.
        Returns (router, bias [layers, X]) in the model's dtype."""
        a = self.arch
        L, E, X = router.shape
        k = a.num_experts_per_tok
        n, steps, step0 = self._BALANCE
        k_scale, k_x = jax.random.split(key)
        scale = jnp.exp(0.25 * jax.random.normal(k_scale, (L, 1, X),
                                                 jnp.float32))
        router = (router.astype(jnp.float32) * scale).astype(self.dtype)
        x = jax.random.normal(k_x, (n, E), jnp.float32)
        logits = jnp.einsum("ne,lex->lnx", x, router.astype(jnp.float32))
        if a.router_scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits)
        else:
            scores = jax.nn.softmax(logits, axis=-1)

        def fit(t, bias):
            _, idx = jax.lax.top_k(scores + bias[:, None, :], k)
            chosen = jnp.zeros((L, X), jnp.float32).at[
                jnp.arange(L)[:, None], idx.reshape(L, -1)].add(1.0)
            load = chosen * (X / (n * k))          # 1 = the even share
            bias = bias + step0 * 0.985 ** t * jnp.clip(1.0 - load, -1.0, 1.0)
            return bias - jnp.mean(bias, axis=-1, keepdims=True)

        bias = jax.lax.fori_loop(0, steps, fit,
                                 jnp.zeros((L, X), jnp.float32))
        return router, bias.astype(self.dtype)

    def _ssm_draw(self, name: str, key: jax.Array, shape: tuple):
        """The mixer's small parameters by Mamba-2's conventions: A in
        1..16, the step dt (softplus of its bias) log-uniform between
        1e-3 and 1e-1, D one, and a convolution with a bias.  A
        delta-rule layer's decay (``gdn_a_log``, ``gdn_dt_bias``) is
        initialised the same way."""
        f32 = jnp.float32
        if name in ("ssm_a_log", "gdn_a_log"):
            v = jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
        elif name in ("ssm_dt_bias", "gdn_dt_bias"):
            dt = jnp.exp(jax.random.uniform(key, shape, f32, math.log(1e-3),
                                            math.log(1e-1)))
            v = dt + jnp.log(-jnp.expm1(-dt))       # softplus's inverse
        elif name == "ssm_d":
            v = jnp.ones(shape, f32)
        elif name == "ssm_conv":
            v = jax.random.normal(key, shape, f32) / math.sqrt(shape[-2])
        else:                                       # ssm_conv_bias
            v = 0.1 * jax.random.normal(key, shape, f32)
        return v.astype(self.dtype)

    def param_logical_axes(self) -> dict:
        """Tree matching init_params with logical axis names per dim."""
        axes: dict = {}
        for name, (_, ax) in self._top_specs().items():
            axes[name] = ax
        for g in self.groups:
            axes[g.name] = {
                name: ("layers",) + ax
                for name, (_, ax) in self._layer_specs(g.moe, g.kind).items()
            }
        return axes

    def latent_head_major(self, params: dict) -> dict:
        """The tree with every latent layer group's ``kv_b_k`` and
        ``kv_b_v`` held a second time head-major, as ``kv_b_k_hm`` [n,
        H, dn, dl] and ``kv_b_v_hm`` [n, H, dv, dl]: what the decode
        kernel's absorb and expand products and flash prefill's
        expansion read (a product batched over heads wants the head
        axis major, and given the stacks as drawn the compiler
        transposes both whole, once a program).  Made once, on the
        engine's load path, from the tree as ``init_params`` draws it,
        which stays as it is: the XLA paths read the flat form.  One
        program makes the pair, so nothing but the pair stays resident
        behind it (the pool is sized from what the device reports in
        use).  The head axis is the one ``_layer_specs`` shards by
        ``heads``."""
        H = self.arch.num_heads
        flat = {name: {k: sub[k] for k in ("kv_b_k", "kv_b_v")}
                for name, sub in params.items()
                if isinstance(sub, dict) and "kv_b_k" in sub}
        made = jax.jit(lambda stacks: {
            name: {k + "_hm": _head_major(w, H) for k, w in sub.items()}
            for name, sub in stacks.items()})(flat)
        return {name: {**sub, **made[name]} if name in made else sub
                for name, sub in params.items()}

    def param_count(self, params: dict) -> int:
        return sum(x.size for x in jax.tree.leaves(params))

    def stack_layers(self, g: LayerGroup) -> list:
        """The model's layers that stack ``g`` holds, in the stack's
        order (a checkpoint names tensors by layer)."""
        if self.kinds is None:
            return list(range(g.start, g.start + g.count))
        experts = self.arch.layer_experts or (0,) * self.arch.num_layers
        return [l for l, (kind, moe) in enumerate(
            zip(self.arch.layer_attention, experts))
            if (kind, bool(moe)) == (g.kind, g.moe)]

    # ------------------------------------------------------------------
    # Flags / rope tables
    # ------------------------------------------------------------------

    def _make_inv_freq_local(self) -> jax.Array:
        # gemma-3 sliding layers use unscaled theta=10k rope
        a = self.arch
        if a.sliding_window_pattern and a.sliding_window:
            from dataclasses import replace

            local = replace(a, rope_theta=10000.0, rope_scaling=None)
            return nn.rope_frequencies(local)
        return self._inv_freq_global

    def _window_flags(self, start: int, count: int) -> Optional[jax.Array]:
        """Per-layer int32 window sizes (or _BIG_WINDOW for global)."""
        a = self.arch
        if not a.sliding_window:
            return None
        idx = jnp.arange(start, start + count)
        if a.sliding_window_pattern:
            is_global = (idx + 1) % a.sliding_window_pattern == 0
        else:
            is_global = jnp.zeros_like(idx, dtype=bool)
        return jnp.where(is_global, _BIG_WINDOW, a.sliding_window).astype(jnp.int32)

    @property
    def moe_combine(self) -> Optional[str]:
        """What takes a grouped expert layer's rows back to their
        tokens, as ``/health`` names it: ``pallas`` where the compact
        un-sort's kernel runs (a layer shared between chips, at the
        widths where a pass holds fewer pairs than were routed:
        ``nn.moe_mlp_ragged``), ``xla`` otherwise; None with no such
        layer."""
        if self.arch.num_experts == 0 or self.moe_impl != "ragged":
            return None
        shared = self.arch.expert_shards > 1
        return "pallas" if self.moe_kernel and shared else "xla"

    @property
    def _scale(self) -> float:
        a = self.arch
        if self.is_mla:
            dn, dr, _, _ = a.mla_dims
            base = 1.0 / math.sqrt(dn + dr)
            # deepseek-yarn: the all-dim mscale lands in the softmax
            # scale (squared — applied to both q and k), while the
            # mscale/mscale_all_dim RATIO rides the rope table
            s = a.rope_scaling or {}
            stype = str(s.get("rope_type", s.get("type", ""))).lower()
            if stype == "yarn" and s.get("mscale_all_dim") is not None:
                m = nn.yarn_get_mscale(float(s.get("factor", 1.0)),
                                       float(s["mscale_all_dim"]))
                base *= m * m
            return base
        denom = a.query_pre_attn_scalar if a.query_pre_attn_scalar else a.head_dim
        return 1.0 / math.sqrt(denom)

    # ------------------------------------------------------------------
    # MLA (DeepSeek-style latent attention)
    # ------------------------------------------------------------------

    def _mla_attention(self, h, p, ck, cv, li, ks, vs, mode, *, positions,
                       page_tables, lengths, true_lens, active,
                       start_pos=None):
        """Latent attention: project to a shared compressed KV latent,
        cache only [c_kv ; k_rope], expand per-head K/V on use (prefill)
        or absorb projections into the query (decode).

        ``ck`` is the full layer-group latent cache riding the layer
        scan as a carry, [Lg, P, ps, 1, dl+dr], or token-flat at the
        stored lanes as the decode kernel reads a page, [Lg, P, ps,
        lanes] (``kv_cache.create_kv_cache``, ``latent_kernel``);
        ``li`` selects this layer.  Over a token-flat pool decode is the
        Pallas kernel (``ops/mla_decode_attention.py``: the absorbed
        query against the live pages, each read once as keys and as
        values) and a fresh chunk is flash prefill on the expanded
        heads (keys of dn+dr, values of dv, one KV head a query head:
        the expanded form costs 2(dn+dr+dv) operations a pair and head,
        the absorbed form 2(2 dl + dr)); a chunk with earlier context
        and every other pool keep the XLA paths.
        ``ks``/``vs`` are the group's page-scale pools when the latent
        stream is int8-quantized (None otherwise); only ``ks`` is live —
        MLA has a single cached stream — but both ride the carry so the
        pytree shape matches the GQA path."""
        a = self.arch
        B, T, E = h.shape
        H = a.num_heads
        dn, dr, dl, dv = a.mla_dims
        rope = partial(nn.apply_rope, inv_freq=self._inv_freq_global,
                       head_dim=dr, mscale=self._rope_mscale,
                       interleave=a.rope_interleave)

        if "q_a" in p:
            x = nn.rms_norm(nn.linear(h, p["q_a"]), p["q_a_norm"],
                            a.rms_norm_eps, False)
            q = nn.linear(x, p["q_b"])
        else:
            x = h
            q = nn.linear(x, p["q"])
        if (dn + dr) % 128 and B * T < x.shape[-1]:
            # a query head that is no whole number of 128-lane tiles
            # (192 = 128 | 64), at fewer rows than the weight has: the
            # split into heads re-lays the activations and not the
            # stack of weights (docs/kv-cache.md, "The attention
            # weights are multiplied where they lie": the rule and its
            # bound on rows, for this place and ``_attn_qkv``'s two)
            q = jax.lax.optimization_barrier(q)
        q = q.reshape(B, T, H, dn + dr)
        q_nope, q_rope = q[..., :dn], q[..., dn:]
        q_rope = rope(q_rope, positions)

        kv = nn.linear(h, p["kv_a"])             # [B, T, dl+dr]
        c_kv = nn.rms_norm(kv[..., :dl], p["kv_a_norm"], a.rms_norm_eps, False)
        k_rope = rope(kv[..., dl:][:, :, None, :], positions)[:, :, 0]
        latent = jnp.concatenate([c_kv, k_rope], axis=-1)  # [B, T, dl+dr]
        # a kernel-read pool is token-flat at its stored lanes
        kernel_pool = ck is not None and ck.ndim == 4
        if kernel_pool:
            latent = jnp.pad(latent, ((0, 0), (0, 0),
                                      (0, ck.shape[-1] - (dl + dr))))
        ps = None if ck is None else ck.shape[2]

        def plain():
            return attn.mla_prefill_attention(
                q_nope, q_rope, c_kv, k_rope, p["kv_b_k"], p["kv_b_v"],
                scale=self._scale, true_len=true_lens)

        if mode == "train":
            out = plain()
        elif mode == "prefill":
            start = (start_pos if start_pos is not None
                     else jnp.zeros((B,), jnp.int32))
            if ks is not None:
                ck, ks = write_prefill_tokens_q(
                    ck, ks, latent[:, :, None, :], page_tables,
                    start, true_lens, ps, layer=li)
            else:
                ck = write_prefill_tokens(ck, latent[:, :, None, :],
                                          page_tables, start, true_lens, ps,
                                          layer=li)
            if start_pos is not None:
                # chunked prefill: attend over the paged latent history
                # (earlier chunks) + this chunk, absolute positions
                out = attn.mla_paged_context_attention(
                    q_nope, q_rope, ck, page_tables, start, true_lens,
                    p["kv_b_k"], p["kv_b_v"], scale=self._scale,
                    kv_lora_rank=dl, layer=li, latent_scale=ks)
            elif kernel_pool and self.attn_impl == "pallas":
                out = self._mla_flash_prefill(q_nope, q_rope, c_kv, k_rope,
                                              p, true_lens)
            else:
                out = plain()
        else:
            if ks is not None:
                ck, ks = write_decode_tokens_q(
                    ck, ks, latent[:, 0][:, None, :], page_tables,
                    positions[:, 0], ps, active, layer=li)
            else:
                ck = write_decode_tokens(ck, latent[:, 0][:, None, :],
                                         page_tables, positions[:, 0], ps,
                                         active, layer=li)
            if kernel_pool and self.attn_impl == "pallas":
                out = self._mla_decode_kernel(
                    q_nope[:, 0], q_rope[:, 0], ck, page_tables, lengths,
                    li, p)[:, None]
            else:
                out = attn.mla_paged_decode_attention(
                    q_nope[:, 0], q_rope[:, 0], ck, page_tables, lengths,
                    p["kv_b_k"], p["kv_b_v"], scale=self._scale,
                    kv_lora_rank=dl, layer=li, latent_scale=ks)[:, None]
        attn_out = nn.linear(out.reshape(B, T, H * dv), p["o"])
        return attn_out, ck, cv, ks, vs

    def _kv_b_head_major(self, p: dict) -> tuple:
        """A layer's ``kv_b_k`` and ``kv_b_v`` as [H, d, dl], what both
        kernels' products read: the pair the engine holds so
        (``latent_head_major``), else made of the flat form in the
        program, where the compiler copies them as it sees fit."""
        if "kv_b_k_hm" in p:
            return p["kv_b_k_hm"], p["kv_b_v_hm"]
        H = self.arch.num_heads
        return _head_major(p["kv_b_k"], H), _head_major(p["kv_b_v"], H)

    def _mla_flash_prefill(self, q_nope, q_rope, c_kv, k_rope, p, true_lens):
        """A fresh chunk through flash prefill on the EXPANDED heads:
        keys ``[k_nope | k_rope]`` at their stored lanes (q padded
        alike), values of dv, one KV head a query head; temporaries of
        the prefill program, never cached.  Returns [B, T, H, dv]."""
        from kaito_tpu.engine.ops.flash_prefill import (
            flash_prefill_attention)

        B, T, H, dn = q_nope.shape
        _, dr, _, dv = self.arch.mla_dims
        with jax.named_scope("mla_expand"):
            wk, wv = self._kv_b_head_major(p)
            k_nope = jnp.einsum("btl,hdl->bthd", c_kv, wk)
            v = jnp.einsum("btl,hdl->bthd", c_kv, wv)
        zeros = jnp.zeros((B, T, H, stored_key_dim(dn + dr) - (dn + dr)),
                          k_nope.dtype)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, T, H, dr)),
             zeros], axis=-1)
        q = jnp.concatenate([q_nope, q_rope, zeros], axis=-1)
        return flash_prefill_attention(q, k, v, true_lens,
                                       jnp.int32(_BIG_WINDOW),
                                       scale=self._scale)

    def _mla_decode_kernel(self, q_nope, q_rope, pool, page_tables, lengths,
                           li, p):
        """Decode over a kernel-read latent pool, in the absorbed form:
        ``q_nope`` [B, H, dn] into latent space (``mla_absorb``), all
        heads against the one stream of the live pages
        (``mla_attention``, the Pallas kernel), the attended latent out
        through ``W_uv`` (``mla_expand``).  Returns [B, H, dv]."""
        from kaito_tpu.engine.ops.mla_decode_attention import (
            mla_paged_decode_attention_pallas)

        B, H, dn = q_nope.shape
        _, dr, dl, dv = self.arch.mla_dims
        wk, wv = self._kv_b_head_major(p)
        with jax.named_scope("mla_absorb"):
            q_lat = jnp.einsum("bhd,hdl->bhl", q_nope, wk,
                               preferred_element_type=jnp.float32)
            q = jnp.concatenate(
                [q_lat * self._scale,
                 q_rope.astype(jnp.float32) * self._scale,
                 jnp.zeros((B, H, pool.shape[-1] - dl - dr), jnp.float32)],
                axis=-1).astype(pool.dtype)
        with jax.named_scope("mla_attention"):
            out_lat = mla_paged_decode_attention_pallas(
                q, pool, page_tables, lengths, li, value_lanes=dl)
        with jax.named_scope("mla_expand"):
            out = jnp.einsum("bhl,hdl->bhd", out_lat, wv,
                             preferred_element_type=jnp.float32)
        return out.astype(q_nope.dtype)

    # ------------------------------------------------------------------
    # Layer body (shared by prefill and decode via mode switch)
    # ------------------------------------------------------------------

    def _pallas_attention(self, kernel, args, head_dims, out_head_dim):
        """Call a Pallas attention kernel on ``args``, per head shard
        when the model sits on a mesh (see ``head_shard``).
        ``head_dims[i]`` is the (kv-)head dimension of ``args[i]``, or
        None for an operand every shard needs whole."""
        if self.head_shard is None:
            return kernel(*args)
        mesh, axis = self.head_shard

        def spec(dim):
            return P() if dim is None else P(*([None] * dim), axis)

        return jax.shard_map(
            kernel, mesh=mesh, in_specs=tuple(spec(d) for d in head_dims),
            out_specs=spec(out_head_dim), check_vma=False)(*args)

    def _attn_qkv(self, x: jax.Array, p: dict, positions: jax.Array,
                  window: Optional[jax.Array], lora: Optional[dict] = None,
                  lora_ids: Optional[jax.Array] = None, overlap=None,
                  kind: Optional[AttnKind] = None):
        """Project to q/k/v heads with norms+rope applied.

        x: [B, T, E]; positions: [B, T] absolute positions.  ``kind``:
        the layer's attention kind, of a model whose layers name
        theirs: its head counts and sizes, its rope table, values
        scaled by ``attention_value_scale``, and q and k zero-padded to
        ``kind.k_dim`` (the scores do not change).

        ``overlap`` is the engine's (mesh, axis) comm-overlap handle
        (docs/multichip.md): when set, the COLUMN-parallel q projection
        — the widest of the three, head-sharded over the TP axis —
        routes through the pipelined all-gather+matmul ring so the
        activation gather hides behind the partial dots.  k/v (the
        narrow kv-head projections) and the rank-r LoRA deltas stay on
        the plain path, whose collectives are noise next to q's.
        """
        a = self.arch
        B, T, _ = x.shape
        ls = self.lora_scaling
        if a.attention_in_multiplier is not None:
            x = x * jnp.asarray(a.attention_in_multiplier, x.dtype)
        if overlap is not None:
            from kaito_tpu.engine.ops.overlap_collectives import (
                ag_matmul_eligible, all_gather_matmul)

            mesh, axis = overlap
            n = int(mesh.shape[axis])
            if ag_matmul_eligible(x, p["q"], n):
                q_proj = all_gather_matmul(x, p["q"], mesh,
                                           axis_name=axis)
            else:
                q_proj = nn.linear(x, p["q"])
        else:
            q_proj = nn.linear(x, p["q"])
        q = q_proj + nn.lora_delta(x, p, "q", ls) \
            + nn.multi_lora_delta(x, lora, "q", lora_ids)
        k = nn.linear(x, p["k"]) + nn.lora_delta(x, p, "k", ls) \
            + nn.multi_lora_delta(x, lora, "k", lora_ids)
        v = nn.linear(x, p["v"]) + nn.lora_delta(x, p, "v", ls) \
            + nn.multi_lora_delta(x, lora, "v", lora_ids)
        if "q_bias" in p:
            q, k, v = q + p["q_bias"], k + p["k_bias"], v + p["v_bias"]
        if a.key_multiplier is not None:
            k = k * jnp.asarray(a.key_multiplier, k.dtype)
        if kind is not None:
            if kind.k_dim != kind.head_dim or a.qk_norm_whole:
                # keys wider than the head (no whole number of 128-lane
                # tiles), or a QK norm over the whole projection (the
                # values' stack would be copied once a program and
                # sliced a step): the split into heads re-lays the
                # activations and not the weights, here at every width
                # (docs/kv-cache.md, "The attention weights are
                # multiplied where they lie")
                q, k, v = jax.lax.optimization_barrier((q, k, v))
            if a.qk_norm and a.qk_norm_whole:
                # one norm over the whole projection, before the split
                q = nn.rms_norm(q, p["q_norm"], a.rms_norm_eps, a.norm_offset)
                k = nn.rms_norm(k, p["k_norm"], a.rms_norm_eps, a.norm_offset)
            q = q.reshape(B, T, kind.num_heads, kind.head_dim)
            k = k.reshape(B, T, kind.num_kv_heads, kind.head_dim)
            v = v.reshape(B, T, kind.num_kv_heads, kind.v_head_dim)
            if a.attention_value_scale is not None:
                v = v * jnp.asarray(a.attention_value_scale, v.dtype)
            if a.qk_norm and not a.qk_norm_whole:
                q = nn.rms_norm(q, p["q_norm"], a.rms_norm_eps, a.norm_offset)
                k = nn.rms_norm(k, p["k_norm"], a.rms_norm_eps, a.norm_offset)
            if a.rotary:
                inv_freq = self._kind_inv_freq[kind.index]
                q = nn.apply_rope(q, positions, inv_freq, kind.head_dim)
                k = nn.apply_rope(k, positions, inv_freq, kind.head_dim)
            pad = kind.k_dim - kind.head_dim
            if pad:
                widths = ((0, 0), (0, 0), (0, 0), (0, pad))
                q, k = jnp.pad(q, widths), jnp.pad(k, widths)
            return q, k, v
        if B * T < x.shape[-1]:
            # fewer rows than the weight has (decode, a short chunk):
            # the split into heads, and the rotary embedding's cut of a
            # head's rotated lanes into halves, re-lay the activations
            # and not the weight stacks (docs/kv-cache.md, "The
            # attention weights are multiplied where they lie")
            q, k, v = jax.lax.optimization_barrier((q, k, v))
        q = q.reshape(B, T, a.num_heads, a.head_dim)
        k = k.reshape(B, T, a.num_kv_heads, a.head_dim)
        v = v.reshape(B, T, a.num_kv_heads, a.head_dim)
        if a.qk_norm:
            q = nn.rms_norm(q, p["q_norm"], a.rms_norm_eps, a.norm_offset)
            k = nn.rms_norm(k, p["k_norm"], a.rms_norm_eps, a.norm_offset)
        if window is None or self._inv_freq_local is self._inv_freq_global:
            inv_freq, mscale = self._rope_select(positions)
        else:
            # sliding-window mix (gemma-3): local layers use the
            # unscaled 10k table with no magnitude correction (no
            # supported arch mixes sliding windows with longrope)
            inv_freq = jnp.where(window >= _BIG_WINDOW,
                                 self._inv_freq_global, self._inv_freq_local)
            mscale = jnp.where(window >= _BIG_WINDOW,
                               self._rope_mscale, 1.0)
        q = nn.apply_rope(q, positions, inv_freq, a.head_dim, mscale=mscale)
        k = nn.apply_rope(k, positions, inv_freq, a.head_dim, mscale=mscale)
        return q, k, v

    def _mlp(self, x: jax.Array, p: dict, moe: bool,
             lora: Optional[dict] = None,
             lora_ids: Optional[jax.Array] = None,
             overlap=None, pf_down=None, valid=None, with_stats=False,
             expert_layer=None):
        """The block's FFN.  ``valid`` [B, T] bool: the tokens an expert
        layer routes (None: all); ``with_stats``: an expert layer also
        returns its counters; ``expert_layer``: ``p``'s expert stacks
        are whole and this is the layer's index (nn.moe_mlp_ragged)."""
        if moe:
            B, T, E = x.shape
            if self.moe_impl == "ragged":
                out = nn.moe_mlp_ragged(
                    x.reshape(B * T, E), p, self.arch,
                    valid=None if valid is None else valid.reshape(B * T),
                    kernel=self.moe_kernel, with_stats=with_stats,
                    layer=expert_layer)
                if with_stats:
                    return out[0].reshape(B, T, E), out[1]
                return out.reshape(B, T, E)
            y = nn.moe_mlp(x.reshape(B * T, E), p, self.arch)
            return y.reshape(B, T, E)
        return nn.mlp(x, p, self.arch, self.lora_scaling,
                      serve_lora=lora, lora_ids=lora_ids,
                      overlap=overlap, pf_down=pf_down)

    def _norm(self, x, p, name):
        if self.arch.norm_type == "layernorm":
            return nn.layer_norm(x, p[name], p.get(f"{name}_bias"), self.arch.rms_norm_eps)
        return nn.rms_norm(x, p[name], self.arch.rms_norm_eps, self.arch.norm_offset)

    def _layer(self, x, p, ck, cv, li, window, moe, mode, *,
               positions, page_tables, lengths, true_lens, active,
               start_pos=None, lora=None, lora_ids=None,
               ks=None, vs=None, pf=None, ssm=None,
               ssm_rows=None, kind: Optional[AttnKind] = None, stats=None,
               expert_layer=None):
        """One transformer block. Returns (x, ck, cv, ks, vs, ssm).

        ``kind``: the layer's attention kind, of a model whose layers
        name theirs; ``ck``/``cv``/``page_tables`` are then that kind's
        pools and table, and the return has a seventh element, as a
        latent-attention layer's has: ``stats`` (int32 [4] or None) with
        an expert layer's counters added.

        ``ssm`` is the mixer's per-slot pools (state [Lg, S, H, P, N],
        convolution tail [Lg, S, K-1, C]) of a model with a state-space
        mixer beside attention, riding the same carry; None otherwise.

        ``ck``/``cv`` are the FULL layer-group page pools
        [Lg, P, ps, Hkv, D] riding the layer scan as a carry; ``li`` is
        this layer's index into them.  Writes are in-place scatters on
        the carry and attention reads gather straight from the big
        buffer — neither materializes a per-layer slice (which cost
        ~14 ms/step when the cache rode the scan as stacked ys).
        ``ks``/``vs`` are the group's [Lg, P, Hkv] page-scale pools when
        the KV pools are int8-quantized, riding the same carry; None in
        bf16 mode."""
        a = self.arch
        B, T, E = x.shape
        # (``norm_after``: the operator reads the residual stream as it
        # is and its OUTPUT is normed, by the same two gains)
        h = x if a.norm_after else self._norm(x, p, "attn_norm")
        if self.is_mla:
            attn_out, ck, cv, ks, vs = self._mla_attention(
                h, p, ck, cv, li, ks, vs, mode, positions=positions,
                page_tables=page_tables, lengths=lengths,
                true_lens=true_lens, active=active, start_pos=start_pos)
            if a.parallel_residual:
                return (x + attn_out + self._mlp(h, p, moe), ck, cv, ks, vs,
                        ssm, stats)
            x = x + attn_out
            h2 = self._norm(x, p, "mlp_norm")
            # an expert layer routes the tokens that are there
            if mode == "decode":
                valid = None if active is None else active[:, None]
            else:
                valid = jnp.arange(T)[None, :] < true_lens[:, None]
            want = moe and stats is not None
            mlp_out = self._mlp(h2, p, moe, valid=valid, with_stats=want,
                                expert_layer=expert_layer)
            if want:
                mlp_out, layer_stats = mlp_out
                stats = stats + layer_stats
            return x + mlp_out, ck, cv, ks, vs, ssm, stats
        # collective-compute overlap (docs/multichip.md): DECODE-only,
        # resolved once here — q (column-parallel, below), o and down
        # (row-parallel, further down) all key off the same handle
        ov = self.overlap if mode == "decode" else None
        q, k_new, v_new = self._attn_qkv(h, p, positions, window,
                                         lora=lora, lora_ids=lora_ids,
                                         overlap=ov, kind=kind)
        # (a model whose layers name their kind keeps token-flat pools;
        # heads narrower than a lane tile lie ``pack`` to a row of them,
        # written as rows and read through queries laid to match:
        # attention.lane_pack_queries)
        pack = 1 if kind is None else kind.pack
        flat_heads = None if kind is None else kind.num_kv_heads // pack
        ps = ck.shape[-3] if kind is None else ck.shape[-2] // flat_heads
        k_w, v_w, q_c = k_new, v_new, q
        if pack > 1:
            k_w = k_new.reshape(B, T, flat_heads, pack * kind.head_dim)
            v_w = v_new.reshape(B, T, flat_heads, pack * kind.v_head_dim)
            q_c = attn.lane_pack_queries(q, kind.num_kv_heads, pack)
        # a sink bias a head (window layers of mimo_v2): one more
        # column of the softmax, probability and no value
        sink = p["sink"].astype(jnp.float32) if "sink" in p else None

        if mode == "prefill_cp":
            # context-parallel single-shot prefill: q/k/v are sharded
            # over the sequence mesh axis; the ring rotates KV shards
            # while the page-pool scatter below (pool replicated over
            # the sequence axis) lets GSPMD all-gather the new KV once.
            # Padding needs no mask of its own: pads sit AFTER true_len,
            # so causal masking already hides them from valid queries,
            # and write_prefill_tokens routes their writes to the null
            # page.  Serving prompts start at position 0 (the engine
            # gates prefix-cache hits off this path).
            from kaito_tpu.parallel.ring_attention import ring_attention

            mesh, axis_name, head_axis, q_tile = self.cp
            start = jnp.zeros((B,), jnp.int32)
            if ks is not None:
                ck, ks = write_prefill_tokens_q(ck, ks, k_new, page_tables,
                                                start, true_lens, ps, layer=li)
                cv, vs = write_prefill_tokens_q(cv, vs, v_new, page_tables,
                                                start, true_lens, ps, layer=li)
            else:
                ck = write_prefill_tokens(ck, k_new, page_tables, start,
                                          true_lens, ps, layer=li)
                cv = write_prefill_tokens(cv, v_new, page_tables, start,
                                          true_lens, ps, layer=li)
            with jax.named_scope("attention"):
                out = ring_attention(
                    q, k_new, v_new, mesh, axis_name, scale=self._scale,
                    causal=True, sliding_window=window,
                    logit_softcap=a.attn_logit_softcap,
                    head_axis=head_axis, q_tile=q_tile)
        elif mode == "prefill":
            start = (start_pos if start_pos is not None
                     else jnp.zeros((B,), jnp.int32))
            if ks is not None:
                ck, ks = write_prefill_tokens_q(ck, ks, k_new, page_tables,
                                                start, true_lens, ps, layer=li)
                cv, vs = write_prefill_tokens_q(cv, vs, v_new, page_tables,
                                                start, true_lens, ps, layer=li)
            else:
                ck = write_prefill_tokens(ck, k_w, page_tables, start,
                                          true_lens, ps, layer=li)
                cv = write_prefill_tokens(cv, v_w, page_tables, start,
                                          true_lens, ps, layer=li)
            if start_pos is not None:
                # chunk attends over cached context + itself (prefix reuse)
                out = attn.paged_context_attention(
                    q_c, ck, cv, page_tables, start, true_lens,
                    scale=self._scale, sliding_window=window,
                    logit_softcap=a.attn_logit_softcap, layer=li,
                    k_scale=ks, v_scale=vs, sink=sink, kv_heads=flat_heads)
                if pack > 1:
                    out = attn.lane_unpack_outputs(out, kind.num_kv_heads,
                                                   pack)
            elif self.attn_impl == "pallas":
                from kaito_tpu.engine.ops.flash_prefill import (
                    flash_prefill_attention)

                win = window if window is not None else jnp.int32(_BIG_WINDOW)
                args = (q, k_new, v_new, true_lens,
                        jnp.asarray(win, jnp.int32))
                head_dims = (2, 2, 2, None, None)

                def flash(q, k, v, tl, win, *sink):
                    return flash_prefill_attention(
                        q, k, v, tl, win, scale=self._scale,
                        softcap=a.attn_logit_softcap,
                        sink=sink[0] if sink else None)

                if sink is not None:
                    args += (sink,)
                    head_dims += (0,)
                out = self._pallas_attention(flash, args, head_dims, 2)
            else:
                out = attn.prefill_attention(
                    q, k_new, v_new, scale=self._scale,
                    sliding_window=window, logit_softcap=a.attn_logit_softcap,
                    true_len=true_lens, sink=sink)
        else:
            if ks is not None:
                ck, ks = write_decode_tokens_q(ck, ks, k_new[:, 0], page_tables,
                                               positions[:, 0], ps, active,
                                               layer=li)
                cv, vs = write_decode_tokens_q(cv, vs, v_new[:, 0], page_tables,
                                               positions[:, 0], ps, active,
                                               layer=li)
            else:
                ck = write_decode_tokens(ck, k_w[:, 0], page_tables,
                                         positions[:, 0], ps, active, layer=li)
                cv = write_decode_tokens(cv, v_w[:, 0], page_tables,
                                         positions[:, 0], ps, active, layer=li)
            if self.attn_impl == "pallas":
                from kaito_tpu.engine.ops.decode_attention import (
                    paged_decode_attention_pallas)

                win = window if window is not None else jnp.int32(_BIG_WINDOW)

                def decode_kernel(q1, ck, cv, pt, ln, win, li, *rest):
                    sk = rest[0] if sink is not None else None
                    k_s, v_s = rest[sink is not None:] or (None, None)
                    return paged_decode_attention_pallas(
                        q1, ck, cv, pt, ln, win, scale=self._scale,
                        softcap=a.attn_logit_softcap, layer=li,
                        k_scale=k_s, v_scale=v_s, sink=sk,
                        kv_heads=flat_heads)

                # q [B, H, D]; pools [Lg, P, ps, Hkv, D]; sink [H];
                # scales [Lg, P, Hkv]
                args = (q_c[:, 0], ck, cv, page_tables, lengths,
                        jnp.asarray(win, jnp.int32), li)
                head_dims = (1, 3, 3, None, None, None, None)
                if sink is not None:
                    args += (sink,)
                    head_dims += (0,)
                if ks is not None:
                    args += (ks, vs)
                    head_dims += (2, 2)
                out = self._pallas_attention(decode_kernel, args,
                                             head_dims, 1)
            else:
                out = attn.paged_decode_attention(
                    q_c[:, 0], ck, cv, page_tables, lengths, scale=self._scale,
                    sliding_window=window, logit_softcap=a.attn_logit_softcap,
                    layer=li, k_scale=ks, v_scale=vs, sink=sink,
                    kv_heads=flat_heads)
            if pack > 1:
                out = attn.lane_unpack_outputs(out, kind.num_kv_heads, pack)
            out = out[:, None]
        if kind is not None:
            o_in = out.reshape(B, T, kind.num_heads * kind.v_head_dim)
        else:
            o_in = out.reshape(B, T, a.num_heads * a.head_dim)
        # collective-compute overlap (docs/multichip.md): the DECODE
        # step's row-parallel attention-out projection routes through
        # the pipelined ring; every prefill mode and the gate-off path
        # keep the plain linear (implicit GSPMD all-reduce) unchanged
        if ov is not None:
            from kaito_tpu.engine.ops.overlap_collectives import (
                overlap_linear)

            o_proj = overlap_linear(o_in, p["o"], ov[0], axis_name=ov[1],
                                    prefetch=(pf or {}).get("o"))
        else:
            o_proj = nn.linear(o_in, p["o"])
        attn_out = o_proj + nn.lora_delta(o_in, p, "o", self.lora_scaling) \
            + nn.multi_lora_delta(o_in, lora, "o", lora_ids)
        if "o_bias" in p:
            attn_out = attn_out + p["o_bias"]
        if a.attention_out_multiplier is not None:
            attn_out = attn_out * jnp.asarray(a.attention_out_multiplier,
                                              attn_out.dtype)
        if self.has_ssm:
            # the mixer reads the same normed input as attention; the
            # two outputs share one residual add
            if mode not in ("prefill", "decode"):
                raise NotImplementedError(
                    f"the state-space mixer has no {mode!r} path")
            ssm_out, ssm = self._ssm_mixer(
                h, p, ssm, li, mode, true_lens=true_lens, active=active,
                start_pos=start_pos, rows=ssm_rows)
            attn_out = attn_out + ssm_out

        if a.parallel_residual:
            mlp_out = self._mlp(h, p, moe, lora=lora, lora_ids=lora_ids,
                                overlap=ov, pf_down=(pf or {}).get("down"))
            return x + attn_out + mlp_out, ck, cv, ks, vs, ssm

        if a.pre_post_norm:
            attn_out = self._norm(attn_out, p, "post_attn_norm")
        if kind is not None:
            x, stats = self._ffn_by_kind(x, attn_out, p, moe, mode, true_lens,
                                         active, stats, expert_layer)
            return x, ck, cv, ks, vs, ssm, stats
        x = x + attn_out
        h2 = self._norm(x, p, "mlp_norm")
        mlp_out = self._mlp(h2, p, moe, lora=lora, lora_ids=lora_ids,
                            overlap=ov, pf_down=(pf or {}).get("down"))
        if a.pre_post_norm:
            mlp_out = self._norm(mlp_out, p, "post_mlp_norm")
        return x + mlp_out, ck, cv, ks, vs, ssm

    def _ffn_by_kind(self, x, mixed, p, moe, mode, true_lens, active, stats,
                     expert_layer):
        """The rest of a block whose layer names its kinds, from its
        mixer's output ``mixed``: the residual add and the FFN, each
        with the block's norm where the architecture has it (in front
        of the FFN, or, ``norm_after``, on the mixer's and the FFN's
        outputs).  An expert layer routes the tokens that are there: the
        rows that decode, a prompt's own positions.  Returns (x,
        stats)."""
        after = self.arch.norm_after
        if after:
            mixed = self._norm(mixed, p, "attn_norm")
        x = x + mixed
        h2 = x if after else self._norm(x, p, "mlp_norm")
        if mode == "decode":
            valid = None if active is None else active[:, None]
        else:
            valid = jnp.arange(x.shape[1])[None, :] < true_lens[:, None]
        want = moe and stats is not None
        mlp_out = self._mlp(h2, p, moe, valid=valid, with_stats=want,
                            expert_layer=expert_layer)
        if want:
            mlp_out, layer_stats = mlp_out
            stats = stats + layer_stats
        if after:
            mlp_out = self._norm(mlp_out, p, "mlp_norm")
        return x + mlp_out, stats

    def _conv_layer(self, x, p, pool, li, moe, mode, *, true_lens, active,
                    start_pos, rows, stats=None, expert_layer=None):
        """One block whose mixer is a gated short convolution (lfm2):
        ``[B | C | u] = h W_in``, ``v = B * u``, a causal depthwise
        convolution of ``conv_kernel`` taps over ``v``
        (``nn.short_conv``), ``(C * c) W_out``; then the FFN.  Returns
        (x, pool, stats).

        ``pool`` is the state pool [conv layers, slots, taps - 1,
        hidden] in the model's type, or None for a forward pass with no
        cache (every sequence from its start); ``li`` this layer's row
        of it.  What a sequence carries is the last ``taps - 1`` values
        of ``v``.  Prefill reads and writes the rows ``rows`` ([B] slot
        indices): a chunk at position 0 starts from zeros, which is
        what resets a reused slot's row, a later chunk from what the
        chunk before left.  Decode shifts every row that ``active``
        names, in place, and leaves the others bit for bit."""
        a = self.arch
        B, T, E = x.shape
        h = self._norm(x, p, "attn_norm")
        gate_b, gate_c, u = jnp.split(nn.linear(h, p["conv_in"]), 3, axis=-1)
        v = gate_b * u
        if mode == "decode":
            carried = pool[li]
        elif pool is None or start_pos is None:
            carried = jnp.zeros((B, a.conv_kernel - 1, E), v.dtype)
        else:
            carried = jnp.where((start_pos > 0)[:, None, None],
                                pool[li, rows], 0)
        c, seen = nn.short_conv(v, carried, p["conv_w"])
        if mode == "decode":
            new = seen[:, 1:].astype(pool.dtype)
            if active is not None:
                new = jnp.where(active[:, None, None], new, carried)
            pool = pool.at[li].set(new)
        elif pool is not None:
            pool = pool.at[li, rows].set(nn.short_conv_carry(
                seen, true_lens, a.conv_kernel).astype(pool.dtype))
        y = (gate_c.astype(jnp.float32) * c).astype(self.dtype)
        x, stats = self._ffn_by_kind(x, nn.linear(y, p["conv_out"]), p, moe,
                                     mode, true_lens, active, stats,
                                     expert_layer)
        return x, pool, stats

    def _gdn_layer(self, x, p, pools, li, moe, mode, *, true_lens, active,
                   start_pos, rows, stats=None, expert_layer=None):
        """One block whose mixer is a gated delta rule (olmo_hybrid's
        ``linear_attention``; ops/gdn.py has the recurrence): ``[q | k |
        v | z] = h W_in``, ``[a | b] = h W_gates``; q, k and v through a
        causal depthwise
        convolution of ``gdn_conv`` taps and SiLU; a head's q and k
        L2-normalised, q scaled by ``dk ** -0.5``; ``beta = scale *
        sigmoid(b)``, ``g = -exp(A_log) * softplus(a + dt_bias)``; the
        recurrence; ``RMSNorm(o) * silu(z)`` a head, out.  Then the
        FFN.  Returns (x, pools, stats).

        ``pools`` is (matrix state [delta layers, slots, dk, H * dv],
        convolution tail [delta layers, slots, taps - 1, channels]), or
        None for a forward pass with no cache (every sequence from a
        zero state); ``li`` this layer's row of them.  The three served
        forms are this one function of (the chunk, the carried state
        and tail): prefill reads and writes the rows ``rows`` ([B] slot
        indices), a chunk at position 0 from zeros, which is what
        resets a reused slot's row, a later chunk from what the chunk
        before left; decode, a chunk of one, updates every row that
        ``active`` names, in place, and leaves the others bit for bit
        (``rows`` is then ``ssm.live_rows(active)``).  Everything is
        computed in float32 and rounded once, where a state is
        written."""
        from kaito_tpu.engine.ops import gdn as G
        from kaito_tpu.engine.ops import ssm as S

        a = self.arch
        B, T, _ = x.shape
        H, dk, dv, C = (a.gdn_heads, a.gdn_key_dim, a.gdn_value_dim,
                        a.gdn_conv_dim)
        inner = H * dv
        f32 = jnp.float32
        h = x if a.norm_after else self._norm(x, p, "attn_norm")
        proj = nn.linear(h, p["gdn_in"])
        qkv = proj[..., :C].astype(f32)
        z = proj[..., C:].astype(f32)
        gates = nn.linear(h, p["gdn_gates"]).astype(f32)         # [a | b]
        g = -jnp.exp(p["gdn_a_log"].astype(f32)) * jax.nn.softplus(
            gates[..., :H] + p["gdn_dt_bias"].astype(f32))
        beta = a.gdn_beta_scale * jax.nn.sigmoid(gates[..., H:])
        w = p["gdn_conv_w"].astype(f32)

        def heads(c):
            q, k = (c[..., i * H * dk:(i + 1) * H * dk].reshape(
                c.shape[:-1] + (H, dk)) for i in (0, 1))
            v = c[..., 2 * H * dk:].reshape(c.shape[:-1] + (H, dv))
            q, k = (t * jax.lax.rsqrt(jnp.sum(jnp.square(t), axis=-1,
                                              keepdims=True) + 1e-6)
                    for t in (q, k))
            return q * dk ** -0.5, k, v

        if mode == "decode":
            st, cv = pools
            tail = cv[li]
            with jax.named_scope("gdn_conv"):
                conv, new_tail = S.conv_step(qkv[:, 0], tail.astype(f32), w)
                new_tail = new_tail.astype(cv.dtype)
                if active is not None:
                    new_tail = jnp.where(active[:, None, None], new_tail,
                                         tail)
                cv = cv.at[li].set(new_tail)
                q, k, v = heads(jax.nn.silu(conv))
            if self.attn_impl == "pallas":
                st, o = G.gdn_state_update(st, li, rows[0], rows[1], q, k, v,
                                           g[:, 0], beta[:, 0])
                if active is not None:
                    o = jnp.where(active[:, None, None], o, 0.0)
            else:
                st, o = G.gdn_state_update_jax(st, li, q, k, v, g[:, 0],
                                               beta[:, 0], active)
            o = o[:, None]                                       # [S, 1, H, dv]
            pools = (st, cv)
        else:
            if pools is None or start_pos is None:
                tail0 = jnp.zeros((B, a.gdn_conv - 1, C), f32)
                s0 = jnp.zeros((B, H, dk, dv), f32)
            else:
                keep = start_pos > 0
                tail0 = jnp.where(keep[:, None, None],
                                  pools[1][li, rows].astype(f32), 0.0)
                s0 = jnp.where(
                    keep[:, None, None, None],
                    G.from_pool_layout(pools[0][li, rows].astype(f32), H),
                    0.0)
            with jax.named_scope("gdn_conv"):
                q, k, v = heads(jax.nn.silu(S.causal_conv(qkv, tail0, w)))
            valid = (jnp.arange(T)[None, :] < true_lens[:, None])[..., None]
            o, s_last = G.gdn_chunked_scan(
                q, k, v, jnp.where(valid, g, 0.0),
                jnp.where(valid, beta, 0.0), s0)
            if pools is not None:
                st, cv = pools
                st = st.at[li, rows].set(
                    G.pool_layout(s_last).astype(st.dtype))
                cv = cv.at[li, rows].set(
                    S.conv_tail(qkv, tail0, true_lens).astype(cv.dtype))
                pools = (st, cv)
        # the gated norm: RMSNorm over a head's values, one gain a lane
        # shared by the heads, then the gate
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                              + a.rms_norm_eps) * p["gdn_norm"].astype(f32)
        y = o.reshape(B, T, inner) * jax.nn.silu(z)
        x, stats = self._ffn_by_kind(
            x, nn.linear(y.astype(self.dtype), p["gdn_out"]), p, moe, mode,
            true_lens, active, stats, expert_layer)
        return x, pools, stats

    def _ssm_mixer(self, h, p, pools, li, mode, *, true_lens, active,
                   start_pos, rows):
        """The state-space mixer (Mamba-2's, as falcon-h1 publishes it)
        on the block's normed input ``h`` [B, T, E].  Returns (its
        output [B, T, E], pools).

        ``pools`` is (state [Lg, S, H, P, N], convolution tail
        [Lg, S, K-1, C]), both in the model's type (the recurrence is
        computed in float32 and rounded once, where a state is written
        back), or None for a forward pass with no cache
        (``train``: every sequence from a zero state).  Prefill reads
        and writes the rows ``rows`` ([B] slot indices): a chunk that
        starts at position 0 starts from zeros, which is what resets a
        reused slot's row; a later chunk starts from what the chunk
        before left.  Decode updates every row that ``active`` names,
        in place, and leaves the others bit for bit: ``rows`` is then
        ``ssm.live_rows(active)``."""
        from kaito_tpu.engine.ops import ssm as S

        a = self.arch
        Bn, T, _ = h.shape
        Hm, Pm, G, N = a.ssm_heads, a.ssm_head_dim, a.ssm_groups, a.ssm_state
        inner, Cd = a.ssm_inner, a.ssm_conv_dim
        f32 = jnp.float32
        if a.ssm_in_multiplier is not None:
            h = h * jnp.asarray(a.ssm_in_multiplier, h.dtype)
        proj = nn.linear(h, p["ssm_in"])
        if a.ssm_multipliers is not None:
            proj = proj * jnp.asarray(self._ssm_mup, proj.dtype)
        z = proj[..., :inner].astype(f32)
        xbc = proj[..., inner:inner + Cd].astype(f32)
        dt = jax.nn.softplus(proj[..., inner + Cd:].astype(f32)
                             + p["ssm_dt_bias"].astype(f32))
        A = -jnp.exp(p["ssm_a_log"].astype(f32))
        D = p["ssm_d"].astype(f32)
        w, wb = p["ssm_conv"].astype(f32), p["ssm_conv_bias"].astype(f32)

        def split(c):
            return (c[..., :inner].reshape(c.shape[:-1] + (Hm, Pm)),
                    c[..., inner:inner + G * N].reshape(c.shape[:-1] + (G, N)),
                    c[..., inner + G * N:].reshape(c.shape[:-1] + (G, N)))

        if mode == "decode":
            st, cv = pools
            tail = cv[li]
            conv, new_tail = S.conv_step(xbc[:, 0], tail.astype(f32), w, wb)
            new_tail = new_tail.astype(cv.dtype)
            if active is not None:
                new_tail = jnp.where(active[:, None, None], new_tail, tail)
            cv = cv.at[li].set(new_tail)
            xs, Bm, Cm = split(jax.nn.silu(conv))
            if self.attn_impl == "pallas":
                st, y = S.ssm_state_update(st, li, rows[0], rows[1], xs,
                                           dt[:, 0], A, Bm, Cm)
                if active is not None:
                    y = jnp.where(active[:, None, None], y, 0.0)
            else:
                st, y = S.ssm_state_update_jax(st, li, xs, dt[:, 0], A, Bm,
                                               Cm, active)
            y = (y + D[None, :, None] * xs)[:, None]         # [S, 1, H, P]
        else:
            if pools is None or start_pos is None:
                tail0 = jnp.zeros((Bn, a.ssm_conv - 1, Cd), f32)
                h0 = jnp.zeros((Bn, Hm, Pm, N), f32)
            else:
                keep = start_pos > 0
                tail0 = jnp.where(keep[:, None, None],
                                  pools[1][li, rows].astype(f32), 0.0)
                h0 = jnp.where(keep[:, None, None, None],
                               pools[0][li, rows].astype(f32), 0.0)
            xs, Bm, Cm = split(jax.nn.silu(S.causal_conv(xbc, tail0, w, wb)))
            valid = jnp.arange(T)[None, :] < true_lens[:, None]
            y, h_last = S.ssm_chunked_scan(
                xs, jnp.where(valid[..., None], dt, 0.0), A, Bm, Cm, h0,
                a.ssm_chunk)
            y = y + D[None, None, :, None] * xs
            if pools is not None:
                st, cv = pools
                st = st.at[li, rows].set(h_last.astype(st.dtype))
                cv = cv.at[li, rows].set(
                    S.conv_tail(xbc, tail0, true_lens).astype(cv.dtype))
        # gated norm, the gate first (mamba_norm_before_gate false):
        # RMSNorm over each group's channels, then one weight a channel
        y = y.reshape(Bn, T, inner) * jax.nn.silu(z)
        yg = y.reshape(Bn, T, G, inner // G)
        yg = yg * jax.lax.rsqrt(jnp.mean(jnp.square(yg), axis=-1,
                                         keepdims=True) + a.rms_norm_eps)
        y = yg.reshape(Bn, T, inner) * p["ssm_norm"].astype(f32)
        out = nn.linear(y.astype(self.dtype), p["ssm_out"])
        if a.ssm_out_multiplier is not None:
            out = out * jnp.asarray(a.ssm_out_multiplier, out.dtype)
        return out, (None if pools is None else (st, cv))

    # ------------------------------------------------------------------
    # Forward passes
    # ------------------------------------------------------------------

    def _run_layers(self, params, cache: Optional[KVCache], x, mode, *,
                    positions, page_tables, lengths, true_lens, active,
                    remat: bool = False, start_pos=None, adapter_ids=None,
                    ssm_rows=None):
        if self.kinds is not None:
            return self._run_layers_kinds(
                params, cache, x, mode, positions=positions,
                page_tables=page_tables, lengths=lengths,
                true_lens=true_lens, active=active, remat=remat,
                start_pos=start_pos, state_rows=ssm_rows)
        serve_lora = params.get("serve_lora") if mode != "train" else None
        if mode != "train":
            if self.has_ssm and cache.ssm_state is None:
                raise ValueError("a model with a state-space mixer serves "
                                 "from a cache with a state pool "
                                 "(kv_cache.create_state_pool)")
            pools = (cache.k, cache.v, cache.k_scale, cache.v_scale,
                     (cache.ssm_state, cache.ssm_conv)
                     if cache.ssm_state is not None else None)
            stats = cache.moe_stats if mode == "decode" else None
        for g in self.groups:
            stack = params[g.name]
            flags = self._window_flags(g.start, g.count)
            if mode == "train":
                def body(carry, xs, moe=g.moe):
                    h = carry
                    (p, window) = xs if flags is not None else (xs[0], None)
                    h = self._layer_train(h, p, window, moe, positions=positions,
                                          true_lens=true_lens)
                    return h, None

                if remat:
                    body = jax.checkpoint(body, prevent_cse=False)
                xs = (stack,) if flags is None else (stack, flags)
                x, _ = jax.lax.scan(body, x, xs)
                continue

            # The page pools ride the scan as a CARRY: writes are
            # in-place scatters at a traced layer index and attention
            # gathers straight from the big buffer.  (Threading them as
            # xs/ys sliced + re-stacked the full pool every step — 14 ms
            # of a 31 ms decode step on a v5e chip.)  The WHOLE pools
            # ride every group's scan and a group's layers index them
            # from ``g.start``: a slice of the pool for the second of
            # two groups (dense layers, then expert layers) would be a
            # copy of it a program (4.2 GB of a 40-layer latent pool).
            # The scale pools (int8 KV mode) and the mixer's per-slot
            # pools ride the same carry; None is a valid empty pytree
            # leaf so the bf16 scan is unchanged.
            # per-request adapters ride the scan as an extra [L, n, ...]
            # stack (None for groups without one, e.g. MoE)
            lora_g = serve_lora.get(g.name) if serve_lora else None
            has_lora = bool(lora_g)
            # comm-overlap decode: the next layer's quantized o/down
            # slabs ride the scan as one more xs stream (rolled stack,
            # docs/multichip.md) feeding the kernel's prefetch DMA.
            # Gate off (or non-decode, or bf16): no extra stream — the
            # scan signature and trace are byte-identical to before.
            pf_g = (_prefetch_stack(stack)
                    if self.overlap is not None and mode == "decode"
                    else None)
            has_pf = pf_g is not None
            # a latent-attention model's grouped expert layer keeps its
            # stacks whole and takes its layer by index (a slice handed
            # to the grouped-matmul kernel would be a copy of the
            # layer's matrices), and counts for the decode programs
            whole = {k: v for k, v in stack.items()
                     if self.is_mla and g.moe and self.moe_impl == "ragged"
                     and k.startswith("experts_")}
            if whole:
                stack = {k: v for k, v in stack.items() if k not in whole}

            def body(carry, xs, moe=g.moe, has_lora=has_lora,
                     has_pf=has_pf, whole=whole, first=g.start):
                h, ck_g, cv_g, ks_g, vs_g, ssm_g, st = carry
                items = list(xs)
                at, p = items[0], items[1]
                # the layer's index into the pools
                li = at + first if first else at
                k = 2
                lora_l = items[k] if has_lora else None
                k += int(has_lora)
                pf_l = items[k] if has_pf else None
                window = items[-1] if flags is not None else None
                out = self._layer(
                    h, {**p, **whole}, ck_g, cv_g, li, window, moe, mode,
                    positions=positions, page_tables=page_tables,
                    lengths=lengths, true_lens=true_lens, active=active,
                    start_pos=start_pos, lora=lora_l, lora_ids=adapter_ids,
                    ks=ks_g, vs=vs_g, pf=pf_l, ssm=ssm_g,
                    ssm_rows=ssm_rows, stats=st,
                    expert_layer=at if whole else None)
                # (a latent layer's seventh element: its counters)
                return out[:6] + (out[6] if self.is_mla else st,), None

            # scan length follows the actual stack: pipeline stages pass
            # stage-local views whose leading axis is a fraction of the
            # arch's layer count
            Lg = jax.tree.leaves(stack)[0].shape[0]
            xs = (jnp.arange(Lg, dtype=jnp.int32), stack)
            if has_lora:
                xs = xs + (lora_g,)
            if has_pf:
                xs = xs + (pf_g,)
            if flags is not None:
                pat = self.arch.sliding_window_pattern
                if Lg != g.count and pat and Lg % pat:
                    # flags[:Lg] only equals every stage's own flags when
                    # the global/local pattern tiles the stage evenly
                    raise NotImplementedError(
                        f"pipeline stage of {Lg} layers does not tile the "
                        f"sliding-window pattern ({pat}); per-stage window "
                        f"flags are not implemented")
                xs = xs + (flags[:Lg],)
            (x, *pools, stats), _ = jax.lax.scan(body, (x, *pools, stats),
                                                 xs)
        if mode == "train":
            return x, None
        k, v, ks, vs, ssm = pools
        st, cv = ssm if ssm is not None else (None, None)
        return x, KVCache(k=k, v=v, k_scale=ks, v_scale=vs, ssm_state=st,
                          ssm_conv=cv,
                          moe_stats=stats if mode == "decode"
                          else cache.moe_stats)

    def _run_layers_kinds(self, params, cache: Optional[KVCache], x, mode,
                          *, positions, page_tables, lengths, true_lens,
                          active, remat, start_pos, state_rows=None):
        """The layers of a model whose layers name their kinds: the
        schedule's runs in order, each a scan over its stretch of its
        stack (the stack rides as a loop invariant and the body takes
        its layer by index: a slice of a stack would be a copy of it).
        ``page_tables`` is [B, 2, pages] where window layers keep a pool
        of their own: a table an attention kind, the full kind's first
        ([B, pages] otherwise); a kind's pools ride the scans of its
        runs, and the short-convolution layers' state pool
        (``cache.conv_state``, rows ``state_rows`` at prefill) or the
        delta-rule layers' (``cache.delta_state`` with the convolutions'
        tails in ``cache.conv_state``) theirs."""
        if mode not in ("train", "prefill", "decode"):
            raise NotImplementedError(
                f"layers that name their attention kind have no "
                f"{mode!r} path")
        two_tables = self.arch.two_kind_cache
        if mode != "train" and two_tables and cache.wk is None:
            raise ValueError("a model with window layers of their own "
                             "geometry serves from a cache with a window "
                             "pool (kv_cache.create_kv_cache)")
        if mode != "train" and self.has_conv and cache.conv_state is None:
            raise ValueError("a model with short-convolution layers serves "
                             "from a cache with rows of conv state "
                             "(kv_cache.create_conv_state_pool)")
        if mode != "train" and self.has_gdn and cache.delta_state is None:
            raise ValueError("a model with delta-rule layers serves from a "
                             "cache with rows of matrix state "
                             "(kv_cache.create_delta_state_pool)")
        # ("train": the cache-free forward pass that scores a prompt)
        pools = None if mode == "train" else \
            [(cache.k, cache.v), (cache.wk, cache.wv)]
        conv_pool = None if mode == "train" else cache.conv_state
        delta_pool = None if mode == "train" else cache.delta_state
        # an expert layer's counters are kept for the decode programs
        # (what the per-layer metrics read)
        stats = cache.moe_stats if mode == "decode" else None
        for run in self.runs:
            stack = params[run.stack]
            gdn = run.kind == MIXER_GDN
            # (a run that reads a row of the state pool and no page)
            conv = gdn or run.kind == MIXER_CONV
            kind = None if conv else self.kinds[run.kind]
            window = None if conv else kind.window
            # an expert layer's stacks stay whole and the layer goes by
            # index: a slice handed to the grouped-matmul kernel would
            # be a copy of the layer's matrices
            whole = {k: v for k, v in stack.items()
                     if run.moe and k.startswith("experts_")}
            rest = {k: v for k, v in stack.items() if k not in whole}

            def take(i, whole=whole, rest=rest, run=run):
                at = run.stack_start + i
                p = jax.tree.map(lambda w: jax.lax.dynamic_index_in_dim(
                    w, at, 0, keepdims=False), rest)
                return {**p, **whole}, (at if whole else None)

            if mode == "train":
                def one(h, p, at, kind=kind, window=window, moe=run.moe,
                        gdn=gdn):
                    if gdn:
                        return self._gdn_layer(
                            h, p, None, None, moe, mode, true_lens=true_lens,
                            active=None, start_pos=None, rows=None,
                            expert_layer=at)[0]
                    if kind is None:
                        return self._conv_layer(
                            h, p, None, None, moe, mode, true_lens=true_lens,
                            active=None, start_pos=None, rows=None,
                            expert_layer=at)[0]
                    return self._layer_train(
                        h, p, window, moe, positions=positions,
                        true_lens=true_lens, kind=kind, expert_layer=at)

                if remat:
                    one = jax.checkpoint(one, prevent_cse=False)
                for i in range(run.count):
                    x = one(x, *take(i))
                continue
            if conv:
                def conv_step(carry, i, run=run, take=take, gdn=gdn):
                    h, pool, st = carry
                    p, at = take(i)
                    layer = self._gdn_layer if gdn else self._conv_layer
                    return layer(
                        h, p, pool, run.cache_start + i, run.moe, mode,
                        true_lens=true_lens, active=active,
                        start_pos=start_pos, rows=state_rows, stats=st,
                        expert_layer=at), None

                # (a delta-rule layer's pool: its matrix state and the
                # convolutions' tails)
                pool = (delta_pool, conv_pool) if gdn else conv_pool
                if run.count == 1:
                    (x, pool, stats), _ = conv_step(
                        (x, pool, stats), jnp.int32(0))
                else:
                    (x, pool, stats), _ = jax.lax.scan(
                        conv_step, (x, pool, stats),
                        jnp.arange(run.count, dtype=jnp.int32))
                if gdn:
                    delta_pool, conv_pool = pool
                else:
                    conv_pool = pool
                continue
            table = page_tables[:, run.kind] if two_tables else page_tables
            ck, cv = pools[run.kind]

            def step(carry, i, run=run, kind=kind, window=window,
                     table=table, take=take):
                h, ck, cv, st = carry
                p, at = take(i)
                h, ck, cv, _, _, _, st = self._layer(
                    h, p, ck, cv, run.cache_start + i, window, run.moe, mode,
                    positions=positions, page_tables=table, lengths=lengths,
                    true_lens=true_lens, active=active, start_pos=start_pos,
                    kind=kind, stats=st, expert_layer=at)
                return (h, ck, cv, st), None

            if run.count == 1:
                (x, ck, cv, stats), _ = step((x, ck, cv, stats),
                                             jnp.int32(0))
            else:
                (x, ck, cv, stats), _ = jax.lax.scan(
                    step, (x, ck, cv, stats),
                    jnp.arange(run.count, dtype=jnp.int32))
            pools[run.kind] = (ck, cv)
        if mode == "train":
            return x, None
        return x, dataclasses.replace(
            cache, k=pools[0][0], v=pools[0][1], wk=pools[1][0],
            wv=pools[1][1], conv_state=conv_pool, delta_state=delta_pool,
            moe_stats=stats if mode == "decode" else cache.moe_stats)

    def _layer_train(self, x, p, window, moe, *, positions, true_lens,
                     kind: Optional[AttnKind] = None, expert_layer=None):
        """Transformer block without KV-cache plumbing (training)."""
        a = self.arch
        B, T, E = x.shape
        h = x if a.norm_after else self._norm(x, p, "attn_norm")
        if self.is_mla:
            attn_out, _, _, _, _ = self._mla_attention(
                h, p, None, None, None, None, None, "train",
                positions=positions, page_tables=None, lengths=None,
                true_lens=true_lens, active=None)
            if a.parallel_residual:
                return x + attn_out + self._mlp(h, p, moe)
            x = x + attn_out
            h2 = self._norm(x, p, "mlp_norm")
            return x + self._mlp(h2, p, moe)
        q, k_new, v_new = self._attn_qkv(h, p, positions, window, kind=kind)
        if kind is not None:
            out = attn.prefill_attention(
                q, k_new, v_new, scale=self._scale, sliding_window=window,
                true_len=true_lens,
                sink=p["sink"].astype(jnp.float32) if "sink" in p else None)
            o_in = out.reshape(B, T, kind.num_heads * kind.v_head_dim)
            return self._ffn_by_kind(x, nn.linear(o_in, p["o"]), p, moe,
                                     "train", true_lens, None, None,
                                     expert_layer)[0]
        if self.ring is not None and window is None:
            # sequence-parallel exact attention over the mesh ring;
            # training batches are packed dense (loss masks handle pads)
            from kaito_tpu.parallel.ring_attention import ring_attention

            mesh, axis = self.ring
            out = ring_attention(q, k_new, v_new, mesh, axis,
                                 scale=self._scale, causal=True)
        else:
            out = attn.prefill_attention(
                q, k_new, v_new, scale=self._scale, sliding_window=window,
                logit_softcap=a.attn_logit_softcap, true_len=true_lens)
        o_in = out.reshape(B, T, a.num_heads * a.head_dim)
        attn_out = nn.linear(o_in, p["o"]) + nn.lora_delta(o_in, p, "o", self.lora_scaling)
        if "o_bias" in p:
            attn_out = attn_out + p["o_bias"]
        if a.attention_out_multiplier is not None:
            attn_out = attn_out * jnp.asarray(a.attention_out_multiplier,
                                              attn_out.dtype)
        if self.has_ssm:
            ssm_out, _ = self._ssm_mixer(
                h, p, None, None, "train", true_lens=true_lens, active=None,
                start_pos=None, rows=None)
            attn_out = attn_out + ssm_out
        if a.parallel_residual:
            return x + attn_out + self._mlp(h, p, moe)
        if a.pre_post_norm:
            attn_out = self._norm(attn_out, p, "post_attn_norm")
        x = x + attn_out
        h2 = self._norm(x, p, "mlp_norm")
        mlp_out = self._mlp(h2, p, moe)
        if a.pre_post_norm:
            mlp_out = self._norm(mlp_out, p, "post_mlp_norm")
        return x + mlp_out

    def _embed(self, params, tokens):
        x = params["embed"][tokens].astype(self.dtype)
        if self.arch.embedding_multiplier:
            x = x * jnp.asarray(self.arch.embedding_multiplier, self.dtype)
        return x

    def _logits(self, params, x):
        head = params["embed"] if self.arch.tie_word_embeddings else params["lm_head"]
        # bf16 inputs with fp32 accumulation: upcasting bf16 weights to
        # fp32 inputs adds no information but runs the MXU at fp32 rate
        logits = jax.lax.dot_general(
            x, head, (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if self.arch.lm_head_multiplier is not None:
            logits = logits * self.arch.lm_head_multiplier
        logits = nn.softcap(logits, self.arch.final_logit_softcap)
        return logits[..., : self.arch.vocab_size]

    def prefill(self, params, cache: KVCache, tokens, true_lens, page_tables,
                start_pos=None, adapter_ids=None, state_rows=None):
        """Process prompts (or prompt suffixes when ``start_pos`` marks a
        cached/chunked prefix already present in the pages).

        tokens: [B, T] padded chunks; true_lens: [B] valid NEW tokens;
        page_tables: [B, pages_per_seq] pre-allocated; state_rows: [B]
        each row's slot in the mixer's state pool (a model with a
        state-space mixer only).  Returns (cache, last_logits [B, vocab],
        last_hidden [B, E]).
        """
        B, T = tokens.shape
        rel = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        positions = rel if start_pos is None else rel + start_pos[:, None]
        if self.has_state and state_rows is None:
            raise ValueError("a model with a state pool prefills into its "
                             "slots' rows of it: state_rows is required")
        x = self._embed(params, tokens)
        x, cache = self._run_layers(
            params, cache, x, "prefill", positions=positions,
            page_tables=page_tables, lengths=true_lens, true_lens=true_lens,
            active=None, start_pos=start_pos, adapter_ids=adapter_ids,
            ssm_rows=state_rows)
        x = self._norm(x, params, "final_norm")
        last = jnp.take_along_axis(
            x, (true_lens - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        return cache, self._logits(params, last), last

    def prefill_cp(self, params, cache: KVCache, tokens, true_lens,
                   page_tables, adapter_ids=None):
        """Context-parallel single-shot prefill: the WHOLE prompt in one
        call, activations sharded over the ``sequence`` mesh axis and
        attention run as a ring (``parallel/ring_attention.py``).

        The serving-side long-context answer the reference delegates to
        vLLM's KV budget (``pkg/model/interface.go:308-312``): TTFT for
        a T-token prompt scales ~1/seq because every chip holds T/seq
        tokens of activations and attention workspace.  Decode stays TP
        — the KV pool is replicated over the sequence axis, so the
        pages this call writes are immediately readable by the ordinary
        decode step.  Same signature/returns as :meth:`prefill` minus
        ``start_pos`` (prefix-cache hits take the chunked path).
        """
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        mesh, axis_name, _, _ = self.cp
        B, T = tokens.shape
        rel = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        x = self._embed(params, tokens)
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(None, axis_name)))
        x, cache = self._run_layers(
            params, cache, x, "prefill_cp", positions=rel,
            page_tables=page_tables, lengths=true_lens, true_lens=true_lens,
            active=None, adapter_ids=adapter_ids)
        x = self._norm(x, params, "final_norm")
        last = jnp.take_along_axis(
            x, (true_lens - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        return cache, self._logits(params, last), last

    def verify_window_logits(self, params, cache: KVCache, tokens,
                             true_lens, page_tables, start_pos,
                             adapter_ids=None):
        """Speculative-decoding verification forward: run a small window
        of proposed tokens (chunked-prefill machinery — paged history +
        causal window attention, KV written in place) and return the
        full-precision logits at EVERY window position.

        tokens: [B, W] (= [last_emitted, proposal...], -pad);
        true_lens: [B] valid window tokens (0 skips a slot — its writes
        mask to the null page); start_pos: [B] absolute position of the
        window start.  Returns (cache, logits [B, W, V] f32).  Callers
        jit this together with their acceptance rule (greedy argmax or
        ``sampler.spec_verify_sample``) so the [B, W, V] tensor never
        leaves the device.
        """
        B, W = tokens.shape
        rel = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32), (B, W))
        positions = rel + start_pos[:, None]
        x = self._embed(params, tokens)
        x, cache = self._run_layers(
            params, cache, x, "prefill", positions=positions,
            page_tables=page_tables, lengths=true_lens, true_lens=true_lens,
            active=None, start_pos=start_pos, adapter_ids=adapter_ids)
        x = self._norm(x, params, "final_norm")
        logits = self._logits(params, x).astype(jnp.float32)   # [B, W, V]
        return cache, logits

    def verify_window(self, params, cache: KVCache, tokens, true_lens,
                      page_tables, start_pos, adapter_ids=None):
        """Greedy verification (the n-gram speculative path): the
        :meth:`verify_window_logits` forward reduced to the GREEDY next
        token and its model logprob at every window position.

        Returns (cache, targets [B, W] int32, lps [B, W] f32).
        """
        from kaito_tpu.engine.sampler import chosen_logprob

        B, W = tokens.shape
        cache, logits = self.verify_window_logits(
            params, cache, tokens, true_lens, page_tables, start_pos,
            adapter_ids=adapter_ids)
        targets = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        flat_lp = chosen_logprob(logits.reshape(B * W, -1),
                                 targets.reshape(B * W))
        return cache, targets, flat_lp.reshape(B, W)

    def decode(self, params, cache: KVCache, tokens, positions, page_tables,
               active=None, adapter_ids=None):
        """One decode step for a batch of slots.

        tokens: [B] last sampled token; positions: [B] their positions;
        lengths after write are positions+1, and 0 for a slot that
        ``active`` leaves out: it writes no KV and attends to nothing
        (the Pallas kernel copies no page for it), and no caller reads
        its logits (docs/kv-cache.md).  Returns (cache, logits).
        """
        B = tokens.shape[0]
        pos2 = positions[:, None].astype(jnp.int32)
        lengths = positions + 1
        if active is not None:
            lengths = jnp.where(active, lengths, 0)
        ssm_rows = None
        if (self.has_ssm or self.has_gdn) and self.attn_impl == "pallas":
            # once a step, for every layer's call of the kernel
            from kaito_tpu.engine.ops.ssm import live_rows

            ssm_rows = live_rows(active if active is not None
                                 else jnp.ones((B,), bool))
        x = self._embed(params, tokens[:, None])
        x, cache = self._run_layers(
            params, cache, x, "decode", positions=pos2,
            page_tables=page_tables, lengths=lengths, true_lens=None,
            active=active, adapter_ids=adapter_ids, ssm_rows=ssm_rows)
        x = self._norm(x, params, "final_norm")
        return cache, self._logits(params, x[:, 0])

    def forward_train(self, params, tokens, mask=None, remat: bool = True):
        """Full-sequence forward for training: [B, T] -> logits [B, T, V].

        Rematerializes each layer (jax.checkpoint) so activation memory
        stays O(sqrt) — the TPU trade the reference never makes because
        HF Trainer owns its training loop.
        """
        B, T = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        true_lens = mask.sum(-1).astype(jnp.int32) if mask is not None else \
            jnp.full((B,), T, jnp.int32)
        x = self._embed(params, tokens)
        x, _ = self._run_layers(
            params, None, x, "train", positions=positions, page_tables=None,
            lengths=None, true_lens=true_lens, active=None, remat=remat)
        x = self._norm(x, params, "final_norm")
        return self._logits(params, x)
