"""Model metadata: the structural facts the planner, estimator and the
serving engine need about a model.

This is the TPU-native analogue of the reference's model registry
(``pkg/model/interface.go:33-45`` ``Model``/``PresetParam`` and the
catalog entries in ``presets/workspace/models/model_catalog.yaml``):
a preset carries enough architecture detail to (a) estimate HBM
(weights + KV-cache bytes/token), (b) plan a device mesh, and (c)
actually instantiate the model in the JAX engine — the reference only
needed (a)+(b) because vLLM owned (c).
"""

from __future__ import annotations

import enum
import math
from dataclasses import dataclass, field, replace
from typing import Optional


def stored_key_dim(head_dim: int) -> int:
    """Lanes a key's head is stored and multiplied at.  A head wider
    than one 128-lane tile and not a whole number of them lies in whole
    tiles of HBM whatever its logical shape (the TPU's (8, 128) tiling),
    and the Pallas kernels copy whole tiles, so the pools say what they
    hold: 192 is stored as 256, the pad zero (docs/kv-cache.md)."""
    if head_dim <= 128 or head_dim % 128 == 0:
        return head_dim
    return -(-head_dim // 128) * 128


def heads_per_lane_row(head_dim: int, v_head_dim: int, kv_heads: int) -> int:
    """KV heads of one token that share a 128-lane row of a token-flat
    page pool.  A head narrower than a lane tile would lie in a tile of
    its own, half of it padding (a 64-wide head: the TPU lays a
    [rows, 64] bf16 array out in 128 lanes, and Mosaic refuses to copy
    a 64-lane slice of it), so a whole number of such heads is stored
    side by side: 8 heads of 64 are 4 rows of 128, byte for byte what
    [8, 64] is.  1: a head is a row (docs/kv-cache.md)."""
    if head_dim != v_head_dim or not 0 < head_dim < 128 or 128 % head_dim:
        return 1
    n = 128 // head_dim
    return n if kv_heads % n == 0 else 1


# ``ModelArch.layer_attention``: what mixes a layer's tokens
MIXER_FULL, MIXER_WINDOW, MIXER_CONV, MIXER_GDN = 0, 1, 2, 3


class AttentionKind(str, enum.Enum):
    """Attention family — drives the KV bytes/token formula (reference:
    ``presets/workspace/generator/generator.go:620`` calculateKVCacheTokenSize)."""

    MHA = "MHA"
    GQA = "GQA"
    MQA = "MQA"
    MLA = "MLA"  # DeepSeek-style latent attention: cache is kv_lora_rank+rope


@dataclass(frozen=True)
class ModelArch:
    """Engine-facing architecture description.

    One config-driven transformer implementation covers the llama /
    mistral / qwen2 / phi-3 / gemma / MoE families; the flags below are
    the union of what those need.
    """

    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    intermediate_size: int
    max_position_embeddings: int = 8192

    # nonlinearity / norms
    hidden_act: str = "silu"          # silu (swiglu) | gelu | gelu_tanh (geglu)
    gated_mlp: bool = True            # False: classic 2-matrix MLP (falcon, phi-2)
    rms_norm_eps: float = 1e-5
    norm_type: str = "rmsnorm"        # rmsnorm | layernorm
    norm_offset: bool = False         # gemma: weight = 1 + w
    pre_post_norm: bool = False       # gemma-2/3: extra post-attn/post-mlp norms
    # olmo-2/3, olmo_hybrid: the block's two norms stand AFTER the
    # operator and the MLP and nowhere else, x + norm(op(x)); both read
    # the residual stream as it is
    norm_after: bool = False
    parallel_residual: bool = False   # falcon/phi-2: x + attn(n(x)) + mlp(n(x))
    linear_bias: bool = False         # phi-2: biases on all projections

    # rotary embedding (``rotary`` false: none; olmo_hybrid's attention
    # layers see positions through the recurrent layers below them)
    rotary: bool = True
    rope_theta: float = 10000.0
    partial_rotary_factor: float = 1.0
    rope_scaling: Optional[dict] = None   # {"rope_type": "llama3"|"linear"|"yarn", ...}

    # attention details
    qk_norm: bool = False             # gemma-3 / qwen-3: RMSNorm on q and k heads
    # olmo-2/3, olmo_hybrid: the QK norm is ONE norm over the whole
    # projection (all heads' lanes), before the split into heads
    qk_norm_whole: bool = False
    qkv_bias: bool = False            # qwen2
    attn_logit_softcap: Optional[float] = None   # gemma-2
    final_logit_softcap: Optional[float] = None  # gemma-2
    sliding_window: Optional[int] = None
    sliding_window_pattern: Optional[int] = None  # gemma-3: 1 global per N layers
    query_pre_attn_scalar: Optional[float] = None  # gemma override for 1/sqrt(d)

    # embeddings / head
    tie_word_embeddings: bool = False
    embedding_multiplier: Optional[float] = None  # gemma scales by sqrt(hidden)

    # MoE (mixtral/deepseek/gpt-oss style); dense model if num_experts == 0
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_intermediate_size: Optional[int] = None
    num_shared_experts: int = 0
    moe_layer_start: int = 0          # deepseek: first k layers dense

    # MLA (deepseek v2/v3)
    kv_lora_rank: Optional[int] = None
    q_lora_rank: Optional[int] = None
    qk_rope_head_dim: Optional[int] = None
    qk_nope_head_dim: Optional[int] = None
    v_head_dim: Optional[int] = None
    # rotary pairs are (2i, 2i+1), as the deepseek-v3 family publishes
    # them (``rope_interleave``); False: (i, i + half), rotate-half
    rope_interleave: bool = False

    # state-space mixer beside attention in every block (falcon-h1: a
    # Mamba-2 mixer fed the same normed input as attention, the two
    # outputs scaled and summed into one residual); no mixer if
    # ssm_state == 0.  The mixer keeps a recurrent state per sequence
    # ([ssm_heads, ssm_head_dim, ssm_state]) and the last ssm_conv-1
    # inputs of its causal convolution: docs/kv-cache.md
    ssm_state: int = 0                # d_state
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1               # B and C are shared by heads/groups heads
    ssm_conv: int = 4                 # depthwise causal conv kernel
    ssm_chunk: int = 128              # prefill scan chunk
    # falcon-h1's published multipliers (muP folded into the forward
    # pass); None = not applied
    attention_in_multiplier: Optional[float] = None
    attention_out_multiplier: Optional[float] = None
    key_multiplier: Optional[float] = None
    ssm_in_multiplier: Optional[float] = None
    ssm_out_multiplier: Optional[float] = None
    ssm_multipliers: Optional[tuple] = None   # over [z | x | B | C | dt]
    mlp_multipliers: Optional[tuple] = None   # (gate pre-activation, down)
    lm_head_multiplier: Optional[float] = None

    # layers of more than one kind in one model (mimo_v2: docs/kv-cache.md,
    # "Two kinds of page"; lfm2: "A row of conv state").
    # ``layer_attention[l]`` says what mixes layer l's tokens: 0 a full
    # attention layer (num_heads / num_kv_heads / head_dim / v_head_dim
    # / rope_theta above), 1 a window layer, which has its own head
    # counts and sizes, its own rope theta, a causal window of
    # ``sliding_window`` positions and, with ``swa_sink``, a learnable
    # sink bias a head, 2 a gated short convolution (lfm2: no attention
    # and no page; ``conv_kernel`` taps a channel over ``hidden_size``
    # channels, and the last ``conv_kernel - 1`` inputs a sequence in
    # the state pool), 3 a gated delta rule (olmo_hybrid's
    # ``linear_attention``: no page; ``gdn_heads`` heads with keys of
    # ``gdn_key_dim`` and values of ``gdn_value_dim``, each a causal
    # depthwise convolution of ``gdn_conv`` taps in front, beta times
    # ``gdn_beta_scale``; a sequence keeps a ``gdn_key_dim x
    # gdn_value_dim`` matrix a head and the convolution's last inputs in
    # the state pool: "A row of matrix state"); ``layer_experts[l]`` is
    # 0 for a dense FFN and 1 for an expert layer.  None: every layer is
    # of the one kind the fields above give.
    layer_attention: Optional[tuple] = None
    layer_experts: Optional[tuple] = None
    conv_kernel: int = 0
    gdn_heads: int = 0
    gdn_key_dim: int = 0
    gdn_value_dim: int = 0
    gdn_conv: int = 4
    gdn_beta_scale: float = 1.0
    swa_num_heads: int = 0
    swa_num_kv_heads: int = 0
    swa_head_dim: int = 0
    swa_v_head_dim: int = 0
    swa_rope_theta: float = 10000.0
    swa_sink: bool = False
    full_sink: bool = False
    attention_value_scale: Optional[float] = None   # v scaled before attention
    # the router: "softmax" (mixtral, deepseek-v2) or "sigmoid" scores;
    # a correction bias a expert that is added to choose the experts and
    # never to weigh them (deepseek-v3's noaux_tc)
    router_scoring: str = "softmax"
    router_bias: bool = False
    routed_scaling_factor: float = 1.0
    # the chip's share of an expert layer: ``expert_shards`` chips share
    # each layer by experts and this is share ``expert_shard`` of them.
    # The router keeps ``num_experts`` outputs; the parameters hold the
    # ``experts_held`` experts [expert_shard * held, (expert_shard+1) * held)
    expert_shards: int = 1
    expert_shard: int = 0

    @property
    def experts_held(self) -> int:
        return self.num_experts // max(self.expert_shards, 1)

    @property
    def two_kind_cache(self) -> bool:
        """Window layers with a page pool and a page table of their own."""
        return MIXER_WINDOW in (self.layer_attention or ())

    def attention_layers(self, kind: int) -> int:
        """How many layers' mixer is of kind ``kind`` (0 full attention,
        1 window attention, 2 short convolution, 3 gated delta rule)."""
        if self.layer_attention is None:
            return self.num_layers if kind == 0 else 0
        return sum(1 for k in self.layer_attention if k == kind)

    @property
    def conv_layers(self) -> int:
        """Layers whose mixer is a short convolution (no page)."""
        return self.attention_layers(MIXER_CONV)

    @property
    def gdn_layers(self) -> int:
        """Layers whose mixer is a gated delta rule (no page)."""
        return self.attention_layers(MIXER_GDN)

    @property
    def gdn_conv_dim(self) -> int:
        """Channels of a delta-rule layer's convolution: [q | k | v]."""
        return self.gdn_heads * (2 * self.gdn_key_dim + self.gdn_value_dim)

    def gdn_state_bytes(self, state_bytes: int = 2,
                        dtype_bytes: int = 2) -> tuple:
        """(matrix state, convolution tail) bytes a sequence holds in
        ONE delta-rule layer: the state in ``state_bytes`` a number,
        the tail in the type the model is served in."""
        return (self.gdn_heads * self.gdn_key_dim * self.gdn_value_dim
                * state_bytes,
                (self.gdn_conv - 1) * self.gdn_conv_dim * dtype_bytes)

    def kv_heads_per_row(self, kind: int) -> int:
        """KV heads a 128-lane row of kind ``kind``'s token-flat pools
        holds (``heads_per_lane_row``)."""
        _, heads, dk, dv = self.kv_page_geometry(kind)
        return heads_per_lane_row(dk, dv, heads)

    def kv_page_geometry(self, kind: int) -> tuple:
        """(layers, kv heads, k head dim, v head dim) of kind ``kind``'s
        page pool."""
        if kind == 1:
            return (self.attention_layers(1), self.swa_num_kv_heads,
                    self.swa_head_dim, self.swa_v_head_dim or self.swa_head_dim)
        return (self.attention_layers(0), self.num_kv_heads, self.head_dim,
                self.v_head_dim or self.head_dim)

    def kv_bytes_per_token_kind(self, kind: int, dtype_bytes: int = 2,
                                stored: bool = False) -> int:
        """Bytes one cached token holds across the layers of one kind;
        ``stored``: as the pool lays it out (``stored_key_dim``)."""
        layers, heads, dk, dv = self.kv_page_geometry(kind)
        if stored:
            dk = stored_key_dim(dk)
        return layers * heads * (dk + dv) * dtype_bytes

    @property
    def ssm_inner(self) -> int:
        """Width of the mixer's x and z streams (d_ssm)."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels of the mixer's convolution: [x | B | C]."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def ssm_proj_dim(self) -> int:
        """Width of the mixer's input projection: [z | x | B | C | dt]."""
        return self.ssm_inner + self.ssm_conv_dim + self.ssm_heads

    def state_bytes_per_seq(self, dtype_bytes: int = 2,
                            state_bytes: Optional[int] = None) -> int:
        """Bytes of recurrent state one sequence holds across all
        layers, whatever its length: the mixer's state and the
        convolution's tail, the short-convolution layers' last inputs,
        or the delta-rule layers' matrix state (``state_bytes`` a
        number where it is held in another type than the rest) and
        convolution tail, in the type the model is served in (0 for a
        model with none of them)."""
        if self.gdn_layers:
            return self.gdn_layers * sum(self.gdn_state_bytes(
                state_bytes or dtype_bytes, dtype_bytes))
        if self.conv_layers:
            return (self.conv_layers * (self.conv_kernel - 1)
                    * self.hidden_size * dtype_bytes)
        if not self.ssm_state:
            return 0
        per_layer = (self.ssm_inner * self.ssm_state
                     + (self.ssm_conv - 1) * self.ssm_conv_dim)
        return self.num_layers * per_layer * dtype_bytes

    @property
    def mla_dims(self) -> tuple:
        """(nope, rope, latent, value) widths of a latent-attention
        head: what a query head multiplies without rotation, what
        rotates (the one key part all heads share), the cached latent's
        rank, and a value head's size."""
        return (self.qk_nope_head_dim or self.head_dim,
                self.qk_rope_head_dim or 64,
                self.kv_lora_rank or 512,
                self.v_head_dim or self.head_dim)

    @property
    def latent_lanes(self) -> int:
        """Lanes a cached token's latent is stored at where the decode
        kernel reads the pool (``stored_key_dim``: 576 lies in 640)."""
        return stored_key_dim(self.kv_cache_dim)

    @property
    def kv_cache_heads(self) -> int:
        """Head count of the KV cache: MLA caches ONE shared latent."""
        return 1 if self.attention_kind == AttentionKind.MLA else self.num_kv_heads

    @property
    def kv_cache_dim(self) -> int:
        """Per-head cache dim: MLA caches [kv_lora_rank + rope] latents."""
        if self.attention_kind == AttentionKind.MLA:
            return (self.kv_lora_rank or 0) + (self.qk_rope_head_dim or 0)
        return self.head_dim

    @property
    def attention_kind(self) -> AttentionKind:
        if self.kv_lora_rank:
            return AttentionKind.MLA
        if self.num_kv_heads == 1:
            return AttentionKind.MQA
        if self.num_kv_heads < self.num_heads:
            return AttentionKind.GQA
        return AttentionKind.MHA

    def param_count(self) -> int:
        """Estimate total parameter count from the architecture."""
        h = self.hidden_size
        embed = self.vocab_size * h * (1 if self.tie_word_embeddings else 2)
        if self.attention_kind == AttentionKind.MLA:
            # q: h->q_lora->heads*(nope+rope); kv: h->kv_lora(+rope); o
            qk = (self.qk_nope_head_dim or 0) + (self.qk_rope_head_dim or 0)
            q_in = self.q_lora_rank or h
            attn = (
                (h * q_in if self.q_lora_rank else 0)
                + q_in * self.num_heads * qk
                + h * ((self.kv_lora_rank or 0) + (self.qk_rope_head_dim or 0))
                + (self.kv_lora_rank or 0) * self.num_heads * ((self.qk_nope_head_dim or 0) + (self.v_head_dim or 0))
                + self.num_heads * (self.v_head_dim or 0) * h
            )
        elif self.layer_attention is not None:
            return self._param_count_by_kind(embed)
        else:
            attn = h * self.num_heads * self.head_dim + 2 * h * self.num_kv_heads * self.head_dim + self.num_heads * self.head_dim * h
        if self.num_experts > 0:
            inter = self.moe_intermediate_size or self.intermediate_size
            # (the experts HELD here: all of them unless the layer is
            # shared between chips)
            experts = self.experts_held + self.num_shared_experts
            mlp_moe = 3 * h * inter * experts + h * self.num_experts
            dense_layers = self.moe_layer_start
            moe_layers = self.num_layers - dense_layers
            mlp_total = moe_layers * mlp_moe + dense_layers * 3 * h * self.intermediate_size
        else:
            mlp_total = self.num_layers * 3 * h * self.intermediate_size
        norms = self.num_layers * 2 * h + h
        mixer = 0
        if self.ssm_state:
            # in/out projections, conv weight and bias, dt_bias, A_log,
            # D, and the gated norm's weight
            mixer = (h * self.ssm_proj_dim + self.ssm_inner * h
                     + (self.ssm_conv + 1) * self.ssm_conv_dim
                     + 3 * self.ssm_heads + self.ssm_inner)
        return (embed + self.num_layers * (attn + mixer) + mlp_total
                + norms)

    def _param_count_by_kind(self, embed: int) -> int:
        """Parameters HELD here by a model whose layers are of more than
        one kind: each layer's attention by its kind, a dense FFN or the
        held experts and the router's full width, two norms a layer."""
        h = self.hidden_size
        total = embed + h
        experts = self.layer_experts or (0,) * self.num_layers
        for kind, moe in zip(self.layer_attention, experts):
            if kind == MIXER_CONV:
                # [B | C | u] in, out, and the taps
                total += h * 3 * h + h * h + self.conv_kernel * h
            elif kind == MIXER_GDN:
                # q, k, v with their taps, the output gate and W_o, the
                # two gates a head (a, b), A_log, dt_bias, the gated
                # norm's one gain a value lane
                Hd, dv = self.gdn_heads, self.gdn_value_dim
                total += (h * self.gdn_conv_dim
                          + self.gdn_conv * self.gdn_conv_dim
                          + 2 * h * Hd * dv + 2 * h * Hd + 2 * Hd + dv)
            else:
                if kind:
                    H, Hkv = self.swa_num_heads, self.swa_num_kv_heads
                    dk = self.swa_head_dim
                    dv = self.swa_v_head_dim or dk
                    sink = H if self.swa_sink else 0
                else:
                    H, Hkv = self.num_heads, self.num_kv_heads
                    dk, dv = self.head_dim, self.v_head_dim or self.head_dim
                    sink = H if self.full_sink else 0
                total += h * H * dk + h * Hkv * (dk + dv) + H * dv * h + sink
                if self.qk_norm and self.qk_norm_whole:
                    total += (H + Hkv) * dk
                elif self.qk_norm:
                    total += 2 * dk
            if moe:
                inter = self.moe_intermediate_size or self.intermediate_size
                total += 3 * h * inter * self.experts_held \
                    + h * self.num_experts \
                    + (self.num_experts if self.router_bias else 0)
            else:
                total += 3 * h * self.intermediate_size
            total += 2 * h
        return total

    def kv_bytes_per_token(self, dtype_bytes: int = 2,
                           stored: bool = False) -> int:
        """KV-cache bytes per token across all layers.

        GQA formula matches the reference
        (``pkg/model/interface.go:217``): ``2*layers*kv_heads*head_dim*dtype``.
        MLA caches the compressed latent + rope key instead; ``stored``:
        at the lanes the kernel-read latent pool lays a token out in
        (``latent_lanes``).
        """
        if self.attention_kind == AttentionKind.MLA:
            per_layer = self.latent_lanes if stored else self.kv_cache_dim
            return self.num_layers * per_layer * dtype_bytes
        if self.layer_attention is not None:
            # what a token holds while every layer still holds it: a
            # window layer's share goes back to its pool once the token
            # is a window behind (docs/kv-cache.md)
            return (self.kv_bytes_per_token_kind(0, dtype_bytes)
                    + self.kv_bytes_per_token_kind(1, dtype_bytes))
        return 2 * self.num_layers * self.num_kv_heads * self.head_dim * dtype_bytes


@dataclass(frozen=True)
class ModelMetadata:
    """A registered model preset (reference: one entry of
    ``model_catalog.yaml`` + ``PresetParam``)."""

    name: str                      # preset name, e.g. "llama-3.1-8b-instruct"
    hf_id: str                     # huggingface repo id
    arch: ModelArch
    weights_dtype_bytes: int = 2   # bf16 on TPU
    model_file_bytes: int = 0      # on-disk safetensors size; 0 = derive
    token_limit: int = 0           # max context; 0 = arch.max_position_embeddings
    download_auth_required: bool = False
    quantization: str = ""         # "", "int8", "mxfp4", ...
    tool_call_parser: str = ""
    reasoning_parser: str = ""
    chat_template: str = ""        # chat template preset name
    tags: tuple[str, ...] = ()
    # "engine" = the first-party JAX engine; "transformers" = the HF
    # fallback runtime for long-tail architectures (reference:
    # RuntimeName in pkg/model/interface.go + the text-generation
    # transformers runtime)
    runtime: str = "engine"
    # default draft preset for two-model speculative decoding; "" = no
    # curated pairing.  Resolved by the `kaito-tpu.io/speculative-draft:
    # auto` annotation; serving stays non-speculative unless asked
    speculative_draft: str = ""

    @property
    def file_bytes(self) -> int:
        if self.model_file_bytes:
            return self.model_file_bytes
        return self.arch.param_count() * self.weights_dtype_bytes

    @property
    def max_model_len(self) -> int:
        return self.token_limit or self.arch.max_position_embeddings

    def kv_bytes_per_token(self, dtype_bytes: int = 2,
                           stored: bool = False) -> int:
        return self.arch.kv_bytes_per_token(dtype_bytes, stored)

    def disk_storage_bytes(self) -> int:
        """Provisioned disk for weights: expand for download+load headroom,
        matching the reference's sizing rule (generator.go: size*2.5 + margin,
        rounded up to 10Gi steps)."""
        GiB = 2**30
        raw = int(self.file_bytes * 2.5) + 48 * GiB
        step = 10 * GiB
        return int(math.ceil(raw / step) * step)

    def with_overrides(self, **kw) -> "ModelMetadata":
        return replace(self, **kw)
