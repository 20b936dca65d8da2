"""Build :class:`ModelMetadata` from a HuggingFace ``config.json`` dict.

The TPU-native analogue of the reference's preset auto-generator
(``presets/workspace/generator/generator.go:805`` GeneratePreset): the
reference queries the HF Hub at reconcile time for safetensors sizes and
``config.json`` and derives ``bytesPerToken``/``modelFileSize``; we do
the same derivation from a config dict.  Network fetch is injected by
the caller (the controller can mount a config or use a hub client), so
this module stays pure and unit-testable.
"""

from __future__ import annotations

from typing import Mapping, Optional

from kaito_tpu.models.metadata import (MIXER_CONV, MIXER_FULL, MIXER_GDN,
                                       ModelArch, ModelMetadata)

# Architectures we can instantiate in the engine.  The analogue of the
# reference's vLLM arch allowlist (presets/workspace/models/
# vllm_model_arch_list.txt) — ours is what the config-driven JAX
# transformer supports.
SUPPORTED_ARCHITECTURES = {
    "LlamaForCausalLM",
    "MistralForCausalLM",
    "Qwen2ForCausalLM",
    "Qwen3ForCausalLM",
    "Phi3ForCausalLM",
    "PhiForCausalLM",
    "Gemma2ForCausalLM",
    "Gemma3ForCausalLM",
    "Gemma3ForConditionalGeneration",
    "MixtralForCausalLM",
    "DeepseekV2ForCausalLM",
    "DeepseekV3ForCausalLM",
    "FalconForCausalLM",
    "FalconH1ForCausalLM",
    "GptOssForCausalLM",
    "MiMoV2ForCausalLM",
    "JoyAILLMFlashForCausalLM",
    "Lfm2ForCausalLM",
    "Lfm2MoeForCausalLM",
    "OlmoHybridForCausalLM",
}


def _first(cfg: Mapping, *keys, default=None):
    for k in keys:
        if k in cfg and cfg[k] is not None:
            return cfg[k]
    return default


def arch_from_hf_config(cfg: Mapping) -> ModelArch:
    """Map a HF ``config.json`` dict onto :class:`ModelArch`."""
    # gemma-3 multimodal nests the LM under text_config
    if "text_config" in cfg and "num_hidden_layers" not in cfg:
        inner = dict(cfg["text_config"])
        inner.setdefault("architectures", cfg.get("architectures"))
        inner.setdefault("model_type", cfg.get("model_type"))
        cfg = inner

    archs = cfg.get("architectures") or []
    arch_name = archs[0] if archs else cfg.get("model_type", "")
    model_type = cfg.get("model_type", "").lower()

    hidden = int(_first(cfg, "hidden_size", "n_embd", default=0))
    layers = int(_first(cfg, "num_hidden_layers", "n_layer", default=0))
    heads = int(_first(cfg, "num_attention_heads", "n_head", default=0))
    kv_heads = int(_first(cfg, "num_key_value_heads", "num_kv_heads", default=heads) or heads)
    head_dim = int(_first(cfg, "head_dim", default=0) or (hidden // max(heads, 1)))
    inter = int(_first(cfg, "intermediate_size", "ffn_hidden_size", default=4 * hidden))
    vocab = int(_first(cfg, "vocab_size", default=32000))
    max_pos = int(_first(cfg, "max_position_embeddings", "n_positions", default=8192))

    act = str(_first(cfg, "hidden_act", "hidden_activation", "activation_function", default="silu"))
    if act in ("gelu_new", "gelu_fast", "gelu_pytorch_tanh"):
        act = "gelu_tanh"

    kw = dict(
        vocab_size=vocab,
        hidden_size=hidden,
        num_layers=layers,
        num_heads=heads,
        num_kv_heads=kv_heads,
        head_dim=head_dim,
        intermediate_size=inter,
        max_position_embeddings=max_pos,
        hidden_act=act,
        rms_norm_eps=float(_first(cfg, "rms_norm_eps", "layer_norm_epsilon", default=1e-5)),
        rope_theta=float(_first(cfg, "rope_theta", default=10000.0)),
        partial_rotary_factor=float(_first(cfg, "partial_rotary_factor", default=1.0)),
        rope_scaling=cfg.get("rope_scaling"),
        tie_word_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        sliding_window=cfg.get("sliding_window"),
        qkv_bias=bool(_first(cfg, "attention_bias", "qkv_bias", default=False)),
    )

    if model_type in ("gemma", "gemma2", "gemma3", "gemma3_text"):
        kw.update(
            norm_offset=True,
            embedding_multiplier=hidden ** 0.5,
            query_pre_attn_scalar=float(_first(cfg, "query_pre_attn_scalar", default=head_dim)),
        )
        if model_type in ("gemma2", "gemma3", "gemma3_text"):
            kw["pre_post_norm"] = True
        if model_type == "gemma2":
            kw["attn_logit_softcap"] = _first(cfg, "attn_logit_softcapping", default=50.0)
            kw["final_logit_softcap"] = _first(cfg, "final_logit_softcapping", default=30.0)
        if model_type in ("gemma3", "gemma3_text"):
            kw["sliding_window_pattern"] = int(_first(cfg, "sliding_window_pattern", default=6))
            kw["qk_norm"] = True

    if model_type == "qwen2":
        kw["qkv_bias"] = True
    if model_type == "qwen3":
        kw["qk_norm"] = True

    if model_type in ("mixtral",):
        kw.update(
            num_experts=int(_first(cfg, "num_local_experts", default=8)),
            num_experts_per_tok=int(_first(cfg, "num_experts_per_tok", default=2)),
        )

    if model_type in ("gpt_oss",):
        kw.update(
            num_experts=int(_first(cfg, "num_local_experts", "num_experts", default=32)),
            num_experts_per_tok=int(_first(cfg, "num_experts_per_tok", "experts_per_token", default=4)),
            moe_intermediate_size=int(_first(cfg, "intermediate_size", default=2880)),
            # gpt-oss alternates sliding/full attention layer types
            sliding_window_pattern=2,
        )

    if model_type in ("deepseek_v2", "deepseek_v3", "joyai_llm_flash"):
        kw.update(_deepseek_fields(cfg, model_type))

    if model_type == "falcon":
        if bool(cfg.get("multi_query", False)) and "num_key_value_heads" not in cfg:
            kw["num_kv_heads"] = 1
        kw.update(gated_mlp=False, parallel_residual=bool(cfg.get("parallel_attn", True)),
                  norm_type="layernorm")

    if model_type == "falcon_h1":
        # a Mamba-2 mixer beside attention in every block, and the
        # published multipliers in the forward pass
        if cfg.get("attn_layer_indices") is not None:
            raise ValueError("falcon_h1 with attn_layer_indices (attention "
                             "in some blocks only) is not implemented")
        if not bool(cfg.get("mamba_rms_norm", True)) or bool(
                cfg.get("mamba_norm_before_gate", False)):
            raise ValueError("falcon_h1 is implemented with the gated "
                             "RMSNorm after the gate only")
        if any(bool(cfg.get(k, False)) for k in (
                "mamba_proj_bias", "mlp_bias", "projectors_bias")):
            raise ValueError("falcon_h1 projection biases are not "
                             "implemented")
        d_ssm = int(_first(cfg, "mamba_d_ssm", default=0)
                    or int(cfg.get("mamba_expand", 2)) * hidden)
        m_heads = int(cfg["mamba_n_heads"])
        m_head_dim = int(_first(cfg, "mamba_d_head", default=0)
                         or d_ssm // m_heads)
        if m_heads * m_head_dim != d_ssm:
            raise ValueError(f"falcon_h1: mamba_n_heads {m_heads} x "
                             f"mamba_d_head {m_head_dim} != mamba_d_ssm "
                             f"{d_ssm}")
        if not bool(cfg.get("mamba_conv_bias", True)):
            raise ValueError("falcon_h1 without mamba_conv_bias is not "
                             "implemented")
        kw.update(
            ssm_state=int(cfg["mamba_d_state"]),
            ssm_heads=m_heads,
            ssm_head_dim=m_head_dim,
            ssm_groups=int(cfg.get("mamba_n_groups", 1)),
            ssm_conv=int(cfg.get("mamba_d_conv", 4)),
            ssm_chunk=int(cfg.get("mamba_chunk_size", 128)),
            embedding_multiplier=float(cfg.get("embedding_multiplier", 1.0)),
            lm_head_multiplier=float(cfg.get("lm_head_multiplier", 1.0)),
            attention_in_multiplier=float(
                cfg.get("attention_in_multiplier", 1.0)),
            attention_out_multiplier=float(
                cfg.get("attention_out_multiplier", 1.0)),
            key_multiplier=float(cfg.get("key_multiplier", 1.0)),
            ssm_in_multiplier=float(cfg.get("ssm_in_multiplier", 1.0)),
            ssm_out_multiplier=float(cfg.get("ssm_out_multiplier", 1.0)),
            ssm_multipliers=tuple(
                float(x) for x in cfg.get("ssm_multipliers", (1.0,) * 5)),
            mlp_multipliers=tuple(
                float(x) for x in cfg.get("mlp_multipliers", (1.0, 1.0))),
        )

    if model_type == "mimo_v2":
        kw.update(_mimo_v2_fields(cfg, layers))

    if model_type in ("lfm2", "lfm2_moe"):
        kw.update(_lfm2_fields(cfg, model_type, layers, kw))

    if model_type == "olmo_hybrid":
        kw.update(_olmo_hybrid_fields(cfg, layers))

    if model_type == "phi":
        kw.update(gated_mlp=False, parallel_residual=True, norm_type="layernorm",
                  linear_bias=True)

    return ModelArch(**kw)


def _olmo_hybrid_fields(cfg: Mapping, layers: int) -> dict:
    """Olmo-Hybrid (``olmo_hybrid``): ``layer_types`` names each
    layer's mixer, ``linear_attention`` a gated delta rule (the
    ``linear_*`` keys: heads, key and value sizes, the taps of the
    convolutions in front, ``linear_allow_neg_eigval``: beta times 2)
    and ``full_attention`` MHA with one RMSNorm over the whole query
    and the whole key projection and NO rotary embedding
    (``rope_parameters.rope_theta`` null); the family's reordered block
    norm (after the operator and after the MLP alone).  What is not
    implemented is refused by name."""
    def refuse(what):
        raise ValueError(f"olmo_hybrid: {what} is not implemented")

    types = tuple(cfg.get("layer_types") or ())
    if len(types) != layers:
        raise ValueError(f"olmo_hybrid: layer_types ({len(types)}) must "
                         f"name each of the {layers} layers")
    known = {"linear_attention": MIXER_GDN, "full_attention": MIXER_FULL}
    for t in types:
        if t not in known:
            refuse(f"a layer_types entry {t!r}")
    heads = int(cfg["linear_num_value_heads"])
    if int(cfg.get("linear_num_key_heads", heads)) != heads:
        refuse(f"linear_num_key_heads {cfg['linear_num_key_heads']} != "
               f"linear_num_value_heads {heads}")
    rope = cfg.get("rope_parameters") or {}
    theta = rope.get("rope_theta", cfg.get("rope_theta"))
    if theta is not None:
        # (until a checkpoint with one is tested against its reference)
        refuse(f"a rotary embedding (rope_theta {theta!r})")
    if bool(cfg.get("attention_bias", False)):
        refuse("attention_bias true")
    taps = int(cfg.get("linear_conv_kernel_dim", 4))
    if taps < 2:
        refuse(f"linear_conv_kernel_dim {taps}")
    return dict(
        rms_norm_eps=float(cfg.get("rms_norm_eps", 1e-6)),
        rotary=False,
        rope_scaling=None,
        qkv_bias=False,
        qk_norm=True,
        qk_norm_whole=True,
        norm_after=True,
        tie_word_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        layer_attention=tuple(known[t] for t in types),
        layer_experts=(0,) * layers,
        gdn_heads=heads,
        gdn_key_dim=int(cfg["linear_key_head_dim"]),
        gdn_value_dim=int(cfg["linear_value_head_dim"]),
        gdn_conv=taps,
        gdn_beta_scale=2.0 if bool(cfg.get("linear_allow_neg_eigval",
                                           False)) else 1.0,
    )


def _lfm2_fields(cfg: Mapping, model_type: str, layers: int, kw: dict) -> dict:
    """LFM2 (``lfm2``) and LFM2-MoE (``lfm2_moe``): ``layer_types``
    names each layer's mixer, ``conv`` a gated short convolution of
    ``conv_L_cache`` taps a channel and ``full_attention`` GQA with an
    RMSNorm on every query and key head before the rotary embedding;
    the head is tied to the embedding.  ``lfm2_moe``: the first
    ``num_dense_layers`` FFNs are dense and the rest a sigmoid-routed
    expert layer whose ``use_expert_bias`` bias chooses and never
    weighs; ``lfm2``: every FFN dense, of the width its ``block_*`` keys
    give.  What is not implemented is refused by name."""
    def refuse(what):
        raise ValueError(f"{model_type}: {what} is not implemented")

    types = tuple(cfg.get("layer_types") or ())
    if len(types) != layers:
        raise ValueError(f"{model_type}: layer_types ({len(types)}) must "
                         f"name each of the {layers} layers")
    known = {"conv": MIXER_CONV, "full_attention": MIXER_FULL}
    for t in types:
        if t not in known:
            refuse(f"a layer_types entry {t!r}")
    if bool(cfg.get("conv_bias", False)):
        refuse("conv_bias true")
    taps = int(cfg.get("conv_L_cache", 3))
    if taps < 2:
        refuse(f"conv_L_cache {taps}")
    scaling = cfg.get("rope_scaling") or {}
    if str(scaling.get("rope_type", scaling.get("type", "default"))) \
            != "default":
        refuse(f"rope_scaling {scaling!r}")
    out = dict(
        rms_norm_eps=float(_first(cfg, "norm_eps", "rms_norm_eps",
                                  default=1e-5)),
        rope_scaling=None,
        qkv_bias=False,
        qk_norm=True,
        tie_word_embeddings=bool(cfg.get("tie_word_embeddings", True)),
        layer_attention=tuple(known[t] for t in types),
        conv_kernel=taps,
    )
    if model_type == "lfm2":
        inter = int(_first(cfg, "block_ff_dim", "intermediate_size",
                           default=kw["intermediate_size"]))
        if bool(cfg.get("block_auto_adjust_ff_dim", False)):
            inter = int(2 * inter / 3)
            if cfg.get("block_ffn_dim_multiplier") is not None:
                inter = int(float(cfg["block_ffn_dim_multiplier"]) * inter)
            mult = int(cfg.get("block_multiple_of", 256))
            inter = mult * ((inter + mult - 1) // mult)
        out.update(intermediate_size=inter, layer_experts=(0,) * layers)
        return out
    if not bool(cfg.get("norm_topk_prob", True)):
        refuse("norm_topk_prob false")
    if not bool(cfg.get("use_expert_bias", True)):
        refuse("use_expert_bias false")
    dense = int(cfg.get("num_dense_layers", 0))
    out.update(
        layer_experts=tuple(int(l >= dense) for l in range(layers)),
        num_experts=int(cfg.get("num_experts", 0)),
        num_experts_per_tok=int(cfg.get("num_experts_per_tok", 0)),
        moe_intermediate_size=_first(cfg, "moe_intermediate_size"),
        router_scoring="sigmoid",
        router_bias=True,
        routed_scaling_factor=float(cfg.get("routed_scaling_factor") or 1.0),
    )
    return out


def _deepseek_fields(cfg: Mapping, model_type: str) -> dict:
    """The DeepSeek-V2/V3 family's keys (``joyai_llm_flash`` publishes
    the same ones): latent attention, the first ``first_k_dense_replace``
    layers dense and an expert layer with shared experts after them.
    The router is read from the keys the family publishes
    (``scoring_func``, ``topk_method`` ``noaux_tc``: a correction bias
    that chooses and never weighs, ``routed_scaling_factor``; a config
    that carries none of them keeps the softmax router of deepseek-v2),
    the rotary pairing from ``rope_interleave``, and the chip's share
    of an expert layer from ``expert_shards`` / ``expert_shard`` (this
    repo's keys, as ``_mimo_v2_fields`` reads them:
    ``n_routed_experts`` then counts the experts held).  What is not
    implemented is refused by name."""
    def refuse(what):
        raise ValueError(f"{model_type}: {what} is not implemented")

    n_group = int(cfg.get("n_group") or 1)
    if n_group > 1 and int(cfg.get("topk_group") or n_group) < n_group:
        refuse(f"group-limited routing (topk_group "
               f"{cfg.get('topk_group')} of n_group {n_group})")
    scoring = str(cfg.get("scoring_func") or "softmax")
    if scoring not in ("softmax", "sigmoid"):
        refuse(f"scoring_func {scoring!r}")
    method = str(cfg.get("topk_method") or "greedy")
    if method not in ("greedy", "noaux_tc"):
        refuse(f"topk_method {method!r}")
    if scoring == "sigmoid" and not bool(cfg.get("norm_topk_prob", True)):
        refuse("sigmoid scores with norm_topk_prob false")
    if int(cfg.get("moe_layer_freq") or 1) != 1:
        refuse(f"moe_layer_freq {cfg.get('moe_layer_freq')!r}")
    scaling = cfg.get("rope_scaling") or {}
    stype = str(scaling.get("rope_type", scaling.get("type", "default")))
    if stype.lower() not in ("default", "linear", "yarn", "llama3"):
        refuse(f"rope_scaling {scaling!r} (no table for it)")
    shards = int(cfg.get("expert_shards", 1))
    shard = int(cfg.get("expert_shard", 0))
    if not 0 <= shard < shards:
        raise ValueError(f"{model_type}: expert_shard {shard} of {shards}")
    return dict(
        num_experts=int(_first(cfg, "n_routed_experts", default=0)) * shards,
        num_experts_per_tok=int(_first(cfg, "num_experts_per_tok", default=0)),
        moe_intermediate_size=_first(cfg, "moe_intermediate_size"),
        num_shared_experts=int(_first(cfg, "n_shared_experts", default=0)),
        moe_layer_start=int(_first(cfg, "first_k_dense_replace", default=0)),
        kv_lora_rank=_first(cfg, "kv_lora_rank"),
        q_lora_rank=_first(cfg, "q_lora_rank"),
        qk_rope_head_dim=_first(cfg, "qk_rope_head_dim"),
        qk_nope_head_dim=_first(cfg, "qk_nope_head_dim"),
        v_head_dim=_first(cfg, "v_head_dim"),
        rope_interleave=bool(cfg.get("rope_interleave", False)),
        router_scoring=scoring,
        router_bias=method == "noaux_tc",
        routed_scaling_factor=float(cfg.get("routed_scaling_factor") or 1.0),
        expert_shards=shards,
        expert_shard=shard,
    )


def _mimo_v2_fields(cfg: Mapping, layers: int) -> dict:
    """MiMo-V2.5's language model: window and full attention layers
    with their own head counts (``hybrid_layer_pattern``), a sink bias
    on the window layers, values narrower than keys, and a
    sigmoid-routed expert layer (``moe_layer_freq``) of which this
    chip may hold a share (``expert_shards`` / ``expert_shard``, this
    repo's keys: ``n_routed_experts`` then counts the experts held).
    What is not implemented is refused by name."""
    def refuse(what):
        raise ValueError(f"mimo_v2: {what} is not implemented")

    pattern = tuple(int(x) for x in cfg.get("hybrid_layer_pattern") or ())
    freq = tuple(int(x) for x in cfg.get("moe_layer_freq") or ())
    if len(pattern) != layers or len(freq) != layers:
        raise ValueError(
            f"mimo_v2: hybrid_layer_pattern ({len(pattern)}) and "
            f"moe_layer_freq ({len(freq)}) must name each of the "
            f"{layers} layers")
    if set(pattern) - {0, 1} or set(freq) - {0, 1}:
        refuse("a layer kind other than 0 and 1")
    if cfg.get("hybrid_block_size") is not None:
        refuse("hybrid_block_size")
    if bool(cfg.get("attention_bias", False)):
        refuse("attention_bias")
    if int(cfg.get("n_group") or 1) != 1 or int(cfg.get("topk_group") or 1) != 1:
        refuse("group-limited routing (n_group, topk_group above 1)")
    if cfg.get("n_shared_experts"):
        refuse("shared experts")
    if str(cfg.get("scoring_func", "sigmoid")) != "sigmoid":
        refuse(f"scoring_func {cfg.get('scoring_func')!r}")
    if str(cfg.get("topk_method", "noaux_tc")) != "noaux_tc":
        refuse(f"topk_method {cfg.get('topk_method')!r}")
    if not bool(cfg.get("norm_topk_prob", True)):
        refuse("norm_topk_prob false")
    scaling = cfg.get("rope_scaling") or {}
    if str(scaling.get("rope_type", scaling.get("type", "default"))) \
            != "default":
        refuse(f"rope_scaling {scaling!r}")
    window = int(_first(cfg, "sliding_window", "sliding_window_size",
                        default=0))
    if any(pattern) and window <= 0:
        refuse("window layers without sliding_window")
    shards = int(cfg.get("expert_shards", 1))
    shard = int(cfg.get("expert_shard", 0))
    held = int(cfg.get("n_routed_experts", 0))
    if not 0 <= shard < shards:
        raise ValueError(f"mimo_v2: expert_shard {shard} of {shards}")
    return dict(
        rms_norm_eps=float(_first(cfg, "layernorm_epsilon", "rms_norm_eps",
                                  default=1e-5)),
        rope_scaling=None,
        qkv_bias=False,
        sliding_window=window or None,
        v_head_dim=int(_first(cfg, "v_head_dim", default=0)) or None,
        layer_attention=pattern,
        layer_experts=freq,
        swa_num_heads=int(_first(cfg, "swa_num_attention_heads",
                                 "num_attention_heads")),
        swa_num_kv_heads=int(_first(cfg, "swa_num_key_value_heads",
                                    "num_key_value_heads")),
        swa_head_dim=int(_first(cfg, "swa_head_dim", "head_dim")),
        swa_v_head_dim=int(_first(cfg, "swa_v_head_dim", "v_head_dim",
                                  "head_dim")),
        swa_rope_theta=float(_first(cfg, "swa_rope_theta", default=10000.0)),
        swa_sink=bool(cfg.get("add_swa_attention_sink_bias", False)),
        full_sink=bool(cfg.get("add_full_attention_sink_bias", False)),
        attention_value_scale=(float(cfg["attention_value_scale"])
                               if cfg.get("attention_value_scale") else None),
        num_experts=held * shards,
        num_experts_per_tok=int(cfg.get("num_experts_per_tok", 0)),
        moe_intermediate_size=_first(cfg, "moe_intermediate_size"),
        router_scoring="sigmoid",
        router_bias=True,
        routed_scaling_factor=float(cfg.get("routed_scaling_factor") or 1.0),
        expert_shards=shards,
        expert_shard=shard,
    )


# Parser-mode derivation for generated presets (the reference's
# reasoning/tool maps, generator.go:45-160, restricted to families this
# engine serves).  The engine's chat route gates think-tag reasoning
# splitting on reasoning_parser; tool extraction is format-sniffing
# (hermes/mistral), with the parser NAME carried for contract parity.
_REASONING_BY_PREFIX = {
    "deepseek-r1": "deepseek_r1",
    "qwq-32b": "deepseek_r1",
    "deepseek-v3": "deepseek_v3",
    "qwen3": "qwen3",
}
_REASONING_BY_ARCH = {
    "DeepseekV3ForCausalLM": "deepseek_v3",
    "Qwen3ForCausalLM": "qwen3",
    "GptOssForCausalLM": "openai_gptoss",
}
_TOOLS_BY_PREFIX = {
    "deepseek-r1": "deepseek_v3",
    "deepseek-v3": "deepseek_v3",
    "mistral": "mistral",
    "ministral": "mistral",
    "qwen2.5": "hermes",
    "qwen3": "hermes",
    "phi-4-mini": "phi4_mini_json",
    "llama-3": "llama3_json",
    "meta-llama-3": "llama3_json",
}
_TOOLS_BY_ARCH = {
    "MistralForCausalLM": "mistral",
    "MixtralForCausalLM": "mistral",
    "LlamaForCausalLM": "llama3_json",
    "Qwen2ForCausalLM": "hermes",
    "Qwen3ForCausalLM": "hermes",
}


def derive_parsers(name: str, archs) -> tuple[str, str]:
    """(tool_call_parser, reasoning_parser) for a model, by name prefix
    first (most specific), architecture fallback."""
    low = name.lower()
    tool = next((v for k, v in _TOOLS_BY_PREFIX.items()
                 if low.startswith(k)), "")
    reasoning = next((v for k, v in _REASONING_BY_PREFIX.items()
                      if low.startswith(k)), "")
    for a in archs or ():
        tool = tool or _TOOLS_BY_ARCH.get(a, "")
        reasoning = reasoning or _REASONING_BY_ARCH.get(a, "")
    return tool, reasoning


def metadata_from_hf_config(
    hf_id: str,
    cfg: Mapping,
    *,
    name: Optional[str] = None,
    model_file_bytes: int = 0,
    download_auth_required: bool = False,
    quantization: str = "",
    tags: tuple[str, ...] = (),
    speculative_draft: str = "",
) -> ModelMetadata:
    """Auto-generate a preset from a HF config dict (reference:
    ``GeneratePreset``, ``presets/workspace/generator/generator.go:805``)."""
    archs = cfg.get("architectures") or []
    runtime = "engine"
    if archs and not (set(archs) & SUPPORTED_ARCHITECTURES):
        # long-tail architecture: serve via the HF transformers
        # fallback runtime (reference: the text-generation runtime for
        # models vLLM can't serve) — the generic ModelArch extraction
        # below still sizes capacity planning
        runtime = "transformers"
    arch = arch_from_hf_config(cfg)
    if runtime == "transformers" and not (
            arch.hidden_size > 0 and arch.num_layers > 0
            and arch.num_heads > 0):
        # non-transformer config (Mamba/encoder-decoder/vision): the
        # generic dims are garbage and would drive capacity planning to
        # a too-small instance — refuse loudly instead
        raise ValueError(
            f"architecture {archs!r} for {hf_id} is not "
            f"transformer-shaped (no usable hidden/layers/heads dims); "
            f"cannot size capacity for the fallback runtime")
    quant = quantization or str(
        (cfg.get("quantization_config") or {}).get("quant_method", "")
    )
    preset_name = name or hf_id.split("/")[-1].lower()
    tool_parser, reasoning_parser = derive_parsers(
        hf_id.split("/")[-1], archs)
    return ModelMetadata(
        name=preset_name,
        hf_id=hf_id,
        arch=arch,
        model_file_bytes=model_file_bytes,
        token_limit=arch.max_position_embeddings,
        download_auth_required=download_auth_required,
        quantization=quant,
        tags=tags + (("fallback-runtime",) if runtime != "engine" else ()),
        tool_call_parser=tool_parser,
        reasoning_parser=reasoning_parser,
        runtime=runtime,
        speculative_draft=speculative_draft,
    )
