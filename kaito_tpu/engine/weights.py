"""Checkpoint loading: HF safetensors -> stacked param trees.

The engine's weights path for real checkpoints (the reference's pods
download HF repos and vLLM loads them; our pods read the ModelMirror
volume / GCS stream and this module maps HF parameter names onto the
scan-stacked layout).  HF linear weights are [out, in]; ours are
[in, out], so projections transpose on load.
"""

from __future__ import annotations

import logging
import os
from typing import Callable, Optional

import jax.numpy as jnp
import numpy as np

from kaito_tpu.engine.model import TransformerLM

logger = logging.getLogger(__name__)

# our layer key -> (HF suffix, transpose?)
_LAYER_MAP = {
    "attn_norm": ("input_layernorm.weight", False),
    "attn_norm_bias": ("input_layernorm.bias", False),
    "mlp_norm": ("post_attention_layernorm.weight", False),
    "mlp_norm_bias": ("post_attention_layernorm.bias", False),
    "q": ("self_attn.q_proj.weight", True),
    "k": ("self_attn.k_proj.weight", True),
    "v": ("self_attn.v_proj.weight", True),
    "o": ("self_attn.o_proj.weight", True),
    "q_bias": ("self_attn.q_proj.bias", False),
    "k_bias": ("self_attn.k_proj.bias", False),
    "v_bias": ("self_attn.v_proj.bias", False),
    "o_bias": ("self_attn.o_proj.bias", False),
    "q_norm": ("self_attn.q_norm.weight", False),
    "k_norm": ("self_attn.k_norm.weight", False),
    "gate": ("mlp.gate_proj.weight", True),
    "up": ("mlp.up_proj.weight", True),
    "down": ("mlp.down_proj.weight", True),
    "up_bias": ("mlp.up_proj.bias", False),
    "down_bias": ("mlp.down_proj.bias", False),
    "post_attn_norm": ("post_attention_layernorm.weight", False),
    "post_mlp_norm": ("post_feedforward_layernorm.weight", False),
}
# gemma-3 swaps the meaning of post_attention_layernorm: pre-MLP norm is
# pre_feedforward_layernorm
_GEMMA_OVERRIDES = {
    "mlp_norm": ("pre_feedforward_layernorm.weight", False),
    "post_attn_norm": ("post_attention_layernorm.weight", False),
}


# lfm2 / lfm2_moe: the block's two norms, the short convolution's
# projections, the attention's own names for its output projection and
# its QK norms, and a dense FFN numbered as mixtral numbers an expert
_LFM2_OVERRIDES = {
    "attn_norm": ("operator_norm.weight", False),
    "mlp_norm": ("ffn_norm.weight", False),
    "conv_in": ("conv.in_proj.weight", True),
    "conv_out": ("conv.out_proj.weight", True),
    "o": ("self_attn.out_proj.weight", True),
    "q_norm": ("self_attn.q_layernorm.weight", False),
    "k_norm": ("self_attn.k_layernorm.weight", False),
    "gate": ("feed_forward.w1.weight", True),
    "up": ("feed_forward.w3.weight", True),
    "down": ("feed_forward.w2.weight", True),
}


# olmo_hybrid: the block's two norms stand after the operator and the
# MLP (and are named for it), and a delta-rule layer's small tensors;
# its projections and taps are fused by ``_olmo_hybrid_fused``
_OLMO_HYBRID_OVERRIDES = {
    "attn_norm": ("post_attention_layernorm.weight", False),
    "mlp_norm": ("post_feedforward_layernorm.weight", False),
    "gdn_a_log": ("linear_attn.A_log", False),
    "gdn_dt_bias": ("linear_attn.dt_bias", False),
    "gdn_norm": ("linear_attn.o_norm.weight", False),
    "gdn_out": ("linear_attn.o_proj.weight", True),
}


def _olmo_hybrid_fused(get, li: int, our_key: str):
    """A delta-rule layer's fused tensors from the family's names:
    ``gdn_in`` = [W_q | W_k | W_v | W_g] and ``gdn_gates`` = [W_a |
    W_b] (each ``x @ W``), ``gdn_conv_w`` = the three depthwise Conv1d
    weights [channels, 1, taps] side by side as [taps, channels], the
    last tap on the newest input in both."""
    pre = f"layers.{li}.linear_attn."
    if our_key in ("gdn_in", "gdn_gates"):
        return np.concatenate(
            [get(f"{pre}{n}_proj.weight").T
             for n in ("qkvg" if our_key == "gdn_in" else "ab")], axis=1)
    return np.concatenate(
        [get(f"{pre}{n}_conv1d.weight")[:, 0, :].T for n in "qkv"], axis=1)


def _reader(directory: str) -> tuple[Callable[[str], Optional[np.ndarray]], list[str]]:
    from safetensors import safe_open

    files = sorted(f for f in os.listdir(directory) if f.endswith(".safetensors"))
    handles = [safe_open(os.path.join(directory, f), framework="numpy")
               for f in files]
    key_to_handle = {}
    for h in handles:
        for k in h.keys():
            key_to_handle[k] = h

    def read(name: str) -> Optional[np.ndarray]:
        h = key_to_handle.get(name)
        if h is None:
            return None
        return np.asarray(h.get_tensor(name))

    return read, sorted(key_to_handle)


def load_safetensors_params(model: TransformerLM, directory: str,
                            leaf_transform=None) -> dict:
    """Assemble the stacked param tree from HF shards on disk."""
    read, all_keys = _reader(directory)
    params = assemble_params(model, read, all_keys,
                             leaf_transform=leaf_transform)
    logger.info("loaded %d stacked tensors from %s", len(all_keys), directory)
    return params


def assemble_params(model: TransformerLM,
                    read: Callable[[str], Optional[np.ndarray]],
                    all_keys: list[str],
                    leaf_transform=None) -> dict:
    """Map HF tensors (via any reader — disk shards or ranged streaming)
    onto the scan-stacked layout.

    ``leaf_transform(group, key, np_array) -> device leaf`` (group ""
    for top-level params) replaces the default ``jnp.asarray``
    placement per assembled tensor — the engine uses it to shard each
    stacked tensor straight onto its mesh and quantize it immediately
    (donated), so a 70B int8 load never materializes the bf16 tree.
    """
    arch = model.arch
    dtype = model.dtype

    def put(group: str, key: str, np_arr: np.ndarray):
        if leaf_transform is not None:
            return leaf_transform(group, key, np.asarray(np_arr))
        return jnp.asarray(np_arr, dtype)

    def get(name: str, required: bool = True) -> Optional[np.ndarray]:
        for prefix in ("model.", "transformer.", ""):
            t = read(prefix + name)
            if t is not None:
                return t
        if required:
            raise KeyError(f"missing tensor {name!r}; have e.g. {all_keys[:5]}")
        return None

    params: dict = {}
    embed = get("embed_tokens.weight")
    pad = model.vocab_padded - embed.shape[0]
    if pad > 0:
        embed = np.concatenate([embed, np.zeros((pad, embed.shape[1]),
                                                embed.dtype)])
    params["embed"] = put("", "embed", embed)
    final_norm = get("norm.weight", required=False)
    if final_norm is None:
        # lfm2 names its last norm after what it follows
        final_norm = get("embedding_norm.weight")
    params["final_norm"] = put("", "final_norm", final_norm)
    fnb = get("norm.bias", required=False)
    if fnb is not None:
        params["final_norm_bias"] = put("", "final_norm_bias", fnb)
    if not arch.tie_word_embeddings:
        head = read("lm_head.weight")
        if head is None:
            head = get("embed_tokens.weight")
        if model.vocab_padded - head.shape[0] > 0:
            head = np.concatenate([
                head, np.zeros((model.vocab_padded - head.shape[0],
                                head.shape[1]), head.dtype)])
        params["lm_head"] = put("", "lm_head", head)

    layer_map = dict(_LAYER_MAP)
    if arch.pre_post_norm:
        layer_map.update(_GEMMA_OVERRIDES)
    if arch.conv_kernel:
        layer_map.update(_LFM2_OVERRIDES)
    if arch.gdn_layers:
        layer_map.update(_OLMO_HYBRID_OVERRIDES)

    for g in model.groups:
        specs = model._layer_specs(g.moe, g.kind)
        stack: dict[str, list] = {}
        for li in model.stack_layers(g):
            fused_qkv = None
            for our_key in specs:
                if "lora" in our_key:
                    continue
                entry = layer_map.get(our_key)
                tensor = None
                if our_key == "conv_w":
                    # a depthwise Conv1d's weight [channels, 1, taps],
                    # its last tap on the newest input: ours is [taps,
                    # channels] with tap k on the input k tokens back
                    w = get(f"layers.{li}.conv.conv.weight")
                    tensor = np.ascontiguousarray(w[:, 0, ::-1].T)
                if our_key in ("gdn_in", "gdn_gates", "gdn_conv_w"):
                    tensor = _olmo_hybrid_fused(get, li, our_key)
                if entry is not None:
                    suffix, transpose = entry
                    tensor = get(f"layers.{li}.{suffix}", required=False)
                    if tensor is not None and transpose:
                        tensor = tensor.T
                if tensor is None and our_key in ("q", "k", "v"):
                    # phi-3 style fused qkv_proj
                    if fused_qkv is None:
                        fused = get(f"layers.{li}.self_attn.qkv_proj.weight",
                                    required=False)
                        if fused is not None:
                            Hq = arch.num_heads * arch.head_dim
                            Hkv = arch.num_kv_heads * arch.head_dim
                            fused = fused.T
                            fused_qkv = {
                                "q": fused[:, :Hq],
                                "k": fused[:, Hq:Hq + Hkv],
                                "v": fused[:, Hq + Hkv:Hq + 2 * Hkv],
                            }
                    if fused_qkv is not None:
                        tensor = fused_qkv[our_key]
                if tensor is None and our_key in ("gate", "up"):
                    # phi-3 style fused gate_up_proj
                    fused = get(f"layers.{li}.mlp.gate_up_proj.weight",
                                required=False)
                    if fused is not None:
                        fused = fused.T
                        I = arch.intermediate_size
                        tensor = fused[:, :I] if our_key == "gate" else fused[:, I:]
                if tensor is None and g.moe:
                    tensor = _read_moe_tensor(get, arch, li, our_key)
                if tensor is None:
                    raise KeyError(
                        f"no source tensor for layer {li} key {our_key!r}")
                stack.setdefault(our_key, []).append(np.asarray(tensor))
        params[g.name] = {
            k: put(g.name, k, np.stack(v)) for k, v in stack.items()}
    return params


# MoE tensors don't fit the flat suffix map: HF stores one tensor per
# expert (mixtral `block_sparse_moe.experts.{e}.w{1,2,3}`, qwen/deepseek
# `mlp.experts.{e}.{gate,up,down}_proj`), ours stack over the expert
# dim.  w1=gate, w3=up, w2=down (mixtral's numbering).
_MOE_EXPERT_SUFFIXES = {
    "experts_gate": ("w1", "gate_proj"),
    "experts_up": ("w3", "up_proj"),
    "experts_down": ("w2", "down_proj"),
}
_MOE_SHARED = {
    "shared_gate": "gate_proj",
    "shared_up": "up_proj",
    "shared_down": "down_proj",
}


def _read_moe_tensor(get, arch, li: int, our_key: str):
    """Load-side MoE mapping: router / stacked experts / shared experts
    from either HF naming convention; None when absent."""
    if our_key == "router":
        for suffix in ("block_sparse_moe.gate.weight", "mlp.gate.weight",
                       "feed_forward.gate.weight"):
            t = get(f"layers.{li}.{suffix}", required=False)
            if t is not None:
                return t.T                          # [X, H] -> [H, X]
        return None
    if our_key == "router_bias":
        # (lfm2_moe's name for the bias that chooses and never weighs)
        return get(f"layers.{li}.feed_forward.expert_bias", required=False)
    if our_key in _MOE_EXPERT_SUFFIXES:
        mix, qwen = _MOE_EXPERT_SUFFIXES[our_key]
        per_expert = []
        for e in range(arch.num_experts):
            t = get(f"layers.{li}.block_sparse_moe.experts.{e}.{mix}.weight",
                    required=False)
            if t is None:
                t = get(f"layers.{li}.mlp.experts.{e}.{qwen}.weight",
                        required=False)
            if t is None:
                # lfm2_moe numbers an expert's matrices as mixtral does
                t = get(f"layers.{li}.feed_forward.experts.{e}.{mix}.weight",
                        required=False)
            if t is None:
                return None
            per_expert.append(t.T)                  # HF [out, in] -> ours
        return np.stack(per_expert)
    if our_key in _MOE_SHARED:
        t = get(f"layers.{li}.mlp.shared_experts."
                f"{_MOE_SHARED[our_key]}.weight", required=False)
        return None if t is None else t.T
    return None


def _export_moe_tensor(out: dict, li: int, our_key: str, t: np.ndarray):
    """Export-side inverse of _read_moe_tensor (mixtral naming)."""
    if our_key == "router":
        out[f"model.layers.{li}.block_sparse_moe.gate.weight"] = \
            np.ascontiguousarray(t.T)
        return True
    if our_key in _MOE_EXPERT_SUFFIXES:
        mix, _ = _MOE_EXPERT_SUFFIXES[our_key]
        for e in range(t.shape[0]):
            out[f"model.layers.{li}.block_sparse_moe.experts.{e}"
                f".{mix}.weight"] = np.ascontiguousarray(t[e].T)
        return True
    if our_key in _MOE_SHARED:
        out[f"model.layers.{li}.mlp.shared_experts."
            f"{_MOE_SHARED[our_key]}.weight"] = np.ascontiguousarray(t.T)
        return True
    return False


def export_hf_state_dict(model: TransformerLM, params: dict) -> dict[str, np.ndarray]:
    """Inverse mapping (ours -> HF names); backs tests and adapter
    export tooling."""
    arch = model.arch
    out: dict[str, np.ndarray] = {}
    out["model.embed_tokens.weight"] = np.asarray(
        params["embed"][: arch.vocab_size])
    out["model.norm.weight"] = np.asarray(params["final_norm"])
    if "lm_head" in params:
        out["lm_head.weight"] = np.asarray(params["lm_head"][: arch.vocab_size])
    layer_map = dict(_LAYER_MAP)
    if arch.pre_post_norm:
        layer_map.update(_GEMMA_OVERRIDES)
    for g in model.groups:
        for our_key, stack in params[g.name].items():
            entry = layer_map.get(our_key)
            if entry is None:
                if g.moe:
                    for i in range(g.count):
                        _export_moe_tensor(out, g.start + i, our_key,
                                           np.asarray(stack[i]))
                continue
            suffix, transpose = entry
            for i in range(g.count):
                t = np.asarray(stack[i])
                # safetensors serializes raw buffers; a transposed VIEW
                # would be written with the wrong layout
                out[f"model.layers.{g.start + i}.{suffix}"] = (
                    np.ascontiguousarray(t.T) if transpose else t)
    return out
