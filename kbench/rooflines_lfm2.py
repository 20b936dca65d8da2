"""What the kernels of an LFM2-MoE decoder have to move and compute,
from shapes: a few GQA layers of 64-wide heads among short-convolution
layers that cache no page, and an expert layer the chip holds whole.
Kept with the benchmark, beside ``rooflines.py`` and the others (which
stay as they are).  The peaks these are set against are in
``peaks.json``."""

import rooflines_moe


def is_lfm2_moe(config: dict) -> bool:
    return "layer_types" in config and "num_experts" in config


def attention_layers(config: dict) -> int:
    """Layers that attend (and cache pages); the rest convolve."""
    return sum(1 for t in config["layer_types"] if t == "full_attention")


def expert_layers(config: dict) -> int:
    """Layers whose FFN is the expert layer: all but the first
    ``num_dense_layers``."""
    return config["num_hidden_layers"] - config.get("num_dense_layers", 0)


def decode_attention_bytes(config: dict, contexts: list,
                           dtype_bytes: int = 2) -> float:
    """Least bytes ONE decode step's attention must read over all its
    attention layers, for live rows of the given context lengths: keys
    and values of ``num_key_value_heads`` heads of ``hidden_size /
    num_attention_heads`` (8 x 64: 2,048 B a token and layer in
    bfloat16), whatever lanes they are stored at.  Whole pages are not
    billed (the kernel copies whole pages: the share reads low, never
    high)."""
    head = config.get("head_dim") or \
        config["hidden_size"] // config["num_attention_heads"]
    per_token = 2.0 * config["num_key_value_heads"] * head * dtype_bytes
    return attention_layers(config) * per_token * sum(contexts)


def moe_decode_bytes(config: dict, touched: float, pairs: float) -> float:
    """``rooflines_moe.moe_decode_bytes`` (the accepted function) at
    this family's keys: each touched expert's three matrices read once
    and each pair's activations."""
    return rooflines_moe.moe_decode_bytes(config, touched, pairs)


def moe_prefill_ops(config: dict, tokens: float) -> float:
    """Operations the expert layers' grouped matmuls need for ``tokens``
    prompt tokens: every token's ``num_experts_per_tok`` pairs, each
    through three matrices of ``hidden_size x moe_intermediate_size``
    (2 operations a weight), in every expert layer.  Exact where every
    expert is held; the rows a bucket or a tile pads are not billed."""
    return tokens * config["num_experts_per_tok"] * expert_layers(config) \
        * 6.0 * config["hidden_size"] * config["moe_intermediate_size"]
