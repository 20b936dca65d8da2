"""What a kernel's call has to move, computed from shapes.  Kept with
the benchmark so that no PR that claims a gain can change the
yardstick.  The peaks these are set against are in ``peaks.json``."""


def kv_bytes_per_token_per_layer(config: dict, dtype_bytes: int = 2,
                                 tensor_parallel: int = 1) -> float:
    """Bytes of keys and values one cached token holds in one layer on
    one device: ``2 * kv_heads * head_dim * dtype_bytes / tp``."""
    heads = config["num_attention_heads"]
    kv_heads = config.get("num_key_value_heads") or heads
    head_dim = config.get("head_dim") or config["hidden_size"] // heads
    return 2.0 * kv_heads * head_dim * dtype_bytes / tensor_parallel


def decode_attention_bytes(config: dict, context_tokens: float,
                           dtype_bytes: int = 2,
                           tensor_parallel: int = 1) -> float:
    """Least bytes one decode-attention call (one layer, one step, the
    whole batch) must read: every cached key and value of the live
    sequences, once.  Queries, outputs and page tables are left out
    (under 1% at these context lengths), so the share reads a little low."""
    return context_tokens * kv_bytes_per_token_per_layer(
        config, dtype_bytes, tensor_parallel)
