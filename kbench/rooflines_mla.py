"""What latent attention has to read and compute, from shapes.  Kept
with the benchmark, beside ``rooflines.py`` (which reckons keys and
values by KV heads and stays as it is).  The peaks these are set
against are in ``peaks.json``.

A latent-attention layer caches one stream a token: the normed latent
and the one rotated key part all heads share, ``kv_lora_rank +
qk_rope_head_dim`` numbers (512 + 64).  Decode attends it in the
absorbed form (every head against the same stream, the latent's lanes
also the values); a fresh prompt is attended on the expanded heads
(keys of ``qk_nope_head_dim + qk_rope_head_dim``, values of
``v_head_dim``)."""


def is_latent(config: dict) -> bool:
    return bool(config.get("kv_lora_rank"))


def layers(config: dict) -> int:
    return int(config["num_hidden_layers"])


def latent_bytes_per_token_per_layer(config: dict,
                                     dtype_bytes: int = 2) -> float:
    """Bytes one cached token holds in one layer, in the logical shape:
    not the lanes it is stored at (640 for 576), so a share reads low,
    never high."""
    return float(config["kv_lora_rank"] + config["qk_rope_head_dim"]) \
        * dtype_bytes


def decode_bytes_per_step(config: dict, contexts: list) -> float:
    """Least bytes ONE decode step's attention must read over all its
    layers, for live rows of the given context lengths: every live
    token's latent once a layer.  Whole pages are not billed, nor the
    queries, outputs and page tables."""
    return layers(config) * latent_bytes_per_token_per_layer(config) \
        * float(sum(contexts))


def decode_ops_per_step(config: dict, contexts: list) -> float:
    """Operations of ONE decode step's attention over all its layers in
    the absorbed form: a head's score against a token is ``latent +
    rope`` multiply-adds and its value ``latent`` more."""
    dl, dr = config["kv_lora_rank"], config["qk_rope_head_dim"]
    per_token = config["num_attention_heads"] * 2.0 * ((dl + dr) + dl)
    return layers(config) * per_token * float(sum(contexts))


def prefill_ops(config: dict, prompt_tokens: int) -> float:
    """Operations of ONE fresh prompt's attention over all its layers on
    the expanded heads: ``n(n+1)/2`` causal (query, key) pairs a head,
    each ``2 x (key width + value width)``.  The stored keys' zero
    lanes, the bucket's padding and the masked half of a diagonal block
    are not billed."""
    n = float(prompt_tokens)
    width = config["qk_nope_head_dim"] + config["qk_rope_head_dim"] \
        + config["v_head_dim"]
    return layers(config) * config["num_attention_heads"] \
        * n * (n + 1.0) / 2.0 * 2.0 * width
