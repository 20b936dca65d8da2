"""What an expert layer's grouped-matmul kernel has to move, computed
from shapes.  Kept with the benchmark, beside ``rooflines.py``, so that
no PR that claims a gain can change the yardstick.  The peaks these are
set against are in ``peaks.json``."""


def expert_matrix_bytes(config: dict, dtype_bytes: int = 2) -> float:
    """Bytes of ONE held expert's three matrices (gate, up, down):
    ``3 * hidden_size * moe_intermediate_size * dtype_bytes``."""
    return 3.0 * config["hidden_size"] * config["moe_intermediate_size"] \
        * dtype_bytes


def expert_layers(config: dict) -> int:
    """How many of the configuration's layers are expert layers."""
    return sum(1 for x in config.get("moe_layer_freq", []) if x)


def moe_decode_bytes(config: dict, touched: float, pairs: float,
                     dtype_bytes: int = 2) -> float:
    """Least bytes the expert kernel's calls must move for ``touched``
    (held expert, layer, step) triples that got a routed pair and
    ``pairs`` routed (token, expert) pairs that landed here: each
    touched expert's three matrices read once, and each pair's
    activations (a row of ``hidden_size`` in and out, and a row of
    ``moe_intermediate_size`` out of gate and up and into down; the
    outputs in float32).  An expert that got no pair is skipped by the
    kernel and is NOT billed.  The activations are under 0.1% of the
    matrices at MiMo-V2.5's sizes."""
    h, i = config["hidden_size"], config["moe_intermediate_size"]
    per_pair = dtype_bytes * (2 * h + i) + 4.0 * (2 * i + h)
    return touched * expert_matrix_bytes(config, dtype_bytes) \
        + pairs * per_pair
