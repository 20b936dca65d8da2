"""What the gated delta rule's decode kernel has to move, computed from
shapes: a linear-attention layer (``olmo_hybrid``'s) keeps a ``key dim x
value dim`` matrix a head and a sequence, and a decoded token reads it
back and rewrites it.  Kept with the benchmark, beside ``rooflines.py``
and the others (which stay as they are).  The peaks these are set
against are in ``peaks.json``."""

STATE_BYTES = {"float32": 4, "bfloat16": 2}


def is_delta_rule(config: dict) -> bool:
    return "linear_num_value_heads" in config and "layer_types" in config


def gdn_dims(config: dict) -> tuple:
    """(heads, key dim, value dim) of a delta-rule layer."""
    return (config["linear_num_value_heads"], config["linear_key_head_dim"],
            config["linear_value_head_dim"])


def linear_layers(config: dict) -> int:
    """Layers that keep a matrix state (and cache no page)."""
    return sum(1 for t in config["layer_types"] if t == "linear_attention")


def gdn_state_bytes_per_row(config: dict, state_bytes: int) -> float:
    """Bytes of matrix state one sequence holds in one layer: ``heads *
    key dim * value dim * state_bytes`` at the LOGICAL lanes, whatever is
    stored; ``state_bytes`` is ``STATE_BYTES`` of the type the
    configuration's file states under ``assumed.state_dtype``."""
    heads, dk, dv = gdn_dims(config)
    return float(heads * dk * dv * state_bytes)


def gdn_decode_update_bytes(config: dict, rows: float,
                            state_bytes: int) -> float:
    """Least bytes the state update of ``rows`` decoded tokens must
    move in one layer: each row's state read once and written once, and
    the row's operands in float32 (q and k, ``heads * key dim`` each; v
    in and o out, ``heads * value dim`` each; the decay and beta, one
    value a head each).  The operands are 2.1% of a bfloat16 state at
    Olmo-Hybrid-7B's sizes."""
    heads, dk, dv = gdn_dims(config)
    operands = 4.0 * (2 * heads * dk + 2 * heads * dv + 2 * heads)
    return rows * (2.0 * gdn_state_bytes_per_row(config, state_bytes)
                   + operands)
