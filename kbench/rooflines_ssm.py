"""What the state-space mixer's decode kernel has to move, computed from
shapes.  Kept with the benchmark, beside ``rooflines.py``, so that no PR
that claims a gain can change the yardstick.  The peaks these are set
against are in ``peaks.json``."""


def ssm_dims(config: dict) -> tuple:
    """(heads, head dim, groups, state dim) of the mixer."""
    heads = config["mamba_n_heads"]
    d_ssm = config.get("mamba_d_ssm") or \
        config.get("mamba_expand", 2) * config["hidden_size"]
    return (heads, config.get("mamba_d_head") or d_ssm // heads,
            config.get("mamba_n_groups", 1), config["mamba_d_state"])


STATE_BYTES = {"float32": 4, "bfloat16": 2}


def ssm_state_bytes_per_row(config: dict, state_bytes: int) -> float:
    """Bytes of recurrent state one sequence holds in one layer:
    ``heads * head_dim * d_state * state_bytes``; ``state_bytes`` is
    ``STATE_BYTES`` of the type the configuration's file states under
    ``assumed.state_dtype``."""
    heads, head_dim, _, d_state = ssm_dims(config)
    return float(heads * head_dim * d_state * state_bytes)


def ssm_decode_update_bytes(config: dict, rows: float,
                            state_bytes: int) -> float:
    """Least bytes the selective state update of ``rows`` decoded tokens
    must move in one layer: each row's state read once and written once,
    and the row's operands in float32 (x in and y out, ``heads *
    head_dim`` each; B and C, ``groups * d_state`` each; the step and
    the decay, one value a head each).  The operands are 0.9% of a
    bfloat16 state at Falcon-H1-34B's sizes."""
    heads, head_dim, groups, d_state = ssm_dims(config)
    operands = 4.0 * (2 * heads * head_dim + 2 * groups * d_state + 2 * heads)
    return rows * (2.0 * ssm_state_bytes_per_row(config, state_bytes)
                   + operands)
