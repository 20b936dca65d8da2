"""What decode attention has to read in a model whose layers are of two
attention kinds, computed from shapes.  Kept with the benchmark, beside
``rooflines.py`` (which reckons one KV geometry and the whole context
for every layer and stays as it is).  The peaks these are set against
are in ``peaks.json``."""


def kinds(config: dict) -> dict:
    """{"full": (layers, bytes a cached token), "window": (...)} from
    the configuration's ``hybrid_layer_pattern`` (0 full, 1 window) and
    the two kinds' head counts and sizes, in the logical shapes: keys
    of ``head_dim`` and values of ``v_head_dim`` in bfloat16."""
    pattern = config["hybrid_layer_pattern"]

    def per_token(pre):
        return 2.0 * config[pre + "num_key_value_heads"] * (
            config[pre + "head_dim"] + config[pre + "v_head_dim"])

    return {"full": (sum(1 for x in pattern if not x), per_token("")),
            "window": (sum(1 for x in pattern if x), per_token("swa_"))}


def decode_attention_bytes_by_kind(config: dict, contexts: list) -> float:
    """Least bytes ONE decode step's attention must read over all its
    layers, for live rows of the given context lengths: a full layer
    reads a row's whole context, a window layer the last
    ``sliding_window`` positions of it.  Whole pages are not billed
    (the kernel copies whole pages, so the share reads low, never
    high), nor the 64 zero lanes a 192-wide key is stored with."""
    k = kinds(config)
    window = config["sliding_window"]
    full = sum(contexts)
    win = sum(min(c, window) for c in contexts)
    return k["full"][0] * k["full"][1] * full \
        + k["window"][0] * k["window"][1] * win


def attention_layers(config: dict) -> int:
    return len(config["hybrid_layer_pattern"])
