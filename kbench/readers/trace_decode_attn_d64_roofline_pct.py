"""Decode attention's share of its roofline in a model only some of
whose layers attend, at heads of 64, from the device trace.

The kernel is bound by memory: an attention layer reads a row's whole
context at 2,048 B a token (``rooflines_lfm2.decode_attention_bytes``:
8 KV heads of 64, keys and values, bfloat16, whatever lanes they are
stored at); the short-convolution layers read no page.  The live rows
and their contexts are what the client saw: at any instant the requests
between their first and last chunk hold their prompt plus the tokens
delivered so far; the mean over the traced span of one step's bytes
stands for every step in it.  A step calls the kernel once an attention
layer, so steps = calls / attention layers.  Share = steps x bytes a
step / bandwidth over the kernel's summed device time.  Never clipped.

A configuration with no ``layer_types`` and ``num_experts`` or a trace
with no such op gives the reader nothing.
"""

import re

import rooflines_lfm2


def mean_step_bytes(config: dict, requests: list, lo: float, hi: float,
                    points: int = 200) -> float:
    total = 0.0
    for k in range(points):
        t = lo + (hi - lo) * (k + 0.5) / points
        contexts = []
        for r in requests:
            c = r["chunk_s"]
            if c and c[0] <= t <= c[-1]:
                contexts.append(r["prompt_tokens"]
                                + sum(1 for x in c if x <= t))
        total += rooflines_lfm2.decode_attention_bytes(config, contexts)
    return total / points


def read(ctx, *, pattern):
    t = ctx["trace"]
    config = ctx["config"]["config"]
    if not t or t["devices"] == 0 or len(ctx.get("traced_s", [])) != 2 \
            or not rooflines_lfm2.is_lfm2_moe(config):
        return None
    rx = re.compile(pattern)
    seconds = sum(s for n, s in t["ops"].items() if rx.search(n))
    calls = sum(c for n, c in t["op_counts"].items() if rx.search(n))
    if seconds <= 0 or calls <= 0:
        return None
    steps = calls / rooflines_lfm2.attention_layers(config)
    need = steps * mean_step_bytes(config, ctx["requests"], *ctx["traced_s"])
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / seconds
