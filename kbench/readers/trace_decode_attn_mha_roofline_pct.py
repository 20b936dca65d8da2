"""Decode attention's share of its roofline in a model only some of
whose layers attend, one query head a KV head (MHA), from the device
trace.

The kernel is bound by memory: an attention layer reads a row's whole
context at 15,360 B a token at Olmo-Hybrid-7B's sizes
(``rooflines_lfm2.decode_attention_bytes``, the accepted function: it
reads ``layer_types``, ``num_key_value_heads`` and the head's width: 30
KV heads of 128, keys and values, bfloat16); the linear-attention layers
read no page.  The live rows and their contexts are what the client
saw: at any instant the requests between their first and last chunk
hold their prompt plus the tokens delivered so far; the mean over the
traced span of one step's bytes stands for every step in it
(``trace_decode_attn_d64_roofline_pct``'s ``mean_step_bytes``, imported;
that reader returns nothing where the configuration has no
``num_experts``).  A
step calls the kernel once an attention layer, so steps = calls /
attention layers.  Share = steps x bytes a step / bandwidth over the
kernel's summed device time.  Never clipped.

A configuration with no ``linear_num_value_heads`` or a trace with no
such op gives the reader nothing.
"""

import re

import rooflines_gdn
import rooflines_lfm2
# (the accepted reader's reckoning of a step's mean bytes over the span,
# through ``rooflines_lfm2.decode_attention_bytes``)
from readers.trace_decode_attn_d64_roofline_pct import mean_step_bytes


def read(ctx, *, pattern):
    t = ctx["trace"]
    config = ctx["config"]["config"]
    if not t or t["devices"] == 0 or len(ctx.get("traced_s", [])) != 2 \
            or not rooflines_gdn.is_delta_rule(config):
        return None
    rx = re.compile(pattern)
    seconds = sum(s for n, s in t["ops"].items() if rx.search(n))
    calls = sum(c for n, c in t["op_counts"].items() if rx.search(n))
    if seconds <= 0 or calls <= 0:
        return None
    steps = calls / rooflines_lfm2.attention_layers(config)
    need = steps * mean_step_bytes(config, ctx["requests"], *ctx["traced_s"])
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / seconds
