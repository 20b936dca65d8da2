"""The expert kernel's share of its roofline in prefill where the chip
holds every expert of a layer, from the device trace.

At a prompt's widths the grouped matmul (``gmm`` in the trace) is bound
by compute: the least time the expert layers of a fresh prompt can take
is their operations (``rooflines_lfm2.moe_prefill_ops``: every token's
pairs through three matrices, exact since every pair is held) over the
bf16 peak.  The prompts billed are those whose first chunk reached the
client inside the traced span: a prompt's first token leaves with its
prefill.  Share = their operations over the peak, over the kernel's
summed device time in the prefill programs.  Rows padded to a bucket or
a tile are not billed, so the share reads low, never high.  Never
clipped.

A configuration with no ``num_experts`` or a trace with no such op
gives the reader nothing.
"""

import re

import rooflines_lfm2


def read(ctx, *, pattern):
    t = ctx["trace"]
    config = ctx["config"]["config"]
    if not t or t["devices"] == 0 or len(ctx.get("traced_s", [])) != 2 \
            or not rooflines_lfm2.is_lfm2_moe(config):
        return None
    rx = re.compile(pattern)
    seconds = sum(s for n, s in t["ops"].items() if rx.search(n))
    if seconds <= 0:
        return None
    lo, hi = ctx["traced_s"]
    tokens = sum(r["prompt_tokens"] for r in ctx["requests"]
                 if r["chunk_s"] and lo <= r["chunk_s"][0] <= hi)
    if tokens <= 0:
        return None
    ops = rooflines_lfm2.moe_prefill_ops(config, tokens)
    return 100.0 * (ops / ctx["peaks"]["bf16_flops_per_s"]) / seconds
