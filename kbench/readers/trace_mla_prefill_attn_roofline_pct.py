"""Flash prefill's share of its roofline on a latent-attention model's
expanded heads, from the device trace.

The kernel is bound by compute: the least time a fresh prompt's
attention can take is its operations (``rooflines_mla.prefill_ops``:
the causal pairs alone, at the logical widths) over the bf16 peak.
The prompts billed are those whose first chunk reached the client
inside the traced span: a prompt's first token leaves with its prefill.
Share = their operations over the peak, over the kernel's summed device
time in the prefill programs.  Never clipped.

A configuration without ``kv_lora_rank`` gives the reader nothing.
"""

import re

import rooflines_mla


def read(ctx, *, pattern):
    t = ctx["trace"]
    config = ctx["config"]["config"]
    if not t or t["devices"] == 0 or len(ctx.get("traced_s", [])) != 2 \
            or not rooflines_mla.is_latent(config):
        return None
    rx = re.compile(pattern)
    seconds = sum(s for n, s in t["ops"].items() if rx.search(n))
    if seconds <= 0:
        return None
    lo, hi = ctx["traced_s"]
    ops = sum(rooflines_mla.prefill_ops(config, r["prompt_tokens"])
              for r in ctx["requests"]
              if r["chunk_s"] and lo <= r["chunk_s"][0] <= hi)
    if ops <= 0:
        return None
    return 100.0 * (ops / ctx["peaks"]["bf16_flops_per_s"]) / seconds
