"""The latent decode kernel's share of its roofline, from the device
trace.

The live rows and their contexts are what the client saw: at any
instant the requests between their first and last chunk hold their
prompt plus the tokens delivered so far; the mean over the traced span
of one step's bytes and operations (``rooflines_mla.py``) stands for
every step in it.  A step calls the kernel once a layer, so steps =
calls / layers.  The least time a step's attention can take is the
larger of its bytes over the HBM bandwidth and its operations over the
bf16 peak (32 heads against one stream are 60 operations a byte, under
the v5e's ridge of 240: memory decides).  Share = steps x that time
over the kernel's summed device time.  Never clipped.

A configuration without ``kv_lora_rank`` gives the reader nothing.
"""

import re

import rooflines_mla


def mean_step_need_s(config: dict, requests: list, lo: float, hi: float,
                     peaks: dict, points: int = 200) -> float:
    total = 0.0
    for k in range(points):
        t = lo + (hi - lo) * (k + 0.5) / points
        contexts = []
        for r in requests:
            c = r["chunk_s"]
            if c and c[0] <= t <= c[-1]:
                contexts.append(r["prompt_tokens"]
                                + sum(1 for x in c if x <= t))
        total += max(
            rooflines_mla.decode_bytes_per_step(config, contexts)
            / peaks["hbm_bytes_per_s"],
            rooflines_mla.decode_ops_per_step(config, contexts)
            / peaks["bf16_flops_per_s"])
    return total / points


def read(ctx, *, pattern):
    t = ctx["trace"]
    config = ctx["config"]["config"]
    if not t or t["devices"] == 0 or len(ctx.get("traced_s", [])) != 2 \
            or not rooflines_mla.is_latent(config):
        return None
    rx = re.compile(pattern)
    seconds = sum(s for n, s in t["ops"].items() if rx.search(n))
    calls = sum(c for n, c in t["op_counts"].items() if rx.search(n))
    if seconds <= 0 or calls <= 0:
        return None
    steps = calls / rooflines_mla.layers(config)
    need = steps * mean_step_need_s(config, ctx["requests"],
                                    *ctx["traced_s"], ctx["peaks"])
    return 100.0 * need / seconds
