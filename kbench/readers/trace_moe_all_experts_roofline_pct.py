"""The expert kernel's share of its roofline in decode where the chip
holds every expert of a layer, from the device trace and the program's
own counters.

What ``trace_moe_experts_roofline_pct`` reads for a share of a layer,
with the experts a layer taken from ``num_experts`` (that reader counts
``n_routed_experts``, the held share's key): the grouped matmul
(``gmm`` in the trace) is bound by memory in decode; each expert that
got a routed pair has its three matrices read once
(``rooflines_lfm2.moe_decode_bytes``, which is the accepted
``rooflines_moe`` function); an expert that got none is skipped and is
not billed.  The counters cover the whole window and the kernel's time
the traced span, so the bytes are scaled by the share of the window's
decode steps that ran inside the span.  Share = bytes / bandwidth over
the kernel's summed device time.  Never clipped.

A configuration with no ``num_experts``, a trace with no such op or a
program with no such counters gives the reader nothing.
"""

import re

import rooflines_lfm2

CALLS = "kaito:engine_moe_expert_calls_total"
TOUCHED = "kaito:engine_moe_experts_touched_total"
PAIRS = "kaito:engine_moe_pairs_held_total"


def read(ctx, *, pattern):
    t = ctx["trace"]
    config = ctx["config"]["config"]
    if not t or t["devices"] == 0 or not rooflines_lfm2.is_lfm2_moe(config) \
            or CALLS not in ctx["after"]:
        return None

    def delta(n):
        return ctx["after"].get(n, 0.0) - ctx["before"].get(n, 0.0)

    rx = re.compile(pattern)
    seconds = sum(s for n, s in t["ops"].items() if rx.search(n))
    kernel_calls = sum(c for n, c in t["op_counts"].items() if rx.search(n))
    layer_steps = delta(CALLS) / config["num_experts"]   # (layer, step) pairs
    if seconds <= 0 or kernel_calls <= 0 or layer_steps <= 0:
        return None
    # three kernel calls (gate, up, down) a layer and step
    in_span = (kernel_calls / 3.0) / layer_steps
    need = rooflines_lfm2.moe_decode_bytes(
        config, delta(TOUCHED) * in_span, delta(PAIRS) * in_span)
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / seconds
