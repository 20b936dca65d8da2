"""The expert kernel's share of its roofline, from the device trace and
the program's own counters.

The kernel (the grouped matmul, ``gmm`` in the trace) is bound by
memory in decode: each held expert that got a routed pair has its three
matrices read once (``rooflines_moe.py``); an expert that got none is
skipped and is not billed.  How many were touched is counted on the
device and read back with the window's tokens
(``kaito:engine_moe_experts_touched_total``), and so are the pairs that
landed here.  The counters cover the whole window and the kernel's time
the traced span, so the bytes are scaled by the share of the window's
decode steps that ran inside the span (the kernel's calls in the trace
over ``kaito:engine_moe_expert_calls_total`` / held experts, three
calls a layer and step).  Share = bytes / bandwidth over the kernel's
summed device time.  Never clipped.

A program with no such kernel or no such counters (another
architecture, or a tree from before them) gives the reader nothing: it
returns None and the metric is left out of the line.
"""

import re

import rooflines_moe

CALLS = "kaito:engine_moe_expert_calls_total"
TOUCHED = "kaito:engine_moe_experts_touched_total"
PAIRS = "kaito:engine_moe_pairs_held_total"


def read(ctx, *, pattern):
    t = ctx["trace"]
    config = ctx["config"]["config"]
    if not t or t["devices"] == 0 or "moe_intermediate_size" not in config \
            or CALLS not in ctx["after"]:
        return None

    def delta(n):
        return ctx["after"].get(n, 0.0) - ctx["before"].get(n, 0.0)

    rx = re.compile(pattern)
    seconds = sum(s for n, s in t["ops"].items() if rx.search(n))
    kernel_calls = sum(c for n, c in t["op_counts"].items() if rx.search(n))
    held = config["n_routed_experts"]
    layer_steps = delta(CALLS) / held            # (layer, step) pairs
    if seconds <= 0 or kernel_calls <= 0 or layer_steps <= 0:
        return None
    # three kernel calls (gate, up, down) a layer and step
    in_span = (kernel_calls / 3.0) / layer_steps
    need = rooflines_moe.moe_decode_bytes(
        config, delta(TOUCHED) * in_span, delta(PAIRS) * in_span)
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / seconds
