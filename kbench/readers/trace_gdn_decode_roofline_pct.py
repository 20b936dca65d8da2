"""The gated delta rule's state-update kernel's share of its roofline,
from the device trace.

The kernel is bound by memory: a decoded token reads and writes its
row's matrix state once in every linear-attention layer
(``rooflines_gdn.py``), in the type the configuration's file states
(``assumed.state_dtype``), at the logical lanes whatever is stored, and
a row that decodes nothing moves nothing.  The rows are counted by the
client, as ``trace_ssm_decode_roofline_pct`` counts them: every chunk
of text it received inside the traced span that a decode step made,
which is every chunk of a request but its first (the prefill's).  Share
= rows x linear layers x bytes a row / bandwidth over the kernel's
summed device time.  Never clipped: a reading above 100 means the bytes
are counted too high or the time leaves out part of the work.

A configuration with no ``linear_num_value_heads`` or a trace with no
such op (another architecture, or a tree from before the kernel) gives
the reader nothing: it returns None and the metric is left out of the
line.
"""

import re

import rooflines_gdn
from readers.trace_ssm_decode_roofline_pct import decoded_tokens


def read(ctx, *, pattern):
    t = ctx["trace"]
    config = ctx["config"]["config"]
    state_bytes = rooflines_gdn.STATE_BYTES.get(
        ctx["config"].get("assumed", {}).get("state_dtype", "")
        .split(":")[0])
    if not t or t["devices"] == 0 or len(ctx.get("traced_s", [])) != 2 \
            or not rooflines_gdn.is_delta_rule(config) or not state_bytes:
        return None
    rx = re.compile(pattern)
    seconds = sum(s for n, s in t["ops"].items() if rx.search(n))
    if seconds <= 0:
        return None
    rows = decoded_tokens(ctx["requests"], *ctx["traced_s"])
    need = rooflines_gdn.linear_layers(config) \
        * rooflines_gdn.gdn_decode_update_bytes(config, rows, state_bytes)
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / seconds
