"""The selective-state-update kernel's share of its roofline, from the
device trace.

The kernel is bound by memory: a decoded token reads and writes its
row's recurrent state once in every layer (``rooflines_ssm.py``), in the
type the configuration's file states (``assumed.state_dtype``), and a
row that decodes nothing moves nothing.  The rows are counted by the
client: every chunk of text it received inside the traced span that a
decode step made, which is every chunk of a request but its first (the
prefill's).  Share = rows x layers x bytes a row / bandwidth over the
kernel's summed device time.  Never clipped: a reading above 100 means
the bytes are counted too high or the time leaves out part of the work.

A program with no such kernel (another architecture, or a tree from
before the kernel) has no such event: the reader returns None and the
metric is left out of the line.
"""

import re

import rooflines_ssm


def decoded_tokens(requests: list, lo: float, hi: float) -> int:
    """Chunks after a request's first that arrived in ``[lo, hi]``."""
    return sum(1 for r in requests for t in r["chunk_s"][1:] if lo <= t <= hi)


def read(ctx, *, pattern):
    t = ctx["trace"]
    config = ctx["config"]["config"]
    state_bytes = rooflines_ssm.STATE_BYTES.get(
        ctx["config"].get("assumed", {}).get("state_dtype"))
    if not t or t["devices"] == 0 or len(ctx.get("traced_s", [])) != 2 \
            or "mamba_d_state" not in config or not state_bytes:
        return None
    rx = re.compile(pattern)
    seconds = sum(s for n, s in t["ops"].items() if rx.search(n))
    if seconds <= 0:
        return None
    rows = decoded_tokens(ctx["requests"], *ctx["traced_s"])
    need = config["num_hidden_layers"] * rooflines_ssm.ssm_decode_update_bytes(
        config, rows, state_bytes)
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / seconds
