"""Decode attention's share of its roofline, from the device trace.

The kernel is bound by memory: the least time a call can take is the
bytes of the keys and values it must read (``rooflines.py``) over the
published HBM bandwidth.  The live context is what the client saw: at
any instant the requests between their first and last chunk hold their
prompt plus the tokens delivered so far; its mean over the traced span
stands for every call in it.  Share = calls x bytes / bandwidth over
the kernel's summed device time.
"""

import re

import rooflines


def live_context_tokens(requests: list, lo: float, hi: float,
                        points: int = 200) -> float:
    total = 0.0
    for k in range(points):
        t = lo + (hi - lo) * (k + 0.5) / points
        for r in requests:
            c = r["chunk_s"]
            if c and c[0] <= t <= c[-1]:
                total += r["prompt_tokens"] + sum(1 for x in c if x <= t)
    return total / points


def read(ctx, *, pattern):
    t = ctx["trace"]
    if not t or t["devices"] == 0 or len(ctx.get("traced_s", [])) != 2:
        return None
    rx = re.compile(pattern)
    seconds = sum(s for n, s in t["ops"].items() if rx.search(n))
    calls = sum(c for n, c in t["op_counts"].items() if rx.search(n))
    if seconds <= 0 or calls <= 0:
        return None
    tokens = live_context_tokens(ctx["requests"], *ctx["traced_s"])
    tp = int(ctx["config"]["server"].get("config_file", {}).get(
        "tensor_parallel_size", 1))
    need = calls * rooflines.decode_attention_bytes(
        ctx["config"]["config"], tokens, tensor_parallel=tp)
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / seconds
