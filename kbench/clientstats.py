"""From what the load generator saw to the end-to-end numbers.

A time to first token runs from when the request was *due* (open loop)
to the arrival of its first chunk of text; an inter-token gap is the
time between two successive chunks of one request; the output rate is
the chunks that arrived inside the window over the window.  All
requests count; a tail is reported only where at least ten samples lie
beyond it.
"""

import math


def percentile(values: list, q: float):
    """Linear-interpolated percentile, or None when fewer than ten
    samples lie beyond it (a maximum of a handful is not a tail)."""
    n = len(values)
    if n == 0 or (q > 50 and n * (100 - q) / 100.0 < 10):
        return None
    s = sorted(values)
    pos = (n - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def failures(requests: list) -> list:
    """Why each failed request failed: non-200 (a shed is 429), a
    timeout or broken stream, fewer text chunks than ``max_tokens``, a
    chunk that was not exactly one token, or no finish chunk."""
    out = []
    for r in requests:
        if r["status"] != 200:
            out.append(f"request {r['idx']}: HTTP {r['status']} {r['error']}")
        elif r["error"]:
            out.append(f"request {r['idx']}: {r['error']}")
        elif len(r["chunk_s"]) != r["max_tokens"] or r["words"] != r["max_tokens"]:
            out.append(f"request {r['idx']}: {len(r['chunk_s'])} chunks, "
                       f"{r['words']} words for max_tokens {r['max_tokens']}")
        elif not (r["finish"] and r["done"]):
            out.append(f"request {r['idx']}: no finish chunk")
    return out


def reduce(result: dict) -> dict:
    reqs, seconds = result["requests"], result["seconds"]
    ttft = [(r["chunk_s"][0] - r["due_s"]) * 1e3 for r in reqs if r["chunk_s"]]
    gaps = [(b - a) * 1e3 for r in reqs
            for a, b in zip(r["chunk_s"], r["chunk_s"][1:])]
    late = [(r["sent_s"] - r["due_s"]) * 1e3 for r in reqs]
    in_window = sum(1 for r in reqs for t in r["chunk_s"] if t < seconds)
    bad = failures(reqs)
    return {
        "attempted": len(reqs), "failed": len(bad), "failures": bad[:10],
        "tokens": sum(len(r["chunk_s"]) for r in reqs),
        "samples": {"ttft": len(ttft), "itl": len(gaps)},
        "ttft_mean_ms": sum(ttft) / len(ttft) if ttft else None,
        "ttft_p50_ms": percentile(ttft, 50),
        "ttft_p95_ms": percentile(ttft, 95),
        "itl_p95_ms": percentile(gaps, 95),
        "itl_p50_ms": percentile(gaps, 50),
        "out_tok_s": in_window / seconds,
        # the 95th percentile, or the maximum where that is no tail yet
        "gen_late_p95_ms": percentile(late, 95) or max(late, default=0.0),
        "last_done_s": max((r["chunk_s"][-1] for r in reqs if r["chunk_s"]),
                           default=0.0),
    }
