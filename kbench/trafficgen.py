"""The one general traffic generator: a mix's data file in, a list of
requests out.

A mix fixes a *set* of work — prompt lengths, output lengths, gaps
between arrivals, which requests share a prefix — drawn from the mix's
own ``mix_seed``, so every run seed gets the same set.  The run seed
only chooses the order (a permutation of the requests and of the gaps)
and the token contents.  Runs with different seeds therefore differ in
order and not in the amount of work: an open loop sends the whole set
inside the window, a closed loop cycles through a small set.

Token ids are drawn uniformly from ``[0, vocab)``; a prompt is sent as
the words ``w<id>`` of the benchmark's word-level tokenizer, one word
per token, so its length in tokens is exact.
"""

import math
import random


def _draw_len(rng: random.Random, spec: dict) -> int:
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        return rng.randint(lo, hi)
    if spec["dist"] == "lognormal":
        x = rng.lognormvariate(math.log(spec["median"]), spec["sigma"])
        return max(lo, min(hi, int(round(x))))
    if spec["dist"] == "fixed":
        return int(spec["value"])
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def _zipf_pick(rng: random.Random, n: int, s: float) -> int:
    weights = [1.0 / (k + 1) ** s for k in range(n)]
    return rng.choices(range(n), weights)[0]


def words(ids) -> str:
    return " ".join(f"w{i}" for i in ids)


def ids_of(text: str) -> list:
    """The ids back from words; raises on anything that is not a word."""
    return [int(w[1:]) for w in text.split()]


def request_set(mix: dict, n: int) -> list:
    """``n`` request shapes from the mix seed: each a dict with
    ``group`` (requests of one group share ``shared`` leading tokens),
    ``shared``, ``unique`` and ``max_tokens``."""
    rng = random.Random(mix["mix_seed"])
    p = mix["prompt"]
    repeat = int(mix.get("repeat", {}).get("times", 1))
    out = []
    if "shared_prefix" in p:        # a few system prompts, Zipf-picked
        sp = p["shared_prefix"]
        for _ in range(n):
            out.append({"group": _zipf_pick(rng, sp["count"], sp["zipf"]),
                        "shared": int(sp["tokens"]),
                        "unique": _draw_len(rng, p["unique"]),
                        "max_tokens": _draw_len(rng, mix["output"])})
        return out
    g = 0
    while len(out) < n:             # documents, each asked `repeat` times
        doc = _draw_len(rng, p["unique"])
        for _ in range(repeat):
            if repeat > 1:
                q = int(mix["repeat"]["question_tokens"])
                shape = {"group": g, "shared": doc - q, "unique": q}
            else:
                shape = {"group": g, "shared": 0, "unique": doc}
            shape["max_tokens"] = _draw_len(rng, mix["output"])
            out.append(shape)
        g += 1
    return out[:n]


def arrival_gaps(mix: dict, n: int) -> list:
    """``n`` gaps between arrivals, in units of the mean gap, from the
    mix seed; normalised to sum to ``n`` so the schedule has one length."""
    rng = random.Random(mix["mix_seed"] + 1)
    kind = mix.get("arrivals", "poisson")
    if kind == "poisson":
        gaps = [rng.expovariate(1.0) for _ in range(n)]
    elif kind == "uniform":
        gaps = [1.0] * n
    else:
        raise ValueError(f"unknown arrival process {kind!r}")
    scale = n / sum(gaps)
    return [g * scale for g in gaps]


def schedule(mix: dict, *, seed: int, vocab: int, seconds: float,
             rate_rps: float = 0.0, count: int = 0) -> list:
    """The requests of one run, in order: ``due_s`` (open loop: seconds
    from the start of the window; closed loop: 0.0 for all, they are
    sent as clients come free), ``prompt_ids`` and ``max_tokens``.

    Open loop: ``round(rate_rps * seconds)`` requests, all due inside
    the window.  Closed loop: ``count`` requests, more than the window
    can finish (the mix's ``distinct`` shapes over and over); the load
    generator stops sending at its end.
    """
    # the run seed permutes the requests and the gaps, and draws the tokens
    order = random.Random(seed)
    if mix["loop"] == "closed":
        # a window starts only the first part of the list, and which part
        # would be the seed's choice: so the set is ``distinct`` shapes,
        # repeated, each copy permuted, and every stretch of the list
        # holds the same work whatever the seed
        n, shapes = count, []
        block = request_set(mix, int(mix["distinct"]))
        while len(shapes) < n:
            shapes += order.sample(block, len(block))
        del shapes[n:]
    else:
        n = max(1, round(rate_rps * seconds))
        shapes = request_set(mix, n)
        order.shuffle(shapes)
    tok = random.Random(seed ^ 0x5EED)
    shared = {}
    reqs = []
    for s in shapes:
        if s["shared"] and s["group"] not in shared:
            # requests of one group share these leading tokens
            shared[s["group"]] = [tok.randrange(vocab)
                                  for _ in range(s["shared"])]
        head = shared.get(s["group"], [])
        reqs.append({"prompt_ids": head + [tok.randrange(vocab)
                                           for _ in range(s["unique"])],
                     "max_tokens": s["max_tokens"], "due_s": 0.0})
    if mix["loop"] == "open":
        gaps = arrival_gaps(mix, n)
        order.shuffle(gaps)
        t = 0.0
        for r, g in zip(reqs, gaps):
            r["due_s"] = t * seconds / n     # the first is due at 0
            t += g
    return reqs
