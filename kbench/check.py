"""The numerical check: served log-probabilities against the plain
reference, under the configuration's written tolerance.

Nothing sampled is compared.  Every clause is either a maximum over
the vocabulary (continuous in the logits, so a flipped near-tie cannot
fail it) or the log-probability of an id *the same response* named
(so two requests that branched apart are never paired).  Check
requests go to an idle server one at a time, before the window.
"""

import hashlib
import json
import math
import os
import random
import sys

from kserver import BenchError, log, run_child
from paths import CACHE, KBENCH
from trafficgen import ids_of, words

RUN_REFERENCE = os.path.join(KBENCH, "reference", "run_reference.py")


def check_prompts(mix: dict, seed: int, vocab: int) -> list:
    """Seeded prompts of the lengths the mix's ``check`` lists (spread
    over its prompt range, crossing a page and a prefill bucket)."""
    rng = random.Random(seed ^ 0xC4EC)
    return [[rng.randrange(vocab) for _ in range(n)]
            for n in mix["check"]["prompt_lens"]]


def _completion(srv, model: str, ids: list, **kw) -> dict:
    body = {"model": model, "prompt": words(ids), "temperature": 0,
            "ignore_eos": True, "logprobs": 1}
    body.update(kw)
    resp = srv.post_json("/v1/completions", body)
    if resp["usage"]["prompt_tokens"] != len(ids):
        raise BenchError(f"a prompt of {len(ids)} words was read as "
                         f"{resp['usage']['prompt_tokens']} tokens")
    return resp


def send_checks(srv, model: str, prompts: list, n_decode: int) -> dict:
    """The check requests, in a fixed order, alone on the server.
    Returns what was served and how many tokens were generated."""
    served = {"first": [], "repeat": None, "decode": [], "score": None}
    tokens = 0
    for ids in prompts:                      # (a) fresh prefill
        lp = _completion(srv, model, ids, max_tokens=1)
        served["first"].append(
            lp["choices"][0]["logprobs"]["token_logprobs"][0])
        tokens += 1
    # (c) prompt 0 again: its pages are in the prefix cache now
    lp = _completion(srv, model, prompts[0], max_tokens=1)
    served["repeat"] = lp["choices"][0]["logprobs"]["token_logprobs"][0]
    tokens += 1
    for ids in prompts:                      # (b) decode through the cache
        lp = _completion(srv, model, ids,
                         max_tokens=n_decode)["choices"][0]["logprobs"]
        got = ids_of(" ".join(lp["tokens"]))
        if len(got) != n_decode or len(lp["token_logprobs"]) != n_decode:
            raise BenchError(f"asked for {n_decode} tokens with logprobs, "
                             f"got {len(got)} words")
        served["decode"].append({"ids": got, "lps": lp["token_logprobs"]})
        tokens += n_decode
    # (d) prompt scoring
    lp = _completion(srv, model, prompts[0], max_tokens=0, echo=True)
    served["score"] = lp["choices"][0]["logprobs"]["token_logprobs"]
    return {"served": served, "tokens": tokens}


def _ref_hash(reference_file: str) -> str:
    """Of the reference the configuration names and the child that
    loads it: an edit to one reference spoils only its own cache."""
    h = hashlib.sha256()
    for path in (reference_file, RUN_REFERENCE):
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def expectations(cfg: dict, weight_seed: int, requests: list, *,
                 platform: str, work_dir: str, perturb: str = "") -> list:
    """The answers of the reference ``cfg`` names (``reference_file``,
    from ``Manifest.config``) for ``requests`` (``tokens``, ``start``),
    from the checkout's cache where they are there, else from one
    reference child that holds the device alone.  ``platform`` is the
    one the server ran on: the child refuses any other, and an
    expectation is cached under it."""
    dtype = cfg["server"].get("config_file", {}).get("dtype", "")
    reference = cfg["reference_file"]
    if not os.path.isfile(reference):
        raise BenchError(f"configuration {cfg['name']}: no reference file "
                         f"{cfg.get('reference')!r}")
    base = json.dumps([cfg["config"], weight_seed, platform, dtype, perturb,
                       _ref_hash(reference)], sort_keys=True)
    paths = [os.path.join(CACHE, "reference", hashlib.sha256(
        (base + json.dumps(r, sort_keys=True)).encode()).hexdigest() + ".json")
        for r in requests]
    missing = [i for i, p in enumerate(paths) if not os.path.exists(p)]
    if missing:
        job = os.path.join(work_dir, "reference_job.json")
        out = os.path.join(work_dir, "reference_out.json")
        with open(job, "w") as f:
            json.dump({"reference": reference, "config": cfg["config"],
                       "weight_seed": weight_seed, "platform": platform,
                       "dtype": dtype, "perturb": perturb,
                       "requests": [requests[i] for i in missing]}, f)
        log(f"reference child for {len(missing)} sequence(s)")
        rc = run_child([sys.executable, RUN_REFERENCE, job, out],
                       os.path.join(work_dir, "reference.log"))
        if rc != 0:
            raise BenchError(f"reference child exited {rc}; see "
                             f"{work_dir}/reference.log")
        with open(out) as f:
            results = json.load(f)["results"]
        os.makedirs(os.path.dirname(paths[0]), exist_ok=True)
        for i, res in zip(missing, results):
            tmp = paths[i] + ".tmp"
            with open(tmp, "w") as f:
                json.dump(res, f)
            os.replace(tmp, paths[i])
    out = []
    for p in paths:
        with open(p) as f:
            out.append(json.load(f))
        if out[-1]["platform"] != platform:
            raise BenchError(f"{p} was computed on {out[-1]['platform']!r}, "
                             f"not on {platform!r}")
    return out


def reference_requests(prompts: list, served: dict) -> list:
    """Prompt k followed by the ids its own decode response named.
    Prompt 0 is scored from its first position (clause d)."""
    return [{"tokens": ids + served["decode"][k]["ids"],
             "start": 0 if k == 0 else len(ids) - 1}
            for k, ids in enumerate(prompts)]


def compare(prompts: list, served: dict, ref: list, tol: float) -> dict:
    """Each clause's largest error, and the clauses that broke ``tol``."""
    worst = {"prefill": 0.0, "decode": 0.0, "decode_near_argmax": 0.0,
             "prefix_repeat": 0.0, "prompt_score": 0.0}

    def note(clause, err):
        worst[clause] = max(worst[clause], err if math.isfinite(err) else 1e9)

    for k, ids in enumerate(prompts):
        start = 0 if k == 0 else len(ids) - 1
        at = len(ids) - 1 - start          # the last prompt position
        note("prefill", abs(served["first"][k] - ref[k]["top"][at]))
        for j, lp in enumerate(served["decode"][k]["lps"]):
            note("decode", abs(lp - ref[k]["target"][at + j]))
            note("decode_near_argmax",
                 ref[k]["top"][at + j] - ref[k]["target"][at + j])
    note("prefix_repeat",
         abs(served["repeat"] - ref[0]["top"][len(prompts[0]) - 1]))
    if served["score"][0] is not None:
        note("prompt_score", 1e9)          # the first token has no prefix
    for i, lp in enumerate(served["score"][1:], start=1):
        note("prompt_score", abs(lp - ref[0]["target"][i - 1]))
    return {"worst": worst, "tolerance": tol,
            "failed": sorted(c for c, e in worst.items() if e > tol)}
