#!/usr/bin/env python3
"""The builder's tool for the tolerance: what one run was served,
against the clean reference and against deliberately cruder ones.

    python kbench/tolerance.py kbench/out/<cell>/report.json [...]

For each report (written by ``run.py``) it regenerates the check
prompts from the run's seed, asks the reference for its expectations —
clean, with the last layer dropped, with the head rounded to int8 and
with every layer's matrices rounded to float8 — and prints each
clause's largest error.  The tolerance in a configuration's file is
set above every clean error and below what a wrong model gives.  Runs
the reference child, so the device must be free.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import check                                    # noqa: E402
from manifest import Manifest, load_json        # noqa: E402

PERTURBATIONS = ("", "drop_last_layer", "head_int8", "weights_fp8")


def main() -> int:
    for path in sys.argv[1:]:
        report = load_json(path)
        args = report["args"]
        m = Manifest(args["manifest"])
        cell = m.cell(args["workload"])
        cfg = m.config(cell["config"])
        mix = m.traffic(cell["traffic"])
        prompts = check.check_prompts(mix, args["seed"],
                                      int(cfg["config"]["vocab_size"]))
        requests = check.reference_requests(prompts, report["served"])
        for perturb in PERTURBATIONS:
            ref = check.expectations(cfg, args["seed"] % (2 ** 31 - 1), requests,
                                     platform=args["expect_platform"],
                                     work_dir=os.path.dirname(path),
                                     perturb=perturb)
            verdict = check.compare(prompts, report["served"], ref,
                                    float(cfg["tolerance"]["logprob_abs"]))
            print(json.dumps({"report": path, "cell": cell["name"],
                              "seed": args["seed"],
                              "reference": perturb or "clean",
                              "worst": verdict["worst"],
                              "failed": verdict["failed"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
