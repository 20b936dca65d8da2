#!/usr/bin/env python3
"""The builder's tool for the tolerance: what one run was served,
against the clean reference and against deliberately cruder ones.

    python kbench/tolerance.py kbench/out/<cell>/report.json [...]

For each report (written by ``run.py``) it regenerates the check
prompts from the run's seed, asks the reference the configuration
names for its expectations — clean, and under each of that module's
``PERTURBATIONS`` (for ``dense_decoder.py``: the last layer dropped,
the head rounded to int8, every layer's matrices rounded to float8) —
and prints each clause's largest error.  The tolerance in a configuration's file is
set above every clean error and below what a wrong model gives.  Runs
the reference child, so the device must be free.
"""

import ast
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import check                                    # noqa: E402
from manifest import Manifest, load_json        # noqa: E402


def perturbations(reference_file: str) -> tuple:
    """The ``PERTURBATIONS`` tuple of a reference file, read from its
    source: importing the module would bring in JAX, and this process
    must leave the device to the reference child."""
    with open(reference_file) as f:
        tree = ast.parse(f.read())
    for node in tree.body:
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and getattr(node.targets[0], "id", "") == "PERTURBATIONS"):
            return tuple(ast.literal_eval(node.value))
    raise ValueError(f"{reference_file} exports no PERTURBATIONS")


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
        for perturb in ("",) + perturbations(cfg["reference_file"]):
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
