"""``sched.first_token_deferred_pct``, rehearsed on the CPU as
``sched.pipeline_primed_pct`` is (test_kbench_pipeline_metric.py): the
closed-loop mix against a server whose two-deep dispatch loop the
environment pins on, in a temporary copy of the rehearsal directory
with one more cell and the real manifest's entry for it.  The rehearsal
manifest itself is a benchmark file and is not edited."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from manifest import Manifest, load_json
from paths import KBENCH, MANIFEST, ROOT
from readers import counter_share_pct
from test_kbench_rehearsal import REHEARSAL, _last_line

CELL = "tiny-untied-deferred"    # no other test file runs this cell
METRIC = "sched.first_token_deferred_pct"


@pytest.fixture
def manifest_with_the_cell(tmp_path):
    root = str(tmp_path / "rehearsal")
    shutil.copytree(os.path.dirname(REHEARSAL), root)
    path = os.path.join(root, "BENCHMARK.json")
    data = load_json(path)
    data["workloads"].append(
        {"name": CELL, "config": "tiny-untied", "traffic": "batch",
         "chips": 1, "why": "rehearsal of the deferred share: the "
         "closed-loop mix, an admission every few windows"})
    for m in data["end_to_end"]:
        if "workloads" in m and "tiny-batch" in m["workloads"]:
            m["workloads"].append(CELL)
    real = {m["name"]: m for m in load_json(MANIFEST)["per_layer"]}
    data["per_layer"].append(dict(real[METRIC], workloads=[CELL]))
    with open(path, "w") as f:
        json.dump(data, f, indent=1)
    return path


def test_the_metric_is_data_on_a_reader_the_benchmark_had():
    spec = load_json(os.path.join(KBENCH, "layer_metrics", METRIC + ".json"))
    assert spec["reader"] == "counter_share_pct"
    assert spec["layer"] == "Scheduler (engine/engine.py)"
    assert spec["moves"] == "out_tok_s" and spec["unit"] == "%"
    assert spec["args"] == {
        "part": "kaito:engine_first_tokens_deferred_total",
        "rest": "kaito:engine_first_tokens_blocking_total"}
    entry = next(m for m in load_json(MANIFEST)["per_layer"]
                 if m["name"] == METRIC)
    assert entry["workloads"] == ["phi4mini-batch"]
    assert entry["source"] == "program_counter"
    # a program without the two counters (the parent, the synchronous
    # loop) reports nothing, and the line leaves the metric out
    other = {"kaito:generation_tokens_total": 5.0}
    assert counter_share_pct.read({"before": {}, "after": other},
                                  **spec["args"]) is None
    grown = {spec["args"]["part"]: 9.0, spec["args"]["rest"]: 3.0}
    assert counter_share_pct.read({"before": other, "after": grown},
                                  **spec["args"]) == 75.0


def test_the_rehearsal_reports_every_first_token_deferred(
        manifest_with_the_cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu", KAITO_ASYNC_DISPATCH="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    res = subprocess.run(
        [sys.executable, os.path.join(KBENCH, "run.py"), "--manifest",
         manifest_with_the_cell, "--workload", CELL, "--seed",
         str(2 ** 31 + 83), "--seconds", "4", "--trace", "1",
         "--expect-platform", "cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    out = _last_line(res)
    # the check requests' logprobs are deferred ones, and the accounting
    # clause holds with first tokens on the device: every chunk counted
    assert out["correct"] is True, res.stderr[-3000:]
    assert out["failed"] == 0 and out["attempted"] > 0
    declared = {x["name"] for x in Manifest(manifest_with_the_cell)
                .metrics_for(CELL, "per_layer")}
    assert declared == {METRIC}
    got = out["metrics"]
    assert set(got) == {METRIC}, sorted(got)
    assert got[METRIC]["unit"] == "%"
    # the mix sends no grammar, penalty or wide stop set
    assert got[METRIC]["value"] == 100.0
