"""``sched.prefill_multi_pct``, rehearsed on the CPU as
``sched.first_token_deferred_pct`` is
(test_kbench_first_token_metric.py): the closed-loop mix against a
server started with ``prefill-pack 1``, as both of the real cells are,
in a temporary copy of the rehearsal directory with one more
configuration (the rehearsal's ``tiny-untied`` plus that argument), one
more cell and the real manifest's entry for the metric.  The rehearsal
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

CONFIG = "tiny-untied-serial"
CELL = "tiny-untied-serial-batch"   # no other test file runs this cell
METRIC = "sched.prefill_multi_pct"


@pytest.fixture
def manifest_with_the_cell(tmp_path):
    root = str(tmp_path / "rehearsal")
    shutil.copytree(os.path.dirname(REHEARSAL), root)
    path = os.path.join(root, "BENCHMARK.json")
    data = load_json(path)
    base = next(c for c in data["configs"] if c["name"] == "tiny-untied")
    cfg = load_json(os.path.join(root, base["file"]))
    cfg["server"]["args"]["prefill-pack"] = 1
    file = f"kbench/configs/{CONFIG}.json"
    with open(os.path.join(root, file), "w") as f:
        json.dump(cfg, f, indent=1)
    data["configs"].append(dict(
        base, name=CONFIG, file=file,
        why="the second architecture under the serial prefill scheduler"))
    data["workloads"].append(
        {"name": CELL, "config": CONFIG, "traffic": "batch",
         "chips": 1, "why": "rehearsal of the multi-prompt turn share: "
         "the closed-loop mix, 16 clients on 8 slots, prefill-pack 1"})
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
        "part": "kaito:engine_prefill_turns_multi_total",
        "rest": "kaito:engine_prefill_turns_single_total"}
    per_layer = load_json(MANIFEST)["per_layer"]
    entry = per_layer[-1]            # appended: nothing before it moved
    assert entry == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_counter",
        "layer": "Scheduler (engine/engine.py)", "moves": "out_tok_s",
        "workloads": ["phi4mini-batch", "falconh1-d6-batch"]}
    # a program without the two counters (the parent) reports nothing,
    # and the line leaves the metric out; so does a window with no turn
    other = {"kaito:generation_tokens_total": 5.0}
    assert counter_share_pct.read({"before": {}, "after": other},
                                  **spec["args"]) is None
    still = {spec["args"]["part"]: 4.0, spec["args"]["rest"]: 7.0}
    assert counter_share_pct.read({"before": still, "after": still},
                                  **spec["args"]) is None
    grown = {spec["args"]["part"]: 9.0, spec["args"]["rest"]: 3.0}
    assert counter_share_pct.read({"before": other, "after": grown},
                                  **spec["args"]) == 75.0


def test_the_rehearsal_reports_the_share_of_multi_prompt_turns(
        manifest_with_the_cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu", KAITO_ASYNC_DISPATCH="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    res = subprocess.run(
        [sys.executable, os.path.join(KBENCH, "run.py"), "--manifest",
         manifest_with_the_cell, "--workload", CELL, "--seed",
         str(2 ** 31 + 34), "--seconds", "4", "--trace", "1",
         "--expect-platform", "cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    out = _last_line(res)
    # several prompts a turn, each through the one-row programs: the
    # check's logprobs and the accounting clause hold as they did
    assert out["correct"] is True, res.stderr[-3000:]
    assert out["failed"] == 0 and out["attempted"] > 0
    declared = {x["name"] for x in Manifest(manifest_with_the_cell)
                .metrics_for(CELL, "per_layer")}
    assert declared == {METRIC}
    got = out["metrics"]
    assert set(got) == {METRIC}, sorted(got)
    assert got[METRIC]["unit"] == "%"
    # 16 clients on 8 slots, outputs of 8-16 tokens: slots free in twos
    # and threes between turns, and a turn takes them together
    assert 0.0 < got[METRIC]["value"] <= 100.0
