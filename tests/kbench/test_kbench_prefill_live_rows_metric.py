"""``sched.prefill_live_rows_pct`` (PR 50): the share of the rows the
prefill programs ran that held a prompt token,
``kaito:engine_prefill_tokens_total`` over
``kaito:engine_prefill_rows_total`` through the accepted
``counter_ratio_pct``.  A data file and a manifest entry, no reader;
what the mixes' shapes make it read on the program's default ladder;
its CPU rehearsal in a temporary copy of the rehearsal directory (the
rehearsal manifest is a benchmark file and is not edited); and the two
tests its entry makes fail for a count alone, held here whole."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import test_kbench_lfm2_moe
import test_kbench_olmo_hybrid
import trafficgen
from manifest import Manifest, load_json, validate
from paths import KBENCH, MANIFEST, ROOT
from readers import counter_ratio_pct
from test_kbench_rehearsal import REHEARSAL, _last_line

from kaito_tpu.engine.config import EngineConfig

CELL = "tiny-untied-live-rows"      # no other test file runs this cell
METRIC = "sched.prefill_live_rows_pct"
CELLS = ["phi4mini-batch", "falconh1-d6-batch", "mimo-v25-d7-ep16-long",
         "joyai-flash-ep16-long-out", "lfm2-8b-a1b-d14-long",
         "olmo-hybrid-7b-d8-long"]
PARENT_LADDER = (128, 256, 512, 1024, 2048, 4096)


def test_the_metric_is_data_on_a_reader_the_benchmark_had():
    spec = load_json(os.path.join(KBENCH, "layer_metrics", METRIC + ".json"))
    assert spec["reader"] == "counter_ratio_pct"
    assert spec["layer"] == "Scheduler (engine/engine.py)"
    assert spec["moves"] == "out_tok_s" and spec["unit"] == "%"
    assert spec["args"] == {"part": "kaito:engine_prefill_tokens_total",
                            "whole": "kaito:engine_prefill_rows_total"}
    m = Manifest()
    assert validate(m) == []
    names = [x["name"] for x in m.data["per_layer"]]
    # appended behind PR 49's last entry (whatever later PRs append
    # stands behind it)
    at = names.index("cache.delta_state_recomputes") + 1
    assert names[at] == METRIC
    assert m.data["per_layer"][at] == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_counter",
        "layer": "Scheduler (engine/engine.py)", "moves": "out_tok_s",
        "workloads": CELLS}
    assert m.layer_metric(METRIC)["args"] == spec["args"]
    for cell in CELLS:
        assert METRIC in {x["name"]
                          for x in m.metrics_for(cell, "per_layer")}
    # no reader came with it
    assert "prefill_live" not in " ".join(
        os.listdir(os.path.join(KBENCH, "readers")))


@pytest.mark.parametrize("before,after,want", [
    # a program without the rows counter (the parent) reports nothing,
    # whatever else it counts, and the line leaves the metric out
    ({}, {"kaito:generation_tokens_total": 5.0}, None),
    ({}, {"kaito:engine_prefill_tokens_total": 40.0}, None),
    # so does a window in which no prefill ran
    ({"kaito:engine_prefill_tokens_total": 40.0,
      "kaito:engine_prefill_rows_total": 48.0},
     {"kaito:engine_prefill_tokens_total": 40.0,
      "kaito:engine_prefill_rows_total": 48.0}, None),
    ({"kaito:engine_prefill_tokens_total": 40.0,
      "kaito:engine_prefill_rows_total": 48.0},
     {"kaito:engine_prefill_tokens_total": 2529.0,
      "kaito:engine_prefill_rows_total": 3120.0}, 100.0 * 2489 / 3072)])
def test_the_reader_on_planted_scrapes(before, after, want):
    args = Manifest().layer_metric(METRIC)["args"]
    got = counter_ratio_pct.read({"before": before, "after": after}, **args)
    assert got == want


def _live_rows_pct(mix_name, ladder, max_model_len):
    mix = load_json(os.path.join(KBENCH, "traffic", mix_name + ".json"))
    lens = [s["shared"] + s["unique"]
            for s in trafficgen.request_set(mix, mix["distinct"])]
    buckets = sorted({b for b in ladder if b < max_model_len}
                     | {max_model_len})
    rows = [next(b for b in buckets if n <= b) for n in lens]
    return 100.0 * sum(lens) / sum(rows)


@pytest.mark.parametrize("mix,max_model_len,parent,tree", [
    ("batch-long", 5120, 75.8, 84.9),
    ("batch-long-t14", 5120, 75.8, 84.9),
    ("batch-long-out", 5120, 72.7, 85.2),
    ("batch", 8192, 73.5, 73.5),
    ("batch-wide", 2048, 70.7, 70.7)])
def test_what_the_mixes_shapes_read_on_the_default_ladder(
        mix, max_model_len, parent, tree):
    """One cycle of a mix's shapes, each a fresh chunk in the smallest
    program that holds it: what the metric reads over whole cycles, on
    the parent's ladder and on the program's default.  The short mixes
    meet no half step."""
    assert round(_live_rows_pct(mix, PARENT_LADDER, max_model_len), 1) \
        == parent
    assert round(_live_rows_pct(mix, EngineConfig.prefill_buckets,
                                max_model_len), 1) == tree


@pytest.mark.parametrize("mod", [test_kbench_lfm2_moe,
                                 test_kbench_olmo_hybrid],
                         ids=["lfm2", "olmo"])
def test_the_cells_report_what_their_issues_list_and_this_metric(
        mod, monkeypatch):
    """``test_the_cell_reports_what_the_issue_lists`` of both files
    counts 30 metrics that every older cell reports; this one is the
    31st (tests/conftest.py marks the two as expected to fail).  Every
    assertion of them holds on the manifest without this PR's entry,
    which stands behind all they name, and the cells report it too."""
    m = Manifest()
    assert METRIC in {x["name"] for x in m.metrics_for(mod.CELL, "per_layer")}
    shared = set.intersection(*({x["name"]
                                 for x in m.metrics_for(c, "per_layer")}
                                for c in mod.OLD_CELLS))
    assert METRIC in shared
    m.data["per_layer"] = [x for x in m.data["per_layer"]
                           if x["name"] != METRIC]
    monkeypatch.setattr(mod, "Manifest", lambda *a: m)
    mod.test_the_cell_reports_what_the_issue_lists()


@pytest.fixture
def manifest_with_the_cell(tmp_path):
    root = str(tmp_path / "rehearsal")
    shutil.copytree(os.path.dirname(REHEARSAL), root)
    path = os.path.join(root, "BENCHMARK.json")
    data = load_json(path)
    data["workloads"].append(
        {"name": CELL, "config": "tiny-untied", "traffic": "batch",
         "chips": 1, "why": "rehearsal of the live share of prefill "
         "rows: the closed-loop mix, 16 clients on 8 slots"})
    for m in data["end_to_end"]:
        if "workloads" in m and "tiny-batch" in m["workloads"]:
            m["workloads"].append(CELL)
    real = {m["name"]: m for m in load_json(MANIFEST)["per_layer"]}
    data["per_layer"].append(dict(real[METRIC], workloads=[CELL]))
    with open(path, "w") as f:
        json.dump(data, f, indent=1)
    return path


def test_the_rehearsal_reports_the_live_share_of_prefill_rows(
        manifest_with_the_cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu", KAITO_ASYNC_DISPATCH="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    res = subprocess.run(
        [sys.executable, os.path.join(KBENCH, "run.py"), "--manifest",
         manifest_with_the_cell, "--workload", CELL, "--seed",
         str(2 ** 31 + 50), "--seconds", "4", "--trace", "1",
         "--expect-platform", "cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    out = _last_line(res)
    assert out["correct"] is True, res.stderr[-3000:]
    assert out["failed"] == 0 and out["attempted"] > 0
    got = out["metrics"]
    assert set(got) == {METRIC}, sorted(got)
    assert got[METRIC]["unit"] == "%"
    # whole prompts in the smallest program that holds each: some of
    # its rows are padding
    assert 0.0 < got[METRIC]["value"] <= 100.0
