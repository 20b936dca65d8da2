"""PR 40's counter metrics on the CPU rehearsal: one run of the
closed-loop mix, traced (a CPU run takes no trace, so the counter
metrics are all a ``--trace 1`` line holds), and the two readers the PR
brings.

The rehearsal manifest is the benchmark's own file, so the cell lives
in a copy of the rehearsal directory with this file's entries appended,
as ``test_kbench_phase_metrics.py`` does it."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from manifest import Manifest, load_json, validate
from paths import KBENCH, MANIFEST, ROOT
from test_kbench_rehearsal import REHEARSAL, _last_line

CELL = "tiny-untied-batch-parts"   # no other test file runs this cell
CELLS = ["phi4mini-batch", "falconh1-d6-batch", "mimo-v25-d7-ep16-long"]
COUNTERS = ("sched.launch_ms", "sched.launch_stall_ms", "sched.args_ms",
            "sched.plan_ms", "sched.replay_stall_ms", "step.compiles",
            "http.chunk_cpu_ms")
TRACED = ("device.idle_in.args", "device.idle_in.launch",
          "device.idle_in.plan", "device.idle_in.resolve")
# what the parts are parts of
WHOLE = ("sched.dispatch_ms", "sched.replay_ms", "sched.loop_stall_ms",
         "step.prefill_ms", "http.chunk_ms")


def test_the_eleven_entries_are_appended_for_every_cell():
    m = Manifest()
    assert validate(m) == []
    names = [x["name"] for x in m.data["per_layer"]]
    assert names[-11:] == list(COUNTERS) + list(TRACED)
    layers = {"sched": "Scheduler (engine/engine.py)",
              "step": "Step programs (engine/model.py)",
              "http": "HTTP front (engine/server.py)",
              "device": "Device (TPU v5e)"}
    for entry in m.data["per_layer"][-11:]:
        spec = m.layer_metric(entry["name"])
        assert entry["workloads"] == CELLS and entry["better"] == "lower"
        assert entry["moves"] == spec["moves"] == "out_tok_s"
        assert entry["layer"] == spec["layer"] \
            == layers[entry["name"].split(".")[0]]
        assert entry["unit"] == spec["unit"]
        assert entry["source"] == ("program_span" if entry["name"] in TRACED
                                   else "program_counter")
        assert os.path.exists(os.path.join(
            KBENCH, "readers", spec["reader"] + ".py"))
    for cell in CELLS:
        assert set(COUNTERS + TRACED) <= {
            x["name"] for x in m.metrics_for(cell, "per_layer")}


def test_pr_34s_and_pr_38s_entries_stand_where_they_stood():
    """What test_kbench_mimo_v2.py::test_pr_34s_entry_stands_where_it_stood
    holds, every assertion of it, with the tail behind PR 34's entry
    compared as a prefix: PR 38's five first, this PR's eleven behind
    them in ISSUE 40's order (tests/conftest.py marks that test as
    expected to fail, for the tail's length alone)."""
    from readers import counter_share_pct

    metric = "sched.prefill_multi_pct"
    spec = load_json(os.path.join(KBENCH, "layer_metrics", metric + ".json"))
    assert spec["reader"] == "counter_share_pct"
    assert spec["layer"] == "Scheduler (engine/engine.py)"
    assert spec["moves"] == "out_tok_s" and spec["unit"] == "%"
    assert spec["args"] == {
        "part": "kaito:engine_prefill_turns_multi_total",
        "rest": "kaito:engine_prefill_turns_single_total"}
    per_layer = Manifest().data["per_layer"]
    names = [x["name"] for x in per_layer]
    at = names.index(metric)
    assert per_layer[at] == {
        "name": metric, "unit": "%", "better": "higher",
        "source": "program_counter",
        "layer": "Scheduler (engine/engine.py)", "moves": "out_tok_s",
        "workloads": CELLS}
    pr38 = ["kernel.moe_experts_roofline",
            "kernel.decode_attn_kinds_roofline", "moe.experts_touched_pct",
            "moe.held_pairs_pct", "cache.window_pages_per_seq"]
    assert at == 24 and names[at + 1:at + 6] == pr38
    assert names[at + 6:at + 17] == list(COUNTERS) + list(TRACED)
    for entry in per_layer[at + 1:at + 6]:
        assert entry["workloads"] == [CELLS[2]]
    other = {"kaito:generation_tokens_total": 5.0}
    assert counter_share_pct.read({"before": {}, "after": other},
                                  **spec["args"]) is None
    still = {spec["args"]["part"]: 4.0, spec["args"]["rest"]: 7.0}
    assert counter_share_pct.read({"before": still, "after": still},
                                  **spec["args"]) is None
    grown = {spec["args"]["part"]: 9.0, spec["args"]["rest"]: 3.0}
    assert counter_share_pct.read({"before": other, "after": grown},
                                  **spec["args"]) == 75.0


def test_a_counter_of_seconds_over_a_histograms_count():
    from readers import counter_per_count_ms as reader

    args = load_json(os.path.join(
        KBENCH, "layer_metrics", "http.chunk_cpu_ms.json"))["args"]
    assert args == {"seconds": "kaito:http_stream_cpu_seconds_total",
                    "count": "kaito:http_stream_chunk_seconds_count"}
    before = {args["seconds"]: 1.5, args["count"]: 1000.0}
    after = {args["seconds"]: 2.0, args["count"]: 3000.0}
    assert reader.read({"before": before, "after": after}, **args) \
        == pytest.approx(0.25)                  # 0.5 s over 2,000 chunks
    # a program without the counter (the parent), and a still window
    old = {args["count"]: 3000.0}
    assert reader.read({"before": {}, "after": old}, **args) is None
    assert reader.read({"before": after, "after": after}, **args) is None
    # counters that start inside the window count from nothing
    assert reader.read({"before": {}, "after": after}, **args) \
        == pytest.approx(2.0 / 3000.0 * 1e3)


@pytest.fixture
def manifest_with_the_cell(tmp_path):
    """The rehearsal's files with one more cell, and for it this PR's
    seven counter entries of the real manifest, with the entries of
    what they are parts of, under the rehearsal's cell name."""
    root = str(tmp_path / "rehearsal")
    shutil.copytree(os.path.dirname(REHEARSAL), root)
    path = os.path.join(root, "BENCHMARK.json")
    data = load_json(path)
    data["workloads"].append(
        {"name": CELL, "config": "tiny-untied", "traffic": "batch",
         "chips": 1, "why": "rehearsal of the part metrics: the "
         "closed-loop mix on the second architecture"})
    for m in data["end_to_end"]:
        if "workloads" in m and "tiny-batch" in m["workloads"]:
            m["workloads"].append(CELL)
    real = {m["name"]: m for m in load_json(MANIFEST)["per_layer"]}
    data["per_layer"] += [dict(real[name], workloads=[CELL])
                          for name in COUNTERS + TRACED + WHOLE]
    with open(path, "w") as f:
        json.dump(data, f, indent=1)
    return path


def test_the_rehearsal_reports_the_seven_counter_metrics(
        manifest_with_the_cell):
    # the loop a chip runs (docs/decode-loop.md); the CPU backend would
    # resolve to the synchronous one
    env = dict(os.environ, JAX_PLATFORMS="cpu", KAITO_ASYNC_DISPATCH="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    res = subprocess.run(
        [sys.executable, os.path.join(KBENCH, "run.py"), "--manifest",
         manifest_with_the_cell, "--workload", CELL, "--seed",
         str(2 ** 31 + 40), "--seconds", "4", "--trace", "1",
         "--expect-platform", "cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    out = _last_line(res)
    assert out["correct"] is True
    declared = {x["name"] for x in Manifest(manifest_with_the_cell)
                .metrics_for(CELL, "per_layer")}
    assert set(COUNTERS + TRACED) <= declared
    got = {k: v["value"] for k, v in out["metrics"].items()}
    # a CPU run takes no trace: the four shares of the idle time are
    # left out, every counter metric is there
    assert set(got) == set(COUNTERS + WHOLE), sorted(got)
    for name in COUNTERS:
        want = "count" if name == "step.compiles" else "ms"
        assert out["metrics"][name]["unit"] == want
        assert got[name] >= 0.0, name
    assert got["sched.launch_ms"] > 0 and got["sched.args_ms"] > 0
    assert got["sched.plan_ms"] > 0 and got["http.chunk_cpu_ms"] > 0
    # each part inside its whole
    assert got["sched.launch_stall_ms"] <= got["sched.launch_ms"]
    assert got["sched.args_ms"] + got["sched.launch_ms"] \
        <= got["sched.dispatch_ms"] + got["step.prefill_ms"]
    assert got["sched.replay_stall_ms"] <= got["sched.replay_ms"]
    assert got["sched.replay_stall_ms"] + got["sched.launch_stall_ms"] \
        <= got["sched.loop_stall_ms"] + 1e-6
