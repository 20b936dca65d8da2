"""The CPU rehearsal reports the program's phase histograms: one run
of the closed-loop mix, traced (a CPU run takes no trace, so the
counter metrics are all a ``--trace 1`` line holds).

The rehearsal manifest is the benchmark's own file, so the cell lives
in a copy of the rehearsal directory with this file's entries appended,
as ``BENCHMARK.json`` appends them for ``phi4mini-batch``."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from manifest import Manifest, load_json
from paths import KBENCH, MANIFEST, ROOT
from test_kbench_rehearsal import REHEARSAL, _last_line

CELL = "tiny-untied-batch"       # no other test file runs this cell
COUNTERS = ("step.decode_ms", "step.prefill_ms", "step.device_wait_ms",
            "sched.schedule_ms", "sched.dispatch_ms", "sched.replay_ms",
            "sched.loop_stall_ms", "http.chunk_ms")


@pytest.fixture
def manifest_with_the_cell(tmp_path):
    """The rehearsal's files with one more cell, and for it the eight
    entries of the real manifest under the rehearsal's cell name."""
    root = str(tmp_path / "rehearsal")
    shutil.copytree(os.path.dirname(REHEARSAL), root)
    path = os.path.join(root, "BENCHMARK.json")
    data = load_json(path)
    data["workloads"].append(
        {"name": CELL, "config": "tiny-untied", "traffic": "batch",
         "chips": 1, "why": "rehearsal of the phase metrics: the "
         "closed-loop mix on the second architecture"})
    for m in data["end_to_end"]:
        if "workloads" in m and "tiny-batch" in m["workloads"]:
            m["workloads"].append(CELL)
    real = {m["name"]: m for m in load_json(MANIFEST)["per_layer"]}
    data["per_layer"] += [dict(real[name], workloads=[CELL])
                          for name in COUNTERS]
    with open(path, "w") as f:
        json.dump(data, f, indent=1)
    return path


def test_the_rehearsal_reports_the_eight_phase_metrics(manifest_with_the_cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    res = subprocess.run(
        [sys.executable, os.path.join(KBENCH, "run.py"), "--manifest",
         manifest_with_the_cell, "--workload", CELL, "--seed",
         str(2 ** 31 + 77), "--seconds", "4", "--trace", "1",
         "--expect-platform", "cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    out = _last_line(res)
    assert out["correct"] is True
    declared = {x["name"] for x in Manifest(manifest_with_the_cell)
                .metrics_for(CELL, "per_layer")}
    assert set(COUNTERS) <= declared
    got = out["metrics"]
    assert set(got) == set(COUNTERS), sorted(got)
    for name in COUNTERS:
        assert got[name]["unit"] == "ms"
        assert got[name]["value"] >= 0.0, name
    # a non-idle iteration decodes or prefills, and streams its tokens
    assert got["step.decode_ms"]["value"] > 0
    assert got["step.prefill_ms"]["value"] > 0
    assert got["http.chunk_ms"]["value"] > 0
    # the children of engine.decode are inside it
    assert got["step.device_wait_ms"]["value"] + got["sched.dispatch_ms"]["value"] \
        + got["sched.replay_ms"]["value"] == pytest.approx(
            got["step.decode_ms"]["value"], rel=0.05)
