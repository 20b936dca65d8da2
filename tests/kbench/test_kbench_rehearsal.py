"""The CPU rehearsal of ``kbench/run.py`` end to end: the real server
at a tiny size, one run per traffic mix, the driver's own command line
plus ``--expect-platform cpu`` (which exists for this only)."""

import json
import os
import subprocess
import sys

import pytest

import check
from manifest import Manifest
from paths import KBENCH, OUT, ROOT

REHEARSAL = os.path.join(KBENCH, "testdata", "rehearsal", "BENCHMARK.json")
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(cell, *extra, trace=0, seed=2 ** 31 + 77, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # one CPU device, as on one chip; four virtual ones for the TP cell
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    res = subprocess.run(
        [sys.executable, os.path.join(KBENCH, "run.py"), "--manifest",
         REHEARSAL, "--workload", cell, "--seed", str(seed), "--seconds", "4",
         "--trace", str(trace), "--expect-platform", "cpu", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    return res


def _last_line(res):
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,trace,devices", [
    ("tiny-chat", 0, 1), ("tiny-batch", 1, 1), ("tiny-rag", 0, 1),
    ("tiny-tp4-chat", 0, 4)])
def test_rehearsal_of_each_mix(cell, trace, devices):
    res = _run(cell, trace=trace, devices=devices)
    out = _last_line(res)
    assert set(out) == KEYS, out
    assert out["correct"] is True, res.stderr[-3000:]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] == devices
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    m = Manifest(REHEARSAL)
    group = "per_layer" if trace else "end_to_end"
    declared = {x["name"] for x in m.metrics_for(cell, group)}
    assert set(out["metrics"]) <= declared
    if trace:
        # a CPU run prints no device metric: the trace readers found
        # nothing and were left out
        assert not any(n.startswith(("device.", "kernel.", "tp."))
                       for n in out["metrics"])
        assert "sched.batch_occupancy" in out["metrics"]
    else:
        assert set(out["metrics"]) == declared
        for name, metric in out["metrics"].items():
            assert metric["value"] > 0, name
    # every generated token reached the client as its own chunk
    assert "INCORRECT" not in res.stderr


def test_a_perturbed_reference_fails_a_named_clause():
    """The comparison, given the reference of a model with one layer
    dropped, on what the last tiny-chat rehearsal was served."""
    report_path = os.path.join(OUT, "tiny-chat", "report.json")
    if not os.path.exists(report_path):
        _last_line(_run("tiny-chat"))
    with open(report_path) as f:
        report = json.load(f)
    m = Manifest(REHEARSAL)
    cfg = m.config("tiny-tied-partial")
    mix = m.traffic("chat")
    seed = report["args"]["seed"]
    prompts = check.check_prompts(mix, seed, cfg["config"]["vocab_size"])
    work = os.path.join(OUT, "tiny-chat")
    for perturb, want_ok in (("", True), ("drop_last_layer", False)):
        ref = check.expectations(
            cfg, seed % (2 ** 31 - 1),
            check.reference_requests(prompts, report["served"]),
            platform="cpu", work_dir=work, perturb=perturb)
        verdict = check.compare(prompts, report["served"], ref,
                                cfg["tolerance"]["logprob_abs"])
        assert (verdict["failed"] == []) == want_ok, verdict
    assert "prefill" in verdict["failed"] and "decode" in verdict["failed"]


def test_the_run_reports_incorrect_when_the_reference_is_perturbed():
    res = _run("tiny-untied-chat", "--perturb-reference", "drop_last_layer")
    out = _last_line(res)
    assert out["correct"] is False
    assert "numerical check, clause" in res.stderr


def test_the_wrong_platform_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, os.path.join(KBENCH, "run.py"), "--manifest",
         REHEARSAL, "--workload", "tiny-chat", "--seed", "1", "--seconds",
         "2", "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=600)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
    assert "expected 'tpu'" in res.stderr


def test_a_directory_with_only_the_benchmark_fails(tmp_path):
    import shutil

    shutil.copytree(KBENCH, tmp_path / "kbench", ignore=shutil.ignore_patterns(
        "cache", "out", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    res = subprocess.run(
        [sys.executable, str(tmp_path / "kbench" / "run.py"), "--workload",
         "phi4mini-batch", "--seed", "1", "--seconds", "2", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert res.returncode != 0 and res.stdout.strip() == ""


def test_the_reference_child_refuses_another_platform(tmp_path):
    """A reference that found no chip would compute float32 on the CPU
    for a server held to the TPU: it fails instead."""
    cfg = Manifest(REHEARSAL).config("tiny-untied")
    job = tmp_path / "job.json"
    job.write_text(json.dumps({
        "reference": cfg["reference_file"], "config": cfg["config"],
        "weight_seed": 1, "platform": "tpu",
        "dtype": "", "perturb": "",
        "requests": [{"tokens": [1, 2, 3], "start": 0}]}))
    res = subprocess.run(
        [sys.executable, os.path.join(KBENCH, "reference", "run_reference.py"),
         str(job), str(tmp_path / "out.json")], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert res.returncode == 1 and "held to 'tpu'" in res.stderr
    assert not (tmp_path / "out.json").exists()
