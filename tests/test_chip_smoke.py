"""chip_smoke.py rehearsed on the CPU: it passes at tiny
size where it expects the CPU, and it cannot pass off the platform it
expects or over a failure the server caught."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(*args, devices=1, **env_extra):
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count="
                             f"{devices}"})
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        env=env, capture_output=True, text=True, timeout=900)


CPU = ("--model", "tiny-llama-test", "--expect-platform", "cpu")


# slow: 164 s alone under the check's command: the whole smoke at tiny size,
# four servers one after another
@pytest.mark.slow
def test_cpu_rehearsal_passes_and_names_the_cpu():
    # two virtual devices: the tp and dp legs run too
    res = _smoke(*CPU, devices=2)
    assert res.returncode == 0, res.stderr[-3000:]
    report, verdict = map(json.loads, res.stdout.strip().splitlines()[-2:])
    # the last line is the verdict with exactly these keys; the report
    # with everything else is the line before it
    assert verdict == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 2}}
    out = report
    assert out["ok"] is True
    assert out["device"] == verdict["device"]
    assert out["model"] == "tiny-llama-test"
    assert out["legs"]["serve"] == out["legs"]["tp"] \
        == out["legs"]["dp"] == "ok"
    assert out["legs"]["kernels"].startswith("skipped: --expect-platform")
    assert out["serve"]["prefix_cache_hits"] >= 1


def test_caught_prefill_failure_fails_the_smoke():
    # the engine catches the failed prefill, fails that one request and
    # keeps serving: exactly what the smoke must not pass over
    res = _smoke(*CPU, "--legs", "serve",
                 KAITO_FAILPOINTS="engine.prefill=raise*1")
    assert res.returncode != 0
    assert res.stdout.strip() == ""
    assert "serve" in json.loads(
        res.stderr.strip().splitlines()[-1])["failed"]


def test_default_invocation_refuses_the_cpu():
    res = _smoke("--legs", "serve")
    assert res.returncode != 0
    assert res.stdout.strip() == ""
    assert "expected 'tpu'" in res.stderr
