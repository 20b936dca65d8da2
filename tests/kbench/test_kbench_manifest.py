"""BENCHMARK.json against the contract's rules a CPU can check, and
the proof that a later PR adds a configuration, a mix, a cell, a
per-layer metric and a reader as new files and entries only."""

import copy
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from manifest import Manifest, validate
from paths import KBENCH, MANIFEST, ROOT

REHEARSAL = os.path.join(KBENCH, "testdata", "rehearsal", "BENCHMARK.json")


@pytest.mark.parametrize("path", [MANIFEST, REHEARSAL])
def test_manifest_is_sound(path):
    assert validate(Manifest(path)) == []


def test_at_most_a_quarter_of_the_cells_take_four_chips():
    cells = Manifest().data["workloads"]
    assert sum(c["chips"] == 4 for c in cells) <= max(1, len(cells) // 4)


def test_every_moves_names_a_metric_each_reporting_cell_reports():
    m = Manifest()
    cells = [c["name"] for c in m.data["workloads"]]
    for metric in m.data["per_layer"]:
        for cell in metric.get("workloads", cells):
            reported = [x["name"] for x in m.metrics_for(cell, "end_to_end")]
            assert metric["moves"] in reported, (metric["name"], cell)


def test_every_layer_metric_has_a_file_and_a_reader():
    m = Manifest()
    for metric in m.data["per_layer"]:
        spec = m.layer_metric(metric["name"])
        assert spec["layer"] == metric["layer"]
        assert spec["moves"] == metric["moves"]
        assert spec["unit"] == metric["unit"]
        assert os.path.exists(os.path.join(KBENCH, "readers",
                                           spec["reader"] + ".py"))


def test_configurations_cut_no_width():
    m = Manifest()
    for c in m.data["configs"]:
        cfg = m.config(c["name"])
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert isinstance(cfg["tolerance"]["logprob_abs"], float)
        assert cfg["tolerance"]["reason"]


def _mutations():
    def dup_cell(d):
        d["workloads"].append(dict(d["workloads"][0]))

    def bad_unit(d):
        d["end_to_end"][0]["unit"] = "tokens per second"

    def no_setup(d):
        d["end_to_end"] = [x for x in d["end_to_end"] if x["name"] != "setup_s"]

    def wide_bound(d):
        d["end_to_end"][0]["bound"] = 0.5

    def moves_unreported(d):
        d["per_layer"][0]["moves"] = "out_tok_s"

    def extra_key(d):
        d["per_layer"][0]["why"] = "not allowed"

    def too_many_four_chip(d):
        for w in d["workloads"]:
            w["chips"] = 4

    def width_reduced(d):
        d["configs"][0]["reduced"] = ["hidden_size"]

    def unknown_config(d):
        d["workloads"][0]["config"] = "nope"

    def long_run(d):
        d["run_seconds"] = 52

    return [dup_cell, bad_unit, no_setup, wide_bound, moves_unreported,
            extra_key, too_many_four_chip, width_reduced, unknown_config,
            long_run]


@pytest.mark.parametrize("mutate", _mutations(), ids=lambda f: f.__name__)
def test_validate_names_a_breach(mutate, tmp_path):
    m = Manifest(REHEARSAL)     # several cells and both kinds of metric
    m.data = copy.deepcopy(m.data)
    mutate(m.data)
    assert validate(m), mutate.__name__


@pytest.mark.parametrize("reference,says", [
    ("kbench/reference/not_there.py", "does not exist"),
    ("kbench/reference/../../kaito_tpu/engine/model.py", "not under paths"),
    ("kaito_tpu/engine/model.py", "not under paths"),
    ("/kbench/reference/dense_decoder.py", "not under paths"),
    ("kbench/reference", "not a Python file"),
    (None, "not a Python file")])
def test_validate_names_a_reference_that_is_missing_or_leaves_paths(
        reference, says, tmp_path):
    root = tmp_path / "rehearsal"
    shutil.copytree(os.path.dirname(REHEARSAL), root)
    path = root / "kbench" / "configs" / "tiny-untied.json"
    cfg = json.loads(path.read_text())
    assert cfg["reference"] == "kbench/reference/dense_decoder.py"
    m = Manifest(str(root / "BENCHMARK.json"))
    assert validate(m) == []       # found beside the harness, not the copy
    assert m.config("tiny-untied")["reference_file"] == os.path.join(
        KBENCH, "reference", "dense_decoder.py")
    if reference is None:
        del cfg["reference"]
    else:
        cfg["reference"] = reference
    path.write_text(json.dumps(cfg))
    bad = validate(m)
    assert len(bad) == 1 and "tiny-untied" in bad[0] and says in bad[0], bad


def _hashes(root):
    out = {}
    for base, dirs, files in os.walk(root):
        dirs[:] = [d for d in dirs if d not in ("cache", "out", "__pycache__")]
        for f in files:
            p = os.path.join(base, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def _copy(tmp_path):
    root = tmp_path / "copy"
    shutil.copytree(KBENCH, root / "kbench", ignore=shutil.ignore_patterns(
        "cache", "out", "__pycache__"))
    shutil.copy(MANIFEST, root / "BENCHMARK.json")
    return root, _hashes(root / "kbench")


def _in_the_copy(root, code):
    """Run ``code`` against the copy's own harness.  The copy holds the
    benchmark only: the program is found in this checkout."""
    res = subprocess.run(
        [sys.executable, "-c", "import sys\n"
         f"sys.path.insert(0, {str(root / 'kbench')!r})\n" + code],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT))
    assert res.returncode == 0 and "ok" in res.stdout, res.stderr[-3000:]


def test_a_later_pr_adds_files_and_entries_and_edits_none(tmp_path):
    """A dummy configuration, mix, cell, per-layer metric and reader,
    in a temporary copy: new files, new entries, no file changed."""
    root, before = _copy(tmp_path)
    kb = root / "kbench"
    cfg = json.loads((kb / "configs" / "phi-4-mini-instruct.json").read_text())
    cfg["config"]["num_hidden_layers"] = 16
    cfg["reduced"] = ["num_hidden_layers"]
    (kb / "configs" / "dummy-d16.json").write_text(json.dumps(cfg))
    # an open-loop mix: the first of its kind in the copy, as in the tree
    mix = json.loads((kb / "testdata" / "rehearsal" / "kbench" / "traffic"
                      / "chat.json").read_text())
    mix["arrivals"] = "uniform"
    (kb / "traffic" / "dummy-steady.json").write_text(json.dumps(mix))
    (kb / "cells").mkdir(exist_ok=True)
    (kb / "cells" / "dummy-cell.json").write_text(json.dumps({"rate_rps": 2.0}))
    (kb / "readers" / "dummy_reader.py").write_text(
        "def read(ctx, *, name):\n    return ctx['after'].get(name)\n")
    (kb / "layer_metrics" / "dummy.running.json").write_text(json.dumps({
        "layer": "Scheduler (engine/engine.py)", "moves": "ttft_p50_ms",
        "unit": "count", "reader": "dummy_reader",
        "args": {"name": "kaito:num_requests_running"}}))

    data = json.loads((root / "BENCHMARK.json").read_text())
    data["configs"].append({
        "name": "dummy-d16", "source": cfg["source"],
        "file": "kbench/configs/dummy-d16.json",
        "reduced": ["num_hidden_layers"], "why": "a dummy"})
    data["workloads"].append(
        {"name": "dummy-cell", "config": "dummy-d16",
         "traffic": "dummy-steady", "chips": 1, "why": "a dummy"})
    data["end_to_end"].append({
        "name": "ttft_p50_ms", "unit": "ms", "better": "lower", "bound": 0.05,
        "source": "host_clock", "workloads": ["dummy-cell"]})
    data["per_layer"].append({
        "name": "dummy.running", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "Scheduler (engine/engine.py)",
        "moves": "ttft_p50_ms", "workloads": ["dummy-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(data))

    after = _hashes(root / "kbench")
    assert {k: v for k, v in after.items() if k in before} == before
    # the copy's own harness finds everything by name
    _in_the_copy(root, (
        "from manifest import Manifest, validate\n"
        "import run, trafficgen\n"
        "m = Manifest()\n"
        "assert validate(m) == [], validate(m)\n"
        "cell = m.cell('dummy-cell')\n"
        "mix = m.traffic(cell['traffic'])\n"
        "reqs = trafficgen.schedule(mix, seed=3, vocab=1000, seconds=10,\n"
        "    rate_rps=m.cell_settings('dummy-cell')['rate_rps'])\n"
        "assert len(reqs) == 20\n"
        "assert m.config('dummy-d16')['config']['num_hidden_layers'] == 16\n"
        "ctx = {'before': {}, 'after': {'kaito:num_requests_running': 3.0},\n"
        "       'polls': [], 'client': {'ttft_p50_ms': 1.0}, 'trace': None}\n"
        "out = run.layer_metrics(m, 'dummy-cell', ctx)\n"
        "assert out['dummy.running']['value'] == 3.0, out\n"
        "print('ok')\n"))


# a reference of another architecture, as a later PR would bring it: a
# file of its own with the interface kbench/README.md gives.  It answers
# with constants, its vocabulary's padded size as the marker.
NEW_REFERENCE = '''
PERTURBATIONS = ("shifted",)


def forward(config, params, tokens, start, *, put=lambda x: x, perturb=""):
    n = len(tokens) - start
    off = 1.0 if perturb == "shifted" else 0.0
    rows = put(params["embed"]).shape[0]
    return {"target": [-2.0 - off] * (n - 1) + [float("nan")],
            "top": [-1.0 - off] * n, "marker": [float(rows)]}
'''

# the phi configuration's expectation, planted where the check caches it
# (the child that would compute it is replaced by one that writes "planted")
PLANT = """
import json
import check
from manifest import Manifest
cfg = Manifest().config('phi-4-mini-instruct')
def planted(cmd, log_path, **kw):
    with open(cmd[2]) as f:
        job = json.load(f)
    assert job['reference'].endswith('kbench/reference/dense_decoder.py')
    with open(cmd[3], 'w') as f:
        json.dump({'results': [{'target': [0.5], 'top': [0.25],
                                'platform': 'cpu', 'planted': True}
                               for _ in job['requests']]}, f)
    return 0
check.run_child = planted
got = check.expectations(cfg, 7, [{'tokens': [1, 2], 'start': 0}],
                         platform='cpu', work_dir=sys.argv[1])
assert got[0]['planted']
print('ok')
"""

USE = """
import json, math, os
import check
from manifest import Manifest, validate
m = Manifest()
assert validate(m) == [], validate(m)
cfg = m.config('dummy-arch')
assert cfg['reference_file'].endswith('kbench/reference/dummy_arch.py')
reqs = [{'tokens': [5, 6, 7, 8], 'start': 1}]
clean, = check.expectations(cfg, 3, reqs, platform='cpu', work_dir=sys.argv[1])
# the new module answered: its constants, and its marker (the vocabulary
# of 2,048 rows that init_params made for it)
assert clean['top'] == [-1.0] * 3 and clean['target'][:2] == [-2.0] * 2
assert math.isnan(clean['target'][2]) and clean['marker'] == [2048.0], clean
assert clean['platform'] == 'cpu'
shifted, = check.expectations(cfg, 3, reqs, platform='cpu',
                              work_dir=sys.argv[1], perturb='shifted')
assert shifted['top'] == [-2.0] * 3
# a perturbation of the other reference is refused by this one's child
try:
    check.expectations(cfg, 3, reqs, platform='cpu', work_dir=sys.argv[1],
                       perturb='drop_last_layer')
    raise SystemExit('not refused')
except check.BenchError:
    with open(os.path.join(sys.argv[1], 'reference.log')) as f:
        assert "knows no perturbation 'drop_last_layer'" in f.read()
# the phi configuration's expectation is still found: no child runs
def no_child(*a, **kw):
    raise AssertionError('the cache was missed')
check.run_child = no_child
got = check.expectations(m.config('phi-4-mini-instruct'), 7,
                         [{'tokens': [1, 2], 'start': 0}], platform='cpu',
                         work_dir=sys.argv[1])
assert got[0]['planted']
# and the harness names no reference file
for name in ('check.py', 'reference/run_reference.py', 'run.py'):
    with open(os.path.join(os.path.dirname(check.__file__), name)) as f:
        assert 'dense_decoder' not in f.read(), name
print('ok')
"""


def test_a_later_pr_brings_its_own_reference_and_edits_none(tmp_path):
    """A configuration whose ``reference`` names a *new* file under
    ``kbench/reference/``, run through ``check.expectations`` on the
    CPU at a tiny size: the new module computed the answer, no file
    that was there changed, and what was cached for the configuration
    that was there is still found."""
    root, before = _copy(tmp_path)
    kb = root / "kbench"
    work = tmp_path / "work"
    work.mkdir()
    _in_the_copy(root, "sys.argv[1:] = [%r]\n" % str(work) + PLANT)

    (kb / "reference" / "dummy_arch.py").write_text(NEW_REFERENCE)
    cfg = json.loads((kb / "testdata" / "rehearsal" / "kbench" / "configs"
                      / "tiny-untied.json").read_text())
    cfg["reference"] = "kbench/reference/dummy_arch.py"
    (kb / "configs" / "dummy-arch.json").write_text(json.dumps(cfg))
    data = json.loads((root / "BENCHMARK.json").read_text())
    data["configs"].append({
        "name": "dummy-arch", "source": cfg["source"],
        "file": "kbench/configs/dummy-arch.json", "reduced": [],
        "why": "a dummy of another architecture"})
    data["workloads"].append(
        {"name": "dummy-arch-batch", "config": "dummy-arch",
         "traffic": "batch", "chips": 1, "why": "a dummy"})
    for metric in data["end_to_end"] + data["per_layer"]:
        if "workloads" in metric:
            metric["workloads"].append("dummy-arch-batch")
    (root / "BENCHMARK.json").write_text(json.dumps(data))

    after = _hashes(kb)
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {"reference/dummy_arch.py",
                                        "configs/dummy-arch.json"}
    _in_the_copy(root, "sys.argv[1:] = [%r]\n" % str(work) + USE)
