"""``benchmarks/gdn_scan.py``: the delta rule's prefill scan timed alone
on a chip, the tree's form beside the forms it replaced and the ones it
may become.  Here, on a CPU and at a tiny size, that every form the
script builds is the recurrence (a yardstick that drifts from the
definition measures nothing), that its reduction of a trace finds the
phases' scopes in compiled HLO, and that it refuses to time a CPU."""

import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

from kaito_tpu.engine.ops import gdn as G

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FORMS = ["tree", "rows+fused/64", "mxu+fused/64", "lanes+fused/64",
         "rows+product/64", "lanes+product/64", "lanes+fused/128",
         "lanes+product/128", "mxu+product/128", "lanes+assoc/128"]


@pytest.fixture(scope="module")
def gs():
    spec = importlib.util.spec_from_file_location(
        "gdn_scan", os.path.join(ROOT, "benchmarks", "gdn_scan.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def case(gs):
    inputs = gs.case(256, 2, 8, 16)
    return inputs, jax.jit(G.gdn_recurrence)(*inputs)


def test_the_forms_listed_here_are_the_scripts(gs):
    assert list(gs.variants()) == FORMS


@pytest.mark.parametrize("name", FORMS)
def test_a_form_is_the_recurrence(gs, case, name):
    inputs, (want_o, want_s) = case
    o, s = jax.jit(gs.variants()[name])(*inputs)
    assert float(jnp.abs(o - want_o).max()) \
        <= gs.TOL * float(jnp.abs(want_o).max())
    assert float(jnp.abs(s - want_s).max()) \
        <= gs.TOL * float(jnp.abs(want_s).max())


def test_compiled_ops_are_found_under_their_phases_scope(gs, case):
    inputs, _ = case
    fn = gs.variants()["lanes+product/128"]
    scopes = gs._scope_of_ops(jax.jit(fn).lower(*inputs).compile().as_text())
    assert {"pairs", "inverse", "wu", "carry", "out"} <= set(scopes.values())
    # the tree's form carries the scope ``gdn_scan`` and none of these
    tree = jax.jit(G.gdn_chunked_scan).lower(*inputs).compile().as_text()
    assert set(gs._scope_of_ops(tree).values()) == {"other"}


def test_it_runs_at_a_tiny_size_and_refuses_to_time_a_cpu(
        gs, tmp_path, monkeypatch, capsys):
    argv = ["gdn_scan.py", "--tokens", "128", "--heads", "2,8,16", "--reps",
            "1", "--only", "tree,rows+fused/64", "--out", str(tmp_path)]
    monkeypatch.setattr(sys, "argv", argv)
    assert gs.main() == 1
    assert "expected a tpu" in capsys.readouterr().err
    monkeypatch.setattr(sys, "argv", argv + ["--expect-platform", "cpu"])
    assert gs.main() == 0
    rows = json.load(open(tmp_path / "rows.json"))
    assert [r["variant"] for r in rows] == ["tree", "rows+fused/64"]
    assert all(r["ok"] and r["T"] == 128 for r in rows)
