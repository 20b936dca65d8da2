"""BENCHMARK.json and the data files it names.

A cell names a configuration and a traffic mix; the harness finds
``configs/<file>``, ``traffic/<mix>.json``, ``cells/<cell>.json`` and
``layer_metrics/<metric>.json`` by those names, so a later PR adds
files and entries and edits none.  ``validate`` checks the contract's
rules that a CPU can check.
"""

import json
import os
import re

from paths import MANIFEST, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
# a key of ``reduced`` that names a width: never allowed
WIDTH = re.compile(r"_dim$|_rank$|^(hidden|intermediate|moe_intermediate|"
                   r"ffn_hidden|expert)_size$|experts_per_tok|expan|"
                   r"(latent|state|proj)\w*_size|^d_(model|state|inner)$")


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


class Manifest:
    """One BENCHMARK.json, with lookups by name.  ``root`` is the
    directory the manifest's relative paths start from."""

    def __init__(self, path: str = MANIFEST):
        self.path = os.path.abspath(path)
        self.root = os.path.dirname(self.path)
        self.data = load_json(self.path)

    def resolve(self, rel: str) -> str:
        """A path from the root of the repo: beside the manifest first
        (the rehearsal's and the tests' own data), else in the harness's
        checkout."""
        for base in (self.root, ROOT):
            path = os.path.join(base, rel)
            if os.path.exists(path):
                return path
        return path

    def find(self, kind: str, name: str) -> str:
        """``kbench/<kind>/<name>.json``, by ``resolve``."""
        return self.resolve(os.path.join("kbench", kind, name + ".json"))

    def cell(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in {self.path}")

    def config_path(self, name: str) -> str:
        for c in self.data["configs"]:
            if c["name"] == name:
                return os.path.join(self.root, c["file"])
        raise KeyError(f"no config {name!r} in {self.path}")

    def config(self, name: str) -> dict:
        """The configuration's file, with its ``name`` and the plain
        reference it names as an absolute path (``reference_file``)."""
        cfg = load_json(self.config_path(name))
        cfg["name"] = name
        cfg["reference_file"] = self.resolve(cfg.get("reference", ""))
        return cfg

    def traffic(self, name: str) -> dict:
        return load_json(self.find("traffic", name))

    def cell_settings(self, name: str) -> dict:
        """Per-cell numbers found on the chip (the rate fixed from the
        knee sweep); a cell with none has an empty file or no file."""
        path = self.find("cells", name)
        return load_json(path) if os.path.exists(path) else {}

    def metrics_for(self, cell: str, group: str) -> list:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
        return [m for m in self.data[group]
                if "workloads" not in m or cell in m["workloads"]]

    def layer_metric(self, name: str) -> dict:
        return load_json(self.find("layer_metrics", name))


def validate(m: Manifest) -> list:
    """Every breach of the contract that can be seen without a chip, as
    a list of sentences (empty when the manifest is sound)."""
    d, bad = m.data, []
    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(d) != want:
        bad.append(f"top-level keys {sorted(d)} != {sorted(want)}")
        return bad
    if not (isinstance(d["run_seconds"], int) and 1 <= d["run_seconds"] <= 51):
        bad.append(f"run_seconds {d['run_seconds']!r} not a whole number 1..51")
    paths = d["paths"]
    for word in d["command"]:
        if word.startswith("/") or ".." in word.split("/"):
            bad.append(f"command word {word!r} leaves the repo")
        if "/" in word and not any(
                word == p or word.startswith(p.rstrip("/") + "/")
                for p in paths):
            bad.append(f"command word {word!r} names a file outside paths")

    def names(items, what):
        seen = set()
        for it in items:
            n = it.get("name", "")
            if not NAME.match(n):
                bad.append(f"{what} name {n!r} is not a name")
            if n in seen:
                bad.append(f"{what} name {n!r} appears twice")
            seen.add(n)
        return seen

    cfgs = names(d["configs"], "config")
    cells = names(d["workloads"], "workload")
    names(d["end_to_end"] + d["per_layer"], "metric")
    files = set()
    for c in d["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config {c.get('name')}: keys {sorted(c)}")
            continue
        if not any(c["file"].startswith(p.rstrip("/") + "/") for p in paths):
            bad.append(f"config file {c['file']} is not under paths")
        if c["file"] in files:
            bad.append(f"config file {c['file']} used twice")
        files.add(c["file"])
        for key in c["reduced"]:
            if WIDTH.search(key):
                bad.append(f"config {c['name']}: reduced names a width, {key}")
        if not os.path.exists(os.path.join(m.root, c["file"])):
            bad.append(f"config file {c['file']} does not exist")
            continue
        ref = load_json(os.path.join(m.root, c["file"])).get("reference")
        if not isinstance(ref, str) or not ref.endswith(".py"):
            bad.append(f"config {c['name']}: reference {ref!r} is not a "
                       "Python file's path")
        elif ref.startswith("/") or ".." in ref.split("/") or not any(
                ref.startswith(p.rstrip("/") + "/") for p in paths):
            bad.append(f"config {c['name']}: reference {ref} is not under "
                       "paths")
        elif not os.path.isfile(m.resolve(ref)):
            bad.append(f"config {c['name']}: reference {ref} does not exist")
    used, pairs, four = set(), set(), 0
    for w in d["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"workload {w.get('name')}: keys {sorted(w)}")
            continue
        if w["config"] not in cfgs:
            bad.append(f"workload {w['name']}: unknown config {w['config']}")
        used.add(w["config"])
        if not NAME.match(w["traffic"]):
            bad.append(f"workload {w['name']}: traffic {w['traffic']!r}")
        elif not os.path.exists(m.find("traffic", w["traffic"])):
            bad.append(f"workload {w['name']}: no traffic file "
                       f"{w['traffic']}.json")
        if (w["config"], w["traffic"]) in pairs:
            bad.append(f"pair {w['config']}/{w['traffic']} appears twice")
        pairs.add((w["config"], w["traffic"]))
        if w["chips"] not in (1, 4):
            bad.append(f"workload {w['name']}: chips {w['chips']}")
        four += w["chips"] == 4
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"] or "\t" in w["why"]:
            bad.append(f"workload {w['name']}: why is not one line of 1..200")
    if cfgs - used:
        bad.append(f"configs used by no cell: {sorted(cfgs - used)}")
    if four > max(1, len(d["workloads"]) // 4):
        bad.append(f"{four} four-chip cells of {len(d['workloads'])}")

    e2e = {x["name"]: x for x in d["end_to_end"]}
    if "setup_s" not in e2e:
        bad.append("no setup_s among end_to_end")
    for x in d["end_to_end"]:
        extra = set(x) - {"name", "unit", "better", "bound", "source",
                          "workloads"}
        if extra or not {"name", "unit", "better", "bound", "source"} <= set(x):
            bad.append(f"metric {x.get('name')}: keys {sorted(x)}")
            continue
        if not 0.01 <= x["bound"] <= 0.1:
            bad.append(f"metric {x['name']}: bound {x['bound']}")
        if x["source"] not in ("host_clock", "device_trace"):
            bad.append(f"metric {x['name']}: source {x['source']}")
    for x in d["per_layer"]:
        extra = set(x) - {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        if extra or not {"name", "unit", "better", "source", "layer",
                         "moves"} <= set(x):
            bad.append(f"metric {x.get('name')}: keys {sorted(x)}")
            continue
        if x["source"] not in SOURCES:
            bad.append(f"metric {x['name']}: source {x['source']}")
        if x["moves"] not in e2e:
            bad.append(f"metric {x['name']} moves unknown {x['moves']}")
            continue
        for cell in x.get("workloads", sorted(cells)):
            moved = e2e[x["moves"]]
            if "workloads" in moved and cell not in moved["workloads"]:
                bad.append(f"metric {x['name']} moves {x['moves']}, which "
                           f"cell {cell} does not report")
        if not os.path.exists(m.find("layer_metrics", x["name"])):
            bad.append(f"metric {x['name']}: no layer_metrics file")
    for x in d["end_to_end"] + d["per_layer"]:
        if not UNIT.match(str(x.get("unit", ""))):
            bad.append(f"metric {x.get('name')}: unit {x.get('unit')!r}")
        if x.get("better") not in ("lower", "higher"):
            bad.append(f"metric {x.get('name')}: better {x.get('better')!r}")
        for cell in x.get("workloads", []):
            if cell not in cells:
                bad.append(f"metric {x.get('name')}: unknown cell {cell}")
    for cell in cells:
        mine = [x["name"] for x in m.metrics_for(cell, "end_to_end")]
        if "setup_s" not in mine or len(mine) < 2:
            bad.append(f"cell {cell} reports {mine}: needs setup_s and one more")
        if not m.metrics_for(cell, "per_layer"):
            bad.append(f"cell {cell} reports no per-layer metric")
    if os.path.getsize(m.path) > 64 * 1024:
        bad.append("BENCHMARK.json is over 64 KiB")
    return bad
