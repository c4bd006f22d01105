"""BENCHMARK.json against the files: every file it names exists, every name
and unit keeps to the driver's characters, every per-layer metric has its
reader, every cell its traffic file."""
import importlib
import json
import os
import re

from conftest import ROOT
from benchmarks.common import reader_module

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_names():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in m[g]]
    assert all(NAME.match(n) for n in names), names
    metric_names = [x["name"] for g in ("end_to_end", "per_layer") for x in m[g]]
    assert len(set(metric_names)) == len(metric_names)
    for g in ("end_to_end", "per_layer"):
        for x in m[g]:
            assert UNIT.match(x["unit"]) and x["better"] in ("lower", "higher")
    assert any(x["name"] == "setup_s" for x in m["end_to_end"])
    assert all(0 < x["bound"] <= 0.1 for x in m["end_to_end"])
    assert 1 <= m["run_seconds"] <= 51


def test_files_exist():
    m = manifest()
    configs = {c["name"]: c for c in m["configs"]}
    for c in m["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in m["paths"]))
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        for part in ("models", "reference"):
            assert os.path.exists(os.path.join(
                ROOT, "benchmarks", part, cfg["family"] + ".py"))
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "drivers", cfg["entry"] + ".py"))
    for w in m["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "traffic", w["traffic"] + ".json"))


def test_metrics_wired():
    m = manifest()
    e2e = {x["name"] for x in m["end_to_end"]}
    cells = {w["name"] for w in m["workloads"]}
    for x in m["per_layer"]:
        assert x["moves"] in e2e
        assert set(x.get("workloads", cells)) <= cells
        mod = importlib.import_module(reader_module(x["name"]))
        assert callable(mod.read)
    for w in cells:
        mine = [x for x in m["end_to_end"]
                if w in x.get("workloads", cells)]
        assert len(mine) >= 2
        assert any(w in x.get("workloads", cells) for x in m["per_layer"])
