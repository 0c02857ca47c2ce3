"""BENCHMARK.json against the benchmark's contract, and every name in it
resolving to its files."""
import json
import os
import re

import pytest

import bench
from fixtures import ROOT

SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "perfbench/run.py"]
    assert SPEC["paths"] == ["perfbench"]
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries_have_just_their_keys(section):
    names = [e["name"] for e in SPEC[section]]
    assert len(names) == len(set(names))
    for e in SPEC[section]:
        extra = set(e) - KEYS[section]
        assert set(e) >= KEYS[section] and extra <= {"workloads"}, e
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in (
                "lower", "higher"), e
        for k in ("why", "layer", "source"):
            if k in e:
                assert 1 <= len(e[k]) <= 200 and "\n" not in e[k] \
                    and "\t" not in e[k], e


def test_run_seconds_fit_the_check_with_24_cells():
    rs = SPEC["run_seconds"]
    assert 1 <= rs <= 51
    runs = 2 + 14 * 24
    assert runs * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_bounds_and_sources():
    names = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in names
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in names
        if m["name"].endswith("_roofline") or "_roofline." in m["name"]:
            assert m["unit"] == "%"


def test_every_cell_resolves_to_its_files():
    cfgs = {c["name"]: c for c in SPEC["configs"]}
    used = set()
    pairs = set()
    for w in SPEC["workloads"]:
        assert w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        c = cfgs[w["config"]]
        assert c["file"].startswith("perfbench/")
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert os.path.exists(os.path.join(ROOT, cfg["reference"]))
        tr = json.load(open(os.path.join(
            ROOT, "perfbench", "traffic", w["traffic"] + ".json")))
        assert os.path.exists(os.path.join(
            ROOT, "perfbench", "drivers", tr["driver"] + ".py"))
        lim = json.load(open(os.path.join(
            ROOT, "perfbench", "limits", w["name"] + ".json")))
        assert lim["checks"]
    assert used == set(cfgs)
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(SPEC["workloads"]) // 2)


def test_every_cell_reports_setup_another_e2e_and_a_layer_metric():
    for w in SPEC["workloads"]:
        e2e = [m["name"] for m in SPEC["end_to_end"]
               if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        layer = [m for m in SPEC["per_layer"]
                 if w["name"] in m.get("workloads", [w["name"]])]
        assert layer, w["name"]
        for m in layer:
            assert m["moves"] in e2e, (w["name"], m["name"])


def test_every_layer_metric_has_a_reader():
    for m in SPEC["per_layer"]:
        assert os.path.exists(bench.reader_path(
            os.path.join(ROOT, "perfbench"), m["name"])), m["name"]
