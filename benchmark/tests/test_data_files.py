"""Every data file loads and BENCHMARK.json keeps to the contract's
names, units and cross-references."""

import glob
import importlib
import json
import os
import re

import pytest

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
FILE = re.compile(r"^[A-Za-z0-9_.\-/]+$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_json_under_benchmark_loads():
    files = [p for d in ("configs", "traffic", "layer_metrics")
             for p in glob.glob(os.path.join(BENCH, d, "*.json"))]
    assert len(files) >= 15
    for p in files + [os.path.join(BENCH, "peaks.json")]:
        with open(p) as f:
            assert isinstance(json.load(f), dict), p


def test_file_names_use_only_the_allowed_characters():
    for dirpath, dirs, names in os.walk(BENCH):
        dirs[:] = [d for d in dirs if d not in ("__pycache__", ".trace")]
        for n in names:
            rel = os.path.relpath(os.path.join(dirpath, n), ROOT)
            assert FILE.match(rel), rel


def test_benchmark_json_names_units_and_keys():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    names = set()
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert m["name"] not in names
        names.add(m["name"])
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in b["end_to_end"])
    assert len(b["end_to_end"]) <= 5
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["why"]) <= 200
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            data = json.load(f)
        assert data["reduced"] == c["reduced"]
        importlib.import_module(f"runners.{data['runner']}")
        importlib.import_module(f"families.{data['family']}")
    cfgs = {c["name"] for c in b["configs"]}
    seen = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        assert os.path.exists(os.path.join(
            BENCH, "traffic", w["traffic"] + ".json"))
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= 1


def test_per_layer_metrics_have_readers_and_move_a_reported_metric():
    b = _bench()
    cells = [w["name"] for w in b["workloads"]]
    e2e = {m["name"]: m for m in b["end_to_end"]}

    def reported(metric, cell):
        return "workloads" not in metric or cell in metric["workloads"]

    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        with open(os.path.join(BENCH, "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        importlib.import_module(f"readers.{spec['reader']}")
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        for cell in cells:
            if reported(m, cell):
                assert reported(e2e[m["moves"]], cell), (m["name"], cell)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for cell in cells:
        assert any(reported(m, cell) for m in b["per_layer"])
        assert sum(reported(m, cell) for m in b["end_to_end"]) >= 2


def test_unknown_device_kind_is_an_error():
    import flops
    assert flops.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks_for("TPU v9")
    with pytest.raises(KeyError):
        flops.peaks_for("_source")
