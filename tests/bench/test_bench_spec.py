"""BENCHMARK.json against the files the harness finds by name: every cell
names a configuration file, a traffic mix whose generator and driver
exist, a reference, and its limits; every per-layer metric has its reader;
every cell reports what the contract asks of it. This is what lets a
later change add a cell, configuration, mix or metric as data."""
import json
import re

import pytest

from bench import common

SPEC = json.loads(common.SPEC.read_text())
CELLS = {w["name"]: w for w in SPEC["workloads"]}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CHECKS = {"train": {"loss_gap", "grad1_gap", "delta_gap"},
          "serve": {"logit_gap"}}


def test_names_and_top_level():
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert set(SPEC["paths"]) == {"bench", "tests/bench"}
    assert 1 <= SPEC["run_seconds"] <= 51
    names = ([c["name"] for c in SPEC["configs"]] + list(CELLS)
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    pairs = [(w["config"], w["traffic"]) for w in CELLS.values()]
    assert len(pairs) == len(set(pairs))
    assert all(0.01 <= m["bound"] <= 0.25 for m in SPEC["end_to_end"])


@pytest.mark.parametrize("name", sorted(CELLS))
def test_cell_files_exist(name):
    cell = common.load_cell(name)
    entry = [c for c in SPEC["configs"]
             if c["name"] == CELLS[name]["config"]][0]
    assert entry["source"] == cell.config["source"]["url"]
    assert sorted(entry["reduced"]) == sorted(cell.config["reduced"])
    assert cell.kind.DRIVER in CHECKS
    assert (common.BENCH / "drivers" / f"{cell.kind.DRIVER}.py").exists()
    assert hasattr(cell.reference, "abstract")
    limits = json.loads((common.BENCH / "workloads" / f"{name}.json")
                        .read_text())["limits"]
    assert set(limits) == CHECKS[cell.kind.DRIVER]
    assert CELLS[name]["chips"] in (1, 4)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_cell_reports_metrics(name):
    cell = common.load_cell(name)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])
        mod = common.load_module(common.BENCH / "metrics"
                                 / f"{m['name']}.py")
        assert callable(mod.read)


def test_metric_entries():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= set(CELLS)
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    layers = {m["layer"] for m in SPEC["per_layer"]}
    assert all(len(x) <= 200 and "\n" not in x for x in layers)
