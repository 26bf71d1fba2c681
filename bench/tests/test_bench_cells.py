"""BENCHMARK.json keeps to its contract, and every cell finds its files
by name: configuration, reference, runner, traffic, limits and the
reader of each per-layer metric it reports."""
from __future__ import annotations

import json
import re

import pytest

from bench_tiny import ROOT
from harness import cells

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WORKLOADS = [w["name"] for w in SPEC["workloads"]]
WIDTHS = re.compile(r"(_dim|_rank)$|^(hidden_size|intermediate_size|"
                    r"head_dim|num_experts_per_tok)$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    assert SPEC["command"][1].startswith(SPEC["paths"][0] + "/")


def test_names_units_and_keys():
    names = []
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        names.append(m["name"])
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
        assert m["source"] in ("host_clock", "device_trace", "program_span",
                               "program_counter")
    assert len(names) == len(set(names))
    assert "setup_s" in names
    assert len(set(WORKLOADS)) == len(WORKLOADS)
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_per_layer_cells_report_what_they_move():
    for m in SPEC["per_layer"]:
        for w in m.get("workloads", WORKLOADS):
            assert m["moves"] in cells.load_cell(w).end_to_end


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_files_load_by_name(workload):
    cell = cells.load_cell(workload)
    assert cell.chips in (1, 4)
    assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
    assert cell.per_layer
    assert hasattr(cells.runner(cell), "run")
    ref = cells.reference(cell.config)
    assert ref.__doc__
    for name in cell.per_layer:
        assert callable(cells.metric_reader(name).read)


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda c: c["name"])
def test_config_entries(entry):
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"]
    assert cfg["source"] == entry["source"]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    assert not any(WIDTHS.search(k) for k in entry["reduced"])
    for key, value in cfg.get("published", {}).items():
        assert key in entry["reduced"] and cfg[key] != value
