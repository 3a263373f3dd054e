"""BENCHMARK.json against the files it names, and the harness against the
rule that it names no cell, configuration or metric in its code."""
from __future__ import annotations

import re
from pathlib import Path

import pytest

from perfbench import cells
from perfbench_tiny import BENCHMARK, CELL_NAMES, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
ALL_METRICS = BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]


def _cells_of(metric):
    return metric.get("workloads", CELL_NAMES)


@pytest.mark.parametrize("name", CELL_NAMES)
def test_cell_resolves_to_files(name):
    cell = cells.load_cell(name)
    assert cell.reference_module().make_inputs
    assert cell.entry_module().build
    assert getattr(cell.reference_module(), cell.traffic["reference"])
    for key in ("entry", "reference", "control_precision",
                "follow_dispatches", "trace_dispatches", "why"):
        assert key in cell.traffic, key
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for number, limit in cell.limits.items():
        assert limit is None or limit >= 0, number


@pytest.mark.parametrize("metric", ALL_METRICS, ids=lambda m: m["name"])
def test_metric_has_a_reader_and_cells_that_exist(metric):
    assert NAME.match(metric["name"])
    assert (cells.HERE / "metrics" / f"{metric['name']}.py").is_file()
    assert callable(cells.load_module(
        cells.HERE / "metrics" / f"{metric['name']}.py").read)
    assert set(_cells_of(metric)) <= set(CELL_NAMES)


@pytest.mark.parametrize("metric", BENCHMARK["per_layer"],
                         ids=lambda m: m["name"])
def test_moves_names_an_end_to_end_metric_each_of_its_cells_reports(metric):
    moved = next(m for m in BENCHMARK["end_to_end"]
                 if m["name"] == metric["moves"])
    assert set(_cells_of(metric)) <= set(_cells_of(moved))


def test_contract_shape():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCHMARK["run_seconds"] <= 51
    four = [w for w in BENCHMARK["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELL_NAMES) // 4)
    assert {w["chips"] for w in BENCHMARK["workloads"]} <= {1, 4}
    for w in BENCHMARK["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
    for c in BENCHMARK["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert any(w["config"] == c["name"] for w in BENCHMARK["workloads"])
        held = cells.load_json(ROOT / c["file"])
        assert held["reduced"] == c["reduced"]
    for m in BENCHMARK["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    layers = (ROOT / "PERF.md").read_text()
    for m in BENCHMARK["per_layer"]:
        assert m["layer"] in layers, m["layer"]


def test_the_harness_names_no_cell_configuration_or_metric():
    names = set(CELL_NAMES)
    names |= {c["name"] for c in BENCHMARK["configs"]}
    names |= {w["traffic"] for w in BENCHMARK["workloads"]}
    names |= {m["name"] for m in ALL_METRICS} - {"setup_s"}
    for path in cells.HERE.glob("*.py"):
        code = path.read_text()
        for name in names:
            assert name not in code, f"{path.name} names {name}"


def test_no_tpu_topology_is_described_on_import():
    for path in list(cells.HERE.rglob("*.py")) + list(
            Path(__file__).parent.glob("*.py")):
        if path.name == Path(__file__).name:
            continue
        assert "get_topology_desc" not in path.read_text(), path
