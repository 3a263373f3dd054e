"""BENCHMARK.json against the files it names, the harness against the rule
that it names no cell, configuration, mix, metric or configuration key in
its code, and the tests' tiny sizes against the rule that they are files
found by name."""
from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

import perfbench_tiny
from perfbench import cells
from perfbench_tiny import BENCHMARK, CELL_NAMES, ROOT, tiny_cell

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
    """Nor a mix or a configuration's key (its sizes: the numbers of its
    file), in the harness or in the code that cuts the cells for the tests:
    a new cell arrives as files."""
    names = set(CELL_NAMES)
    names |= {c["name"] for c in BENCHMARK["configs"]}
    names |= {w["traffic"] for w in BENCHMARK["workloads"]}
    names |= {m["name"] for m in ALL_METRICS} - {"setup_s"}
    for c in BENCHMARK["configs"]:
        names |= {key for key, value in cells.load_json(ROOT / c["file"]).items()
                  if isinstance(value, (int, float))}
    code_files = list(cells.HERE.glob("*.py")) + [Path(perfbench_tiny.__file__)]
    for path in code_files:
        code = path.read_text()
        for name in names:
            assert name not in code, f"{path.name} names {name}"


# ------------------------------------------------- the tests' tiny sizes
@pytest.mark.parametrize("name", CELL_NAMES)
def test_a_tiny_cell_is_the_shipped_cell_with_its_overlays_laid_over(name):
    shipped, tiny = cells.load_cell(name), tiny_cell(name)
    entry = next(w for w in BENCHMARK["workloads"] if w["name"] == name)
    for kind, whose, key, before, after in (
            ("config", entry["config"], "sizes", shipped.config, tiny.config),
            ("traffic", entry["traffic"], "sizes", shipped.traffic,
             tiny.traffic),
            ("limits", name, "limits", shipped.limits, tiny.limits)):
        held = cells.load_json(perfbench_tiny.TINY / f"{kind}.{whose}.json")
        assert held["how"] and held[key], (kind, whose)
        assert after == {**before, **held[key]}
        assert list(after) == list(before)       # no key added, none moved


def test_an_overlay_key_the_shipped_file_lacks_is_an_error(tmp_path,
                                                           monkeypatch):
    monkeypatch.setattr(perfbench_tiny, "TINY", tmp_path)
    (tmp_path / "config.some.json").write_text(json.dumps(
        {"how": "a renamed key", "sizes": {"width": 8, "depth_renamed": 2}}))
    shipped = {"width": 1024, "depth": 24}
    with pytest.raises(KeyError, match="depth_renamed"):
        perfbench_tiny.lay_over(shipped, "config", "some", "sizes")
    assert shipped == {"width": 1024, "depth": 24}  # nothing half laid over
    (tmp_path / "config.some.json").write_text(json.dumps(
        {"how": "the keys it has", "sizes": {"width": 8}}))
    perfbench_tiny.lay_over(shipped, "config", "some", "sizes")
    assert shipped == {"width": 8, "depth": 24}


def test_a_missing_overlay_names_the_file_to_add(tmp_path, monkeypatch):
    monkeypatch.setattr(perfbench_tiny, "TINY", tmp_path)
    with pytest.raises(FileNotFoundError) as e:
        tiny_cell(CELL_NAMES[0])
    config = next(w for w in BENCHMARK["workloads"]
                  if w["name"] == CELL_NAMES[0])["config"]
    assert str(tmp_path / f"config.{config}.json") in str(e.value)


def test_no_tpu_topology_is_described_on_import():
    for path in list(cells.HERE.rglob("*.py")) + list(
            Path(__file__).parent.glob("*.py")):
        if path.name == Path(__file__).name:
            continue
        assert "get_topology_desc" not in path.read_text(), path
