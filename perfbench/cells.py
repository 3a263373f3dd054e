"""Find a cell's files by the names in `BENCHMARK.json`.

A cell names a configuration and a traffic mix; a configuration is
`configs/<name>.json` with its plain reference `configs/<name>.py` beside it;
a traffic mix is `traffic/<name>.json`, which names the entry into the program
(`entries/<entry>.py`) and the reference function it is held against; a
cell's limits are `limits/<cell>.json`; a metric's reader is
`metrics/<name>.py`. Nothing here knows any of those names.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load_module(path: Path) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"no such file: {path}")
    name = "perfbench._found." + "".join(
        c if c.isalnum() else "_" for c in str(path.relative_to(HERE)))
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict[str, Any]
    traffic: dict[str, Any]
    limits: dict[str, float | None]
    end_to_end: list[dict[str, Any]]
    per_layer: list[dict[str, Any]]

    def reference_module(self) -> ModuleType:
        return load_module(HERE / "configs" / f"{self.config_name}.py")

    def entry_module(self) -> ModuleType:
        return load_module(HERE / "entries" / f"{self.traffic['entry']}.py")


def _reported_in(metric: dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, benchmark: dict[str, Any] | None = None) -> Cell:
    bench = benchmark or load_json(ROOT / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(
            f"no workload {name!r} in BENCHMARK.json (it has "
            f"{[w['name'] for w in bench['workloads']]})")
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    return Cell(
        name=name,
        chips=entry["chips"],
        config_name=config["name"],
        config=load_json(ROOT / config["file"]),
        traffic=load_json(HERE / "traffic" / f"{entry['traffic']}.json"),
        limits=load_json(HERE / "limits" / f"{name}.json")["limits"],
        end_to_end=[m for m in bench["end_to_end"] if _reported_in(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reported_in(m, name)],
    )


def read_metrics(metrics: list[dict[str, Any]], run: Any) -> dict[str, Any]:
    """Each metric through its own reader; one that finds nothing to read is
    left out of the line."""
    out = {}
    for metric in metrics:
        reader = load_module(HERE / "metrics" / f"{metric['name']}.py")
        value = reader.read(run)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def peaks_of(device_kind: str) -> dict[str, Any]:
    table = load_json(HERE / "peaks.json")
    if device_kind not in table:
        raise ValueError(
            f"no peaks recorded for device_kind {device_kind!r} (known: "
            f"{sorted(table)}): add its published peaks and their source to "
            "perfbench/peaks.json")
    return table[device_kind]
