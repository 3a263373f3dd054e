"""Cells cut to a size the CPU holds, for the tests of the benchmark.

What a cell is cut to is data, found by name like everything else of a cell:
`data/tiny/config.<configuration>.json` and `data/tiny/traffic.<mix>.json`
hold the sizes laid over the shipped files (under ``"sizes"``), and
`data/tiny/limits.<cell>.json` the limits of `correct` at that size (under
``"limits"``, with how they were set). A new cell, mix or configuration
brings its file; nothing here names one.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any

from perfbench import cells

ROOT = Path(__file__).resolve().parents[2]
TINY = Path(__file__).parent / "data" / "tiny"
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL_NAMES = [w["name"] for w in BENCHMARK["workloads"]]


def lay_over(shipped: dict[str, Any], kind: str, name: str, key: str) -> None:
    """`data/tiny/<kind>.<name>.json`'s ``key`` over ``shipped``. A missing
    file is an error that says which to add; so is a key the shipped file
    does not have, since a renamed key would leave the cell at full size."""
    path = TINY / f"{kind}.{name}.json"
    if not path.is_file():
        raise FileNotFoundError(
            f"the tests cut every cell to a size the CPU holds: add {path} "
            f'({{"how": ..., "{key}": {{...}}}}; perfbench/README.md, '
            '"Adding things")')
    over = json.loads(path.read_text())[key]
    unknown = sorted(set(over) - set(shipped))
    if unknown:
        raise KeyError(
            f"{path} names {unknown}, which the shipped {kind} {name!r} does "
            f"not have (it has {sorted(shipped)})")
    shipped.update(over)


def tiny_cell(name: str) -> cells.Cell:
    """The cell as shipped (entry, reference, layout), with only its sizes
    cut and its limits those of that size."""
    cell = cells.load_cell(name)
    entry = next(w for w in BENCHMARK["workloads"] if w["name"] == name)
    lay_over(cell.config, "config", entry["config"], "sizes")
    lay_over(cell.traffic, "traffic", entry["traffic"], "sizes")
    lay_over(cell.limits, "limits", name, "limits")
    return cell
