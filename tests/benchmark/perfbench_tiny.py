"""Cells cut to a size the CPU holds, for the tests of the benchmark."""
from __future__ import annotations

import json
from pathlib import Path

from perfbench import cells

ROOT = Path(__file__).resolve().parents[2]
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL_NAMES = [w["name"] for w in BENCHMARK["workloads"]]

TINY_CONFIG = dict(n_embd=64, n_layer=2, n_head=4, n_positions=32, n_ctx=32,
                   vocab_size=97, rows_per_station=512, n_features=10)
TINY_TRAFFIC = dict(seq_len=32, batch=2, batch_size=64, local_steps=2)
# the cells' own limits are for their own size on the chip; at the tiny size
# the same rule gives these (data/tiny_limits.json says how)
TINY_LIMITS = json.loads(
    (Path(__file__).parent / "data" / "tiny_limits.json").read_text())


def tiny_cell(name: str) -> cells.Cell:
    """The cell as shipped (entry, reference, limits, layout), with only the
    sizes cut. Keys a configuration does not have are not added."""
    cell = cells.load_cell(name)
    cell.config.update(
        {k: v for k, v in TINY_CONFIG.items() if k in cell.config})
    cell.traffic.update(
        {k: v for k, v in TINY_TRAFFIC.items() if k in cell.traffic})
    cell.limits = TINY_LIMITS[name]
    return cell
