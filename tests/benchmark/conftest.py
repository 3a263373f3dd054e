"""What a new entry into the program brings to the benchmark's tests beside
its file: `test_perfbench_launch.py` holds, by entry, how many array leaves
one launch hands the compiled program (`HANDED_OVER`). An entry added as a
file cannot add its row to that table, so its file states the count itself
(`handed_over(param_leaves)`), and this fixture lays every such entry's
count beside the table's own rows before a test of that module runs. No row
that is there is changed (asserted), and a row is added only for an entry
that a cell of BENCHMARK.json runs. A stop-gap: the next `benchmark` PR
should have the test read `handed_over` from the entry's file and delete
this conftest."""
from __future__ import annotations

import pytest

from perfbench import cells


def _entries_of_the_cells() -> set[str]:
    """The entries that some cell of BENCHMARK.json runs through its mix."""
    benchmark = cells.load_json(cells.ROOT / "BENCHMARK.json")
    return {
        cells.load_json(
            cells.HERE / "traffic" / f"{cell['traffic']}.json")["entry"]
        for cell in benchmark["workloads"]}


_LAID = set()  # (test module, entry) rows this fixture added, once each


@pytest.fixture(autouse=True)
def _entries_state_what_they_hand_over(request):
    table = getattr(request.module, "HANDED_OVER", None)
    if table is not None:
        before = {k: v for k, v in table.items()
                  if (request.module.__name__, k) not in _LAID}
        for path in sorted((cells.HERE / "entries").glob("*.py")):
            if (request.module.__name__, path.stem) in _LAID:
                continue
            count = getattr(cells.load_module(path), "handed_over", None)
            if count is None:
                continue
            assert path.stem not in table, (
                f"{path.name} states handed_over, but the table of "
                f"{request.module.__name__} has that entry's row already")
            assert path.stem in _entries_of_the_cells(), (
                f"{path.name} states handed_over and no cell of "
                "BENCHMARK.json runs that entry")
            table[path.stem] = count
            _LAID.add((request.module.__name__, path.stem))
        assert all(table[k] is v for k, v in before.items())
    yield
