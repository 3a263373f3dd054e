"""The two readers of the program's own spans, on each cell at a tiny size.
The CPU has no device plane, so the cells run untraced (which calls no
per-layer reader) and each reader is then handed a `harness.Run` built from
that run's window."""
from __future__ import annotations

import functools

import jax
import pytest

from perfbench import cells, harness
from perfbench.window import Window
from perfbench_tiny import CELL_NAMES, tiny_cell
from vantage6_tpu.runtime.tracing import TRACER

SEED = 2**31 + 54321
METRICS = ["launch_host_ms", "launch_buffers"]
# the array leaves each entry hands its compiled program, from the leaves of
# the parameters: Adam's two moments and its count, tokens and mask beside
# them; the empty sgd state, x, y, counts, mask and key
HANDED_OVER = {"fed_transformer_round": lambda p: 3 * p + 3,
               "fedavg_run_rounds": lambda p: p + 5}


def _reader(metric):
    return cells.load_module(cells.HERE / "metrics" / f"{metric}.py").read


def _run_of(cell, result) -> harness.Run:
    n = result["dispatches"]["n"]
    window = Window(
        elapsed_s=result["metrics"]["round_ms"]["value"] / 1e3
        * result["rounds"],
        rounds=result["rounds"], dispatch_s=result["dispatches"]["each_s"],
        rounds_per_dispatch=result["rounds"] // n)
    return harness.Run(cell=cell, setup_s=result["metrics"]["setup_s"]["value"],
                       window=window, window_compiles=0, flops_per_round=0.0,
                       min_bytes_per_round=None, peaks=None)


@functools.cache
def _traced_run(name):
    """One untraced run of the tiny cell with the tracer on, and the spans
    it left."""
    TRACER.configure(enabled=True, sample=1.0)
    TRACER.clear()
    cell = tiny_cell(name)
    result = harness.run_cell(cell, SEED, 0.05, trace=False,
                              require_chip=False)
    return cell, result, TRACER.drain()


@pytest.fixture
def spans_of(monkeypatch):
    """The tracer's buffer as the named cell's run left it."""
    def restore(name):
        cell, result, spans = _traced_run(name)
        monkeypatch.setattr(TRACER, "drain", lambda trace_id=None: list(spans))
        return cell, result
    return restore


@pytest.mark.parametrize("name", CELL_NAMES)
def test_launch_buffers_is_the_leaf_count_of_the_state(name, spans_of):
    cell, result = spans_of(name)
    inputs = cell.reference_module().make_inputs(
        cell.config, cell.traffic, harness.key_from_seed(SEED))
    leaves = len(jax.tree.leaves(inputs["params"]))
    expected = HANDED_OVER[cell.traffic["entry"]](leaves)
    assert _reader("launch_buffers")(_run_of(cell, result)) == expected


@pytest.mark.parametrize("name", CELL_NAMES)
def test_launch_host_ms_is_positive_and_under_the_round(name, spans_of):
    cell, result = spans_of(name)
    value = _reader("launch_host_ms")(_run_of(cell, result))
    assert 0 < value < result["metrics"]["round_ms"]["value"]


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("name", CELL_NAMES)
def test_a_buffer_that_lost_part_of_the_window_reads_nothing(
        name, metric, spans_of, monkeypatch):
    cell, result = spans_of(name)
    run = _run_of(cell, result)
    assert _reader(metric)(run) is not None
    # a window of more dispatches than the buffer holds calls
    run.window.dispatch_s = [0.0] * (len(_traced_run(name)[2]) + 1)
    assert _reader(metric)(run) is None
    # the buffer cleared
    monkeypatch.undo()
    TRACER.clear()
    assert _reader(metric)(_run_of(cell, result)) is None


@pytest.mark.parametrize("name", CELL_NAMES)
def test_with_the_tracer_off_both_read_nothing(name):
    TRACER.configure(enabled=False)
    TRACER.clear()
    try:
        cell = tiny_cell(name)
        result = harness.run_cell(cell, SEED, 0.05, trace=False,
                                  require_chip=False)
    finally:
        TRACER.configure(enabled=True, sample=1.0)
    assert result["correct"]
    run = _run_of(cell, result)
    assert [_reader(m)(run) for m in METRICS] == [None, None]
