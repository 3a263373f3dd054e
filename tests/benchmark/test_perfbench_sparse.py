"""The readers of sparse attention's metrics (`indexer_ms`, `select_ms`,
`indexer_loss_ms` and the two roofline shares on a by-scope table written by
hand, `selected_tile_share` on recorded spans), and the counts of
`keye-vl2-30b-a3b-ep8-2st` against the arithmetic of its cut."""
from __future__ import annotations

import jax
import pytest

from perfbench import cells, harness, trace
from perfbench.window import Window
from vantage6_tpu.runtime.tracing import TRACER

CELL = cells.load_cell("keye.sparse16k-1chip")
BLOCK = "jit(_round)/local_train/vmap(jvp(jit(layer_block)))"
BACK = "jit(_round)/local_train/vmap(transpose(jvp(jit(layer_block))))"
# own seconds of each path over two traced rounds
TABLE = {
    f"{BLOCK}/indexer/dot_general": 0.010,
    f"{BLOCK}/while/body/closed_call/indexer/pallas_call": 0.200,
    f"{BACK}/transpose(jvp(indexer))/dot_general": 0.004,
    f"{BLOCK}/while/body/closed_call/select/reduce_sum": 0.060,
    f"{BLOCK}/indexer_loss/indexer_loss/while/body/dot_general": 0.040,
    f"{BACK}/checkpoint/indexer_loss/indexer_loss/while/body/add": 0.020,
    f"{BLOCK}/attention/while/body/dot_general": 0.300,
    f"{BACK}/checkpoint/attention/while/body/dot_general": 0.500,
    trace.NO_SCOPE: 0.012,
}
# ms a round
EXPECTED = {"indexer_ms": 107.0, "select_ms": 30.0, "indexer_loss_ms": 30.0}


def _reader(metric):
    return cells.load_module(cells.HERE / "metrics" / f"{metric}.py").read


def _run(table, window_from=trace.FROM_MARKS, peaks=None):
    reduced = trace.Reduced(
        n_devices=1, window_s=2.0, window_from=window_from, busy_s=1.2,
        collective_s=0.0, collective_exposed_s=0.0, device_ops=[],
        idle_gaps=[], scopes={path: {"s": s} for path, s in table.items()})
    window = Window(elapsed_s=2.0, rounds=2, dispatch_s=[1.0, 1.0],
                    rounds_per_dispatch=1)
    return harness.Run(cell=CELL, setup_s=0.0, window=window,
                       window_compiles=0, flops_per_round=0.0,
                       min_bytes_per_round=None, peaks=peaks,
                       traced_rounds=2, trace=reduced)


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_each_reads_its_rows_per_traced_round(metric):
    assert _reader(metric)(_run(TABLE)) == pytest.approx(EXPECTED[metric])
    assert _reader(metric)(_run(TABLE, window_from=trace.FROM_OPS)) is None
    scope = metric.removesuffix("_ms")
    left = {path: s for path, s in TABLE.items()
            if f"/{scope}/" not in path and f"({scope})" not in path}
    assert _reader(metric)(_run(left)) is None


@pytest.mark.parametrize("metric,scope,counts", [
    ("indexer_roofline_share", "indexer", "indexer_flops"),
    ("sparse_attention_roofline_share", "attention", "sparse_attention_flops"),
])
def test_a_share_is_its_counts_over_its_scopes_time_and_the_peak(
        metric, scope, counts):
    peaks = cells.peaks_of("TPU v5 lite")
    run = _run(TABLE, peaks=peaks)
    flops = getattr(CELL.reference_module(), counts)(CELL.config, CELL.traffic)
    per_round_s = sum(s for path, s in TABLE.items()
                      if f"/{scope}/" in path or f"({scope})" in path) / 2
    assert _reader(metric)(run) == pytest.approx(
        100 * flops / (per_round_s * peaks["bf16_flops"]))
    assert _reader(metric)(_run(TABLE)) is None  # no peaks off the chip


def test_selected_tile_share_reads_the_windows_span(monkeypatch):
    attrs = {"rounds": 2, "tiles_selected_per_layer": [500.0, 400.0],
             "tiles_visible_per_layer": 1056, "selected_tile_share": 0.4261}
    spans = [{"name": "sparse.tiles", "attrs": {**attrs, "rounds": 3}},
             {"name": "sparse.tiles", "attrs": attrs}]
    monkeypatch.setattr(TRACER, "drain", lambda trace_id=None: list(spans))
    read = _reader("selected_tile_share")
    assert read(_run(TABLE)) == 0.4261
    spans.reverse()  # the last span is of other rounds than the window's
    assert read(_run(TABLE)) is None
    spans.clear()
    assert read(_run(TABLE)) is None


# ------------------------------------------------------------------ counts
def test_the_cut_holds_the_parameters_its_arithmetic_says():
    """Per layer q/k/v 10.49 M, o 8.39 M, the indexer 2.26 M, the router
    0.26 M and 16 experts of 4.72 M; four layers and a head and embedding
    over 18,992 rows: 465.4 M parameters, 11.17 GB at 24 bytes."""
    c = CELL.config
    shapes = jax.eval_shape(lambda: CELL.reference_module().make_params(
        c, jax.random.key(0)))
    layer = sum(x.size for x in jax.tree.leaves(shapes["layers"][0]))
    total = sum(x.size for x in jax.tree.leaves(shapes))
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"],
            c["moe_intermediate_size"], c["num_experts_per_tok"],
            c["num_local_experts"]) == (2048, 32, 4, 128, 768, 8, 128)
    assert layer == pytest.approx(96.9e6, rel=1e-3)
    assert total == pytest.approx(465.4e6, rel=1e-3)
    assert 24 * total == pytest.approx(11.17e9, rel=1e-3)


def test_the_selected_pairs_by_hand():
    """A query keeps min(2048, t + 1) keys: 2,098,176 pairs for the first
    2,048 queries, 29,360,128 for the rest of the 16,384."""
    mod = CELL.reference_module()
    assert mod.selected_pairs(16384, 2048) == 2_098_176 + 29_360_128
    assert mod.selected_pairs(32, 64) == mod.visible_pairs(32) == 528
    per_pass = 4 * 32 * 128 * 2 * 4 * (2_098_176 + 29_360_128)
    assert mod.sparse_attention_flops(CELL.config, CELL.traffic) == (
        4 * per_pass)
