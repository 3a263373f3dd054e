"""The readers of the transformer block's scopes (`qkv_ms`, `attn_out_ms`,
`norms_ms`, `rotary_ms`), of the operations in no scope (`unscoped_ms`) and
of the engine's own host time (`engine_host_ms`): the first five on a
by-scope table written by hand, the last on the spans one tiny
`FedTransformer.round` records on the CPU."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from perfbench import cells, harness, trace
from perfbench.window import Window
from vantage6_tpu.runtime.tracing import TRACER
from vantage6_tpu.workloads import fed_transformer as FT

BLOCK = "jit(_round)/local_train/vmap(jvp(jit(layer_block)))"
BACK = "jit(_round)/local_train/vmap(transpose(jvp(jit(layer_block))))"
# own seconds of each path over two traced rounds
TABLE = {
    f"{BLOCK}/norms/reduce_sum": 0.010,
    f"{BLOCK}/qkv/dot_general": 0.040,
    f"{BACK}/transpose(jvp(qkv))/dot_general": 0.060,
    f"{BLOCK}/rotary/concatenate": 0.008,
    f"{BLOCK}/attn_out/add": 0.030,
    f"{BLOCK}/attn_out/norms/mul": 0.004,
    f"{BLOCK}/mlp/norms/reduce_sum": 0.006,
    f"{BLOCK}/mlp/dot_general": 0.100,
    # `jnp.linalg.norm` lowers as `jit(norm)`: no `norms` scope
    "jit(_round)/server_update/jit(norm)/sqrt": 0.002,
    trace.NO_SCOPE: 0.012,
}
# ms a round: the nested `attn_out/norms` counts once in each of its scopes
EXPECTED = {"qkv_ms": 50.0, "attn_out_ms": 17.0, "norms_ms": 10.0,
            "rotary_ms": 4.0, "unscoped_ms": 6.0}


def _reader(metric):
    return cells.load_module(cells.HERE / "metrics" / f"{metric}.py").read


def _run(table, window_from=trace.FROM_MARKS, dispatches=2):
    reduced = trace.Reduced(
        n_devices=1, window_s=0.5, window_from=window_from, busy_s=0.3,
        collective_s=0.0, collective_exposed_s=0.0, device_ops=[],
        idle_gaps=[], scopes={path: {"s": s} for path, s in table.items()})
    window = Window(elapsed_s=0.5, rounds=dispatches,
                    dispatch_s=[0.25] * dispatches, rounds_per_dispatch=1)
    return harness.Run(cell=None, setup_s=0.0, window=window,
                       window_compiles=0, flops_per_round=0.0,
                       min_bytes_per_round=None, peaks=None,
                       traced_rounds=2, trace=reduced)


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_each_reads_its_rows_per_traced_round(metric):
    assert _reader(metric)(_run(TABLE)) == pytest.approx(EXPECTED[metric])


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_without_a_mark_each_reads_nothing(metric):
    assert _reader(metric)(_run(TABLE, window_from=trace.FROM_OPS)) is None
    untraced = _run(TABLE)
    untraced.trace = None
    assert _reader(metric)(untraced) is None


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_without_its_rows_each_reads_nothing(metric):
    left = {path: s for path, s in TABLE.items()
            if path != trace.NO_SCOPE and metric.removesuffix("_ms") not in path}
    assert _reader(metric)(_run(left)) is None


# ---------------------------------------------------------- engine_host_ms
@pytest.fixture(scope="module")
def two_rounds():
    """The spans two rounds of a tiny `FedTransformer` left."""
    TRACER.configure(enabled=True, sample=1.0)
    TRACER.clear()
    cfg = FT.TransformerConfig(vocab=97, d_model=32, n_heads=4, n_layers=2,
                               max_len=16, attention="recompute",
                               flash_interpret=True)
    engine = FT.make_engine(4, 1, cfg, devices=jax.devices()[:1])
    params, opt_state = engine.init(jax.random.key(0))
    tokens = engine.shard_tokens(FT.make_federated_tokens(4, 2, 16, 97))
    for _ in range(2):
        params, opt_state, loss = engine.round(
            params, opt_state, tokens, jnp.ones(4))
    jax.block_until_ready(loss)
    return TRACER.drain()


@pytest.fixture
def spans(two_rounds, monkeypatch):
    monkeypatch.setattr(TRACER, "drain",
                        lambda trace_id=None: list(two_rounds))
    return two_rounds


def test_engine_host_ms_is_the_calls_less_their_launches(spans):
    calls = [s for s in spans if s["name"] == "engine.call"]
    ids = {s["span_id"] for s in calls}
    launches = [s for s in spans
                if s["name"] == "device.launch" and s["parent_id"] in ids]
    assert (len(calls), len(launches)) == (2, 2)
    own = sum(s["dur"] for s in calls) - sum(s["dur"] for s in launches)
    value = _reader("engine_host_ms")(_run({}))
    assert value == pytest.approx(1e3 * own / 2)
    assert 0 < value < 1e3 * max(s["dur"] for s in calls)


def test_engine_host_ms_reads_nothing_where_a_dispatch_has_no_span(spans):
    assert _reader("engine_host_ms")(_run({}, dispatches=3)) is None
