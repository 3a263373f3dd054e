"""The round path under the tracer: `engine.call` and `device.launch` spans
around `FedAvg` and `FedTransformer`, the same spans as host events of a
profiler session, and the `jax.named_scope` names on the device's
operations. CPU, tiny sizes."""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import importlib
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from perfbench import trace as bench_trace
from vantage6_tpu.core.mesh import FederationMesh
from vantage6_tpu.fed.compression import CompressorSpec
from vantage6_tpu.fed.fedavg import FedAvg, FedAvgSpec
from vantage6_tpu.runtime.profiling import DEVICE_SCOPES, observed_jit
from vantage6_tpu.runtime.tracing import TRACER
from vantage6_tpu.workloads import fed_transformer as FT

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the module: `vantage6_tpu.ops.flash_attention` the attribute is a function
FA = importlib.import_module("vantage6_tpu.ops.flash_attention")


@pytest.fixture(autouse=True)
def _tracer_on():
    TRACER.configure(enabled=True, sample=1.0)
    TRACER.clear()
    yield
    TRACER.configure(enabled=True, sample=1.0)


# ------------------------------------------------------------ tiny engines
def _transformer(attention="recompute", slots=1):
    ring = attention == "ring"
    cfg = FT.TransformerConfig(
        vocab=97, d_model=32, n_heads=4, n_layers=2, max_len=16,
        attention=attention, flash_interpret=True, remat=ring)
    engine = FT.make_engine(4, 2 if ring else 1, cfg,
                            devices=jax.devices()[: 8 if ring else slots])
    params, opt_state = engine.init(jax.random.key(0))
    tokens = engine.shard_tokens(FT.make_federated_tokens(4, 2, 16, 97))
    return engine, (params, opt_state, tokens, jnp.ones(4))


def _transformer_experts():
    """A block with a router and an expert layer (chip 1 of 2 holds 2 of
    the 4 experts) where the dense one has its MLP."""
    cfg = FT.TransformerConfig(
        vocab=97, d_model=32, n_heads=4, n_layers=2, max_len=16,
        attention="recompute", flash_interpret=True, remat=True,
        norm="rmsnorm", n_kv_heads=2, positions="rotary",
        rope_layout=(0, 1), window=8, window_layout=(0, 1), ffn="experts",
        n_experts=4, top_k=2, d_expert=16, experts_held=(2, 3),
        tie_head=False)
    engine = FT.make_engine(4, 1, cfg, devices=jax.devices()[:1])
    params, opt_state = engine.init(jax.random.key(0))
    tokens = engine.shard_tokens(FT.make_federated_tokens(4, 2, 16, 97))
    return engine, (params, opt_state, tokens, jnp.ones(4))


def _transformer_sparse():
    """Learned sparse attention (an indexer of two heads keeping 4 keys a
    query) beside SwiGLU experts routed on the normed stream."""
    cfg = FT.TransformerConfig(
        vocab=97, d_model=32, n_heads=4, n_layers=2, max_len=16,
        attention="recompute", flash_interpret=True, remat=True,
        norm="rmsnorm", n_kv_heads=2, positions="rotary", qk_norm=True,
        ffn="experts", router_input="normed", expert_act="silu",
        n_experts=4, top_k=2, d_expert=16, experts_held=(2, 3),
        tie_head=False, sparse_top_k=4, indexer_heads=2, indexer_dim=8)
    engine = FT.make_engine(4, 1, cfg, devices=jax.devices()[:1])
    params, opt_state = engine.init(jax.random.key(0))
    tokens = engine.shard_tokens(FT.make_federated_tokens(4, 2, 16, 97))
    return engine, (params, opt_state, tokens, jnp.ones(4))


def _transformer_looped(slots=1):
    """A stack of two layers walked three times over the same weights, with
    the exit gate, norms after each half too and the gated MLP."""
    cfg = FT.TransformerConfig(
        vocab=97, d_model=32, n_heads=4, n_layers=2, max_len=16,
        attention="recompute", flash_interpret=True, remat=True,
        norm="rmsnorm", norm_after=True, positions="rotary", ffn="swiglu",
        d_ff=48, tie_head=False, loops=3, exit_beta=0.05)
    engine = FT.make_engine(4, 1, cfg, devices=jax.devices()[:slots])
    params, opt_state = engine.init(jax.random.key(0))
    tokens = engine.shard_tokens(FT.make_federated_tokens(4, 2, 16, 97))
    return engine, (params, opt_state, tokens, jnp.ones(4))


def _nll(p, x, y, w):
    z = x @ p["w"] + p["b"]
    return jnp.sum(w * (jnp.logaddexp(0.0, z) - y * z)) / jnp.sum(w)


def _fedavg(y_dtype=jnp.float32, streamed=False, **spec):
    """``streamed``: the rule steered to the streamed kernel, which the CPU
    interprets (the rule itself streams on a TPU alone)."""
    mesh = FederationMesh(8)
    engine = FedAvg(mesh, FedAvgSpec(
        loss_fn=_nll, local_steps=2, batch_size=8, **spec))
    if streamed:
        engine.gather_path = lambda x, y: "streamed"
    rng = np.random.default_rng(0)
    x = mesh.shard_stacked(jnp.asarray(rng.normal(size=(8, 16, 5)),
                                       jnp.float32))
    y = mesh.shard_stacked(jnp.asarray(rng.integers(0, 2, (8, 16)), y_dtype))
    params = {"w": jnp.zeros(5), "b": jnp.zeros(())}
    return engine, params, (x, y, jnp.full((8,), 16.0))


def _fedavg_lowered(engine, params, data):
    x, y, counts = data
    placed = engine._place(params, engine.init(params), counts,
                           jnp.ones(8), jax.random.key(1))
    p, state, counts, mask, key = placed
    return engine._run.lower(p, state, x, y, counts, mask, key,
                             n_rounds=3)


# ------------------------------------------------------------------ scopes
TRANSFORMER_SCOPES = {"local_train", "embed", "qkv", "attention", "attn_out",
                      "mlp", "norms", "lm_head_loss", "aggregate",
                      "server_update"}
# the block's scopes that only ever nest inside the others or sit beside them
BLOCK_SCOPES = ("qkv", "rotary", "attn_out", "norms")
FEDAVG_SCOPES = {"pack_table", "local_train", "gather", "loss_grad",
                 "learning_stats", "aggregate", "server_update"}
PROGRAMS = {
    "transformer-recompute": (lambda: _transformer("recompute"),
                              TRANSFORMER_SCOPES),
    "transformer-ring": (lambda: _transformer("ring"), TRANSFORMER_SCOPES),
    "transformer-flash": (lambda: _transformer("flash"), TRANSFORMER_SCOPES),
    "transformer-experts": (
        _transformer_experts,
        TRANSFORMER_SCOPES - {"mlp"} | {"router", "experts", "rotary"}),
    "transformer-looped": (
        _transformer_looped,
        TRANSFORMER_SCOPES | {"loop", "exit_gate", "rotary"}),
    "transformer-sparse": (
        _transformer_sparse,
        TRANSFORMER_SCOPES - {"mlp"} | {"router", "experts", "rotary",
                                        "indexer", "select", "indexer_loss"}),
    "fedavg-fused": (lambda: _fedavg(), FEDAVG_SCOPES),
    "fedavg-streamed": (lambda: _fedavg(streamed=True), FEDAVG_SCOPES),
    "fedavg-compressed-zero1": (
        lambda: _fedavg(
            compressor=CompressorSpec(topk_ratio=0.5, int8=True),
            shard_server_update=True, server_optimizer=optax.adam(1e-2)),
        FEDAVG_SCOPES | {"compress"}),
}


def _lowered(built):
    if isinstance(built[0], FT.FedTransformer):
        engine, args = built
        return engine._round.lower(engine, *args)
    return _fedavg_lowered(*built)


def _op_names(lowered) -> list[str]:
    return re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())


@functools.cache
def _program_op_names(program) -> tuple[str, ...]:
    """The compiled program's operation paths, once a program a worker."""
    return tuple(_op_names(_lowered(PROGRAMS[program][0]())))


def _scopes_in(names) -> set[str]:
    """Scope names as the compiled operations' metadata carries them, which
    is what a device trace shows: an element of `op_name`'s path
    (`.../attention/...`) or, backward, `transpose(jvp(attention))`. An
    operation's own name (`.../gather`) ends the path and is not one."""
    return {s for s in DEVICE_SCOPES
            if any(re.search(rf"[/(]{s}[/)]", n) for n in names)}


@pytest.mark.parametrize("program", PROGRAMS)
def test_the_compiled_operations_carry_each_scope_name(program):
    assert _scopes_in(_program_op_names(program)) == PROGRAMS[program][1]


@pytest.mark.parametrize(
    "program", [p for p in PROGRAMS if p.startswith("transformer")])
def test_the_block_scopes_never_enclose_another_scope(program):
    """`qkv`, `rotary`, `attn_out` and `norms` nest inside the other scopes
    or sit beside them: no other scope comes after one of them in a path, so
    each metric of the other scopes reads what it read without them."""
    def at(scope, name):
        return [m.start() for m in re.finditer(rf"[/(]{scope}[/)]", name)]

    others = [s for s in DEVICE_SCOPES if s not in BLOCK_SCOPES]
    for name in _program_op_names(program):
        first = min((i for s in BLOCK_SCOPES for i in at(s, name)),
                    default=None)
        if first is not None:
            assert not [s for s in others
                        if any(i > first for i in at(s, name))], name


def test_the_pack_lies_outside_local_train_and_the_gather_inside_it():
    """`pack_table` is paid once per dispatch, `gather` once per local
    step: an operation's path holds one of them, never both, and only the
    gather's lies under `local_train`."""
    names = _program_op_names("fedavg-fused")
    packs = [n for n in names if "/pack_table/" in n]
    gathers = [n for n in names if re.search(r"/gather/.*gather", n)]
    assert packs and gathers
    assert not [n for n in packs if "local_train" in n or "while" in n]
    assert all("/local_train/" in n and "/while/" in n for n in gathers)


def test_the_streamed_kernel_and_its_sort_lie_inside_the_gather():
    """Streamed, the step's sort and the kernel are the `gather` scope's,
    under `local_train` and inside the loop over steps, as the gather
    was: `gather_ms` reads them. (A sort's comparator carries a path
    relative to its call, `vmap()/...`: its parameters take no time.)"""
    names = [n for n in _program_op_names("fedavg-streamed")
             if n.startswith("jit(")]
    kernel = [n for n in names if "stream_gather" in n]
    sort = [n for n in names if re.search(r"/gather/.*sort", n)]
    assert kernel and sort
    assert all("/local_train/" in n and "/gather/" in n and "/while/" in n
               for n in kernel + sort)


def test_with_labels_of_another_width_no_pack_is_on_the_device():
    lowered = _fedavg_lowered(*_fedavg(y_dtype=jnp.int8))
    assert _scopes_in(_op_names(lowered)) == FEDAVG_SCOPES - {"pack_table"}


def test_every_scope_of_the_tuple_is_opened_by_some_program():
    assert set().union(*(s for _, s in PROGRAMS.values())) == set(
        DEVICE_SCOPES)
    assert len(set(DEVICE_SCOPES)) == len(DEVICE_SCOPES)


@pytest.mark.parametrize("program", ["transformer-recompute",
                                     "transformer-looped",
                                     "fedavg-compressed-zero1"])
def test_scopes_are_metadata_and_nothing_else(program, monkeypatch,
                                             fresh_compiles):
    """The program as XLA gets it (the text without locations, which is
    what the persistent cache keys on) is the same without the scopes. So
    the build without them compiles with that cache off: loaded, it would
    carry the names of the build with them."""
    build, _ = PROGRAMS[program]
    with_scopes = _lowered(build())
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = _lowered(build())
    assert with_scopes.as_text() == without.as_text()
    assert not _scopes_in(_op_names(without))


# ------------------------------------------------------------------- spans
def _call_transformer():
    engine, args = _transformer()
    out = engine.round(*args)
    return out, "fed_transformer.round", 1, len(jax.tree.leaves(args))


def _call_run_rounds(**kw):
    engine, params, (x, y, counts) = _fedavg(**kw)
    out = engine.run_rounds(params, x, y, counts, jax.random.key(1), 3)
    # params, the empty sgd state, x, y, counts, mask, key
    return out, "fedavg.run_rounds", 3, len(jax.tree.leaves(params)) + 5


def _call_fedavg_round(**kw):
    engine, params, (x, y, counts) = _fedavg(**kw)
    state = engine.init(params)
    out = engine.round(params, state, x, y, counts, jax.random.key(1))
    return out, "fedavg.round", 1, len(jax.tree.leaves(params)) + 5


ENGINES = {"fed_transformer.round": _call_transformer,
           "fedavg.run_rounds": _call_run_rounds,
           "fedavg.round": _call_fedavg_round}
# what an engine says of the program it launches, beside engine and rounds
# (the transformer: a sequence of 16 is one tile; two layers of one head)
NO_RING = {"aggregate_overlap": "none", "aggregate_groups": 0,
           "aggregate_bytes": 0}
ENGINE_ATTRS = {"fed_transformer.round": {"attention_path": "walk",
                                          "attention_tile": "16x16",
                                          "attention_tiles_visited": 2,
                                          "attention_tiles": 2,
                                          "remat_saved": [], **NO_RING},
                "fedavg.run_rounds": {"gather": "packed"},
                "fedavg.round": {"gather": "packed"}}


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


@pytest.mark.parametrize("caller", ["rooted", "joined"])
@pytest.mark.parametrize("engine", ENGINES)
def test_one_engine_call_with_one_launch_under_it(engine, caller):
    if caller == "joined":
        with TRACER.span("researcher.step") as outer:
            _, name, rounds, n_buffers = ENGINES[engine]()
    else:
        _, name, rounds, n_buffers = ENGINES[engine]()
    spans = TRACER.drain()
    (call,) = _named(spans, "engine.call")
    assert call["kind"] == "engine"
    assert call["attrs"] == {"engine": name, "rounds": rounds,
                             **ENGINE_ATTRS[engine]}
    (launch,) = [s for s in _named(spans, "device.launch")
                 if s["parent_id"] == call["span_id"]]
    assert launch["kind"] == "device"
    assert launch["trace_id"] == call["trace_id"]
    assert launch["attrs"]["n_buffers"] == n_buffers
    # the transformer counts the leaves it donates (all it was handed but
    # `tokens` and `mask`); FedAvg's launches record none
    assert launch["attrs"].get("n_donated") == (
        n_buffers - 2 if engine == "fed_transformer.round" else None)
    assert launch["attrs"]["function"].startswith(name)
    assert 0 < launch["dur"] <= call["dur"]
    if caller == "joined":
        assert call["parent_id"] == outer.context.span_id
        assert call["trace_id"] == outer.context.trace_id
    else:
        assert call["parent_id"] is None


@pytest.mark.parametrize("y_dtype,path", [(jnp.float32, "packed"),
                                          (jnp.int8, "separate"),
                                          (jnp.float32, "streamed")])
@pytest.mark.parametrize("engine", ["fedavg.run_rounds", "fedavg.round"])
def test_the_engine_call_says_which_gather_its_program_was_built_with(
        engine, y_dtype, path):
    """The counter that the packed gather engaged: a string on the span
    that exists, the same rule the traced program read. Streamed, the span
    also says the kernel's block of table rows and how many blocks a
    station's table is streamed in: 16 rows are one block of 16."""
    ENGINES[engine](y_dtype=y_dtype, streamed=path == "streamed")
    (call,) = _named(TRACER.drain(), "engine.call")
    said = {k: v for k, v in call["attrs"].items() if k.startswith("gather")}
    assert said == ({"gather": path, "gather_block_rows": 16,
                     "gather_blocks": 1} if path == "streamed"
                    else {"gather": path})


@pytest.mark.parametrize("attention", ["recompute", "flash", "ring"])
def test_the_engine_call_says_which_tiles_the_attention_walks(
        attention, monkeypatch):
    """The counter that `recompute_attention` walked visible tiles only: the
    tile its shapes gave and, over the layers of one sequence and head, the
    tiles visited of the tiles there are; the sum of `_key_block_range`
    over the query blocks. With tiles of 4 a causal sequence of 16 visits
    1 + 2 + 3 + 4 of 16 in each of two layers. The other paths say
    nothing."""
    monkeypatch.setattr(FA, "TILED_BLOCK", 4)
    engine, args = _transformer(attention)
    engine.round(*args)
    (call,) = _named(TRACER.drain(), "engine.call")
    said = {k: v for k, v in call["attrs"].items() if k.startswith("attention")}
    if attention != "recompute":
        assert said == {}
        return
    walked = 0
    for i in range(4):
        lo, hi = FA._key_block_range(i, 4, 4, 4, 16, 0, 0, True, None)
        walked += int(hi) - int(lo)
    assert walked == 10
    assert said == {"attention_path": "walk", "attention_tile": "4x4",
                    "attention_tiles_visited": 2 * walked,
                    "attention_tiles": 2 * 16}


def test_the_engine_call_says_that_the_kernels_walk(monkeypatch):
    """Where the program is compiled for the chip (`flash_interpret` False)
    and a head is a whole lane tile the walk runs inside the kernels: the
    span says so, and the blocks they really use (a sequence of 16 is one
    block of a whole lane tile; at 4,096 it is 512 x 512, 36 of 64 tiles a
    layer). Heads of 8 stay on the XLA walk. The program itself is not run
    here: a kernel compiles for a TPU alone."""
    cfg = FT.TransformerConfig(
        vocab=97, d_model=32, n_heads=2, head_dim=128, n_layers=2,
        max_len=16, attention="recompute")
    engine = FT.make_engine(4, 1, cfg, devices=jax.devices()[:1])
    params, opt_state = engine.init(jax.random.key(0))
    tokens = engine.shard_tokens(FT.make_federated_tokens(4, 2, 16, 97))
    monkeypatch.setattr(
        FT.FedTransformer, "_round",
        lambda self, params, opt_state, tokens, mask: (
            params, opt_state, jnp.float32(0), None, None))
    engine.round(params, opt_state, tokens, jnp.ones(4))
    (call,) = _named(TRACER.drain(), "engine.call")
    assert {k: v for k, v in call["attrs"].items()
            if k.startswith("attention")} == {
        "attention_path": "kernel", "attention_tile": "128x128",
        "attention_tiles_visited": 2, "attention_tiles": 2}
    assert engine.attention_walk(4096) == {
        "attention_path": "kernel", "attention_tile": "512x512",
        "attention_tiles_visited": 2 * 36, "attention_tiles": 2 * 64}
    narrow, _ = _transformer()
    narrow.cfg = dataclasses.replace(narrow.cfg, flash_interpret=False)
    assert narrow.attention_walk(16)["attention_path"] == "walk"


def test_a_windowed_layer_visits_fewer_tiles(monkeypatch):
    """One full and one windowed layer (window 8, tiles of 4, sequence 16):
    the windowed one leaves out the tiles wholly before the window too."""
    monkeypatch.setattr(FA, "TILED_BLOCK", 4)
    engine, args = _transformer_experts()
    walk = engine.attention_walk(args[2].shape[-1])
    windowed = 0
    for i in range(4):
        lo, hi = FA._key_block_range(i, 4, 4, 4, 16, 0, 0, True, 8)
        windowed += int(hi) - int(lo)
    assert windowed == 1 + 2 + 3 + 3
    assert walk == {"attention_path": "walk", "attention_tile": "4x4",
                    "attention_tiles_visited": 10 + windowed,
                    "attention_tiles": 32}


def test_a_looped_stack_says_its_walks_and_counts_tiles_over_applications(
        monkeypatch):
    """`loops` and `layer_applications` on the `engine.call` span of a stack
    walked more than once, and the attention's tiles counted over the layer
    applications (two layers, three walks, tiles of 4 in a sequence of 16:
    6 x 10 of 6 x 16); a stack walked once says neither."""
    monkeypatch.setattr(FA, "TILED_BLOCK", 4)
    engine, args = _transformer_looped()
    engine.round(*args)
    (call,) = _named(TRACER.drain(), "engine.call")
    assert call["attrs"] == {
        "engine": "fed_transformer.round", "rounds": 1,
        "attention_path": "walk", "attention_tile": "4x4",
        "attention_tiles_visited": 60,
        "attention_tiles": 96, "loops": 3, "layer_applications": 6,
        "remat_saved": [], **NO_RING}
    TRACER.clear()
    engine, args = _transformer()
    engine.round(*args)
    (call,) = _named(TRACER.drain(), "engine.call")
    assert not {"loops", "layer_applications"} & set(call["attrs"])


@pytest.mark.parametrize("engine", ["one_slot", "four_slots",
                                    "looped_on_four_slots"])
def test_the_engine_call_says_how_the_cross_station_mean_is_taken(engine):
    """`aggregate_overlap`, `aggregate_groups` and `aggregate_bytes` on the
    recorded `engine.call` span of a round: a ring on several slots (the two
    tiny layers are one group; what a chip sends is the parameters' bytes
    twice round less a chunk, and the padding), none on one slot, and none
    where a stack is walked more than once, which keeps `fed_mean`'s
    all-reduces on any mesh."""
    slots = 1 if engine == "one_slot" else 4
    if len(jax.devices()) < slots:
        pytest.skip(f"needs {slots} fake devices")
    build = _transformer_looped if "looped" in engine else _transformer
    engine_, args = build(slots=slots)
    held = sum(x.nbytes for x in jax.tree.leaves(args[0]))
    out = engine_.round(*args)
    assert np.isfinite(float(out[2]))
    (call,) = _named(TRACER.drain(), "engine.call")
    said = {k: v for k, v in call["attrs"].items() if k in NO_RING}
    if engine != "four_slots":
        assert said == NO_RING
        return
    assert said.pop("aggregate_bytes") >= 2 * 3 * held // 4
    assert said == {"aggregate_overlap": "ring", "aggregate_groups": 1}


def test_the_exit_distribution_is_one_span_read_outside_the_round():
    """A round of a looped stack leaves its exit distribution on the device
    and records nothing of it; `record_exit_distribution` then records ONE
    `exits.distribution` span for the rounds since the last call."""
    engine, args = _transformer_looped()
    state = args[:2]
    for _ in range(2):
        *state, _ = engine.round(*state, *args[2:])
    assert not _named(TRACER.drain(), "exits.distribution")
    attrs = engine.record_exit_distribution()
    (span,) = _named(TRACER.drain(), "exits.distribution")
    assert span["kind"] == "engine" and span["attrs"] == attrs
    assert set(attrs) == {"rounds", "mean", "by_round", "expected_exit_step"}
    assert attrs["rounds"] == 2 and np.shape(attrs["by_round"]) == (2, 3)
    assert sum(attrs["mean"]) == pytest.approx(1.0)
    assert 1.0 < attrs["expected_exit_step"] < 3.0
    assert engine.record_exit_distribution() is None
    plain, args = _transformer()
    plain.round(*args)
    assert plain.record_exit_distribution() is None


@pytest.mark.parametrize("build", [_transformer, _transformer_experts])
def test_counting_the_walk_runs_no_program(build, monkeypatch):
    """What the span says of the walk is host arithmetic on Python integers:
    the first `round()` builds as many programs with it as with a walk that
    says nothing (a `jnp` call for it would be one more each, and each a
    program of every cell's set-up)."""
    from perfbench.meter import CompileMeter

    monkeypatch.setattr(FA, "TILED_BLOCK", 4)

    def programs_of_the_first_round():
        engine, args = build()
        TRACER.clear()
        meter = CompileMeter()
        try:
            jax.block_until_ready(engine.round(*args))
        finally:
            meter.close()
        (call,) = _named(TRACER.drain(), "engine.call")
        return meter.compiles, call["attrs"]

    with_walk, said = programs_of_the_first_round()
    assert {type(v) for k, v in said.items()
            if k.startswith("attention_tiles")} == {int}
    assert type(FA.tiles_visited(16, 16, 4, 4, True, 8)[0]) is int
    monkeypatch.setattr(FT.FedTransformer, "attention_walk",
                        lambda self, t: {})
    without, said = programs_of_the_first_round()
    assert not any(k.startswith("attention") for k in said)
    assert with_walk == without >= 1


@pytest.mark.parametrize("block", ["dense", "experts", "looped"])
def test_a_transformer_launch_counts_the_state_it_donates(block):
    """`n_donated` beside `n_buffers`: every leaf of `params` and
    `opt_state` (three trees of the parameters' shape and Adam's count)."""
    engine, args = {"dense": _transformer, "experts": _transformer_experts,
                    "looped": _transformer_looped}[block]()
    engine.round(*args)
    (launch,) = _named(TRACER.drain(), "device.launch")
    n_params = len(jax.tree.leaves(args[0]))
    assert launch["attrs"] == {
        "function": "fed_transformer.round",
        "n_buffers": 3 * n_params + 3, "n_donated": 3 * n_params + 1}


def test_a_launch_that_compiles_has_the_compile_under_it():
    fn = observed_jit("t.launch", lambda x: x * 2.0)
    fn(jnp.ones(4))
    fn(jnp.ones(4))
    spans = TRACER.drain()
    first, second = _named(spans, "device.launch")
    assert first["attrs"] == {"function": "t.launch", "n_buffers": 1}
    (compiled,) = _named(spans, "device.compile")
    assert compiled["parent_id"] == first["span_id"]
    assert compiled["dur"] <= first["dur"]
    assert not [s for s in spans if s["parent_id"] == second["span_id"]]


def test_under_an_outer_jit_an_observed_function_opens_no_launch():
    inner = observed_jit("t.inner", lambda x: x + 1.0)
    jax.jit(lambda x: inner(x) * 2.0)(jnp.ones(4))
    assert not _named(TRACER.drain(), "device.launch")


@pytest.mark.parametrize("engine", ["fed_transformer.round",
                                    "fedavg.run_rounds"])
def test_with_the_tracer_off_nothing_is_recorded_and_nothing_changes(engine):
    traced = jax.device_get(ENGINES[engine]()[0])
    assert _named(TRACER.drain(), "engine.call")
    TRACER.configure(enabled=False)
    TRACER.clear()
    plain = jax.device_get(ENGINES[engine]()[0])
    assert TRACER.drain() == []
    for a, b in zip(jax.tree.leaves(traced), jax.tree.leaves(plain)):
        np.testing.assert_array_equal(a, b)


# ------------------------------------------------------ the profiler's clock
def _host_events(log_dir):
    from jax.profiler import ProfileData

    path = bench_trace.find_xplane(str(log_dir))
    plain = bench_trace.load_xplane(path)
    events = {}
    for plane in plain["planes"]:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                events.setdefault(name, []).append((start, start + dur))
    ids = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for event in line.events:
                if event.name in ("engine.call", "device.launch"):
                    ids[event.name] = dict(event.stats).get("span_id")
    return events, ids


def test_a_span_is_a_host_event_of_a_profiler_session(tmp_path):
    engine, args = _transformer()
    # compiled before the session; the round consumes its state, so the
    # traced one goes on from what this one returns
    args = (*engine.round(*args)[:2], *args[2:])
    TRACER.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(engine.round(*args))
        TRACER.configure(sample=0.0)
        with TRACER.span("unsampled.root"):
            pass
        TRACER.configure(enabled=False)
        with TRACER.span("disabled.span"):
            pass
    finally:
        jax.profiler.stop_trace()
    events, ids = _host_events(tmp_path)
    (call,), (launch,) = events["engine.call"], events["device.launch"]
    assert call[0] <= launch[0] and launch[1] <= call[1]
    # jax's own launch event lies inside the program's, on the same clock
    inside = [name for name, spans in events.items()
              if bench_trace.host_kind(name) == "dispatch"
              and any(launch[0] <= a and b <= launch[1] for a, b in spans)]
    assert inside, sorted(events)[:40]
    spans = {s["name"]: s for s in TRACER.drain()}
    for name in ("engine.call", "device.launch"):
        # the profiler reads an id of digits alone as a number
        assert str(ids[name]).lstrip("0") == spans[name]["span_id"].lstrip("0")
    assert "unsampled.root" not in events and "disabled.span" not in events


def test_a_process_that_cannot_import_jax_still_records_spans():
    code = f"""
import sys, types
sys.modules["jax"] = None  # `import jax` now raises ImportError
package = types.ModuleType("vantage6_tpu")  # its __init__ imports jax
package.__path__ = [{os.path.join(ROOT, "vantage6_tpu")!r}]
sys.modules["vantage6_tpu"] = package
from vantage6_tpu.runtime.tracing import TRACER
TRACER.configure(enabled=True, sample=1.0)
with TRACER.span("client.task_create") as outer:
    with TRACER.span("rest"):
        pass
names = [s["name"] for s in TRACER.drain(outer.context.trace_id)]
assert names == ["rest", "client.task_create"], names
assert "jax.profiler" not in sys.modules
print("recorded", len(names))
"""
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "recorded 2"


# ------------------------------------------- the names the benchmark reads
# what `perfbench/trace.py::host_kind` makes of each span name of the round
# and task paths: a name may hold one of its marks only if it is of that kind
SPAN_KINDS = {
    "engine.call": None, "device.launch": None, "device.compile": "compile",
    "device.profile": None, "device.step": None, "device.compress": None,
    "device.decompress": None, "server.dispatch": "dispatch",
    "fused.rounds": None, "runner.exec": None, "aggregate": None,
    "learning.round": None,
}


@pytest.mark.parametrize("name", SPAN_KINDS)
def test_a_span_name_reads_as_the_kind_it_is(name):
    assert bench_trace.host_kind(name) == SPAN_KINDS[name]


def test_the_round_path_records_no_span_name_outside_that_table():
    for call in ENGINES.values():
        call()
    assert {s["name"] for s in TRACER.drain()} <= set(SPAN_KINDS)
