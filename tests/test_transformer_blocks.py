"""A block chosen by the configuration (`TransformerConfig`): the default
block is the parent's, bit for bit; grouped-query heads, windows, rotary or
no positions, the router and the expert layer agree with the plain
reference of `smallthinker-21b-ep8-2st`; the tiled attention equals the
dense masked softmax. CPU, tiny sizes, seeded weights, float32."""
from __future__ import annotations

import dataclasses
import hashlib
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import cells, compare
from vantage6_tpu.models import experts as X
from vantage6_tpu.runtime import profiling
from vantage6_tpu.runtime.tracing import TRACER
from vantage6_tpu.workloads import fed_transformer as FT

FA = importlib.import_module("vantage6_tpu.ops.flash_attention")
REFERENCE = cells.load_module(
    cells.HERE / "configs" / "smallthinker-21b-ep8-2st.py")

# the tiny SmallThinker: 16 experts routed over, chip 1 of 4 holds 4, two a
# token; four windows of 8 in a sequence of 32; layouts as published
CONFIG = {
    "name": "tiny", "head_dim": 8, "hidden_size": 32,
    "max_position_embeddings": 32, "moe_ffn_hidden_size": 16,
    "moe_num_active_primary_experts": 2, "moe_num_primary_experts": 4,
    "num_attention_heads": 4, "num_hidden_layers": 4,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_layout": [0, 1, 1, 1], "rope_theta": 1500000,
    "sliding_window_layout": [0, 1, 1, 1], "sliding_window_size": 8,
    "tie_word_embeddings": False, "vocab_size": 97,
    "expert_parallel": {"chips": 4, "this_chip": 1},
    "initializer_range": 0.02, "embedding_initializer_range": 1.0,
    "n_stations": 2,
    "adam": {"lr": 0.001, "b1": 0.9, "b2": 0.999, "eps": 1e-08},
}
TRAFFIC = {"batch": 2, "seq_len": 32, "n_batches": 3, "zipf_exponent": 1.0,
           "compute_dtype": "float32", "attention": "recompute",
           "remat": True}


@pytest.fixture(autouse=True)
def _tiles_of_eight(monkeypatch):
    """The tiled attention's default tile (512) would hold a sequence of 32
    whole: with tiles of 8 the engine's rounds walk several key blocks and
    skip those outside the window, as the cell's own size does."""
    monkeypatch.setattr(FA, "TILED_BLOCK", 8)


def _block_config(**changes) -> FT.TransformerConfig:
    c = CONFIG
    held = c["moe_num_primary_experts"]
    first = c["expert_parallel"]["this_chip"] * held
    return dataclasses.replace(FT.TransformerConfig(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_layers=c["num_hidden_layers"],
        max_len=c["max_position_embeddings"], dtype=jnp.float32,
        attention="recompute", remat=True, flash_interpret=True,
        norm="rmsnorm", norm_eps=c["rms_norm_eps"], head_dim=c["head_dim"],
        n_kv_heads=c["num_key_value_heads"], positions="rotary",
        rope_layout=tuple(c["rope_layout"]),
        rope_theta=float(c["rope_theta"]), window=c["sliding_window_size"],
        window_layout=tuple(c["sliding_window_layout"]), ffn="experts",
        n_experts=held * c["expert_parallel"]["chips"],
        top_k=c["moe_num_active_primary_experts"],
        d_expert=c["moe_ffn_hidden_size"],
        experts_held=tuple(range(first, first + held)), tie_head=False),
        **changes)


@pytest.fixture(scope="module")
def inputs():
    got = REFERENCE.make_inputs(CONFIG, TRAFFIC, jax.random.key(11))
    # scales away from 1 and a router whose choices are well apart, so that
    # a scale left out or a float32 rounding flipping a choice would show
    for i, layer in enumerate(got["params"]["layers"]):
        layer["router"] = layer["router"] * 100.0
        layer["norm1"] = layer["norm1"] * (1.3 - 0.1 * i)
        layer["norm2"] = layer["norm2"] * (0.7 + 0.1 * i)
    got["params"]["final_norm"] = got["params"]["final_norm"] * 1.1
    return got


def _fresh_state(engine, inputs):
    """A state of the round's own: a round consumes what it is handed, and
    the module's `inputs` are every test's."""
    params = jax.tree.map(jnp.copy, inputs["params"])
    return params, engine.optimizer.init(params)


# ------------------------------------- the new block against the reference
def test_the_rounds_follow_the_plain_reference(inputs):
    """`make_engine` + `FedTransformer.round` on the tiny SmallThinker: the
    losses, the first gradient and the parameters' change are the
    reference's, and the `experts.load` record holds the counts the
    reference computes."""
    TRACER.configure(enabled=True, sample=1.0)
    TRACER.clear()
    engine = FT.make_engine(2, 1, _block_config(), lr=CONFIG["adam"]["lr"],
                            devices=jax.devices()[:1])
    params, opt_state = _fresh_state(engine, inputs)
    losses, grad_norms = [], None
    for step in range(2):
        params, opt_state, loss = engine.round(
            params, opt_state, engine.shard_tokens(inputs["tokens"][step]),
            inputs["mask"])
        losses.append(float(loss))
        if step == 0:
            grad_norms = compare.leaf_norms(opt_state[0].mu, scale=10.0)
    change = compare.leaf_norms(
        jax.tree.map(jnp.subtract, params, inputs["params"]))
    want = REFERENCE.reference_train(CONFIG, TRAFFIC, inputs, 2)
    np.testing.assert_allclose(losses, want["losses"], rtol=2e-6)
    assert set(grad_norms) == set(want["grad_norms"])
    for name, norm in want["grad_norms"].items():
        assert grad_norms[name] == pytest.approx(norm, rel=2e-4), name
    for name, norm in want["change_norms"].items():
        assert change[name] == pytest.approx(norm, rel=2e-3), name

    recorded = engine.record_expert_load()
    assert recorded["rounds"] == 2 and recorded["dropped"] == 0
    assert recorded["max_over_mean"] > 1
    first = REFERENCE.expert_load(CONFIG, inputs["params"],
                                  inputs["tokens"][0])
    assert first.shape == (4, 4)
    assert sum(recorded["assignments_by_round"]) == pytest.approx(
        2 * np.sum(recorded["assignments_per_round"]))
    assert recorded["assignments_by_round"][0] == first.sum()
    span = [s for s in TRACER.drain() if s["name"] == "experts.load"][-1]
    assert span["attrs"] == recorded
    # the walk over row blocks: 2 stations x 4 layers, one chunk each
    block, blocks = X.row_walk(inputs["tokens"][0][0].size, 2)
    assert span["attrs"]["row_block"] == block
    assert span["attrs"]["row_blocks"] == 2 * 4 * blocks
    assert 0 < span["attrs"]["row_blocks_walked"] <= 2 * 4 * blocks
    assert engine.record_expert_load() is None  # read, and emptied


def test_one_round_holds_the_reference_counts_per_layer_and_expert(inputs):
    engine = FT.make_engine(2, 1, _block_config(),
                            devices=jax.devices()[:1])
    engine.round(*_fresh_state(engine, inputs),
                 engine.shard_tokens(inputs["tokens"][1]), inputs["mask"])
    recorded = engine.record_expert_load()
    want = REFERENCE.expert_load(CONFIG, inputs["params"],
                                 inputs["tokens"][1])
    assert np.array_equal(recorded["assignments_per_round"], want)


def test_the_load_record_counts_the_row_blocks_walked(inputs, monkeypatch):
    """`experts.load` says what the expert layers walked: the blocks of
    ``row_block`` rows that carried an assignment, station by station and
    layer by layer, beside what the worst case would walk."""
    monkeypatch.setattr(X, "ROW_TILE", 8)
    TRACER.configure(enabled=True, sample=1.0)
    TRACER.clear()
    engine = FT.make_engine(2, 1, _block_config(),
                            devices=jax.devices()[:1])
    tokens = inputs["tokens"][1]
    engine.round(*_fresh_state(engine, inputs),
                 engine.shard_tokens(tokens), inputs["mask"])
    engine.record_expert_load()
    attrs = [s for s in TRACER.drain() if s["name"] == "experts.load"][-1][
        "attrs"]
    rows = tokens[0].size * 2  # a station's tokens, two choices each
    assert attrs["row_block"] == 8
    assert attrs["row_blocks"] == 2 * 4 * (rows // 8)
    per_station = [REFERENCE.expert_load(
        CONFIG, inputs["params"], tokens[s:s + 1]).sum(1) for s in (0, 1)]
    assert attrs["row_blocks_walked"] == sum(
        -(-int(live) // 8) for layers in per_station for live in layers)
    assert attrs["row_blocks_walked"] < attrs["row_blocks"]


def _logits(cfg, params, tokens):
    engine = FT.make_engine(1, 1, cfg, devices=jax.devices()[:1])
    P = jax.sharding.PartitionSpec
    return jax.shard_map(
        lambda p, t: FT.forward_local(p, t, cfg), mesh=engine.mesh,
        in_specs=(P(), P(None, FT.SEQ_AXIS)), out_specs=P(None, FT.SEQ_AXIS),
        check_vma=False)(params, tokens)


def test_a_layer_without_rotation_takes_no_positions(inputs):
    """With no layer rotating, nothing of the model reads a position: the
    rope's base changes nothing, and there is no position table. With the
    published layout it does."""
    tokens = inputs["tokens"][0, 0]
    nope = _block_config(rope_layout=(0, 0, 0, 0))
    assert "pos" not in FT.init_params(jax.random.key(0), nope)
    a = _logits(nope, inputs["params"], tokens)
    b = _logits(dataclasses.replace(nope, rope_theta=10.0),
                inputs["params"], tokens)
    assert np.array_equal(a, b)
    published = _block_config()
    c = _logits(published, inputs["params"], tokens)
    d = _logits(dataclasses.replace(published, rope_theta=10.0),
                inputs["params"], tokens)
    assert float(jnp.max(jnp.abs(c - d))) > 1e-5
    assert float(jnp.max(jnp.abs(a - c))) > 1e-5


def test_the_router_and_experts_scopes_are_on_the_device_operations(inputs):
    engine = FT.make_engine(2, 1, _block_config(),
                            devices=jax.devices()[:1])
    params = inputs["params"]
    text = engine._round.lower(
        engine, params, engine.optimizer.init(params),
        engine.shard_tokens(inputs["tokens"][0]), inputs["mask"]
    ).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    for scope in ("router", "experts", "attention", "embed",
                  "lm_head_loss", "qkv", "rotary", "attn_out", "norms"):
        assert any(re.search(rf"[/(]{scope}[/)]", n) for n in names), scope
    assert not any(re.search(r"[/(]mlp[/)]", n) for n in names)


@pytest.mark.parametrize("attention", ["ring", "flash"])
def test_ring_and_flash_refuse_a_block_they_cannot_run(attention):
    with pytest.raises(ValueError, match="recompute"):
        _block_config(attention=attention)
    with pytest.raises(ValueError, match="recompute"):
        FT.TransformerConfig(attention=attention, window=8)
    with pytest.raises(ValueError, match="recompute"):
        FT.TransformerConfig(attention=attention, n_heads=4, n_kv_heads=2)
    q = jnp.zeros((1, 4, 8, 8))
    with pytest.raises(ValueError, match="key/value heads"):
        FA.flash_attention(q, q[:, :2], q[:, :2], interpret=True)


def test_a_layout_of_another_length_is_refused():
    with pytest.raises(ValueError, match="rope_layout"):
        FT.TransformerConfig(n_layers=2, positions="rotary",
                             rope_layout=(0, 1, 1))
    with pytest.raises(ValueError, match="experts_held"):
        FT.TransformerConfig(ffn="experts", n_experts=4, top_k=2)


# ------------------------------------------------------ the tiled attention
def _qkv(h_q, h_kv, t, d=8, b=2, seed=3):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (b, h_q, t, d)),
            jax.random.normal(ks[1], (b, h_kv, t, d)),
            jax.random.normal(ks[2], (b, h_kv, t, d)),
            jax.random.normal(ks[3], (b, h_q, t, d)))


def _value_and_grads(f, q, k, v, w):
    return jax.value_and_grad(
        lambda q, k, v: jnp.sum(w * f(q, k, v)), argnums=(0, 1, 2))(q, k, v)


def _close(got, want, rtol=2e-5, atol=2e-5):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=rtol, atol=atol)


def test_grouped_query_attention_equals_repeated_kv_attention():
    """[B, 2, T, D] keys and values beside [B, 6, T, D] queries give what
    the same path gives on keys and values repeated to 6 heads, forward and
    backward (a kv head's gradient is the sum over its query heads), and
    both give the dense masked softmax."""
    q, k, v, w = _qkv(6, 2, 40)
    grouped = _value_and_grads(
        lambda q, k, v: FA.recompute_attention(q, k, v, causal=True,
                                               block_q=16, block_k=8),
        q, k, v, w)
    repeated = _value_and_grads(
        lambda q, k, v: FA.recompute_attention(
            q, jnp.repeat(k, 3, axis=1), jnp.repeat(v, 3, axis=1),
            causal=True), q, k, v, w)
    dense = _value_and_grads(
        lambda q, k, v: FA.reference(q, k, v, causal=True), q, k, v, w)
    _close(grouped, repeated)
    _close(grouped, dense)
    _close(repeated, dense)


@pytest.mark.parametrize("t,window,block_q,block_k,h_kv", [
    (40, 12, 16, 8, 2),    # T > window, blocks that skip
    (37, 5, 16, 8, 2),     # nothing a multiple of anything
    (32, 7, 8, 8, 4),      # as many kv heads as query heads
    (32, 64, 8, 16, 1),    # a window longer than the sequence: full
])
def test_a_windowed_layer_equals_the_dense_masked_softmax(
        t, window, block_q, block_k, h_kv):
    q, k, v, w = _qkv(4, h_kv, t)
    tiled = _value_and_grads(
        lambda q, k, v: FA.recompute_attention(
            q, k, v, causal=True, window=window, block_q=block_q,
            block_k=block_k), q, k, v, w)
    dense = _value_and_grads(
        lambda q, k, v: FA.reference(q, k, v, causal=True, window=window),
        q, k, v, w)
    _close(tiled, dense)


def test_the_tiled_path_keeps_a_shards_offsets():
    q, k, v, w = _qkv(4, 2, 32)
    tiled = _value_and_grads(
        lambda q, k, v: FA.recompute_attention(
            q, k, v, q_offset=32, k_offset=16, causal=True, window=20,
            block_q=8, block_k=8), q, k, v, w)
    dense = _value_and_grads(
        lambda q, k, v: FA.reference(q, k, v, q_offset=32, k_offset=16,
                                     causal=True, window=20), q, k, v, w)
    _close(tiled, dense)


def test_key_blocks_outside_the_window_are_not_visited():
    """The walk of a query block is `[lo, hi)` key blocks: none above the
    causal diagonal, none wholly before the window."""
    lo, hi = FA._key_block_range(
        jnp.int32(5), 8, 8, 8, 64, jnp.int32(0), jnp.int32(0), True, 16)
    # queries 40..47 see keys 25..47: key blocks 3, 4, 5
    assert (int(lo), int(hi)) == (3, 6)
    lo, hi = FA._key_block_range(
        jnp.int32(5), 8, 8, 8, 64, jnp.int32(0), jnp.int32(0), True, None)
    assert (lo, int(hi)) == (0, 6)
    with pytest.raises(ValueError, match="causal"):
        FA.recompute_attention(*_qkv(4, 2, 16)[:3], window=4)


# --------------------------------- the default block is the parent's block
# taken on the parent commit (51a384b) with the script in PERF.md section 6:
# sha256 of `engine._round.lower(...).as_text()` (the program as XLA gets
# it), the first loss and the norm of the first gradient, for
# TransformerConfig(vocab=97, d_model=32, n_heads=4, n_layers=2, max_len=16).
# That commit's `_round` donated nothing, and its `_forward` ran a plain Python
# block for every layer: the hashes are held to the round's body under a
# `jax.jit` that donates nothing either, lowered with the block's own `jit`
# taken out (`_plain_block`), and the program that runs to that text plus the
# donated arguments' attributes (`_DONOR`) and the block as a function called
# once a layer (`test_the_block_is_traced_and_lowered_once_a_kind`).
# The `recompute` rows pinned the untiled walk, which is gone: their hashes
# are the tiled walk's (the same recipe, at 282bc0e's child), and their loss
# and gradient norm are held to the `ring` row's, another algorithm for the
# same mathematics. PR 38 put the walk inside two Pallas kernels where the
# program is compiled for a TPU; these rows (and `PINNED_SMALLTHINKER`) say
# `flash_interpret=True`, `attention_tile` leaves them on the XLA walk, and
# their texts stand unchanged
# (`test_the_pinned_recompute_rows_take_the_xla_walk`)
PARENT = {
    ("recompute", False): (
        "51d2fa5c50337eb3f53d7eb7240a1ff45a4a1056be2998f69eef33924b10e4ea",
        "0x1.25426a0000000p+2", 0.8295300006866455),
    ("recompute", True): (
        "bfbcface6de379a3f115e055ea30dcdb6c58d6dba8df4bba256fa0b955bf4513",
        "0x1.25426a0000000p+2", 0.8295300006866455),
    ("flash", False): (
        "59d4fc8162b74b07a60a46e49cf1a41fffa05ca5edb49744bbc20b3c8af3d64e",
        "0x1.25426a0000000p+2", 0.8295300006866455),
    ("ring", False): (
        "60c7745e806715842d2163ffa328a0b8bbc973ea416eb20466751bb45bf2487b",
        "0x1.25426a0000000p+2", 0.8295300006866455),
}
# `_block_config()`'s `_round` body with tiles of 8 and the plain block (the
# same recipe). On the parent commit (282bc0e) it read 8a6da479...; the walk is
# the one that configuration ran there, and the text differs where the
# backward adds `dK` / `dV` into their slice: four scatters (what jax's rule
# made of the update under the stations' `vmap`) are four
# `dynamic_update_slice`s (579dc598... from PR 34 on). Since PR 37 the expert
# layer walks only the row blocks that carry an assignment, one station
# after another, and writes its own backward pass: the text is another
PINNED_SMALLTHINKER = (
    "87916e5665453fd7afe50e2386b85b4abd33c5635382a5881b881dd8ad9784e1")


def _parent_init_params(key, cfg):
    """`init_params` as the parent commit had it, word for word."""
    keys = jax.random.split(key, 2 + 4 * cfg.n_layers)
    s = 0.02
    params = {
        "embed": s * jax.random.normal(keys[0], (cfg.vocab, cfg.d_model)),
        "pos": s * jax.random.normal(keys[1], (cfg.max_len, cfg.d_model)),
        "layers": [],
    }
    for i in range(cfg.n_layers):
        k = keys[2 + 4 * i: 6 + 4 * i]
        params["layers"].append({
            "qkv": s * jax.random.normal(k[0], (cfg.d_model, 3 * cfg.d_model)),
            "proj": s * jax.random.normal(k[1], (cfg.d_model, cfg.d_model)),
            "w_up": s * jax.random.normal(k[2], (cfg.d_model, 4 * cfg.d_model)),
            "w_down": s * jax.random.normal(k[3], (4 * cfg.d_model, cfg.d_model)),
        })
    return params


# what `donate_argnums` writes on an argument of the lowered `main`: the
# output it is aliased to or, where jax leaves the pairing to XLA, a mark
_DONOR = re.compile(
    r", (?:tf\.aliasing_output = \d+ : i32|jax\.buffer_donor = true)")


def _default_block(attention, remat, n_layers=2):
    cfg = FT.TransformerConfig(
        vocab=97, d_model=32, n_heads=4, n_layers=n_layers, max_len=16,
        attention=attention, flash_interpret=True, remat=remat)
    engine = FT.make_engine(4, 1, cfg, devices=jax.devices()[:1])
    tokens = engine.shard_tokens(FT.make_federated_tokens(4, 2, 16, 97))
    return engine, (*engine.init(jax.random.key(0)), tokens, jnp.ones(4))


def _body_text(engine, args) -> str:
    """The round's body lowered under a `jax.jit` that donates nothing."""
    body = FT.FedTransformer._round.__wrapped__
    return jax.jit(body, static_argnums=0).lower(engine, *args).as_text()


class _JaxWithoutJit:
    """`jax` as `fed_transformer` names it, with a `jit` that hands its
    function back."""

    def __getattr__(self, name):
        return getattr(jax, name)

    @staticmethod
    def jit(fun, **_):
        return fun


@pytest.fixture
def plain_block(monkeypatch):
    """`_forward` runs the plain Python block for every layer, as it did
    before the block was jitted: what the pinned texts were taken with, and
    the copy the jitted block's numbers are held to. A program traced under
    it belongs to the engine it was traced for (jax keys its caches on the
    engine): take a new engine for the jitted block."""
    monkeypatch.setattr(FT, "jax", _JaxWithoutJit())


@pytest.mark.parametrize("attention,remat", sorted(PARENT))
def test_donation_marks_the_states_arguments_and_changes_nothing_else(
        attention, remat):
    """The program that runs is the body's text with one attribute more on
    each leaf of `params` and `opt_state` (three trees of the parameters'
    shape and Adam's count) and none on `tokens` or `mask`: the same work,
    written into the buffers it was handed."""
    engine, args = _default_block(attention, remat)
    text = engine._round.lower(engine, *args).as_text()
    stripped, n_marked = _DONOR.subn("", text)
    assert stripped == _body_text(engine, args)
    assert n_marked == 3 * len(jax.tree.leaves(args[0])) + 1
    main = re.search(r"func\.func public @main\((.*?)\) -> ", text).group(1)
    marked = [bool(_DONOR.search(a)) for a in main.split("%arg")[1:]]
    assert marked == [True] * n_marked + [False, False]


@pytest.mark.parametrize("attention,remat", sorted(PARENT))
def test_the_default_block_is_the_parents_bit_for_bit(
        attention, remat, plain_block):
    """`init_params` gives the parent's arrays, `_round`'s body with the
    plain block lowers to the parent's program (so its loss and gradient are
    the parent's bits on any machine), and on this one they read the parent's
    golden values."""
    engine, (params, opt_state, tokens, mask) = _default_block(
        attention, remat)
    cfg = engine.cfg
    want = _parent_init_params(jax.random.key(0), cfg)
    assert jax.tree.structure(params) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        assert np.array_equal(a, b)
    lowered, loss_hex, grad_norm = PARENT[attention, remat]
    text = _body_text(engine, (params, opt_state, tokens, mask))
    assert "layer_block" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == lowered
    _, new_state, loss = engine.round(params, opt_state, tokens, mask)
    assert float(loss) == pytest.approx(float.fromhex(loss_hex), rel=1e-6)
    assert (loss_hex, grad_norm) == PARENT["ring", False][1:]
    assert _first_grad_norm(new_state) == pytest.approx(
        grad_norm, rel=1e-6 if attention == "recompute" else 1e-5)
    assert engine.record_expert_load() is None  # a block without experts


def test_the_pinned_recompute_rows_take_the_xla_walk(inputs):
    """What the pins of this file hold is the XLA walk: the rule keeps it
    wherever a kernel would be interpreted, and says so on the span."""
    engine, _ = _default_block("recompute", False)
    assert engine.attention_walk(16)["attention_path"] == "walk"
    engine, _ = _smallthinker_round(inputs)
    assert engine.attention_walk(16)["attention_path"] == "walk"
    # compiled, heads of whole lane tiles would leave it: not these
    cfg = engine.cfg
    for head_dim, path in ((cfg.head_dim, "walk"), (128, "kernel")):
        assert FA.attention_tile(
            16, 16, head_dim, cfg.n_heads // cfg.n_kv_heads, cfg.dtype,
            False).path == path


def _first_grad_norm(opt_state) -> float:
    """The norm of the first averaged gradient, from Adam's first moment
    after one step (mu = 0.1 g)."""
    return 10 * float(jnp.sqrt(sum(
        jnp.sum(x ** 2) for x in jax.tree.leaves(opt_state[0].mu))))


def _smallthinker_round(inputs):
    engine = FT.make_engine(2, 1, _block_config(),
                            devices=jax.devices()[:1])
    params = inputs["params"]
    return engine, (params, engine.optimizer.init(params),
                    engine.shard_tokens(inputs["tokens"][0]), inputs["mask"])


def test_the_smallthinker_block_lowers_to_the_pinned_text(
        inputs, plain_block):
    """The configuration that ran the tiled walk before every call did: its
    `_round` body, at the tiny sizes and the tiles of this file."""
    text = _body_text(*_smallthinker_round(inputs))
    assert hashlib.sha256(text.encode()).hexdigest() == PINNED_SMALLTHINKER


# ------------------------------------------- one block per kind of layer
def _functions(text: str) -> list[str]:
    return re.findall(r"func\.func (?:public |private )?@([\w.]+)", text)


@pytest.mark.parametrize("attention,remat", [
    ("ring", False), ("ring", True), ("recompute", False), ("flash", True)])
def test_the_block_is_traced_and_lowered_once_a_kind(attention, remat):
    """`_forward` hands every layer of a kind to one jitted block: the
    lowered round holds the block's function once a direction (forward and
    backward) and calls it once a layer, so the text's functions do not grow
    with the depth; the block's scopes are still on the compiled
    operations."""
    def lowered(n_layers):
        engine, args = _default_block(attention, remat, n_layers)
        return engine._round.lower(engine, *args)

    six = lowered(6)
    text = six.as_text()
    blocks = [name for name in _functions(text)
              if name.startswith("layer_block")]
    assert len(blocks) == 2
    assert len(_functions(text)) == len(_functions(lowered(2).as_text()))
    for name in blocks:  # forward, backward: each called once a layer
        assert len(re.findall(rf"call @{name}\(", text)) == 6
    names = re.findall(r'op_name="([^"]*)"', six.compile().as_text())
    for scope in ("qkv", "attention", "attn_out", "mlp", "norms"):
        assert any(re.search(rf"/local_train/.*jit\(layer_block\).*/{scope}/", n)
                   for n in names), scope
    # the block's own name is no scope of the device's operations
    assert "layer_block" not in profiling.DEVICE_SCOPES


@pytest.mark.parametrize("which", ["six_layers", "smallthinker"])
def test_tracing_the_round_runs_the_block_once_a_kind(
        which, inputs, monkeypatch):
    """Counted, not timed: `_norm` runs while `_forward` is traced, twice in
    a dense block (before attention and before the MLP) and once for the
    head, so six layers of one kind call it 2 + 1 times. SmallThinker's four
    layers are of two kinds (full and no rotation; window and rotary); a
    block with experts calls it once and `expert_half`, which stays outside
    the block and is traced for every layer, once more: 2 + 4 + 1."""
    calls = []
    norm = FT._norm
    monkeypatch.setattr(FT, "_norm", lambda *a: calls.append(1) or norm(*a))
    if which == "six_layers":
        engine, args = _default_block("ring", False, n_layers=6)
        expected = 2 + 1
    else:
        engine, args = _smallthinker_round(inputs)
        cfg = engine.cfg
        kinds = {(cfg.layer_window(i), cfg.layer_rotates(i))
                 for i in range(cfg.n_layers)}
        assert (len(kinds), cfg.n_layers) == (2, 4)
        expected = 2 + 4 + 1
    jax.make_jaxpr(FT.FedTransformer._round.__wrapped__, static_argnums=0)(
        engine, *args)
    assert len(calls) == expected


@pytest.mark.parametrize("attention,remat", [
    ("ring", False), ("recompute", False), ("recompute", True)])
def test_the_jitted_block_reads_what_the_plain_block_reads(
        attention, remat, request):
    """Three rounds of six layers with the block jitted (as shipped) and with
    the plain Python block for every layer: the same losses and the same
    first gradient, to rounding (XLA inlines the calls; what it fuses after
    that may round another way)."""
    def three_rounds():
        engine, (params, opt_state, tokens, mask) = _default_block(
            attention, remat, n_layers=6)
        losses = []
        for _ in range(3):
            params, opt_state, loss = engine.round(
                params, opt_state, tokens, mask)
            losses.append(float(loss))
            if len(losses) == 1:
                grad = _first_grad_norm(opt_state)
        return losses, grad

    jitted = three_rounds()
    request.getfixturevalue("plain_block")
    plain = three_rounds()
    assert jitted[0] == pytest.approx(plain[0], rel=1e-6)
    assert jitted[1] == pytest.approx(plain[1], rel=1e-5)
    assert jitted[0][2] < jitted[0][0]  # and the rounds learn


def test_under_the_stations_vmap_attention_adds_no_scatter():
    """`FedTransformer._round` walks the packed stations with `vmap`: the
    backward's in-place `dK` / `dV` update stays an update there (`_add_at`;
    as the scatter jax's rule makes of it, it cost the chip more than the
    tile's products). The round holds the scatters the embedding brings,
    as many as with the ring, which updates nothing in place."""
    def scatters(attention):
        engine, args = _default_block(attention, False)
        text = engine._round.lower(engine, *args).as_text()
        return text.count("stablehlo.scatter")

    assert scatters("recompute") == scatters("ring") > 0
