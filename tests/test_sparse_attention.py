"""Learned sparse attention and the block around it (`TransformerConfig`'s
``qk_norm``, ``router_input="normed"``, ``expert_act="silu"`` and
``sparse_top_k``): the program against the plain reference of
`keye-vl2-30b-a3b-ep8-2st` on seeded weights (losses, logits, every leaf's
gradient), the op against `recompute_attention` and `lax.top_k`, each loss
reaching only its own leaves, the shares of the SwiGLU expert layer, the
chunked head, and what the round records. CPU, tiny sizes, float32."""
from __future__ import annotations

import dataclasses
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import cells, compare
from vantage6_tpu.models import experts as X
from vantage6_tpu.ops import sparse_attention as SA
from vantage6_tpu.runtime.tracing import TRACER
from vantage6_tpu.workloads import fed_transformer as FT

FA = importlib.import_module("vantage6_tpu.ops.flash_attention")
REFERENCE = cells.load_module(
    cells.HERE / "configs" / "keye-vl2-30b-a3b-ep8-2st.py")

# the tiny Keye: 16 experts routed over, chip 1 of 4 holds 4, two a token;
# an indexer of two heads of 8 keeping 8 keys a query of 32
CONFIG = {
    "name": "tiny", "head_dim": 8, "hidden_size": 32, "hidden_act": "silu",
    "max_position_embeddings": 32, "moe_intermediate_size": 16,
    "num_experts": 4, "num_experts_per_tok": 2, "num_local_experts": 16,
    "num_attention_heads": 4, "num_hidden_layers": 2,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-6, "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                  "indexer_num_kv_heads": 1, "topk": 8},
    "tie_word_embeddings": False, "vocab_size": 97,
    "expert_parallel": {"chips": 4, "this_chip": 1},
    "initializer_range": 0.02, "embedding_initializer_range": 1.0,
    "n_stations": 2,
    "adam": {"lr": 0.001, "b1": 0.9, "b2": 0.999, "eps": 1e-08},
}
TRAFFIC = {"batch": 2, "seq_len": 32, "n_batches": 3, "zipf_exponent": 1.0}
T = TRAFFIC["seq_len"]


@pytest.fixture(autouse=True)
def _small_blocks(monkeypatch):
    """Tiles of 8 and selections of 4 rows: the walks cross several key
    blocks and a tile's rows come from two selections, as at the cell's
    size."""
    monkeypatch.setattr(FA, "TILED_BLOCK", 8)
    monkeypatch.setattr(SA, "SELECT_ROWS", 4)


def _config(**changes) -> FT.TransformerConfig:
    c, sa = CONFIG, CONFIG["sa_config"]
    held = c["num_experts"]
    first = c["expert_parallel"]["this_chip"] * held
    return dataclasses.replace(FT.TransformerConfig(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_layers=c["num_hidden_layers"],
        max_len=c["max_position_embeddings"], dtype=jnp.float32,
        attention="recompute", remat=True, flash_interpret=True,
        norm="rmsnorm", norm_eps=c["rms_norm_eps"], head_dim=c["head_dim"],
        n_kv_heads=c["num_key_value_heads"], positions="rotary",
        rope_theta=float(c["rope_theta"]), qk_norm=True, ffn="experts",
        router_input="normed", expert_act="silu",
        n_experts=c["num_local_experts"], top_k=c["num_experts_per_tok"],
        d_expert=c["moe_intermediate_size"],
        experts_held=tuple(range(first, first + held)), tie_head=False,
        sparse_top_k=sa["topk"], indexer_heads=sa["indexer_num_heads"],
        indexer_dim=sa["indexer_head_dim"]), **changes)


@pytest.fixture(scope="module")
def inputs():
    got = REFERENCE.make_inputs(CONFIG, TRAFFIC, jax.random.key(5))
    # scales away from 1 and a router and indexer whose choices are well
    # apart, so that a scale left out or a rounding flipping a choice shows
    for i, layer in enumerate(got["params"]["layers"]):
        layer["router"] = layer["router"] * 100.0
        for name in ("idx_q", "idx_k", "idx_w"):
            layer[name] = layer[name] * 30.0
        for name, scale in (("norm1", 1.3), ("norm2", 0.7), ("q_norm", 1.2),
                            ("k_norm", 0.8), ("idx_norm", 1.1)):
            layer[name] = layer[name] * (scale + 0.1 * i)
    return got


def _fresh_state(engine, inputs):
    params = jax.tree.map(jnp.copy, inputs["params"])
    return params, engine.optimizer.init(params)


def _in_mesh(fn, cfg, params, tokens):
    """``fn(params, tokens [B, T])`` inside the one-device mesh the engine
    builds, where `_forward` finds its sequence axis."""
    engine = FT.make_engine(1, 1, cfg, devices=jax.devices()[:1])
    P = jax.sharding.PartitionSpec
    return jax.shard_map(fn, mesh=engine.mesh,
                         in_specs=(P(), P(None, FT.SEQ_AXIS)), out_specs=P(),
                         check_vma=False)(params, tokens)


def _program_parts(cfg):
    """The program's logits [B, T, V], mean LM loss and indexer loss (a mean
    over the positions, summed over the layers)."""
    def parts(params, tokens):
        states, _, terms = FT._forward(params, tokens, cfg, FT.SEQ_AXIS)
        logits = states[0] @ FT._head(params, cfg)
        logp = jax.nn.log_softmax(logits[:, :-1])
        lm = -jnp.mean(jnp.take_along_axis(
            logp, tokens[:, 1:, None], axis=-1))
        indexer = sum(loss for loss, _ in terms) / tokens.size
        return logits, lm, indexer
    return parts


def _reference_parts(params, row):
    h, kl, _ = REFERENCE._sequence_forward(params, row, CONFIG, "float32")
    logits = REFERENCE.logits(params, h)
    logp = jax.nn.log_softmax(logits[:-1])
    lm = -jnp.mean(jnp.take_along_axis(logp, row[1:, None], axis=-1))
    return logits, lm, kl / row.size


# ------------------------------------------ the program against the reference
def test_the_rounds_follow_the_plain_reference(inputs):
    """`make_engine` + `FedTransformer.round`: the losses, the first
    gradient and the parameters' change are the reference's, and the
    `experts.load` record holds the counts the reference computes."""
    engine = FT.make_engine(2, 1, _config(), lr=CONFIG["adam"]["lr"],
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
    want = REFERENCE.reference_train(
        CONFIG, {**TRAFFIC, "seq_len": T}, inputs, 2)
    np.testing.assert_allclose(losses, want["losses"], rtol=2e-6)
    assert set(grad_norms) == set(want["grad_norms"])
    for name, norm in want["grad_norms"].items():
        assert grad_norms[name] == pytest.approx(norm, rel=2e-4), name
    for name, norm in want["change_norms"].items():
        assert change[name] == pytest.approx(norm, rel=2e-3), name
    recorded = engine.record_expert_load()
    first = REFERENCE.expert_load(CONFIG, inputs["params"],
                                  inputs["tokens"][0])
    assert recorded["assignments_by_round"][0] == first.sum()


def test_logits_both_losses_and_every_gradient_are_the_references(inputs):
    cfg = _config()
    params, row = inputs["params"], inputs["tokens"][0, 0, :1]
    logits, lm, indexer = jax.jit(
        lambda p: _in_mesh(_program_parts(cfg), cfg, p, row))(params)
    want = jax.jit(_reference_parts)(params, row[0])
    np.testing.assert_allclose(logits[0], want[0], rtol=1e-5, atol=1e-5)
    assert float(lm) == pytest.approx(float(want[1]), rel=1e-6)
    assert float(indexer) == pytest.approx(float(want[2]), rel=1e-5)
    assert float(indexer) > 0  # the indexer is not yet the attention

    def program_loss(p):
        _, lm, indexer = _in_mesh(_program_parts(cfg), cfg, p, row)
        return lm + indexer

    got = jax.jit(jax.grad(program_loss))(params)
    ref = jax.jit(jax.grad(
        lambda p: sum(_reference_parts(p, row[0])[1:])))(params)
    flat, _ = jax.tree_util.tree_flatten_with_path(ref)
    for (path, r), g in zip(flat, jax.tree.leaves(got)):
        scale = float(jnp.max(jnp.abs(r)))
        assert scale > 0, compare.leaf_name(path)
        np.testing.assert_allclose(g, r, rtol=1e-3, atol=1e-4 * scale,
                                   err_msg=compare.leaf_name(path))


def test_each_loss_reaches_only_its_own_leaves(inputs):
    """The LM loss reaches the indexer through nothing (top-k has no
    gradient) and the indexer's loss reaches nothing but the indexer (its
    input and the attention's probabilities are constants to it)."""
    cfg = _config()
    params, row = inputs["params"], inputs["tokens"][1, 0, :1]
    parts = _program_parts(cfg)
    lm, idx = jax.jit(lambda p: [
        jax.grad(lambda p: _in_mesh(parts, cfg, p, row)[i])(p)
        for i in (1, 2)])(params)
    indexer_leaves = ("idx_q", "idx_k", "idx_w", "idx_norm")
    for tree, zero_where in ((lm, True), (idx, False)):
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        for path, g in flat:
            name = compare.leaf_name(path)
            is_indexer = name.split(".")[-1] in indexer_leaves
            if is_indexer == zero_where:
                assert not np.any(np.asarray(g)), name
            else:
                assert np.any(np.asarray(g)), name


def _selections(cfg, inputs) -> int:
    """The selection's passes in the compiled gradient of the tiny loss
    (`_program_parts`): the while loops of `_threshold`'s bit search, the
    one loop under the `select` scope, once a pass."""
    params, row = inputs["params"], inputs["tokens"][0, 0, :1]
    parts = _program_parts(cfg)

    def loss(p):
        _, lm, indexer = _in_mesh(parts, cfg, p, row)
        return lm + indexer

    text = jax.jit(jax.grad(loss)).lower(params).compile().as_text()
    names = re.findall(r' while\(.*op_name="([^"]*)"', text)
    return sum(name.endswith("/select/while") for name in names)


@pytest.mark.parametrize("remat", [True, False])
def test_the_selection_runs_once_a_layer(inputs, monkeypatch, remat):
    """The layer's checkpoint keeps the selection (`FT.remat_saved`), so
    the backward pass rebuilds neither the indexer's scores nor the top-k
    search: one selection a layer, as without remat. The plain checkpoint
    keeps nothing, and its backward pass selects again."""
    cfg = _config(remat=remat)
    assert FT.remat_saved(cfg) == (FT.SELECTION if remat else ())
    assert _selections(cfg, inputs) == cfg.n_layers
    if remat:
        monkeypatch.setattr(FT, "remat_saved", lambda cfg: ())
        assert _selections(cfg, inputs) == 2 * cfg.n_layers


def test_the_kept_selection_gives_the_plain_checkpoints_bits(
        inputs, monkeypatch):
    """The selection kept across the checkpoint is the one the plain
    checkpoint rebuilds, bit for bit: the tiny round's loss and every leaf
    of its gradient are the plain checkpoint's exactly."""
    cfg = _config()
    params, row = inputs["params"], inputs["tokens"][0, 0, :1]

    def loss_and_grad():
        return jax.jit(jax.value_and_grad(lambda p: _in_mesh(
            lambda p, t: FT._loss_and_load(p, t, cfg, FT.SEQ_AXIS)[0],
            cfg, p, row)))(params)

    kept = loss_and_grad()
    monkeypatch.setattr(FT, "remat_saved", lambda cfg: ())
    plain = loss_and_grad()
    assert float(kept[0]).hex() == float(plain[0]).hex()
    flat, _ = jax.tree_util.tree_flatten_with_path(plain[1])
    for (path, want), got in zip(flat, jax.tree.leaves(kept[1])):
        assert np.array_equal(got, want), compare.leaf_name(path)


@pytest.mark.parametrize("router_input", ["block", "normed"])
def test_beside_sparse_attention_the_router_reads_what_the_config_names(
        inputs, router_input):
    """Layer 0's expert counts are those of routing the block's input (the
    embedding) or the normed stream after attention, as ``router_input``
    says; the two differ (a norm scale of its own per channel turns the
    stream the router reads)."""
    cfg = _config(router_input=router_input)
    params, row = inputs["params"], inputs["tokens"][0, 0, :1]
    params = {**params, "layers": [{**params["layers"][0], "norm2": jnp.exp(
        jax.random.normal(jax.random.key(7), params["layers"][0]["norm2"].shape
                          ))}, *params["layers"][1:]]}
    loads = jax.jit(lambda p: _in_mesh(
        lambda p, t: FT._forward(p, t, cfg, FT.SEQ_AXIS)[1], cfg, p,
        row))(params)
    normed = REFERENCE.expert_load(CONFIG, params, row[None])[0]
    choice, _ = REFERENCE.route(params["embed"][row[0]],
                                params["layers"][0]["router"], cfg.top_k)
    block = np.array([int(jnp.sum(choice == e)) for e in cfg.experts_held])
    assert not np.array_equal(normed, block)
    want = block if router_input == "block" else normed
    assert np.array_equal(np.asarray(loads[0]["assignments"]), want)


# ----------------------------------------------------------------- the op
def _op_inputs(t=T, h_q=4, h_kv=2, d=8, h_i=2, d_i=8, b=2, seed=3):
    ks = jax.random.split(jax.random.key(seed), 7)
    return ([jax.random.normal(ks[0], (b, h_q, t, d)),
             jax.random.normal(ks[1], (b, h_kv, t, d)),
             jax.random.normal(ks[2], (b, h_kv, t, d))],
            [jax.random.normal(ks[3], (b, t, h_i, d_i)),
             jax.random.normal(ks[4], (b, t, d_i)),
             jax.random.normal(ks[5], (b, t, h_i))],
            jax.random.normal(ks[6], (b, h_q, t, d)))


def _unpack(keep, t):
    """`select`'s keep -> [B, T, T] bool."""
    bits = (keep[..., None] >> jnp.arange(8, dtype=jnp.uint8)) & 1
    n, b, rows = keep.shape[:3]
    bits = jnp.moveaxis(bits.reshape(n, b, rows, t), 0, 1)
    return bits.reshape(b, t, t) != 0


def test_keeping_every_key_is_causal_recompute_attention():
    (q, k, v), idx, w = _op_inputs()
    keep, _, tiles = SA.select(*idx, T, 8, 8, interpret=True)
    assert int(tiles) == 2 * 10  # every visible tile of 4 x 4, both rows

    def sparse(q, k, v):
        return SA.attend(q, k, v, keep, 0, 0, 8, 8)[0]

    def dense(q, k, v):
        return FA.recompute_attention(q, k, v, causal=True, block_q=8,
                                      block_k=8)

    got = jax.value_and_grad(lambda *a: jnp.sum(w * sparse(*a)),
                             argnums=(0, 1, 2))(q, k, v)
    want = jax.value_and_grad(lambda *a: jnp.sum(w * dense(*a)),
                              argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("top", [1, 5, 8, 31])
def test_the_selection_is_top_k_of_the_reference_scores(top):
    """Through `select`: the kept keys are `lax.top_k` of the reference's
    scores, kept where visible, and the tiles counted are those that hold
    one."""
    _, idx, _ = _op_inputs(seed=top)
    keep, log_norm, tiles = SA.select(*idx, top, 8, 8, interpret=True)
    got = _unpack(keep, T)
    for b in range(2):
        scores = REFERENCE.scores(idx[0][b], idx[1][b], idx[2][b])
        want = REFERENCE.selection(scores, jnp.arange(T), top)
        assert np.array_equal(got[b], want), b
        np.testing.assert_allclose(
            log_norm[b], jax.nn.logsumexp(
                jnp.where(want, scores, -jnp.inf), -1), rtol=1e-6)
    held = got.reshape(2, 4, 8, 4, 8).any(axis=(2, 4))
    assert int(tiles) == int(held.sum())


@pytest.mark.parametrize("first", [0, 16, 48])  # x 8
def test_the_scores_kernel_is_the_xla_sum_where_a_block_sees(
        first, monkeypatch):
    """`_block_scores`' Pallas kernel, interpreted, on the keys a block of
    16 queries starting at ``first`` sees (key tiles of 128), under the
    stations' `vmap` too: the XLA sum's scores; tiles wholly after the
    block's last query are 0."""
    import functools

    monkeypatch.setattr(SA, "SCORE_TILE", 16 * 16)
    monkeypatch.setattr(SA.pl, "pallas_call", functools.partial(
        SA.pl.pallas_call, interpret=True))
    _, (q_idx, k_idx, w), _ = _op_inputs(t=512, seed=first)
    first = first * 8
    q_blk, w_blk = q_idx[:, first:first + 16], w[:, first:first + 16]
    want = SA.indexer_scores(q_blk, k_idx, w_blk)
    seen = np.arange(512) // 128 * 128 < first + 16
    for got in (SA._block_scores(q_blk, k_idx, w_blk, first, False),
                jax.vmap(lambda q, k, w: SA._block_scores(
                    q, k, w, first, False))(
                    q_blk[:, None], k_idx[:, None], w_blk[:, None])[:, 0]):
        np.testing.assert_allclose(got[..., seen], want[..., seen],
                                   rtol=1e-5, atol=1e-5)
        assert not np.any(np.asarray(got[..., ~seen]))


@pytest.mark.parametrize("seed", range(4))
def test_ties_go_to_the_lower_key_as_lax_top_k_has_it(seed):
    """Scores on a coarse grid (many equal, ``-0.0`` beside ``0.0``): the
    kept pairs are the reference's dense `lax.top_k`, ties included."""
    key = jax.random.key(seed)
    scores = jnp.round(jax.random.normal(key, (T, T)) * 2) / 2
    scores = jnp.where(scores == 0, -0.0, scores).at[::3].set(0.0)
    q_pos = jnp.arange(T)
    for top in (1, 3, 8, 20):
        k = jnp.minimum(top, q_pos + 1)
        got = SA.top_keys(scores, jnp.arange(T)[None] <= q_pos[:, None], k)
        want = REFERENCE.selection(scores + 0.0, q_pos, top)
        assert np.array_equal(got, want), top


# ------------------------------------------------- the SwiGLU expert layer
def test_the_shares_of_all_eight_chips_add_up_to_the_uncut_layer():
    """SwiGLU experts routed over 16, held two a chip on eight chips: the
    chips' parts add up to the reference's layer with every expert held."""
    d, f, n_experts, chips = 16, 8, 16, 8
    ks = jax.random.split(jax.random.key(2), 5)
    h = jax.random.normal(ks[0], (24, d))
    w = {name: 0.3 * jax.random.normal(k, shape) for name, k, shape in (
        ("w_gate", ks[1], (n_experts, d, f)),
        ("w_up", ks[2], (n_experts, d, f)),
        ("w_down", ks[3], (n_experts, f, d)))}
    choice, weight = REFERENCE.route(
        h, 3.0 * jax.random.normal(ks[4], (d, n_experts)), 4)
    per = n_experts // chips
    total = sum(X.expert_layer(
        h, choice.astype(jnp.int32), weight,
        {name: x[c * per:(c + 1) * per] for name, x in w.items()},
        tuple(range(c * per, (c + 1) * per)), n_experts, interpret=True,
        activation="silu")[0] for c in range(chips))

    def mm(a, b):
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)

    uncut, _ = REFERENCE.held_experts_part(
        h, choice, weight, tuple(range(n_experts)), w["w_gate"], w["w_up"],
        w["w_down"], mm)
    np.testing.assert_allclose(total, uncut, rtol=1e-5, atol=1e-5)


# ------------------------------------------------------- the chunked head
def test_large_logits_are_taken_in_chunks_to_the_same_loss(inputs,
                                                          monkeypatch):
    """Where a sequence's float32 logits exceed `HEAD_LOGITS_BYTES` the head
    goes through `_token_nll`: the same loss and gradient. The cells whose
    programs are pinned stay under it."""
    cfg = _config()
    params, row = inputs["params"], inputs["tokens"][0, 0, :1]

    def loss_and_grad():
        return jax.jit(jax.value_and_grad(lambda p: _in_mesh(
            lambda p, t: FT._loss_and_load(p, t, cfg, FT.SEQ_AXIS)[0],
            cfg, p, row)))(params)

    whole = loss_and_grad()
    monkeypatch.setattr(FT, "HEAD_LOGITS_BYTES", 0)
    monkeypatch.setattr(FT, "HEAD_CHUNK", 8)
    chunked = loss_and_grad()
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(chunked)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    # a station's logits: GPT-2 [2, 1024] x 50,257 and SmallThinker
    # [1, 8192] x 18,992 whole; Keye's [1, 16384] x 18,992 in chunks
    for positions, vocab, chunked in ((2048, 50257, False),
                                      (8192, 18992, False),
                                      (16384, 18992, True)):
        assert (positions * vocab * 4 > 2**30) == chunked


# ------------------------------------------------------ what it records
def test_the_round_says_sparse_and_records_its_tiles(inputs):
    TRACER.configure(enabled=True, sample=1.0)
    TRACER.clear()
    engine = FT.make_engine(2, 1, _config(), devices=jax.devices()[:1])
    tokens = inputs["tokens"][0]
    state = _fresh_state(engine, inputs)
    for _ in range(2):
        *state, loss = engine.round(*state, engine.shard_tokens(tokens),
                                    inputs["mask"])
    jax.block_until_ready(loss)
    call = [s for s in TRACER.drain() if s["name"] == "engine.call"][-1]
    attrs = call["attrs"]
    assert attrs["attention_path"] == "sparse"
    assert attrs["attention_tile"] == "8x8"
    assert (attrs["sparse_topk"], attrs["indexer_heads"]) == (8, 2)
    assert attrs["remat_saved"] == ["keep", "log_norm", "tiles"]
    assert attrs["attention_tiles_visited"] == 2 * 10  # 2 layers, 4 x 4
    recorded = engine.record_sparse_tiles()
    span = [s for s in TRACER.drain() if s["name"] == "sparse.tiles"][-1]
    assert span["attrs"] == recorded
    assert recorded["rounds"] == 2
    assert recorded["tiles_visible_per_layer"] == 2 * 2 * 10
    per_layer = recorded["tiles_selected_per_layer"]
    assert len(per_layer) == 2 and all(0 < n <= 40 for n in per_layer)
    assert recorded["selected_tile_share"] == pytest.approx(
        sum(per_layer) / (2 * 40))
    assert engine.record_sparse_tiles() is None  # read, and emptied


def test_a_block_the_options_cannot_hold_is_refused():
    with pytest.raises(ValueError, match="sparse"):
        _config(indexer_heads=0)
    with pytest.raises(ValueError, match="sparse"):
        _config(attention="ring")
    with pytest.raises(ValueError, match="router input"):
        _config(router_input="after")
    with pytest.raises(ValueError, match="gate"):
        _config(expert_act="gelu")
