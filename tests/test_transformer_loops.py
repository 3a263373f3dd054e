"""A stack walked several times over the same weights (`TransformerConfig.
loops`), the exit gate and the exit-weighted loss, norms after each half of
the block and the gated MLP: the program agrees with the plain reference of
`ouro-2.6b-4l-2st`, loss and every gradient leaf; the block is lowered once
however often it is walked. CPU, tiny sizes, seeded weights, float32."""
from __future__ import annotations

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import cells, compare
from vantage6_tpu.runtime.tracing import TRACER
from vantage6_tpu.workloads import fed_transformer as FT

REFERENCE = cells.load_module(cells.HERE / "configs" / "ouro-2.6b-4l-2st.py")

CONFIG = {
    "name": "tiny", "head_dim": 8, "hidden_size": 32,
    "intermediate_size": 48, "max_position_embeddings": 32,
    "num_attention_heads": 4, "num_hidden_layers": 3,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "tie_word_embeddings": False, "total_ut_steps": 4, "vocab_size": 97,
    "initializer_range": 0.02, "embedding_initializer_range": 1.0,
    "exit_beta": 0.05, "n_stations": 2,
    "adam": {"lr": 0.001, "b1": 0.9, "b2": 0.999, "eps": 1e-08},
}
TRAFFIC = {"batch": 2, "seq_len": 32, "n_batches": 3, "zipf_exponent": 1.0,
           "compute_dtype": "float32", "attention": "recompute",
           "remat": True}
P = jax.sharding.PartitionSpec


def _config(**changes) -> FT.TransformerConfig:
    c = CONFIG
    return dataclasses.replace(FT.TransformerConfig(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_layers=c["num_hidden_layers"],
        max_len=c["max_position_embeddings"], dtype=jnp.float32,
        attention="recompute", remat=True, flash_interpret=True,
        norm="rmsnorm", norm_eps=c["rms_norm_eps"], norm_after=True,
        head_dim=c["head_dim"], n_kv_heads=c["num_key_value_heads"],
        positions="rotary", rope_theta=float(c["rope_theta"]), ffn="swiglu",
        d_ff=c["intermediate_size"], tie_head=False,
        loops=c["total_ut_steps"], exit_beta=c["exit_beta"]), **changes)


@pytest.fixture(scope="module")
def inputs():
    got = REFERENCE.make_inputs(CONFIG, TRAFFIC, jax.random.key(11))
    # scales away from 1 and a gate well away from 0, so that a scale left
    # out, a norm in the wrong place or a gate that weighs nothing would show
    for i, layer in enumerate(got["params"]["layers"]):
        layer["norm1"] = layer["norm1"] * (1.3 - 0.1 * i)
        layer["norm1_post"] = layer["norm1_post"] * (0.8 + 0.2 * i)
        layer["norm2"] = layer["norm2"] * (0.7 + 0.1 * i)
        layer["norm2_post"] = layer["norm2_post"] * (1.2 - 0.15 * i)
    got["params"]["final_norm"] = got["params"]["final_norm"] * 1.1
    gate = got["params"]["exit_gate"]
    gate["w"], gate["b"] = gate["w"] * 20.0, gate["b"] - 0.3
    return got


def _engine(cfg, n_stations=2):
    return FT.make_engine(n_stations, 1, cfg, lr=CONFIG["adam"]["lr"],
                          devices=jax.devices()[:1])


def _fresh_state(engine, params):
    """A state of the round's own: a round consumes what it is handed."""
    params = jax.tree.map(jnp.copy, params)
    return params, engine.optimizer.init(params)


def _loss_and_grads(cfg, params, tokens):
    """One station's loss and gradient as `_round` takes them."""
    engine = _engine(cfg, 1)
    return jax.shard_map(
        lambda p, t: jax.value_and_grad(FT.loss_local)(p, t, cfg),
        mesh=engine.mesh, in_specs=(P(), P(None, FT.SEQ_AXIS)),
        out_specs=(P(), P()), check_vma=False)(params, tokens)


def _gated(params, bias):
    """``params`` with the gate's product off and its bias at ``bias``."""
    gate = {"w": jnp.zeros_like(params["exit_gate"]["w"]),
            "b": jnp.full_like(params["exit_gate"]["b"], bias)}
    return {**params, "exit_gate": gate}


# --------------------------------------------- the program and the reference
def test_loss_and_every_gradient_leaf_are_the_references(inputs):
    """Float32 against float32 (the reference at HIGHEST, the program at the
    CPU's default, which is float32 too): the loss to 2e-6; every leaf of
    the gradient to 2e-4 of its largest entry (sums of another order over
    sixteen block applications), the shared layers' leaves, which gather
    four cotangents each, and the gate's among them."""
    tokens = inputs["tokens"][0, 0]
    loss, grads = _loss_and_grads(_config(), inputs["params"], tokens)
    want_loss, want = jax.value_and_grad(REFERENCE._loss)(
        inputs["params"], tokens, CONFIG, "float32")
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-6)
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    flat, _ = jax.tree_util.tree_flatten_with_path(want)
    for (path, ref), got in zip(flat, jax.tree.leaves(grads)):
        name = compare.leaf_name(path)
        assert float(jnp.max(jnp.abs(ref))) > 0, name
        np.testing.assert_allclose(
            got, ref, rtol=0, atol=2e-4 * float(jnp.max(jnp.abs(ref))),
            err_msg=name)


def test_the_rounds_follow_the_plain_reference(inputs):
    """`make_engine` + `FedTransformer.round`: the losses, the first averaged
    gradient and the parameters' change are the reference's, and the
    `exits.distribution` record holds what the reference computes."""
    TRACER.configure(enabled=True, sample=1.0)
    TRACER.clear()
    engine = _engine(_config())
    params, opt_state = _fresh_state(engine, inputs["params"])
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

    recorded = engine.record_exit_distribution()
    assert recorded["rounds"] == 2
    first = REFERENCE.exit_distribution(CONFIG, inputs["params"],
                                        inputs["tokens"][0])
    np.testing.assert_allclose(recorded["by_round"][0], first, rtol=1e-5)
    np.testing.assert_allclose(np.sum(recorded["by_round"], axis=1), 1.0,
                               rtol=1e-12)
    np.testing.assert_allclose(
        recorded["mean"], np.mean(recorded["by_round"], axis=0))
    assert recorded["expected_exit_step"] == pytest.approx(
        np.dot(recorded["mean"], [1, 2, 3, 4]))
    span = [s for s in TRACER.drain() if s["name"] == "exits.distribution"][-1]
    assert span["kind"] == "engine" and span["attrs"] == recorded
    assert engine.record_exit_distribution() is None  # read, and emptied
    assert engine.record_expert_load() is None        # no expert layer


def test_at_a_zero_gate_a_token_leaves_by_halves(inputs):
    engine = _engine(_config())
    engine.round(*_fresh_state(engine, _gated(inputs["params"], 0.0)),
                 engine.shard_tokens(inputs["tokens"][0]), inputs["mask"])
    # what the round left on the device: the distribution summed over the
    # 2 stations x 2 rows x 31 predicted positions, each of which sums to 1
    assert float(jnp.sum(engine._exits[-1])) == pytest.approx(2 * 2 * 31)
    recorded = engine.record_exit_distribution()
    np.testing.assert_allclose(recorded["mean"], [0.5, 0.25, 0.125, 0.125],
                               rtol=1e-6)
    assert recorded["expected_exit_step"] == pytest.approx(1.875, rel=1e-6)


def _cross_entropy_of_exit(params, tokens, r):
    """The mean next-token cross-entropy of the reference's r-th exit."""
    def one(row):
        states = REFERENCE._sequence_states(params, row, CONFIG, "float32")
        mm, _, _ = REFERENCE._products("float32")
        logp = jax.nn.log_softmax(mm(states[r][:-1], params["head"]))
        return -jnp.mean(jnp.take_along_axis(logp, row[1:, None], axis=-1))
    return float(jnp.mean(jax.vmap(one)(tokens)))


@pytest.mark.parametrize("bias,exit_", [(-30.0, 3), (30.0, 0)])
def test_a_gate_held_shut_or_open_leaves_one_exits_cross_entropy(
        inputs, bias, exit_):
    """With no entropy term and the gate's bias at -30 every token stays to
    the end and the loss is the last exit's cross-entropy; at +30 every
    token leaves after the first walk, and the loss is the one the program
    reads with `loops=1` (the plain loss of a stack walked once)."""
    tokens = inputs["tokens"][0, 0]
    params = _gated(inputs["params"], bias)
    loss, _ = _loss_and_grads(_config(exit_beta=0.0), params, tokens)
    assert float(loss) == pytest.approx(
        _cross_entropy_of_exit(params, tokens, exit_), rel=2e-6)
    if exit_ == 0:
        once = {k: v for k, v in params.items() if k != "exit_gate"}
        plain, _ = _loss_and_grads(_config(loops=1), once, tokens)
        assert float(loss) == pytest.approx(float(plain), rel=1e-6)


def test_walked_once_the_block_has_no_gate_and_runs_the_plain_program(inputs):
    """`loops=1` draws the arrays `loops=4` draws, less the gate; its round
    opens neither the `loop` nor the `exit_gate` scope, holds no second pass
    of the head, and leaves nothing on the device; its loss is the
    reference's at `total_ut_steps` 1, where the distribution is [1] and
    its entropy 0."""
    looped = FT.init_params(jax.random.key(5), _config())
    once_cfg = _config(loops=1)
    once = FT.init_params(jax.random.key(5), once_cfg)
    assert set(looped) - set(once) == {"exit_gate"}
    looped.pop("exit_gate")
    assert jax.tree.structure(looped) == jax.tree.structure(once)
    for a, b in zip(jax.tree.leaves(looped), jax.tree.leaves(once)):
        assert np.array_equal(a, b)

    params = {k: v for k, v in inputs["params"].items() if k != "exit_gate"}
    engine = _engine(once_cfg)
    state = _fresh_state(engine, params)
    tokens = engine.shard_tokens(inputs["tokens"][0])
    names = re.findall(r'op_name="([^"]*)"', engine._round.lower(
        engine, *state, tokens, inputs["mask"]).compile().as_text())
    for scope in ("loop", "exit_gate"):
        assert not any(re.search(rf"[/(]{scope}[/)]", n) for n in names)
    *_, loss = engine.round(*state, tokens, inputs["mask"])
    assert engine.record_exit_distribution() is None
    want = REFERENCE.reference_train(
        {**CONFIG, "total_ut_steps": 1}, TRAFFIC, inputs, 1)
    assert float(loss) == pytest.approx(want["losses"][0], rel=2e-6)


# ----------------------------------------------- one block, however often
def _functions(text: str) -> list[str]:
    return re.findall(r"func\.func (?:public |private )?@([\w.]+)", text)


@pytest.mark.parametrize("loops", [1, 2, 4])
def test_the_round_holds_the_block_once_a_direction_whatever_loops_is(
        inputs, loops):
    """The stack's layers are of one kind, so the lowered `_round` holds
    `layer_block` once forward and once backward and calls each once a
    block application (`loops` x layers); the head's product is there once a
    walk and direction, checkpointed; and the scopes are on the compiled
    operations."""
    cfg = _config(loops=loops)
    engine = _engine(cfg)
    params = FT.init_params(jax.random.key(0), cfg)
    lowered = engine._round.lower(
        engine, params, engine.optimizer.init(params),
        engine.shard_tokens(inputs["tokens"][0]), inputs["mask"])
    text = lowered.as_text()
    blocks = [n for n in _functions(text) if n.startswith("layer_block")]
    assert len(blocks) == 2
    for name in blocks:
        assert len(re.findall(rf"call @{name}\(", text)) == loops * 3
    names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    found = {s for s in ("loop", "exit_gate", "mlp", "attention",
                         "lm_head_loss")
             if any(re.search(rf"[/(]{s}[/)]", n) for n in names)}
    assert found == {"mlp", "attention", "lm_head_loss"} | (
        {"loop", "exit_gate"} if loops > 1 else set())
    if loops > 1:  # the block lies under the loop; the head does not
        assert any(re.search(r"[/(]loop[/)].*jit\(layer_block\).*/mlp/", n)
                   for n in names)
        assert not any(re.search(r"[/(]loop[/)]", n) and "lm_head_loss" in n
                       for n in names)


def test_a_block_the_configuration_cannot_describe_is_refused():
    with pytest.raises(ValueError, match="d_ff"):
        FT.TransformerConfig(ffn="swiglu")
    with pytest.raises(ValueError, match="once or more"):
        FT.TransformerConfig(loops=0)
    experts = dict(ffn="experts", n_experts=4, top_k=2, d_expert=8,
                   experts_held=(0, 1), attention="recompute")
    FT.TransformerConfig(**experts)
    for more in ({"loops": 2}, {"norm_after": True}):
        with pytest.raises(ValueError, match="experts"):
            FT.TransformerConfig(**experts, **more)


# -------------------------------------------------------------- the counts
def test_the_shipped_cells_counts_by_hand():
    cell = cells.load_cell("ouro.looped4k-1chip")
    c, t = cell.config, cell.traffic
    assert (c["hidden_size"], c["intermediate_size"], c["head_dim"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["vocab_size"], c["total_ut_steps"], c["num_hidden_layers"]
            ) == (2048, 5632, 128, 16, 16, 49152, 4, 4)
    assert c["published"] == {"num_hidden_layers": 48}
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_388_416                       # "51.39 M"
    held = 4 * layer + 2 * 49152 * 2048 + 2048 + 2048 + 1
    shapes = jax.eval_shape(
        lambda k: REFERENCE.make_params(c, k), jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == held == 406_884_353
    whole = 48 * layer + 2 * 49152 * 2048 + 2048 + 2048 + 1
    assert round(whole / 1e9, 3) == 2.668
    # 3 x sequences x (T x 2 x (R L (4 d d + 3 d f) + R d V + R d)
    #                  + 4 d R L T (T + 1) / 2)
    products = 16 * (4 * 2048 * 2048 + 3 * 2048 * 5632) + 4 * 2048 * 49152 \
        + 4 * 2048
    by_hand = 3 * 2 * (4096 * 2 * products + 4 * 2048 * 16 * 4096 * 4097 // 2)
    assert REFERENCE.flops_per_round(c, t) == by_hand
    assert round(by_hand / 1e12, 1) == 66.8
    glu = 4 * 2 * 3 * 2048 * 5632 * 8192 * 16        # four passes under remat
    assert REFERENCE.glu_flops(c, t) == glu and round(glu / 1e12, 1) == 36.3
    assert REFERENCE.glu_flops(c, {**t, "remat": False}) == glu * 3 // 4
    assert REFERENCE.min_bytes_per_round(c, t) is None
