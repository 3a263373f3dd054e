"""The expert layer that holds one chip's share (models/experts.py) against
the plain reference's (perfbench/configs/smallthinker-21b-ep8-2st.py): the
shares add up to the uncut layer, no token is dropped under any skew, and
the counts are the reference's. CPU, tiny sizes, seeded weights, float32;
the Pallas grouped products run interpreted."""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import cells
from vantage6_tpu.models import experts as X

REFERENCE = cells.load_module(
    cells.HERE / "configs" / "smallthinker-21b-ep8-2st.py")
N, D, F, EXPERTS, TOP_K, CHIPS = 48, 32, 16, 16, 3, 4
HELD = EXPERTS // CHIPS


def _mm(a, w):
    return jnp.matmul(a, w, precision=jax.lax.Precision.HIGHEST)


@pytest.fixture(scope="module")
def layer():
    ks = jax.random.split(jax.random.key(7), 5)
    return {
        "h": jax.random.normal(ks[0], (N, D)),
        "router": jax.random.normal(ks[1], (D, EXPERTS)),
        "w_gate": 0.3 * jax.random.normal(ks[2], (EXPERTS, D, F)),
        "w_up": 0.3 * jax.random.normal(ks[3], (EXPERTS, D, F)),
        "w_down": 0.3 * jax.random.normal(ks[4], (EXPERTS, F, D)),
    }


def _share(layer, chip):
    held = tuple(range(chip * HELD, (chip + 1) * HELD))
    weights = {name: layer[name][chip * HELD:(chip + 1) * HELD]
               for name in ("w_gate", "w_up", "w_down")}
    return held, weights


def _uncut(layer, h=None):
    """The whole layer as the reference writes it: every expert held."""
    h = layer["h"] if h is None else h
    choice, weight = REFERENCE.route(h, layer["router"], TOP_K)
    return REFERENCE.held_experts_part(
        h, choice, weight, tuple(range(EXPERTS)), layer["w_gate"],
        layer["w_up"], layer["w_down"], _mm)


def _assert_trees_close(got, want):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * float(jnp.max(jnp.abs(b))))


def test_the_program_routes_as_the_reference_does(layer):
    choice, weight = X.route(layer["h"], layer["router"], TOP_K)
    ref_choice, ref_weight = REFERENCE.route(layer["h"], layer["router"],
                                             TOP_K)
    assert np.array_equal(choice, ref_choice)
    np.testing.assert_allclose(weight, ref_weight, rtol=1e-6)
    np.testing.assert_allclose(weight.sum(-1), 1.0, rtol=1e-6)


@pytest.mark.parametrize("token_chunk", [2048, 16])
def test_the_shares_of_all_chips_add_up_to_the_uncut_layer(layer,
                                                           token_chunk):
    """Each chip routes over all 16 experts and computes its own 4's part;
    the parts add up to the uncut reference's layer, and the assignments to
    every token's every choice are counted once."""
    choice, weight = X.route(layer["h"], layer["router"], TOP_K)
    total, assignments = 0.0, []
    for chip in range(CHIPS):
        held, weights = _share(layer, chip)
        y, load = X.expert_layer(layer["h"], choice, weight, weights, held,
                                 EXPERTS, interpret=True,
                                 token_chunk=token_chunk)
        total = total + y
        assignments.append(np.asarray(load["assignments"]))
        assert int(load["routed_here"]) == int(load["assignments"].sum())
    want, counts = _uncut(layer)
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)
    assert np.array_equal(np.concatenate(assignments), counts)
    assert int(np.concatenate(assignments).sum()) == N * TOP_K


@pytest.mark.parametrize("chip", range(CHIPS))
def test_a_share_and_its_gradients_equal_the_references(layer, chip):
    held, weights = _share(layer, chip)

    def program(h, router, weights):
        choice, weight = X.route(h, router, TOP_K)
        y, _ = X.expert_layer(h, choice, weight, weights, held, EXPERTS,
                              interpret=True)
        return jnp.sum(y * y)

    def reference(h, router, weights):
        choice, weight = REFERENCE.route(h, router, TOP_K)
        y, _ = REFERENCE.held_experts_part(
            h, choice, weight, held, weights["w_gate"], weights["w_up"],
            weights["w_down"], _mm)
        return jnp.sum(y * y)

    args = (layer["h"], layer["router"], weights)
    got = jax.value_and_grad(program, argnums=(0, 1, 2))(*args)
    want = jax.value_and_grad(reference, argnums=(0, 1, 2))(*args)
    _assert_trees_close(got, want)


@pytest.mark.parametrize("row_tile", [X.ROW_TILE, 16])
@pytest.mark.parametrize("token_chunk", [2048, 16])
def test_every_token_to_one_expert_drops_none(layer, monkeypatch,
                                              token_chunk, row_tile):
    """The worst skew: every token's every choice names a held expert, and
    the first of them is the same for all. No capacity, so the fullest
    expert receives every token and `dropped` is 0; the row buffer is full
    and every block of it is walked (one block, or 9 or 3 of 16 rows)."""
    monkeypatch.setattr(X, "ROW_TILE", row_tile)
    held, weights = _share(layer, 1)
    choice = jnp.tile(jnp.asarray([held[2], held[0], held[3]], jnp.int32),
                      (N, 1))
    weight = jnp.tile(jnp.asarray([0.5, 0.3, 0.2]), (N, 1))
    y, load = X.expert_layer(layer["h"], choice, weight, weights, held,
                             EXPERTS, interpret=True,
                             token_chunk=token_chunk)
    want, counts = REFERENCE.held_experts_part(
        layer["h"], choice, weight, held, weights["w_gate"],
        weights["w_up"], weights["w_down"], _mm)
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-5)
    assert np.asarray(load["assignments"]).tolist() == [N, 0, N, N]
    assert np.array_equal(load["assignments"], counts)
    summary = X.load_summary(load["assignments"], load["routed_here"])
    assert summary["dropped"] == 0
    assert summary["max_over_mean"] == pytest.approx(4 / 3)
    assert int(load["row_blocks_walked"]) == X.row_walk(
        N, TOP_K, token_chunk)[1]


def test_a_chip_none_of_whose_experts_is_chosen_adds_nothing(layer):
    held, weights = _share(layer, 0)
    choice = jnp.full((N, TOP_K), EXPERTS - 1, jnp.int32)
    weight = jnp.full((N, TOP_K), 1 / TOP_K)
    y, load = X.expert_layer(layer["h"], choice, weight, weights, held,
                             EXPERTS, interpret=True)
    assert not np.asarray(y).any() and np.isfinite(np.asarray(y)).all()
    assert int(load["assignments"].sum()) == 0
    grads = jax.grad(lambda h: jnp.sum(X.expert_layer(
        h, choice, weight, weights, held, EXPERTS, interpret=True)[0]))(
            layer["h"])
    assert not np.asarray(grads).any()  # and no NaN from the unwritten rows


def test_under_vmap_and_recomputation_as_the_round_runs_it(layer):
    """`FedTransformer._round` walks the packed stations with a vmap and
    differentiates inside it; the expert layer recomputes its chunks."""
    held, weights = _share(layer, 2)
    hs = jnp.stack([layer["h"], layer["h"][::-1] * 0.5])

    def program(h, weights):
        choice, weight = X.route(h, layer["router"], TOP_K)
        y, load = X.expert_layer(h, choice, weight, weights, held, EXPERTS,
                                 interpret=True, token_chunk=16)
        return jnp.sum(y * y), load["assignments"]

    def reference(h, weights):
        choice, weight = REFERENCE.route(h, layer["router"], TOP_K)
        y, counts = REFERENCE.held_experts_part(
            h, choice, weight, held, weights["w_gate"], weights["w_up"],
            weights["w_down"], _mm)
        return jnp.sum(y * y), counts

    def stations(f):
        return jax.jit(jax.vmap(jax.value_and_grad(
            jax.checkpoint(f), argnums=(0, 1), has_aux=True),
            in_axes=(0, None)))(hs, weights)

    (got, counts), got_g = stations(program)
    (want, ref_counts), want_g = stations(reference)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert np.array_equal(counts, ref_counts)
    _assert_trees_close(got_g, want_g)


@pytest.mark.parametrize("batch, seq_len, remat", [
    (1, 4 * X.TOKEN_CHUNK, True),   # the cell's: whole chunks, recomputed
    (1, 4 * X.TOKEN_CHUNK, False),  # the block's remat has no say in it
    (1, X.TOKEN_CHUNK, True),       # one chunk: nothing recomputed
    (2, X.TOKEN_CHUNK // 4, True),
    (1, X.TOKEN_CHUNK + 8, True),   # no whole number of chunks
])
def test_the_roofline_counts_the_passes_the_layer_runs(layer, batch, seq_len,
                                                       remat):
    """`experts_flops` counts a fourth pass exactly where `expert_layer`
    recomputes its chunks, whatever the block's `remat` says."""
    held, weights = _share(layer, 0)
    n = batch * seq_len
    traced = str(jax.make_jaxpr(jax.grad(lambda h, c, w: jnp.sum(
        X.expert_layer(h, c, w, weights, held, EXPERTS, interpret=True)[0])))(
        jnp.zeros((n, D)), jnp.zeros((n, TOP_K), jnp.int32),
        jnp.zeros((n, TOP_K))))
    # the passes over the three products that a gradient runs: forward, the
    # backward's two, and the forward again where the backward recomputes
    products = traced.count("name=gmm") + traced.count("name=tgmm")
    assert products in (9, 12)
    recomputes = products == 12
    assert REFERENCE.EXPERT_CHUNK_TOKENS == X.TOKEN_CHUNK
    config = {"hidden_size": D, "moe_ffn_hidden_size": F, "head_dim": 8,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "moe_num_primary_experts": HELD,
              "expert_parallel": {"chips": CHIPS, "this_chip": 0},
              "moe_num_active_primary_experts": TOP_K,
              "num_hidden_layers": 1, "vocab_size": 97}
    traffic = {"batch": batch, "seq_len": seq_len, "remat": remat}
    one = 2.0 * 3 * D * F
    assert REFERENCE.experts_flops(config, traffic, 10.0) == (
        products // 3 * 10.0 * one)
    assert recomputes == (n > X.TOKEN_CHUNK and n % X.TOKEN_CHUNK == 0)


# ------------------------------------------------ the walk over row blocks
BLOCK = 16  # the tests' ROW_TILE: N * TOP_K = 144 rows are 9 blocks
HELD_BY_1 = tuple(range(HELD, 2 * HELD))  # `_share(layer, 1)`'s experts


def _assignments(live, seed=0):
    """[N, TOP_K] choices of which ``live`` name an expert held by chip 1,
    unevenly and scattered over tokens and slots, and the others one held
    elsewhere; random weights."""
    rng = np.random.default_rng(seed)
    flat = rng.choice([0, 1, 2, 3, 8, 9, 15], size=N * TOP_K)
    here = rng.permutation(N * TOP_K)[:live]
    flat[here] = rng.choice(HELD_BY_1, size=live, p=[0.5, 0.25, 0.25, 0.0])
    weight = rng.uniform(0.1, 1.0, size=(N, TOP_K)).astype(np.float32)
    return (jnp.asarray(flat.reshape(N, TOP_K), jnp.int32),
            jnp.asarray(weight))


def _program_and_reference(held, choice, token_chunk):
    def program(h, weight, weights):
        y, load = X.expert_layer(h, choice, weight, weights, held, EXPERTS,
                                 interpret=True, token_chunk=token_chunk)
        return jnp.sum(y * y), load

    def reference(h, weight, weights):
        y, counts = REFERENCE.held_experts_part(
            h, choice, weight, held, weights["w_gate"], weights["w_up"],
            weights["w_down"], _mm)
        return jnp.sum(y * y), counts

    return program, reference


@pytest.mark.parametrize("token_chunk", [2048, 16])
@pytest.mark.parametrize(
    "live", [0, BLOCK - 1, BLOCK, BLOCK + 1, 5 * BLOCK + 3, N * TOP_K])
def test_the_walk_ends_with_the_last_block_that_carries_an_assignment(
        layer, monkeypatch, live, token_chunk):
    """The layer and its gradients are the reference's wherever the
    assignments to held experts end: in no block, one row short of a block's
    end, at it, one past it, and at the buffer's (every choice of every
    token held here: the worst case is walked whole and drops nothing). The
    blocks walked are those that carry an assignment, chunk by chunk."""
    monkeypatch.setattr(X, "ROW_TILE", BLOCK)
    held, weights = _share(layer, 1)
    choice, weight = _assignments(live)
    program, reference = _program_and_reference(held, choice, token_chunk)
    args = (layer["h"], weight, weights)
    (got, load), got_g = jax.value_and_grad(
        program, argnums=(0, 1, 2), has_aux=True)(*args)
    (want, counts), want_g = jax.value_and_grad(
        reference, argnums=(0, 1, 2), has_aux=True)(*args)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    _assert_trees_close(got_g, want_g)
    assert np.array_equal(load["assignments"], counts)
    assert int(load["assignments"].sum()) == int(load["routed_here"]) == live
    chunk = min(token_chunk, N)
    block, blocks = X.row_walk(N, TOP_K, token_chunk)
    assert block == min(BLOCK, chunk * TOP_K)
    assert blocks == N // chunk * -(-chunk * TOP_K // block)
    here = np.isin(np.asarray(choice), held).reshape(N // chunk, -1).sum(1)
    assert int(load["row_blocks_walked"]) == int(np.sum(-(-here // block)))
    if live == N * TOP_K:
        assert int(load["row_blocks_walked"]) == blocks


@pytest.fixture
def two_stations(layer, monkeypatch):
    """Two packed stations of unequal load, as `FedTransformer._round` runs
    them: `value_and_grad` INSIDE the stations' `vmap`, the weights shared.
    Station 0 fills 2 blocks and a row, station 1 all 9."""
    monkeypatch.setattr(X, "ROW_TILE", BLOCK)
    held, weights = _share(layer, 1)
    choices, weight = zip(*(_assignments(live, seed) for seed, live in
                            enumerate((2 * BLOCK + 1, N * TOP_K))))
    hs = jnp.stack([layer["h"], layer["h"][::-1] * 0.5])

    def stations(token_chunk, which):
        def station(h, choice, weight, weights):
            return _program_and_reference(held, choice, token_chunk)[which](
                h, weight, weights)

        return jax.jit(jax.vmap(jax.value_and_grad(
            station, argnums=(0, 2, 3), has_aux=True),
            in_axes=(0, 0, 0, None))), (
                hs, jnp.stack(choices), jnp.stack(weight), weights)

    return stations


@pytest.mark.parametrize("token_chunk", [2048, 16])
def test_packed_stations_of_unequal_load_each_walk_their_own_blocks(
        two_stations, token_chunk):
    stations = two_stations
    program, args = stations(token_chunk, 0)
    reference, _ = stations(token_chunk, 1)
    (got, load), got_g = program(*args)
    (want, counts), want_g = reference(*args)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    _assert_trees_close(got_g, want_g)
    assert np.array_equal(load["assignments"], counts)
    chunk = min(token_chunk, N)
    block = X.row_walk(N, TOP_K, token_chunk)[0]
    here = np.isin(np.asarray(args[1]), HELD_BY_1)
    want_walked = (-(-here.reshape(2, N // chunk, -1).sum(2) // block)).sum(1)
    assert np.asarray(load["row_blocks_walked"]).tolist() == (
        want_walked.tolist())
    assert load["row_blocks_walked"][0] < load["row_blocks_walked"][1]


def test_the_stations_vmap_does_not_turn_the_walk_into_a_select(
        two_stations):
    """In the lowered two-station program the rows are walked by loops whose
    bound is read off the station's own routing, one station after another:
    no operation of it is over the two stations' row buffers at once (the
    parent's passes over [S * m, d]; what jax's own batching of a loop or a
    conditional makes of a per-station bound: a `select` over the whole
    carry). The test fails if the skip is compiled to that."""
    stations = two_stations
    chunk = N // 2  # two chunks, as the cell runs: each recomputed
    program, args = stations(chunk, 0)
    text = program.lower(*args).as_text()
    block, blocks = X.row_walk(N, TOP_K, chunk)
    m_rows = blocks // 2 * block
    assert m_rows not in (N, chunk)  # a shape no other array has
    for width in (D, F):
        assert f"tensor<{m_rows}x{width}x" in text  # one station's buffer
        assert f"x{m_rows}x{width}x" not in text  # and nothing over both's
    # the loops' bound is data: the blocks that carry an assignment,
    # (live + block - 1) // block, is a carry of the loop that its counter
    # is compared with (value names are a function's own: the first match is
    # the function's that computes the count)
    count = re.search(
        r"(%\d+) = call @floor_divide\(%\d+, %\S+\) : "
        r"\(tensor<i32>, tensor<i32>\)", text).group(1)
    bounded = re.findall(
        rf"stablehlo\.while\(.*(%iterArg\w*) = {count}[,)].*\n\s*cond {{\n"
        r"\s*%\d+ = stablehlo\.compare  LT, %iterArg\w*, (%iterArg\w*),",
        text)
    assert bounded and bounded[0][0] == bounded[0][1]
