"""The expert layer that holds one chip's share (models/experts.py) against
the plain reference's (perfbench/configs/smallthinker-21b-ep8-2st.py): the
shares add up to the uncut layer, no token is dropped under any skew, and
the counts are the reference's. CPU, tiny sizes, seeded weights, float32;
the Pallas grouped products run interpreted."""
from __future__ import annotations

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
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * float(jnp.max(jnp.abs(b))))


@pytest.mark.parametrize("token_chunk", [2048, 16])
def test_every_token_to_one_expert_drops_none(layer, token_chunk):
    """The worst skew: every token's every choice names a held expert, and
    the first of them is the same for all. No capacity, so the fullest
    expert receives every token and `dropped` is 0."""
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
    for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * float(jnp.max(jnp.abs(b))))


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
    traced = jax.make_jaxpr(lambda h, c, w: X.expert_layer(
        h, c, w, weights, held, EXPERTS, interpret=True)[0])(
        jnp.zeros((n, D)), jnp.zeros((n, TOP_K), jnp.int32),
        jnp.zeros((n, TOP_K)))
    recomputes = "remat2" in str(traced)  # jax.checkpoint's primitive
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
        (4 if recomputes else 3) * 10.0 * one)
    assert recomputes == (n > X.TOKEN_CHUNK and n % X.TOKEN_CHUNK == 0)
