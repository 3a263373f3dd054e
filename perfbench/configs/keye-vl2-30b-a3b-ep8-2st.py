"""Keye-VL-2.0-30B-A3B's language model, one chip's share of an 8-way
expert-parallel deployment, under federated averaging: inputs from the seed,
the plain reference, and the operation counts — the yardstick of
`keye-vl2-30b-a3b-ep8-2st`.

The reference is the layer of `keye-vl2-30b-a3b-ep8-2st.json` written from
its equations in straightforward `jax.numpy`: float32 throughout, every
matrix product at ``precision=HIGHEST``, one station after another, the
stations' gradients averaged, Adam written out. It imports nothing of
`vantage6_tpu` and takes nothing the program made. With x the residual
stream [T, d]:

- ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``, g learned;
- attention on ``h = RMSNorm(x)``: H_q query heads and H_kv key/value heads
  of ``head_dim`` (H_q / H_kv query heads read one kv head), an RMSNorm of
  every q and k head over ``head_dim`` with its own learned scale, rotate-
  half RoPE (``rope_theta``) on q and k, scale ``1/sqrt(head_dim)``, no
  biases;
- the lightning indexer on ``h`` taken as a constant: ``qI = h W_qI`` (H_I
  heads of D_I), ``kI = LayerNorm(h W_kI) * g_I`` (one head; no bias), the
  first half of every qI and kI rotated as q and k are, ``w = h W_w /
  sqrt(H_I)``; ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s] / sqrt(D_I))``
  for ``s <= t``;
- each query's ``S_t``: a dense `lax.top_k` of its row of I (the lower s on a
  tie), kept where ``s <= t`` (the first ``top_k`` queries keep every key they
  see); the attention is the softmax over ``S_t`` alone; output projection,
  residual;
- the indexer's loss ``L_I = mean_t KL(p_t || softmax_{S_t} I[t])``, ``p_t``
  the attention's probabilities over ``S_t`` averaged over the H_q heads, as
  a constant: it trains the indexer alone, and the LM loss, which reaches the
  indexer through nothing, trains the rest;
- the router after attention on ``u = RMSNorm(x + attention)``: ``p =
  softmax(u W_r)`` over all the experts of the deployment, the ``k`` largest,
  renormalised; ``y = sum_e w_e W_down,e (silu(W_gate,e u) * (W_up,e u))``
  over the chosen experts e that are HELD HERE, a loop over the held experts
  with a mask; residual;
- final RMSNorm, an output head of its own, next-token cross-entropy over
  the slice of the vocabulary held; the loss is its mean plus every layer's
  ``L_I``.

Everything over the keys is computed one block of queries at a time (and
within it one kv head's group of query heads at a time), each block
recomputed in the backward pass, so that no [T, T] array exists; that is
bookkeeping, not mathematics.

``precision`` other than ``"float32"`` computes the same mathematics with the
operands of every matrix product rounded first, and on the way back the
cotangent that reaches it (perfbench/precision.py): the control that
`correct` has to fail. The router's product and the indexer's (its
projections, scores and choice) are float32 in the configuration itself
(`assumed`), so the control leaves them alone. ``fault`` plants one of the
faults the comparison has to catch.
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from perfbench.compare import leaf_norms
from perfbench.precision import cotangent_rounder, rounder

MATRICES = ("qkv", "proj", "router", "w_gate", "w_up", "w_down",
            "idx_q", "idx_k", "idx_w")
QUERY_BLOCK = 128  # queries of one block of the reference's dense softmax
HEAD_BLOCK = 1024  # positions whose logits are live at once
HIGHEST = lax.Precision.HIGHEST


def _sizes(config: dict[str, Any]) -> dict[str, int]:
    held = config["num_experts"]
    experts = held * config["expert_parallel"]["chips"]
    if experts != config["num_local_experts"]:
        raise ValueError(
            f"{held} experts held on each of "
            f"{config['expert_parallel']['chips']} chips are not the "
            f"{config['num_local_experts']} the router chooses among")
    sa = config["sa_config"]
    return {
        "d": config["hidden_size"], "hd": config["head_dim"],
        "hq": config["num_attention_heads"],
        "hkv": config["num_key_value_heads"],
        "f": config["moe_intermediate_size"], "held": held,
        "experts": experts, "k": config["num_experts_per_tok"],
        "layers": config["num_hidden_layers"], "v": config["vocab_size"],
        "hi": sa["indexer_num_heads"], "di": sa["indexer_head_dim"],
        "top": sa["topk"],
    }


def held_experts(config: dict[str, Any]) -> tuple[int, ...]:
    """The ids, among all the deployment's experts, of those this chip
    holds: chip c of the layer's ``chips`` holds ``[c * held, (c + 1) *
    held)``."""
    held = config["num_experts"]
    first = config["expert_parallel"]["this_chip"] * held
    return tuple(range(first, first + held))


# ------------------------------------------------------------------ inputs
def _layer_shapes(config: dict[str, Any]) -> dict[str, tuple[int, ...]]:
    z = _sizes(config)
    return {
        "qkv": (z["d"], (z["hq"] + 2 * z["hkv"]) * z["hd"]),
        "proj": (z["hq"] * z["hd"], z["d"]),
        "router": (z["d"], z["experts"]),
        "w_gate": (z["held"], z["d"], z["f"]),
        "w_up": (z["held"], z["d"], z["f"]),
        "w_down": (z["held"], z["f"], z["d"]),
        "idx_q": (z["d"], z["hi"] * z["di"]),
        "idx_k": (z["d"], z["di"]),
        "idx_w": (z["d"], z["hi"]),
    }


def make_params(config: dict[str, Any], key: jax.Array) -> dict[str, Any]:
    """Matrices ~ N(0, initializer_range), the input embedding ~ N(0,
    embedding_initializer_range), norm scales 1, float32, in the pytree the
    repo's transformer takes for this block: embed [V, d], head [d, V],
    final_norm [d], layers[i]{qkv, proj, router, w_gate, w_up, w_down,
    idx_q, idx_k, idx_w, norm1, norm2 [d], q_norm, k_norm [head_dim],
    idx_norm [D_I]}. One jitted call makes all of them on the device."""
    z = _sizes(config)
    s = config["initializer_range"]
    shapes = _layer_shapes(config)
    scales = {"norm1": z["d"], "norm2": z["d"], "q_norm": z["hd"],
              "k_norm": z["hd"], "idx_norm": z["di"]}

    def build(key):
        keys = jax.random.split(key, 2 + z["layers"])
        layers = []
        for i in range(z["layers"]):
            sub = jax.random.split(keys[2 + i], len(MATRICES))
            layer = {name: s * jax.random.normal(sub[j], shapes[name],
                                                 jnp.float32)
                     for j, name in enumerate(MATRICES)}
            layer.update({name: jnp.ones((n,), jnp.float32)
                          for name, n in scales.items()})
            layers.append(layer)
        return {
            "embed": config["embedding_initializer_range"]
            * jax.random.normal(keys[0], (z["v"], z["d"]), jnp.float32),
            "head": s * jax.random.normal(keys[1], (z["d"], z["v"]),
                                          jnp.float32),
            "final_norm": jnp.ones((z["d"],), jnp.float32),
            "layers": layers,
        }

    return jax.jit(build)(key)


def make_tokens(config: dict[str, Any], traffic: dict[str, Any],
                key: jax.Array) -> jax.Array:
    """[n_batches, S, B, T] int32 from the rows of the vocabulary held:
    ranks drawn Zipf (``zipf_exponent``) by the inverse of the cumulative
    distribution, and every station maps ranks to ids by a permutation of
    its own, so the stations' frequent tokens differ (non-IID) and routing
    is uneven, differently so per station."""
    s, b, t = config["n_stations"], traffic["batch"], traffic["seq_len"]
    v = config["vocab_size"]

    def build(key):
        k_rank, k_perm = jax.random.split(key)
        weight = (1.0 + jnp.arange(v, dtype=jnp.float32)) ** (
            -traffic["zipf_exponent"])
        cdf = jnp.cumsum(weight) / jnp.sum(weight)
        u = jax.random.uniform(k_rank, (traffic["n_batches"], s, b, t))
        rank = jnp.clip(jnp.searchsorted(cdf, u), 0, v - 1)
        perms = jnp.stack([jax.random.permutation(k, v)
                           for k in jax.random.split(k_perm, s)])
        station = jnp.arange(s)[None, :, None, None]
        return perms[station, rank].astype(jnp.int32)

    return jax.jit(build)(key)


def make_inputs(config: dict[str, Any], traffic: dict[str, Any],
                key: jax.Array) -> dict[str, Any]:
    k_params, k_tokens = jax.random.split(key)
    return {
        "params": make_params(config, k_params),
        "tokens": make_tokens(config, traffic, k_tokens),
        "mask": jnp.ones((config["n_stations"],), jnp.float32),
    }


# ------------------------------------------------------------------ counts
def visible_pairs(t: int) -> int:
    """(query, key) pairs of one causal sequence of ``t`` tokens."""
    return t * (t + 1) // 2


def selected_pairs(t: int, top: int) -> int:
    """Pairs the sparse attention attends: ``sum_t min(top, t + 1)``."""
    if top >= t:
        return visible_pairs(t)
    return top * (top + 1) // 2 + (t - top) * top


def expert_flops_per_assignment(config: dict[str, Any]) -> float:
    """One token through one expert, forward: three products of d x f."""
    z = _sizes(config)
    return 2.0 * 3 * z["d"] * z["f"]


def _pairs(config, traffic):
    z = _sizes(config)
    t = traffic["seq_len"]
    sequences = config["n_stations"] * traffic["batch"]
    return (sequences * z["layers"] * visible_pairs(t),
            sequences * z["layers"] * selected_pairs(t, z["top"]))


def flops_per_round(config: dict[str, Any], traffic: dict[str, Any]) -> float:
    """Operations one round's forward and backward passes require, no
    recomputation counted: the products of every token (q, k, v, output,
    router, the indexer's projections, the head; forward and the backward's
    two), the experts at the UNIFORM EXPECTATION of ``k * held / experts``
    assignments a token (what the router really sends is in the
    `experts.load` record), the attention over the SELECTED pairs (scores
    and values, forward and backward), the indexer's scores of every
    visible pair once (the choice needs them and nothing differentiates
    it), and its loss over the selected pairs: the heads' probabilities
    once, the indexer's gradient's two products."""
    z = _sizes(config)
    t = traffic["seq_len"]
    sequences = config["n_stations"] * traffic["batch"]
    per_token_layer = (
        z["d"] * (z["hq"] + 2 * z["hkv"]) * z["hd"]      # q, k, v
        + z["hq"] * z["hd"] * z["d"]                     # output projection
        + z["d"] * z["experts"]                          # router
    )
    indexer_projections = z["d"] * (z["hi"] * z["di"] + z["di"] + z["hi"])
    matmuls = 2.0 * (per_token_layer * z["layers"] + z["d"] * z["v"])
    expected = z["k"] * z["held"] / z["experts"]
    experts = expected * expert_flops_per_assignment(config) * z["layers"]
    visible, selected = _pairs(config, traffic)
    score = 2.0 * z["hi"] * z["di"]
    return (3.0 * sequences * t * (matmuls + experts)
            + 3.0 * 4.0 * z["hq"] * z["hd"] * selected
            + 2.0 * sequences * t * z["layers"] * indexer_projections
            + score * visible
            + (2.0 * z["hq"] * z["hd"] + 2 * score) * selected)


def min_bytes_per_round(config: dict[str, Any],
                        traffic: dict[str, Any]) -> float | None:
    """Not bandwidth-bound: the configuration reports no HBM share."""
    return None


# models/experts.py::TOKEN_CHUNK, stated again: this file imports nothing of
# the program's
EXPERT_CHUNK_TOKENS = 2048
# the MXU passes of a float32 product at HIGHEST (three bfloat16 parts of
# each operand, six products of parts)
HIGHEST_PASSES = 6


def experts_flops(config: dict[str, Any], traffic: dict[str, Any],
                  assignments: float) -> float:
    """Operations the program runs under its `experts` scope in one round
    for ``assignments`` (token, held expert) pairs, summed over layers and
    stations: the forward products, the backward's two per forward product,
    and the forward again where the layer recomputes its chunks (a
    station's tokens beyond one chunk of `EXPERT_CHUNK_TOKENS`, and a whole
    number of chunks)."""
    tokens = traffic["batch"] * traffic["seq_len"]  # of one station
    recomputed = (tokens > EXPERT_CHUNK_TOKENS
                  and tokens % EXPERT_CHUNK_TOKENS == 0)
    passes = 4 if recomputed else 3
    return passes * assignments * expert_flops_per_assignment(config)


def indexer_flops(config: dict[str, Any], traffic: dict[str, Any]) -> float:
    """The bfloat16 MXU operations the program's `indexer` scope needs in one
    round: the scores of every visible pair, twice (the forward and its
    recomputation under remat), and the three projections from the normed
    stream, forward twice and the weights' gradient once; all float32
    products at HIGHEST, so each counts `HIGHEST_PASSES` bfloat16 passes. For
    the scope's share of the bf16 peak (`indexer_roofline_share`)."""
    z = _sizes(config)
    visible, _ = _pairs(config, traffic)
    tokens = config["n_stations"] * traffic["batch"] * traffic["seq_len"]
    projections = 2.0 * z["d"] * (z["hi"] * z["di"] + z["di"] + z["hi"])
    return HIGHEST_PASSES * (
        2 * 2.0 * z["hi"] * z["di"] * visible
        + 3 * tokens * z["layers"] * projections)


def sparse_attention_flops(config: dict[str, Any],
                           traffic: dict[str, Any]) -> float:
    """The attention's products over the SELECTED pairs in one round, as
    the `attention` scope has to run them under remat: ``4 * Hq * hd`` a
    pair and pass (scores and values), forward, recomputed forward and
    backward (its four products are two passes). For the scope's share of
    the bf16 peak (`sparse_attention_roofline_share`); the masked walk
    runs every visible tile, so the share says how far that is from the
    pairs the model needs."""
    z = _sizes(config)
    _, selected = _pairs(config, traffic)
    return 4 * 4.0 * z["hq"] * z["hd"] * selected


# --------------------------------------------------------------- reference
def _rms(x: jax.Array, g: jax.Array, eps: float) -> jax.Array:
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rotate_half(x: jax.Array, theta: float) -> jax.Array:
    """x [T, H, D] at positions 0..T-1: the pair (x[i], x[i + D/2]) turns by
    ``position * theta^(-2i/D)``."""
    t, _, d = x.shape
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[:, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos + turned * sin


def _rotate_first_half(x: jax.Array, theta: float) -> jax.Array:
    """The first half of every head's width rotated as `_rotate_half` does,
    the second half as it is."""
    half = x.shape[-1] // 2
    return jnp.concatenate(
        [_rotate_half(x[..., :half], theta), x[..., half:]], -1)


def route(x: jax.Array, w_router: jax.Array, k: int):
    """The ``k`` largest of ``softmax(x W_r)`` and their renormalised
    probabilities: float32 at HIGHEST whatever the control's precision."""
    p = jax.nn.softmax(jnp.matmul(x, w_router, precision=HIGHEST), -1)
    top_p, choice = lax.top_k(p, k)
    return choice, top_p / jnp.sum(top_p, -1, keepdims=True)


def indexer(h: jax.Array, layer: dict[str, Any], config: dict[str, Any]):
    """``qI`` [T, H_I, D_I], ``kI`` [T, D_I], ``w`` [T, H_I] from the normed
    stream ``h`` [T, d], float32 at HIGHEST."""
    z = _sizes(config)
    t = h.shape[0]
    theta = float(config["rope_theta"])
    eps = config["rms_norm_eps"]
    q = jnp.matmul(h, layer["idx_q"], precision=HIGHEST).reshape(
        t, z["hi"], z["di"])
    k = jnp.matmul(h, layer["idx_k"], precision=HIGHEST)
    k = ((k - jnp.mean(k, -1, keepdims=True))
         * lax.rsqrt(jnp.var(k, -1, keepdims=True) + eps) * layer["idx_norm"])
    w = jnp.matmul(h, layer["idx_w"], precision=HIGHEST) / math.sqrt(z["hi"])
    return (_rotate_first_half(q, theta),
            _rotate_first_half(k[:, None], theta)[:, 0], w)


def scores(q_idx, k_idx, w):
    """``I`` [bq, T] of a block of the indexer's queries against every key,
    no mask: a sum over the heads, each head's product on its own."""
    out = jnp.zeros((q_idx.shape[0], k_idx.shape[0]), jnp.float32)
    for j in range(q_idx.shape[1]):
        a = jnp.matmul(q_idx[:, j], k_idx.T, precision=HIGHEST)
        out = out + w[:, j:j + 1] * jax.nn.relu(a / math.sqrt(q_idx.shape[-1]))
    return out


def selection(i_blk, q_pos, top):
    """``S_t`` of a block of queries as a [bq, T] bool: the dense top-k of
    each row of I, kept where the key is visible. `lax.top_k` orders by
    value and, on a tie, by the lower index, so its last pick (value v,
    index i) says which keys it took: those above v, and those at v up to
    i."""
    t = i_blk.shape[-1]
    key = jnp.arange(t)[None, :]
    seen = key <= q_pos[:, None]
    masked = jnp.where(seen, i_blk, -jnp.inf)
    value, idx = lax.top_k(masked, min(top, t))
    last, at = value[:, -1:], idx[:, -1:]
    return ((masked > last) | ((masked == last) & (key <= at))) & seen


def _sparse_attention(q, k, v, idx, top, rnd, after):
    """The attention over each query's ``S_t``, and the indexer's loss
    summed over the queries. q [T, Hq, D], k, v [T, Hkv, D]; ``idx`` the
    indexer's (qI, kI, w). One block of queries at a time, and in it one kv
    head's group of query heads at a time; each block recomputed in the
    backward pass."""
    t, hq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    block = min(QUERY_BLOCK, t)
    assert t % block == 0, (t, block)
    n_blocks = t // block
    q_idx, k_idx, w = idx

    @jax.checkpoint
    def one_block(blk):
        q_pos = blk * block + jnp.arange(block)
        qi = lax.dynamic_slice_in_dim(q_idx, blk * block, block, 0)
        wi = lax.dynamic_slice_in_dim(w, blk * block, block, 0)
        i_blk = scores(qi, k_idx, wi)                       # [block, T]
        chosen = selection(lax.stop_gradient(i_blk), q_pos, top)
        q_blk = lax.dynamic_slice_in_dim(q, blk * block, block, 0)

        @jax.checkpoint
        def one_head(h):  # the query heads of kv head h
            q_h = q_blk.reshape(block, hkv, g, d)[:, h]    # [block, G, D]
            s = after(jnp.einsum("qgd,sd->gqs", rnd(q_h), rnd(k[:, h]),
                                 precision=HIGHEST)) / math.sqrt(d)
            p = jax.nn.softmax(jnp.where(chosen[None], s, -jnp.inf), -1)
            o = after(jnp.einsum("gqs,sd->qgd", rnd(p), rnd(v[:, h]),
                                 precision=HIGHEST))
            return o, jnp.sum(p, 0)

        o, p_sum = lax.map(one_head, jnp.arange(hkv))
        p = lax.stop_gradient(jnp.sum(p_sum, 0) / hq)      # [block, T]
        log_q = jax.nn.log_softmax(jnp.where(chosen, i_blk, -jnp.inf), -1)
        kl = jnp.sum(jnp.where(chosen & (p > 0),
                               p * (jnp.log(jnp.where(p > 0, p, 1.0)) - log_q),
                               0.0))
        return jnp.moveaxis(o, 0, 1).reshape(block, hq * d), kl

    o, kl = lax.map(one_block, jnp.arange(n_blocks))
    return o.reshape(t, hq * d), jnp.sum(kl)


def held_experts_part(h, choice, weight, held, w_gate, w_up, w_down, mm):
    """``sum_e w_e W_down,e (silu(W_gate,e h) * (W_up,e h))`` over the
    chosen experts e among ``held`` (their ids, beside their stacked
    weights), one expert after another, every token through each with its
    weight or 0, its products recomputed in the backward pass (16 experts'
    of 16,384 tokens would hold 4.8 GB). Beside y, the assignments each
    held expert received."""
    @jax.checkpoint
    def one_expert(y, e):
        expert, w_g, w_u, w_d = e
        chosen = choice == expert
        w_e = jnp.sum(jnp.where(chosen, weight, 0.0), -1)
        mid = jax.nn.silu(mm(h, w_g)) * mm(h, w_u)
        return y + w_e[:, None] * mm(mid, w_d), jnp.sum(chosen)

    return lax.scan(one_expert, jnp.zeros_like(h),
                    (jnp.asarray(held, jnp.int32), w_gate, w_up, w_down))


def _sequence_forward(params, tokens, config, precision):
    """One sequence [T]: the stream after the final norm [T, d] (the head
    reads it), the indexer's loss summed over the layers and the queries,
    and per layer the assignments each held expert received [L, held]."""
    rnd, after = rounder(precision), cotangent_rounder(precision)
    z = _sizes(config)
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    held = held_experts(config)
    t = tokens.shape[0]

    def mm(a, w):
        return after(jnp.matmul(rnd(a), rnd(w), precision=HIGHEST))

    def block(x, layer):
        h = _rms(x, layer["norm1"], eps)
        nq, nkv = z["hq"] * z["hd"], z["hkv"] * z["hd"]
        qkv = mm(h, layer["qkv"])
        q = _rms(qkv[:, :nq].reshape(t, z["hq"], z["hd"]), layer["q_norm"],
                 eps)
        k = _rms(qkv[:, nq: nq + nkv].reshape(t, z["hkv"], z["hd"]),
                 layer["k_norm"], eps)
        v = qkv[:, nq + nkv:].reshape(t, z["hkv"], z["hd"])
        q, k = _rotate_half(q, theta), _rotate_half(k, theta)
        idx = indexer(lax.stop_gradient(h), layer, config)
        attn, kl = _sparse_attention(q, k, v, idx, z["top"], rnd, after)
        x = x + mm(attn, layer["proj"])
        u = _rms(x, layer["norm2"], eps)
        choice, weight = route(u, layer["router"], z["k"])
        y, counts = held_experts_part(
            u, choice, weight, held, layer["w_gate"], layer["w_up"],
            layer["w_down"], mm)
        return x + y, (kl, counts)

    x = params["embed"][tokens]
    # a layer's activations recomputed in the backward pass so that the
    # reference fits beside its own gradients, and the layers called one
    # after another on their own weights (a loop over stacked weights would
    # hold a stacked copy of them and of their gradients, 3 GB): neither
    # changes the mathematics
    kl, counts = [], []
    for layer in params["layers"]:
        x, (layer_kl, layer_counts) = jax.checkpoint(block)(x, layer)
        kl.append(layer_kl)
        counts.append(layer_counts)
    return (_rms(x, params["final_norm"], eps), jnp.sum(jnp.stack(kl)),
            jnp.stack(counts))


def logits(params, h, precision="float32"):
    """The head's logits [T, V] of the stream after the final norm."""
    rnd, after = rounder(precision), cotangent_rounder(precision)
    return after(jnp.matmul(rnd(h), rnd(params["head"]), precision=HIGHEST))


def _sequence_loss(params, tokens, config, precision):
    """Next-token cross-entropy summed over one sequence [T] (the last
    position predicts nothing), and the indexer's loss summed over its
    layers and queries. The logits are taken `HEAD_BLOCK` positions at a
    time, each block recomputed in the backward pass."""
    h, kl, _ = _sequence_forward(params, tokens, config, precision)
    t = tokens.shape[0]
    block = min(HEAD_BLOCK, t)
    targets = jnp.roll(tokens, -1)

    @jax.checkpoint
    def one_block(at):
        logp = jax.nn.log_softmax(logits(
            params, lax.dynamic_slice_in_dim(h, at, block, 0), precision))
        nll = -jnp.take_along_axis(
            logp, lax.dynamic_slice_in_dim(targets, at, block)[:, None],
            axis=-1)[:, 0]
        return jnp.sum(jnp.where(at + jnp.arange(block) < t - 1, nll, 0.0))

    return jnp.sum(lax.map(one_block, jnp.arange(0, t, block))), kl


def expert_load(config: dict[str, Any], params: dict[str, Any],
                tokens: jax.Array) -> np.ndarray:
    """[L, held]: the assignments each held expert receives in one round on
    ``tokens`` [S, B, T], summed over stations and rows: what the program's
    `experts.load` record has to hold for that round."""
    rows = tokens.reshape(-1, tokens.shape[-1])
    counts = jax.jit(lambda p, rows: jnp.sum(lax.map(
        lambda row: _sequence_forward(p, row, config, "float32")[2], rows),
        axis=0))(params, rows)
    return np.asarray(counts)


def _loss(params, tokens, config, precision):
    """The loss of one station's [B, T] tokens: the mean next-token
    cross-entropy plus every layer's indexer loss, a mean over the
    positions."""
    b, t = tokens.shape
    nll, kl = lax.map(
        lambda row: _sequence_loss(params, row, config, precision), tokens)
    return jnp.sum(nll) / (b * (t - 1)) + jnp.sum(kl) / (b * t)


def reference_train(
    config: dict[str, Any], traffic: dict[str, Any], inputs: dict[str, Any],
    n_steps: int, precision: str = "float32", fault: str | None = None,
) -> dict[str, Any]:
    """Follow the first ``n_steps`` rounds: each station's loss and gradient
    on its own batch, the masked mean over stations, one Adam step. Returns
    what the comparison reads: every step's loss, the norm of every leaf of
    the first averaged gradient, and of the parameters' change after the
    last step.

    ``fault``: ``"half_batch"`` leaves out the second half of every
    station's rows, or of its one row's tokens (the mean is over the rest);
    ``"no_exchange"`` leaves out the cross-station mean (station 0's
    gradient is applied alone).
    """
    if fault not in (None, "half_batch", "no_exchange"):
        raise ValueError(f"no such fault: {fault!r}")
    p0 = inputs["params"]
    mask = np.asarray(inputs["mask"], np.float64)
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, tok: _loss(p, tok, config, precision)))
    add = jax.jit(lambda acc, g, w: jax.tree.map(
        lambda a, x: a + w * x, acc, g), donate_argnums=0)
    hyper = config["adam"]

    # the moments are written into their own buffers: eight trees of the
    # parameters' size would not fit beside each other on one chip
    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def adam(p, m, v, g, step):
        b1, b2 = hyper["b1"], hyper["b2"]
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
        c1, c2 = 1 - b1 ** step, 1 - b2 ** step
        p = jax.tree.map(
            lambda p, m, v: p - hyper["lr"] * (m / c1)
            / (jnp.sqrt(v / c2) + hyper["eps"]), p, m, v)
        return p, m, v

    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
    params, m, v = p0, zeros(p0), zeros(p0)
    losses, grad_norms = [], None
    for step in range(n_steps):
        tokens = inputs["tokens"][step % inputs["tokens"].shape[0]]
        if fault == "half_batch" and tokens.shape[1] > 1:
            tokens = tokens[:, : tokens.shape[1] // 2]
        elif fault == "half_batch":
            tokens = tokens[:, :, : tokens.shape[2] // 2]
        g_mean, loss_sum = zeros(p0), 0.0
        weights = mask / mask.sum()
        for s in range(tokens.shape[0]):
            if weights[s] == 0:
                continue
            loss, g = grad_fn(params, tokens[s])
            loss_sum += weights[s] * float(loss)
            w = weights[s]
            if fault == "no_exchange":
                w = 1.0 if s == 0 else 0.0
            g_mean = add(g_mean, g, jnp.float32(w))
        losses.append(loss_sum)
        if step == 0:
            grad_norms = leaf_norms(g_mean)
        params, m, v = adam(params, m, v, g_mean, jnp.float32(step + 1))
    change = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))(params, p0)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": leaf_norms(change)}
