"""SmallThinker-21BA3B-Instruct, one chip's share of an 8-way expert-parallel
deployment, under federated averaging: inputs from the seed, the plain
reference, and the operation counts — the yardstick of
`smallthinker-21b-ep8-2st`.

The reference is the layer of `smallthinker-21b-ep8-2st.json` written from
its equations in straightforward `jax.numpy`: float32 throughout, every
matrix product at ``precision=HIGHEST``, one station after another, the
stations' gradients averaged, Adam written out. It imports nothing of
`vantage6_tpu` and takes nothing the program made. With x the residual
stream [T, d]:

- ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``, g learned;
- the router BEFORE attention, on the block's input x (before the input
  norm): ``p = softmax(x W_r)`` over all the experts of the deployment, the
  ``k`` largest, their p renormalised to sum 1;
- attention on ``RMSNorm(x)``: H_q query heads and H_kv key/value heads of
  ``head_dim`` (H_q / H_kv query heads read one kv head), scale
  ``1/sqrt(head_dim)``, causal, no biases; a layer whose
  ``sliding_window_layout`` entry is 1 sees keys ``i - window < j <= i``,
  one whose ``rope_layout`` entry is 1 rotates q and k (rotate-half,
  ``rope_theta``), and one whose entry is 0 takes no positions at all;
  output projection, residual;
- the experts on ``RMSNorm(x + attention)``: ``y = sum_e w_e W_down,e
  (relu(W_gate,e h) * (W_up,e h))`` over the chosen experts e that are HELD
  HERE, as a loop over the held experts with a mask (no sort, no grouped
  product); what the absent experts would have added is left out; residual;
- final RMSNorm, an output head of its own, mean next-token cross-entropy
  over the slice of the vocabulary held.

The dense masked softmax is computed one kv head's group of query heads and
one block of queries at a time, each block recomputed in the backward pass,
so that the [H_q, T, T] float32 scores never exist; that is bookkeeping, not
mathematics.

``precision`` other than ``"float32"`` computes the same mathematics with the
operands of every matrix product rounded first, and on the way back the
cotangent that reaches it (perfbench/precision.py): the control that
`correct` has to fail. The router's product is float32 in the
configuration itself (`assumed.routing`), so the control leaves it alone: a
path in the next precision down would too. ``fault`` plants one of the
faults the comparison has to catch.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from perfbench.compare import leaf_norms
from perfbench.precision import cotangent_rounder, rounder

MATRICES = ("qkv", "proj", "router", "w_gate", "w_up", "w_down")
SCALES = ("norm1", "norm2")
QUERY_BLOCK = 512  # queries of one block of the reference's dense softmax


def _sizes(config: dict[str, Any]) -> dict[str, int]:
    held = config["moe_num_primary_experts"]
    return {
        "d": config["hidden_size"], "hd": config["head_dim"],
        "hq": config["num_attention_heads"],
        "hkv": config["num_key_value_heads"],
        "f": config["moe_ffn_hidden_size"], "held": held,
        "experts": held * config["expert_parallel"]["chips"],
        "k": config["moe_num_active_primary_experts"],
        "layers": config["num_hidden_layers"], "v": config["vocab_size"],
    }


def held_experts(config: dict[str, Any]) -> tuple[int, ...]:
    """The ids, among all the deployment's experts, of those this chip
    holds: chip c of the layer's ``chips`` holds ``[c * held, (c + 1) *
    held)``."""
    held = config["moe_num_primary_experts"]
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
    }


def make_params(config: dict[str, Any], key: jax.Array) -> dict[str, Any]:
    """Matrices ~ N(0, initializer_range), the input embedding ~ N(0,
    embedding_initializer_range), norm scales 1, float32, in the pytree the repo's transformer takes for this block: embed [V, d], head
    [d, V], final_norm [d], layers[i]{qkv, proj, router, w_gate, w_up,
    w_down, norm1, norm2}. One jitted call makes all of them on the device."""
    z = _sizes(config)
    s = config["initializer_range"]
    shapes = _layer_shapes(config)

    def build(key):
        keys = jax.random.split(key, 2 + z["layers"])
        layers = []
        for i in range(z["layers"]):
            sub = jax.random.split(keys[2 + i], len(MATRICES))
            layer = {name: s * jax.random.normal(sub[j], shapes[name],
                                                 jnp.float32)
                     for j, name in enumerate(MATRICES)}
            layer.update({name: jnp.ones((z["d"],), jnp.float32)
                          for name in SCALES})
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
def visible_pairs(t: int, window: int | None) -> int:
    """(query, key) pairs of one causal sequence of ``t`` tokens, each query
    seeing its last ``window`` keys (itself among them), or all of them."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def _layer_windows(config: dict[str, Any]) -> list[int | None]:
    return [config["sliding_window_size"] if on else None
            for on in config["sliding_window_layout"]]


def expert_flops_per_assignment(config: dict[str, Any]) -> float:
    """One token through one expert, forward: three products of d x f."""
    z = _sizes(config)
    return 2.0 * 3 * z["d"] * z["f"]


def flops_per_round(config: dict[str, Any], traffic: dict[str, Any]) -> float:
    """Operations one round's forward and backward passes require: no
    recomputation counted, attention counted as the (query, key) pairs its
    mask leaves, the experts at the UNIFORM EXPECTATION of ``k * held /
    experts`` assignments a token (what the router really sends is in the
    `experts.load` record, and `experts_flops` counts that)."""
    z = _sizes(config)
    t = traffic["seq_len"]
    sequences = config["n_stations"] * traffic["batch"]
    per_token_layer = (
        z["d"] * (z["hq"] + 2 * z["hkv"]) * z["hd"]      # q, k, v
        + z["hq"] * z["hd"] * z["d"]                     # output projection
        + z["d"] * z["experts"]                          # router
    )
    matmuls = 2.0 * (per_token_layer * z["layers"] + z["d"] * z["v"])
    expected = z["k"] * z["held"] / z["experts"]
    experts = expected * expert_flops_per_assignment(config) * z["layers"]
    pairs = sum(visible_pairs(t, w) for w in _layer_windows(config))
    attention = 4.0 * z["hq"] * z["hd"] * pairs          # scores and values
    return 3.0 * sequences * (t * (matmuls + experts) + attention)


def min_bytes_per_round(config: dict[str, Any],
                        traffic: dict[str, Any]) -> float | None:
    """Not bandwidth-bound: the configuration reports no HBM share."""
    return None


# models/experts.py::TOKEN_CHUNK, stated again: this file imports nothing of
# the program's
EXPERT_CHUNK_TOKENS = 2048


def experts_flops(config: dict[str, Any], traffic: dict[str, Any],
                  assignments: float) -> float:
    """Operations the program runs under its `experts` scope in one round
    for ``assignments`` (token, held expert) pairs, summed over layers and
    stations: the forward products, the backward's two products per forward
    product, and the forward products again where the program recomputes
    them. That is the expert layer's own doing, not ``remat``'s (the layer
    is kept out of the block's recomputation): a station's tokens beyond
    one chunk of `EXPERT_CHUNK_TOKENS`, and a whole number of chunks, go
    through chunk by chunk, each recomputed in the backward pass. For the
    scope's share of its roofline, so recomputation IS counted here (it is
    time the scope spends)."""
    tokens = traffic["batch"] * traffic["seq_len"]  # of one station
    recomputed = (tokens > EXPERT_CHUNK_TOKENS
                  and tokens % EXPERT_CHUNK_TOKENS == 0)
    passes = 4 if recomputed else 3
    return passes * assignments * expert_flops_per_assignment(config)


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


def route(x: jax.Array, w_router: jax.Array, k: int):
    """The ``k`` largest of ``softmax(x W_r)`` and their renormalised
    probabilities: float32 at HIGHEST whatever the control's precision."""
    p = jax.nn.softmax(
        jnp.matmul(x, w_router, precision=lax.Precision.HIGHEST), -1)
    top_p, choice = lax.top_k(p, k)
    return choice, top_p / jnp.sum(top_p, -1, keepdims=True)


def _attention(q, k, v, window, rnd, after):
    """Dense masked softmax of one sequence: q [T, Hq, D], k, v [T, Hkv, D].
    One kv head's query heads and one block of queries at a time, each
    block recomputed in the backward pass."""
    hi = lax.Precision.HIGHEST
    t, hq, d = q.shape
    hkv = k.shape[1]
    block = min(QUERY_BLOCK, t)
    assert t % block == 0, (t, block)
    n_blocks = t // block
    qg = q.reshape(n_blocks, block, hkv, hq // hkv, d)
    key_pos = jnp.arange(t)

    @jax.checkpoint
    def one(at):  # one (kv head, query block) of the hkv * n_blocks
        h, blk = at // n_blocks, at % n_blocks
        q_blk = qg[blk, :, h]                              # [block, G, D]
        k_h, v_h = k[:, h], v[:, h]                        # [T, D]
        scores = after(jnp.einsum(
            "qgd,sd->gqs", rnd(q_blk), rnd(k_h), precision=hi))
        q_pos = blk * block + jnp.arange(block)
        seen = key_pos[None, :] <= q_pos[:, None]
        if window is not None:
            seen = seen & (key_pos[None, :] > q_pos[:, None] - window)
        p = jax.nn.softmax(
            jnp.where(seen[None], scores / math.sqrt(d), -jnp.inf), -1)
        return after(jnp.einsum("gqs,sd->qgd", rnd(p), rnd(v_h),
                                precision=hi))

    out = lax.map(one, jnp.arange(hkv * n_blocks))  # [hkv * nb, block, G, D]
    out = out.reshape(hkv, t, hq // hkv, d).transpose(1, 0, 2, 3)
    return out.reshape(t, hq * d)


def held_experts_part(h, choice, weight, held, w_gate, w_up, w_down, mm):
    """``sum_e w_e W_down,e (relu(W_gate,e h) * (W_up,e h))`` over the
    chosen experts e among ``held`` (their ids, beside their stacked
    weights), one expert after another, every token through each with its
    weight or 0. Beside y, the assignments each held expert received."""
    def one_expert(y, e):
        expert, w_g, w_u, w_d = e
        chosen = choice == expert
        w_e = jnp.sum(jnp.where(chosen, weight, 0.0), -1)
        mid = jax.nn.relu(mm(h, w_g)) * mm(h, w_u)
        return y + w_e[:, None] * mm(mid, w_d), jnp.sum(chosen)

    return lax.scan(one_expert, jnp.zeros_like(h),
                    (jnp.asarray(held, jnp.int32), w_gate, w_up, w_down))


def _sequence_forward(params, tokens, config, precision):
    """One sequence [T]: the logits [T, V] and, per layer, the assignments
    each held expert received [L, held]."""
    rnd, after = rounder(precision), cotangent_rounder(precision)
    hi = lax.Precision.HIGHEST
    z = _sizes(config)
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    held = held_experts(config)
    t = tokens.shape[0]

    def mm(a, w):
        return after(jnp.matmul(rnd(a), rnd(w), precision=hi))

    def block(x, layer, window, rotates):
        choice, weight = route(x, layer["router"], z["k"])
        h = _rms(x, layer["norm1"], eps)
        nq, nkv = z["hq"] * z["hd"], z["hkv"] * z["hd"]
        qkv = mm(h, layer["qkv"])
        q = qkv[:, :nq].reshape(t, z["hq"], z["hd"])
        k = qkv[:, nq: nq + nkv].reshape(t, z["hkv"], z["hd"])
        v = qkv[:, nq + nkv:].reshape(t, z["hkv"], z["hd"])
        if rotates:
            q, k = _rotate_half(q, theta), _rotate_half(k, theta)
        x = x + mm(_attention(q, k, v, window, rnd, after), layer["proj"])
        y, counts = held_experts_part(
            _rms(x, layer["norm2"], eps), choice, weight, held,
            layer["w_gate"], layer["w_up"], layer["w_down"], mm)
        return x + y, counts

    x = params["embed"][tokens]
    windows = _layer_windows(config)
    kinds = [(windows[i], bool(config["rope_layout"][i]))
             for i in range(z["layers"])]
    counts = []
    i = 0
    while i < z["layers"]:
        # a run of layers of one kind is one loop over their stacked
        # weights (it compiles once), and a layer's activations are
        # recomputed in the backward pass so that the reference fits beside
        # its own gradients: neither changes the mathematics
        j = i
        while j < z["layers"] and kinds[j] == kinds[i]:
            j += 1
        one_layer = jax.checkpoint(
            lambda x, layer, kind=kinds[i]: block(x, layer, *kind))
        stacked = jax.tree.map(lambda *ws: jnp.stack(ws),
                               *params["layers"][i:j])
        x, run_counts = lax.scan(one_layer, x, stacked)
        counts.append(run_counts)
        i = j
    logits = mm(_rms(x, params["final_norm"], eps), params["head"])
    return logits, jnp.concatenate(counts)


def _sequence_loss(params, tokens, config, precision):
    """Next-token cross-entropy, summed over one sequence [T]."""
    logits, _ = _sequence_forward(params, tokens, config, precision)
    logp = jax.nn.log_softmax(logits[:-1])
    return -jnp.sum(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))


def expert_load(config: dict[str, Any], params: dict[str, Any],
                tokens: jax.Array) -> np.ndarray:
    """[L, held]: the assignments each held expert receives in one round on
    ``tokens`` [S, B, T], summed over stations and rows: what the program's
    `experts.load` record has to hold for that round."""
    rows = tokens.reshape(-1, tokens.shape[-1])
    counts = jax.jit(lambda p, rows: jnp.sum(lax.map(
        lambda row: _sequence_forward(p, row, config, "float32")[1], rows),
        axis=0))(params, rows)
    return np.asarray(counts)


def _loss(params, tokens, config, precision):
    """Mean next-token cross-entropy of one station's [B, T] tokens."""
    b, t = tokens.shape
    total = jnp.sum(lax.map(
        lambda row: _sequence_loss(params, row, config, precision), tokens))
    return total / (b * (t - 1))


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

    @jax.jit
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
