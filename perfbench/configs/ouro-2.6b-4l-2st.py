"""Ouro-2.6B (a looped language model: one stack of layers walked
`total_ut_steps` times over the same weights), one pipeline stage's four
layers, under federated averaging: inputs from the seed, the plain reference,
and the operation counts — the yardstick of `ouro-2.6b-4l-2st`.

The reference is the model of `ouro-2.6b-4l-2st.json` written from its
equations in straightforward `jax.numpy`: float32 throughout, every matrix
product at ``precision=HIGHEST``, a Python loop over the steps and the
layers, one station after another, the stations' gradients averaged, Adam
written out. It imports nothing of `vantage6_tpu` and takes nothing the
program made. With x the stream [T, d] and ``N_g(x) = x / sqrt(mean(x^2) +
eps) * g``, g learned:

- block: ``a = N_1(x)``; q, k, v = a W_q, a W_k, a W_v (16 heads of 128, as
  many kv heads); rotate-half RoPE at ``rope_theta`` on q and k; causal
  softmax attention over all earlier keys at scale ``1/sqrt(128)``, no
  window, no biases; ``x = x + N_2(attention W_o)``; ``m = N_3(x)``;
  ``x = x + N_4((silu(m W_gate) * (m W_up)) W_down)``: a norm before AND
  after each half;
- loop: ``h_0 = E[tokens]``; for r = 1..R: ``h_r = N_f(block_L(...
  block_1(h_{r-1})))``, the same L blocks and the same positions every step;
  the normed ``h_r`` is what the next step starts from and what gate and head
  read; ``logits_r = h_r W_head``; ``lambda_r = sigmoid(h_r w_g + b_g)`` per
  token;
- exit distribution per token: ``p_1 = lambda_1``, ``p_r = lambda_r prod_{j<r}
  (1 - lambda_j)`` for r < R, ``p_R = prod_{j<R} (1 - lambda_j)``: it sums
  to 1;
- loss (the paper's first-stage objective, uniform prior; arXiv 2510.25741):
  over the T - 1 predicted positions the mean of ``sum_r p_r CE(logits_r,
  next token) - beta H(p)``, ``H(p) = -sum_r p_r log p_r``.

Bookkeeping, not mathematics (each a departure from "no checkpoint", which
16 GB do not allow at T = 4,096: one block application's dense [16, T, T]
float32 scores are 1 GB, and there are sixteen): a block's activations are
recomputed in the backward pass; the dense masked softmax is computed one
head and one block of queries at a time, each recomputed in the backward
pass; an exit's [T, V] logits are recomputed in the backward pass, so one
exit's are live at a time.

``precision`` other than ``"float32"`` computes the same mathematics with
the operands of every matrix product rounded first, and on the way back the
cotangent that reaches it (perfbench/precision.py): the control that
`correct` has to fail. The gate's product is float32 in the configuration
itself (`precision`), so the control leaves it alone: a path in the next
precision down would too. ``fault`` plants one of the faults the comparison
has to catch.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.scipy.special import xlogy

from perfbench.compare import leaf_norms
from perfbench.precision import cotangent_rounder, rounder

MATRICES = ("qkv", "proj", "w_gate", "w_up", "w_down")
SCALES = ("norm1", "norm1_post", "norm2", "norm2_post")
QUERY_BLOCK = 512  # queries of one block of the reference's dense softmax


def _sizes(config: dict[str, Any]) -> dict[str, int]:
    return {
        "d": config["hidden_size"], "hd": config["head_dim"],
        "hq": config["num_attention_heads"],
        "hkv": config["num_key_value_heads"],
        "f": config["intermediate_size"],
        "layers": config["num_hidden_layers"], "v": config["vocab_size"],
        "steps": config["total_ut_steps"],
    }


# ------------------------------------------------------------------ inputs
def _layer_shapes(config: dict[str, Any]) -> dict[str, tuple[int, ...]]:
    z = _sizes(config)
    return {
        "qkv": (z["d"], (z["hq"] + 2 * z["hkv"]) * z["hd"]),
        "proj": (z["hq"] * z["hd"], z["d"]),
        "w_gate": (z["d"], z["f"]),
        "w_up": (z["d"], z["f"]),
        "w_down": (z["f"], z["d"]),
    }


def make_params(config: dict[str, Any], key: jax.Array) -> dict[str, Any]:
    """Matrices ~ N(0, initializer_range), the gate's [d, 1] among them and
    its bias 0, the input embedding ~ N(0, embedding_initializer_range), norm
    scales 1, float32, in the pytree the repo's transformer takes for this
    block: embed [V, d], head [d, V], final_norm [d], exit_gate{w, b},
    layers[i]{qkv, proj, w_gate, w_up, w_down, norm1, norm1_post, norm2,
    norm2_post}. One jitted call makes all of them on the device."""
    z = _sizes(config)
    s = config["initializer_range"]
    shapes = _layer_shapes(config)

    def build(key):
        keys = jax.random.split(key, 3 + z["layers"])
        layers = []
        for i in range(z["layers"]):
            sub = jax.random.split(keys[3 + i], len(MATRICES))
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
            "exit_gate": {
                "w": s * jax.random.normal(keys[2], (z["d"], 1), jnp.float32),
                "b": jnp.zeros((1,), jnp.float32)},
            "layers": layers,
        }

    return jax.jit(build)(key)


def make_tokens(config: dict[str, Any], traffic: dict[str, Any],
                key: jax.Array) -> jax.Array:
    """[n_batches, S, B, T] int32 over the whole vocabulary: ranks drawn
    Zipf (``zipf_exponent``) by the inverse of the cumulative distribution,
    and every station maps ranks to ids by a permutation of its own, so the
    stations' frequent tokens differ (non-IID)."""
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
def _applications(config: dict[str, Any]) -> int:
    """Block applications a sequence: every layer once a step."""
    return config["total_ut_steps"] * config["num_hidden_layers"]


def flops_per_round(config: dict[str, Any], traffic: dict[str, Any]) -> float:
    """Operations one round's forward and backward passes require: no
    recomputation counted, attention counted as the (query, key) pairs the
    causal mask leaves. The stack and the head are taken once a step; the
    parameters, and so `aggregate` and `server_update`, once."""
    z = _sizes(config)
    t = traffic["seq_len"]
    sequences = config["n_stations"] * traffic["batch"]
    per_token_block = (
        z["d"] * (z["hq"] + 2 * z["hkv"]) * z["hd"]      # q, k, v
        + z["hq"] * z["hd"] * z["d"]                     # output projection
        + 3 * z["d"] * z["f"]                            # gate, up, down
    )
    per_token = 2.0 * (per_token_block * _applications(config)
                       + z["steps"] * (z["d"] * z["v"] + z["d"]))
    pairs = t * (t + 1) // 2
    attention = 4.0 * z["hq"] * z["hd"] * _applications(config) * pairs
    return 3.0 * sequences * (t * per_token + attention)


def min_bytes_per_round(config: dict[str, Any],
                        traffic: dict[str, Any]) -> float | None:
    """Not bandwidth-bound: the configuration reports no HBM share."""
    return None


def glu_flops(config: dict[str, Any], traffic: dict[str, Any]) -> float:
    """Operations the program runs under its `mlp` scope in one round: the
    three products of the gated MLP over every block application, station
    and token, counted as the program runs them under ``remat`` (forward,
    forward again in the backward pass, two backward products each: 4
    passes; 3 without). For the scope's share of its roofline, so
    recomputation IS counted here (it is time the scope spends)."""
    z = _sizes(config)
    tokens = config["n_stations"] * traffic["batch"] * traffic["seq_len"]
    passes = 4 if traffic["remat"] else 3
    return (passes * 2.0 * 3 * z["d"] * z["f"] * tokens
            * _applications(config))


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


def _attention(q, k, v, rnd, after):
    """Dense causal softmax of one sequence: q, k, v [T, H, D]. One head and
    one block of queries at a time, each block recomputed in the backward
    pass."""
    hi = lax.Precision.HIGHEST
    t, h, d = q.shape
    block = min(QUERY_BLOCK, t)
    assert t % block == 0, (t, block)
    n_blocks = t // block
    qb = q.reshape(n_blocks, block, h, d)
    key_pos = jnp.arange(t)

    @jax.checkpoint
    def one(at):  # one (head, query block) of the h * n_blocks
        head, blk = at // n_blocks, at % n_blocks
        scores = after(jnp.einsum(
            "qd,sd->qs", rnd(qb[blk, :, head]), rnd(k[:, head]),
            precision=hi))
        q_pos = blk * block + jnp.arange(block)
        seen = key_pos[None, :] <= q_pos[:, None]
        p = jax.nn.softmax(
            jnp.where(seen, scores / math.sqrt(d), -jnp.inf), -1)
        return after(jnp.einsum("qs,sd->qd", rnd(p), rnd(v[:, head]),
                                precision=hi))

    out = lax.map(one, jnp.arange(h * n_blocks))        # [h * nb, block, D]
    return out.reshape(h, t, d).transpose(1, 0, 2).reshape(t, h * d)


def _products(precision: str):
    """``mm``, the matrix product at HIGHEST with what the control does
    around it, and the two roundings themselves (for the attention's
    products): operands rounded on the way forward, the cotangent on the way
    back; nothing at float32."""
    rnd, after = rounder(precision), cotangent_rounder(precision)

    def mm(a, w):
        return after(jnp.matmul(rnd(a), rnd(w),
                                precision=lax.Precision.HIGHEST))

    return mm, rnd, after


def _sequence_states(params, tokens, config, precision):
    """One sequence [T]: the normed state h_r [T, d] after every step."""
    mm, rnd, after = _products(precision)
    z = _sizes(config)
    assert z["hq"] == z["hkv"], "the published model has no grouped heads"
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    t = tokens.shape[0]

    @jax.checkpoint
    def block(x, layer):
        a = _rms(x, layer["norm1"], eps)
        nq, nkv = z["hq"] * z["hd"], z["hkv"] * z["hd"]
        qkv = mm(a, layer["qkv"])
        q = qkv[:, :nq].reshape(t, z["hq"], z["hd"])
        k = qkv[:, nq: nq + nkv].reshape(t, z["hkv"], z["hd"])
        v = qkv[:, nq + nkv:].reshape(t, z["hkv"], z["hd"])
        q, k = _rotate_half(q, theta), _rotate_half(k, theta)
        attended = mm(_attention(q, k, v, rnd, after), layer["proj"])
        x = x + _rms(attended, layer["norm1_post"], eps)
        m = _rms(x, layer["norm2"], eps)
        y = mm(jax.nn.silu(mm(m, layer["w_gate"])) * mm(m, layer["w_up"]),
               layer["w_down"])
        return x + _rms(y, layer["norm2_post"], eps)

    # written out in Python, sixteen block applications compile for about a
    # minute in every run, and nothing shorter fits: as `lax.scan`s over the
    # layers' stacked weights the reference's temporaries are 6.7 GB (both
    # loops) or 10.3 GB (the layers' alone) where these are 3.6 GB, beside
    # five trees of the parameters' size (compiled for the v5e, PR 35)
    h = params["embed"][tokens]
    states = []
    for _ in range(z["steps"]):
        for layer in params["layers"]:  # the same blocks every step
            h = block(h, layer)
        h = _rms(h, params["final_norm"], eps)
        states.append(h)
    return states


def _exit_probabilities(states, gate) -> jax.Array:
    """[R, T]: ``p_1 = lambda_1``, ``p_r = lambda_r prod_{j<r} (1 -
    lambda_j)``, ``p_R = prod_{j<R} (1 - lambda_j)``; the gate's product in
    float32 at HIGHEST whatever the control's precision."""
    lam = [jax.nn.sigmoid(jnp.matmul(
        h, gate["w"], precision=lax.Precision.HIGHEST)[:, 0] + gate["b"])
        for h in states]
    p, stayed = [], jnp.ones_like(lam[0])
    for r in range(len(states) - 1):
        p.append(lam[r] * stayed)
        stayed = stayed * (1.0 - lam[r])
    return jnp.stack(p + [stayed])


def _sequence_loss(params, tokens, config, precision):
    """``sum_r p_r CE_r - beta H(p)``, summed over the T - 1 predicted
    positions of one sequence [T]."""
    states = _sequence_states(params, tokens, config, precision)
    mm, _, _ = _products(precision)

    @jax.checkpoint
    def cross_entropy(h):  # one exit's logits at a time
        logp = jax.nn.log_softmax(mm(h[:-1], params["head"]))
        return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1)[:, 0]

    ce = jnp.stack([cross_entropy(h) for h in states])          # [R, T - 1]
    p = _exit_probabilities(states, params["exit_gate"])[:, :-1]
    entropy = -jnp.sum(xlogy(p, p), axis=0)
    return jnp.sum(jnp.sum(p * ce, axis=0) - config["exit_beta"] * entropy)


def exit_distribution(config: dict[str, Any], params: dict[str, Any],
                      tokens: jax.Array) -> np.ndarray:
    """[R]: the mean exit distribution of one round on ``tokens`` [S, B, T]
    over stations, rows and predicted positions: what the program's
    `exits.distribution` record has to hold for that round."""
    rows = tokens.reshape(-1, tokens.shape[-1])

    def one(p, row):
        states = _sequence_states(p, row, config, "float32")
        return jnp.mean(
            _exit_probabilities(states, p["exit_gate"])[:, :-1], axis=1)

    return np.asarray(jax.jit(lambda p, rows: jnp.mean(
        lax.map(lambda row: one(p, row), rows), axis=0))(params, rows))


def _loss(params, tokens, config, precision):
    """The mean loss of one station's [B, T] tokens."""
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
