"""GPT-2 medium under federated averaging: inputs from the seed, the plain
reference, and the operation count — the yardstick of `gpt2-medium-4st`.

The reference is the block of `gpt2-medium-4st.json` in straightforward
`jax.numpy`: float32 throughout, every matrix product at
``precision=HIGHEST``, one station after another, the stations' gradients
averaged, Adam written out. It imports nothing of `vantage6_tpu` and takes
nothing the program made: weights and tokens come from ``make_inputs``, which
the harness hands to the program and to the reference alike.

``precision`` other than ``"float32"`` computes the same mathematics with the
operands of every matrix product rounded first, and on the way back the
cotangent that reaches it (perfbench/precision.py): that is the control that
`correct` has to fail (see perfbench/README.md). ``fault`` plants one of the
faults the comparison has to catch.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from perfbench.precision import cotangent_rounder, rounder


LAYER_LEAVES = ("qkv", "proj", "w_up", "w_down")


# ------------------------------------------------------------------ inputs
def _layer_shapes(d: int) -> dict[str, tuple[int, int]]:
    return {"qkv": (d, 3 * d), "proj": (d, d), "w_up": (d, 4 * d),
            "w_down": (4 * d, d)}


def make_params(config: dict[str, Any], key: jax.Array) -> dict[str, Any]:
    """Weights ~ N(0, initializer_range), float32, in the pytree the repo's
    transformer takes: embed [V, d], pos [T, d], layers[i]{qkv, proj, w_up,
    w_down}. One jitted call makes all of them on the device."""
    d, n_layer = config["n_embd"], config["n_layer"]
    s = config["initializer_range"]
    shapes = _layer_shapes(d)

    def build(key):
        keys = jax.random.split(key, 2 + n_layer)
        layers = []
        for i in range(n_layer):
            sub = jax.random.split(keys[2 + i], len(LAYER_LEAVES))
            layers.append({
                name: s * jax.random.normal(sub[j], shapes[name], jnp.float32)
                for j, name in enumerate(LAYER_LEAVES)
            })
        return {
            "embed": s * jax.random.normal(
                keys[0], (config["vocab_size"], d), jnp.float32),
            "pos": s * jax.random.normal(
                keys[1], (config["n_positions"], d), jnp.float32),
            "layers": layers,
        }

    return jax.jit(build)(key)


def make_tokens(config: dict[str, Any], traffic: dict[str, Any],
                key: jax.Array) -> jax.Array:
    """[n_batches, S, B, T] int32: every station draws its ids around a
    centre of its own (non-IID stations), every row differs."""
    s, b, t = config["n_stations"], traffic["batch"], traffic["seq_len"]
    v = config["vocab_size"]

    def build(key):
        centre = (jnp.arange(1, s + 1) * v // (s + 1)).astype(jnp.float32)
        z = jax.random.normal(key, (traffic["n_batches"], s, b, t))
        ids = jnp.round(centre[None, :, None, None] + z * (v / 6))
        return jnp.clip(ids, 0, v - 1).astype(jnp.int32)

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
def flops_per_round(config: dict[str, Any], traffic: dict[str, Any]) -> float:
    """Operations one round's forward and backward passes require (no
    recomputation counted, causal attention counted as the half it is):
    per token 6 x (12 d^2 L + V d) for the matrix products and 6 T d L for
    attention; a round is S x B x T tokens."""
    d, n_layer, v = config["n_embd"], config["n_layer"], config["vocab_size"]
    t = traffic["seq_len"]
    per_token = 6 * (12 * d * d * n_layer + v * d) + 6 * t * d * n_layer
    return float(per_token * config["n_stations"] * traffic["batch"] * t)


def min_bytes_per_round(config: dict[str, Any],
                        traffic: dict[str, Any]) -> float | None:
    """Not bandwidth-bound: the configuration reports no HBM share."""
    return None


# --------------------------------------------------------------- reference
def _ln(x: jax.Array, eps: float) -> jax.Array:
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps)


def _loss(params: dict[str, Any], tokens: jax.Array, config: dict[str, Any],
          precision: str) -> jax.Array:
    """Mean next-token cross-entropy of one station's [B, T] tokens."""
    rnd, after = rounder(precision), cotangent_rounder(precision)
    hi = lax.Precision.HIGHEST
    n_head, eps = config["n_head"], config["layer_norm_epsilon"]
    b, t = tokens.shape
    d = params["embed"].shape[1]
    causal = jnp.tril(jnp.ones((t, t), bool))

    def mm(a, w):
        return after(jnp.matmul(rnd(a), rnd(w), precision=hi))

    def block(x, layer):
        h = _ln(x, eps)
        q, k, v = jnp.split(mm(h, layer["qkv"]), 3, axis=-1)
        q, k, v = (z.reshape(b, t, n_head, d // n_head) for z in (q, k, v))
        scores = after(
            jnp.einsum("bthd,bshd->bhts", rnd(q), rnd(k), precision=hi))
        scores = jnp.where(causal, scores / math.sqrt(d // n_head), -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        attn = after(
            jnp.einsum("bhts,bshd->bthd", rnd(p), rnd(v), precision=hi))
        x = x + mm(attn.reshape(b, t, d), layer["proj"])
        h = _ln(x, eps)
        up = jax.nn.gelu(mm(h, layer["w_up"]), approximate=True)
        return x + mm(up, layer["w_down"]), None

    x = params["embed"][tokens] + params["pos"][:t][None]
    # the layers' activations are recomputed in the backward pass so that the
    # reference fits beside its own gradients: exact in the mathematics
    x, _ = lax.scan(jax.checkpoint(block), x, params["layers"])
    logits = mm(_ln(x, eps), params["embed"].T)
    logp = jax.nn.log_softmax(logits[:, :-1])
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll)


def _stack_layers(params: dict[str, Any]) -> dict[str, Any]:
    return {
        "embed": params["embed"], "pos": params["pos"],
        "layers": {name: jnp.stack([l[name] for l in params["layers"]])
                   for name in LAYER_LEAVES},
    }


def leaf_norms(stacked: dict[str, Any]) -> dict[str, float]:
    """L2 norm of every leaf, named as the program's pytree names them."""
    out = {"embed": float(jnp.linalg.norm(stacked["embed"])),
           "pos": float(jnp.linalg.norm(stacked["pos"]))}
    for name in LAYER_LEAVES:
        per = np.asarray(jnp.sqrt(jnp.sum(
            stacked["layers"][name] ** 2, axis=(1, 2))))
        out.update({f"layers.{i}.{name}": float(v) for i, v in enumerate(per)})
    return out


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
    station's rows (the mean is over the rest); ``"no_exchange"`` leaves out
    the cross-station mean (station 0's gradient is applied alone).
    """
    if fault not in (None, "half_batch", "no_exchange"):
        raise ValueError(f"no such fault: {fault!r}")
    p0 = _stack_layers(inputs["params"])
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
        tokens = inputs["tokens"][step]
        if fault == "half_batch":
            tokens = tokens[:, : max(1, tokens.shape[1] // 2)]
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
