"""Federated logistic regression on a tabular federation: inputs from the
seed, the plain references, and the operation and byte counts — the yardstick
of `logreg-tabular-32st`.

``reference_fedavg`` is federated averaging with local minibatch SGD, as
`FedAvg.run_rounds` states it: per round and station ``local_steps`` steps on
``batch_size`` rows drawn with replacement, the deltas averaged with the
stations' row counts times the participation mask as weights, the server
adding the mean delta. The rows a step draws are part of the algorithm's
statement (``split`` of the dispatch key per round, ``fold_in`` of the
station, ``split`` per step, ``randint`` below the station's count), so the
reference draws them the same way, with `jax.random` alone. It is float32
`jax.numpy` with every product at ``precision=HIGHEST``, one station after
another, and imports nothing of `vantage6_tpu`.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from perfbench.precision import cotangent_rounder, rounder


# ------------------------------------------------------------------ inputs
def make_inputs(config: dict[str, Any], traffic: dict[str, Any],
                key: jax.Array) -> dict[str, Any]:
    """The stacked table [S, n, d] float32 with labels [S, n] and counts [S],
    and the initial parameters, made on the device in one jitted call."""
    s, n, d = (config["n_stations"], config["rows_per_station"],
               config["n_features"])

    def build(key):
        kx, kw, ks, ky, kp = jax.random.split(key, 5)
        x = jax.random.normal(kx, (s, n, d), jnp.float32)
        w_true = jax.random.normal(kw, (d,), jnp.float32) / jnp.sqrt(d)
        shift = jax.random.normal(ks, (s, 1), jnp.float32)
        z = jnp.einsum("snd,d->sn", x, w_true,
                       precision=lax.Precision.HIGHEST) + shift
        y = jax.random.bernoulli(ky, jax.nn.sigmoid(z)).astype(jnp.float32)
        params = {"w": 0.01 * jax.random.normal(kp, (d, 1), jnp.float32),
                  "b": jnp.zeros((1,), jnp.float32)}
        return x, y, params

    x, y, params = jax.jit(build)(key)
    return {
        "x": x, "y": y, "params": params,
        "counts": jnp.full((s,), n, jnp.int32),
        "mask": jnp.ones((s,), jnp.float32),
        "key": jax.random.fold_in(key, 1),
    }


# ------------------------------------------------------------------ counts
def _rows_per_round(config: dict[str, Any], traffic: dict[str, Any]) -> int:
    """Rows one round reads: every station's local steps times the batch."""
    per_station = traffic["local_steps"] * traffic["batch_size"]
    return config["n_stations"] * per_station


def flops_per_round(config: dict[str, Any], traffic: dict[str, Any]) -> float:
    """Forward x.w and backward x^T.dz: 2 d operations a row each."""
    return float(4 * config["n_features"] * _rows_per_round(config, traffic))


def min_bytes_per_round(config: dict[str, Any],
                        traffic: dict[str, Any]) -> float:
    """The least one round has to move: each sampled row read once per local
    step, at the table's own width (features and label)."""
    row = 4 * (config["n_features"] + 1)
    return float(row * _rows_per_round(config, traffic))


# -------------------------------------------------------------- references
def _nll_sum(params, x, y, precision):
    rnd, after = rounder(precision), cotangent_rounder(precision)
    z = after(jnp.matmul(rnd(x), rnd(params["w"]),
                         precision=lax.Precision.HIGHEST))
    z = z[:, 0] + params["b"][0]
    return jnp.sum(jnp.logaddexp(0.0, z) - y * z)


def _norms(tree: dict[str, Any]) -> dict[str, float]:
    return {k: float(jnp.linalg.norm(v)) for k, v in tree.items()}


def reference_fedavg(
    config: dict[str, Any], traffic: dict[str, Any], inputs: dict[str, Any],
    n_steps: int, precision: str = "float32", fault: str | None = None,
) -> dict[str, Any]:
    """Follow the first dispatch's ``n_steps`` rounds. ``fault``:
    ``"half_batch"`` draws the same rows and leaves the second half of every
    batch out; ``"no_exchange"`` applies station 0's delta alone."""
    if fault not in (None, "half_batch", "no_exchange"):
        raise ValueError(f"no such fault: {fault!r}")
    steps, batch = traffic["local_steps"], traffic["batch_size"]
    lr = traffic["local_lr"]
    keep = batch // 2 if fault == "half_batch" else batch

    @jax.jit
    def local_update(x, y, count, station, params, round_key):
        key = jax.random.fold_in(round_key, station)
        p, losses = params, []
        for step_key in jax.random.split(key, steps):
            idx = jax.random.randint(step_key, (batch,), 0, count)[:keep]
            loss, g = jax.value_and_grad(
                lambda p: _nll_sum(p, x[idx], y[idx], precision) / keep)(p)
            p = jax.tree.map(lambda a, b: a - lr * b, p, g)
            losses.append(loss)
        delta = jax.tree.map(jnp.subtract, p, params)
        return delta, jnp.mean(jnp.stack(losses))

    params = p0 = inputs["params"]
    counts = np.asarray(inputs["counts"])
    weights = counts * np.asarray(inputs["mask"], np.float64)
    weights = weights / weights.sum()
    losses, grad_norms = [], None
    for round_key in jax.random.split(inputs["key"], n_steps):
        mean_delta = jax.tree.map(jnp.zeros_like, params)
        loss = 0.0
        for s in range(config["n_stations"]):
            if weights[s] == 0:
                continue
            delta, station_loss = local_update(
                inputs["x"][s], inputs["y"][s], counts[s], s, params,
                round_key)
            loss += weights[s] * float(station_loss)
            w = weights[s]
            if fault == "no_exchange":
                w = 1.0 if s == 0 else 0.0
            mean_delta = jax.tree.map(
                lambda a, d: a + jnp.float32(w) * d, mean_delta, delta)
        losses.append(loss)
        if grad_norms is None:
            grad_norms = {"update": float(jnp.sqrt(sum(
                jnp.sum(d * d) for d in jax.tree.leaves(mean_delta))))}
        params = jax.tree.map(jnp.add, params, mean_delta)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": _norms(jax.tree.map(jnp.subtract, params, p0))}
