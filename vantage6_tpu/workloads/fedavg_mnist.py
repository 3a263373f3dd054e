"""FedAvg 2-layer CNN — the flagship workload (BASELINE.md workload 3).

Reference shape: an algorithm repo's central function loops rounds of
`client.task.create(partial_train)` + `wait_for_results` + weighted average
(SURVEY.md §3.2). Here both forms exist:

- `central_fedavg` keeps that reference-shaped loop through the
  AlgorithmClient API (each round = one SPMD dispatch instead of N
  containers);
- `train_fedavg` drives the FedAvg engine directly with the full round loop
  in lax.scan — the maximum-performance path bench.py measures.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax

from vantage6_tpu.algorithm.decorators import algorithm_client, device_step
from vantage6_tpu.core.mesh import FederationMesh
from vantage6_tpu.fed.collectives import fed_mean
from vantage6_tpu.fed.fedavg import FedAvg, FedAvgSpec
from vantage6_tpu.models.cnn import CNN, accuracy, cross_entropy_loss
from vantage6_tpu.utils.datasets import (
    image_classes,
    partition_dirichlet,
    pad_shards,
    synthetic_image_classes,
)

MODEL = CNN()


def weighted_ce_loss(params, bx, by, w):
    """Per-example-weighted cross entropy (FedAvgSpec.loss_fn signature)."""
    logits = MODEL.apply({"params": params}, bx)
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, by[:, None], axis=1)[:, 0]
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)


def init_params(key: jax.Array, image_shape=(28, 28, 1)) -> Any:
    return MODEL.init(key, jnp.zeros((1, *image_shape), jnp.float32))["params"]


# ------------------------------------------------------------ direct engine
def make_engine(
    mesh: FederationMesh,
    local_steps: int = 10,
    batch_size: int = 32,
    local_lr: float = 0.05,
    server_optimizer: optax.GradientTransformation | None = None,
    shard_server_update: bool = False,
    comm_dtype: Any = None,
    compressor: Any = None,
    learning_stats: bool = True,
) -> FedAvg:
    return FedAvg(
        mesh,
        FedAvgSpec(
            loss_fn=weighted_ce_loss,
            local_steps=local_steps,
            batch_size=batch_size,
            local_lr=local_lr,
            server_optimizer=server_optimizer,
            shard_server_update=shard_server_update,
            comm_dtype=comm_dtype,
            compressor=compressor,
            # False in the pure-throughput bench legs: a timed round must
            # not compute stats it immediately discards (and the baseline
            # trend stays comparable to pre-learning-plane rounds)
            learning_stats=learning_stats,
        ),
    )


def make_federated_data(
    n_stations: int,
    n_per_station: int = 256,
    alpha: float = 0.5,
    seed: int = 0,
    mesh: FederationMesh | None = None,
    noise: float = 0.7,
):
    """MNIST-shaped data (REAL MNIST when a local copy exists — see
    utils.datasets.load_mnist — synthetic templates otherwise), federated
    by :func:`federate`. ``noise`` hardens the synthetic task (see
    utils.datasets.image_classes)."""
    x, y = image_classes(n_stations * n_per_station, seed=seed, noise=noise)
    return federate(x, y, n_stations, alpha=alpha, seed=seed, mesh=mesh)


def federate(
    x: np.ndarray,
    y: np.ndarray,
    n_stations: int,
    alpha: float = 0.5,
    seed: int = 0,
    mesh: FederationMesh | None = None,
):
    """Examples -> (stacked x, stacked y, counts): Dirichlet non-iid across
    stations, padded + stacked, and with a mesh placed on it (shards over
    the station axis, counts on every device)."""
    shards = partition_dirichlet(x, y, n_stations, alpha=alpha, seed=seed)
    sx, sy, counts = pad_shards(shards)
    if mesh is None:
        return sx, sy, jnp.asarray(counts)
    return mesh.shard_stacked(sx), mesh.shard_stacked(sy), mesh.replicate(counts)


def train_fedavg(
    mesh: FederationMesh,
    n_rounds: int = 20,
    seed: int = 0,
    **engine_kw: Any,
):
    """End-to-end training on synthetic data; returns (params, losses)."""
    engine = make_engine(mesh, **engine_kw)
    sx, sy, counts = make_federated_data(mesh.n_stations, mesh=mesh)
    key = jax.random.key(seed)
    params = init_params(jax.random.fold_in(key, 1))
    params, _, losses, _ = engine.run_rounds(
        params, sx, sy, counts, jax.random.fold_in(key, 2), n_rounds
    )
    return params, losses


def evaluate(params: Any, x: np.ndarray, y: np.ndarray) -> float:
    logits = MODEL.apply({"params": params}, jnp.asarray(x))
    return float(accuracy(logits, jnp.asarray(y)))


# ----------------------------------------------- reference-shaped algorithm
@device_step
def partial_train(data_: Any, params: Any, local_steps: int = 10,
                  batch_size: int = 32, lr: float = 0.05,
                  round_seed: int = 0) -> dict[str, Any]:
    """One station's local training (device mode): global params in, delta
    out. data_ = {"x": [n,...], "y": [n], "count": [], "sid": []}."""
    key = jax.random.fold_in(jax.random.key(round_seed), data_["sid"])
    safe = jnp.maximum(data_["count"].astype(jnp.int32), 1)

    def step(p, k):
        idx = jax.random.randint(k, (batch_size,), 0, safe)
        bx = jnp.take(data_["x"], idx, axis=0)
        by = jnp.take(data_["y"], idx, axis=0)
        loss, grads = jax.value_and_grad(
            lambda q: cross_entropy_loss(MODEL.apply({"params": q}, bx), by)
        )(p)
        p = jax.tree.map(lambda a, g: a - lr * g, p, grads)
        return p, loss

    new_params, losses = jax.lax.scan(step, params, jax.random.split(
        key, local_steps))
    return {
        "delta": jax.tree.map(lambda n, o: n - o, new_params, params),
        "count": data_["count"],
        "loss": jnp.mean(losses),
    }


@algorithm_client
def central_fedavg(client: Any, n_rounds: int = 5, local_steps: int = 10,
                   batch_size: int = 32, lr: float = 0.05,
                   seed: int = 0) -> dict[str, Any]:
    """Reference-shaped central loop: subtask per round, aggregate on device.

    Ports the v6 FedAvg central-function pattern; `wait_for_stacked_result`
    replaces seconds of HTTPS polling with an on-device stacked pytree.
    """
    params = init_params(jax.random.key(seed))
    orgs = [o["id"] for o in client.organization.list()]
    losses = []
    for r in range(n_rounds):
        task = client.task.create(
            input_={
                "method": "partial_train",
                "args": [params],
                "kwargs": {
                    "local_steps": local_steps,
                    "batch_size": batch_size,
                    "lr": lr,
                    "round_seed": seed * 100003 + r,
                },
            },
            organizations=orgs,
            name=f"round_{r}",
        )
        stacked, mask = client.wait_for_stacked_result(task["id"])
        weights = stacked["count"] * mask
        mean_delta = fed_mean(stacked["delta"], weights=weights)
        params = jax.tree.map(lambda p, d: p + d, params, mean_delta)
        losses.append(float(fed_mean(stacked["loss"], weights=weights)))
    return {"params": params, "losses": losses}
