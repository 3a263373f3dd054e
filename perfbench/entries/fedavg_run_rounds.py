"""`FedAvg.run_rounds`: K federated-averaging rounds fused into one dispatch,
with the shipped defaults (learning statistics on, donation, `observed_jit`).
The loss is a weighted binary negative log-likelihood over
`models/logistic.py::logits`."""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np


def _weighted_nll(params, x, y, w):
    from vantage6_tpu.models.logistic import logits

    z = logits(params, x)[:, 0]
    return jnp.sum(w * (jnp.logaddexp(0.0, z) - y * z)) / jnp.sum(w)


class Program:
    def __init__(self, config: dict[str, Any], traffic: dict[str, Any],
                 make_inputs: Callable[[], dict[str, Any]], devices: list):
        from vantage6_tpu.core.mesh import FederationMesh
        from vantage6_tpu.fed.fedavg import FedAvg, FedAvgSpec

        self.rounds_per_dispatch = traffic["rounds_per_dispatch"]
        self.mesh = FederationMesh(config["n_stations"], devices=devices)
        self.engine = FedAvg(self.mesh, FedAvgSpec(
            loss_fn=_weighted_nll,
            local_steps=traffic["local_steps"],
            batch_size=traffic["batch_size"],
            local_lr=traffic["local_lr"],
            learning_stats=traffic["learning_stats"],
        ))
        self.restart(make_inputs)

    def restart(self, make_inputs: Callable[[], dict[str, Any]]) -> None:
        """State from ``make_inputs``, on the engine already built."""
        inputs = make_inputs()
        self.x = self.mesh.shard_stacked(inputs["x"])
        self.y = self.mesh.shard_stacked(inputs["y"])
        self.counts, self.mask = inputs["counts"], inputs["mask"]
        # the start is kept on the host: run_rounds donates what it is given
        self._start = jax.device_get(inputs["params"])
        self.params = inputs["params"]
        self.opt_state = self.engine.init(self.params)
        # one key per dispatch, the first of them the one the reference
        # follows; kept as host words so that no device program runs between
        # dispatches to make the next
        self._first_key = inputs["key"]
        self._key_words = np.asarray(jax.random.key_data(
            jax.random.split(jax.random.fold_in(inputs["key"], 7), 4096)))
        self.out = None
        self.step = 0

    def _key(self) -> jax.Array:
        if self.step == 0:
            return self._first_key
        return jax.random.wrap_key_data(
            self._key_words[self.step % len(self._key_words)])

    def dispatch(self) -> None:
        self.params, self.opt_state, losses, stats = self.engine.run_rounds(
            self.params, self.x, self.y, self.counts, self._key(),
            self.rounds_per_dispatch, mask=self.mask, opt_state=self.opt_state,
        )
        self.step += 1
        self.out = (losses, stats)
        jax.block_until_ready((self.params, self.opt_state, self.out))

    def first_steps(self, n_dispatches: int) -> dict[str, Any]:
        """The first dispatch's rounds: each round's loss, the norm of the
        first pooled update as the server got it (the learning statistics'
        ``update_norm``), and the parameters' change after the dispatch. A
        fused dispatch shows no state between its rounds, so the change is
        read after all of them."""
        if n_dispatches != 1:
            raise ValueError("a fused dispatch is followed whole, once")
        self.dispatch()
        losses, stats = jax.device_get(self.out)
        now = jax.device_get(self.params)
        return {
            "losses": [float(v) for v in losses],
            "grad_norms": {"update": float(stats["update_norm"][0])},
            "change_norms": {k: float(np.linalg.norm(now[k] - self._start[k]))
                             for k in now},
        }

    def drop_state(self) -> None:
        """Free what the program holds on the device; the engine stays."""
        self.x = self.y = self.params = self.opt_state = self.out = None


def build(config, traffic, make_inputs, devices) -> Program:
    return Program(config, traffic, make_inputs, devices)
