"""`FedTransformer.round` through `make_engine`: one federated round of a
decoder-only transformer per dispatch — every station's gradient on its own
batch, `fed_mean` across stations, Adam."""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from perfbench.compare import leaf_norms


class Program:
    rounds_per_dispatch = 1

    def __init__(self, config: dict[str, Any], traffic: dict[str, Any],
                 make_inputs: Callable[[], dict[str, Any]], devices: list):
        from vantage6_tpu.workloads import fed_transformer as FT

        cfg = FT.TransformerConfig(
            vocab=config["vocab_size"], d_model=config["n_embd"],
            n_heads=config["n_head"], n_layers=config["n_layer"],
            max_len=config["n_positions"],
            dtype=jnp.dtype(traffic["compute_dtype"]),
            attention=traffic["attention"], remat=traffic["remat"],
            # off the TPU (the tests) the Pallas kernel runs interpreted
            flash_interpret=devices[0].platform != "tpu",
        )
        self.engine = FT.make_engine(
            config["n_stations"], 1, cfg, lr=config["adam"]["lr"],
            devices=devices,
        )
        self._b1 = config["adam"]["b1"]
        self.restart(make_inputs)

    def restart(self, make_inputs: Callable[[], dict[str, Any]]) -> None:
        """State from ``make_inputs``, on the engine already built."""
        self._make_inputs = make_inputs
        inputs = make_inputs()
        # placed as FedTransformer.init places its own: everything the round
        # carries is committed to the mesh, so the first signature jit sees
        # is the steady one
        rep = NamedSharding(self.engine.mesh, P())
        self.params = jax.device_put(inputs["params"], rep)
        self.opt_state = jax.device_put(
            self.engine.optimizer.init(self.params), rep)
        self.mask = jax.device_put(inputs["mask"], rep)
        self.batches = [self.engine.shard_tokens(t) for t in inputs["tokens"]]
        self.loss = None
        self.step = 0

    def dispatch(self) -> None:
        tokens = self.batches[self.step % len(self.batches)]
        self.params, self.opt_state, self.loss = self.engine.round(
            self.params, self.opt_state, tokens, self.mask)
        self.step += 1
        jax.block_until_ready((self.params, self.opt_state, self.loss))

    def first_steps(self, n_dispatches: int) -> dict[str, Any]:
        """The first rounds, through ``dispatch`` and on the state the window
        goes on with. The first gradient is read from Adam's first moment
        after one step (mu = (1 - b1) g)."""
        losses, grad_norms = [], None
        for i in range(n_dispatches):
            self.dispatch()
            losses.append(float(self.loss))
            if i == 0:
                grad_norms = leaf_norms(
                    self.opt_state[0].mu, scale=1.0 / (1.0 - self._b1))
        start = self._make_inputs()["params"]
        change = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))(
            self.params, jax.device_put(start, self.params["embed"].sharding))
        del start
        return {"losses": losses, "grad_norms": grad_norms,
                "change_norms": leaf_norms(change)}

    def drop_state(self) -> None:
        """Free what the program holds on the device; the engine stays."""
        self.params = self.opt_state = self.batches = self.loss = None


def build(config, traffic, make_inputs, devices) -> Program:
    return Program(config, traffic, make_inputs, devices)
