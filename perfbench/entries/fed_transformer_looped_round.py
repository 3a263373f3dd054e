"""`FedTransformer.round` through `make_engine`, for a configuration whose
stack is walked several times over the same weights (`total_ut_steps`) with
an exit gate and the exit-weighted loss, sandwich norms and a gated dense
MLP, named by the published keys: one federated round per dispatch, exactly
as `fed_transformer_round` drives it (same state, same dispatch, same first
steps). After the first steps and after the window, outside what is timed,
it has the engine read the exit distributions off the device and record them
(`exits.distribution`)."""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax.numpy as jnp

from perfbench import cells

_round = cells.load_module(cells.HERE / "entries" / "fed_transformer_round.py")


def handed_over(param_leaves: int) -> int:
    """Array leaves one launch hands the compiled program: the parameters,
    Adam's two moments and its count, tokens and mask."""
    return 3 * param_leaves + 3


class Program(_round.Program):
    def __init__(self, config: dict[str, Any], traffic: dict[str, Any],
                 make_inputs: Callable[[], dict[str, Any]], devices: list):
        from vantage6_tpu.workloads import fed_transformer as FT

        block = dict(
            norm="rmsnorm", norm_eps=config["rms_norm_eps"], norm_after=True,
            head_dim=config["head_dim"],
            n_kv_heads=config["num_key_value_heads"],
            positions="rotary", rope_theta=float(config["rope_theta"]),
            ffn="swiglu", d_ff=config["intermediate_size"],
            tie_head=config["tie_word_embeddings"],
            loops=config["total_ut_steps"], exit_beta=config["exit_beta"],
        )
        known = {f.name for f in dataclasses.fields(FT.TransformerConfig)}
        if set(block) - known:
            raise SystemExit(
                "this program's TransformerConfig cannot describe the block "
                f"of {config['name']}: it has no "
                f"{sorted(set(block) - known)}")
        cfg = FT.TransformerConfig(
            vocab=config["vocab_size"], d_model=config["hidden_size"],
            n_heads=config["num_attention_heads"],
            n_layers=config["num_hidden_layers"],
            max_len=config["max_position_embeddings"],
            dtype=jnp.dtype(traffic["compute_dtype"]),
            attention=traffic["attention"], remat=traffic["remat"],
            # off the TPU (the tests) a Pallas kernel would run interpreted
            flash_interpret=devices[0].platform != "tpu",
            **block,
        )
        self.engine = FT.make_engine(
            config["n_stations"], 1, cfg, lr=config["adam"]["lr"],
            devices=devices,
        )
        self._b1 = config["adam"]["b1"]
        self.restart(make_inputs)

    def first_steps(self, n_dispatches: int) -> dict[str, Any]:
        observed = super().first_steps(n_dispatches)
        self.engine.record_exit_distribution()
        return observed

    def drop_state(self) -> None:
        self.engine.record_exit_distribution()  # the window's rounds
        super().drop_state()


def build(config, traffic, make_inputs, devices) -> Program:
    return Program(config, traffic, make_inputs, devices)
