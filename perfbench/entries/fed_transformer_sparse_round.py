"""`FedTransformer.round` through `make_engine`, for a configuration whose
block has learned sparse attention (an indexer under `sa_config`), per-head
q/k norms, a router after attention and SwiGLU experts, by the published key
names: one federated round per dispatch, exactly as `fed_transformer_round`
drives it (same state, same dispatch, same first steps). After the first
steps and after the window, outside what is timed, it has the engine read
the counts the rounds left on the device and record them (`experts.load`,
`sparse.tiles`)."""
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

        held = config["num_experts"]
        first = config["expert_parallel"]["this_chip"] * held
        sa = config["sa_config"]
        block = dict(
            norm="rmsnorm", norm_eps=config["rms_norm_eps"],
            head_dim=config["head_dim"],
            n_kv_heads=config["num_key_value_heads"],
            positions="rotary", rope_theta=float(config["rope_theta"]),
            qk_norm=True, ffn="experts", router_input="normed",
            expert_act=config["hidden_act"],
            n_experts=config["num_local_experts"],
            top_k=config["num_experts_per_tok"],
            d_expert=config["moe_intermediate_size"],
            experts_held=tuple(range(first, first + held)),
            tie_head=config["tie_word_embeddings"],
            sparse_top_k=sa["topk"], indexer_heads=sa["indexer_num_heads"],
            indexer_dim=sa["indexer_head_dim"],
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
            # off the TPU (the tests) the Pallas kernels run interpreted
            flash_interpret=devices[0].platform != "tpu",
            **block,
        )
        self.engine = FT.make_engine(
            config["n_stations"], 1, cfg, lr=config["adam"]["lr"],
            devices=devices,
        )
        self._b1 = config["adam"]["b1"]
        self.restart(make_inputs)

    def _record(self) -> None:
        self.engine.record_expert_load()
        self.engine.record_sparse_tiles()

    def first_steps(self, n_dispatches: int) -> dict[str, Any]:
        observed = super().first_steps(n_dispatches)
        self._record()
        return observed

    def drop_state(self) -> None:
        self._record()  # the window's rounds
        super().drop_state()


def build(config, traffic, make_inputs, devices) -> Program:
    return Program(config, traffic, make_inputs, devices)
