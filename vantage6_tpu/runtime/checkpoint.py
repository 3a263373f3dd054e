"""Checkpoint/resume of federated training state.

The reference has NO mid-task checkpointing (SURVEY.md §5): a failed task is
simply resubmitted, and algorithm state lives only in task payloads. For
multi-hour TPU training that is not acceptable, so this is a deliberate
capability ADD: orbax checkpoints of (global model, server opt state, round
index, rng key) with atomic write + latest-resume, so a preempted pod
resumes mid-run.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Any

import jax
import numpy as np
import orbax.checkpoint as ocp


@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    round_index: int
    rng_key: Any
    # learning-plane round history (runtime.learning.RoundHistory
    # .state_arrays()): checkpointing it keeps the norm-decay trajectory
    # CONTINUOUS across a resume, so the watchdog's non_convergence /
    # model_divergence rules never see a restart as a fresh (alarming)
    # trajectory. Optional and absent-tolerant both ways: old checkpoints
    # restore with history=None, and a None history writes the exact
    # pre-learning-plane tree.
    history: Any = None

    def as_pytree(self) -> dict[str, Any]:
        tree = {
            "params": self.params,
            "opt_state": self.opt_state,
            "round_index": np.asarray(self.round_index, np.int64),
            "rng_key": jax.random.key_data(self.rng_key),
        }
        if self.history is not None:
            tree["history"] = self.history
        return tree

    @classmethod
    def from_pytree(cls, tree: dict[str, Any]) -> "TrainState":
        return cls(
            params=tree["params"],
            opt_state=tree["opt_state"],
            round_index=int(np.asarray(tree["round_index"])),
            rng_key=jax.random.wrap_key_data(
                np.asarray(tree["rng_key"], dtype=np.uint32)
            ),
            history=tree.get("history"),
        )


class CheckpointManager:
    """Thin wrapper over orbax CheckpointManager keyed by round index."""

    def __init__(self, directory: str | Path, max_to_keep: int = 3):
        self.directory = Path(directory).absolute()
        self.directory.mkdir(parents=True, exist_ok=True)
        self._mgr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep, create=True
            ),
        )

    def save(self, state: TrainState, wait: bool = False) -> None:
        self._mgr.save(
            state.round_index, args=ocp.args.StandardSave(state.as_pytree())
        )
        if wait:
            self._mgr.wait_until_finished()

    def latest_round(self) -> int | None:
        return self._mgr.latest_step()

    def restore(self, round_index: int | None = None) -> TrainState | None:
        step = round_index if round_index is not None else self.latest_round()
        if step is None:
            return None
        tree = self._mgr.restore(step)
        return TrainState.from_pytree(tree)

    def close(self) -> None:
        self._mgr.wait_until_finished()
        self._mgr.close()
