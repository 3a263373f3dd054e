"""Device time per round under the program's `loop` scope: the stack of
layers walked `loops` times over the same weights, forward, recomputed and
backward, with the final norm after every walk; `attention_ms` and `mlp_ms`
lie inside it, the embedding, the heads and the exit gate outside. From the
device trace, by the scope path of each operation (`harness.Run.scope_ms`),
mean over the chips; reads nothing where no operation carries the scope (a
stack walked once opens none)."""


def read(run):
    return run.scope_ms("loop")
