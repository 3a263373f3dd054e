"""Device time per round under the program's `exit_gate` scope: the gate's
product on every walk's state, the exit distribution, its entropy and the
weighting of the walks' cross-entropies, forward and backward. From the
device trace, by the scope path of each operation (`harness.Run.scope_ms`),
mean over the chips; reads nothing where no operation carries the scope."""


def read(run):
    return run.scope_ms("exit_gate")
