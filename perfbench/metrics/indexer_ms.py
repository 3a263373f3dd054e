"""Device time per round under the program's `indexer` scope: the lightning
indexer's projections from the normed stream and its scores of every visible
(query, key) pair, forward, recomputed under remat and, for the projections,
backward (ops/sparse_attention.py). From the device trace, by the scope path
of each operation (`harness.Run.scope_ms`); reads nothing where no operation
carries the scope."""


def read(run):
    return run.scope_ms("indexer")
