"""Device time per round under the program's `indexer_loss` scope: the
indexer's KL to the attention's head-averaged probabilities over the kept
keys, forward, recomputed under remat and its gradient to the indexer
(ops/sparse_attention.py). From the device trace (`harness.Run.scope_ms`);
reads nothing where no operation carries the scope."""


def read(run):
    return run.scope_ms("indexer_loss")
