"""Device time per round under the program's `select` scope: finding each
query's kept keys from the indexer's scores (the search for the k-th largest
score, the ties, the kept pairs written out as a mask, and the count of
tiles that hold one), forward and recomputed under remat. From the device
trace (`harness.Run.scope_ms`); reads nothing where no operation carries the
scope."""


def read(run):
    return run.scope_ms("select")
