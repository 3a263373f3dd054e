"""Device time per round under the program's `gather` scope: a local step's
minibatch: the index draw, the one row gather over the packed table and the
label column read back out of the gathered rows. From the device trace, by
the scope path of each operation (`harness.Run.scope_ms`), mean over the
chips; reads nothing where no operation carries the scope."""


def read(run):
    return run.scope_ms("gather")
