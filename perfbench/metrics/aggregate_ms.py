"""Device time per round under the program's `aggregate` scope: the cross-
station aggregation (`fed_mean` / `fed_sum`): across chips the all-reduces,
on one chip the reduction over the stacked stations. From the device trace,
by the scope path of each operation (`harness.Run.scope_ms`), mean over the
chips; reads nothing where no operation carries the scope."""


def read(run):
    return run.scope_ms("aggregate")
