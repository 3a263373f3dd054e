"""Device time per round under the program's `server_update` scope: the
server's optimizer step on the aggregated update. From the device trace, by
the scope path of each operation (`harness.Run.scope_ms`), mean over the
chips; reads nothing where no operation carries the scope."""


def read(run):
    return run.scope_ms("server_update")
