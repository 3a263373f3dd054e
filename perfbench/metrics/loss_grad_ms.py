"""Device time per round under the program's `loss_grad` scope: a local step's
loss and its gradient on the gathered rows. From the device trace, by the
scope path of each operation (`harness.Run.scope_ms`), mean over the chips;
reads nothing where no operation carries the scope."""


def read(run):
    return run.scope_ms("loss_grad")
