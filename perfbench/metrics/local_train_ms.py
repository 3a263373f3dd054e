"""Device time per round under the program's `local_train` scope: every
station's local training: the row gathers and the loss and its gradient
(FedAvg), or the embedding, the blocks and the head with their backward
passes (transformer). From the device trace, by the scope path of each
operation (`harness.Run.scope_ms`), mean over the chips; reads nothing where
no operation carries the scope."""


def read(run):
    return run.scope_ms("local_train")
