"""Device time per round under the program's `router` scope: the routers of
the expert blocks, before attention: the float32 product over all the
deployment's experts, the softmax, the top-k choice and the renormalised
weights, forward, recomputed and backward. From the device trace, by the
scope path of each operation (`harness.Run.scope_ms`), mean over the chips;
reads nothing where no operation carries the scope (a program without
expert blocks)."""


def read(run):
    return run.scope_ms("router")
