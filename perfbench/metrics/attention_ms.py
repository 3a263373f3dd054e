"""Device time per round under the program's `attention` scope: the attention
layers, forward, recomputed and backward: the score and value products and
the softmax between them, without the qkv and output projections, which the
block opens no scope around. From the device trace, by the scope path of
each operation (`harness.Run.scope_ms`), mean over the chips; reads nothing
where no operation carries the scope."""


def read(run):
    return run.scope_ms("attention")
