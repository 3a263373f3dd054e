"""Device time per round under the program's `attn_out` scope: the block's
output projection, the norm after it where the configuration has one (it
nests as `attn_out/norms`) and the residual add, which XLA fuses into the
product's output, forward, recomputed and backward. From the device trace
(`harness.Run.scope_ms`), mean over the chips; reads nothing where no
operation carries the scope."""


def read(run):
    return run.scope_ms("attn_out")
