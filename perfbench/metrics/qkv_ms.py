"""Device time per round under the program's `qkv` scope: the block's qkv
product and its split into heads, forward, recomputed and backward, with
what XLA fuses into the product (the pre-attention norm's scale and the
weights' cast where they feed it). From the device trace, by the scope path
of each operation (`harness.Run.scope_ms`), mean over the chips; reads
nothing where no operation carries the scope."""


def read(run):
    return run.scope_ms("qkv")
