"""Device time per round under the program's `norms` scope, which every norm
of the transformer opens (before and after each half of the block, after
each walk of the stack, before the head), wherever it nests: inside `mlp`,
`experts`, `attn_out`, `loop` or `lm_head_loss` as well as alone. The
statistics and what XLA does not fuse into the product that follows. From
the device trace (`harness.Run.scope_ms`), mean over the chips; reads
nothing where no operation carries the scope."""


def read(run):
    return run.scope_ms("norms")
