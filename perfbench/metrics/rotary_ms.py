"""Device time per round under the program's `rotary` scope: rotate-half
RoPE of q and k, forward, recomputed and backward. From the device trace
(`harness.Run.scope_ms`), mean over the chips; reads nothing where no
operation carries the scope (a block without rotary positions)."""


def read(run):
    return run.scope_ms("rotary")
