"""Device time per round under the program's `experts` scope: the expert
layers' share held on this chip: the norm before them, the sort of the
assignments by expert, the rows gathered into sorted order, the three
grouped products over the experts held, the rows back in token order and
their weighted sum, forward, recomputed and backward. From the device
trace, by the scope path of each operation (`harness.Run.scope_ms`), mean
over the chips; reads nothing where no operation carries the scope."""


def read(run):
    return run.scope_ms("experts")
