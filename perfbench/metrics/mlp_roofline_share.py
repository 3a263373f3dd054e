"""The products of the gated MLP as a share of the chip's bf16 peak over the
device time of the whole `mlp` scope: the operations the program runs there
(`glu_flops` of the configuration's reference module: three products a block
application, forward, the forward again under remat and two backward
products each), over the scope's device time per traced round. Compute-bound
by its products; the scope also holds the norms before and after and the
gate's activation. In percent; reads nothing without a trace or without the
scope."""


def read(run):
    flops = run.cell.reference_module().glu_flops(
        run.cell.config, run.cell.traffic)
    return run.scope_share_of_peak("mlp", flops, "bf16_flops")
