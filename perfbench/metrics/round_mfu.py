"""The whole round as a share of the chips' bf16 peak: the operations one
round's forward and backward passes require, from the configuration's
shapes (no recomputation counted), over the traced round time, the chips
and the peak. In percent."""


def read(run):
    return run.share_of_peak(run.flops_per_round, "bf16_flops")
