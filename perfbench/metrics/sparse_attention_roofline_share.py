"""The sparse attention's products over the pairs each query keeps, as a
share of the chip's bf16 peak over the device time of the `attention` scope:
``4 * Hq * head_dim`` operations a kept pair and pass, forward, recomputed
under remat and backward (`sparse_attention_flops` of the configuration's
reference module). The walk runs every visible tile with the selection as a
mask, so the share also says how far that walk is from the pairs the model
needs. In percent; reads nothing without a trace, without the scope or where
the configuration has no sparse attention."""


def read(run):
    module = run.cell.reference_module()
    if not hasattr(module, "sparse_attention_flops"):
        return None
    return run.scope_share_of_peak(
        "attention",
        module.sparse_attention_flops(run.cell.config, run.cell.traffic),
        "bf16_flops")
