"""The indexer's products as a share of the chip's peak over the device time
of the whole `indexer` scope: its scores of every visible pair (forward and
recomputed) and its projections (forward, recomputed and the weights'
gradient), float32 at HIGHEST, each counted as the bfloat16 passes it costs
the MXU (`indexer_flops` of the configuration's reference module), over the
bf16 peak. In percent; reads nothing without a trace, without the scope or
where the configuration has no indexer."""


def read(run):
    module = run.cell.reference_module()
    if not hasattr(module, "indexer_flops"):
        return None
    return run.scope_share_of_peak(
        "indexer", module.indexer_flops(run.cell.config, run.cell.traffic),
        "bf16_flops")
