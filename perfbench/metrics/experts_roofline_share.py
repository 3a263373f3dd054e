"""The grouped products of the expert layers as a share of the chip's bf16
peak over the device time of the whole `experts` scope: the operations the
program runs there for the assignments the router really made (the window's
`experts.load` record: per round, summed over layers and held experts),
forward, the recomputed forward and backward counted as the program runs
them (`experts_flops` of the configuration's reference module), over the
scope's device time per traced round. Compute-bound by its products; the
scope also holds the sort, the gathers and the masks over the whole
worst-case row buffer, which is what keeps the share low. In percent; reads
nothing without a trace, without the scope or without the record."""
from perfbench import cells

_load = cells.load_module(
    cells.HERE / "metrics" / "expert_load_max_over_mean.py")


def read(run):
    load = _load.window_load(run)
    if load is None:
        return None
    assignments = sum(map(sum, load["assignments_per_round"]))
    flops = run.cell.reference_module().experts_flops(
        run.cell.config, run.cell.traffic, assignments)
    return run.scope_share_of_peak("experts", flops, "bf16_flops")
