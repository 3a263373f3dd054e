"""Device time per round in which a collective is under way (all-reduce,
reduce-scatter, all-gather, collective-permute), on the slowest chip; from
the device trace. Reads nothing where no collective ran."""


def read(run):
    if run.trace is None or run.trace.collective_s <= 0:
        return None
    return 1e3 * run.trace.collective_s / run.traced_rounds
