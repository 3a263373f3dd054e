"""The part of the collective time per round during which no compute ran on
that chip; from the device trace."""


def read(run):
    if run.trace is None or run.trace.collective_s <= 0:
        return None
    return 1e3 * run.trace.collective_exposed_s / run.traced_rounds
