"""The step a token would leave the looped stack at, in expectation, over
the window: ``sum_r r * p_r`` of the mean exit distribution (1 = every token
after the first walk, `loops` = after the last). The source is what the
program records itself (`program_counter`): the last `exits.distribution`
span of `vantage6_tpu.runtime.tracing.TRACER`, which the entry has the
engine record after the window, outside what is timed, from sums the rounds
left on the device. Reads nothing where the program records no such span (a
stack walked once, the tracer off) or where the span's rounds are not the
window's."""


def window_exits(run):
    """The attributes of the window's `exits.distribution` span, or None."""
    from vantage6_tpu.runtime.tracing import TRACER

    spans = [s for s in TRACER.drain() if s["name"] == "exits.distribution"]
    if not spans or spans[-1]["attrs"]["rounds"] != run.window.rounds:
        return None
    return spans[-1]["attrs"]


def read(run):
    exits = window_exits(run)
    return None if exits is None else exits["expected_exit_step"]
