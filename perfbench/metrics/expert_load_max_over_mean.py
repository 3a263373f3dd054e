"""How unevenly the router loaded the experts held on this chip over the
window: the assignments of the fullest held expert over those of the mean
one, over all expert layers and the window's rounds (1 = even). The source
is what the program records itself (`program_counter`): the last
`experts.load` span of `vantage6_tpu.runtime.tracing.TRACER`, which the
entry has the engine record after the window, outside what is timed, from
counts the rounds left on the device. Reads nothing where the program
records no such span (a program without expert layers, the tracer off)."""


def window_load(run):
    """The attributes of the window's `experts.load` span, or None."""
    from vantage6_tpu.runtime.tracing import TRACER

    loads = [s for s in TRACER.drain() if s["name"] == "experts.load"]
    if not loads or loads[-1]["attrs"]["rounds"] != run.window.rounds:
        return None
    return loads[-1]["attrs"]


def read(run):
    load = window_load(run)
    return None if load is None else load["max_over_mean"]
