"""Host time per round inside the program's own `device.launch` spans: the
call into the compiled program and nothing else, summed over the window's
dispatches, over the window's rounds. Beside the idle gap before each round
it says whether the gap is the program handing its buffers over or lies
below the launch.

The source is what the program records itself (`program_counter`): the
spans of `vantage6_tpu.runtime.tracing.TRACER`, read in this process after
the run. The window's `engine.call` spans are the last as many as the window
had dispatches (the program is not called after the window); their
`device.launch` children are found by `parent_id`. Read over the whole
window, not only over the traced dispatches. Reads nothing where the program
records no such span (an older program, the tracer off) or the tracer's
buffer no longer holds the whole window."""


def window_launches(run):
    """The `device.launch` spans under the window's `engine.call` spans, or
    None where some dispatch of the window has none in the buffer."""
    from vantage6_tpu.runtime.tracing import TRACER

    spans = TRACER.drain()
    n = len(run.window.dispatch_s)
    calls = [s for s in spans if s["name"] == "engine.call"][-n:]
    if len(calls) < n:
        return None
    ids = {s["span_id"] for s in calls}
    launches = [s for s in spans
                if s["name"] == "device.launch" and s["parent_id"] in ids]
    if {s["parent_id"] for s in launches} != ids:
        return None
    return launches


def read(run):
    launches = window_launches(run)
    if launches is None:
        return None
    return 1e3 * sum(s["dur"] for s in launches) / run.window.rounds
