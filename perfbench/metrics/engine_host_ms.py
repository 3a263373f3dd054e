"""Host time per round inside the program's own `engine.call` spans and
outside their `device.launch` children: the engine's own host work around
the launch (placement, telemetry, history), summed over the window's
dispatches, over the window's rounds. Beside `launch_host_ms` it splits the
host's part of the idle gap before each round.

The source is what the program records itself (`program_counter`), found as
`launch_host_ms.window_launches` finds it: the window's last `engine.call`
spans and their `device.launch` children. Reads nothing where that reads
nothing."""
from perfbench import cells

_launch_host_ms = cells.load_module(
    cells.HERE / "metrics" / "launch_host_ms.py")


def read(run):
    from vantage6_tpu.runtime.tracing import TRACER

    launches = _launch_host_ms.window_launches(run)
    if launches is None:
        return None
    ids = {s["parent_id"] for s in launches}
    calls = [s for s in TRACER.drain()
             if s["name"] == "engine.call" and s["span_id"] in ids]
    own = sum(s["dur"] for s in calls) - sum(s["dur"] for s in launches)
    return 1e3 * own / run.window.rounds
