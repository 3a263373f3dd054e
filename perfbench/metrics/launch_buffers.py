"""Arrays handed to the compiled program per launch: the mean `n_buffers` of
the window's `device.launch` spans (array leaves of the dynamic arguments,
counted as arrays, not as per-chip shards). The count beside
`launch_host_ms`, at the same boundary; the same spans, found the same way,
over the whole window (`program_counter`: the program's own spans). Reads
nothing where that reads nothing."""
from perfbench import cells

_launch_host_ms = cells.load_module(
    cells.HERE / "metrics" / "launch_host_ms.py")


def read(run):
    launches = _launch_host_ms.window_launches(run)
    if launches is None:
        return None
    return sum(s["attrs"]["n_buffers"] for s in launches) / len(launches)
