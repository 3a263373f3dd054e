"""The share of the sparse attention walk's visible tiles that held at least
one kept (query, key) pair over the window: the count each round leaves on
the device per layer, over the visible tiles of the same sequences (1 = no
tile could have been skipped). The source is what the program records itself
(`program_counter`): the last `sparse.tiles` span of
`vantage6_tpu.runtime.tracing.TRACER`, which the entry has the engine record
after the window, outside what is timed. Reads nothing where the program
records no such span for the window's rounds."""


def read(run):
    from vantage6_tpu.runtime.tracing import TRACER

    spans = [s for s in TRACER.drain() if s["name"] == "sparse.tiles"]
    if not spans or spans[-1]["attrs"]["rounds"] != run.window.rounds:
        return None
    return spans[-1]["attrs"]["selected_tile_share"]
