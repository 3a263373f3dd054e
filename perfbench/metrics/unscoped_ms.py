"""Device time per traced round of the operations that carry no scope path
at all, the by-scope table's `no scope` row (`trace.NO_SCOPE`): what the
compiler inserts (copies between memory spaces, layout copies, broadcasts
with no metadata) and anything the program runs outside every
`jax.named_scope`. Mean over the chips. Reads nothing where the trace holds
no mark (its rounds are then not the window's) or no such row."""
from perfbench import trace


def read(run):
    if run.traced_round_s() is None:
        return None
    row = run.trace.scopes.get(trace.NO_SCOPE)
    return None if row is None else 1e3 * row["s"] / run.traced_rounds
