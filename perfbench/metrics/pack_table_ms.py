"""Device time per round under the program's `pack_table` scope: joining
features and labels into rows that carry their label, once per dispatch,
spread over the dispatch's rounds; the relayout of the joined table is a
copy that carries no path and is not in it. From the device trace, by the
scope path of each operation (`harness.Run.scope_ms`), mean over the chips;
reads nothing where no operation carries the scope."""


def read(run):
    return run.scope_ms("pack_table")
