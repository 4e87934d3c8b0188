"""Mean of ``ReshardEvent.recompile_s`` over the window's reshards: the
first step on the new mesh from its dispatch to its loss, the re-trace
and the load from the compile cache included."""

import statistics


def read(run):
    xs = run["spans"].get("recompile_s")
    return statistics.mean(xs) if xs else None
