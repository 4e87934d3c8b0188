"""Mean of ``load_s`` over the window's ``reshard.recompile`` spans: the
executable of the first step on a new mesh, compiled by XLA or found in
the persistent cache and loaded onto the new mesh's chips."""

import statistics

from benchmark.reduce import program


def read(run):
    spans = program.reshard_recompiles(run)
    if not spans:
        return None
    return statistics.mean(s.attrs["load_s"] for s in spans)
