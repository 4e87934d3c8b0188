"""Mean of ``trace_s + lower_s`` over the window's ``reshard.recompile``
spans (the warm cycle left out, as ``reshard_recompile_s`` does): the
part of the first step on a new mesh that is Python re-tracing the step
and lowering it, from JAX's own compile events while that step ran."""

import statistics

from benchmark.reduce import program


def read(run):
    spans = program.reshard_recompiles(run)
    if not spans:
        return None
    return statistics.mean(
        s.attrs["trace_s"] + s.attrs["lower_s"] for s in spans)
