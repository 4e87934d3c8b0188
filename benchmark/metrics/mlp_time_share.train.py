"""Share of the traced window that is device self time of operations
under the ``mlp`` scope of the model code (forward, recomputation and
backward of the SwiGLU block)."""

from benchmark.reduce import program


def read(run):
    return program.scope_share(run, ("mlp",))
