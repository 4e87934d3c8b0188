"""Share of the traced window that is device self time of operations
under the ``attn`` scope of the model code: projections, RoPE, the
cache write and the attention itself, in the decode block and in the
prefill programs alike."""

from benchmark.reduce import program


def read(run):
    return program.scope_share(run, ("attn",))
