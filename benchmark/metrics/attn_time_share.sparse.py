"""Share of the traced window that is device self time under the
``attn`` scope of the model code: the projections, RoPE, the cache's
writes, the indexer (``attn.index``), the selection (``attn.select``)
and the attention over the chosen (``attn.sparse``), in the decode
block and the prefill programs alike."""

from benchmark.reduce import program


def read(run):
    return program.scope_share(run, ("attn",))
