"""Share of the traced window that is device self time under the
``attn`` scope of the model code: the four attention layers'
projections, the cache write and the attention itself
(``edl_decode_attn`` over the packed cache in a decode step,
``edl_flash_fwd`` in a prefill)."""

from benchmark.reduce import program


def read(run):
    return program.scope_share(run, ("attn",))
