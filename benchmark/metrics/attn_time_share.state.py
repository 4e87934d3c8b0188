"""Share of the traced window that is device self time under the
``attn`` scope of the model code: the projections, the head norms,
RoPE, the gate, and the retention itself (``attn.retention_step`` in a
decode step, ``attn.retention_chunk`` in a prefill)."""

from benchmark.reduce import program


def read(run):
    return program.scope_share(run, ("attn",))
