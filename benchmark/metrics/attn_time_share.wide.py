"""Share of the traced window that is device self time under the
``attn`` scope of the model code: the projections, RoPE, the latent
cache's write, the absorbed attention of a decode step
(``attn.latent_absorb``) and the expanded attention of a prefill
(``attn.latent_expand`` and the flash kernel)."""

from benchmark.reduce import program


def read(run):
    return program.scope_share(run, ("attn",))
