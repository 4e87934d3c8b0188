"""Share of the traced window that is device self time under
``attn.index``: the indexer's projections and the scores of each query
against the index keys of the earlier positions, in both programs."""

from benchmark.reduce import mla_dsa_moe


def read(run):
    return mla_dsa_moe.scope_share(run, mla_dsa_moe.INDEX)
