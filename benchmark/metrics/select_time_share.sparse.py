"""Share of the traced window that is device self time under
``attn.select``: choosing the ``index_topk`` positions of each query
from its scores (``top_k`` in a decode step, the bisection and the mask
in a prefill), in both programs."""

from benchmark.reduce import mla_dsa_moe


def read(run):
    return mla_dsa_moe.scope_share(run, mla_dsa_moe.SELECT)
