"""Share of the traced window that is device self time under the
``moe`` scope of the model code: an expert layer's norm, router
(``moe.router``), routed experts (``moe.experts``: sort, grouped
matmuls, combine) and shared experts (``moe.shared``), in the decode
block and the prefill programs alike."""

from benchmark.reduce import mla_moe


def read(run):
    return mla_moe.scope_share(run, "moe")
