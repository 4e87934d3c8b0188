"""Share of the traced window that is device self time under the
``moe`` scope of the model code: an expert layer's norm, router
(``moe.router``), the routed experts held here (``moe.experts``) and the
shared expert (``moe.shared``), in both programs."""

from benchmark.reduce import mla_dsa_moe


def read(run):
    return mla_dsa_moe.scope_share(run, "moe")
