"""Share of the traced window that is device self time under the
``ssm`` scope of the model code: a state-space layer's mixing, its
projections, convolution (``ssm.conv``), recurrence (``ssm.step`` in a
decode step: every live slot's state read, updated, queried and
written; ``ssm.chunk`` in a prefill) and gated norm
(``ssm.gate_norm``). The mechanism's own share, beside
``attn_time_share.hybrid``."""

from benchmark.reduce import ssm_hybrid


def read(run):
    return ssm_hybrid.scope_share(run, ssm_hybrid.LAYER)
