"""Share of the traced window in which a chip had nothing to run and
was inside no program: under ``train.data``, ``train.dispatch``,
``train.step``'s self time, any ``reshard*`` span, ``train.host_block``
(the host late to notice the step's end) or the caller's loop."""

from benchmark.reduce import idle


def read(run):
    return idle.host_share(run)
