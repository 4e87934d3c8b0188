"""Share of the traced window in which the chip had nothing to run
while the engine's thread was under ``serving.admit`` or its children
``serving.prefill`` and ``serving.queue``: an admission's host work
around its prefill."""

from benchmark.reduce import idle


def read(run):
    return idle.span_share(run, idle.ADMIT)
