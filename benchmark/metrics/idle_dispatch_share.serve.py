"""Share of the traced window in which the chip had nothing to run
while the engine's thread was under ``serving.account`` (the step's
gauges, and what a dispatch prepares before its program call) or
``serving.dispatch`` (the enqueue): what stands between a first-token
sync and the next block. None for a program without
``serving.account``."""

from benchmark.reduce import idle


def read(run):
    return idle.span_share(run, idle.DISPATCH, needs=("serving.account",))
