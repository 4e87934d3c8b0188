"""Share of the traced window in which the chip had nothing to run
while the engine's thread was under ``serving.drain`` (the host late to
notice a block's end) or ``serving.replay`` (the block's tokens replayed
into the host's bookkeeping with nothing behind it on the device, as
``_drain_all`` before an admission does). None for a program without
``serving.replay``."""

from benchmark.reduce import idle


def read(run):
    return idle.span_share(run, idle.DRAIN, needs=("serving.replay",))
