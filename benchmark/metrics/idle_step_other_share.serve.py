"""Share of the traced window in which the chip had nothing to run
and the engine's thread was in none of the admit, dispatch and drain
spans: ``serving.step``'s self time, ``serving.recover``, and the
caller's loop between two steps. None for a program whose step is not
tiled by ``serving.account`` and ``serving.replay`` (its self time would
hold their work)."""

from benchmark.reduce import idle


def read(run):
    return idle.host_share(
        run, but=idle.ADMIT + idle.DISPATCH + idle.DRAIN,
        needs=("serving.account", "serving.replay"))
