"""Share of the traced window in which the chip had nothing to run
and was inside no program: under any of the engine's spans or in the
caller's loop (in the open-loop cell that includes waiting for the next
arrival)."""

from benchmark.reduce import idle


def read(run):
    return idle.host_share(run)
