"""Of the four host rows (admit, dispatch, drain, step-other), the
share of the traced window in which the owning span's thread was not on
a CPU (the span's own wall time less its own ``cpu_s`` less what the
machine's CPU clock cannot resolve; waits on the device left out): an
overlay on those rows, not a part beside them. None for a
program whose spans carry no ``cpu_s``."""

from benchmark.reduce import idle


def read(run):
    return idle.offcpu_share(run)
