"""The off-CPU overlay of ``idle_host_share.open``: the part of it in
which the owning span's thread was not on a CPU. None for a program
whose spans carry no ``cpu_s``."""

from benchmark.reduce import idle


def read(run):
    return idle.offcpu_share(run)
