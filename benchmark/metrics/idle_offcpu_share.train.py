"""The off-CPU overlay of ``idle_host_share.train``: the part of it in
which the owning span's thread was not on a CPU (``train.host_block``
and ``reshard.device_transfer`` wait by design and are left out). None
for a program whose spans carry no ``cpu_s``."""

from benchmark.reduce import idle


def read(run):
    return idle.offcpu_share(run)
