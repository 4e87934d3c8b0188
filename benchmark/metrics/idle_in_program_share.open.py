"""``idle_in_program_share.serve`` in the open-loop cell: idle inside
a running program, as a share of the traced window."""

from benchmark.reduce import idle


def read(run):
    return idle.in_program_share(run)
