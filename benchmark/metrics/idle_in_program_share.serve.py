"""Share of the traced window in which the chip ran no operation and
was inside a running program (an ``XLA Modules`` event): the turns of a
loop, the waits between two operations of one block or prefill. No
change on the host moves it."""

from benchmark.reduce import idle


def read(run):
    return idle.in_program_share(run)
