"""Share of the traced window in which a chip ran no operation inside
a running ``edl_train_step`` (averaged over the chips traced): waits
between the operations of one step, collectives' among them."""

from benchmark.reduce import idle


def read(run):
    return idle.in_program_share(run)
