"""Mean over the window's decode blocks of the share of the slots whose
per-slot state the block reads and writes (the live ones: an idle
slot's state is not fetched), as the engine reckons it from its slot
table at every ``serving.dispatch``. A count of the program's own."""

from benchmark.reduce import ssm_hybrid


def read(run):
    return ssm_hybrid.dispatch_counter(run, "state_live_share")
