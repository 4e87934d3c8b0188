"""Mean over the window's decode blocks of the share of the routed
experts HELD here that at least one live slot's token chose, mean over
the expert layers, as the block program counts it on the device and the
engine drains it onto ``serving.dispatch``. The weights of an expert
nobody chose are not read."""

from benchmark.reduce import mla_dsa_moe


def read(run):
    return mla_dsa_moe.dispatch_counter(run, "experts_hit_share")
