"""Mean over the window's decode blocks of the share of an expert
layer's routed experts that at least one live slot's token chose, mean
over the expert layers, as the block program counts it on the device
and the engine drains it onto ``serving.dispatch``. The weights of an
expert nobody chose are not read."""

from benchmark.reduce import mla_moe


def read(run):
    return mla_moe.dispatch_counter(run, "experts_hit_share")
