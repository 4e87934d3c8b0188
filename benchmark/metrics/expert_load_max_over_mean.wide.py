"""Mean over the window's decode blocks of the busiest expert's rows
over the mean expert's (mean over the expert layers), from the block
program's own count: 1.0 is a perfectly even router."""

from benchmark.reduce import mla_moe


def read(run):
    return mla_moe.dispatch_counter(run, "expert_load_max_over_mean")
