"""Mean over the window's decode blocks of the busiest HELD expert's
rows over the mean held expert's (mean over the expert layers), from
the block program's own count: 1.0 is a router that spreads a step's
choices evenly over the experts this chip holds. The seed draws the
router, so this and ``experts_hit_share.sparse`` are what a run's block
time differs by from another's."""

from benchmark.reduce import mla_dsa_moe


def read(run):
    return mla_dsa_moe.dispatch_counter(run, "expert_load_max_over_mean")
