"""Mean over the window's decode blocks of the positions a step attends
over the positions live (``min(live, index_topk)`` a slot, summed over
the live slots, over their tokens), as the engine reckons it from its
slot table at every ``serving.dispatch``: what the selection saves of a
dense read of the latent rows."""

from benchmark.reduce import mla_dsa_moe


def read(run):
    return mla_dsa_moe.dispatch_counter(run, "kv_selected_share")
