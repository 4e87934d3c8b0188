"""Weights of the routed experts the decode steps traced had to read
(the held experts hit as the traced dispatches' spans count them, all
expert layers, a step) over (the device time
under the ``moe.experts`` scope in the decode block programs x the
chip's HBM peak). Bound: memory. The time holds the kernel's combine
weights and hit list beside ``edl_expert_mlp``; the bytes are the hit
experts' weights alone."""

from benchmark.reduce import mla_dsa_moe, peaks


def read(run):
    hit = mla_dsa_moe.traced(run, "experts_hit_share")
    steps = mla_dsa_moe.steps_traced(run)
    seconds = mla_dsa_moe.block_seconds(run, mla_dsa_moe.EXPERTS)
    if hit is None or not steps or not seconds \
            or not mla_dsa_moe.on_the_chip(run):
        return None
    _, bw = peaks.peak(run["device"]["kind"])
    need = steps * run["cell"].family.needed.expert_bytes(run["config"], hit)
    return 100.0 * need / (seconds * bw)
