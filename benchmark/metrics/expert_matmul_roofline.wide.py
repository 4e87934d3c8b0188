"""Weights of the routed experts the decode steps traced had to read
(the experts hit, all expert layers, a step) over (the device time
under the ``moe.experts`` scope in the decode block program x the
chip's HBM peak). Bound: memory. The time holds the sort, the gathers
and the combine beside the grouped matmuls; the bytes are the hit
experts' weights alone."""

from benchmark.reduce import mla_moe, peaks


def read(run):
    hit = mla_moe.dispatch_counter(run, "experts_hit_share")
    if hit is None or run["device"]["platform"] != "tpu":
        return None
    seconds = mla_moe.self_seconds(
        run, lambda ev: "moe.experts" in mla_moe.scope_parts(ev),
        programs="edl_serve_block")
    steps = mla_moe.steps_traced(run)
    if not seconds or not steps:
        return None
    _, bw = peaks.peak(run["device"]["kind"])
    need = steps * run["cell"].family.needed.expert_bytes(run["config"], hit)
    return 100.0 * need / (seconds * bw)
