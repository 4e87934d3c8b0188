"""Bytes a decode step needs (attention, indexer, router, shared and
dense weights and the head once; the weights of the held experts hit;
the index keys of the tokens resident and the latent rows of the
positions chosen, ``min(live, index_topk)`` a slot, all as the traced
dispatches' spans give them) over (the step
period x the chip's HBM peak): the whole step's share of its roofline.
The step period is the device's: the median ``edl_serve_block`` of the
trace over the steps a block runs (``horizon``). Needed bytes, not the
program's: a position not chosen and the cache's padding are not in
it."""

from benchmark.reduce import mla_dsa_moe, peaks, program


def read(run):
    block_ms = program.block_device_ms(run)
    hit = mla_dsa_moe.traced(run, "experts_hit_share")
    live = mla_dsa_moe.traced(run, "live_slots")
    tokens = mla_dsa_moe.traced(run, "kv_live_tokens")
    if not block_ms or hit is None or live is None or tokens is None \
            or not mla_dsa_moe.on_the_chip(run):
        return None
    period = block_ms * 1e-3 / mla_dsa_moe.horizon(run)
    _, bw = peaks.peak(run["device"]["kind"])
    need = run["cell"].family.needed.decode_step_bytes(
        run["config"], live, tokens, hit)
    return 100.0 * need / (period * bw)
