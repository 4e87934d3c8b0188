"""Bytes a decode step needs (attention, router, shared and dense
weights and the head once; the weights of the experts the step's tokens
hit; the latent rows of the tokens resident, averaged over the window's
steps) over (the step period x the chip's HBM peak). The step period
is the device's: the median ``edl_serve_block`` of the trace over the
steps a block runs (``horizon``, from the dispatch spans). Needed
bytes, not the program's: an expert nobody chose and the cache's
padding are not in it."""

from benchmark.reduce import mla_moe, peaks, program


def read(run):
    block_ms = program.block_device_ms(run)
    hit = mla_moe.dispatch_counter(run, "experts_hit_share")
    if not block_ms or hit is None or run["device"]["platform"] != "tpu":
        return None
    period = block_ms * 1e-3 / mla_moe.horizon(run)
    _, bw = peaks.peak(run["device"]["kind"])
    need = run["cell"].family.needed.decode_step_bytes(
        run["config"], run["counters"]["resident_tokens_mean"], hit)
    return 100.0 * need / (period * bw)
