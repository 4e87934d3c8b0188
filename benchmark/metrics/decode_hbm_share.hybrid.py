"""Bytes a decode step needs (every parameter once, the embedding once
as the head; each live slot's state read once and written once, live
slots averaged over the window's dispatches; the keys and values of the
tokens resident, averaged over the window's steps) over (the step
period x the chip's HBM peak): the whole step's share of its roofline.
The step period is the device's: the median ``edl_serve_block`` of the
trace over the steps a block runs (``horizon``, from the dispatch
spans). Needed bytes, not the program's."""

from benchmark.reduce import mla_moe, peaks, program, ssm_hybrid


def read(run):
    block_ms = program.block_device_ms(run)
    live = ssm_hybrid.live_slots(run)
    if not block_ms or live is None or run["device"]["platform"] != "tpu":
        return None
    period = block_ms * 1e-3 / mla_moe.horizon(run)
    _, bw = peaks.peak(run["device"]["kind"])
    need = run["cell"].family.needed.decode_step_bytes(
        run["config"], live, run["counters"].get("resident_tokens_mean", 0.0))
    return 100.0 * need / (period * bw)
