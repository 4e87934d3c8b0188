"""State the decode blocks traced had to move (each live slot's state
read once and written once, a block's ``horizon`` steps) over (the
device time under the ``attn.retention_step`` scope in those blocks x
the chip's HBM peak). Bound: memory. The time is the scope's, whatever
implements the step (the kernel ``edl_retention_step`` and the
normaliser's few operations beside it); the bytes are the packed
state's."""

from benchmark.reduce import mla_moe, peaks, program, retention


def read(run):
    live = retention.live_slots(run)
    blocks = retention.blocks_traced(run)
    if live is None or not blocks or run["device"]["platform"] != "tpu":
        return None
    timed = retention.scope_time(run, retention.STEP, program.BLOCK_PROGRAM)
    if not timed:
        return None
    _, bw = peaks.peak(run["device"]["kind"])
    need = blocks * mla_moe.horizon(run) * 2 * live \
        * run["cell"].family.needed.state_bytes_per_slot(run["config"])
    return 100.0 * need / (timed[0] * bw)
