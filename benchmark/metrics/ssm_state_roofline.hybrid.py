"""State the decode blocks traced had to move (each live slot's ``S``
of all state-space layers read once and written once, a block's
``horizon`` steps) over (the device time under the ``ssm.step`` scope
in those blocks x the chip's HBM peak). Bound: memory. The time is the
scope's, whatever implements the step (the kernel ``edl_ssm_step`` and
the few operations beside it that make its operands); the bytes are
``S``'s alone, float32."""

from benchmark.reduce import mla_moe, peaks, program, ssm_hybrid


def read(run):
    live = ssm_hybrid.live_slots(run)
    blocks = ssm_hybrid.blocks_traced(run)
    if live is None or not blocks or run["device"]["platform"] != "tpu":
        return None
    timed = ssm_hybrid.scope_time(
        run, ssm_hybrid.STEP, program.BLOCK_PROGRAM)
    if not timed:
        return None
    _, bw = peaks.peak(run["device"]["kind"])
    need = blocks * mla_moe.horizon(run) * 2 * live \
        * run["cell"].family.needed.ssm_state_bytes_per_slot(run["config"])
    return 100.0 * need / (timed[0] * bw)
