"""Operations the recurrence's own products take for the positions the
traced prefill programs ran (``needed.ssd_scan_flops``: every position
into the state once and out of it once, all state-space layers) over
(the device time under the ``ssm.chunk`` scope in the prefill programs
x the chip's bf16 peak). Bound: compute. The chunked form does more
arithmetic than the recurrence (the masked products inside a chunk) and
its decays are elementwise work, so this reads well under 100."""

from benchmark.reduce import peaks, program, ssm_hybrid


def read(run):
    tokens = ssm_hybrid.prefilled_tokens_traced(run)
    if not tokens or run["device"]["platform"] != "tpu":
        return None
    timed = ssm_hybrid.scope_time(
        run, ssm_hybrid.CHUNK, program.PREFILL_PROGRAMS)
    if not timed:
        return None
    flops, _ = peaks.peak(run["device"]["kind"])
    need = run["cell"].family.needed.ssd_scan_flops(run["config"], tokens)
    return 100.0 * need / (timed[0] * flops)
