"""Operations the state's products take for the positions the traced
prefill programs ran (``needed.retention_chunk_flops``: every key into
the state, every query head out of it) over (the device time under the
``attn.retention_chunk`` scope in the prefill programs x the chip's
bf16 peak). Bound: compute. The time holds the chunk's own masked
products and the feature maps beside the state's products."""

from benchmark.reduce import peaks, program, retention


def read(run):
    tokens = retention.prefilled_tokens_traced(run)
    if not tokens or run["device"]["platform"] != "tpu":
        return None
    timed = retention.scope_time(
        run, retention.CHUNK, program.PREFILL_PROGRAMS)
    if not timed:
        return None
    flops, _ = peaks.peak(run["device"]["kind"])
    need = run["cell"].family.needed.retention_chunk_flops(
        run["config"], tokens)
    return 100.0 * need / (timed[0] * flops)
