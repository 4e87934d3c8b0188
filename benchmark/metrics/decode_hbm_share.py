"""Bytes a decode step needs (every matmul weight once + keys and values
of the tokens resident, averaged over the window's steps) over (the
step period x the chip's HBM peak). The step period is the median gap
between a request's tokens: at horizon 1 every step gives each slot one,
and the engine's dispatch-to-drain block time spans two steps of its
double buffer. Needed bytes, not the padded program's: it falls when a
program wastes more."""

import statistics

from benchmark.reduce import peaks


def read(run):
    gaps = run["spans"].get("itl_s")
    if not gaps or run["device"]["platform"] != "tpu":
        return None
    _, bw = peaks.peak(run["device"]["kind"])
    need = run["cell"].family.needed.decode_step_bytes(
        run["config"], run["counters"]["resident_tokens_mean"])
    return 100.0 * need / (statistics.median(gaps) * bw)
