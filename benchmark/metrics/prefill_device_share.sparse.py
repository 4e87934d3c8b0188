"""Share of the traced window in which the device ran a prefill program
(``edl_serve_prefill_<bucket>`` on the ``XLA Modules`` line): a prompt
of 2k-24k tokens walked in pieces inside one program, while every one
of the 32 running slots waits."""

from benchmark.reduce import program


def read(run):
    planes = program.planes_of(run)
    if not planes or not run["trace"] or not program.chips_traced(planes):
        return None
    times = program.module_times(planes)
    if program.BLOCK_PROGRAM not in times:
        return None
    ns = sum(sum(v) for k, v in times.items()
             if k.startswith(program.PREFILL_PROGRAMS))
    return 100.0 * ns / 1e9 / program.chips_traced(planes) \
        / run["trace"]["window_s"]
