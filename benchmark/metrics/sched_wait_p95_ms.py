"""95th percentile of the ``serving.queue`` spans (submit -> the
scheduler's pop, generator lateness not in it) of the window's requests
that were popped before the profiler session began: the queue as an
untraced run has it. Warm-up requests are left out."""

from benchmark import harness
from benchmark.reduce import program


def read(run):
    planes = program.planes_of(run)
    if not planes or not program.chips_traced(planes):
        return None  # no profiler session over a device: nothing to place
    spans, t0 = program.ring()
    joined = program.join(planes, spans, t0)
    if joined is None:
        return None
    waits = [s.dur_s for seq, s in spans.items()
             if s.name == "serving.queue" and seq < joined["session_seq"]
             and not str(s.attrs.get("rid", "")).startswith("warm-")]
    return harness.percentile(waits, 0.95) * 1e3 if waits else None
