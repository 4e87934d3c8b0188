"""Seconds the device is busy for each token a serving cell's window
serves: the steadiness ISSUE 29 asks of ``kanana2.decode-wide``, which
a stall of the host does not move. One run of the cell as
``benchmark.run`` makes it (``--trace 0``), with the profiler on around
the WHOLE window (the harness traces its last seconds only, and which
prompts' prefills fall into three seconds follows the run). Busy is the
time of the programs on each chip's ``XLA Modules`` line, read with
``jax.profiler.ProfileData``; the block the engine has queued when the
window closes is in it and its tokens are not (one step of ~1000).
``serve_tokens_per_s`` of such a run has the profiler's cost in it.

    python scripts/exp_device_s_per_token.py --workload \
        kanana2.decode-wide --seed 7 --seconds 40
"""

import json
import os
import shutil
import sys

import jax

from benchmark import harness, run
from benchmark.reduce import trace

OUT = os.path.join(harness.TRACE_DIR, "whole_window")
KINDS = []


def whole(window):
    def wrapped(self, seconds):
        shutil.rmtree(OUT, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 0
        jax.profiler.start_trace(OUT, profiler_options=options)
        try:
            window(self, seconds)
        finally:
            jax.profiler.stop_trace()
        KINDS.append(self)
    return wrapped


def main() -> int:
    from jax.profiler import ProfileData

    kind = harness.load_kind("serve").Kind
    kind.window = whole(kind.window)
    rc = run.main(sys.argv[1:])
    busy, programs = [], {}
    for plane in ProfileData.from_file(trace.find_xplane(OUT)).planes:
        if not plane.name.startswith(trace.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name != trace.MODULES_LINE:
                continue
            events = [(ev.name, int(ev.duration_ns)) for ev in line.events]
            if events:
                busy.append(sum(ns for _, ns in events) / 1e9)
            for name, ns in events:
                name = name.split("(")[0]
                n, s = programs.get(name, (0, 0.0))
                programs[name] = (n + 1, s + ns / 1e9)
    c = KINDS[0].counters
    busy_s = sum(busy) / max(len(busy), 1)
    print("DEVICE " + json.dumps({
        "busy_s": busy_s, "tokens": c["tokens"], "window_s": c["window_s"],
        "engine_steps": c["engine_steps"],
        "busy_s_per_token": busy_s / c["tokens"],
        "busy_share_of_window": busy_s / c["window_s"],
        "programs": {k: [n, round(s, 6)] for k, (n, s) in
                     sorted(programs.items())}}))
    shutil.rmtree(OUT, ignore_errors=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
