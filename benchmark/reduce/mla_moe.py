"""What the cell ``kanana2.decode-wide``'s readers share: device self
time by the scopes ``models/deepseek_v3.py`` names (``moe``,
``moe.experts``, ``attn.latent_absorb``: dotted, which
``reduce/program.py``'s phase list does not hold), told apart by the
program the operation ran in, and the counters the block program
drains onto its ``serving.dispatch`` spans (``horizon`` among their
attributes: the steps of a block). Every function gives ``None`` where
there is nothing to read: no trace, a CPU rehearsal, or a program
without the scopes or the counters."""

from __future__ import annotations

import statistics
from typing import Callable, Dict, List, Optional, Tuple

from benchmark.reduce import program, serving, trace


# XLA's own kernels for ``jax.lax.ragged_dot`` (the grouped matmuls and
# the pass that lays out their groups). Their events carry the
# expansion's op_name, not the scope the call stood under; nothing but
# an expert layer's ``moe.experts`` makes them.
GROUPED_MATMUL = "ragged-dot"
EXPERTS = ("moe", "moe.experts")


def scope_parts(event) -> List[str]:
    """The path of scopes an operation stands under, from its op_name;
    a grouped-matmul kernel is put back under ``moe/moe.experts``."""
    parts = event[3].get(program.OP_NAME_STAT, "").rstrip(":").split("/")
    if GROUPED_MATMUL in event[0] and not set(EXPERTS) & set(parts):
        parts = parts + list(EXPERTS)
    return parts


def self_time(run: Dict, wanted: Callable, programs: str = ""
              ) -> Optional[Tuple[float, float]]:
    """(seconds of device self time, how many operations), averaged
    over chips, of operations for which ``wanted(event)`` holds and
    that ran inside a program whose name starts with ``programs``. Self
    time: an operation's time less the operations nested in it."""
    planes = program.planes_of(run)
    if not planes or not run["trace"] or not program.chips_traced(planes):
        return None
    total = found = 0
    lines = program.device_lines(planes, trace.OPS_LINE)
    for events, modules in zip(
            lines, program.device_lines(planes, trace.MODULES_LINE)):
        inside = [(s, e) for name, s, e, _ in modules
                  if program.program_name(name).startswith(programs)]
        stack: List[List] = []  # [counts, end, self ns]

        def close(upto: int) -> int:
            done = 0
            while stack and stack[-1][1] <= upto:
                counts, _, own = stack.pop()
                done += own if counts else 0
            return done

        for ev in sorted(events, key=lambda ev: (ev[1], -ev[2])):
            _, s, e, _ = ev
            total += close(s)
            if stack:
                stack[-1][2] -= min(e, stack[-1][1]) - s
            counts = wanted(ev) and any(a <= s and e <= b for a, b in inside)
            found += bool(counts)
            stack.append([counts, e, e - s])
        total += close(1 << 62)
    return total / len(lines) / 1e9, found / len(lines)


def self_seconds(run: Dict, wanted: Callable, programs: str = ""
                 ) -> Optional[float]:
    timed = self_time(run, wanted, programs)
    return timed[0] if timed else None


def latent_kernel(run: Dict) -> Optional[Tuple[float, float]]:
    """(seconds, calls) of ``edl_decode_attn_latent``, the Mosaic
    kernel under ``attn.latent_absorb``, in the decode block programs
    that lie whole in the trace: one call a layer a decode step."""
    return self_time(
        run, lambda ev: trace.MOSAIC in ev[0]
        and "attn.latent_absorb" in scope_parts(ev),
        programs=program.BLOCK_PROGRAM)


def scope_share(run: Dict, scope: str) -> Optional[float]:
    """Percent of the traced window that is self time under ``scope``
    (a path component of the operation's name), in every program."""
    seconds = self_seconds(run, lambda ev: scope in scope_parts(ev))
    if not seconds:
        return None  # no trace, or a program that has no such scope
    return 100.0 * seconds / run["trace"]["window_s"]


def dispatch_counter(run: Dict, name: str) -> Optional[float]:
    """Mean of a counter over the window's ``serving.dispatch`` spans
    (those that carry a request of the window). A count of the
    program's own, so a rehearsal reports it too."""
    spans, _ = program.ring()
    values = [
        float(s.attrs[name]) for s in spans.values()
        if s.name == serving.DISPATCH and name in s.attrs
        and any(not str(r).startswith(serving.WARM)
                for r in s.attrs.get("rids", ()))
    ]
    return statistics.fmean(values) if values else None


def horizon(run: Dict) -> int:
    """Decode steps a block program runs, as its dispatches say."""
    return round(dispatch_counter(run, "horizon") or 1)


def steps_traced(run: Dict) -> Optional[float]:
    """Decode steps of the block programs that lie whole in the trace:
    the latent kernel runs once a layer a step."""
    timed = latent_kernel(run)
    return timed[1] / run["config"]["num_hidden_layers"] if timed else None
