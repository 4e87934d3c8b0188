"""What the cell ``brumby14b.decode-state``'s readers share: device self
time under the scopes ``models/retention.py`` and ``ops/retention.py``
name (``attn.retention_step`` in the decode block, ``attn.retention_chunk``
in the prefill programs: dotted, which ``reduce/program.py``'s phase
list does not hold), the live slots the engine writes onto its
``serving.dispatch`` spans (``state_live_share``), and the prompts the
traced prefill programs took. Every function gives ``None`` where there
is nothing to read: no trace, a CPU rehearsal, or a program without the
scopes or the attribute."""

from __future__ import annotations

from typing import Dict, Optional, Tuple

from benchmark.reduce import mla_moe, program

STEP, CHUNK = "attn.retention_step", "attn.retention_chunk"
# the traced-window readers below share mla_moe's: self time of the
# operations a predicate picks, inside the programs a prefix names, and
# the mean of an attribute over the window's dispatch spans
dispatch_counter = mla_moe.dispatch_counter


def scope_time(run: Dict, scope: str, programs: str = ""
               ) -> Optional[Tuple[float, float]]:
    """(seconds of device self time, operations) under ``scope`` (a path
    component of an operation's name) in the programs whose name starts
    with ``programs``; None where the trace has no such operation."""
    timed = mla_moe.self_time(
        run, lambda ev: scope in mla_moe.scope_parts(ev), programs)
    return timed if timed and timed[0] else None


def scope_share(run: Dict, *scopes: str) -> Optional[float]:
    """Percent of the traced window that is self time under any of the
    scopes, in every program."""
    timed = mla_moe.self_time(
        run, lambda ev: bool(set(scopes) & set(mla_moe.scope_parts(ev))))
    if not timed or not timed[0]:
        return None
    return 100.0 * timed[0] / run["trace"]["window_s"]


def live_slots(run: Dict) -> Optional[float]:
    """Mean live slots of the window's decode blocks: the dispatch
    spans' ``state_live_share`` times the engine's slots."""
    share = dispatch_counter(run, "state_live_share")
    if share is None:
        return None
    return share * run["cell"].spec["engine"]["max_slots"]


def blocks_traced(run: Dict) -> Optional[int]:
    """Runs of the decode block program that the trace holds."""
    planes = program.planes_of(run)
    times = program.module_times(planes).get(program.BLOCK_PROGRAM) \
        if planes else None
    return len(times) if times else None


def prefilled_tokens_traced(run: Dict) -> Optional[float]:
    """Positions the traced prefill programs took, a program's bucket
    each (``edl_serve_prefill_<bucket>``), averaged over chips: what
    the chunk scope's time was spent on. The bucket, not the prompt:
    the program computes every row of it."""
    planes = program.planes_of(run)
    if not planes or not program.chips_traced(planes):
        return None
    total = sum(
        int(name[len(program.PREFILL_PROGRAMS):]) * len(times)
        for name, times in program.module_times(planes).items()
        if name.startswith(program.PREFILL_PROGRAMS)
        and name[len(program.PREFILL_PROGRAMS):].isdigit())
    return total / program.chips_traced(planes) if total else None
