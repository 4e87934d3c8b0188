"""Whose time the idle device is: every nanosecond of the traced window
in which a chip ran no operation, put down to the program's own span.

On ``program.planes_of(run)``, so on the profiler's clock alone: device
events and the program's ``edl.*`` annotations lie in one file and need
no offset. A chip's idle gaps are the complement of the union of its
``XLA Ops`` events inside the traced window. A gap inside an event of
the chip's ``XLA Modules`` line is **in a program** (the device was
between two operations of one program: the turn of a loop, a wait for a
DMA) and no host span could have shortened it. Every other gap is split
by overlap among the INNERMOST ``edl.*`` annotation open at each instant
on the host line that drives the device, the one that holds
``edl.serving.step`` / ``edl.train.step``; time under no annotation is
the caller's loop (``CALLER``). Of each piece the **off-CPU** part is
its length times the share of its own wall time that the ring span that
owns it was off a CPU (joined by ``seq``: ``dur_s`` less ``cpu_s``, both
taken less the span's children, which the ring names by ``parent``, and
less what the machine's CPU clock cannot resolve), for spans that are
not waits on the device by design (``WAITS``): the thread was runnable
there, and was not running. Seconds are averaged over the chips traced,
as ``trace.summarize`` does.

The traced window ends where the driving line's last annotation ends
(``benchmark/run.py: Tracer`` reads its stopwatch right after the last
step returns) and is ``window_s`` long. What the device ran outside it
(``trace.summarize`` counts every operation of the trace in ``busy_s``)
is reported as ``busy_outside_s`` and left out: under 0.4 ms in the
traced runs of PR 39, since a block still in flight when the profiler
stops leaves no events.

``python3 -m benchmark.reduce.idle .bench_trace/<cell> [window_s]``
prints the table by span name for a by-hand look.
"""

from __future__ import annotations

import bisect
import functools
import sys
import time
from typing import Dict, List, Optional, Tuple

from benchmark.reduce import program, trace

STEP_ANNOTATIONS = ("edl.serving.step", "edl.train.step")
CALLER = "(caller)"
# spans in which the thread is blocked on the device by design: being
# off the CPU there says nothing
WAITS = ("serving.drain", "serving.prefill", "train.host_block",
         "reshard.device_transfer")
ADMIT = ("serving.admit", "serving.prefill", "serving.queue")
DISPATCH = ("serving.account", "serving.dispatch")
DRAIN = ("serving.drain", "serving.replay")

Interval = Tuple[int, int]
Owner = Tuple[int, int, str, int]  # start ns, end ns, span name, seq


def driving_line(planes: Dict) -> List[program.Event]:
    """The annotations of the host line that holds the most step
    annotations; empty where no line holds one."""
    best: List[program.Event] = []
    most = 0
    for name, lines in planes.items():
        if name.startswith(trace.DEVICE_PREFIX):
            continue
        for events in lines.values():
            steps = sum(1 for ev in events if ev[0] in STEP_ANNOTATIONS)
            if steps > most:
                best, most = events, steps
    return best


def innermost(events: List[program.Event]) -> List[Owner]:
    """The line cut into pieces that do not overlap, each owned by the
    innermost annotation open there (a parent owns what its children
    leave), sorted by start."""
    out: List[Owner] = []
    stack: List[List] = []  # [end, name, seq, where its next piece starts]

    def close(upto: int) -> None:
        while stack and stack[-1][0] <= upto:
            end, name, seq, at = stack.pop()
            if end > at:
                out.append((at, end, name, seq))
            if stack:
                stack[-1][3] = max(stack[-1][3], end)

    for name, s, e, stats in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(s)
        if stack:
            e = min(e, stack[-1][0])  # a child ends with its parent
            if s > stack[-1][3]:
                out.append((stack[-1][3], s, stack[-1][1], stack[-1][2]))
            stack[-1][3] = max(stack[-1][3], s)
        stack.append([e, name[len(program.ANNOTATION_PREFIX):],
                      int(stats.get("seq", 0)), s])
    close(1 << 62)
    return sorted(out)


def clip(intervals: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def complement(merged: List[Interval], lo: int, hi: int) -> List[Interval]:
    """What of [lo, hi] the merged, sorted intervals leave."""
    out, at = [], lo
    for s, e in clip(merged, lo, hi):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def overlaps(gap: Interval, pieces: List[Interval], starts: List[int]):
    """(index, start, end) of what the gap shares with each of the
    sorted, disjoint pieces (``starts``: their starts, for the search)."""
    i = max(bisect.bisect_right(starts, gap[0]) - 1, 0)
    while i < len(pieces) and pieces[i][0] < gap[1]:
        s, e = max(pieces[i][0], gap[0]), min(pieces[i][1], gap[1])
        if e > s:
            yield i, s, e
        i += 1


def own_times(spans: Dict[int, object]) -> Dict[int, List[float]]:
    """{seq: [wall seconds, CPU seconds]} of every ring span that has
    ``cpu_s``, each less the span's children (which the ring names by
    ``parent``): a span's self time."""
    own: Dict[int, List[float]] = {
        s.seq: [s.dur_s, s.cpu_s] for s in spans.values()
        if getattr(s, "cpu_s", None) is not None}
    for s in spans.values():
        mine = own.get(getattr(s, "parent", 0))
        if mine is not None and s.seq in own:
            mine[0] -= s.dur_s
            mine[1] -= s.cpu_s
    return own


def own_off_cpu(spans: Dict[int, object],
                slack_s: float = 0.0) -> Dict[int, float]:
    """{seq: share of the span's own wall time its thread was off a
    CPU}, for every ring span that has ``cpu_s`` and is no wait on the
    device: own wall time less own CPU time less ``slack_s``, what the
    CPU figure may be short by on this machine (:func:`cpu_slack`),
    over own wall time. A span shorter than the slack says nothing and
    reads 0."""
    return {seq: min(max(dur - cpu - slack_s, 0.0) / dur, 1.0)
            for seq, (dur, cpu) in own_times(spans).items()
            if dur > 0 and spans[seq].name not in WAITS}


@functools.lru_cache(maxsize=1)
def cpu_slack() -> float:
    """Seconds a span's ``cpu_s`` may be short of the truth here: the
    step of ``time.thread_time`` on this machine, found by spinning
    until it moves (nanoseconds on most kernels, a 10 ms tick on the
    builder's TPU host), and twice the age up to which the tracer
    carries a reading forward."""
    t0 = time.thread_time()
    while True:
        t1 = time.thread_time()
        if t1 != t0:
            break
    try:
        from edl_tpu.utils import tracing
    except ImportError:
        return t1 - t0
    return t1 - t0 + 2 * getattr(tracing, "CPU_READ_EVERY_S", 0.0)


def split(planes: Dict, window_s: Optional[float] = None,
          spans: Optional[Dict[int, object]] = None,
          slack_s: float = 0.0) -> Optional[Dict]:
    """Seconds of the traced window, averaged over the chips traced:
    ``busy_s``, ``in_program_s``, ``by_span`` {span name or CALLER:
    idle seconds it owns}, ``offcpu`` {span name: the off-CPU part of
    those} (empty where the ring's spans carry no ``cpu_s``), and
    ``busy_outside_s``. None without a device's operations or without a
    driving line."""
    ops = program.device_lines(planes, trace.OPS_LINE)
    line = driving_line(planes)
    if not ops or not line:
        return None
    hi = max(ev[2] for ev in line)
    lo = (hi - int(round(window_s * 1e9)) if window_s
          else min(ev[1] for ev in line))
    owners = innermost(line)
    pieces = [(s, e) for s, e, _, _ in owners]
    starts = [s for s, _ in pieces]
    off_cpu = own_off_cpu(spans or {}, slack_s)
    modules = {name: lines.get(trace.MODULES_LINE, [])
               for name, lines in planes.items()
               if name.startswith(trace.DEVICE_PREFIX)
               and lines.get(trace.OPS_LINE)}
    chips = len(modules)
    busy = outside = in_program = 0
    by_span: Dict[str, int] = {}
    offcpu: Dict[str, float] = {}
    for name in sorted(modules):
        merged = trace.union(
            [(s, e) for _, s, e, _ in planes[name][trace.OPS_LINE]])
        inside = sum(e - s for s, e in clip(merged, lo, hi))
        busy += inside
        outside += sum(e - s for s, e in merged) - inside
        running = trace.union([(s, e) for _, s, e, _ in modules[name]])
        run_starts = [s for s, _ in running]
        for gap in complement(merged, lo, hi):
            # what of the gap lies inside a running program is nobody's
            host, at = [], gap[0]
            for _, s, e in overlaps(gap, running, run_starts):
                in_program += e - s
                if s > at:
                    host.append((at, s))
                at = e
            if gap[1] > at:
                host.append((at, gap[1]))
            for part in host:
                left = part[1] - part[0]
                for i, s, e in overlaps(part, pieces, starts):
                    _, _, owner, seq = owners[i]
                    by_span[owner] = by_span.get(owner, 0) + e - s
                    left -= e - s
                    if seq in off_cpu:
                        offcpu[owner] = offcpu.get(owner, 0.0) + (
                            e - s) * off_cpu[seq]
                if left:
                    by_span[CALLER] = by_span.get(CALLER, 0) + left
    return {
        "window_s": (hi - lo) / 1e9,
        "chips_traced": chips,
        "busy_s": busy / chips / 1e9,
        "busy_outside_s": outside / chips / 1e9,
        "in_program_s": in_program / chips / 1e9,
        "by_span": {k: v / chips / 1e9 for k, v in by_span.items()},
        "offcpu": {k: v / chips / 1e9 for k, v in offcpu.items()},
        "has_cpu": bool(off_cpu),
        "annotated": {ev[0][len(program.ANNOTATION_PREFIX):] for ev in line},
    }


# -- what the metric readers share ------------------------------------------

def of_run(run: Dict) -> Optional[Dict]:
    """:func:`split` of the run's trace with this process's ring; None
    without a trace (``--trace 0``), without a device plane (a CPU
    rehearsal) or without a step annotation. Kept in the run for its
    next reader."""
    if "idle" not in run:
        planes = program.planes_of(run) if run.get("trace") else None
        run["idle"] = planes and split(
            planes, run["trace"]["window_s"], program.ring()[0],
            cpu_slack())
    return run["idle"] or None


def _share(run: Dict, needs: Tuple[str, ...], seconds) -> Optional[float]:
    """Percent of the traced window that ``seconds(found)`` is; None
    where there is nothing to split, or where the driving line holds no
    annotation of a span in ``needs`` (the program of a parent that has
    no such span)."""
    found = of_run(run)
    if not found or not set(needs) <= found["annotated"]:
        return None
    value = seconds(found)
    return None if value is None else 100.0 * value / found["window_s"]


def in_program_share(run: Dict) -> Optional[float]:
    return _share(run, (), lambda found: found["in_program_s"])


def span_share(run: Dict, names: Tuple[str, ...],
               needs: Tuple[str, ...] = ()) -> Optional[float]:
    """The device idle under the named spans."""
    return _share(run, needs, lambda found: sum(
        found["by_span"].get(n, 0.0) for n in names))


def host_share(run: Dict, but: Tuple[str, ...] = (),
               needs: Tuple[str, ...] = ()) -> Optional[float]:
    """The device idle under any span or none, less the spans in
    ``but``."""
    return _share(run, needs, lambda found: sum(
        v for k, v in found["by_span"].items() if k not in but))


def offcpu_share(run: Dict) -> Optional[float]:
    """The device idle and the owning span's thread off its CPU; None
    where the spans carry no ``cpu_s``."""
    return _share(run, (), lambda found: sum(
        found["offcpu"].values()) if found["has_cpu"] else None)


def describe(found: Dict) -> str:
    """The table by span name: idle seconds, share of the window, and
    the off-CPU part."""
    window = found["window_s"]
    rows = [f"window {window:.6f}s on {found['chips_traced']} chip(s); "
            f"busy inside {found['busy_s']:.6f}s, after or before it "
            f"{found['busy_outside_s']:.6f}s; idle "
            f"{100 * (1 - found['busy_s'] / window):.4f}%"]
    table = [("(in a program)", found["in_program_s"], None)] + [
        (k, v, found["offcpu"].get(k) if found["has_cpu"] else None)
        for k, v in sorted(found["by_span"].items(), key=lambda kv: -kv[1])]
    for name, seconds, off in table:
        rows.append(
            f"  {name:<28}{seconds:>11.6f}s {100 * seconds / window:>8.4f}%"
            + ("" if off is None else f"  off CPU {off:.6f}s"))
    total = found["in_program_s"] + sum(found["by_span"].values())
    rows.append(f"  {'sum':<28}{total:>11.6f}s {100 * total / window:>8.4f}%")
    return "\n".join(rows)


if __name__ == "__main__":
    path = trace.find_xplane(sys.argv[1]) or sys.argv[1]
    seconds = float(sys.argv[2]) if len(sys.argv) > 2 else None
    table = split(program.load(path), seconds)
    print(describe(table) if table else
          "no device operations, or no edl.serving.step / edl.train.step")
