"""From a profiler trace (``.xplane.pb``) to busy and idle time, the
operations that took most of the device's time, and the longest idle
gaps named by what the host was doing.

Read with ``jax.profiler.ProfileData`` and nothing else. A device plane
is one whose name starts with ``/device:TPU:``; its operations are the
events of the line named ``XLA Ops``. Host spans are the events whose
name starts with ``bench.``: the benchmark's own ``TraceAnnotation``s
around its calls into each layer.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "bench."
MODULES_LINE = "XLA Modules"
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")
MOSAIC = "tpu_custom_call"  # in the HLO text of a Pallas kernel's event

Event = Tuple[str, int, int]  # name, start ns, end ns


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """{plane name: {line name: [(name, start ns, end ns)]}}."""
    from jax.profiler import ProfileData

    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in ProfileData.from_file(path).planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                start = int(ev.start_ns)
                events.append((ev.name, start, start + int(ev.duration_ns)))
    return out


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted intervals."""
    merged: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def self_times(events: List[Event]) -> Dict[str, int]:
    """Nanoseconds per operation name with the time of operations
    nested inside it taken out (a ``while`` holds its body's events)."""
    out: Dict[str, int] = {}
    stack: List[List] = []  # [name, end, self ns]

    def close(upto: int) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0) + own

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    close(1 << 62)
    return out


def host_spans(planes: Dict) -> List[Event]:
    return sorted(
        (ev for name, lines in planes.items()
         if not name.startswith(DEVICE_PREFIX)
         for events in lines.values() for ev in events
         if ev[0].startswith(HOST_SPAN_PREFIX)),
        key=lambda ev: ev[1])


def name_gap(gap: Tuple[int, int], spans: List[Event]) -> str:
    """The host span that covers most of an idle gap."""
    best, best_ns = "(no bench span open)", 0
    for name, s, e in spans:
        over = min(e, gap[1]) - max(s, gap[0])
        if over > best_ns:
            best, best_ns = name, over
    return best


def short(name: str) -> str:
    """An event is named by its whole HLO instruction; its result name
    is what tells operations apart."""
    return name.split(" = ", 1)[0].lstrip("%")


def is_collective(name: str) -> bool:
    return any(c in short(name) for c in COLLECTIVES)


def summarize(planes: Dict, window_s: float, top: int = 10) -> Dict:
    """busy_s (averaged over the chips that ran anything), the top
    operations by self time, the longest idle gaps, and per-name self
    seconds (averaged over chips) for the metric readers."""
    spans = host_spans(planes)
    busy_ns: List[int] = []
    op_ns: Dict[str, int] = {}
    gaps: List[Tuple[int, Tuple[int, int]]] = []
    for name, lines in sorted(planes.items()):
        if not name.startswith(DEVICE_PREFIX) or not lines.get(OPS_LINE):
            continue
        events = lines[OPS_LINE]
        merged = union([(s, e) for _, s, e in events])
        busy_ns.append(sum(e - s for s, e in merged))
        for op, ns in self_times(events).items():
            op_ns[op] = op_ns.get(op, 0) + ns
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            gaps.append((s1 - e0, (e0, s1)))
    chips = max(len(busy_ns), 1)
    modules = max((len(lines.get(MODULES_LINE, []))
                   for name, lines in planes.items()
                   if name.startswith(DEVICE_PREFIX)), default=0)
    by_span: Dict[str, int] = {}
    for ns, gap in sorted(gaps, reverse=True)[:2000]:
        label = name_gap(gap, spans)
        by_span[label] = by_span.get(label, 0) + ns
    ops = sorted(op_ns.items(), key=lambda kv: -kv[1])
    return {
        "busy_s": sum(busy_ns) / chips / 1e9,
        "window_s": window_s,
        "chips_traced": len(busy_ns),
        "modules_run": modules,
        "mosaic_s": sum(v for k, v in ops if MOSAIC in k) / chips / 1e9,
        "collective_s": sum(
            v for k, v in ops if is_collective(k)) / chips / 1e9,
        "device_ops": [[short(k), v / chips / 1e9] for k, v in ops[:top]],
        "idle_gaps": [[k, v / chips / 1e9] for k, v in sorted(
            by_span.items(), key=lambda kv: -kv[1])[:top]],
        "longest_gap_s": max((g[0] for g in gaps), default=0) / 1e9,
    }


def describe(planes: Dict, limit: int = 12) -> str:
    """A by-hand look: planes, lines, event counts and the first names."""
    rows = []
    for pname, lines in sorted(planes.items()):
        rows.append(f"plane {pname!r}")
        for lname, events in lines.items():
            names: Dict[str, int] = {}
            for n, s, e in events:
                names[n] = names.get(n, 0) + (e - s)
            top = sorted(names.items(), key=lambda kv: -kv[1])
            picked = top[:limit] + [
                kv for kv in top[limit:]
                if "custom" in kv[0] or is_collective(kv[0])][:limit]
            rows.append(f"  line {lname!r}: {len(events)} events; "
                        + "; ".join(f"{n[:160]}={ns / 1e6:.2f}ms"
                                    for n, ns in picked))
    return "\n".join(rows)
