"""What the program itself put into a profiler trace, read with the
events' stats: device time per program (the ``XLA Modules`` line, by the
name of the jitted function), device self time per model phase (the
``jax.named_scope`` in an operation's ``op_name``), and the program's
own host spans (``tracing.span`` opens a ``TraceAnnotation`` named
``edl.<span>`` that carries the span's ``seq``).

``reduce/trace.py`` reads names and times only; this file reads the
same ``.xplane.pb`` again, finding it by the cell's name, so nothing
that was there is edited. ``jax.profiler.ProfileData`` gives an event's
own stats; a device operation's ``op_name`` is a stat of its *event
metadata* (``tf_op``), which ``ProfileData`` does not show, so that one
stat is read from the file's protobuf wire format directly
(:func:`metadata_stat`, no schema needed beyond the field numbers of
``XPlane``, ``XEventMetadata``, ``XStatMetadata`` and ``XStat``). Every reader gets ``None`` where there is
nothing to read: no trace (``--trace 0``), no device plane (a CPU
rehearsal), or a program that has no such names or spans (the parent
of the PR that added them).
"""

from __future__ import annotations

import functools
import os
import re
import statistics
import sys
from typing import Dict, List, Optional, Tuple

from benchmark import harness
from benchmark.reduce import trace

ANNOTATION_PREFIX = "edl."
BLOCK_PROGRAM = "edl_serve_block"
PREFILL_PROGRAMS = "edl_serve_prefill_"
SCOPES = ("embed", "attn", "mlp", "head", "loss", "optimizer")
OP_NAME_STAT = "tf_op"  # of the event metadata: "jit(f)/.../mlp/dot:"
_SCOPE_PART = re.compile(r"(?:[a-z]+\()*([a-z_]+)\)*")

Event = Tuple[str, int, int, Dict]  # name, start ns, end ns, stats


def load(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """{plane: {line: [(name, start ns, end ns, stats)]}} of the lines
    read here: a device's ``XLA Modules`` and ``XLA Ops``, and on the
    host planes the ``edl.*`` annotations alone."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        op_names = metadata_stat(f.read(), OP_NAME_STAT)
    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(trace.DEVICE_PREFIX)
        named = op_names.get(plane.name, {})
        for line in plane.lines:
            if device and line.name not in (trace.OPS_LINE,
                                            trace.MODULES_LINE):
                continue
            events = []
            for ev in line.events:
                if not device and not ev.name.startswith(ANNOTATION_PREFIX):
                    continue
                start = int(ev.start_ns)
                stats = dict(ev.stats)
                if ev.name in named:
                    stats[OP_NAME_STAT] = named[ev.name]
                events.append((ev.name, start, start + int(ev.duration_ns),
                               stats))
            if events:
                out.setdefault(plane.name, {})[line.name] = events
    return out


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if not byte & 0x80:
            return value, i


def _fields(buf: bytes):
    """(field number, value) of one protobuf message: an int for a
    varint, the bytes for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire}")
        yield key >> 3, value


def metadata_stat(xspace: bytes, stat: str) -> Dict[str, Dict[str, str]]:
    """{plane name: {event name: value}} of one string stat of the
    planes' *event metadata*. Field numbers: XSpace.planes 1;
    XPlane.name 2, .event_metadata 4, .stat_metadata 5 (maps: key 1,
    value 2); XEventMetadata.name 2, .stats 5; XStatMetadata.id 1,
    .name 2; XStat.metadata_id 1, .str_value 5, .ref_value 7 (a string
    kept as another stat metadata's name)."""
    out: Dict[str, Dict[str, str]] = {}
    for field, plane in _fields(xspace):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for field, value in _fields(plane):
            if field == 2:
                name = value.decode()
            elif field == 4:
                events.append(dict(_fields(value)).get(2, b""))
            elif field == 5:
                meta = dict(_fields(dict(_fields(value)).get(2, b"")))
                stat_names[meta.get(1, 0)] = meta.get(2, b"").decode()
        wanted = {k for k, v in stat_names.items() if v == stat}
        if not wanted:
            continue
        for event in events:
            ev_name, found = "", None
            for field, value in _fields(event):
                if field == 2:
                    ev_name = value.decode()
                elif field == 5:
                    st = dict(_fields(value))
                    if st.get(1) in wanted:
                        found = (st[5].decode() if 5 in st
                                 else stat_names.get(st.get(7), ""))
            if found:
                out.setdefault(name, {})[ev_name] = found
    return out


@functools.lru_cache(maxsize=2)
def _load_cached(path: str, _mtime: float):
    return load(path)


def planes_of(run: Dict) -> Optional[Dict]:
    """The run's trace, found by the cell's name; None without one."""
    path = trace.find_xplane(
        os.path.join(harness.TRACE_DIR, run["cell"].name))
    if path is None:
        return None
    return _load_cached(path, os.path.getmtime(path))


def device_lines(planes: Dict, line: str) -> List[List[Event]]:
    """One list of events per chip that has the line."""
    return [lines[line] for name, lines in sorted(planes.items())
            if name.startswith(trace.DEVICE_PREFIX) and lines.get(line)]


# -- programs ---------------------------------------------------------------


def program_name(module_event: str) -> str:
    """``jit_edl_serve_block(123)`` -> ``edl_serve_block``."""
    name = module_event.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def module_times(planes: Dict) -> Dict[str, List[int]]:
    """Nanoseconds of every run of every program, by program name, over
    all chips (a sharded program runs once on each)."""
    out: Dict[str, List[int]] = {}
    for events in device_lines(planes, trace.MODULES_LINE):
        for name, s, e, _ in events:
            out.setdefault(program_name(name), []).append(e - s)
    return out


def chips_traced(planes: Dict) -> int:
    return len(device_lines(planes, trace.OPS_LINE))


# -- phases -----------------------------------------------------------------


def scope_of(path: str) -> Optional[str]:
    """The outermost ``named_scope`` of an op_name that is one of the
    model's phases. A scope is a path component, bare (``mlp``) or
    inside the marks of a transformation (``transpose(jvp(head))``)."""
    for part in path.rstrip(":").split("/"):
        found = _SCOPE_PART.fullmatch(part)
        if found and found.group(1) in SCOPES:
            return found.group(1)
    return None


def scope_self_times(planes: Dict) -> Dict[str, float]:
    """Seconds of device self time per phase, averaged over chips: an
    operation's time less the operations nested in it (a ``while`` holds
    its body's), counted under the scope in its own op_name. Time of
    operations under no phase is under ``""``."""
    lines = device_lines(planes, trace.OPS_LINE)
    out: Dict[str, float] = {}
    for events in lines:
        stack: List[List] = []  # [scope, end, self ns]

        def close(upto: int) -> None:
            while stack and stack[-1][1] <= upto:
                scope, _, own = stack.pop()
                out[scope] = out.get(scope, 0.0) + own

        for ev in sorted(events, key=lambda ev: (ev[1], -ev[2])):
            _, s, e, _ = ev
            close(s)
            if stack:
                stack[-1][2] -= min(e, stack[-1][1]) - s
            stack.append(
                [scope_of(ev[3].get(OP_NAME_STAT, "")) or "", e, e - s])
        close(1 << 62)
    return {k: v / len(lines) / 1e9 for k, v in out.items()}


# -- host spans -------------------------------------------------------------


def annotations(planes: Dict) -> List[Event]:
    """The program's spans as the profiler saw them, by start."""
    return sorted(
        (ev for name, lines in planes.items()
         if not name.startswith(trace.DEVICE_PREFIX)
         for events in lines.values() for ev in events
         if "seq" in ev[3]),
        key=lambda ev: ev[1])


def ring() -> Tuple[Dict[int, object], float]:
    """({seq: span}, timebase) of this process's tracer ring: a span's
    start on ``time.perf_counter`` is ``timebase + span.start_s``.
    Empty where the program has no tracer."""
    try:
        from edl_tpu.utils import tracing
    except ImportError:
        return {}, 0.0
    tracer = tracing.tracer()
    return {s.seq: s for s in tracer.spans()}, tracer.t0


def join(planes: Dict, spans: Dict[int, object], t0: float) -> Optional[Dict]:
    """Annotations and ring spans of one process, joined by ``seq``.
    ``session_seq``: the lowest seq in the trace, so every span with a
    lower one was opened before the profiler session began.
    ``offset_ns``: what to add to the ring's clock (``(t0 + start_s) *
    1e9``) to get the profiler's nanoseconds: the median over the spans
    found in both (None if there is none). None where the trace holds
    no annotation."""
    notes = annotations(planes)
    if not notes:
        return None
    offsets = []
    for name, start, end, stats in notes:
        span = spans.get(int(stats["seq"]))
        if span is None or ANNOTATION_PREFIX + span.name != name:
            continue
        # the annotation of a span recorded after the fact marks when
        # it was recorded, not when it began: only a span that the
        # annotation timed too gives the offset
        if abs((end - start) - span.dur_s * 1e9) <= 20_000:
            offsets.append(start - (t0 + span.start_s) * 1e9)
    return {
        "session_seq": min(int(ev[3]["seq"]) for ev in notes),
        "offset_ns": statistics.median(offsets) if offsets else None,
        "joined": len(offsets),
        "annotations": len(notes),
    }


# -- what the metric readers share ------------------------------------------


def block_device_ms(run: Dict) -> Optional[float]:
    planes = planes_of(run)
    times = module_times(planes).get(BLOCK_PROGRAM) if planes else None
    return statistics.median(times) / 1e6 if times else None


def scope_share(run: Dict, scopes: Tuple[str, ...]) -> Optional[float]:
    """Percent of the traced window under the given phases; None where
    no operation of the trace is under any phase at all."""
    planes = planes_of(run)
    if not planes or not run["trace"] or not chips_traced(planes):
        return None
    by_scope = scope_self_times(planes)
    if not any(k for k in by_scope):
        return None
    return 100.0 * sum(by_scope.get(k, 0.0) for k in scopes) \
        / run["trace"]["window_s"]


def reshard_recompiles(run: Dict) -> List:
    """The ``reshard.recompile`` spans of the window's reshards (as many
    as the kind counted, the newest), if they say what the first step
    was made of."""
    count = len(run["spans"].get("recompile_s") or ())
    spans, _ = ring()
    mine = sorted((s for s in spans.values()
                   if s.name == "reshard.recompile"), key=lambda s: s.seq)
    mine = mine[-count:] if count else []
    return [s for s in mine if "trace_s" in s.attrs]


def describe(planes: Dict, limit: int = 8) -> str:
    """A by-hand look: lines, stat keys, programs, phases, host spans."""
    rows = []
    for pname, lines in sorted(planes.items()):
        rows.append(f"plane {pname!r}")
        for lname, events in lines.items():
            keys = sorted({k for ev in events for k in ev[3]})
            rows.append(f"  line {lname!r}: {len(events)} events; stats "
                        f"{keys}")
            for ev in events[:limit]:
                stats = {k: (v[:120] if isinstance(v, str) else v)
                         for k, v in ev[3].items()}
                rows.append(f"    {ev[0][:100]!r} {ev[2] - ev[1]}ns {stats}")
    rows.append("programs: " + "; ".join(
        f"{k} x{len(v)} median {statistics.median(v) / 1e6:.3f}ms"
        for k, v in sorted(module_times(planes).items())))
    if chips_traced(planes):
        rows.append("phases (self s): " + "; ".join(
            f"{k or '(none)'}={v:.4f}"
            for k, v in sorted(scope_self_times(planes).items())))
    names: Dict[str, int] = {}
    for ev in annotations(planes):
        names[ev[0]] = names.get(ev[0], 0) + 1
    rows.append(f"host spans: {names}")
    return "\n".join(rows)


if __name__ == "__main__":
    found = trace.find_xplane(sys.argv[1]) or sys.argv[1]
    print(describe(load(found)))
