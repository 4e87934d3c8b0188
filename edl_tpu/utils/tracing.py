"""Tracing — span recording, on the profiler's clock too.

The reference has no tracing at all (SURVEY §5: closest is log15 caller
stacks); here the north-star metric is rescale-stall seconds, so the
elastic runtime emits timed spans (reshard phases, checkpoint I/O,
recompiles) into a process-wide tracer that can be dumped as
chrome://tracing / Perfetto JSON.

One primitive, two places. Every span is written to the ring (on
``time.perf_counter``, with the opening thread's CPU seconds beside its
wall seconds and the ``seq`` of the span it opened under) and, in a
process that has imported JAX, is also a
``jax.profiler.TraceAnnotation`` named ``"edl." + name`` that carries
the span's ``seq``: under a profiler session (``jax.profiler.trace`` /
``start_trace``) the span sits in the ``.xplane.pb`` beside the
device's events, and outside one the annotation is a flag check. A span
found in both gives the offset between ``perf_counter`` and the
profiler's nanoseconds, and the lowest ``seq`` in a trace is the moment
its session began on the program's own clock (:func:`clock_offset_ns`).
JAX is never imported from here: a process without it (``edl`` CLI,
monitor, coordinator) records to the ring alone.

Usage:
    from edl_tpu.utils import tracing
    with tracing.span("reshard", job="ctr", to=8):
        ...
    tracing.dump("/tmp/trace.json")
"""

from __future__ import annotations

import contextlib
import itertools
import json
import os
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Deque, Dict, Iterator, List, Optional

from edl_tpu.utils.logging import kv_logger

log = kv_logger("tracing")


@dataclass
class Span:
    name: str
    start_s: float  # perf_counter-based, process-relative
    dur_s: float
    attrs: Dict[str, Any] = field(default_factory=dict)
    thread: int = 0
    # per-tracer monotonic id, taken when the span OPENS (survives ring
    # eviction); the profiler annotation of the span carries the same
    seq: int = 0
    # CPU seconds of the opening thread between open and close
    # (``time.thread_time``, exact to 2 x CPU_READ_EVERY_S and the
    # clock's own step): a span that waits on nothing and reads far
    # under ``dur_s`` was off its CPU. None for ``record()``, which
    # times nothing itself
    cpu_s: Optional[float] = None
    # seq of the span open on the same thread when this one opened, or
    # was recorded (of the same tracer); 0 for none
    parent: int = 0


# distributed-trace context hooks (installed by edl_tpu.obs.disttrace
# at import): ``enter()`` runs at span open and returns (state, attrs)
# — the attrs carry the span's trace/span/parent ids when a trace is
# active — and ``exit(state)`` restores the enclosing context. Kept as
# injected callables so this low-level module stays free of obs
# imports and the hook costs one None-check when tracing alone.
_ctx_enter = None
_ctx_exit = None


def set_span_context_hooks(enter, exit) -> None:
    global _ctx_enter, _ctx_exit
    _ctx_enter, _ctx_exit = enter, exit


# The thread's CPU clock is a system call, and not a cheap one
# everywhere: 0.3 us on the machine the tests run on, 6.0 us on the
# builder's TPU host (whose clock also steps in ticks of 10 ms), where
# two reads a span were 12 of a span's 17 us (PERF.md, PR 39). A reading
# younger than this is carried forward as if the thread had run since:
# what ``cpu_s`` is for, a thread held off its CPU for tens of
# milliseconds or seconds, is far above it.
CPU_READ_EVERY_S = 1e-3

ANNOTATION_PREFIX = "edl."
_profiler = None  # jax.profiler, once the process has imported jax


def _annotation(name: str, seq: int, step_num: Optional[int] = None):
    """The span as a profiler annotation, or None in a process that has
    not imported JAX (this module never does)."""
    global _profiler
    if _profiler is None:
        jax = sys.modules.get("jax")
        if jax is None or not hasattr(jax, "profiler"):
            return None
        _profiler = jax.profiler
    if step_num is None:
        return _profiler.TraceAnnotation(ANNOTATION_PREFIX + name, seq=seq)
    return _profiler.StepTraceAnnotation(
        ANNOTATION_PREFIX + name, step_num=step_num, seq=seq
    )


def _thread_cpu(local, now: float) -> float:
    """The calling thread's CPU seconds at ``now`` (perf_counter):
    read, or carried forward from a reading under CPU_READ_EVERY_S
    old."""
    at, cpu = local.cpu
    if 0.0 <= now - at < CPU_READ_EVERY_S:
        return cpu + (now - at)
    cpu = time.thread_time()
    local.cpu = (now, cpu)
    return cpu


def clock_offset_ns(span: "Span", annotation_start_ns: int,
                    t0: float) -> float:
    """What to add to ``perf_counter() * 1e9`` to get the profiler's
    nanoseconds, from one span found in both the ring (``span``, of a
    tracer whose timebase is ``t0``) and a trace (its annotation's
    start)."""
    return annotation_start_ns - (t0 + span.start_s) * 1e9


class Tracer:
    """Thread-safe in-memory span recorder.

    The buffer is a bounded RING: past ``max_spans`` the OLDEST span
    is evicted and ``dropped`` counts evictions — an always-on tracer
    must keep the spans closest to the incident, and the old
    drop-newest policy silently threw away exactly those (a reshard
    storm after a long soak recorded nothing). The eviction count
    surfaces in :meth:`summary` (the ``_tracer`` entry) and in the
    chrome-trace metadata, so a truncated trace is never mistaken for
    a complete one. ``add_listener`` subscribes observers (the obs
    bridge turns spans into scrapeable histograms) — listeners run
    outside the lock and must be cheap/non-throwing."""

    def __init__(self, max_spans: int = 100_000):
        self._lock = threading.Lock()
        self._spans: Deque[Span] = deque(maxlen=max_spans)
        # adjacent reads: t0_wall anchors start_s (perf_counter-
        # relative) on the wall clock, which is what lets span windows
        # from different processes merge onto one axis (obs/disttrace)
        self._t0 = time.perf_counter()
        self.t0_wall = time.time()
        self.t0 = self._t0  # public timebase (flight-recorder merge)
        # span ids, taken at open; never reset
        self._ids = itertools.count(1)
        # spans ever written to the ring, in the order they closed:
        # the /trace paging cursor (an id taken at open cannot be one,
        # a parent closes after children that were already shipped)
        self._closed = 0
        self.max_spans = max_spans
        self.enabled = True
        self.dropped = 0  # spans evicted after the ring filled
        self._listeners: List[Callable[[Span], None]] = []
        # per thread: ``seqs`` of the spans open on it, innermost last,
        # and ``cpu``, its last reading of the CPU clock (when, what)
        self._open = threading.local()

    def span(self, name: str, **attrs: Any):
        """Time the block. Yields the span's attribute dict, so what is
        only known at the end can still be recorded
        (``with span("serving.admit") as a: ...; a["admitted"] = n``).
        The stored span keeps that very dict: what the device reports
        after the span has closed can still be written into it (the
        serving engine's block counters, drained a step later)."""
        return self._span(name, None, attrs)

    def step_span(self, name: str, step_num: int, **attrs: Any):
        """:meth:`span` for one step of a loop: its annotation is a
        ``StepTraceAnnotation``, which the profiler's tools group the
        device's work by."""
        return self._span(name, step_num, attrs)

    @contextlib.contextmanager
    def _span(self, name: str, step_num: Optional[int],
              attrs: Dict[str, Any]) -> Iterator[Dict[str, Any]]:
        if not self.enabled:
            yield attrs
            return
        seq = next(self._ids)
        local = self._open
        try:
            stack = local.seqs
        except AttributeError:
            stack = local.seqs = []
            local.cpu = (-1.0, 0.0)
        parent = stack[-1] if stack else 0
        stack.append(seq)
        state = ctx_attrs = None
        if _ctx_enter is not None:
            # the span body runs inside its OWN child trace context:
            # nested spans parent here, and flight events emitted
            # within carry these ids (how /trace and /events agree)
            state, ctx_attrs = _ctx_enter()
        note = _annotation(name, seq, step_num)
        # the clocks are read back to back: perf_counter and the
        # annotation's are the pair that joins ring and trace
        # (clock_offset_ns)
        start = time.perf_counter()
        if note is not None:
            note.__enter__()
        cpu = _thread_cpu(local, start)
        try:
            yield attrs
        finally:
            if note is not None:
                note.__exit__(None, None, None)
            end = time.perf_counter()
            dur = end - start
            cpu = max(_thread_cpu(local, end) - cpu, 0.0)
            stack.pop()
            if _ctx_exit is not None:
                _ctx_exit(state)
            if ctx_attrs:
                attrs.update(ctx_attrs)
            self._store(Span(name, start - self._t0, dur, attrs,
                             threading.get_ident(), seq, cpu, parent))

    def record(self, name: str, start_s: float, dur_s: float,
               attrs: Optional[Dict[str, Any]] = None) -> None:
        """A span timed by the caller, after the fact. ``start_s`` is
        absolute time.perf_counter(); stored relative to tracer start so
        chrome-trace timestamps line up across threads. Its annotation
        is a mark at the moment of recording (the profiler takes no
        past events): it places the span's ``seq`` in the trace, and
        ``parent`` is the span that mark lies in."""
        if not self.enabled:
            return
        seq = next(self._ids)
        stack = getattr(self._open, "seqs", None)
        note = _annotation(name, seq)
        if note is not None:
            with note:
                pass
        self._store(Span(name, start_s - self._t0, dur_s,
                         dict(attrs or {}), threading.get_ident(), seq,
                         None, stack[-1] if stack else 0))

    def _store(self, span: Span) -> None:
        with self._lock:
            self._closed += 1
            if len(self._spans) >= self.max_spans:
                # ring semantics: evict the OLDEST, keep the new span
                if self.dropped == 0:
                    log.warn(
                        "span ring full; evicting oldest spans",
                        max_spans=self.max_spans,
                    )
                self.dropped += 1
            self._spans.append(span)
            listeners = list(self._listeners)
        for fn in listeners:
            try:
                fn(span)
            except Exception as e:  # telemetry must never take us down
                log.warn("span listener failed", error=str(e))

    def add_listener(self, fn: Callable[[Span], None]) -> None:
        """Subscribe ``fn(span)`` to every recorded span (called
        outside the tracer lock, after the span is stored)."""
        with self._lock:
            if fn not in self._listeners:
                self._listeners.append(fn)

    def remove_listener(self, fn: Callable[[Span], None]) -> None:
        with self._lock:
            if fn in self._listeners:
                self._listeners.remove(fn)

    def spans(self, name: Optional[str] = None) -> List[Span]:
        with self._lock:
            out = list(self._spans)
        return [s for s in out if name is None or s.name == name]

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self.dropped = 0

    def summary(self) -> Dict[str, Dict[str, float]]:
        """Per-span-name {count, total_s, max_s} rollup, plus a
        ``_tracer`` meta entry carrying the ring-buffer accounting
        (retained span count + evictions) so a truncated window is
        visible to every summary consumer."""
        spans, dropped = self._snapshot()
        out: Dict[str, Dict[str, float]] = {}
        for s in spans:
            agg = out.setdefault(s.name, {"count": 0, "total_s": 0.0, "max_s": 0.0})
            agg["count"] += 1
            agg["total_s"] += s.dur_s
            agg["max_s"] = max(agg["max_s"], s.dur_s)
        out["_tracer"] = {"spans": len(spans), "dropped": dropped}
        return out

    def _snapshot(self):
        """(spans, dropped) under one lock acquire: readers must see a
        consistent pair (the unguarded ``self.dropped`` reads were an
        `edl check` lockset-race finding)."""
        with self._lock:
            return list(self._spans), self.dropped

    def _page(self, since: int, last_n: Optional[int]):
        """(spans, dropped, cursor): the spans that closed after the
        first ``since`` ever stored, newest ``last_n`` kept, and the
        cursor that resumes after the last of them."""
        with self._lock:
            spans, dropped, closed = (
                list(self._spans), self.dropped, self._closed)
        spans = spans[max(0, since - (closed - len(spans))):]
        if last_n is not None:
            spans = spans[-max(int(last_n), 0):]
        return spans, dropped, (closed if spans else since)

    def to_chrome_doc(
        self, since_seq: int = 0, last_n: Optional[int] = None
    ) -> Dict[str, Any]:
        """Full chrome-trace JSON document: the events plus a metadata
        ("M") event and top-level ``dropped``, so a viewer AND a raw
        reader both see ring-buffer truncation. Served by the obs
        exporter's ``/trace`` and written by :meth:`dump`.

        ``since_seq``/``last_n`` bound the window (the ``/events``
        paging mirror): ``since_seq`` is a cursor over spans in the
        order they CLOSED (the ring's order) — only spans stored after
        the first ``since_seq`` ship, newest ``last_n`` kept. The
        metadata event carries ``max_seq``, the next cursor, so a fleet
        cadence tick fetches the delta, not the whole ring. (An event's
        own ``seq`` is its id from when it opened: the key it shares
        with its profiler annotation, not a cursor; ``parent`` is the
        ``seq`` of the span it opened under, ``cpu_s`` the CPU seconds
        its thread used meanwhile.)"""
        spans, dropped, cursor = self._page(since_seq, last_n)
        events = [
            {
                "name": s.name,
                "ph": "X",
                "ts": s.start_s * 1e6,
                "dur": s.dur_s * 1e6,
                "pid": os.getpid(),
                "tid": s.thread % 2**31,
                "seq": s.seq,
                "parent": s.parent,
                "cpu_s": s.cpu_s,
                "args": s.attrs,
            }
            for s in spans
        ]
        events.append(
            {
                "name": "edl_tracer",
                "ph": "M",
                "pid": os.getpid(),
                "tid": 0,
                "args": {
                    "dropped": dropped,
                    "max_spans": self.max_spans,
                    "spans": len(events),
                    "max_seq": cursor,
                },
            }
        )
        return {"traceEvents": events, "dropped": dropped}

    def dump(self, path: str) -> None:
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        doc = self.to_chrome_doc()
        with open(path, "w") as f:
            json.dump(doc, f)
        log.info(
            "trace written",
            path=path,
            spans=max(len(doc["traceEvents"]) - 1, 0),
            dropped=doc["dropped"],
        )


_global = Tracer()


def tracer() -> Tracer:
    return _global


def span(name: str, **attrs: Any):
    return _global.span(name, **attrs)


def step_span(name: str, step_num: int, **attrs: Any):
    return _global.step_span(name, step_num, **attrs)


def dump(path: str) -> None:
    _global.dump(path)


def summary() -> Dict[str, Dict[str, float]]:
    return _global.summary()
