"""Runtime compile observability — the dynamic twin of `edl check`'s
static recompile-hazard rule.

One listener on JAX's own monitoring events (:func:`install`, once a
process, from the modules that build jit programs) sees every program
the process traces, lowers and compiles — wrapped by nobody, with no
frame of ours on any dispatch. The program's name is the event's
``fun_name``: the name of the function under ``jax.jit``
(``edl_train_step``, ``edl_serve_block``, ``edl_serve_prefill_512``…),
the same name the profiler's ``XLA Modules`` line shows. Per program
and stage it observes ``edl_compile_seconds{program,stage}``:

* ``trace`` — Python to jaxpr (the outermost trace of the program),
* ``lower`` — jaxpr to an MLIR module,
* ``backend`` — XLA compiling it (a miss of the persistent cache), or
* ``cache_load`` — the persistent cache's lookup, retrieval and load
  onto the devices, when that is a hit;

and counts the program once in ``edl_compiles_total{program}`` when
its executable arrives, however it did.

After :func:`mark_warm` — called by harnesses once their warmup pass
has paid the expected compiles — any further program additionally
emits an ``obs.recompile`` flight-recorder event (severity ``warn``):
a steady-state serving loop that compiles is paying seconds of latency
someone should see on the incident timeline, exactly the hazard class
the static rule flags at review time. The acceptance gate asserts ZERO
such events on the steady-state serving loop (`edl profile --dryrun`).

:class:`Window` sums the same durations while it is open: the elastic
trainer opens one around the first step on a new mesh, which is how a
``ReshardEvent`` says what part of that step was re-tracing, lowering
and loading (runtime/elastic.py).

Metrics go to the process default registry on purpose — compile
activity is process-level truth regardless of which private registry
an engine's serving metrics use.
"""

from __future__ import annotations

import threading
from typing import List

from edl_tpu.obs import metrics as obs_metrics

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

_lock = threading.Lock()
_warm = False
_installed = False
_windows: List["Window"] = []
# a program is built on the thread that first calls it, stage after
# stage: what one stage's event leaves for the next lives here
_building = threading.local()


def mark_warm() -> None:
    """Declare warmup over: compiles from here on are RE-compiles and
    land on the flight-recorder timeline."""
    global _warm
    with _lock:
        _warm = True


def is_warm() -> bool:
    with _lock:
        return _warm


def reset() -> None:
    """Back to warmup (tests)."""
    global _warm
    with _lock:
        _warm = False


class Window:
    """Sums the stage durations of every program built while open:
    ``trace_s``, ``lower_s``, ``load_s`` (backend compile, or the
    persistent cache's retrieval and load), the number of programs and
    how many of them came from the persistent cache."""

    def __init__(self):
        self.trace_s = self.lower_s = self.load_s = 0.0
        self.programs = self.cache_hits = 0

    @property
    def cache_hit(self) -> bool:
        """Every program built in the window came from the cache."""
        return self.programs > 0 and self.cache_hits == self.programs

    def __enter__(self) -> "Window":
        with _lock:
            _windows.append(self)
        return self

    def __exit__(self, *exc) -> bool:
        with _lock:
            _windows.remove(self)
        return False


def _observe(program: str, stage: str, secs: float) -> None:
    obs_metrics.default_registry().histogram(
        "edl_compile_seconds",
        "time to build one jit program, by stage (trace, lower, "
        "backend compile or persistent-cache load)",
        ("program", "stage"),
    ).observe(secs, program=program, stage=stage)
    with _lock:
        for w in _windows:
            if stage == "trace":
                w.trace_s += secs
            elif stage == "lower":
                w.lower_s += secs
            else:
                w.load_s += secs
                w.programs += 1
                w.cache_hits += stage == "cache_load"


def _on_duration(event: str, secs: float, fun_name: str = "", **_kw) -> None:
    if fun_name.endswith(")"):
        # lowering and compiling say "jit(edl_train_step)", tracing
        # "edl_train_step": the function's name is the program's
        fun_name = fun_name[fun_name.find("(") + 1:-1]
    b = _building
    if event == _TRACE:
        # a jit called inside another is traced inside it and its event
        # comes first: the outermost trace of a program is the last one
        # under its name before its lowering
        traces = getattr(b, "traces", None)
        if traces is None:
            traces = b.traces = {}
        traces[fun_name] = secs
    elif event == _LOWER:
        traces = getattr(b, "traces", None) or {}
        trace_s = traces.pop(fun_name, None)
        traces.clear()
        if trace_s is not None:  # None: the jaxpr was still cached
            _observe(fun_name, "trace", trace_s)
        _observe(fun_name, "lower", secs)
        b.front_s = (trace_s or 0.0) + secs
    elif event == _BACKEND:
        hit = getattr(b, "hit", False)
        front_s = getattr(b, "front_s", 0.0)
        b.hit, b.front_s = False, 0.0
        _observe(fun_name, "cache_load" if hit else "backend", secs)
        obs_metrics.default_registry().counter(
            "edl_compiles_total",
            "distinct jit programs built, by the jitted function's name",
            ("program",),
        ).inc(program=fun_name)
        if is_warm():
            from edl_tpu.obs import events as flight

            flight.emit(
                "obs.recompile", severity="warn", program=fun_name,
                seconds=round(front_s + secs, 6), cache_hit=hit,
            )


def _on_event(event: str, **_kw) -> None:
    # fires inside the backend-compile event of the program it is for
    if event == _CACHE_HIT:
        _building.hit = True


def install() -> None:
    """Register the listener with ``jax.monitoring``; idempotent. Called
    at import by the modules that build jit programs, so a process that
    never imports JAX never pays for it."""
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
