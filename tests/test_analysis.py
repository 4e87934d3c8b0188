"""`edl check` static analysis (edl_tpu/analysis/): per-rule fixture
snippets (true positive, clean negative, suppressed), the baseline
round-trip, the CLI verb, and the self-check that the shipped codebase
is clean against its committed baseline. jax-free — the analyzer is
pure stdlib-ast."""

import json
import os
import textwrap

import pytest

from edl_tpu import analysis
from edl_tpu.cli.main import main as cli_main


def run_on(tmp_path, source, rules=None, name="mod.py", extra=None):
    """Analyze one fixture module (plus optional sibling files) rooted
    at tmp_path; returns the Report."""
    p = tmp_path / name
    p.write_text(textwrap.dedent(source))
    for rel, text in (extra or {}).items():
        q = tmp_path / rel
        q.parent.mkdir(parents=True, exist_ok=True)
        q.write_text(textwrap.dedent(text))
    return analysis.run_check([str(p)], rules=rules, root=str(tmp_path))


def rules_of(report):
    return [f.rule for f in report.findings]


# ---------------------------------------------------------------------------
# donation-safety


DONATED_DEF = """
    from functools import partial
    import jax

    @partial(jax.jit, donate_argnums=(0,))
    def step(state, x):
        return state + x
"""


def test_donation_read_after_donate_is_flagged(tmp_path):
    rep = run_on(tmp_path, DONATED_DEF + """
    def loop(state, xs):
        total = 0.0
        for x in xs:
            new = step(state, x)
            total += float(state.sum())  # stale read of the donated buffer
            state = new
        return total
    """, rules=["donation-safety"])
    assert rules_of(rep) == ["donation-safety"]
    assert "'state' is read after being donated to step" in rep.findings[0].message
    assert rep.findings[0].severity == "error"


def test_donation_rebind_is_clean(tmp_path):
    rep = run_on(tmp_path, DONATED_DEF + """
    def loop(state, xs):
        for x in xs:
            state = step(state, x)  # rebound: the blessed pattern
        return state
    """, rules=["donation-safety"])
    assert rep.findings == []


def test_donation_factory_and_self_attr_pattern(tmp_path):
    """The engine shape: a factory whose nested def carries the
    donation, bound to self.X, called with subscripted tuple args —
    reading the tuple afterwards is the PR 2 stale-buffer bug."""
    rep = run_on(tmp_path, """
    from functools import partial
    import jax

    def _program(cfg):
        def make():
            @partial(jax.jit, donate_argnums=(1, 2))
            def run(params, kc, vc):
                return kc, vc
            return run
        return make()

    class Engine:
        def __init__(self, cfg):
            self._decode = _program(cfg)

        def dispatch(self):
            old = (self._kc, self._vc)
            self._kc, self._vc = self._decode(self.params, old[0], old[1])
            return old[0].sum()  # stale read through the tuple
    """, rules=["donation-safety"])
    assert rules_of(rep) == ["donation-safety"]
    assert "'old'" in rep.findings[0].message


ENGINE_SHAPE = """
    from functools import partial
    import jax

    def _block(cfg, n):
        def make():
            @partial(jax.jit, donate_argnums=(1, 2) + tuple(range(4, 4 + n)))
            def run(params, tok, pos, eosv, *rest):
                *cache, key = rest
                return (tok, pos, *cache)
            return run
        return make()

    def _prefill(cfg, tb, n=2):
        def make():
            @partial(jax.jit, donate_argnums=tuple(range(2, 3 + n)))
            def run(params, tokens, tok, *cache):
                return (tok, *cache)
            return run
        return make()

    class Engine:
        def __init__(self, cfg, paged):
            self._decode = _block(cfg, 4) if paged else None
            self._decode = _block(cfg, 2)
            self._prefill_for = partial(_prefill, cfg, n=2)
"""


@pytest.mark.parametrize("expr, fixed, tail", [
    ("(1, 2)", (1, 2), None),
    ("(1, 2, 3, 4) + tuple(range(6, 6 + n))", (1, 2, 3, 4), 6),
    ("tuple(range(6, 11 + n))", (6, 7, 8, 9, 10), 11),
    ("tuple(range(6, 11)) + tuple(range(13, 13 + n))",
     (6, 7, 8, 9, 10), 13),
    ("tuple(range(n))", (), 0),
    ("tuple(sorted(x))", None, None),
])
def test_donation_argnums_literal_positions_and_an_open_run(expr, fixed, tail):
    """What ``donate_argnums`` of a program taking ``*cache`` resolves
    to: its literal positions, and where the run of computed length
    starts; an expression the rule cannot read resolves to nothing."""
    import ast
    from edl_tpu.analysis.rules._util import literal_int_tuple

    nums = literal_int_tuple(ast.parse(expr, mode="eval").body)
    if fixed is None:
        assert nums is None
    else:
        assert tuple(nums) == fixed and nums.tail == tail and bool(nums)


def test_donation_through_a_splatted_cache_tuple(tmp_path):
    """The engine's one dispatch a kind: the cache rides in a tuple
    that is splatted into a program donating a computed run. Reading
    the tuple afterwards is the stale read; rebinding from the result
    (a starred target among them) is clean."""
    rep = run_on(tmp_path, ENGINE_SHAPE + """
        def dispatch(self, where):
            old = (self._tok, self._pos) + self._cache
            self._tok, self._pos, *cache = self._decode(
                self.params, *old[:2], self._eosv, *where, *old[2:], self.key)
            self._cache = tuple(cache)
            return self._cache[0].sum()  # rebound: clean

        def bad(self, where):
            old = (self._tok, self._pos) + self._cache
            out = self._decode(
                self.params, *old[:2], self._eosv, *where, *old[2:], self.key)
            self._check(*old)  # stale read through the splat
            return out
    """, rules=["donation-safety"])
    assert rules_of(rep) == ["donation-safety"]
    assert "'old' is read after being donated to self._decode" in (
        rep.findings[0].message)


def test_donation_through_a_factory_bound_to_an_attribute(tmp_path):
    """``self.X = partial(factory, ...)``: the program ``self.X(tb)``
    builds donates what the factory's does, called in place or through
    a local binding; the cache attribute itself, splatted, is dead
    until it is rebound."""
    rep = run_on(tmp_path, ENGINE_SHAPE + """
        def prefill(self, tb, toks):
            old = (self._tok,) + self._cache
            self._tok, *cache = self._prefill_for(tb)(
                self.params, toks, *old)
            self._cache = tuple(cache)

        def bad_in_place(self, tb, toks):
            out = self._prefill_for(tb)(
                self.params, toks, self._tok, *self._cache)
            return self._cache[0].sum()  # not rebound

        def bad_local(self, tb, toks):
            prog = self._prefill_for(tb)
            old = (self._tok,) + self._cache
            out = prog(self.params, toks, *old)
            return old[1].sum()
    """, rules=["donation-safety"])
    assert rules_of(rep) == ["donation-safety"] * 2
    assert [f.message.split(" is read")[0] for f in rep.findings] == [
        "'self._cache'", "'old'"]
    assert "donated to self._prefill_for(...)" in rep.findings[0].message
    assert "donated to prog" in rep.findings[1].message


def test_donation_suppression(tmp_path):
    rep = run_on(tmp_path, DONATED_DEF + """
    def probe(state, x):
        new = step(state, x)
        # edl: no-lint[donation-safety] deliberate is_deleted probe
        assert state.is_deleted()
        return new
    """, rules=["donation-safety"])
    assert rep.findings == []
    assert rep.suppressed == 1


def test_donation_tuple_unpack_through_helper(tmp_path):
    """One-level call summary: `a, b = split(buf)` donates buf even
    though the jit call is inside the helper (the satellite-task false
    negative — previously invisible to the per-function dataflow)."""
    rep = run_on(tmp_path, DONATED_DEF + """
    def split(buf, x):
        a = step(buf, x)
        return a, x

    def use(buf, x):
        a, b = split(buf, x)
        return float(buf.sum())  # stale: buf was donated inside split
    """, rules=["donation-safety"])
    assert rules_of(rep) == ["donation-safety"]
    assert "'buf' is read after being donated to split" in rep.findings[0].message


def test_donation_helper_negatives_are_clean(tmp_path):
    """No summary for a helper that doesn't donate, or that rebinds
    the parameter before the donating call (the donated value is the
    callee's own, not the caller's)."""
    rep = run_on(tmp_path, DONATED_DEF + """
    def noop(buf, x):
        return buf + x  # no donation inside

    def shield(buf, x):
        buf = buf + 0.0  # rebound: callee donates its own copy
        return step(buf, x)

    def use(buf, x):
        y = noop(buf, x)
        z = shield(buf, x)
        return float(buf.sum())
    """, rules=["donation-safety"])
    assert rep.findings == []


def test_donation_helper_method_level(tmp_path):
    """`self._advance(state)` donates through one method-call level;
    rebinding from the helper's result stays the blessed pattern."""
    rep = run_on(tmp_path, DONATED_DEF + """
    class Engine:
        def _advance(self, state, x):
            return step(state, x)

        def run(self, state, xs):
            for x in xs:
                state = self._advance(state, x)  # rebound: clean
            return state

        def bad(self, state, x):
            out = self._advance(state, x)
            return float(state.sum())  # stale read through the helper
    """, rules=["donation-safety"])
    assert rules_of(rep) == ["donation-safety"]
    f = rep.findings[0]
    assert "'state' is read after being donated to self._advance" in f.message


# ---------------------------------------------------------------------------
# lockset-race


def test_lockset_cross_context_no_lock_is_flagged(tmp_path):
    rep = run_on(tmp_path, """
    import threading

    class Pusher:
        def __init__(self):
            self._streak = 0

        def start(self):
            threading.Thread(target=self._run, daemon=True).start()

        def _run(self):
            while True:
                self.push_once()

        def push_once(self):
            self._streak += 1

        def stop(self):
            self.push_once()  # main thread touches the same state
    """, rules=["lockset-race"])
    assert rules_of(rep) == ["lockset-race"]
    assert "Pusher._streak" in rep.findings[0].message


def test_lockset_mixed_guard_is_flagged_and_common_lock_is_clean(tmp_path):
    flagged = run_on(tmp_path, """
    import threading

    class Conn:
        def __init__(self):
            self.lock = threading.Lock()
            self.sock = None

        def use(self):
            with self.lock:
                return self.sock

        def close(self):
            self.sock = None  # unguarded write
    """, rules=["lockset-race"])
    assert rules_of(flagged) == ["lockset-race"]
    assert "mixed locking" in flagged.findings[0].message

    clean = run_on(tmp_path, """
    import threading

    class Conn:
        def __init__(self):
            self.lock = threading.Lock()
            self.sock = None

        def use(self):
            with self.lock:
                return self.sock

        def close(self):
            with self.lock:
                self.sock = None
    """, rules=["lockset-race"], name="clean.py")
    assert clean.findings == []


def test_lockset_locked_suffix_convention(tmp_path):
    """Methods named *_locked are assumed called with the lock held —
    the documented convention for internal helpers."""
    rep = run_on(tmp_path, """
    import threading

    class Q:
        def __init__(self):
            self._lock = threading.Lock()
            self._todo = []

        def get(self):
            with self._lock:
                self._reap_locked()
                return self._todo.pop()

        def _reap_locked(self):
            self._todo.append(1)
    """, rules=["lockset-race"])
    assert rep.findings == []


def test_lockset_init_and_readonly_are_exempt(tmp_path):
    rep = run_on(tmp_path, """
    import threading

    class Server:
        def __init__(self):
            self._cfg = {"a": 1}   # written only at construction
            threading.Thread(target=self._loop, daemon=True).start()

        def _loop(self):
            while True:
                self.handle()

        def handle(self):
            return self._cfg["a"]  # read-only after init: safe
    """, rules=["lockset-race"])
    assert rep.findings == []


def test_lockset_acquire_release_statements_guard(tmp_path):
    """Bare self._lock.acquire()/try/finally-release() counts as a
    guarded region, same as `with self._lock` (previously invisible:
    the accesses in between looked unguarded and produced a spurious
    mixed-lockset finding)."""
    rep = run_on(tmp_path, """
    import threading

    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self.n = 0
            threading.Thread(target=self._run, daemon=True).start()

        def _run(self):
            self._lock.acquire()
            try:
                self.n += 1
            finally:
                self._lock.release()

        def bump(self):
            with self._lock:
                self.n += 1
    """, rules=["lockset-race"])
    assert rep.findings == []


def test_lockset_rlock_reentrant_nested_helper_is_clean(tmp_path):
    """The satellite-task fixture: a nested helper defined under the
    RLock runs under it (def-site lockset inheritance) — re-entry in
    the helper is NOT a fresh unguarded access."""
    rep = run_on(tmp_path, """
    import threading

    class Ledger:
        def __init__(self):
            self._lock = threading.RLock()
            self.total = 0
            threading.Thread(target=self.loop, daemon=True).start()

        def loop(self):
            with self._lock:
                def add(v):
                    self.total += v  # runs under the outer RLock
                add(1)
                self._lock.acquire()  # re-entrant acquire, same lock
                try:
                    add(2)
                finally:
                    self._lock.release()

        def read(self):
            with self._lock:
                return self.total
    """, rules=["lockset-race"])
    assert rep.findings == []


def test_lockset_nested_thread_target_does_not_inherit(tmp_path):
    """The counterweight to def-site inheritance: a nested def handed
    to Thread(target=...) runs in the NEW thread, where nothing is
    held — it must stay unguarded and flag."""
    rep = run_on(tmp_path, """
    import threading

    class Spawner:
        def __init__(self):
            self._lock = threading.Lock()
            self.n = 0

        def start(self):
            with self._lock:
                def work():
                    self.n += 1  # new thread: the lock is NOT held
                threading.Thread(target=work, daemon=True).start()

        def read(self):
            with self._lock:
                return self.n
    """, rules=["lockset-race"])
    assert rules_of(rep) == ["lockset-race"]
    assert "Spawner.n" in rep.findings[0].message


def test_lockset_private_helper_inherits_caller_lock(tmp_path):
    """One-level interprocedural context: a private helper invoked
    only under the lock is guarded; add one bare caller and the race
    is visible again."""
    clean = run_on(tmp_path, """
    import threading

    class Counter:
        def __init__(self):
            self._lock = threading.Lock()
            self.n = 0
            threading.Thread(target=self.loop, daemon=True).start()

        def _bump(self):
            self.n += 1  # only ever called under the lock

        def loop(self):
            with self._lock:
                self._bump()

        def read(self):
            with self._lock:
                return self.n
    """, rules=["lockset-race"])
    assert clean.findings == []

    mixed = run_on(tmp_path, """
    import threading

    class Counter:
        def __init__(self):
            self._lock = threading.Lock()
            self.n = 0
            threading.Thread(target=self.loop, daemon=True).start()

        def _bump(self):
            self.n += 1

        def loop(self):
            with self._lock:
                self._bump()

        def poke(self):
            self._bump()  # bare public caller: the race is back

        def read(self):
            with self._lock:
                return self.n
    """, rules=["lockset-race"], name="mixed.py")
    assert rules_of(mixed) == ["lockset-race"]
    assert "Counter.n" in mixed.findings[0].message


# ---------------------------------------------------------------------------
# recompile-hazard


def test_recompile_per_call_jit_flagged_memo_clean(tmp_path):
    flagged = run_on(tmp_path, """
    import jax

    def predict(params, rows):
        fwd = jax.jit(lambda p, x: p @ x)  # fresh wrapper per call
        return [fwd(params, r) for r in rows]
    """, rules=["recompile-hazard"])
    assert rules_of(flagged) == ["recompile-hazard"]
    assert "fresh wrapper per call" in flagged.findings[0].message

    clean = run_on(tmp_path, """
    import jax

    _cache = {}

    def predict(params, rows):
        fn = _cache.get("fwd")
        if fn is None:
            fn = jax.jit(lambda p, x: p @ x)  # built once behind the guard
            _cache["fwd"] = fn
        return [fn(params, r) for r in rows]
    """, rules=["recompile-hazard"], name="clean.py")
    assert clean.findings == []


def test_recompile_host_sync_inside_jit(tmp_path):
    rep = run_on(tmp_path, """
    import jax
    import numpy as np

    @jax.jit
    def bad(x):
        return float(x) + np.asarray(x).sum() + x.mean().item()
    """, rules=["recompile-hazard"])
    msgs = " | ".join(f.message for f in rep.findings)
    assert ".item() inside jitted" in msgs
    assert "float() coercion" in msgs
    assert "np.asarray() on a traced value" in msgs


def test_recompile_shape_branch_and_validation_exemption(tmp_path):
    rep = run_on(tmp_path, """
    import jax

    @jax.jit
    def f(x):
        if x.shape[0] > 4:   # recompiles per shape class
            x = x * 2
        if x.shape[1] != 8:  # trace-time validation: exempt
            raise ValueError("bad width")
        return x
    """, rules=["recompile-hazard"])
    assert len(rep.findings) == 1
    assert "shape-dependent Python branch" in rep.findings[0].message


def test_recompile_unhashable_static_args(tmp_path):
    rep = run_on(tmp_path, """
    from functools import partial
    import jax

    @partial(jax.jit, static_argnums=(1,))
    def f(x, cfg):
        return x

    def call(x):
        return f(x, [1, 2, 3])  # list at a static position: TypeError
    """, rules=["recompile-hazard"])
    assert rules_of(rep) == ["recompile-hazard"]
    assert "unhashable literal" in rep.findings[0].message
    assert rep.findings[0].severity == "error"


# ---------------------------------------------------------------------------
# silent-failure


def test_silent_failure_flagged_and_handled_variants_clean(tmp_path):
    rep = run_on(tmp_path, """
    def swallow():
        try:
            work()
        except Exception:
            pass
    """, rules=["silent-failure"])
    assert rules_of(rep) == ["silent-failure"]

    clean = run_on(tmp_path, """
    def loud(log, errs, counter):
        try:
            work()
        except Exception as e:
            log.warn("work failed", error=str(e))
        try:
            work()
        except Exception as e:
            errs.append(e)       # exception object flows onward
        try:
            work()
        except Exception:
            counter.inc()        # counted = visible
        try:
            work()
        except Exception:
            raise
        try:
            work()
        except OSError:
            pass                 # narrow catch: a stated decision
    """, rules=["silent-failure"], name="clean.py")
    assert clean.findings == []


def test_silent_failure_suppression_counted(tmp_path):
    rep = run_on(tmp_path, """
    def teardown():
        try:
            close()
        # edl: no-lint[silent-failure] best-effort teardown
        except Exception:
            pass
    """, rules=["silent-failure"])
    assert rep.findings == [] and rep.suppressed == 1


# ---------------------------------------------------------------------------
# telemetry-conventions


def test_telemetry_metric_name_and_event_kind(tmp_path):
    rep = run_on(tmp_path, """
    def instrument(reg, events):
        reg.counter("requests_total", "no prefix")
        reg.gauge("edl_ok_gauge", "fine")
        events.emit("recovered", rid="r1")     # not site.verb
        events.emit("serve.recover", rid="r1") # fine
    """, rules=["telemetry-conventions"])
    msgs = " | ".join(f.message for f in rep.findings)
    assert "'requests_total' does not follow" in msgs
    assert "event kind 'recovered'" in msgs
    assert len(rep.findings) == 2


def test_telemetry_suffix_kind_conventions(tmp_path):
    """Counters must end _total; nothing else may; _ratio/_fraction
    must be gauges (the hardware-efficiency families' convention)."""
    rep = run_on(tmp_path, """
    def instrument(reg):
        reg.counter("edl_widgets", "counter without _total")
        reg.gauge("edl_things_total", "gauge posing as a counter")
        reg.histogram("edl_kv_occupancy_ratio", "ratio as histogram")
        reg.counter("edl_ok_total", "fine")
        reg.gauge("edl_bw_util_ratio", "fine", ("phase",))
        reg.gauge("edl_goodput_fraction", "fine")
        reg.histogram("edl_step_seconds", "fine")
    """, rules=["telemetry-conventions"])
    msgs = [f.message for f in rep.findings]
    assert len(msgs) == 3, msgs
    assert any("must end '_total'" in m for m in msgs)
    assert any("ends '_total' but is not a counter" in m for m in msgs)
    assert any(
        "ends '_ratio'/'_fraction' but is not a gauge" in m for m in msgs
    )


def test_telemetry_conflicting_registration(tmp_path):
    rep = run_on(tmp_path, """
    def a(reg):
        reg.counter("edl_widgets_total", "as counter")

    def b(reg):
        reg.gauge("edl_widgets_total", "same name, other kind")
    """, rules=["telemetry-conventions"])
    assert any("conflicting schema" in f.message for f in rep.findings)


def test_telemetry_trace_keys_only_via_disttrace(tmp_path):
    """Hand-rolled trace-context key access (subscript, .get, dict
    literal) is flagged everywhere EXCEPT obs/disttrace.py — the
    helpers own the wire format."""
    rep = run_on(tmp_path, """
    def relay(corr, remote):
        corr["trace_id"] = remote.trace_id          # subscript write
        parent = corr.get("parent_id")              # dict-method read
        return {"span_id": parent}                  # dict literal
    """, rules=["telemetry-conventions"])
    msgs = [f.message for f in rep.findings]
    assert len(msgs) == 3, msgs
    assert all("obs/disttrace helpers" in m for m in msgs)
    assert any("'trace_id' (subscript)" in m for m in msgs)
    assert any("'parent_id' (.get())" in m for m in msgs)
    assert any("'span_id' (dict literal)" in m for m in msgs)


def test_telemetry_trace_keys_clean_patterns(tmp_path):
    """The sanctioned shapes stay clean: disttrace.py itself, helper
    calls, and attribute access (ctx.trace_id is not a dict key)."""
    home = run_on(tmp_path, """
    def inject(d, ctx):
        d["trace_id"] = ctx.trace_id
        return d.get("span_id")
    """, rules=["telemetry-conventions"], name="disttrace.py",
        extra={"obs/__init__.py": ""})
    # fixture file is named disttrace.py but not under obs/ — still
    # flagged; the real home path is exempt
    assert len(home.findings) == 2
    ok = run_on(tmp_path, """
    from edl_tpu.obs import disttrace

    def relay(corr):
        ctx = disttrace.extract(corr)
        tid = ctx.trace_id if ctx else None
        return disttrace.inject({}, ctx), tid
    """, rules=["telemetry-conventions"])
    assert ok.findings == []


def test_telemetry_trace_keys_exempt_in_disttrace_home(tmp_path):
    p = tmp_path / "obs"
    p.mkdir()
    (p / "disttrace.py").write_text(
        'def inject(d, c):\n    d["trace_id"] = c.trace_id\n    return d\n'
    )
    import edl_tpu.analysis as analysis_mod

    rep = analysis_mod.run_check(
        [str(p / "disttrace.py")],
        rules=["telemetry-conventions"], root=str(tmp_path),
    )
    assert rep.findings == []


def test_telemetry_fault_site_coverage(tmp_path):
    covered = run_on(tmp_path, """
    from edl_tpu.utils import faults

    def lease():
        faults.fault_point("data.lease")

    def push():
        faults.fault_point("obscure.site")
    """, rules=["telemetry-conventions"], extra={
        "tests/test_chaos.py": 'PLAN = "data.lease:raise@n=1"\n',
    })
    assert len(covered.findings) == 1
    assert "'obscure.site' is not referenced" in covered.findings[0].message


def test_telemetry_alert_rules_series_must_exist(tmp_path):
    """A DEFAULT_RULES entry watching a series nothing registers is an
    error — an alert rule over a typo'd name silently never fires."""
    rep = run_on(tmp_path, """
    DEFAULT_RULES = {
        "time_scale": 1.0,
        "rules": [
            {"name": "ok_rule", "type": "threshold",
             "series": "edl_widgets_total", "op": ">", "value": 1.0},
            {"name": "ghost_rule", "type": "threshold",
             "series": "edl_ghost_series", "op": ">", "value": 1.0},
        ],
    }

    def instrument(reg):
        reg.counter("edl_widgets_total", "exists")
    """, rules=["telemetry-conventions"])
    assert len(rep.findings) == 1
    f = rep.findings[0]
    assert "ghost_rule" in f.message and "'edl_ghost_series'" in f.message
    assert f.severity == "error"


def test_telemetry_alert_rules_skip_partial_runs(tmp_path):
    """With no registrations in scope (a partial run over one file),
    the series check cannot judge and stays silent."""
    rep = run_on(tmp_path, """
    DEFAULT_RULES = {
        "rules": [
            {"name": "r", "type": "threshold",
             "series": "edl_anything", "op": ">", "value": 1.0},
        ],
    }
    """, rules=["telemetry-conventions"])
    assert rep.findings == []


def test_telemetry_alert_namespace_kinds(tmp_path):
    """Only alert.fire / alert.resolve may live in the alert.* event
    namespace — postmortem's incident chainer pairs exactly those."""
    rep = run_on(tmp_path, """
    def transitions(events):
        events.emit("alert.fired", rule="r")    # wrong spelling
        events.emit("alert.fire", rule="r")     # fine
        events.emit("alert.resolve", rule="r")  # fine
    """, rules=["telemetry-conventions"])
    assert len(rep.findings) == 1
    assert "alert.* namespace" in rep.findings[0].message
    assert "'alert.fired'" in rep.findings[0].message


# ---------------------------------------------------------------------------
# kv-block


def test_kv_block_free_without_table_clear_is_flagged(tmp_path):
    rep = run_on(tmp_path, """
    class Engine:
        def evict(self, i):
            tbl = self._tables[i]
            for j, bid in enumerate(tbl):
                if bid != 0:
                    self._balloc.free(bid)  # table entry never cleared
    """, rules=["kv-block"])
    assert rules_of(rep) == ["kv-block"]
    assert "'bid'" in rep.findings[0].message
    assert "table" in rep.findings[0].message
    assert rep.findings[0].severity == "error"


def test_kv_block_free_with_table_clear_is_clean(tmp_path):
    rep = run_on(tmp_path, """
    SCRATCH = 0

    class Engine:
        def evict(self, i):
            tbl = self._tables[i]
            for j, bid in enumerate(tbl):
                if bid != SCRATCH:
                    self._balloc.free(bid)
                    tbl[j] = SCRATCH

        def cow(self, slot, j):
            tbl = self._tables[slot]
            bid = tbl[j]
            dst = self._balloc.alloc()
            tbl[j] = dst
            self._balloc.free(bid)
    """, rules=["kv-block"])
    assert rep.findings == []


def test_kv_block_non_table_free_is_exempt(tmp_path):
    # the prefix cache freeing its own map entries references no
    # table — refcount-only releases are not the hazard
    rep = run_on(tmp_path, """
    class PrefixCache:
        def evict_one(self):
            for key, bid in self._map.items():
                if self._alloc.refcount(bid) == 1:
                    del self._map[key]
                    self._alloc.free(bid)
                    return True
            return False
    """, rules=["kv-block"])
    assert rep.findings == []


def test_kv_block_suppression(tmp_path):
    rep = run_on(tmp_path, """
    class Engine:
        def drop(self, i):
            tbl = self._tables[i]
            bid = tbl[0]
            # edl: no-lint[kv-block] table discarded wholesale below
            self._balloc.free(bid)
            del self._tables[i]
    """, rules=["kv-block"])
    assert rep.findings == []
    assert rep.suppressed == 1


# ---------------------------------------------------------------------------
# baseline round-trip + framework


def test_baseline_round_trip(tmp_path):
    src = """
    def swallow():
        try:
            work()
        except Exception:
            pass
    """
    rep = run_on(tmp_path, src, rules=["silent-failure"])
    assert len(rep.findings) == 1

    bl = tmp_path / "baseline.json"
    analysis.write_baseline(str(bl), rep.findings)
    rep2 = analysis.run_check(
        [str(tmp_path / "mod.py")], rules=["silent-failure"],
        baseline=str(bl), root=str(tmp_path),
    )
    assert rep2.findings == [] and len(rep2.baselined) == 1
    assert not rep2.failed

    # a SECOND instance of the same pattern exceeds the baseline count
    (tmp_path / "mod.py").write_text(
        textwrap.dedent(src) + textwrap.dedent(src).replace("swallow", "gulp")
    )
    rep3 = analysis.run_check(
        [str(tmp_path / "mod.py")], rules=["silent-failure"],
        baseline=str(bl), root=str(tmp_path),
    )
    assert len(rep3.findings) == 1 and len(rep3.baselined) == 1
    assert rep3.failed


def test_unknown_rule_rejected(tmp_path):
    (tmp_path / "m.py").write_text("x = 1\n")
    with pytest.raises(ValueError, match="unknown rule"):
        analysis.run_check([str(tmp_path / "m.py")], rules=["bogus"])


def test_syntax_error_is_reported_not_fatal(tmp_path):
    (tmp_path / "bad.py").write_text("def broken(:\n")
    rep = analysis.run_check([str(tmp_path / "bad.py")], root=str(tmp_path))
    assert rep.failed and rep.errors and "bad.py" in rep.errors[0]


# ---------------------------------------------------------------------------
# CLI verb


def test_cli_check_json_and_exit_codes(tmp_path, capsys):
    mod = tmp_path / "m.py"
    mod.write_text(textwrap.dedent("""
    def swallow():
        try:
            work()
        except Exception:
            pass
    """))
    rc = cli_main(["check", str(mod), "--json", "--root", str(tmp_path)])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1 and doc["ok"] is False
    assert doc["findings"][0]["rule"] == "silent-failure"

    bl = tmp_path / "bl.json"
    rc = cli_main([
        "check", str(mod), "--root", str(tmp_path),
        "--write-baseline", str(bl),
    ])
    capsys.readouterr()
    assert rc == 0 and bl.exists()
    rc = cli_main([
        "check", str(mod), "--root", str(tmp_path), "--baseline", str(bl),
    ])
    out = capsys.readouterr().out
    assert rc == 0 and "0 findings (1 baselined" in out


# ---------------------------------------------------------------------------
# the self-check: the shipped package is clean against its baseline


def test_repo_is_clean_under_edl_check():
    """THE acceptance gate: `edl check` over edl_tpu/ reports zero
    non-baselined findings (every deliberate violation carries an
    in-code `# edl: no-lint[...]` reason or a baseline entry), and the
    full-package run stays inside the 30 s wall-time budget."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rep = analysis.run_check(
        [os.path.join(root, "edl_tpu")],
        baseline=os.path.join(root, "analysis_baseline.json"),
        root=root,
    )
    assert rep.findings == [], analysis.render_text(rep)
    assert rep.errors == []
    assert rep.files > 80  # the whole package was actually walked
    assert rep.suppressed >= 5  # triaged deliberate sites are counted
    assert rep.duration_s < 30.0
