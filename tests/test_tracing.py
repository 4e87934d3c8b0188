"""Tracing subsystem + reshard span instrumentation + checkpoint/resume."""

import json
import os

import jax
import numpy as np
import optax
import pytest

from edl_tpu.models import linreg
from edl_tpu.runtime import checkpoint as ckpt
from edl_tpu.runtime.elastic import ElasticTrainer
from edl_tpu.utils import tracing


@pytest.fixture(autouse=True)
def _clear_tracer():
    tracing.tracer().clear()
    yield
    tracing.tracer().clear()


def _data_fn(bs, seed=0):
    x, y = linreg.synthetic_dataset(max(bs, 64), seed=seed)
    return lambda n: {"x": x[:n], "y": y[:n]}


def _trainer(**kw):
    return ElasticTrainer(
        linreg.loss_fn, optax.sgd(0.05), chips_per_worker=1, per_chip_batch=8, **kw
    )


def test_span_recording_and_chrome_dump(tmp_path):
    tr = tracing.Tracer()
    with tr.span("outer", job="j"):
        with tr.span("inner"):
            pass
    assert [s.name for s in tr.spans()] == ["inner", "outer"]
    assert tr.spans("outer")[0].attrs == {"job": "j"}
    assert tr.summary()["outer"]["count"] == 1
    assert tr.summary()["_tracer"] == {"spans": 2, "dropped": 0}

    g_path = str(tmp_path / "t.json")
    tr.dump(g_path)
    with open(g_path) as f:
        doc = json.load(f)
    assert doc["dropped"] == 0
    events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert len(events) == 2
    assert all(e["dur"] >= 0 for e in events)
    # the ring-buffer accounting rides as chrome-trace metadata
    assert meta and meta[0]["args"]["dropped"] == 0
    # inner nests within outer on the timeline
    inner = next(e for e in events if e["name"] == "inner")
    outer = next(e for e in events if e["name"] == "outer")
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1.0


def test_ring_buffer_keeps_most_recent_spans():
    """Overflow policy: the ring evicts the OLDEST span (the old
    behavior silently dropped the NEWEST — exactly the spans closest
    to an incident) and the eviction count surfaces everywhere."""
    tr = tracing.Tracer(max_spans=3)
    for i in range(7):
        tr.record(f"s{i}", 0.0, 0.1)
    assert [s.name for s in tr.spans()] == ["s4", "s5", "s6"]
    assert tr.dropped == 4
    assert tr.summary()["_tracer"] == {"spans": 3, "dropped": 4}
    doc = tr.to_chrome_doc()
    assert doc["dropped"] == 4
    meta = next(e for e in doc["traceEvents"] if e["ph"] == "M")
    assert meta["args"]["dropped"] == 4 and meta["args"]["max_spans"] == 3
    tr.clear()
    assert tr.dropped == 0 and tr.spans() == []


def test_tracer_listener_sees_every_span():
    tr = tracing.Tracer()
    seen = []
    listener = lambda s: seen.append(s.name)  # noqa: E731
    tr.add_listener(listener)
    with tr.span("a"):
        pass
    tr.record("b", 0.0, 0.5)
    assert seen == ["a", "b"]
    tr.remove_listener(listener)
    tr.record("c", 0.0, 0.5)
    assert seen == ["a", "b"]


def test_reshard_emits_spans(cpu_devices):
    t = _trainer(devices=cpu_devices[:4])
    t.start(linreg.init_params(jax.random.PRNGKey(0)), n_workers=2)
    data = _data_fn(64)
    t.train_steps(data, 2)
    t.request_rescale(4)
    t.train_steps(data, 2)
    names = {s.name for s in tracing.tracer().spans()}
    assert "reshard" in names
    assert "reshard.build_mesh" in names
    assert "reshard.recompile" in names
    ev = tracing.tracer().spans("reshard")[0]
    assert ev.attrs["from_workers"] == 2 and ev.attrs["to_workers"] == 4


def test_periodic_checkpoint_and_resume(tmp_path, cpu_devices):
    cdir = str(tmp_path / "ckpt")
    t = _trainer(
        devices=cpu_devices[:4], checkpoint_dir=cdir, checkpoint_every_steps=2
    )
    t.start(linreg.init_params(jax.random.PRNGKey(0)), n_workers=2)
    t.train_steps(_data_fn(64), 5)
    assert os.path.isdir(os.path.join(cdir, "step-2"))
    assert os.path.isdir(os.path.join(cdir, "step-4"))
    assert "checkpoint.save" in tracing.tracer().summary()

    # resume onto a DIFFERENT worker count (elastic warm restart)
    t2 = _trainer(devices=cpu_devices[:4])
    t2.resume(
        linreg.init_params(jax.random.PRNGKey(1)),
        n_workers=4,
        checkpoint_path=os.path.join(cdir, "step-4"),
    )
    assert int(np.asarray(jax.device_get(t2.state.step))) == 4
    assert ckpt.load_metadata(os.path.join(cdir, "step-4"))["n_workers"] == 2

    # resumed params equal the checkpointed ones, not the fresh template
    from edl_tpu.train.trainer import TrainState

    saved = ckpt.load(
        os.path.join(cdir, "step-4"),
        TrainState.create(linreg.init_params(jax.random.PRNGKey(1)), optax.sgd(0.05)),
    )
    np.testing.assert_allclose(
        np.asarray(jax.device_get(t2.state.params["w"])),
        np.asarray(saved.params["w"]),
    )
    report = t2.train_steps(_data_fn(64), 2)
    assert int(np.asarray(jax.device_get(t2.state.step))) == 6
    assert np.isfinite(report.losses).all()


def test_force_checkpoint(tmp_path, cpu_devices):
    t = _trainer(devices=cpu_devices[:2], checkpoint_dir=str(tmp_path))
    t.start(linreg.init_params(jax.random.PRNGKey(0)), n_workers=2)
    t.train_steps(_data_fn(32), 1)
    path = t.maybe_checkpoint(force=True)
    assert path and os.path.isdir(path)
    assert t.maybe_checkpoint(force=True) is None  # same step: no rewrite


# ---------------------------------------------------------------------------
# one primitive on the profiler's clock


def _xplane_annotations(logdir):
    """{seq: (name, start ns, end ns)} of the edl.* annotations of the
    newest trace under ``logdir``."""
    import glob

    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(tracing.ANNOTATION_PREFIX):
                    stats = dict(ev.stats)
                    start = int(ev.start_ns)
                    out[int(stats["seq"])] = (
                        ev.name, start, start + int(ev.duration_ns), stats)
    return out


def test_span_is_in_the_ring_and_in_the_profile_with_one_seq(tmp_path):
    import time

    tr = tracing.tracer()
    with tracing.span("before.session"):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("first", rid="a"):
            time.sleep(0.002)
        time.sleep(0.005)
        with tracing.span("second"):
            time.sleep(0.002)
        with tracing.step_span("a.step", 7):
            pass
        tr.record("after.the.fact", time.perf_counter() - 0.5, 0.5)
    finally:
        jax.profiler.stop_trace()
    ring = {s.name: s for s in tr.spans()}
    notes = _xplane_annotations(str(tmp_path))
    # every span opened under the session is in both, under one seq
    for name in ("first", "second", "a.step", "after.the.fact"):
        assert notes[ring[name].seq][0] == "edl." + name
    assert notes[ring["a.step"].seq][3]["step_num"] == 7
    # the lowest seq in the trace: spans below it predate the session
    assert min(notes) == ring["first"].seq > ring["before.session"].seq
    # one joined span gives the offset between the two clocks; it
    # places another span's start within a millisecond
    first, second = ring["first"], ring["second"]
    offset = tracing.clock_offset_ns(first, notes[first.seq][1], tr.t0)
    predicted = (tr.t0 + second.start_s) * 1e9 + offset
    assert abs(predicted - notes[second.seq][1]) < 1e6
    # and the annotation lasted as long as the span
    assert abs((notes[first.seq][2] - notes[first.seq][1])
               - first.dur_s * 1e9) < 1e6


def test_seq_is_taken_at_open_and_paging_follows_the_ring():
    tr = tracing.Tracer()
    with tr.span("parent") as attrs:
        with tr.span("child"):
            pass
        # a puller comes by while the parent is still open
        doc = tr.to_chrome_doc()
        meta = next(e for e in doc["traceEvents"] if e["ph"] == "M")
        cursor = meta["args"]["max_seq"]
        attrs["found"] = 1  # known only at the end
    parent, child = tr.spans("parent")[0], tr.spans("child")[0]
    assert parent.seq < child.seq  # ids in opening order
    assert parent.attrs == {"found": 1}
    # the parent closed after the cursor was handed out: the next page
    # still has it
    page = tr.to_chrome_doc(since_seq=cursor)
    assert [e["name"] for e in page["traceEvents"] if e["ph"] == "X"] == [
        "parent"]


def _spin(seconds):
    import time

    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def test_cpu_s_tells_a_wait_from_work():
    import time

    tr = tracing.Tracer()
    with tr.span("sleeps"):
        time.sleep(0.05)
    sleeps = tr.spans("sleeps")[0]
    assert sleeps.dur_s >= 0.05 and sleeps.cpu_s < 0.2 * sleeps.dur_s
    # a spinning thread that the machine takes off its CPU reads low,
    # which is the point of the field: under the test run's other
    # workers, one of a few short spins has to have kept its CPU
    for _ in range(20):
        with tr.span("spins"):
            _spin(0.01)
        spins = tr.spans("spins")[-1]
        if abs(spins.cpu_s - spins.dur_s) <= 0.2 * spins.dur_s:
            break
    else:
        pytest.fail(f"no spin of 20 kept its CPU: {tr.spans('spins')}")
    tr.record("after.the.fact", time.perf_counter() - 0.5, 0.5)
    # record() times nothing itself
    assert tr.spans("after.the.fact")[0].cpu_s is None


def test_the_cpu_clock_is_read_once_a_millisecond_at_most(monkeypatch):
    """The thread's CPU clock is a system call (6 us a read on the TPU
    host): a reading under CPU_READ_EVERY_S old is carried forward as
    if the thread had run since, so spans in quick succession cost no
    call each and a span that outlasts it is read at its close."""
    import time

    reads = []
    real = time.thread_time

    def counted():
        reads.append(time.perf_counter())
        return real()

    monkeypatch.setattr(tracing.time, "thread_time", counted)
    tr = tracing.Tracer()
    t0 = time.perf_counter()
    for _ in range(100):
        with tr.span("quick"):
            pass
    took = time.perf_counter() - t0
    assert len(reads) <= 2 + took / tracing.CPU_READ_EVERY_S
    quick = tr.spans("quick")
    # carried forward, a span's CPU time is its wall time
    assert sum(1 for s in quick if s.cpu_s == pytest.approx(
        s.dur_s, abs=1e-6)) >= 90
    before = len(reads)
    with tr.span("long"):
        time.sleep(3 * tracing.CPU_READ_EVERY_S)
    # read at its close (its open was carried, or read if the last
    # reading had just aged out)
    assert before + 1 <= len(reads) <= before + 2
    assert tr.spans("long")[0].cpu_s < tracing.CPU_READ_EVERY_S


def test_parent_is_the_enclosing_span_on_the_same_thread():
    import threading
    import time

    tr = tracing.Tracer()

    def elsewhere():
        with tr.span("other.thread"):
            pass

    with tr.span("outer"):
        with tr.span("inner"):
            with tr.step_span("innermost", 3):
                pass
        with tr.span("sibling"):
            pass
        # a span timed by the caller lies where it was recorded
        tr.record("recorded", time.perf_counter() - 0.1, 0.1)
        t = threading.Thread(target=elsewhere)
        t.start()
        t.join()
    with tr.span("next"):
        pass
    by = {s.name: s for s in tr.spans()}
    assert by["outer"].parent == 0 and by["next"].parent == 0
    assert by["inner"].parent == by["outer"].seq
    assert by["innermost"].parent == by["inner"].seq
    assert by["sibling"].parent == by["outer"].seq
    assert by["recorded"].parent == by["outer"].seq
    # opened while "outer" was open, but on a thread of its own
    assert by["other.thread"].parent == 0
    # another tracer's spans are not this one's parents
    other = tracing.Tracer()
    with tr.span("mine"):
        with other.span("theirs"):
            pass
    assert other.spans("theirs")[0].parent == 0


def test_a_span_that_raises_still_closes_its_place_in_the_nesting():
    tr = tracing.Tracer()
    with pytest.raises(ValueError):
        with tr.span("outer"):
            with tr.span("fails"):
                raise ValueError("x")
    with tr.span("after"):
        pass
    by = {s.name: s for s in tr.spans()}
    assert by["fails"].parent == by["outer"].seq
    assert by["after"].parent == 0 and by["fails"].cpu_s is not None


def test_cpu_s_and_parent_ride_the_chrome_doc():
    tr = tracing.Tracer()
    with tr.span("outer"):
        with tr.span("inner"):
            _spin(0.002)
    tr.record("recorded", 0.0, 0.1)
    events = {e["name"]: e for e in tr.to_chrome_doc()["traceEvents"]
              if e["ph"] == "X"}
    assert events["inner"]["parent"] == events["outer"]["seq"]
    assert events["outer"]["parent"] == 0
    assert 0 < events["inner"]["cpu_s"] <= events["outer"]["cpu_s"]
    assert events["recorded"]["cpu_s"] is None
    json.dumps(events)  # /trace serves it as JSON
    # the list of bare events went with its last caller
    assert not hasattr(tr, "to_chrome_trace")


def test_tracing_imports_and_records_with_jax_blocked():
    import subprocess
    import sys

    code = (
        "import sys; sys.modules['jax'] = None\n"
        "from edl_tpu.utils import tracing\n"
        "with tracing.span('a', k=1):\n    pass\n"
        "with tracing.step_span('b', 3):\n    pass\n"
        "tracing.tracer().record('c', 0.0, 0.1)\n"
        "assert [s.name for s in tracing.tracer().spans()] == list('abc')\n"
        "a, b, c = tracing.tracer().spans()\n"
        "assert a.cpu_s >= 0 and b.cpu_s >= 0 and c.cpu_s is None\n"
        "assert (a.parent, b.parent, c.parent) == (0, 0, 0)\n"
        "assert not hasattr(tracing, 'jax_profile')\n"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    subprocess.run([sys.executable, "-c", code], check=True, cwd=root)


def test_disabled_tracer_opens_no_annotation(tmp_path):
    tr = tracing.Tracer()
    tr.enabled = False
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("never"):
            pass
    finally:
        jax.profiler.stop_trace()
    assert tr.spans() == []
    assert not any(n[0] == "edl.never"
                   for n in _xplane_annotations(str(tmp_path)).values())


def test_train_loop_spans_nest_and_first_step_of_a_mesh_is_taken_apart(
        cpu_devices):
    tr = _trainer(devices=cpu_devices[:4])
    tr.start(linreg.init_params(jax.random.PRNGKey(0)), n_workers=4)
    tr.train_steps(_data_fn(64), 2)
    tr.request_rescale(2)
    tr.train_steps(_data_fn(64), 2)
    spans = tracing.tracer().spans()
    names = [s.name for s in spans]
    assert names.count("train.step") == 4
    assert names.count("train.data") == names.count("train.dispatch") == 4
    assert names.count("train.host_block") == 2
    step = next(s for s in spans if s.name == "train.step")
    for child in ("train.data", "train.dispatch"):
        c = next(s for s in spans if s.name == child)
        assert step.start_s <= c.start_s
        assert c.start_s + c.dur_s <= step.start_s + step.dur_s + 1e-6
    # 4 -> 2: the first step there is re-traced, lowered and compiled,
    # and the event says how long each took
    ev = tr.report.reshards[-1]
    assert (ev.from_workers, ev.to_workers) == (4, 2)
    assert ev.trace_s > 0 and ev.lower_s > 0 and ev.load_s > 0
    assert ev.trace_s + ev.lower_s + ev.load_s <= ev.recompile_s
    assert ev.cache_hit is False  # conftest: no persistent cache
    rec = tracing.tracer().spans("reshard.recompile")[-1]
    assert rec.attrs["trace_s"] == ev.trace_s
    assert rec.attrs["load_s"] == ev.load_s and rec.attrs["to_workers"] == 2


def test_first_step_of_a_mesh_the_job_has_had_builds_nothing(cpu_devices):
    """4 -> 2 -> 4 -> 2: the first visit of the 2-mesh traces, lowers and
    compiles its step; the returns to the 4-mesh and to the 2-mesh find
    theirs kept, and JAX reports no trace while their first step runs."""
    traced = []

    def on_duration(event, secs, **kw):
        if event.endswith("jaxpr_trace_duration"):
            traced.append(kw.get("fun_name"))

    tr = _trainer(devices=cpu_devices[:4])
    tr.start(linreg.init_params(jax.random.PRNGKey(0)), n_workers=4)
    data = _data_fn(64)
    tr.train_steps(data, 2)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        during_first_step = []
        for workers in (2, 4, 2):
            tr.request_rescale(workers)
            traced.clear()
            tr.train_steps(data, 1)  # the reshard and the first step
            during_first_step.append(list(traced))
            tr.train_steps(data, 1)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    first, *returns = tr.report.reshards
    assert (first.from_workers, first.to_workers) == (4, 2)
    assert first.step_reused is False
    assert first.trace_s > 0 and first.lower_s > 0 and first.load_s > 0
    assert "edl_train_step" in during_first_step[0]
    assert [(e.from_workers, e.to_workers) for e in returns] == [(2, 4), (4, 2)]
    for ev, seen in zip(returns, during_first_step[1:]):
        assert ev.step_reused is True
        assert (ev.trace_s, ev.lower_s, ev.load_s) == (0.0, 0.0, 0.0)
        assert ev.cache_hit is False  # nothing was loaded
        assert ev.recompile_s > 0.0  # still timed as the mesh's first
        assert seen == []
    builds = tracing.tracer().spans("reshard.build_mesh")
    assert [b.attrs["step_reused"] for b in builds] == [False, True, True]
    recs = tracing.tracer().spans("reshard.recompile")
    assert len(recs) == 3
    for rec, ev in zip(recs, tr.report.reshards):
        # the four attributes the benchmark's readers take, on a return too
        assert {"trace_s", "lower_s", "load_s", "cache_hit"} <= set(rec.attrs)
        assert rec.attrs["trace_s"] == ev.trace_s
        assert rec.attrs["load_s"] == ev.load_s
        assert rec.attrs["step_reused"] is ev.step_reused
        assert rec.dur_s == ev.recompile_s


# -- the engine step's host side --------------------------------------------


def _engine(**kw):
    from edl_tpu.models import llama
    from edl_tpu.serving.engine import ContinuousBatchingEngine

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return ContinuousBatchingEngine(params, cfg, max_slots=2, max_len=64,
                                    **kw)


def _children(step):
    return [s for s in tracing.tracer().spans() if s.parent == step.seq]


@pytest.mark.parametrize("admits", [True, False])
def test_engine_step_is_tiled_by_named_children(admits):
    """A step that admits and one that only decodes: ``serving.account``
    (twice: the step's gauges, and what a dispatch does before its
    program call) and ``serving.replay`` are children of
    ``serving.step`` by ``parent``, beside the spans that were there."""
    eng = _engine()
    eng.submit("r1", [2, 3, 4, 5], 12)
    eng.step()  # admits r1, dispatches block 1
    eng.step()  # dispatches block 2, drains block 1
    if admits:
        eng.submit("r2", [6, 7, 8], 5)
    tracing.tracer().clear()
    eng.step()
    step, = tracing.tracer().spans("serving.step")
    kids = _children(step)
    names = [s.name for s in sorted(kids, key=lambda s: s.seq)]
    assert names == (["serving.admit"] if admits else []) + [
        "serving.account", "serving.account", "serving.dispatch",
        "serving.drain", "serving.replay"]
    # the children tile the step: none overlaps the next
    ordered = sorted(kids, key=lambda s: s.start_s)
    for a, b in zip(ordered, ordered[1:]):
        assert a.start_s + a.dur_s <= b.start_s + 1e-6
    assert step.start_s <= ordered[0].start_s
    assert sum(s.dur_s for s in kids) <= step.dur_s
    replay = next(s for s in kids if s.name == "serving.replay")
    drain = next(s for s in kids if s.name == "serving.drain")
    assert replay.attrs["rids"] == drain.attrs["rids"] == ["r1"]
    assert replay.attrs["tokens"] == 1
    assert all(s.cpu_s is not None for s in kids)
    if admits:
        admit = next(s for s in kids if s.name == "serving.admit")
        assert {s.name for s in _children(admit)} == {
            "serving.queue", "serving.prefill"}
    # what was there keeps its attributes
    dispatch = next(s for s in kids if s.name == "serving.dispatch")
    assert dispatch.attrs["horizon"] == 1 and "kv_read_share" in dispatch.attrs
    assert dispatch.attrs["rids"] == (["r1", "r2"] if admits else ["r1"])
    eng.run()
    assert eng.results["r1"].outcome == "done"


def test_drain_all_replays_every_block_in_flight():
    eng = _engine()
    eng.submit("r1", [2, 3, 4, 5], 12)
    eng.step()
    eng._dispatch_block()
    assert len(eng._inflight) == 2
    tracing.tracer().clear()
    assert eng._drain_all() == 2
    names = [s.name for s in sorted(tracing.tracer().spans(),
                                    key=lambda s: s.seq)]
    assert names == ["serving.drain", "serving.replay"] * 2
    assert [s.attrs["tokens"]
            for s in tracing.tracer().spans("serving.replay")] == [1, 1]


def test_paged_and_speculative_dispatches_account_too():
    eng = _engine(block_size=16, spec_k=3)
    eng.submit("r1", [5, 6, 5, 6, 5, 6, 5, 6], 8)
    eng.run()
    assert eng.results["r1"].outcome == "done"
    spans = tracing.tracer().spans()
    by_seq = {s.seq: s for s in spans}
    for name in ("serving.account", "serving.replay", "serving.dispatch"):
        mine = [s for s in spans if s.name == name]
        assert mine
        assert all(by_seq[s.parent].name == "serving.step" for s in mine)
    # a verify dispatch prepares its draft matrix and table under the
    # same name
    verify = [s for s in spans if s.name == "serving.dispatch"
              and "spec_k" in s.attrs]
    assert verify
    before = by_seq[verify[0].seq - 1]
    assert before.name == "serving.account"
