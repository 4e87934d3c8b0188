"""benchmark/reduce/program.py and the readers built on it, on a small
recorded fixture of their own, against numbers worked out by hand."""

import json
import os
import types

import pytest

from benchmark import harness
from benchmark.reduce import program

FIXTURE = os.path.join(os.path.dirname(program.__file__),
                       "fixture_program.json")
NEW_METRICS = (
    "reshard_retrace_s", "reshard_program_load_s", "block_device_ms.decode",
    "block_device_ms.open", "prefill_device_share.open", "sched_wait_p95_ms",
    "mlp_time_share.train", "head_time_share.train")


@pytest.fixture(scope="module")
def planes():
    with open(FIXTURE) as f:
        raw = json.load(f)["planes"]
    return {p: {l: [tuple(e) for e in evs] for l, evs in lines.items()}
            for p, lines in raw.items()}


def span(seq, name, start_s, dur_s, **attrs):
    return types.SimpleNamespace(seq=seq, name=name, start_s=start_s,
                                 dur_s=dur_s, attrs=attrs)


# the ring of the process that wrote the fixture's host plane: its
# timebase is 100 s on perf_counter, and the profiler's zero lies at
# 107 s of it, so offset_ns = -107e9. Requests q1..q4 were popped before
# the session began (seq 14 is the first span in it), q5 inside it.
T0 = 100.0
RING = {s.seq: s for s in [
    span(3, "serving.queue", 1.0, 0.010, rid="warm-64"),
    span(5, "serving.queue", 2.0, 0.020, rid="q1"),
    span(7, "serving.queue", 3.0, 0.200, rid="q2"),
    span(9, "serving.queue", 4.0, 0.030, rid="q3"),
    span(11, "serving.queue", 5.0, 0.040, rid="q4"),
    span(12, "serving.step", 7.0005, 0.0004),
    span(13, "serving.admit", 7.00051, 0.00019),
    # recorded after the fact: the annotation marks the pop, the span
    # began a wait earlier, so it joins by seq and gives no offset
    span(14, "serving.queue", 6.5, 0.50052, rid="q5"),
    span(15, "serving.step", 7.00095, 0.00004),
    span(16, "train.step", 7.001, 0.0004),
    span(17, "reshard.recompile", 8.0, 3.0, to_workers=2),
    span(18, "reshard.recompile", 12.0, 2.5, to_workers=2, trace_s=0.5,
         lower_s=0.25, load_s=1.0, cache_hit=True),
    span(19, "reshard.recompile", 16.0, 2.0, to_workers=4, trace_s=0.25,
         lower_s=0.25, load_s=0.5, cache_hit=True),
]}


def test_programs_by_name(planes):
    assert program.program_name("jit_edl_serve_block(9)") == "edl_serve_block"
    assert program.program_name("jit__step(1)") == "_step"
    times = program.module_times(planes)
    assert times["edl_serve_block"] == [2000, 2500, 2000]
    assert times["edl_serve_prefill_512"] == [4000]
    assert times["edl_train_step"] == [11600]
    assert program.chips_traced(planes) == 1


@pytest.mark.parametrize("path, scope", [
    ("jit(edl_train_step)/jvp()/while/body/closed_call/mlp/dot_general:", "mlp"),
    ("jit(edl_train_step)/transpose(jvp(head))/dot_general:", "head"),
    ("jit(edl_serve_block)/while/body/attn/jit(_where)/select_n:", "attn"),
    ("jit(edl_train_step)/jvp(loss)/jit(take_along_axis)/gather:", "loss"),
    ("jit(edl_train_step)/optimizer/mul:", "optimizer"),
    ("jit(edl_train_step)/jvp()/while:", None),
    ("jit(mlp_probe)/dot_general:", None),  # a function, not a scope
    ("", None),
])
def test_scope_of_an_op_name(path, scope):
    assert program.scope_of(path) == scope


def test_scope_self_time_with_a_nested_while(planes):
    by = program.scope_self_times(planes)
    # the while spans 8000 ns and holds 3000 (mlp) + 2000 (attn) + 2500
    # (mlp) of its body: 500 ns are its own, under no phase, as is the
    # 1000 ns copy that has no op_name at all
    assert by["mlp"] == pytest.approx(5.5e-6)
    assert by["attn"] == pytest.approx(2e-6)
    assert by["head"] == pytest.approx(1e-6)
    assert by["loss"] == pytest.approx(0.4e-6)
    assert by["optimizer"] == pytest.approx(1e-6)
    assert by["embed"] == pytest.approx(0.2e-6)
    assert by[""] == pytest.approx(1.5e-6)
    assert sum(by.values()) == pytest.approx(11.6e-6)  # the module's time


def test_the_seq_join_and_the_session_start(planes):
    joined = program.join(planes, RING, T0)
    assert joined["session_seq"] == 12
    assert joined["annotations"] == 5
    # serving.queue's annotation is a mark: four spans give the offset
    assert joined["joined"] == 4
    assert joined["offset_ns"] == pytest.approx(-107e9, abs=2.0)
    # a trace without the program's annotations joins to nothing
    bare = {"/host:CPU": {"python3": [("bench.submit", 0, 10, {})]}}
    assert program.join(bare, RING, T0) is None


def test_metadata_stat_is_read_from_the_wire_format():
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out

    def field(number, value):
        if isinstance(value, int):
            return varint(number << 3) + varint(value)
        return varint(number << 3 | 2) + varint(len(value)) + value

    def entry(key, message):  # one entry of a map<int64, message>
        return field(1, key) + field(2, message)

    stat_names = {1: b"flops", 2: b"tf_op", 3: b"jit(f)/mlp/dot:"}
    plane = field(2, b"/device:TPU:0")
    for key, name in stat_names.items():
        plane += field(5, entry(key, field(1, key) + field(2, name)))
    # an inline string, a string kept by reference, and no tf_op at all
    plane += field(4, entry(10, field(1, 10) + field(2, b"%fusion.1")
                            + field(5, field(1, 1) + field(3, 99))
                            + field(5, field(1, 2) + field(5, b"jit(f)/attn/exp:"))))
    plane += field(4, entry(11, field(1, 11) + field(2, b"%fusion.2")
                            + field(5, field(1, 2) + field(7, 3))))
    plane += field(4, entry(12, field(1, 12) + field(2, b"%copy.3")
                            + field(5, field(1, 1) + field(3, 7))))
    plane += field(3, field(2, b"XLA Ops"))  # a line: skipped whole
    other = field(2, b"/host:CPU") + field(5, entry(1, field(1, 1) + field(2, b"seq")))
    xspace = field(1, plane) + field(1, other)
    assert program.metadata_stat(xspace, "tf_op") == {"/device:TPU:0": {
        "%fusion.1": "jit(f)/attn/exp:", "%fusion.2": "jit(f)/mlp/dot:"}}


def test_load_reads_the_programs_annotations_with_their_seq(tmp_path):
    import jax
    import jax.numpy as jnp

    from edl_tpu.utils import tracing

    out = str(tmp_path / "trace")
    jax.profiler.start_trace(out)
    with tracing.span("serving.step"):
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    planes = program.load(program.trace.find_xplane(out))
    notes = program.annotations(planes)
    assert [n[0] for n in notes] == ["edl.serving.step"]
    ring, t0 = program.ring()
    assert ring[notes[0][3]["seq"]].name == "serving.step"
    joined = program.join(planes, ring, t0)
    assert joined["session_seq"] == notes[0][3]["seq"]
    assert joined["joined"] == 1
    # no device plane on this machine: nothing to say about programs
    assert program.module_times(planes) == {} and not program.chips_traced(planes)


# -- the eight readers ------------------------------------------------------


def reader(name):
    return harness.load_module(os.path.join(
        harness.ROOT, "benchmark", "metrics", name + ".py"))


def a_run(**spans):
    return {"cell": types.SimpleNamespace(name="no-such-cell"),
            "trace": {"window_s": 20e-6}, "spans": spans, "counters": {}}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_finds_nothing_in_an_empty_run(name, monkeypatch):
    # no trace was written for this cell, and the ring has nothing
    monkeypatch.setattr(program, "ring", lambda: ({}, 0.0))
    assert reader(name).read(a_run()) is None
    run = a_run()
    run["trace"] = None
    assert reader(name).read(run) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_finds_nothing_in_what_the_parent_writes(name, monkeypatch):
    """A program without the names and spans: modules called jit_run,
    no op_name scopes, no edl.* annotations, a reshard.recompile span
    that does not say what it was made of."""
    old = {"/device:TPU:0": {
        "XLA Modules": [("jit_run(1)", 0, 100, {}), ("jit__step(2)", 100, 900, {})],
        "XLA Ops": [("%fusion.1 = f32[] fusion()", 0, 100,
                     {"tf_op": "jit(run)/jit(main)/while/body/dot_general:"})]},
        "/host:CPU": {}}
    monkeypatch.setattr(program, "planes_of", lambda run: old)
    monkeypatch.setattr(program, "ring", lambda: ({17: RING[17]}, T0))
    assert reader(name).read(a_run(recompile_s=[3.0])) is None


@pytest.mark.parametrize("name, value", [
    # the two newest reshard.recompile spans: (0.5 + 0.25 + 0.25 + 0.25) / 2
    ("reshard_retrace_s", 0.625),
    ("reshard_program_load_s", 0.75),
    # median of 2000, 2500, 2000 ns
    ("block_device_ms.decode", 0.002),
    ("block_device_ms.open", 0.002),
    # 4000 ns of prefill in a 20000 ns window
    ("prefill_device_share.open", 20.0),
    # q1..q4 (20, 200, 30, 40 ms) were popped before seq 12; the warm-up
    # request and q5 are left out: nearest rank of 0.95 x 4 is the 4th
    ("sched_wait_p95_ms", 200.0),
    # 5500 ns of mlp, 1000 + 400 ns of head and loss, in 20000 ns
    ("mlp_time_share.train", 27.5),
    ("head_time_share.train", 7.0),
])
def test_reader_on_the_fixture(name, value, planes, monkeypatch):
    monkeypatch.setattr(program, "planes_of", lambda run: planes)
    monkeypatch.setattr(program, "ring", lambda: (RING, T0))
    run = a_run(recompile_s=[2.5, 2.0])
    assert reader(name).read(run) == pytest.approx(value)
    assert name in {m["name"] for m in harness.load_json(os.path.join(
        harness.ROOT, "BENCHMARK.json"))["per_layer"]}
