"""The four per-layer metrics of the ragged decode attention
(``attn_time_share.*`` from the device trace's ``attn`` scope,
``kv_read_share.*`` from the engine's ``serving.dispatch`` spans), on
recorded fixtures against numbers worked out by hand, and on what a
program without the scope or the attribute writes."""

import json
import os
import types

import pytest

from benchmark import harness
from benchmark.reduce import program, serving

FIXTURE = os.path.join(os.path.dirname(program.__file__),
                       "fixture_program.json")
METRICS = ("attn_time_share.decode", "attn_time_share.open",
           "kv_read_share.decode", "kv_read_share.open")
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


@pytest.fixture(scope="module")
def planes():
    with open(FIXTURE) as f:
        raw = json.load(f)["planes"]
    return {p: {l: [tuple(e) for e in evs] for l, evs in lines.items()}
            for p, lines in raw.items()}


def span(seq, name, **attrs):
    return types.SimpleNamespace(seq=seq, name=name, start_s=float(seq),
                                 dur_s=0.001, attrs=attrs)


# two warm-up blocks, then the window's: one still shared with a
# warm-up request, one of a program that has no such attribute
RING = {s.seq: s for s in [
    span(1, "serving.dispatch", horizon=1, rids=["warm-64"],
         kv_read_share=0.03125),
    span(2, "serving.dispatch", horizon=1, rids=["warm-64", "warm-128"],
         kv_read_share=0.0625),
    span(3, "serving.drain", rids=["warm-64"]),
    span(4, "serving.dispatch", horizon=1, rids=["warm-128", "q1"],
         kv_read_share=0.25),
    span(5, "serving.dispatch", horizon=1, rids=["q1", "q2"],
         kv_read_share=0.5),
    span(6, "serving.dispatch", horizon=1, rids=["q2"], kv_read_share=0.75),
    span(7, "serving.dispatch", horizon=1, rids=["q3"]),
]}


def reader(name):
    return harness.load_module(os.path.join(
        harness.ROOT, "benchmark", "metrics", name + ".py"))


def a_run(device=TPU):
    return {"cell": types.SimpleNamespace(name="no-such-cell"),
            "trace": {"window_s": 20e-6}, "spans": {}, "counters": {},
            "device": device}


@pytest.mark.parametrize("name", METRICS)
def test_reader_is_declared_with_its_one_cell(name):
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    cell = ("deepseek7b.decode-closed" if name.endswith(".decode")
            else "mistral7b.serve-open")
    assert entry["workloads"] == [cell]
    assert entry["better"] == "lower"
    moved = next(m for m in bench["end_to_end"] if m["name"] == entry["moves"])
    assert cell in moved["workloads"]
    assert entry in harness.Cell(cell).per_layer()


@pytest.mark.parametrize("name", METRICS)
def test_reader_finds_nothing_in_an_empty_run(name, monkeypatch):
    # no trace was written for this cell, and the ring has nothing
    monkeypatch.setattr(program, "ring", lambda: ({}, 0.0))
    assert reader(name).read(a_run()) is None
    run = a_run()
    run["trace"] = None
    assert reader(name).read(run) is None


@pytest.mark.parametrize("name", METRICS)
def test_reader_finds_nothing_in_what_the_parent_writes(name, monkeypatch):
    """A program whose operations carry no scope and whose dispatch
    spans say nothing of the cache."""
    old = {"/device:TPU:0": {
        "XLA Modules": [("jit_run(1)", 0, 100, {})],
        "XLA Ops": [("%fusion.1 = f32[] fusion()", 0, 100,
                     {"tf_op": "jit(run)/jit(main)/while/body/dot_general:"})]},
        "/host:CPU": {}}
    monkeypatch.setattr(program, "planes_of", lambda run: old)
    monkeypatch.setattr(program, "ring", lambda: ({7: RING[7]}, 0.0))
    assert reader(name).read(a_run()) is None


@pytest.mark.parametrize("name, value", [
    # the kernel's 2000 ns under attn in a 20000 ns window
    ("attn_time_share.decode", 10.0),
    ("attn_time_share.open", 10.0),
    # the three blocks that carry a request of the window: the mean of
    # 0.25, 0.5 and 0.75
    ("kv_read_share.decode", 0.5),
    ("kv_read_share.open", 0.5),
])
def test_reader_on_the_fixture(name, value, planes, monkeypatch):
    monkeypatch.setattr(program, "planes_of", lambda run: planes)
    monkeypatch.setattr(program, "ring", lambda: (RING, 0.0))
    assert reader(name).read(a_run()) == pytest.approx(value)


def test_kv_read_share_is_the_chips_to_report(monkeypatch):
    monkeypatch.setattr(program, "ring", lambda: (RING, 0.0))
    cpu = {"platform": "cpu", "kind": "cpu", "count": 1}
    assert serving.kv_read_share(a_run(cpu)) is None
    assert serving.kv_read_share(a_run()) == pytest.approx(0.5)


def test_the_engine_writes_what_the_reader_reads():
    """A contiguous engine's ``serving.dispatch`` spans carry
    ``kv_read_share``: 1.0 from the dense program, the live S-blocks
    over all of them from the ``use_flash`` one."""
    import jax

    from edl_tpu.models import llama
    from edl_tpu.serving.engine import ContinuousBatchingEngine
    from edl_tpu.utils import tracing

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    eng = ContinuousBatchingEngine(params, cfg, max_slots=4, max_len=64)
    assert eng._attn_block == 64 and eng._kv_read_share() == 1.0
    eng.submit("a", list(range(2, 19)), 4)  # 17 tokens: two blocks of 16
    eng.submit("b", list(range(2, 7)), 4)
    before = len(tracing.tracer().spans("serving.dispatch"))
    eng.run()
    mine = tracing.tracer().spans("serving.dispatch")[before:]
    assert mine and all(s.attrs["kv_read_share"] == 1.0 for s in mine)
    # the same slot table read in blocks of 16 positions: a (17 + 4
    # tokens at most) holds 2 blocks, b 1, the two idle slots 1 each
    eng = ContinuousBatchingEngine(params, cfg, max_slots=4, max_len=64)
    eng._attn_block = 16
    eng.submit("a", list(range(2, 19)), 4)
    eng.submit("b", list(range(2, 7)), 4)
    eng.step()
    assert eng._kv_read_share() == pytest.approx((2 + 1 + 1 + 1) / 16)
