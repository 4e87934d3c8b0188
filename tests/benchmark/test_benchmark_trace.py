"""The trace reduction on a small recorded trace, against numbers
worked out by hand."""

import json
import os

import pytest

from benchmark.reduce import trace

FIXTURE = os.path.join(os.path.dirname(trace.__file__), "fixture_trace.json")


@pytest.fixture(scope="module")
def planes():
    with open(FIXTURE) as f:
        raw = json.load(f)["planes"]
    return {p: {l: [tuple(e) for e in evs] for l, evs in lines.items()}
            for p, lines in raw.items()}


def test_busy_idle_and_modules(planes):
    s = trace.summarize(planes, window_s=15e-6)
    # busy: [1000, 9000] and [12000, 14000] -> 10000 ns of a 15000 ns window
    assert s["busy_s"] == pytest.approx(10e-6)
    assert s["window_s"] == 15e-6
    assert s["modules_run"] == 2
    assert s["chips_traced"] == 1


def test_self_time_takes_nested_operations_out(planes):
    s = trace.summarize(planes, window_s=15e-6)
    ops = dict(s["device_ops"])
    # the while spans 6000 ns and holds 2000 + 3000 ns of fusions
    assert ops["while.1"] == pytest.approx(1e-6)
    # fusion.1 runs twice: 2000 ns inside the while, 2000 ns later
    assert ops["fusion.1"] == pytest.approx(4e-6)
    assert s["device_ops"][0][0] == "fusion.1"
    assert s["mosaic_s"] == pytest.approx(1e-6)
    assert s["collective_s"] == pytest.approx(1e-6)


def test_gap_is_named_by_the_host_span_that_covers_it(planes):
    s = trace.summarize(planes, window_s=15e-6)
    # idle from 9000 to 12000: bench.rescale covers 2300 of it,
    # bench.train_steps 500 + 200
    assert s["idle_gaps"] == [["bench.rescale", pytest.approx(3e-6)]]
    assert s["longest_gap_s"] == pytest.approx(3e-6)


def test_union_and_short_names():
    assert trace.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert trace.short("%fusion.2 = bf16[4] fusion(%a)") == "fusion.2"
    assert trace.is_collective("%all-gather-start.1 = (f32[8]) all-gather-start()")
    assert not trace.is_collective("%fusion.9 = f32[8] fusion(%all-gather.2)")


def test_load_reads_a_trace_the_profiler_wrote(tmp_path):
    import jax
    import jax.numpy as jnp

    out = str(tmp_path / "trace")
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("bench.train_steps"):
        jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = trace.find_xplane(out)
    assert path is not None
    planes = trace.load(path)
    assert any(ev[0] == "bench.train_steps" for ev in trace.host_spans(planes))
    # no TPU plane on this machine: nothing ran on a device
    assert trace.summarize(planes, 1.0)["busy_s"] == 0.0
