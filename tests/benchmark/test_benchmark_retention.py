"""Family ``retention`` and its cell ``brumby14b.decode-state``: the
files hold to their source, the family's needed bytes and operations
are the arithmetic of ISSUE 35 to the byte, the cell rehearses through
the engine with its readers reporting, and the readers read what the
program writes (a fixture worked out by hand) and nothing where there
is nothing."""

import json
import os
import types

import pytest

from benchmark import harness, run
from benchmark.reduce import program

CELL = "brumby14b.decode-state"
BENCH = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
STATE = [m for m in BENCH["per_layer"] if m["name"].endswith(".state")]
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def family():
    return harness.Cell(CELL).family


def reader(name):
    return harness.load_module(os.path.join(
        harness.ROOT, "benchmark", "metrics", name + ".py"))


# -- the files ----------------------------------------------------------------


def test_configuration_keeps_published_widths():
    """``test_configuration_keeps_published_widths``'s rule
    (tests/benchmark/test_benchmark_files.py), applied to this
    configuration: every width equals the source's, ``reduced`` lists
    depth alone, within the family's floor."""
    entry = next(c for c in BENCH["configs"] if c["name"] == "brumby-14b-L8")
    conf = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
    pub = harness.load_json(os.path.join(
        harness.ROOT, "benchmark", "published",
        "manifestai.Brumby-14B-Base.json"))
    fam = family()
    assert conf["source"] == entry["source"] == pub["source"]
    for key in fam.widths:
        assert conf[key] == pub[key], key
    assert entry["reduced"] == sorted(conf["reduced"]) == ["num_hidden_layers"]
    assert fam.reducible == {"num_hidden_layers": 4}
    assert conf["published"] == {"num_hidden_layers": 40}
    assert 4 <= conf["num_hidden_layers"] == 8 < pub["num_hidden_layers"] == 40


def test_configuration_is_the_catalog_row_cut_in_depth_alone():
    cell = harness.Cell(CELL)
    pub = harness.load_json(os.path.join(
        harness.ROOT, "benchmark", "published",
        "manifestai.Brumby-14B-Base.json"))
    if os.path.exists(CATALOG):
        row = next(json.loads(l) for l in open(CATALOG)
                   if "Brumby-14B-Base" in l)
        assert {k: pub[k] for k in row["config"]} == row["config"]
        assert pub["source"] == row["source_url"]
    for key, value in pub.items():
        if key not in ("recorded", "num_hidden_layers"):
            assert cell.config[key] == value, key
    assert {"deployment", "assumed"} <= set(cell.config)
    # each size the source does not give is stated with its reason,
    # and the numbers among them are keys the program and the
    # reference both read
    assert set(cell.config["assumed"]) == {
        "retention_degree", "gate", "gate_draw", "norms_and_rope",
        "normaliser", "state_precision", "weights"}
    for key, value in cell.family.ASSUMED.items():
        assert cell.config[key] == value and key not in pub
    assert "four further pipeline stages" in cell.config["deployment"]
    assert cell.chips == 1 and cell.kind == "serve"
    assert cell.spec["engine"] == {
        "max_slots": 24, "max_len": 4096, "horizon": 1}
    assert cell.spec["check_requests"] == 4
    assert cell.spec["trace"] == {"at": "end", "seconds": 3.0}


def test_every_published_size_and_constant_is_a_width():
    fam = family()
    sizes = {k for k in fam.rehearsal_config()
             if k != "num_hidden_layers" and k not in fam.ASSUMED}
    assert sizes == set(fam.widths)


def test_traffic_is_the_issues():
    mix = harness.Cell(CELL).traffic
    assert (mix["loop"], mix["clients"], mix["cycle"], mix["order_seed"]) \
        == ("closed", 24, 72, 35)
    assert mix["prompt"] == {"median": 1024, "sigma": 0.5, "lo": 256,
                             "hi": 3072}
    assert mix["output"] == {"median": 512, "sigma": 0.5, "lo": 256,
                             "hi": 1023}
    assert mix["prompt"]["hi"] + mix["output"]["hi"] < 4096


def test_the_entries_are_appended_and_name_one_cell():
    assert BENCH["workloads"][-1]["name"] == CELL
    assert BENCH["workloads"][-1]["chips"] == 1
    assert BENCH["configs"][-1]["name"] == "brumby-14b-L8"
    tokens = next(m for m in BENCH["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert tokens["workloads"][-1] == CELL and tokens["bound"] == 0.01
    assert [m["name"] for m in BENCH["per_layer"][-len(STATE):]] == [
        m["name"] for m in STATE]
    assert {m["name"] for m in STATE} == {
        "block_device_ms.state", "itl_p50_ms.state",
        "prefill_device_share.state", "attn_time_share.state",
        "retention_time_share.state", "decode_hbm_share.state",
        "state_update_roofline.state", "retention_chunk_roofline.state",
        "state_live_share.state"}
    for m in STATE:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "serve_tokens_per_s"


@pytest.mark.parametrize("training", [False, True])
def test_program_config_reads_the_published_keys(training):
    cell = harness.Cell(CELL)
    if training:
        with pytest.raises(NotImplementedError, match="served, not trained"):
            cell.family.program_config(cell.config, training=True)
        return
    cfg = cell.family.program_config(cell.config, training=False)
    assert (cfg.vocab, cfg.d_model, cfg.n_layers, cfg.d_ff) == (
        151936, 5120, 8, 17408)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (40, 8, 128)
    assert (cfg.rope_theta, cfg.norm_eps) == (1e6, 1e-6)
    assert (cfg.gate_bias, cfg.eps) == (6.93, 1e-6)
    assert cfg.use_kernel and cfg.dtype.__name__ == "bfloat16"


# -- the arithmetic of ISSUE 35's Motivation, to the byte ----------------------


def test_the_cut_holds_the_bytes_the_configuration_states():
    import numpy as np

    cell = harness.Cell(CELL)
    layer = sum(int(np.prod(shape[1:])) for path, (shape, _, _)
                in cell.layout.items() if path[0] == "layers")
    # wq 26.21 M, wk + wv 10.49 M, wo 26.21 M, the SwiGLU 267.39 M,
    # gate and norms 0.05 M: 330.35 M = 0.661 GB in bf16
    assert layer == (2 * 5120 * 5120 + 2 * 5120 * 1024 + 3 * 5120 * 17408
                     + 5120 * 8 + 8 + 2 * 5120 + 2 * 128)
    assert round(layer / 1e6, 2) == 330.35
    assert round(2 * layer / 1e9, 3) == 0.661
    n = sum(int(np.prod(shape)) for shape, _, _ in cell.layout.values())
    assert n == 8 * layer + 2 * 151936 * 5120 + 5120
    assert round(n / 1e9, 3) == 4.199 and round(2 * n / 1e9, 2) == 8.40
    # the program prices itself with the same count
    cfg = cell.family.program_config(cell.config, training=False)
    assert cfg.n_params() == n


def test_needed_counts_are_the_issues_arithmetic():
    cell = harness.Cell(CELL)
    needed, config = cell.family.needed, cell.config
    # D = 128 x 129 / 2 = 8256; 8 kv heads x D x 128 numbers a layer
    assert cell.family.state_width(config) == 8256
    a_layer = 8 * 8256 * 128 * 4
    assert round(a_layer / 1e6, 1) == 33.8
    assert needed.state_bytes_per_slot(config) == 8 * a_layer
    assert round(24 * needed.state_bytes_per_slot(config) / 1e9, 2) == 6.49
    # a step of 24 live slots: 5.29 GB of layer weights + 1.56 GB of
    # head once, the state read and written: 19.8 GB, 65% of it state
    weights = needed.weight_bytes(config)
    assert weights == needed.decode_step_bytes(config, 0.0)
    assert round((weights - 2 * 5120 * 151936) / 1e9, 2) == 5.29
    assert round(2 * 5120 * 151936 / 1e9, 2) == 1.56
    step = needed.decode_step_bytes(config, 24)
    assert step == weights + 2 * 24 * 8 * a_layer
    assert round(step / 1e9, 1) == 19.8
    assert round(1e3 * step / 819e9, 1) == 24.2
    assert round(100 * (step - weights) / step) == 65
    # half the slots live move half the state
    assert needed.decode_step_bytes(config, 12) - weights \
        == (step - weights) / 2
    # 2 x 8256 x 128 x (8 + 40) = 101 MFLOP a token a layer
    a_token = needed.retention_chunk_flops(config, 1) / 8
    assert a_token == 2 * 8256 * 128 * 48
    assert round(a_token / 1e6) == 101
    assert needed.retention_chunk_flops(config, 4096) == 4096 * 8 * a_token
    # weights, state and the chip: 87% before temporaries
    assert round(100 * (2 * 4199101440 + 24 * 8 * a_layer)
                 / (16 * 2 ** 30)) == 87


def test_what_the_program_stores_is_stated_beside_what_is_needed():
    """The needed bytes are counted at D = 8256 whatever the program
    stores: it stores 65 rows of 128 (8320, +0.8%) and ``z`` beside."""
    cell = harness.Cell(CELL)
    cfg = cell.family.program_config(cell.config, training=False)
    needed = cell.family.needed.state_bytes_per_slot(cell.config)
    stored = cfg.state_bytes_per_slot()
    assert stored == 8 * 8 * 129 * 8320 * 4
    assert 1.015 < stored / needed < 1.017
    spec = cfg.serve_cache_spec(24, 4096)
    assert [shape for shape, _ in spec] == [
        (8, 24, 8, 128, 8320), (8, 24, 8, 8320)]
    assert all(str(dtype.__name__) == "float32" for _, dtype in spec)


# -- the rehearsal ------------------------------------------------------------


def rehearse(capsys, *argv):
    code = run.main(["--rehearse", "--workload", CELL, *argv])
    lines = capsys.readouterr().out.strip().splitlines()
    return code, json.loads(lines[-1]), lines


@pytest.mark.parametrize("trace, reported", [
    (0, {"serve_tokens_per_s", "setup_s"}),
    # the counts are the program's own; times, shares of the device and
    # of a peak are the chip's to report
    (1, {"itl_p50_ms.state", "state_live_share.state"}),
])
def test_the_cell_rehearses_through_the_engine(capsys, trace, reported):
    code, line, lines = rehearse(
        capsys, "--seed", "3000000019", "--seconds", "2", "--trace",
        str(trace))
    assert code == 0
    assert line["correct"] is True, [l for l in lines if "compared" in l]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"rehearsal." + m for m in reported}
    if trace:
        live = line["metrics"]["rehearsal.state_live_share.state"]["value"]
        assert 0.0 < live <= 1.0


def served_gaps(seed, dtype):
    """At each position of one sequence, how far the token that the
    lower precision puts first lies under the reference's best."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import retention as reference

    fam = family()
    config = fam.rehearsal_config()
    params = harness.make_params(seed, fam.param_layout(config), jnp.float32)
    tokens = jnp.asarray(
        np.random.default_rng(seed).integers(0, 256, (64,), dtype=np.int32))
    ref = reference.logits_row(params, tokens, config)
    with reference.operands_rounded_to(dtype):
        low = jax.jit(lambda p, t: reference.logits_row(p, t, config))(
            params, tokens)
    first = jnp.argmax(low, axis=-1)
    gap = jnp.max(ref, -1) - jnp.take_along_axis(ref, first[:, None], 1)[:, 0]
    return float(gap.max()), float(gap.mean())


@pytest.mark.parametrize("seed", [1, 2])
def test_serving_control_fails_and_the_stated_precision_passes(seed):
    """A precision below the configuration's (float8 operands) fails
    the rehearsal's limits, bfloat16 passes both."""
    import jax.numpy as jnp

    cell = harness.Cell(CELL)
    cell.for_rehearsal()
    lim = cell.limits
    worst, mean = served_gaps(seed, jnp.bfloat16)
    assert worst <= lim["served_token_gap_max"]
    assert mean <= lim["served_token_gap_mean"]
    worst, mean = served_gaps(seed, jnp.float8_e4m3fn)
    assert mean > 3 * lim["served_token_gap_mean"]


# -- the readers --------------------------------------------------------------


def span(seq, name, **attrs):
    return types.SimpleNamespace(seq=seq, name=name, start_s=float(seq),
                                 dur_s=0.001, attrs=attrs)


RING = {s.seq: s for s in [
    span(1, "serving.dispatch", horizon=1, rids=["warm-512"],
         state_live_share=1 / 24),
    span(2, "serving.dispatch", horizon=1, rids=["warm-512", "q1"],
         state_live_share=0.5),
    span(3, "serving.dispatch", horizon=1, rids=["q1", "q2"],
         state_live_share=1.0),
    span(4, "serving.dispatch", horizon=1, rids=["q3"]),
]}

OP = "jit(edl_serve_block)/while/body/closed_call/"
PRE = "jit(edl_serve_prefill_512)/while/body/closed_call/"
# a 20,000 ns window: one prefill of 6000 ns and two blocks of 4000
PLANES = {"/device:TPU:0": {
    "XLA Modules": [
        ("jit_edl_serve_prefill_512(7)", 0, 6000, {}),
        ("jit_edl_serve_block(9)", 6000, 10000, {}),
        ("jit_edl_serve_block(9)", 10000, 14000, {}),
    ],
    "XLA Ops": [
        ("%fusion.9 = fusion()", 0, 1000, {"tf_op": PRE + "attn/dot_general:"}),
        ("%k = custom-call() tpu_custom_call edl_retention_chunk", 1000, 3000,
         {"tf_op": PRE + "attn/attn.retention_chunk/while/body/"
          "edl_retention_chunk:"}),
        ("%fusion.5 = fusion()", 3000, 3500,
         {"tf_op": PRE + "attn/attn.retention_chunk/while/body/dot_general:"}),
        ("%fusion.6 = fusion()", 3500, 5000, {"tf_op": PRE + "mlp/dot:"}),
        ("%fusion.8 = fusion()", 5000, 6000,
         {"tf_op": "jit(edl_serve_prefill_512)/head/dot:"}),
        # block one: a while that holds everything
        ("%while.1 = while()", 6000, 10000, {"tf_op": OP[:-18] + ":"}),
        ("%fusion.1 = fusion()", 6000, 6500,
         {"tf_op": OP + "attn/dot_general:"}),
        ("%k = custom-call() tpu_custom_call edl_retention_step", 6500, 8500,
         {"tf_op": OP + "attn/attn.retention_step/edl_retention_step:"}),
        ("%fusion.2 = fusion()", 8500, 9000,
         {"tf_op": OP + "attn/attn.retention_step/mul:"}),
        ("%fusion.3 = fusion()", 9000, 10000, {"tf_op": OP + "mlp/dot:"}),
        # block two
        ("%k = custom-call() tpu_custom_call edl_retention_step", 10000,
         12500,
         {"tf_op": OP + "attn/attn.retention_step/edl_retention_step:"}),
        ("%fusion.3 = fusion()", 12500, 13500, {"tf_op": OP + "mlp/dot:"}),
        ("%fusion.4 = fusion()", 13500, 14000, {"tf_op": OP + "head/argmax:"}),
    ]},
    "/host:CPU": {}}

# a token every 30 ms, once behind a prefill
GAPS = (0.03, 0.03, 0.09, 0.03, 0.03)


def a_run(device=TPU, gaps=GAPS):
    cell = harness.Cell(CELL)
    cell.name = "no-such-cell"  # no trace of its own on the disk
    return {"cell": cell, "config": cell.config,
            "trace": {"window_s": 20e-6}, "device": device,
            "spans": {"itl_s": list(gaps)}, "counters": {}}


def expected():
    cell = harness.Cell(CELL)
    needed, config = cell.family.needed, cell.config
    live = 24 * (0.5 + 1.0) / 2  # the two blocks that carry the window
    hbm = 819e9
    return {
        "block_device_ms.state": 4000 / 1e6,
        "itl_p50_ms.state": 30.0,
        "prefill_device_share.state": 100 * 6000 / 20000,
        # attn: 1000 + 2000 + 500 of the prefill; 500 + 2000 + 500 and
        # 2500 of the blocks
        "attn_time_share.state": 100 * 9000 / 20000,
        "retention_time_share.state": 100 * (2500 + 2500 + 2500) / 20000,
        "decode_hbm_share.state":
            100 * needed.decode_step_bytes(config, live) / (4000e-9 * hbm),
        "state_update_roofline.state":
            100 * 2 * 2 * live * needed.state_bytes_per_slot(config)
            / (5000e-9 * hbm),
        "retention_chunk_roofline.state":
            100 * needed.retention_chunk_flops(config, 512)
            / (2500e-9 * 197e12),
        "state_live_share.state": 0.75,
    }


@pytest.mark.parametrize("metric", [m["name"] for m in STATE])
def test_reader_reads_the_fixture(metric, monkeypatch):
    monkeypatch.setattr(program, "planes_of", lambda run: PLANES)
    monkeypatch.setattr(program, "ring", lambda: (RING, 0.0))
    got = reader(metric).read(a_run())
    assert got == pytest.approx(expected()[metric], rel=1e-9)


@pytest.mark.parametrize("metric", [m["name"] for m in STATE])
def test_reader_returns_none_without_a_chip(metric, monkeypatch):
    """No trace and an empty ring (the parent's program, a ``--trace
    0`` run): nothing to read, and nothing raised. With a CPU's record
    the shares of a peak stay unreported whatever the ring holds."""
    monkeypatch.setattr(program, "planes_of", lambda run: None)
    monkeypatch.setattr(program, "ring", lambda: ({}, 0.0))
    cpu = {"platform": "cpu", "kind": "cpu", "count": 1}
    bare = a_run(cpu, gaps=())
    bare["trace"] = None
    assert reader(metric).read(bare) is None
    if metric not in ("itl_p50_ms.state", "state_live_share.state"):
        monkeypatch.setattr(program, "ring", lambda: (RING, 0.0))
        assert reader(metric).read(a_run(cpu)) is None


def test_a_share_of_a_peak_cannot_pass_100_on_the_fixtures_terms():
    """The step's needed bytes at 24 live slots over the HBM peak are
    24.2 ms: a block faster than that would read over 100%, and the
    reader does not clip it."""
    cell = harness.Cell(CELL)
    need = cell.family.needed.decode_step_bytes(cell.config, 24)
    assert need / 819e9 > 0.0241
