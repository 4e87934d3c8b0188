"""Family ``ssm_hybrid`` and its cell ``granite4h.decode-hybrid``: the
files hold to their source (the catalog row, uncut), the family's
needed bytes and operations are the arithmetic of ISSUE 37 to the byte,
the cell rehearses through the engine with its readers reporting, and
the readers read what the program writes (a fixture worked out by hand)
and nothing where there is nothing."""

import json
import os
import types

import pytest

from benchmark import harness, run
from benchmark.reduce import program

CELL = "granite4h.decode-hybrid"
CONF = "granite4h-micro"
BENCH = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
HYBRID = [m for m in BENCH["per_layer"] if m["name"].endswith(".hybrid")]
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PUBLISHED = os.path.join(harness.ROOT, "benchmark", "published",
                         "ibm-granite.granite-4.0-h-micro.json")


def family():
    return harness.Cell(CELL).family


def reader(name):
    return harness.load_module(os.path.join(
        harness.ROOT, "benchmark", "metrics", name + ".py"))


# -- the files ----------------------------------------------------------------


def test_configuration_is_the_catalog_row_uncut():
    """Every published key equals the source's, ``layer_types`` whole;
    ``reduced`` is empty and says so."""
    entry = next(c for c in BENCH["configs"] if c["name"] == CONF)
    cell = harness.Cell(CELL)
    pub = harness.load_json(PUBLISHED)
    assert cell.config["source"] == entry["source"] == pub["source"]
    if os.path.exists(CATALOG):
        row = next(json.loads(l) for l in open(CATALOG)
                   if '"granite-4.0-h-micro"' in l)
        assert {k: pub[k] for k in row["config"]} == row["config"]
        assert pub["source"] == row["source_url"]
    for key, value in pub.items():
        if key != "recorded":
            assert cell.config[key] == value, key
    assert entry["reduced"] == sorted(cell.config["reduced"]) == []
    assert cell.config["published"] == {}
    assert cell.config["num_hidden_layers"] == 40 == len(
        cell.config["layer_types"])
    assert [i for i, k in enumerate(cell.config["layer_types"])
            if k == "attention"] == [5, 15, 25, 35]
    assert "nothing is cut" in cell.config["uncut"]
    assert "3,191,396,096 parameters" in cell.config["uncut"]
    assert "the whole model on one chip" in cell.config["deployment"]
    # each size the source does not give is stated with its reason, and
    # the numbers among them are keys the family reads
    assert set(cell.config["assumed"]) == {
        "state_precision", "dt_limit", "head_dim", "stored_layouts", "draw"}
    for key, value in cell.family.ASSUMED.items():
        assert cell.config[key] == value and key not in pub
    assert cell.family.reducible == {"num_hidden_layers": 14,
                                     "layer_types": 14}
    assert cell.chips == 1 and cell.kind == "serve"
    assert cell.spec["engine"] == {
        "max_slots": 72, "max_len": 4096, "horizon": 1}
    assert cell.spec["check_requests"] == 4
    assert cell.spec["trace"] == {"at": "end", "seconds": 3.0}


def test_every_published_size_and_constant_is_a_width():
    fam = family()
    pub = harness.load_json(PUBLISHED)
    sizes = {k for k in fam.rehearsal_config()
             if k not in fam.reducible and k not in fam.ASSUMED}
    assert sizes == set(fam.widths)
    # all the source publishes but its name, the context it declares and
    # the RoPE keys of a model that has no positional embedding
    assert set(pub) - set(fam.widths) - set(fam.reducible) == {
        "source", "recorded", "model_type", "max_position_embeddings",
        "rope_theta", "rope_scaling"}
    for key in ("attention_multiplier", "embedding_multiplier",
                "residual_multiplier", "logits_scaling", "mamba_d_state",
                "mamba_n_heads", "tie_word_embeddings",
                "position_embedding_type"):
        assert key in fam.widths


def test_traffic_is_the_issues():
    mix = harness.Cell(CELL).traffic
    assert (mix["loop"], mix["clients"], mix["cycle"], mix["order_seed"]) \
        == ("closed", 72, 216, 37)
    assert mix["prompt"] == {"median": 512, "sigma": 0.7, "lo": 128,
                             "hi": 3072}
    assert mix["output"] == {"median": 512, "sigma": 0.5, "lo": 256,
                             "hi": 1023}
    assert mix["prompt"]["hi"] + mix["output"]["hi"] < 4096


def test_the_entries_are_appended_and_name_one_cell():
    """Where this PR put them: after everything the parent had, and in
    one piece. (Positions, not "last": a later PR appends after these.)"""
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells.index(CELL) == 6 and cells[5] == "brumby14b.decode-state"
    assert BENCH["workloads"][6]["chips"] == 1
    assert [w["name"] for w in BENCH["workloads"]
            if w["config"] == CONF] == [CELL]
    assert [c["name"] for c in BENCH["configs"]].index(CONF) == 6
    assert sum(w["chips"] == 4 for w in BENCH["workloads"][:7]) == 1
    tokens = next(m for m in BENCH["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert tokens["workloads"][:4] == [
        "deepseek7b.decode-closed", "kanana2.decode-wide",
        "brumby14b.decode-state", CELL] and tokens["bound"] == 0.01
    names = [m["name"] for m in BENCH["per_layer"]]
    first = names.index("block_device_ms.hybrid")
    assert names[first - 1] == "state_live_share.state"
    assert names[first:first + 10] == [
        "block_device_ms.hybrid", "itl_p50_ms.hybrid",
        "prefill_device_share.hybrid", "ssm_time_share.hybrid",
        "attn_time_share.hybrid", "state_live_share.hybrid",
        "kv_read_share.hybrid", "decode_hbm_share.hybrid",
        "ssm_state_roofline.hybrid", "ssd_scan_roofline.hybrid"]
    assert names[first:first + 10] == [m["name"] for m in HYBRID]
    for m in HYBRID:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "serve_tokens_per_s"
    assert os.path.getsize(
        os.path.join(harness.ROOT, "BENCHMARK.json")) < 65536


@pytest.mark.parametrize("training", [False, True])
def test_program_config_reads_the_published_keys(training):
    cell = harness.Cell(CELL)
    if training:
        with pytest.raises(NotImplementedError, match="served, not trained"):
            cell.family.program_config(cell.config, training=True)
        return
    cfg = cell.family.program_config(cell.config, training=False)
    assert (cfg.vocab, cfg.d_model, cfg.n_layers, cfg.d_ff) == (
        100352, 2048, 40, 8192)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (32, 8, 64)
    assert (cfg.mamba_heads, cfg.mamba_head_dim, cfg.d_state, cfg.d_conv,
            cfg.chunk) == (64, 64, 128, 4, 256)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling, cfg.norm_eps) == (
        12.0, 0.22, 0.015625, 8.0, 1e-5)
    assert cfg.layer_types == tuple(cell.config["layer_types"])
    assert cfg.use_kernel and cfg.dtype.__name__ == "bfloat16"


# -- the arithmetic of ISSUE 37's Motivation, to the byte ----------------------


def test_the_configuration_holds_the_bytes_it_states():
    import numpy as np

    cell = harness.Cell(CELL)
    count = lambda kind: sum(
        int(np.prod(shape[1:])) for path, (shape, _, _)
        in cell.layout.items() if path[0] == kind)
    # in_proj 2048 x 8512 (z | xBC and the dt columns), the
    # convolution, A_log / D / dt_bias, the gated norm, out_proj, the
    # SwiGLU, two norms
    assert count("mamba") == 76_182_976 == (
        2048 * 8512 + 4352 * 4 + 4352 + 192 + 4096 + 4096 * 2048
        + 3 * 2048 * 8192 + 4096)
    assert count("attn") == 60_821_504 == (
        2 * 4_194_304 + 2 * 1_048_576 + 50_331_648 + 4096)
    n = sum(int(np.prod(shape)) for shape, _, _ in cell.layout.values())
    assert n == 36 * 76_182_976 + 4 * 60_821_504 + 205_522_944
    assert n == 3_191_396_096 and round(2 * n / 1e9, 2) == 6.38
    # the program and the family price themselves with the same count
    cfg = cell.family.program_config(cell.config, training=False)
    assert cfg.n_params() == n == cell.family.needed.n_params(cell.config)


def test_needed_counts_are_the_issues_arithmetic():
    cell = harness.Cell(CELL)
    needed, config = cell.family.needed, cell.config
    assert needed.weight_bytes(config) == 2 * 3_191_396_096
    # S 64 x 64 x 128 float32 a layer x 36 = 75.50 MB; the tail 4352 x 3
    # bfloat16 x 36 = 0.94 MB
    assert needed.ssm_state_bytes_per_slot(config) == 36 * 2_097_152
    assert round(36 * 2_097_152 / 1e6, 2) == 75.50
    tail = needed.state_bytes_per_slot(config) - 36 * 2_097_152
    assert tail == 36 * 4352 * 3 * 2 and round(tail / 1e6, 2) == 0.94
    # 2 x 8 x 64 x 2 B a token a layer x 4 layers
    assert needed.kv_bytes_per_token(config) == 8192
    assert round(4096 * 8192 / 1e6, 2) == 33.55
    slot = needed.state_bytes_per_slot(config) + 4096 * 8192
    assert round(slot / 1e6, 1) == 110.0
    assert round(72 * slot / 1e9, 2) == 7.92
    assert round((72 * slot + 2 * 3_191_396_096) / 1e9, 2) == 14.30
    assert round(100 * (72 * slot + 2 * 3_191_396_096) / (16 * 2 ** 30)) == 83
    # a step of 72 live slots at ~900 tokens each: ~17.9 GB, 61% of it
    # the state-space layers' state
    weights = needed.decode_step_bytes(config, 0, 0)
    assert weights == needed.weight_bytes(config)
    step = needed.decode_step_bytes(config, 72, 72 * 900)
    assert step == weights + 2 * 72 * needed.state_bytes_per_slot(config) \
        + 72 * 900 * 8192
    assert round(step / 1e9, 1) == 17.9
    assert round(2 * 72 * 36 * 2_097_152 / 1e9, 2) == 10.87
    assert round(100 * 2 * 72 * 36 * 2_097_152 / step) == 61
    assert round(1e3 * step / 819e9, 1) == 21.9
    # half the slots live move half the state
    assert needed.decode_step_bytes(config, 36, 0) - weights \
        == needed.state_bytes_per_slot(config) * 72
    # tokens x 36 x 4 x H x P x N
    assert needed.ssd_scan_flops(config, 1) == 36 * 4 * 64 * 64 * 128
    assert needed.ssd_scan_flops(config, 4096) \
        == 4096 * needed.ssd_scan_flops(config, 1)


def test_what_the_program_stores_is_what_is_needed():
    """The cache tuple at the cell's engine sizes: two kinds, two
    depths, and the bytes of the issue's arithmetic."""
    cell = harness.Cell(CELL)
    cfg = cell.family.program_config(cell.config, training=False)
    assert cfg.state_bytes_per_slot() \
        == cell.family.needed.state_bytes_per_slot(cell.config)
    spec = cfg.serve_cache_spec(72, 4096)
    assert [shape for shape, _ in spec] == [
        (36, 72, 64, 64, 128), (36, 72, 3 * 4352), (4, 72, 4096, 4, 128),
        (4, 72, 4096, 4, 128)]
    assert [str(dtype.__name__) for _, dtype in spec] == [
        "float32", "bfloat16", "bfloat16", "bfloat16"]
    assert cfg.serve_cache_kinds == ("state", "state", "kv", "kv")
    kv = 2 * 4 * 72 * 4096 * 4 * 128 * 2
    assert kv == 72 * 4096 * cell.family.needed.kv_bytes_per_token(
        cell.config)


def test_the_draw_is_the_published_parametrisation():
    """``published_form`` sets ``A`` and the step sizes over the
    library's ranges, head by head, and leaves the rest of the draw."""
    import jax.numpy as jnp

    fam = family()
    config = fam.rehearsal_config()
    params = harness.make_params(5, fam.param_layout(config), jnp.float32)
    pub = fam.published_form(params)
    a = jnp.exp(pub["mamba"]["A_log"])
    dt = jnp.log1p(jnp.exp(pub["mamba"]["dt_bias"]))  # softplus
    assert a.shape == dt.shape == (3, 8)
    assert jnp.allclose(a[:, 0], 1.0) and jnp.allclose(a[:, -1], 16.0)
    assert jnp.allclose(dt[:, 0], 1e-3, rtol=1e-3)
    assert jnp.allclose(dt[:, -1], 1e-1, rtol=1e-3)
    horizon = 1.0 / (dt * a)
    assert 999 < float(horizon.max()) < 1001 and float(horizon.min()) < 0.7
    for name, leaf in pub["mamba"].items():
        if name not in ("A_log", "dt_bias"):
            assert leaf is params["mamba"][name]
    assert pub["attn"] is params["attn"] and pub["embed"] is params["embed"]
    again = fam.published_form(pub)
    assert bool(jnp.all(again["mamba"]["dt_bias"] == pub["mamba"]["dt_bias"]))
    # the embedding at 0.02 / 12: what enters the first layer is 0.02
    assert abs(float(jnp.std(params["embed"])) * 12 / 0.02 - 1) < 0.05


# -- the rehearsal ------------------------------------------------------------


def rehearse(capsys, *argv):
    code = run.main(["--rehearse", "--workload", CELL, *argv])
    lines = capsys.readouterr().out.strip().splitlines()
    return code, json.loads(lines[-1]), lines


@pytest.mark.parametrize("trace, reported", [
    (0, {"serve_tokens_per_s", "setup_s"}),
    # the counts are the program's own; times, shares of the device and
    # of a peak are the chip's to report
    (1, {"itl_p50_ms.hybrid", "state_live_share.hybrid"}),
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
        live = line["metrics"]["rehearsal.state_live_share.hybrid"]["value"]
        assert 0.0 < live <= 1.0


def served_gaps(seed, dtype):
    """At each position of one sequence, how far the token that the
    lower precision puts first lies under the reference's best."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import ssm_hybrid as reference

    fam = family()
    config = fam.rehearsal_config()
    params = harness.make_params(seed, fam.param_layout(config), jnp.float32)
    tokens = jnp.asarray(
        np.random.default_rng(seed).integers(0, 256, (64,), dtype=np.int32))
    logits = jax.jit(lambda p, t: fam.reference_logits(p, t, config))
    ref = logits(params, tokens)
    with reference.operands_rounded_to(dtype):
        low = jax.jit(lambda p, t: fam.reference_logits(p, t, config))(
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
    assert worst > lim["served_token_gap_max"]


# -- the readers --------------------------------------------------------------


def span(seq, name, **attrs):
    return types.SimpleNamespace(seq=seq, name=name, start_s=float(seq),
                                 dur_s=0.001, attrs=attrs)


RING = {s.seq: s for s in [
    span(1, "serving.dispatch", horizon=1, rids=["warm-512"],
         state_live_share=1 / 72, kv_read_share=0.01),
    span(2, "serving.dispatch", horizon=1, rids=["warm-512", "q1"],
         state_live_share=0.5, kv_read_share=0.2),
    span(3, "serving.dispatch", horizon=1, rids=["q1", "q2"],
         state_live_share=1.0, kv_read_share=0.4),
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
        ("%fusion.9 = fusion()", 0, 1000, {"tf_op": PRE + "ssm/dot_general:"}),
        ("%fusion.4 = fusion()", 1000, 3000,
         {"tf_op": PRE + "ssm/ssm.chunk/while/body/dot_general:"}),
        ("%fusion.5 = fusion()", 3000, 3500,
         {"tf_op": "jit(edl_serve_prefill_512)/attn/edl_flash_fwd:"}),
        ("%fusion.6 = fusion()", 3500, 5000, {"tf_op": PRE + "mlp/dot:"}),
        ("%fusion.8 = fusion()", 5000, 6000,
         {"tf_op": "jit(edl_serve_prefill_512)/head/dot:"}),
        # block one: a while that holds a run of state-space layers
        ("%while.1 = while()", 6000, 9000, {"tf_op": OP[:-18] + ":"}),
        ("%fusion.1 = fusion()", 6000, 6500,
         {"tf_op": OP + "ssm/dot_general:"}),
        ("%k = custom-call() tpu_custom_call edl_ssm_step", 6500, 8000,
         {"tf_op": OP + "ssm/ssm.step/edl_ssm_step:"}),
        ("%fusion.2 = fusion()", 8000, 8500,
         {"tf_op": OP + "ssm/ssm.step/mul:"}),
        ("%fusion.3 = fusion()", 8500, 9000, {"tf_op": OP + "mlp/dot:"}),
        ("%k = custom-call() tpu_custom_call edl_decode_attn", 9000, 10000,
         {"tf_op": "jit(edl_serve_block)/attn/edl_decode_attn:"}),
        # block two
        ("%k = custom-call() tpu_custom_call edl_ssm_step", 10000, 12500,
         {"tf_op": OP + "ssm/ssm.step/edl_ssm_step:"}),
        ("%fusion.3 = fusion()", 12500, 13500, {"tf_op": OP + "mlp/dot:"}),
        ("%fusion.4 = fusion()", 13500, 14000,
         {"tf_op": "jit(edl_serve_block)/head/argmax:"}),
    ]},
    "/host:CPU": {}}

# a token every 30 ms, once behind a prefill
GAPS = (0.03, 0.03, 0.09, 0.03, 0.03)
RESIDENT = 40_000.0


def a_run(device=TPU, gaps=GAPS):
    cell = harness.Cell(CELL)
    cell.name = "no-such-cell"  # no trace of its own on the disk
    return {"cell": cell, "config": cell.config,
            "trace": {"window_s": 20e-6}, "device": device,
            "spans": {"itl_s": list(gaps)},
            "counters": {"resident_tokens_mean": RESIDENT}}


def expected():
    cell = harness.Cell(CELL)
    needed, config = cell.family.needed, cell.config
    live = 72 * (0.5 + 1.0) / 2  # the two blocks that carry the window
    hbm = 819e9
    return {
        "block_device_ms.hybrid": 4000 / 1e6,
        "itl_p50_ms.hybrid": 30.0,
        "prefill_device_share.hybrid": 100 * 6000 / 20000,
        # ssm: 1000 + 2000 of the prefill; 500 + 1500 + 500 and 2500 of
        # the blocks
        "ssm_time_share.hybrid": 100 * 8000 / 20000,
        "attn_time_share.hybrid": 100 * 1500 / 20000,
        "state_live_share.hybrid": 0.75,
        "kv_read_share.hybrid": 0.3,
        "decode_hbm_share.hybrid":
            100 * needed.decode_step_bytes(config, live, RESIDENT)
            / (4000e-9 * hbm),
        "ssm_state_roofline.hybrid":
            100 * 2 * 2 * live * needed.ssm_state_bytes_per_slot(config)
            / (4500e-9 * hbm),
        "ssd_scan_roofline.hybrid":
            100 * needed.ssd_scan_flops(config, 512) / (2000e-9 * 197e12),
    }


@pytest.mark.parametrize("metric", [m["name"] for m in HYBRID])
def test_reader_reads_the_fixture(metric, monkeypatch):
    monkeypatch.setattr(program, "planes_of", lambda run: PLANES)
    monkeypatch.setattr(program, "ring", lambda: (RING, 0.0))
    got = reader(metric).read(a_run())
    assert got == pytest.approx(expected()[metric], rel=1e-9)


@pytest.mark.parametrize("metric", [m["name"] for m in HYBRID])
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
    if metric not in ("itl_p50_ms.hybrid", "state_live_share.hybrid"):
        monkeypatch.setattr(program, "ring", lambda: (RING, 0.0))
        assert reader(metric).read(a_run(cpu)) is None


def test_a_share_of_a_peak_cannot_pass_100_on_the_fixtures_terms():
    """The step's needed bytes at 72 live slots of ~900 tokens over the
    HBM peak are 21.9 ms, the state's alone 13.3 ms: a block or a step
    scope faster than that would read over 100%, and no reader clips.
    The recurrence's own products for a 4096 bucket take 1.6 ms at the
    bf16 peak: the chunked form cannot be faster."""
    cell = harness.Cell(CELL)
    needed, config = cell.family.needed, cell.config
    assert needed.decode_step_bytes(config, 72, 72 * 900) / 819e9 > 0.0218
    assert 2 * 72 * needed.ssm_state_bytes_per_slot(config) / 819e9 > 0.0132
    assert needed.ssd_scan_flops(config, 4096) / 197e12 > 0.0015
    for m in HYBRID:
        if m["unit"] == "%":
            text = open(os.path.join(
                harness.ROOT, "benchmark", "metrics", m["name"] + ".py")).read()
            assert "min(" not in text, m["name"]
