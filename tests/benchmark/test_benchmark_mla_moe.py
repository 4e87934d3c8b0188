"""Family ``mla_moe`` and its cell ``kanana2.decode-wide``: the files
hold to their source, the cell rehearses through the engine with its
readers reporting, the needed bytes add up, and the readers read what
the program writes (fixtures worked out by hand) and nothing where
there is nothing."""

import glob
import json
import os
import re
import types

import pytest

from benchmark import harness, run
from benchmark.reduce import program

CELL = "kanana2.decode-wide"
BENCH = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
WIDE = [m for m in BENCH["per_layer"] if m["name"].endswith(".wide")]
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def family():
    return harness.Cell(CELL).family


def reader(name):
    return harness.load_module(os.path.join(
        harness.ROOT, "benchmark", "metrics", name + ".py"))


# -- the files ----------------------------------------------------------------


def test_configuration_is_the_catalog_row_cut_in_depth_alone():
    cell = harness.Cell(CELL)
    row = next(json.loads(l) for l in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if "kanana-2-30b-a3b-instruct-2601" in l) if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else None
    pub = harness.load_json(os.path.join(
        harness.ROOT, "benchmark", "published",
        "kakaocorp.kanana-2-30b-a3b-instruct-2601.json"))
    if row is not None:
        assert {k: pub[k] for k in row["config"]} == row["config"]
        assert pub["source"] == row["source_url"]
    for key, value in pub.items():
        if key not in ("recorded", "num_hidden_layers"):
            assert cell.config[key] == value, key
    assert cell.config["num_hidden_layers"] == 8
    assert cell.config["published"] == {"num_hidden_layers": 48}
    assert list(cell.config["reduced"]) == ["num_hidden_layers"]
    assert {"deployment", "assumed"} <= set(cell.config)
    assert cell.chips == 1 and cell.kind == "serve"
    assert cell.spec["engine"] == {
        "max_slots": 96, "max_len": 4096, "horizon": 1}


def test_every_size_and_constant_of_the_arithmetic_is_a_width():
    fam = family()
    sizes = {k for k, v in fam.rehearsal_config().items()
             if k != "num_hidden_layers"}
    assert sizes == set(fam.widths)
    assert fam.reducible == {"num_hidden_layers": 5}


def test_the_cut_holds_the_bytes_the_configuration_states():
    cell = harness.Cell(CELL)
    n = sum(int(__import__("numpy").prod(shape))
            for shape, _, _ in cell.layout.values())
    assert round(n / 1e9, 2) == 5.07
    # the program prices itself with the same count
    cfg = cell.family.program_config(cell.config, training=False)
    assert cfg.n_params() == n
    eng = cell.spec["engine"]
    latent = cell.family.needed.latent_bytes_per_token(cell.config)
    assert latent == 1152
    cache = eng["max_slots"] * eng["max_len"] * 8 * latent
    assert round(cache / 1e9, 2) == 3.62
    assert 0.25 < (2 * n + cache) / (16 * 2 ** 30) < 0.9


def test_traffic_is_the_issues():
    mix = harness.Cell(CELL).traffic
    assert (mix["loop"], mix["clients"], mix["cycle"]) == ("closed", 96, 288)
    assert mix["prompt"] == {"median": 1536, "sigma": 0.5, "lo": 512,
                             "hi": 3072}
    assert mix["output"] == {"median": 512, "sigma": 0.5, "lo": 256,
                             "hi": 1023}
    assert mix["prompt"]["hi"] + mix["output"]["hi"] < 4096


def benchmark_sources():
    here = os.path.join(harness.ROOT, "benchmark")
    return {os.path.relpath(p, here): open(p).read() for p in glob.glob(
        os.path.join(here, "**", "*.py"), recursive=True)}


def importers(files, what):
    return {p for p, text in files.items()
            if re.search(rf"^\s*(from|import) .*{what}", text, re.M)}


def test_the_programs_model_code_is_named_under_families_alone():
    """The line ``test_benchmark_families.py`` draws, with the families
    read from the directory and not from a list: whichever family there
    is, only files under benchmark/families/ import ``edl_tpu.models``,
    and ``harness.py`` holds no word of a model's shape."""
    files = benchmark_sources()
    naming = importers(files, r"edl_tpu\.models")
    assert naming and all(
        os.path.dirname(p) == "families" for p in naming), naming
    for word in ("LlamaConfig", "hidden_size", "vocab_size", "lm_head",
                 '"layers"', "num_hidden_layers"):
        assert word not in files["harness.py"], f"harness.py holds {word}"


def test_a_reference_is_imported_by_one_family_and_nothing_else():
    files = benchmark_sources()
    references = [os.path.basename(p)[:-3] for p in files
                  if os.path.dirname(p) == "reference"
                  and not p.endswith("__init__.py")]
    assert "mla_moe" in references
    for ref in references + ["needed"]:
        package = "reduce" if ref == "needed" else "reference"
        users = importers(files, rf"{package}(\.| import ){ref}\b")
        assert len(users) == 1 and os.path.dirname(
            next(iter(users))) == "families", (ref, users)


@pytest.mark.parametrize("training", [False, True])
def test_program_config_reads_the_published_keys(training):
    cell = harness.Cell(CELL)
    if training:
        with pytest.raises(NotImplementedError, match="served, not trained"):
            cell.family.program_config(cell.config, training=True)
        return
    cfg = cell.family.program_config(cell.config, training=False)
    assert (cfg.vocab, cfg.d_model, cfg.n_layers, cfg.n_heads) == (
        128256, 2048, 8, 32)
    assert (cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_dim, cfg.kv_rank) == (
        128, 64, 128, 512)
    assert (cfg.d_ff, cfg.n_dense_layers, cfg.d_expert, cfg.n_experts,
            cfg.n_shared, cfg.top_k) == (6144, 1, 768, 128, 2, 6)
    assert (cfg.route_scale, cfg.norm_topk, cfg.rope_theta, cfg.norm_eps) \
        == (2.448, True, 1e6, 1e-6)
    assert cfg.use_flash and cfg.n_experts == 128
    assert cfg.latent_width == 576 and cfg.cache_width == 640


# -- needed bytes -------------------------------------------------------------


def test_needed_bytes_of_a_step_add_up():
    cell = harness.Cell(CELL)
    needed, config = cell.family.needed, cell.config
    every = needed.decode_step_bytes(config, 0.0, 1.0)
    none = needed.decode_step_bytes(config, 0.0, 0.0)
    # all experts of seven layers: 7 x 128 x 3 x 2048 x 768 x 2 bytes
    assert every - none == needed.expert_bytes(config, 1.0) \
        == 7 * 128 * 3 * 2048 * 768 * 2
    # what is left is every parameter but the experts and the embedding
    n = sum(int(__import__("numpy").prod(s)) for p, (s, _, _)
            in cell.layout.items()
            if p[-1] not in ("we1", "we2", "we3", "embed"))
    norms_and_bias = sum(
        int(__import__("numpy").prod(s)) for p, (s, _, _)
        in cell.layout.items()
        if p[-1] in ("ln1", "ln2", "ln_f", "kv_norm", "router_bias"))
    assert none == 2 * (n - norms_and_bias)
    # a resident token costs its latent rows in every layer
    assert needed.decode_step_bytes(config, 1000.0, 0.5) \
        - needed.decode_step_bytes(config, 0.0, 0.5) == 1000 * 8 * 1152


# -- the rehearsal ------------------------------------------------------------


def rehearse(capsys, *argv):
    code = run.main(["--rehearse", "--workload", CELL, *argv])
    lines = capsys.readouterr().out.strip().splitlines()
    return code, json.loads(lines[-1]), lines


@pytest.mark.parametrize("trace, reported", [
    (0, {"serve_tokens_per_s", "setup_s"}),
    # the counts are the program's own; times, shares of the device and
    # of a peak are the chip's to report
    (1, {"itl_p50_ms.wide", "experts_hit_share.wide",
         "expert_load_max_over_mean.wide"}),
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
        hit = line["metrics"]["rehearsal.experts_hit_share.wide"]["value"]
        skew = line["metrics"][
            "rehearsal.expert_load_max_over_mean.wide"]["value"]
        assert 0.0 < hit <= 1.0 and skew >= 1.0


def served_gaps(seed, dtype):
    """At each position of one sequence, how far the token that the
    lower precision puts first lies under the reference's best (the
    reference with its operands rounded, as test_benchmark_control.py
    does for the decoder)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import mla_moe as reference

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


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serving_control_fails_and_the_stated_precision_passes(seed):
    """A precision below the configuration's (float8 operands) fails
    both of the rehearsal's limits, bfloat16 passes both."""
    import jax.numpy as jnp

    cell = harness.Cell(CELL)
    cell.for_rehearsal()
    lim = cell.limits
    worst, mean = served_gaps(seed, jnp.bfloat16)
    assert worst <= lim["served_token_gap_max"]
    assert mean <= lim["served_token_gap_mean"]
    worst, mean = served_gaps(seed, jnp.float8_e4m3fn)
    assert worst > lim["served_token_gap_max"]
    assert mean > 3 * lim["served_token_gap_mean"]


# -- the readers --------------------------------------------------------------


def span(seq, name, **attrs):
    return types.SimpleNamespace(seq=seq, name=name, start_s=float(seq),
                                 dur_s=0.001, attrs=attrs)


# four steps a block (the attribute the engine writes on every
# dispatch): the readers divide by it whatever the cell's file says
RING = {s.seq: s for s in [
    span(1, "serving.dispatch", horizon=4, rids=["warm-512"],
         kv_read_share=0.1, experts_hit_share=0.1,
         expert_load_max_over_mean=9.0),
    span(2, "serving.dispatch", horizon=4, rids=["warm-512", "q1"],
         kv_read_share=0.25, experts_hit_share=0.5,
         expert_load_max_over_mean=2.0),
    span(3, "serving.dispatch", horizon=4, rids=["q1", "q2"],
         kv_read_share=0.75, experts_hit_share=1.0,
         expert_load_max_over_mean=4.0),
    span(4, "serving.dispatch", horizon=4, rids=["q3"]),
]}

OP = "jit(edl_serve_block)/while/body/closed_call/"
PRE = "jit(edl_serve_prefill_512)/"
# a 20,000 ns window: two blocks of 4000 ns and one prefill of 6000
PLANES = {"/device:TPU:0": {
    "XLA Modules": [
        ("jit_edl_serve_prefill_512(7)", 0, 6000, {}),
        ("jit_edl_serve_block(9)", 6000, 10000, {}),
        ("jit_edl_serve_block(9)", 10000, 14000, {}),
    ],
    "XLA Ops": [
        ("%fusion.9 = fusion()", 0, 2000,
         {"tf_op": PRE + "attn/attn.latent_expand/dot_general:"}),
        # XLA's grouped-matmul kernel: its op_name is the expansion's
        ("%ragged-dot-none.3 = custom-call() tpu_custom_call", 2000, 5000,
         {"tf_op": "ragged-dot-none"}),
        ("%fusion.8 = fusion()", 5000, 6000, {"tf_op": PRE + "head/dot:"}),
        # block one: a while that holds everything
        ("%while.1 = while()", 6000, 10000, {"tf_op": OP[:-18] + ":"}),
        ("%k = custom-call() tpu_custom_call edl_decode_attn_latent",
         6000, 7000,
         {"tf_op": OP + "attn/attn.latent_absorb/edl_decode_attn_latent:"}),
        ("%fusion.1 = fusion()", 7000, 7500,
         {"tf_op": OP + "attn/attn.latent_absorb/dot_general:"}),
        ("%fusion.2 = fusion()", 7500, 8000,
         {"tf_op": OP + "moe/moe.router/dot_general:"}),
        ("%ragged-dot-metadata.1 = custom-call() tpu_custom_call", 8000,
         8100, {"tf_op": "ragged-dot-metadata"}),
        ("%ragged-dot-none.1 = custom-call() tpu_custom_call", 8100, 9000,
         {"tf_op": "ragged-dot-none"}),
        ("%fusion.7 = fusion()", 9000, 9500,
         {"tf_op": OP + "moe/moe.experts/gather:"}),
        ("%fusion.3 = fusion()", 9500, 10000,
         {"tf_op": OP + "moe/moe.shared/dot_general:"}),
        # block two
        ("%k = custom-call() tpu_custom_call edl_decode_attn_latent",
         10000, 11000,
         {"tf_op": OP + "attn/attn.latent_absorb/edl_decode_attn_latent:"}),
        ("%ragged-dot-none.1 = custom-call() tpu_custom_call", 11000, 13500,
         {"tf_op": "ragged-dot-none"}),
        ("%fusion.4 = fusion()", 13500, 14000, {"tf_op": OP + "head/argmax:"}),
    ]},
    "/host:CPU": {}}


# a token every 20 ms, once behind a prefill
GAPS = (0.02, 0.02, 0.06, 0.02, 0.02)


def a_run(device=TPU, gaps=GAPS):
    cell = harness.Cell(CELL)
    cell.name = "no-such-cell"  # no trace of its own on the disk
    return {"cell": cell, "config": cell.config,
            "trace": {"window_s": 20e-6}, "device": device,
            "spans": {"itl_s": list(gaps)},
            "counters": {"resident_tokens_mean": 100000.0}}


def expected():
    bw = 819e9
    cell = harness.Cell(CELL)
    needed = cell.family.needed
    hit = 0.75  # the mean of the two blocks that carry a window's request
    return {
        "block_device_ms.wide": 0.004,
        "itl_p50_ms.wide": 20.0,
        "prefill_device_share.wide": 30.0,
        # under moe: 3000 (prefill) + 500 + 1500 + 500 + 2500 of 20000
        "moe_time_share.wide": 40.0,
        # under attn: 2000 + 1000 + 500 + 1000
        "attn_time_share.wide": 22.5,
        "experts_hit_share.wide": hit,
        "expert_load_max_over_mean.wide": 3.0,
        "kv_read_share.wide": 0.5,
        "decode_hbm_share.wide": 100 * needed.decode_step_bytes(
            cell.config, 100000.0, hit) / (4000e-9 / 4 * bw),
        # two calls of the kernel, one layer of one step each, 1000 ns
        "latent_decode_attn_roofline.wide":
            100 * 2 * 100000.0 * 1152 / (2000e-9 * bw),
        # moe.experts inside the blocks: 1500 + 2500 ns; two calls of
        # the latent kernel are a quarter of an eight-layer step
        "expert_matmul_roofline.wide":
            100 * 0.25 * needed.expert_bytes(cell.config, hit)
            / (4000e-9 * bw),
    }


def test_every_wide_metric_has_its_reader_and_lists_the_one_cell():
    assert {m["name"] for m in WIDE} == set(expected())
    for m in WIDE:
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
        assert m in harness.Cell(CELL).per_layer()


@pytest.mark.parametrize("name", sorted(m["name"] for m in WIDE))
def test_reader_on_the_fixture(name, monkeypatch):
    monkeypatch.setattr(program, "planes_of", lambda run: PLANES)
    monkeypatch.setattr(program, "ring", lambda: (RING, 0.0))
    assert reader(name).read(a_run()) == pytest.approx(expected()[name])


@pytest.mark.parametrize("name", sorted(m["name"] for m in WIDE))
def test_reader_finds_nothing_in_an_empty_run(name, monkeypatch):
    monkeypatch.setattr(program, "ring", lambda: ({}, 0.0))
    run_ = a_run(gaps=())
    assert reader(name).read(run_) is None
    run_["trace"] = None
    assert reader(name).read(run_) is None


@pytest.mark.parametrize("name", sorted(
    m["name"] for m in WIDE if m["name"] not in (
        "itl_p50_ms.wide", "block_device_ms.wide",
        "prefill_device_share.wide")))
def test_reader_finds_nothing_in_a_program_without_the_names(
        name, monkeypatch):
    """The dense decoder's scopes and spans (what the parent writes)."""
    old = {"/device:TPU:0": {
        "XLA Modules": [("jit_run(1)", 0, 100, {})],
        "XLA Ops": [("%fusion.1 = f32[] fusion()", 0, 100,
                     {"tf_op": "jit(run)/jit(main)/while/body/dot_general:"})]},
        "/host:CPU": {}}
    monkeypatch.setattr(program, "planes_of", lambda run: old)
    monkeypatch.setattr(program, "ring", lambda: ({4: RING[4]}, 0.0))
    assert reader(name).read(a_run()) is None
