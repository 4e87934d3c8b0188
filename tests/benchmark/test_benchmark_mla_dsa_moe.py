"""Family ``mla_dsa_moe`` and its cell ``glm5.long-sparse``: the files
hold to their source and to the share of the deployment they state, the
cell rehearses through the engine with its readers reporting, the
needed bytes add up, and the readers read what the program writes
(fixtures worked out by hand) and nothing where there is nothing."""

import json
import os
import types

import pytest

from benchmark import harness, run
from benchmark.reduce import program

CELL = "glm5.long-sparse"
CONFIG = "glm5-L5-ep32"
BENCH = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
SPARSE = [m for m in BENCH["per_layer"] if m["name"].endswith(".sparse")]
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CUT = {"num_hidden_layers": (78, 5), "first_k_dense_replace": (3, 1),
       "n_routed_experts": (256, 8), "vocab_size": (154880, 19360),
       "num_nextn_predict_layers": (1, 0)}


def reader(name):
    return harness.load_module(os.path.join(
        harness.ROOT, "benchmark", "metrics", name + ".py"))


# -- the files ----------------------------------------------------------------


def test_configuration_is_the_catalog_row_with_the_stated_cuts_alone():
    cell = harness.Cell(CELL)
    pub = harness.load_json(os.path.join(
        harness.ROOT, "benchmark", "published", "zai-org.GLM-5.json"))
    if os.path.exists(CATALOG):
        row = next(json.loads(l) for l in open(CATALOG)
                   if '"name": "GLM-5"' in l)
        assert {k: pub[k] for k in row["config"]} == row["config"]
        assert pub["source"] == row["source_url"]
    for key, value in pub.items():
        if key not in CUT:
            assert cell.config[key] == value, key
    for key, (published, here) in CUT.items():
        assert pub[key] == published and cell.config[key] == here
        assert cell.config["published"][key] == published
    assert list(cell.config["reduced"]) == list(CUT)
    assert cell.config["first_routed_expert"] == 0
    assert {"deployment", "assumed"} <= set(cell.config)
    assert {"index_key_norm", "index_split", "index_precision",
            "index_ties", "weights"} <= set(cell.config["assumed"])
    assert cell.chips == 1 and cell.kind == "serve"
    assert cell.spec["engine"] == {
        "max_slots": 32, "max_len": 32768, "horizon": 1}
    assert cell.spec["check_requests"] == 5


def test_the_entries_name_one_configuration_one_cell_and_fifteen_readers():
    """By name, wherever later entries put them in their lists."""
    conf = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    assert len(conf) == 1 and conf[0]["reduced"] == sorted(CUT)
    assert conf[0]["file"] == "benchmark/configs/glm5-L5-ep32.json"
    cells = [w for w in BENCH["workloads"] if w["config"] == CONFIG]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [
        (CELL, "long-sparse", 1)]
    rate = next(m for m in BENCH["end_to_end"]
                if m["name"] == "serve_tokens_per_s")
    assert CELL in rate["workloads"] and rate["bound"] == 0.01
    assert {m["name"] for m in harness.Cell(CELL).end_to_end()} == {
        "serve_tokens_per_s", "setup_s"}
    assert len(SPARSE) == 15
    for m in BENCH["per_layer"]:
        assert (CELL in m.get("workloads", ())) == (m in SPARSE), m["name"]


def test_every_size_and_constant_of_the_arithmetic_is_a_width():
    fam = harness.Cell(CELL).family
    rehearsal = fam.rehearsal_config()
    cut = set(fam.reducible) | {"first_routed_expert", "published"}
    assert {k for k in rehearsal if k not in cut} == set(fam.widths)
    # the guide's floors
    assert fam.reducible == {
        "num_hidden_layers": 5, "first_k_dense_replace": 1,
        "n_routed_experts": 8, "vocab_size": 154880 // 8,
        "num_nextn_predict_layers": 0}
    config = harness.Cell(CELL).config
    for key, floor in fam.reducible.items():
        assert config[key] >= floor, key
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4


def test_the_cut_holds_the_bytes_the_configuration_states():
    import numpy as np

    cell = harness.Cell(CELL)
    n = sum(int(np.prod(shape)) for shape, _, _ in cell.layout.values())
    assert round(n / 1e9, 2) == 2.70
    cfg = cell.family.program_config(cell.config, training=False)
    assert cfg.n_params() == n
    assert cell.family.needed.weight_bytes(cell.config) == 2 * n
    by_leaf = lambda name: sum(
        int(np.prod(s)) for p, (s, _, _) in cell.layout.items()
        if p[:2] == ("layers", "01") and p[-1] in name)
    assert round(by_leaf(("we1", "we3", "we2")) / 8e6, 2) == 37.75
    assert round(by_leaf(("router",)) / 1e6, 2) == 1.57
    eng = cell.spec["engine"]
    spec = cfg.serve_cache_spec(eng["max_slots"], eng["max_len"])
    cache = sum(int(np.prod(shape)) * 2 for shape, _ in spec)
    assert [shape[-1] for shape, _ in spec] == [640, 128]
    assert round(cache / 1e9, 2) == 8.05
    assert 0.70 < (2 * n + cache) / (16 * 2 ** 30) < 0.9


def test_traffic_is_the_issues():
    mix = harness.Cell(CELL).traffic
    assert (mix["loop"], mix["clients"], mix["cycle"], mix["order_seed"]) \
        == ("closed", 32, 96, 41)
    assert mix["prompt"] == {"median": 8192, "sigma": 0.6, "lo": 2048,
                             "hi": 24576}
    assert mix["output"] == {"median": 1024, "sigma": 0.5, "lo": 256,
                             "hi": 2047}
    assert mix["prompt"]["hi"] + mix["output"]["hi"] < 32768


@pytest.mark.parametrize("training", [False, True])
def test_program_config_reads_the_published_keys_and_the_share(training):
    cell = harness.Cell(CELL)
    if training:
        with pytest.raises(NotImplementedError, match="served, not trained"):
            cell.family.program_config(cell.config, training=True)
        return
    cfg = cell.family.program_config(cell.config, training=False)
    assert (cfg.vocab, cfg.d_model, cfg.n_layers, cfg.n_heads) == (
        19360, 6144, 5, 64)
    assert (cfg.q_rank, cfg.kv_rank, cfg.qk_nope_dim, cfg.qk_rope_dim,
            cfg.v_dim) == (2048, 512, 192, 64, 256)
    assert (cfg.index_heads, cfg.index_dim, cfg.index_topk) == (32, 128, 2048)
    # the router as wide as published, 8 experts held from the first
    assert (cfg.n_experts, cfg.held, cfg.first_expert, cfg.top_k) == (
        256, 8, 0, 8)
    assert (cfg.d_ff, cfg.n_dense_layers, cfg.d_expert, cfg.n_shared) == (
        12288, 1, 2048, 1)
    assert (cfg.route_scale, cfg.norm_topk, cfg.rope_theta, cfg.norm_eps) \
        == (2.5, True, 1e6, 1e-5)
    from edl_tpu.models import glm_dsa

    assert cfg.use_flash and glm_dsa.PREFILL_PIECE <= 4096


# -- needed operations and bytes -------------------------------------------------


def test_needed_bytes_of_a_step_add_up():
    import numpy as np

    cell = harness.Cell(CELL)
    needed, config = cell.family.needed, cell.config
    every = needed.decode_step_bytes(config, 0.0, 0.0, 1.0)
    none = needed.decode_step_bytes(config, 0.0, 0.0, 0.0)
    # the eight held experts of four layers
    assert every - none == needed.expert_bytes(config, 1.0) \
        == 4 * 8 * 3 * 6144 * 2048 * 2
    # what is left is every matrix but the experts and the embedding
    small = ("ln1", "ln2", "ln_f", "q_norm", "kv_norm", "ki_norm",
             "ki_bias", "router_bias")
    n = sum(int(np.prod(s)) for p, (s, _, _) in cell.layout.items()
            if p[-1] not in ("we1", "we2", "we3", "embed") + small)
    assert none == 2 * n
    # a live position costs its index key in every layer; a chosen one
    # its latent row too, and no more than index_topk a slot are chosen
    assert needed.index_key_bytes(config) == 256
    assert needed.selected_row_bytes(config) == 1152
    few = needed.decode_step_bytes(config, 4, 4 * 1000.0, 0.5)
    many = needed.decode_step_bytes(config, 4, 4 * 10000.0, 0.5)
    base = needed.decode_step_bytes(config, 4, 0.0, 0.5)
    assert few - base == 5 * 4000 * (256 + 1152)
    assert many - base == 5 * (40000 * 256 + 4 * 2048 * 1152)
    assert needed.selected_positions(config, 40000.0, 4) == 4 * 2048
    assert needed.index_score_flops(config, 2048, 8192) \
        == 2 * 2048 * 8192 * 32 * 128


# -- the rehearsal ------------------------------------------------------------


def rehearse(capsys, *argv):
    code = run.main(["--rehearse", "--workload", CELL, *argv])
    lines = capsys.readouterr().out.strip().splitlines()
    return code, json.loads(lines[-1]), lines


@pytest.mark.parametrize("trace, reported", [
    (0, {"serve_tokens_per_s", "setup_s"}),
    # the counts are the program's own; times, shares of the device and
    # of a peak are the chip's to report
    (1, {"itl_p50_ms.sparse", "experts_hit_share.sparse",
         "kv_selected_share.sparse", "expert_load_max_over_mean.sparse"}),
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
        hit = line["metrics"]["rehearsal.experts_hit_share.sparse"]["value"]
        chosen = line["metrics"][
            "rehearsal.kv_selected_share.sparse"]["value"]
        # contexts of 16-60 positions, 8 of them attended
        assert 0.0 < hit <= 1.0 and 0.1 < chosen < 0.6


def served_gaps(seed, dtype):
    """At each position of one sequence, how far the token that the
    lower precision puts first lies under the reference's best (the
    reference with its operands rounded)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import mla_dsa_moe as reference

    fam = harness.Cell(CELL).family
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
def test_a_lower_precision_moves_the_statistic_the_cell_compares(seed):
    """bfloat16 operands pass both of the rehearsal's limits; float8
    operands read several times bfloat16's mean. At these widths (8
    positions chosen of a few dozen) the rehearsal's limits are set to
    catch a fault of the mechanism, which one flipped choice must not
    trip (the chip's, at the published widths with five requests
    compared, decide the precision too: the cell's ``limits_from``)."""
    import jax.numpy as jnp

    cell = harness.Cell(CELL)
    cell.for_rehearsal()
    lim = cell.limits
    worst, mean = served_gaps(seed, jnp.bfloat16)
    assert worst <= lim["served_token_gap_max"]
    assert mean <= lim["served_token_gap_mean"] / 4
    _, low = served_gaps(seed, jnp.float8_e4m3fn)
    assert low > 3 * mean


def test_the_references_runs_of_queries_give_the_whole_sequences_logits(
        monkeypatch):
    """A long sequence's queries go in runs, each against the positions
    up to its own end: the logits are those of the sequence taken
    whole, and a sequence that does not divide is taken whole."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import mla_dsa_moe as reference

    fam = harness.Cell(CELL).family
    config = fam.rehearsal_config()
    params = harness.make_params(5, fam.param_layout(config), jnp.float32)
    tokens = jnp.asarray(
        np.random.default_rng(5).integers(0, 256, (48,), dtype=np.int32))
    monkeypatch.setattr(reference, "QUERY_BLOCK", 4)
    assert reference._segments(48) == [(0, 12), (12, 24), (24, 36), (36, 48)]
    assert reference._segments(40) == [(0, 40)]
    runs = jax.jit(lambda p, t: reference.logits_row(p, t, config))(
        params, tokens)
    monkeypatch.setattr(reference, "SEGMENTS", 1)
    whole = jax.jit(lambda p, t: reference.logits_row(p, t, config))(
        params, tokens)
    assert float(jnp.max(jnp.abs(whole))) > 1.0
    assert float(jnp.max(jnp.abs(runs - whole))) < 1e-5


# -- the readers --------------------------------------------------------------


def span(seq, name, **attrs):
    return types.SimpleNamespace(seq=seq, name=name, start_s=float(seq),
                                 dur_s=0.001, attrs=attrs)


RING = {s.seq: s for s in [
    span(1, "serving.dispatch", horizon=2, rids=["warm-2048"],
         kv_read_share=0.9, kv_selected_share=1.0, experts_hit_share=0.1,
         expert_load_max_over_mean=8.0),
    span(2, "serving.dispatch", horizon=2, rids=["warm-2048", "q1"],
         kv_read_share=0.3, kv_selected_share=0.5, kv_live_tokens=30000,
         experts_hit_share=0.5, expert_load_max_over_mean=2.0),
    span(3, "serving.dispatch", horizon=2, rids=["q1", "q2", "q3", "q4"],
         kv_read_share=0.5, kv_selected_share=0.25, kv_live_tokens=50000,
         experts_hit_share=1.0, expert_load_max_over_mean=4.0),
    span(4, "serving.dispatch", horizon=2, rids=["q5"]),
    # after the trace: no reader of a share of a peak counts it
    span(5, "serving.dispatch", horizon=2, rids=["q6"],
         kv_read_share=0.9, kv_selected_share=0.9, kv_live_tokens=9,
         experts_hit_share=0.125, expert_load_max_over_mean=8.0),
]}

OP = "jit(edl_serve_block)/while/body/closed_call/"
PRE = "jit(edl_serve_prefill_8192)/while/body/"
# a 20,000 ns window: two blocks of 4000 ns and one prefill of 6000
PLANES = {"/device:TPU:0": {
    "XLA Modules": [
        ("jit_edl_serve_prefill_8192(7)", 0, 6000, {}),
        ("jit_edl_serve_block(9)", 6000, 10000, {}),
        ("jit_edl_serve_block(9)", 10000, 14000, {}),
    ],
    "XLA Ops": [
        ("%fusion.9 = fusion()", 0, 1000,
         {"tf_op": PRE + "attn/attn.index/dot_general:"}),
        ("%fusion.10 = fusion()", 1000, 2500,
         {"tf_op": PRE + "attn/attn.select/while/body/reduce_sum:"}),
        ("%fusion.11 = fusion()", 2500, 4500,
         {"tf_op": PRE + "attn/attn.sparse/while/body/dot_general:"}),
        ("%k = custom-call() tpu_custom_call edl_grouped_expert_mlp", 4500,
         5500, {"tf_op": PRE + "moe/moe.experts/edl_grouped_expert_mlp:"}),
        ("%fusion.8 = fusion()", 5500, 6000, {"tf_op": PRE + "head/dot:"}),
        # block one: a while that holds everything
        ("%while.1 = while()", 6000, 10000, {"tf_op": OP[:-18] + ":"}),
        ("%fusion.1 = fusion()", 6000, 6400,
         {"tf_op": OP + "attn/dot_general:"}),
        ("%fusion.2 = fusion()", 6400, 7000,
         {"tf_op": OP + "attn/attn.index/while/body/dot_general:"}),
        ("%topk = custom-call()", 7000, 8000,
         {"tf_op": OP + "attn/attn.select/top_k:"}),
        ("%gather.1 = fusion()", 8000, 8500,
         {"tf_op": OP + "attn/attn.sparse/gather:"}),
        ("%fusion.3 = fusion()", 8500, 8800,
         {"tf_op": OP + "moe/moe.router/dot_general:"}),
        ("%k = custom-call() tpu_custom_call edl_expert_mlp", 8800, 9600,
         {"tf_op": OP + "moe/moe.experts/edl_expert_mlp:"}),
        ("%fusion.4 = fusion()", 9600, 10000,
         {"tf_op": OP + "moe/moe.shared/dot_general:"}),
        # block two
        ("%fusion.2 = fusion()", 10000, 10400,
         {"tf_op": OP + "attn/attn.index/while/body/dot_general:"}),
        ("%topk = custom-call()", 10400, 11400,
         {"tf_op": OP + "attn/attn.select/top_k:"}),
        ("%gather.1 = fusion()", 11400, 11900,
         {"tf_op": OP + "attn/attn.sparse/gather:"}),
        ("%k = custom-call() tpu_custom_call edl_expert_mlp", 11900, 13100,
         {"tf_op": OP + "moe/moe.experts/edl_expert_mlp:"}),
        ("%fusion.5 = fusion()", 13100, 14000, {"tf_op": OP + "head/argmax:"}),
    ]},
    # the two dispatches the profiler saw, by their ``seq``
    "/host:CPU": {"python3": [
        ("edl.serving.dispatch", 5900, 6000, {"seq": 2}),
        ("edl.serving.drain", 6000, 9900, {"seq": 7}),
        ("edl.serving.dispatch", 9900, 10000, {"seq": 3}),
    ]}}

# a token every 20 ms, once behind a prefill
GAPS = (0.02, 0.02, 0.6, 0.02, 0.02)
RESIDENT = 40000.0  # tokens resident, the mean over the traced dispatches


def a_run(device=TPU, gaps=GAPS):
    cell = harness.Cell(CELL)
    cell.name = "no-such-cell"  # no trace of its own on the disk
    return {"cell": cell, "config": cell.config,
            "trace": {"window_s": 20e-6}, "device": device,
            "spans": {"itl_s": list(gaps)},
            # the window's mean: its fill is not its end, no reader
            # of a share of a peak takes it
            "counters": {"resident_tokens_mean": 123.0}}


def expected():
    bw = 819e9
    cell = harness.Cell(CELL)
    needed = cell.family.needed
    # the two dispatches the trace holds: 2 and 4 slots live (a warm-up
    # request that rides beside one of the window's is live)
    hit, live, steps = 0.75, 3.0, 2 * 2
    return {
        "block_device_ms.sparse": 0.004,
        "itl_p50_ms.sparse": 20.0,
        "prefill_device_share.sparse": 30.0,
        # under attn: 4500 (prefill) + 2500 + 1900 of 20000
        "attn_time_share.sparse": 44.5,
        # under attn.index: 1000 + 600 + 400
        "index_time_share.sparse": 10.0,
        # under attn.select: 1500 + 1000 + 1000
        "select_time_share.sparse": 17.5,
        # under moe: 1000 + 300 + 800 + 400 + 1200
        "moe_time_share.sparse": 18.5,
        # the window's dispatches, the one after the trace too
        "kv_selected_share.sparse": (0.5 + 0.25 + 0.9) / 3,
        "experts_hit_share.sparse": (0.5 + 1.0 + 0.125) / 3,
        "expert_load_max_over_mean.sparse": (2.0 + 4.0 + 8.0) / 3,
        # bytes of both arrays read over both whole, the chip's to report
        "kv_read_share.sparse": (0.3 + 0.5 + 0.9) / 3,
        "decode_hbm_share.sparse": 100 * needed.decode_step_bytes(
            cell.config, live, RESIDENT, hit) / (4000e-9 / 2 * bw),
        # every resident token's index key, five layers, four steps,
        # over the 1000 ns under attn.index in the blocks
        "index_score_roofline.sparse":
            100 * steps * 5 * RESIDENT * 256 / (1000e-9 * bw),
        # 2048 rows a live slot (13333 tokens a slot are resident), over
        # the 3000 ns under attn.select and attn.sparse in the blocks
        "sparse_attn_roofline.sparse":
            100 * steps * 5 * live * 2048 * 1152 / (3000e-9 * bw),
        # moe.experts inside the blocks: 800 + 1200 ns
        "expert_matmul_roofline.sparse":
            100 * steps * needed.expert_bytes(cell.config, hit)
            / (2000e-9 * bw),
    }


def test_every_sparse_metric_has_its_reader_and_lists_the_one_cell():
    assert {m["name"] for m in SPARSE} == set(expected())
    for m in SPARSE:
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
        assert m in harness.Cell(CELL).per_layer()
        if "roofline" in m["name"] or m["name"].endswith("_share.sparse"):
            assert m["unit"] in ("%", "ratio")


@pytest.mark.parametrize("name", sorted(m["name"] for m in SPARSE))
def test_reader_on_the_fixture(name, monkeypatch):
    monkeypatch.setattr(program, "planes_of", lambda run: PLANES)
    monkeypatch.setattr(program, "ring", lambda: (RING, 0.0))
    assert reader(name).read(a_run()) == pytest.approx(expected()[name])


@pytest.mark.parametrize("name", sorted(m["name"] for m in SPARSE))
def test_reader_finds_nothing_in_an_empty_run(name, monkeypatch):
    monkeypatch.setattr(program, "ring", lambda: ({}, 0.0))
    run_ = a_run(gaps=())
    assert reader(name).read(run_) is None
    run_["trace"] = None
    assert reader(name).read(run_) is None


@pytest.mark.parametrize("name", sorted(
    m["name"] for m in SPARSE if m["name"] not in (
        "itl_p50_ms.sparse", "block_device_ms.sparse",
        "prefill_device_share.sparse")))
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


@pytest.mark.parametrize("name", [
    "decode_hbm_share.sparse", "index_score_roofline.sparse",
    "sparse_attn_roofline.sparse", "expert_matmul_roofline.sparse",
    "kv_read_share.sparse"])
def test_a_share_of_a_peak_is_the_chips_to_report(name, monkeypatch):
    monkeypatch.setattr(program, "planes_of", lambda run: PLANES)
    monkeypatch.setattr(program, "ring", lambda: (RING, 0.0))
    cpu = {"platform": "cpu", "kind": "cpu", "count": 1}
    assert reader(name).read(a_run(device=cpu)) is None
