"""``models/deepseek_v3.py`` (latent attention, dropless sigmoid-routed
experts beside shared ones) against the benchmark's plain reference, at
tiny widths with seeded random weights: logits, not tokens. Tolerances:
float32 at ``highest`` against float32 at ``highest`` differs by the
order of summation alone (a few 1e-6 at logits of order 4), so 1e-4
holds every path and a bfloat16 run (errors of 1e-2 and more) fails
each of them."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.families import mla_moe as family
from benchmark.reference import mla_moe as reference
from edl_tpu.models import deepseek_v3 as ds
from edl_tpu.models import llama
from edl_tpu.ops.decode_attention import decode_attention_latent
from edl_tpu.ops.flash_attention import flash_attention, interpret_kernels
from edl_tpu.parallel import moe
from edl_tpu.serving.engine import ContinuousBatchingEngine
from edl_tpu.utils import tracing

CONFIG = family.rehearsal_config()
LAYOUT = family.param_layout(CONFIG)
TOL = 1e-4


def cfg_of(dtype=jnp.float32, **kw):
    return dataclasses.replace(
        family.program_config(CONFIG, training=False),
        **{"dtype": dtype, "use_flash": False, **kw})


@pytest.fixture(scope="module")
def params():
    return harness.make_params(11, LAYOUT, jnp.float32)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, 256, (2, 40), dtype=np.int32)


@pytest.fixture(scope="module")
def ref_logits(params, tokens):
    return jnp.stack([reference.logits_row(params, jnp.asarray(row), CONFIG)
                      for row in tokens])


def err(a, b):
    return float(jnp.max(jnp.abs(a - b)))


# -- (a) forward against the reference ----------------------------------------


def test_forward_is_the_references_in_float32(params, tokens, ref_logits):
    with jax.default_matmul_precision("highest"):
        got = ds.forward(params, jnp.asarray(tokens), cfg_of())
    assert err(got, ref_logits) < TOL


def test_a_bfloat16_forward_fails_that_tolerance(params, tokens, ref_logits):
    got = ds.forward(params, jnp.asarray(tokens), cfg_of(jnp.bfloat16))
    assert err(got, ref_logits) > 10 * TOL


def test_init_params_has_the_benchmarks_layout():
    tree = ds.init_params(jax.random.PRNGKey(0), cfg_of())
    flat = {tuple(k.key for k in path): leaf.shape for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert flat == {path: shape for path, (shape, _, _) in LAYOUT.items()}


# -- (b) prefill, then decode through the engine and the latent cache ---------


def test_prefill_then_slot_decode_is_the_references_full_forward(
        params, tokens, ref_logits):
    cfg = cfg_of()
    toks = jnp.asarray(tokens)
    with jax.default_matmul_precision("highest"):
        # two prompts of different lengths in one padded batch
        last = jnp.array([15, 9])
        logits, rows = ds.prefill_padded(params, toks[:, :16], last, cfg)
        assert err(logits[0], ref_logits[0, 15]) < TOL
        assert err(logits[1], ref_logits[1, 9]) < TOL
        cache = jnp.zeros((cfg.n_layers, 2, 64, cfg.cache_width))
        cache = cache.at[:, :, :16].set(rows)
        pos = last + 1
        for _ in range(8):
            tok = toks[jnp.arange(2), pos]
            logits, cache, _ = ds.decode_step_slots(params, tok, pos, cache, cfg)
            for r in range(2):
                assert err(logits[r], ref_logits[r, pos[r]]) < TOL
            pos = pos + 1


@pytest.mark.parametrize("use_flash, horizon", [
    (False, 1), (True, 1),
    (True, 4),  # kanana2.decode-wide's: four steps a dispatch
])
def test_engine_serves_the_references_greedy_tokens(
        params, use_flash, horizon):
    """The engine (scheduler, slots, buckets, double buffer) over the
    latent cache: every served token is the float32 reference's first
    choice, or within bfloat16's reach of it."""
    cfg = cfg_of(use_flash=use_flash)
    eng = ContinuousBatchingEngine(
        params, cfg, max_slots=3, max_len=64, horizon=horizon)
    rng = np.random.default_rng(1)
    prompts = {f"r{i}": [int(t) for t in rng.integers(0, 256, n)]
               for i, n in enumerate((9, 17, 5, 12))}
    with interpret_kernels(), jax.default_matmul_precision("highest"):
        for rid, prompt in prompts.items():
            eng.submit(rid, prompt, 6)
        results = eng.run()
    assert eng.recoveries == 0
    for rid, prompt in prompts.items():
        out = list(results[rid].tokens)
        assert results[rid].outcome == "done" and len(out) == 6
        seq = jnp.asarray(prompt + out[:-1])
        lg = reference.logits_row(params, seq, CONFIG)[len(prompt) - 1:]
        gap = jnp.max(lg, -1) - lg[jnp.arange(6), jnp.asarray(out)]
        assert float(jnp.max(gap)) < TOL, (rid, gap)


def test_the_block_counts_its_routing_onto_the_dispatch_span(params):
    cfg = cfg_of()
    eng = ContinuousBatchingEngine(params, cfg, max_slots=2, max_len=32)
    before = len(tracing.tracer().spans("serving.dispatch"))
    eng.submit("a", [3, 4, 5, 6, 7], 4)
    eng.run()
    spans = tracing.tracer().spans("serving.dispatch")[before:]
    assert spans
    for s in spans:
        assert s.attrs["kv_read_share"] == 1.0  # the dense lines read all
    # one live row of three choices among eight experts; the block the
    # double buffer sent after the row froze counts no row at all
    hits = [s.attrs["experts_hit_share"] for s in spans]
    assert hits[0] == pytest.approx(3 / 8) and set(hits) <= {3 / 8, 0.0}
    assert spans[0].attrs["expert_load_max_over_mean"] == pytest.approx(8 / 3)


@pytest.mark.parametrize("option", [
    {"block_size": 16}, {"block_size": 16, "kv_quant": "int8"},
    {"block_size": 16, "prefill_chunk": 16},
    {"block_size": 16, "prefix_cache": True}, {"spec_k": 2}])
def test_the_twins_refuse_another_model_at_construction(params, option):
    with pytest.raises(ValueError, match="contiguous cache alone"):
        ContinuousBatchingEngine(
            params, cfg_of(), max_slots=2, max_len=32, **option)


def test_a_config_of_no_served_model_is_refused():
    @dataclasses.dataclass(frozen=True)
    class Stray:
        vocab: int = 8

    with pytest.raises(TypeError, match="cannot be served"):
        ContinuousBatchingEngine({}, Stray(), max_slots=1, max_len=8)


# -- (c) absorbed against expanded attention ----------------------------------


def test_absorbed_attention_is_the_expanded(params):
    cfg = cfg_of()
    lp = params["layers"]["01"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 12, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        q_nope, q_rope, row = ds._latent(cfg, x, lp)
        want = ds.attention_expanded(cfg, q_nope, q_rope, row, lp)
        cache = jnp.zeros((2, 2, 16, cfg.cache_width)).at[1, :, :12].set(row)
        for t in (0, 5, 11):
            got = ds.attention_absorbed(
                cfg, q_nope[:, t], q_rope[:, t], cache, 1,
                jnp.array([t, t]), lp)
            assert err(got, want[:, t]) < 1e-5


def test_rope_turns_neighbouring_pairs():
    """The program takes pairs apart and rotates halves, the reference
    turns them in place: the same numbers, evens before odds."""
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 7, 3, 8))
    got = ds._rope_pairs(x, 1e4, None)[0]
    want = reference._rope_pairs(x[0], 1e4)
    assert err(got, jnp.concatenate(
        [want[..., 0::2], want[..., 1::2]], -1)) < 1e-6


# -- the kernels --------------------------------------------------------------


@pytest.mark.parametrize("block_s", [8, 16, 32])
def test_latent_kernel_is_the_dense_lines(block_s):
    key = jax.random.split(jax.random.PRNGKey(5), 2)
    layers, b, s, h, rank, width = 3, 5, 32, 4, 128, 256
    cache = jax.random.normal(key[0], (layers, b, s, width), jnp.float32)
    q = jax.random.normal(key[1], (b, h, width), jnp.float32)
    pos = jnp.array([0, 7, 8, 20, 31])
    got = decode_attention_latent(
        q, cache, pos, jnp.int32(2), rank=rank, sm_scale=0.11,
        block_s=block_s, interpret=True)
    want = ds.slot_attention_latent_dense(q, cache[2], pos, rank, 0.11)
    assert got.shape == (b, h, rank)
    assert err(got, want) < 2e-5


def test_flash_forward_takes_values_of_another_width():
    key = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(key[0], (1, 256, 2, 24), jnp.float32)
    k = jax.random.normal(key[1], (1, 256, 2, 24), jnp.float32)
    v = jax.random.normal(key[2], (1, 256, 2, 16), jnp.float32)
    got = flash_attention(q, k, v, interpret=True)
    s = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(24)
    s = jnp.where(jnp.tril(jnp.ones((256, 256), bool)), s, -jnp.inf)
    want = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v)
    assert got.shape == (1, 256, 2, 16) and err(got, want) < 2e-5
    with pytest.raises(NotImplementedError, match="forward only"):
        jax.grad(lambda q: flash_attention(q, k, v, interpret=True).sum())(q)


# -- (d) the router -----------------------------------------------------------


def test_the_bias_moves_the_choice_and_not_the_weight():
    key = jax.random.split(jax.random.PRNGKey(7), 2)
    x = jax.random.normal(key[0], (64, 16))
    router = jax.random.normal(key[1], (16, 8)) * 0.25
    none = jnp.zeros(8)
    idx0, w0 = moe.route_sigmoid_topk(x, router, none, 3, 2.448)
    # a bias that lifts expert 5 over everything: always chosen
    idx1, w1 = moe.route_sigmoid_topk(
        x, router, none.at[5].set(10.0), 3, 2.448)
    assert bool(jnp.all(jnp.any(idx1 == 5, axis=-1)))
    assert not bool(jnp.all(jnp.any(idx0 == 5, axis=-1)))
    # weights are the UNCORRECTED scores of the chosen, normalised
    s = jax.nn.sigmoid(x @ router)
    chosen = jnp.take_along_axis(s, idx1, -1)
    want = chosen / chosen.sum(-1, keepdims=True) * 2.448
    assert err(w1, want) < 1e-6
    np.testing.assert_allclose(np.asarray(w1.sum(-1)), 2.448, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w0.sum(-1)), 2.448, rtol=1e-6)
    # where the bias changed nothing of the choice, nothing changed
    same = jnp.all(jnp.sort(idx0, -1) == jnp.sort(idx1, -1), axis=-1)
    assert err(jnp.sort(w0, -1)[same], jnp.sort(w1, -1)[same]) < 1e-6
    # the reference's table says the same
    table = reference.route(x, router, none.at[5].set(10.0), {
        "num_experts_per_tok": 3, "norm_topk_prob": True,
        "routed_scaling_factor": 2.448})
    dense = jnp.zeros((64, 8)).at[jnp.arange(64)[:, None], idx1].set(w1)
    assert err(table, dense) < 1e-6


# -- (e) no drops -------------------------------------------------------------


def expert_weights(key, e=8, d=16, f=12):
    k = jax.random.split(key, 3)
    return (jax.random.normal(k[0], (e, d, f)) * d ** -0.5,
            jax.random.normal(k[1], (e, d, f)) * d ** -0.5,
            jax.random.normal(k[2], (e, f, d)) * f ** -0.5)


def test_every_token_sent_to_one_expert_still_gets_its_result():
    """48 tokens, all routed to expert 2 (and 5): a capacity of 1.25
    would keep 23 and drop the rest."""
    w1, w3, w2 = expert_weights(jax.random.PRNGKey(8))
    x = jax.random.normal(jax.random.PRNGKey(9), (48, 16))
    idx = jnp.tile(jnp.array([[2, 5]]), (48, 1))
    w = jnp.tile(jnp.array([[0.7, 0.3]]), (48, 1))
    with jax.default_matmul_precision("highest"):
        got = moe.moe_dropless(x, idx, w, w1, w3, w2)
        one = lambda e: (jax.nn.silu(x @ w1[e]) * (x @ w3[e])) @ w2[e]
        want = 0.7 * one(2) + 0.3 * one(5)
    assert err(got, want) < 1e-5
    assert float(jnp.min(jnp.linalg.norm(got, axis=-1))) > 0


def test_dropless_layer_is_the_references_table(params):
    lp = params["layers"]["02"]
    x = jax.random.normal(jax.random.PRNGKey(10), (50, CONFIG["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        idx, w = moe.route_sigmoid_topk(
            x, lp["router"], lp["router_bias"], 3, 2.448)
        got = moe.moe_dropless(x, idx, w, lp["we1"], lp["we3"], lp["we2"])
    table = reference.route(x, lp["router"], lp["router_bias"], CONFIG)
    want = reference.routed(x, table, lp["we1"], lp["we3"], lp["we2"])
    assert err(got, want) < 1e-5


def test_int8_records_go_through_the_grouped_matmul():
    w1, w3, w2 = expert_weights(jax.random.PRNGKey(11))
    x = jax.random.normal(jax.random.PRNGKey(12), (20, 16))
    idx = jax.random.randint(jax.random.PRNGKey(13), (20, 2), 0, 8)
    w = jnp.full((20, 2), 0.5)
    tree = {"layers": {"00": {"we1": w1, "we3": w3, "we2": w2}},
            "lm_head": jnp.ones((4, 4))}
    q = ds.quantize_params_int8(tree)["layers"]["00"]
    assert q["we1"]["q8"].dtype == jnp.int8 and q["we1"]["s8"].shape == (8, 12)
    got = moe.moe_dropless(x, idx, w, q["we1"], q["we3"], q["we2"])
    want = moe.moe_dropless(x, idx, w, w1, w3, w2)
    assert 1e-4 < err(got, want) < 0.1  # int8's error, and no more


# -- (f) shares of the experts add up ------------------------------------------


def test_eight_shares_of_the_experts_add_up_to_the_uncut_layer():
    """128 experts held as eight shares of 16, each share's chip
    scoring all 128: the shares' routed terms sum to the reference's
    whole layer, and the shared expert is counted once."""
    d, f, e, k = 32, 16, 128, 6
    key = jax.random.split(jax.random.PRNGKey(14), 8)
    lp = {"router": jax.random.normal(key[0], (d, e)) * d ** -0.5,
          "router_bias": jax.random.normal(key[1], (e,)) * 0.02}
    lp["we1"], lp["we3"], lp["we2"] = expert_weights(key[2], e, d, f)
    shared = expert_weights(key[3], 1, d, 2 * f)
    x = jax.random.normal(key[4], (40, d))
    config = {"num_experts_per_tok": k, "norm_topk_prob": True,
              "routed_scaling_factor": 2.448}
    with jax.default_matmul_precision("highest"):
        idx, w = moe.route_sigmoid_topk(
            x, lp["router"], lp["router_bias"], k, 2.448)
        total = jnp.zeros_like(x)
        for first in range(0, e, 16):
            total += moe.moe_dropless(
                x, idx, w, lp["we1"][first:first + 16],
                lp["we3"][first:first + 16], lp["we2"][first:first + 16],
                first=first)
        total += reference._swiglu(x, shared[0][0], shared[1][0], shared[2][0])
    table = reference.route(x, lp["router"], lp["router_bias"], config)
    want = reference.routed(x, table, lp["we1"], lp["we3"], lp["we2"]) \
        + reference._swiglu(x, shared[0][0], shared[1][0], shared[2][0])
    assert err(total, want) < 1e-5
    # and one share alone is not the layer
    assert err(moe.moe_dropless(
        x, idx, w, lp["we1"][:16], lp["we3"][:16], lp["we2"][:16]), want) > 0.1


def test_expert_load_counts_live_rows():
    idx = jnp.array([[0, 1], [0, 2], [3, 3]])
    hit, skew = moe.expert_load(idx, 8)
    assert float(hit) == 0.5 and float(skew) == pytest.approx(2 / (6 / 8))
    hit, skew = moe.expert_load(idx, 8, jnp.array([True, False, False]))
    assert float(hit) == 0.25 and float(skew) == pytest.approx(1 / (2 / 8))


# -- (g) the dense decoder through the seam -----------------------------------


def test_the_dense_decoder_answers_the_seam_with_its_own_programs():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    eng = ContinuousBatchingEngine(params, cfg, max_slots=2, max_len=32)
    # the dense decoder's cache is the spec's two arrays
    assert [(c.shape, c.dtype) for c in eng._cache] == [
        (shape, dtype) for shape, dtype in cfg.serve_cache_spec(2, 32)]
    assert eng._cache[0].shape == (
        cfg.n_layers, 2, 32, cfg.n_kv_heads, cfg.head_dim)
    prompt = [5, 6, 7, 8, 9]
    eng.submit("a", prompt, 6)
    got = list(eng.run()["a"].tokens)
    want = llama.generate(params, jnp.asarray([prompt]), cfg, max_new=6)
    assert got == [int(t) for t in np.asarray(want)[0]]


# -- the cost model's seam -----------------------------------------------------


def test_the_cost_model_counts_activated_parameters():
    from edl_tpu.obs import costmodel as cm

    cfg = ds.DeepseekV3Config(n_layers=8)
    attn = 2048 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 + 4096 * 2048
    assert cfg.attn_params() == attn
    moe_layer = 3 * 2048 * 768 * (6 + 2) + 2048 * 128
    assert cm.matmul_params(cfg) == 8 * attn + 3 * 2048 * 6144 \
        + 7 * moe_layer + 2048 * 128256
    assert round(cm.n_params(cfg) / 1e9, 2) == 5.07
    # the cache the engine holds: 640 columns a position, 8 layers, bf16
    assert cm.kv_cache_bytes(cfg, 96, 4096) == 96 * 4096 * 8 * 640 * 2
    # the dense decoder is priced as it was
    dense = llama.LlamaConfig.tiny()
    d, h, kv, hd, ff, L, V = cm._dims(dense)
    assert cm.matmul_params(dense) == L * (
        d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff) + d * V
    assert cm.kv_cache_bytes(dense, 2, 16) == 2.0 * L * 2 * 16 * kv * hd * 2


# -- ``edl serve`` ------------------------------------------------------------


def test_cli_serve_serves_a_deepseek_v3_export(tmp_path, params):
    """The same verb, scheduler and engine as the dense decoder's: an
    export whose record says ``deepseek_v3`` is served, its tokens the
    float32 reference's; what the engine keeps for the dense decoder is
    refused by name."""
    import json
    import os
    import subprocess
    import sys

    from edl_tpu.runtime.export import export_params

    cfg = cfg_of()
    assert ds.DeepseekV3Config.from_meta(
        json.loads(json.dumps(cfg.to_meta()))) == cfg
    export_params(str(tmp_path), params, step=1, dtype="float32",
                  model_meta=cfg.to_meta())
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.path.dirname(os.path.dirname(__file__))}
    prompt = [int(t) for t in np.random.default_rng(2).integers(0, 256, 11)]
    serve = [sys.executable, "-m", "edl_tpu.cli", "serve", str(tmp_path)]
    out = subprocess.run(
        serve + ["--max-slots", "2", "--max-len", "32"],
        input=json.dumps({"id": "a", "prompt": prompt, "max_new": 5}) + "\n",
        capture_output=True, text=True, env=env)
    assert out.returncode == 0, out.stderr
    (rec,) = [json.loads(l) for l in out.stdout.strip().splitlines()]
    assert rec["outcome"] == "done" and len(rec["tokens"]) == 5
    lg = reference.logits_row(
        params, jnp.asarray(prompt + rec["tokens"][:-1]), CONFIG)[10:]
    gap = jnp.max(lg, -1) - lg[jnp.arange(5), jnp.asarray(rec["tokens"])]
    assert float(jnp.max(gap)) < 1e-3
    bad = subprocess.run(
        serve + ["--block-size", "16", "--max-len", "32"],
        input='{"prompt": [1]}\n', capture_output=True, text=True, env=env)
    assert bad.returncode != 0 and "contiguous cache alone" in bad.stderr
    gen = subprocess.run(
        [sys.executable, "-m", "edl_tpu.cli", "generate", str(tmp_path),
         "--prompt", "1,2"], capture_output=True, text=True, env=env)
    assert gen.returncode == 1 and "no llama architecture" in gen.stderr
