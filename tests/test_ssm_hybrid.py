"""``models/ssm_hybrid.py`` (Mamba-2 layers beside attention layers, a
per-slot state and a positional cache in one cache tuple) against the
benchmark's plain reference, which runs the recurrence position by
position, at tiny widths with seeded weights and both kinds of layer in
a pattern that is not the published one: logits, not tokens.
Tolerances: float32 against float32 at ``highest`` differs by the order
of summation alone, so 1e-6 holds every path at logits of order 0.01
(they are divided by ``logits_scaling`` and the tied embedding is drawn
at 0.02 / ``embedding_multiplier``: a few 1e-8 read); a bfloat16 run
differs by rounding (1e-4 read), and each part of the layer left out or
mis-scaled fails by far more than either."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.families import ssm_hybrid as family
from benchmark.reference import ssm_hybrid as reference
from edl_tpu.models import llama
from edl_tpu.models import ssm_hybrid as sh
from edl_tpu.obs import costmodel as cm
from edl_tpu.obs import memledger
from edl_tpu.ops.flash_attention import interpret_kernels
from edl_tpu.serving.engine import ContinuousBatchingEngine
from edl_tpu.utils import faults, tracing

CONFIG = family.rehearsal_config()
LAYOUT = family.param_layout(CONFIG)
TOL = 1e-6


# one compile a shape: run op by op, the reference and the model make
# thousands of small programs, and XLA:CPU has crashed on the way
fwd = jax.jit(sh.forward, static_argnums=2)
prefill = jax.jit(sh.prefill_padded, static_argnums=3)
step = jax.jit(sh.decode_step_slots, static_argnums=4)
ref_row = jax.jit(lambda p, row: reference.logits_row(p, row, CONFIG))


def cfg_of(dtype=jnp.float32, **kw):
    return dataclasses.replace(
        family.program_config(CONFIG, training=False),
        **{"dtype": dtype, "use_kernel": False, **kw})


@pytest.fixture(scope="module")
def params():
    return family.published_form(
        harness.make_params(11, LAYOUT, jnp.float32))


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, 256, (2, 40), dtype=np.int32)


@pytest.fixture(scope="module")
def ref_logits(params, tokens):
    return jnp.stack([ref_row(params, jnp.asarray(row))
                      for row in tokens])


def err(a, b):
    return float(jnp.max(jnp.abs(a - b)))


def empty_cache(cfg, slots, max_len=64):
    return tuple(jnp.zeros(shape, dtype)
                 for shape, dtype in cfg.serve_cache_spec(slots, max_len))


def into(cfg, rows, slots, max_len=64):
    """A prefill's rows written into the first slots of an empty cache."""
    return tuple(
        jax.lax.dynamic_update_slice(c, r, (0,) * c.ndim)
        for c, r in zip(empty_cache(cfg, slots, max_len), rows))


# -- (a) the sizes ---------------------------------------------------------------


def test_the_sizes_exercise_both_kinds_and_the_packed_cache():
    cfg = cfg_of()
    assert cfg.layer_types == (
        "mamba", "attention", "mamba", "mamba", "attention")
    published = sh.SSMHybridConfig().layer_types
    assert cfg.layer_types != published[:5]
    # runs of one kind: a run of state-space layers is one loop
    assert cfg.runs == (("mamba", 0, 1), ("attention", 0, 1),
                        ("mamba", 1, 2), ("attention", 1, 1))
    assert sh.SSMHybridConfig().runs == (
        ("mamba", 0, 5), ("attention", 0, 1), ("mamba", 5, 9),
        ("attention", 1, 1), ("mamba", 14, 9), ("attention", 2, 1),
        ("mamba", 23, 9), ("attention", 3, 1), ("mamba", 32, 4))
    # two kv heads of 64 a 128-lane row, as published
    assert (cfg.head_dim, cfg.n_kv_heads, cfg.kv_pack) == (64, 2, 2)
    assert sh.SSMHybridConfig().kv_pack == 2
    assert [shape for shape, _ in cfg.serve_cache_spec(3, 64)] == [
        (3, 3, 8, 16, 32), (3, 3, 3 * 192), (2, 3, 64, 1, 128),
        (2, 3, 64, 1, 128)]
    # a head as wide as a lane tile, or kv heads that do not pair, stay
    assert dataclasses.replace(cfg, head_dim=128).kv_pack == 1
    assert dataclasses.replace(cfg, n_heads=3, n_kv_heads=3).kv_pack == 1


# -- (b) forward against the reference --------------------------------------------


@pytest.mark.parametrize("chunk", [3, 8, 16, 64])
def test_forward_is_the_references(params, tokens, ref_logits, chunk):
    """Chunk lengths that divide T = 40 (8), that do not (3, 16), and
    one longer than the sequence."""
    got = fwd(params, jnp.asarray(tokens), cfg_of(chunk=chunk))
    assert err(got, ref_logits) < TOL


def test_forward_in_bfloat16_is_the_references_by_rounding(tokens):
    p16 = family.published_form(harness.make_params(11, LAYOUT, jnp.bfloat16))
    ref = jnp.stack([ref_row(p16, jnp.asarray(row))
                     for row in tokens])
    got = fwd(p16, jnp.asarray(tokens), cfg_of(jnp.bfloat16))
    assert 20 * TOL < err(got, ref) < 6e-4


def test_the_kernels_are_the_plain_lines(params, tokens, ref_logits):
    """``edl_flash_fwd`` at the config's scale in the Pallas interpreter
    (a bucket of 128: the kernel's smallest block)."""
    toks = jnp.asarray(np.random.default_rng(1).integers(
        0, 256, (1, 128), dtype=np.int32))
    plain = fwd(params, toks, cfg_of())
    with interpret_kernels():
        kernel = fwd(params, toks, cfg_of(use_kernel=True))
    assert err(kernel, plain) < TOL


@pytest.mark.parametrize("fault", [
    "residual_multiplier", "attention_scale", "skip_connection",
    "embedding_multiplier", "logits_scaling", "conv_bias", "tied_head"])
def test_the_comparison_sees_each_part_of_the_model(
        params, tokens, ref_logits, fault):
    """What the chip's planted faults (and a few more) do to the
    logits, held here exactly: each leaves the reference by far more
    than the tolerance."""
    cfg, p = cfg_of(), params
    if fault == "residual_multiplier":
        cfg = cfg_of(residual_multiplier=1.0)
    elif fault == "attention_scale":
        cfg = cfg_of(attention_multiplier=cfg.head_dim ** -0.5)
    elif fault == "skip_connection":
        p = {**p, "mamba": {**p["mamba"], "D": 0 * p["mamba"]["D"]}}
    elif fault == "embedding_multiplier":
        cfg = cfg_of(embedding_multiplier=1.0)
    elif fault == "logits_scaling":
        cfg = cfg_of(logits_scaling=1.0)
    elif fault == "conv_bias":
        p = {**p, "mamba": {**p["mamba"],
                            "conv_b": 0 * p["mamba"]["conv_b"]}}
    elif fault == "tied_head":
        p = {**p, "embed": p["embed"][::-1]}
    got = fwd(p, jnp.asarray(tokens), cfg)
    assert err(got, ref_logits) > 100 * TOL


# -- (c) prefill into a padded bucket, then decode through the cache ---------------


# ``last`` at 0, 1, 2 (the convolution's tail reaches before position
# 0), mid-bucket and at the bucket's last row
@pytest.mark.parametrize("last", [0, 1, 2, 9, 15])
@pytest.mark.parametrize("kernel", [False, True])
def test_prefill_then_decode_is_the_references_full_forward(
        params, tokens, ref_logits, last, kernel):
    cfg = cfg_of(use_kernel=kernel)
    bucket = 16
    padded = np.full((2, bucket), 7, np.int32)  # garbage past ``last``
    padded[:, :last + 1] = tokens[:, :last + 1]
    logits, *rows = prefill(
        params, jnp.asarray(padded), jnp.array([last, last]), cfg_of())
    assert err(logits, ref_logits[:, last]) < TOL
    cache = into(cfg, rows, 2)
    with interpret_kernels():
        for t in range(last + 1, last + 20):
            logits, cache = step(
                params, jnp.asarray(tokens[:, t]), jnp.full((2,), t), cache,
                cfg)
            assert err(logits, ref_logits[:, t]) < TOL, t


@pytest.mark.parametrize("bucket", [16, 32, 64])
def test_a_prompt_leaves_the_same_cache_in_every_bucket(params, tokens, bucket):
    """The state after ``last``, the tail at ``last - 2 .. last`` and
    the keys up to ``last`` do not depend on the padding."""
    last = 10
    exact = prefill(
        params, jnp.asarray(tokens[:, :last + 1]), jnp.array([last, last]),
        cfg_of())
    padded = np.full((2, bucket), 3, np.int32)
    padded[:, :last + 1] = tokens[:, :last + 1]
    got = prefill(
        params, jnp.asarray(padded), jnp.array([last, last]), cfg_of())
    assert err(got[0], exact[0]) < TOL
    # the cache's entries are of order 1 to 10, not a logit's 0.01
    assert err(got[1], exact[1]) < 1e-5 and err(got[2], exact[2]) < 1e-5
    for g, e in zip(got[3:], exact[3:]):
        assert err(g[:, :, :last + 1], e) < 1e-5


def test_a_padded_tail_let_into_the_state_is_seen(params, tokens):
    """The planted fault of the chip's comparison: without the mask the
    bucket's rows past ``last`` decay the state and enter it."""
    last = 5
    padded = np.full((2, 16), 3, np.int32)
    padded[:, :last + 1] = tokens[:, :last + 1]
    args = (params, jnp.asarray(padded))
    want = prefill(*args, jnp.array([last, last]), cfg_of())
    wrong = prefill(*args, jnp.array([15, 15]), cfg_of())
    assert err(wrong[1], want[1]) > 1e-3 and err(wrong[2], want[2]) > 1e-3


def test_an_idle_row_keeps_its_state_and_its_tail(params, tokens):
    """A frozen row's recurrence is not re-run: a step with the row not
    live leaves its state and its tail, and the live row's are what
    they are with both live."""
    cfg = cfg_of()
    _, *rows = prefill(
        params, jnp.asarray(tokens[:, :8]), jnp.array([7, 7]), cfg)
    cache = into(cfg, rows, 2)
    tok, pos = jnp.asarray(tokens[:, 8]), jnp.full((2,), 8)
    _, both = step(params, tok, pos, cache, cfg)
    _, one = step(
        params, tok, pos, cache, cfg, live=jnp.array([True, False]))
    for i in (0, 1):  # S, the tail
        assert err(one[i][:, 1], cache[i][:, 1]) == 0.0
        assert err(one[i][:, 0], both[i][:, 0]) == 0.0
        assert err(both[i][:, 1], cache[i][:, 1]) > 1e-4


# -- (d) precision: the control ----------------------------------------------------


def test_int8_differs_from_bfloat16_by_more_than_bfloat16_from_the_reference(
        tokens):
    """The control of the chip's comparison at tiny size: the served
    tree in bfloat16 leaves the float32 reference by rounding; the int8
    records leave the bfloat16 program by clearly more."""
    p16 = family.published_form(harness.make_params(11, LAYOUT, jnp.bfloat16))
    cfg = cfg_of(jnp.bfloat16)
    toks = jnp.asarray(tokens)
    bf16 = fwd(p16, toks, cfg)
    q = sh.quantize_params_int8(p16)
    int8 = fwd(q, toks, cfg)
    ref16 = jnp.stack([ref_row(p16, row)
                       for row in toks])
    mean = lambda a, b: float(jnp.mean(jnp.abs(a - b)))
    sound, control = mean(bf16, ref16), mean(int8, bf16)
    assert control > 1.5 * sound > 0
    assert q["mamba"]["in_proj"]["q8"].dtype == jnp.int8
    assert q["attn"]["wq"]["q8"].dtype == jnp.int8
    assert q["lm_head"]["q8"].shape == (64, 256)  # the embedding, as a head
    assert q["mamba"]["dt_proj"].dtype == jnp.bfloat16  # the steps stay
    assert q["embed"].dtype == jnp.bfloat16  # the lookup keeps its rows
    # the family's mapping is applied once however often it is called
    again = family.published_form(p16)
    assert all(bool(jnp.all(a == b)) for a, b in zip(
        jax.tree_util.tree_leaves(again), jax.tree_util.tree_leaves(p16)))


def test_a_bfloat16_state_is_seen(params, tokens, ref_logits):
    """The state kept in bfloat16 (a planted fault of the chip's
    comparison): rounded after every step, the carried sums leave the
    reference by far more than the tolerance."""
    cfg = cfg_of()
    cache = empty_cache(cfg, 2)
    worst = 0.0
    for t in range(30):
        logits, cache = step(
            params, jnp.asarray(tokens[:, t]), jnp.full((2,), t), cache, cfg)
        cache = (jax.lax.reduce_precision(cache[0], 8, 7),) + cache[1:]
        worst = max(worst, err(logits, ref_logits[:, t]))
    assert worst > 3 * TOL  # 5.7e-6 read at logits of std 0.0017


# -- (e) the engine ------------------------------------------------------------------


def alone(params, cfg, prompt, n):
    """The tokens a request gets with the server to itself."""
    eng = ContinuousBatchingEngine(params, cfg, max_slots=1, max_len=64)
    eng.submit("x", prompt, n)
    return list(eng.run()["x"].tokens)


def prompts_of(tokens):
    return {"a": [int(t) for t in tokens[0, :11]],
            "b": [int(t) for t in tokens[1, :19]],
            "c": [int(t) for t in tokens[0, 5:7]],  # shorter than the tail
            "d": [int(t) for t in tokens[1, 3:30]]}


@pytest.mark.parametrize("kernel", [False, True])
def test_the_engine_serves_the_reference(params, tokens, kernel):
    """Prefill into a slot, then decode through the engine's cache: the
    served tokens are the reference's first choice at every position
    (logits: the gap of each served token under the reference's best)."""
    cfg = cfg_of(use_kernel=kernel)
    prompt = prompts_of(tokens)["b"]
    with interpret_kernels():
        eng = ContinuousBatchingEngine(params, cfg, max_slots=2, max_len=64)
        # one tuple, four arrays of two depths and two kinds
        assert [(c.shape, c.dtype) for c in eng._cache] == [
            (shape, dtype) for shape, dtype in cfg.serve_cache_spec(2, 64)]
        assert [c.shape[0] for c in eng._cache] == [3, 3, 2, 2]
        eng.submit("b", prompt, 9)
        out = list(eng.run()["b"].tokens)
    lg = ref_row(params, jnp.asarray(prompt + out[:-1]))[len(prompt) - 1:]
    gap = jnp.max(lg, -1) - lg[jnp.arange(9), jnp.asarray(out)]
    assert float(jnp.max(gap)) < TOL


@pytest.mark.parametrize("horizon", [1, 4])
def test_joins_and_leaves_give_each_request_the_tokens_it_gets_alone(
        params, tokens, horizon):
    """Continuous batching over both kinds of cache: requests of
    different lengths join and leave mid-run through two slots (so
    slots are reused after a finished request, and a slot's first step
    follows another request's last), and every one gets the tokens it
    gets alone: batched greedy is sequential generation."""
    cfg = cfg_of()
    prompts = prompts_of(tokens)
    budget = {"a": 5, "b": 12, "c": 3, "d": 7}
    eng = ContinuousBatchingEngine(
        params, cfg, max_slots=2, max_len=64, horizon=horizon)
    eng.submit("a", prompts["a"], budget["a"])
    eng.submit("b", prompts["b"], budget["b"])
    for _ in range(3):
        eng.step()
    eng.submit("c", prompts["c"], budget["c"])
    eng.submit("d", prompts["d"], budget["d"])
    results = eng.run()
    for rid, prompt in prompts.items():
        assert list(results[rid].tokens) == alone(
            params, cfg, prompt, budget[rid]), rid


def test_a_reused_slot_starts_from_a_clean_cache(params, tokens):
    """One slot, two requests in turn: the second's prefill replaces
    the state and the tail whole, so it gets what it gets on a fresh
    engine."""
    cfg = cfg_of()
    prompts = prompts_of(tokens)
    eng = ContinuousBatchingEngine(params, cfg, max_slots=1, max_len=64)
    eng.submit("d", prompts["d"], 9)
    eng.run()
    assert float(jnp.max(jnp.abs(eng._cache[0]))) > 1e-3
    eng.submit("c", prompts["c"], 6)
    assert list(eng.run()["c"].tokens) == alone(params, cfg, prompts["c"], 6)


def test_the_dispatch_span_carries_both_shares(params, tokens):
    cfg = cfg_of()
    eng = ContinuousBatchingEngine(params, cfg, max_slots=4, max_len=64)
    assert eng._attn_block == 64
    eng.submit("a", prompts_of(tokens)["a"], 4)
    before = len(tracing.tracer().spans("serving.dispatch"))
    eng.run()
    mine = tracing.tracer().spans("serving.dispatch")[before:]
    assert mine and all(s.attrs["state_live_share"] == 0.25 for s in mine)
    # the dense read: the live slot's one block and one a idle slot
    assert all(s.attrs["kv_read_share"] == 1.0 for s in mine)
    held = [3, None, 60, None]
    assert cfg.serve_cache_read(held, 64, 16) == {
        "state_live_share": 0.5,
        "kv_read_share": llama.positional_read_share(held, 64, 16)}
    assert cfg.serve_cache_read(held, 64, 16)["kv_read_share"] == 7 / 16


def test_the_other_configs_answer_the_widened_seam_as_they_did():
    """One contract: every served config names its arrays' kinds and
    answers ``serve_cache_read`` with a share a kind it has."""
    from edl_tpu.models import deepseek_v3, retention

    dense = llama.LlamaConfig.tiny()
    held = [21, 5, None, None]
    assert dense.serve_cache_kinds == ("kv", "kv")
    assert dense.serve_cache_read(held, 64, 16) == {
        "kv_read_share": llama.positional_read_share(held, 64, 16)}
    assert retention.RetentionConfig.serve_cache_kinds == ("state", "state")
    assert deepseek_v3.DeepseekV3Config.serve_cache_kinds == ("kv",)
    for cfg in (dense, cfg_of()):
        assert len(cfg.serve_cache_kinds) == len(cfg.serve_cache_spec(2, 64))


def test_recovery_replays_into_both_kinds_of_cache(params, tokens):
    """A fault at a dispatch loses the device's cache; the replay
    re-prefills each live slot from ``prompt + generated`` and the
    requests finish with the tokens of a run without the fault."""
    cfg = cfg_of()
    prompts = prompts_of(tokens)
    want = {r: alone(params, cfg, prompts[r], 8) for r in ("a", "b")}
    eng = ContinuousBatchingEngine(params, cfg, max_slots=2, max_len=64)
    eng.submit("a", prompts["a"], 8)
    eng.submit("b", prompts["b"], 8)
    faults.arm("serve.dispatch:raise@n=3", seed=0)
    try:
        results = eng.run()
    finally:
        faults.disarm()
    assert eng.recoveries == 1
    assert {r: list(results[r].tokens) for r in want} == want


@pytest.mark.parametrize("option", [
    {"block_size": 16}, {"block_size": 16, "kv_quant": "int8"},
    {"spec_k": 2}, {"block_size": 16, "prefix_cache": True},
    {"block_size": 16, "prefill_chunk": 16}])
def test_the_engine_refuses_what_is_the_dense_decoders(params, option):
    with pytest.raises(ValueError, match="contiguous cache alone"):
        ContinuousBatchingEngine(
            params, cfg_of(), max_slots=2, max_len=64, **option)


def test_the_ledger_files_each_array_under_its_own_kind(params):
    """One engine sets both gauges: ``edl_hbm_bytes{category="state"}``
    and ``{category="kv"}``; a recovery replaces them, not adds."""
    memledger.reset_default_ledger()
    cfg = cfg_of()
    eng = ContinuousBatchingEngine(params, cfg, max_slots=2, max_len=64)
    state = 2 * cfg.state_bytes_per_slot()
    kv = 2 * 64 * cfg.cache_numbers_per_token() * 4  # float32 here
    assert sum(c.nbytes for c in eng._cache) == state + kv
    cats = memledger.default_ledger().categories()
    assert cats["state"] == state and cats["kv"] == kv
    eng._alloc_device_state()
    assert memledger.default_ledger().categories()["state"] == state
    del eng


def test_the_dense_decoders_ledger_entry_is_what_it_was():
    memledger.reset_default_ledger()
    cfg = llama.LlamaConfig.tiny()
    p = llama.init_params(jax.random.PRNGKey(0), cfg)
    eng = ContinuousBatchingEngine(p, cfg, max_slots=2, max_len=32)
    cats = memledger.default_ledger().categories()
    assert cats["kv"] == sum(c.nbytes for c in eng._cache)
    assert cats.get("state", 0) == 0
    del eng


# -- (f) the config: hf, meta, cost model -------------------------------------------


def published():
    return harness.load_json(os.path.join(
        harness.ROOT, "benchmark", "published",
        "ibm-granite.granite-4.0-h-micro.json"))


def test_from_hf_reads_the_published_config():
    cfg = sh.SSMHybridConfig.from_hf(published())
    assert cfg == sh.SSMHybridConfig()
    assert (cfg.n_layers, cfg.n_mamba, cfg.n_attn) == (40, 36, 4)
    assert [i for i, k in enumerate(cfg.layer_types) if k == "attention"] \
        == [5, 15, 25, 35]
    assert (cfg.d_inner, cfg.conv_width, cfg.head_dim) == (4096, 4352, 64)
    assert cfg.rope_theta is None  # the published rope_theta is not read


@pytest.mark.parametrize("key, value", [
    ("num_local_experts", 8), ("position_embedding_type", "rope"),
    ("attention_bias", True), ("mamba_proj_bias", True),
    ("mamba_conv_bias", False), ("mamba_n_groups", 8),
    ("tie_word_embeddings", False), ("mamba_expand", 4)])
def test_from_hf_refuses_what_is_not_implemented(key, value):
    with pytest.raises(NotImplementedError, match=key.split("_")[1]):
        sh.SSMHybridConfig.from_hf({**published(), key: value})


def test_layer_types_must_name_the_two_kinds():
    with pytest.raises(ValueError, match="layer_types"):
        sh.SSMHybridConfig(layer_types=("mamba", "window"))


def test_meta_round_trip():
    cfg = cfg_of(jnp.bfloat16, use_kernel=True)
    meta = json.loads(json.dumps(cfg.to_meta()))
    assert meta["family"] == "ssm_hybrid"
    assert meta["layer_types"] == list(cfg.layer_types)
    back = sh.SSMHybridConfig.from_meta(meta)
    assert back == cfg
    with pytest.raises(ValueError, match="not a ssm_hybrid export"):
        sh.SSMHybridConfig.from_meta(llama.LlamaConfig.tiny().to_meta())


def test_the_cost_model_prices_both_kinds_of_cache():
    cfg = sh.SSMHybridConfig()
    assert cm.n_params(cfg) == 3_191_396_096
    # S 64 x 64 x 128 float32 a layer and the tail 3 x 4352 bfloat16
    per_slot = 36 * (2_097_152 + 26_112)
    assert cfg.state_bytes_per_slot() == per_slot
    assert cfg.cache_step_bytes_per_slot() == 2 * per_slot
    assert cfg.cache_numbers_per_token() * 2 == 8192  # bytes a token
    model = cm.CostModel(cfg, peak=cm.peak_for_kind("v5e"),
                         param_bytes_total=1000.0)
    kv = lambda slots, s: cm.kv_cache_bytes(cfg, slots, s)
    full = model.decode_block(72, 1, 4096)
    assert full.hbm_bytes == 1000.0 + 72 * 2 * per_slot + kv(72, 4096)
    # each kind scaled by its own share
    part = model.decode_block(72, 1, 4096, {
        "state_live_share": 0.5, "kv_read_share": 0.25})
    assert part.hbm_bytes == 1000.0 + 36 * 2 * per_slot + kv(72, 1024)
    # the state's products are in a token's operations: each position
    # enters the state once and reads it once, 36 layers
    assert cm.matmul_params(cfg) - (
        cm.n_params(cfg) - 36 * (21_760 + 192 + 4096 + 4096)
        - 4 * 4096 - 2048) == 36 * 2 * 64 * 64 * 128
    assert cm.decode_flops_per_token(cfg, 0) == 2.0 * cm.matmul_params(cfg)
    # attention grows with the context in 4 of the 40 layers
    assert cm.decode_flops_per_token(cfg, 100) - cm.decode_flops_per_token(
        cfg, 0) == pytest.approx(4.0 * 4 * 100 * 32 * 64)


# -- ``edl serve`` ---------------------------------------------------------------


def test_cli_serve_serves_an_ssm_hybrid_export(tmp_path, params):
    """The same verb, scheduler and engine as the dense decoder's: an
    export whose record says ``ssm_hybrid`` is served, its tokens the
    float32 reference's; what the engine keeps for the dense decoder is
    refused by name."""
    from edl_tpu.runtime.export import export_params

    cfg = cfg_of()
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
    lg = ref_row(params, jnp.asarray(prompt + rec["tokens"][:-1]))[10:]
    gap = jnp.max(lg, -1) - lg[jnp.arange(5), jnp.asarray(rec["tokens"])]
    assert float(jnp.max(gap)) < 1e-5
    bad = subprocess.run(
        serve + ["--block-size", "16", "--max-len", "32"],
        input='{"prompt": [1]}\n', capture_output=True, text=True, env=env)
    assert bad.returncode != 0 and "contiguous cache alone" in bad.stderr
