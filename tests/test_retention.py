"""``models/retention.py`` and ``ops/retention.py`` (power retention of
degree 2: a fixed state a slot instead of a cache indexed by position)
against the benchmark's plain reference, which is the ATTENTION form,
at tiny widths with seeded random weights and gates drawn near 1:
logits, not tokens. Tolerances: float32 against float32 at ``highest``
differs by the order of summation alone (the recurrent form sums a
position at a time what the attention form sums in one product: a few
1e-5 at logits of order 4), so 2e-4 holds every path, and a bfloat16
run (errors of 1e-2 and more), a wrong group, a degree of 1, a missing
decay or a missing normaliser fails each of them. (A missing ``1 /
sqrt(d)`` cannot: it cancels between numerator and normaliser.)"""

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
from benchmark.families import retention as family
from benchmark.reference import retention as reference
from edl_tpu.models import llama
from edl_tpu.models import retention as rt
from edl_tpu.obs import costmodel as cm
from edl_tpu.obs import memledger
from edl_tpu.ops import retention as ops
from edl_tpu.ops.flash_attention import interpret_kernels
from edl_tpu.serving.engine import ContinuousBatchingEngine
from edl_tpu.utils import faults, tracing

CONFIG = family.rehearsal_config()
LAYOUT = family.param_layout(CONFIG)
TOL = 2e-4


def cfg_of(dtype=jnp.float32, **kw):
    return dataclasses.replace(
        family.program_config(CONFIG, training=False),
        **{"dtype": dtype, "use_kernel": False, "chunk": 8, "piece": 16, **kw})


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


def empty_cache(cfg, slots):
    return [jnp.zeros(shape, dtype)
            for shape, dtype in cfg.serve_cache_spec(slots, 64)]


# -- (a) the feature map -------------------------------------------------------


@pytest.mark.parametrize("d", [2, 8, 16, 128])
def test_phi_is_the_square_of_the_dot_product(d):
    """``phi(a) . phi(b) = (a . b) ** 2 / d``: what makes the recurrent
    form the attention form, term by term."""
    a, b = np.random.default_rng(d).normal(size=(2, 5, d)).astype(np.float32)
    got = jnp.sum(ops.phi(jnp.asarray(a)) * ops.phi(jnp.asarray(b)), -1)
    want = np.sum(a * b, -1) ** 2 / d
    assert ops.phi(jnp.asarray(a)).shape == (5, (d // 2 + 1) * d)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)


def test_phi_rows_are_rolls():
    """Row ``m`` is ``w_m * a * roll(a, m)``: what the decode kernel
    makes in registers is what the prefill's products with the two
    selector matrices make."""
    a = jnp.asarray(np.random.default_rng(1).normal(size=(3, 8)), jnp.float32)
    want = jnp.concatenate(
        [w * a * jnp.roll(a, m, -1)
         for m, w in enumerate(ops.phi_weights(8).tolist())], -1)
    assert err(ops.phi(a), want) < 1e-6
    with pytest.raises(ValueError, match="even"):
        ops.phi_weights(7)


# -- (b) the forms agree with the reference's attention form -------------------


def test_the_sizes_exercise_the_group_and_the_scale():
    """Five query heads a kv head as published, and widths at which a
    wrong group and a degree of 1 each move the logits far beyond the
    tolerance (the next test but one)."""
    assert CONFIG["num_attention_heads"] // CONFIG["num_key_value_heads"] == 5
    assert CONFIG["retention_degree"] == 2
    assert cfg_of().groups == 5


@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("chunk,piece", [(4, 8), (8, 16), (16, 64), (64, 64)])
def test_chunked_forward_is_the_references_attention_form(
        params, tokens, ref_logits, chunk, piece, kernel):
    """The chunked form at four chunk sizes, the rows in one piece and
    in several: the plain lines, and the kernel ``edl_retention_chunk``
    under the interpreter."""
    with interpret_kernels():
        got = rt.forward(params, jnp.asarray(tokens), cfg_of(
            chunk=chunk, piece=piece, use_kernel=kernel))
    assert err(got, ref_logits) < TOL
    assert float(jnp.max(jnp.abs(ref_logits))) > 1.0


def test_the_chunk_kernel_is_the_plain_lines():
    """``edl_retention_chunk`` under the interpreter against the plain
    lines on one layer's inputs: outputs and the state after the last
    valid position, from a state that is not empty, with a padded
    tail."""
    rng = np.random.default_rng(4)
    b, t, kvh, g, d = 2, 24, 2, 5, 8
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    q, k, v = f(b, t, kvh, g, d), f(b, t, kvh, d), f(b, t, kvh, d)
    log_g = -jnp.abs(f(b, t, kvh)) * 0.1
    valid = jnp.arange(t)[None, :] <= jnp.array([[23], [13]])
    start = (f(b, kvh, d, ops.phi_width(d)),
             jnp.abs(f(b, kvh, ops.phi_width(d))) + 1.0)
    kw = dict(chunk=8, eps=1e-6, dtype=jnp.float32)
    want = ops.retention_chunked(q, k, v, log_g, valid, start, **kw)
    got = ops.retention_chunked(q, k, v, log_g, valid, start,
                                use_kernel=True, interpret=True, **kw)
    # float32 sums in another order; an output is a ratio, and large
    # where its summed weights are small
    close = lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
    close(got[0][0], want[0][0])  # the row without padding
    close(got[0][1, :14], want[0][1, :14])
    close(got[1], want[1])
    close(got[2], want[2])
    assert float(jnp.max(jnp.abs(want[1]))) > 1.0


@pytest.mark.parametrize("kernel", [False, True])
def test_recurrent_steps_are_the_references_attention_form(
        params, tokens, ref_logits, kernel):
    """A position at a time through the state: the plain lines, and the
    kernel ``edl_retention_step`` under the interpreter."""
    cfg = cfg_of(use_kernel=kernel)
    state, z = empty_cache(cfg, 2)
    outs = []
    with interpret_kernels():
        for t in range(24):
            logits, state, z = rt.decode_step_slots(
                params, jnp.asarray(tokens[:, t]), jnp.full((2,), t), state,
                z, cfg)
            outs.append(logits)
    assert err(jnp.stack(outs, 1), ref_logits[:, :24]) < TOL


@pytest.mark.parametrize("fault", ["group", "degree", "decay", "normaliser"])
def test_the_comparison_sees_each_part_of_the_layer(
        params, tokens, ref_logits, fault, monkeypatch):
    """A reference with one part of the mathematics altered leaves the
    program's logits by far more than the tolerance: the tolerance
    holds the program to every part."""
    config = dict(CONFIG)
    if fault == "group":
        # query heads dealt to kv heads in turn instead of in runs
        real = reference.retention
        monkeypatch.setattr(reference, "layer_row", _layer_row_dealt(real))
    elif fault == "degree":
        config["retention_degree"] = 1
    elif fault == "decay":
        config["gate_bias"] = 30.0  # g = 1: nothing is forgotten
    else:
        config["retention_eps"] = 1e6
    got = reference.logits_row(params, jnp.asarray(tokens[0]), config)
    assert err(got, ref_logits[0]) > 50 * TOL


def test_the_scale_cancels_under_the_normaliser(
        params, tokens, ref_logits, monkeypatch):
    """``1 / sqrt(d)`` stands in numerator and normaliser alike and
    leaves the output but for ``eps``: no comparison of outputs can
    hold it, and none here claims to. What a wrong scale does move is
    the size of the carried state (the float32 range it has to sit in),
    which the state's own test holds."""
    monkeypatch.setattr(reference.math, "sqrt", lambda x: 1.0)
    got = reference.logits_row(params, jnp.asarray(tokens[0]), CONFIG)
    assert err(got, ref_logits[0]) < TOL
    k = jnp.asarray(np.random.default_rng(5).normal(size=(8,)), jnp.float32)
    assert float(jnp.sum(ops.phi(k) ** 2)) == pytest.approx(
        float(jnp.sum(k * k)) ** 2 / 8, rel=1e-5)


def _layer_row_dealt(real_retention):
    """``reference.layer_row`` with query head ``h`` given kv head ``h %
    KV`` (the wrong grouping)."""
    base = reference.layer_row

    def layer_row(lp, x, config):
        h, kvh = config["num_attention_heads"], config["num_key_value_heads"]
        hd = config["head_dim"]
        order = np.arange(h).reshape(h // kvh, kvh).T.reshape(-1)
        lp = dict(lp, wq=lp["wq"].reshape(-1, h, hd)[:, order].reshape(
            lp["wq"].shape))
        return base(lp, x, config)

    return layer_row


# -- (c) prefill: buckets and the state after ``last`` -------------------------


@pytest.mark.parametrize("bucket", [16, 32, 64])
def test_a_prompt_leaves_the_same_state_in_every_bucket(
        params, tokens, ref_logits, bucket):
    """An END-padded prompt's state is the state after position
    ``last``, not after the bucket's end: the same prompt through three
    buckets gives the same logits and the same state as unpadded."""
    cfg = cfg_of()
    n = 13
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = tokens[0, :n]
    padded[0, n:] = 77  # what a pad holds must not matter
    logits, s, z = rt.prefill_padded(
        params, jnp.asarray(padded), jnp.int32(n - 1), cfg)
    want_logits, want_s, want_z = rt.prefill_padded(
        params, jnp.asarray(tokens[:1, :n]), jnp.int32(n - 1), cfg)
    assert err(logits, ref_logits[:1, n - 1]) < TOL
    assert err(logits, want_logits) < 1e-5
    assert err(s, want_s) < 1e-5 and err(z, want_z) < 1e-5
    assert float(jnp.max(jnp.abs(want_s))) > 0.1


def test_a_padded_tail_let_into_the_state_is_seen(params, tokens):
    """The planted fault of the chip's comparison, at tiny size: with
    every position counted valid the state after a padded bucket is not
    the prompt's."""
    cfg = cfg_of()
    padded = np.zeros((1, 32), np.int32)
    padded[0, :13] = tokens[0, :13]
    _, s, _ = rt._run(params, jnp.asarray(padded), cfg,
                      valid=jnp.ones((1, 32), bool), last=jnp.array([12]))
    _, want, _ = rt.prefill_padded(
        params, jnp.asarray(padded), jnp.int32(12), cfg)
    assert err(s, want) > 0.01


def test_prefill_into_a_slot_replaces_that_row_alone(params, tokens):
    """The engine's prefill program over ``serve_prefill``: the states
    go into row ``slot`` of the stacked caches, whatever the row held;
    the other rows keep theirs."""
    from edl_tpu.serving import engine

    cfg = cfg_of()
    cache = tuple(jnp.full(shape, 3.0, dtype)
                  for shape, dtype in cfg.serve_cache_spec(3, 64))
    prompt = jnp.asarray(tokens[:1, :32])
    want_logits, (want_s, want_z) = cfg.serve_prefill(
        params, prompt, jnp.int32(20))
    i32 = lambda: jnp.zeros((3,), jnp.int32)  # donated: one array each
    t0, *_, s, z = engine._prefill_program(cfg, 32, False)(
        params, prompt, jnp.int32(20), jnp.int32(1), jnp.int32(4),
        jnp.int32(-1), i32(), i32(), jnp.zeros((3,), bool), i32(), i32(),
        *cache, jax.random.PRNGKey(0), jnp.float32(0.0))
    assert int(t0) == int(jnp.argmax(want_logits[0]))
    assert err(s[:, 1], want_s[:, 0]) < 1e-6
    assert err(z[:, 1], want_z[:, 0]) < 1e-6
    assert float(jnp.min(s[:, 0])) == float(jnp.max(s[:, 2])) == 3.0


# -- (d) prefill, then decode through the cache --------------------------------


@pytest.mark.parametrize("kernel", [False, True])
def test_prefill_then_decode_is_the_references_full_forward(
        params, tokens, ref_logits, kernel):
    """A padded prefill, then decode steps through the state it left,
    one slot live and one idle: the reference's logits at every
    position, and the idle slot's state untouched."""
    cfg = cfg_of(use_kernel=kernel)
    n = 17
    padded = np.zeros((1, 32), np.int32)
    padded[0, :n] = tokens[1, :n]
    with interpret_kernels():
        logits, rows_s, rows_z = rt.prefill_padded(
            params, jnp.asarray(padded), jnp.int32(n - 1), cfg)
    assert err(logits[0], ref_logits[1, n - 1]) < TOL
    state, z = empty_cache(cfg, 2)
    state = state.at[:, 1].set(rows_s[:, 0]).at[:, 0].set(5.0)
    z = z.at[:, 1].set(rows_z[:, 0])
    live = jnp.array([False, True])
    with interpret_kernels():
        for t in range(n, 30):
            tok = jnp.asarray([0, tokens[1, t]])
            logits, state, z = rt.decode_step_slots(
                params, tok, jnp.asarray([0, t]), state, z, cfg, live=live)
            assert err(logits[1], ref_logits[1, t]) < TOL, t
    assert float(jnp.min(state[:, 0])) == float(jnp.max(state[:, 0])) == 5.0


def test_the_kernel_is_the_plain_lines(params):
    """``edl_retention_step`` under the interpreter against the plain
    lines on one layer's inputs: numerators, states, and an idle slot
    whose state is not touched."""
    rng = np.random.default_rng(3)
    b, kvh, g, d = 3, 2, 5, 8
    q, = (jnp.asarray(rng.normal(size=(b, kvh, g, d)), jnp.float32),)
    k, v = (jnp.asarray(rng.normal(size=(b, kvh, d)), jnp.float32)
            for _ in range(2))
    log_g = jnp.asarray(-rng.uniform(0.01, 0.2, (b, kvh)), jnp.float32)
    state = jnp.asarray(rng.normal(size=(2, b, kvh, d, ops.phi_width(d))),
                        jnp.float32)
    z = jnp.abs(jnp.asarray(rng.normal(size=(2, b, kvh, ops.phi_width(d))),
                            jnp.float32)) + 1.0
    live = jnp.array([True, False, True])
    kw = dict(eps=1e-6, dtype=jnp.float32)
    want = ops.retention_step(q, k, v, log_g, state, z, 1, live,
                              use_kernel=False, **kw)
    got = ops.retention_step(q, k, v, log_g, state, z, 1, live,
                             use_kernel=True, interpret=True, **kw)
    for a, b_ in zip(got, want):
        assert err(a, b_) < 1e-4  # sums of 40 products of order 1
    assert err(got[1][0], state[0]) == 0.0  # the other layer
    assert err(got[1][1, 1], state[1, 1]) == 0.0  # the idle slot
    assert err(got[1][1, 0], state[1, 0]) > 0.01
    assert float(jnp.max(jnp.abs(got[0][1]))) == 0.0


# -- (e) precision: the control ------------------------------------------------


def test_int8_differs_from_bfloat16_by_more_than_bfloat16_from_the_reference(
        tokens, ref_logits):
    """The control of the chip's comparison at tiny size: the served
    tree in bfloat16 leaves the float32 reference by rounding; the int8
    records leave the bfloat16 program by clearly more."""
    p16 = harness.make_params(11, LAYOUT, jnp.bfloat16)
    cfg = cfg_of(jnp.bfloat16)
    toks = jnp.asarray(tokens)
    bf16 = rt.forward(p16, toks, cfg)
    int8 = rt.forward(rt.quantize_params_int8(p16), toks, cfg)
    ref16 = jnp.stack([reference.logits_row(p16, row, CONFIG)
                       for row in toks])
    # means over all logits: the widest single gap is one draw of each
    mean = lambda a, b: float(jnp.mean(jnp.abs(a - b)))
    sound, control = mean(bf16, ref16), mean(int8, bf16)
    assert control > 1.8 * sound > 0  # 0.027-0.029 against 0.012-0.014
    q = rt.quantize_params_int8(p16)
    assert q["layers"]["wq"]["q8"].dtype == jnp.int8
    assert q["layers"]["wg"].dtype == jnp.bfloat16  # the gate stays


def test_a_bfloat16_state_is_seen(params, tokens, ref_logits):
    """The state kept in bfloat16 (a planted fault of the chip's
    comparison): rounded after every step, the carried sums leave the
    reference by far more than the tolerance."""
    cfg = cfg_of()
    state, z = empty_cache(cfg, 2)
    worst = 0.0
    for t in range(24):
        logits, state, z = rt.decode_step_slots(
            params, jnp.asarray(tokens[:, t]), jnp.full((2,), t), state, z,
            cfg)
        state = state.astype(jnp.bfloat16).astype(jnp.float32)
        z = z.astype(jnp.bfloat16).astype(jnp.float32)
        worst = max(worst, err(logits, ref_logits[:, t]))
    assert worst > 10 * TOL


# -- (f) the engine ------------------------------------------------------------


def alone(params, cfg, prompt, n):
    """The tokens a request gets with the server to itself."""
    eng = ContinuousBatchingEngine(params, cfg, max_slots=1, max_len=64)
    eng.submit("x", prompt, n)
    return list(eng.run()["x"].tokens)


def prompts_of(tokens):
    return {"a": [int(t) for t in tokens[0, :11]],
            "b": [int(t) for t in tokens[1, :19]],
            "c": [int(t) for t in tokens[0, 5:12]],
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
        # the cache is the spec's two arrays: no position axis
        assert [(c.shape, c.dtype) for c in eng._cache] == [
            (shape, dtype) for shape, dtype in cfg.serve_cache_spec(2, 64)]
        assert eng._cache[0].shape == (2, 2, 2, 8, 40)
        eng.submit("b", prompt, 9)
        out = list(eng.run()["b"].tokens)
    lg = reference.logits_row(
        params, jnp.asarray(prompt + out[:-1]), CONFIG)[len(prompt) - 1:]
    gap = jnp.max(lg, -1) - lg[jnp.arange(9), jnp.asarray(out)]
    assert float(jnp.max(gap)) < TOL


@pytest.mark.parametrize("horizon", [1, 4])
def test_joins_and_leaves_give_each_request_the_tokens_it_gets_alone(
        params, tokens, horizon):
    """Continuous batching over a state: requests of different lengths
    join and leave mid-run through two slots (so slots are reused after
    a finished request, and a slot's first step follows another
    request's last), and every one gets the tokens it gets alone."""
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


def test_a_reused_slot_starts_from_a_clean_state(params, tokens):
    """One slot, two requests in turn: the second's prefill replaces
    the state whole, so it gets what it gets on a fresh engine."""
    cfg = cfg_of()
    prompts = prompts_of(tokens)
    eng = ContinuousBatchingEngine(params, cfg, max_slots=1, max_len=64)
    eng.submit("d", prompts["d"], 9)
    eng.run()
    held = float(jnp.max(jnp.abs(eng._cache[0])))
    assert held > 0.1  # the finished request's state is still there
    eng.submit("a", prompts["a"], 6)
    assert list(eng.run()["a"].tokens) == alone(params, cfg, prompts["a"], 6)


def test_the_dispatch_span_says_what_share_of_the_states_a_block_moves(
        params, tokens):
    cfg = cfg_of()
    eng = ContinuousBatchingEngine(params, cfg, max_slots=4, max_len=64)
    assert eng._attn_block == 64
    eng.submit("a", prompts_of(tokens)["a"], 4)
    before = len(tracing.tracer().spans("serving.dispatch"))
    eng.run()
    mine = tracing.tracer().spans("serving.dispatch")[before:]
    assert mine and all(s.attrs["state_live_share"] == 0.25 for s in mine)
    assert all("kv_read_share" not in s.attrs for s in mine)
    assert cfg.serve_cache_read([3, None, 60, None], 64, 64) == {
        "state_live_share": 0.5}


def test_a_positional_cache_answers_as_it_did():
    """The dense decoder's share of the cache read, through the seam:
    the S-blocks fetched over all there are, byte for byte the engine's
    own count before the seam asked the config."""
    cfg = llama.LlamaConfig.tiny()
    held = [21, 5, None, None]
    for blk in (64, 16, 8):
        fetched = sum(1 if n is None else -(-n // blk) for n in held)
        assert cfg.serve_cache_read(held, 64, blk) == {
            "kv_read_share": fetched / (4 * (64 // blk))}


def test_recovery_replays_into_the_same_state(params, tokens):
    """A fault at a dispatch loses the device's states; the replay
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


def test_the_ledger_files_the_cache_as_state(params):
    memledger.reset_default_ledger()
    cfg = cfg_of()
    eng = ContinuousBatchingEngine(params, cfg, max_slots=2, max_len=64)
    want = 2 * cfg.state_bytes_per_slot()
    assert sum(c.nbytes for c in eng._cache) == want
    cats = memledger.default_ledger().categories()
    assert cats["state"] == want and cats.get("kv", 0) == 0
    del eng


# -- (g) the config: seam, meta, cost model -------------------------------------


def test_from_hf_reads_the_published_config_and_refuses_what_it_lacks():
    published = harness.load_json(os.path.join(
        harness.ROOT, "benchmark", "published",
        "manifestai.Brumby-14B-Base.json"))
    cfg = rt.RetentionConfig.from_hf(published)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        5120, 40, 8, 128)
    assert (cfg.d_ff, cfg.vocab, cfg.n_layers) == (17408, 151936, 40)
    assert cfg.groups == 5 and cfg.state_width == 65 * 128
    assert cfg == rt.RetentionConfig()
    with pytest.raises(NotImplementedError, match="rope_scaling"):
        rt.RetentionConfig.from_hf({**published, "rope_scaling": {"f": 2}})
    with pytest.raises(NotImplementedError, match="retention_degree"):
        rt.RetentionConfig.from_hf({**published, "retention_degree": 3})


def test_meta_round_trip():
    cfg = cfg_of(jnp.bfloat16, use_kernel=True)
    meta = json.loads(json.dumps(cfg.to_meta()))
    assert meta["family"] == "retention"
    # the prefill's loop sizes are the program's, not the export's
    assert "chunk" not in meta and "piece" not in meta
    assert rt.RetentionConfig.from_meta(meta) == dataclasses.replace(
        cfg, chunk=128, piece=1024)
    with pytest.raises(ValueError, match="not a retention export"):
        rt.RetentionConfig.from_meta(llama.LlamaConfig.tiny().to_meta())


def test_the_cost_model_prices_a_state_by_the_slot():
    cfg = rt.RetentionConfig(n_layers=8)
    layer = 2 * 5120 * 5120 + 2 * 5120 * 1024 + 3 * 5120 * 17408
    assert cm.n_params(cfg) == 2 * 151936 * 5120 + 5120 + 8 * (
        layer + 5121 * 8 + 2 * 5120 + 256)
    assert round(cm.n_params(cfg) / 1e9, 3) == 4.199
    # a slot's state as stored: S [8, 128, 8320] and z [8, 8320], float32
    per_slot = 8 * 8 * (128 + 1) * 8320 * 4
    assert cfg.state_bytes_per_slot() == per_slot
    assert cfg.cache_step_bytes_per_slot() == 2 * per_slot
    model = cm.CostModel(cfg, peak=cm.peak_for_kind("v5e"),
                         param_bytes_total=1000.0)
    full = model.decode_block(24, 1, 4096)
    assert full.hbm_bytes == 1000.0 + 24 * 2 * per_slot
    half = model.decode_block(24, 1, 4096, 0.5)
    assert half.hbm_bytes == 1000.0 + 12 * 2 * per_slot
    assert half.flops == full.flops  # nothing grows with the context
    # a positional cache is priced as it was: the share scales its rows
    dense = llama.LlamaConfig.tiny()
    m = cm.CostModel(dense, peak=cm.peak_for_kind("v5e"),
                     param_bytes_total=1000.0)
    assert m.decode_block(4, 2, 64, 0.25).hbm_bytes == 2 * (
        1000.0 + cm.kv_cache_bytes(dense, 4, 16))
    assert m.decode_block(4, 2, 16).hbm_bytes == \
        m.decode_block(4, 2, 64, 0.25).hbm_bytes


# -- ``edl serve`` -------------------------------------------------------------


def test_cli_serve_serves_a_retention_export(tmp_path, params):
    """The same verb, scheduler and engine as the dense decoder's: an
    export whose record says ``retention`` is served, its tokens the
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
    lg = reference.logits_row(
        params, jnp.asarray(prompt + rec["tokens"][:-1]), CONFIG)[10:]
    gap = jnp.max(lg, -1) - lg[jnp.arange(5), jnp.asarray(rec["tokens"])]
    assert float(jnp.max(gap)) < 1e-3
    bad = subprocess.run(
        serve + ["--block-size", "16", "--max-len", "32"],
        input='{"prompt": [1]}\n', capture_output=True, text=True, env=env)
    assert bad.returncode != 0 and "contiguous cache alone" in bad.stderr
