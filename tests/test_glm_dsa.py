"""``models/glm_dsa.py`` (latent attention with a low-rank query, a
learned indexer that chooses ``index_topk`` positions a query, dropless
sigmoid-routed experts of which a share is held) against the benchmark's
plain reference, at tiny widths with seeded random weights and contexts
several times ``index_topk``: logits, not tokens. Tolerances: float32 at
``highest`` against float32 at ``highest`` differs by the order of
summation alone (a few 1e-6 at logits of order 3), so 1e-4 holds every
path, and each planted fault (a part of the mathematics left out or
done wrong) fails it by orders of magnitude. bfloat16 is not held to a
logit's tolerance here: with 8 positions chosen of a few dozen, one
near-tied choice that rounding flips swaps an eighth of a query's keys
(the benchmark's rehearsal limits say how far that reaches)."""

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
from benchmark.families import mla_dsa_moe as family
from benchmark.reference import mla_dsa_moe as reference
from edl_tpu.models import glm_dsa as g
from edl_tpu.ops.flash_attention import interpret_kernels
from edl_tpu.serving import engine as engine_module
from edl_tpu.serving.engine import ContinuousBatchingEngine
from edl_tpu.utils import tracing

CONFIG = family.rehearsal_config()
LAYOUT = family.param_layout(CONFIG)
TOL = 1e-4
TOPK = CONFIG["index_topk"]


def cfg_of(dtype=jnp.float32, **kw):
    return dataclasses.replace(
        family.program_config(CONFIG, training=False),
        **{"dtype": dtype, "use_flash": False, **kw})


def walk_in(monkeypatch, piece=8, key_block=4):
    """The model's pieces and key blocks, small enough that these
    contexts are several of each. They are read when a program is
    traced, and the engine keeps its programs by config: those traced
    with other sizes go."""
    monkeypatch.setattr(g, "PREFILL_PIECE", piece)
    monkeypatch.setattr(g, "KEY_BLOCK", key_block)
    engine_module._programs.clear()


@pytest.fixture(autouse=True)
def small_pieces(monkeypatch):
    walk_in(monkeypatch)
    yield
    engine_module._programs.clear()


@pytest.fixture(scope="module")
def params():
    return harness.make_params(11, LAYOUT, jnp.float32)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, 256, (2, 48), dtype=np.int32)


@pytest.fixture(scope="module")
def ref_logits(params, tokens):
    run = jax.jit(lambda p, t: reference.logits_row(p, t, CONFIG))
    return jnp.stack([run(params, jnp.asarray(row)) for row in tokens])


def err(a, b):
    return float(jnp.max(jnp.abs(a - b)))


def forward(params, tokens, cfg):
    with interpret_kernels(), jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, t: g.forward(p, t, cfg))(
            params, jnp.asarray(tokens))


# -- (a) forward against the reference ----------------------------------------


@pytest.mark.parametrize("piece, key_block, use_flash", [
    (8, 4, False),  # the first piece dense (8 positions <= index_topk)
    (16, 8, False),  # every piece chooses: a piece longer than index_topk
    (4, 4, False), (48, 16, False), (8, 8, True)])
def test_forward_is_the_references_in_float32(
        params, tokens, ref_logits, monkeypatch, piece, key_block, use_flash):
    walk_in(monkeypatch, piece, key_block)
    got = forward(params, tokens, cfg_of(use_flash=use_flash))
    assert got.shape == ref_logits.shape
    assert err(got, ref_logits) < TOL
    assert float(jnp.max(jnp.abs(ref_logits))) > 1.0


def test_the_contexts_are_several_times_the_positions_chosen(tokens):
    assert tokens.shape[1] >= 4 * TOPK


def test_init_params_has_the_benchmarks_layout():
    cfg = cfg_of()
    tree = jax.jit(lambda k: g.init_params(k, cfg))(jax.random.PRNGKey(0))
    flat = {tuple(k.key for k in path): leaf.shape for path, leaf
            in jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert flat == {path: shape for path, (shape, _, _) in LAYOUT.items()}
    assert cfg.n_params() == sum(
        int(np.prod(shape)) for shape in flat.values())


# -- (b) the planted faults: each is seen at the tiny size ---------------------


def _no_relu(qi, w, ki):
    s = jnp.einsum("bthd,bsd->bths", qi, ki,
                   preferred_element_type=jnp.float32)
    return jnp.sum(s * w[..., None], axis=2) + 0.0


def _first_mask(scores, valid, k):
    return valid & (jnp.arange(scores.shape[-1]) < k)


# name -> (config fields replaced, module functions replaced)
FAULTS = {
    "every_key_attended": ({"index_topk": 1 << 20}, {}),
    "the_first_positions_not_the_top": ({}, {"select_mask": _first_mask}),
    "relu_left_out": ({}, {"index_scores": _no_relu}),
    "index_weights_left_out": ({}, {
        "index_scores": lambda qi, w, ki: g_index_scores(
            qi, jnp.ones_like(w), ki)}),
    "index_key_rope_left_out": ({}, {
        "_rope_first": lambda cfg, x, positions: x if x.shape[2] == 1
        else g_rope_first(cfg, x, positions)}),
    "query_norm_left_out": ({}, {
        "_query_rank": lambda cfg, a, lp: g._ll._matw(a, lp["wqa"])}),
    "routed_scaling_factor_left_out": ({"route_scale": 1.0}, {}),
}
g_index_scores, g_rope_first = g.index_scores, g._rope_first


def plant(monkeypatch, name):
    fields, functions = FAULTS[name]
    for attr, fn in functions.items():
        monkeypatch.setattr(g, attr, fn)
    return fields


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_comparison_sees_each_part_of_the_mechanism(
        params, tokens, ref_logits, monkeypatch, fault):
    """Forward, and prefill then decode through the cache: a fault
    moves both past the tolerance by a hundred times and more."""
    cfg = cfg_of(**plant(monkeypatch, fault))
    got = forward(params, tokens, cfg)
    assert err(got, ref_logits) > 100 * TOL, fault
    worst = served_gap(params, tokens, ref_logits, cfg)
    assert worst > 100 * TOL, fault


def served_gap(params, tokens, ref_logits, cfg):
    """Prefill of 24 positions in a bucket of 32, then 16 decode steps
    through the cache: the widest distance from the reference's
    logits."""
    toks = jnp.asarray(tokens)
    worst = 0.0
    with interpret_kernels(), jax.default_matmul_precision("highest"):
        last = jnp.array([23, 17])
        logits, (lat, kidx) = jax.jit(
            lambda p, t, l: g.prefill_padded(p, t, l, cfg))(
            params, toks[:, :32], last)
        for r in range(2):
            worst = max(worst, err(logits[r], ref_logits[r, last[r]]))
        cache = tuple(
            jnp.zeros((c.shape[0], 2, 64) + c.shape[3:]).at[:, :, :32].set(c)
            for c in (lat, kidx))
        step = jax.jit(lambda p, t, ps, c: g.decode_step_slots(
            p, t, ps, c, cfg))
        pos = last + 1
        for _ in range(16):
            tok = toks[jnp.arange(2), pos]
            logits, cache, _ = step(params, tok, pos, cache)
            for r in range(2):
                worst = max(worst, err(logits[r], ref_logits[r, pos[r]]))
            pos = pos + 1
    return worst


# -- (c) prefill, then decode through the two arrays of the cache --------------


@pytest.mark.parametrize("use_flash", [False, True])
def test_prefill_then_slot_decode_is_the_references_full_forward(
        params, tokens, ref_logits, use_flash):
    assert served_gap(
        params, tokens, ref_logits, cfg_of(use_flash=use_flash)) < TOL


@pytest.mark.parametrize("bucket, piece", [
    (8, 4), (16, 4), (16, 8), (32, 4), (32, 16), (32, 32)])
def test_pieces_of_any_length_give_one_cache_and_the_last_rows_logits(
        params, tokens, ref_logits, monkeypatch, bucket, piece):
    """Every bucket to several ``last``: the logits are the
    reference's at ``last``, the rows up to ``last`` do not depend on
    the piece, and a position past ``last`` holds zeros."""
    toks = jnp.asarray(tokens)[:, :bucket]
    cfg = cfg_of()

    def run(piece):
        walk_in(monkeypatch, piece)
        return jax.jit(lambda p, t, l: g.prefill_padded(p, t, l, cfg))

    for last in ([bucket - 1, bucket // 2], [0, bucket - 3]):
        last = jnp.array(last)
        with jax.default_matmul_precision("highest"):
            logits, cache = run(min(piece, bucket))(params, toks, last)
            _, want = run(bucket)(params, toks, last)
        for r in range(2):
            n = int(last[r]) + 1
            assert err(logits[r], ref_logits[r, n - 1]) < TOL
            for got, full in zip(cache, want):
                assert err(got[:, r, :n], full[:, r, :n]) < 1e-5
                assert n == bucket \
                    or float(jnp.max(jnp.abs(got[:, r, n:]))) == 0.0


def test_an_idle_slot_is_left_alone_and_moves_nobody(
        params, tokens, ref_logits):
    """Slot 1 idle (not live, position 0): slot 0's logits are the
    reference's, and slot 1 counts no expert."""
    cfg = cfg_of()
    toks = jnp.asarray(tokens)
    with jax.default_matmul_precision("highest"):
        _, (lat, kidx) = jax.jit(
            lambda p, t, l: g.prefill_padded(p, t, l, cfg))(
            params, toks[:1, :32], jnp.array([23]))
        cache = tuple(
            jnp.zeros((c.shape[0], 2, 64) + c.shape[3:]).at[:, :1, :32].set(c)
            for c in (lat, kidx))
        logits, _, (hit, _) = jax.jit(
            lambda p, t, ps, c, on: g.decode_step_slots(
                p, t, ps, c, cfg, live=on))(
            params, jnp.array([toks[0, 24], 0]), jnp.array([24, 0]), cache,
            jnp.array([True, False]))
    assert err(logits[0], ref_logits[0, 24]) < TOL
    # one live row of three choices among eight experts, four held
    assert 0.0 <= float(hit) <= 3 / 4


def test_selection_by_bisection_is_top_k_with_ties_to_the_lower_position():
    rng = np.random.default_rng(3)
    scores = rng.normal(size=(5, 7, 64)).astype(np.float32)
    scores[0, 0, 10:30] = 0.5  # a run of equal scores across the bar
    scores[1, 1, :] = 0.0
    scores[1, 2, 5] = -0.0
    upto = rng.integers(0, 64, (5, 7))
    valid = np.arange(64)[None, None, :] <= upto[..., None]
    for k in (1, 8, 63, 64):
        got = np.asarray(g.select_mask(
            jnp.asarray(scores), jnp.asarray(valid), k))
        for i in range(5):
            for j in range(7):
                n = upto[i, j] + 1
                want = np.zeros(64, bool)
                # a stable sort descending: the lower position first
                order = np.argsort(-scores[i, j, :n], kind="stable")
                want[order[:k]] = True
                assert (got[i, j] == want).all(), (k, i, j)

@pytest.mark.parametrize("block_s", [8, 16, 32])
def test_latent_kernel_masks_the_positions_not_chosen(block_s):
    """``edl_decode_attn_latent`` with its ``chosen`` operand against
    the dense lines: the chosen may lie in any block (none in the first,
    none in the last, one alone), the others are read and masked."""
    from edl_tpu.ops.decode_attention import decode_attention_latent

    key = jax.random.split(jax.random.PRNGKey(5), 3)
    layers, b, s, h, rank, width = 3, 6, 32, 4, 128, 256
    cache = jax.random.normal(key[0], (layers, b, s, width), jnp.float32)
    q = jax.random.normal(key[1], (b, h, width), jnp.float32)
    pos = jnp.array([0, 7, 8, 20, 31, 31])
    chosen = jax.random.bernoulli(key[2], 0.3, (b, s))
    chosen = chosen.at[:, 0].set(True).at[4, :24].set(False) \
        .at[4, 30].set(True).at[5].set(False).at[5, 3].set(True)
    chosen = chosen & (jnp.arange(s)[None, :] <= pos[:, None])
    got = decode_attention_latent(
        q, cache, pos, jnp.int32(2), rank=rank, sm_scale=0.11,
        block_s=block_s, interpret=True, chosen=chosen)
    sc = jnp.einsum("bhw,bsw->bhs", q, cache[2]) * 0.11
    p = jax.nn.softmax(jnp.where(chosen[:, None], sc, -jnp.inf), axis=-1)
    want = jnp.einsum("bhs,bsr->bhr", p, cache[2][..., :rank])
    assert got.shape == (b, h, rank)
    assert err(got, want) < 2e-5
    # and it is not the attention over every live position
    every = decode_attention_latent(
        q, cache, pos, jnp.int32(2), rank=rank, sm_scale=0.11,
        block_s=block_s, interpret=True)
    assert err(every[1:], want[1:]) > 1e-2


# -- (c') a prefill piece's attention as one kernel -----------------------------

PIECE, BLOCK = 128, 128  # the least the kernel takes: an int8 tile's lanes
LONG = 4 * PIECE


@pytest.fixture
def whole_tiles(monkeypatch):
    walk_in(monkeypatch, PIECE, BLOCK)


def _piece_case(j, b=1, topk=24, last=None, dtype=jnp.float32, mask=None,
                seed=0):
    """Operands of piece ``j`` (queries ``j * PIECE ..``) of a bucket of
    ``LONG`` at the rehearsal's head widths: (cfg, q, lat, sel, n_blocks,
    lp). The rows behind the piece's live key blocks are NaN: nothing
    of them may reach an output."""
    cfg = cfg_of(dtype=dtype, use_flash=True, index_topk=topk)
    key = jax.random.split(jax.random.PRNGKey(seed + 7 * j), 4)
    q = jax.random.normal(
        key[0], (b, PIECE, cfg.n_heads, cfg.qk_dim), jnp.float32)
    lat = jax.random.normal(
        key[1], (2, b, LONG, cfg.cache_width), jnp.float32)
    lat = lat.at[..., cfg.latent_width:].set(0)
    n_blocks = (j + 1) * PIECE // BLOCK
    lat = lat.at[:, :, n_blocks * BLOCK:].set(jnp.nan)
    wkvb = jax.random.normal(
        key[2], (cfg.kv_rank, cfg.n_heads * (cfg.qk_nope_dim + cfg.v_dim)),
        jnp.float32) * cfg.kv_rank ** -0.5
    positions = j * PIECE + jnp.arange(PIECE)
    last = jnp.full((b,), LONG - 1) if last is None else jnp.asarray(last)
    upto = jnp.minimum(positions[None, :], last[:, None])
    valid = jnp.arange(LONG)[None, None, :] <= upto[..., None]
    table = jax.random.normal(key[3], (b, PIECE, LONG), jnp.float32)
    sel = (mask or g.select_mask)(table, valid, topk)
    return (cfg, q.astype(dtype), lat.astype(dtype), sel,
            jnp.int32(n_blocks), {"wkvb": wkvb})


def _kernel(cfg, q, lat, sel, n_blocks, lp, layer=1, **form):
    from edl_tpu.ops.sparse_prefill_attention import sparse_prefill_attention

    with jax.default_matmul_precision("highest"):
        return sparse_prefill_attention(
            q, lat, lp["wkvb"], sel, jnp.int32(layer), n_blocks,
            rank=cfg.kv_rank, rope=cfg.qk_rope_dim,
            sm_scale=cfg.qk_dim ** -0.5, block_k=BLOCK, interpret=True,
            **form)


def _swept(cfg, q, lat, sel, n_blocks, lp, layer=1):
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda q, lat, sel, n: g._sweep(
            cfg, q, lat, layer, sel, n, lp))(q, lat, sel, n_blocks)


SWEEP_CASES = {
    # a different count of live key blocks for each piece, the dead
    # ones behind them NaN; serving's batch of 1 and ``forward``'s of 2
    "piece_0_one_live_block": dict(j=0),
    "piece_1_two_live_blocks": dict(j=1, b=2),
    "piece_2_three_live_blocks": dict(j=2),
    "piece_3_every_block_live": dict(j=3, b=2),
    # the prompt ends inside the piece: the rows past it attend what
    # ``last``'s does
    "last_inside_the_piece": dict(j=1, b=2, last=[PIECE + 37, LONG - 1]),
    # piece 0's early queries have fewer positions than index_topk,
    # and its first a single one
    "fewer_positions_than_index_topk": dict(j=0, b=2, topk=64),
    "one_position_chosen_a_row": dict(j=2, topk=1),
    "heads_one_a_step_rows_in_tiles": dict(j=2, b=2, heads=1, block_q=32),
    "bfloat16": dict(j=3, dtype=jnp.bfloat16),
}


@pytest.mark.parametrize("case", sorted(SWEEP_CASES))
def test_the_sparse_prefill_kernel_is_the_sweep(whole_tiles, case):
    """``edl_sparse_prefill_attn`` (the interpreter) against ``_sweep``
    on one piece's operands."""
    spec = dict(SWEEP_CASES[case])
    form = {k: spec.pop(k) for k in ("heads", "block_q") if k in spec}
    args = _piece_case(**spec)
    got, want = _kernel(*args, **form), _swept(*args)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert not bool(jnp.isnan(got).any())
    tol = 2e-5 if got.dtype == jnp.float32 else 2e-2
    assert err(got.astype(jnp.float32), want.astype(jnp.float32)) < tol
    assert float(jnp.max(jnp.abs(want))) > 0.3


def test_the_kernel_with_every_position_marked_is_causal_attention(
        whole_tiles):
    """A mask of all the valid positions: plain causal attention over
    the expanded keys and values, written out here."""
    cfg, q, lat, sel, n_blocks, lp = _piece_case(2, b=2, topk=LONG)
    got = _kernel(cfg, q, lat, sel, n_blocks, lp)
    n, r = cfg.qk_nope_dim, cfg.kv_rank
    live = lat[1, :, :3 * PIECE]
    with jax.default_matmul_precision("highest"):
        kv = (live[..., :r] @ lp["wkvb"]).reshape(2, 3 * PIECE, cfg.n_heads,
                                                  -1)
        k = jnp.concatenate([kv[..., :n], jnp.broadcast_to(
            live[:, :, None, r:cfg.latent_width],
            kv.shape[:3] + (cfg.qk_rope_dim,))], axis=-1)
        s = jnp.einsum("bphd,bshd->bhps", q, k) * cfg.qk_dim ** -0.5
        causal = (jnp.arange(3 * PIECE)[None, :]
                  <= 2 * PIECE + jnp.arange(PIECE)[:, None])
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        want = jnp.einsum("bhps,bshd->bphd", p, kv[..., n:])
    assert err(got, want.reshape(got.shape)) < 2e-5


def test_the_kernel_tells_the_first_positions_from_the_best_as_the_sweep(
        whole_tiles):
    """The planted fault ``_first_mask`` moves the kernel's output by
    what it moves ``_sweep``'s."""
    best = _piece_case(3, b=2)
    first = _piece_case(3, b=2, mask=_first_mask)
    moved = _kernel(*first) - _kernel(*best)
    assert err(moved, _swept(*first) - _swept(*best)) < 4e-5
    assert float(jnp.max(jnp.abs(moved))) > 0.3


def test_a_row_that_attends_nothing_reads_zero_as_the_sweeps(whole_tiles):
    cfg, q, lat, sel, n_blocks, lp = _piece_case(1)
    sel = sel.at[0, 5].set(False)
    got = _kernel(cfg, q, lat, sel, n_blocks, lp)
    assert float(jnp.max(jnp.abs(got[0, 5]))) == 0.0
    assert err(got, _swept(cfg, q, lat, sel, n_blocks, lp)) < 2e-5


@pytest.fixture(scope="module")
def long_tokens():
    return np.random.default_rng(1).integers(0, 256, (2, 3 * PIECE),
                                             dtype=np.int32)


def test_forward_through_the_kernel_is_the_references(
        params, long_tokens, monkeypatch):
    """Pieces and key blocks of whole tiles and ``use_flash``: every
    piece of ``forward`` (none is dense: a piece is longer than
    ``index_topk``) attends through ``edl_sparse_prefill_attn``, and
    the logits are the reference's at that length."""
    from edl_tpu.ops import sparse_prefill_attention as spa

    walk_in(monkeypatch, PIECE, BLOCK)
    calls = []
    kernel = spa.sparse_prefill_attention
    monkeypatch.setattr(
        spa, "sparse_prefill_attention",
        lambda *a, **kw: calls.append(kw["block_k"]) or kernel(*a, **kw))
    monkeypatch.setattr(g, "_sweep", None)
    run = jax.jit(lambda p, t: reference.logits_row(p, t, CONFIG))
    want = jnp.stack([run(params, jnp.asarray(row)) for row in long_tokens])
    got = forward(params, long_tokens, cfg_of(use_flash=True))
    assert err(got, want) < TOL
    # traced once a layer in the scan's body (the first piece is a
    # piece of the scan too)
    assert calls == [BLOCK] * CONFIG["num_hidden_layers"]


@pytest.mark.parametrize("piece, key_block, use_flash, int8, takes", [
    (PIECE, BLOCK, True, False, "kernel"),
    (2 * PIECE, BLOCK, True, False, "kernel"),
    (8, 8, True, False, "sweep"),  # the other tests' sizes: not whole tiles
    (PIECE, 8, True, False, "sweep"),
    (PIECE, BLOCK, False, False, "sweep"),
    (PIECE, BLOCK, True, True, "sweep"),  # an int8 record for Wkvb
])
def test_which_pieces_take_the_kernel(
        params, long_tokens, monkeypatch, piece, key_block, use_flash, int8,
        takes):
    from edl_tpu.ops import sparse_prefill_attention as spa

    walk_in(monkeypatch, piece, key_block)
    took = []
    monkeypatch.setattr(spa, "sparse_prefill_attention", lambda q, *a, **kw: (
        took.append("kernel"), jnp.zeros(q.shape[:2] + (64,), q.dtype))[1])
    sweep = g._sweep
    monkeypatch.setattr(g, "_sweep", lambda *a: (
        took.append("sweep"), sweep(*a))[1])
    cfg = cfg_of(use_flash=use_flash)
    tree = g.quantize_params_int8(params) if int8 else params
    with interpret_kernels():
        jax.eval_shape(lambda p, t: g.forward(p, t, cfg), tree,
                       jnp.asarray(long_tokens[:, :2 * PIECE]))
    assert set(took) == {takes}


# -- (d) the share ties to the model -------------------------------------------


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_reference():
    """Four shares of two experts each (``first`` = 0, 2, 4, 6), the
    shared expert and the residual counted once, add up to the
    reference's layer with all eight experts held."""
    config = {**CONFIG, "n_routed_experts": 8}
    layout = family.param_layout(config)
    whole = harness.make_params(5, layout, jnp.float32)["layers"]["01"]
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 24, 64))
    with jax.default_matmul_precision("highest"):
        m = reference._rmsnorm(x[0], whole["ln2"], CONFIG["rms_norm_eps"])
        table = reference.route(
            m, whole["router"], whole["router_bias"], config)
        want = (x[0] + reference.routed(
            m, table, whole["we1"], whole["we3"], whole["we2"])
            + reference._swiglu(m, whole["ws1"], whole["ws3"], whole["ws2"]))
        total = jnp.zeros_like(x)
        for first in range(0, 8, 2):
            cfg = cfg_of(experts_held=2, first_expert=first)
            lp = {k: (v[first:first + 2] if k in ("we1", "we3", "we2") else v)
                  for k, v in whole.items()}
            y, _ = g._ffn(cfg, x, lp)
            # the share's routed term alone: less the residual and the
            # shared expert every chip computes alike
            none = {**lp, **{k: jnp.zeros_like(lp[k])
                             for k in ("we1", "we3", "we2")}}
            base, _ = g._ffn(cfg, x, none)
            total = total + (y - base)
            # the reference is given the same share
            part = reference.held_columns(
                table, lp, {**config, "first_routed_expert": first})
            ref_part = reference.routed(
                m, part, lp["we1"], lp["we3"], lp["we2"])
            assert err((y - base)[0], ref_part) < TOL
        total = total + base
    assert err(total[0], want) < TOL
    assert float(jnp.max(jnp.abs(want - x[0]))) > 0.1


# -- (e) the engine --------------------------------------------------------------


_reference_64 = jax.jit(lambda p, t: reference.logits_row(p, t, CONFIG))


def gaps_of(params, prompt, out):
    """How far each served token lies under the reference's best (the
    reference is causal: a sequence END-padded to 64 reads the same)."""
    seq = prompt + out[:-1]
    lg = _reference_64(params, jnp.asarray(seq + [0] * (64 - len(seq))))
    lg = lg[len(prompt) - 1:len(seq)]
    return jnp.max(lg, -1) - lg[jnp.arange(len(out)), jnp.asarray(out)]


@pytest.mark.parametrize("use_flash", [False, True])
def test_engine_serves_the_references_greedy_tokens(params, use_flash):
    """Joins, leaves and a reused slot over the two-array cache, prompts
    of one to four pieces: every served token is the float32
    reference's first choice."""
    cfg = cfg_of(use_flash=use_flash)
    eng = ContinuousBatchingEngine(params, cfg, max_slots=2, max_len=64)
    rng = np.random.default_rng(1)
    prompts = {f"r{i}": [int(t) for t in rng.integers(0, 256, n)]
               for i, n in enumerate((9, 30, 17, 26))}
    with interpret_kernels(), jax.default_matmul_precision("highest"):
        for rid, prompt in prompts.items():
            eng.submit(rid, prompt, 6)
        results = eng.run()
    assert eng.recoveries == 0
    assert [(c.shape, c.dtype) for c in eng._cache] == [
        (shape, dtype) for shape, dtype in cfg.serve_cache_spec(2, 64)]
    for rid, prompt in prompts.items():
        out = list(results[rid].tokens)
        assert results[rid].outcome == "done" and len(out) == 6
        assert float(jnp.max(gaps_of(params, prompt, out))) < TOL, rid


def test_the_engines_spans_say_what_the_block_reads_and_the_pieces(params):
    cfg = cfg_of()
    eng = ContinuousBatchingEngine(params, cfg, max_slots=2, max_len=64)
    before = {n: len(tracing.tracer().spans(n))
              for n in ("serving.dispatch", "serving.prefill")}
    eng.submit("a", list(range(3, 23)), 4)  # 20 tokens: bucket 32
    eng.run()
    prefill = tracing.tracer().spans("serving.prefill")[
        before["serving.prefill"]:]
    assert [(s.attrs["bucket"], s.attrs["pieces"]) for s in prefill] \
        == [(32, 4)]
    spans = tracing.tracer().spans("serving.dispatch")[
        before["serving.dispatch"]:]
    first = spans[0].attrs
    # one slot of 21 tokens: 8 of them attended; the dense lines read
    # both arrays whole
    assert first["kv_selected_share"] == pytest.approx(8 / 21)
    assert first["kv_read_share"] == 1.0
    assert 0.0 < first["experts_hit_share"] <= 3 / 4
    assert first["expert_load_max_over_mean"] >= 1.0
    # both arrays are filed under "kv"
    assert eng._ledger.total("kv") == sum(c.nbytes for c in eng._cache) \
        if hasattr(eng._ledger, "total") else True


def test_engine_recovers_a_crashed_dispatch(params):
    """A dispatch that raises once: the engine reallocates both arrays,
    replays, and the tokens are still the reference's."""
    cfg = cfg_of()
    eng = ContinuousBatchingEngine(params, cfg, max_slots=2, max_len=64)
    prompt = [int(t) for t in np.random.default_rng(4).integers(0, 256, 19)]
    real, calls = eng._decode, {"n": 0}

    def flaky(*args):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("planted")
        return real(*args)

    eng._decode = flaky
    with jax.default_matmul_precision("highest"):
        eng.submit("a", prompt, 6)
        out = list(eng.run()["a"].tokens)
    assert eng.recoveries == 1 and len(out) == 6
    assert float(jnp.max(gaps_of(params, prompt, out))) < TOL


@pytest.mark.parametrize("option", [
    {"block_size": 16}, {"block_size": 16, "kv_quant": "int8"},
    {"block_size": 16, "prefill_chunk": 16},
    {"block_size": 16, "prefix_cache": True}, {"spec_k": 2}])
def test_the_dense_decoders_options_are_refused_at_construction(
        params, option):
    with pytest.raises(ValueError, match="contiguous cache alone"):
        ContinuousBatchingEngine(
            params, cfg_of(), max_slots=2, max_len=32, **option)


# -- (f) the config ----------------------------------------------------------------


PUBLISHED = os.path.join(
    harness.ROOT, "benchmark", "published", "zai-org.GLM-5.json")


def test_from_hf_reads_the_published_keys():
    cfg = g.GlmDsaConfig.from_hf(harness.load_json(PUBLISHED))
    assert (cfg.vocab, cfg.d_model, cfg.n_layers, cfg.n_heads) == (
        154880, 6144, 78, 64)
    assert (cfg.q_rank, cfg.kv_rank, cfg.qk_nope_dim, cfg.qk_rope_dim,
            cfg.v_dim) == (2048, 512, 192, 64, 256)
    assert (cfg.index_heads, cfg.index_dim, cfg.index_topk) == (32, 128, 2048)
    assert (cfg.d_ff, cfg.n_dense_layers, cfg.d_expert, cfg.n_experts,
            cfg.held, cfg.n_shared, cfg.top_k) == (
        12288, 3, 2048, 256, 256, 1, 8)
    assert (cfg.route_scale, cfg.norm_topk, cfg.rope_theta, cfg.norm_eps) \
        == (2.5, True, 1e6, 1e-5)
    assert cfg.latent_width == 576 and cfg.cache_width == 640


@pytest.mark.parametrize("key, value", [
    ("n_group", 8), ("topk_group", 4), ("scoring_func", "softmax"),
    ("rope_interleave", False), ("indexer_rope_interleave", False),
    ("attention_bias", True), ("q_lora_rank", None), ("index_topk", 0),
    ("rope_parameters", {"rope_theta": 1e6, "rope_type": "yarn"}),
    ("rope_scaling", {"type": "yarn"})])
def test_from_hf_refuses_what_the_file_does_not_implement(key, value):
    with pytest.raises(NotImplementedError, match=key.split("_")[0]):
        g.GlmDsaConfig.from_hf({**harness.load_json(PUBLISHED), key: value})


def test_meta_round_trips_and_names_its_family():
    cfg = cfg_of()
    meta = json.loads(json.dumps(cfg.to_meta()))
    assert meta["family"] == "glm_dsa" and meta["experts_held"] == 4
    assert g.GlmDsaConfig.from_meta(meta) == cfg
    with pytest.raises(ValueError, match="not a glm_dsa export"):
        g.GlmDsaConfig.from_meta({**meta, "family": "deepseek_v3"})


def test_the_cost_model_prices_the_share_and_the_sparse_read():
    from edl_tpu.obs import costmodel as cm

    cell = harness.Cell("glm5.long-sparse")
    cfg = cell.family.program_config(cell.config, training=False)
    attn = (6144 * 2048 + 2048 * 64 * 256 + 6144 * 576 + 512 * 64 * 448
            + 64 * 256 * 6144)
    index = 2048 * 32 * 128 + 6144 * 128 + 6144 * 32
    assert cfg.attn_params() == attn + index
    assert round(attn / 1e6, 2) == 165.02 and round(index / 1e6, 2) == 9.37
    assert round(cm.n_params(cfg) / 1e9, 2) == 2.70
    # a token multiplies its 8 choices' share of the 8 held of 256
    moe = 3 * 6144 * 2048 * (8 * 8 / 256 + 1) + 6144 * 256
    assert cm.matmul_params(cfg) == 5 * (attn + index) \
        + 3 * 6144 * 12288 + 4 * moe + 6144 * 19360
    # both arrays: 640 + 128 columns a position a layer
    assert cm.kv_cache_bytes(cfg, 32, 32768) \
        == 32 * 32768 * 5 * (640 + 128) * 2
    # a block is priced by what it reads: every slot's index keys to
    # the farthest live position (in blocks of 4096), each slot's latent
    # rows to its own last token (in the kernel's blocks of 1024, an
    # idle slot one block): read and masked, not gathered
    held = [10000, 20000] + [None] * 30
    assert cfg.serve_attn_block(32768) == 1024
    shares = cfg.serve_cache_read(held, 32768, 1024)
    assert shares["kv_selected_share"] == pytest.approx(4096 / 30000)
    model = cm.CostModel(cfg)
    dense = model.decode_block(32, 1, 32768, 1.0)
    sparse = model.decode_block(32, 1, 32768, shares)
    read = 5 * 2 * (32 * 20480 * 128 + (10240 + 20480 + 30 * 1024) * 640)
    assert dense.hbm_bytes - sparse.hbm_bytes == pytest.approx(
        32 * 32768 * 5 * 768 * 2 - read, rel=1e-6)


# -- ``edl serve`` ---------------------------------------------------------------


def test_cli_serve_serves_a_glm_dsa_export(tmp_path, params):
    from edl_tpu.runtime.export import export_params

    cfg = cfg_of()
    export_params(str(tmp_path), params, step=1, dtype="float32",
                  model_meta=cfg.to_meta())
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.path.dirname(os.path.dirname(__file__))}
    prompt = [int(t) for t in np.random.default_rng(2).integers(0, 256, 21)]
    serve = [sys.executable, "-m", "edl_tpu.cli", "serve", str(tmp_path)]
    out = subprocess.run(
        serve + ["--max-slots", "2", "--max-len", "64"],
        input=json.dumps({"id": "a", "prompt": prompt, "max_new": 5}) + "\n",
        capture_output=True, text=True, env=env)
    assert out.returncode == 0, out.stderr
    (rec,) = [json.loads(l) for l in out.stdout.strip().splitlines()]
    assert rec["outcome"] == "done" and len(rec["tokens"]) == 5
    assert float(jnp.max(gaps_of(params, prompt, rec["tokens"]))) < 1e-3
    bad = subprocess.run(
        serve + ["--block-size", "16", "--max-len", "32"],
        input='{"prompt": [1]}\n', capture_output=True, text=True, env=env)
    assert bad.returncode != 0 and "contiguous cache alone" in bad.stderr
