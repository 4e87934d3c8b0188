"""``ops.decode_attention`` (``edl_decode_attn``): the ragged
single-query kernel that ``llama.decode_step_slots`` runs under
``cfg.use_flash``, under the Pallas interpreter.

The reference is the dense form it replaces
(``llama.slot_attention_dense``, the ``use_flash=False`` path): all
``S`` positions read, masked to ``<= pos`` afterwards. The kernel reads
only the S-blocks up to each slot's ``pos``; what it returns has to be
the same attention within bf16 rounding, for MHA and GQA alike, with
``pos`` on either side of a block boundary, and the cache it is handed
comes back from the model step exactly as the dense path leaves it.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.models import llama
from edl_tpu.ops.decode_attention import (
    block_positions,
    decode_attention,
    head_bias,
)
from edl_tpu.ops.flash_attention import NEG_INF, interpret_kernels
from edl_tpu.serving.engine import ContinuousBatchingEngine

L, B, S, HD = 3, 5, 64, 32


def _operands(kvh, groups, dtype=jnp.bfloat16, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    kc = jax.random.normal(k1, (L, B, S, kvh, HD), jnp.float32).astype(dtype)
    vc = jax.random.normal(k2, (L, B, S, kvh, HD), jnp.float32).astype(dtype)
    q = jax.random.normal(k3, (B, kvh, groups, HD), jnp.float32).astype(dtype)
    return q, kc, vc


def _positions(case, block_s):
    """pos [B] for a named case; a boundary is the first position of the
    second S-block."""
    return {
        "zero": [0] * B,
        "boundary-1": [block_s - 1] * B,
        "boundary": [block_s] * B,
        "last": [S - 1] * B,
        "mixed": [0, block_s - 1, block_s, S - 1, S // 2 + 3],
    }[case]


@pytest.mark.parametrize("block_s", [16, 32])
@pytest.mark.parametrize(
    "case", ["zero", "boundary-1", "boundary", "last", "mixed"]
)
@pytest.mark.parametrize("kvh,groups", [(4, 1), (2, 4)])
def test_kernel_matches_the_dense_lines(kvh, groups, case, block_s):
    q, kc, vc = _operands(kvh, groups)
    pos = jnp.asarray(_positions(case, block_s), jnp.int32)
    layer = 1
    got = decode_attention(
        q, kc, vc, pos, jnp.int32(layer), block_s=block_s, interpret=True
    )
    want = llama.slot_attention_dense(q, kc[layer], vc[layer], pos)
    assert got.shape == want.shape and got.dtype == want.dtype
    # bf16 outputs of O(1): the dense lines round the scores and the
    # probabilities to bf16, the kernel keeps both in float32
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=3e-2, rtol=3e-2,
    )


def test_kernel_in_float32_is_the_dense_lines_to_rounding():
    """With nothing rounded to bf16 on either side the two agree to
    float32 rounding: the tolerance above is precision, not slack."""
    q, kc, vc = _operands(2, 4, jnp.float32)
    pos = jnp.asarray(_positions("mixed", 16), jnp.int32)
    got = decode_attention(
        q, kc, vc, pos, jnp.int32(2), block_s=16, interpret=True
    )
    want = llama.slot_attention_dense(q, kc[2], vc[2], pos)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_blocks_past_pos_are_never_read():
    """Poison (NaN) every S-block past the one that holds a slot's
    ``pos``, and every layer but the one asked for: the output does not
    move. (Inside the last live block the positions past ``pos`` are
    read and masked, as the dense lines do with all of them.)"""
    q, kc, vc = _operands(4, 1)
    pos = jnp.asarray([0, 15, 16, 63, 35], jnp.int32)
    clean = decode_attention(
        q, kc, vc, pos, jnp.int32(1), block_s=16, interpret=True
    )
    live_to = (pos // 16 + 1) * 16
    dead = (jnp.arange(S)[None, :] >= live_to[:, None])[
        None, :, :, None, None]
    other = (jnp.arange(L) != 1)[:, None, None, None, None]
    poison = lambda c: jnp.where(dead | other, jnp.nan, c)
    got = decode_attention(
        q, poison(kc), poison(vc), pos, jnp.int32(1), block_s=16,
        interpret=True,
    )
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(clean, np.float32)
    )


def test_head_bias_opens_each_query_head_to_its_own_kv_head():
    bias = np.asarray(head_bias(2, 4, 3))  # H 8, columns (p, k) = 3 x 2
    assert bias.shape == (8, 6)
    for h in range(8):
        for c in range(6):
            assert bias[h, c] == (0.0 if c % 2 == h // 4 else NEG_INF)


@pytest.mark.parametrize(
    "kvh,hd,s,want",
    [(32, 128, 2048, 64), (8, 128, 2048, 256), (8, 128, 128, 128),
     (2, 16, 64, 64)],
)
def test_block_positions_follow_the_bytes_of_a_position(kvh, hd, s, want):
    assert block_positions(kvh, hd, 2, s) == want


def test_block_that_does_not_divide_the_cache_is_refused():
    q, kc, vc = _operands(4, 1)
    with pytest.raises(ValueError, match="must divide"):
        decode_attention(
            q, kc, vc, jnp.zeros((B,), jnp.int32), jnp.int32(0),
            block_s=24, interpret=True,
        )


# -- through the model step ---------------------------------------------------


def _tiny(use_flash, n_layers):
    return dataclasses.replace(
        llama.LlamaConfig.tiny(), n_layers=n_layers, use_flash=use_flash,
        dtype=jnp.bfloat16,
    )


@pytest.mark.parametrize("n_layers", [1, 2])
def test_decode_step_slots_under_use_flash(n_layers):
    """One slot step with the kernel against the dense path: logits
    within bf16 tolerance; the cache written by a one-layer model is
    bit-equal (its new K/V do not pass through any attention), and a
    deeper model's differs only in the rows the step wrote."""
    dense, flash = _tiny(False, n_layers), _tiny(True, n_layers)
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16),
        llama.init_params(jax.random.PRNGKey(0), dense),
    )
    b, s = 4, 32
    shape = (n_layers, b, s, dense.n_kv_heads, dense.head_dim)
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    kc = jax.random.normal(k1, shape, jnp.float32).astype(jnp.bfloat16)
    vc = jax.random.normal(k2, shape, jnp.float32).astype(jnp.bfloat16)
    tok = jnp.asarray([3, 5, 7, 11], jnp.int32)
    pos = jnp.asarray([0, 7, 8, 31], jnp.int32)
    want, kd, vd = llama.decode_step_slots(params, tok, pos, kc, vc, dense)
    with interpret_kernels():
        got, kf, vf = llama.decode_step_slots(params, tok, pos, kc, vc, flash)
    np.testing.assert_allclose(got, want, atol=5e-2, rtol=5e-2)
    if n_layers == 1:
        np.testing.assert_array_equal(
            np.asarray(kf, np.float32), np.asarray(kd, np.float32))
        np.testing.assert_array_equal(
            np.asarray(vf, np.float32), np.asarray(vd, np.float32))
    else:
        written = np.zeros((b, s), bool)
        written[np.arange(b), np.asarray(pos)] = True
        for f, c in ((kf, kc), (vf, vc)):
            same = np.asarray(f, np.float32) == np.asarray(c, np.float32)
            assert same[:, ~written].all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("records", [False, True], ids=["plain", "int8"])
@pytest.mark.parametrize(
    "heads,kv_heads", [(8, 8), (32, 8)], ids=["mha", "gqa8"]
)
def test_the_cached_steps_projection_is_qkv(heads, kv_heads, records, dtype):
    """``_qkv_cached`` (the cached steps' projections: the products
    held behind a barrier before the head split, so the chip reads the
    stacked weights where they lie) against ``_qkv`` on the same layer
    of the same stacked tree, both jitted: the same q, k, v. float32
    exactly; bfloat16 within one ulp of the product (a barrier may stop
    the compiler from carrying a product wider into RoPE)."""
    cfg = dataclasses.replace(
        llama.LlamaConfig.tiny(), d_model=heads * 16, n_heads=heads,
        n_kv_heads=kv_heads, n_layers=2, dtype=dtype,
    )
    params = jax.tree_util.tree_map(
        lambda x: x.astype(dtype),
        llama.init_params(jax.random.PRNGKey(0), cfg),
    )
    if records:
        params = llama.quantize_params_int8(params)
    b = 5
    a = jax.random.normal(
        jax.random.PRNGKey(1), (b, 1, cfg.d_model), jnp.float32
    ).astype(dtype)
    pos = jnp.asarray([0, 3, 17, 40, 63], jnp.int32)[:, None]

    def layer(project, i):
        def run(layers, a, pos):
            lp = jax.tree_util.tree_map(lambda w: w[i], layers)
            return project(cfg, a, lp, pos)
        return jax.jit(run)(params["layers"], a, pos)

    for i in range(cfg.n_layers):
        want = layer(llama._qkv, i)
        got = layer(llama._qkv_cached, i)
        for g, w, n in zip(got, want, (heads, kv_heads, kv_heads)):
            assert g.shape == (b, 1, n, cfg.head_dim) and g.dtype == dtype
            g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
            if dtype == jnp.float32:
                np.testing.assert_array_equal(g, w)
            else:  # one ulp of bfloat16 at the value's own exponent
                ulp = 2.0 ** (np.floor(np.log2(np.abs(w) + 1e-30)) - 7)
                assert (np.abs(g - w) <= ulp).all()


def _traced_barriers(program):
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(), n_layers=2)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    b, s, bs = 2, 16, 4
    i32 = jnp.zeros((b,), jnp.int32)
    kc = jnp.zeros((2, b, s, cfg.n_kv_heads, cfg.head_dim), cfg.dtype)
    pool = jnp.zeros((2, 9, bs, cfg.n_kv_heads, cfg.head_dim), cfg.dtype)
    table = jnp.zeros((b, s // bs), jnp.int32)
    draft = jnp.zeros((b, 2), jnp.int32)
    on = jnp.ones((b,), bool)
    run = {
        "train": lambda p: jax.grad(llama.make_loss_fn(cfg))(
            p, {"tokens": jnp.zeros((b, 9), jnp.int32)}),
        "prefill": lambda p: llama.prefill_padded(
            p, jnp.zeros((b, 8), jnp.int32), i32, cfg),
        "slots": lambda p: llama.decode_step_slots(p, i32, i32, kc, kc, cfg),
        "generate": lambda p: llama._decode_step(
            p, i32, jnp.int32(3), kc, kc, cfg),
        "paged": lambda p: llama.decode_step_slots_paged(
            p, i32, i32, table, (pool, pool), cfg, bs),
        "chunk": lambda p: llama.prefill_paged(
            p, jnp.zeros((1, 8), jnp.int32), jnp.int32(0), jnp.int32(7),
            table[0], (pool, pool), cfg, bs),
        "verify": lambda p: llama.verify_step_slots(
            p, i32, draft, i32, on, i32 + 4, i32 - 1, kc, kc, cfg),
        "verify-paged": lambda p: llama.verify_step_slots_paged(
            p, i32, draft, i32, on, i32 + 4, i32 - 1, table, (pool, pool),
            cfg, bs),
    }[program]
    return str(jax.make_jaxpr(run)(params)).count("optimization_barrier")


@pytest.mark.parametrize(
    "program,layers_held",
    [("train", 0), ("prefill", 0), ("slots", 2), ("generate", 2),
     ("paged", 2), ("chunk", 2), ("verify", 2), ("verify-paged", 2)],
)
def test_which_program_is_built_decides_the_projections_form(
    program, layers_held
):
    """No flag, no test of a model or of a row count: the programs whose
    layers are unrolled over the stacked tree against a cache hold each
    layer's products behind one barrier (``_qkv_cached``); the training
    step and the scanned prefill trace none (``_qkv`` bare, the
    parent's programs to the byte)."""
    assert _traced_barriers(program) == layers_held


@pytest.mark.parametrize("horizon", [1, 4])
def test_engine_under_use_flash_serves_the_dense_engines_tokens(horizon):
    """The contiguous engine with ``use_flash=True`` (prefill through
    the flash kernel, every decode step through ``edl_decode_attn``,
    both interpreted) emits the greedy tokens of the ``use_flash=False``
    engine, with requests joining mid-stream and slots reused."""
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    prompts = [list(range(2, 2 + n)) for n in (4, 7, 3, 9, 5)]
    max_news = [6, 3, 9, 5, 7]

    def serve(cfg):
        eng = ContinuousBatchingEngine(
            params, cfg, max_slots=3, max_len=64, horizon=horizon
        )
        for i in range(3):
            eng.submit(f"r{i}", prompts[i], max_news[i])
        eng.step()
        for i in range(3, 5):
            eng.submit(f"r{i}", prompts[i], max_news[i])
        return {rid: r.tokens for rid, r in eng.run().items()}

    want = serve(cfg)
    with interpret_kernels():
        got = serve(dataclasses.replace(cfg, use_flash=True))
    assert got == want
    assert all(len(want[f"r{i}"]) == max_news[i] for i in range(5))


def test_rows_that_are_not_live_read_one_block_and_live_rows_do_not_move():
    """``live`` (the horizon's ``active``) under ``use_flash``: a frozen
    row's attention is read at position 0 whatever ``pos`` it froze at
    (the kernel's own tests show that blocks past it are not fetched);
    whatever a finished row holds, NaNs too, the live rows' logits and
    cache rows are as without the mask."""
    flash = _tiny(True, 2)
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16),
        llama.init_params(jax.random.PRNGKey(0), flash),
    )
    b, s = 4, 32
    shape = (2, b, s, flash.n_kv_heads, flash.head_dim)
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    kc = jax.random.normal(k1, shape, jnp.float32).astype(jnp.bfloat16)
    vc = jax.random.normal(k2, shape, jnp.float32).astype(jnp.bfloat16)
    tok = jnp.asarray([3, 5, 7, 11], jnp.int32)
    pos = jnp.asarray([4, 20, 9, 31], jnp.int32)
    live = jnp.asarray([True, False, True, False])
    with interpret_kernels():
        want, _, _ = llama.decode_step_slots(params, tok, pos, kc, vc, flash)
        # rows 1 and 3 are finished: poison what they hold past position 0
        dead = (~live)[None, :, None, None, None] & (
            jnp.arange(s) > 0)[None, None, :, None, None]
        got, kf, _ = llama.decode_step_slots(
            params, tok, pos, jnp.where(dead, jnp.nan, kc),
            jnp.where(dead, jnp.nan, vc), flash, live=live,
        )
    rows = np.asarray(live)
    np.testing.assert_array_equal(np.asarray(got)[rows], np.asarray(want)[rows])
    assert np.isfinite(np.asarray(got, np.float32)[rows]).all()
    assert np.isfinite(np.asarray(kf, np.float32)[:, rows]).all()


def test_a_position_past_the_cache_reads_the_whole_cache_and_no_further():
    q, kc, vc = _operands(4, 1)
    at = lambda p: decode_attention(
        q, kc, vc, jnp.full((B,), p, jnp.int32), jnp.int32(0), block_s=16,
        interpret=True,
    )
    np.testing.assert_array_equal(
        np.asarray(at(S), np.float32), np.asarray(at(S - 1), np.float32))
