"""Quantized paged KV cache (``--kv-quant int8/int4``).

The contract under test: the OFF lane stays byte-identical to the
unquantized paged engine (same tokens, same dispatch counters, no
quantized program keys), the quantized lanes store int8/int4 blocks +
per-(block, kv-head) f32 scales whose round-trip error is bounded by
the quantization step, copy-on-write carries a block's scales with its
values, crash recovery replays within tolerance, and the speculative
acceptance guard flags an injected quality regression.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.models import llama
from edl_tpu.serving import engine as engine_mod
from edl_tpu.serving.engine import ContinuousBatchingEngine, SpecAcceptGuard
from edl_tpu.utils import faults

CFG = llama.LlamaConfig.tiny()
PARAMS = llama.init_params(jax.random.PRNGKey(0), CFG)

PROMPTS = [list(range(2, 2 + n)) for n in (4, 7, 3, 9, 5, 6)]
MAX_NEWS = [6, 3, 13, 5, 7, 9]


@pytest.fixture(autouse=True)
def _disarm():
    faults.disarm()
    yield
    faults.disarm()


def _engine(**kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("block_size", 8)
    return ContinuousBatchingEngine(PARAMS, CFG, **kw)


def _run_all(eng, reqs=None):
    reqs = reqs if reqs is not None else list(zip(PROMPTS, MAX_NEWS))
    for i, (p, mn) in enumerate(reqs):
        eng.submit(f"r{i}", p, mn)
    res = eng.run()
    return [res[f"r{i}"].tokens for i in range(len(reqs))]


def _agreement(a, b):
    n = max(len(a), len(b))
    return sum(x == y for x, y in zip(a, b)) / n if n else 1.0


# -- store/unpack round-trip ---------------------------------------------------


@pytest.mark.parametrize("kv_quant", ["int8", "int4"])
def test_kvq_store_roundtrip_error_bound(kv_quant):
    """Decode-order writes into one block: dequantized content tracks
    the written f32 values within the quantization step at the block's
    final scale (the last write exactly; earlier offsets accumulate at
    most half a step per rescale as the block's amax grew)."""
    rng = np.random.RandomState(3)
    L, nb, bs, kvh, hd = 2, 5, 8, 2, 16
    hdp = llama.kvq_packed_head_dim(kv_quant, hd)
    pool = jnp.zeros((L, nb, bs, kvh, hdp), jnp.int8)
    scale = jnp.zeros((L, nb, kvh), jnp.float32)
    vals = rng.randn(bs, kvh, hd).astype(np.float32)
    for off in range(bs):
        pool, scale = llama._kvq_store(
            pool, scale, 0,
            jnp.asarray([1], jnp.int32), jnp.asarray([off], jnp.int32),
            jnp.asarray(vals[off][None]), kv_quant,
        )
    sc = np.asarray(scale[0, 1])  # [kvh]
    assert np.all(sc > 0)
    deq = np.asarray(
        llama._kvq_unpack(pool[0, 1], kv_quant)
    ) * sc[None, :, None]
    step = sc[None, :, None]
    # last write: a single quantization at the final (largest) scale
    assert np.all(np.abs(deq[-1] - vals[-1]) <= 0.5 * step[0] + 1e-6)
    # earlier offsets: + at most half a step per intervening rescale
    assert np.all(np.abs(deq - vals) <= (0.5 * bs) * step + 1e-6)
    # per-head scale actually covers the block's absmax
    assert np.all(
        np.abs(vals).max(axis=(0, 2))
        <= sc * llama._KVQ_QMAX[kv_quant] * (1 + 1e-6)
    )


def test_kvq_store_fresh_block_resets_scale():
    """A write at offset 0 marks the block FRESH: the previous tenant's
    large scale is dropped (not inherited) and its stale content reads
    back as zero instead of garbage under the new scale."""
    L, nb, bs, kvh, hd = 1, 3, 4, 2, 8
    pool = jnp.zeros((L, nb, bs, kvh, hd), jnp.int8)
    scale = jnp.zeros((L, nb, kvh), jnp.float32)
    big = jnp.full((1, kvh, hd), 100.0, jnp.float32)
    for off in range(bs):  # old tenant fills block 1 with huge values
        pool, scale = llama._kvq_store(
            pool, scale, 0, jnp.asarray([1], jnp.int32),
            jnp.asarray([off], jnp.int32), big, "int8",
        )
    small = jnp.full((1, kvh, hd), 0.5, jnp.float32)
    pool, scale = llama._kvq_store(
        pool, scale, 0, jnp.asarray([1], jnp.int32),
        jnp.asarray([0], jnp.int32), small, "int8",
    )
    sc = np.asarray(scale[0, 1])
    assert np.all(sc == pytest.approx(0.5 / 127.0))  # reset, not 100/127
    deq = np.asarray(llama._kvq_unpack(pool[0, 1], "int8"))
    assert np.all(deq[1:] == 0)  # stale offsets zeroed
    assert np.asarray(deq[0] * sc[:, None]) == pytest.approx(0.5, abs=1e-5)


def test_kvq_int4_needs_even_head_dim():
    with pytest.raises(ValueError, match="even head_dim"):
        llama.kvq_packed_head_dim("int4", 5)
    assert llama.kvq_packed_head_dim("int4", 16) == 8
    assert llama.kvq_packed_head_dim("int8", 16) == 16


# -- the OFF lane is byte-identical --------------------------------------------


def test_kv_quant_off_byte_identical():
    """``kv_quant="off"`` is the same engine, not a quantized engine
    with a wide tolerance: identical tokens, identical dispatch
    counters, float pools, no scale planes, and the plain paged
    engine's very programs: running it memoizes nothing new."""
    plain = _engine(horizon=4)
    off = _engine(horizon=4, kv_quant="off")
    toks_plain = _run_all(plain)
    keys_plain = set(engine_mod._programs)
    toks_off = _run_all(off)
    assert toks_plain == toks_off
    s1, s2 = plain.metrics.snapshot(), off.metrics.snapshot()
    for k in ("dispatches_decode", "dispatches_prefill", "tokens_out"):
        assert s1[k] == s2[k], k
    assert len(off._cache) == len(plain._cache) == 2  # no scale planes
    assert off._cache[0].dtype == plain._cache[0].dtype != jnp.int8
    assert off._kvq_guard is None
    # one factory a kind of program, keyed by ``kv_quant``: the off
    # lane IS the plain paged engine's programs, and nothing quantized
    # was built for it
    assert off._decode is plain._decode
    assert off._copyblk is plain._copyblk
    assert set(engine_mod._programs) == keys_plain


def test_kv_quant_constructor_validation():
    with pytest.raises(ValueError, match="kv_quant"):
        ContinuousBatchingEngine(PARAMS, CFG, max_len=64, kv_quant="int8")
    with pytest.raises(ValueError, match="kv_quant"):
        _engine(kv_quant="fp8")


# -- quantized lanes: quality, pool dtype, ledger ------------------------------


@pytest.mark.parametrize("kv_quant", ["int8", "int4"])
def test_kv_quant_pool_layout_and_ledger(kv_quant):
    """Quantized pools are int8 with the packed head dim; the memory
    ledger's kv category and bytes-per-token gauge report the REAL
    (values + scales) figure, 2-4x under the float pool."""
    from edl_tpu.obs import memledger
    from edl_tpu.obs import metrics as obs_metrics

    reg = obs_metrics.reset_default_registry()
    memledger.reset_default_ledger(reg)
    try:
        eng = _engine(kv_quant=kv_quant)
        hdp = llama.kvq_packed_head_dim(kv_quant, CFG.head_dim)
        assert eng._cache[0].dtype == jnp.int8
        assert eng._cache[0].shape[-1] == hdp
        assert eng._cache[2].shape == (
            CFG.n_layers, eng.pool_blocks, CFG.n_kv_heads
        )
        pool_b = (
            eng._cache[0].nbytes + eng._cache[1].nbytes + eng._cache[2].nbytes
            + eng._cache[3].nbytes
        )
        assert reg.get("edl_hbm_bytes").value(category="kv") == pool_b
        cap = eng.pool_blocks * eng.block_size
        assert reg.get("edl_kv_bytes_per_token").value() == pytest.approx(
            pool_b / cap
        )
        # the whole point: fewer bytes than the float pool would hold
        el = np.dtype(CFG.dtype).itemsize
        float_b = (
            2 * CFG.n_layers * eng.pool_blocks * eng.block_size
            * CFG.n_kv_heads * CFG.head_dim * el
        )
        assert float_b / pool_b >= 1.8, (float_b, pool_b)
    finally:
        memledger.reset_default_ledger(obs_metrics.reset_default_registry())


def test_kv_quant_int8_output_quality():
    """int8-KV greedy streams track the float paged engine's within a
    pinned fractional-token tolerance (exact identity is not the
    contract — near-tied logits may flip — but wholesale divergence
    means the dequant discipline broke)."""
    toks_f = _run_all(_engine(horizon=4))
    toks_q = _run_all(_engine(horizon=4, kv_quant="int8"))
    agr = [_agreement(a, b) for a, b in zip(toks_f, toks_q)]
    assert np.mean(agr) >= 0.9, agr
    for t in toks_q:
        assert len(t) > 0


def test_kv_quant_int4_runs_to_completion():
    """int4 is the same machinery at half the bytes: noisier (no
    agreement pin) but every request must complete with its full
    budget or a real EOS."""
    eng = _engine(kv_quant="int4")
    toks = _run_all(eng)
    for t, mn in zip(toks, MAX_NEWS):
        assert 0 < len(t) <= mn
    assert eng._balloc.allocated_blocks == 0


# -- copy-on-write carries scales ----------------------------------------------


def test_cow_block_copy_carries_scales():
    """The quantized CoW program copies the block's SCALES with its
    values — a copied block that kept stale scales would dequantize
    to garbage."""
    eng = _engine(max_slots=2, kv_quant="int8", prefix_cache=True)
    kc = eng._cache[0].at[:, 3].set(5)
    vc = eng._cache[1].at[:, 3].set(-3)
    ks = eng._cache[2].at[:, 3].set(0.25)
    vs = eng._cache[3].at[:, 3].set(0.5)
    kc, vc, ks, vs = eng._copyblk(
        kc, vc, ks, vs, jnp.int32(3), jnp.int32(4)
    )
    assert np.all(np.asarray(kc[:, 4]) == 5)
    assert np.all(np.asarray(vc[:, 4]) == -3)
    assert np.all(np.asarray(ks[:, 4]) == 0.25)
    assert np.all(np.asarray(vs[:, 4]) == 0.5)
    assert np.all(np.asarray(ks[:, 2]) == 0.0)  # only the dst block moved


def test_prefix_full_hit_cow_identical_under_int8():
    """An identical prompt served from the prefix cache (full-chain
    hit -> CoW of the last block) reads the SAME quantized blocks the
    first request wrote: the two greedy streams must match exactly —
    any scale lost in the copy would split them immediately."""
    prompt = list(range(2, 26))  # three full 8-blocks
    eng = _engine(kv_quant="int8", prefix_cache=True)
    eng.submit("one", prompt, 7)
    res = eng.run()
    eng.submit("two", prompt, 7)
    res2 = eng.run()
    assert res2["two"].tokens == res["one"].tokens
    assert eng._prefix.hits >= 3


# -- crash recovery ------------------------------------------------------------


@pytest.mark.parametrize("plan", [
    "serve.dispatch:raise@n=2",
    "serve.prefill:raise@n=1",
])
def test_int8_recovery_replay_within_tolerance(plan):
    """Crash recovery rebuilds the quantized pool + scale planes from
    host truth and replays resident tokens through the quantized
    prefill. Replay quantizes whole blocks under their final amax
    while the original run grew scales incrementally, so exact
    identity is not guaranteed — but streams must stay within the
    pinned agreement tolerance of a fault-free quantized run."""
    base = _run_all(_engine(kv_quant="int8", horizon=4))
    faults.arm(plan, seed=0)
    eng = _engine(kv_quant="int8", horizon=4, max_recoveries=3)
    toks = _run_all(eng)
    faults.disarm()
    assert eng.recoveries >= 1
    assert eng._cache[0].dtype == jnp.int8  # rebuilt pool is still quantized
    agr = [_agreement(a, b) for a, b in zip(base, toks)]
    assert np.mean(agr) >= 0.8, (plan, agr)
    for t, mn in zip(toks, MAX_NEWS):
        assert 0 < len(t) <= mn


# -- the speculative-acceptance quality gate -----------------------------------


def test_spec_accept_guard_fires_on_injected_regression():
    from edl_tpu.obs import metrics as obs_metrics

    reg = obs_metrics.MetricsRegistry()
    g = SpecAcceptGuard(reg, warmup=5, tol=0.05, alpha=0.5)
    gauge = reg.get("edl_kv_quant_quality_ok")
    assert gauge.value() == 1.0
    g.observe(0, 0)  # no drafts: ignored, not a 0% observation
    for _ in range(5):
        g.observe(10, 8)
    assert g.baseline == pytest.approx(0.8)
    assert g.ok and gauge.value() == 1.0
    for _ in range(10):  # injected regression: acceptance collapses
        g.observe(10, 2)
    assert not g.ok and gauge.value() == 0.0
    assert g.ema < g.baseline - g.tol
    for _ in range(30):  # and the flag clears when quality returns
        g.observe(10, 8)
    assert g.ok and gauge.value() == 1.0


def test_engine_wires_guard_only_for_quantized_spec():
    e = _engine(kv_quant="int8", spec_k=2, spec_ngram=2)
    assert e._kvq_guard is not None
    assert _engine(spec_k=2, spec_ngram=2)._kvq_guard is None
    assert _engine(kv_quant="int8")._kvq_guard is None


def test_int8_spec_decoding_accepts_and_observes():
    """Speculation composes with the quantized cache: a repetitive
    prompt yields real acceptances, and every verify block feeds the
    quality guard's EMA."""
    eng = _engine(max_slots=1, kv_quant="int8", spec_k=4, spec_ngram=3,
                  horizon=1)
    eng.submit("rep", [5, 9] * 6, 24)
    eng.run()
    snap = eng.metrics.snapshot()
    assert snap["spec_drafted"] > 0
    assert snap["spec_accepted"] > 0
    assert eng._kvq_guard is not None and eng._kvq_guard.ema is not None
