"""Paged KV cache (block pool + per-slot block tables).

The correctness contract: a paged engine (``block_size > 0``) emits
GREEDY tokens identical to sequential ``llama.generate`` — and hence
to the contiguous engine — at every horizon, for any membership
history: joins mid-stream, prompts straddling block boundaries,
mid-block EOS, prefix-cache hits (shared blocks + copy-on-write),
chunked prefill, pool-pressure preemption, and across fault-injected
crash/recovery that rebuilds pool and tables from host truth.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.models import llama
from edl_tpu.serving import paged
from edl_tpu.serving.engine import ContinuousBatchingEngine
from edl_tpu.utils import faults

CFG = llama.LlamaConfig.tiny()
PARAMS = llama.init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(autouse=True)
def _disarm():
    faults.disarm()
    yield
    faults.disarm()


def _sequential(prompt, max_new):
    toks = llama.generate(
        PARAMS, jnp.asarray([prompt], jnp.int32), CFG, max_new=max_new
    )
    return [int(t) for t in np.asarray(toks)[0]]


def _paged_engine(**kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("block_size", 8)
    return ContinuousBatchingEngine(PARAMS, CFG, **kw)


# -- host-side allocator / prefix cache units --------------------------------


def test_block_allocator_basics():
    a = paged.BlockAllocator(5, 8)
    assert a.free_blocks == 4  # block 0 is scratch
    b1, b2 = a.alloc(), a.alloc()
    assert b1 == 1 and b2 == 2  # ascending, never scratch
    assert a.allocated_blocks == 2
    a.incref(b1)
    assert a.refcount(b1) == 2
    assert a.free(b1) is False  # one ref remains
    assert a.free(b1) is True  # back to the pool
    with pytest.raises(ValueError):
        a.free(b1)  # double free
    with pytest.raises(ValueError):
        a.incref(paged.SCRATCH)
    assert a.free(paged.SCRATCH) is False  # scratch no-op
    while a.alloc() is not None:
        pass
    assert a.free_blocks == 0  # exhaustion returns None, never raises


def test_chain_keys_and_blocks_for():
    toks = list(range(20))
    keys = paged.chain_keys(toks, 8)
    assert keys == [tuple(range(8)), tuple(range(16))]  # full blocks only
    assert paged.blocks_for(0, 8) == 0
    assert paged.blocks_for(1, 8) == 1
    assert paged.blocks_for(8, 8) == 1
    assert paged.blocks_for(9, 8) == 2


def test_prefix_cache_match_insert_evict():
    a = paged.BlockAllocator(8, 4)
    c = paged.PrefixCache(a)
    b1, b2 = a.alloc(), a.alloc()
    k = paged.chain_keys(list(range(8)), 4)
    c.insert(k[0], b1)
    c.insert(k[1], b2)
    assert a.refcount(b1) == 2  # cache holds its own ref
    assert c.match(list(range(8))) == [b1, b2]
    assert c.match(list(range(4)) + [99, 99, 99, 99]) == [b1]  # divergence
    assert c.match([7, 7, 7, 7]) == []
    # refcount-1 entries (cache-only) are evictable once callers free
    a.free(b1), a.free(b2)
    assert c.evictable() == 2
    assert c.evict_one() is True  # LRU first
    assert len(c) == 1 and a.free_blocks == 6
    assert c.evict_one() is True and c.evict_one() is False


# -- token identity vs the contiguous/sequential reference -------------------

PROMPTS = [list(range(2, 2 + n)) for n in (4, 7, 3, 9, 5, 6)]
MAX_NEWS = [6, 3, 13, 5, 7, 9]


@pytest.mark.parametrize("horizon", [1, 4, 16])
def test_paged_greedy_token_identity(horizon):
    """The tentpole acceptance contract: paged decode with mid-stream
    joins is token-identical to sequential generate at H in {1,4,16}."""
    eng = _paged_engine(horizon=horizon)
    for i in range(3):
        eng.submit(f"r{i}", PROMPTS[i], MAX_NEWS[i])
    eng.step()  # first block in flight
    for i in range(3, 6):  # join while a block is mid-pipeline
        eng.submit(f"r{i}", PROMPTS[i], MAX_NEWS[i])
    res = eng.run()
    assert set(res) == {f"r{i}" for i in range(6)}
    for i in range(6):
        assert res[f"r{i}"].tokens == _sequential(PROMPTS[i], MAX_NEWS[i]), (
            f"r{i} at horizon {horizon}"
        )
        assert res[f"r{i}"].outcome == "done"
    # every block went back to the pool once everything finished
    assert eng._balloc.allocated_blocks == 0


def test_paged_eos_mid_block():
    prompt = [5, 6, 7, 8]
    full = _sequential(prompt, 8)
    eos = full[2]  # mid-block at H=8
    eng = _paged_engine(max_slots=2, horizon=8)
    eng.submit("stops", prompt, 8, eos_id=eos)
    eng.submit("runs", [9, 10, 11], 6)
    res = eng.run()
    assert res["stops"].tokens == full[:3]
    assert res["stops"].outcome == "eos"
    assert res["runs"].tokens == _sequential([9, 10, 11], 6)


def test_paged_block_boundary_prompts():
    """Prompt lengths exactly at, one under, and one over a block
    boundary — the scatter/gather addressing edge cases."""
    cases = [(7, 9), (8, 8), (9, 7), (16, 5), (17, 4)]
    eng = _paged_engine(max_slots=2, block_size=8)
    for j, (plen, mn) in enumerate(cases):
        eng.submit(f"b{j}", list(range(2, 2 + plen)), mn)
    res = eng.run()
    for j, (plen, mn) in enumerate(cases):
        assert res[f"b{j}"].tokens == _sequential(
            list(range(2, 2 + plen)), mn
        ), f"prompt len {plen}"


def test_paged_deadline_evict_then_reuse():
    """Join/evict over the pool: a deadline eviction frees the slot's
    blocks mid-decode; a new request reuses the lane and pool without
    cross-request token leaks."""
    t = [0.0]
    eng = _paged_engine(max_slots=2, clock=lambda: t[0])
    eng.submit("slow", [1, 2, 3], 40, deadline_s=5.0)
    eng.submit("ok", [4, 5, 6], 4)
    for _ in range(3):
        eng.step()
    t[0] = 10.0  # past slow's deadline
    eng.step()
    eng.submit("next", [7, 8, 9, 10], 6)
    res = eng.run()
    assert res["slow"].outcome == "timeout"
    full = _sequential([1, 2, 3], 40)
    assert res["slow"].tokens == full[: len(res["slow"].tokens)]
    assert res["ok"].tokens == _sequential([4, 5, 6], 4)
    assert res["next"].tokens == _sequential([7, 8, 9, 10], 6)
    assert eng._balloc.allocated_blocks == 0


# -- prefix cache: shared blocks, CoW, skipped prefill ------------------------


def test_prefix_hit_skips_prefill_and_stays_identical():
    """A warm prefix-cache hit maps shared blocks instead of
    re-prefilling them: the dispatch counter proves the skip, the
    tokens prove correctness, and divergence past the shared prefix
    (different tails) stays isolated (copy-on-write territory)."""
    shared = list(range(2, 18))  # two full 8-blocks
    a = shared + [30, 31, 32]
    b = shared + [40, 41]
    eng = _paged_engine(max_slots=2, prefix_cache=True)
    eng.submit("a", a, 6)
    res = eng.run()
    assert res["a"].tokens == _sequential(a, 6)
    hits_before = eng._prefix.hits
    pf_before = eng.metrics.snapshot()["dispatches_prefill"]
    eng.submit("b", b, 6)
    res = eng.run()
    assert res["b"].tokens == _sequential(b, 6)
    assert eng._prefix.hits - hits_before == 2  # both shared blocks hit
    # exactly ONE prefill dispatch for b, covering only the tail — the
    # shared 16 tokens issued zero prefill work
    assert eng.metrics.snapshot()["dispatches_prefill"] - pf_before == 1


def test_full_prefix_hit_cow_divergence():
    """An IDENTICAL prompt (full-chain hit, length % block_size == 0)
    re-prefills only its last token into a copy-on-written block; both
    requests emit identical greedy streams and shared blocks survive
    for a third divergent request."""
    prompt = list(range(2, 26))  # 24 tokens = three full 8-blocks
    want = _sequential(prompt, 7)
    eng = _paged_engine(max_slots=3, prefix_cache=True)
    eng.submit("one", prompt, 7)
    res = eng.run()
    assert res["one"].tokens == want
    eng.submit("two", prompt, 7)  # full hit -> CoW of the last block
    eng.submit("three", prompt[:16] + [50] * 8, 5)  # diverges at block 2
    res = eng.run()
    assert res["two"].tokens == want
    assert res["three"].tokens == _sequential(prompt[:16] + [50] * 8, 5)
    assert eng._prefix.hits >= 5  # 3 (full) + 2 (partial)
    assert eng._balloc.allocated_blocks == len(eng._prefix)  # cache-only refs


def test_prefix_hit_counter_and_blocks_free_gauge():
    from edl_tpu.obs import memledger
    from edl_tpu.obs import metrics as obs_metrics

    reg = obs_metrics.reset_default_registry()
    memledger.reset_default_ledger(reg)
    eng = _paged_engine(max_slots=2, prefix_cache=True)
    prompt = list(range(2, 18))
    eng.submit("a", prompt, 4)
    eng.run()
    eng.submit("b", prompt + [60, 61], 4)
    eng.run()
    c = reg.get("edl_kv_prefix_hit_total")
    assert c is not None and c.value() >= 2
    g = reg.get("edl_kv_blocks_free")
    assert g is not None and g.value() > 0
    occ = reg.get("edl_kv_occupancy_ratio")
    assert occ is not None  # block-aware path exercised
    memledger.reset_default_ledger()


# -- chunked prefill ----------------------------------------------------------


def test_chunked_prefill_token_identity_and_interleave():
    """A long prompt admitted as bounded chunks: tokens identical, and
    the chunk dispatches interleave with decode blocks instead of one
    monolithic prefill (prefill dispatch count goes UP, per chunk)."""
    long_p = list(range(2, 42))  # 40 tokens, chunk=8 -> 4 chunks + tail
    short = [3, 4, 5]
    eng = _paged_engine(max_slots=2, prefill_chunk=8, horizon=2)
    eng.submit("short", short, 12)
    eng.step()
    eng.submit("long", long_p, 6)
    res = eng.run()
    assert res["long"].tokens == _sequential(long_p, 6)
    assert res["short"].tokens == _sequential(short, 12)
    snap = eng.metrics.snapshot()
    # short: 1; long: 4 chunks + 1 final piece
    assert snap["dispatches_prefill"] == 6


def test_chunked_prefill_recovery_replays_inline():
    faults.arm("serve.dispatch:raise@n=2", seed=0)
    eng = _paged_engine(max_slots=2, prefill_chunk=8, horizon=4)
    long_p = list(range(2, 30))
    eng.submit("long", long_p, 8)
    eng.submit("short", [9, 9, 2], 6)
    res = eng.run()
    faults.disarm()
    assert res["long"].tokens == _sequential(long_p, 8)
    assert res["short"].tokens == _sequential([9, 9, 2], 6)
    assert eng.recoveries >= 1


# -- pool pressure: block-gated admission + preemption ------------------------


def test_admission_gates_on_blocks_not_slots():
    """A pool smaller than max_slots' worth of sequences admits by
    free blocks: everything still completes token-identically, with
    head-of-line FIFO preserved through requeues."""
    # usable pool = 8 blocks of 8 = 64 tokens; max_len 64 means one
    # full-length sequence fits, concurrency comes from short ones
    eng = _paged_engine(max_slots=4, block_size=8, pool_blocks=9)
    for i in range(6):
        eng.submit(f"r{i}", PROMPTS[i], MAX_NEWS[i])
    res = eng.run()
    for i in range(6):
        assert res[f"r{i}"].tokens == _sequential(PROMPTS[i], MAX_NEWS[i]), (
            f"r{i} under pool pressure"
        )
    assert eng._balloc.allocated_blocks == 0


def test_preemption_restores_and_completes():
    """Decode growth under a tight pool preempts the youngest slot
    back to the queue; the preempted request restarts and both emit
    exact greedy streams."""
    eng = _paged_engine(max_slots=2, block_size=8, pool_blocks=9,
                        max_len=64)
    eng.submit("deep", [1, 2, 3, 4], 44)  # grows to 6 blocks
    eng.submit("young", list(range(5, 21)), 20)  # 2 blocks + growth
    res = eng.run()
    assert res["deep"].tokens == _sequential([1, 2, 3, 4], 44)
    assert res["young"].tokens == _sequential(list(range(5, 21)), 20)
    assert eng._balloc.allocated_blocks == 0


# -- crash recovery rebuilds pool + tables ------------------------------------


@pytest.mark.parametrize("plan", [
    "serve.dispatch:raise@n=3",
    "serve.drain:raise@n=2",
    "serve.prefill:raise@n=2",
])
def test_paged_recovery_token_identity(plan):
    faults.arm(plan, seed=0)
    eng = _paged_engine(horizon=8, max_recoveries=3, prefix_cache=True)
    for i in range(3):
        eng.submit(f"r{i}", PROMPTS[i], MAX_NEWS[i])
    eng.step()
    for i in range(3, 6):
        eng.submit(f"r{i}", PROMPTS[i], MAX_NEWS[i])
    res = eng.run()
    faults.disarm()
    for i in range(6):
        assert res[f"r{i}"].tokens == _sequential(PROMPTS[i], MAX_NEWS[i]), (
            f"r{i} under {plan}"
        )
    assert eng.recoveries >= 1
    # only the prefix cache's own refs remain once every slot freed
    assert eng._balloc.allocated_blocks == len(eng._prefix)


def test_recovery_rebuilds_consistent_tables():
    """After a crash the pool, allocator, and tables are rebuilt from
    host truth: live slots' tables cover exactly their resident tokens
    and reference only allocated blocks."""
    faults.arm("serve.dispatch:raise@n=2", seed=0)
    eng = _paged_engine(max_slots=2, horizon=4)
    eng.submit("a", PROMPTS[0], 20)
    eng.submit("b", PROMPTS[1], 20)
    for _ in range(3):
        eng.step()
    faults.disarm()
    assert eng.recoveries >= 1
    for i, sl in enumerate(eng._slots):
        if sl is None:
            continue
        resident = len(sl.prompt) + len(sl.generated)
        nb = paged.blocks_for(resident, eng.block_size)
        tbl = eng._tables[i]
        for j in range(nb):
            assert tbl[j] != paged.SCRATCH
            assert eng._balloc.refcount(tbl[j]) >= 1
    res = eng.run()
    assert res["a"].tokens == _sequential(PROMPTS[0], 20)
    assert res["b"].tokens == _sequential(PROMPTS[1], 20)


# -- donation + construction validation ---------------------------------------


# (the pool updated in place, the old buffers dead: a case of
# tests/test_serving.py::test_cache_updates_in_place_and_old_buffers_die)


def test_paged_constructor_validation():
    with pytest.raises(ValueError, match="multiple"):
        ContinuousBatchingEngine(PARAMS, CFG, max_len=60, block_size=8)
    with pytest.raises(ValueError, match="pool_blocks"):
        ContinuousBatchingEngine(
            PARAMS, CFG, max_len=64, block_size=8, pool_blocks=4
        )
    with pytest.raises(ValueError, match="block_size"):
        ContinuousBatchingEngine(PARAMS, CFG, max_len=64, prefix_cache=True)
    with pytest.raises(ValueError, match="block_size"):
        ContinuousBatchingEngine(PARAMS, CFG, max_len=64, prefill_chunk=8)
