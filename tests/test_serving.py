"""Continuous-batching serving engine (edl_tpu/serving/).

The correctness contract: batched slot-table decode is TOKEN-IDENTICAL
to sequential ``llama.generate`` under greedy decoding, for any
membership history — including requests admitted while others are
mid-decode and evicted while others continue. Plus: admission control,
serving metrics through the collector plumbing, and the `edl serve`
CLI consumer.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.models import llama
from edl_tpu.monitor.collector import Collector, ServingSource
from edl_tpu.obs import events as flight
from edl_tpu.runtime.export import export_params
from edl_tpu.serving.engine import ContinuousBatchingEngine
from edl_tpu.serving.metrics import ServingMetrics
from edl_tpu.serving.scheduler import (
    AdmissionError,
    InterleavePolicy,
    Request,
    RequestQueue,
)

CFG = llama.LlamaConfig.tiny()
PARAMS = llama.init_params(jax.random.PRNGKey(0), CFG)


def _sequential(prompt, max_new, params=PARAMS, cfg=CFG):
    toks = llama.generate(
        params, jnp.asarray([prompt], jnp.int32), cfg, max_new=max_new
    )
    return [int(t) for t in np.asarray(toks)[0]]


# -- engine correctness ------------------------------------------------------


@pytest.mark.parametrize("horizon", [1, 4, 16])
def test_horizon_greedy_token_identity(horizon):
    """The fused-horizon acceptance contract: H decode steps per
    dispatch (per-slot termination ON DEVICE) emit exactly sequential
    ``generate``'s tokens — at H=1 (the classic per-token iteration),
    H=4 and H=16, with budgets deliberately NOT divisible by H and
    requests joining mid-stream so admission lands on block
    boundaries while other slots are mid-block."""
    prompts = [list(range(2, 2 + n)) for n in (4, 7, 3, 9, 5, 6)]
    max_news = [6, 3, 13, 5, 7, 9]  # none divisible by 4 or 16
    eng = ContinuousBatchingEngine(
        PARAMS, CFG, max_slots=3, max_len=64, horizon=horizon
    )
    for i in range(3):
        eng.submit(f"r{i}", prompts[i], max_news[i])
    eng.step()  # first block in flight
    for i in range(3, 6):  # join while a block is mid-pipeline
        eng.submit(f"r{i}", prompts[i], max_news[i])
    res = eng.run()
    assert set(res) == {f"r{i}" for i in range(6)}
    for i in range(6):
        assert res[f"r{i}"].tokens == _sequential(prompts[i], max_news[i]), (
            f"r{i} at horizon {horizon}"
        )
        assert res[f"r{i}"].outcome == "done"


def test_horizon_eos_mid_block():
    """EOS hit in the MIDDLE of a fused block freezes the row on
    device: the EOS token is the last emitted (outcome "eos"), later
    lanes of the block emit nothing, and slot-mates decode through the
    same block unaffected."""
    prompt = [5, 6, 7, 8]
    full = _sequential(prompt, 8)
    eos = full[2]  # 3rd token of an 8-budget request: mid-block at H=8
    eng = ContinuousBatchingEngine(PARAMS, CFG, max_slots=2, max_len=64,
                                   horizon=8)
    eng.submit("stops", prompt, 8, eos_id=eos)
    eng.submit("runs", [9, 10, 11], 6)
    res = eng.run()
    assert res["stops"].tokens == full[:3]
    assert res["stops"].outcome == "eos"
    assert res["runs"].tokens == _sequential([9, 10, 11], 6)
    assert res["runs"].outcome == "done"


def test_horizon_dispatch_amortization():
    """The perf contract behind the fused loop: decode-heavy traffic
    at H=8 runs >= 4x fewer device dispatches per generated token than
    H=1 (the regression the exp_serving --dryrun CI lane also pins)."""
    prompts = [[2, 3, 4], [5, 6], [7, 8, 9, 10]]
    dpt = {}
    for h in (1, 8):
        eng = ContinuousBatchingEngine(
            PARAMS, CFG, max_slots=3, max_len=64, horizon=h
        )
        for i, p in enumerate(prompts):
            eng.submit(f"r{i}", p, 40 + i)  # deep budgets: decode-bound
        eng.run()
        snap = eng.metrics.snapshot()
        assert snap["tokens_out"] == sum(40 + i for i in range(3))
        dpt[h] = snap["dispatches_per_token"]
        assert snap["dispatches_prefill"] == 3
    assert dpt[1] / dpt[8] >= 4.0, dpt


def test_paged_engine_matches_contiguous_engine():
    """Cross-engine parity: the block-table paged cache (block_size>0)
    and the contiguous per-slot cache serve the SAME workload to
    byte-identical greedy tokens — mid-stream joins included. The
    paged engine's own coverage lives in tests/test_paged_kv.py;
    this pins the two engine modes against EACH OTHER."""
    prompts = [list(range(2, 2 + n)) for n in (4, 9, 3, 7)]
    max_news = [6, 5, 11, 8]
    results = {}
    for mode, kw in (
        ("contiguous", {}),
        ("paged", {"block_size": 8, "prefix_cache": True}),
    ):
        eng = ContinuousBatchingEngine(
            PARAMS, CFG, max_slots=2, max_len=64, horizon=4, **kw
        )
        eng.submit("r0", prompts[0], max_news[0])
        eng.submit("r1", prompts[1], max_news[1])
        eng.step()
        eng.submit("r2", prompts[2], max_news[2])
        eng.submit("r3", prompts[3], max_news[3])
        results[mode] = {
            rid: r.tokens for rid, r in eng.run().items()
        }
    assert results["paged"] == results["contiguous"]
    for i in range(4):
        assert results["paged"][f"r{i}"] == _sequential(
            prompts[i], max_news[i]
        )


# every KV layout the engine serves, and the arrays its cache tuple holds
LAYOUTS = {
    "contiguous": (dict(), 2),
    "paged": (dict(block_size=8), 2),
    "paged-int8": (dict(block_size=8, kv_quant="int8"), 4),
    "paged-int4": (dict(block_size=8, kv_quant="int4"), 4),
}


@pytest.mark.parametrize("layout, kw", [
    ("contiguous", dict(horizon=4)),
    ("paged", dict(horizon=4)),
    ("paged-int8", dict(horizon=4)),
    ("paged-int4", dict(horizon=4)),
    ("contiguous", dict(horizon=1, spec_k=4)),  # the verify dispatch
], ids=["contiguous", "paged", "paged-int8", "paged-int4", "verify"])
def test_cache_updates_in_place_and_old_buffers_die(layout, kw):
    """The stale-buffer invariant, in every layout: every dispatch
    donates the whole cache (and the slot-state vectors), so
    pre-dispatch references are DEAD — a second use raises from jax,
    and the engine's own invariant saw the buffers consumed (in-place
    update, no per-step cache copy). The verify dispatch keeps the
    same chain as the block program."""
    layout_kw, arity = LAYOUTS[layout]
    eng = ContinuousBatchingEngine(PARAMS, CFG, max_slots=2, max_len=64,
                                   **layout_kw, **kw)
    prompt = [5, 6, 7, 5, 6, 7, 5, 6, 7, 5, 6]  # repeats: drafts land
    eng.submit("a", prompt, 12)
    for _ in range(2):  # prefill + first block, then one more dispatch
        old = eng._cache
        ptrs = [c.unsafe_buffer_pointer() for c in old]
        eng.step()
        assert eng._donates is True  # CPU/TPU backends donate
        assert len(old) == len(eng._cache) == arity
        assert all(c.is_deleted() for c in old)
        with pytest.raises(RuntimeError, match="deleted"):
            np.asarray(old[0])
        # buffer identity: the live cache occupies the ORIGINAL
        # buffers' memory — the update chain is genuinely in place, no
        # per-dispatch cache allocation + copy
        assert [c.unsafe_buffer_pointer() for c in eng._cache] == ptrs
    # the live handles still serve: the engine never touches the dead
    # references, and the request completes (token-identically where
    # the cache is not quantized)
    res = eng.run()
    if arity == 2:
        assert res["a"].tokens == _sequential(prompt, 12)
    else:
        assert 0 < len(res["a"].tokens) <= 12
    if "spec_k" in kw:
        assert eng.metrics.snapshot()["dispatches_verify"] >= 1


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_every_kind_of_dispatch_donates_the_whole_cache_tuple(layout):
    """One cache tuple in every layout: after a decode block, a final
    prefill piece, a prefill chunk and a block copy (the last two exist
    under paging only), every array the cache held before is dead and
    the cache has the layout's arity — the scale planes of a quantized
    pool ride in the tuple, not beside it."""
    from edl_tpu.serving.engine import _Slot

    layout_kw, arity = LAYOUTS[layout]
    paged = "block_size" in layout_kw
    if paged:
        layout_kw = dict(layout_kw, prefill_chunk=8, prefix_cache=True)
    eng = ContinuousBatchingEngine(PARAMS, CFG, max_slots=2, max_len=64,
                                   horizon=2, **layout_kw)
    assert len(eng._cache) == arity
    seq = list(range(2, 26))  # three blocks of 8

    def dispatched(fn):
        old = eng._cache
        out = fn()
        assert all(c.is_deleted() for c in old), fn
        assert len(eng._cache) == arity
        assert not any(c.is_deleted() for c in eng._cache)
        return out

    if paged:
        start = eng._pg_setup_table(0, seq)
        dispatched(lambda: eng._dispatch_prefill_chunk(0, seq, start))
        tok0 = dispatched(lambda: eng._dispatch_prefill_final(
            0, seq, start + 8, 4, None))
        # a second reference makes the slot's first block shared: the
        # copy-on-write copies it in every array of the cache
        shared = eng._tables[0][0]
        eng._balloc.incref(shared)
        dispatched(lambda: eng._pg_make_writable(0, 0))
        assert eng._tables[0][0] != shared
        eng._balloc.free(shared)
    else:
        tok0 = dispatched(lambda: eng._prefill_into(0, seq, 4, None))
    eng._slots[0] = _Slot(rid="a", prompt=seq, max_new=4, eos_id=None,
                          generated=[tok0])
    dispatched(eng._dispatch_block)
    assert eng._drain_all() == 2
    if arity == 2:
        assert [tok0] + eng._slots[0].generated[1:] == _sequential(seq, 3)
    snap = eng.metrics.snapshot()
    assert snap["dispatches_prefill"] == (2 if paged else 1)
    assert snap["dispatches_decode"] == 1


def test_program_cache_lru_keeps_hot_entry():
    """Satellite: the module-level program caches evict the OLDEST
    entry at the cap instead of clearing everything (which dropped the
    hot decode program mid-traffic)."""
    from edl_tpu.serving import engine as eng_mod

    # engine program cache: oldest evicted, touched entry survives
    saved = eng_mod._programs.copy()
    try:
        eng_mod._programs.clear()
        for i in range(eng_mod._PROGRAM_CAP):
            eng_mod._memo(("fake", i), lambda: i)
        eng_mod._memo(("fake", 0), lambda: "miss")  # touch: now MRU
        eng_mod._memo(("fresh",), lambda: "new")  # evicts ("fake", 1)
        assert ("fake", 0) in eng_mod._programs
        assert ("fake", 1) not in eng_mod._programs
        assert ("fresh",) in eng_mod._programs
        assert len(eng_mod._programs) == eng_mod._PROGRAM_CAP
    finally:
        eng_mod._programs.clear()
        eng_mod._programs.update(saved)

    # llama generate cache: same policy
    saved = llama._generate_programs.copy()
    try:
        llama._generate_programs.clear()
        for i in range(llama._GENERATE_PROGRAM_CAP):
            llama._generate_programs[("fake", i)] = i
        llama.generate(
            PARAMS, jnp.asarray([[1, 2]], jnp.int32), CFG, max_new=2
        )
        assert len(llama._generate_programs) == llama._GENERATE_PROGRAM_CAP
        assert ("fake", 0) not in llama._generate_programs  # oldest out
        assert ("fake", 1) in llama._generate_programs  # rest intact
        real = [k for k in llama._generate_programs if k[0] != "fake"]
        assert len(real) == 1
        # a hit moves the real program to MRU — it survives the next
        # eviction instead of being the oldest casualty of a clear
        llama._generate_programs.move_to_end(real[0], last=False)
        llama.generate(
            PARAMS, jnp.asarray([[1, 2]], jnp.int32), CFG, max_new=2
        )
        assert next(reversed(llama._generate_programs)) == real[0]
    finally:
        llama._generate_programs.clear()
        llama._generate_programs.update(saved)


def test_batched_greedy_token_identical_with_midstream_join_evict():
    """The acceptance contract: a mixed-length prompt set served
    through 3 slots — with half the requests submitted only after
    others are mid-decode (join) and short-budget requests finishing
    while long ones continue (evict) — produces exactly sequential
    ``generate``'s tokens for every request."""
    prompts = [list(range(2, 2 + n)) for n in (4, 7, 3, 9, 5, 6, 8, 4)]
    max_news = [6, 3, 8, 5, 7, 2, 4, 8]  # mixed: evictions interleave
    eng = ContinuousBatchingEngine(PARAMS, CFG, max_slots=3, max_len=64)
    for i in range(4):
        eng.submit(f"r{i}", prompts[i], max_news[i])
    for _ in range(3):  # one admission per step: r0..r2 in, r3 queued
        eng.step()
    assert eng.active_slots >= 2 and eng.queue.depth >= 1
    for i in range(4, 8):  # join mid-stream
        eng.submit(f"r{i}", prompts[i], max_news[i])
    res = eng.run()
    assert set(res) == {f"r{i}" for i in range(8)}
    for i in range(8):
        got = res[f"r{i}"].tokens
        assert got == _sequential(prompts[i], max_news[i]), f"r{i}"
        assert res[f"r{i}"].outcome == "done"


def test_engine_eos_eviction():
    """A request stops at its EOS token (included in the output,
    outcome "eos") while slot-mates keep decoding to budget."""
    prompt = [5, 6, 7, 8]
    full = _sequential(prompt, 8)
    eos = full[2]  # greedy emits this 3rd — decode must stop there
    eng = ContinuousBatchingEngine(PARAMS, CFG, max_slots=2, max_len=64)
    eng.submit("stops", prompt, 8, eos_id=eos)
    eng.submit("runs", [9, 10, 11], 6)
    res = eng.run()
    assert res["stops"].tokens == full[:3]
    assert res["stops"].outcome == "eos"
    assert res["runs"].tokens == _sequential([9, 10, 11], 6)
    assert res["runs"].outcome == "done"


def test_engine_single_token_budget_and_slot_reuse():
    """max_new=1 completes at prefill (never occupies a decode step)
    and its slot is immediately reusable; the cache row left by a
    previous occupant never leaks into the next request's tokens."""
    eng = ContinuousBatchingEngine(PARAMS, CFG, max_slots=1, max_len=64)
    for i, (n, mn) in enumerate([(9, 7), (3, 1), (6, 5)]):
        prompt = list(range(1, 1 + n))
        eng.submit(f"r{i}", prompt, mn)
    res = eng.run()
    assert res["r1"].tokens == _sequential(list(range(1, 4)), 1)
    for i, (n, mn) in enumerate([(9, 7), (3, 1), (6, 5)]):
        assert res[f"r{i}"].tokens == _sequential(list(range(1, 1 + n)), mn)


def test_engine_drain_half_close_pins_admission():
    """Graceful drain (the fleet's drain-before-evict primitive):
    after ``half_close()`` no queued request is admitted — not one
    token is generated for them — while in-flight requests run to
    their full budget token-identically; ``drain()`` then hands the
    queued residuals back intact (order and fields preserved), and
    ``reopen()`` restores admission."""
    eng = ContinuousBatchingEngine(PARAMS, CFG, max_slots=2, max_len=64)
    eng.submit("in0", [1, 2, 3, 4], 6)
    eng.submit("in1", [5, 6, 7], 5)
    eng.step()  # admits in0 (one prefill per step)
    eng.step()  # admits in1 — both in flight now
    # these land in the queue behind a full slot table
    eng.submit("q0", [8, 9, 10], 4)
    eng.submit("q1", [11, 12, 13, 14], 3)
    assert eng.queue.depth == 2
    residual = eng.drain()
    # in-flight finished exactly as without the drain
    assert eng.results["in0"].tokens == _sequential([1, 2, 3, 4], 6)
    assert eng.results["in1"].tokens == _sequential([5, 6, 7], 5)
    assert eng.results["in0"].outcome == "done"
    # queued requests: zero tokens generated, residuals intact
    assert [r.rid for r in residual] == ["q0", "q1"]
    assert residual[0].prompt == [8, 9, 10]
    assert residual[0].max_new == 4
    assert residual[1].prompt == [11, 12, 13, 14]
    assert "q0" not in eng.results and "q1" not in eng.results
    assert eng.queue.depth == 0 and eng.active_slots == 0
    assert eng.draining and not eng.has_work
    # a half-closed engine refuses no submits (admission control is
    # the queue's job) but never starts them
    eng.submit("late", [2, 3], 2)
    eng.step()
    assert eng.active_slots == 0 and eng.queue.depth == 1
    # reopen: the engine serves again, token-identically
    eng.reopen()
    res = eng.run()
    assert res["late"].tokens == _sequential([2, 3], 2)
    ev_kinds = [r["kind"] for r in flight.default_recorder().records()]
    assert "serve.halfclose" in ev_kinds and "serve.drained" in ev_kinds


def test_engine_int8_records_compose():
    """The engine serves the weight-only int8 records unchanged
    (`edl serve --int8`): batched greedy tokens == sequential generate
    through the same records."""
    qp = jax.jit(llama.quantize_params_int8)(PARAMS)
    prompts = [[1, 2, 3, 4, 5], [7, 8, 9], [11, 12, 13, 14]]
    eng = ContinuousBatchingEngine(qp, CFG, max_slots=2, max_len=64)
    for i, p in enumerate(prompts):
        eng.submit(f"q{i}", p, 5)
    res = eng.run()
    for i, p in enumerate(prompts):
        assert res[f"q{i}"].tokens == _sequential(p, 5, params=qp)


def test_engine_sharded_params_compose(tmp_path, cpu_devices):
    """The engine serves a sharded export (`edl serve --mesh`): params
    loaded onto a tp×fsdp mesh decode token-identically."""
    from edl_tpu.parallel.mesh import MeshPlan
    from edl_tpu.runtime.export import load_export_sharded

    export_params(
        str(tmp_path), PARAMS, step=1, dtype="float32",
        model_meta=CFG.to_meta(),
    )
    plan = MeshPlan.parse("tp=2,fsdp=2,dp", 8)
    loaded, _ = load_export_sharded(
        str(tmp_path), plan.build(), llama.param_pspecs(CFG, plan)
    )
    eng = ContinuousBatchingEngine(loaded, CFG, max_slots=2, max_len=32)
    eng.submit("a", [1, 2, 3, 4], 5)
    eng.submit("b", [5, 6, 7], 4)
    res = eng.run()
    assert res["a"].tokens == _sequential([1, 2, 3, 4], 5)
    assert res["b"].tokens == _sequential([5, 6, 7], 4)


def test_engine_sampling_shape_and_determinism():
    """Temperature sampling: deterministic under a fixed seed, tokens
    in-vocab, EOS/budget still honored."""
    runs = []
    for _ in range(2):
        eng = ContinuousBatchingEngine(
            PARAMS, CFG, max_slots=2, max_len=64, temperature=0.9, seed=11
        )
        eng.submit("s0", [1, 2, 3], 6)
        eng.submit("s1", [4, 5, 6, 7], 4)
        res = eng.run()
        runs.append({k: v.tokens for k, v in res.items()})
    assert runs[0] == runs[1]
    assert len(runs[0]["s0"]) == 6 and len(runs[0]["s1"]) == 4
    assert all(0 <= t < CFG.vocab for ts in runs[0].values() for t in ts)


# -- scheduler / admission control ------------------------------------------


def test_queue_admission_reasons():
    q = RequestQueue(max_total_len=32, max_depth=2, max_prompt_len=8,
                     max_new_cap=10)
    q.submit(Request("ok", [1, 2, 3], 4))
    with pytest.raises(AdmissionError) as e:
        q.submit(Request("long", list(range(9)), 4))
    assert e.value.reason == "prompt_too_long"
    with pytest.raises(AdmissionError) as e:
        q.submit(Request("cap", [1], 11))
    assert e.value.reason == "budget"
    with pytest.raises(AdmissionError) as e:
        q.submit(Request("slot", [1, 2, 3, 4, 5], 28))  # 5+28 > 32
    assert e.value.reason == "budget"
    with pytest.raises(AdmissionError) as e:
        q.submit(Request("empty", [], 4))
    assert e.value.reason == "bad_request"
    q.submit(Request("fill", [1], 4))
    with pytest.raises(AdmissionError) as e:
        q.submit(Request("over", [1], 4))
    assert e.value.reason == "queue_full"
    assert q.depth == 2
    assert q.pop().rid == "ok"  # FIFO


def test_engine_submit_rejections_counted():
    """Engine-level admission: vocab bounds and duplicate ids reject
    with typed reasons, and the metrics counters see every rejection."""
    eng = ContinuousBatchingEngine(PARAMS, CFG, max_slots=1, max_len=16)
    eng.submit("a", [1, 2], 3)
    with pytest.raises(AdmissionError) as e:
        eng.submit("bad", [1, CFG.vocab + 5], 3)
    assert e.value.reason == "bad_request"
    with pytest.raises(AdmissionError) as e:
        eng.submit("huge", [1, 2, 3], 99)  # 3+99 > 16
    assert e.value.reason == "budget"
    eng.run()
    with pytest.raises(AdmissionError) as e:
        eng.submit("a", [1, 2], 3)  # id already completed
    assert e.value.reason == "bad_request"
    snap = eng.metrics.snapshot()
    assert snap["submitted"] == 4
    assert snap["admitted"] == 1
    assert snap["rejected"] == 3
    assert snap["rejected_bad_request"] == 2
    assert snap["rejected_budget"] == 1


def test_interleave_policy_budget():
    p = InterleavePolicy(prefills_per_step=2)
    assert p.budget(free_slots=3, queue_depth=5) == 2
    assert p.budget(free_slots=1, queue_depth=5) == 1
    assert p.budget(free_slots=3, queue_depth=0) == 0
    # at most one prefill per step by default (decode must not starve)
    assert InterleavePolicy().budget(4, 4) == 1


def test_interleave_policy_block_budget():
    """Admission lands on block boundaries under a fused horizon: one
    boundary admits what H per-step boundaries would have, still
    capped by free slots and queue depth."""
    p = InterleavePolicy()
    assert p.block_budget(free_slots=8, queue_depth=9, horizon=4) == 4
    assert p.block_budget(free_slots=2, queue_depth=9, horizon=4) == 2
    assert p.block_budget(free_slots=8, queue_depth=1, horizon=4) == 1
    assert p.block_budget(free_slots=8, queue_depth=0, horizon=4) == 0
    # H=1 degenerates to the per-step budget exactly
    assert p.block_budget(4, 4, 1) == p.budget(4, 4) == 1
    assert InterleavePolicy(prefills_per_step=2).block_budget(8, 9, 4) == 8


# -- timeout accounting (the ISSUE-5 double-count audit) ---------------------


def test_timeout_shed_counts_once_as_rejected_never_completed():
    """A queued request shed at pop counts exactly ONCE, as
    rejected:timeout — never through on_finish, so `completed` and the
    outcome counter stay untouched (the shed request was never
    admitted)."""
    from edl_tpu.obs.metrics import MetricsRegistry

    t = [0.0]
    eng = ContinuousBatchingEngine(
        PARAMS, CFG, max_slots=1, max_len=64, clock=lambda: t[0],
        metrics=ServingMetrics(clock=lambda: t[0],
                               registry=MetricsRegistry()),
    )
    eng.submit("busy", [1, 2, 3], 6)  # occupies the only slot
    eng.submit("stale", [4, 5, 6], 4, deadline_s=5.0)  # waits in queue
    t[0] = 10.0  # deadline passes while queued
    res = eng.run()
    assert res["stale"].outcome == "timeout" and res["stale"].tokens == []
    assert res["busy"].outcome == "done"
    m = eng.metrics
    assert m.rejected == {"timeout": 1}
    # exactly once: completed counts ONLY the admitted request, and the
    # outcome counter has no timeout entry (no on_finish for the shed)
    assert m.completed == 1
    assert m.outcomes == {"done": 1}
    snap = m.snapshot()
    assert snap["rejected_timeout"] == 1
    assert "outcome_timeout" not in snap
    # the registry twin agrees: 2 submitted, 1 rejected, 1 completed
    assert m._m_requests.value(event="submitted") == 2
    assert m._m_requests.value(event="rejected") == 1
    assert m._m_requests.value(event="completed") == 1


def test_timeout_eviction_counts_once_as_completed_never_rejected():
    """An in-flight slot past its deadline counts exactly ONCE, as
    completed{outcome=timeout} — never as a rejection — and keeps the
    tokens drained so far."""
    from edl_tpu.obs.metrics import MetricsRegistry

    t = [0.0]
    eng = ContinuousBatchingEngine(
        PARAMS, CFG, max_slots=2, max_len=64, clock=lambda: t[0],
        metrics=ServingMetrics(clock=lambda: t[0],
                               registry=MetricsRegistry()),
    )
    eng.submit("slow", [1, 2, 3], 40, deadline_s=5.0)
    eng.submit("ok", [4, 5, 6], 4)
    for _ in range(3):
        eng.step()
    t[0] = 10.0  # slow's deadline passes mid-flight
    res = eng.run()
    assert res["slow"].outcome == "timeout"
    assert 0 < len(res["slow"].tokens) < 40  # partial tokens kept
    assert res["ok"].outcome == "done"
    m = eng.metrics
    assert m.rejected == {}  # never rejected:timeout for the evicted path
    assert m.outcomes["timeout"] == 1 and m.completed == 2
    assert m._m_requests.value(event="rejected") == 0
    assert m._m_requests.value(event="completed") == 2


def test_timeout_evicted_slot_reuse_leaks_no_stale_tokens():
    """The audit's correctness half: a deadline eviction is host-only
    (the device row keeps decoding), so a block dispatched BEFORE the
    eviction still carries the old request's tokens in that lane. The
    engine must drain those blocks before reusing the slot — the new
    occupant's output stays token-identical to sequential generate."""
    t = [0.0]
    eng = ContinuousBatchingEngine(
        PARAMS, CFG, max_slots=1, max_len=64, horizon=4,
        clock=lambda: t[0],
    )
    eng.submit("old", [1, 2, 3], 20, deadline_s=5.0)
    eng.step()  # old admitted; one horizon-4 block left in flight
    assert eng._inflight
    t[0] = 10.0  # old's deadline passes with the block undrained
    eng.submit("new", [4, 5, 6], 6)
    res = eng.run()
    assert res["old"].outcome == "timeout"
    assert res["new"].outcome == "done"
    assert res["new"].tokens == _sequential([4, 5, 6], 6)
    # accounting stayed exactly-once through the reuse
    m = eng.metrics
    assert m.outcomes == {"timeout": 1, "done": 1}
    assert m.completed == 2 and m.rejected == {}


# -- metrics + collector plumbing -------------------------------------------


def test_metrics_ttft_and_throughput_deterministic_clock():
    t = [0.0]
    clock = lambda: t[0]  # noqa: E731
    m = ServingMetrics(clock=clock)
    m.on_submit("a")
    t[0] = 1.0
    m.on_admit("a", prompt_len=4)
    m.on_token("a")  # first token at 1.0 -> TTFT 1.0
    t[0] = 3.0
    for _ in range(3):
        m.on_token("a")
    m.on_finish("a", "done")
    m.on_step(1, 4, 2)
    snap = m.snapshot()
    assert snap["ttft_avg_s"] == pytest.approx(1.0)
    assert snap["tokens_out"] == 4
    # busy window = first admit (1.0) .. last token (3.0) -> 2 tok/s
    assert snap["agg_tokens_per_s"] == pytest.approx(2.0)
    assert snap["queue_depth"] == 2
    assert snap["slot_occupancy"] == pytest.approx(0.25)
    st = m.request_stats("a")
    assert st["ttft_s"] == pytest.approx(1.0)
    assert st["outcome"] == "done"


def test_metrics_per_block_tokens_and_dispatches():
    """Per-block accounting: on_tokens(rid, n) lands n tokens with one
    clock read; dispatch counters feed dispatches_per_token; TTFT is
    stamped by the admission-time on_token, NOT the block drain."""
    t = [0.0]
    m = ServingMetrics(clock=lambda: t[0])
    m.on_submit("a")
    t[0] = 1.0
    m.on_admit("a", prompt_len=4)
    m.on_dispatch("prefill")
    m.on_token("a")  # first token with the prefill: TTFT = 1.0
    t[0] = 9.0
    m.on_dispatch("decode")
    m.on_tokens("a", 8)  # one horizon-8 block drained at t=9
    m.on_finish("a", "done")
    snap = m.snapshot()
    assert snap["ttft_avg_s"] == pytest.approx(1.0)  # not 9.0
    assert snap["tokens_out"] == 9
    assert snap["dispatches_decode"] == 1
    assert snap["dispatches_prefill"] == 1
    assert snap["dispatches_per_token"] == pytest.approx(2 / 9)


def test_serving_source_through_collector():
    """Serving load rides the SAME collector plumbing as training load:
    ServingSource samples a live engine's metrics into MonitorSample
    and the render shows the SERVING block."""
    eng = ContinuousBatchingEngine(PARAMS, CFG, max_slots=2, max_len=32)
    col = Collector(ServingSource(eng.metrics), interval_s=0.0)
    eng.submit("a", [1, 2, 3], 4)
    eng.submit("b", [4, 5, 6, 7], 3)
    eng.run()
    s = col.poll()
    assert s.serving["admitted"] == 2
    assert s.serving["tokens_out"] == 7
    assert 0.0 < s.serving["slot_occupancy"] <= 1.0
    text = s.render()
    assert "SERVING:" in text and "tokens=7" in text
    # training-fleet samples keep their legacy render untouched
    from edl_tpu.monitor.collector import MonitorSample

    assert "SERVING" not in MonitorSample(ts=0.0).render()


# -- CLI + soak harness ------------------------------------------------------


def _env():
    return {
        **os.environ,
        "PYTHONPATH": os.path.dirname(os.path.dirname(__file__)),
        "JAX_PLATFORMS": "cpu",
    }


def test_cli_serve_jsonl(tmp_path):
    """`edl serve` end to end: JSONL feed in, JSONL completions out
    (submit order), admission rejections typed, metrics on stderr —
    and every completion token-identical to sequential generate."""
    export_params(
        str(tmp_path), PARAMS, step=1, dtype="float32",
        model_meta=CFG.to_meta(),
    )
    feed = tmp_path / "reqs.jsonl"
    feed.write_text(
        json.dumps({"id": "a", "prompt": [1, 2, 3, 4], "max_new": 5}) + "\n"
        + json.dumps({"prompt": [7, 8, 9], "max_new": 4}) + "\n"
        + json.dumps({"id": "big", "prompt": [1], "max_new": 500}) + "\n"
    )
    out = subprocess.run(
        [
            sys.executable, "-m", "edl_tpu.cli", "serve", str(tmp_path),
            "--requests", str(feed), "--max-slots", "2", "--max-len", "32",
            "--metrics-port", "0",
        ],
        capture_output=True, text=True, env=_env(),
    )
    assert out.returncode == 0, out.stderr
    recs = [json.loads(l) for l in out.stdout.strip().splitlines()]
    assert [r["id"] for r in recs] == ["a", "req-2", "big"]
    assert recs[0]["tokens"] == _sequential([1, 2, 3, 4], 5)
    assert recs[1]["tokens"] == _sequential([7, 8, 9], 4)
    assert recs[0]["outcome"] == "done" and recs[0]["ttft_s"] >= 0
    assert recs[2]["outcome"] == "rejected:budget"
    assert "SERVING:" in out.stderr and "rejected=1" in out.stderr
    # obs surface: --metrics-port announces the endpoint and the
    # histogram-backed percentiles render in the final SERVING block
    assert "# metrics endpoint http://127.0.0.1:" in out.stderr
    assert "latency: ttft p50/p95/p99=" in out.stderr


def test_cli_serve_stdin_and_flag_validation(tmp_path):
    export_params(
        str(tmp_path), PARAMS, step=1, dtype="float32",
        model_meta=CFG.to_meta(),
    )
    out = subprocess.run(
        [sys.executable, "-m", "edl_tpu.cli", "serve", str(tmp_path),
         "--max-new", "3"],
        input=json.dumps({"id": "x", "prompt": [2, 3]}) + "\n",
        capture_output=True, text=True, env=_env(),
    )
    assert out.returncode == 0, out.stderr
    (rec,) = [json.loads(l) for l in out.stdout.strip().splitlines()]
    assert rec["tokens"] == _sequential([2, 3], 3)

    # flag/feed mistakes fail BEFORE any export loads
    bad = subprocess.run(
        [sys.executable, "-m", "edl_tpu.cli", "serve",
         str(tmp_path / "nowhere"), "--requests", str(tmp_path / "missing")],
        capture_output=True, text=True, env=_env(),
    )
    assert bad.returncode == 1 and "bad request feed" in bad.stderr
    bad = subprocess.run(
        [sys.executable, "-m", "edl_tpu.cli", "serve", str(tmp_path),
         "--temperature", "-1"],
        input="", capture_output=True, text=True, env=_env(),
    )
    assert bad.returncode == 1 and "temperature" in bad.stderr
    both = subprocess.run(
        [sys.executable, "-m", "edl_tpu.cli", "serve", str(tmp_path),
         "--int8", "--mesh", "tp=2"],
        input='{"prompt": [1]}\n',
        capture_output=True, text=True, env=_env(),
    )
    assert both.returncode == 1 and "mutually exclusive" in both.stderr


def test_generate_rejects_top_flags_at_greedy():
    """Satellite (ADVICE r5): library callers get the CLI's signal —
    generate() raises when greedy decoding would silently ignore
    explicit top_k/top_p."""
    with pytest.raises(ValueError, match="temperature > 0"):
        llama.generate(
            PARAMS, jnp.asarray([[1, 2]], jnp.int32), CFG, max_new=2, top_k=5
        )
    with pytest.raises(ValueError, match="temperature > 0"):
        llama.generate(
            PARAMS, jnp.asarray([[1, 2]], jnp.int32), CFG, max_new=2,
            top_p=0.5,
        )


def test_crd_env_admits_list_form():
    """Satellite (ADVICE r5): the CRD spec.env schema admits BOTH forms
    the client parser accepts — the string mapping and the k8s
    container-style [{name, value}] list."""
    import pathlib

    import yaml

    crd_path = pathlib.Path(__file__).resolve().parent.parent / "deploy/crd.yaml"
    (crd,) = list(yaml.safe_load_all(crd_path.read_text()))
    (v1,) = [v for v in crd["spec"]["versions"] if v["name"] == "v1"]
    env = v1["schema"]["openAPIV3Schema"]["properties"]["spec"][
        "properties"]["env"]
    forms = env["anyOf"]
    types = {f["type"] for f in forms}
    assert types == {"object", "array"}
    (listform,) = [f for f in forms if f["type"] == "array"]
    assert listform["items"]["required"] == ["name"]
    assert set(listform["items"]["properties"]) == {"name", "value"}


@pytest.mark.slow
def test_exp_serving_soak_batched_beats_sequential():
    """The throughput acceptance: the soak harness's continuous engine
    strictly beats one-request-at-a-time serving on a >=8-request
    mixed-length workload (CPU dryrun)."""
    out = subprocess.run(
        [sys.executable, "scripts/exp_serving.py"],
        capture_output=True, text=True, env=_env(),
        cwd=os.path.dirname(os.path.dirname(__file__)),
    )
    assert out.returncode == 0, out.stderr
    assert "continuous-batching speedup" in out.stdout
    speedup = float(
        out.stdout.split("continuous-batching speedup: ")[1].split("x")[0]
    )
    assert speedup > 1.0, out.stdout
