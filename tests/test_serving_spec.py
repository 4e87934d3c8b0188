"""Fused speculative decoding (draft–verify in the horizon).

The correctness contract: with ``spec_k > 0`` the engine's greedy
output is TOKEN-IDENTICAL to sequential ``llama.generate`` for every
(K, horizon, contiguous/paged) configuration — acceptance and
rejection are invisible in the stream, only in the dispatch counts.
Plus: the host-side n-gram drafter and acceptance policy, the verify
program's donation contract, mid-verify EOS, speculation metrics, and
crash recovery mid-speculation (the recovery matrix itself lives in
tests/test_serving_recovery.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.models import llama
from edl_tpu.obs import events as flight
from edl_tpu.serving import spec
from edl_tpu.serving.engine import ContinuousBatchingEngine

CFG = llama.LlamaConfig.tiny()
PARAMS = llama.init_params(jax.random.PRNGKey(0), CFG)

# a prompt whose tail repeats: the n-gram drafter fires from the first
# decode step, and tiny()'s greedy continuations fall into repetitive
# attractors that keep acceptance going mid-stream
REPETITIVE = [1, 2, 3, 4, 1, 2, 3, 4, 1, 2]


def _sequential(prompt, max_new):
    toks = llama.generate(
        PARAMS, jnp.asarray([prompt], jnp.int32), CFG, max_new=max_new
    )
    return [int(t) for t in np.asarray(toks)[0]]


# -- drafter + policy (host-side, jax-free) ---------------------------------


def test_draft_ngram_prompt_lookup():
    """Suffix n-gram lookup: longest n first, MOST RECENT earlier
    occurrence wins, continuation truncated at the context end."""
    # trailing [3, 4] occurred twice; most recent match (ending at
    # index 6) continues with [5, 6]
    ctx = [1, 2, 3, 4, 9, 3, 4, 5, 6, 3, 4]
    assert spec.draft_ngram(ctx, ngram=2, max_draft=2) == [5, 6]
    assert spec.draft_ngram(ctx, ngram=2, max_draft=4) == [5, 6, 3, 4]
    # no repeated suffix at any n: no draft
    assert spec.draft_ngram([1, 2, 3, 4, 5], ngram=3, max_draft=4) == []
    # 1-gram fallback when no longer n-gram repeats
    assert spec.draft_ngram([7, 1, 8, 1], ngram=3, max_draft=2) == [8, 1]
    # degenerate contexts draft nothing
    assert spec.draft_ngram([], 3, 4) == []
    assert spec.draft_ngram([5], 3, 4) == []
    assert spec.draft_ngram([5, 5], 3, 0) == []


def test_spec_policy_warmup_then_disable():
    """Below ``warmup`` drafted tokens every request drafts; past it a
    request under ``min_accept`` is disabled permanently, and
    ``forget`` drops its counters."""
    pol = spec.SpecPolicy(min_accept=0.5, warmup=8)
    assert pol.should_draft("a")  # no data: draft
    pol.observe("a", drafted=4, accepted=0)
    assert pol.should_draft("a")  # 4 < warmup: still probing
    pol.observe("a", drafted=4, accepted=0)
    assert not pol.should_draft("a")  # 0/8 < 0.5: disabled
    pol.observe("b", drafted=16, accepted=12)
    assert pol.should_draft("b")  # 12/16 >= 0.5
    pol.forget("a")
    assert pol.should_draft("a")  # fresh request id: probe again
    # min_accept <= 0 never disables, whatever the history
    free = spec.SpecPolicy(min_accept=0.0, warmup=1)
    free.observe("c", drafted=100, accepted=0)
    assert free.should_draft("c")


def test_spec_engine_validation():
    with pytest.raises(ValueError, match="spec_k"):
        ContinuousBatchingEngine(PARAMS, CFG, spec_k=-1)
    with pytest.raises(ValueError, match="temperature"):
        ContinuousBatchingEngine(PARAMS, CFG, spec_k=2, temperature=0.7)
    with pytest.raises(ValueError, match="spec_ngram"):
        ContinuousBatchingEngine(PARAMS, CFG, spec_k=2, spec_ngram=0)


# -- token identity ----------------------------------------------------------


@pytest.mark.parametrize("paged", [False, True], ids=["contig", "paged"])
@pytest.mark.parametrize("horizon", [1, 4])
@pytest.mark.parametrize("spec_k", [2, 4, 8])
def test_spec_greedy_token_identity(spec_k, horizon, paged):
    """The speculation acceptance contract: for every draft width K,
    horizon, and cache layout, greedy tokens are exactly sequential
    ``generate``'s — for repetitive traffic (drafts accept), arbitrary
    traffic (drafts reject), and requests joining mid-stream while
    slot-mates are mid-speculation."""
    prompts = [list(REPETITIVE), [5, 6, 7, 8, 9, 10], [3] * 8]
    max_news = [17, 11, 13]  # not divisible by K or horizon
    kw = {"block_size": 8, "pool_blocks": 64} if paged else {}
    eng = ContinuousBatchingEngine(
        PARAMS, CFG, max_slots=2, max_len=96, horizon=horizon,
        spec_k=spec_k, spec_ngram=3, **kw,
    )
    eng.submit("r0", prompts[0], max_news[0])
    eng.submit("r1", prompts[1], max_news[1])
    eng.step()  # r2 joins while r0/r1 are mid-speculation
    eng.submit("r2", prompts[2], max_news[2])
    res = eng.run()
    for i in range(3):
        assert res[f"r{i}"].tokens == _sequential(prompts[i], max_news[i]), (
            f"r{i} diverged at spec_k={spec_k} h={horizon} paged={paged}"
        )
        assert res[f"r{i}"].outcome == "done"


def test_spec_midstream_join_evict_token_identity():
    """Short-budget requests finishing (evict) while long repetitive
    ones keep speculating, with late joins landing in freed slots —
    every stream still matches sequential."""
    prompts = [list(REPETITIVE), [9, 10], [4] * 6, list(REPETITIVE),
               [11, 12, 13], [2, 5, 2, 5, 2, 5]]
    max_news = [15, 2, 7, 9, 3, 11]
    eng = ContinuousBatchingEngine(
        PARAMS, CFG, max_slots=3, max_len=96, horizon=1, spec_k=4,
    )
    for i in range(4):
        eng.submit(f"r{i}", prompts[i], max_news[i])
    for _ in range(3):
        eng.step()
    for i in range(4, 6):
        eng.submit(f"r{i}", prompts[i], max_news[i])
    res = eng.run()
    assert set(res) == {f"r{i}" for i in range(6)}
    for i in range(6):
        assert res[f"r{i}"].tokens == _sequential(prompts[i], max_news[i]), (
            f"r{i}"
        )


def test_spec_mid_verify_eos():
    """EOS landing INSIDE an accepted run terminates the row
    mid-verify on device: the EOS token is the last emitted, later
    accepted lanes (and the bonus token) are discarded, and the
    outcome is "eos" — while a slot-mate speculates through the same
    dispatch unaffected."""
    full = _sequential(REPETITIVE, 20)
    # pick an EOS deep enough that speculation is mid-run when it hits
    eos = full[6]
    want = full[:full.index(eos) + 1]
    eng = ContinuousBatchingEngine(
        PARAMS, CFG, max_slots=2, max_len=96, horizon=1, spec_k=4,
    )
    eng.submit("stops", list(REPETITIVE), 20, eos_id=eos)
    eng.submit("runs", [3] * 8, 13)
    res = eng.run()
    assert res["stops"].tokens == want
    assert res["stops"].outcome == "eos"
    assert res["runs"].tokens == _sequential([3] * 8, 13)
    assert res["runs"].outcome == "done"


def test_spec_zero_acceptance_streak_stays_identical():
    """A stream whose drafts NEVER accept (policy disabled after
    warmup, sentinel lanes thereafter) still emits exactly sequential
    tokens — a rejected verify commits precisely one plain greedy
    step, and the disable flips nothing but dispatch shape."""
    prompt = list(range(20, 29))  # non-repetitive: drafter rarely right
    eng = ContinuousBatchingEngine(
        PARAMS, CFG, max_slots=1, max_len=96, horizon=1,
        spec_k=4, spec_min_accept=1.1, spec_ngram=3,
    )
    # min_accept > 1 disables every request the moment warmup ends —
    # the permanent-disable path, not just low acceptance
    eng._spec_policy.warmup = 4
    eng.submit("r0", prompt, 24)
    res = eng.run()
    assert res["r0"].tokens == _sequential(prompt, 24)
    snap = eng.metrics.snapshot()
    # the policy actually disabled drafting: drafting stopped at/near
    # warmup instead of riding the whole 24-token stream
    assert snap["spec_drafted"] <= 12


# -- donation ---------------------------------------------------------------


# (the verify dispatch keeps the in-place update chain: a case of
# tests/test_serving.py::test_cache_updates_in_place_and_old_buffers_die)


def test_spec_metrics_and_flight_events():
    """A repetitive stream drafts and accepts: the spec counters move,
    the snapshot exposes the acceptance rate, accepted tokens per
    decode-phase dispatch beats 1.0, and each drained verify block
    leaves a ``serve.verify`` flight event with the per-rid accepted
    run length."""
    from edl_tpu.obs.metrics import MetricsRegistry
    from edl_tpu.serving.metrics import ServingMetrics

    flight.reset_default_recorder()
    eng = ContinuousBatchingEngine(
        PARAMS, CFG, max_slots=2, max_len=96, horizon=1, spec_k=4,
        metrics=ServingMetrics(registry=MetricsRegistry()),
    )
    eng.submit("r0", list(REPETITIVE), 40)
    res = eng.run()
    assert res["r0"].tokens == _sequential(REPETITIVE, 40)
    snap = eng.metrics.snapshot()
    assert snap["spec_drafted"] > 0
    assert snap["spec_accepted"] > 0
    assert 0 < snap["spec_acceptance_rate"] <= 1.0
    assert snap["spec_acceptance_rate"] == pytest.approx(
        snap["spec_accepted"] / snap["spec_drafted"]
    )
    assert snap["dispatches_verify"] > 0
    # the point of the whole machinery: more than one token lands per
    # decode-phase dispatch on repetitive traffic
    decode_dispatches = snap["dispatches_verify"] + snap["dispatches_decode"]
    assert snap["tokens_out"] / decode_dispatches > 1.0
    evs = [
        r for r in flight.default_recorder().records()
        if r["kind"] == "serve.verify"
    ]
    assert evs, "no serve.verify flight events recorded"
    assert all(e["corr"]["rid"] == "r0" for e in evs)
    assert sum(e["attrs"]["accepted"] for e in evs) == snap["spec_accepted"]
    assert sum(e["attrs"]["drafted"] for e in evs) == snap["spec_drafted"]
    assert all(e["attrs"]["emitted"] >= e["attrs"]["accepted"] for e in evs)
    # the prometheus twins carry the same counts
    m = eng.metrics
    assert m._m_spec_drafted.value() == snap["spec_drafted"]
    assert m._m_spec_accepted.value() == snap["spec_accepted"]
    assert m._m_spec_rate.value() == pytest.approx(
        snap["spec_acceptance_rate"]
    )


def test_spec_disabled_is_zero_overhead():
    """``spec_k=0`` leaves the engine byte-for-byte on the horizon
    path: identical dispatch counts to an engine that never heard of
    speculation, zero verify dispatches, zero spec counters."""
    def counts(**kw):
        eng = ContinuousBatchingEngine(
            PARAMS, CFG, max_slots=2, max_len=64, horizon=4, **kw
        )
        eng.submit("a", [2, 3, 4], 9)
        eng.submit("b", [5, 6], 7)
        res = eng.run()
        return eng.metrics.snapshot(), {r: res[r].tokens for r in res}

    base_snap, base_toks = counts()
    off_snap, off_toks = counts(spec_k=0, spec_ngram=5, spec_min_accept=0.9)
    assert off_toks == base_toks
    for k in ("dispatches_decode", "dispatches_prefill",
              "dispatches_verify", "tokens_out", "dispatches_per_token"):
        assert off_snap[k] == base_snap[k], k
    assert off_snap["spec_drafted"] == 0
    assert off_snap["spec_acceptance_rate"] == 0.0


def test_spec_multi_token_drain_records_honest_itl():
    """Satellite: a verify drain landing k tokens at once goes through
    the SAME honest-tail ITL accounting as a horizon block — one full
    inter-drain gap + k-1 zeros, so p99 still sees the stall while
    count/sum match the per-token view (PR 6 convention)."""
    from edl_tpu.obs.metrics import MetricsRegistry
    from edl_tpu.serving.metrics import ServingMetrics

    t = [0.0]
    m = ServingMetrics(clock=lambda: t[0], registry=MetricsRegistry())
    m.on_submit("r")
    m.on_pop("r")
    m.on_admit("r", 4)
    t[0] = 1.0
    m.on_tokens("r", 1)       # first token: TTFT, no ITL yet
    t[0] = 1.5
    m.on_tokens("r", 4)       # verify drain lands 4 tokens
    st = m.itl_hist.stats()
    assert st["count"] == 4   # one gap + three zeros
    assert st["sum"] == pytest.approx(0.5)
    assert m.itl_hist.percentile(0.99) >= 0.25  # the stall shows at p99


def test_top_serving_strip_shows_acceptance():
    """`edl top` renders a spec line (live acceptance rate) only when
    the scraped engine actually drafted — quiet otherwise."""
    from edl_tpu.obs import metrics as obs_metrics
    from edl_tpu.obs.top import summarize

    r = obs_metrics.MetricsRegistry()
    r.counter("edl_serving_tokens_total", "").inc(40)
    r.counter("edl_serving_dispatch_total", "", ("kind",)).inc(
        10, kind="verify"
    )
    fams = obs_metrics.parse_prometheus_text(r.render())
    assert not any("spec accept" in l for l in summarize(fams))
    r.counter("edl_serving_spec_drafted_total", "").inc(32)
    r.counter("edl_serving_spec_accepted_total", "").inc(24)
    fams = obs_metrics.parse_prometheus_text(r.render())
    (line,) = [l for l in summarize(fams) if "spec accept" in l]
    assert "75.0%" in line and "drafted=32" in line and "accepted=24" in line
