"""Serving metrics — TTFT/ITL/TPOT latency histograms, the
queue-wait/prefill/block latency decomposition, tokens/s, queue
depth, slot occupancy, and tenant/SLO-class-labeled outcome counters.

The training side publishes load through ``monitor/collector.py`` so
the autoscaler can act on it; serving publishes through the SAME
plumbing (``monitor.collector.ServingSource`` wraps
:meth:`ServingMetrics.snapshot`), so a future autoscaler consumes
serving load exactly like training load. Additionally every hook
records into an :class:`~edl_tpu.obs.metrics.MetricsRegistry`
(default: the process-wide one), which is what the obs HTTP exporter
scrapes — ``edl_serving_ttft_seconds`` / ``edl_serving_itl_seconds``
histograms, dispatch/request counters, queue/slot gauges. Pure host
bookkeeping — the engine calls the ``on_*`` hooks from its step loop;
nothing here touches jax. ``clock`` is injectable so tests are
deterministic.
"""

from __future__ import annotations

import time
from collections import Counter
from dataclasses import dataclass
from typing import Dict, Optional

from edl_tpu.obs import metrics as obs_metrics

# sub-ms..minutes: TTFT on a loaded box can hit seconds (queue wait +
# prefill), ITL sits at sub-ms..tens of ms; the shared ladder keeps
# fleet merges exact
_LATENCY_BUCKETS = obs_metrics.DEFAULT_BUCKETS


@dataclass
class _ReqRecord:
    has_submit: bool = False  # submit_s is meaningful (0.0 is a valid time)
    submit_s: float = 0.0
    has_pop: bool = False  # pop_s is meaningful
    pop_s: float = 0.0  # queue pop (queue-wait ends, prefill begins)
    admit_s: float = 0.0
    first_token_s: float = 0.0
    last_token_s: float = 0.0
    finish_s: float = 0.0
    prompt_len: int = 0
    tokens: int = 0
    outcome: str = ""  # done | eos | rejected:<reason>
    tenant: str = ""  # multi-tenant attribution ("" = unattributed)
    slo_class: str = ""  # SLO class label ("" = unclassified)


class ServingMetrics:
    """Aggregates one engine's serving telemetry.

    Counters: submitted / admitted / rejected (by reason) / completed
    (by outcome) / tokens_out / dispatches (by kind — the fused-horizon
    engine's efficiency metric is dispatches per token). Gauges: queue
    depth, active slots, slot occupancy (mean active/max over decode
    steps). Latency: per-request TTFT (first generated token, which
    lands with the prefill, minus submit) and tokens/s; aggregate
    tokens/s over the busy window (first admission to last token);
    TTFT and inter-token-latency HISTOGRAMS with p50/p95/p99 in
    :meth:`snapshot` (obs fixed-bucket type, so the percentiles a
    scraper derives from /metrics match the snapshot's).

    Token accounting is PER-BLOCK under a fused decode horizon: the
    engine drains a block's [slots, H] token matrix in one go and
    reports each request's share via :meth:`on_tokens` (one clock
    read, n tokens). TTFT is NOT distorted by that batching — the
    first token always lands with the prefill at admission, which
    stays a synchronous :meth:`on_token`, so ``ttft_*`` measures
    prefill latency, never block-drain latency.

    **Honest tail ITL.** A drained block of n tokens lands as ONE
    observation of the FULL inter-drain gap plus n-1 zeros — the user
    actually waited the whole gap for the block's first token and got
    the rest in the same drain. (The old per-token-mean bucketing kept
    count and sum exact but hid every stall under the mean: at H=8 a
    400 ms freeze bucketed as 8×50 ms and p99 ITL never saw it.)
    Count and sum are unchanged, only the tail is truthful now. The
    amortization-proof per-request figure is **TPOT** —
    ``(finish − first token) / (tokens − 1)`` — observed once per
    finished request into ``edl_serving_tpot_seconds``.

    **Latency decomposition.** Each request's life splits into three
    exactly-adjacent phases the engine stamps separately:
    submit→pop (``edl_serving_queue_wait_seconds``, via
    :meth:`on_pop`), pop→first token (``edl_serving_prefill_seconds``,
    stamped when the first token lands), first token→finish (decode,
    derivable; per drained block the dispatch→drain wall time lands in
    ``edl_serving_block_seconds`` via :meth:`on_block`). The phases
    sum to finish−submit per request (the tests/test_loadgen.py
    invariant), so "TTFT regressed" decomposes into "queue grew" vs
    "prefill got slower" instead of one conflated number."""

    def __init__(
        self,
        clock=time.monotonic,
        registry: Optional[obs_metrics.MetricsRegistry] = None,
    ):
        self.clock = clock
        self.registry = registry or obs_metrics.default_registry()
        self.submitted = 0
        self.admitted = 0
        self.completed = 0
        self.tokens_out = 0
        self.recoveries = 0  # engine crash-recovery passes
        self.rejected: Counter = Counter()  # reason -> n
        self.outcomes: Counter = Counter()  # done/eos/timeout/failed -> n
        self.dispatches: Counter = Counter()  # decode/prefill -> n
        self.requests: Dict[str, _ReqRecord] = {}
        self._steps = 0
        self._active_slot_steps = 0
        self._max_slots = 0
        self._queue_depth = 0
        self._active_now = 0
        self._t_first_admit: Optional[float] = None
        self._t_last_token: Optional[float] = None
        r = self.registry
        self._m_requests = r.counter(
            "edl_serving_requests_total", "request lifecycle events", ("event",)
        )
        self._m_tokens = r.counter("edl_serving_tokens_total", "generated tokens")
        self._m_dispatch = r.counter(
            "edl_serving_dispatch_total", "device program dispatches", ("kind",)
        )
        self._m_recoveries = r.counter(
            "edl_serving_recoveries_total",
            "engine crash-recovery passes (device state rebuilt, live "
            "slots re-prefilled from prompt + generated)",
        )
        # terminal outcomes with tenant/SLO-class attribution — the
        # counter a postmortem reads to answer "which tenant got shed"
        self._m_outcomes = r.counter(
            "edl_serving_outcomes_total",
            "terminal request outcomes by tenant and SLO class",
            ("outcome", "tenant", "slo_class"),
        )
        # per-ENGINE histograms back the snapshot percentiles (several
        # engines may share the process registry; their union belongs
        # on /metrics, not in one engine's snapshot) …
        self.ttft_hist = obs_metrics.Histogram(
            "ttft_s", "per-engine TTFT", buckets=_LATENCY_BUCKETS
        )
        self.itl_hist = obs_metrics.Histogram(
            "itl_s", "per-engine ITL", buckets=_LATENCY_BUCKETS
        )
        self.tpot_hist = obs_metrics.Histogram(
            "tpot_s", "per-engine per-request TPOT", buckets=_LATENCY_BUCKETS
        )
        self.queue_wait_hist = obs_metrics.Histogram(
            "queue_wait_s", "per-engine queue wait", buckets=_LATENCY_BUCKETS
        )
        self.prefill_hist = obs_metrics.Histogram(
            "prefill_s", "per-engine prefill phase", buckets=_LATENCY_BUCKETS
        )
        self.block_hist = obs_metrics.Histogram(
            "block_s", "per-engine block dispatch->drain",
            buckets=_LATENCY_BUCKETS,
        )
        # … and the registry-resident twins are what the exporter
        # scrapes (identical bucket ladder, so the two views agree)
        self._r_ttft = r.histogram(
            "edl_serving_ttft_seconds",
            "time to first token (submit -> first token)",
            buckets=_LATENCY_BUCKETS,
        )
        self._r_itl = r.histogram(
            "edl_serving_itl_seconds",
            "inter-token latency (per generated token)",
            buckets=_LATENCY_BUCKETS,
        )
        self._r_tpot = r.histogram(
            "edl_serving_tpot_seconds",
            "user-perceived time per output token: (finish - first "
            "token) / (tokens - 1), once per finished request",
            buckets=_LATENCY_BUCKETS,
        )
        self._r_queue_wait = r.histogram(
            "edl_serving_queue_wait_seconds",
            "queue wait (submit -> scheduler pop)",
            buckets=_LATENCY_BUCKETS,
        )
        self._r_prefill = r.histogram(
            "edl_serving_prefill_seconds",
            "prefill phase (scheduler pop -> first token)",
            buckets=_LATENCY_BUCKETS,
        )
        self._r_block = r.histogram(
            "edl_serving_block_seconds",
            "fused decode block wall time (dispatch -> drain)",
            buckets=_LATENCY_BUCKETS,
        )
        # speculative decoding: drafted vs accepted draft tokens (the
        # acceptance-rate numerator/denominator) + a live-rate gauge.
        # Counters so fleet aggregation and PromQL rate() work; the
        # gauge is the at-a-glance figure `edl top` renders.
        self.spec_drafted = 0
        self.spec_accepted = 0
        self._m_spec_drafted = r.counter(
            "edl_serving_spec_drafted_total",
            "draft tokens proposed to verify dispatches",
        )
        self._m_spec_accepted = r.counter(
            "edl_serving_spec_accepted_total",
            "draft tokens accepted by greedy verification",
        )
        self._m_spec_rate = r.gauge(
            "edl_serving_spec_acceptance_rate",
            "cumulative accepted/drafted ratio of speculative decoding",
        )
        self._m_queue = r.gauge(
            "edl_serving_queue_depth", "requests waiting for a KV slot"
        )
        self._m_active = r.gauge("edl_serving_active_slots", "occupied KV slots")
        self._m_occupancy = r.gauge(
            "edl_serving_slot_occupancy", "mean active/max slots over decode steps"
        )

    # -- engine hooks -------------------------------------------------------

    def on_submit(
        self,
        rid: str,
        tenant: Optional[str] = None,
        slo_class: Optional[str] = None,
    ) -> None:
        self.submitted += 1
        self.requests[rid] = _ReqRecord(
            has_submit=True, submit_s=self.clock(),
            tenant=tenant or "", slo_class=slo_class or "",
        )
        self._m_requests.inc(event="submitted")

    def on_reject(self, rid: str, reason: str) -> None:
        self.rejected[reason] += 1
        rec = self.requests.setdefault(
            rid, _ReqRecord(has_submit=True, submit_s=self.clock())
        )
        rec.outcome = f"rejected:{reason}"
        self._m_requests.inc(event="rejected")
        self._m_outcomes.inc(
            outcome=f"rejected:{reason}",
            tenant=rec.tenant, slo_class=rec.slo_class,
        )

    def on_pop(self, rid: str) -> None:
        """The scheduler handed this request to the engine: queue wait
        ends here, the prefill phase begins. (A crash-recovery requeue
        pops again — the LAST pop wins, so queue wait includes the
        re-queued time, which is what the user experienced.)"""
        now = self.clock()
        rec = self.requests.setdefault(rid, _ReqRecord())
        rec.pop_s = now
        rec.has_pop = True
        if rec.has_submit:
            w = now - rec.submit_s
            self.queue_wait_hist.observe(w)
            self._r_queue_wait.observe(w)

    def on_admit(self, rid: str, prompt_len: int) -> None:
        self.admitted += 1
        rec = self.requests.setdefault(rid, _ReqRecord())
        rec.admit_s = self.clock()
        rec.prompt_len = prompt_len
        if self._t_first_admit is None:
            self._t_first_admit = rec.admit_s
        self._m_requests.inc(event="admitted")

    def on_token(self, rid: str) -> None:
        """One generated token (the first lands with the prefill)."""
        self.on_tokens(rid, 1)

    def on_tokens(self, rid: str, n: int) -> None:
        """``n`` tokens observed at once — the per-block accounting
        path (one clock read for a request's whole share of a drained
        horizon block)."""
        now = self.clock()
        rec = self.requests.setdefault(rid, _ReqRecord())
        if rec.tokens == 0:
            rec.first_token_s = now
            if rec.has_submit:
                ttft = now - rec.submit_s
                self.ttft_hist.observe(ttft)
                self._r_ttft.observe(ttft)
            if rec.has_pop:
                pf = now - rec.pop_s
                self.prefill_hist.observe(pf)
                self._r_prefill.observe(pf)
            if n > 1:
                # tokens beyond the first in the same drain: zero
                # observable inter-token gap at this clock resolution
                self.itl_hist.observe(0.0, n=n - 1)
                self._r_itl.observe(0.0, n=n - 1)
        elif rec.last_token_s:
            # honest tail: the user waited the FULL inter-drain gap
            # for this block's first token; the other n-1 arrived in
            # the same drain. One full-gap observation + n-1 zeros
            # keeps count and sum identical to the old per-token-mean
            # bucketing while letting p99 see the stall (a mean of
            # gap/n hid every block-sized freeze as H grew).
            gap = now - rec.last_token_s
            self.itl_hist.observe(gap)
            self._r_itl.observe(gap)
            if n > 1:
                self.itl_hist.observe(0.0, n=n - 1)
                self._r_itl.observe(0.0, n=n - 1)
        rec.last_token_s = now
        rec.tokens += n
        self.tokens_out += n
        self._t_last_token = now
        self._m_tokens.inc(n)

    def on_dispatch(self, kind: str) -> None:
        """One device program dispatch (``decode`` = a fused horizon
        block, ``prefill`` = an admission insert)."""
        self.dispatches[kind] += 1
        self._m_dispatch.inc(kind=kind)

    def on_spec(self, drafted: int, accepted: int) -> None:
        """One drained verify block's speculation outcome: ``drafted``
        draft tokens went in, ``accepted`` matched greedy argmax.
        (Bonus tokens — the one guaranteed emission per dispatch — are
        deliberately NOT counted here: acceptance rate measures the
        DRAFTER, and counting freebies would floor it at 1/K.)"""
        if drafted <= 0 and accepted <= 0:
            return
        self.spec_drafted += drafted
        self.spec_accepted += accepted
        if drafted > 0:
            self._m_spec_drafted.inc(drafted)
        if accepted > 0:
            self._m_spec_accepted.inc(accepted)
        if self.spec_drafted > 0:
            self._m_spec_rate.set(self.spec_accepted / self.spec_drafted)

    def on_block(self, seconds: float) -> None:
        """One fused horizon block's dispatch→drain wall time — the
        decode-phase granule. Under the double-buffered pipeline a
        block's drain overlaps the NEXT block's device work, so this
        is end-to-end block latency as the host observed it, not pure
        device time (that is what makes it the right number for SLO
        accounting). It SPANS TWO STEPS of the double buffer: block k
        is dispatched in step k and drained in step k+1, after block
        k+1 went out, so a steady engine reads about twice its step
        period here (36 ms against an 18 ms step on the chip, PERF.md).
        The device's own time for a block is the ``edl_serve_block``
        module in a profiler trace."""
        self.block_hist.observe(seconds)
        self._r_block.observe(seconds)

    def on_recovery(self, live_slots: int) -> None:
        """One engine recovery pass: in-flight blocks discarded, device
        state rebuilt, ``live_slots`` requests replayed in place."""
        self.recoveries += 1
        self._m_recoveries.inc()

    def on_finish(self, rid: str, outcome: str) -> None:
        self.completed += 1
        self.outcomes[outcome] += 1
        rec = self.requests.setdefault(rid, _ReqRecord())
        rec.outcome = outcome
        rec.finish_s = self.clock()
        if rec.tokens >= 2 and rec.first_token_s:
            # user-perceived TPOT over the whole decode: block
            # amortization cannot hide a stall from this one
            tpot = (rec.finish_s - rec.first_token_s) / (rec.tokens - 1)
            self.tpot_hist.observe(tpot)
            self._r_tpot.observe(tpot)
        self._m_requests.inc(event="completed")
        self._m_outcomes.inc(
            outcome=outcome, tenant=rec.tenant, slo_class=rec.slo_class
        )

    def on_step(self, active_slots: int, max_slots: int, queue_depth: int):
        """One engine iteration (decode step or idle-admit pass)."""
        self._steps += 1
        self._active_slot_steps += active_slots
        self._max_slots = max(self._max_slots, max_slots)
        self._active_now = active_slots
        self._queue_depth = queue_depth
        self._m_queue.set(queue_depth)
        self._m_active.set(active_slots)
        # occupancy is a slow-moving running mean — refreshing the
        # mirror gauge every 16 steps keeps the per-step hook under
        # the 1% overhead budget on tiny CPU-dryrun blocks
        if self._max_slots and (self._steps & 15) == 0:
            self._m_occupancy.set(
                self._active_slot_steps / (self._steps * self._max_slots)
            )

    # -- views --------------------------------------------------------------

    def request_stats(self, rid: str) -> Dict[str, float]:
        rec = self.requests[rid]
        ttft = (
            rec.first_token_s - rec.submit_s if rec.first_token_s else 0.0
        )
        dur = (rec.finish_s or self.clock()) - (rec.admit_s or rec.submit_s)
        return {
            "ttft_s": ttft,
            "tokens": rec.tokens,
            "tokens_per_s": rec.tokens / dur if dur > 0 else 0.0,
            "outcome": rec.outcome,
        }

    def phase_breakdown(self, rid: str) -> Dict[str, float]:
        """One request's latency decomposition — queue wait (submit →
        pop), prefill (pop → first token), decode (first token →
        finish), total (submit → finish). The three phases are
        exactly adjacent stamps of one clock, so
        ``queue_wait + prefill + decode == total`` for any finished
        request. Zeros where a phase never happened (e.g. shed before
        pop). Attached to the flight-recorder ``serve.finish`` event
        by the engine, so `edl postmortem` shows WHERE the time went."""
        rec = self.requests.get(rid)
        if rec is None:
            return {"queue_wait_s": 0.0, "prefill_s": 0.0,
                    "decode_s": 0.0, "total_s": 0.0}
        end = rec.finish_s or self.clock()
        return {
            "queue_wait_s": (
                rec.pop_s - rec.submit_s
                if rec.has_submit and rec.has_pop else 0.0
            ),
            "prefill_s": (
                rec.first_token_s - rec.pop_s
                if rec.has_pop and rec.first_token_s else 0.0
            ),
            "decode_s": (
                end - rec.first_token_s if rec.first_token_s else 0.0
            ),
            "total_s": end - rec.submit_s if rec.has_submit else 0.0,
        }

    def snapshot(self) -> Dict[str, float]:
        """Flat numeric record — what ``ServingSource`` samples into a
        MonitorSample and the autoscaler would consume as serving
        load."""
        ttfts = [
            r.first_token_s - r.submit_s
            for r in self.requests.values()
            if r.first_token_s
        ]
        busy = 0.0
        if self._t_first_admit is not None and self._t_last_token is not None:
            busy = self._t_last_token - self._t_first_admit
        snap: Dict[str, float] = {
            "submitted": float(self.submitted),
            "admitted": float(self.admitted),
            "rejected": float(sum(self.rejected.values())),
            "completed": float(self.completed),
            "recoveries": float(self.recoveries),
            "tokens_out": float(self.tokens_out),
            "queue_depth": float(self._queue_depth),
            "active_slots": float(self._active_now),
            "max_slots": float(self._max_slots),
            "slot_occupancy": (
                self._active_slot_steps / (self._steps * self._max_slots)
                if self._steps and self._max_slots
                else 0.0
            ),
            "ttft_avg_s": sum(ttfts) / len(ttfts) if ttfts else 0.0,
            "ttft_max_s": max(ttfts) if ttfts else 0.0,
            # histogram-derived percentiles (same interpolation a
            # PromQL histogram_quantile over /metrics would give).
            # NOTE: the backing histograms are registry-resident, so
            # with the shared default registry they aggregate across
            # every engine in the process — construct with a private
            # registry for per-engine isolation.
            "ttft_p50_s": self.ttft_hist.percentile(0.50),
            "ttft_p95_s": self.ttft_hist.percentile(0.95),
            "ttft_p99_s": self.ttft_hist.percentile(0.99),
            "itl_p50_s": self.itl_hist.percentile(0.50),
            "itl_p95_s": self.itl_hist.percentile(0.95),
            "itl_p99_s": self.itl_hist.percentile(0.99),
            "tpot_p50_s": self.tpot_hist.percentile(0.50),
            "tpot_p95_s": self.tpot_hist.percentile(0.95),
            "tpot_p99_s": self.tpot_hist.percentile(0.99),
            # the TTFT decomposition (queue wait + prefill ≈ TTFT):
            # "TTFT regressed" resolves into "queue grew" vs "prefill
            # slowed" from the snapshot alone
            "queue_wait_p50_s": self.queue_wait_hist.percentile(0.50),
            "queue_wait_p95_s": self.queue_wait_hist.percentile(0.95),
            "queue_wait_p99_s": self.queue_wait_hist.percentile(0.99),
            "prefill_p50_s": self.prefill_hist.percentile(0.50),
            "prefill_p95_s": self.prefill_hist.percentile(0.95),
            "prefill_p99_s": self.prefill_hist.percentile(0.99),
            "block_p50_s": self.block_hist.percentile(0.50),
            "block_p95_s": self.block_hist.percentile(0.95),
            "block_p99_s": self.block_hist.percentile(0.99),
            "agg_tokens_per_s": self.tokens_out / busy if busy > 0 else 0.0,
            "dispatches_decode": float(self.dispatches["decode"]),
            "dispatches_prefill": float(self.dispatches["prefill"]),
            "dispatches_verify": float(self.dispatches["verify"]),
            # speculation: drafted/accepted totals + cumulative
            # acceptance rate (0 when speculation never ran)
            "spec_drafted": float(self.spec_drafted),
            "spec_accepted": float(self.spec_accepted),
            "spec_acceptance_rate": (
                self.spec_accepted / self.spec_drafted
                if self.spec_drafted
                else 0.0
            ),
            # the fused-horizon efficiency headline: device dispatches
            # per generated token (1/H + admission overhead when the
            # pipeline is healthy; ~1.0 means per-token dispatch)
            "dispatches_per_token": (
                sum(self.dispatches.values()) / self.tokens_out
                if self.tokens_out
                else 0.0
            ),
        }
        for reason, n in sorted(self.rejected.items()):
            snap[f"rejected_{reason}"] = float(n)
        for outcome, n in sorted(self.outcomes.items()):
            snap[f"outcome_{outcome}"] = float(n)
        # tenant / SLO-class attribution: terminal outcomes per label
        # (the flat-dict twin of edl_serving_outcomes_total — what a
        # label-blind ServingSource consumer still gets to see)
        by_class: Counter = Counter()
        by_tenant: Counter = Counter()
        for rec in self.requests.values():
            if not rec.outcome:
                continue
            if rec.slo_class:
                by_class[rec.slo_class] += 1
            if rec.tenant:
                by_tenant[rec.tenant] += 1
        for name, n in sorted(by_class.items()):
            snap[f"class_{name}_finished"] = float(n)
        for name, n in sorted(by_tenant.items()):
            snap[f"tenant_{name}_finished"] = float(n)
        return snap
