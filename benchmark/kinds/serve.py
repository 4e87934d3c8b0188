"""Kind ``serve``: the engine the cell's family builds (for the decoder
one ``ContinuousBatchingEngine`` with ``edl serve``'s defaults: contiguous
KV, horizon 1), driven by one thread.

The traffic file's ``loop`` says how: ``open`` submits each request when
it is due and times it from then, whatever the server is doing;
``closed`` keeps ``clients`` callers each with one request in flight.
Set-up warms every prefill bucket the mix can hit and the block program
on the one engine the window uses.
"""

from __future__ import annotations

import gc
import time
from collections import deque
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness
from benchmark.traffic import generate
from edl_tpu.obs.metrics import MetricsRegistry
from edl_tpu.serving.metrics import ServingMetrics
from edl_tpu.serving.scheduler import AdmissionError

# an open-loop window that has not drained this long after its last
# arrival is cut, and what is unfinished counts as failed
DRAIN_LIMIT_S = 60.0


class RecordingMetrics(ServingMetrics):
    """The engine's own hooks, with every stamp kept as it was taken
    (the histograms behind them are bucketed)."""

    def __init__(self):
        super().__init__(registry=MetricsRegistry())
        self.token_stamps: Dict[str, List[float]] = {}
        self.pop_stamps: Dict[str, float] = {}
        self.block_seconds: List[float] = []

    def on_tokens(self, rid, n):
        self.token_stamps.setdefault(rid, []).extend([self.clock()] * n)
        super().on_tokens(rid, n)

    def on_pop(self, rid):
        self.pop_stamps[rid] = self.clock()
        super().on_pop(rid)

    def on_block(self, seconds):
        self.block_seconds.append(seconds)
        super().on_block(seconds)


class Kind:

    def __init__(self, ctx):
        self.ctx = ctx
        self.cell = ctx.cell
        self.mix = self.cell.traffic
        self.vocab = self.cell.config["vocab_size"]
        self.spans: Dict[str, list] = {}
        self.counters: Dict[str, float] = {}
        self.attempted = self.failed = 0
        self.sent: Dict[str, generate.Request] = {}
        self.due_at: Dict[str, float] = {}

    # -- set-up -------------------------------------------------------------

    def setup(self) -> None:
        ctx, family = self.ctx, self.cell.family
        self.model_cfg = family.program_config(
            self.cell.config, training=False)
        self.params = harness.make_params(
            ctx.seed, self.cell.layout, jnp.bfloat16)
        served = self.params
        if ctx.control:
            # the bf16 tree goes, and check() draws it again
            served = family.control_params(self.params)
            self.params = None
        self.metrics = RecordingMetrics()
        self.engine = family.engine(
            served, self.model_cfg, self.cell.spec["engine"], self.metrics)
        self.clock = self.engine.clock
        self.warm()

    def warm(self) -> None:
        """One request per prefill bucket the mix can hit; the block
        program compiles with the first of them."""
        e, p = self.engine, self.mix["prompt"]
        buckets = sorted({e._bucket(n) for n in range(p["lo"], p["hi"] + 1)})
        rng = np.random.default_rng(self.ctx.seed + 1)
        for b in buckets:
            n = min(b, p["hi"])
            e.submit(f"warm-{b}", [int(t) for t in
                                   rng.integers(0, self.vocab, n)], 2)
        e.run()
        bad = [r for r in e.results.values() if r.outcome != "done"]
        if bad or e.recoveries:
            raise RuntimeError(f"warm-up failed: {bad} recoveries="
                               f"{e.recoveries}")
        print(f"warmed prefill buckets {buckets} and the block program",
              flush=True)

    # -- the window ---------------------------------------------------------

    def submit(self, req: generate.Request, due_at: float) -> bool:
        self.sent[req.rid] = req
        self.due_at[req.rid] = due_at
        try:
            with jax.profiler.TraceAnnotation("bench.submit"):
                self.engine.submit(req.rid, req.prompt, req.max_new)
            return True
        except AdmissionError as e:
            print(f"refused {req.rid}: {e.reason}", flush=True)
            return False

    def engine_step(self) -> None:
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            self.engine.step()
        # tokens resident in the cache after this step, for the needed
        # bytes of a decode step
        recs = self.metrics.requests
        self._resident_sum += sum(
            len(s.prompt) + recs[s.rid].tokens
            for s in self.engine._slots if s is not None)
        self._steps += 1

    def window(self, seconds: float) -> None:
        self._resident_sum = self._steps = 0
        self.t0 = self.clock()
        if self.mix["loop"] == "open":
            self.open_loop(seconds)
        else:
            self.closed_loop(seconds)
        self.t_end = self.clock()
        self.ctx.tracer.finish()
        self.reduce(seconds)

    def open_loop(self, seconds: float) -> None:
        tracer, clock, t0 = self.ctx.tracer, self.clock, self.t0
        pending = deque(generate.open_loop(
            self.mix, self.ctx.seed, self.vocab, seconds))
        last_due = pending[-1].due_s
        lateness = self.spans.setdefault("lateness_s", [])
        while True:
            now = clock() - t0
            tracer.tick(now)
            while pending and pending[0].due_s <= now:
                req = pending.popleft()
                lateness.append(now - req.due_s)
                self.submit(req, t0 + req.due_s)
            if self.engine.has_work:
                self.engine_step()
            elif pending:
                with jax.profiler.TraceAnnotation("bench.idle_wait"):
                    time.sleep(min(max(pending[0].due_s - now, 0.0), 0.002))
            else:
                break
            if now > last_due + DRAIN_LIMIT_S:
                print("window cut: the backlog did not drain", flush=True)
                break

    def closed_loop(self, seconds: float) -> None:
        tracer, clock, t0 = self.ctx.tracer, self.clock, self.t0
        stream = generate.closed_loop(self.mix, self.ctx.seed, self.vocab)
        live: List[str] = []
        results = self.engine.results
        while True:
            now = clock() - t0
            if now >= seconds:
                break
            tracer.tick(now)
            live = [rid for rid in live if rid not in results]
            while len(live) < self.mix["clients"]:
                req = next(stream)
                if self.submit(req, clock()):
                    live.append(req.rid)
                elif len(self.sent) > 100 * self.mix["clients"]:
                    raise RuntimeError("the engine refuses every request")
            self.engine_step()
        self.in_flight = set(live) - set(results)

    def reduce(self, seconds: float) -> None:
        """Stamps to spans and counters; attempted and failed."""
        m, results = self.metrics, self.engine.results
        in_flight = getattr(self, "in_flight", set())
        ttft, wait, prefill, itl = [], [], [], []
        tokens = 0
        for rid, req in self.sent.items():
            if rid in in_flight:
                stamps = m.token_stamps.get(rid, [])
                tokens += sum(1 for t in stamps if t <= self.t_end)
                itl.extend(np.diff(stamps).tolist())
                continue
            self.attempted += 1
            res = results.get(rid)
            stamps = m.token_stamps.get(rid, [])
            if res is None or res.outcome != "done" or not stamps:
                self.failed += 1
                ttft.append(float("inf"))
                continue
            tokens += len(stamps)
            ttft.append(stamps[0] - self.due_at[rid])
            wait.append(m.pop_stamps[rid] - self.due_at[rid])
            prefill.append(stamps[0] - m.pop_stamps[rid])
            itl.extend(np.diff(stamps).tolist())
        worst = max([t for t in ttft if t != float("inf")] + [seconds])
        self.spans.update(
            ttft_s=[min(t, worst) for t in ttft], queue_wait_s=wait,
            prefill_s=prefill, itl_s=itl, block_s=m.block_seconds)
        self.counters.update(
            tokens=tokens, window_s=self.t_end - self.t0,
            resident_tokens_mean=self._resident_sum / max(self._steps, 1),
            engine_steps=self._steps, recoveries=self.engine.recoveries,
            requests_finished=self.attempted - self.failed)
        slo = self.mix.get("slo")
        if slo:
            # how the knee is judged (PERF.md): a request meets the
            # limits if its first token and its mean gap both do
            met = sum(
                1 for rid in self.sent
                if rid in results and results[rid].outcome == "done"
                and self.meets(rid, slo))
            self.counters["slo_attainment"] = met / max(self.attempted, 1)
            last_due = max(self.due_at.values())
            print(f"limits met by {met}/{self.attempted}; drained "
                  f"{self.t_end - last_due:.2f}s after the last arrival; "
                  f"generator late by at most "
                  f"{max(self.spans.get('lateness_s', [0])) * 1e3:.1f}ms",
                  flush=True)
        print(f"window: {self.attempted} attempted, {self.failed} failed, "
              f"{tokens} tokens in {self.t_end - self.t0:.2f}s, "
              f"{self._steps} engine steps", flush=True)

    def meets(self, rid: str, slo: Dict) -> bool:
        stamps = self.metrics.token_stamps[rid]
        ttft = stamps[0] - self.due_at[rid]
        gap = (stamps[-1] - stamps[0]) / max(len(stamps) - 1, 1)
        return (ttft * 1e3 <= slo["ttft_ms"]
                and gap * 1e3 <= slo["mean_itl_ms"])

    def end_to_end(self) -> Dict[str, float]:
        c = self.counters
        out = {"serve_tokens_per_s": c["tokens"] / c["window_s"]}
        if self.spans["ttft_s"]:
            out["ttft_p95_ms"] = 1e3 * harness.percentile(
                self.spans["ttft_s"], 0.95)
        return out

    # -- correct ------------------------------------------------------------

    def release(self) -> None:
        self.finished = {
            rid: list(res.tokens)
            for rid, res in self.engine.results.items()
            if rid in self.sent and res.outcome == "done"}
        self.engine = None
        gc.collect()

    def sample(self) -> List[str]:
        """The longest finished request and others drawn from the seed."""
        rids = sorted(self.finished)
        if not rids:
            return []
        size = lambda r: len(self.sent[r].prompt) + len(self.finished[r])
        longest = max(rids, key=size)
        rest = [r for r in rids if r != longest]
        rng = np.random.default_rng(self.ctx.seed + 2)
        k = min(len(rest), int(self.cell.spec["check_requests"]) - 1)
        picked = rng.choice(len(rest), size=k, replace=False) if k else []
        return [longest] + [rest[i] for i in picked]

    def check(self, compared) -> None:
        lim = self.cell.limits
        compared.add("engine_recoveries",
                     float(self.counters["recoveries"]), 0.0)
        max_len = int(self.cell.spec["engine"]["max_len"])
        config, reference_logits = (
            self.cell.config, self.cell.family.reference_logits)

        @jax.jit
        def gaps(params, tokens, served):
            # how far each served token's logit lies under the
            # reference's best at its position; ``served`` is -1 where
            # the position holds no served token
            lg = reference_logits(params, tokens, config)
            at = jnp.take_along_axis(
                lg, jnp.maximum(served, 0)[:, None], 1)[:, 0]
            return jnp.where(served >= 0, jnp.max(lg, axis=-1) - at, 0.0)

        if self.params is None:
            self.params = harness.make_params(
                self.ctx.seed, self.cell.layout, jnp.bfloat16)
        t0 = time.perf_counter()
        all_gaps: List[float] = []
        picked = self.sample()
        for rid in picked:
            prompt, out = self.sent[rid].prompt, self.finished[rid]
            tokens = np.zeros(max_len, np.int32)
            served = np.full(max_len, -1, np.int32)
            seq = prompt + out[:-1]
            tokens[:len(seq)] = seq
            served[len(prompt) - 1:len(prompt) - 1 + len(out)] = out
            g = np.asarray(gaps(self.params, tokens, served))
            all_gaps.extend(g[served >= 0].tolist())
        print(f"reference: {len(picked)} requests, {len(all_gaps)} served "
              f"tokens in {time.perf_counter() - t0:.1f}s; "
              f"{sum(1 for g in all_gaps if g > 0)} not the reference's "
              f"first choice", flush=True)
        if not all_gaps:
            compared.add("served_tokens_checked", 0.0, -1.0)
            return
        compared.add("served_token_gap_max", float(max(all_gaps)),
                     lim["served_token_gap_max"])
        compared.add("served_token_gap_mean",
                     float(np.mean(all_gaps)), lim["served_token_gap_mean"])
