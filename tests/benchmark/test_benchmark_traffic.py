"""The traffic generator: the same seed the same bytes, every seed the
same work in the same order with other token ids; open-loop timing
counts from the due time."""

import json
import time
import types

import pytest

from benchmark.traffic import generate

MIX_OPEN = {"loop": "open", "order_seed": 23, "rate_rps": 50.0,
            "prompt": {"median": 12, "sigma": 0.6, "lo": 8, "hi": 24},
            "output": {"median": 6, "sigma": 0.6, "lo": 2, "hi": 8}}
MIX_CLOSED = {"loop": "closed", "order_seed": 23, "clients": 3, "cycle": 6,
              "prompt": MIX_OPEN["prompt"], "output": MIX_OPEN["output"]}


def as_bytes(reqs):
    return json.dumps([r.__dict__ for r in reqs], sort_keys=True).encode()


@pytest.mark.parametrize("seed", [0, 7, 3000000019])
def test_same_seed_same_bytes(seed):
    a = generate.open_loop(MIX_OPEN, seed, 256, 2.0)
    b = generate.open_loop(MIX_OPEN, seed, 256, 2.0)
    assert as_bytes(a) == as_bytes(b)
    assert as_bytes(a) != as_bytes(generate.open_loop(MIX_OPEN, seed + 1, 256, 2.0))


def test_every_seed_holds_the_same_work_in_the_same_order():
    a = generate.open_loop(MIX_OPEN, 1, 256, 2.0)
    b = generate.open_loop(MIX_OPEN, 2, 256, 2.0)
    assert len(a) == len(b) == 100
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert [r.max_new for r in a] == [r.max_new for r in b]
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    # another order is another mix: its own data file
    c = generate.open_loop(dict(MIX_OPEN, order_seed=24), 1, 256, 2.0)
    assert sorted(len(r.prompt) for r in c) == sorted(len(r.prompt) for r in a)
    assert [len(r.prompt) for r in c] != [len(r.prompt) for r in a]
    assert all(0 <= r.due_s < 2.0 for r in a)
    assert all(8 <= len(r.prompt) <= 24 and 2 <= r.max_new <= 8 for r in a)
    assert sorted(set(len(r.prompt) for r in a)) != [len(a[0].prompt)]


def test_closed_loop_cycles_and_staggers_the_first_wave():
    it = generate.closed_loop(MIX_CLOSED, 5, 256)
    reqs = [next(it) for _ in range(18)]
    full = sorted(generate.length_set(MIX_CLOSED["output"], 6))
    # later cycles hold the whole set; the first wave's budgets are cut
    assert sorted(r.max_new for r in reqs[6:12]) == full
    assert sorted(r.max_new for r in reqs[12:18]) == full
    assert sum(r.max_new for r in reqs[:3]) <= sum(sorted(full)[-3:])
    assert len({r.rid for r in reqs}) == 18


class StallingEngine:
    """Answers every request one step after it was admitted, and stalls
    once for ``stall_s`` in the middle of the window."""

    def __init__(self, metrics, stall_at, stall_s):
        self.metrics, self.clock = metrics, time.monotonic
        self.stall_at, self.stall_s = stall_at, stall_s
        self.queue, self.results, self._slots = [], {}, []
        self.recoveries = 0
        self.t0 = None

    @property
    def has_work(self):
        return bool(self.queue)

    def submit(self, rid, prompt, max_new):
        self.metrics.on_submit(rid)
        self.queue.append(rid)

    def step(self):
        if self.t0 is None:
            self.t0 = self.clock()
        if self.stall_s and self.clock() - self.t0 >= self.stall_at:
            time.sleep(self.stall_s)
            self.stall_s = 0.0
        rid = self.queue.pop(0)
        self.metrics.on_pop(rid)
        self.metrics.on_tokens(rid, 2)
        self.results[rid] = types.SimpleNamespace(outcome="done", tokens=[1, 2])


def open_window(stall_s):
    from benchmark.kinds import serve

    cell = types.SimpleNamespace(
        traffic=dict(MIX_OPEN), config={"vocab_size": 256}, spec={}, limits={})
    tracer = types.SimpleNamespace(tick=lambda t: None, finish=lambda: None)
    kind = serve.Kind(types.SimpleNamespace(
        cell=cell, seed=3, tracer=tracer, control=False))
    kind.metrics = serve.RecordingMetrics()
    kind.engine = StallingEngine(kind.metrics, 0.2, stall_s)
    kind.clock = kind.engine.clock
    kind.window(1.0)
    return kind


def test_open_loop_times_each_request_from_when_it_was_due():
    calm, stalled = open_window(0.0), open_window(0.3)
    assert calm.attempted == stalled.attempted == 50 and stalled.failed == 0
    # requests due during the stall wait for it: their first token is
    # late by up to the stall, and the generator's lateness says why
    assert max(calm.spans["ttft_s"]) < 0.05
    assert max(stalled.spans["ttft_s"]) > 0.2
    assert max(stalled.spans["lateness_s"]) > 0.2
    assert max(calm.spans["lateness_s"]) < 0.05
    assert stalled.end_to_end()["ttft_p95_ms"] > 100.0
    # the wait is charged to the queue (due -> pop), not to the prefill
    assert max(stalled.spans["queue_wait_s"]) > 0.2
    assert max(stalled.spans["prefill_s"]) < 0.05
