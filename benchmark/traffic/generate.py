"""The one traffic generator: every serving mix is a data file of
parameters that this module turns into requests.

Every seed gets the SAME prompt lengths, output budgets and
inter-arrival gaps (the quantiles of the mix's distributions) in the
SAME order (a shuffle fixed by the mix's ``order_seed``); the seed draws
the token ids. On the chip a seed that also drew the order moved the
95th percentile of time to first token by +-15%, because which long
prompt meets which burst IS the work of a queue, while two runs of one
order agreed within a few percent (PERF.md, PR 23). So the order is part
of the mix, and a seed changes what is said, not who meets whom.

A length distribution is a log-normal clipped to [lo, hi], given by its
median, as ``edl_tpu/serving/loadgen.py`` does. Arrivals are ``poisson``
(exponential gaps at ``rate_rps``; requests are due on that schedule
whatever the server does) or ``closed`` (``clients`` callers, each
sending its next request when its last completes).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, Iterator, List

import numpy as np


@dataclass
class Request:
    rid: str
    due_s: float  # offset into the window at which it is due (open loop)
    prompt: List[int]
    max_new: int


def length_set(dist: Dict, n: int) -> List[int]:
    """n lengths at the quantiles (i + 0.5) / n of the clipped
    log-normal."""
    mu, sigma = math.log(dist["median"]), dist["sigma"]
    normal = NormalDist()
    out = []
    for i in range(n):
        v = math.exp(mu + sigma * normal.inv_cdf((i + 0.5) / n))
        out.append(int(min(max(round(v), dist["lo"]), dist["hi"])))
    return out


def gap_set(rate_rps: float, n: int) -> List[float]:
    """n inter-arrival gaps at the quantiles of Exp(rate)."""
    return [-math.log(1.0 - (i + 0.5) / n) / rate_rps for i in range(n)]


def _prompt(rng, vocab: int, n: int) -> List[int]:
    return [int(t) for t in rng.integers(0, vocab, n)]


def open_loop(mix: Dict, seed: int, vocab: int, seconds: float
              ) -> List[Request]:
    """Requests due inside ``seconds``, in due order."""
    order = np.random.default_rng(mix["order_seed"])
    rng = np.random.default_rng(seed)
    n = max(1, math.ceil(mix["rate_rps"] * seconds))
    gaps = order.permutation(gap_set(mix["rate_rps"], n))
    prompts = order.permutation(length_set(mix["prompt"], n))
    outputs = order.permutation(length_set(mix["output"], n))
    due = np.cumsum(gaps)
    return [
        Request(f"r{i:05d}", float(due[i]),
                _prompt(rng, vocab, int(prompts[i])), int(outputs[i]))
        for i in range(n) if due[i] < seconds
    ]


def closed_loop(mix: Dict, seed: int, vocab: int) -> Iterator[Request]:
    """An endless stream for ``clients`` callers: cycle after cycle of
    the same ``cycle`` lengths, each cycle in a new order. The first
    ``clients`` budgets are scaled by a U(0, 1) draw, so the slots are
    out of phase from the first second."""
    order = np.random.default_rng(mix["order_seed"])
    rng = np.random.default_rng(seed)
    m = int(mix["cycle"])
    i = 0
    while True:
        prompts = order.permutation(length_set(mix["prompt"], m))
        outputs = order.permutation(length_set(mix["output"], m))
        for p, o in zip(prompts, outputs):
            o = int(o)
            if i < mix["clients"]:
                o = max(1, int(o * order.uniform()))
            yield Request(f"r{i:05d}", 0.0, _prompt(rng, vocab, int(p)), o)
            i += 1
