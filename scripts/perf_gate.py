#!/usr/bin/env python3
"""Perf-regression gate over the committed BENCH_r*.json trajectory.

The bench trajectory is the repo's efficiency ground truth (CTR
examples/s, train MFU, the decode bandwidth ladder, reshard stalls,
p2p plane). Until now nothing MACHINE-checked that a round didn't
regress it — a 20% MFU drop would ride into the history as one more
JSON file. This gate compares a candidate round against the best prior
value of each metric, with per-metric tolerances sized to each
measurement's observed noise (host jitter on sub-second stalls is
~10-20%; long-loop throughput is ~1-3%).

Rules, in order:

* a metric is compared only when the candidate carries it with a
  POSITIVE value — the bench publishes explicit ``-1.0`` sentinels for
  failed measurements and ``0.0`` on CPU smoke runs; sentinels are
  reported as ``skipped``, never silently passed as zero;
* config-keyed metrics (train throughput/MFU keyed by
  ``llama_config``, the decode rungs by ``decode_config``) only
  compare rounds measuring the SAME config — BENCH_r01's llama figure
  predates the flagship config and must not poison the reference;
* no comparable prior → ``bootstrap`` (pass): the first round that
  publishes a metric establishes its reference;
* otherwise fail iff the candidate is worse than the best prior by
  more than the metric's relative tolerance.

CLI (the CI phase runs this bare — candidate defaults to the
highest-numbered committed round, trajectory to the rounds before it):

    python scripts/perf_gate.py [--dir REPO] [--candidate FILE]
        [--json] [-v]

Library surface (tests/test_perf_gate.py drives synthetic
improving/regressing/noisy/empty trajectories through it):
``gate(trajectory, candidate) -> GateReport``.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional


@dataclass(frozen=True)
class MetricSpec:
    """direction: +1 higher-is-better, -1 lower-is-better.
    rel_tol: allowed fractional regression vs the best prior value.
    config_key: bench field that must MATCH between rounds for the
    values to be comparable (None = always comparable)."""

    direction: int
    rel_tol: float
    config_key: Optional[str] = None


# The gated catalog. Tolerances are sized to >=2x each measurement's
# round-to-round noise as observed over five rounds on an earlier
# installation (records since deleted; re-derive on today's machine):
# long-loop throughput ~1-3% noise -> 5%; MFU ~0.1% -> 3%; sub-second
# stall timings ~6% -> 25%; host/p2p plane bandwidth is
# interference-prone -> 20%.
METRICS: Dict[str, MetricSpec] = {
    # CTR (the reference production workload; headline "value")
    "value": MetricSpec(+1, 0.05),
    # flagship llama training
    "llama_tokens_per_sec_per_chip": MetricSpec(+1, 0.05, "llama_config"),
    "mfu": MetricSpec(+1, 0.03, "llama_config"),
    "int8_mfu": MetricSpec(+1, 0.03, "llama_config"),
    "llama_long_tokens_per_sec_per_chip": MetricSpec(+1, 0.05, "llama_config"),
    "long_mfu": MetricSpec(+1, 0.03, "llama_config"),
    "int8_long_mfu": MetricSpec(+1, 0.03, "llama_config"),
    # decode ladder (the serving roofline)
    "decode_tokens_per_sec": MetricSpec(+1, 0.10, "decode_config"),
    "decode_pct_peak_bw": MetricSpec(+1, 0.05, "decode_config"),
    "decode_int8_tokens_per_sec": MetricSpec(+1, 0.10, "decode_config"),
    "decode_int8_pct_peak_bw": MetricSpec(+1, 0.05, "decode_config"),
    "decode_int8_b1_tokens_per_sec": MetricSpec(+1, 0.10, "decode_config"),
    "decode_int8_b1_pct_peak_bw": MetricSpec(+1, 0.05, "decode_config"),
    "prefill_s": MetricSpec(-1, 0.25, "decode_config"),
    # serving engine + goodput rungs
    "serving_tokens_per_sec_h8": MetricSpec(+1, 0.10, "serving_config"),
    "serving_horizon_speedup": MetricSpec(+1, 0.10, "serving_config"),
    "serving_goodput_rps": MetricSpec(+1, 0.15, "serving_goodput_config"),
    "serving_ttft_slo_attainment": MetricSpec(
        +1, 0.10, "serving_goodput_config"
    ),
    # paged KV rungs: block-packing concurrency at a fixed HBM budget
    # (counts, deterministic) and warm prefix-hit TTFT (wall-clock;
    # wide tolerance for host timing noise on tiny CPU models)
    "serving_effective_concurrency_at_fixed_hbm": MetricSpec(
        +1, 0.15, "serving_paged_config"
    ),
    "serving_prefix_hit_ttft_ms": MetricSpec(
        -1, 0.30, "serving_paged_config"
    ),
    # speculative decoding rungs: per-dispatch amplification is a
    # deterministic count ratio (tight), wall-clock b=1 rate rides the
    # usual serving timing noise
    "serving_spec_accepted_per_dispatch": MetricSpec(
        +1, 0.10, "serving_spec_config"
    ),
    "serving_spec_b1_tokens_per_sec": MetricSpec(
        +1, 0.15, "serving_spec_config"
    ),
    # quantized paged-KV rungs: concurrency at a fixed pool byte
    # budget and analytic decode-step bytes moved are deterministic
    # count/arithmetic ratios (tight); the b=1 wall clock rides the
    # usual serving timing noise. All keyed on kv_quant_config.
    "serving_kvq_concurrency_at_fixed_hbm": MetricSpec(
        +1, 0.10, "kv_quant_config"
    ),
    "decode_kvq8_bytes_moved_ratio": MetricSpec(
        +1, 0.05, "kv_quant_config"
    ),
    "decode_kvq8_b1_tokens_per_sec": MetricSpec(
        +1, 0.15, "kv_quant_config"
    ),
    # chip-lease elasticity rungs (scripts/exp_elasticity.py via the
    # bench's _elasticity_bench): the handover-window stall is a tiny
    # in-place reshard (sub-second host timing -> wide tolerance); the
    # grant->READY ramp is dominated by process boot + compile, noisy
    # on a shared box -> 50%; the p2p warm fetch is a wall-clock wire
    # pull of a tiny tree -> 50%. cold_load_s rides along ungated.
    "elasticity_handover_stall_s": MetricSpec(
        -1, 0.30, "elasticity_config"
    ),
    "elasticity_grant_ready_s": MetricSpec(-1, 0.50, "elasticity_config"),
    "elasticity_warm_fetch_s": MetricSpec(-1, 0.50, "elasticity_config"),
    # elastic protocol (lower is better; sub-second timing noise)
    "reshard_stall_s": MetricSpec(-1, 0.25),
    "reshard_stall_host_fallback_s": MetricSpec(-1, 0.25),
    "stall_model_8b_1host_s": MetricSpec(-1, 0.20),
    "stall_model_8b_migrate_s": MetricSpec(-1, 0.25),
    # shard plane
    "p2p_bw_gbs": MetricSpec(+1, 0.20),
    "p2p_agg_bw_gbs": MetricSpec(+1, 0.20),
    "host_stage_bw_gbs": MetricSpec(+1, 0.30),
}


@dataclass
class Verdict:
    metric: str
    status: str  # pass | fail | bootstrap | skipped
    candidate: Optional[float] = None
    reference: Optional[float] = None
    reference_round: Optional[str] = None
    detail: str = ""


@dataclass
class GateReport:
    verdicts: List[Verdict] = field(default_factory=list)

    @property
    def failed(self) -> List[Verdict]:
        return [v for v in self.verdicts if v.status == "fail"]

    @property
    def ok(self) -> bool:
        return not self.failed

    def to_json(self) -> str:
        return json.dumps(
            {
                "ok": self.ok,
                "verdicts": [v.__dict__ for v in self.verdicts],
            },
            sort_keys=True,
        )


def _value(doc: dict, name: str) -> Optional[float]:
    v = doc.get(name)
    if isinstance(v, (int, float)) and v > 0:
        return float(v)
    return None  # absent, sentinel (-1.0) or CPU zero: not a measurement


def gate(
    trajectory: List[dict],
    candidate: dict,
    metrics: Optional[Dict[str, MetricSpec]] = None,
) -> GateReport:
    """Compare ``candidate`` against the best prior value per metric.
    ``trajectory`` dicts may carry ``_round`` labels for reporting."""
    metrics = metrics or METRICS
    report = GateReport()
    for name, spec in metrics.items():
        cand = _value(candidate, name)
        if cand is None:
            if name in candidate:
                report.verdicts.append(
                    Verdict(name, "skipped", detail="sentinel/zero value")
                )
            continue
        ckey = spec.config_key
        cconf = candidate.get(ckey) if ckey else None
        pool = []
        for prior in trajectory:
            v = _value(prior, name)
            if v is None:
                continue
            if ckey and prior.get(ckey) != cconf:
                continue  # different measurement config: incomparable
            pool.append((v, prior.get("_round", "?")))
        if not pool:
            report.verdicts.append(
                Verdict(name, "bootstrap", candidate=cand,
                        detail="no comparable prior round")
            )
            continue
        if spec.direction > 0:
            ref, rnd = max(pool)
            worst_ok = ref * (1.0 - spec.rel_tol)
            bad = cand < worst_ok
            detail = f"{cand:.6g} vs best {ref:.6g} (floor {worst_ok:.6g})"
        else:
            ref, rnd = min(pool)
            worst_ok = ref * (1.0 + spec.rel_tol)
            bad = cand > worst_ok
            detail = f"{cand:.6g} vs best {ref:.6g} (ceiling {worst_ok:.6g})"
        report.verdicts.append(
            Verdict(
                name, "fail" if bad else "pass",
                candidate=cand, reference=ref, reference_round=rnd,
                detail=detail,
            )
        )
    return report


# ---------------------------------------------------------------------------
# committed-trajectory loading


_ROUND_RE = re.compile(r"BENCH_r(\d+)\.json$")


def load_rounds(repo_dir: str) -> List[dict]:
    """All committed BENCH_r*.json rounds, ordered, each tagged with
    ``_round``."""
    rounds = []
    for path in glob.glob(os.path.join(repo_dir, "BENCH_r*.json")):
        m = _ROUND_RE.search(path)
        if not m:
            continue
        with open(path) as f:
            doc = json.load(f)
        doc = doc.get("parsed", doc)
        doc["_round"] = f"r{int(m.group(1)):02d}"
        rounds.append((int(m.group(1)), doc))
    return [d for _, d in sorted(rounds, key=lambda t: t[0])]


def render(report: GateReport, verbose: bool = False) -> str:
    lines = [f"{'metric':<36} {'status':<10} detail"]
    for v in report.verdicts:
        if not verbose and v.status == "pass":
            continue
        lines.append(f"{v.metric:<36} {v.status:<10} {v.detail}")
    n = {s: sum(1 for v in report.verdicts if v.status == s)
         for s in ("pass", "fail", "bootstrap", "skipped")}
    lines.append(
        f"perf gate: {n['pass']} pass, {n['fail']} FAIL, "
        f"{n['bootstrap']} bootstrap, {n['skipped']} skipped"
    )
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--dir", default=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))),
        help="repo dir holding BENCH_r*.json (default: this repo)",
    )
    ap.add_argument(
        "--candidate", default=None,
        help="candidate bench JSON (default: the highest committed "
        "round; the rounds before it form the trajectory)",
    )
    ap.add_argument("--json", action="store_true")
    ap.add_argument("-v", "--verbose", action="store_true",
                    help="also list passing metrics")
    args = ap.parse_args(argv)

    rounds = load_rounds(args.dir)
    if args.candidate:
        with open(args.candidate) as f:
            cand = json.load(f)
        cand = cand.get("parsed", cand)
        cand.setdefault("_round", os.path.basename(args.candidate))
        trajectory = rounds
    else:
        if not rounds:
            print("no BENCH_r*.json rounds found — nothing to gate "
                  "(bootstrap)", file=sys.stderr)
            return 0
        cand, trajectory = rounds[-1], rounds[:-1]

    report = gate(trajectory, cand)
    if args.json:
        print(report.to_json())
    else:
        print(f"candidate {cand.get('_round')} vs "
              f"{len(trajectory)} prior round(s)")
        print(render(report, verbose=args.verbose))
    return 0 if report.ok else 1


if __name__ == "__main__":
    sys.exit(main())
