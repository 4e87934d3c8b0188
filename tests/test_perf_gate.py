"""Perf-regression gate (scripts/perf_gate.py): synthetic improving /
regressing / noisy trajectories, the empty-trajectory bootstrap,
sentinel and config-mismatch skipping, and an on-disk BENCH_r*
trajectory loaded the way the CI phase-8 invocation loads one (a
synthetic one in tmp_path: the rounds once committed here came from an
earlier installation and are deleted). jax-free."""

import json
import os

from scripts.perf_gate import (
    METRICS,
    MetricSpec,
    gate,
    load_rounds,
    main,
    render,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SPECS = {
    "tps": MetricSpec(+1, 0.10, "config"),
    "stall_s": MetricSpec(-1, 0.20),
}


def r(tps=None, stall=None, config="c1", rnd="r?"):
    d = {"_round": rnd, "config": config}
    if tps is not None:
        d["tps"] = tps
    if stall is not None:
        d["stall_s"] = stall
    return d


def verdict(report, metric):
    return next(v for v in report.verdicts if v.metric == metric)


def test_improving_trajectory_passes():
    traj = [r(tps=100, stall=2.0, rnd="r1"), r(tps=120, stall=1.5, rnd="r2")]
    rep = gate(traj, r(tps=130, stall=1.2), metrics=SPECS)
    assert rep.ok
    v = verdict(rep, "tps")
    assert v.status == "pass" and v.reference == 120 and v.reference_round == "r2"
    assert verdict(rep, "stall_s").reference == 1.5


def test_regression_fails_both_directions():
    traj = [r(tps=100, stall=1.0, rnd="r1")]
    rep = gate(traj, r(tps=80, stall=1.5), metrics=SPECS)
    assert not rep.ok
    assert {v.metric for v in rep.failed} == {"tps", "stall_s"}
    # renders the failures
    assert "FAIL" in render(rep)


def test_noise_within_tolerance_passes():
    traj = [r(tps=100, stall=1.0, rnd="r1")]
    rep = gate(traj, r(tps=91, stall=1.19), metrics=SPECS)  # -9% / +19%
    assert rep.ok, [v.detail for v in rep.failed]


def test_empty_trajectory_bootstraps():
    rep = gate([], r(tps=100, stall=1.0), metrics=SPECS)
    assert rep.ok
    assert {v.status for v in rep.verdicts} == {"bootstrap"}


def test_sentinel_values_are_skipped_not_passed():
    traj = [r(tps=100, rnd="r1")]
    cand = r(stall=1.0)
    cand["tps"] = -1.0  # the bench's failed-measurement sentinel
    rep = gate(traj, cand, metrics=SPECS)
    assert verdict(rep, "tps").status == "skipped"
    # a sentinel PRIOR is ignored too — never a reference of -1
    traj2 = [r(rnd="r1"), r(tps=100, rnd="r2")]
    traj2[0]["tps"] = -1.0
    rep2 = gate(traj2, r(tps=95), metrics=SPECS)
    v = verdict(rep2, "tps")
    assert v.status == "pass" and v.reference == 100


def test_config_mismatch_is_incomparable():
    # a big "regression" vs a DIFFERENT measurement config bootstraps
    traj = [r(tps=100000, config="old", rnd="r1")]
    rep = gate(traj, r(tps=100, config="new"), metrics=SPECS)
    assert verdict(rep, "tps").status == "bootstrap"
    # the real shape: a first round that predates llama_config
    traj2 = [{"_round": "r1", "tps": 100000}]  # no config key at all
    rep2 = gate(traj2, r(tps=100, config="new"), metrics=SPECS)
    assert verdict(rep2, "tps").status == "bootstrap"


def write_synthetic_trajectory(dirpath, n_rounds=5):
    """Write ``BENCH_r01..rNN.json`` in the on-disk shape the gate loads
    (``{"parsed": {...}}``): a slowly improving trajectory of made-up
    values — round k is 1% better than round k-1 on every metric — with
    the first round predating ``llama_config``, like the first recorded
    round did. No number here was measured anywhere. Also what
    scripts/run_tests.sh phase 8 feeds the CLI."""
    base = {
        "value": 1000.0, "llama_tokens_per_sec_per_chip": 100.0,
        "mfu": 0.5, "long_mfu": 0.4, "decode_tokens_per_sec": 200.0,
        "decode_pct_peak_bw": 0.8, "prefill_s": 0.2,
        "reshard_stall_s": 0.1, "reshard_stall_host_fallback_s": 5.0,
        "p2p_bw_gbs": 1.0, "host_stage_bw_gbs": 0.5,
    }
    for k in range(1, n_rounds + 1):
        doc = {
            name: v * (1.01 ** k if METRICS[name].direction > 0
                       else 0.99 ** k)
            for name, v in base.items()
        }
        doc["decode_config"] = "synthetic-decode"
        if k > 1:
            doc["llama_config"] = "synthetic-llama"
        path = os.path.join(str(dirpath), f"BENCH_r{k:02d}.json")
        with open(path, "w") as f:
            json.dump({"parsed": doc}, f)


def test_loaded_trajectory_passes_and_synthetic_regression_fails(tmp_path):
    write_synthetic_trajectory(tmp_path)
    rounds = load_rounds(str(tmp_path))
    assert len(rounds) >= 5, "synthetic BENCH_r*.json rounds missing"
    assert [d["_round"] for d in rounds] == [f"r{k:02d}" for k in range(1, 6)]
    cand, traj = rounds[-1], rounds[:-1]
    rep = gate(traj, cand)
    assert rep.ok, [v.detail for v in rep.failed]
    # the gate is not vacuous: >= 8 real comparisons happened
    assert sum(1 for v in rep.verdicts if v.status == "pass") >= 8
    # a synthetically-regressed last round (MFU -30%, CTR -30%) must FAIL
    bad = dict(cand)
    bad["mfu"] = cand["mfu"] * 0.7
    bad["value"] = cand["value"] * 0.7
    rep2 = gate(traj, bad)
    assert {v.metric for v in rep2.failed} >= {"mfu", "value"}


def test_cli_main_json_and_exit_codes(tmp_path, capsys):
    write_synthetic_trajectory(tmp_path)
    assert main(["--dir", str(tmp_path), "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["ok"] is True
    # a regressed candidate file fails with exit 1
    rounds = load_rounds(str(tmp_path))
    bad = dict(rounds[-1])
    bad["mfu"] = bad["mfu"] * 0.5
    p = tmp_path / "cand_bad.json"
    p.write_text(json.dumps({"parsed": bad}))
    assert main(["--dir", str(tmp_path), "--candidate", str(p)]) == 1
    # an empty directory bootstraps (what the repo root is until the
    # benchmark records rounds on today's machine)
    empty = tmp_path / "empty"
    empty.mkdir()
    assert main(["--dir", str(empty)]) == 0


def test_gated_catalog_covers_the_headline_metrics():
    for name in ("value", "mfu", "decode_pct_peak_bw",
                 "reshard_stall_s", "p2p_bw_gbs", "serving_goodput_rps"):
        assert name in METRICS
    # direction sanity: stalls are lower-better
    assert METRICS["reshard_stall_s"].direction == -1
    assert METRICS["mfu"].direction == +1
