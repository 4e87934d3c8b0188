"""Each kind of cell driven end to end at toy size by the benchmark's
own ``--rehearse``: the last line has exactly the contract's keys, a
run without the chip gives no result, and a timed path that is broken
underneath comes out not correct."""

import json

import pytest

from benchmark import run

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def rehearse(capsys, *argv):
    code = run.main(["--rehearse", *argv])
    lines = capsys.readouterr().out.strip().splitlines()
    return code, json.loads(lines[-1]), lines


@pytest.mark.parametrize("cell, trace, reported", [
    ("mistral7b.train-steady", 0, {"train_tokens_per_s_per_chip", "setup_s"}),
    ("mistral7b.train-steady", 1, {"step_ms.train"}),
    ("deepseek7b.decode-closed", 0, {"serve_tokens_per_s", "setup_s"}),
    ("mistral7b.serve-open", 0, {"ttft_p95_ms", "setup_s"}),
    ("mistral7b.serve-open", 1, {"queue_wait_p95_ms", "itl_p95_ms.open",
                                 "prefill_p50_ms"}),
])
def test_last_line_has_the_contracts_keys(capsys, cell, trace, reported):
    code, line, lines = rehearse(
        capsys, "--workload", cell, "--seed", "3000000019", "--seconds", "2",
        "--trace", str(trace))
    assert code == 0
    assert set(line) - {"breakdown"} == KEYS
    assert line["correct"] is True, [l for l in lines if "compared" in l]
    assert line["attempted"] > 0 and line["failed"] == 0
    # a rehearsal's numbers never stand under a device metric's name
    assert set(line["metrics"]) == {"rehearsal." + m for m in reported}
    assert all(set(v) == {"value", "unit"} for v in line["metrics"].values())
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert any(l.startswith("compared ") and "limit" in l for l in lines)


def test_elastic_rehearsal_reshards_and_reports(capsys):
    code, line, lines = rehearse(
        capsys, "--workload", "mistral7b.elastic-424", "--seed", "5",
        "--seconds", "6", "--trace", "0")
    assert code == 0 and set(line) == KEYS
    assert set(line["metrics"]) == {
        "rehearsal.train_tokens_per_s_per_chip", "rehearsal.reshard_stall_s",
        "rehearsal.setup_s"}
    assert line["device"]["count"] == 4
    compared = {l.split()[1].rstrip(":"): l for l in lines
                if l.startswith("compared ")}
    assert compared["leaves_changed_by_a_reshard"].endswith("ok")
    assert compared["parameter_change_norm_gap"].endswith("ok")
    # this test run keeps no compile cache, so each reshard's re-traced
    # step compiles in the window, and the run says so
    assert compared["compilations_in_window"].endswith("FAILED")
    assert line["correct"] is False


def test_no_chip_no_result(capsys):
    code = run.main(["--workload", "mistral7b.train-steady", "--seed", "1",
                     "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert code != 0 and out.out.strip() == ""
    assert "No result without the chip" in out.err


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        capsys, monkeypatch):
    from edl_tpu.train import trainer

    monkeypatch.setattr(
        trainer, "_apply_update",
        lambda loss_fn, tx, state, batch: (state, loss_fn(state.params, batch)))
    code, line, lines = rehearse(
        capsys, "--workload", "mistral7b.train-steady", "--seed", "7",
        "--seconds", "1", "--trace", "0")
    assert code == 0 and line["correct"] is False
    failed = [l.split()[1].rstrip(":") for l in lines if l.endswith("FAILED")]
    assert "parameter_change_norm_gap" in failed
    assert "first_gradient_norm_gap" in failed


def test_a_token_altered_where_it_is_produced_is_not_correct(
        capsys, monkeypatch):
    from edl_tpu.serving.engine import ContinuousBatchingEngine as Engine

    finish = Engine._finish

    def altered(self, slot, outcome):
        generated = self._slots[slot].generated
        if len(generated) > 2:
            generated[1] = (generated[1] + 1) % self.cfg.vocab
        return finish(self, slot, outcome)

    monkeypatch.setattr(Engine, "_finish", altered)
    code, line, lines = rehearse(
        capsys, "--workload", "deepseek7b.decode-closed", "--seed", "7",
        "--seconds", "2", "--trace", "0")
    assert code == 0 and line["correct"] is False
    assert any(l.startswith("compared served_token_gap_max")
               and l.endswith("FAILED") for l in lines)
