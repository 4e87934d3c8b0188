"""BENCHMARK.json and the files it names: everything loads, names only
what is declared, and a cell, a configuration and a per-layer metric are
added as new files, with no edit to a file that is there."""

import glob
import json
import os
import re
import shutil

import pytest

from benchmark import harness

BENCH = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
WIDTHS = ("hidden_size", "intermediate_size", "num_attention_heads",
          "num_key_value_heads", "vocab_size", "rope_theta", "rms_norm_eps")
PUBLISHED = {
    "mistralai/Mistral-7B-v0.3": dict(
        hidden_size=4096, intermediate_size=14336, num_attention_heads=32,
        num_key_value_heads=8, vocab_size=32768, rope_theta=1e6,
        rms_norm_eps=1e-5, layers=32),
    "deepseek-ai/deepseek-llm-7b-base": dict(
        hidden_size=4096, intermediate_size=11008, num_attention_heads=32,
        num_key_value_heads=32, vocab_size=102400, rope_theta=1e4,
        rms_norm_eps=1e-6, layers=30),
}


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) < 65536
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    for m in BENCH["end_to_end"]:
        assert 0 < m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_keeps_published_widths(entry):
    assert NAME.match(entry["name"]) and len(entry["why"]) <= 200
    conf = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
    pub = next(v for k, v in PUBLISHED.items() if k in entry["source"])
    assert conf["source"] == entry["source"]
    for key in WIDTHS:
        assert conf[key] == pub[key], key
    assert entry["reduced"] == sorted(conf["reduced"]) == ["num_hidden_layers"]
    assert conf["published"]["num_hidden_layers"] == pub["layers"]
    assert conf["num_hidden_layers"] < pub["layers"]
    assert "assumed" in conf
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("entry", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_loads_and_reports_what_it_declares(entry):
    assert NAME.match(entry["name"]) and NAME.match(entry["traffic"])
    assert len(entry["why"]) <= 200 and entry["chips"] in (1, 4)
    cell = harness.Cell(entry["name"])
    assert cell.spec["why"] and cell.traffic["what"]
    assert os.path.exists(os.path.join(
        harness.ROOT, "benchmark", "kinds", cell.kind + ".py"))
    e2e = {m["name"] for m in cell.end_to_end()}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer(), "a cell reports at least one per-layer metric"
    for m in cell.per_layer():
        assert m["moves"] in e2e, (m["name"], "moves a metric the cell lacks")


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader_and_names_declared_things(metric):
    assert NAME.match(metric["name"])
    assert set(metric) <= {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric["workloads"]) <= cells
    path = os.path.join(harness.ROOT, "benchmark", "metrics",
                        metric["name"] + ".py")
    assert callable(harness.load_module(path).read)
    if metric["name"].endswith("_roofline") or "_roofline." in metric["name"]:
        assert metric["unit"] == "%"


def test_every_file_is_named_and_used():
    named = {os.path.basename(c["file"]) for c in BENCH["configs"]}
    assert {os.path.basename(p) for p in glob.glob(
        os.path.join(harness.ROOT, "benchmark", "configs", "*.json"))} == named
    cells = {w["name"] + ".json" for w in BENCH["workloads"]}
    assert {os.path.basename(p) for p in glob.glob(
        os.path.join(harness.ROOT, "benchmark", "workloads", "*.json"))} == cells
    readers = {m["name"] + ".py" for m in BENCH["per_layer"]}
    assert {os.path.basename(p) for p in glob.glob(
        os.path.join(harness.ROOT, "benchmark", "metrics", "*.py"))} == readers


def test_a_cell_a_configuration_and_a_metric_are_added_as_new_files(tmp_path):
    """What a later PR does: copy nothing, edit nothing, add files and
    entries. The harness finds all three by name."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(harness.ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: open(p, "rb").read() for p in glob.glob(
        os.path.join(root, "benchmark", "**", "*.*"), recursive=True)}
    conf = harness.load_json(os.path.join(
        root, "benchmark", "configs", "deepseek7b-L12.json"))
    conf["num_hidden_layers"] = 6
    with open(os.path.join(root, "benchmark", "configs", "deepseek7b-L6.json"), "w") as f:
        json.dump(conf, f)
    with open(os.path.join(root, "benchmark", "traffic", "decode-short.json"), "w") as f:
        json.dump({"what": "short answers", "loop": "closed", "clients": 4,
                   "cycle": 8,
                   "prompt": {"median": 64, "sigma": 0.5, "lo": 16, "hi": 128},
                   "output": {"median": 32, "sigma": 0.5, "lo": 8, "hi": 64}}, f)
    with open(os.path.join(root, "benchmark", "workloads", "deepseek7b.decode-short.json"), "w") as f:
        json.dump({"kind": "serve", "why": "a later PR's cell",
                   "engine": {"max_slots": 4, "max_len": 256},
                   "check_requests": 2,
                   "limits": {"served_token_gap_max": 1.0,
                              "served_token_gap_mean": 1.0}}, f)
    with open(os.path.join(root, "benchmark", "metrics", "steps_per_token.py"), "w") as f:
        f.write("def read(run):\n"
                "    c = run['counters']\n"
                "    return c['engine_steps'] / c['tokens'] if c.get('tokens') else None\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": "deepseek7b-L6", "source": conf["source"],
        "file": "benchmark/configs/deepseek7b-L6.json",
        "reduced": ["num_hidden_layers"], "why": "a later PR's configuration"})
    bench["workloads"].append({
        "name": "deepseek7b.decode-short", "config": "deepseek7b-L6",
        "traffic": "decode-short", "chips": 1, "why": "a later PR's cell"})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append("deepseek7b.decode-short")
    bench["per_layer"].append({
        "name": "steps_per_token", "unit": "steps/token", "better": "lower",
        "source": "program_counter", "layer": "serving engine",
        "moves": "serve_tokens_per_s",
        "workloads": ["deepseek7b.decode-short"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cell = harness.Cell("deepseek7b.decode-short", root=root)
    assert cell.config["num_hidden_layers"] == 6 and cell.kind == "serve"
    assert [m["name"] for m in cell.per_layer()] == ["steps_per_token"]
    run = {"counters": {"engine_steps": 30, "tokens": 60}}
    assert harness.read_per_layer(cell, run) == {
        "steps_per_token": {"value": 0.5, "unit": "steps/token"}}
    # a reader that finds nothing to read is left out of the line
    assert harness.read_per_layer(cell, {"counters": {}}) == {}
    for p, data in before.items():
        assert open(p, "rb").read() == data, f"{p} was edited"
