"""BENCHMARK.json and the files it names: everything loads, names only
what is declared, every configuration keeps the widths its source
publishes (benchmark/published/) and cuts only what its family allows,
and a cell, a configuration, a per-layer metric and a whole family are
added as new files (tests/benchmark/later_pr/), with no edit to a file
that is there."""

import glob
import json
import os
import re
import shutil

import pytest

from benchmark import harness

BENCH = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
LATER_PR = os.path.join(os.path.dirname(__file__), "later_pr")


def published_for(source, root=harness.ROOT):
    """The one file under benchmark/published/ that records ``source``."""
    found = [p for p in glob.glob(
        os.path.join(root, "benchmark", "published", "*.json"))
        if harness.load_json(p)["source"] == source]
    assert len(found) == 1, (source, found)
    return harness.load_json(found[0])


def size(value):
    """A list (a pattern with one entry a layer) is as large as it is
    long."""
    return len(value) if isinstance(value, list) else value


def check_configuration(entry, bench, root=harness.ROOT):
    """A configuration keeps its source's widths, and what it cuts is
    what its family allows, no further than its floor, and listed."""
    assert NAME.match(entry["name"]) and len(entry["why"]) <= 200
    conf = harness.load_json(os.path.join(root, entry["file"]))
    assert conf["source"] == entry["source"]
    pub = published_for(entry["source"], root)
    family = harness.load_module(os.path.join(
        root, "benchmark", "families", conf["family"] + ".py"))
    for key in family.widths:
        assert conf[key] == pub[key], key
    assert entry["reduced"] == sorted(conf["reduced"])
    assert set(entry["reduced"]) <= set(family.reducible)
    for key, floor in family.reducible.items():
        if key in entry["reduced"]:
            assert conf["published"][key] == pub[key], key
            assert floor <= size(conf[key]) < size(pub[key]), key
        else:
            assert conf[key] == pub[key], (key, "cut and not listed")
    assert "assumed" in conf
    assert any(w["config"] == entry["name"] for w in bench["workloads"])


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
    check_configuration(entry, BENCH)


def test_the_two_sources_that_are_there_are_cut_in_depth_alone():
    """What the closed table of PR 23 asserted beyond the checks above,
    of the files that took its place."""
    layers = {"mistralai/Mistral-7B-v0.3": 32,
              "deepseek-ai/deepseek-llm-7b-base": 30}
    for entry in BENCH["configs"]:
        repo = next((k for k in layers if k in entry["source"]), None)
        if repo is not None:
            assert entry["reduced"] == ["num_hidden_layers"]
            assert published_for(entry["source"])["num_hidden_layers"] \
                == layers[repo]


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
    def files(directory, pattern):
        return {os.path.basename(p) for p in glob.glob(
            os.path.join(harness.ROOT, "benchmark", directory, pattern))}

    confs = [harness.load_json(os.path.join(harness.ROOT, c["file"]))
             for c in BENCH["configs"]]
    assert files("configs", "*.json") == {
        os.path.basename(c["file"]) for c in BENCH["configs"]}
    assert files("workloads", "*.json") == {
        w["name"] + ".json" for w in BENCH["workloads"]}
    assert files("metrics", "*.py") == {
        m["name"] + ".py" for m in BENCH["per_layer"]}
    assert files("families", "*.py") - {"__init__.py"} == {
        c["family"] + ".py" for c in confs}
    sources = {c["source"] for c in BENCH["configs"]}
    assert {harness.load_json(os.path.join(
        harness.ROOT, "benchmark", "published", f))["source"]
        for f in files("published", "*.json")} == sources
    assert len(files("published", "*.json")) == len(sources)


def later_pr_tree(tmp_path):
    """A copy of benchmark/ with a later PR's files laid over it and its
    entries appended to a copy of BENCHMARK.json. Returns (root, the new
    BENCHMARK.json, {path: bytes} of every file that was there)."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(harness.ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: open(p, "rb").read() for p in glob.glob(
        os.path.join(root, "benchmark", "**", "*.*"), recursive=True)}
    added = glob.glob(os.path.join(LATER_PR, "benchmark", "**", "*.*"),
                      recursive=True)
    for p in added:
        to = os.path.join(root, os.path.relpath(p, LATER_PR))
        assert not os.path.exists(to), f"{to} is there: a later PR adds"
        shutil.copy(p, to)
    bench = json.loads(json.dumps(BENCH))
    entries = harness.load_json(os.path.join(LATER_PR, "entries.json"))
    for section, more in entries.items():
        bench[section].extend(more)
    for m in bench["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"] += [w["name"] for w in entries["workloads"]]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root, bench, before


def test_a_cell_a_configuration_and_a_metric_are_added_as_new_files(tmp_path):
    """What a later PR does: copy nothing, edit nothing, add files and
    entries. The harness finds all three by name."""
    root, bench, before = later_pr_tree(tmp_path)
    cell = harness.Cell("deepseek7b.decode-short", root=root)
    assert cell.config["num_hidden_layers"] == 6 and cell.kind == "serve"
    assert cell.family.__file__ == os.path.join(
        root, "benchmark", "families", "decoder.py")
    assert [m["name"] for m in cell.per_layer()] == ["steps_per_token"]
    run = {"counters": {"engine_steps": 30, "tokens": 60}}
    assert harness.read_per_layer(cell, run) == {
        "steps_per_token": {"value": 0.5, "unit": "steps/token"}}
    # a reader that finds nothing to read is left out of the line
    assert harness.read_per_layer(cell, {"counters": {}}) == {}
    check_configuration(bench["configs"][-2], bench, root)
    for p, data in before.items():
        assert open(p, "rb").read() == data, f"{p} was edited"


def test_a_family_is_added_as_new_files(tmp_path):
    """The proof a later ``model_config`` PR rests on: a family whose
    parameter tree is not the decoder's, its published file, a
    configuration reduced in two keys and a cell on it load, rehearse
    and are priced by the family's own ``needed``, with no edit to a
    file that was there."""
    import jax
    import jax.numpy as jnp

    root, bench, before = later_pr_tree(tmp_path)
    entry = next(c for c in bench["configs"] if c["name"] == "sliced24-L6-V8k")
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    check_configuration(entry, bench, root)

    cell = harness.Cell("sliced24.decode-short", root=root)
    family = cell.family
    assert family.__file__ == os.path.join(
        root, "benchmark", "families", "sliced.py")
    assert cell.config["vocab_size"] == 8192 and "deployment" in cell.config
    assert cell.layout[("global", "mix")] == ((2, 512, 512), 512 ** -0.5, True)
    assert family.program_config(cell.config, training=False).layers == (4, 2)

    # a reader that asks the cell's family prices the family's own step
    run = {"cell": cell, "config": cell.config,
           "counters": {"engine_steps": 30, "tokens": 60,
                        "resident_tokens_mean": 100.0}}
    need = 2 * (4 * 2 * 512 * 2048 + 2 * 512 * 512 + 512 * 8192) \
        + 100 * 2 * 512 * 2
    assert harness.read_per_layer(cell, run) == {
        "steps_per_token": {"value": 0.5, "unit": "steps/token"},
        "needed_step_mb": {"value": need / 1e6, "unit": "MB"}}
    decoder_need = harness.Cell("deepseek7b.decode-short", root=root) \
        .family.needed.decode_step_bytes(cell.config | {
            "num_attention_heads": 4, "num_key_value_heads": 4}, 100.0)
    assert decoder_need != need

    # the rehearsal swaps in the new family's tiny widths, and the
    # generic machinery builds its tree: three levels deep, two stacked
    # groups of different leading lengths
    cell.for_rehearsal()
    assert cell.config == family.rehearsal_config()
    params = harness.make_params(5, cell.layout, jnp.float32)
    shapes = {jax.tree_util.keystr(p): leaf.shape for p, leaf in
              jax.tree_util.tree_flatten_with_path(params)[0]}
    assert shapes == {
        "['embed']": (128, 32), "['head']['norm']": (32,),
        "['head']['out']": (32, 128), "['global']['mix']": (3, 32, 32),
        "['local']['mlp']['up']": (3, 32, 64),
        "['local']['mlp']['down']": (3, 64, 32), "['local']['norm']": (3, 32)}
    assert bool(jnp.all(params["local"]["norm"] == 1.0))
    up = params["local"]["mlp"]["up"]
    assert not bool(jnp.all(up[0] == up[1])), "each layer has its own draw"
    assert float(jnp.std(up)) == pytest.approx(32 ** -0.5, rel=0.05)
    again = harness.make_params(5, cell.layout, jnp.float32)
    assert bool(jnp.all(again["global"]["mix"] == params["global"]["mix"]))
    # one leaf drawn alone is the tree's (what change_sumsq relies on)
    alone = jax.jit(lambda k: harness.initial_leaf(
        k, cell.layout, ["global", "mix"], jnp.float32))(harness.seed_key(5))
    assert bool(jnp.all(alone == params["global"]["mix"]))

    for p, data in before.items():
        assert open(p, "rb").read() == data, f"{p} was edited"


def test_a_configuration_without_a_family_is_an_error_that_says_so(tmp_path):
    root, bench, _ = later_pr_tree(tmp_path)
    path = os.path.join(root, "benchmark", "configs", "sliced24-L6-V8k.json")
    conf = harness.load_json(path)
    with open(path, "w") as f:
        json.dump(dict(conf, family="absent"), f)
    with pytest.raises(FileNotFoundError, match="benchmark/families/absent.py"):
        harness.Cell("sliced24.decode-short", root=root)
    del conf["family"]
    with open(path, "w") as f:
        json.dump(conf, f)
    with pytest.raises(KeyError, match="names no .family."):
        harness.Cell("sliced24.decode-short", root=root)
