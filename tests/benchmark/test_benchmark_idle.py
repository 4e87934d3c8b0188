"""benchmark/reduce/idle.py and the twelve readers built on it, on a
hand-made fixture of their own, against numbers worked out by hand.

The fixture's window is 100000 ns (so a nanosecond is 0.001 of a point)
and ends at 101000. Chip 0 is idle 19500 ns of it, chip 1 13000:

    chip 0  1000- 4000  step 1000, admit 500 + 400, queue 100, prefill 1000
            9000-10000  inside the prefill's module event: in a program
           16000-26000  prefill 2000, admit 2000, step 1000, account 2000,
                        step 500, account 1500, dispatch 1000
           40000-40500  inside a block's module event: in a program
           96000-101000 replay 4000, step 1000
    chip 1 42000-46000  under no annotation 3000, step 500, account 500
           70000-72000  in a program
           94000-101000 drain 1000, replay 5000, step 1000
"""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import harness
from benchmark.reduce import idle, program

FIXTURE = os.path.join(os.path.dirname(idle.__file__), "fixture_idle.json")
WINDOW_S = 100e-6
SERVE = ["deepseek7b.decode-closed", "kanana2.decode-wide",
         "brumby14b.decode-state", "granite4h.decode-hybrid"]
OPEN = ["mistral7b.serve-open"]
TRAIN = ["mistral7b.train-steady", "mistral7b.elastic-424"]
# name: (layer, moves, workloads, percent of the fixture's window)
ENTRIES = {
    "idle_in_program_share.serve": (
        "model programs", "serve_tokens_per_s", SERVE, 1.75),
    "idle_admit_share.serve": (
        "serving engine", "serve_tokens_per_s", SERVE, 3.0),
    "idle_dispatch_share.serve": (
        "serving engine", "serve_tokens_per_s", SERVE, 2.5),
    "idle_drain_share.serve": (
        "serving engine", "serve_tokens_per_s", SERVE, 5.0),
    "idle_step_other_share.serve": (
        "serving engine", "serve_tokens_per_s", SERVE, 4.0),
    "idle_offcpu_share.serve": (
        "serving engine", "serve_tokens_per_s", SERVE, 2.75),
    "idle_in_program_share.open": (
        "model programs", "ttft_p95_ms", OPEN, 1.75),
    "idle_host_share.open": ("serving engine", "ttft_p95_ms", OPEN, 14.5),
    "idle_offcpu_share.open": ("serving engine", "ttft_p95_ms", OPEN, 2.75),
    "idle_in_program_share.train": (
        "model programs", "train_tokens_per_s_per_chip", TRAIN, 1.75),
    "idle_host_share.train": (
        "trainer", "train_tokens_per_s_per_chip", TRAIN, 14.5),
    "idle_offcpu_share.train": (
        "trainer", "train_tokens_per_s_per_chip", TRAIN, 2.75),
}
PARTS = {
    ".serve": ["idle_in_program_share.serve", "idle_admit_share.serve",
               "idle_dispatch_share.serve", "idle_drain_share.serve",
               "idle_step_other_share.serve"],
    ".open": ["idle_in_program_share.open", "idle_host_share.open"],
    ".train": ["idle_in_program_share.train", "idle_host_share.train"],
}
# readers that say nothing of a program without this PR's spans
NEED_THE_NEW_SPANS = {
    "idle_dispatch_share.serve", "idle_drain_share.serve",
    "idle_step_other_share.serve", "idle_offcpu_share.serve",
    "idle_offcpu_share.open", "idle_offcpu_share.train"}


@pytest.fixture(scope="module")
def planes():
    with open(FIXTURE) as f:
        raw = json.load(f)["planes"]
    return {p: {l: [tuple(e) for e in evs] for l, evs in lines.items()}
            for p, lines in raw.items()}


def span(seq, name, dur_us, cpu_us, parent):
    return types.SimpleNamespace(
        seq=seq, name=name, start_s=0.0, dur_s=dur_us * 1e-6,
        cpu_s=None if cpu_us is None else cpu_us * 1e-6, parent=parent,
        attrs={})


# the ring of the process that wrote the fixture's driving line. A
# span's own time is its duration less its children's: step 10 has 4.5
# us of its own and was on a CPU for all of them (12.6 - 8.1), admit 11
# 3 us of which 1.5 on a CPU (the queue span, recorded after the fact,
# times nothing and takes nothing off), account 14 was off its CPU from
# end to end, replay 25 for a fifth, account 21 for half. The waits
# (prefill 13, drains 17 and 24) are off their CPU by design.
RING = {s.seq: s for s in [
    span(10, "serving.step", 40, 12.6, 0),
    span(11, "serving.admit", 18, 2.5, 10),
    span(12, "serving.queue", 500, None, 11),
    span(13, "serving.prefill", 15, 1, 11),
    span(14, "serving.account", 2, 0, 10),
    span(15, "serving.account", 1.5, 1.5, 10),
    span(16, "serving.dispatch", 2, 2, 10),
    span(17, "serving.drain", 10, 0.1, 10),
    span(18, "serving.replay", 2, 2, 10),
    span(19, "checkpoint.save_shards", 200, 1, 0),
    span(20, "serving.step", 56, 9.45, 0),
    span(21, "serving.account", 1.5, 0.75, 20),
    span(22, "serving.account", 1, 1, 20),
    span(23, "serving.dispatch", 2, 2, 20),
    span(24, "serving.drain", 45, 0.2, 20),
    span(25, "serving.replay", 5, 4, 20),
]}


def ns(seconds):
    return round(seconds * 1e9, 3)


def test_innermost_annotation_owns_each_instant(planes):
    line = idle.driving_line(planes)
    assert line is planes["/host:CPU"]["python3"]  # not the writer's
    owners = idle.innermost(line)
    first = [(s, e, name) for s, e, name, _ in owners if e <= 28000]
    assert first == [
        (1000, 2000, "serving.step"), (2000, 2500, "serving.admit"),
        (2500, 2600, "serving.queue"), (2600, 3000, "serving.admit"),
        (3000, 18000, "serving.prefill"), (18000, 20000, "serving.admit"),
        (20000, 21000, "serving.step"), (21000, 23000, "serving.account"),
        (23000, 23500, "serving.step"), (23500, 25000, "serving.account"),
        (25000, 27000, "serving.dispatch"), (27000, 28000, "serving.step")]
    # pieces never overlap, and a step's pieces and its children's
    # together are the step
    for a, b in zip(owners, owners[1:]):
        assert a[1] <= b[0]
    assert sum(e - s for s, e, _, _ in owners) == 40000 + 56000
    assert {seq for _, _, name, seq in owners
            if name == "serving.account"} == {14, 15, 21, 22}


def test_gaps_are_split_by_overlap_and_averaged_over_chips(planes):
    found = idle.split(planes, WINDOW_S, RING)
    assert found["chips_traced"] == 2
    assert ns(found["window_s"]) == 100000
    # (80500 + 87000) / 2 inside; 9000 after it on chip 0, 500 before
    # it on chip 1
    assert ns(found["busy_s"]) == 83750
    assert ns(found["busy_outside_s"]) == 4750
    # inside a module event and blamed on no span: (1000 + 500 + 2000) / 2
    assert ns(found["in_program_s"]) == 1750
    by = {k: ns(v) for k, v in found["by_span"].items()}
    assert by == {
        "serving.step": 2500,      # (1000 + 1000 + 500 + 1000 + 1500) / 2
        "serving.admit": 1450,     # (500 + 400 + 2000) / 2, less prefill's
        "serving.queue": 50,
        "serving.prefill": 1500,   # (1000 + 2000) / 2: the innermost wins
        "serving.account": 2000,   # (2000 + 1500 + 500) / 2
        "serving.dispatch": 500,   # 26000 on: the block runs
        "serving.drain": 500,
        "serving.replay": 4500,    # (4000 + 5000) / 2
        idle.CALLER: 1500,         # 42000-45000 on chip 1, no span open
    }
    # nothing counted twice or lost
    total = found["in_program_s"] + sum(found["by_span"].values())
    assert ns(total) == ns(found["window_s"] - found["busy_s"]) == 16250
    # the other thread's annotation covers everything and owns nothing
    assert "checkpoint.save_shards" not in found["by_span"]


def test_off_cpu_is_an_overlay_of_the_host_rows(planes):
    found = idle.split(planes, WINDOW_S, RING)
    assert found["has_cpu"]
    off = {k: ns(v) for k, v in found["offcpu"].items() if ns(v)}
    assert off == {
        "serving.admit": 725,     # 2900 x (1 - 1.5 / 3) / 2
        "serving.account": 1125,  # (2000 x 1 + 1500 x 0 + 500 x 0.5) / 2
        "serving.replay": 900,    # (4000 + 5000) x (1 - 4 / 5) / 2
    }
    # the waits are left out whatever their cpu_s says, and a span
    # recorded after the fact has none
    for name in ("serving.drain", "serving.prefill", "serving.queue"):
        assert name not in found["offcpu"]
    assert sum(found["offcpu"].values()) <= sum(found["by_span"].values())
    # a ring without the fields (the parent's): no overlay, the same split
    old = {k: types.SimpleNamespace(seq=s.seq, name=s.name, dur_s=s.dur_s)
           for k, s in RING.items()}
    bare = idle.split(planes, WINDOW_S, old)
    assert not bare["has_cpu"] and bare["offcpu"] == {}
    assert bare["by_span"] == found["by_span"]


def test_own_time_off_cpu_takes_the_children_off():
    off = idle.own_off_cpu(RING)
    assert off[10] == pytest.approx(0.0)   # 1 - (12.6 - 8.1) / (40 - 35.5)
    assert off[11] == pytest.approx(0.5)   # 1 - (2.5 - 1) / (18 - 15)
    assert off[14] == 1.0 and off[21] == pytest.approx(0.5)
    assert off[25] == pytest.approx(0.2)
    assert not {12, 13, 17, 24} & set(off)
    # what the machine's CPU clock cannot resolve is taken off first: at
    # 1 us of slack account 14 (2 us, none on a CPU) is known to have
    # been off for 1 us, replay 25 (5 us, 4 on) for none
    slack = idle.own_off_cpu(RING, 1e-6)
    assert slack[14] == pytest.approx(0.5) and slack[25] == 0.0
    assert slack[11] == pytest.approx(0.5 / 3)
    assert idle.cpu_slack() > 0 and idle.cpu_slack() == idle.cpu_slack()
    own = idle.own_times(RING)
    assert own[10] == pytest.approx([4.5e-6, 4.5e-6])  # serving.step's
    assert own[20] == pytest.approx([1.5e-6, 1.5e-6])  # self time


def test_without_the_stopwatch_the_window_is_the_driving_lines(planes):
    # first annotation 1000 to last annotation 101000: the same window
    assert idle.split(planes)["by_span"] == idle.split(
        planes, WINDOW_S)["by_span"]
    # a shorter window leaves out what lies before it
    late = idle.split(planes, 50e-6)
    assert ns(late["window_s"]) == 50000
    assert {k: ns(v) for k, v in late["by_span"].items()} == {
        "serving.drain": 500, "serving.replay": 4500, "serving.step": 1000}
    assert ns(late["in_program_s"]) == 1000


# -- the twelve readers -----------------------------------------------------


def reader(name):
    return harness.load_module(os.path.join(
        harness.ROOT, "benchmark", "metrics", name + ".py"))


def a_run():
    return {"cell": types.SimpleNamespace(name="no-such-cell"),
            "trace": {"window_s": WINDOW_S}, "spans": {}, "counters": {}}


def test_the_entries_are_found_by_name():
    per_layer = {m["name"]: m for m in harness.load_json(os.path.join(
        harness.ROOT, "BENCHMARK.json"))["per_layer"]}
    for name, (layer, moves, workloads, _) in ENTRIES.items():
        assert per_layer[name] == {
            "name": name, "unit": "%", "better": "lower",
            "source": "program_span", "layer": layer, "moves": moves,
            "workloads": workloads}


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_reader_on_the_fixture(name, planes, monkeypatch):
    monkeypatch.setattr(program, "planes_of", lambda run: planes)
    monkeypatch.setattr(program, "ring", lambda: (RING, 0.0))
    # the fixture's spans last microseconds: a CPU clock that resolves
    # them
    monkeypatch.setattr(idle, "cpu_slack", lambda: 0.0)
    assert reader(name).read(a_run()) == pytest.approx(ENTRIES[name][3])
    if "offcpu" in name:
        # on this machine's clock (a reading carried forward for a
        # millisecond, at least) spans so short say nothing
        monkeypatch.undo()
        monkeypatch.setattr(program, "planes_of", lambda run: planes)
        monkeypatch.setattr(program, "ring", lambda: (RING, 0.0))
        assert reader(name).read(a_run()) == 0.0


@pytest.mark.parametrize("suffix", sorted(PARTS))
def test_the_parts_add_up_to_the_idle_share(suffix, planes, monkeypatch):
    monkeypatch.setattr(program, "planes_of", lambda run: planes)
    monkeypatch.setattr(program, "ring", lambda: (RING, 0.0))
    monkeypatch.setattr(idle, "cpu_slack", lambda: 0.0)
    run = a_run()
    parts = [reader(name).read(run) for name in PARTS[suffix]]
    assert sum(parts) == pytest.approx(100 * (1 - 83750 / 100000))
    host = sum(parts[1:])
    assert 0 < reader("idle_offcpu_share" + suffix).read(run) <= host


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_reader_finds_nothing_without_a_trace_or_a_device(
        name, planes, monkeypatch):
    monkeypatch.setattr(program, "ring", lambda: (RING, 0.0))
    # --trace 0: no trace was read, and none lies under the cell's name
    run = a_run()
    run["trace"] = None
    assert reader(name).read(run) is None
    assert reader(name).read(a_run()) is None
    # a CPU rehearsal: the program's annotations and no device plane
    host_only = {"/host:CPU": planes["/host:CPU"]}
    monkeypatch.setattr(program, "planes_of", lambda run: host_only)
    assert reader(name).read(a_run()) is None
    # a device and no step annotation: nobody to ask
    no_step = {p: lines for p, lines in planes.items() if p != "/host:CPU"}
    monkeypatch.setattr(program, "planes_of", lambda run: no_step)
    assert reader(name).read(a_run()) is None


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_reader_on_a_program_without_the_new_spans(
        name, planes, monkeypatch):
    """The parent's program: no ``serving.account`` or ``serving.replay``
    annotation, ring spans without ``cpu_s`` and ``parent``. What needs
    them reads None; what does not reads what it reads of any program."""
    old = dict(planes)
    old["/host:CPU"] = {"python3": [
        ev for ev in planes["/host:CPU"]["python3"]
        if ev[0] not in ("edl.serving.account", "edl.serving.replay")]}
    ring = {k: types.SimpleNamespace(seq=s.seq, name=s.name, dur_s=s.dur_s)
            for k, s in RING.items() if s.name not in (
                "serving.account", "serving.replay")}
    monkeypatch.setattr(program, "planes_of", lambda run: old)
    monkeypatch.setattr(program, "ring", lambda: (ring, 0.0))
    value = reader(name).read(a_run())
    if name in NEED_THE_NEW_SPANS:
        assert value is None
    else:
        # account's and replay's time falls to the step that holds them
        assert value == pytest.approx(ENTRIES[name][3])


def test_the_table_by_hand(planes, tmp_path):
    text = idle.describe(idle.split(planes, WINDOW_S, RING))
    assert "idle 16.2500%" in text and "(in a program)" in text
    rows = {line.split()[0]: line for line in text.splitlines()[1:]}
    assert "4.5000%" in rows["serving.replay"]
    assert "off CPU 0.000001s" in rows["serving.replay"]
    assert "16.2500%" in rows["sum"]
    # the module's own entry point, on a directory that holds no trace
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.reduce.idle", str(tmp_path)],
        cwd=harness.ROOT, capture_output=True, text=True)
    assert out.returncode != 0
