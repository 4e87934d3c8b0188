"""Where does the host lose ~0.1 s in a serving cell's window (PERF.md
section 2: five engine steps of ``kanana2.decode-wide`` in five runs of
twelve, 8-36 of ``deepseek7b.decode-closed``)? One run of the cell as
``benchmark.run`` makes it, with stamps taken around every
``engine.step()`` of the window: wall clock, this thread's CPU time,
its context switches (``RUSAGE_THREAD``) and the collector's runs. A
second thread wakes every 2 ms and keeps its longest sleeps. A stall in
which the engine's thread used the CPU all along is Python's own (the
collector, if its time says so); one in which it did not, and the second
thread overslept too, took the process off the CPU (the machine's
scheduler or a CPU quota: ``cpu.stat``'s ``nr_throttled`` and
``/proc/stat``'s steal seconds are printed before and after); one in
which only the engine's thread waited was inside a call. PR 35's
``brumby14b.decode-state`` runs met the second kind: 0.9-3.9 s in three
runs of twelve, tens of milliseconds in most.
Passive: a dozen clock reads a step.

    python scripts/exp_host_stall.py --workload kanana2.decode-wide \
        --seed 7 --seconds 40
"""

import gc
import json
import os
import resource
import sys
import threading
import time

from benchmark import harness, run

STEPS, BEATS, COLLECTIONS = [], [], []


def cpu_stat():
    out = {}
    for path in ("/sys/fs/cgroup/cpu.stat", "/sys/fs/cgroup/cpu/cpu.stat"):
        try:
            for line in open(path):
                k, v = line.split()
                if "throttled" in k or k == "nr_periods":
                    out[k] = int(v)
        except OSError:
            pass
    try:
        out["pressure"] = open("/proc/pressure/cpu").readline().strip()
    except OSError:
        pass
    try:
        # seconds the hypervisor ran someone else while this machine's
        # CPUs had work (all CPUs together), and the load beside it
        cpu = open("/proc/stat").readline().split()
        out["steal_s"] = int(cpu[8]) / os.sysconf("SC_CLK_TCK")
        out["loadavg"] = open("/proc/loadavg").read().split()[:3]
    except (OSError, IndexError, ValueError):
        pass
    return out


def on_gc(phase, info):
    if phase == "start":
        COLLECTIONS.append([time.perf_counter(), 0.0, info["generation"]])
    else:
        COLLECTIONS[-1][1] = time.perf_counter() - COLLECTIONS[-1][0]


def heartbeat(stop):
    last = time.perf_counter()
    while not stop.is_set():
        time.sleep(0.002)
        now = time.perf_counter()
        if now - last > 0.02:
            BEATS.append((last, now - last))
        last = now


def probed(step):
    def wrapped(self):
        r0 = resource.getrusage(resource.RUSAGE_THREAD)
        t0, c0 = time.perf_counter(), time.thread_time()
        step(self)
        t1, c1 = time.perf_counter(), time.thread_time()
        r1 = resource.getrusage(resource.RUSAGE_THREAD)
        STEPS.append((t0, t1 - t0, c1 - c0, r1.ru_nvcsw - r0.ru_nvcsw,
                      r1.ru_nivcsw - r0.ru_nivcsw))
    return wrapped


def main() -> int:
    kind = harness.load_kind("serve").Kind
    kind.engine_step = probed(kind.engine_step)
    stop = threading.Event()
    before = cpu_stat()
    gc.callbacks.append(on_gc)
    threading.Thread(target=heartbeat, args=(stop,), daemon=True).start()
    rc = run.main(sys.argv[1:])
    stop.set()
    # the window's steps are the last run of them (warm-up runs none
    # through Kind.engine_step)
    t = [s[0] for s in STEPS]
    gaps = sorted(((t[i + 1] - t[i] - STEPS[i][1], t[i]) for i in
                   range(len(t) - 1)), reverse=True)[:3]
    worst = sorted(STEPS, key=lambda s: -s[1])[:6]
    med = sorted(s[1] for s in STEPS)[len(STEPS) // 2]
    print("STALL " + json.dumps({
        "steps": len(STEPS), "median_step_s": med,
        "longest_steps": [
            {"at_s": s[0] - t[0], "wall_s": s[1], "thread_cpu_s": s[2],
             "voluntary_switches": s[3], "involuntary_switches": s[4],
             "collector_s": sum(
                 d for at, d, _ in COLLECTIONS if s[0] <= at <= s[0] + s[1]),
             "heartbeat_overslept_s": max(
                 [d for at, d in BEATS if s[0] - 0.05 <= at <= s[0] + s[1]],
                 default=0.0)} for s in worst],
        "longest_between_steps_s": [g for g, _ in gaps],
        "heartbeat_oversleeps_in_window": len(
            [1 for at, _ in BEATS if t[0] <= at <= t[-1]]),
        "cpu_stat_before": before, "cpu_stat_after": cpu_stat()}))
    return rc


if __name__ == "__main__":
    sys.exit(main())
