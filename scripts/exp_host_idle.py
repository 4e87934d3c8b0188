"""Who owns the idle device in a cell's traced window, and what the ring
costs: one run of a cell as ``benchmark.run`` makes it, in this process,
so that the tracer's ring is still here when the run has printed its
line.

After a ``--trace 1`` run it prints ``benchmark/reduce/idle.py``'s table
(idle seconds by span, in a program, off CPU), how many of the trace's
``edl.*`` annotations join a ring span by ``seq``, the self time of
``serving.step`` / ``train.step`` (its duration less its children by
``parent``) and the spans' own medians, and with ``--dump`` writes what
``idle.py`` read (each chip's merged operations and module events, the
driving line's annotations, the ring's spans) as JSON, small enough to
come back from the chip and be read again here.

``--ring off`` disables the tracer before the run (no span is stored and
no annotation opened): the pair on / off is what the ring costs a cell.

``--span-cost`` times the span primitive alone instead, of the checkout
``--tree`` names (default: this one): a bare ``Tracer`` before JAX is
imported, the same with JAX's annotation, and the process-wide tracer as
the serving engine uses it (``edl_tpu.serving.engine`` imported: the
distributed-trace hooks installed, an obs bridge listening).

``--pieces`` times what an engine step does on the host around its two
program calls, call by call, on a tiny model with ``--slots`` live slots
(the host's Python is the same at any model size; set
``JAX_PLATFORMS=cpu``): what ``serving.account``, ``serving.replay`` and
the tail of a dispatch are made of.

    PYTHONPATH=. python3 scripts/exp_host_idle.py \\
        --workload deepseek7b.decode-closed --seed 7 --trace 1 \\
        --dump chiprun_out/idle_decode-closed.json
"""

import argparse
import gc
import gzip
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def span_cost(tr, label, n=200_000):
    """Microseconds a span of an empty body, median of five rounds."""
    rounds = []
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n):
            with tr.span("x"):
                pass
        rounds.append((time.perf_counter() - t0) / n * 1e6)
    fields = [f for f in ("cpu_s", "parent")
              if hasattr(tr.spans()[-1], f)]
    print(f"SPAN_COST {label}: {statistics.median(rounds):.3f} us a span "
          f"(rounds {[round(r, 3) for r in rounds]}); fields {fields}; "
          f"jax imported: {'jax' in sys.modules}", flush=True)


def clock_cost(n=1_000_000):
    for name in ("perf_counter", "thread_time"):
        fn = getattr(time, name)
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        print(f"CLOCK_COST time.{name}: "
              f"{(time.perf_counter() - t0) / n * 1e9:.1f} ns a read",
              flush=True)


def compact(planes, spans, t0, window_s):
    from benchmark.reduce import idle, trace

    chips = {}
    for name, lines in planes.items():
        if name.startswith(trace.DEVICE_PREFIX) and lines.get(
                trace.OPS_LINE):
            chips[name] = {
                "ops": trace.union(
                    [(s, e) for _, s, e, _ in lines[trace.OPS_LINE]]),
                "n_ops": len(lines[trace.OPS_LINE]),
                "modules": [[n, s, e] for n, s, e, _ in lines.get(
                    trace.MODULES_LINE, [])]}
    return {
        "window_s": window_s, "t0": t0, "chips": chips,
        "line": [[n, s, e, {k: v for k, v in st.items()
                            if k in ("seq", "step_num")}]
                 for n, s, e, st in idle.driving_line(planes)],
        "ring": [[s.seq, s.name, s.start_s, s.dur_s,
                  getattr(s, "cpu_s", None), getattr(s, "parent", None)]
                 for s in spans.values()]}


def longest_gaps(planes, window_s, spans, top=6):
    """The window's longest idle gaps on the first chip, each with the
    spans that own it and, of the span that owns most of it, the wall
    and CPU seconds."""
    from benchmark.reduce import idle, program, trace

    line = idle.driving_line(planes)
    hi = max(ev[2] for ev in line)
    lo = hi - int(round(window_s * 1e9))
    owners = idle.innermost(line)
    events = program.device_lines(planes, trace.OPS_LINE)[0]
    gaps = idle.complement(
        trace.union([(s, e) for _, s, e, _ in events]), lo, hi)
    for g in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        own = {}
        for s, e, name, seq in owners:
            over = min(e, g[1]) - max(s, g[0])
            if over > 0:
                own[(name, seq)] = own.get((name, seq), 0) + over
        (name, seq), _ = max(own.items(), key=lambda kv: kv[1],
                             default=(("(caller)", 0), 0))
        span = spans.get(seq)
        print(f"GAP {(g[1] - g[0]) / 1e3:.1f} us at "
              f"{(g[0] - lo) / 1e6:.3f} ms: "
              + ", ".join(f"{n} {ns / 1e3:.1f}"
                          for (n, _), ns in own.items())
              + (f"; {name} seq {seq} lasted {span.dur_s * 1e3:.3f} ms "
                 f"with {span.cpu_s * 1e3:.3f} ms of CPU"
                 if span is not None and getattr(span, "cpu_s", None)
                 is not None else ""), flush=True)


def after_trace(cell_name, dump):
    from benchmark import harness
    from benchmark.reduce import idle, program, trace

    path = trace.find_xplane(os.path.join(harness.TRACE_DIR, cell_name))
    if path is None:
        print("IDLE no trace", flush=True)
        return
    planes = program.load(path)
    spans, t0 = program.ring()
    window_s = WINDOW["s"]
    slack = idle.cpu_slack()
    print(f"IDLE the CPU clock resolves {slack * 1e3:.3f} ms here "
          "(its step, and twice a carried reading's age)", flush=True)
    found = idle.split(planes, window_s, spans, slack)
    if found is None:
        print("IDLE nothing to split (no device plane or no step "
              "annotation)", flush=True)
    else:
        print("IDLE " + idle.describe(found).replace("\n", "\nIDLE "),
              flush=True)
        longest_gaps(planes, found["window_s"], spans)
    joined = program.join(planes, spans, t0)
    print(f"JOIN {joined}", flush=True)
    notes = program.annotations(planes)
    by_name = {}
    for name, s, e, stats in notes:
        span = spans.get(int(stats["seq"]))
        ok = span is not None and "edl." + span.name == name
        hit = by_name.setdefault(name, [0, 0])
        hit[0] += 1
        hit[1] += ok
    print("JOIN by name (annotations, joined): " + json.dumps(by_name),
          flush=True)
    session = joined["session_seq"] if joined else 0
    mine = {k: s for k, s in spans.items() if k >= session}
    own = idle.own_times(mine)
    for step in ("serving.step", "train.step"):
        whole = [s.dur_s for s in mine.values() if s.name == step]
        self_s = [own[s.seq][0] for s in mine.values() if s.name == step]
        if self_s:
            print(f"SELF {step}: {len(self_s)} spans, self time median "
                  f"{statistics.median(self_s) * 1e6:.1f} us, mean "
                  f"{statistics.mean(self_s) * 1e6:.1f} us, max "
                  f"{max(self_s) * 1e6:.1f} us; whole median "
                  f"{statistics.median(whole) * 1e3:.3f} ms", flush=True)
    names = {}
    for s in mine.values():
        names.setdefault(s.name, []).append(s)
    for name, group in sorted(names.items()):
        durs = [s.dur_s for s in group]
        cpus = [s.cpu_s for s in group
                if getattr(s, "cpu_s", None) is not None]
        print(f"SPAN {name}: n {len(group)}, dur median "
              f"{statistics.median(durs) * 1e6:.1f} us sum "
              f"{sum(durs):.6f} s max {max(durs) * 1e3:.3f} ms"
              + (f"; cpu sum {sum(cpus):.6f} s" if cpus else ""),
              flush=True)
    if dump:
        os.makedirs(os.path.dirname(dump) or ".", exist_ok=True)
        with gzip.open(dump, "wt") as f:
            json.dump(compact(planes, spans, t0, window_s), f)
        print(f"DUMP {dump} {os.path.getsize(dump)} bytes", flush=True)


WINDOW = {"s": None}


def pieces(slots, n=3000):
    import jax

    from edl_tpu.models import llama
    from edl_tpu.obs import events as flight
    from edl_tpu.serving.engine import ContinuousBatchingEngine
    from edl_tpu.utils import faults

    cfg = llama.LlamaConfig.tiny()
    eng = ContinuousBatchingEngine(
        llama.init_params(jax.random.PRNGKey(0), cfg), cfg,
        max_slots=slots, max_len=256)
    for i in range(slots):
        eng.submit(f"r{i}", [2, 3, 4, 5], 240)
    for _ in range(slots + 4):
        eng.step()
    shares = eng._cache_read()
    old = (eng._dtok, eng._dpos, eng._dact, eng._drem) + eng._cache
    todo = {
        "account: metrics.on_step": lambda: eng.metrics.on_step(
            slots, slots, 0),
        "account: occupancy sum over the slots": lambda: sum(
            min(len(s.prompt) + len(s.generated), eng.max_len)
            for s in eng._slots if s is not None),
        "account: ledger.set_kv_usage": lambda: eng._ledger.set_kv_usage(
            eng._ledger_owner, 100, 1000),
        "account: decoding count": lambda: sum(
            1 for s in eng._slots if s is not None and s.pf_next is None),
        "account: rids list": lambda: [
            s.rid for s in eng._slots if s is not None],
        "account: _cache_read": eng._cache_read,
        "account: CostModel.decode_block": lambda: eng._cost.decode_block(
            eng.max_slots, eng.horizon, eng.max_len, shares),
        "dispatch: _next_key": eng._next_key,
        "dispatch: _temp": eng._temp,
        "tail: metrics.on_dispatch": lambda: eng.metrics.on_dispatch(
            "decode"),
        "tail: is_deleted of the donated": lambda: [
            a.is_deleted() for a in old],
        "tail: flight.emit serve.block": lambda: flight.emit(
            "serve.block", active=slots, horizon=1),
        "tail: fault_point": lambda: faults.fault_point("serve.dispatch"),
        "tail: members dict": lambda: {
            i: s.rid for i, s in enumerate(eng._slots)
            if s is not None and s.pf_next is None},
        "replay: metrics.on_block": lambda: eng.metrics.on_block(0.01),
        "replay: _eff.observe": lambda: eng._eff.observe(
            "decode", eng._block_cost, 0.01),
        "replay: metrics.on_tokens (one slot)": lambda: (
            eng.metrics.on_tokens("r1", 1)),
        "step: _evict_overdue": eng._evict_overdue,
    }
    for name, fn in todo.items():
        fn()
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        print(f"PIECE {slots} slots, {name}: "
              f"{(time.perf_counter() - t0) / n * 1e6:.2f} us", flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--ring", choices=("on", "off"), default="on")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--dump", default="")
    ap.add_argument("--span-cost", action="store_true")
    ap.add_argument("--pieces", action="store_true")
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--tree", default=ROOT,
                    help="the checkout to import from")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.tree))
    if args.pieces:
        pieces(args.slots)
        return 0
    if args.span_cost:
        from edl_tpu.utils import tracing

        print(f"SPAN_COST tree {os.path.abspath(args.tree)}", flush=True)
        clock_cost()
        span_cost(tracing.Tracer(), "bare, no jax")
        import jax  # noqa: F401

        span_cost(tracing.Tracer(), "bare, jax's annotation")
        import edl_tpu.serving.engine  # noqa: F401
        from edl_tpu import obs

        tr = tracing.tracer()
        obs.bridge_tracer(obs.MetricsRegistry(), tr)
        span_cost(tr, "as the engine opens it")
        with tr.span("outer"):
            span_cost(tr, "as the engine opens it, nested")
        return 0

    from benchmark import run
    from edl_tpu.utils import tracing

    if args.ring == "off":
        tracing.tracer().enabled = False
    finish = run.Tracer.finish

    def keep_window(self):
        finish(self)
        if self.window_s:
            WINDOW["s"] = self.window_s

    run.Tracer.finish = keep_window
    pauses, began = [], [0.0]

    def on_gc(phase, info):
        if phase == "start":
            began[0] = time.perf_counter()
        elif time.perf_counter() - began[0] > 0.002:
            pauses.append((info["generation"], began[0],
                           time.perf_counter() - began[0]))

    gc.callbacks.append(on_gc)
    argv = ["--workload", args.workload, "--seed", str(args.seed),
            "--seconds", str(args.seconds), "--trace", str(args.trace)]
    if args.rehearse:
        argv.append("--rehearse")
    rc = run.main(argv)
    gc.callbacks.remove(on_gc)
    print(f"RING {args.ring}: {len(tracing.tracer().spans())} spans kept",
          flush=True)
    t0 = tracing.tracer().t0
    print("GC collections over 2 ms (generation, at s on the ring's "
          "clock, ms): " + json.dumps(
              [(g, round(at - t0, 4), round(d * 1e3, 2))
               for g, at, d in pauses]), flush=True)
    if rc == 0 and args.trace:
        after_trace(args.workload, args.dump)
    return rc


if __name__ == "__main__":
    sys.exit(main())
