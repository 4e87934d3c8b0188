"""One run of one cell: ``python3 -m benchmark.run --workload <cell>
--seed <n> --seconds <s> --trace <0|1>``.

A new process: finds the chip (and fails without it), makes weights and
traffic from the seed, warms the cell's own shapes, measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON line last. ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics with the
device's busy time and a breakdown from the profiler's trace.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

from benchmark import harness  # noqa: E402


class Tracer:
    """Traces a few seconds of the window; a kind calls ``tick`` between
    its calls into the program and ``finish`` when the window closes.
    ``at`` is ``end`` (the window's last seconds: the profiler's stop,
    which stalls the host, then falls after the window) or ``start``."""

    def __init__(self, on: bool, at: str, seconds: float, window: float,
                 out: str):
        self.on = on
        self.start_s = max(0.0, window - seconds) if at == "end" else 0.0
        self.stop_s = float("inf") if at == "end" else seconds
        self.out = out
        self.running = False
        self.window_s = 0.0

    def tick(self, t: float) -> None:
        if not self.on:
            return
        import jax

        if not self.running and self.start_s <= t < self.stop_s:
            shutil.rmtree(self.out, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            # the benchmark's own spans are TraceAnnotations; Python's
            # call tracer would only slow the host it is measuring
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.out, profiler_options=options)
            self.running, self._t0 = True, time.perf_counter()
        elif self.running and t >= self.stop_s:
            self.finish()

    def finish(self) -> None:
        if not self.running:
            return
        import jax

        self.window_s = time.perf_counter() - self._t0
        jax.profiler.stop_trace()
        self.running, self.on = False, False


class Context:
    def __init__(self, cell, args, devices):
        self.cell = cell
        self.seed = args.seed
        self.rehearse = args.rehearse
        self.control = args.control
        self.describe_trace = args.describe_trace
        self.devices = devices
        tr = cell.spec.get("trace", {})
        self.tracer = Tracer(
            bool(args.trace), tr.get("at", "end"),
            float(tr.get("seconds", 5.0)), float(args.seconds),
            os.path.join(harness.TRACE_DIR, cell.name))


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths, interpreted kernels, any device: "
                    "for the CPU tests; prints under no device metric's "
                    "name")
    ap.add_argument("--control", action="store_true",
                    help="switch the program's int8 path on: the run has "
                    "to come out not correct")
    ap.add_argument("--describe-trace", action="store_true",
                    help="print the trace's planes, lines and heaviest "
                    "events (a look by hand)")
    ap.add_argument("--set", action="append", default=[],
                    metavar="traffic.key=value",
                    help="override one value of the cell's files (sweeps)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    overrides = {}
    for item in args.set:
        key, _, value = item.partition("=")
        overrides[key] = json.loads(value)
    cell = harness.Cell(args.workload, overrides=overrides)
    if args.rehearse:
        cell.for_rehearsal()
    try:
        devices, record = harness.start_jax(cell.chips, args.rehearse)
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    # setup_s counts from here: how long the TPU runtime takes to hand
    # the chip over (8.6-21.7 s, run to run, on the machine of PR 23) is
    # neither the program's nor the benchmark's, and would drown what a
    # PR moves into set-up
    t_ready = time.time()
    print(f"chip ready {t_ready - T_PROCESS:.2f}s after process start",
          flush=True)
    ctx = Context(cell, args, devices)
    kind = harness.load_kind(cell.kind).Kind(ctx)

    with harness.kernels(args.rehearse):
        kind.setup()
        setup_s = time.time() - t_ready
        print(f"set-up {setup_s:.2f}s; window {args.seconds}s", flush=True)
        with harness.CompileCount() as compiled:
            kind.window(args.seconds)
    print(f"window: {compiled.compiles} compilations, {compiled.traces} "
          f"traces", flush=True)
    record["memory_peak_bytes"] = harness.memory_peak_bytes(devices)

    compared = harness.Compared()
    compared.add("compilations_in_window", float(compiled.compiles), 0.0)
    kind.release()
    with harness.kernels(args.rehearse):
        kind.check(compared)

    values = dict(kind.end_to_end(), setup_s=setup_s)
    result = {"correct": compared.correct, "attempted": kind.attempted,
              "failed": kind.failed, "device": record}
    if args.trace:
        run = {"cell": cell, "config": cell.config, "spans": kind.spans,
               "counters": kind.counters, "end_to_end": values,
               "device": record, "trace": None}
        summary = read_trace(ctx)
        if summary is not None:
            run["trace"] = summary
            record["busy_s"] = summary["busy_s"]
            record["window_s"] = summary["window_s"]
            result["breakdown"] = {"device_ops": summary["device_ops"],
                                   "idle_gaps": summary["idle_gaps"]}
        result["metrics"] = harness.read_per_layer(cell, run)
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end()}
        result["metrics"] = {
            name: {"value": float(values[name]), "unit": unit}
            for name, unit in units.items()}
    if args.rehearse:
        # a rehearsal's numbers are the CPU's: never under a device
        # metric's name
        result["metrics"] = {"rehearsal." + k: v
                             for k, v in result["metrics"].items()}
    print(json.dumps(result), flush=True)
    return 0


def read_trace(ctx):
    from benchmark.reduce import trace

    path = trace.find_xplane(ctx.tracer.out)
    if path is None:
        print("no trace was written", flush=True)
        return None
    planes = trace.load(path)
    if ctx.describe_trace:
        print(trace.describe(planes), flush=True)
    return trace.summarize(planes, ctx.tracer.window_s)


if __name__ == "__main__":
    sys.exit(main())
