"""The numbers compared for ``correct`` over many seeds in one process:
what the limits in a cell's file are set from (PERF.md gives the
readings beside each limit).

``python3 -m benchmark.readings --workload <cell> --seeds 1,2,3
--control-seeds 4,5,6 --seconds <s>`` builds the cell's program once per
seed, as a run does, drives its set-up (and a window of ``--seconds``,
which a serving cell needs to have finished requests to compare),
releases it and runs the cell's own check; with ``--control-seeds`` the
same with the program's int8 path switched on. One line of JSON a seed.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time

from benchmark import harness, run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload)
    if args.rehearse:
        cell.for_rehearsal()
    try:
        devices, _ = harness.start_jax(cell.chips, args.rehearse)
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    todo = [(int(s), False) for s in args.seeds.split(",") if s] + \
           [(int(s), True) for s in args.control_seeds.split(",") if s]
    for seed, control in todo:
        one = argparse.Namespace(
            seed=seed, seconds=args.seconds, rehearse=args.rehearse,
            control=control, describe_trace=False, trace=0)
        t0 = time.perf_counter()
        kind = harness.load_kind(cell.kind).Kind(
            run.Context(cell, one, devices))
        compared = harness.Compared()
        with harness.kernels(args.rehearse):
            kind.setup()
            if args.seconds > 0:
                kind.window(args.seconds)
            kind.release()
            kind.check(compared)
        print("READING " + json.dumps({
            "workload": cell.name, "seed": seed, "control": control,
            "correct": compared.correct,
            "seconds": round(time.perf_counter() - t0, 1),
            "rows": {r["name"]: r["value"] for r in compared.rows}}),
            flush=True)
        del kind
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
