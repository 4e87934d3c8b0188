"""What the first step after a reshard is made of, by the program's own
spans. One run of ``mistral7b.elastic-424`` as ``benchmark.run`` makes
it (all its arguments pass through), then the span ring is read: for
each reshard, from the ``reshard`` span's start to the end of the
``train.host_block`` that closes the first ``train_steps`` call on the
new mesh, which span holds how much. Run from the root of a checkout
(the parent's too: it has the same spans, without ``step_reused``).

    python scripts/exp_reshard_first_step.py --workload \
        mistral7b.elastic-424 --seed 7 --seconds 40 --trace 0

Prints one line a reshard and the means by direction; writes the same as
JSON to ``chiprun_out/reshard_first_step.<checkout>.<seed>.json``.
"""

import json
import os
import statistics
import sys

sys.path.insert(0, os.getcwd())

from benchmark import run  # noqa: E402
from edl_tpu.utils import tracing  # noqa: E402

PARTS = ("reshard.build_mesh", "reshard.device_transfer", "train.data",
         "train.dispatch", "reshard.recompile", "train.host_block")


def first_steps(spans):
    """One dict a ``reshard`` span: the parts that follow it, and what
    lies between them under no span."""
    spans = sorted(spans, key=lambda s: s.start_s)
    out = []
    for i, r in enumerate(spans):
        if r.name != "reshard":
            continue
        row = {"from": r.attrs["from_workers"], "to": r.attrs["to_workers"],
               "reshard": r.dur_s}
        end = r.start_s + r.dur_s
        for s in spans[i + 1:]:
            if s.name == "reshard":
                break
            if s.name in PARTS and s.name not in row:
                row[s.name] = s.dur_s
                end = max(end, s.start_s + s.dur_s)
                if s.name == "reshard.recompile":
                    row["step_reused"] = s.attrs.get("step_reused")
            if s.name == "train.host_block":
                break
        row["whole"] = end - r.start_s
        # the recompile span runs from the dispatch to the loss: the
        # dispatch lies inside it
        row["no_span"] = row["whole"] - sum(
            row.get(k, 0.0) for k in
            ("reshard", "train.data", "reshard.recompile", "train.host_block"))
        out.append(row)
    return out


def main() -> int:
    rc = run.main(sys.argv[1:])
    rows = first_steps(tracing.tracer().spans())
    for row in rows:
        print(" ".join(
            f"{k}={v:.4f}" if isinstance(v, float) else f"{k}={v}"
            for k, v in row.items()), flush=True)
    means = {}
    for a, b in sorted({(r["from"], r["to"]) for r in rows}):
        # the first visit of a mesh is set-up's: leave the warm cycle out
        # of the means as the cell's metrics do
        mine = [r for r in rows if (r["from"], r["to"]) == (a, b)][1:]
        if mine:
            means[f"{a}->{b}"] = {
                k: statistics.mean(r.get(k, 0.0) for r in mine)
                for k in ("whole", "reshard", *PARTS, "no_span")}
    print("means by direction, the warm cycle left out: "
          + json.dumps(means), flush=True)
    seed = run.parse(sys.argv[1:]).seed
    # beside this script's checkout, whichever checkout ran
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chiprun_out")
    os.makedirs(out, exist_ok=True)
    tree = os.path.basename(os.getcwd())
    with open(f"{out}/reshard_first_step.{tree}.{seed}.json", "w") as f:
        json.dump({"reshards": rows, "means": means}, f, indent=1)
    return rc


if __name__ == "__main__":
    sys.exit(main())
