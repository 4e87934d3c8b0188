"""Does ``kanana2.decode-wide``'s comparison catch a fault of the routed
experts' path, with their down projection drawn at a quarter of the
fan-in std (``benchmark/families/mla_moe.py: ROUTED_DOWN_STD``)? Each
fault is planted in the PROGRAM (``edl_tpu.parallel.moe``, by patching
the module the model calls into); the reference is its own code and is
left alone. One reading a fault, as ``benchmark.readings`` makes them:
the cell's set-up, a window, the cell's own check (PERF.md section 2,
PR 29).

    python scripts/exp_decode_wide_faults.py [--seconds 25] [--rehearse]
"""

import argparse
import gc
import json

import jax.numpy as jnp

from benchmark import harness, run
from edl_tpu.parallel import moe
from edl_tpu.serving import engine

CELL = "kanana2.decode-wide"
route, dropless = moe.route_sigmoid_topk, moe.moe_dropless


def no_scale(x, router, bias, k, scale, normalize=True):
    """``routed_scaling_factor`` (2.448) left out."""
    return route(x, router, bias, k, 1.0, normalize)


def bias_in_weight(x, router, bias, k, scale, normalize=True):
    """The corrected score ``s + b`` weighs the chosen experts, where
    the bias may only choose them."""
    idx, w = route(x, router, bias, k, 1.0, False)
    w = w + bias.astype(jnp.float32)[idx]
    if normalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * scale


def one_expert_dropped(x, idx, w, *weights, **kw):
    """Rows sent to expert 5 come back as zeros."""
    return dropless(x, idx, jnp.where(idx == 5, 0.0, w), *weights, **kw)


FAULTS = {
    "sound": {},
    "no_scale": {"route_sigmoid_topk": no_scale},
    "bias_in_weight": {"route_sigmoid_topk": bias_in_weight},
    "one_expert_dropped": {"moe_dropless": one_expert_dropped},
    # no fault: what the sound program reads with the down projection
    # at half the fan-in std
    "down_std_0.5": {"ROUTED_DOWN_STD": 0.5},
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seed", type=int, default=2100000101)
    ap.add_argument("--only", default=",".join(FAULTS))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell = harness.Cell(CELL)
    if args.rehearse:
        cell.for_rehearsal()
    devices, _ = harness.start_jax(cell.chips, args.rehearse)
    for n, name in enumerate(args.only.split(",")):
        std = FAULTS[name].get("ROUTED_DOWN_STD", 0.25)
        cell.family.ROUTED_DOWN_STD = std
        for attr, fn in (("route_sigmoid_topk", route),
                         ("moe_dropless", dropless)):
            setattr(moe, attr, FAULTS[name].get(attr, fn))
        engine._programs.clear()  # traced with the last fault in them
        one = argparse.Namespace(
            seed=args.seed + n, seconds=args.seconds, control=False,
            rehearse=args.rehearse, describe_trace=False, trace=0)
        kind = harness.load_kind(cell.kind).Kind(
            run.Context(cell, one, devices))
        compared = harness.Compared()
        with harness.kernels(args.rehearse):
            kind.setup()
            kind.window(args.seconds)
            kind.release()
            kind.check(compared)
        print("FAULT " + json.dumps({
            "fault": name, "seed": one.seed, "down_std": std,
            "correct": compared.correct,
            "rows": {r["name"]: r["value"] for r in compared.rows}}),
            flush=True)
        del kind
        gc.collect()


if __name__ == "__main__":
    main()
