"""Does ``granite4h.decode-hybrid``'s comparison catch a fault of the
hybrid model? Each fault is planted in the PROGRAM (``edl_tpu.ops.ssm``,
by patching the functions the model calls into, or the program's config
where the fault is a constant of the layer); the reference is its own
code and is left alone. One reading a fault and seed, as
``benchmark.readings`` makes them: the cell's set-up, a window, the
cell's own check (PERF.md section 2, PR 37). Every fault of one seed
runs on the same weights and prompts, the sound program among them. The
window is the cell's own 40 s (a closed loop's first ``clients``
requests have their budgets cut at random, so a shorter window compares
those short answers alone: PERF.md section 6, PR 35).

    python scripts/exp_decode_hybrid_faults.py [--seconds 40] [--rehearse]
"""

import argparse
import dataclasses
import gc
import json

import jax
import jax.numpy as jnp

from benchmark import harness, run
from edl_tpu.ops import ssm as ops
from edl_tpu.serving import engine

CELL = "granite4h.decode-hybrid"
step, chunked, conv_prefill = ops.ssm_step, ops.ssd_chunked, ops.conv_prefill


def _rounded(x):
    """To bfloat16's 8 exponent and 7 mantissa bits, by the operation
    that says so: a pair of converts there and back is no rounding on
    the chip (PERF.md section 6, PR 35)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def bf16_state_step(x, bm, cm, dt, a, d, state, layer, live, **kw):
    """``S`` kept in bfloat16: rounded after every decode step (a
    prefill's state with the first step that follows it)."""
    y, state = step(x, bm, cm, dt, a, d, state, layer, live, **kw)
    kept = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    return y, jax.lax.dynamic_update_index_in_dim(
        state, _rounded(kept), layer, 0)


def padded_tail_chunked(x, bm, cm, dt, a, d, valid=None, start=None, **kw):
    """A padded bucket's tail let into the state: every row of the
    bucket counts as a position."""
    return chunked(x, bm, cm, dt, a, d, None, start, **kw)


def padded_tail_conv(xbc, w, b, last):
    """A padded bucket's tail let into the convolution's tail: the
    three inputs at the bucket's end, whatever ``last`` is."""
    return conv_prefill(xbc, w, b, jnp.full_like(last, xbc.shape[1] - 1))


def no_skip_step(x, bm, cm, dt, a, d, *rest, **kw):
    """``D x`` left out of a decode step's output."""
    return step(x, bm, cm, dt, a, jnp.zeros_like(d), *rest, **kw)


def no_skip_chunked(x, bm, cm, dt, a, d, *rest, **kw):
    """``D x`` left out of a prefill's output."""
    return chunked(x, bm, cm, dt, a, jnp.zeros_like(d), *rest, **kw)


# a fault whose step is slower than the sound program's gets a window
# as much longer, so that it finishes the requests the sound run does:
# the rounding is one more pass over every layer's state
SLOWER = {"bf16_state": 1.5}

# name -> (functions of ops/ssm.py to replace, fields of the program's
# config to replace)
FAULTS = {
    "sound": ({}, {}),
    "bf16_state": ({"ssm_step": bf16_state_step}, {}),
    "padded_tail_state": ({"ssd_chunked": padded_tail_chunked}, {}),
    "padded_tail_conv": ({"conv_prefill": padded_tail_conv}, {}),
    "no_skip": ({"ssm_step": no_skip_step, "ssd_chunked": no_skip_chunked},
                {}),
    "attention_scale": ({}, {"attention_multiplier": 64 ** -0.5}),
    "no_residual_multiplier": ({}, {"residual_multiplier": 1.0}),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seeds", default="2100000137")
    ap.add_argument("--only", default=",".join(FAULTS))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell = harness.Cell(CELL)
    if args.rehearse:
        cell.for_rehearsal()
    devices, _ = harness.start_jax(cell.chips, args.rehearse)
    sound_config = cell.family.program_config
    for seed, name in ((int(s), name) for s in args.seeds.split(",")
                       for name in args.only.split(",") if name):
        patched, fields = FAULTS[name]
        for attr, fn in (("ssm_step", step), ("ssd_chunked", chunked),
                         ("conv_prefill", conv_prefill)):
            setattr(ops, attr, patched.get(attr, fn))
        cell.family.program_config = lambda *a, fields=fields, **kw: \
            dataclasses.replace(sound_config(*a, **kw), **fields)
        engine._programs.clear()  # traced with the last fault in them
        one = argparse.Namespace(
            seed=seed, seconds=args.seconds * SLOWER.get(name, 1.0),
            control=False,
            rehearse=args.rehearse, describe_trace=False, trace=0)
        kind = harness.load_kind(cell.kind).Kind(
            run.Context(cell, one, devices))
        compared = harness.Compared()
        with harness.kernels(args.rehearse):
            kind.setup()
            kind.window(one.seconds)
            kind.release()
            kind.check(compared)
        # a greedy answer that fell into one token or a short cycle
        # would say little of the state: how many different tokens the
        # compared answers hold
        print("FAULT " + json.dumps({
            "fault": name, "seed": one.seed, "correct": compared.correct,
            "tokens": kind.counters["tokens"],
            "engine_steps": kind.counters["engine_steps"],
            "distinct_tokens_of": [
                [len(set(kind.finished[r])), len(kind.finished[r])]
                for r in kind.sample()],
            "rows": {r["name"]: r["value"] for r in compared.rows}}),
            flush=True)
        del kind
        gc.collect()


if __name__ == "__main__":
    main()
