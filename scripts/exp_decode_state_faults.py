"""Does ``brumby14b.decode-state``'s comparison catch a fault of the
retention layer? Each fault is planted in the PROGRAM
(``edl_tpu.ops.retention``, by patching the functions the model calls
into); the reference is its own code and is left alone. One reading a
fault and seed, as ``benchmark.readings`` makes them: the cell's
set-up, a window, the cell's own check (PERF.md section 2, PR 35).
Every fault of one seed runs on the same weights and prompts, the
sound program among them. The window is the cell's own 40 s: a closed
loop's first ``clients`` requests have their budgets cut at random
(``traffic/generate.py``), so in a window of 20 s the finished requests
are those short ones (~140 tokens each), and a fault that grows with
the decode steps is not seen.

    python scripts/exp_decode_state_faults.py [--seconds 40] [--rehearse]
"""

import argparse
import gc
import json

import jax
import jax.numpy as jnp

from benchmark import harness, run
from edl_tpu.ops import retention as ops
from edl_tpu.serving import engine

CELL = "brumby14b.decode-state"
step, chunked = ops.retention_step, ops.retention_chunked


def _rounded(x):
    """To bfloat16's 8 exponent and 7 mantissa bits, by the operation
    that says so: a pair of converts there and back is no rounding on
    the chip, where XLA takes it out (``xla_allow_excess_precision``),
    and the fault planted that way served the sound program's tokens
    to the last digit (PERF.md section 6, PR 35)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def rounding_probe() -> None:
    """What each way of rounding does to a stacked float32 array under
    jit on this device, written as the fault writes it: the share of
    layer 0's numbers that come back changed."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 128, 1024))
    pair = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    out = {}
    for name, fn in (("convert_pair", pair), ("reduce_precision", _rounded)):
        y = jax.jit(lambda s: s.at[0].set(fn(s[0])))(x)
        out[name] = float(jnp.mean(y[0] != x[0]))
    print("ROUNDING " + json.dumps(out), flush=True)


def bf16_state_step(q, k, v, log_g, state, z, layer, live, **kw):
    """The state kept in bfloat16: rounded after every decode step (a
    prefill's state with the first step that follows it)."""
    y, state, z = step(q, k, v, log_g, state, z, layer, live, **kw)
    return (y, state.at[layer].set(_rounded(state[layer])),
            z.at[layer].set(_rounded(z[layer])))


def no_decay_step(q, k, v, log_g, *rest, **kw):
    """The decay left out of the carried state: a decode step adds to
    the state and never shrinks it."""
    return step(q, k, v, jnp.zeros_like(log_g), *rest, **kw)


def padded_tail_chunked(q, k, v, log_g, valid=None, start=None, **kw):
    """A padded bucket's tail let into the state: every row of the
    bucket counts as a position."""
    return chunked(q, k, v, log_g, None, start, **kw)


def no_normaliser_step(q, k, v, log_g, state, z, layer, live, **kw):
    """The normaliser left out: the summed weights do not divide the
    output."""
    y, state, z = step(q, k, v, log_g, state, z, layer, live, **kw)
    den = jnp.einsum("bkgD,bkD->bkg", ops.phi(q), z[layer])
    return (y.astype(jnp.float32) * (den[..., None] + kw["eps"])
            ).astype(y.dtype), state, z


# a fault whose step is slower than the sound program's gets a window
# as much longer, so that it finishes the requests the sound run does:
# the rounding's passes over the whole state make a step 54 ms for 29.5
SLOWER = {"bf16_state": 1.65}

FAULTS = {
    "sound": {},
    "bf16_state": {"retention_step": bf16_state_step},
    "no_decay_in_state": {"retention_step": no_decay_step},
    "padded_tail": {"retention_chunked": padded_tail_chunked},
    "no_normaliser": {"retention_step": no_normaliser_step},
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seeds", default="2100000101")
    ap.add_argument("--only", default=",".join(FAULTS))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell = harness.Cell(CELL)
    if args.rehearse:
        cell.for_rehearsal()
    devices, _ = harness.start_jax(cell.chips, args.rehearse)
    rounding_probe()
    for seed, name in ((int(s), name) for s in args.seeds.split(",")
                       for name in args.only.split(",") if name):
        for attr, fn in (("retention_step", step),
                         ("retention_chunked", chunked)):
            setattr(ops, attr, FAULTS[name].get(attr, fn))
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
            "distinct_tokens_of": [
                [len(set(kind.finished[r])), len(kind.finished[r])]
                for r in kind.sample()],
            "rows": {r["name"]: r["value"] for r in compared.rows}}),
            flush=True)
        del kind
        gc.collect()


if __name__ == "__main__":
    main()
