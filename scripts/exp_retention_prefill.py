"""Device time of ``brumby14b.decode-state``'s prefill programs by
bucket, chunk size and implementation (the kernel
``edl_retention_chunk`` against the plain lines), at the cell's own
sizes: 24 slots of state beside the weights, one prompt a program
(PERF.md section 6, PR 35). Wall time around ``block_until_ready`` of
back-to-back runs: the device is never idle between them.

    python scripts/exp_retention_prefill.py [--chunks 128,256] [--plain 128]
"""

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp

from benchmark import harness
from edl_tpu.serving import engine

CELL = "brumby14b.decode-state"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chunks", default="128,256,512")
    ap.add_argument("--plain", default="128",
                    help="chunk sizes to run with the plain lines too")
    ap.add_argument("--buckets", default="1024,2048,4096")
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell = harness.Cell(CELL)
    if args.rehearse:
        cell.for_rehearsal()
    harness.start_jax(1, args.rehearse)
    base = cell.family.program_config(cell.config, training=False)
    params = harness.make_params(7, cell.layout, jnp.bfloat16)
    spec = cell.spec["engine"]
    slots = int(spec["max_slots"])
    todo = [(int(c), True) for c in args.chunks.split(",") if c] + \
           [(int(c), False) for c in args.plain.split(",") if c]
    i32 = lambda: jnp.zeros((slots,), jnp.int32)
    with harness.kernels(args.rehearse):
        for chunk, kernel in todo:
            cfg = dataclasses.replace(base, chunk=chunk, use_kernel=kernel)
            for tb in (int(t) for t in args.buckets.split(",")):
                if args.rehearse:
                    tb = min(tb, 32)
                prog = engine._prefill_program(cfg, tb, False)
                cache = [jnp.zeros(s, d) for s, d in
                         cfg.serve_cache_spec(slots, int(spec["max_len"]))]
                carry = [i32(), i32(), jnp.zeros((slots,), bool), i32(), i32()]
                tokens = jnp.ones((1, tb), jnp.int32)
                key, temp = jax.random.PRNGKey(0), jnp.float32(0)
                times = []
                for _ in range(args.runs + 1):
                    t0 = time.perf_counter()
                    out = prog(params, tokens, jnp.int32(tb - 3), jnp.int32(1),
                               jnp.int32(4), jnp.int32(-1), *carry, *cache, key,
                               temp)
                    jax.block_until_ready(out)
                    times.append(time.perf_counter() - t0)
                    carry, cache = list(out[1:6]), list(out[6:])
                print("PREFILL " + json.dumps({
                    "bucket": tb, "chunk": chunk, "kernel": kernel,
                    "ms": [round(1e3 * t, 2) for t in times[1:]]}), flush=True)
                del cache, out


if __name__ == "__main__":
    main()
