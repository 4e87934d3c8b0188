"""CTR throughput probe — the bench's CTR section alone, repeated.

VERDICT r3 item: two bench rounds on an earlier installation differed
by -4.5% in CTR examples/s with no CTR code change between them
(verified: models/ctr.py and the measure path were byte-identical).
This probe isolates the CTR measurement and repeats it N times in one
process to quantify run-to-run spread on one chip.

Run: python scripts/ctr_probe.py [N]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from edl_tpu.utils import jaxcache

jaxcache.configure()
import jax.numpy as jnp
import numpy as np
import optax

from edl_tpu.models import ctr
from edl_tpu.parallel.mesh import MeshPlan
from edl_tpu.train.trainer import (
    TrainState,
    make_train_multistep,
    shard_state,
    stack_batches,
)

BATCH = 16384
MEASURE = 30
CHUNK = 6


def main() -> None:
    reps = int(sys.argv[1]) if len(sys.argv) > 1 else 5
    n_dev = len(jax.devices())
    plan = MeshPlan.data_parallel(n_dev)
    mesh = plan.build()
    params = ctr.init_params(jax.random.PRNGKey(0))
    tx = optax.adam(1e-3)
    state = shard_state(TrainState.create(params, tx), plan, mesh)
    rng = np.random.RandomState(0)
    raw = [ctr.synthetic_batch(rng, BATCH) for _ in range(4)]
    stacked = stack_batches(
        [raw[i % len(raw)] for i in range(CHUNK)], plan, mesh
    )
    multi = make_train_multistep(ctr.make_loss_fn(jnp.bfloat16), tx, plan, mesh)
    state, m = multi(state, stacked)
    float(m["loss"])  # compile fence
    for _ in range(2):
        state, m = multi(state, stacked)
    float(m["loss"])

    rates = []
    for r in range(reps):
        t0 = time.perf_counter()
        for _ in range(MEASURE // CHUNK):
            state, m = multi(state, stacked)
        float(m["loss"])  # dependent-scalar fence
        dt = time.perf_counter() - t0
        rates.append(BATCH * (MEASURE // CHUNK) * CHUNK / dt / n_dev)
        print(f"# loop {r}: {rates[-1]:,.0f} examples/s/chip")
    rates = np.asarray(rates)
    print(json.dumps({
        "ctr_probe_best": round(float(rates.max()), 1),
        "ctr_probe_median": round(float(np.median(rates)), 1),
        "ctr_probe_min": round(float(rates.min()), 1),
        "spread_pct": round(
            100 * (rates.max() - rates.min()) / rates.max(), 2
        ),
        "n_loops": reps,
    }))


if __name__ == "__main__":
    main()
