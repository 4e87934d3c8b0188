"""Sweep of ``edl_decode_attn_latent``'s S-block and timing of the
routed experts' grouped matmuls, standalone on one chip at the sizes of
``kanana2.decode-wide`` (PERF.md section 6, PR 29).

    python scripts/exp_latent_decode.py            # on the chip
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from edl_tpu.ops.decode_attention import decode_attention_latent
from edl_tpu.parallel import moe

HBM = 819e9


def timed(fn, *args, n=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def latent_sweep(slots=96, s=4096, layers=8, width=640, h=32):
    key = jax.random.PRNGKey(0)
    cache = jax.random.normal(key, (layers, slots, s, width), jnp.bfloat16)
    q = jax.random.normal(key, (slots, h, width), jnp.bfloat16)
    rng = np.random.default_rng(0)
    for name, pos in (
        ("full", np.full(slots, s - 1)),
        ("mix", rng.integers(600, 3800, slots)),
    ):
        pos = jnp.asarray(pos, jnp.int32)
        live = float(jnp.sum(pos + 1)) * 576 * 2
        for bs in (64, 128, 256, 512, 1024):
            f = jax.jit(lambda q, c, p, bs=bs: decode_attention_latent(
                q, c, p, jnp.int32(3), rank=512, sm_scale=192 ** -0.5,
                block_s=bs))
            dt = timed(f, q, cache, pos)
            print(json.dumps({
                "kernel": "edl_decode_attn_latent", "slots": name,
                "block_s": bs, "us": round(dt * 1e6, 1),
                "live_latent_share_of_hbm_peak":
                    round(live / dt / HBM, 3)}), flush=True)


def experts(n_tokens, e=128, d=2048, f=768, k=6):
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (n_tokens, d), jnp.bfloat16)
    router = jax.random.normal(ks[1], (d, e), jnp.bfloat16) * d ** -0.5
    bias = jnp.zeros((e,), jnp.bfloat16)
    w1 = jax.random.normal(ks[2], (e, d, f), jnp.bfloat16) * d ** -0.5
    w3 = jax.random.normal(ks[3], (e, d, f), jnp.bfloat16) * d ** -0.5
    w2 = jax.random.normal(ks[4], (e, f, d), jnp.bfloat16) * f ** -0.5

    @jax.jit
    def layer(x, router, bias, w1, w3, w2):
        idx, w = moe.route_sigmoid_topk(x, router, bias, k, 2.448)
        return moe.moe_dropless(x, idx, w, w1, w3, w2), \
            moe.expert_load(idx, e)[0]

    dt = timed(layer, x, router, bias, w1, w3, w2)
    hit = float(layer(x, router, bias, w1, w3, w2)[1])
    print(json.dumps({
        "layer": "router + dropless experts", "tokens": n_tokens,
        "us": round(dt * 1e6, 1), "experts_hit_share": round(hit, 3),
        "hit_weights_share_of_hbm_peak":
            round(hit * e * 3 * d * f * 2 / dt / HBM, 3),
        "tflops": round(n_tokens * k * 3 * d * f * 2 / dt / 1e12, 2),
    }), flush=True)


if __name__ == "__main__":
    print(jax.devices(), flush=True)
    latent_sweep()
    for n in (96, 512, 2048, 4096):
        experts(n)
