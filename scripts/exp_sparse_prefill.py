"""``edl_sparse_prefill_attn`` alone and ``glm_dsa._sweep`` alone on one
chip at ``glm5.long-sparse``'s published widths (64 heads of 192 + 64 /
256 over latent rows of 512 + 64): a piece of 2048 query rows against
4096 / 8192 / 16384 / 32768 live keys, each query attending 2048 of the
keys at or before it, in ms a layer and TFLOP/s (PERF.md section 6, PR
42).

    PYTHONPATH=. python scripts/exp_sparse_prefill.py            # the chip
    PYTHONPATH=. python scripts/exp_sparse_prefill.py --tune     # the forms
    PYTHONPATH=. python scripts/exp_sparse_prefill.py --compile  # no chip

The operations counted are what the mathematics needs for the live
keys: scores and value products ``2 x 2 x P x keys x H x 256`` and the
expansion of every live row once a piece ``2 x keys x 512 x H x (192 +
256)``; a share of the chip's bf16 peak of them is what a
``sparse_prefill_roofline.sparse`` would read.
"""

import argparse
import json
import time

import jax
import jax.numpy as jnp

from edl_tpu.models import glm_dsa
from edl_tpu.ops import sparse_prefill_attention as spa

PEAK = 197e12  # a v5e's bf16 matmul peak
CFG = glm_dsa.GlmDsaConfig(n_layers=1, dtype=jnp.bfloat16, use_flash=True)
P = glm_dsa.PREFILL_PIECE


def needed_flops(keys: int, cfg=CFG, p: int = P) -> float:
    """Matmul operations of one piece's attention over ``keys`` live
    positions, one layer: scores, value products, and each live row
    expanded once."""
    h = cfg.n_heads
    return (2.0 * p * keys * h * (cfg.qk_dim + cfg.v_dim)
            + 2.0 * keys * cfg.kv_rank * h * (cfg.qk_nope_dim + cfg.v_dim))


def operands(keys: int, bucket: int, seed: int = 0, cfg=CFG, p: int = P):
    """(q, lat, wkvb, sel) of the piece that ENDS at ``keys`` in a
    bucket of ``bucket``: every query attends ``index_topk`` random
    positions at or before its own."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (1, p, cfg.n_heads, cfg.qk_dim), cfg.dtype)
    lat = jax.random.normal(ks[1], (1, 1, bucket, cfg.cache_width), cfg.dtype)
    lat = lat.at[..., cfg.latent_width:].set(0)
    wkvb = (jax.random.normal(
        ks[2], (cfg.kv_rank, cfg.n_heads * (cfg.qk_nope_dim + cfg.v_dim)),
        jnp.float32) * cfg.kv_rank ** -0.5).astype(cfg.dtype)
    at = keys - p + jnp.arange(p)
    valid = jnp.arange(bucket)[None, None, :] <= at[None, :, None]
    sel = glm_dsa.select_mask(
        jax.random.uniform(ks[3], (1, p, bucket)), valid, cfg.index_topk)
    return q, lat, wkvb, sel


def kernel(block_k=spa.BLOCK_K, cfg=CFG, **form):
    """``form``: ``block_q`` and ``heads``, where not the kernel's own."""
    def run(q, lat, wkvb, sel, n_keys):
        return spa.sparse_prefill_attention(
            q, lat, wkvb, sel, jnp.int32(0), n_keys // block_k,
            rank=cfg.kv_rank, rope=cfg.qk_rope_dim,
            sm_scale=cfg.qk_dim ** -0.5, block_k=block_k, **form)
    return jax.jit(run)


def sweep(cfg=CFG):
    def run(q, lat, wkvb, sel, n_keys):
        return glm_dsa._sweep(cfg, q, lat, 0, sel,
                              n_keys // glm_dsa.KEY_BLOCK, {"wkvb": wkvb})
    return jax.jit(run)


def timed(fn, *args, n=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n, out


def line(name, keys, bucket, dt, **more):
    visits = keys // glm_dsa.KEY_BLOCK
    print(json.dumps({
        "what": name, "live_keys": keys, "bucket": bucket,
        "ms_a_layer": round(dt * 1e3, 3),
        "ms_a_visit_of_512": round(dt * 1e3 / visits, 4),
        "tflops": round(needed_flops(keys) / dt / 1e12, 2),
        "share_of_bf16_peak": round(needed_flops(keys) / dt / PEAK, 4),
        **more}), flush=True)


def measure(lengths):
    for keys in lengths:
        args = operands(keys, keys) + (jnp.int32(keys),)
        dt, got = timed(kernel(), *args)
        line("edl_sparse_prefill_attn", keys, keys, dt)
        dt, want = timed(sweep(), *args)
        gap = jnp.max(jnp.abs(got.astype(jnp.float32)
                              - want.astype(jnp.float32)))
        line("_sweep", keys, keys, dt, kernel_max_abs_gap=float(gap),
             max_abs=float(jnp.max(jnp.abs(want.astype(jnp.float32)))))
    # dead blocks behind the live ones: the largest bucket, 8192 live
    args = operands(8192, 32768) + (jnp.int32(8192),)
    dt, _ = timed(kernel(), *args)
    line("edl_sparse_prefill_attn", 8192, 32768, dt)


def tune(keys=8192):
    args = operands(keys, keys) + (jnp.int32(keys),)
    line("_sweep", keys, keys, timed(sweep(), *args, n=3)[0])
    for heads in (1, 2, 4, 8):
        for block_k in (512, 1024, 2048):
            for block_q in (256, 512, 1024):
                try:
                    dt, _ = timed(kernel(block_k, block_q=block_q,
                                         heads=heads), *args, n=3)
                except Exception as e:  # the compiler's refusal is a reading
                    print(json.dumps({
                        "heads": heads, "block_k": block_k,
                        "block_q": block_q,
                        "refused": str(e).splitlines()[0][:200]}), flush=True)
                    continue
                line("edl_sparse_prefill_attn", keys, keys, dt, heads=heads,
                     block_k=block_k, block_q=block_q)


def compile_only(keys=8192, bucket=32768, **form):
    """The kernel compiled for a DESCRIBED v5e (no chip): the compiler's
    refusals cost no chip time."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    shapes = jax.eval_shape(lambda: operands(keys, bucket))
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)
            for a in shapes + (jax.ShapeDtypeStruct((), jnp.int32),)]
    t0 = time.perf_counter()
    compiled = kernel(**form).lower(*args).compile()
    mem = compiled.memory_analysis()
    print(json.dumps({"compiled_s": round(time.perf_counter() - t0, 1),
                      "temp_bytes": mem.temp_size_in_bytes, **form}))


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--tune", action="store_true")
    ap.add_argument("--compile", action="store_true")
    ap.add_argument("--lengths", default="4096,8192,16384,32768")
    ap.add_argument("--block-k", type=int)
    ap.add_argument("--block-q", type=int)
    ap.add_argument("--heads", type=int)
    a = ap.parse_args()
    form = {k: v for k, v in (("block_k", a.block_k), ("block_q", a.block_q),
                              ("heads", a.heads)) if v}
    if a.compile:
        compile_only(**form)
    else:
        print(jax.devices(), flush=True)
        if a.tune:
            tune()
        else:
            measure([int(n) for n in a.lengths.split(",")])
