"""Flash-attention kernel tuning sweep — block sizes at the flagship
bench shape, 16 chained calls per dispatch to amortize dispatch overhead.

Run on the TPU chip: python scripts/exp_flash.py [bq,bk ...]
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


import jax

from edl_tpu.utils import jaxcache

jaxcache.configure()

import jax.numpy as jnp
import numpy as np

from edl_tpu.ops import flash_attention as fa

B, T, H, D = 16, 2048, 16, 128
CHAIN = 16
PEAK = 197e12


def fence(x):
    leaf = jax.tree_util.tree_leaves(x)[0]
    return float(jnp.sum(jnp.ravel(leaf)[:1]))


def main():
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.bfloat16)
    att_flops = B * H * (T * T / 2) * 4 * D * CHAIN

    variants = sys.argv[1:] or [
        "512,512", "1024,512", "512,1024", "1024,1024",
        "2048,512", "2048,1024", "256,512", "512,256",
    ]
    print(f"platform={jax.devices()[0].platform} fwd, {CHAIN} chained calls", flush=True)
    for vstr in variants:
        bq, bk = map(int, vstr.split(","))
        try:
            @jax.jit
            def f(q, k, v, bq=bq, bk=bk):
                o = q
                for _ in range(CHAIN):
                    o = fa.flash_attention(
                        o, k, v, causal=True, block_q=bq, block_k=bk
                    )
                return o

            out = f(q, k, v)
            fence(out)
            best = float("inf")
            for _ in range(2):
                t0 = time.perf_counter()
                out = f(q, k, v)
                fence(out)
                best = min(best, time.perf_counter() - t0)
            print(
                f"bq={bq:5d} bk={bk:5d}  {best/CHAIN*1e3:7.2f} ms/call  "
                f"{att_flops/best/1e12:6.1f} TF/s ({att_flops/best/PEAK*100:4.1f}%)",
                flush=True,
            )
        except Exception as e:
            print(f"bq={bq:5d} bk={bk:5d}  FAILED: {str(e)[:120]}", flush=True)
        finally:
            jax.clear_caches()

    # fwd+bwd at the default and best-looking blocks
    for vstr in variants[:4]:
        bq, bk = map(int, vstr.split(","))
        try:
            g = jax.jit(
                jax.grad(
                    lambda q, k, v, bq=bq, bk=bk: sum(
                        fa.flash_attention(
                            q, k, v, causal=True, block_q=bq, block_k=bk
                        )
                        .astype(jnp.float32)
                        .sum()
                        for _ in range(4)
                    ),
                    (0, 1, 2),
                )
            )
            out = g(q, k, v)
            fence(out)
            best = float("inf")
            for _ in range(2):
                t0 = time.perf_counter()
                out = g(q, k, v)
                fence(out)
                best = min(best, time.perf_counter() - t0)
            fb_flops = B * H * (T * T / 2) * 4 * D * 4 * 3
            print(
                f"f+b bq={bq:4d} bk={bk:4d}  {best/4*1e3:7.2f} ms/call  "
                f"{fb_flops/best/1e12:6.1f} TF/s model ({fb_flops/best/PEAK*100:4.1f}%)",
                flush=True,
            )
        except Exception as e:
            print(f"f+b bq={bq:4d} bk={bk:4d}  FAILED: {str(e)[:120]}", flush=True)
        finally:
            jax.clear_caches()


if __name__ == "__main__":
    main()
