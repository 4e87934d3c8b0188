"""Int8 MXU probe — is the 2x int8 MXU path a real training lever?

VERDICT r4 #8: the 0.53 MFU plateau is a proven bf16 roofline
(scripts/exp_breakdown.py); the one untried lever on v5e is the 2x
int8 MXU rate (394.7 TOPS int8 vs 197.4 TFLOPs bf16). This probe
answers the gating question EMPIRICALLY before any model surgery:
what does an int8 matmul actually deliver at the flagship's shapes,
once the unavoidable quantization overhead (VPU abs-max reduces,
rounding, rescale) is paid?

The measured unit is an MLP-shaped PAIR (up-projection then
down-projection, [BT,d]@[d,ff] then [BT,ff]@[ff,d]) chained as a
fori_loop carry, so the numbers compose exactly like the model's hot
path. Three variants:

  bf16      as the model runs today (what the MFU plateau is made of)
  int8-dyn  AQT-style dynamic quantization INSIDE the step: per-row
            abs-max of activations, per-col abs-max of weights, round
            to int8, s8xs8->s32 dot, rescale — the drop-in quantized
            training matmul, overhead included
  int8-wq   weights pre-quantized OUTSIDE the loop (weights are static
            within a step; also the serving/decode shape of the lever)

Decision rule (to be written into doc/design.md with the numbers): the
quantizable matmuls are at most ~2 of the step's 4 fwd-units under
mandatory remat; if int8-dyn delivers < ~1.3x over bf16 here, the
end-to-end step gain is < ~10% before any accuracy cost — close the
lever as measured-out.

Run on the bench chip:  python scripts/exp_int8.py
"""

import sys
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np

from edl_tpu.utils import jaxcache

jaxcache.configure()

STEPS = 48
CHUNK = 6


def _fence(x) -> float:
    # dependent scalar fetch: cannot return before the device work
    # that produces it completes
    return float(jnp.sum(x[:1, :1]))


def _quant_rows(x):
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True).astype(jnp.float32) / 127.0
    q = jnp.round(x.astype(jnp.float32) / s).astype(jnp.int8)
    return q, s


def _quant_cols(w):
    s = jnp.max(jnp.abs(w), axis=0, keepdims=True).astype(jnp.float32) / 127.0
    q = jnp.round(w.astype(jnp.float32) / s).astype(jnp.int8)
    return q, s


def _dot_i8(xq, wq):
    return jax.lax.dot_general(
        xq, wq, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


def pair_bf16(x, w_up, w_dn):
    y = (x @ w_up).astype(jnp.bfloat16)
    return (y @ w_dn).astype(jnp.bfloat16)


def pair_int8_dyn(x, w_up, w_dn):
    xq, xs = _quant_rows(x)
    uq, us = _quant_cols(w_up)
    y = (_dot_i8(xq, uq).astype(jnp.float32) * (xs * us)).astype(jnp.bfloat16)
    yq, ys = _quant_rows(y)
    dq, ds = _quant_cols(w_dn)
    return (_dot_i8(yq, dq).astype(jnp.float32) * (ys * ds)).astype(
        jnp.bfloat16
    )


def pair_int8_wq(x, uq, us, dq, ds):
    xq, xs = _quant_rows(x)
    y = (_dot_i8(xq, uq).astype(jnp.float32) * (xs * us)).astype(jnp.bfloat16)
    yq, ys = _quant_rows(y)
    return (_dot_i8(yq, dq).astype(jnp.float32) * (ys * ds)).astype(
        jnp.bfloat16
    )


def bench(fn, x, consts, flops_per_step: float) -> float:
    """Best-of-3 over STEPS chained steps (CHUNK per dispatch); TF/s."""
    loop = jax.jit(
        lambda x0, c: jax.lax.fori_loop(
            0, CHUNK, lambda i, xx: fn(xx, *c), x0
        )
    )
    out = loop(x, consts)
    _fence(out)
    best = float("inf")
    for _ in range(3):
        o = out
        t0 = time.perf_counter()
        for _ in range(STEPS // CHUNK):
            o = loop(o, consts)
        _fence(o)
        best = min(best, time.perf_counter() - t0)
    return STEPS * flops_per_step / best / 1e12


def main():
    dev = jax.devices()[0]
    print(f"# device: {dev.device_kind} ({dev.platform})")
    rng = np.random.RandomState(0)
    shapes = [
        ("d2048/ff6144/bt8192 (flagship MLP)", 8192, 2048, 6144),
        ("d2048/ff2048/bt8192 (attn-proj-ish)", 8192, 2048, 2048),
        ("d4096/ff14336/bt4096 (8B-class MLP)", 4096, 4096, 14336),
    ]
    for name, bt, d, ff in shapes:
        x = jnp.asarray(rng.rand(bt, d) - 0.5, jnp.bfloat16)
        w_up = jnp.asarray(rng.rand(d, ff) - 0.5, jnp.bfloat16)
        w_dn = jnp.asarray(rng.rand(ff, d) - 0.5, jnp.bfloat16)
        flops = 2 * bt * d * ff * 2  # up + down
        tf_bf16 = bench(pair_bf16, x, (w_up, w_dn), flops)
        tf_dyn = bench(pair_int8_dyn, x, (w_up, w_dn), flops)
        uq, us = jax.jit(_quant_cols)(w_up)
        dq, ds = jax.jit(_quant_cols)(w_dn)
        float(jnp.sum(us) + jnp.sum(ds))
        tf_wq = bench(pair_int8_wq, x, (uq, us, dq, ds), flops)
        print(
            f"{name}: bf16 {tf_bf16:.1f} TF/s | int8-dyn {tf_dyn:.1f} "
            f"({tf_dyn / tf_bf16:.2f}x) | int8-wq {tf_wq:.1f} "
            f"({tf_wq / tf_bf16:.2f}x)"
        )


if __name__ == "__main__":
    main()
