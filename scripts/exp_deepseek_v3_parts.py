"""Where a served ``deepseek_v3`` model parts from its float32
reference, piece by piece on one chip at published widths (three
layers): the flash forward at 192 / 128, ``edl_decode_attn_latent``,
the grouped expert matmuls, then the whole forward and prefill + decode
against ``benchmark/reference/mla_moe.py``.

    PYTHONPATH=. python scripts/exp_deepseek_v3_parts.py
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness
from benchmark.families import mla_moe as family
from benchmark.reference import mla_moe as reference
from edl_tpu.models import deepseek_v3 as ds
from edl_tpu.ops.decode_attention import decode_attention_latent
from edl_tpu.ops.flash_attention import flash_attention
from edl_tpu.parallel import moe


def say(**kw):
    print(json.dumps({k: (round(float(v), 6) if not isinstance(v, (str, int))
                          else v) for k, v in kw.items()}), flush=True)


def err(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))


def flash_part():
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    t, h = 1024, 32
    q = jax.random.normal(k[0], (1, t, h, 192), jnp.bfloat16)
    kk = jax.random.normal(k[1], (1, t, h, 192), jnp.bfloat16)
    v = jax.random.normal(k[2], (1, t, h, 128), jnp.bfloat16)
    got = flash_attention(q, kk, v)
    s = jnp.einsum("bthd,bshd->bhts", q, kk,
                   preferred_element_type=jnp.float32) / np.sqrt(192)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    want = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1),
                      v.astype(jnp.float32))
    say(part="flash 192/128", max_err=err(got, want),
        scale=float(jnp.max(jnp.abs(want))))


def latent_part():
    k = jax.random.split(jax.random.PRNGKey(1), 2)
    layers, b, s, w = 2, 16, 4096, 640
    cache = jax.random.normal(k[0], (layers, b, s, w), jnp.bfloat16)
    q = jax.random.normal(k[1], (b, 32, w), jnp.bfloat16)
    pos = jnp.asarray(np.random.default_rng(0).integers(0, s, b), jnp.int32)
    for bs in (256, 1024):
        got = decode_attention_latent(q, cache, pos, jnp.int32(1), rank=512,
                                      sm_scale=192 ** -0.5, block_s=bs)
        want = ds.slot_attention_latent_dense(
            q.astype(jnp.float32), cache[1].astype(jnp.float32), pos, 512,
            192 ** -0.5)
        say(part=f"latent kernel block {bs}", max_err=err(got, want),
            scale=float(jnp.max(jnp.abs(want))))


def expert_part():
    e, d, f, kk = 128, 2048, 768, 6
    k = jax.random.split(jax.random.PRNGKey(2), 6)
    router = jax.random.normal(k[1], (d, e), jnp.bfloat16) * d ** -0.5
    bias = jax.random.normal(k[5], (e,), jnp.bfloat16) * 0.02
    w1 = jax.random.normal(k[2], (e, d, f), jnp.bfloat16) * d ** -0.5
    w3 = jax.random.normal(k[3], (e, d, f), jnp.bfloat16) * d ** -0.5
    w2 = jax.random.normal(k[4], (e, f, d), jnp.bfloat16) * f ** -0.5
    config = {"num_experts_per_tok": kk, "norm_topk_prob": True,
              "routed_scaling_factor": 2.448}
    for n in (96, 1024):
        x = jax.random.normal(k[0], (n, d), jnp.bfloat16)
        idx, w = jax.jit(lambda x: moe.route_sigmoid_topk(
            x, router, bias, kk, 2.448))(x)
        got = jax.jit(lambda x, idx, w: moe.moe_dropless(
            x, idx, w, w1, w3, w2))(x, idx, w)
        table = reference.route(x, router, bias, config)
        dense = jnp.zeros((n, e)).at[jnp.arange(n)[:, None], idx].set(w)
        want = jax.jit(reference.routed)(
            x.astype(jnp.float32), dense, w1, w3, w2)
        say(part=f"experts n={n}", max_err=err(got, want),
            scale=float(jnp.max(jnp.abs(want))),
            table_err=err(table, dense),
            tokens_with_another_choice=int(jnp.sum(jnp.any(
                (table > 0) != (dense > 0), axis=-1))))


def gaps(ref, got_logits):
    first = jnp.argmax(got_logits, axis=-1)
    gap = jnp.max(ref, -1) - jnp.take_along_axis(ref, first[:, None], 1)[:, 0]
    return float(gap.max()), float(gap.mean()), int(jnp.sum(gap > 0))


def model_part(layers=3, t=512):
    config = dict(harness.load_json("benchmark/configs/kanana2-30b-a3b-L8.json"))
    config["num_hidden_layers"] = layers
    layout = family.param_layout(config)
    params = harness.make_params(7, layout, jnp.bfloat16)
    tokens = jnp.asarray(np.random.default_rng(7).integers(
        0, config["vocab_size"], t, dtype=np.int32))
    ref = jax.jit(lambda p, tk: reference.logits_row(p, tk, config))(
        params, tokens)
    base = family.program_config(config, training=False)
    for name, cfg in (
        ("bf16 flash", base),
        ("bf16 dense", dataclasses.replace(base, use_flash=False)),
        ("f32 dense", dataclasses.replace(base, use_flash=False,
                                          dtype=jnp.float32)),
    ):
        lg = jax.jit(lambda p, tk: ds.forward(p, tk[None], cfg)[0])(
            params, tokens)
        worst, mean, n = gaps(ref, lg)
        say(part=f"forward {name}", logits_err=err(lg, ref), gap_max=worst,
            gap_mean=mean, not_first=n, of=t)
    with reference.operands_rounded_to(jnp.bfloat16):
        low = jax.jit(lambda p, tk: reference.logits_row(p, tk, config))(
            params, tokens)
    worst, mean, n = gaps(ref, low)
    say(part="reference with bf16 operands", logits_err=err(low, ref),
        gap_max=worst, gap_mean=mean, not_first=n, of=t)
    # prefill 256, then decode through the kernel
    cfg = base
    pre = 256
    logits, rows = jax.jit(lambda p, tk: ds.prefill_padded(
        p, tk[None, :pre], jnp.array([pre - 1]), cfg))(params, tokens)
    cache = jnp.zeros((layers, 4, 1024, cfg.cache_width), cfg.dtype)
    cache = cache.at[:, 1:2, :pre].set(rows)
    step = jax.jit(lambda p, tok, pos, c: ds.decode_step_slots(
        p, tok, pos, c, cfg)[:2])
    got = [logits[0]]
    for p_ in range(pre, pre + 64):
        tok = jnp.zeros(4, jnp.int32).at[1].set(tokens[p_])
        pos = jnp.zeros(4, jnp.int32).at[1].set(p_)
        lg, cache = step(params, tok, pos, cache)
        got.append(lg[1])
    got = jnp.stack(got)
    worst, mean, n = gaps(ref[pre - 1:pre + 64], got)
    say(part="prefill 256 + 64 decode steps (kernels)",
        logits_err=err(got, ref[pre - 1:pre + 64]), gap_max=worst,
        gap_mean=mean, not_first=n, of=65)


if __name__ == "__main__":
    print(jax.devices(), flush=True)
    flash_part()
    latent_part()
    expert_part()
    model_part()
