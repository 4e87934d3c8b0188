"""Plain float32 reference of the ``deepseek_v3`` layer as
``kakaocorp/kanana-2-30b-a3b-instruct-2601`` publishes it
(``config.json``, ``model_type: deepseek_v3``; the arithmetic is that of
Hugging Face's ``modeling_deepseek_v3.py``): pre-norm residual layers of
multi-head latent attention, the first ``first_k_dense_replace`` closed
by a SwiGLU, the others by sigmoid-routed SwiGLU experts beside shared
ones; a final RMSNorm and an untied head.

One layer on ``x [T, d]``:

- ``x1 = rmsnorm(x)``; ``q = x1 Wq`` -> per head ``q_nope | q_rope``;
  ``x1 Wkva`` -> ``c | k_rope``; ``c = rmsnorm(c)``; rotary embedding on
  ``q_rope`` of every head and on the single ``k_rope``, each pair of
  NEIGHBOURING columns turned by ``pos * theta ** (-2j / rope)``
  (``rope_interleave``); ``c Wkvb`` -> per head ``k_nope | v``; a key is
  ``k_nope | k_rope``; causal softmax of ``q . k / sqrt(nope + rope)``;
  ``Wo``.
- ``x2 = rmsnorm(x)``; a dense layer adds ``SwiGLU(x2)``; an expert
  layer scores ``s = sigmoid(x2 Wg)`` over all experts, chooses the
  ``num_experts_per_tok`` largest of ``s + b``, weighs the chosen by
  their ``s`` over the sum of those (``norm_topk_prob``) times
  ``routed_scaling_factor``, and adds ``sum_i w_i SwiGLU_i(x2)`` and the
  shared SwiGLU of width ``n_shared_experts * moe_intermediate_size``.

Written from that description in ``jax.numpy``: every matrix product in
float32 at ``highest`` precision; attention always expanded (every
position's keys and values are made from its latent row), never
absorbed; every expert applied to every position in turn and weighed by
a ``[T, experts]`` table that is zero where it was not chosen: no
sorting, no grouped product, no cache, no kernel. It imports nothing of
the program and nothing of another family's reference.

Departures from Hugging Face's listing. (1) The rotary pairs are turned
where they lie; HF moves evens before odds first and turns the halves.
The same numbers in another column order, which a dot product of a
query with a key does not see. (2) ``n_group = topk_group = 1``: the
group step chooses the one group there is and is left out. (3) HF runs
the router in float32 and the rest in the checkpoint's bfloat16; here
everything is float32. (4) For memory only: layers are walked one at a
time, an expert layer's experts one at a time (its weights are 2.4 GB
in float32), positions in blocks under ``jax.checkpoint``.
"""

from __future__ import annotations

import contextlib
import math
from typing import Dict

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512  # query positions scored against all keys at once
TOKEN_BLOCK = 1024  # positions through a layer's second half or the head
_HI = jax.lax.Precision.HIGHEST

# None for the reference itself. The control (the reference computed in
# a precision below the configuration's) rounds every matrix product's
# operands to this type first.
_OPERANDS = None


@contextlib.contextmanager
def operands_rounded_to(dtype):
    """While open, functions traced here round the operands of every
    matrix product to ``dtype``: the control of the tests' comparison,
    never the reference."""
    global _OPERANDS
    before, _OPERANDS = _OPERANDS, dtype
    try:
        yield
    finally:
        _OPERANDS = before


def _f32(x):
    if _OPERANDS is not None:
        x = x.astype(_OPERANDS)
    return x.astype(jnp.float32)


def _mm(a, w):
    return jnp.matmul(_f32(a), _f32(w), precision=_HI)


def _rmsnorm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _by_blocks(fn, x):
    """``fn`` over blocks of TOKEN_BLOCK positions of x [T, ...], one
    at a time, each under ``jax.checkpoint``."""
    t = x.shape[0]
    tb = min(TOKEN_BLOCK, t)
    pad = (-t) % tb
    blocks = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)).reshape(
        ((t + pad) // tb, tb) + x.shape[1:])
    out = jax.lax.map(jax.checkpoint(fn), blocks)
    return out.reshape((t + pad,) + out.shape[2:])[:t]


def _rope_pairs(x, theta):
    """x [T, H, rope], positions 0..T-1: the pair (x[2j], x[2j+1]) is
    turned by ``pos * theta ** (-2j / rope)``."""
    t, _, n = x.shape
    inv = theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1)
    return out.reshape(x.shape)


def _attention(q, k, v):
    """Causal attention of one sequence, q and k [T, H, dk], v [T, H,
    dv]; query blocks in turn so no [H, T, T] table is held."""
    t, h, dk = q.shape
    qb = min(QUERY_BLOCK, t)
    pad = (-t) % qb
    blocks = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, qb, h, dk)
    starts = jnp.arange(blocks.shape[0]) * qb

    @jax.checkpoint
    def block(args):
        qblk, start = args
        s = jnp.einsum("qhd,khd->hqk", _f32(qblk), _f32(k), precision=_HI)
        s = s / math.sqrt(dk)
        seen = jnp.arange(t)[None, :] <= (start + jnp.arange(qb))[:, None]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", _f32(p), _f32(v), precision=_HI)

    return jax.lax.map(block, (blocks, starts)).reshape(t + pad, h, -1)[:t]


def _swiglu(m, w1, w3, w2):
    return _mm(jax.nn.silu(_mm(m, w1)) * _mm(m, w3), w2)


def route(m, router, bias, config):
    """m [T, d] -> the [T, experts] table of weights, zero where an
    expert was not chosen."""
    s = jax.nn.sigmoid(_mm(m, router))
    left = s + bias.astype(jnp.float32)
    chosen = jnp.zeros(s.shape, bool)
    for _ in range(config["num_experts_per_tok"]):
        best = jnp.argmax(left, axis=-1)
        hit = jax.nn.one_hot(best, s.shape[-1], dtype=bool)
        chosen, left = chosen | hit, jnp.where(hit, -jnp.inf, left)
    w = jnp.where(chosen, s, 0.0)
    if config["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * config["routed_scaling_factor"]


def routed(m, table, w1, w3, w2):
    """sum over experts e of table[:, e] * SwiGLU_e(m): every expert in
    turn on every position; w1, w3 [E, d, f], w2 [E, f, d] are the
    experts of the table's columns."""

    def one(acc, args):
        col, a, b, c = args
        return acc + col[:, None] * _swiglu(m, a, b, c), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(m), (table.T, w1, w3, w2))
    return out


def layer_row(lp: Dict, x, config: Dict):
    """One layer on one sequence x [T, d] (float32)."""
    h, r = config["num_attention_heads"], config["kv_lora_rank"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    t = x.shape[0]
    a = _rmsnorm(x, lp["ln1"], eps)
    q = _mm(a, lp["wq"]).reshape(t, h, nope + rope)
    ckr = _mm(a, lp["wkva"])
    c = _rmsnorm(ckr[:, :r], lp["kv_norm"], eps)
    k_rope = _rope_pairs(ckr[:, None, r:], theta)
    q_rope = _rope_pairs(q[..., nope:], theta)
    kv = _mm(c, lp["wkvb"]).reshape(t, h, -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (t, h, rope))], axis=-1)
    q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    o = _attention(q, k, kv[..., nope:]).reshape(t, -1)
    x = x + _mm(o, lp["wo"])

    def second_half(xb):
        m = _rmsnorm(xb, lp["ln2"], eps)
        if "router" not in lp:
            return xb + _swiglu(m, lp["w1"], lp["w3"], lp["w2"])
        table = route(m, lp["router"], lp["router_bias"], config)
        return (xb + routed(m, table, lp["we1"], lp["we3"], lp["we2"])
                + _swiglu(m, lp["ws1"], lp["ws3"], lp["ws2"]))

    return _by_blocks(second_half, x)


def logits_row(params: Dict, tokens, config: Dict):
    """tokens [T] -> logits [T, V] of one sequence: a full forward
    pass, no cache. ``params["layers"]`` is {name: layer}, walked in the
    order of the names."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    for name in sorted(params["layers"]):
        x = jax.checkpoint(
            lambda x, lp: layer_row(lp, x, config))(x, params["layers"][name])
    x = _rmsnorm(x, params["ln_f"], config["rms_norm_eps"])
    return _by_blocks(lambda xb: _mm(xb, params["lm_head"]), x)
