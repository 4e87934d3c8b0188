"""Plain float32 reference of the power-retention decoder layer that
``manifestai/Brumby-14B-Base`` publishes (``config.json``, ``model_type:
brumby``): a pre-norm residual decoder of Qwen3's shapes (per-head
RMSNorm of queries and keys, rotary embedding, grouped kv heads,
SwiGLU, untied head) whose softmax attention is replaced by power
retention (Manifest AI, "Scaling Context Requires Rethinking
Attention", arXiv:2507.04239), in its ATTENTION form.

One layer on ``x [T, d]``, head width ``hd``, query head ``h`` in the
group ``j = h // (H / KV)`` of its kv head:

    a_t = rmsnorm(x_t, ln1)
    q_t^h = rope(rmsnorm(a_t Wq^h, q_norm), t)    v_t^j = a_t Wv^j
    k_t^j = rope(rmsnorm(a_t Wk^j, k_norm), t)
    log g_t^j = logsigmoid(a_t Wg^j + gate_bias * b_g^j)
    A_ts = (q_t^h . k_s^j / sqrt(hd)) ** p * exp(sum_{r=s+1..t} log g_r^j)
           for s <= t, else 0
    y_t^h = sum_s A_ts v_s^j / (sum_s A_ts + eps)
    x_t <- x_t + concat_h(y_t^h) Wo ;   x_t <- x_t + swiglu(rmsnorm(x_t, ln2))

Written from that description in ``jax.numpy``: every matrix product in
float32 at ``highest`` precision; the ``[T, T]`` weights of one kv
head's query heads made a block of query rows at a time; no state, no
chunk, no feature map, no cache, no kernel. It imports nothing of the
program and nothing of another family's reference.

Not in the published ``config.json`` and set here by the family's
convention (the configuration's file lists each under ``assumed``):
``retention_degree`` p = 2, ``gate_bias``, ``retention_eps``; one gate
scalar a kv head; the norms and RoPE of the dense layer; the output
normalised by the summed weights. Departures from the published
kernels that the writer knows of: none in the mathematics; for memory
only, layers are walked one at a time and positions in blocks under
``jax.checkpoint``. The rotary pairs are (column ``c``, column ``c +
hd / 2``), as Hugging Face's ``rotate_half`` has them.
"""

from __future__ import annotations

import contextlib
import math
from typing import Dict

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512  # query positions weighed against all keys at once
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


def _rope(x, theta):
    """x [T, H, hd], positions 0..T-1: the pair (x[c], x[c + hd / 2])
    is turned by ``pos * theta ** (-2c / hd)``."""
    t, _, n = x.shape
    inv = theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    lo, hi = x[..., :n // 2], x[..., n // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def retention(q, k, v, log_g, degree: int, eps: float):
    """The attention form for one kv head: q [T, G, hd] (its query
    heads), k and v [T, hd], log_g [T] -> [T, G, hd]. Query blocks in
    turn, so no [G, T, T] table is held."""
    t, g, hd = q.shape
    qb = min(QUERY_BLOCK, t)
    pad = (-t) % qb
    run = jnp.cumsum(log_g)  # position t's own decay is in run[t]
    blocks = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, qb, g, hd)
    runs = jnp.pad(run, (0, pad)).reshape(-1, qb)
    starts = jnp.arange(blocks.shape[0]) * qb

    @jax.checkpoint
    def block(args):
        qblk, rblk, start = args
        s = jnp.einsum("qgd,kd->gqk", _f32(qblk), _f32(k), precision=_HI)
        s = (s / math.sqrt(hd)) ** degree
        seen = jnp.arange(t)[None, :] <= (start + jnp.arange(qb))[:, None]
        decay = jnp.exp(jnp.where(seen, rblk[:, None] - run[None, :], 0.0))
        a = jnp.where(seen[None], s * decay[None], 0.0)
        num = jnp.einsum("gqk,kd->qgd", _f32(a), _f32(v), precision=_HI)
        return num / (jnp.sum(a, axis=-1).T[..., None] + eps)

    return jax.lax.map(block, (blocks, runs, starts)).reshape(
        t + pad, g, hd)[:t]


def _swiglu(m, w1, w3, w2):
    return _mm(jax.nn.silu(_mm(m, w1)) * _mm(m, w3), w2)


def layer_row(lp: Dict, x, config: Dict):
    """One layer on one sequence x [T, d] (float32)."""
    h, kvh = config["num_attention_heads"], config["num_key_value_heads"]
    hd, eps = config["head_dim"], config["rms_norm_eps"]
    theta = float(config["rope_theta"])
    t = x.shape[0]
    a = _rmsnorm(x, lp["ln1"], eps)
    q = _rope(_rmsnorm(_mm(a, lp["wq"]).reshape(t, h, hd), lp["q_norm"],
                       eps), theta)
    k = _rope(_rmsnorm(_mm(a, lp["wk"]).reshape(t, kvh, hd), lp["k_norm"],
                       eps), theta)
    v = _mm(a, lp["wv"]).reshape(t, kvh, hd)
    log_g = jax.nn.log_sigmoid(
        _mm(a, lp["wg"])
        + config["gate_bias"] * lp["bg"].astype(jnp.float32))  # [T, KV]
    y = jax.lax.map(
        lambda args: retention(*args, config["retention_degree"],
                               config["retention_eps"]),
        (q.reshape(t, kvh, h // kvh, hd).transpose(1, 0, 2, 3),
         k.transpose(1, 0, 2), v.transpose(1, 0, 2), log_g.T))
    x = x + _mm(y.transpose(1, 0, 2, 3).reshape(t, h * hd), lp["wo"])
    return _by_blocks(
        lambda xb: xb + _swiglu(_rmsnorm(xb, lp["ln2"], eps),
                                lp["w1"], lp["w3"], lp["w2"]), x)


def logits_row(params: Dict, tokens, config: Dict):
    """tokens [T] -> logits [T, V] of one sequence: a full forward
    pass, no cache. ``params["layers"]`` holds every layer's leaves
    stacked on a leading axis, walked one layer at a time."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    for i in range(config["num_hidden_layers"]):
        lp = {name: leaf[i] for name, leaf in params["layers"].items()}
        x = jax.checkpoint(lambda x, lp: layer_row(lp, x, config))(x, lp)
    x = _rmsnorm(x, params["ln_f"], config["rms_norm_eps"])
    return _by_blocks(lambda xb: _mm(xb, params["lm_head"]), x)
