"""Plain float32 reference of the decoder that
``ibm-granite/granite-4.0-h-micro`` publishes (``config.json``,
``model_type: granitemoehybrid`` with no experts; Hugging Face's
``modeling_granitemoehybrid.py`` is the published description): a
pre-norm residual decoder whose layer ``l`` mixes by a Mamba-2 layer
(arXiv:2405.21060) where ``layer_types[l] == "mamba"`` and by softmax
attention with no positional embedding where it is ``"attention"``, a
SwiGLU after each, the head tied to the embedding.

With ``e`` = ``embedding_multiplier``, ``r`` = ``residual_multiplier``,
on one sequence ``x [T, d]``:

    x0      = e * embed[token]
    layer l:  a = rmsnorm(x, ln1);  x = x + r * mix_l(a)
              m = rmsnorm(x, ln2);  x = x + r * W2 (silu(W1 m) * (W3 m))
    logits  = (rmsnorm(x_L, ln_f) @ embed^T) / logits_scaling

    attention:  q = a Wq (H heads), k = a Wk, v = a Wv (KV heads),
                softmax(attention_multiplier * q k^T + causal) v, then Wo

    mamba2 (H heads of width P, state width N, one group, K taps):
        z | xBC = a @ in_proj ;  dt_raw = a @ dt_proj
        xBC_t = silu(sum_j conv_w[j] * xBC_{t-K+1+j} + conv_b)
        x | B | C = xBC_t ;  dt = softplus(dt_raw + dt_bias) ;  A = -exp(A_log)
        S_t = exp(dt A) S_{t-1} + (dt x_t) B_t^T        [H, P, N]
        y_t = S_t C_t + D x_t
        out = rmsnorm(y_t * silu(z), norm) @ out_proj

Written from that description in ``jax.numpy``: every matrix product in
float32 at ``highest`` precision; the recurrence position by position
(``lax.scan`` over ``t``: no chunked form); the convolution as ``K``
shifted products; no cache, no kernel. It imports nothing of the
program and nothing of another family's reference.

Stored layouts (the configuration's file lists them under ``assumed``):
``in_proj``'s columns ``z | xBC`` and the ``dt`` columns as a matrix of
their own, the SwiGLU's two input halves ``w1`` / ``w3``, the
convolution ``[K, channels]`` with tap ``K - 1`` on the current input.
``dt`` is not clamped. Departures from the published description that
the writer knows of: none in the mathematics; for memory only, layers
are walked one at a time and positions in blocks under
``jax.checkpoint``.
"""

from __future__ import annotations

import contextlib
from typing import Dict

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512  # query positions weighed against all keys at once
TOKEN_BLOCK = 1024  # positions through a projection, a SwiGLU or the head
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


def _swiglu(m, w1, w3, w2):
    return _mm(jax.nn.silu(_mm(m, w1)) * _mm(m, w3), w2)


def attention(q, k, v, scale: float):
    """Causal softmax attention of one sequence: q [T, H, hd], k and v
    [T, KV, hd] -> [T, H, hd]. Query blocks in turn, so no [H, T, T]
    table is held."""
    t, h, hd = q.shape
    kvh = k.shape[1]
    qb = min(QUERY_BLOCK, t)
    pad = (-t) % qb
    blocks = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, qb, kvh, h // kvh, hd)
    starts = jnp.arange(blocks.shape[0]) * qb

    @jax.checkpoint
    def block(args):
        qblk, start = args
        s = jnp.einsum("qkgd,skd->kgqs", _f32(qblk), _f32(k),
                       precision=_HI) * scale
        seen = jnp.arange(t)[None, :] <= (start + jnp.arange(qb))[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", _f32(p), _f32(v), precision=_HI)

    return jax.lax.map(block, (blocks, starts)).reshape(
        t + pad, h, hd)[:t]


def selective_scan(x, b, c, dt, a, d):
    """The recurrence of one sequence, position by position: x [T, H,
    P], b and c [T, N], dt [T, H], a and d [H] -> y [T, H, P]."""
    h, p = x.shape[1:]

    def step(s, at):
        x_t, b_t, c_t, dt_t = at
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return s, jnp.sum(s * c_t[None, None, :], axis=-1) + d[:, None] * x_t

    s0 = jnp.zeros((h, p, b.shape[-1]), jnp.float32)
    return jax.lax.scan(step, s0, (x, b, c, dt))[1]


def mamba_row(lp: Dict, a, config: Dict):
    """The Mamba-2 mixing of one sequence: a [T, d] (normed) -> [T, d]."""
    heads, p = config["mamba_n_heads"], config["mamba_d_head"]
    n, taps = config["mamba_d_state"], config["mamba_d_conv"]
    di = heads * p
    t = a.shape[0]
    zx = _by_blocks(lambda blk: _mm(blk, lp["in_proj"]), a)
    dt = jax.nn.softplus(
        _by_blocks(lambda blk: _mm(blk, lp["dt_proj"]), a)
        + lp["dt_bias"].astype(jnp.float32))
    z, xbc = zx[:, :di], zx[:, di:]
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    conv = lp["conv_b"].astype(jnp.float32) + sum(
        padded[j:j + t] * lp["conv_w"][j].astype(jnp.float32)
        for j in range(taps))
    xbc = jax.nn.silu(conv)
    x = xbc[:, :di].reshape(t, heads, p)
    y = selective_scan(
        x, xbc[:, di:di + n], xbc[:, di + n:], dt,
        -jnp.exp(lp["A_log"].astype(jnp.float32)),
        lp["D"].astype(jnp.float32)).reshape(t, di)
    g = _rmsnorm(y * jax.nn.silu(z), lp["norm"], config["rms_norm_eps"])
    return _by_blocks(lambda blk: _mm(blk, lp["out_proj"]), g)


def attention_row(lp: Dict, a, config: Dict):
    """The attention mixing of one sequence: a [T, d] -> [T, d]."""
    h, kvh = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["head_dim"]
    t = a.shape[0]
    q = _mm(a, lp["wq"]).reshape(t, h, hd)
    k = _mm(a, lp["wk"]).reshape(t, kvh, hd)
    v = _mm(a, lp["wv"]).reshape(t, kvh, hd)
    o = attention(q, k, v, config["attention_multiplier"])
    return _mm(o.reshape(t, h * hd), lp["wo"])


def layer_row(kind: str, lp: Dict, x, config: Dict):
    """One layer of ``kind`` on one sequence x [T, d] (float32)."""
    eps, r = config["rms_norm_eps"], config["residual_multiplier"]
    mix = mamba_row if kind == "mamba" else attention_row
    x = x + r * mix(lp, _rmsnorm(x, lp["ln1"], eps), config)
    return _by_blocks(
        lambda xb: xb + r * _swiglu(_rmsnorm(xb, lp["ln2"], eps),
                                    lp["w1"], lp["w3"], lp["w2"]), x)


def logits_row(params: Dict, tokens, config: Dict):
    """tokens [T] -> logits [T, V] of one sequence: a full forward
    pass, no cache. ``params["mamba"]`` and ``params["attn"]`` hold the
    layers of each kind stacked on a leading axis, in the order
    ``layer_types`` names them; walked one layer at a time."""
    x = config["embedding_multiplier"] * jnp.take(
        params["embed"], tokens, axis=0).astype(jnp.float32)
    seen = {"mamba": 0, "attention": 0}
    for kind in config["layer_types"]:
        tree = params["mamba" if kind == "mamba" else "attn"]
        lp = {name: leaf[seen[kind]] for name, leaf in tree.items()}
        seen[kind] += 1
        x = jax.checkpoint(
            lambda x, lp, kind=kind: layer_row(kind, lp, x, config))(x, lp)
    x = _rmsnorm(x, params["ln_f"], config["rms_norm_eps"])
    head = params["embed"]
    return _by_blocks(
        lambda xb: jnp.einsum("td,vd->tv", _f32(xb), _f32(head),
                              precision=_HI), x
    ) / config["logits_scaling"]
