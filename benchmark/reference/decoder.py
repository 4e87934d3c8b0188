"""Plain float32 reference of the decoder both configurations publish.

Mistral-7B-v0.3 and DeepSeek-LLM-7B are the same published block:
pre-norm residual layers of RMSNorm, rotary position embedding in the
rotate-half convention, causal grouped-query attention (one key/value
head per ``num_attention_heads / num_key_value_heads`` query heads),
a SwiGLU feed-forward, a final RMSNorm and an untied output head; the
training loss is the mean next-token cross entropy. Written from that
description in ``jax.numpy``: every matrix product in float32 at
``highest`` precision, no kernel, no cache, no batching trick. It
imports nothing of the program.

Departures from a textbook listing, both for memory only: the layers
are stored stacked (the optimizer's leaves are then the program's, see
``train_steps``) and rows and query blocks are walked one at a time
under ``jax.checkpoint``, so the reference fits beside nothing larger
than its own parameters and gradients.
"""

from __future__ import annotations

import contextlib
import math
from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

QUERY_BLOCK = 512  # query positions scored against all keys at once
TOKEN_BLOCK = 1024  # positions through the feed-forward or the head at once
_HI = jax.lax.Precision.HIGHEST


def dims(config: Dict) -> Tuple[int, int, int, int, int, int, int]:
    """(d, heads, kv heads, head dim, ff, layers, vocab) of a published
    ``config.json``."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    return (d, h, config["num_key_value_heads"], d // h,
            config["intermediate_size"], config["num_hidden_layers"],
            config["vocab_size"])


# None for the reference itself. The control (the reference put in the
# program's place, computed in a precision below the configuration's)
# rounds every matrix product's operands to this type first.
_OPERANDS = None


@contextlib.contextmanager
def operands_rounded_to(dtype):
    """While open, functions traced here round the operands of every
    matrix product to ``dtype``: the control of the comparison that
    decides ``correct`` (tests/benchmark), never the reference."""
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


def _rope(x, theta):
    """x [T, H, hd]; rotate-half convention, positions 0..T-1."""
    t, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention_row(q, k, v):
    """Causal attention of one sequence. q [T, H, hd]; k, v [T, KV, hd].
    Query blocks are walked in turn so no [H, T, T] table is held."""
    t, h, hd = q.shape
    groups = h // k.shape[1]
    k = jnp.repeat(k, groups, axis=1)
    v = jnp.repeat(v, groups, axis=1)
    qb = min(QUERY_BLOCK, t)
    pad = (-t) % qb
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    blocks = qp.reshape((t + pad) // qb, qb, h, hd)
    starts = jnp.arange(blocks.shape[0]) * qb

    @jax.checkpoint
    def block(args):
        qblk, start = args
        s = jnp.einsum("qhd,khd->hqk", _f32(qblk), _f32(k), precision=_HI)
        s = s / math.sqrt(hd)
        qpos = start + jnp.arange(qb)
        mask = jnp.arange(t)[None, :] <= qpos[:, None]
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", _f32(p), _f32(v), precision=_HI)

    out = jax.lax.map(block, (blocks, starts))
    return out.reshape(t + pad, h, hd)[:t]


def _by_blocks(fn, x, *more):
    """``fn`` over blocks of TOKEN_BLOCK positions of x [T, ...] (and of
    each array in ``more``), one block at a time, each under
    ``jax.checkpoint``: position-wise work needs no more of the
    sequence in memory than that."""
    t = x.shape[0]
    tb = min(TOKEN_BLOCK, t)
    pad = (-t) % tb
    args = tuple(
        jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
            ((t + pad) // tb, tb) + a.shape[1:])
        for a in (x,) + more)
    out = jax.lax.map(jax.checkpoint(lambda blk: fn(*blk)), args)
    return out.reshape((t + pad,) + out.shape[2:])[:t]


def _layer_row(lp, x, config):
    """One decoder layer on one sequence x [T, d]."""
    d, h, kv, hd, _, _, _ = dims(config)
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    t = x.shape[0]
    a = _rmsnorm(x, lp["ln1"], eps)
    q = _rope(_mm(a, lp["wq"]).reshape(t, h, hd), theta)
    k = _rope(_mm(a, lp["wk"]).reshape(t, kv, hd), theta)
    v = _mm(a, lp["wv"]).reshape(t, kv, hd)
    o = _attention_row(q, k, v).reshape(t, h * hd)
    x = x + _mm(o, lp["wo"])

    def feed_forward(xb):
        m = _rmsnorm(xb, lp["ln2"], eps)
        return xb + _mm(jax.nn.silu(_mm(m, lp["w1"])) * _mm(m, lp["w3"]),
                        lp["w2"])

    return _by_blocks(feed_forward, x)


def _layer(lp, x, config, spread=None):
    """One layer on x [G, R, T, d]: G is the axis a caller may spread
    over devices (``spread``: the sharding that keeps activations split
    along it); the R rows of a group are walked in turn."""
    row = jax.checkpoint(lambda xr: _layer_row(lp, xr, config))
    x = jax.vmap(lambda xs: jax.lax.map(row, xs))(x)
    if spread is not None:
        x = jax.lax.with_sharding_constraint(x, spread)
    return x


def _embed(params, tokens):
    return jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)


def _head_loss(ln_f, lm_head, x, targets, config):
    """Mean next-token cross entropy from the last layer's output."""
    x = _rmsnorm(x, ln_f, config["rms_norm_eps"])

    def nll(xb, tb):
        lg = _mm(xb, lm_head)
        lse = jax.nn.logsumexp(lg, axis=-1)
        return lse - jnp.take_along_axis(lg, tb[:, None], 1)[:, 0]

    rows = jax.vmap(lambda xs, ts: jax.lax.map(
        lambda a: jnp.mean(_by_blocks(nll, *a)), (xs, ts)))(x, targets)
    return jnp.mean(rows)


def logits_row(params, tokens, config):
    """tokens [T] -> logits [T, V] of one sequence (the serving check):
    a full forward pass, no cache."""
    x = _embed(params, tokens[None, None])
    x, _ = jax.lax.scan(
        lambda x, lp: (_layer(lp, x, config), None), x, params["layers"])
    x = _rmsnorm(x[0, 0], params["ln_f"], config["rms_norm_eps"])
    return _by_blocks(lambda xb: _mm(xb, params["lm_head"]), x)


def loss(params, tokens, config):
    """tokens [G, R, T+1] -> mean next-token cross entropy."""
    x = _embed(params, tokens[..., :-1])
    x, _ = jax.lax.scan(
        lambda x, lp: (_layer(lp, x, config), None), x, params["layers"])
    return _head_loss(params["ln_f"], params["lm_head"], x,
                      tokens[..., 1:], config)


def loss_and_grads(params, tokens, config, spread=None):
    """The same loss with its gradient, a layer at a time: forward keeps
    each layer's input, backward takes one layer's vector-Jacobian
    product per call, so no call holds more than one layer's
    temporaries. Equal to ``jax.value_and_grad(loss)`` (the tests pin
    it); this form fits beside 8 bytes a parameter."""
    n_layers = config["num_hidden_layers"]
    layer = jax.jit(lambda lp, x: _layer(lp, x, config, spread))

    @jax.jit
    def layer_vjp(lp, x, dy):
        _, pull = jax.vjp(lambda lp, x: _layer(lp, x, config, spread),
                          lp, x)
        return pull(dy)

    @jax.jit
    def head(ln_f, lm_head, x, targets):
        return jax.value_and_grad(
            lambda a, b, c: _head_loss(a, b, c, targets, config),
            argnums=(0, 1, 2))(ln_f, lm_head, x)

    def at(i):
        return jax.tree_util.tree_map(lambda a: a[i], params["layers"])

    xs = [jax.jit(_embed)(params, tokens[..., :-1])]
    for i in range(n_layers):
        xs.append(layer(at(i), xs[-1]))
    value, (g_ln_f, g_head, dx) = head(
        params["ln_f"], params["lm_head"], xs.pop(), tokens[..., 1:])
    per_layer = [None] * n_layers
    for i in reversed(range(n_layers)):
        per_layer[i], dx = layer_vjp(at(i), xs.pop(), dx)
    g_layers = {}
    for name in list(per_layer[0]):
        g_layers[name] = jnp.stack([g.pop(name) for g in per_layer])
    g_embed = jax.jit(
        lambda dx, tok: jnp.zeros_like(params["embed"]).at[tok].add(dx)
    )(dx, tokens[..., :-1])
    return value, {"embed": g_embed, "layers": g_layers, "ln_f": g_ln_f,
                   "lm_head": g_head}


def leaf_sumsq(tree) -> Dict[str, float]:
    """{leaf path: sum of squares} as host floats."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {
        jax.tree_util.keystr(path): float(jnp.sum(jnp.square(
            leaf.astype(jnp.float32))))
        for path, leaf in flat
    }


def train_steps(params, batches: List[np.ndarray], config: Dict,
                learning_rate: float, shardings=None, spread=None):
    """Follow the program's first steps: adafactor(learning_rate) on
    float32 parameters, one update per batch in ``batches`` (each
    [G, R, T+1] int32). Returns (losses, sum of squares of the first
    gradient per leaf, final parameters).

    The leaves are the stacked tree because adafactor's update clipping
    and parameter scaling take a root-mean-square over a whole leaf: a
    per-layer tree would be another optimizer. On several chips
    ``shardings`` is where each parameter (and its gradient) lives and
    ``spread`` how activations are split; None on one chip.
    """
    tx = optax.adafactor(learning_rate)

    @partial(jax.jit, donate_argnums=(0,))
    def update(p, g, st):
        u, st = tx.update(g, st, p)
        return optax.apply_updates(p, u), st

    # one optimizer per leaf: nothing in adafactor crosses leaves, and
    # a leaf at a time keeps the update's temporaries to one leaf
    leaves, treedef = jax.tree_util.tree_flatten(params)
    states = [tx.init(leaf) for leaf in leaves]
    losses, first = [], None
    for tokens in batches:
        value, grads = loss_and_grads(
            jax.tree_util.tree_unflatten(treedef, leaves), tokens, config,
            spread)
        if shardings is not None:
            grads = jax.device_put(grads, shardings)
        losses.append(float(value))
        if first is None:
            first = leaf_sumsq(grads)
        gl = jax.tree_util.tree_leaves(grads)
        del grads
        for i in range(len(leaves)):
            leaves[i], states[i] = update(leaves[i], gl[i], states[i])
            gl[i] = None
    return losses, first, jax.tree_util.tree_unflatten(treedef, leaves)
