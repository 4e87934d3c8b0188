"""Plain float32 reference of the ``glm_moe_dsa`` layer as
``zai-org/GLM-5`` publishes it (``config.json``, ``model_type:
glm_moe_dsa``): pre-norm residual layers of multi-head latent attention
with a low-rank query, in which a learned indexer chooses the
``index_topk`` earlier positions each query attends; the first
``first_k_dense_replace`` layers closed by a SwiGLU, the others by
sigmoid-routed SwiGLU experts beside a shared one; a final RMSNorm and
an untied head.

One layer on ``x [T, d]``, ``a = rmsnorm(x)``:

- ``cq = rmsnorm(a Wqa)``; ``q = cq Wqb`` -> per head ``q_nope |
  q_rope``; ``a Wkva`` -> ``c | k_rope``; ``c = rmsnorm(c)``; rotary
  embedding on ``q_rope`` of every head and on the single ``k_rope``,
  each pair of NEIGHBOURING columns turned by ``pos * theta ** (-2j /
  rope)``; ``c Wkvb`` -> per head ``k_nope | v``; a key is ``k_nope |
  k_rope``.
- the indexer: ``qI = cq WqI`` -> ``index_n_heads`` heads of
  ``index_head_dim``; ``kI = layernorm(a WkI)``, one key a position;
  the first ``qk_rope_head_dim`` columns of both turned the same way;
  ``w = a Ww * index_n_heads ** -0.5 * index_head_dim ** -0.5``;
  ``I[t, s] = sum_h w[t, h] relu(qI[t, h] . kI[s])`` for ``s <= t``.
  ``S_t``: the ``index_topk`` positions of largest ``I[t, .]`` (every
  ``s <= t`` while there are no more), the lower position first among
  equals.
- softmax over ``s`` in ``S_t`` of ``q . k / sqrt(nope + rope)``; ``Wo``.
- ``m = rmsnorm(x)``; a dense layer adds ``SwiGLU(m)``; an expert layer
  scores ``s = sigmoid(m Wg)`` over ALL the experts the model has,
  chooses the ``num_experts_per_tok`` largest of ``s + b``, weighs the
  chosen by their ``s`` over the sum of those (``norm_topk_prob``) times
  ``routed_scaling_factor``, and adds the terms of the experts HELD
  (``n_routed_experts`` of them from ``first_routed_expert``; the
  router's width is ``published.n_routed_experts``) and the shared
  SwiGLU. What the absent experts would add is left out, as the chip
  that holds this share leaves it out, and the partial sum goes on.

Written from that description in ``jax.numpy``: every matrix product in
float32 at ``highest`` precision; attention always expanded and dense,
the positions not chosen masked out; the selection by a full sort of
each query's scores; every held expert applied to every position in
turn and weighed by a ``[T, experts]`` table that is zero where it was
not chosen: no bisection, no gather, no grouped product, no cache, no
kernel. It imports nothing of the program and nothing of another
family's reference.

Departures from the published listing. (1) The rotary pairs are turned
where they lie. (2) ``n_group = topk_group = 1``: the group step is left
out. (3) Everything is float32: no FP8 index keys and no Hadamard
rotation of ``qI`` and ``kI`` (an orthogonal rotation of both leaves
``I`` unchanged). (4) The multi-token-prediction module is left out: it
does not enter the next-token distribution. (5) For memory and time
only: layers one at a time, the selection in blocks of queries and kept
as tables of bytes, attention by groups of heads and blocks of queries,
an expert layer's experts one at a time, positions in blocks; and a
long sequence's queries in ``SEGMENTS`` runs, each scored, sorted and
attended against the positions up to its own end alone (those after it
are masked out whatever they hold: 10/16 of the square's work).
"""

from __future__ import annotations

import contextlib
import math
from typing import Dict

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256  # query positions scored against all keys at once
SEGMENTS = 4  # runs of queries, each against the keys up to its end
HEAD_GROUP = 8  # heads whose keys and values are expanded at once
TOKEN_BLOCK = 1024  # positions through a layer's second half or the head
INDEX_NORM_EPS = 1e-6  # the index key's LayerNorm (assumed)
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


def _layernorm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
            + b.astype(jnp.float32))


def _blocks(x, size):
    """x [T, ...] -> ([n, size, ...] zero-padded, the blocks' starts)."""
    t = x.shape[0]
    pad = (-t) % size
    out = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)).reshape(
        ((t + pad) // size, size) + x.shape[1:])
    return out, jnp.arange(out.shape[0]) * size


def _by_blocks(fn, x):
    """``fn`` over blocks of TOKEN_BLOCK positions of x [T, ...], one
    at a time."""
    t = x.shape[0]
    blocks, _ = _blocks(x, min(TOKEN_BLOCK, t))
    out = jax.lax.map(fn, blocks)
    return out.reshape((-1,) + out.shape[2:])[:t]


def _rope_pairs(x, theta):
    """x [T, H, n], positions 0..T-1: the pair (x[2j], x[2j+1]) is
    turned by ``pos * theta ** (-2j / n)``."""
    t, _, n = x.shape
    inv = theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1)
    return out.reshape(x.shape)


def _rope_first(x, n, theta):
    return jnp.concatenate([_rope_pairs(x[..., :n], theta), x[..., n:]], -1)


def _segments(t: int):
    """(start, end) of the runs of queries a sequence of ``t`` positions
    is walked in: ``SEGMENTS`` equal runs of whole query blocks, or the
    sequence whole where it does not divide so."""
    n = SEGMENTS if t % (SEGMENTS * QUERY_BLOCK) == 0 else 1
    return [(j * t // n, (j + 1) * t // n) for j in range(n)]


def chosen(qi, w, ki, topk: int):
    """Which positions each query attends, a table (int8) a run of
    queries: ``[end - start, end]`` for the run ``start .. end`` against
    the positions ``0 .. end`` (a later one is never seen). qi [T, hI,
    dI], w [T, hI], ki [T, dI]. Query blocks in turn; a query's scores
    are sorted whole and the ``topk``-th largest is the bar; of the
    positions that hold exactly the bar, the lowest."""
    return [_chosen_run(qi[s:e], w[s:e], ki[:e], s, topk)
            for s, e in _segments(qi.shape[0])]


def _chosen_run(qi, w, ki, first: int, topk: int):
    """The table [Q, S] of the queries qi [Q, hI, dI], which stand at
    the positions ``first .. first + Q``, over the keys ki [S, dI] at
    ``0 .. S``."""
    t, n = ki.shape[0], qi.shape[0]
    qb = min(QUERY_BLOCK, n)
    (q_blocks, starts), (w_blocks, _) = _blocks(qi, qb), _blocks(w, qb)
    k = min(topk, t)

    def block(args):
        qblk, wblk, start = args
        s = jnp.einsum("qhd,kd->qhk", _f32(qblk), _f32(ki), precision=_HI)
        score = jnp.sum(jax.nn.relu(s) * wblk[:, :, None], axis=1)
        seen = jnp.arange(t)[None, :] <= (
            first + start + jnp.arange(qb))[:, None]
        score = jnp.where(seen, score, -jnp.inf)
        bar = jnp.sort(score, axis=-1)[:, t - k][:, None]
        above = score > bar
        level = (score == bar) & seen
        room = k - jnp.sum(above, axis=-1, keepdims=True)
        level = level & (jnp.cumsum(level, axis=-1) <= room)
        return ((above | level) & seen).astype(jnp.int8)

    out = jax.lax.map(block, (q_blocks, w_blocks, starts))
    return out.reshape(-1, t)[:n]


def _attention(q, k, v, table):
    """Attention of one group of heads over the positions ``table [Q,
    S]`` marks: q [Q, G, dk], k [S, G, dk], v [S, G, dv]; query blocks
    in turn so no [G, Q, S] table is held."""
    t, g, dk = q.shape
    qb = min(QUERY_BLOCK, t)
    (q_blocks, _), (t_blocks, _) = _blocks(q, qb), _blocks(table, qb)

    def block(args):
        qblk, tblk = args
        s = jnp.einsum("qhd,khd->hqk", _f32(qblk), _f32(k), precision=_HI)
        s = jnp.where(tblk[None] > 0, s / math.sqrt(dk), -jnp.inf)
        # a padded query row marks nothing: all -inf, softmax NaN, cut off
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", _f32(p), _f32(v), precision=_HI)

    out = jax.lax.map(block, (q_blocks, t_blocks))
    return out.reshape((-1,) + out.shape[2:])[:t]


def _swiglu(m, w1, w3, w2):
    return _mm(jax.nn.silu(_mm(m, w1)) * _mm(m, w3), w2)


def route(m, router, bias, config):
    """m [T, d] -> the [T, experts] table of weights over ALL the
    experts the router scores, zero where an expert was not chosen."""
    s = jax.nn.sigmoid(_mm(m, router))
    left = s + bias.astype(jnp.float32)
    picked = jnp.zeros(s.shape, bool)
    for _ in range(config["num_experts_per_tok"]):
        best = jnp.argmax(left, axis=-1)
        hit = jax.nn.one_hot(best, s.shape[-1], dtype=bool)
        picked, left = picked | hit, jnp.where(hit, -jnp.inf, left)
    w = jnp.where(picked, s, 0.0)
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


def held_columns(table, lp, config):
    """The columns of the router's table whose experts are held: the
    layer's ``we*`` leaves hold ``n_routed_experts`` of them from
    ``first_routed_expert``."""
    first = int(config.get("first_routed_expert", 0))
    return table[:, first:first + lp["we1"].shape[0]]


def theta_of(config: Dict) -> float:
    rope = config.get("rope_parameters") or {}
    return float(rope.get("rope_theta", config.get("rope_theta", 1e6)))


def layer_row(lp: Dict, x, config: Dict):
    """One layer on one sequence x [T, d] (float32)."""
    h, r = config["num_attention_heads"], config["kv_lora_rank"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    hi, di = config["index_n_heads"], config["index_head_dim"]
    eps, theta = config["rms_norm_eps"], theta_of(config)
    t = x.shape[0]
    a = _rmsnorm(x, lp["ln1"], eps)
    cq = _rmsnorm(_mm(a, lp["wqa"]), lp["q_norm"], eps)
    q = _mm(cq, lp["wqb"]).reshape(t, h, nope + rope)
    q = jnp.concatenate(
        [q[..., :nope], _rope_pairs(q[..., nope:], theta)], axis=-1)
    ckr = _mm(a, lp["wkva"])
    c = _rmsnorm(ckr[:, :r], lp["kv_norm"], eps)
    k_rope = _rope_pairs(ckr[:, None, r:], theta)

    qi = _rope_first(_mm(cq, lp["wqi"]).reshape(t, hi, di), rope, theta)
    ki = _layernorm(_mm(a, lp["wki"]), lp["ki_norm"], lp["ki_bias"],
                    INDEX_NORM_EPS)
    ki = _rope_first(ki[:, None], rope, theta)[:, 0]
    w = _mm(a, lp["ww"]) * (hi ** -0.5 * di ** -0.5)
    tables = chosen(qi, w, ki, config["index_topk"])

    g = min(HEAD_GROUP, h)
    wkvb = lp["wkvb"].reshape(r, h // g, g, -1)

    def heads(args):
        qg, wg = args  # [T, G, dk], [r, G, nope + v]
        kv = _mm(c, wg.reshape(r, -1)).reshape(t, g, -1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, (t, g, rope))], axis=-1)
        v = kv[..., nope:]
        return jnp.concatenate([
            _attention(qg[s:e], k[:e], v[:e], table)
            for (s, e), table in zip(_segments(t), tables)])

    o = jax.lax.map(heads, (
        jnp.moveaxis(q.reshape(t, h // g, g, -1), 1, 0),
        jnp.moveaxis(wkvb, 1, 0)))  # [h / G, T, G, v]
    x = x + _mm(jnp.moveaxis(o, 0, 1).reshape(t, -1), lp["wo"])

    def second_half(xb):
        m = _rmsnorm(xb, lp["ln2"], eps)
        if "router" not in lp:
            return xb + _swiglu(m, lp["w1"], lp["w3"], lp["w2"])
        table = held_columns(
            route(m, lp["router"], lp["router_bias"], config), lp, config)
        return (xb + routed(m, table, lp["we1"], lp["we3"], lp["we2"])
                + _swiglu(m, lp["ws1"], lp["ws3"], lp["ws2"]))

    return _by_blocks(second_half, x)


def logits_row(params: Dict, tokens, config: Dict):
    """tokens [T] -> logits [T, V] of one sequence: a full forward
    pass, no cache. ``params["layers"]`` is {name: layer}, walked in the
    order of the names."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    for name in sorted(params["layers"]):
        x = layer_row(params["layers"][name], x, config)
    x = _rmsnorm(x, params["ln_f"], config["rms_norm_eps"])
    return _by_blocks(lambda xb: _mm(xb, params["lm_head"]), x)
