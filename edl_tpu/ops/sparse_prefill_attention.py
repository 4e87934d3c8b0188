"""Pallas TPU kernel: a prefill piece's attention over the positions its
indexer chose (``models/glm_dsa.py``, multi-head latent attention with
a learned indexer), the masked sweep as one call a layer a piece.

What it computes is ``glm_dsa._sweep``: queries ``q [B, P, H, nope +
rope]`` of one piece against the latent rows ``lat[layer]`` of the key
blocks ``0 .. n_blocks - 1``, each block's keys and values expanded out
of its rows by the head's slice of ``Wkvb`` as it is visited, scores in
float32 from bfloat16 operands, the entries ``sel`` does not mark out
of the softmax, a running softmax in float32, ``p`` cast to the
operands' dtype for the value product, ``o = acc / l``.

Why a kernel: in XLA the sweep's loop state lives in HBM, so a visit of
a 512-key block by a 2048-row piece of 64 heads writes and reads the
scores (268 MB in float32), the exponentials and the accumulator
(134 MB each way): about 1 GB of traffic around 84 GFLOP of matmul.
Here ``m``, ``l`` and ``acc`` of a group of heads stay in VMEM across
the key blocks, nothing score-sized reaches HBM, and the output is
written once.

Form:

- grid ``(batch, head groups, key blocks)``, key blocks innermost; the
  whole piece is the query tile of a head, so a head's keys and values
  are expanded once a piece: ``rows [bk, W] x Wk_h [W, nope + rope]``
  and ``rows[:, :rank] x Wv_h [rank, v]`` inside the kernel.
  ``Wk_h`` carries the head's ``W_uk`` over the ``rank`` columns and an
  identity over the row's RoPE columns, so the 256-wide key of a head
  leaves one product (the shared RoPE columns pass through exactly: one
  bfloat16 times 1.0, accumulated in float32 with zeros) and the scores
  are one 256-deep product, not a 192-deep and a 64-deep one;
- the selection arrives as int8, one byte an entry, and is made a
  float32 bias (0 / ``NEG_INF``) once a grid step for all the heads of
  the group: a head pays one add an entry for it. A masked entry's
  ``exp2`` is an exact 0 beside any attended one, and a row that has
  attended nothing yet is wiped by the first attended entry's maximum
  (``decode_attention``'s ``chosen`` does the same); a row that attends
  nothing at all reads 0, as ``_sweep``'s;
- the live key-block count is traced (it grows with the piece inside a
  scan): a static grid over the bucket's blocks, the count a scalar
  prefetch, ``pl.when`` around the body, and index maps clamped to the
  last live block so a dead step fetches nothing;
- the piece's rows go through the softmax in tiles of ``block_q`` (a
  ``fori_loop``: the score tile is ``[block_q, block_k]`` float32).

Measured on a v5e at the published widths: ``scripts/
exp_sparse_prefill.py`` and PERF.md section 6, PR 42.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from edl_tpu.ops.expert_mlp import VMEM_BYTES
from edl_tpu.ops.flash_attention import LOG2E, NEG_INF, _scores

# tuned on a v5e at 64 heads of 256, pieces of 2048 (PERF.md section 6,
# PR 42): heads a grid step, key positions a block, query rows a tile
HEADS = 4
BLOCK_K = 1024
BLOCK_Q = 1024


def _kernel(n_ref, layer_ref, q_ref, rows_ref, sel_ref, wk_ref, wv_ref,
            o_ref, m_ref, l_ref, acc_ref, bias_ref, k_ref, v_ref, *,
            heads: int, block_q: int, rank: int, sm_scale: float):
    del layer_ref
    j = pl.program_id(2)
    p = q_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j < n_ref[0])
    def _visit():
        # 1 at an attended entry: 0 there, NEG_INF elsewhere
        bias_ref[...] = (sel_ref[...].astype(jnp.float32) - 1.0) * -NEG_INF
        rows = rows_ref[...]  # [bk, W]

        def head(g, carry):
            k_ref[...] = jnp.dot(
                rows, wk_ref[g], preferred_element_type=jnp.float32
            ).astype(k_ref.dtype)
            v_ref[...] = jnp.dot(
                rows[:, :rank], wv_ref[g], preferred_element_type=jnp.float32
            ).astype(v_ref.dtype)

            def tile(i, carry):
                r = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
                # base-2 scores: LOG2E folded into the scale, every
                # exponential a bare exp2 (ops/flash_attention.py)
                s = _scores(q_ref[g, r, :], k_ref[...], sm_scale * LOG2E) \
                    + bias_ref[r, :]
                m_prev = m_ref[g, r, :]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
                e = jnp.exp2(s - m_new)
                alpha = jnp.exp2(m_prev - m_new)
                l_ref[g, r, :] = l_ref[g, r, :] * alpha + jnp.sum(
                    e, axis=1, keepdims=True)
                acc_ref[g, r, :] = acc_ref[g, r, :] * alpha + jnp.dot(
                    e.astype(v_ref.dtype), v_ref[...],
                    preferred_element_type=jnp.float32)
                m_ref[g, r, :] = m_new
                return carry

            return jax.lax.fori_loop(0, p // block_q, tile, carry)

        jax.lax.fori_loop(0, heads, head, 0)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        # a row whose maximum never left NEG_INF attended nothing
        o = jnp.where(m_ref[...] > 0.5 * NEG_INF,
                      acc_ref[...] / jnp.maximum(l_ref[...], 1e-30), 0.0)
        o_ref[...] = o.astype(o_ref.dtype)


def fits(p: int, block_k: int, tb: int) -> bool:
    """Whether a piece of ``p`` rows over key blocks of ``block_k`` of a
    bucket of ``tb`` is whole tiles: an int8 tile is 32 rows of 128
    columns, the widest of the operands'."""
    return p % 32 == 0 and block_k % 128 == 0 and tb % block_k == 0


def head_weights(wkvb, n_heads: int, nope: int, rope: int, rank: int,
                 width: int):
    """``Wkvb [rank, H * (nope + v)]`` per head, for a latent row of
    ``width`` columns (``c | k_rope | 0``): ``Wk [H, width, nope +
    rope]`` (``W_uk`` over the first ``rank`` rows, an identity from the
    row's RoPE columns to the key's last ``rope``) and ``Wv [H, rank,
    v]``."""
    w = jnp.moveaxis(wkvb.reshape(rank, n_heads, -1), 1, 0)
    eye = jnp.zeros((width - rank, nope + rope), wkvb.dtype).at[
        jnp.arange(rope), nope + jnp.arange(rope)].set(1)
    wk = jnp.concatenate([
        jnp.pad(w[..., :nope], ((0, 0), (0, 0), (0, rope))),
        jnp.broadcast_to(eye, (n_heads,) + eye.shape)], axis=1)
    return wk, w[..., nope:]


@functools.partial(jax.jit, static_argnames=(
    "rank", "rope", "sm_scale", "block_k", "block_q", "heads", "interpret"))
def sparse_prefill_attention(
    q: jnp.ndarray,
    lat: jnp.ndarray,
    wkvb: jnp.ndarray,
    sel: jnp.ndarray,
    layer,
    n_blocks,
    *,
    rank: int,
    rope: int,
    sm_scale: float,
    block_k: int,
    block_q: int = BLOCK_Q,
    heads: int = HEADS,
    interpret: bool = False,
) -> jnp.ndarray:
    """q [B, P, H, nope + rope] (each head ``q_nope | q_rope``); lat [L,
    B, Tb, W], the stacked latent rows ``c | k_rope | 0`` (``rank``,
    ``rope`` and padding columns); wkvb [rank, H * (nope + v)]; sel [B,
    P, Tb] bool, the positions each query attends; layer and n_blocks,
    traced int32 scalars: the key blocks ``0 .. n_blocks - 1`` of
    ``block_k`` positions are visited (every marked position lies in
    them), the others neither read nor expanded. Returns [B, P, H * v]
    in q's dtype."""
    b, p, h, qk = q.shape
    _, _, tb, width = lat.shape
    if not fits(p, block_k, tb):
        raise ValueError(
            f"a piece of {p} rows over key blocks of {block_k} of {tb} "
            f"positions is not whole tiles")
    wk, wv = head_weights(wkvb.astype(q.dtype), h, qk - rope, rope, rank,
                          width)
    v = wv.shape[-1]
    while h % heads:
        heads -= 1
    block_q = min(block_q, p)
    while p % block_q:
        block_q //= 2
    nk = tb // block_k
    item = q.dtype.itemsize
    # Mosaic holds a kernel to 16 MiB of VMEM unless told otherwise: q
    # and o twice (one in use, one arriving) beside the accumulator
    # and the lane-padded m and l; the bias and the mask it is made of;
    # rows, weights, keys and values; the score tile and its
    # exponentials, with the compiler's own copies
    vmem = (heads * p * (2 * qk * item + 2 * v * item + 4 * v + 2 * 4 * 128)
            + p * block_k * (4 + 2)
            + 2 * block_k * width * item
            + 2 * heads * (width * qk + rank * v) * item
            + block_k * (qk + v) * item
            + 6 * block_q * block_k * 4 + (8 << 20))
    kernel = functools.partial(
        _kernel, heads=heads, block_q=block_q, rank=rank, sm_scale=sm_scale)

    def live(j, n_ref):
        return jnp.minimum(j, n_ref[0] - 1)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h // heads, nk),
            in_specs=[
                pl.BlockSpec((None, heads, p, qk),
                             lambda i, g, j, *_: (i, g, 0, 0)),
                pl.BlockSpec((None, None, block_k, width),
                             lambda i, g, j, n_ref, layer_ref:
                             (layer_ref[0], i, live(j, n_ref), 0)),
                pl.BlockSpec((None, p, block_k),
                             lambda i, g, j, n_ref, layer_ref:
                             (i, 0, live(j, n_ref))),
                pl.BlockSpec((heads, width, qk),
                             lambda i, g, j, *_: (g, 0, 0)),
                pl.BlockSpec((heads, rank, v),
                             lambda i, g, j, *_: (g, 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, heads, p, v),
                                   lambda i, g, j, *_: (i, g, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((heads, p, 1), jnp.float32),  # running max
                pltpu.VMEM((heads, p, 1), jnp.float32),  # running sum
                pltpu.VMEM((heads, p, v), jnp.float32),  # accumulator
                pltpu.VMEM((p, block_k), jnp.float32),  # the mask, a bias
                pltpu.VMEM((block_k, qk), q.dtype),  # a head's keys
                pltpu.VMEM((block_k, v), q.dtype),  # and values
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, p, v), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=int(min(vmem, VMEM_BYTES - (8 << 20))),
        ),
        interpret=interpret,
        name="edl_sparse_prefill_attn",
    )(
        jnp.clip(jnp.reshape(n_blocks, (1,)).astype(jnp.int32), 1, nk),
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        jnp.moveaxis(q, 2, 1), lat, sel.astype(jnp.int8), wk, wv,
    )
    return jnp.moveaxis(out, 1, 2).reshape(b, p, h * v)
