"""Pallas TPU ragged single-query decode attention over the stacked KV
cache — what ``llama.decode_step_slots`` runs under ``cfg.use_flash``.

The dense form reads all ``S`` padded positions of all ``B`` slots of
``kc[i]`` / ``vc[i]`` and masks afterwards; in the serving cells 59-90%
of those bytes belong to no request, and XLA materialises the per-layer
``kc[i]`` slice before it reads it (PERF.md section 5, PR 24). This
kernel takes the WHOLE ``[L, B, S, KV, hd]`` caches plus the layer index
and each slot's ``pos`` as scalar-prefetch operands, and fetches only
the S-blocks a slot has written:

- the grid is the list of LIVE blocks, slot after slot: ``pos[slot] //
  block_s + 1`` steps a slot, their sum the grid's (dynamic) length.
  Which (slot, block) a step is comes from two more scalar-prefetch
  vectors made from ``pos`` beside the call; the K/V index maps pick
  ``(layer, slot_of[t], block_of[t])``. A block past a slot's ``pos``
  is no step at all: not fetched, not computed, and not 0.35 us of an
  empty grid step either (what a (slots, S-blocks) grid with repeated
  indices cost: 60% of the kernel's time in serve-open);
- the cache is read in the layout it is stored in. ``[S, KV, hd]`` of
  one slot is viewed as ``[S * KV, hd]`` (a bitcast: heads are the
  second-minor dim), so a block is ``block_s * KV`` rows of ``hd``
  lanes and no head is ever gathered out of its tile. All ``H = KV *
  groups`` query heads of a slot are the rows of one matmul against
  those rows; a column belongs to position ``c // KV`` and kv head
  ``c % KV``, and a query head sees only its own kv head's columns (an
  additive bias of the static pattern, fetched once). The MXU does
  ``KV`` times the needed products; the kernel is bound by the K/V DMA
  all the same (a block's rows pass the MXU once either way);
- online softmax in float32 across the S-blocks of a slot (base-2, the
  scale folded into the score multiply as in ``flash_attention``);
  positions ``> pos[slot]`` inside the last live block are masked;
- K and V stay in the dtype they are stored in, products accumulate in
  float32 (``preferred_element_type``).

``groups`` 1 (MHA) and 4 (GQA) are the same code; the S-block length
follows from the bytes of one position (:func:`block_positions`).
A slot the caller considers inactive is fed ``pos = 0`` and costs one
block. The layer index is a traced scalar and the function is jitted,
so the unrolled layer loop of a model program traces and lowers the
kernel once.

Off-TPU the kernel runs only under the Pallas interpreter, asked for by
the caller (``interpret=True``, or ``flash_attention.interpret_kernels``
around the model call).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from edl_tpu.ops.flash_attention import LOG2E, NEG_INF

# one K (or V) block of a slot. A slot rounds up to whole blocks (half a
# block on average), so the smallest block that still streams at the
# rate of a large one: on v5e a 512 KiB block reads at 86-91% of the HBM
# peak like 1 and 2 MiB ones, a 256 KiB one at 70-76% (PERF.md section
# 6, PR 25)
BLOCK_BYTES = 512 << 10


def block_positions(kvh: int, hd: int, itemsize: int, s: int) -> int:
    """Positions in one S-block of the kernel for a cache of
    ``[.., s, kvh, hd]``: the power of two whose K block is
    ``BLOCK_BYTES`` (64 at MHA 32x128 bf16, 256 at GQA 8x128), halved
    until it divides ``s``. The engine's ``kv_read_share`` counts in
    these."""
    blk = 1 << (max(BLOCK_BYTES // (kvh * hd * itemsize), 8).bit_length() - 1)
    while blk > 1 and s % blk:
        blk //= 2
    return blk


def _online_block(
    q, k, v, bias, live_cols, scale, m_ref, l_ref, acc_ref, o_ref, last: bool
):
    """One S-block of a slot's online softmax (base-2, float32): scores
    of the query rows ``q`` against the block's rows ``k``, values
    ``v``; on the slot's ``last`` block columns ``>= live_cols`` are
    masked and the output is written, else the running state is."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # [H, columns], base-2
    if bias is not None:
        s = s + bias
    if last:
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < live_cols, s, NEG_INF)
    m_prev = m_ref[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp2(s - m_new)
    alpha = jnp.exp2(m_prev - m_new)
    l_new = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc = acc_ref[:] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if last:
        o_ref[...] = (acc / l_new).astype(o_ref.dtype)
    else:
        m_ref[:], l_ref[:], acc_ref[:] = m_new, l_new, acc


def _init_state(si, m_ref, l_ref, acc_ref):
    @pl.when(si == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)


def _kernel(
    slot_ref, blk_ref, pos_ref, layer_ref, q_ref, bias_ref, k_ref, v_ref,
    o_ref, m_ref, l_ref, acc_ref, *, block_s: int, kvh: int, sm_scale: float,
):
    del layer_ref  # read by the index maps alone
    t = pl.program_id(0)
    si = blk_ref[t]  # this step's S-block of its slot
    pos = pos_ref[slot_ref[t]]
    _init_state(si, m_ref, l_ref, acc_ref)

    def _block(last: bool):
        # q [H, hd]; k, v [block_s * KV, hd]. Column c is position
        # si * block_s + c // KV: live while that is <= pos
        _online_block(
            q_ref[...], k_ref[...], v_ref[...], bias_ref[...],
            (pos - si * block_s + 1) * kvh, sm_scale * LOG2E,
            m_ref, l_ref, acc_ref, o_ref, last,
        )

    # the position mask's iota/compare/select runs on the one block that
    # holds ``pos``, which is also the slot's last step; the blocks
    # below it are live whole
    pl.when(si < pos // block_s)(lambda: _block(False))
    pl.when(si == pos // block_s)(lambda: _block(True))


def _latent_kernel(
    slot_ref, blk_ref, pos_ref, layer_ref, q_ref, c_ref, *rest,
    block_s: int, rank: int, sm_scale: float, chosen: bool = False,
):
    """:func:`_kernel` for a latent cache: one operand, read once, is
    the keys (all its columns) and the values (its first ``rank``).
    ``chosen``: a further operand, one row of float32 a slot and block,
    is added to every head's scores: 0 at a position the slot attends,
    ``NEG_INF`` at one it does not (a block with none leaves a state
    that the first attended position's maximum wipes out)."""
    del layer_ref
    bias_ref = rest[0] if chosen else None
    o_ref, m_ref, l_ref, acc_ref = rest[-4:]
    t = pl.program_id(0)
    si = blk_ref[t]
    pos = pos_ref[slot_ref[t]]
    _init_state(si, m_ref, l_ref, acc_ref)

    def _block(last: bool):
        c = c_ref[...]  # [block_s, rank + rope]
        _online_block(
            q_ref[...], c, c[:, :rank],
            bias_ref[...] if chosen else None, pos - si * block_s + 1,
            sm_scale * LOG2E, m_ref, l_ref, acc_ref, o_ref, last,
        )

    pl.when(si < pos // block_s)(lambda: _block(False))
    pl.when(si == pos // block_s)(lambda: _block(True))


def live_blocks(pos: jnp.ndarray, block_s: int, n_blocks: int):
    """The kernel's grid, from each slot's ``pos``: (steps, slot_of [T],
    block_of [T]) with ``T = B * n_blocks + 1`` (the longest list, and
    one entry for a pipeline that looks a step ahead). Step ``t <
    steps`` is block ``block_of[t]`` of slot ``slot_of[t]``; a slot's
    blocks are consecutive and ascending. Entries past ``steps`` stay
    inside the cache and are never run."""
    b = pos.shape[0]
    per_slot = pos // block_s + 1
    ends = jnp.cumsum(per_slot)
    t = jnp.arange(b * n_blocks + 1, dtype=jnp.int32)
    slot_of = jnp.minimum(
        jnp.sum(t[:, None] >= ends[None, :], axis=1), b - 1
    ).astype(jnp.int32)
    block_of = jnp.minimum(
        t - (ends - per_slot)[slot_of], n_blocks - 1
    ).astype(jnp.int32)
    return ends[-1].astype(jnp.int32), slot_of, block_of


def head_bias(kvh: int, groups: int, block_s: int) -> jnp.ndarray:
    """[H, block_s * KV] float32: 0 where column ``c`` (kv head
    ``c % KV``) is query head ``h``'s own kv head (``h // groups``),
    NEG_INF elsewhere. The same for every block."""
    heads = jnp.arange(kvh * groups, dtype=jnp.int32)[:, None] // groups
    cols = jnp.arange(block_s * kvh, dtype=jnp.int32)[None, :] % kvh
    return jnp.where(heads == cols, 0.0, NEG_INF).astype(jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("block_s", "sm_scale", "interpret"))
def decode_attention(
    q: jnp.ndarray,
    kc: jnp.ndarray,
    vc: jnp.ndarray,
    pos: jnp.ndarray,
    layer,
    *,
    block_s: int | None = None,
    sm_scale: float | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Single-query attention of each slot over its own live prefix.

    q [B, KV, groups, hd] (the slot's one new query, grouped by kv
    head); kc / vc [L, B, S, KV, hd], the stacked caches, never a
    layer's slice; pos [B] int32, the last live position of each slot,
    INCLUSIVE (the one the caller just wrote); layer, an int32 scalar
    (traced: every layer of a model is one compiled kernel);
    ``sm_scale`` multiplies the scores (``1 / sqrt(hd)`` if None).
    Returns [B, KV, groups, hd] in q's dtype."""
    b, kvh, groups, hd = q.shape
    n_layers, _, s, _, _ = kc.shape
    h = kvh * groups
    if block_s is None:
        block_s = block_positions(kvh, hd, kc.dtype.itemsize, s)
    if s % block_s:
        raise ValueError(f"block_s={block_s} must divide the cache length {s}")
    rows = block_s * kvh
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(hd)
    kernel = functools.partial(
        _kernel, block_s=block_s, kvh=kvh, sm_scale=sm_scale
    )

    # a position past the cache would be a block past it
    pos = jnp.clip(pos.astype(jnp.int32), 0, s - 1)
    steps, slot_of, block_of = live_blocks(pos, block_s, s // block_s)

    def slot_map(t, slot_ref, *_):
        return (slot_ref[t], 0, 0)

    def kv_map(t, slot_ref, blk_ref, pos_ref, layer_ref):
        return (layer_ref[0], slot_ref[t], blk_ref[t], 0)

    kv_spec = pl.BlockSpec((None, None, rows, hd), kv_map)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(steps,),
            in_specs=[
                pl.BlockSpec((None, h, hd), slot_map),
                pl.BlockSpec((h, rows), lambda t, *_: (0, 0)),
                kv_spec,
                kv_spec,
            ],
            out_specs=pl.BlockSpec((None, h, hd), slot_map),
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32),  # running max
                pltpu.VMEM((h, 1), jnp.float32),  # running sum
                pltpu.VMEM((h, hd), jnp.float32),  # output accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="edl_decode_attn",
    )(
        slot_of,
        block_of,
        pos,
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        q.reshape(b, h, hd),
        head_bias(kvh, groups, block_s),
        # heads are the second-minor dim, so this view is the stored
        # bytes: no copy
        kc.reshape(n_layers, b, s * kvh, hd),
        vc.reshape(n_layers, b, s * kvh, hd),
    )
    return out.reshape(b, kvh, groups, hd)


# one S-block of a latent cache. All of a slot's query heads are the 32
# rows of one matmul against a block, so a step's fixed cost weighs
# more than in the per-head kernel and the block is larger: on v5e, 96
# slots x 4096 x 640 bf16, all full / at 600-3800 positions, blocks of
# 64, 128, 256, 512, 1024 positions take 3134 / 1794, 1840 / 1072, 1178
# / 715, 838 / 536 and 714 / 500 us (PERF.md section 6, PR 29)
LATENT_BLOCK_BYTES = 1280 << 10


def latent_block_positions(width: int, itemsize: int, s: int) -> int:
    """Positions in one S-block of :func:`decode_attention_latent` for a
    cache of ``[.., s, width]``: the power of two whose block is
    ``LATENT_BLOCK_BYTES`` (1024 at 640 bf16 columns), halved until it
    divides ``s``. The engine's ``kv_read_share`` counts in these."""
    blk = 1 << (max(LATENT_BLOCK_BYTES // (width * itemsize), 8).bit_length()
                - 1)
    while blk > 1 and s % blk:
        blk //= 2
    return blk


@functools.partial(
    jax.jit, static_argnames=("rank", "sm_scale", "block_s", "interpret")
)
def decode_attention_latent(
    q: jnp.ndarray,
    cache: jnp.ndarray,
    pos: jnp.ndarray,
    layer,
    *,
    rank: int,
    sm_scale: float,
    block_s: int | None = None,
    interpret: bool = False,
    chosen: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Single-query attention in the latent space (the absorbed form of
    multi-head latent attention): every query head of a slot against
    the ONE latent row a position holds.

    q [B, H, W], each head's query already carried into the cache's
    space (``q_nope @ W_uk`` beside ``q_rope``); cache [L, B, S, W], the
    stacked latent cache, ``W = rank + rope`` columns a position:
    scores take all ``W``, values are the first ``rank``, so a block is
    fetched once and serves as both; pos [B], each slot's last live
    position, inclusive; layer, a traced int32 scalar; ``sm_scale`` the
    model's (of the EXPANDED head width, not of ``W``). Returns
    [B, H, rank] in q's dtype, for the caller to expand (``@ W_uv``).

    Grid, scalar prefetch and softmax are :func:`decode_attention`'s:
    the list of live (slot, S-block) pairs, base-2 online softmax in
    float32. There is no head bias: all heads read all columns.

    ``chosen`` [B, S] bool: the positions each slot attends, of those
    up to its ``pos`` (at least one of them); every live block is still
    read, and the positions not chosen are masked out of the softmax (a
    model whose indexer chooses a slot's keys; models/glm_dsa.py)."""
    b, h, width = q.shape
    n_layers, _, s, _ = cache.shape
    if block_s is None:
        block_s = latent_block_positions(width, cache.dtype.itemsize, s)
    if s % block_s:
        raise ValueError(f"block_s={block_s} must divide the cache length {s}")
    kernel = functools.partial(
        _latent_kernel, block_s=block_s, rank=rank, sm_scale=sm_scale,
        **({} if chosen is None else {"chosen": True})
    )
    pos = jnp.clip(pos.astype(jnp.int32), 0, s - 1)
    steps, slot_of, block_of = live_blocks(pos, block_s, s // block_s)

    def slot_map(t, slot_ref, *_):
        return (slot_ref[t], 0, 0)

    def cache_map(t, slot_ref, blk_ref, pos_ref, layer_ref):
        return (layer_ref[0], slot_ref[t], blk_ref[t], 0)

    masks, mask_specs = (), []
    if chosen is not None:
        masks = (jnp.where(chosen, 0.0, NEG_INF).astype(jnp.float32)
                 .reshape(b, 1, s),)
        mask_specs = [pl.BlockSpec(
            (None, 1, block_s),
            lambda t, slot_ref, blk_ref, *_: (slot_ref[t], 0, blk_ref[t]))]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(steps,),
            in_specs=[
                pl.BlockSpec((None, h, width), slot_map),
                pl.BlockSpec((None, None, block_s, width), cache_map),
                *mask_specs,
            ],
            out_specs=pl.BlockSpec((None, h, rank), slot_map),
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, rank), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="edl_decode_attn_latent",
    )(
        slot_of, block_of, pos,
        jnp.reshape(layer, (1,)).astype(jnp.int32), q, cache, *masks,
    )
