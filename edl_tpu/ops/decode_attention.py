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


def _kernel(
    slot_ref, blk_ref, pos_ref, layer_ref, q_ref, bias_ref, k_ref, v_ref,
    o_ref, m_ref, l_ref, acc_ref, *, block_s: int, kvh: int, sm_scale: float,
):
    del layer_ref  # read by the index maps alone
    t = pl.program_id(0)
    si = blk_ref[t]  # this step's S-block of its slot
    pos = pos_ref[slot_ref[t]]

    @pl.when(si == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _block(last: bool):
        q = q_ref[...]  # [H, hd]
        k = k_ref[...]  # [block_s * KV, hd]
        v = v_ref[...]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * (sm_scale * LOG2E) + bias_ref[...]  # [H, block_s * KV], base-2
        if last:
            # column c is position si * block_s + c // KV: live while
            # that is <= pos
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(cols < (pos - si * block_s + 1) * kvh, s, NEG_INF)
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

    # the position mask's iota/compare/select runs on the one block that
    # holds ``pos``, which is also the slot's last step; the blocks
    # below it are live whole
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


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention(
    q: jnp.ndarray,
    kc: jnp.ndarray,
    vc: jnp.ndarray,
    pos: jnp.ndarray,
    layer,
    *,
    block_s: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Single-query attention of each slot over its own live prefix.

    q [B, KV, groups, hd] (the slot's one new query, grouped by kv
    head); kc / vc [L, B, S, KV, hd], the stacked caches, never a
    layer's slice; pos [B] int32, the last live position of each slot,
    INCLUSIVE (the one the caller just wrote); layer, an int32 scalar
    (traced: every layer of a model is one compiled kernel). Returns
    [B, KV, groups, hd] in q's dtype."""
    b, kvh, groups, hd = q.shape
    n_layers, _, s, _, _ = kc.shape
    h = kvh * groups
    if block_s is None:
        block_s = block_positions(kvh, hd, kc.dtype.itemsize, s)
    if s % block_s:
        raise ValueError(f"block_s={block_s} must divide the cache length {s}")
    rows = block_s * kvh
    kernel = functools.partial(
        _kernel, block_s=block_s, kvh=kvh, sm_scale=1.0 / np.sqrt(hd)
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
