"""MXU-friendly embedding lookup with a sorted block-matmul backward.

The reference's CTR workload keeps its embedding table on parameter
servers as `is_sparse` rows (reference: example/ctr/ctr/train.py:46-64);
push/pull of sparse rows rides the pserver RPC. On TPU the table is a
dense in-mesh array and the gradient becomes a scatter-add — which the
TPU scatter engine processes row-by-row (~100 ns/row): for a Criteo
batch (16k x 26 ids) that is ~50 ms, dwarfing the MLP. This module
replaces the scatter with dense MXU work:

1. sort ids, carrying the cotangent rows as extra sort operands
   (one fused multi-operand sort, no reorder gather);
2. scan over fixed-size blocks of sorted rows: each block touches a
   narrow, contiguous vocab window, so its contribution is a small
   one-hot matmul `onehot[BN,TV]^T @ ct[BN,E]` accumulated into the
   dense gradient with dynamic_slice/dynamic_update_slice (in-place
   under XLA);
3. a block whose rows span more than one window gets a second,
   disjoint window anchored at its last row (rare: only when a
   block's ids spread wider than TV);
4. if any block spans more than two windows (adversarial id
   distribution), the whole gradient falls back to the plain
   scatter-add inside a lax.cond — bit-exact semantics always.

Accumulation is always float32 (preferred_element_type), which is
*more* accurate than XLA's scatter-add in the table dtype.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

# Rows of sorted ids per scan block, and the vocab-window width each
# block accumulates into. BN=1024/TV=4096 measured fastest on v5e for
# the Criteo-shaped workload; correctness does not depend on them.
BLOCK_ROWS = 1024
VOCAB_WINDOW = 4096
# Below this many ids the scatter is cheap and the sort isn't worth it.
MIN_FAST_IDS = 65_536


def _plain_grad(ids_flat, ct_flat, vocab, dtype):
    return (
        jnp.zeros((vocab, ct_flat.shape[-1]), jnp.float32)
        .at[ids_flat]
        .add(ct_flat.astype(jnp.float32), mode="drop")
        .astype(dtype)
    )


def _blocked_grad(ids_flat, ct_flat, vocab, dtype):
    """Sorted block-matmul gradient; exact for blocks spanning <= 2
    vocab windows, guarded by a lax.cond fallback otherwise."""
    n, e = ct_flat.shape
    bn, tv = BLOCK_ROWS, VOCAB_WINDOW
    npad = -(-n // bn) * bn

    # Two-operand sort (ids, iota) then one row-gather of the cotangent
    # by the permutation. Carrying the payload inside the sort instead
    # (multi-operand lax.sort) looks like it should win — it skips the
    # gather — but each extra sort operand inflates both the comparator
    # compile time (17 ops ≈ 190 s) and the runtime: measured on v5e,
    # 9-op packed sort ≈ 13 ms vs 2-op sort 4 ms + 426k-row gather 6 ms.
    sids, perm = jax.lax.sort(
        (ids_flat, jax.lax.iota(jnp.int32, n)), num_keys=1
    )
    sct = jnp.take(ct_flat, perm, axis=0)
    # pad with the last REAL id: a vocab-1 pad would stretch the final
    # block's span to the vocab end and trip the `bad` fallback on
    # every batch whose max id sits below vocab - 2*TV
    sids = jnp.concatenate(
        [sids, jnp.broadcast_to(sids[n - 1], (npad - n,))]
    )
    sct = jnp.concatenate([sct, jnp.zeros((npad - n, e), sct.dtype)])
    sids_b = sids.reshape(-1, bn)
    sct_b = sct.reshape(-1, bn, e)

    vstart = jnp.minimum(sids_b[:, 0], vocab - tv)
    # second window: anchored so the block's last row fits; >= vstart+tv
    # keeps it disjoint from window one except at the vocab-end clamp,
    # which the `floor` row mask below handles.
    vstart2 = jnp.minimum(
        jnp.maximum(vstart + tv, sids_b[:, -1] - (tv - 1)), vocab - tv
    )
    spans2 = (sids_b[:, -1] - vstart) >= tv  # block needs window two
    bad = jnp.any((sids_b[:, -1] - vstart) >= 2 * tv)

    def window(acc, sid, ct_rows, start, floor):
        """Accumulate rows with id >= floor and id - start < tv."""
        local = sid - start
        keep = (sid >= floor) & (local >= 0) & (local < tv)
        onehot = jnp.where(
            keep[:, None],
            local[:, None]
            == jax.lax.broadcasted_iota(jnp.int32, (bn, tv), 1),
            False,
        )
        contrib = jnp.dot(
            onehot.astype(ct_rows.dtype).T,
            ct_rows,
            preferred_element_type=jnp.float32,
        )
        tile = jax.lax.dynamic_slice(acc, (start, 0), (tv, e))
        return jax.lax.dynamic_update_slice(acc, tile + contrib, (start, 0))

    def body(acc, blk):
        sid, ct_rows, v1, v2, has2 = blk
        acc = window(acc, sid, ct_rows, v1, floor=0)
        acc = jax.lax.cond(
            has2,
            lambda a: window(a, sid, ct_rows, v2, floor=v1 + tv),
            lambda a: a,
            acc,
        )
        return acc, None

    def fast(_):
        acc = jnp.zeros((vocab, e), jnp.float32)
        acc, _ = jax.lax.scan(
            body, acc, (sids_b, sct_b, vstart, vstart2, spans2)
        )
        return acc.astype(dtype)

    return jax.lax.cond(
        bad, lambda _: _plain_grad(ids_flat, ct_flat, vocab, dtype), fast, 0
    )


@jax.custom_vjp
def embedding_lookup(table: jax.Array, ids: jax.Array) -> jax.Array:
    """`table[ids]` with a TPU-fast backward. table [V, E]; ids int32 of
    any shape; result [*ids.shape, E]. Out-of-range ids are clamped to
    [0, V-1] (``jnp.take`` mode="clip") in BOTH directions — without the
    clamp a single stray id (e.g. a -1 padding sentinel) would shift the
    windowed gradient of every other row in its sort block."""
    return jnp.take(table, jnp.clip(ids, 0, table.shape[0] - 1), axis=0)


def _fwd(table, ids):
    # zero-element prototype: its *static* shape/dtype carry vocab and
    # table dtype into the backward (dtypes aren't valid residual leaves)
    proto = jnp.zeros((table.shape[0], 0), table.dtype)
    return embedding_lookup(table, ids), (ids, proto)


def _bwd(res, ct):
    ids, proto = res
    vocab, dtype = proto.shape[0], proto.dtype
    ids_flat = jnp.clip(ids.reshape(-1), 0, vocab - 1)
    ct_flat = ct.reshape(ids_flat.shape[0], ct.shape[-1])
    if ids_flat.shape[0] >= MIN_FAST_IDS and vocab >= 2 * VOCAB_WINDOW:
        grad = _blocked_grad(ids_flat, ct_flat, vocab, dtype)
    else:
        grad = _plain_grad(ids_flat, ct_flat, vocab, dtype)
    return grad, None


embedding_lookup.defvjp(_fwd, _bwd)


def sharded_embedding_lookup(
    table: jax.Array,
    ids: jax.Array,
    mesh,
    vocab_axis: str = "tp",
    ids_pspec=None,
):
    """Lookup with the table partitioned over the vocab dimension — the
    TPU-native analog of the reference's parameter-sharded embedding on
    pservers (reference: sparse parameter ports ports_num_for_sparse,
    pkg/jobparser.go:232-247; --no_split_var block splitting,
    example/ctr/ctr/train.py:80-84). Each ``vocab_axis`` shard looks up
    only its own vocab range (rows outside it contribute zeros) and the
    partial embeddings are summed over ICI with a psum; the backward
    lands each shard's gradient on its local table rows, through the
    same blocked fast path.

    table [V, E] sharded P(vocab_axis, None); V must divide the axis
    size. ids int32, any shape, sharded ``ids_pspec`` (default
    replicated). Returns [*ids.shape, E] sharded like the ids.
    """
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    n = mesh.shape[vocab_axis]
    vocab, _ = table.shape
    if vocab % n:
        raise ValueError(f"vocab {vocab} not divisible by {vocab_axis}={n}")
    per = vocab // n
    if ids_pspec is None:
        ids_pspec = P(*(None,) * ids.ndim)
    out_pspec = P(*ids_pspec, None)

    def local(tab, ids):
        lo = jax.lax.axis_index(vocab_axis) * per
        loc = ids - lo
        mine = (loc >= 0) & (loc < per)
        emb = embedding_lookup(tab, jnp.where(mine, loc, 0))
        emb = jnp.where(mine[..., None], emb, 0)
        return jax.lax.psum(emb, vocab_axis)

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(P(vocab_axis, None), ids_pspec),
        out_specs=out_pspec,
        check_vma=False,
    )(table, ids)
