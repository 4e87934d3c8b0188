"""Pallas TPU routed-expert SwiGLU for a decode step's few rows — what
``parallel.moe.moe_dropless`` runs under ``kernel=True`` when a step has
at most :data:`MAX_ROWS` rows.

A decode step of ``N`` tokens that each choose ``k`` of ``E`` experts
gives every hit expert ``N * k / E`` rows (4.5 at 96 x 6 / 128): the
layer is the experts' weights passing through the chip once, and the
sorted, grouped form (``jax.lax.ragged_dot`` three times, with a sort,
two gathers and a combine around them) streams them at 56% of a v5e's
HBM peak (PERF.md section 6, PR 36). This kernel does not sort:

- the grid is the list of the experts HIT, ascending, one expert a
  step; its ``[d, f]``, ``[d, f]`` and ``[f, d]`` matrices are fetched
  where they lie in the layer's ``[E, ...]`` leaves, whole and
  contiguous (3.1 MB each at 2048 x 768 bf16), while the expert before
  it computes. The list is a scalar-prefetch operand padded with its
  last entry: a step past the hit experts names the block already
  there, so nothing is fetched, and ``pl.when`` skips its arithmetic.
  An expert nobody chose is never read;
- all ``N`` rows go through each hit expert: ``h = silu(x @ w1) * (x @
  w3)`` in float32, times the row's combine weight for this expert (a
  column of ``c [N, E]`` float32: the routing weight where the row
  chose the expert; a row that did not is set to 0 by ``where``, not by
  the product), rounded to the rows' dtype once, ``acc += h @ w2`` in a
  float32 accumulator that stays in VMEM with the rows for the whole
  call, rounded to the rows' dtype once at the end. ``E * N * 6 d f``
  operations where the sorted form does ``N * k * 6 d f``: at ``N``
  operations a byte the wasted rows stay under the chip's ridge
  (~240) and hide under the weights' DMA;
- no rounding the grouped form does not make: it rounds ``x @ w1``,
  ``x @ w3``, each expert's output and the sum to the rows' dtype, this
  one ``h`` and the sum.

A combine weight of exactly 0 reads as "not chosen": the row gets
nothing from that expert, whatever the expert computes. An expert is
taken whole: shapes whose expert twice over (one computing, one
arriving) would not fit a v5e's VMEM are refused, not tiled.

Off-TPU the kernel runs only under the Pallas interpreter, asked for by
the caller (``interpret=True``, or ``flash_attention.interpret_kernels``
around the model call).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the most rows the kernel takes. Every row passes every hit expert, so
# the arithmetic grows with the rows while the bytes do not: at 128 rows
# it is 128 operations a byte, half a v5e's ridge (197 TFLOP/s over 819
# GB/s = 240), and one row tile of its 128 x 128 MXU
MAX_ROWS = 128
VMEM_BYTES = 128 << 20  # a v5e's
ROW_TILE = 16  # a bf16 tile's sublanes: rows are padded to whole tiles


def _kernel(
    hit_ref, n_ref, x_ref, c_ref, w1_ref, w3_ref, w2_ref, o_ref, acc_ref
):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i < n_ref[0])
    def _expert():
        x = x_ref[...]
        dt = x.dtype
        a = jnp.dot(x, w1_ref[...].astype(dt),
                    preferred_element_type=jnp.float32)
        b = jnp.dot(x, w3_ref[...].astype(dt),
                    preferred_element_type=jnp.float32)
        c = c_ref[...]  # [N, E]: this expert's column, by its lane
        lane = jax.lax.broadcasted_iota(jnp.int32, c.shape, 1)
        col = jnp.sum(jnp.where(lane == hit_ref[i], c, 0.0), axis=1,
                      keepdims=True)
        h = jnp.where(col != 0.0, jax.nn.silu(a) * b * col, 0.0).astype(dt)
        acc_ref[...] += jnp.dot(h, w2_ref[...].astype(dt),
                                preferred_element_type=jnp.float32)

    @pl.when(i == pl.num_programs(0) - 1)
    def _out():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def combine_weights(idx: jnp.ndarray, w: jnp.ndarray, held: int, first: int):
    """(c [N, held] float32, hit [held] int32, n_hit [1] int32) of a
    step's routing over the experts ``first .. first + held``: ``c`` is
    each row's weight for each held expert (0 where not chosen; a row
    that names an expert twice adds its weights up), ``hit`` the held
    experts with a row, ascending, padded with the last of them, and
    ``n_hit`` how many."""
    experts = jnp.arange(held, dtype=jnp.int32)
    chosen = (idx.astype(jnp.int32) - first)[..., None] == experts
    c = jnp.sum(jnp.where(chosen, w.astype(jnp.float32)[..., None], 0.0),
                axis=1)
    is_hit = jnp.any(chosen, axis=(0, 1))
    n_hit = jnp.sum(is_hit, dtype=jnp.int32)
    # compaction without a sort: expert e is entry rank[e] of the list
    rank = jnp.cumsum(is_hit, dtype=jnp.int32) - 1
    at = is_hit[None, :] & (rank[None, :] == experts[:, None])
    hit = jnp.sum(jnp.where(at, experts[None, :], 0), axis=1)
    last = jnp.max(jnp.where(is_hit, experts, 0))
    hit = jnp.where(experts < n_hit, hit, last).astype(jnp.int32)
    return c, hit, n_hit.reshape(1)


@functools.partial(jax.jit, static_argnames=("first", "interpret"))
def expert_mlp(
    x: jnp.ndarray,
    idx: jnp.ndarray,
    w: jnp.ndarray,
    w1: jnp.ndarray,
    w3: jnp.ndarray,
    w2: jnp.ndarray,
    *,
    first: int = 0,
    interpret: bool = False,
) -> jnp.ndarray:
    """The routed experts' SwiGLU over a step's rows, no token dropped.

    x [N, d], ``N <= MAX_ROWS``; idx / w [N, k], each row's chosen
    experts and their weights (``parallel.moe.route_sigmoid_topk``); w1
    / w3 [E_held, d, f] and w2 [E_held, f, d], the experts ``first ..
    first + E_held`` of those the router scored, read where they lie.
    Returns [N, d] in x's dtype: for each row the weighted sum of the
    held experts it chose (zeros where it chose none of them)."""
    n, d = x.shape
    held, _, f = w1.shape
    if n > MAX_ROWS:
        raise ValueError(f"{n} rows: the kernel takes at most {MAX_ROWS}")
    c, hit, n_hit = combine_weights(idx, w, held, first)
    pad = -n % ROW_TILE
    if pad:  # whole row tiles: a padded row has weight 0 everywhere
        x = jnp.pad(x, ((0, pad), (0, 0)))
        c = jnp.pad(c, ((0, pad), (0, 0)))
    rows = n + pad

    def expert(i, hit_ref, n_ref):
        return (hit_ref[i], 0, 0)

    # the experts twice (one computing, one arriving) and a copy where
    # they are cast; rows in and out twice and their accumulator; a, b,
    # h and the compiler's own; the combine weights
    vmem = (3 * 3 * d * f * max(w1.dtype.itemsize, x.dtype.itemsize)
            + rows * d * (4 * x.dtype.itemsize + 4)
            + 6 * rows * f * 4
            + 2 * rows * max(held, 128) * 4 + (4 << 20))
    if vmem > VMEM_BYTES:
        raise ValueError(
            f"experts of {d} x {f} need {vmem >> 20} MiB of VMEM whole; "
            f"the chip has {VMEM_BYTES >> 20}")
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(held,),
            in_specs=[
                pl.BlockSpec((rows, d), lambda i, *_: (0, 0)),
                pl.BlockSpec((rows, held), lambda i, *_: (0, 0)),
                pl.BlockSpec((None, d, f), expert),
                pl.BlockSpec((None, d, f), expert),
                pl.BlockSpec((None, f, d), expert),
            ],
            out_specs=pl.BlockSpec((rows, d), lambda i, *_: (0, 0)),
            scratch_shapes=[pltpu.VMEM((rows, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(vmem),
        ),
        interpret=interpret,
        name="edl_expert_mlp",
    )(hit, n_hit, x, c, w1, w3, w2)
    return out[:n] if pad else out
