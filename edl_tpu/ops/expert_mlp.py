"""Pallas TPU routed-expert SwiGLU, what ``parallel.moe.moe_dropless``
runs under ``kernel=True`` over plain weight arrays. The row count picks
the kernel:

- at most :data:`MAX_ROWS` rows (a decode step): ``edl_expert_mlp``, no
  sort, every row through every hit expert, masked;
- more (every prefill bucket): ``edl_grouped_expert_mlp``, the rows
  sorted by expert and each hit expert's own run of them, in tiles of
  :data:`GROUP_TILE` rows.

Both fetch each hit expert's three matrices once, whole, where they lie
in the layer's ``[E, ...]`` leaves, while the expert before it computes,
and never read an expert nobody chose.

**A decode step's few rows.** ``N`` tokens that each choose ``k`` of
``E`` experts give every hit expert ``N * k / E`` rows (4.5 at 96 x 6 /
128): the layer is the experts' weights passing through the chip once,
and the sorted, grouped form (``jax.lax.ragged_dot`` three times, with
a sort, two gathers and a combine around them) streams them at 56% of a
v5e's HBM peak (PERF.md section 6, PR 36). ``edl_expert_mlp`` does not
sort:

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
nothing from that expert, whatever the expert computes.

**A prefill's many.** A bucket of 1024-4096 tokens x 6 over 128 experts
gives an expert 48-192 rows, still under the ridge: the layer is still
its weights passing through once, and ``ragged_dot`` x 3 streams them
at 171-226 GB/s, a fifth to a quarter of the peak (PERF.md section 6,
PR 38; this kernel at 439-663). ``edl_grouped_expert_mlp`` takes
the rows as ``moe_dropless`` sorts them and the runs' sizes:

- the grid is the list of (expert, row tile) pairs to visit, expert by
  expert and tile by tile inside an expert's run (``group_visits``:
  scalar-prefetch operands, repeating the last visit to the grid's
  static length). A tile that a run's end crosses is visited once for
  each run in it: ``rows / GROUP_TILE + experts hit - 1`` visits at most;
- a visit puts its whole tile through its expert (``h`` stays in VMEM,
  rounded once; float32 accumulation) and writes the rows of the tile
  that are the expert's, by ``where``: the tile stays in VMEM while
  consecutive visits name it. The rows are written where they were read
  (input and output alias);
- the experts lie in two VMEM buffers filled by hand: an expert's first
  visit starts the fetch of the next expert with rows, which so arrives
  under ALL of this expert's visits (as BlockSpec operands it would be
  asked for one visit ahead, under the last visit alone);
- the roundings are ``h`` and each expert's output, two fewer than
  ``ragged_dot`` x 3; sort, gathers, un-sort and the float32 combine
  stay ``moe_dropless``'s.

An expert that fits a v5e's VMEM twice over (one computing, one
arriving) is taken whole by both. A wider one (6144 x 2048: 75 MB)
passes in tiles of its width ``f``, which SwiGLU splits cleanly: ``h[:,
j] = silu(x @ w1[:, j]) * (x @ w3[:, j])`` and ``y = sum_j h[:, j] @
w2[j, :]``, the sum in the float32 accumulator. Both kernels then run
over a second grid axis of ``f`` tiles with all three matrices as
BlockSpec operands (the pipeline fetches tile ``j + 1`` under tile
``j``); the shapes that fit take the whole-expert paths they took.

Off-TPU the kernels run only under the Pallas interpreter, asked for by
the caller (``interpret=True``, or ``flash_attention.interpret_kernels``
around the model call).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the most rows ``edl_expert_mlp`` takes; more go sorted through
# ``edl_grouped_expert_mlp``. Every row passes every hit expert, so the
# arithmetic grows with the rows while the bytes do not: at 128 rows it
# is 128 operations a byte, half a v5e's ridge (197 TFLOP/s over 819
# GB/s = 240), and one row tile of its 128 x 128 MXU
MAX_ROWS = 128
VMEM_BYTES = 128 << 20  # a v5e's
ROW_TILE = 16  # a bf16 tile's sublanes: rows are padded to whole tiles


def _weighted_term(x_ref, c_ref, expert, w1_ref, w3_ref, w2_ref):
    """The rows through the matrices the refs hold (an expert's, or one
    tile of its ``f``), each weighted by its ``c`` for ``expert``:
    float32 [N, d], zero for a row that did not choose it."""
    x = x_ref[...]
    dt = x.dtype
    a = jnp.dot(x, w1_ref[...].astype(dt),
                preferred_element_type=jnp.float32)
    b = jnp.dot(x, w3_ref[...].astype(dt),
                preferred_element_type=jnp.float32)
    c = c_ref[...]  # [N, E]: this expert's column, by its lane
    lane = jax.lax.broadcasted_iota(jnp.int32, c.shape, 1)
    col = jnp.sum(jnp.where(lane == expert, c, 0.0), axis=1,
                  keepdims=True)
    h = jnp.where(col != 0.0, jax.nn.silu(a) * b * col, 0.0).astype(dt)
    return jnp.dot(h, w2_ref[...].astype(dt),
                   preferred_element_type=jnp.float32)


def _kernel(
    hit_ref, n_ref, x_ref, c_ref, w1_ref, w3_ref, w2_ref, o_ref, acc_ref
):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i < n_ref[0])
    def _expert():
        acc_ref[...] += _weighted_term(
            x_ref, c_ref, hit_ref[i], w1_ref, w3_ref, w2_ref)

    @pl.when(i == pl.num_programs(0) - 1)
    def _out():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _tiled_kernel(
    hit_ref, n_ref, x_ref, c_ref, w1_ref, w3_ref, w2_ref, o_ref, acc_ref
):
    """``_kernel`` over a second grid axis of ``f`` tiles: one tile of
    an expert's ``h`` a step, summed into the same accumulator."""
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i < n_ref[0])
    def _tile():
        acc_ref[...] += _weighted_term(
            x_ref, c_ref, hit_ref[i], w1_ref, w3_ref, w2_ref)

    @pl.when((i == pl.num_programs(0) - 1) & (j == pl.num_programs(1) - 1))
    def _out():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def f_tile(d: int, f: int, itemsize: int, budget: int) -> int:
    """The widest tile of an expert's ``f`` columns, a multiple of 128
    that divides ``f``, whose three blocks (``[d, tile]`` twice and
    ``[tile, d]``) fit ``budget`` bytes three times over (computing,
    arriving, and a copy where they are cast)."""
    for tile in range(f, 0, -128):
        if f % tile == 0 and tile % 128 == 0 \
                and 3 * 3 * d * tile * itemsize <= budget:
            return tile
    raise ValueError(
        f"experts of {d} x {f}: no tile of f, a multiple of 128 that "
        f"divides it, fits {budget >> 20} MiB of VMEM")


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
        out = _expert_mlp_tiled(hit, n_hit, x, c, w1, w3, w2, interpret)
        return out[:n] if pad else out
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


def _expert_mlp_tiled(hit, n_hit, x, c, w1, w3, w2, interpret):
    """``edl_expert_mlp`` for an expert too wide to take whole: the
    grid is (hit experts, tiles of ``f``); a step past the hit experts
    names the last tile of the last of them, which is already there."""
    rows, d = x.shape
    held, _, f = w1.shape
    item = max(w1.dtype.itemsize, x.dtype.itemsize)
    fixed = rows * d * (4 * x.dtype.itemsize + 4) \
        + 2 * rows * max(held, 128) * 4 + (4 << 20)
    tile = f_tile(d, f, item, (VMEM_BYTES // 2) - fixed)
    n_f = f // tile

    def at(i, j, hit_ref, n_ref):
        return hit_ref[i], jnp.where(i < n_ref[0], j, n_f - 1)

    def up(i, j, hit_ref, n_ref):
        e, t = at(i, j, hit_ref, n_ref)
        return (e, 0, t)

    def down(i, j, hit_ref, n_ref):
        e, t = at(i, j, hit_ref, n_ref)
        return (e, t, 0)

    vmem = 3 * 3 * d * tile * item + fixed + 6 * rows * tile * 4
    return pl.pallas_call(
        _tiled_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(held, n_f),
            in_specs=[
                pl.BlockSpec((rows, d), lambda i, j, *_: (0, 0)),
                pl.BlockSpec((rows, held), lambda i, j, *_: (0, 0)),
                pl.BlockSpec((None, d, tile), up),
                pl.BlockSpec((None, d, tile), up),
                pl.BlockSpec((None, tile, d), down),
            ],
            out_specs=pl.BlockSpec((rows, d), lambda i, j, *_: (0, 0)),
            scratch_shapes=[pltpu.VMEM((rows, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(vmem),
        ),
        interpret=interpret,
        name="edl_expert_mlp",
    )(hit, n_hit, x, c, w1, w3, w2)


# -- more rows than MAX_ROWS: each expert its own run of the sorted rows -----

GROUP_TILE = 128  # rows a visit: one pass of the 128 x 128 MXU a weight tile


def group_visits(sizes: jnp.ndarray, n_tiles: int, tile: int):
    """The (expert, row tile) pairs a grouped call has to visit, expert
    by expert and tile by tile inside an expert, as int32 arrays for the
    kernel's scalar memory. ``sizes [held]``: rows of each expert's run
    in the sorted rows (runs lie back to back from row 0; rows past the
    last run belong to nobody). A tile that a run's end crosses is
    visited once for each run in it, so there are at most ``n_tiles +
    held - 1`` visits: the arrays have that length and repeat their
    last visit past ``n_visits``.

    Returns (expert [V], tile [V], fresh [V]: 1 where the visit is its
    expert's first, slot [V]: which of the two weight buffers the expert
    lies in, ahead [V]: the next expert with rows, -1 after the last,
    edges [held + 1]: where each run starts, n_visits [1])."""
    held = sizes.shape[0]
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first_tile = starts // tile
    tiles = jnp.where(sizes > 0, (ends - 1) // tile - first_tile + 1, 0)
    upto = jnp.cumsum(tiles)  # visits up to and with expert g
    n_visits = upto[-1]
    v = jnp.minimum(jnp.arange(n_tiles + held - 1, dtype=jnp.int32),
                    jnp.maximum(n_visits - 1, 0))
    # the expert of visit v: how many experts' visits end at or before it
    g = jnp.minimum(jnp.sum(upto[None, :] <= v[:, None], axis=1,
                            dtype=jnp.int32), held - 1)
    since = v - (upto[g] - tiles[g])
    # the visit after an expert's last is the next expert's first
    after = jnp.minimum(upto[g], v.shape[0] - 1)
    ahead = jnp.where(upto[g] < n_visits, g[after], -1)
    rank = jnp.cumsum(sizes > 0, dtype=jnp.int32) - 1
    edges = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return (g, first_tile[g] + since, (since == 0).astype(jnp.int32),
            rank[g] % 2, ahead, edges, n_visits.reshape(1))


def visit_tile(x_ref, w1, w3, w2, o_ref, t, start, end):
    """Row tile ``t`` whole through one expert's matrices; the rows
    ``start <= row < end`` (the expert's run) are written. The tile
    stays in VMEM while consecutive visits name it: each run of rows in
    it is written by its own expert's visit."""
    _write_run(o_ref, _swiglu_tile(x_ref[...], w1, w3, w2), t, start, end)


def _swiglu_tile(x, w1, w3, w2):
    """A row tile through an expert's matrices (or one tile of their
    ``f``): float32 [tile, d]."""
    dt = x.dtype
    a = jnp.dot(x, w1.astype(dt), preferred_element_type=jnp.float32)
    b = jnp.dot(x, w3.astype(dt), preferred_element_type=jnp.float32)
    h = (jax.nn.silu(a) * b).astype(dt)
    return jnp.dot(h, w2.astype(dt), preferred_element_type=jnp.float32)


def _write_run(o_ref, y, t, start, end):
    """Row tile ``t``'s rows ``start <= row < end`` take ``y``'s; the
    others keep what the tile holds."""
    tile = y.shape[0]
    row = t * tile + jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
    mine = (row >= start) & (row < end)
    o_ref[...] = jnp.where(mine, y.astype(o_ref.dtype), o_ref[...])


def _grouped_tiled_kernel(
    g_ref, t_ref, edge_ref, n_ref, x_ref, w1_ref, w3_ref, w2_ref, o_ref,
    acc_ref
):
    """A visit over a second grid axis of ``f`` tiles: the row tile's
    ``y`` summed tile by tile in float32, written (the rows of the
    visit's expert, by ``where``) with the last."""
    v, j = pl.program_id(0), pl.program_id(1)

    @pl.when(v < n_ref[0])
    def _visit():
        y = _swiglu_tile(x_ref[...], w1_ref[...], w3_ref[...], w2_ref[...])

        @pl.when(j == 0)
        def _first():
            acc_ref[...] = y

        @pl.when(j > 0)
        def _more():
            acc_ref[...] += y

        @pl.when(j == pl.num_programs(1) - 1)
        def _write():
            g = g_ref[v]
            _write_run(o_ref, acc_ref[...], t_ref[v], edge_ref[g],
                       edge_ref[g + 1])


def _grouped_tiled(rows, visits, n_tiles, w1, w3, w2, tile, interpret):
    """``edl_grouped_expert_mlp`` for an expert too wide to take whole:
    the grid is (visits, tiles of ``f``), the three matrices BlockSpec
    operands. An expert whose run spans several row tiles is fetched
    once a row tile."""
    g, t, _, _, _, edges, n_visits = visits
    _, d = rows.shape
    held, _, f = w1.shape
    item = max(w1.dtype.itemsize, rows.dtype.itemsize)
    fixed = 4 * tile * d * rows.dtype.itemsize + 2 * tile * d * 4 + (4 << 20)
    ft = f_tile(d, f, item, (VMEM_BYTES // 2) - fixed)
    n_f = f // ft

    def row_tile(v, j, g_ref, t_ref, *_):
        return (t_ref[v], 0)

    def at(v, j, g_ref, t_ref, edge_ref, n_ref):
        return g_ref[v], jnp.where(v < n_ref[0], j, n_f - 1)

    def up(v, j, *refs):
        e, c = at(v, j, *refs)
        return (e, 0, c)

    def down(v, j, *refs):
        e, c = at(v, j, *refs)
        return (e, c, 0)

    vmem = 3 * 3 * d * ft * item + fixed + 4 * tile * ft * 4
    return pl.pallas_call(
        _grouped_tiled_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_tiles + held - 1, n_f),
            in_specs=[
                pl.BlockSpec((tile, d), row_tile),
                pl.BlockSpec((None, d, ft), up),
                pl.BlockSpec((None, d, ft), up),
                pl.BlockSpec((None, ft, d), down),
            ],
            out_specs=pl.BlockSpec((tile, d), row_tile),
            scratch_shapes=[pltpu.VMEM((tile, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(rows.shape, rows.dtype),
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(vmem),
        ),
        interpret=interpret,
        name="edl_grouped_expert_mlp",
    )(g, t, edges, n_visits, rows, w1, w3, w2)


def _grouped_kernel(
    g_ref, t_ref, fresh_ref, slot_ref, ahead_ref, edge_ref, n_ref,
    x_ref, w1_hbm, w3_hbm, w2_hbm, o_ref, b1, b3, b2, sem
):
    v = pl.program_id(0)

    def copies(e, slot):
        return [pltpu.make_async_copy(src.at[e], dst.at[slot], sem.at[m, slot])
                for m, (src, dst) in enumerate(
                    ((w1_hbm, b1), (w3_hbm, b3), (w2_hbm, b2)))]

    @pl.when((v == 0) & (n_ref[0] > 0))
    def _first():  # nothing to hide the first expert's arrival under
        for c in copies(g_ref[0], 0):
            c.start()

    @pl.when(v < n_ref[0])
    def _visit():
        g, slot = g_ref[v], slot_ref[v]

        @pl.when(fresh_ref[v] == 1)
        def _arrive():
            @pl.when(ahead_ref[v] >= 0)
            def _next():  # arrives under ALL of this expert's visits
                for c in copies(ahead_ref[v], 1 - slot):
                    c.start()

            for c in copies(g, slot):
                c.wait()

        visit_tile(x_ref, b1[slot], b3[slot], b2[slot], o_ref,
                   t_ref[v], edge_ref[g], edge_ref[g + 1])


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def grouped_expert_mlp(
    rows: jnp.ndarray,
    sizes: jnp.ndarray,
    w1: jnp.ndarray,
    w3: jnp.ndarray,
    w2: jnp.ndarray,
    *,
    tile: int = GROUP_TILE,
    interpret: bool = False,
) -> jnp.ndarray:
    """Each expert's SwiGLU over its own run of rows sorted by expert:
    ``(silu(r @ w1[e]) * (r @ w3[e])) @ w2[e]`` for row ``r`` of run
    ``e``, in rows' dtype (``jax.lax.ragged_dot`` three times, as one
    kernel).

    rows [M, d], sorted so that expert ``e``'s ``sizes[e]`` rows follow
    expert ``e - 1``'s from row 0; w1 / w3 [E_held, d, f], w2 [E_held,
    f, d], read where they lie, each expert with a row once. Returns
    [M, d]; a row past the last run holds whatever was there."""
    m, d = rows.shape
    held, _, f = w1.shape
    pad = -m % tile
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    n_tiles = (m + pad) // tile
    item = max(w1.dtype.itemsize, rows.dtype.itemsize)
    # the experts twice (one computing, one arriving) and a copy where
    # they are cast; a tile of rows in and out twice; a, b, h, y and the
    # compiler's own
    vmem = (2 * 3 * d * f * w1.dtype.itemsize + 3 * d * f * item
            + 4 * tile * d * rows.dtype.itemsize
            + 4 * tile * f * 4 + 2 * tile * d * 4 + (4 << 20))
    visits = group_visits(sizes, n_tiles, tile)
    if vmem > VMEM_BYTES:
        out = _grouped_tiled(
            rows, visits, n_tiles, w1, w3, w2, tile, interpret)
        return out[:m] if pad else out

    def row_tile(v, g_ref, t_ref, *_):
        return (t_ref[v], 0)

    out = pl.pallas_call(
        _grouped_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(visits),
            grid=(n_tiles + held - 1,),
            in_specs=[
                pl.BlockSpec((tile, d), row_tile),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((tile, d), row_tile),
            scratch_shapes=[
                pltpu.VMEM((2, d, f), w1.dtype),
                pltpu.VMEM((2, d, f), w3.dtype),
                pltpu.VMEM((2, f, d), w2.dtype),
                pltpu.SemaphoreType.DMA((3, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(rows.shape, rows.dtype),
        input_output_aliases={len(visits): 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(vmem),
        ),
        interpret=interpret,
        name="edl_grouped_expert_mlp",
    )(*visits, rows, w1, w3, w2)
    return out[:m] if pad else out
