"""Power retention of degree 2: the hot parts of ``models/retention.py``.

Attention whose weights are ``(q . k / sqrt(d)) ** 2`` times a per-head
decay has an exact recurrent form, because a squared dot product is a
dot product of squares: with ``phi(a) . phi(b) = (a . b) ** 2 / d``,

    S_t = g_t S_{t-1} + v_t phi(k_t)^T        z_t = g_t z_{t-1} + phi(k_t)
    y_t = S_t phi(q_t) / (z_t . phi(q_t) + eps)

so a sequence's whole past is ``S`` and ``z``, of one size whatever its
length (Manifest AI, "Scaling Context Requires Rethinking Attention",
arXiv:2507.04239, whose symmetric power is the smallest such ``phi``).

**How the state is laid out.** ``phi`` is the symmetric square, taken
along the diagonals of ``a a^T`` instead of along its rows: row ``m``
of ``phi(a)`` is ``w_m * a * roll(a, m)`` (lane ``i`` holds ``a_i
a_{i-m}``), for ``m = 0 .. d/2``. Each unordered pair ``{i, j}`` at
circular distance ``m`` lies on row ``m`` once (``w = sqrt(2 / d)``),
the squares on row 0 (``w = sqrt(1 / d)``), and the pairs at distance
``d / 2`` twice, so that row weighs ``sqrt(1 / d)``: ``(d / 2 + 1) *
d`` numbers, 8320 at ``d = 128`` against the 8256 of the packed
triangle (+0.8%). What it buys: every row is ``d`` whole lanes, made
from the ``d``-wide vector by one lane rotation and one multiply, so
neither ``phi(k)`` nor ``phi(q)`` ever exists outside the decode
kernel's registers. ``S`` is stored values-major, ``[.., d(v), (d / 2 +
1) * d]`` float32: the rank-one update is then a column (``v``) times a
row (``phi(k)``), and the five query heads of a group are the rows of
one matmul that contracts the minor dimension of both operands.

The decode step (:func:`retention_step`, scope ``attn.retention_step``)
reads each LIVE (slot, kv head)'s ``S`` once, decays it, adds the
rank-one term, answers the group's query heads from the new state and
writes it once, in place in the donated cache (kernel
``edl_retention_step``; the plain lines do the same arithmetic and are
what it is tested against). ``z`` is 1/128 of the state and is XLA's.
The prefill (:func:`retention_chunked`, scope ``attn.retention_chunk``)
walks chunks: inside one the masked, decayed squared products against
``v``, between them ``S`` and ``z`` carried in float32.

Departures from the published kernels that the writer knows of: the
query reads the new ``S`` and ``z`` rounded to the activation dtype
(bfloat16), accumulating in float32, as every other product of the
model does; the carried state itself is never rounded. ``phi(k)`` is float32 in the
decode step and rounded to the activation dtype in a prefill chunk's
state update (a matmul over the chunk).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

GROUP_ROWS = 8  # query heads of one kv head, padded to a float32 tile


def phi_rows(d: int) -> int:
    return d // 2 + 1


def phi_width(d: int) -> int:
    """Numbers of ``phi`` of a ``d``-wide vector, as stored."""
    return phi_rows(d) * d


def phi_weights(d: int) -> np.ndarray:
    """``w_m`` of each row: ``sum_m w_m ** 2 (a a_m)(b b_m) = (a . b) **
    2 / d``."""
    if d % 2:
        raise ValueError(f"the head width must be even, got {d}")
    w = np.full(phi_rows(d), np.sqrt(2.0 / d))
    w[0] = w[-1] = np.sqrt(1.0 / d)
    return w


@functools.lru_cache(maxsize=None)
def _selectors(d: int):
    """Two [d, phi_width(d)] matrices of zeros and ones: ``x @ tiled`` is
    ``x`` repeated for every row ``m``, ``x @ rolled`` is ``x`` rolled by
    every ``m`` in turn, side by side."""
    i = np.arange(d)
    tiled = np.zeros((d, phi_rows(d), d), np.float32)
    rolled = np.zeros((d, phi_rows(d), d), np.float32)
    for m in range(phi_rows(d)):
        tiled[i, m, i] = 1.0
        rolled[(i - m) % d, m, i] = 1.0
    return tiled.reshape(d, -1), rolled.reshape(d, -1)


def phi(x: jnp.ndarray) -> jnp.ndarray:
    """[..., d] -> [..., phi_width(d)] float32, rows ``m`` side by side.
    Both factors are products with a matrix of zeros and ones (exact in
    any dtype), which come out ``phi_width`` lanes wide as they are. A
    roll is a slice and a concatenation on the minor dimension, and 65
    of them concatenated again XLA:TPU writes as 65 passes over the
    whole result; a broadcast to [.., 65, d] reshaped to [.., 65 * d]
    it lays out anew in float32, twice."""
    d = x.shape[-1]
    tiled, rolled = (
        jnp.matmul(x, jnp.asarray(sel, x.dtype),
                   precision=jax.lax.Precision.HIGHEST).astype(jnp.float32)
        for sel in _selectors(d))
    return jnp.repeat(jnp.asarray(phi_weights(d), jnp.float32), d) \
        * tiled * rolled


# -- decode: one new position a slot ------------------------------------------


def _step_kernel(slot_ref, layer_ref, q_ref, kvg_ref, s_ref, y_ref, s_out_ref,
                 *, d: int, mxu_dtype):
    """One (live slot, kv head): the whole ``S`` [d(v), rows * d] in,
    decayed, updated, queried and out. ``q_ref`` [GROUP_ROWS, d] float32
    holds the group's query heads; ``kvg_ref`` [GROUP_ROWS, d] float32
    holds ``k`` in row 0, ``v`` in row 1 and the decay ``g`` in every
    lane of row 2."""
    del slot_ref, layer_ref  # read by the index maps alone
    q, kvg = q_ref[...], kvg_ref[...]
    g = kvg[2:3, :]
    # v as a column: the row spread down the sublanes, its diagonal kept
    r = jax.lax.broadcasted_iota(jnp.int32, (d, d), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (d, d), 1)
    v_col = jnp.sum(
        jnp.where(r == c, jnp.broadcast_to(kvg[1:2, :], (d, d)), 0.0),
        axis=1, keepdims=True)
    acc = jnp.zeros((GROUP_ROWS, d), jnp.float32)
    for m, w in enumerate(phi_weights(d).tolist()):
        pk = (kvg * pltpu.roll(kvg, m, 1))[0:1, :] * w  # phi(k), row m
        pq = (q * pltpu.roll(q, m, 1) * w).astype(mxu_dtype)
        s = g * s_ref[:, m * d:(m + 1) * d] + v_col * pk  # [d(v), d(i)]
        s_out_ref[:, m * d:(m + 1) * d] = s
        acc += jax.lax.dot_general(
            pq, s.astype(mxu_dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    y_ref[...] = acc


def live_slots(live: jnp.ndarray):
    """(how many, their indices first) of a [B] bool mask: the kernel's
    grid. Entries past the count stay inside the cache and never run."""
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    return jnp.sum(live).astype(jnp.int32), order


@functools.partial(jax.jit, static_argnames=("mxu_dtype", "interpret"))
def retention_step_kernel(q, k, v, g, state, live, layer, *, mxu_dtype,
                          interpret: bool = False):
    """The kernel call: q [B, KV, G, d], k and v [B, KV, d], g [B, KV]
    float32 (the decay itself), state [L, B, KV, d, phi_width(d)]
    float32, live [B] bool, layer a traced int32 scalar. Returns (the
    numerators [B, KV, G, d] float32, garbage in rows that are not
    live; the state, updated in place at ``layer`` for the live rows
    and untouched elsewhere)."""
    b, kvh, groups, d = q.shape
    if groups > GROUP_ROWS:
        raise ValueError(f"{groups} query heads a kv head; the kernel "
                         f"holds {GROUP_ROWS}")
    width = phi_width(d)
    f32 = jnp.float32
    rows_to = lambda x: jnp.pad(
        x.astype(f32), ((0, 0), (0, 0), (0, GROUP_ROWS - x.shape[2]), (0, 0)))
    q8 = rows_to(q)
    kvg = rows_to(jnp.stack(
        [k, v, jnp.broadcast_to(g[..., None], (b, kvh, d))], axis=2))
    n_live, order = live_slots(live)

    def small(t, slot_ref, layer_ref):
        return (slot_ref[t // kvh], t % kvh, 0, 0)

    def big(t, slot_ref, layer_ref):
        return (layer_ref[0], slot_ref[t // kvh], t % kvh, 0, 0)

    small_spec = pl.BlockSpec((None, None, GROUP_ROWS, d), small)
    big_spec = pl.BlockSpec((None, None, None, d, width), big)
    block_bytes = d * width * 4
    y, state = pl.pallas_call(
        functools.partial(_step_kernel, d=d, mxu_dtype=mxu_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_live * kvh,),
            in_specs=[small_spec, small_spec, big_spec],
            out_specs=[small_spec, big_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, kvh, GROUP_ROWS, d), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # the state's blocks in and out are one buffer: a (slot, head)
        # the grid never visits keeps what it held
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # a block in and a block out, each double-buffered
            vmem_limit_bytes=4 * block_bytes + (16 << 20),
        ),
        interpret=interpret,
        name="edl_retention_step",
    )(order, jnp.reshape(layer, (1,)).astype(jnp.int32), q8, kvg, state)
    return y[:, :, :groups], state


def retention_step(q, k, v, log_g, state, z, layer: int, live, *, eps: float,
                   dtype, use_kernel: bool, interpret: bool = False):
    """One decode step of one layer over B slots.

    q [B, KV, G, d]; k, v [B, KV, d]; log_g [B, KV] float32; state [L,
    B, KV, d, phi_width(d)] and z [L, B, KV, phi_width(d)] float32, the
    stacked caches (never a layer's slice); live [B] bool. Rows that
    are not live keep their state and read zeros. Returns (y [B, KV, G,
    d] in ``dtype``, state, z)."""
    with jax.named_scope("attn.retention_step"):
        f32 = jnp.float32
        g = jnp.exp(log_g)
        pk = phi(k)
        pq = phi(q).astype(dtype)
        z_new = jnp.where(live[:, None, None], g[..., None] * z[layer] + pk,
                          z[layer])
        z = z.at[layer].set(z_new)
        den = jnp.einsum("bkgD,bkD->bkg", pq, z_new.astype(dtype),
                         preferred_element_type=f32)
        if use_kernel:
            num, state = retention_step_kernel(
                q, k, v, g, state, live, jnp.int32(layer), mxu_dtype=dtype,
                interpret=interpret)
        else:
            s_new = g[..., None, None] * state[layer] \
                + v.astype(f32)[..., :, None] * pk[..., None, :]
            s_new = jnp.where(live[:, None, None, None], s_new, state[layer])
            state = state.at[layer].set(s_new)
            num = jnp.einsum("bkgD,bkvD->bkgv", pq, s_new.astype(dtype),
                             preferred_element_type=f32)
        y = num / (den[..., None] + eps)
        y = jnp.where(live[:, None, None, None], y, 0.0)
        return y.astype(dtype), state, z


def empty_state(b: int, kvh: int, d: int):
    """(S, z) of a sequence that has seen nothing."""
    width = phi_width(d)
    return (jnp.zeros((b, kvh, d, width), jnp.float32),
            jnp.zeros((b, kvh, width), jnp.float32))


# -- prefill: a whole prompt, chunk by chunk ----------------------------------


Z_ROWS = 8  # ``z`` rides under ``S`` as one more row, padded to a tile


def _chunk_kernel(q_ref, k_ref, vwt_ref, tot_ref, s_ref, num_ref, den_ref,
                  s_out_ref, *, d: int, mxu_dtype):
    """One kv head's chunk against the state it starts from: ``s_ref``
    [d + Z_ROWS, rows * d] float32 holds ``S`` and under it ``z``.
    ``q_ref`` [C * G, d] are the chunk's query rows, ``k_ref`` [C, d] its
    keys, ``vwt_ref`` [d + Z_ROWS, C] its values, each times what is
    left of it at the chunk's end, transposed, with those weights alone
    as the row under them; ``tot_ref`` [8, d] the chunk's whole decay in
    every lane. Out: what the state gives every query (``num_ref`` [C *
    G, d(v)], and ``den_ref`` [C * G, d], whose lanes the caller sums),
    and the state after the chunk. ``phi`` of queries and keys is made
    a row at a time and never leaves the registers."""
    f32 = jnp.float32
    q, k = q_ref[...].astype(f32), k_ref[...].astype(f32)
    vwt, total = vwt_ref[...], tot_ref[0:1, :]
    num_ref[...] = jnp.zeros_like(num_ref)
    den_ref[...] = jnp.zeros_like(den_ref)
    for m, w in enumerate(phi_weights(d).tolist()):
        s = s_ref[:, m * d:(m + 1) * d]  # [d(v) + Z_ROWS, d(i)]
        pq = (q * pltpu.roll(q, m, 1) * w).astype(mxu_dtype)
        num_ref[...] += jax.lax.dot_general(
            pq, s[:d].astype(mxu_dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=f32)
        den_ref[...] += pq.astype(f32) * s[d:d + 1].astype(mxu_dtype).astype(
            f32)
        pk = (k * pltpu.roll(k, m, 1) * w).astype(mxu_dtype)
        s_out_ref[:, m * d:(m + 1) * d] = total * s + jnp.dot(
            vwt, pk, preferred_element_type=f32)


@functools.partial(jax.jit, static_argnames=("mxu_dtype", "interpret"))
def retention_chunk_kernel(q, k, vwt, total, s_ext, *, mxu_dtype,
                           interpret: bool = False):
    """The kernel call, one grid step a (batch row, kv head): q [N, C *
    G, d], k [N, C, d], vwt [N, d + Z_ROWS, C], total [N] float32,
    s_ext [N, d + Z_ROWS, phi_width(d)] float32. Returns (num [N, C *
    G, d] float32, den [N, C * G] float32, s_ext after the chunk,
    updated in place)."""
    n, rows, d = q.shape
    c = k.shape[1]
    width = phi_width(d)
    f32 = jnp.float32
    tot = jnp.broadcast_to(total.astype(f32)[:, None, None], (n, 8, d))
    at = lambda i: (i, 0, 0)
    spec = lambda a: pl.BlockSpec((None,) + a.shape[1:], at)
    block_bytes = (d + Z_ROWS) * width * 4
    num, den, s_ext = pl.pallas_call(
        functools.partial(_chunk_kernel, d=d, mxu_dtype=mxu_dtype),
        grid=(n,),
        in_specs=[spec(q), spec(k), spec(vwt), spec(tot), spec(s_ext)],
        out_specs=[spec(q), spec(q), spec(s_ext)],
        out_shape=[jax.ShapeDtypeStruct((n, rows, d), f32),
                   jax.ShapeDtypeStruct((n, rows, d), f32),
                   jax.ShapeDtypeStruct(s_ext.shape, f32)],
        input_output_aliases={4: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # the state in and out and the chunk's rows (queries in,
            # two float32 accumulators out), each double-buffered
            vmem_limit_bytes=4 * block_bytes + 24 * rows * d + (16 << 20),
        ),
        interpret=interpret,
        name="edl_retention_chunk",
    )(q, k, vwt, tot, s_ext)
    return num, jnp.sum(den, axis=-1), s_ext


def retention_chunked(q, k, v, log_g, valid=None, start=None, *, chunk: int,
                      eps: float, dtype, use_kernel: bool = False,
                      interpret: bool = False):
    """Causal retention over [B, T]: q [B, T, KV, G, d]; k, v [B, T, KV,
    d]; log_g [B, T, KV] float32; valid [B, T] bool (positions that
    exist: one that does not neither decays the state nor enters it);
    start, the (S, z) the sequence begins from (zeros if None).
    Returns (y [B, T, KV, G, d] in ``dtype``, S [B, KV, d,
    phi_width(d)], z [B, KV, phi_width(d)]: the state after the last
    valid position).

    Inside a chunk everything is laid out kv head first, a chunk's ``C
    * G`` query rows of one kv head together, so each product is a
    plain batched matmul over (B, KV). What the carried state gives the
    chunk's queries and what the chunk's keys add to it is the kernel
    ``edl_retention_chunk`` under ``use_kernel`` (``phi`` of neither is
    ever written out), else the plain lines with ``phi`` made whole in
    the layout its one reader wants: what the kernel is tested
    against, and 1.3 MB of ``phi`` a position a layer through HBM."""
    with jax.named_scope("attn.retention_chunk"):
        b, t, kvh, groups, d = q.shape
        f32 = jnp.float32
        c = min(chunk, t)
        pad = (-t) % c
        if valid is None:
            valid = jnp.ones((b, t), bool)
        if pad:
            widen = lambda x: jnp.pad(
                x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            q, k, v, log_g, valid = map(widen, (q, k, v, log_g, valid))
        log_g = jnp.where(valid[..., None], log_g, 0.0)
        n = (t + pad) // c

        def split(x, heads: bool):
            # [B, T, (KV, ...)] -> [N, B, (KV,) C, ...]: chunks lead,
            # for the scan
            x = jnp.moveaxis(x.reshape((b, n, c) + x.shape[2:]), 1, 0)
            return jnp.moveaxis(x, 3, 2) if heads else x

        seen = jnp.tril(jnp.ones((c, c), bool))[:, None, :]  # [C, 1, S]
        rows = c * groups
        flat = lambda x: x.reshape((b * kvh,) + x.shape[2:])

        def from_state(q2, kc, vw, left, total, carry):
            """(what the carried state gives each query row: numerator
            [B, KV, rows, d] and summed weights [B, KV, rows]; the
            state after the chunk), plain."""
            s_prev, z_prev = carry
            pq = phi(q2).astype(dtype)
            num = jnp.einsum("bkrD,bkvD->bkrv", pq, s_prev.astype(dtype),
                             preferred_element_type=f32)
            den = jnp.einsum("bkrD,bkD->bkr", pq, z_prev.astype(dtype),
                             preferred_element_type=f32)
            pk = phi(kc).astype(dtype)
            s_new = total[..., None, None] * s_prev + jnp.einsum(
                "bksv,bksD->bkvD", vw, pk, preferred_element_type=f32)
            z_new = total[..., None] * z_prev + jnp.einsum(
                "bks,bksD->bkD", left.astype(dtype), pk,
                preferred_element_type=f32)
            return num, den, (s_new, z_new)

        def from_state_kernel(q2, kc, vw, left, total, carry):
            vwt = jnp.concatenate([
                jnp.swapaxes(vw, -1, -2), left.astype(dtype)[:, :, None, :],
                jnp.zeros((b, kvh, Z_ROWS - 1, c), dtype)], axis=2)
            num, den, s_ext = retention_chunk_kernel(
                flat(q2), flat(kc), flat(vwt), flat(total), flat(carry),
                mxu_dtype=dtype, interpret=interpret)
            return (num.reshape(b, kvh, rows, d), den.reshape(b, kvh, rows),
                    s_ext.reshape(carry.shape))

        def body(carry, xs):
            qc, kc, vc, lg, ok = xs  # [B, KV, C, (G,) d], [B, KV, C], [B, C]
            q2 = qc.reshape(b, kvh, rows, d)
            run = jnp.cumsum(lg, axis=-1)  # this position's decay is in it
            sc = jnp.einsum("bkrd,bksd->bkrs", q2, kc,
                            preferred_element_type=f32) * d ** -0.5
            between = run[..., :, None, None] - run[..., None, None, :]
            a = sc.reshape(b, kvh, c, groups, c)
            a = jnp.where(seen, a * a * jnp.exp(jnp.where(seen, between, 0.0)),
                          0.0).reshape(b, kvh, rows, c)
            num = jnp.einsum("bkrs,bksv->bkrv", a.astype(dtype), vc,
                             preferred_element_type=f32)
            den = jnp.sum(a, axis=-1)
            # the chunks before this one, through the carried state,
            # and this chunk into it: what is left of each position at
            # the chunk's end, and of the state that came in
            left = jnp.exp(run[..., -1:] - run) * ok[:, None, :]  # [B, KV, C]
            vw = (vc.astype(f32) * left[..., None]).astype(dtype)
            num_s, den_s, carry = (
                from_state_kernel if use_kernel else from_state)(
                q2, kc, vw, left, jnp.exp(run[..., -1]), carry)
            since = jnp.repeat(jnp.exp(run), groups, axis=-1)  # [B, KV, rows]
            num += since[..., None] * num_s
            den += since * den_s
            y = (num / (den[..., None] + eps)).astype(dtype)
            return carry, y.reshape(b, kvh, c, groups, d)

        if start is None:
            start = empty_state(b, kvh, d)
        if use_kernel:
            s0, z0 = start
            start = jnp.concatenate([
                s0, z0[:, :, None, :],
                jnp.zeros((b, kvh, Z_ROWS - 1, z0.shape[-1]), f32)], axis=2)
        end, ys = jax.lax.scan(body, start, (
            split(q, True), split(k, True), split(v, True),
            split(log_g, True), split(valid, False)))
        s, z = (end[:, :, :d], end[:, :, d]) if use_kernel else end
        # [N, B, KV, C, G, d] -> [B, T, KV, G, d]
        y = ys.transpose(1, 0, 3, 2, 4, 5).reshape(b, t + pad, kvh, groups, d)
        return y[:, :t], s, z
