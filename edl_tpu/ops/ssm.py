"""The selective state-space layer (Mamba-2, "Transformers are SSMs",
arXiv:2405.21060): the hot parts of ``models/ssm_hybrid.py``.

One head ``h`` of width ``P`` keeps a state ``S [P, N]`` in float32
(``N`` = ``d_state``); with one group, ``B_t`` and ``C_t`` ``[N]`` are
shared by all heads, and ``dt_t^h > 0``, ``A^h < 0``, ``D^h`` are
scalars a head:

    S_t = exp(dt_t A) S_{t-1} + (dt_t x_t) B_t^T
    y_t = S_t C_t + D x_t

so a sequence's whole past is ``S``, of one size whatever its length,
and the three inputs of the causal convolution before it (the tail).

The decode step (:func:`ssm_step`, scope ``ssm.step``) reads each LIVE
slot's ``S`` of one layer once, decays it, adds the rank-one term,
answers ``y`` from the new state and writes it once, in place in the
donated stacked cache ``[L, slots, H, P, N]`` (kernel ``edl_ssm_step``:
one grid step a live slot, its 64 heads' ``[P, N]`` blocks in turn, the
read-out ``S C`` a matmul a head; an idle slot is not touched; the
plain lines do the same arithmetic and are what it is tested against;
XLA's own lines for them copy the whole stacked state a layer, 9.9 ms
where the kernel takes 0.47: ``scripts/exp_ssm_step.py``). ``x`` and ``y`` cross the kernel
transposed, ``[P, H]``: a head's ``x`` is then one lane of the tile,
spread along the sublanes its state's rows lie on, and no transposition
is done inside.

The prefill (:func:`ssd_chunked`, scope ``ssm.chunk``) is the same
recurrence in the chunked ("SSD") form: inside a chunk of ``Q``
positions the quadratic form ``(C B^T masked by the cumulative decay)
(dt x)``, between chunks ``S`` carried in float32. ``C B^T`` is made
once a chunk for all heads (one group).

The causal convolution (``d_conv`` taps, depthwise) is four shifted
products; its carried tail ``[.., (d_conv - 1) * channels]`` holds the
inputs at ``t - 3 .. t - 1`` one after another (scope ``ssm.conv``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# (how many, their indices first) of a [B] bool mask: the kernel's grid
from edl_tpu.ops.retention import live_slots

ROWS = 8  # a float32 tile's sublanes: small operands are padded to it
LANES = 128


# -- the causal convolution -----------------------------------------------------


def conv_prefill(xbc, w, b, last):
    """Depthwise causal convolution over [B, T, C] rows that start a
    sequence (zeros before position 0): ``w`` [K, C] (tap ``j``
    multiplies the input ``K - 1 - j`` positions back), ``b`` [C].
    Returns (silu of the convolution [B, T, C], the tail [B, (K - 1) *
    C]: the inputs at ``last - K + 2 .. last`` one after another, zeros
    where that is before position 0)."""
    with jax.named_scope("ssm.conv"):
        bsz, t, c = xbc.shape
        k = w.shape[0]
        padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
        acc = b.astype(jnp.float32)
        for j in range(k):
            acc = acc + padded[:, j:j + t].astype(jnp.float32) \
                * w[j].astype(jnp.float32)
        # row ``last + 1 + j`` of ``padded`` is input ``last - K + 2 + j``
        at = last[:, None] + 1 + jnp.arange(k - 1)[None, :]
        tail = jnp.take_along_axis(padded, at[..., None], axis=1)
        return (jax.nn.silu(acc).astype(xbc.dtype),
                tail.reshape(bsz, (k - 1) * c))


def conv_step(xbc, tail, layer, w, b, live):
    """One new position a slot: xbc [B, C]; tail [L, B, (K - 1) * C],
    the stacked cache (a slot's three inputs one after another: whole
    lane tiles, slots on the sublanes); ``layer`` a (traced) index. A
    row that is not ``live`` keeps its tail. Returns (silu of the
    convolution [B, C], tail)."""
    with jax.named_scope("ssm.conv"):
        k, c = w.shape
        old = jax.lax.dynamic_index_in_dim(tail, layer, 0, keepdims=False)
        window = jnp.concatenate([old, xbc.astype(old.dtype)], axis=1)
        acc = b.astype(jnp.float32)
        for j in range(k):
            acc = acc + window[:, j * c:(j + 1) * c].astype(jnp.float32) \
                * w[j].astype(jnp.float32)
        new = jnp.where(live[:, None], window[:, c:], old)
        tail = jax.lax.dynamic_update_index_in_dim(tail, new, layer, 0)
        return jax.nn.silu(acc).astype(xbc.dtype), tail


# -- decode: one new position a slot ----------------------------------------------


def _step_kernel(slot_ref, layer_ref, xdt_ref, dec_ref, b_ref, c_ref, s_ref,
                 y_ref, s_out_ref, *, heads: int):
    """One live slot: every head's ``S`` [P, N] in, decayed, updated,
    read and out. ``xdt_ref`` [P, H] float32 is ``dt x`` transposed (a
    head a lane); ``dec_ref`` [P, H] holds ``exp(dt A)`` in every row
    of a head's lane (a scalar is not spread over both sublanes and
    lanes in one step); ``b_ref`` [ROWS, N] holds ``B`` in row 0;
    ``c_ref`` [N, LANES] holds ``C`` in every column, in the dtype the
    read-out multiplies in: ``S C`` is then one matmul a head whose
    every lane holds the answer, ``y_ref`` [P, H] takes lane ``h`` of
    it. (A lane reduction a head on the VPU reads 66% of the HBM peak,
    this 78%: ``scripts/exp_ssm_step.py``.)"""
    del slot_ref, layer_ref  # read by the index maps alone
    xdt, dec = xdt_ref[...], dec_ref[...]
    b_row, c_mat = b_ref[0:1, :], c_ref[...]
    for h in range(heads):
        s = dec[:, h:h + 1] * s_ref[h] + xdt[:, h:h + 1] * b_row  # [P, N]
        s_out_ref[h] = s
        y = jnp.dot(s.astype(c_mat.dtype), c_mat,
                    preferred_element_type=jnp.float32)  # [P, LANES]
        y_ref[:, h:h + 1] = y[:, h:h + 1]


@functools.partial(jax.jit, static_argnames=("mxu_dtype", "interpret"))
def ssm_step_kernel(xdt, decay, bm, cm, state, live, layer, *, mxu_dtype,
                    interpret: bool = False):
    """The kernel call: xdt [B, H, P] float32 (``dt x``), decay [B, H]
    float32 (``exp(dt A)``), bm and cm [B, N] float32, state [L, B, H,
    P, N] float32, live [B] bool, layer a traced int32 scalar;
    ``mxu_dtype`` is what the new state is rounded to for the read-out
    ``S C`` (the carried state is not rounded). Returns (``S C`` [B, H,
    P] float32, garbage in rows that are not live; the state, updated
    in place at ``layer`` for the live rows and untouched elsewhere)."""
    b, h, p = xdt.shape
    n = bm.shape[-1]
    f32 = jnp.float32
    rows = lambda x: jnp.pad(x, ((0, 0), (0, ROWS - x.shape[1]), (0, 0)))
    xdt_t = jnp.swapaxes(xdt, 1, 2)  # [B, P, H]
    dec = jnp.broadcast_to(decay[:, None, :], (b, p, h))
    b8 = rows(bm[:, None, :])
    c_mat = jnp.broadcast_to(cm.astype(mxu_dtype)[:, :, None], (b, n, LANES))
    n_live, order = live_slots(live)

    def small(t, slot_ref, layer_ref):
        return (slot_ref[t], 0, 0)

    def big(t, slot_ref, layer_ref):
        return (layer_ref[0], slot_ref[t], 0, 0, 0)

    spec = lambda *shape: pl.BlockSpec((None,) + shape, small)
    big_spec = pl.BlockSpec((None, None, h, p, n), big)
    block_bytes = h * p * n * 4
    y_t, state = pl.pallas_call(
        functools.partial(_step_kernel, heads=h),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_live,),
            in_specs=[spec(p, h), spec(p, h), spec(ROWS, n),
                      spec(n, LANES), big_spec],
            out_specs=[spec(p, h), big_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, p, h), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # the state's blocks in and out are one buffer: a slot the grid
        # never visits keeps what it held
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # a slot's heads in and out, each double-buffered
            vmem_limit_bytes=4 * block_bytes + (16 << 20),
        ),
        interpret=interpret,
        name="edl_ssm_step",
    )(order, jnp.reshape(layer, (1,)).astype(jnp.int32), xdt_t, dec, b8,
      c_mat, state)
    return jnp.swapaxes(y_t, 1, 2), state


def ssm_step(x, bm, cm, dt, a, d, state, layer, live, *, dtype,
             use_kernel: bool, interpret: bool = False):
    """One decode step of one layer over B slots.

    x [B, H, P]; bm, cm [B, N]; dt [B, H] float32 (after the softplus);
    a [H] float32 (``-exp(A_log)``); d [H]; state [L, B, H, P, N]
    float32, the stacked cache (never a layer's slice); ``layer`` a
    (traced) index; live [B] bool. Rows that are not live keep their
    state and read zeros. Returns (y [B, H, P] in ``dtype``, state).
    Under ``use_kernel`` the read-out ``S C`` multiplies the new state
    rounded to ``dtype`` (accumulating in float32, as a prefill chunk
    reads the carried state); the plain lines read it in float32."""
    with jax.named_scope("ssm.step"):
        f32 = jnp.float32
        xf = x.astype(f32)
        decay = jnp.exp(dt * a.astype(f32))
        xdt = xf * dt[..., None]
        if use_kernel:
            y, state = ssm_step_kernel(
                xdt, decay, bm.astype(f32), cm.astype(f32), state, live,
                jnp.asarray(layer, jnp.int32), mxu_dtype=dtype,
                interpret=interpret)
        else:
            old = jax.lax.dynamic_index_in_dim(state, layer, 0, False)
            s_new = decay[..., None, None] * old \
                + xdt[..., None] * bm.astype(f32)[:, None, None, :]
            s_new = jnp.where(live[:, None, None, None], s_new, old)
            state = jax.lax.dynamic_update_index_in_dim(
                state, s_new, layer, 0)
            y = jnp.sum(s_new * cm.astype(f32)[:, None, None, :], axis=-1)
        y = y + d.astype(f32)[None, :, None] * xf
        y = jnp.where(live[:, None, None], y, 0.0)
        return y.astype(dtype), state


# -- prefill: a whole prompt, chunk by chunk ----------------------------------------


def ssd_chunked(x, bm, cm, dt, a, d, valid=None, start=None, *, chunk: int,
                dtype):
    """The recurrence over [B, T] in the chunked form: x [B, T, H, P];
    bm, cm [B, T, N]; dt [B, T, H] float32; a, d [H]; valid [B, T] bool
    (positions that exist: one that does not neither decays the state
    nor enters it); start, the ``S`` [B, H, P, N] the sequence begins
    from (zeros if None). Returns (y [B, T, H, P] in ``dtype``, ``S``
    after the last valid position). Products take ``dtype`` operands
    and accumulate in float32; the carried state is never rounded."""
    with jax.named_scope("ssm.chunk"):
        b, t, h, p = x.shape
        n = bm.shape[-1]
        f32 = jnp.float32
        q = min(chunk, t)
        pad = (-t) % q
        if valid is None:
            valid = jnp.ones((b, t), bool)
        dt = jnp.where(valid[..., None], dt, 0.0)
        if pad:
            widen = lambda v: jnp.pad(
                v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            x, bm, cm, dt = map(widen, (x, bm, cm, dt))
        nc = (t + pad) // q
        # chunks lead, for the scan; inside one, heads lead: every
        # product is a batched matmul over (B, H) with positions minor
        split = lambda v: jnp.moveaxis(
            v.reshape((b, nc, q) + v.shape[2:]), 1, 0)
        heads = lambda v: jnp.moveaxis(split(v), 3, 2)  # [nc, B, H, Q, ..]
        seen = jnp.tril(jnp.ones((q, q), bool))  # [t, s]: s <= t
        af = a.astype(f32)

        def body(s_prev, xs):
            xc, bc, cc, dtc = xs  # [B, H, Q, P], [B, Q, N] x 2, [B, H, Q]
            cum = jnp.cumsum(dtc * af[:, None], axis=-1)  # own decay in
            xdt = (xc.astype(f32) * dtc[..., None]).astype(dtype)
            g = jnp.einsum("btn,bsn->bts", cc, bc,
                           preferred_element_type=f32)
            between = cum[..., :, None] - cum[..., None, :]  # [B, H, t, s]
            w = jnp.where(seen, jnp.exp(jnp.where(seen, between, 0.0)), 0.0)
            w = (w * g[:, None]).astype(dtype)
            y = jnp.einsum("bhts,bhsp->bhtp", w, xdt,
                           preferred_element_type=f32)
            # the chunks before this one, through the carried state
            y += jnp.exp(cum)[..., None] * jnp.einsum(
                "btn,bhpn->bhtp", cc, s_prev.astype(dtype),
                preferred_element_type=f32)
            # and this chunk into it: what is left of each position at
            # the chunk's end
            left = jnp.exp(cum[..., -1:] - cum)  # [B, H, Q]
            s_new = jnp.exp(cum[..., -1])[..., None, None] * s_prev \
                + jnp.einsum(
                    "bhsp,bsn->bhpn",
                    (xdt.astype(f32) * left[..., None]).astype(dtype), bc,
                    preferred_element_type=f32)
            y += d.astype(f32)[:, None, None] * xc.astype(f32)
            return s_new, y.astype(dtype)

        if start is None:
            start = jnp.zeros((b, h, p, n), f32)  # has seen nothing
        end, ys = jax.lax.scan(
            body, start, (heads(x), split(bm), split(cm), heads(dt)))
        # [nc, B, H, Q, P] -> [B, T, H, P]
        y = ys.transpose(1, 0, 3, 2, 4).reshape(b, t + pad, h, p)
        return y[:, :t], end
