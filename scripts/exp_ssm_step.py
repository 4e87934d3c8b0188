"""One state-space layer's decode step alone, at ``granite4h.decode-hybrid``'s
sizes (72 slots x 64 heads x [64, 128] float32 of state a layer): how
near the HBM peak each way of writing it comes. Host clock over many
calls, the state donated; GB/s of the live slots' state read once and
written once (PERF.md section 6, PR 37).

    PYTHONPATH=. python scripts/exp_ssm_step.py [--layers 12] [--calls 20]

Forms: the plain ``jax.numpy`` lines (``ops.ssm.ssm_step`` with
``use_kernel=False``), the kernel as ``ops/ssm.py`` has it (``lane_mxu``
since this script was read; ``lane_vpu`` before), and the
kernel bodies below, which differ in where a head's decay comes from (a
lane of a [P, H] tile, or a scalar) and in how ``S C`` is read out (a
lane reduction a head on the VPU / XLU, or one matmul against ``C``
spread over 128 columns, ``S`` rounded to bfloat16 for it).
"""

import argparse
import json
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from edl_tpu.ops import ssm as ops

B, H, P, N = 72, 64, 64, 128


def body_lane_decay_vpu(slot_ref, layer_ref, xdt_ref, dec_ref, bc_ref, s_ref,
                        y_ref, s_out_ref):
    xdt, dec = xdt_ref[...], dec_ref[...]
    b_row, c_row = bc_ref[0:1, :], bc_ref[1:2, :]
    for h in range(H):
        s = dec[:, h:h + 1] * s_ref[h] + xdt[:, h:h + 1] * b_row
        s_out_ref[h] = s
        y_ref[:, h:h + 1] = jnp.sum(s * c_row, axis=1, keepdims=True)


def body_scalar_decay_vpu(slot_ref, layer_ref, dec_ref, xdt_ref, bc_ref,
                          s_ref, y_ref, s_out_ref):
    t = pl.program_id(0)
    xdt = xdt_ref[...]
    b_row, c_row = bc_ref[0:1, :], bc_ref[1:2, :]
    for h in range(H):
        s = dec_ref[slot_ref[t], h] * s_ref[h] + xdt[:, h:h + 1] * b_row
        s_out_ref[h] = s
        y_ref[:, h:h + 1] = jnp.sum(s * c_row, axis=1, keepdims=True)


def body_scalar_decay_mxu(slot_ref, layer_ref, dec_ref, xdt_ref, bc_ref,
                          cmat_ref, s_ref, y_ref, s_out_ref):
    t = pl.program_id(0)
    xdt = xdt_ref[...]
    b_row = bc_ref[0:1, :]
    cmat = cmat_ref[...]  # [N, 128] bfloat16: C in every column
    for h in range(H):
        s = dec_ref[slot_ref[t], h] * s_ref[h] + xdt[:, h:h + 1] * b_row
        s_out_ref[h] = s
        y = jnp.dot(s.astype(jnp.bfloat16), cmat,
                    preferred_element_type=jnp.float32)  # [P, 128], all equal
        y_ref[:, h:h + 1] = y[:, h:h + 1]


def body_lane_decay_mxu(slot_ref, layer_ref, xdt_ref, dec_ref, bc_ref,
                        cmat_ref, s_ref, y_ref, s_out_ref):
    xdt, dec = xdt_ref[...], dec_ref[...]
    b_row = bc_ref[0:1, :]
    cmat = cmat_ref[...]
    for h in range(H):
        s = dec[:, h:h + 1] * s_ref[h] + xdt[:, h:h + 1] * b_row
        s_out_ref[h] = s
        y = jnp.dot(s.astype(jnp.bfloat16), cmat,
                    preferred_element_type=jnp.float32)
        y_ref[:, h:h + 1] = y[:, h:h + 1]


def call(form, xdt, decay, bm, cm, state, live, layer):
    f32 = jnp.float32
    rows = lambda x: jnp.pad(x, ((0, 0), (0, 8 - x.shape[1]), (0, 0)))
    xdt_t = jnp.swapaxes(xdt, 1, 2)
    bc = rows(jnp.stack([bm, cm], axis=1))
    cmat = jnp.broadcast_to(
        cm.astype(jnp.bfloat16)[:, :, None], (B, N, 128))
    n_live, order = ops.live_slots(live)
    scalar = form.startswith("scalar")
    small = lambda t, slot_ref, *_: (slot_ref[t], 0, 0)
    big = (lambda t, slot_ref, layer_ref, *_:
           (layer_ref[0], slot_ref[t], 0, 0, 0))
    spec = lambda *shape: pl.BlockSpec((None,) + shape, small)
    big_spec = pl.BlockSpec((None, None, H, P, N), big)
    prefetch = [order, jnp.reshape(layer, (1,)).astype(jnp.int32)]
    ins, in_specs = [xdt_t], [spec(P, H)]
    if scalar:
        prefetch.append(decay)
    else:
        ins.append(jnp.broadcast_to(decay[:, None, :], (B, P, H)))
        in_specs.append(spec(P, H))
    ins.append(bc)
    in_specs.append(spec(8, N))
    if form.endswith("mxu"):
        ins.append(cmat)
        in_specs.append(spec(N, 128))
    ins.append(state)
    in_specs.append(big_spec)
    body = {"lane_vpu": body_lane_decay_vpu,
            "scalar_vpu": body_scalar_decay_vpu,
            "scalar_mxu": body_scalar_decay_mxu,
            "lane_mxu": body_lane_decay_mxu}[form]
    y_t, state = pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=(n_live,),
            in_specs=in_specs, out_specs=[spec(P, H), big_spec]),
        out_shape=[jax.ShapeDtypeStruct((B, P, H), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={len(prefetch) + len(ins) - 1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=4 * H * P * N * 4 + (16 << 20)),
        name="exp_ssm_step_" + form,
    )(*prefetch, *ins)
    return jnp.swapaxes(y_t, 1, 2), state


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--forms", default="plain,ops,lane_vpu,scalar_vpu,"
                    "scalar_mxu,lane_mxu")
    args = ap.parse_args()
    k = iter(jax.random.split(jax.random.PRNGKey(0), 8))
    draw = lambda *shape: jax.random.normal(next(k), shape, jnp.float32)
    x, bm, cm = draw(B, H, P), draw(B, N), draw(B, N)
    dt = jnp.exp(draw(B, H) - 4.0)
    a, d = -jnp.linspace(1.0, 16.0, H), jnp.ones((H,))
    live = jnp.ones((B,), bool).at[5].set(False)
    moved = 2 * int(live.sum()) * H * P * N * 4
    layers = args.layers
    want = None
    for form in args.forms.split(","):
        if form in ("plain", "ops"):
            # the kernel's read-out rounds to ``dtype``: the serving one
            one = lambda state, layer, form=form: ops.ssm_step(
                x, bm, cm, dt, a, d, state, layer, live,
                dtype=jnp.bfloat16 if form == "ops" else jnp.float32,
                use_kernel=form == "ops")
        else:
            def one(state, layer, form=form):
                y, state = call(form, x * dt[..., None], jnp.exp(dt * a), bm,
                                cm, state, live, layer)
                y = jnp.where(live[:, None, None], y + d[None, :, None] * x,
                              0.0)
                return y, state

        def every_layer(state, one=one):
            """All layers' steps in one program, as the block has them:
            a call's dispatch is then nothing beside its device time."""
            def body(i, carry):
                y, state = one(carry[1], i)
                return carry[0] + y, state
            return jax.lax.fori_loop(
                0, layers, body, (jnp.zeros((B, H, P), jnp.float32), state))

        step = jax.jit(every_layer, donate_argnums=0)
        state = jax.random.normal(
            jax.random.PRNGKey(7), (layers, B, H, P, N), jnp.float32) * 0.1
        try:
            y, state = step(state)
            jax.block_until_ready(y)
        except Exception as e:  # a form the compiler refuses
            print("FORM " + json.dumps({"form": form,
                                        "refused": str(e)[:300]}), flush=True)
            continue
        if want is None:
            want = y
        off = float(jnp.max(jnp.abs(y - want)) / jnp.max(jnp.abs(want)))
        t0 = time.perf_counter()
        for i in range(args.calls):
            y, state = step(state)
        jax.block_until_ready((y, state))
        ms = 1e3 * (time.perf_counter() - t0) / args.calls / layers
        print("FORM " + json.dumps({
            "form": form, "ms_a_layer": round(ms, 4),
            "GB/s": round(moved / ms / 1e6, 1), "off_first": off}),
            flush=True)


if __name__ == "__main__":
    main()
