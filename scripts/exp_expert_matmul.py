"""The routed experts of one ``kanana2.decode-wide`` layer, alone on the
chip: how fast each candidate streams the HIT experts' weights at a
decode step's few rows (PERF.md section 6, PR 36) and at a prefill
bucket's many (PR 38).

    chiprun -- python scripts/exp_expert_matmul.py
    chiprun -- python scripts/exp_expert_matmul.py --rows 512 1024 2048 4096 --iters 30

128 experts of 2048 x 768 (bf16, three matrices each), N tokens that
each choose 6, the routing drawn with a popularity skew so that at N =
96 about 0.95 of the experts are hit and the busiest has ~3 x the mean
(what the cell's ``experts_hit_share`` / ``expert_load_max_over_mean``
read). Candidates, one JSON line each at every N:

- ``ragged_dot_x3``: ``jax.lax.ragged_dot`` three times over rows
  already sorted (the grouped matmuls alone);
- ``moe_dropless_ragged``: the whole layer as ``parallel.moe`` ran it
  before PR 36 (sort, three grouped matmuls, un-sort, combine);
- ``gmm_x3[tm,tk,tn]``: ``pallas.ops.tpu.megablox.gmm`` in their place;
- ``moe_dropless_gmm[tm,tk,tn]``: the whole layer with ``gmm`` where
  ``moe_dropless`` has ``ragged_dot`` (sort, three ``gmm``, un-sort,
  combine): what swapping the library call in would give;
- ``edl_expert_mlp``: ``ops.expert_mlp.expert_mlp`` (no sort, one
  expert a grid step through the BlockSpec pipeline), the whole layer;
- ``edl_expert_mlp_ring[<buffers>]``: the same arithmetic with the
  experts fetched by hand into a ring of ``buffers`` (this file's
  ``expert_mlp_ring``: what deeper buffering would buy);
- past ``MAX_ROWS`` rows, where ``edl_expert_mlp`` does not go:
  ``grouped_x1[tile]``: ``ops.expert_mlp.grouped_expert_mlp`` in the
  three grouped matmuls' place (each hit expert fetched by hand under
  ALL the visits of the one before it), ``grouped_pipeline_x1[tile]``:
  the same visits with the experts as BlockSpec operands (this file's
  ``grouped_pipeline``: an expert arrives under the LAST visit of the
  one before it alone), and ``moe_dropless_grouped``: the whole layer
  as ``parallel.moe`` runs it under ``kernel`` since PR 38.

``ms`` is the host clock over ``--iters`` back-to-back calls ended by
one ``block_until_ready``; ``gbps`` the hit experts' weights over it;
``tflops`` the N * 6 rows' 6 * d * f operations over it;
``err`` the largest difference from the float32 table, over the
largest entry of the table (the grouped matmuls alone: from
``ragged_dot_x3``'s rows, over their largest entry).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from edl_tpu.ops import expert_mlp as em
from edl_tpu.parallel import moe

E, D, F, K = 128, 2048, 768, 6
SKEW = 0.45


def draw_routing(seed: int, n: int):
    """(idx [n, K], w [n, K]) with a popularity skew over the experts."""
    r = np.random.default_rng(seed)
    score = r.normal(0, SKEW, E) + r.gumbel(size=(n, E))
    idx = np.argsort(-score, axis=1)[:, :K].astype(np.int32)
    w = r.uniform(0.2, 1.0, (n, K)).astype(np.float32)
    w = w / w.sum(-1, keepdims=True) * 2.448
    load = np.bincount(idx.ravel(), minlength=E)
    return idx, w, float((load > 0).mean()), float(load.max() / load.mean())


def routing_like_the_cell(n: int):
    """The first seed whose draw at 96 rows reads as the cell's
    counters do; the same seed at the other row counts."""
    for seed in range(1000):
        _, _, hit, skew = draw_routing(seed, 96)
        if 0.945 <= hit <= 0.96 and 2.7 <= skew <= 3.3:
            return draw_routing(seed, n)
    raise SystemExit("no seed draws the cell's routing")


def sorted_rows(x, idx):
    key = idx.reshape(-1)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(E)[None, :], axis=0,
                    dtype=jnp.int32)
    return x[order // K], sizes


@jax.jit
def ragged_x3(rows, sizes, w1, w3, w2):
    h = jax.nn.silu(jax.lax.ragged_dot(rows, w1, sizes)) * jax.lax.ragged_dot(
        rows, w3, sizes)
    return jax.lax.ragged_dot(h, w2, sizes)


@functools.partial(jax.jit, static_argnames=("tiling",))
def gmm_x3(rows, sizes, w1, w3, w2, tiling):
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    tm, tk, tn = tiling
    g = functools.partial(gmm, group_sizes=sizes,
                          preferred_element_type=jnp.bfloat16)
    h = jax.nn.silu(g(rows, w1, tiling=(tm, tk, tn))) * g(
        rows, w3, tiling=(tm, tk, tn))
    return g(h, w2, tiling=(tm, tn, tk))


@functools.partial(jax.jit, static_argnames=("tiling",))
def moe_dropless_gmm(x, idx, w, w1, w3, w2, tiling):
    """``parallel.moe.moe_dropless``'s grouped form over all the
    experts, ``gmm`` for ``ragged_dot`` (rows padded to whole row
    tiles: the padding belongs to no group)."""
    n, k = idx.shape
    key = idx.reshape(-1)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(E)[None, :], axis=0,
                    dtype=jnp.int32)
    rows = x[order // k]
    rows = jnp.pad(rows, ((0, -rows.shape[0] % tiling[0]), (0, 0)))
    out = gmm_x3(rows, sizes, w1, w3, w2, tiling)[:n * k]
    out = out[jnp.argsort(order)].reshape(n, k, -1).astype(jnp.float32)
    return jnp.sum(out * w[..., None], axis=1).astype(x.dtype)


def _ring_kernel(hit_ref, n_ref, x_ref, c_ref, w1_hbm, w3_hbm, w2_hbm, o_ref,
                 b1, b3, b2, sem, acc_ref, *, buffers: int):
    n = n_ref[0]

    def copies(t, slot):
        e = hit_ref[t]
        return [pltpu.make_async_copy(src.at[e], dst.at[slot], sem.at[m, slot])
                for m, (src, dst) in enumerate(
                    ((w1_hbm, b1), (w3_hbm, b3), (w2_hbm, b2)))]

    for t in range(buffers - 1):
        @pl.when(t < n)
        def _start(t=t):
            for c in copies(t, t):
                c.start()

    x = x_ref[...]
    cw = c_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, cw.shape, 1)

    acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(t, _):
        slot = t % buffers
        ahead = t + buffers - 1

        @pl.when(ahead < n)
        def _start():
            for c in copies(ahead, ahead % buffers):
                c.start()

        for c in copies(t, slot):
            c.wait()
        a = jnp.dot(x, b1[slot], preferred_element_type=jnp.float32)
        b = jnp.dot(x, b3[slot], preferred_element_type=jnp.float32)
        col = jnp.sum(jnp.where(lane == hit_ref[t], cw, 0.0), axis=1,
                      keepdims=True)
        h = jnp.where(col != 0.0, jax.nn.silu(a) * b * col, 0.0).astype(
            x.dtype)
        acc_ref[...] += jnp.dot(h, b2[slot],
                                preferred_element_type=jnp.float32)

    jax.lax.fori_loop(0, n, body, None)
    o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("buffers",))
def expert_mlp_ring(x, idx, w, w1, w3, w2, buffers: int = 3):
    n, d = x.shape
    held, _, f = w1.shape
    c, hit, n_hit = em.combine_weights(idx, w, held, 0)
    return pl.pallas_call(
        functools.partial(_ring_kernel, buffers=buffers),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[
                pl.BlockSpec((n, d), lambda i, *_: (0, 0)),
                pl.BlockSpec((n, held), lambda i, *_: (0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((n, d), lambda i, *_: (0, 0)),
            scratch_shapes=[
                pltpu.VMEM((buffers, d, f), w1.dtype),
                pltpu.VMEM((buffers, d, f), w1.dtype),
                pltpu.VMEM((buffers, f, d), w1.dtype),
                pltpu.SemaphoreType.DMA((3, buffers)),
                pltpu.VMEM((n, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=buffers * 3 * d * f * 2 + (16 << 20),
        ),
        name="edl_expert_mlp_ring",
    )(hit, n_hit, x, c, w1, w3, w2)


def _pipeline_kernel(g_ref, t_ref, fresh_ref, slot_ref, ahead_ref, edge_ref,
                     n_ref, x_ref, w1_ref, w3_ref, w2_ref, o_ref):
    v = pl.program_id(0)

    @pl.when(v < n_ref[0])
    def _visit():
        g = g_ref[v]
        em.visit_tile(x_ref, w1_ref[...], w3_ref[...], w2_ref[...], o_ref,
                      t_ref[v], edge_ref[g], edge_ref[g + 1])


@functools.partial(jax.jit, static_argnames=("tile",))
def grouped_pipeline(rows, sizes, w1, w3, w2, tile: int = em.GROUP_TILE):
    """``em.grouped_expert_mlp``'s visits with the experts as BlockSpec
    operands: the pipeline asks for a visit's blocks one visit ahead."""
    m, d = rows.shape
    held, _, f = w1.shape
    n_tiles = m // tile
    visits = em.group_visits(sizes, n_tiles, tile)
    row_tile = lambda v, g_ref, t_ref, *_: (t_ref[v], 0)
    expert = lambda v, g_ref, *_: (g_ref[v], 0, 0)
    return pl.pallas_call(
        _pipeline_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(visits),
            grid=(n_tiles + held - 1,),
            in_specs=[
                pl.BlockSpec((tile, d), row_tile),
                pl.BlockSpec((None, d, f), expert),
                pl.BlockSpec((None, d, f), expert),
                pl.BlockSpec((None, f, d), expert),
            ],
            out_specs=pl.BlockSpec((tile, d), row_tile),
        ),
        out_shape=jax.ShapeDtypeStruct(rows.shape, rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=3 * 3 * d * f * 2 + 8 * tile * d * 4 + (8 << 20),
        ),
        name="edl_grouped_pipeline",
    )(*visits, rows, w1, w3, w2)


def table(x, idx, w, w1, w3, w2):
    """The float32 layer, every expert over every row (on the host's
    terms: ``highest``)."""
    with jax.default_matmul_precision("highest"):
        x = x.astype(jnp.float32)
        c = jnp.zeros((x.shape[0], E)).at[
            jnp.arange(x.shape[0])[:, None], idx].add(w)
        out = jnp.zeros_like(x)
        for e in range(E):
            h = jax.nn.silu(x @ w1[e].astype(jnp.float32)) * (
                x @ w3[e].astype(jnp.float32))
            out += c[:, e:e + 1] * (h @ w2[e].astype(jnp.float32))
        return out


def timed(fn, iters: int) -> float:
    for _ in range(3):
        out = fn()
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, nargs="+", default=[16, 48, 96, 128])
    ap.add_argument("--iters", type=int, default=100)
    ap.add_argument("--out", default="chiprun_out/exp_expert_matmul.jsonl")
    args = ap.parse_args()
    dev = jax.devices()[0]
    key = jax.random.split(jax.random.PRNGKey(36), 4)
    draw = jax.jit(lambda k, shape, std: (
        jax.random.normal(k, shape, jnp.float32) * std).astype(jnp.bfloat16),
        static_argnums=(1, 2))
    w1 = draw(key[0], (E, D, F), D ** -0.5)
    w3 = draw(key[1], (E, D, F), D ** -0.5)
    w2 = draw(key[2], (E, F, D), 0.25 * F ** -0.5)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    lines = []

    def report(**kw):
        line = json.dumps({"device": dev.device_kind, **kw})
        print(line, flush=True)
        lines.append(line)

    for n in args.rows:
        idx_h, w_h, hit, skew = routing_like_the_cell(n)
        idx, w = jnp.asarray(idx_h), jnp.asarray(w_h)
        x = jax.random.normal(key[3], (n, D), jnp.float32).astype(jnp.bfloat16)
        rows, sizes = sorted_rows(x, idx)
        pad = -rows.shape[0] % 128  # gmm wants whole row tiles
        rows_p = jnp.pad(rows, ((0, pad), (0, 0)))
        need = hit * E * 3 * D * F * 2
        want = table(x, idx, w, w1, w3, w2)
        top = float(jnp.max(jnp.abs(want)))
        base = ragged_x3(rows, sizes, w1, w3, w2).astype(jnp.float32)
        base_top = float(jnp.max(jnp.abs(base)))
        forms = {
            "ragged_dot_x3": (lambda: ragged_x3(rows, sizes, w1, w3, w2), None),
            "moe_dropless_ragged": (functools.partial(
                jax.jit(moe.moe_dropless), x, idx, w, w1, w3, w2), True),
        }
        many = n > em.MAX_ROWS
        for tiling in ((128, 2048, 768), (64, 2048, 768), (128, 1024, 768),
                       (128, 512, 768), (256, 2048, 768), (512, 1024, 768)):
            if rows_p.shape[0] % tiling[0] == 0 and (
                    many or tiling[0] <= 128):
                forms[f"gmm_x3{list(tiling)}"] = (functools.partial(
                    gmm_x3, rows_p, sizes, w1, w3, w2, tiling), None)
        for tiling in ((128, 2048, 768), (64, 2048, 768)):
            forms[f"moe_dropless_gmm{list(tiling)}"] = (functools.partial(
                moe_dropless_gmm, x, idx, w, w1, w3, w2, tiling), True)
        if many:
            for tile in (64, 128, 256):
                if rows_p.shape[0] % tile == 0:
                    forms[f"grouped_x1[{tile}]"] = (functools.partial(
                        em.grouped_expert_mlp, rows_p, sizes, w1, w3, w2,
                        tile=tile), None)
                    forms[f"grouped_pipeline_x1[{tile}]"] = (
                        functools.partial(grouped_pipeline, rows_p, sizes,
                                          w1, w3, w2, tile=tile), None)
            forms["moe_dropless_grouped"] = (functools.partial(
                jax.jit(functools.partial(moe.moe_dropless, kernel=True)),
                x, idx, w, w1, w3, w2), True)
        else:
            forms["edl_expert_mlp"] = (functools.partial(
                em.expert_mlp, x, idx, w, w1, w3, w2), True)
            if n % 16 == 0:
                for buffers in (2, 3, 4):
                    forms[f"edl_expert_mlp_ring[{buffers}]"] = (
                        functools.partial(expert_mlp_ring, x, idx, w, w1, w3,
                                          w2, buffers=buffers), True)
        for name, (fn, whole) in forms.items():
            try:
                ms = timed(fn, args.iters)
                # a whole layer against the float32 table; grouped
                # matmuls alone against ``ragged_dot``'s, run by run
                ref, scale = (want, top) if whole else (base, base_top)
                err = float(jnp.max(jnp.abs(fn().astype(jnp.float32)[
                    :ref.shape[0]] - ref))) / scale
                report(form=name, rows=n, hit_share=hit, max_over_mean=skew,
                       ms=ms, gbps=need / ms / 1e6,
                       tflops=n * K * 6 * D * F / ms / 1e9, err=err)
            except Exception as e:  # a form the compiler refuses is a finding
                report(form=name, rows=n, error=str(e)[:300])
    with open(args.out, "w") as f:
        f.write("\n".join(lines) + "\n")


if __name__ == "__main__":
    main()
