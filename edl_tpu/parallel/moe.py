"""Mixture-of-Experts layer with expert parallelism over the ``ep`` axis.

Absent from the reference (SURVEY §2.5: "Expert parallelism: NO").
Top-k token routing with capacity-bounded dispatch expressed as dense
einsums — the XLA-native formulation: with the expert dimension of the
weights sharded over ``ep``, the dispatch/combine einsums lower to
all-to-all-style collectives over ICI, with no per-token scatter loops
(which would kill the MXU pipeline).
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


def init_moe_params(
    key: jax.Array, d_model: int, d_ff: int, n_experts: int, dtype=jnp.float32
) -> Dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "router": jax.random.normal(k1, (d_model, n_experts), dtype) * 0.02,
        "w_in": jax.random.normal(k2, (n_experts, d_model, d_ff), dtype)
        * np.sqrt(2.0 / d_model),
        "w_out": jax.random.normal(k3, (n_experts, d_ff, d_model), dtype)
        * np.sqrt(1.0 / d_ff),
    }


def moe_pspecs(plan) -> Dict:
    """Experts sharded over ep; expert-internal dims over tp/fsdp if
    present."""
    ep = "ep" if plan.axis_size("ep") > 1 else None
    tp = "tp" if plan.axis_size("tp") > 1 else None
    return {
        "router": P(None, None),
        "w_in": P(ep, None, tp),
        "w_out": P(ep, tp, None),
    }


def moe_ffn(
    params: Dict,
    x: jnp.ndarray,
    k: int = 2,
    capacity_factor: float = 1.25,
    int8_mxu: bool = False,
    int8_wgrad_bf16: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k routed expert FFN. x [B, T, D] → (y [B, T, D], aux_loss).

    aux_loss is the standard load-balance loss (mean_prob · mean_assign
    · n_experts), to be added to the training loss.

    ``int8_mxu`` runs the two expert batched matmuls on the MXU's
    double-rate int8 path (ops/int8_matmul.int8_batched_matmul) —
    the routing/dispatch einsums stay full precision (they are
    bandwidth-shaped one-hot contractions, not FLOPs).
    ``int8_wgrad_bf16`` keeps their wgrad on the bf16 path (the
    outlier-resolution escape hatch, same contract as
    ``LlamaConfig.int8_wgrad_bf16``).
    """
    b, t, d = x.shape
    n_tokens = b * t
    n_experts = params["router"].shape[-1]
    capacity = int(np.ceil(capacity_factor * k * n_tokens / n_experts))

    flat = x.reshape(n_tokens, d)
    logits = flat @ params["router"]  # [N, E]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    # top-k choice per token
    topk_prob, topk_idx = jax.lax.top_k(probs, k)  # [N, k]
    # position of each token within its expert's queue (capacity cutoff)
    onehot = jax.nn.one_hot(topk_idx, n_experts, dtype=jnp.float32)  # [N,k,E]
    # priority: expert slots filled in token order, k-th choices after
    flat_choice = onehot.reshape(n_tokens * k, n_experts)
    position = jnp.cumsum(flat_choice, axis=0) - flat_choice  # [N*k, E]
    within_cap = (position < capacity) * flat_choice
    slot = jnp.einsum("ne,ne->n", position, flat_choice).astype(jnp.int32)
    keep = jnp.einsum("ne,ne->n", within_cap, flat_choice) > 0

    # dispatch tensor [N, k, E, C]
    slot_onehot = jax.nn.one_hot(slot.reshape(n_tokens, k), capacity, dtype=x.dtype)
    dispatch = (
        onehot.astype(x.dtype)
        * keep.reshape(n_tokens, k, 1).astype(x.dtype)
    )[..., None] * slot_onehot[:, :, None, :]
    dispatch = dispatch.sum(axis=1)  # [N, E, C]

    # combine weights: renormalized top-k prob at the token's slot
    weights = (
        (topk_prob / jnp.maximum(topk_prob.sum(-1, keepdims=True), 1e-9))
        .astype(x.dtype)
        .reshape(n_tokens, k, 1, 1)
        * onehot.astype(x.dtype)[..., None]
        * slot_onehot[:, :, None, :]
        * keep.reshape(n_tokens, k, 1, 1).astype(x.dtype)
    ).sum(axis=1)  # [N, E, C]

    # expert compute: [E, C, D] batched matmuls (MXU-friendly)
    expert_in = jnp.einsum("nec,nd->ecd", dispatch, flat)
    if int8_mxu:
        from edl_tpu.ops.int8_matmul import int8_batched_matmul

        h = jax.nn.relu(
            int8_batched_matmul(
                expert_in, params["w_in"], wgrad_bf16=int8_wgrad_bf16
            )
        )
        expert_out = int8_batched_matmul(
            h, params["w_out"], wgrad_bf16=int8_wgrad_bf16
        )
    else:
        h = jax.nn.relu(
            jnp.einsum("ecd,edf->ecf", expert_in, params["w_in"])
        )
        expert_out = jnp.einsum("ecf,efd->ecd", h, params["w_out"])
    y = jnp.einsum("nec,ecd->nd", weights, expert_out)

    # load-balance auxiliary loss
    assign_frac = jnp.mean(
        jax.nn.one_hot(topk_idx[:, 0], n_experts, dtype=jnp.float32), axis=0
    )
    prob_frac = jnp.mean(probs, axis=0)
    aux = jnp.sum(assign_frac * prob_frac) * n_experts

    return y.reshape(b, t, d), aux


# -- dropless routed experts (serving; models/deepseek_v3.py) ----------------
#
# ``moe_ffn`` above bounds every expert's queue (capacity 1.25) and drops
# what overflows, which no published checkpoint's arithmetic does. The
# functions below never drop, and the weights of an expert nobody chose
# are not multiplied, and on a bandwidth-bound decode step not read.
# How many rows a step has, and the weights' kind, pick the algorithm
# (``moe_dropless``):
#
# - up to ``ops.expert_mlp.MAX_ROWS`` (128) rows (a decode step) under
#   ``kernel``: no sort. An expert has N * k / E rows (4.5 at 96 x 6 /
#   128), the layer is its weights passing through the chip once, and
#   XLA's grouped matmuls stream them at 56% of a v5e's HBM peak;
#   ``edl_expert_mlp`` puts all N rows through each hit expert while the
#   next one's weights arrive. Why 128: the wasted rows cost N
#   operations a byte against the chip's ridge of ~240, and 128 rows are
#   one pass of its 128 x 128 MXU per weight tile; past that the
#   arithmetic shows and sorting pays;
# - more rows (every prefill bucket): every (token, chosen expert) pair
#   is a row, rows are sorted by expert, and each expert multiplies its
#   own contiguous run of rows. A bucket of 1024-4096 tokens gives an
#   expert 48-192 rows, still under the ridge: under ``kernel``
#   ``edl_grouped_expert_mlp`` fetches each hit expert once and passes
#   its run through in row tiles;
# - an int8 record, or no kernels asked for, at any row count: the
#   sorted rows through ``jax.lax.ragged_dot`` (grouped matmuls whose
#   group sizes are data), the plain form the kernels are tested
#   against.


def route_sigmoid_topk(
    x: jnp.ndarray,
    router: jnp.ndarray,
    bias: jnp.ndarray,
    k: int,
    scale: float,
    normalize: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The ``deepseek_v3`` router with one group: x [N, d], router
    [d, E], bias [E] -> (idx [N, k] int32, w [N, k] float32).

    Scores are ``sigmoid(x @ router)`` in float32 over ALL ``E`` experts
    whatever share of them the caller holds. The choice is the top ``k``
    of ``score + bias`` (``e_score_correction_bias``: it steers load and
    is no part of the output); the weights are the UNCORRECTED scores of
    the chosen, divided by their sum when ``normalize``, times
    ``scale`` (``routed_scaling_factor``)."""
    with jax.named_scope("moe.router"):
        s = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        ))
        _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), k)
        w = jnp.take_along_axis(s, idx, axis=-1)
        if normalize:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return idx.astype(jnp.int32), w * scale


def _ragged(rows: jnp.ndarray, w, sizes: jnp.ndarray, expert_of: jnp.ndarray):
    """``rows [M, a] @ w[expert_of[m]]`` over runs of rows sorted by
    expert. ``w`` is ``[E, a, b]`` or its weight-only int8 record
    ``{"q8" [E, a, b], "s8" [E, b]}`` (``llama._matw``'s discipline:
    the column scale multiplies the product, in float32)."""
    if isinstance(w, dict):
        out = jax.lax.ragged_dot(rows, w["q8"].astype(rows.dtype), sizes)
        return (out.astype(jnp.float32) * w["s8"][expert_of]).astype(rows.dtype)
    return jax.lax.ragged_dot(rows, w.astype(rows.dtype), sizes)


def moe_dropless(
    x: jnp.ndarray,
    idx: jnp.ndarray,
    w: jnp.ndarray,
    w1,
    w3,
    w2,
    first: int = 0,
    kernel: bool = False,
) -> jnp.ndarray:
    """SwiGLU experts over routed rows, no token dropped: x [N, d], idx
    / w [N, k] from :func:`route_sigmoid_topk`; w1 / w3 [E_held, d, f]
    and w2 [E_held, f, d] are the experts ``first .. first + E_held`` of
    those the router scored (default: all of them). Returns [N, d], the
    weighted sum of the HELD experts' outputs for each token: the shares
    of disjoint ranges add up to the whole layer's routed term.

    ``kernel`` (the model's ``use_flash``) lets a step over plain
    weight arrays run ``edl_expert_mlp`` (at most
    ``ops.expert_mlp.MAX_ROWS`` rows, unsorted) or
    ``edl_grouped_expert_mlp`` (more rows, in the three grouped
    matmuls' place), a share of the experts too; the row count and the
    weights' kind decide, nothing else. Past ``MAX_ROWS``, and without
    kernels, the form is grouped: rows are the N * k (token, choice)
    pairs, sorted by expert (stable: a token's rows keep their order);
    a pair whose expert is not held sorts behind every group, belongs
    to none, and is given weight 0."""
    n = idx.shape[0]
    interpret = False
    kernel = kernel and not isinstance(w1, dict)
    with jax.named_scope("moe.experts"):
        if kernel:
            from edl_tpu.ops import expert_mlp as _em
            from edl_tpu.ops.flash_attention import _INTERPRET

            interpret = _INTERPRET.get()
            if n <= _em.MAX_ROWS:
                return _em.expert_mlp(
                    x, idx, w, w1, w3, w2, first=first, interpret=interpret)
        return _sorted_experts(x, idx, w, w1, w3, w2, first=first,
                               kernel=kernel, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("first", "kernel", "interpret"))
def _sorted_experts(x, idx, w, w1, w3, w2, *, first, kernel, interpret):
    """``moe_dropless``'s grouped form. Its own ``jit``: a model's
    layers are traced one by one, and the layers after the first take
    this jaxpr as traced (a prefill program's seven expert layers cost
    its set-up one trace, not seven)."""
    n, k = idx.shape
    held = (w1["q8"] if isinstance(w1, dict) else w1).shape[0]
    local = idx.reshape(-1) - first
    mine = (local >= 0) & (local < held)
    key = jnp.where(mine, local, held)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(
        key[:, None] == jnp.arange(held, dtype=key.dtype)[None, :],
        axis=0, dtype=jnp.int32,
    )
    rows = x[order // k]
    if kernel:
        from edl_tpu.ops.expert_mlp import grouped_expert_mlp

        out = grouped_expert_mlp(rows, sizes, w1, w3, w2, interpret=interpret)
    else:
        expert_of = jnp.minimum(key[order], held - 1)
        h = jax.nn.silu(_ragged(rows, w1, sizes, expert_of)) * _ragged(
            rows, w3, sizes, expert_of
        )
        out = _ragged(h, w2, sizes, expert_of)
    # back to (token, choice) order: a gather, where a scatter-add
    # over tokens would serialise on the TPU
    out = out[jnp.argsort(order)].reshape(n, k, -1)
    wk = jnp.where(mine.reshape(n, k), w, 0.0)
    # a row of no group holds whatever the grouped matmul left there
    out = jnp.where(mine.reshape(n, k, 1), out.astype(jnp.float32), 0.0)
    return jnp.sum(out * wk[..., None], axis=1).astype(x.dtype)


def expert_load(idx: jnp.ndarray, n_experts: int, rows=None):
    """What a step's routing did to the experts, as two float32
    scalars: the share of the ``n_experts`` with at least one row, and
    the busiest expert's rows over the mean. ``rows`` [N] bool leaves
    tokens out (a frozen serving slot computes and is nobody's)."""
    hit = idx[..., None] == jnp.arange(n_experts, dtype=idx.dtype)
    if rows is not None:
        hit = hit & rows[:, None, None]
    load = jnp.sum(hit, axis=(0, 1), dtype=jnp.float32)
    mean = jnp.maximum(jnp.mean(load), 1e-9)
    return jnp.mean((load > 0).astype(jnp.float32)), jnp.max(load) / mean
