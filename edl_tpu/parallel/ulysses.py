"""Ulysses sequence parallelism — all-to-all head/sequence exchange.

The second long-context strategy (SURVEY §5 lists both: "ring-attention
over ICI neighbor exchange; Ulysses-style all-to-all within a slice";
the reference has neither). Inputs arrive sequence-sharded over the
``sp`` axis; an all-to-all re-shards them over attention heads so every
device computes *full-sequence* attention for ``H / sp`` heads, and a
second all-to-all restores sequence sharding. Two collectives per
attention call (vs one ppermute per ring step) but each device sees the
whole sequence, so any attention kernel — including the pallas flash
kernel — drops in unchanged.

Trade-off vs ring attention: Ulysses is bandwidth-cheaper for moderate
sequence lengths inside one slice (all-to-all rides full ICI bisection),
while ring attention overlaps compute with neighbor exchange and scales
past the head-count limit (sp must divide n_heads here).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from edl_tpu.parallel.ring_attention import reference_attention


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = True,
    use_flash: bool = False,
) -> jnp.ndarray:
    """Attention over sequence-sharded [B, S, H, d] q/k/v.

    S is the *global* sequence length (each device holds S/sp); H must
    be divisible by the ``axis`` size. Returns output with the same
    sequence sharding as q. ``use_flash`` (the model's
    ``LlamaConfig.use_flash``) runs the local full-sequence attention
    through the pallas kernel and raises on a length it does not
    support; off, it is the f32 dense oracle. The caller chooses — no
    device probe does.
    """
    n = mesh.shape[axis]
    if q.shape[2] % n:
        raise ValueError(f"n_heads={q.shape[2]} not divisible by {axis}={n}")
    # GQA: K/V ride the all-to-all at their (smaller) kv-head width and
    # expand only locally, after the exchange — when kv_heads divides
    # the axis; otherwise expand up front (correct, more bytes)
    kv_heads = k.shape[2]
    if kv_heads % n and q.shape[2] != kv_heads:
        rep = q.shape[2] // kv_heads
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    # batch dim keeps whatever data-axis sharding it has (as ring_attention)
    other = tuple(a for a in mesh.axis_names if a != axis)
    spec = P(tuple(a for a in other if a in ("dp", "fsdp")) or None, axis, None, None)

    def local(q, k, v):
        out_dtype = q.dtype

        # [B, S/n, H, d] --all-to-all--> [B, S, H/n, d]  (activation-dtype
        # bytes on the wire; the f32 upcast happens after the exchange)
        def scatter_heads(x):
            return jax.lax.all_to_all(
                x, axis, split_axis=2, concat_axis=1, tiled=True
            )

        q, k, v = scatter_heads(q), scatter_heads(k), scatter_heads(v)
        if use_flash:
            from edl_tpu.ops.flash_attention import attention_auto

            # full-sequence attention on the local head shard via the
            # blockwise pallas kernel (GQA-native, O(S) memory) — the
            # whole point of Ulysses: any single-device kernel drops in
            o = attention_auto(q, k, v, causal=causal)
        else:
            # dense oracle: f32 softmax (the bf16-drift guard
            # ring_attention documents), O(S^2) scores
            if k.shape[2] != q.shape[2]:  # expand GQA groups
                k = jnp.repeat(k, q.shape[2] // k.shape[2], axis=2)
                v = jnp.repeat(v, q.shape[2] // v.shape[2], axis=2)
            o = reference_attention(
                q.astype(jnp.float32),
                k.astype(jnp.float32),
                v.astype(jnp.float32),
                causal=causal,
            ).astype(out_dtype)
        # [B, S, H/n, d] --all-to-all--> [B, S/n, H, d]
        return jax.lax.all_to_all(o, axis, split_axis=1, concat_axis=2, tiled=True)

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)
