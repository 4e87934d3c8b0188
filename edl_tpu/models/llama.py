"""Llama-3-family decoder — the flagship model (BASELINE config:
"Llama-3-8B elastic FSDP across growing TPU slice").

No reference analog (the reference's models are 2018-era CTR/word2vec,
SURVEY §5); built TPU-first:

- layers are scan-stacked ([L, ...] params + ``lax.scan``) so compile
  time is O(1) in depth and pipeline stages can slice the leading axis;
- explicit 2D TP×FSDP partition specs per parameter (attention heads /
  ffn width over tp, the other big dim over fsdp) — the standard
  ICI-friendly layout;
- RoPE, GQA (grouped KV heads), RMSNorm, SwiGLU — Llama-3 architecture;
- bfloat16 activations with float32 params/optimizer (MXU-native).
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from edl_tpu.obs import compilewatch
from edl_tpu.obs import costmodel as _costmodel
from edl_tpu.parallel import remat
from edl_tpu.parallel.mesh import MeshPlan

compilewatch.install()  # compile telemetry for every program built here


@dataclass(frozen=True)
class LlamaConfig:
    vocab: int = 128256
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 14336
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16  # activation dtype (params stay f32)
    # pallas flash attention; a length flash_supported() rejects raises
    # (never a silent dense substitute)
    use_flash: bool = False
    # rematerialize each layer in the backward pass: only the [B,T,d]
    # layer inputs are saved across the scan, trading ~33% more forward
    # FLOPs for O(L·B·T·d) instead of O(L·B·T·(d+ff+heads)) activation
    # HBM — what lets non-toy configs train on one chip
    remat: bool = False
    # what the remat keeps beside the layer inputs: the FLOPs/HBM dial.
    # Kept values are names in the traced layer (``KEEP_ORDER``, which
    # gives each one's bytes a token a layer and the matmul it spares):
    #   "fit":  what of ``KEEP_ORDER`` fits the room the trainer
    #           offers for this step on this mesh, taken in its order
    #           (``parallel/remat.py``; ``make_train_step`` sizes the
    #           offer from the device's limit and holds the compiler's
    #           ``memory_analysis()`` against it). "full" where nothing
    #           fits, nothing is offered (a caller that is no trainer)
    #           or nothing is named.
    #   "full": recompute everything (least memory; the backward runs
    #           the flash forward kernel and five of the seven
    #           projections a second time, a third of the forward's
    #           matmul FLOPs not counting wo and w2, whose second run is
    #           dead and dropped)
    #   "attn": keep the flash kernel's output and logsumexp; the
    #           backward reuses them instead of running the (VPU-bound)
    #           forward kernel again. Raises without the kernel.
    #   "mlp":  keep the two [B,T,d_ff] products m @ w1 and m @ w3
    #           (w1's BEFORE the silu: see ``_mlp``): skips both
    #           recomputed MLP matmuls
    #   "dots": keep every weight-matmul output (near-zero recompute,
    #           most HBM: jax dots_with_no_batch_dims_saveable)
    remat_policy: str = "fit"
    # sequence/context parallelism implementation when the mesh plan has
    # an sp axis: "ring" (ppermute neighbor exchange, scales past the
    # head count) or "ulysses" (two all-to-alls, full-sequence attention
    # on H/sp heads). Ignored when sp == 1.
    sp_impl: str = "ring"
    # GPipe microbatch count when the plan has a pp axis (0 = one
    # microbatch per stage). Bubble fraction (pp-1)/(n_micro+pp-1).
    pp_microbatches: int = 0
    # run the seven per-layer projection matmuls on the MXU's
    # double-rate int8 path (ops/int8_matmul.py: dynamic absmax
    # quantization of both operands in flight, STE gradients, fwd +
    # dgrad + wgrad all int8). Master weights/optimizer/attention/
    # lm_head stay full precision; training-only (never rides
    # to_meta — exports are dense, serving unaffected).
    int8_mxu: bool = False
    # with int8_mxu: keep wgrad (a^T @ g) on the bf16 MXU path while
    # fwd/dgrad stay int8 (ADVICE r6) — gradients are heavy-tailed and
    # wgrad contracts the batch·seq axis, so one outlier crushes a
    # whole slice's absmax resolution; this caps long-run update noise
    # at bf16 rounding for ~1/6 of the 2x rate win. Training-only,
    # ignored without int8_mxu, never rides to_meta.
    int8_wgrad_bf16: bool = False

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def to_meta(self) -> Dict:
        """JSON-safe architecture record (rides export manifests so a
        serving consumer can rebuild the config; runtime/export.py)."""
        return {
            "family": "llama",
            "vocab": self.vocab,
            "d_model": self.d_model,
            "n_layers": self.n_layers,
            "n_heads": self.n_heads,
            "n_kv_heads": self.n_kv_heads,
            "d_ff": self.d_ff,
            "rope_theta": self.rope_theta,
            "norm_eps": self.norm_eps,
            "dtype": jnp.dtype(self.dtype).name,
            "use_flash": self.use_flash,
        }

    @classmethod
    def from_meta(cls, meta: Dict) -> "LlamaConfig":
        if meta.get("family") != "llama":
            raise ValueError(f"not a llama export: family={meta.get('family')!r}")
        return cls(
            vocab=int(meta["vocab"]),
            d_model=int(meta["d_model"]),
            n_layers=int(meta["n_layers"]),
            n_heads=int(meta["n_heads"]),
            n_kv_heads=int(meta["n_kv_heads"]),
            d_ff=int(meta["d_ff"]),
            rope_theta=float(meta["rope_theta"]),
            norm_eps=float(meta["norm_eps"]),
            dtype=jnp.dtype(meta["dtype"]),
            use_flash=bool(meta.get("use_flash", False)),
        )

    # -- what ``serving/engine.py`` asks a config it serves from the
    # contiguous cache (its docstring gives the contract); here the
    # answers are the programs the engine always ran

    def serve_cache_spec(self, slots: int, max_len: int):
        """Two arrays, keys and values: [L, slots, max_len, KV, hd]."""
        shape = (self.n_layers, slots, max_len, self.n_kv_heads, self.head_dim)
        return ((shape, self.dtype), (shape, self.dtype))

    def serve_prefill(self, params: Dict, tokens, last):
        logits, ks, vs = prefill_padded(params, tokens, last, self)
        return logits, (ks, vs)

    def serve_decode_block(self, params, tok, pos, active, rem, eosv, cache,
                           **kw):
        toks, tok, pos, active, rem, kc, vc = decode_horizon_slots(
            params, tok, pos, active, rem, eosv, *cache, self, **kw)
        return toks, tok, pos, active, rem, (kc, vc), {}

    # what the memory ledger files each array of the cache under
    serve_cache_kinds = ("kv", "kv")

    def serve_cache_read(self, held, max_len: int, block: int):
        return {"kv_read_share": positional_read_share(
            held, max_len, block)}

    def serve_attn_block(self, max_len: int) -> int:
        """Positions of one S-block ``edl_decode_attn`` fetches; the
        dense read is one block of ``max_len`` a slot."""
        if not self.use_flash:
            return max_len
        from edl_tpu.ops.decode_attention import block_positions

        return block_positions(
            self.n_kv_heads, self.head_dim, jnp.dtype(self.dtype).itemsize,
            max_len)

    @classmethod
    def llama3_8b(cls) -> "LlamaConfig":
        return cls()

    @classmethod
    def flagship(cls) -> "LlamaConfig":
        """THE flagship training definition (BASELINE config #5 at the
        scale one v5e chip trains): d2048/L16/ff6144/v32768, bf16
        activations, pallas flash attention, per-layer remat. The ONE
        factory ``chip_smoke.py``, ``bench.py`` and every
        ``scripts/exp_*`` measurement share — a drifted inline copy
        silently invalidates "same config as the recorded numbers".
        Serving uses it with ``remat=False`` (what an export's
        ``to_meta`` round trip yields)."""
        return cls(
            vocab=32768, d_model=2048, n_layers=16, n_heads=16,
            n_kv_heads=8, d_ff=6144, dtype=jnp.bfloat16, use_flash=True,
            remat=True,
        )

    @classmethod
    def tiny(cls, vocab: int = 256) -> "LlamaConfig":
        """Test/dry-run size: same architecture, toy dims."""
        return cls(
            vocab=vocab,
            d_model=64,
            n_layers=2,
            n_heads=4,
            n_kv_heads=2,
            d_ff=128,
            dtype=jnp.float32,
        )


def positional_read_share(held, max_len: int, blk: int) -> float:
    """S-blocks of a positional cache that a decode block fetches, over
    the blocks of the padded cache, from the host's slot table
    (``held``: the tokens each slot holds, None for an idle one): a
    slot is read up to the block that holds its last token, an idle
    slot (fed ``pos = 0``) costs one block. 1.0 for the dense program,
    whose one block a slot is ``max_len``."""
    fetched = sum(1 if n is None else -(-n // blk) for n in held)
    return fetched / (len(held) * (max_len // blk))


def state_live_share(held) -> float:
    """Slots whose per-slot state a decode block moves, over all slots,
    from the same slot table: a live slot's state is read and written
    whole whatever it holds, an idle one's not at all
    (``models/retention.py``, ``models/ssm_hybrid.py``)."""
    return sum(n is not None for n in held) / len(held)


def init_params(key: jax.Array, cfg: LlamaConfig) -> Dict:
    """Scan-stacked parameter tree: every per-layer weight carries a
    leading [n_layers] axis."""
    k = jax.random.split(key, 10)
    d, h, kv, hd, ff, L = (
        cfg.d_model,
        cfg.n_heads,
        cfg.n_kv_heads,
        cfg.head_dim,
        cfg.d_ff,
        cfg.n_layers,
    )

    def norm_init(kk, *shape, scale):
        return jax.random.normal(kk, shape, jnp.float32) * scale

    return {
        "embed": norm_init(k[0], cfg.vocab, d, scale=0.02),
        "layers": {
            "ln1": jnp.ones((L, d), jnp.float32),
            "wq": norm_init(k[1], L, d, h * hd, scale=d**-0.5),
            "wk": norm_init(k[2], L, d, kv * hd, scale=d**-0.5),
            "wv": norm_init(k[3], L, d, kv * hd, scale=d**-0.5),
            "wo": norm_init(k[4], L, h * hd, d, scale=(h * hd) ** -0.5),
            "ln2": jnp.ones((L, d), jnp.float32),
            "w1": norm_init(k[5], L, d, ff, scale=d**-0.5),  # gate
            "w3": norm_init(k[6], L, d, ff, scale=d**-0.5),  # up
            "w2": norm_init(k[7], L, ff, d, scale=ff**-0.5),  # down
        },
        "ln_f": jnp.ones((d,), jnp.float32),
        "lm_head": norm_init(k[8], d, cfg.vocab, scale=d**-0.5),
    }


def param_pspecs(cfg: LlamaConfig, plan: MeshPlan) -> Dict:
    """2D TP×FSDP layout: tp on head/ffn width, fsdp on the other large
    dim; vocab-dim tp for embed/lm_head. Falls back gracefully when an
    axis is absent, and drops an axis from any dimension it does not
    divide (elastic worlds are not always powers of two — a 6-way fsdp
    mesh must still compile; the undivisible param is replicated on
    that axis instead, exactly what the generic rule in
    parallel/sharding.py does)."""
    tp = "tp" if plan.axis_size("tp") > 1 else None
    fs = "fsdp" if plan.axis_size("fsdp") > 1 else None
    # pipeline stages: the scan-stacked layer axis shards over pp, so
    # each stage's devices hold only their own layers at rest; the
    # pipeline shard_map gathers the fs/tp dims per step (ZeRO-style)
    pp = "pp" if plan.axis_size("pp") > 1 else None
    d, h, kv, hd, ff, L, V = (
        cfg.d_model,
        cfg.n_heads,
        cfg.n_kv_heads,
        cfg.head_dim,
        cfg.d_ff,
        cfg.n_layers,
        cfg.vocab,
    )

    from edl_tpu.parallel.sharding import fit_pspec

    def fit(shape, *axes):
        return fit_pspec(plan, shape, *axes)

    return {
        "embed": fit((V, d), tp, fs),
        "layers": {
            "ln1": fit((L, d), pp, None),
            "wq": fit((L, d, h * hd), pp, fs, tp),
            "wk": fit((L, d, kv * hd), pp, fs, tp),
            "wv": fit((L, d, kv * hd), pp, fs, tp),
            "wo": fit((L, h * hd, d), pp, tp, fs),
            "ln2": fit((L, d), pp, None),
            "w1": fit((L, d, ff), pp, fs, tp),
            "w3": fit((L, d, ff), pp, fs, tp),
            "w2": fit((L, ff, d), pp, tp, fs),
        },
        "ln_f": P(None),
        "lm_head": fit((d, V), fs, tp),
    }


def _rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale.astype(x.dtype)


def _rope(
    x: jnp.ndarray, theta: float, positions: Optional[jnp.ndarray] = None
) -> jnp.ndarray:
    """Rotary embedding over [B, T, H, hd]. ``positions`` [T] overrides
    the default 0..T-1 (the decode path rotates single tokens at their
    absolute position); a [B, T] positions array rotates each batch row
    at its OWN absolute positions (the continuous-batching slot decode,
    where concurrent requests sit at different depths)."""
    _, t, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    if positions is None:
        positions = jnp.arange(t, dtype=jnp.float32)
    angles = positions.astype(jnp.float32)[..., :, None] * freqs  # [..., T, hd/2]
    if angles.ndim == 2:
        angles = angles[None]  # shared positions broadcast over B
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    cfg: LlamaConfig,
    mesh=None,
    sp: int = 1,
) -> jnp.ndarray:
    """Causal GQA attention. q [B,T,H,hd]; k,v [B,T,KV,hd].

    With ``sp > 1`` the sequence dim arrives sharded over the mesh's
    ``sp`` axis and attention goes through ring attention (ppermute
    K/V rotation) or Ulysses (head/sequence all-to-all) per
    ``cfg.sp_impl`` — the long-context path (SURVEY §5)."""
    b, t, h, hd = q.shape
    if sp > 1:
        if mesh is None:
            raise ValueError("sp attention needs the mesh")
        # both sp kernels are GQA-aware: K/V travel the collectives at
        # kv-head width and expand inside the local block compute
        if cfg.sp_impl == "ring":
            from edl_tpu.parallel.ring_attention import ring_attention

            return ring_attention(q, k, v, mesh, axis="sp", causal=True)
        elif cfg.sp_impl == "ulysses":
            from edl_tpu.parallel.ulysses import ulysses_attention

            return ulysses_attention(
                q, k, v, mesh, axis="sp", causal=True,
                use_flash=cfg.use_flash,
            )
        raise ValueError(f"unknown sp_impl {cfg.sp_impl!r}")
    if cfg.use_flash:
        from edl_tpu.ops.flash_attention import attention_auto

        # the kernel or its ValueError (a length it does not support),
        # never a silent dense substitute. GQA-native: no K/V repeat.
        if mesh is not None and mesh.size > 1:
            return _flash_per_shard(q, k, v, mesh)
        return attention_auto(q, k, v, causal=True)
    groups = h // k.shape[2]
    k = jnp.repeat(k, groups, axis=2)
    v = jnp.repeat(v, groups, axis=2)
    scores = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(hd)
    mask = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(mask[None, None], scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


def _flash_per_shard(q, k, v, mesh) -> jnp.ndarray:
    """The flash kernel under a multi-device mesh. GSPMD cannot
    partition a Mosaic kernel (the TPU compiler refuses the program:
    "wrap the call in a shard_map"), and attention is independent per
    batch row and per kv-head group — so each device runs the kernel on
    its own shard: batch over the mesh's batch axes, heads over ``tp``
    when it divides the kv heads (contiguous head blocks keep each
    query head beside its GQA kv head). Any other axis sees replicas."""
    from jax import shard_map

    from edl_tpu.api.job import BATCH_AXES
    from edl_tpu.ops.flash_attention import attention_auto

    batch = tuple(a for a in mesh.axis_names if a in BATCH_AXES)
    tp = (
        "tp"
        if "tp" in mesh.axis_names and k.shape[2] % mesh.shape["tp"] == 0
        else None
    )
    spec = P(batch or None, None, tp, None)
    return shard_map(
        lambda q, k, v: attention_auto(q, k, v, causal=True),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)


_INT8_WEIGHTS = ("wq", "wk", "wv", "wo", "w1", "w2", "w3")


def _matw(
    a: jnp.ndarray, p, int8_mxu: bool = False, wgrad_bf16: bool = False
) -> jnp.ndarray:
    """``a @ W`` where ``W`` is a plain weight array or a weight-only
    int8 record ``{"q8", "s8"}`` from :func:`quantize_params_int8`.

    The int8 record form computes ``(a @ q8) * s8`` — mathematically
    equal to ``a @ (q8 * s8)`` because ``s8`` is constant along the
    contraction axis — so the dot's rhs is a bare ``convert(int8→dt)``
    that XLA fuses into the operand read: HBM streams the int8 bytes
    and no dequantized weight temp is ever materialized. That halved
    traffic is the whole point — small-batch decode is
    weight-bandwidth-bound (see bench.py ``_decode_step_bytes``).

    ``int8_mxu`` (training, ``LlamaConfig.int8_mxu``) instead runs the
    dense matmul on the MXU's double-rate int8 path with dynamic
    quantization of BOTH operands and STE gradients
    (``ops/int8_matmul.py``) — a throughput lever, not a memory one."""
    dt = a.dtype
    if isinstance(p, dict):
        # the column-scale multiply stays f32: casting s8 to bf16 first
        # would truncate each scale to an 8-bit mantissa, stacking up to
        # ~0.2% systematic error on top of the colmax/254 quantization
        # bound (ADVICE r5)
        return ((a @ p["q8"].astype(dt)).astype(jnp.float32) * p["s8"]).astype(dt)
    if int8_mxu:
        from edl_tpu.ops.int8_matmul import int8_matmul

        # no dtype cast: quantization reads the f32 MASTER weight (a
        # bf16 pre-cast would stack ~2^-9 truncation under the int8
        # noise and materialize a bf16 weight copy per step)
        return int8_matmul(a, p, wgrad_bf16=wgrad_bf16)
    return a @ p.astype(dt)


def quantize_params_int8(params: Dict) -> Dict:
    """Weight-only int8 for the serving/decode path (the quantization
    lever of VERDICT r4 #3): every matmul weight the decode step
    streams — the seven per-layer projection matrices and ``lm_head``
    — becomes ``{"q8": int8 [..., din, dout], "s8": f32 [..., dout]}``
    with symmetric per-output-column absmax scales, so the max error
    per element is ``colmax/254``. Master weights are untouched; the
    embedding stays dense (decode gathers B rows of it per step, not
    the whole table, so quantizing it buys no bandwidth) and norm
    scales are vectors. The returned tree feeds ``generate``/
    ``forward`` unchanged — ``_matw`` dispatches on the record."""

    from edl_tpu.ops.int8_matmul import absmax_quant

    def q(w):
        q8, s = absmax_quant(w, -2)  # absmax over din: per-out-column
        return {"q8": q8, "s8": s[..., 0, :]}

    out = dict(params)
    out["layers"] = {
        k: (q(v) if k in _INT8_WEIGHTS else v)
        for k, v in params["layers"].items()
    }
    out["lm_head"] = q(params["lm_head"])
    return out


def _qkv(
    cfg: LlamaConfig, a: jnp.ndarray, lp: Dict, positions=None,
    split_after: bool = False, qk_norm=None,
):
    """Projections + RoPE — shared by the training layer and the
    KV-cache decode so the model math cannot diverge between them.

    ``split_after`` holds the three ``[b, t, n]`` products behind an
    ``optimization_barrier`` before they are split into heads; the
    cached steps pass it (:func:`_qkv_cached`), training and prefill
    do not. ``qk_norm`` is the (query, key) pair of per-head RMSNorm
    weights of a model that norms its heads before RoPE
    (``models/retention.py``); this decoder has none. A config whose
    ``rope_theta`` is None has no positional embedding
    (``models/ssm_hybrid.py``) and ``positions`` is not read."""
    b, t, _ = a.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    i8, wb = cfg.int8_mxu, cfg.int8_wgrad_bf16

    def product(name, n):
        # bare, each product is split where it is made: the operations
        # in the order the training step and prefill have always traced
        # them, so their lowered text and compile-cache entries stand
        y = _matw(a, lp[name], i8, wb)
        if split_after:
            return y
        # named for the training layer's remat (``KEEP_ORDER``): RoPE
        # and the split are elementwise and redone from these
        return checkpoint_name(y, "attn_" + name[1]).reshape(b, t, n, hd)

    q, k, v = product("wq", h), product("wk", kv), product("wv", kv)
    if split_after:
        q, k, v = jax.lax.optimization_barrier((q, k, v))
        q = q.reshape(b, t, h, hd)
        k = k.reshape(b, t, kv, hd)
        v = v.reshape(b, t, kv, hd)
    if qk_norm is not None:
        q = _rmsnorm(q, qk_norm[0], cfg.norm_eps)
        k = _rmsnorm(k, qk_norm[1], cfg.norm_eps)
    if cfg.rope_theta is None:
        return q, k, v
    q = _rope(q, cfg.rope_theta, positions)
    k = _rope(k, cfg.rope_theta, positions)
    return q, k, v


def _qkv_cached(cfg: LlamaConfig, a: jnp.ndarray, lp: Dict, positions,
                qk_norm=None):
    """:func:`_qkv` for the steps that run against a KV cache: a few
    rows a weight, the layers unrolled over the stacked tree.

    Without the barrier XLA folds the head split into the dot, the
    weight becomes a ``[d_in, h, hd]`` operand, and layout assignment
    wants it ``d_in``-minor, which the stored ``[L, d_in, d_out]`` leaf
    is not: every layer's ``wq`` / ``wk`` / ``wv`` was sliced out,
    physically transposed (32 MB at 7B widths) and only then read, 36
    to 48 weight-sized copies a step beside a 16-row product (PERF.md
    section 6, PR 34). Held to the activation, the split costs the
    ``[b, t, n]`` result (kilobytes) and the weights enter their dot
    fusions as the stacked parameter, read once where they lie, as
    ``w1`` / ``w3`` / ``wo`` / ``lm_head`` do. Training and prefill
    multiply thousands of rows a weight and must stay free to fuse and
    to differentiate: they call :func:`_qkv` bare."""
    return _qkv(cfg, a, lp, positions, split_after=True, qk_norm=qk_norm)


@jax.named_scope("mlp")
def _mlp(cfg: LlamaConfig, x: jnp.ndarray, lp: Dict,
         residual: Optional[float] = None) -> jnp.ndarray:
    """Post-attention SwiGLU block (residual included) — shared by the
    training layer and the decode step. ``residual`` scales what the
    block adds to the stream, for a model that publishes such a
    multiplier (``models/ssm_hybrid.py``)."""
    i8, wb = cfg.int8_mxu, cfg.int8_wgrad_bf16
    m = _rmsnorm(x, lp["ln2"], cfg.norm_eps)
    # the two matmuls' products are what a remat policy can keep: the
    # silu and the product are elementwise and cheap to redo, and
    # silu's backward wants the pre-activation (keeping silu's output
    # instead spared no matmul: PERF.md section 6, PR 40)
    gate = jax.nn.silu(checkpoint_name(_matw(m, lp["w1"], i8, wb), "mlp_gate"))
    up = checkpoint_name(_matw(m, lp["w3"], i8, wb), "mlp_up")
    out = _matw(gate * up, lp["w2"], i8, wb)
    return x + out if residual is None else x + residual * out


def _layer(
    cfg: LlamaConfig,
    x: jnp.ndarray,
    lp: Dict,
    mesh=None,
    sp: int = 1,
    with_kv: bool = False,
):
    """One decoder layer. ``with_kv`` also returns this layer's (k, v)
    — the prefill path collects them into the decode cache; the
    training path must NOT set it (materializing every layer's K/V
    across the scan costs O(L·B·T) HBM)."""
    b, t, d = x.shape
    with jax.named_scope("attn"):
        a = _rmsnorm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = _qkv(cfg, a, lp)
        o = attention(q, k, v, cfg, mesh=mesh, sp=sp).reshape(b, t, -1)
        x = x + _matw(o, lp["wo"], cfg.int8_mxu, cfg.int8_wgrad_bf16)
    out = _mlp(cfg, x, lp)
    return (out, k, v) if with_kv else out


# What a rematerialised layer can keep beside its input, best first by
# seconds of recomputation spared a byte kept (bytes a token a layer in
# bfloat16 at Mistral-7B's widths; PERF.md section 6, PR 40):
#   flash_out + flash_lse  8.1 KiB  spares the second edl_flash_fwd
#   mlp_up                  28 KiB  spares m @ w3
#   mlp_gate                28 KiB  spares m @ w1 (the PRE-activation:
#                                   silu's backward wants it, and with
#                                   silu's output kept instead the
#                                   matmul was recomputed all the same)
#   attn_q, attn_k, attn_v  12 KiB  spare a @ wq, a @ wk, a @ wv
# The last three spare as many FLOPs a byte as each other (0.068 TFLOP
# a KiB a token), so where ``mlp_up`` does not fit the smaller q/k/v
# after it still may: ``remat.choose`` passes over what is too large.
# ``wo``'s and ``w2``'s products are dead in the backward and XLA drops
# their second run unasked.
KEEP_ORDER = (
    ("flash_out", "flash_lse"),
    ("mlp_up",),
    ("mlp_gate",),
    ("attn_q", "attn_k", "attn_v"),
)
_POLICY_NAMES = {
    "attn": KEEP_ORDER[0],
    "mlp": KEEP_ORDER[2] + KEEP_ORDER[1],
}


def keep_candidates(cfg: LlamaConfig, tokens: int, flash: bool, tp: int = 1):
    """``KEEP_ORDER`` with each entry's bytes on a device over all
    layers, for ``tokens`` tokens a device: what :func:`remat.choose
    <edl_tpu.parallel.remat.choose>` is asked. ``flash`` says whether
    the traced program runs the flash kernel; the names of one that
    does not are no candidates."""
    s = jnp.dtype(cfg.dtype).itemsize
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    a_token = {
        "flash_out": h * hd * s + h * 4,  # the lse: one float32 a head
        "mlp_up": cfg.d_ff * s,
        "mlp_gate": cfg.d_ff * s,
        "attn_q": (h + 2 * kv) * hd * s,
    }
    return [
        (names, cfg.n_layers * tokens * a_token[names[0]] // tp)
        for names in KEEP_ORDER
        if flash or names[0] != "flash_out"
    ]


def step_working_bytes(cfg: LlamaConfig, params: Dict, tokens: int,
                       shards: int = 1) -> int:
    """What a training step of this model fills on a device beside the
    trainer's state with nothing kept, for ``tokens`` tokens a device
    and parameters split ``shards`` ways: an estimate from shapes, for
    the choice that has to be made before the one compile. Through the
    whole step: the activation-dtype casts of the stacked weights (XLA
    hoists them out of the scan) and each layer's input. Then the
    larger of two phases. The head's: the logits in float32 and in the
    activation dtype, beside the gradients of the head and the
    embedding. The backward scan's: the layers' stacked gradients,
    whole until the scan ends, beside about seven ``[tokens, d_ff]``
    arrays of one layer's recomputation. Against
    ``memory_analysis().peak_memory_in_bytes`` of described-v5e
    compiles (Mistral widths L4 at 2, 4 and 5 rows of 4096, L9 fsdp 2
    on 4 and 2 chips, the flagship at 8 rows of 2048) it reads 0.0 to
    0.2 GiB low; the trainer's margin covers that."""
    s = jnp.dtype(cfg.dtype).itemsize

    def nbytes(tree):
        return sum(x.size * jnp.dtype(x.dtype).itemsize
                   for x in jax.tree_util.tree_leaves(tree)) // shards

    layers = nbytes(params["layers"])
    rest = nbytes({k: v for k, v in params.items() if k != "layers"})
    casts = sum(x.size for x in jax.tree_util.tree_leaves(
        params["layers"])) * s // shards
    inputs = cfg.n_layers * tokens * cfg.d_model * s
    head = rest + tokens * cfg.vocab * (4 + s)
    scan = layers + 7 * tokens * cfg.d_ff * s
    return casts + inputs + max(head, scan)


def _remat_policy(cfg: LlamaConfig, kept: Tuple[str, ...] = ()):
    """The remat FLOPs/HBM dial (see LlamaConfig.remat_policy); ``kept``
    is what ``"fit"`` resolved to."""
    if cfg.remat_policy == "attn" and not cfg.use_flash:
        raise ValueError(
            'remat_policy="attn" saves the flash kernel\'s named '
            "residuals; without use_flash there is nothing to "
            "save and the policy would silently degrade to full "
            "rematerialization"
        )
    if cfg.remat_policy in _POLICY_NAMES:
        kept = _POLICY_NAMES[cfg.remat_policy]
    elif cfg.remat_policy == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    elif cfg.remat_policy not in ("fit", "full"):
        raise ValueError(f"unknown remat_policy {cfg.remat_policy!r}")
    if not kept:
        return None
    return jax.checkpoint_policies.save_only_these_names(*kept)


def _fit_kept(cfg: LlamaConfig, params: Dict, x: jnp.ndarray,
              plan: Optional[MeshPlan], sp: int, pp: int):
    """``remat_policy="fit"``: what of ``KEEP_ORDER`` fits the room the
    trainer offers (``parallel/remat.py``), sized for one device of the
    plan. Nothing under a pipeline, whose schedule
    holds several microbatches' residuals at once, and nothing with no
    offer open: both are ``"full"``."""
    if pp > 1:
        return ()
    tp = plan.axis_size("tp") if plan is not None else 1
    shards = tp * (plan.axis_size("fsdp") if plan is not None else 1)
    rows = x.shape[0] // (plan.batch_shards() if plan is not None else 1)
    tokens = max(rows, 1) * (x.shape[1] // sp)
    return remat.choose(
        keep_candidates(cfg, tokens, cfg.use_flash and sp == 1, tp),
        step_working_bytes(cfg, params, tokens, shards),
    )


def forward(
    params: Dict,
    tokens: jnp.ndarray,
    cfg: LlamaConfig,
    mesh=None,
    plan: Optional[MeshPlan] = None,
) -> jnp.ndarray:
    """tokens [B, T] int32 → logits [B, T, vocab].

    ``plan``/``mesh`` activate the parallel strategies beyond what GSPMD
    infers from param shardings alone:

    - ``sp > 1``: activations are sequence-sharded right after the
      embedding (``plan.sequence_pspec``) and attention runs ring or
      Ulysses over the sp axis — long-context training where no single
      device ever holds a full-sequence activation.
    - ``pp > 1``: the scan-stacked layer axis splits into pp stages
      driven by the GPipe schedule (``parallel.pipeline.pipeline_apply``)
      with microbatched activations flowing over ppermute.
    """
    sp = plan.axis_size("sp") if plan is not None else 1
    pp = plan.axis_size("pp") if plan is not None else 1
    if (sp > 1 or pp > 1) and mesh is None:
        raise ValueError("sp/pp forward needs the mesh")
    if sp > 1 and pp > 1:
        # ring/ulysses attention is itself a shard_map; nesting it inside
        # the pipeline shard_map is not supported by jax
        raise ValueError("sp and pp cannot be combined in one llama mesh")
    if sp > 1 and cfg.remat and cfg.remat_policy == "attn":
        # the sp paths never run the flash kernel, so the flash_out /
        # flash_lse names the policy saves would not exist — the policy
        # would silently degrade to full remat (the failure its
        # use_flash guard documents)
        raise ValueError(
            'remat_policy="attn" requires the flash kernel, which the '
            "sp (ring/Ulysses) attention paths do not use"
        )
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    if sp > 1:
        if tokens.shape[1] % sp:
            raise ValueError(
                f"sequence {tokens.shape[1]} not divisible by sp={sp}"
            )
        x = jax.lax.with_sharding_constraint(
            x, plan.sequence_sharding(mesh, rank=3)
        )

    # inside the pipeline's own shard_map every array is already a
    # per-device shard: attention must not open a second one
    layer_mesh = None if pp > 1 else mesh

    def body(carry, lp):
        return _layer(cfg, carry, lp, mesh=layer_mesh, sp=sp), None

    if cfg.remat:
        kept = (_fit_kept(cfg, params, x, plan, sp, pp)
                if cfg.remat_policy == "fit" else ())
        body = jax.checkpoint(body, policy=_remat_policy(cfg, kept))

    if pp > 1:
        from edl_tpu.parallel.pipeline import pipeline_apply

        L, b = cfg.n_layers, x.shape[0]
        if L % pp:
            raise ValueError(f"n_layers {L} not divisible by pp={pp}")
        n_micro = cfg.pp_microbatches or pp
        if b % n_micro:
            raise ValueError(f"batch {b} not divisible by n_micro={n_micro}")
        shards = plan.batch_shards()
        if (b // n_micro) % shards:
            raise ValueError(
                f"microbatch rows {b // n_micro} do not divide over the "
                f"{shards} data shards (dp×fsdp) — lower pp_microbatches "
                f"or raise the batch"
            )
        stage_params = jax.tree_util.tree_map(
            lambda l: l.reshape((pp, L // pp) + l.shape[1:]), params["layers"]
        )

        def stage_fn(sp_params, xm):
            y, _ = jax.lax.scan(body, xm, sp_params)
            return y

        xm = x.reshape((n_micro, b // n_micro) + x.shape[1:])
        xm = pipeline_apply(
            stage_fn, stage_params, xm, mesh,
            data_axes=plan.batch_axes(),
        )
        x = xm.reshape((b,) + xm.shape[2:])
    else:
        x, _ = jax.lax.scan(body, x, params["layers"])
    with jax.named_scope("head"):
        x = _rmsnorm(x, params["ln_f"], cfg.norm_eps)
        return _matw(x, params["lm_head"]).astype(jnp.float32)


# -- inference: KV-cache decode ---------------------------------------------
#
# The serving half of the export story (runtime/export.py publishes the
# params; this consumes them). TPU-first: prefill is one full forward
# whose per-layer K/V are collected by the SAME lax.scan that runs the
# layers, and the decode loop is a single lax.scan over positions with
# the cache as carry — one compiled program for the whole generation,
# no per-token dispatch, static [B, max_len] shapes throughout.


def _prefill(params: Dict, tokens: jnp.ndarray, cfg: LlamaConfig):
    """Forward over the prompt, returning (logits_last [B, V],
    k_cache, v_cache [L, B, T, KV, hd]). Runs the SAME ``_layer`` as
    training (``with_kv=True`` collects the cache)."""
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)

    def body(carry, lp):
        y, k, v = _layer(cfg, carry, lp, with_kv=True)
        return y, (k, v)

    x, (ks, vs) = jax.lax.scan(body, x, params["layers"])
    with jax.named_scope("head"):
        x = _rmsnorm(x, params["ln_f"], cfg.norm_eps)
        logits = _matw(x[:, -1], params["lm_head"]).astype(jnp.float32)
    return logits, ks, vs


def _decode_step(params: Dict, tok: jnp.ndarray, pos, kc, vc, cfg: LlamaConfig):
    """One cached decode step. tok [B] int32; kc/vc [L, B, S, KV, hd]
    (S = max_len); pos = index this token writes. Returns
    (logits [B, V], kc, vc).

    The layer loop is UNROLLED with static layer indices, and each
    layer writes ONLY its new token's row into the stacked cache
    (``dynamic_update_slice`` at a static layer offset). This is what
    lets XLA keep every cache update in place: the earlier scan-based
    body carried the caches as scan xs/ys, which re-stacked — read AND
    wrote — the entire cache every token. Measured on the flagship at
    B=8 (wide-window differencing, best-of-6): 1.45x faster at
    T0=512, 2.15x at T0=2048 — the S-slope drops ~4x once the restack
    is gone. Four alternatives measured SLOWER (doc/design.md
    "Serving"): cache-as-scan-carry with traced-index slicing,
    per-layer cache leaves, int8 KV, and a pallas single-query flash
    kernel — XLA's dense cached attention is already efficient once
    the restack is gone. Unrolling costs O(L) compile once per
    (cfg, shape) — the memoized ``generate`` program.

    That kernel verdict holds HERE: one ``pos`` for every row and a
    cache of a few MB a layer, nearly all of it live. The slot path
    (:func:`decode_step_slots`) has a ``pos`` per slot, 59-90% of its
    padded cache dead at 7B widths, and XLA copied each ``kc[i]`` out
    before reading it; there ``ops.decode_attention`` reads the live
    blocks straight from the stacked cache (PERF.md section 6, PR 25).
    This function keeps the dense form."""
    b = tok.shape[0]
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    groups = h // kv
    s = kc.shape[2]
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tok[:, None], axis=0).astype(cfg.dtype)
    positions = jnp.full((1,), pos)
    for i in range(cfg.n_layers):
        lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
        dt = x.dtype
        with jax.named_scope("attn"):
            a = _rmsnorm(x, lp["ln1"], cfg.norm_eps)
            # same projections/RoPE as training (_qkv); only the
            # cache-update + masked-dense attention differ by construction
            q, knew, vnew = _qkv_cached(cfg, a, lp, positions)
            kc = jax.lax.dynamic_update_slice(kc, knew[None], (i, 0, pos, 0, 0))
            vc = jax.lax.dynamic_update_slice(vc, vnew[None], (i, 0, pos, 0, 0))
            kci, vci = kc[i], vc[i]  # static-index slices of the carry
            # GQA-native: group the query heads against the un-repeated
            # cache — no groups-fold bandwidth multiplier on the
            # token-latency-critical path
            qg = q.reshape(b, 1, kv, groups, hd)
            scores = jnp.einsum("btkgd,bskd->bkgts", qg, kci) / np.sqrt(hd)
            mask = (jnp.arange(s) <= pos)[None, None, None, None, :]
            scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
            probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(dt)
            o = jnp.einsum("bkgts,bskd->btkgd", probs, vci).reshape(b, 1, h * hd)
            x = x + _matw(o, lp["wo"])
        x = _mlp(cfg, x, lp)
    with jax.named_scope("head"):
        x = _rmsnorm(x, params["ln_f"], cfg.norm_eps)
        logits = _matw(x[:, 0], params["lm_head"]).astype(jnp.float32)
    return logits, kc, vc


def prefill_padded(params: Dict, tokens: jnp.ndarray, last, cfg: LlamaConfig):
    """Prefill over an END-padded prompt batch [B, Tb], returning the
    logits at each row's ``last`` index (its final REAL token) plus the
    K/V cache [L, B, Tb, KV, hd].

    Causality makes end-padding invisible to every real position: pad
    rows attend backward into the prompt but no real row ever attends
    forward into a pad, so logits and cache rows at positions <= last
    are exactly an unpadded prefill's. This is what lets the serving
    engine prefill mixed-length prompts into a handful of power-of-two
    buckets — O(log max_prompt) compiled programs instead of one per
    prompt length. ``last`` is a traced scalar or [B] vector, so every
    length inside a bucket reuses one program."""
    b = tokens.shape[0]
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)

    def body(carry, lp):
        y, k, v = _layer(cfg, carry, lp, with_kv=True)
        return y, (k, v)

    x, (ks, vs) = jax.lax.scan(body, x, params["layers"])
    with jax.named_scope("head"):
        x = _rmsnorm(x, params["ln_f"], cfg.norm_eps)
        xl = x[jnp.arange(b), last]  # [B, d] — each row's last real token
        logits = _matw(xl, params["lm_head"]).astype(jnp.float32)
    return logits, ks, vs


def decode_step_slots(
    params: Dict,
    tok: jnp.ndarray,
    pos: jnp.ndarray,
    kc: jnp.ndarray,
    vc: jnp.ndarray,
    cfg: LlamaConfig,
    live: Optional[jnp.ndarray] = None,
):
    """One continuous-batching decode step over B independent KV slots.
    tok [B] int32 (each slot's previous token); pos [B] int32 (the
    cache position each slot writes this step); kc/vc [L, B, S, KV, hd].
    Returns (logits [B, V], kc, vc).

    Per-row math is IDENTICAL to :func:`_decode_step` — same unrolled
    layer loop, shared ``_qkv``/``_mlp``, the same GQA-grouped cached
    attention — except positions, cache writes, and the causal mask are
    per-row, so requests at different depths decode in one batched step
    (the serving engine's slot table, ``edl_tpu/serving/engine.py``).
    The cache write is a per-row scatter at (row, pos[row]) — unique
    indices, so XLA keeps it in place like the dynamic_update_slice of
    the uniform-position path. Rows the caller considers inactive
    should be fed (tok=0, pos=0) and their outputs ignored: they
    re-write slot position 0 each step, which the next prefill-insert
    overwrites before it is ever unmasked.

    Under ``cfg.use_flash`` the attention of a layer is
    ``ops.decode_attention``: one kernel that reads each slot's live
    prefix (positions ``<= pos[row]``, so an inactive row costs one
    block) straight out of the stacked cache. ``live`` [B] bool marks
    the rows whose logits the caller will use: the kernel reads the
    others at position 0 alone, whatever ``pos`` they froze at (a
    finished request's prefix is nobody's to read; the row's write
    still lands at its ``pos``, past everything that request read).
    The dense lines (:func:`slot_attention_dense`) read all ``S``
    padded positions of ``kc[i]`` and are the ``use_flash=False``
    path, which ``live`` does not touch."""
    b = tok.shape[0]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    groups = h // kvh
    rows = jnp.arange(b)
    if cfg.use_flash:
        from edl_tpu.ops.decode_attention import decode_attention
        from edl_tpu.ops.flash_attention import _INTERPRET

        read_to = pos if live is None else jnp.where(live, pos, 0)
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tok[:, None], axis=0).astype(cfg.dtype)
    for i in range(cfg.n_layers):
        lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
        with jax.named_scope("attn"):
            a = _rmsnorm(x, lp["ln1"], cfg.norm_eps)
            q, knew, vnew = _qkv_cached(cfg, a, lp, pos[:, None])
            kc = kc.at[i, rows, pos].set(knew[:, 0])
            vc = vc.at[i, rows, pos].set(vnew[:, 0])
            qg = q.reshape(b, kvh, groups, hd)
            if cfg.use_flash:
                # the kernel or its error, as in ``attention``: each
                # slot's live prefix, read out of the stacked cache
                # (never ``kc[i]``: XLA copies that slice out first)
                o = decode_attention(
                    qg, kc, vc, read_to, jnp.int32(i),
                    interpret=_INTERPRET.get(),
                )
            else:
                o = slot_attention_dense(qg, kc[i], vc[i], pos)
            x = x + _matw(o.reshape(b, 1, h * hd), lp["wo"])
        x = _mlp(cfg, x, lp)
    with jax.named_scope("head"):
        x = _rmsnorm(x, params["ln_f"], cfg.norm_eps)
        logits = _matw(x[:, 0], params["lm_head"]).astype(jnp.float32)
    return logits, kc, vc


def slot_attention_dense(qg, kci, vci, pos, sm_scale=None):
    """The dense form of one layer's slot attention: qg [B, KV, groups,
    hd] against ALL ``S`` positions of kci / vci [B, S, KV, hd], masked
    to ``<= pos[row]`` afterwards. The ``use_flash=False`` path, and
    what ``ops.decode_attention`` is tested against. ``sm_scale``
    multiplies the scores in place of ``1 / sqrt(hd)``."""
    b, kvh, groups, hd = qg.shape
    s = kci.shape[1]
    scores = jnp.einsum(
        "btkgd,bskd->bkgts", qg[:, None], kci
    )
    scores = scores / np.sqrt(hd) if sm_scale is None else scores * sm_scale
    mask = (jnp.arange(s)[None, :] <= pos[:, None])[:, None, None, None, :]
    scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(qg.dtype)
    return jnp.einsum("bkgts,bskd->btkgd", probs, vci).reshape(qg.shape)


def decode_horizon_slots(
    params: Dict,
    tok: jnp.ndarray,
    pos: jnp.ndarray,
    active: jnp.ndarray,
    rem: jnp.ndarray,
    eosv: jnp.ndarray,
    kc: jnp.ndarray,
    vc: jnp.ndarray,
    cfg: LlamaConfig,
    horizon: int,
    key: Optional[jax.Array] = None,
    temperature=None,
    sampling: bool = False,
):
    """A fused HORIZON of ``horizon`` slot-decode steps in one program —
    ``lax.scan`` over :func:`decode_step_slots` with per-slot
    termination handled ON DEVICE, so the serving engine pays one
    dispatch (and one host sync, deferrable) per H tokens per slot
    instead of one per token.

    Per-slot device state (all [B], the scan carry alongside the KV
    cache): ``tok`` the previous token, ``pos`` the cache position the
    next step writes, ``active`` whether the slot is still decoding,
    ``rem`` tokens the slot may still emit, ``eosv`` its stop token
    (-1 = none; read-only here — only admission changes it). Each step
    every row runs the SAME batched math (the program never changes
    shape); a row that emits its ``eosv`` token or exhausts ``rem``
    FREEZES: tok/pos/rem stop advancing and its output lanes read -1.
    A frozen row keeps re-running the identical step — its cache
    rewrite at the frozen ``pos`` is idempotent (same token, same
    position, same visible cache ⇒ bit-identical K/V) and strictly
    row-local, so active rows decode exactly as if the frozen row had
    been evicted. (Under ``use_flash`` a frozen row's attention reads
    position 0 alone, ``live=active`` below, so what it rewrites at its
    frozen ``pos`` differs past layer 0; that position lies past
    everything its finished request read, and the next prefill into
    the slot resets it.) Greedy output is therefore token-identical to
    stepping :func:`decode_step_slots` one position at a time, which
    is itself per-row identical to sequential :func:`generate` — the
    contract ``tests/test_serving.py`` pins at H ∈ {1, 4, 16}.

    Returns ``(toks [B, horizon], tok, pos, active, rem, kc, vc)`` —
    ``toks`` rows are emitted tokens with -1 in frozen lanes, and the
    non-cache carries come back as device arrays so the engine can
    dispatch the NEXT block without ever syncing them to the host (the
    double-buffered pipeline in ``serving/engine.py``).

    ``sampling`` (static) draws from ``logits / temperature`` with a
    per-step key split from ``key``; greedy ignores both."""

    def step(tok, pos, cache, active):
        logits, kc, vc = decode_step_slots(
            params, tok, pos, *cache, cfg, live=active
        )
        return logits, (kc, vc), ()

    toks, tok, pos, active, rem, (kc, vc), _ = horizon_scan(
        step, tok, pos, active, rem, eosv, (kc, vc), horizon,
        key=key, temperature=temperature, sampling=sampling,
    )
    return toks, tok, pos, active, rem, kc, vc


def horizon_scan(
    step_fn, tok, pos, active, rem, eosv, cache, horizon: int,
    key=None, temperature=None, sampling: bool = False,
):
    """The scan of :func:`decode_horizon_slots`, for any model's slot
    step: ``step_fn(tok, pos, cache, active) -> (logits [B, V], cache,
    extra)`` is run ``horizon`` times; the token choice, the freezing
    of finished rows and the carries are the same for every model.
    Returns ``(toks [B, horizon], tok, pos, active, rem, cache,
    extras)`` with ``extras`` the steps' ``extra`` stacked."""

    def step(carry, k):
        tok, pos, active, rem, cache = carry
        logits, cache, extra = step_fn(tok, pos, cache, active)
        with jax.named_scope("head"):
            if sampling:
                nxt = jax.random.categorical(
                    k, logits / temperature, axis=-1
                )
            else:
                nxt = jnp.argmax(logits, axis=-1)
        nxt = jnp.where(active, nxt.astype(jnp.int32), tok)
        out = jnp.where(active, nxt, -1)
        pos = jnp.where(active, pos + 1, pos)
        rem = jnp.where(active, rem - 1, rem)
        hit = active & (eosv >= 0) & (nxt == eosv)
        active = active & ~hit & (rem > 0)
        return (nxt, pos, active, rem, cache), (out, extra)

    keys = jax.random.split(
        key if key is not None else jax.random.PRNGKey(0), horizon
    )
    (tok, pos, active, rem, cache), (outs, extras) = jax.lax.scan(
        step, (tok, pos, active, rem, cache), keys
    )
    return jnp.swapaxes(outs, 0, 1), tok, pos, active, rem, cache, extras


# -- inference: paged KV cache (block tables) --------------------------------
#
# The vLLM-PagedAttention memory layout adapted to the donated-buffer,
# fused-horizon programs above: instead of one contiguous
# [L, B, max_len, KV, hd] region, K/V live in a POOL of fixed-size
# blocks [L, n_blocks, block_size, KV, hd] and each slot carries a
# BLOCK TABLE row mapping its logical positions to physical blocks —
# logical position p of row r lives at
# (table[r, p // block_size], p % block_size). The serving engine
# allocates blocks on demand as each request grows, frees them on
# finish, and can map LEADING table entries of different rows to the
# SAME physical block (refcounted shared-prefix reuse) — HBM scales
# with tokens actually resident, not slots × max_len.
#
# Program-stability contract is unchanged: one compiled program per
# (cfg, shapes); the table is a TRACED int32 operand, so allocation,
# sharing, and frees are host bookkeeping — membership and mapping
# changes never retrace. Physical block 0 is reserved by the engine as
# a SCRATCH block: inactive/frozen lanes and prompt-bucket padding
# route their writes there, and no live table entry ever maps to it,
# so colliding scratch writes are never read back.
#
# -- paged KV quantization (kv_quant = "int8" | "int4") ----------------------
#
# Decode is KV-bandwidth-bound once weights are int8 (BENCH_r05: b=1
# at ~99.5% of peak HBM BW), so the paged pool can optionally store
# QUANTIZED K/V: int8 (or packed int4) values with per-block-per-kv-
# head f32 absmax scales — the fixed-size block is the quantization
# unit, which is what lets quantization compose with refcounted CoW
# prefix sharing (a block copy carries its scale entry with it).
#
# The dequantize follows ``_matw``'s int8-weight discipline: the scale
# never touches the contraction —
#
# * K side: the scale is constant along the contracted ``hd`` axis, so
#   ``scores = einsum(q, kq.astype(dt)) * ks`` — XLA fuses the
#   convert(int8→dt) into the operand read and HBM streams int8 bytes;
#   the f32 scale multiply lands on the [.., S] scores, not on a
#   dequantized [S, KV, hd] temp;
# * V side: the scale varies along the contracted ``s`` axis but is
#   indexed exactly like the softmax probs, so it folds into them:
#   ``o = einsum((probs * vs).astype(dt), vq.astype(dt))``.
#
# Writes quantize ON THE FLY inside the same program that computes the
# fresh K/V (decode lanes, verify lanes, prefill chunks — one shared
# scatter discipline, :func:`_kvq_store`): per dispatch, each written
# block's scale is grown to cover the new values' absmax (scatter-max),
# RESET when the write lands at block offset 0 (a block's first write
# is always offset 0 — decode crosses boundaries at offset 0, prefill
# starts block-aligned, and the CoW full-hit rewrite targets the last
# offset of a COPIED block that brought its scale along), and resident
# block content is rescaled under the grown scale so earlier tokens
# stay consistent. Scales only grow between resets, so the rescale
# ratio is <= 1 and an idempotent frozen-lane rewrite is exact
# (ratio 1). Exact greedy token identity cannot survive quantization;
# the serving engine keeps ``kv_quant="off"`` byte-identical to the
# unquantized path (these branches are trace-time: "off" traces the
# program it always did) and gates the quantized path on output
# tolerance + the speculative acceptance EMA (engine-side).

_KVQ_QMAX = {"int8": 127.0, "int4": 7.0}


def kvq_packed_head_dim(kv_quant: str, head_dim: int) -> int:
    """Innermost stored dim of one pool entry: int4 packs two 4-bit
    values per int8 byte along ``hd`` (requires even head_dim)."""
    if kv_quant == "int4":
        if head_dim % 2:
            raise ValueError(
                f"kv_quant int4 needs an even head_dim, got {head_dim}"
            )
        return head_dim // 2
    return head_dim


def _kvq_pack(q: jnp.ndarray, kv_quant: str) -> jnp.ndarray:
    """Rounded/clipped quantized values (f32 in [-qmax, qmax]) ->
    stored int8. int4 packs index pairs along the last axis: even
    index = low nibble, odd = high nibble."""
    qi = q.astype(jnp.int32)
    if kv_quant == "int8":
        return qi.astype(jnp.int8)
    lo = qi[..., 0::2]
    hi = qi[..., 1::2]
    return ((hi << 4) | (lo & 0xF)).astype(jnp.int8)


def _kvq_unpack(p: jnp.ndarray, kv_quant: str) -> jnp.ndarray:
    """Stored int8 -> quantized values as f32 in [-qmax, qmax]."""
    if kv_quant == "int8":
        return p.astype(jnp.float32)
    x = p.astype(jnp.int32)
    hi = x >> 4  # arithmetic shift sign-extends the high nibble
    lo = ((x & 0xF) ^ 8) - 8  # sign-extend the low nibble
    both = jnp.stack([lo, hi], axis=-1)  # [..., hd/2, 2]
    return both.reshape(*p.shape[:-1], p.shape[-1] * 2).astype(
        jnp.float32
    )


def _kvq_store(
    pool: jnp.ndarray,
    scale: jnp.ndarray,
    i: int,
    wblk: jnp.ndarray,
    woff: jnp.ndarray,
    new: jnp.ndarray,
    kv_quant: str,
):
    """Quantize ``new`` [N, KV, hd] lane writes into layer ``i`` of the
    packed ``pool`` [L, nb, bs, KV, hdp] at (``wblk``, ``woff``) [N],
    maintaining per-(block, kv-head) f32 ``scale`` [L, nb, KV].

    Per dispatch: (1) scatter-max the new values' absmax into per-block
    scale proposals; (2) a write at offset 0 marks its block FRESH —
    the scale resets instead of inheriting a freed previous tenant's
    (a block's first real write is always offset 0, see the section
    comment); (3) touched blocks' resident content is rescaled under
    the grown scale (gather-modify-scatter of the written blocks only;
    duplicate block indices carry identical payloads, so the scatter is
    deterministic; fresh blocks' stale content is zeroed); (4) the new
    values quantize under the final scale and land at their offsets.
    Only refcount-1 blocks are ever written (the engine copy-on-writes
    shared blocks first), so no two rows contend for one block — except
    SCRATCH, whose content and scale are never read."""
    qmax = _KVQ_QMAX[kv_quant]
    newf = new.astype(jnp.float32)
    amax = jnp.max(jnp.abs(newf), axis=-1)  # [N, KV]
    nb = scale.shape[1]
    s_old = scale[i]  # [nb, KV]
    prop = jnp.zeros_like(s_old).at[wblk].max(amax)
    fresh = (
        jnp.zeros((nb,), jnp.int32)
        .at[wblk]
        .max((woff == 0).astype(jnp.int32))
        > 0
    )
    touched = jnp.zeros((nb,), bool).at[wblk].set(True)
    s_new = jnp.maximum(
        jnp.where(fresh[:, None], 0.0, s_old), prop / qmax
    )
    s_new = jnp.where(touched[:, None], s_new, s_old)
    s_safe = jnp.where(s_new > 0.0, s_new, 1.0)
    # resident-content rescale: exact identity (ratio 1) when the scale
    # did not move; fresh blocks' stale previous-tenant content zeroes
    ratio = (jnp.where(fresh[:, None], 0.0, s_old) / s_safe)[wblk]
    cur = _kvq_unpack(pool[i][wblk], kv_quant)  # [N, bs, KV, hd]
    resc = jnp.clip(
        jnp.round(cur * ratio[:, None, :, None]), -qmax, qmax
    )
    pool = pool.at[i, wblk].set(_kvq_pack(resc, kv_quant))
    qnew = jnp.clip(
        jnp.round(newf / s_safe[wblk][:, :, None]), -qmax, qmax
    )
    pool = pool.at[i, wblk, woff].set(_kvq_pack(qnew, kv_quant))
    scale = scale.at[i].set(s_new)
    return pool, scale


def _kvq_scale_strip(scale_i: jnp.ndarray, table: jnp.ndarray, bs: int):
    """Per-position scale strip for the attention gather: gather the
    [.., M, KV] block scales through the table and expand to
    [.., KV, 1, 1, S], broadcastable against the ``bkgts`` score/prob
    layout (block j's scale covers positions j*bs .. (j+1)*bs - 1)."""
    sc = jnp.repeat(scale_i[table], bs, axis=-2)  # [.., S, KV]
    sc = jnp.swapaxes(sc, -1, -2)  # [.., KV, S]
    if sc.ndim == 2:  # single-slot table (prefill): add the batch axis
        sc = sc[None]
    return sc[:, :, None, None, :]


def decode_step_slots_paged(
    params: Dict,
    tok: jnp.ndarray,
    pos: jnp.ndarray,
    table: jnp.ndarray,
    cache: Tuple[jnp.ndarray, ...],
    cfg: LlamaConfig,
    block_size: int,
    kv_quant: str = "off",
):
    """One slot-decode step over the paged pool. tok/pos [B] int32;
    table [B, M] int32 physical block ids; ``cache`` the pool's arrays,
    (kc, vc) each [L, n_blocks, block_size, KV, hd]. Returns
    (logits [B, V], cache), the cache with the arity it came in.

    Per-row math is IDENTICAL to :func:`decode_step_slots` — the only
    differences are the scatter target (the row's CURRENT block at
    ``pos % block_size`` instead of cache row ``pos``) and the
    attention read (a table gather reassembles each row's logical
    [M·bs, KV, hd] view; the ``arange(S) <= pos`` mask hides garbage
    in covered-but-unwritten and scratch-mapped positions exactly as
    it hides the contiguous cache's tail). Greedy output is therefore
    token-identical to the contiguous path whenever the engine's
    tables cover every written position — the contract
    tests/test_paged_kv.py pins at H ∈ {1, 4, 16}.

    ``kv_quant`` != "off" says the pool is quantized storage: ``cache``
    is (kc, vc, ks, vs), int8 or packed int4 entries + per-block-per-
    kv-head f32 scales ``ks``/``vs`` [L, nb, KV] (see the section
    comment); lane writes quantize on the fly and the gather
    dequantizes via the factored scale multiply. The "off" path is
    byte-identical to before the knob existed — the branch is
    trace-time."""
    kc, vc, ks, vs = (*cache, None, None)[:4]  # no scale planes when off
    b = tok.shape[0]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    groups = h // kvh
    bs = block_size
    m = table.shape[1]
    s = m * bs
    rows = jnp.arange(b)
    quant = kv_quant != "off"
    # rows whose pos ran past the table (a frozen lane parked one past
    # its last token, or a stale lane the host stopped tracking) write
    # to the scratch block — a clamped gather would alias the LAST real
    # block and corrupt it
    inb = pos < s
    blk = jnp.where(
        inb, table[rows, jnp.clip(pos // bs, 0, m - 1)], 0
    )  # [B] physical block per row
    off = jnp.where(inb, pos % bs, 0)
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tok[:, None], axis=0).astype(cfg.dtype)
    for i in range(cfg.n_layers):
        lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
        dt = x.dtype
        with jax.named_scope("attn"):
            a = _rmsnorm(x, lp["ln1"], cfg.norm_eps)
            q, knew, vnew = _qkv_cached(cfg, a, lp, pos[:, None])
            if quant:
                kc, ks = _kvq_store(kc, ks, i, blk, off, knew[:, 0], kv_quant)
                vc, vs = _kvq_store(vc, vs, i, blk, off, vnew[:, 0], kv_quant)
                kci = _kvq_unpack(kc[i][table], kv_quant).reshape(
                    b, s, kvh, hd
                ).astype(dt)
                vci = _kvq_unpack(vc[i][table], kv_quant).reshape(
                    b, s, kvh, hd
                ).astype(dt)
                ksc = _kvq_scale_strip(ks[i], table, bs)  # [B, KV, 1, 1, S]
                vsc = _kvq_scale_strip(vs[i], table, bs)
            else:
                kc = kc.at[i, blk, off].set(knew[:, 0])
                vc = vc.at[i, blk, off].set(vnew[:, 0])
                # table gather: [n_blocks, bs, KV, hd][table] -> the row's
                # logical [B, M, bs, KV, hd] view, flat to [B, S, KV, hd]
                kci = kc[i][table].reshape(b, s, kvh, hd)
                vci = vc[i][table].reshape(b, s, kvh, hd)
            qg = q.reshape(b, 1, kvh, groups, hd)
            scores = jnp.einsum("btkgd,bskd->bkgts", qg, kci) / np.sqrt(hd)
            if quant:
                # the K-side dequant: per-(block, kv-head) scale lands on
                # the f32 scores (constant along the contracted hd axis),
                # never on a dequantized [S, KV, hd] temp — _matw's
                # discipline, the f32 multiply included
                scores = scores.astype(jnp.float32) * ksc
            mask = (jnp.arange(s)[None, :] <= pos[:, None])[:, None, None, None, :]
            scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
            probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
            if quant:
                # the V-side dequant folds into the probs (scale varies
                # along the contracted s axis but indexes like the probs)
                probs = probs * vsc
            probs = probs.astype(dt)
            o = jnp.einsum("bkgts,bskd->btkgd", probs, vci).reshape(b, 1, h * hd)
            x = x + _matw(o, lp["wo"])
        x = _mlp(cfg, x, lp)
    with jax.named_scope("head"):
        x = _rmsnorm(x, params["ln_f"], cfg.norm_eps)
        logits = _matw(x[:, 0], params["lm_head"]).astype(jnp.float32)
    return logits, (kc, vc, ks, vs)[:len(cache)]


def decode_horizon_slots_paged(
    params: Dict,
    tok: jnp.ndarray,
    pos: jnp.ndarray,
    active: jnp.ndarray,
    rem: jnp.ndarray,
    eosv: jnp.ndarray,
    table: jnp.ndarray,
    cache: Tuple[jnp.ndarray, ...],
    cfg: LlamaConfig,
    block_size: int,
    horizon: int,
    key: Optional[jax.Array] = None,
    temperature=None,
    sampling: bool = False,
    kv_quant: str = "off",
):
    """The paged twin of :func:`decode_horizon_slots`: a fused horizon
    of ``horizon`` :func:`decode_step_slots_paged` steps with the SAME
    on-device freeze semantics (frozen lanes emit -1, rewrite their
    frozen position idempotently, and never disturb other rows). The
    block table is READ-ONLY across the horizon — the engine covers
    every position the horizon can write before dispatching, so no
    mid-horizon allocation is ever needed on device.

    ``cache`` rides the scan carry whole (the scale planes with the
    pools under ``kv_quant``) and comes back in one piece: ``(toks
    [B, H], tok, pos, active, rem, cache)``."""

    def step(carry, k):
        tok, pos, active, rem, cache = carry
        logits, cache = decode_step_slots_paged(
            params, tok, pos, table, cache, cfg, block_size,
            kv_quant=kv_quant,
        )
        with jax.named_scope("head"):
            if sampling:
                nxt = jax.random.categorical(
                    k, logits / temperature, axis=-1
                )
            else:
                nxt = jnp.argmax(logits, axis=-1)
        nxt = jnp.where(active, nxt.astype(jnp.int32), tok)
        out = jnp.where(active, nxt, -1)
        pos = jnp.where(active, pos + 1, pos)
        rem = jnp.where(active, rem - 1, rem)
        hit = active & (eosv >= 0) & (nxt == eosv)
        active = active & ~hit & (rem > 0)
        return (nxt, pos, active, rem, cache), out

    keys = jax.random.split(
        key if key is not None else jax.random.PRNGKey(0), horizon
    )
    (tok, pos, active, rem, cache), outs = jax.lax.scan(
        step, (tok, pos, active, rem, tuple(cache)), keys
    )
    return jnp.swapaxes(outs, 0, 1), tok, pos, active, rem, cache


def prefill_paged(
    params: Dict,
    tokens: jnp.ndarray,
    start,
    last,
    table: jnp.ndarray,
    cache: Tuple[jnp.ndarray, ...],
    cfg: LlamaConfig,
    block_size: int,
    kv_quant: str = "off",
):
    """Prefill one CHUNK of one slot's prompt into the paged pool.

    ``tokens`` [1, Tb] covers logical positions ``start .. start+Tb-1``
    with real tokens only through local index ``last`` (end-padding,
    same bucket contract as :func:`prefill_padded`); positions below
    ``start`` must already be resident in the pool (earlier chunks, or
    shared prefix blocks another request prefilled). ``table`` [M] is
    the ONE slot's block-table row; ``cache`` is the pool's arrays as
    :func:`decode_step_slots_paged` takes them. Returns (logits [1, V]
    at ``last``, cache).

    This one function serves admission prefill (start = prefix-hit
    length), CHUNKED prefill of long prompts (each bounded chunk is a
    separate dispatch, interleaved with decode blocks), and the
    crash-recovery replay. Queries attend causally to the pool —
    chunk token t sees every position <= start + t, which includes the
    chunk's own K/V because the scatter lands before the gather. Pad
    tokens (t > last) write to the scratch block (never read) and
    their query rows are discarded by the caller taking ``last``'s
    logits only.

    Under ``kv_quant`` != "off" the whole chunk quantizes on the fly
    (one :func:`_kvq_store` per layer per plane — the chunk's writes to
    a block land together, so its scale converges in one step)."""
    kc, vc, ks, vs = (*cache, None, None)[:4]
    b, tb = tokens.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    groups = h // kvh
    bs = block_size
    m = table.shape[0]
    s = m * bs
    positions = start + jnp.arange(tb)  # [Tb] absolute positions
    tpos = jnp.arange(tb)
    real = tpos <= last
    # per-token write targets; pads route to the scratch block so a
    # bucket overhanging the covered table never writes out of range
    wblk = jnp.where(
        real, table[jnp.clip(positions // bs, 0, m - 1)], 0
    )
    woff = jnp.where(real, positions % bs, 0)
    quant = kv_quant != "off"
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    qmask = (jnp.arange(s)[None, :] <= positions[:, None])[
        None, None, None, :, :
    ]  # [1,1,1,Tb,S]: query t sees pool positions <= start + t
    for i in range(cfg.n_layers):
        lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
        dt = x.dtype
        with jax.named_scope("attn"):
            a = _rmsnorm(x, lp["ln1"], cfg.norm_eps)
            q, knew, vnew = _qkv_cached(cfg, a, lp, positions)
            if quant:
                kc, ks = _kvq_store(kc, ks, i, wblk, woff, knew[0], kv_quant)
                vc, vs = _kvq_store(vc, vs, i, wblk, woff, vnew[0], kv_quant)
                kci = _kvq_unpack(kc[i][table], kv_quant).reshape(
                    1, s, kvh, hd
                ).astype(dt)
                vci = _kvq_unpack(vc[i][table], kv_quant).reshape(
                    1, s, kvh, hd
                ).astype(dt)
                ksc = _kvq_scale_strip(ks[i], table, bs)
                vsc = _kvq_scale_strip(vs[i], table, bs)
            else:
                kc = kc.at[i, wblk, woff].set(knew[0])
                vc = vc.at[i, wblk, woff].set(vnew[0])
                kci = kc[i][table].reshape(1, s, kvh, hd)
                vci = vc[i][table].reshape(1, s, kvh, hd)
            qg = q.reshape(b, tb, kvh, groups, hd)
            scores = jnp.einsum("btkgd,bskd->bkgts", qg, kci) / np.sqrt(hd)
            if quant:
                scores = scores.astype(jnp.float32) * ksc
            scores = jnp.where(qmask, scores, jnp.finfo(scores.dtype).min)
            probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
            if quant:
                probs = probs * vsc
            probs = probs.astype(dt)
            o = jnp.einsum("bkgts,bskd->btkgd", probs, vci).reshape(b, tb, h * hd)
            x = x + _matw(o, lp["wo"])
        x = _mlp(cfg, x, lp)
    with jax.named_scope("head"):
        x = _rmsnorm(x, params["ln_f"], cfg.norm_eps)
        xl = x[jnp.arange(b), last]  # [1, d] — the chunk's last real token
        logits = _matw(xl, params["lm_head"]).astype(jnp.float32)
    return logits, (kc, vc, ks, vs)[:len(cache)]


# -- inference: speculative decoding (draft-verify) --------------------------
#
# Single-stream decode is weight-bandwidth-bound (BENCH_r05: int8 b=1
# already at ~99.5% of peak HBM bandwidth), so the only remaining
# latency lever is emitting MORE THAN ONE token per weight pass. The
# verify programs below score K = D+1 query lanes per slot in one
# dispatch — the pending token plus D host-drafted continuation
# guesses — under length-K masked attention over the same KV cache the
# horizon programs use. Greedy acceptance keeps the stream
# token-identical to sequential decode: lane j's argmax is the true
# next token after consuming lanes 0..j, so the longest draft prefix
# matching argmax can be committed, plus the first non-matching argmax
# as a bonus token (always >= 1 token per dispatch — a rejected draft
# degrades to exactly one plain decode step, never worse).
#
# KV discipline: lane j writes its token's K/V at position pos+j
# BEFORE the gather, so causal lanes see their own prefix. Rejected
# lanes leave garbage at positions past the accepted run — safe under
# the same overwrite-before-unmask invariant the horizon path uses:
# the next dispatch re-writes every position it unmasks before reading
# it (its lane 0 rewrites the new pending token's position, lane j its
# own). Out-of-range writes (a row near the end of its cache) are
# DROPPED (mode="drop"), matching the frozen-row behavior of
# ``decode_step_slots`` at pos == S.


def verify_step_slots(
    params: Dict,
    tok: jnp.ndarray,
    draft: jnp.ndarray,
    pos: jnp.ndarray,
    active: jnp.ndarray,
    rem: jnp.ndarray,
    eosv: jnp.ndarray,
    kc: jnp.ndarray,
    vc: jnp.ndarray,
    cfg: LlamaConfig,
):
    """One speculative draft–verify step over B independent KV slots:
    score the pending token plus D drafted tokens in ONE dispatch and
    commit the longest greedy-consistent prefix ON DEVICE.

    tok [B] int32 (each slot's pending token, K/V not yet written);
    draft [B, D] int32 host-proposed continuations, -1 = no draft in
    that lane (-1 never matches an argmax, so a row with all -1 drafts
    degrades to exactly one plain decode step — per-slot drafting is
    disabled by feeding sentinels, membership never changes the
    program); pos/rem/eosv [B] int32 and active [B] bool with the SAME
    semantics as :func:`decode_horizon_slots`. kc/vc
    [L, B, S, KV, hd]. Returns ``(outs [B, K], tok, pos, active, rem,
    kc, vc)`` with K = D+1 — ``outs`` rows are the committed tokens in
    emission order with -1 tails (frozen lanes, rejected drafts,
    post-EOS lanes), the exact drain contract of the horizon programs.

    Lane j embeds token j of ``[tok, draft]`` at position pos+j,
    writes its K/V there, and attends causally to positions <= pos+j
    (its own write and earlier lanes' writes land before the gather).
    Lane j's argmax is therefore the true greedy successor of the
    sequence ``... tok draft[0..j-1]`` — if every draft before lane j
    matched argmax, lane j's argmax is exactly what sequential decode
    would emit. Acceptance commits ``a`` = longest matching draft
    prefix plus lane a's argmax as the bonus token (1 <= emitted <=
    K), truncated by the row's remaining budget and cut AFTER the
    first emitted EOS (the EOS itself is emitted, mid-verify, exactly
    like the horizon's on-device EOS freeze). Frozen rows emit
    nothing and keep their state; their lane-0 rewrite at the frozen
    ``pos`` is idempotent and later lanes drop or are overwritten
    before unmask. Greedy output is token-identical to sequential
    :func:`generate` under EVERY acceptance outcome — the contract
    tests/test_serving_spec.py pins."""
    b, d = draft.shape
    k = d + 1
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    groups = h // kvh
    s = kc.shape[2]
    rows = jnp.arange(b)
    # -1 sentinels embed as token 0; their lanes are never accepted
    # (argmax >= 0 never equals -1), so the embedded value is dead
    toks = jnp.concatenate([tok[:, None], jnp.maximum(draft, 0)], axis=1)
    qpos = pos[:, None] + jnp.arange(k)[None, :]  # [B, K] absolute
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], toks, axis=0).astype(cfg.dtype)
    # lane j sees cache positions <= pos+j — its own write included,
    # garbage beyond masked exactly like the decode step's tail
    qmask = (jnp.arange(s)[None, None, :] <= qpos[:, :, None])[
        :, None, None, :, :
    ]  # [B,1,1,K,S]
    for i in range(cfg.n_layers):
        lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
        dt = x.dtype
        with jax.named_scope("attn"):
            a = _rmsnorm(x, lp["ln1"], cfg.norm_eps)
            q, knew, vnew = _qkv_cached(cfg, a, lp, qpos)
            # per-row K-lane scatter; rows[:, None] broadcasts against the
            # [B, K] positions. Writes past S drop (frozen rows parked at
            # the cache end), never clamp — a clamp would alias S-1.
            kc = kc.at[i, rows[:, None], qpos].set(knew, mode="drop")
            vc = vc.at[i, rows[:, None], qpos].set(vnew, mode="drop")
            kci, vci = kc[i], vc[i]
            qg = q.reshape(b, k, kvh, groups, hd)
            scores = jnp.einsum("btkgd,bskd->bkgts", qg, kci) / np.sqrt(hd)
            scores = jnp.where(qmask, scores, jnp.finfo(scores.dtype).min)
            probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(dt)
            o = jnp.einsum("bkgts,bskd->btkgd", probs, vci).reshape(b, k, h * hd)
            x = x + _matw(o, lp["wo"])
        x = _mlp(cfg, x, lp)
    with jax.named_scope("head"):
        x = _rmsnorm(x, params["ln_f"], cfg.norm_eps)
        logits = _matw(x, params["lm_head"]).astype(jnp.float32)  # [B, K, V]
        out = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, K]
    return _spec_accept(tok, draft, out, pos, active, rem, eosv, kc, vc)


def _spec_accept(tok, draft, out, pos, active, rem, eosv, *cache):
    """On-device acceptance shared by the contiguous and paged verify
    steps: commit the longest draft prefix matching greedy argmax plus
    one bonus token, truncated by the remaining budget and cut after
    the first emitted EOS. Pure slot-state bookkeeping — the K/V for
    every committed position was already written by the verify lanes
    (committed lane j's input token IS the matched draft); ``cache``
    is handed back behind the slot state as it came."""
    b, d = draft.shape
    k = d + 1
    rows = jnp.arange(b)
    # a = accepted draft prefix length: drafts match out shifted by one
    # (out[:, j] is the successor of the sequence THROUGH draft[j-1])
    match = (draft == out[:, :d]).astype(jnp.int32)
    a = jnp.sum(jnp.cumprod(match, axis=1), axis=1)  # [B] in 0..D
    idx = jnp.arange(k)[None, :]
    emit = (
        active[:, None]
        & (idx < (a + 1)[:, None])  # accepted run + bonus token
        & (idx < rem[:, None])  # budget truncation, same as horizon rem
    )
    is_eos = (eosv[:, None] >= 0) & (out == eosv[:, None])
    eos_emitted = emit & is_eos
    # lanes strictly AFTER the first emitted EOS are cut; the EOS
    # itself is emitted (exclusive running count: cumsum minus self)
    before = jnp.cumsum(eos_emitted.astype(jnp.int32), axis=1) - (
        eos_emitted.astype(jnp.int32)
    )
    emit = emit & (before == 0)
    e = jnp.sum(emit.astype(jnp.int32), axis=1)  # [B] emitted count
    outs = jnp.where(emit, out, -1)
    # the new pending token is the LAST emitted one (its K/V is not
    # yet written — the next dispatch's lane 0 writes it, the same
    # pending-token contract every decode program shares)
    tok = jnp.where(e > 0, out[rows, jnp.clip(e - 1, 0, k - 1)], tok)
    pos = pos + e
    rem = rem - e
    hit = jnp.any(eos_emitted & emit, axis=1)
    active = active & ~hit & (rem > 0)
    return (outs, tok, pos, active, rem, *cache)


def verify_step_slots_paged(
    params: Dict,
    tok: jnp.ndarray,
    draft: jnp.ndarray,
    pos: jnp.ndarray,
    active: jnp.ndarray,
    rem: jnp.ndarray,
    eosv: jnp.ndarray,
    table: jnp.ndarray,
    cache: Tuple[jnp.ndarray, ...],
    cfg: LlamaConfig,
    block_size: int,
    kv_quant: str = "off",
):
    """The paged twin of :func:`verify_step_slots`: K = D+1 query lanes
    per row routed through the [B, M] block table, same on-device
    acceptance; ``cache`` is the pool's arrays as
    :func:`decode_step_slots_paged` takes them, and the result is
    ``(outs [B, K], tok, pos, active, rem, cache)``. Lane writes
    target (table[row, (pos+j) // bs], (pos+j) % bs); out-of-table lanes and uncovered positions route to
    the scratch block (collisions there are never read). The engine
    covers every position the accepted run can commit before
    dispatching (``_ensure_cover`` sized to max(horizon, K)), so
    committed lanes always land in mapped private blocks — uncovered
    garbage from rejected lanes dies in scratch or is overwritten
    before its position is ever unmasked.

    Under ``kv_quant`` != "off" the [B, K] lane writes flatten into one
    :func:`_kvq_store` per plane per layer (rejected-lane garbage can
    only GROW a resident block's scale — a monotone rescale, never a
    corruption; the garbage values themselves are overwritten before
    their positions unmask)."""
    kc, vc, ks, vs = (*cache, None, None)[:4]
    b, d = draft.shape
    k = d + 1
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    groups = h // kvh
    bs = block_size
    m = table.shape[1]
    s = m * bs
    rows = jnp.arange(b)
    toks = jnp.concatenate([tok[:, None], jnp.maximum(draft, 0)], axis=1)
    qpos = pos[:, None] + jnp.arange(k)[None, :]  # [B, K]
    inb = qpos < s
    # per-lane physical write targets; lanes past the table go to
    # scratch like the decode step's frozen/stale rows
    wblk = jnp.where(
        inb, table[rows[:, None], jnp.clip(qpos // bs, 0, m - 1)], 0
    )
    woff = jnp.where(inb, qpos % bs, 0)
    quant = kv_quant != "off"
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], toks, axis=0).astype(cfg.dtype)
    qmask = (jnp.arange(s)[None, None, :] <= qpos[:, :, None])[
        :, None, None, :, :
    ]
    for i in range(cfg.n_layers):
        lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
        dt = x.dtype
        with jax.named_scope("attn"):
            a = _rmsnorm(x, lp["ln1"], cfg.norm_eps)
            q, knew, vnew = _qkv_cached(cfg, a, lp, qpos)
            if quant:
                kc, ks = _kvq_store(
                    kc, ks, i, wblk.reshape(-1), woff.reshape(-1),
                    knew.reshape(b * k, kvh, hd), kv_quant,
                )
                vc, vs = _kvq_store(
                    vc, vs, i, wblk.reshape(-1), woff.reshape(-1),
                    vnew.reshape(b * k, kvh, hd), kv_quant,
                )
                kci = _kvq_unpack(kc[i][table], kv_quant).reshape(
                    b, s, kvh, hd
                ).astype(dt)
                vci = _kvq_unpack(vc[i][table], kv_quant).reshape(
                    b, s, kvh, hd
                ).astype(dt)
                ksc = _kvq_scale_strip(ks[i], table, bs)
                vsc = _kvq_scale_strip(vs[i], table, bs)
            else:
                kc = kc.at[i, wblk, woff].set(knew)
                vc = vc.at[i, wblk, woff].set(vnew)
                kci = kc[i][table].reshape(b, s, kvh, hd)
                vci = vc[i][table].reshape(b, s, kvh, hd)
            qg = q.reshape(b, k, kvh, groups, hd)
            scores = jnp.einsum("btkgd,bskd->bkgts", qg, kci) / np.sqrt(hd)
            if quant:
                scores = scores.astype(jnp.float32) * ksc
            scores = jnp.where(qmask, scores, jnp.finfo(scores.dtype).min)
            probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
            if quant:
                probs = probs * vsc
            probs = probs.astype(dt)
            o = jnp.einsum("bkgts,bskd->btkgd", probs, vci).reshape(b, k, h * hd)
            x = x + _matw(o, lp["wo"])
        x = _mlp(cfg, x, lp)
    with jax.named_scope("head"):
        x = _rmsnorm(x, params["ln_f"], cfg.norm_eps)
        logits = _matw(x, params["lm_head"]).astype(jnp.float32)
        out = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return _spec_accept(tok, draft, out, pos, active, rem, eosv) + (
        (kc, vc, ks, vs)[:len(cache)],
    )


def generate(
    params: Dict,
    tokens: jnp.ndarray,
    cfg: LlamaConfig,
    max_new: int,
    temperature: float = 0.0,
    key: Optional[jax.Array] = None,
    top_k: int = 0,
    top_p: float = 1.0,
) -> jnp.ndarray:
    """Autoregressive generation from a prompt [B, T0] → [B, max_new].

    Greedy at ``temperature == 0`` (the default), categorical sampling
    otherwise (``key`` required), with the standard serving controls:
    ``top_k > 0`` restricts sampling to the k most likely tokens,
    ``top_p < 1`` to the smallest nucleus whose probability mass
    reaches p (the first token always stays eligible). Both compose
    (k-truncate, then nucleus within it). One jit per (shape, cfg,
    top_k, top_p-active): prefill + a ``lax.scan`` decode loop over
    positions with the KV cache as carry; temperature and p are traced
    scalars (sweeping them costs zero recompiles). Accepts params
    straight from ``runtime.export.load_export`` (cast float leaves to
    ``cfg.dtype``-compatible types first if the export was bf16 and
    you want f32 math)."""
    if temperature > 0 and key is None:
        raise ValueError("sampling (temperature > 0) needs a PRNG key")
    if temperature <= 0 and (top_k or top_p < 1.0):
        # greedy argmax ignores the sampling filters — raising mirrors
        # the CLI's rejection so library callers get the same signal
        # instead of silently-inert arguments (ADVICE r5)
        raise ValueError(
            "top_k/top_p require temperature > 0 "
            "(greedy decoding ignores them)"
        )
    if max_new < 1:
        raise ValueError(f"max_new must be >= 1, got {max_new}")
    if top_k < 0 or top_k > cfg.vocab:
        raise ValueError(f"top_k must be in [0, vocab], got {top_k}")
    if not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if cfg.int8_mxu:
        # training-only throughput flag: left on it would dynamically
        # quantize SOME decode matmuls (the _qkv/_mlp shared ones) but
        # not others — silently inconsistent numerics on the serving
        # path. Serving quantization is quantize_params_int8 instead.
        import dataclasses

        cfg = dataclasses.replace(cfg, int8_mxu=False, int8_wgrad_bf16=False)
    b, t0 = tokens.shape
    run = _generate_program(
        cfg, b, t0, int(max_new), temperature > 0, int(top_k), top_p < 1.0
    )
    return run(
        params,
        tokens,
        key if key is not None else jax.random.PRNGKey(0),
        jnp.float32(temperature if temperature > 0 else 1.0),
        jnp.float32(top_p),
    )


_generate_programs: "OrderedDict" = OrderedDict()
_GENERATE_PROGRAM_CAP = 64


def _generate_program(cfg: LlamaConfig, b: int, t0: int, max_new: int,
                      sampling: bool, top_k: int, use_top_p: bool):
    """Memoized jit program per (cfg, shapes, greedy-vs-sampling,
    top_k, top_p-active) — repeat generate() calls reuse the compiled
    prefill+decode scan instead of re-tracing (a full-size model pays
    minutes per compile). Temperature and the nucleus threshold are
    TRACED scalars: sweeping them costs zero recompiles; only the
    top_k VALUE is static (it sets the truncated shape).

    The cache is LRU (move-to-end on hit, evict-oldest at the cap):
    the previous clear-everything eviction dropped the HOT serving
    program the moment a 65th shape appeared, re-paying a full-size
    compile mid-traffic."""
    cache_key = (cfg, b, t0, max_new, sampling, top_k, use_top_p)
    run = _generate_programs.get(cache_key)
    if run is not None:
        _generate_programs.move_to_end(cache_key)
        return run
    kvh, hd, L = cfg.n_kv_heads, cfg.head_dim, cfg.n_layers
    max_len = t0 + max_new

    @jax.jit
    def edl_llama_generate(params, tokens, key, temperature, top_p):
        logits, ks, vs = _prefill(params, tokens, cfg)
        pad = jnp.zeros((L, b, max_len - t0, kvh, hd), ks.dtype)
        kc = jnp.concatenate([ks, pad], axis=2)
        vc = jnp.concatenate([vs, pad], axis=2)

        def sample(logits, k):
            if not sampling:
                return jnp.argmax(logits, axis=-1)
            if not top_k and not use_top_p:
                return jax.random.categorical(k, logits / temperature, axis=-1)
            # truncate to the top-m subspace (descending), sample the
            # INDEX within it, then map back through the gathered ids —
            # nucleus filtering only ever sees the sorted tail
            m = top_k if top_k else logits.shape[-1]
            vals, idx = jax.lax.top_k(logits, m)  # [B, m] descending
            scaled = vals / temperature
            if use_top_p:
                probs = jax.nn.softmax(scaled, axis=-1)
                # exclusive cumulative mass: the first token's mass is
                # 0, so it is always eligible (top_p -> 0 degenerates
                # to greedy, never to an empty support)
                cum = jnp.cumsum(probs, axis=-1) - probs
                scaled = jnp.where(cum < top_p, scaled, -jnp.inf)
            j = jax.random.categorical(k, scaled, axis=-1)
            return jnp.take_along_axis(idx, j[:, None], axis=-1)[:, 0]

        def step(carry, i):
            logits, kc, vc, k = carry
            k, sub = jax.random.split(k)
            with jax.named_scope("head"):
                tok = sample(logits, sub).astype(jnp.int32)
            logits, kc, vc = _decode_step(params, tok, t0 + i, kc, vc, cfg)
            return (logits, kc, vc, k), tok

        (_, _, _, _), toks = jax.lax.scan(
            step, (logits, kc, vc, key), jnp.arange(max_new)
        )
        return jnp.swapaxes(toks, 0, 1)  # [B, max_new]

    # the function's name is the program's: compile telemetry
    # (edl_compile_seconds{program="edl_llama_generate"}) and the
    # profiler's XLA Modules line both read it
    run = edl_llama_generate
    while len(_generate_programs) >= _GENERATE_PROGRAM_CAP:
        _generate_programs.popitem(last=False)  # evict least-recent
    _generate_programs[cache_key] = run
    return run


def train_flops_per_token(cfg: LlamaConfig, seq: int) -> float:
    """Model FLOPs per trained token (fwd+bwd), the MFU numerator:
    6 × matmul params (embedding lookup excluded, lm_head included)
    plus causal attention 12·L·(T/2)·d_attn. Remat recompute is NOT
    counted (MFU convention: model FLOPs, not hardware FLOPs).

    The formula itself lives in ``obs/costmodel.py`` — the ONE analytic
    cost model bench.py, exp_mfu, and the live efficiency gauges share
    (tests/test_costmodel.py pins the call sites agree)."""
    return _costmodel.train_flops_per_token(cfg, seq)


def make_loss_fn(cfg: LlamaConfig, plan: Optional[MeshPlan] = None, mesh=None):
    """Next-token cross entropy; batch = {tokens [B, T+1]}.

    ``plan``/``mesh`` flow through to :func:`forward` to activate sp/pp
    (the trainable-strategy contract: the worker runtime builds the loss
    via ``Workload.make_loss(plan, mesh)`` after every rendezvous, so
    the program matches the current elastic mesh). The [B, T+1] token
    feed stays batch-sharded — int32 tokens are negligible bytes; the
    sp sharding starts at the embedding output inside ``forward``."""

    def loss_fn(params, batch):
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        logits = forward(params, inputs, cfg, mesh=mesh, plan=plan)
        # fused CE (logsumexp - target logit): two reductions over the
        # vocab axis instead of materializing the full [B,T,V]
        # log-softmax (4+ GB of f32 at the bench config)
        import optax

        if plan is not None and plan.axis_size("sp") > 1:
            # align targets with the sequence-sharded logits so the CE
            # stays local to each sp shard (the mean is global)
            targets = jax.lax.with_sharding_constraint(
                targets, plan.sequence_sharding(mesh, rank=2)
            )
        from edl_tpu.models.losses import row_mean

        with jax.named_scope("loss"):
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits, targets
            )
            # per-row mean over T, then the runtime's real-row weighting
            # (identical to the global mean when no "_w" rides the batch)
            return row_mean(jnp.mean(ce, axis=-1), batch)

    return loss_fn


def synthetic_tokens(
    rng: np.random.RandomState, batch: int, seq: int, vocab: int
) -> Dict[str, np.ndarray]:
    """Markov-ish synthetic text: next token correlates with current, so
    the loss curve has signal."""
    toks = np.zeros((batch, seq + 1), np.int32)
    toks[:, 0] = rng.randint(0, vocab, batch)
    drift = rng.randint(1, 7, (batch,))
    for t in range(1, seq + 1):
        noise = rng.rand(batch) < 0.1
        toks[:, t] = np.where(
            noise, rng.randint(0, vocab, batch), (toks[:, t - 1] + drift) % vocab
        )
    return {"tokens": toks}
