"""Mixture-of-Experts transformer — the expert-parallel model family.

The reference has no MoE anywhere (SURVEY §2.5: "Expert parallelism:
NO — optional"); this makes EP a full model family rather than just a
layer: a Llama-style decoder whose FFN is a top-k routed expert FFN
(``parallel.moe.moe_ffn``: two-matrix ReLU experts, not SwiGLU; queues
bound by a capacity factor, overflow dropped), with the expert
dimension of every expert weight sharded over the ``ep`` mesh axis so the
dispatch/combine einsums lower to all-to-all-style collectives over
ICI. Attention, RoPE, rmsnorm, and the flash kernel are shared with
models/llama.py — one implementation of the hot path.

The load-balance auxiliary loss (standard mean-prob x mean-assign) is
folded into the training loss with coefficient ``aux_coef``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from edl_tpu.models import llama as _ll
from edl_tpu.parallel.mesh import MeshPlan
from edl_tpu.parallel.moe import moe_ffn

# shared synthetic data: the loss-curve contract is the same
synthetic_tokens = _ll.synthetic_tokens


@dataclass(frozen=True)
class MoEConfig:
    vocab: int = 32768
    d_model: int = 1024
    n_layers: int = 8
    n_heads: int = 8
    n_kv_heads: int = 4
    d_ff: int = 2816  # per-expert hidden
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_coef: float = 0.01
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    use_flash: bool = False
    remat: bool = False
    # run the attention projections + expert batched matmuls on the
    # MXU's double-rate int8 path (training-only, same contract as
    # LlamaConfig.int8_mxu: weights at rest stay dense, the flag never
    # rides the export record)
    int8_mxu: bool = False
    # with int8_mxu: keep wgrad on the bf16 path (same contract as
    # LlamaConfig.int8_wgrad_bf16 — the outlier-resolution escape
    # hatch; training-only, never rides the export record)
    int8_wgrad_bf16: bool = False

    def to_meta(self) -> dict:
        """JSON-safe architecture record for export manifests
        (the one shared rule: models/meta.py)."""
        from edl_tpu.models.meta import dataclass_meta

        meta = dataclass_meta(self, "moe")
        meta.pop("int8_mxu")  # training-only: never a load contract
        meta.pop("int8_wgrad_bf16")
        return meta

    @classmethod
    def from_meta(cls, meta: dict) -> "MoEConfig":
        from edl_tpu.models.meta import dataclass_from_meta

        return dataclass_from_meta(cls, meta, "moe")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @classmethod
    def tiny(cls, vocab: int = 256) -> "MoEConfig":
        return cls(
            vocab=vocab,
            d_model=64,
            n_layers=2,
            n_heads=4,
            n_kv_heads=2,
            d_ff=96,
            n_experts=4,
            dtype=jnp.float32,
        )

    def _llama_view(self) -> _ll.LlamaConfig:
        """The attention-relevant subset, for reusing llama's blocks."""
        return _ll.LlamaConfig(
            vocab=self.vocab,
            d_model=self.d_model,
            n_layers=self.n_layers,
            n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads,
            d_ff=self.d_ff,
            rope_theta=self.rope_theta,
            norm_eps=self.norm_eps,
            dtype=self.dtype,
            use_flash=self.use_flash,
            remat=self.remat,
        )


def init_params(key: jax.Array, cfg: MoEConfig) -> Dict:
    """Scan-stacked tree: per-layer weights carry a leading [L] axis;
    expert weights carry [L, E, ...]."""
    k = jax.random.split(key, 12)
    d, h, kv, hd, ff, L, E = (
        cfg.d_model,
        cfg.n_heads,
        cfg.n_kv_heads,
        cfg.head_dim,
        cfg.d_ff,
        cfg.n_layers,
        cfg.n_experts,
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
            "router": norm_init(k[5], L, d, E, scale=0.02),
            "w_in": norm_init(k[6], L, E, d, ff, scale=d**-0.5),
            "w_out": norm_init(k[7], L, E, ff, d, scale=ff**-0.5),
        },
        "ln_f": jnp.ones((d,), jnp.float32),
        "lm_head": norm_init(k[8], d, cfg.vocab, scale=d**-0.5),
    }


def param_pspecs(cfg: MoEConfig, plan: MeshPlan) -> Dict:
    """Experts over ep, expert-internal width over tp, dense dims over
    fsdp — with llama's divisibility fallback (replicate on any axis
    that does not divide)."""
    tp = "tp" if plan.axis_size("tp") > 1 else None
    fs = "fsdp" if plan.axis_size("fsdp") > 1 else None
    ep = "ep" if plan.axis_size("ep") > 1 else None
    d, h, kv, hd, ff, L, E, V = (
        cfg.d_model,
        cfg.n_heads,
        cfg.n_kv_heads,
        cfg.head_dim,
        cfg.d_ff,
        cfg.n_layers,
        cfg.n_experts,
        cfg.vocab,
    )

    from edl_tpu.parallel.sharding import fit_pspec

    def fit(shape, *axes):
        return fit_pspec(plan, shape, *axes)

    return {
        "embed": fit((V, d), tp, fs),
        "layers": {
            "ln1": P(None, None),
            "wq": fit((L, d, h * hd), None, fs, tp),
            "wk": fit((L, d, kv * hd), None, fs, tp),
            "wv": fit((L, d, kv * hd), None, fs, tp),
            "wo": fit((L, h * hd, d), None, tp, fs),
            "ln2": P(None, None),
            "router": fit((L, d, E), None, fs, None),
            "w_in": fit((L, E, d, ff), None, ep, fs, tp),
            "w_out": fit((L, E, ff, d), None, ep, tp, fs),
        },
        "ln_f": P(None),
        "lm_head": fit((d, V), fs, tp),
    }


def _layer(cfg: MoEConfig, x: jnp.ndarray, lp: Dict):
    lcfg = cfg._llama_view()
    dt = x.dtype
    b, t, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    i8, wb = cfg.int8_mxu, cfg.int8_wgrad_bf16
    # attention block — llama's, verbatim building blocks (_matw
    # routes through the int8 MXU path when the flag is set)
    a = _ll._rmsnorm(x, lp["ln1"], cfg.norm_eps)
    q = _ll._matw(a, lp["wq"], i8, wb).reshape(b, t, h, hd)
    k = _ll._matw(a, lp["wk"], i8, wb).reshape(b, t, kv, hd)
    v = _ll._matw(a, lp["wv"], i8, wb).reshape(b, t, kv, hd)
    q, k = _ll._rope(q, cfg.rope_theta), _ll._rope(k, cfg.rope_theta)
    o = _ll.attention(q, k, v, lcfg).reshape(b, t, h * hd)
    x = x + _ll._matw(o, lp["wo"], i8, wb)
    # routed expert FFN
    m = _ll._rmsnorm(x, lp["ln2"], cfg.norm_eps)
    y, aux = moe_ffn(
        {
            "router": lp["router"].astype(dt),
            "w_in": lp["w_in"] if i8 else lp["w_in"].astype(dt),
            "w_out": lp["w_out"] if i8 else lp["w_out"].astype(dt),
        },
        m,
        k=cfg.top_k,
        capacity_factor=cfg.capacity_factor,
        int8_mxu=i8,
        int8_wgrad_bf16=wb,
    )
    return x + y, aux


def forward(params: Dict, tokens: jnp.ndarray, cfg: MoEConfig):
    """tokens [B, T] int32 → (logits [B, T, vocab], aux scalar)."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)

    def body(carry, lp):
        x, aux = carry
        x, a = _layer(cfg, x, lp)
        return (x, aux + a), None

    if cfg.remat:
        body = jax.checkpoint(body)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), params["layers"])
    x = _ll._rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = (x @ params["lm_head"].astype(cfg.dtype)).astype(jnp.float32)
    return logits, aux / cfg.n_layers


def make_loss_fn(cfg: MoEConfig):
    """Next-token CE + load-balance aux; batch = {tokens [B, T+1]}."""

    def loss_fn(params, batch):
        tokens = batch["tokens"]
        logits, aux = forward(params, tokens[:, :-1], cfg)
        targets = tokens[:, 1:]
        # fused CE (see models/llama.py): no [B,T,V] log-softmax
        # materialization
        import optax

        from edl_tpu.models.losses import row_mean

        ce = row_mean(
            jnp.mean(
                optax.softmax_cross_entropy_with_integer_labels(
                    logits, targets
                ),
                axis=-1,
            ),
            batch,
        )
        # aux (load-balance regularizer over gate statistics) stays
        # unweighted: it is a router-health term, not a data loss
        return ce + cfg.aux_coef * aux

    return loss_fn
