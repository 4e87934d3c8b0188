"""The ``deepseek_v3`` decoder layer, served: multi-head latent attention
(MLA) over a latent cache, a leading dense SwiGLU layer, then layers of
sigmoid-routed SwiGLU experts beside shared ones, none of whose tokens
is ever dropped. What ``kakaocorp/kanana-2-30b-a3b-instruct-2601``
(and DeepSeek-V3 itself, less its ``q_lora`` and its expert groups)
publishes as ``model_type: deepseek_v3``.

The layer (``x`` the residual stream, ``d`` wide; ``H`` heads of
``nope + rope`` query/key and ``v`` value columns; latent rank ``r``):

- ``x1 = rmsnorm(x)``; ``q = x1 Wq`` -> per head ``q_nope | q_rope``;
  ``x1 Wkva`` -> ``c [r] | k_rope [rope]``; ``c = rmsnorm_r(c)``; RoPE
  on ``q_rope`` of every head and on the one ``k_rope`` all heads
  share. The checkpoint keeps a rotary pair in neighbouring columns
  (``rope_interleave``): the pairs are taken apart first (evens, then
  odds), and the halves rotate as ``llama._rope`` rotates them. The
  cache holds ``c | k_rope``: ``r + rope`` numbers a token a layer.
- expanded (:func:`forward`, prefill): ``c Wkvb`` -> per head ``k_nope
  | v``; a key is ``k_nope | k_rope``; causal softmax of ``q . k /
  sqrt(nope + rope)``; ``Wo``.
- absorbed (decode): ``Wkvb`` taken apart per head into ``W_uk [r,
  nope]`` and ``W_uv [r, v]``; ``q_lat = q_nope W_uk^T | q_rope``
  scores against the cached ``c | k_rope`` directly, ``o = (sum p c)
  W_uv``. The same mathematics with one latent row a position read
  once for keys and values, instead of re-expanding every cached
  position into ``H`` keys and values each step.
- the first ``n_dense_layers`` close with a SwiGLU of width ``d_ff``;
  the others with ``sum_i w_i SwiGLU_i(x2)`` over the ``top_k`` experts
  ``parallel.moe.route_sigmoid_topk`` chooses, plus one SwiGLU of width
  ``n_shared * d_expert`` that every token passes.

Parameters are a tree of per-layer leaves (``params["layers"]["00"]``
...), not stacked on a layer axis: a stacked expert leaf would be
sliced per layer inside the decode program, and a slice that XLA
copies before the grouped matmul reads it doubles the step's traffic.
``rmsnorm``, the embedding, the head and the token choice are
``models/llama.py``'s.

Serving goes through ``serving/engine.py``'s model seam (the
``serve_*`` functions at the end): the contiguous latent cache at any
horizon. The paged, quantized-cache, chunked-prefill and verify
programs are the dense decoder's.
"""

from __future__ import annotations

from dataclasses import dataclass, fields
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from edl_tpu.models import llama as _ll
from edl_tpu.parallel import moe as _moe

_INT8_WEIGHTS = (
    "wq", "wkva", "wkvb", "wo", "w1", "w3", "w2",
    "we1", "we3", "we2", "ws1", "ws3", "ws2",
)


@dataclass(frozen=True)
class DeepseekV3Config:
    vocab: int = 128256
    d_model: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_dim: int = 128
    kv_rank: int = 512
    d_ff: int = 6144  # the leading dense layers' SwiGLU
    n_dense_layers: int = 1
    d_expert: int = 768
    n_experts: int = 128
    n_shared: int = 2
    top_k: int = 6
    route_scale: float = 2.448
    norm_topk: bool = True
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # the Pallas kernels: ``edl_flash_fwd`` in prefill,
    # ``edl_decode_attn_latent`` and ``edl_expert_mlp`` in decode. Off:
    # the dense lines and the grouped matmuls.
    use_flash: bool = False

    @classmethod
    def from_hf(cls, config: Dict, **overrides) -> "DeepseekV3Config":
        """From a published ``config.json`` of ``model_type:
        deepseek_v3``. What this file does not implement is refused
        rather than ignored."""
        for key, want in (("q_lora_rank", None), ("rope_scaling", None),
                          ("n_group", 1), ("topk_group", 1),
                          ("scoring_func", "sigmoid"),
                          ("rope_interleave", True),
                          ("attention_bias", False)):
            if config.get(key, want) != want:
                raise NotImplementedError(
                    f"deepseek_v3 with {key}={config[key]!r} (only {want!r})"
                )
        return cls(**{**dict(
            vocab=config["vocab_size"], d_model=config["hidden_size"],
            n_layers=config["num_hidden_layers"],
            n_heads=config["num_attention_heads"],
            qk_nope_dim=config["qk_nope_head_dim"],
            qk_rope_dim=config["qk_rope_head_dim"],
            v_dim=config["v_head_dim"], kv_rank=config["kv_lora_rank"],
            d_ff=config["intermediate_size"],
            n_dense_layers=config["first_k_dense_replace"],
            d_expert=config["moe_intermediate_size"],
            n_experts=config["n_routed_experts"],
            n_shared=config["n_shared_experts"],
            top_k=config["num_experts_per_tok"],
            route_scale=float(config["routed_scaling_factor"]),
            norm_topk=bool(config["norm_topk_prob"]),
            rope_theta=float(config["rope_theta"]),
            norm_eps=float(config["rms_norm_eps"]),
        ), **overrides})

    def to_meta(self) -> Dict:
        """JSON-safe architecture record (rides export manifests so
        ``edl serve`` can rebuild the config; runtime/export.py)."""
        meta = {f.name: getattr(self, f.name) for f in fields(self)}
        return {**meta, "family": "deepseek_v3",
                "dtype": jnp.dtype(self.dtype).name}

    @classmethod
    def from_meta(cls, meta: Dict) -> "DeepseekV3Config":
        if meta.get("family") != "deepseek_v3":
            raise ValueError(
                f"not a deepseek_v3 export: family={meta.get('family')!r}")
        known = {f.name for f in fields(cls)}
        kw = {k: v for k, v in meta.items() if k in known}
        return cls(**{**kw, "dtype": jnp.dtype(meta["dtype"])})

    @property
    def qk_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def latent_width(self) -> int:
        """Numbers of one position's latent row: ``c | k_rope``."""
        return self.kv_rank + self.qk_rope_dim

    @property
    def cache_width(self) -> int:
        """Columns of one position in the cache: the latent row, zero-
        padded to whole 128-lane tiles (576 -> 640). The chip's memory
        is tiled 128 wide in the minor dimension either way; left at
        576 the compiler stores the array positions-minor instead, and
        transposes all of it before and after every kernel call."""
        return -(-self.latent_width // 128) * 128

    # -- what ``obs/costmodel.py`` asks a config that prices itself --------

    def attn_params(self) -> int:
        d, h = self.d_model, self.n_heads
        return (d * h * self.qk_dim + d * self.latent_width
                + self.kv_rank * h * (self.qk_nope_dim + self.v_dim)
                + h * self.v_dim * d)

    def matmul_params(self) -> float:
        """Parameters a token multiplies: attention, its layer's SwiGLU
        (``top_k`` experts, the shared ones and the router in an expert
        layer) and the head."""
        d = self.d_model
        moe = 3 * d * self.d_expert * (self.top_k + self.n_shared) \
            + d * self.n_experts
        n_moe = self.n_layers - self.n_dense_layers
        return (self.n_layers * self.attn_params()
                + self.n_dense_layers * 3 * d * self.d_ff
                + n_moe * moe + d * self.vocab)

    def n_params(self) -> float:
        d = self.d_model
        moe = 3 * d * self.d_expert * (self.n_experts + self.n_shared) \
            + (d + 1) * self.n_experts
        n_moe = self.n_layers - self.n_dense_layers
        return (2 * self.vocab * d + d
                + self.n_layers * (self.attn_params() + 2 * d + self.kv_rank)
                + self.n_dense_layers * 3 * d * self.d_ff + n_moe * moe)

    def attn_width(self) -> int:
        """Columns of one position's scores-and-values products summed
        over heads (``4 * this * context`` FLOPs a decoded token a
        layer, expanded form)."""
        return self.n_heads * (self.qk_dim + self.v_dim) // 2

    def cache_numbers_per_token(self) -> int:
        return self.n_layers * self.cache_width

    # -- what ``serving/engine.py`` asks a config it serves from the
    # contiguous cache (its docstring gives the contract)

    def serve_cache_spec(self, slots: int, max_len: int):
        """One array: the latent cache, ``cache_width`` a position."""
        return (((self.n_layers, slots, max_len, self.cache_width),
                 self.dtype),)

    def serve_prefill(self, params, tokens, last):
        logits, rows = prefill_padded(params, tokens, last, self)
        return logits, (rows,)

    def serve_decode_block(self, params, tok, pos, active, rem, eosv, cache,
                           **kw):
        toks, tok, pos, active, rem, latent, counters = decode_horizon_slots(
            params, tok, pos, active, rem, eosv, cache[0], self, **kw)
        return toks, tok, pos, active, rem, (latent,), counters

    serve_cache_kinds = ("kv",)

    def serve_cache_read(self, held, max_len: int, block: int):
        return {"kv_read_share": _ll.positional_read_share(
            held, max_len, block)}

    def serve_attn_block(self, max_len: int) -> int:
        """Positions of one S-block the decode attention fetches (the
        engine's ``kv_read_share`` counts in these)."""
        if not self.use_flash:
            return max_len
        from edl_tpu.ops.decode_attention import latent_block_positions

        return latent_block_positions(
            self.cache_width, jnp.dtype(self.dtype).itemsize, max_len)


def layer_names(cfg: DeepseekV3Config):
    return [f"{i:02d}" for i in range(cfg.n_layers)]


def init_params(key: jax.Array, cfg: DeepseekV3Config) -> Dict:
    """Float32 normal weights (std ``fan_in ** -0.5``, the embedding
    0.02), unit norms, a small router bias."""
    d, h, e, f = cfg.d_model, cfg.n_heads, cfg.n_experts, cfg.d_expert
    keys = iter(jax.random.split(key, 16 * cfg.n_layers + 2))

    def w(shape, fan_in):
        return jax.random.normal(next(keys), shape, jnp.float32) * fan_in ** -0.5

    layers = {}
    for i, name in enumerate(layer_names(cfg)):
        lp = {
            "ln1": jnp.ones((d,), jnp.float32),
            "ln2": jnp.ones((d,), jnp.float32),
            "wq": w((d, h * cfg.qk_dim), d),
            "wkva": w((d, cfg.latent_width), d),
            "kv_norm": jnp.ones((cfg.kv_rank,), jnp.float32),
            "wkvb": w((cfg.kv_rank, h * (cfg.qk_nope_dim + cfg.v_dim)),
                      cfg.kv_rank),
            "wo": w((h * cfg.v_dim, d), h * cfg.v_dim),
        }
        if i < cfg.n_dense_layers:
            lp.update(w1=w((d, cfg.d_ff), d), w3=w((d, cfg.d_ff), d),
                      w2=w((cfg.d_ff, d), cfg.d_ff))
        else:
            fs = cfg.n_shared * f
            lp.update(
                router=w((d, cfg.n_experts), d),
                router_bias=jax.random.normal(
                    next(keys), (cfg.n_experts,), jnp.float32) * 0.02,
                we1=w((e, d, f), d), we3=w((e, d, f), d), we2=w((e, f, d), f),
                ws1=w((d, fs), d), ws3=w((d, fs), d), ws2=w((fs, d), fs),
            )
        layers[name] = lp
    return {
        "embed": jax.random.normal(next(keys), (cfg.vocab, d), jnp.float32)
        * 0.02,
        "layers": layers,
        "ln_f": jnp.ones((d,), jnp.float32),
        "lm_head": w((d, cfg.vocab), d),
    }


def quantize_params_int8(params: Dict) -> Dict:
    """``llama.quantize_params_int8`` for this tree: every matrix a
    decode step streams (the experts' too, a scale a column of each
    expert) becomes ``{"q8", "s8"}``; the router, norms and embedding
    stay as they are."""
    from edl_tpu.ops.int8_matmul import absmax_quant

    def q(w):
        q8, s = absmax_quant(w, -2)
        return {"q8": q8, "s8": s[..., 0, :]}

    out = dict(params)
    out["layers"] = {
        name: {k: (q(v) if k in _INT8_WEIGHTS else v) for k, v in lp.items()}
        for name, lp in params["layers"].items()
    }
    out["lm_head"] = q(params["lm_head"])
    return out


# -- the layer ---------------------------------------------------------------


def _rope_pairs(x: jnp.ndarray, theta: float, positions) -> jnp.ndarray:
    """RoPE on [B, T, H, rope] whose rotary pairs lie in neighbouring
    columns: evens then odds, then ``llama._rope``'s rotation of the
    two halves. Queries and keys come out in the same (de-interleaved)
    order, which is all a dot product asks."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return _ll._rope(x, theta, positions)


def _latent(cfg: DeepseekV3Config, a: jnp.ndarray, lp: Dict, positions=None):
    """Normed input [B, T, d] -> (q_nope [B, T, H, nope], q_rope [B, T,
    H, rope], row [B, T, cache_width]): the queries, and what the cache
    holds of each position (``c`` normed, ``k_rope`` rotated, zeros up
    to the cache's width)."""
    b, t, _ = a.shape
    q = _ll._matw(a, lp["wq"]).reshape(b, t, cfg.n_heads, cfg.qk_dim)
    q_nope, q_rope = q[..., :cfg.qk_nope_dim], q[..., cfg.qk_nope_dim:]
    ckr = _ll._matw(a, lp["wkva"])
    c = _ll._rmsnorm(ckr[..., :cfg.kv_rank], lp["kv_norm"], cfg.norm_eps)
    k_rope = _rope_pairs(
        ckr[..., None, cfg.kv_rank:], cfg.rope_theta, positions)[:, :, 0]
    q_rope = _rope_pairs(q_rope, cfg.rope_theta, positions)
    pad = jnp.zeros((b, t, cfg.cache_width - cfg.latent_width), c.dtype)
    return q_nope, q_rope, jnp.concatenate([c, k_rope, pad], axis=-1)


def attention_expanded(cfg: DeepseekV3Config, q_nope, q_rope, row, lp):
    """Causal attention with every position's keys and values expanded
    out of its latent row: [B, T, H * v]."""
    b, t, h, _ = q_nope.shape
    with jax.named_scope("attn.latent_expand"):
        kv = _ll._matw(row[..., :cfg.kv_rank], lp["wkvb"]).reshape(
            b, t, h, cfg.qk_nope_dim + cfg.v_dim)
        k_rope = jnp.broadcast_to(
            row[:, :, None, cfg.kv_rank:cfg.latent_width],
            (b, t, h, cfg.qk_rope_dim))
        k = jnp.concatenate([kv[..., :cfg.qk_nope_dim], k_rope], axis=-1)
        v = kv[..., cfg.qk_nope_dim:]
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
    if cfg.use_flash:
        from edl_tpu.ops.flash_attention import attention_auto

        # the forward kernel with a value width of its own (192 / 128)
        o = attention_auto(q, k, v, causal=True)
    else:
        s = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(cfg.qk_dim)
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask[None, None], s, jnp.finfo(s.dtype).min)
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
        o = jnp.einsum("bhts,bshd->bthd", p, v)
    return o.reshape(b, t, h * cfg.v_dim)


def _kvb_parts(cfg: DeepseekV3Config, wkvb):
    """``Wkvb`` [r, H * (nope + v)] (or its int8 record) per head:
    (W_uk [r, H, nope], W_uv [r, H, v], their column scales or None)."""
    h, n = cfg.n_heads, cfg.qk_nope_dim
    if isinstance(wkvb, dict):
        w = wkvb["q8"].reshape(cfg.kv_rank, h, -1)
        s = wkvb["s8"].reshape(h, -1)
        return w[..., :n], w[..., n:], s[:, :n], s[:, n:]
    w = wkvb.reshape(cfg.kv_rank, h, -1)
    return w[..., :n], w[..., n:], None, None


def slot_attention_latent_dense(q_lat, cache_i, pos, rank: int, scale: float):
    """The dense form of one layer's absorbed slot attention: q_lat [B,
    H, W] against ALL ``S`` rows of cache_i [B, S, W], masked to ``<=
    pos[row]``. The ``use_flash=False`` path, and what
    ``ops.decode_attention.decode_attention_latent`` is tested
    against."""
    s = jnp.einsum("bhw,bsw->bhs", q_lat, cache_i) * scale
    mask = (jnp.arange(cache_i.shape[1])[None, :] <= pos[:, None])[:, None]
    s = jnp.where(mask, s, jnp.finfo(s.dtype).min)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q_lat.dtype)
    return jnp.einsum("bhs,bsr->bhr", p, cache_i[..., :rank])


def attention_absorbed(cfg, q_nope, q_rope, cache, layer: int, read_to, lp):
    """One new query a slot against the latent cache, never expanded:
    q_nope [B, H, nope], q_rope [B, H, rope], cache [L, B, S, W] (W =
    ``cfg.cache_width``) with this step's rows already written, read_to [B] each slot's last
    live position. Returns [B, H * v]."""
    dt = q_nope.dtype
    w_uk, w_uv, s_uk, s_uv = _kvb_parts(cfg, lp["wkvb"])
    with jax.named_scope("attn.latent_absorb"):
        if s_uk is not None:
            q_nope = (q_nope.astype(jnp.float32) * s_uk).astype(dt)
        pad = jnp.zeros(
            q_rope.shape[:2] + (cfg.cache_width - cfg.latent_width,), dt)
        q_lat = jnp.concatenate(
            [jnp.einsum("bhn,rhn->bhr", q_nope, w_uk.astype(dt)), q_rope,
             pad], axis=-1)
        scale = 1.0 / float(np.sqrt(cfg.qk_dim))
        if cfg.use_flash:
            from edl_tpu.ops.decode_attention import decode_attention_latent
            from edl_tpu.ops.flash_attention import _INTERPRET

            o_lat = decode_attention_latent(
                q_lat, cache, read_to, jnp.int32(layer), rank=cfg.kv_rank,
                sm_scale=scale, interpret=_INTERPRET.get(),
            )
        else:
            o_lat = slot_attention_latent_dense(
                q_lat, cache[layer], read_to, cfg.kv_rank, scale)
        o = jnp.einsum("bhr,rhv->bhv", o_lat, w_uv.astype(dt))
        if s_uv is not None:
            o = (o.astype(jnp.float32) * s_uv).astype(dt)
    return o.reshape(o.shape[0], -1)


def _swiglu(m, w1, w3, w2):
    return _ll._matw(jax.nn.silu(_ll._matw(m, w1)) * _ll._matw(m, w3), w2)


def _ffn(cfg: DeepseekV3Config, x: jnp.ndarray, lp: Dict, rows=None):
    """The layer's second half on [B, T, d], residual included. Returns
    (x, load): ``load`` is ``parallel.moe.expert_load`` of an expert
    layer's routing over ``rows`` [B * T] (None for a dense layer)."""
    if "router" not in lp:
        with jax.named_scope("mlp"):
            m = _ll._rmsnorm(x, lp["ln2"], cfg.norm_eps)
            return x + _swiglu(m, lp["w1"], lp["w3"], lp["w2"]), None
    with jax.named_scope("moe"):
        m = _ll._rmsnorm(x, lp["ln2"], cfg.norm_eps)
        flat = m.reshape(-1, m.shape[-1])
        idx, w = _moe.route_sigmoid_topk(
            flat, lp["router"], lp["router_bias"], cfg.top_k,
            cfg.route_scale, cfg.norm_topk)
        y = _moe.moe_dropless(
            flat, idx, w, lp["we1"], lp["we3"], lp["we2"],
            kernel=cfg.use_flash)
        with jax.named_scope("moe.shared"):
            y = y + _swiglu(flat, lp["ws1"], lp["ws3"], lp["ws2"])
        load = _moe.expert_load(idx, cfg.n_experts, rows)
        return x + y.reshape(x.shape), load


def _layers(params: Dict, cfg: DeepseekV3Config):
    return [params["layers"][name] for name in layer_names(cfg)]


def _run_expanded(params, tokens, cfg):
    """Embedding and every layer in the expanded form over [B, T]:
    (x [B, T, d], the latent rows [L, B, T, cache_width])."""
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    rows = []
    for lp in _layers(params, cfg):
        with jax.named_scope("attn"):
            a = _ll._rmsnorm(x, lp["ln1"], cfg.norm_eps)
            q_nope, q_rope, row = _latent(cfg, a, lp)
            o = attention_expanded(cfg, q_nope, q_rope, row, lp)
            x = x + _ll._matw(o, lp["wo"])
        rows.append(row)
        x, _ = _ffn(cfg, x, lp)
    return x, jnp.stack(rows)


def forward(params: Dict, tokens: jnp.ndarray, cfg: DeepseekV3Config):
    """tokens [B, T] int32 -> logits [B, T, vocab] (float32)."""
    x, _ = _run_expanded(params, tokens, cfg)
    with jax.named_scope("head"):
        x = _ll._rmsnorm(x, params["ln_f"], cfg.norm_eps)
        return _ll._matw(x, params["lm_head"]).astype(jnp.float32)


def prefill_padded(params: Dict, tokens: jnp.ndarray, last, cfg):
    """``llama.prefill_padded`` for this layer: an END-padded prompt
    batch [B, Tb] -> (logits [B, V] at each row's ``last`` index, the
    latent cache rows [L, B, Tb, cache_width]). Expanded attention: a
    prompt's keys and values are used once, by its own queries."""
    b = tokens.shape[0]
    x, rows = _run_expanded(params, tokens, cfg)
    with jax.named_scope("head"):
        x = _ll._rmsnorm(x, params["ln_f"], cfg.norm_eps)
        logits = _ll._matw(
            x[jnp.arange(b), last], params["lm_head"]).astype(jnp.float32)
    return logits, rows


def decode_step_slots(
    params: Dict,
    tok: jnp.ndarray,
    pos: jnp.ndarray,
    cache: jnp.ndarray,
    cfg: DeepseekV3Config,
    live: Optional[jnp.ndarray] = None,
):
    """``llama.decode_step_slots`` over the latent cache [L, B, S,
    cache_width], absorbed attention: (logits [B, V], cache, load). ``load``
    is (experts_hit_share, expert_load_max_over_mean) of the ``live``
    rows' routing, each the mean over the expert layers."""
    b = tok.shape[0]
    rows = jnp.arange(b)
    read_to = pos if live is None else jnp.where(live, pos, 0)
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tok[:, None], axis=0).astype(cfg.dtype)
    loads = []
    for i, lp in enumerate(_layers(params, cfg)):
        with jax.named_scope("attn"):
            a = _ll._rmsnorm(x, lp["ln1"], cfg.norm_eps)
            q_nope, q_rope, row = _latent(cfg, a, lp, pos[:, None])
            cache = cache.at[i, rows, pos].set(row[:, 0])
            o = attention_absorbed(
                cfg, q_nope[:, 0], q_rope[:, 0], cache, i, read_to, lp)
            x = x + _ll._matw(o[:, None], lp["wo"])
        x, load = _ffn(cfg, x, lp, live)
        if load is not None:
            loads.append(load)
    with jax.named_scope("head"):
        x = _ll._rmsnorm(x, params["ln_f"], cfg.norm_eps)
        logits = _ll._matw(x[:, 0], params["lm_head"]).astype(jnp.float32)
    load = tuple(jnp.mean(jnp.stack(v)) for v in zip(*loads)) if loads \
        else (jnp.float32(0), jnp.float32(0))
    return logits, cache, load


def decode_horizon_slots(
    params, tok, pos, active, rem, eosv, cache, cfg: DeepseekV3Config,
    horizon: int, key=None, temperature=None, sampling: bool = False,
):
    """``llama.decode_horizon_slots`` over the latent cache: the scan,
    the token choice and the freezing of finished rows are
    ``llama.horizon_scan``'s. Returns ``(toks [B, horizon], tok, pos, active, rem, cache,
    counters)``; ``counters`` holds the routing's two numbers, the mean
    over the block's steps."""

    def step(tok, pos, cache, active):
        return decode_step_slots(params, tok, pos, cache, cfg, live=active)

    toks, tok, pos, active, rem, cache, (hit, skew) = _ll.horizon_scan(
        step, tok, pos, active, rem, eosv, cache, horizon,
        key=key, temperature=temperature, sampling=sampling)
    counters = {"experts_hit_share": jnp.mean(hit),
                "expert_load_max_over_mean": jnp.mean(skew)}
    return toks, tok, pos, active, rem, cache, counters
