"""The ``glm_moe_dsa`` decoder layer, served: multi-head latent attention
with a low-rank query, a learned indexer that chooses which cached
positions each query attends, leading dense SwiGLU layers, then layers
of sigmoid-routed SwiGLU experts beside a shared one, of which this
chip may hold a share. What ``zai-org/GLM-5`` publishes as
``model_type: glm_moe_dsa``. The latent row and its RoPE, the absorbed
form, the router and both expert kernels are ``models/deepseek_v3.py``'s
and ``parallel/moe.py``'s, imported.

The layer (``x`` the residual stream, ``a = rmsnorm(x)``):

- query: ``cq = rmsnorm(a Wqa)`` (``q_rank`` wide); ``q = cq Wqb`` ->
  per head ``q_nope | q_rope``, RoPE on ``q_rope`` (pairs in
  neighbouring columns);
- latent: ``a Wkva`` -> ``c | k_rope``, ``c`` normed, ``k_rope`` rotated:
  the first cache array holds ``c | k_rope`` (``cache_width`` columns);
- indexer: ``qI = cq WqI`` -> ``index_heads`` heads of ``index_dim``
  (the first ``qk_rope_dim`` columns rotated), ``kI = layernorm(a WkI)``
  (one key a position, the same split and rotation; the second cache
  array holds it), ``w = a Ww * index_heads ** -0.5 * index_dim **
  -0.5``. ``I[t, s] = sum_h w[t, h] relu(qI[t, h] . kI[s])`` for ``s <=
  t``, accumulated in float32; ``S_t`` is the ``index_topk`` positions
  of largest ``I[t, .]`` (every ``s <= t`` while there are no more),
  ties towards the lower position;
- attention over ``S_t`` only, scale ``1 / sqrt(nope + rope)``: absorbed
  in a decode step (``q_nope W_uk^T | q_rope`` against the latent rows,
  ``o = (sum p c) W_uv``), expanded in a prefill (``c Wkvb`` -> per head
  ``k_nope | v``); a prompt's first ``index_topk`` positions, where
  every earlier position is attended, run
  ``deepseek_v3.attention_expanded`` (the flash kernel under
  ``use_flash``);
- the SwiGLU of a dense layer, or ``sum_i w_i SwiGLU_i(m)`` over the
  ``top_k`` of ``n_experts`` by ``parallel.moe.route_sigmoid_topk`` plus
  one shared SwiGLU. The chip holds the experts ``first_expert ..
  first_expert + experts_held`` and adds their terms alone: the weights
  are normalised over all ``top_k`` chosen, and the partial sum goes on.

**Prefill in pieces.** A bucket is walked in pieces of ``PREFILL_PIECE``
query rows inside one program (a scan; the latent rows and index keys
built so far are the carry), every layer inside a piece: index scores
against the earlier positions in blocks of keys, the selection as a
mask (the ``index_topk``-th largest score by bisection on the scores'
bits, no sort), and a masked sweep over the earlier latent rows in
blocks, each expanded as it is visited, with a running softmax. The
sweep of a piece is one Pallas kernel a layer
(``edl_sparse_prefill_attn``, ``ops/sparse_prefill_attention.py``: the
running maximum, sum and accumulator stay in VMEM across the key
blocks) under ``use_flash`` where ``Wkvb`` is a plain array and pieces
and key blocks are whole tiles (``_kernel_key_block``); otherwise
``_sweep``, the same mathematics in XLA's own lines, which the kernel is
tested against (an int8 record for ``Wkvb``, ``use_flash=False``, the
tests' tiny pieces). A piece that starts past ``last`` is skipped; a
position past ``last`` is never selected, and its rows are written as
zeros.

**Decode.** One query a slot: index scores against the slot's index
keys in blocks up to the farthest live position, the selection as a
mask (the same bisection), and absorbed attention over the slot's live
latent rows with the positions not chosen masked out
(``edl_decode_attn_latent`` with its ``chosen`` operand under
``use_flash``).

Serving goes through ``serving/engine.py``'s model seam: the contiguous
cache, two positional arrays. The multi-token-prediction module of the
checkpoint is not served (it does not enter the next-token
distribution).
"""

from __future__ import annotations

from dataclasses import dataclass, fields
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from edl_tpu.models import deepseek_v3 as _ds
from edl_tpu.models import llama as _ll
from edl_tpu.parallel import moe as _moe

_INT8_WEIGHTS = _ds._INT8_WEIGHTS + ("wqa", "wqb")
_NEG = -1e30  # a masked score: finite, so a row of them has no NaN


@dataclass(frozen=True)
class GlmDsaConfig:
    vocab: int = 154880
    d_model: int = 6144
    n_layers: int = 78
    n_heads: int = 64
    q_rank: int = 2048
    qk_nope_dim: int = 192
    qk_rope_dim: int = 64
    v_dim: int = 256
    kv_rank: int = 512
    index_heads: int = 32
    index_dim: int = 128
    index_topk: int = 2048
    d_ff: int = 12288  # the leading dense layers' SwiGLU
    n_dense_layers: int = 3
    d_expert: int = 2048
    n_experts: int = 256  # the router's width: every expert is scored
    # the share of them whose weights are here: ``first_expert ..
    # first_expert + experts_held`` (None: all of them)
    experts_held: Optional[int] = None
    first_expert: int = 0
    n_shared: int = 1
    top_k: int = 8
    route_scale: float = 2.5
    norm_topk: bool = True
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    index_norm_eps: float = 1e-6  # the index key's LayerNorm
    dtype: Any = jnp.bfloat16
    # the Pallas kernels: ``edl_flash_fwd`` over a prompt's first
    # positions, ``edl_decode_attn_latent`` in decode, ``edl_expert_mlp``
    # / ``edl_grouped_expert_mlp``. Off: the dense lines and the grouped
    # matmuls.
    use_flash: bool = False

    @classmethod
    def from_hf(cls, config: Dict, **overrides) -> "GlmDsaConfig":
        """From a published ``config.json`` of ``model_type:
        glm_moe_dsa``. What this file does not implement is refused
        rather than ignored; ``num_nextn_predict_layers`` is not among
        those: the prediction module is left out of serving whatever
        its count."""
        rope = config.get("rope_parameters") or {}
        for key, got, want in (
                ("n_group", config.get("n_group", 1), 1),
                ("topk_group", config.get("topk_group", 1), 1),
                ("scoring_func", config.get("scoring_func", "sigmoid"),
                 "sigmoid"),
                ("rope_interleave", config.get("rope_interleave", True), True),
                ("indexer_rope_interleave",
                 config.get("indexer_rope_interleave", True), True),
                ("attention_bias", config.get("attention_bias", False), False),
                ("rope_parameters.rope_type",
                 rope.get("rope_type", "default"), "default"),
                ("rope_scaling", config.get("rope_scaling"), None)):
            if got != want:
                raise NotImplementedError(
                    f"glm_moe_dsa with {key}={got!r} (only {want!r})")
        for key in ("q_lora_rank", "index_topk", "index_n_heads",
                    "index_head_dim"):
            if not config.get(key):
                raise NotImplementedError(
                    f"glm_moe_dsa without {key}: a layer whose every key "
                    f"is attended is models/deepseek_v3.py's")
        return cls(**{**dict(
            vocab=config["vocab_size"], d_model=config["hidden_size"],
            n_layers=config["num_hidden_layers"],
            n_heads=config["num_attention_heads"],
            q_rank=config["q_lora_rank"],
            qk_nope_dim=config["qk_nope_head_dim"],
            qk_rope_dim=config["qk_rope_head_dim"],
            v_dim=config["v_head_dim"], kv_rank=config["kv_lora_rank"],
            index_heads=config["index_n_heads"],
            index_dim=config["index_head_dim"],
            index_topk=config["index_topk"],
            d_ff=config["intermediate_size"],
            n_dense_layers=config["first_k_dense_replace"],
            d_expert=config["moe_intermediate_size"],
            n_experts=config["n_routed_experts"],
            n_shared=config["n_shared_experts"],
            top_k=config["num_experts_per_tok"],
            route_scale=float(config["routed_scaling_factor"]),
            norm_topk=bool(config["norm_topk_prob"]),
            rope_theta=float(rope.get("rope_theta",
                                      config.get("rope_theta", 1e6))),
            norm_eps=float(config["rms_norm_eps"]),
        ), **overrides})

    def to_meta(self) -> Dict:
        """JSON-safe architecture record (rides export manifests so
        ``edl serve`` can rebuild the config; runtime/export.py)."""
        meta = {f.name: getattr(self, f.name) for f in fields(self)}
        return {**meta, "family": "glm_dsa",
                "dtype": jnp.dtype(self.dtype).name}

    @classmethod
    def from_meta(cls, meta: Dict) -> "GlmDsaConfig":
        if meta.get("family") != "glm_dsa":
            raise ValueError(
                f"not a glm_dsa export: family={meta.get('family')!r}")
        known = {f.name for f in fields(cls)}
        kw = {k: v for k, v in meta.items() if k in known}
        return cls(**{**kw, "dtype": jnp.dtype(meta["dtype"])})

    @property
    def held(self) -> int:
        return self.n_experts if self.experts_held is None \
            else self.experts_held

    @property
    def qk_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def latent_width(self) -> int:
        """Numbers of one position's latent row: ``c | k_rope``."""
        return self.kv_rank + self.qk_rope_dim

    @property
    def cache_width(self) -> int:
        """Columns of one position in the first cache array: the latent
        row zero-padded to whole 128-lane tiles (576 -> 640), as
        ``DeepseekV3Config.cache_width`` says why."""
        return -(-self.latent_width // 128) * 128

    @property
    def index_scale(self) -> float:
        return self.index_heads ** -0.5 * self.index_dim ** -0.5

    # -- what ``obs/costmodel.py`` asks a config that prices itself --------

    def attn_params(self) -> int:
        """Attention and indexer matrices of one layer."""
        d, h = self.d_model, self.n_heads
        return (d * self.q_rank + self.q_rank * h * self.qk_dim
                + d * self.latent_width
                + self.kv_rank * h * (self.qk_nope_dim + self.v_dim)
                + h * self.v_dim * d
                + self.q_rank * self.index_heads * self.index_dim
                + d * self.index_dim + d * self.index_heads)

    def matmul_params(self) -> float:
        """Parameters a token multiplies here: attention and indexer,
        its layer's SwiGLU (of an expert layer the shared expert, the
        router and the ``top_k`` experts' share held here) and the
        head."""
        d = self.d_model
        routed = self.top_k * self.held / self.n_experts
        moe = 3 * d * self.d_expert * (routed + self.n_shared) \
            + d * self.n_experts
        n_moe = self.n_layers - self.n_dense_layers
        return (self.n_layers * self.attn_params()
                + self.n_dense_layers * 3 * d * self.d_ff
                + n_moe * moe + d * self.vocab)

    def n_params(self) -> float:
        d = self.d_model
        moe = 3 * d * self.d_expert * (self.held + self.n_shared) \
            + (d + 1) * self.n_experts
        n_moe = self.n_layers - self.n_dense_layers
        norms = 2 * d + self.q_rank + self.kv_rank + 2 * self.index_dim
        return (2 * self.vocab * d + d
                + self.n_layers * (self.attn_params() + norms)
                + self.n_dense_layers * 3 * d * self.d_ff + n_moe * moe)

    def attn_width(self) -> int:
        """Columns of one attended position's scores-and-values
        products summed over heads, absorbed form (``4 * this *
        positions`` FLOPs a decoded token a layer)."""
        return self.n_heads * (self.latent_width + self.kv_rank) // 2

    def cache_numbers_per_token(self) -> int:
        return self.n_layers * (self.cache_width + self.index_dim)

    # -- what ``serving/engine.py`` asks a config it serves from the
    # contiguous cache (its comment gives the contract)

    def serve_cache_spec(self, slots: int, max_len: int):
        """Two positional arrays: the latent rows and the index keys."""
        return (((self.n_layers, slots, max_len, self.cache_width),
                 self.dtype),
                ((self.n_layers, slots, max_len, self.index_dim),
                 self.dtype))

    serve_cache_kinds = ("kv", "kv")

    def serve_prefill(self, params, tokens, last):
        return prefill_padded(params, tokens, last, self)

    def serve_decode_block(self, params, tok, pos, active, rem, eosv, cache,
                           **kw):
        return decode_horizon_slots(
            params, tok, pos, active, rem, eosv, cache, self, **kw)

    def serve_prefill_pieces(self, bucket: int) -> int:
        """Pieces a prefill of ``bucket`` positions is walked in."""
        return -(-bucket // min(PREFILL_PIECE, bucket))

    def serve_attn_block(self, max_len: int) -> int:
        """Positions of one S-block of latent rows the decode attention
        fetches (``max_len``: the dense lines read the slot whole)."""
        if not self.use_flash:
            return max_len
        from edl_tpu.ops.decode_attention import latent_block_positions

        return latent_block_positions(
            self.cache_width, jnp.dtype(self.dtype).itemsize, max_len)

    def serve_cache_read(self, held, max_len: int, block: int):
        """``kv_read_share``: bytes of both arrays the block reads over
        the bytes they hold: every slot's index keys up to the block of
        them that holds the farthest live position of any slot, and
        each slot's latent rows up to the S-block that holds its last
        token (an idle slot one block): the rows not chosen are read
        and masked. ``kv_selected_share``: positions attended over
        positions live, and ``kv_live_tokens`` the latter, a count."""
        live = [n for n in held if n is not None]
        kb = min(_DECODE_KEY_BLOCK, max_len)
        far = min(-(-max(live, default=1) // kb) * kb, max_len)
        rows = sum(block if n is None else -(-n // block) * block
                   for n in held)
        read = len(held) * far * self.index_dim + rows * self.cache_width
        whole = len(held) * max_len * (self.index_dim + self.cache_width)
        return {
            "kv_read_share": read / whole,
            "kv_selected_share": sum(
                min(n, self.index_topk) for n in live) / max(sum(live), 1),
            "kv_live_tokens": sum(live),
        }


# Read when a program is traced; a test sets them small.
PREFILL_PIECE = 2048  # query rows of one piece of a prefill
KEY_BLOCK = 512  # key positions of one block of a prefill's sweeps
# key positions of one block of a decode step's index scores
_DECODE_KEY_BLOCK = 4096


def layer_names(cfg: GlmDsaConfig):
    return [f"{i:02d}" for i in range(cfg.n_layers)]


def init_params(key: jax.Array, cfg: GlmDsaConfig) -> Dict:
    """Float32 normal weights (std ``fan_in ** -0.5``, the embedding
    0.02), unit norms, a small router bias; an expert layer's leaves
    hold the ``held`` experts."""
    d, h, e, f = cfg.d_model, cfg.n_heads, cfg.held, cfg.d_expert
    keys = iter(jax.random.split(key, 24 * cfg.n_layers + 2))

    def w(shape, fan_in):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * fan_in ** -0.5)

    layers = {}
    for i, name in enumerate(layer_names(cfg)):
        lp = {
            "ln1": jnp.ones((d,), jnp.float32),
            "ln2": jnp.ones((d,), jnp.float32),
            "wqa": w((d, cfg.q_rank), d),
            "q_norm": jnp.ones((cfg.q_rank,), jnp.float32),
            "wqb": w((cfg.q_rank, h * cfg.qk_dim), cfg.q_rank),
            "wkva": w((d, cfg.latent_width), d),
            "kv_norm": jnp.ones((cfg.kv_rank,), jnp.float32),
            "wkvb": w((cfg.kv_rank, h * (cfg.qk_nope_dim + cfg.v_dim)),
                      cfg.kv_rank),
            "wo": w((h * cfg.v_dim, d), h * cfg.v_dim),
            "wqi": w((cfg.q_rank, cfg.index_heads * cfg.index_dim),
                     cfg.q_rank),
            "wki": w((d, cfg.index_dim), d),
            "ki_norm": jnp.ones((cfg.index_dim,), jnp.float32),
            "ki_bias": jax.random.normal(
                next(keys), (cfg.index_dim,), jnp.float32) * 0.02,
            "ww": w((d, cfg.index_heads), d),
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
    """``deepseek_v3.quantize_params_int8`` for this tree: every large
    matrix a decode step streams becomes ``{"q8", "s8"}``; the indexer,
    the router, norms and embedding stay as they are."""
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


def _rope_first(cfg: GlmDsaConfig, x, positions):
    """RoPE on the first ``qk_rope_dim`` columns of [B, T, H, n], pairs
    in neighbouring columns; the rest as they are."""
    n = cfg.qk_rope_dim
    return jnp.concatenate(
        [_ds._rope_pairs(x[..., :n], cfg.rope_theta, positions), x[..., n:]],
        axis=-1)


def _query_rank(cfg: GlmDsaConfig, a, lp: Dict):
    """The normed low-rank query ``cq`` [B, T, q_rank], which the
    attention's and the indexer's queries are both made of."""
    return _ll._rmsnorm(_ll._matw(a, lp["wqa"]), lp["q_norm"], cfg.norm_eps)


def _latent(cfg: GlmDsaConfig, a, lp: Dict, positions):
    """Normed input [B, T, d] -> (cq [B, T, q_rank], q_nope [B, T, H,
    nope], q_rope [B, T, H, rope], row [B, T, cache_width])."""
    b, t, _ = a.shape
    cq = _query_rank(cfg, a, lp)
    q = _ll._matw(cq, lp["wqb"]).reshape(b, t, cfg.n_heads, cfg.qk_dim)
    q_nope, q_rope = q[..., :cfg.qk_nope_dim], q[..., cfg.qk_nope_dim:]
    ckr = _ll._matw(a, lp["wkva"])
    c = _ll._rmsnorm(ckr[..., :cfg.kv_rank], lp["kv_norm"], cfg.norm_eps)
    k_rope = _ds._rope_pairs(
        ckr[..., None, cfg.kv_rank:], cfg.rope_theta, positions)[:, :, 0]
    q_rope = _ds._rope_pairs(q_rope, cfg.rope_theta, positions)
    pad = jnp.zeros((b, t, cfg.cache_width - cfg.latent_width), c.dtype)
    return cq, q_nope, q_rope, jnp.concatenate([c, k_rope, pad], axis=-1)


def _layernorm(x, w, bias, eps: float):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    y = ((x32 - mean) * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    return y * w.astype(x.dtype) + bias.astype(x.dtype)


def _indexer(cfg: GlmDsaConfig, a, cq, lp: Dict, positions):
    """(qI [B, T, hI, dI], kI [B, T, dI], w [B, T, hI] float32) of the
    normed input ``a`` and the normed low-rank query ``cq``."""
    b, t, _ = a.shape
    qi = _ll._matw(cq, lp["wqi"]).reshape(
        b, t, cfg.index_heads, cfg.index_dim)
    qi = _rope_first(cfg, qi, positions)
    ki = _layernorm(_ll._matw(a, lp["wki"]), lp["ki_norm"], lp["ki_bias"],
                    cfg.index_norm_eps)
    ki = _rope_first(cfg, ki[:, :, None], positions)[:, :, 0]
    w = _ll._matw(a, lp["ww"]).astype(jnp.float32) * cfg.index_scale
    return qi, ki, w


def index_scores(qi, w, ki):
    """``sum_h w[.., h] relu(qI[.., h] . kI[s])``: qi [B, T, hI, dI], w
    [B, T, hI] float32, ki [B, S, dI] -> [B, T, S] float32. The ``+
    0.0`` makes a zero of either sign the same zero, which the
    selection's order of bits then ranks as equal."""
    s = jnp.einsum("bthd,bsd->bths", qi, ki,
                   preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * w[..., None], axis=2) + 0.0


def select_mask(scores, valid, k: int):
    """The ``k`` largest of ``scores [..., S]`` among ``valid`` (all the
    valid where there are no more than ``k``), ties towards the lower
    position, as a mask. No sort: the ``k``-th largest value is found
    by bisection on the scores' bits, four bits a pass."""
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.int32)
    # float order as unsigned order; an invalid entry below every score
    u = jnp.where(bits < 0, ~bits, bits ^ jnp.int32(-2 ** 31))
    u = jnp.where(valid, jax.lax.bitcast_convert_type(u, jnp.uint32),
                  jnp.uint32(0))
    step = jnp.arange(1, 16, dtype=jnp.uint32)

    def narrow(i, kth):
        shift = (28 - 4 * i).astype(jnp.uint32)
        cand = kth[..., None] | (step << shift)  # [..., 15]
        n = jnp.sum(u[..., None, :] >= cand[..., None], axis=-1,
                    dtype=jnp.int32)
        return kth | (jnp.sum(n >= k, axis=-1).astype(jnp.uint32) << shift)

    kth = jax.lax.fori_loop(
        0, 8, narrow, jnp.zeros(u.shape[:-1], jnp.uint32))[..., None]
    above = u > kth
    tied = (u == kth) & valid
    room = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    n_tied = jnp.sum(tied, axis=-1, keepdims=True, dtype=jnp.int32)
    # the tied fit but where several positions hold the k-th value:
    # then the lowest of them, by their running count
    fits = jnp.all(n_tied <= room)
    tied = jax.lax.cond(
        fits, lambda: tied,
        lambda: tied & (jnp.cumsum(tied, axis=-1, dtype=jnp.int32) <= room))
    return (above & valid) | tied


def _sweep(cfg: GlmDsaConfig, q, lat, layer: int, sel, n_blocks, lp: Dict):
    """Attention of q [B, P, H, nope + rope] over the positions of
    ``layer`` that ``sel [B, P, Tb]`` marks, key blocks ``0 .. n_blocks
    - 1`` of the latent rows lat [L, B, Tb, W] in turn with a running
    softmax, each block's keys and values expanded out of its rows as
    it is visited: [B, P, H * v]. Expanded, not absorbed: a score and a
    value product over 256 columns a head where the absorbed form takes
    576 and 512, for one ``[block, r] x Wkvb`` a visit."""
    b, p, h, _ = q.shape
    w = lat.shape[3]
    bs, r, n = min(KEY_BLOCK, lat.shape[2]), cfg.kv_rank, cfg.qk_nope_dim
    scale = 1.0 / float(np.sqrt(cfg.qk_dim))

    def block(j, state):
        m, l, acc = state
        rows = jax.lax.dynamic_slice(
            lat, (layer, 0, j * bs, 0), (1, b, bs, w))[0]
        chosen = jax.lax.dynamic_slice_in_dim(sel, j * bs, bs, 2)[:, None]
        kv = _ll._matw(rows[..., :r], lp["wkvb"]).reshape(b, bs, h, -1)
        k = jnp.concatenate([kv[..., :n], jnp.broadcast_to(
            rows[:, :, None, r:cfg.latent_width],
            (b, bs, h, cfg.qk_rope_dim))], axis=-1)
        s = jnp.einsum("bphd,bshd->bhps", q, k,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(chosen, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        e = jnp.where(chosen, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhps,bshd->bhpd", e.astype(q.dtype), kv[..., n:],
            preferred_element_type=jnp.float32)
        return m_new, l * alpha + jnp.sum(e, axis=-1), acc

    m, l, acc = jax.lax.fori_loop(0, n_blocks, block, (
        jnp.full((b, h, p), _NEG, jnp.float32),
        jnp.zeros((b, h, p), jnp.float32),
        jnp.zeros((b, h, p, cfg.v_dim), jnp.float32)))
    o = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    return jnp.moveaxis(o, 1, 2).reshape(b, p, h * cfg.v_dim)


def _absorb_q(cfg: GlmDsaConfig, q_nope, q_rope, lp: Dict):
    """(q_lat [..., H, cache_width], W_uv, its scales or None): the
    query against a latent row."""
    dt = q_nope.dtype
    w_uk, w_uv, s_uk, s_uv = _ds._kvb_parts(cfg, lp["wkvb"])
    if s_uk is not None:
        q_nope = (q_nope.astype(jnp.float32) * s_uk).astype(dt)
    pad = jnp.zeros(
        q_rope.shape[:-1] + (cfg.cache_width - cfg.latent_width,), dt)
    q_lat = jnp.concatenate(
        [jnp.einsum("...hn,rhn->...hr", q_nope, w_uk.astype(dt)), q_rope, pad],
        axis=-1)
    return q_lat, w_uv, s_uv


def _absorb_o(o_lat, w_uv, s_uv, dt):
    o = jnp.einsum("...hr,rhv->...hv", o_lat.astype(dt), w_uv.astype(dt))
    if s_uv is not None:
        o = (o.astype(jnp.float32) * s_uv).astype(dt)
    return o.reshape(o.shape[:-2] + (-1,))


def _ffn(cfg: GlmDsaConfig, x, lp: Dict, rows=None):
    """The layer's second half on [B, T, d], residual included. Returns
    (x, load): ``load`` is ``parallel.moe.expert_load`` of an expert
    layer's routing over the experts HELD and the ``rows`` [B * T]
    (None for a dense layer)."""
    if "router" not in lp:
        with jax.named_scope("mlp"):
            m = _ll._rmsnorm(x, lp["ln2"], cfg.norm_eps)
            return x + _ds._swiglu(m, lp["w1"], lp["w3"], lp["w2"]), None
    with jax.named_scope("moe"):
        m = _ll._rmsnorm(x, lp["ln2"], cfg.norm_eps)
        flat = m.reshape(-1, m.shape[-1])
        idx, w = _moe.route_sigmoid_topk(
            flat, lp["router"], lp["router_bias"], cfg.top_k,
            cfg.route_scale, cfg.norm_topk)
        y = _moe.moe_dropless(
            flat, idx, w, lp["we1"], lp["we3"], lp["we2"],
            first=cfg.first_expert, kernel=cfg.use_flash)
        with jax.named_scope("moe.shared"):
            y = y + _ds._swiglu(flat, lp["ws1"], lp["ws3"], lp["ws2"])
        load = _moe.expert_load(idx - cfg.first_expert, cfg.held, rows)
        return x + y.reshape(x.shape), load


def _layers(params: Dict, cfg: GlmDsaConfig):
    return [params["layers"][name] for name in layer_names(cfg)]


# -- prefill: a bucket in pieces ---------------------------------------------


def _piece(params, cfg: GlmDsaConfig, tokens, last, start, lat, kidx,
           dense: bool):
    """One piece of a prompt through every layer: tokens [B, P] at
    positions ``start ..``, ``last`` [B], the rows built so far lat [L,
    B, Tb, W] and kidx [L, B, Tb, dI]. Returns (x [B, P, d], lat,
    kidx). ``dense``: the piece starts at 0 and holds no more than
    ``index_topk`` positions, so every earlier position is attended."""
    b, p = tokens.shape
    tb = lat.shape[2]
    positions = start + jnp.arange(p)
    written = (positions[None, :] <= last[:, None])[..., None]
    kb = min(KEY_BLOCK, tb)
    n_blocks = (start + p + kb - 1) // kb
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    for i, lp in enumerate(_layers(params, cfg)):
        with jax.named_scope("attn"):
            a = _ll._rmsnorm(x, lp["ln1"], cfg.norm_eps)
            cq, q_nope, q_rope, row = _latent(cfg, a, lp, positions)
            with jax.named_scope("attn.index"):
                qi, ki, w = _indexer(cfg, a, cq, lp, positions)
            lat = jax.lax.dynamic_update_slice(
                lat, jnp.where(written, row, 0)[None], (i, 0, start, 0))
            kidx = jax.lax.dynamic_update_slice(
                kidx, jnp.where(written, ki, 0)[None], (i, 0, start, 0))
            if dense:
                o = _ds.attention_expanded(cfg, q_nope, q_rope, row, lp)
            else:
                o = _attend_selected(
                    cfg, q_nope, q_rope, qi, w, lat, kidx, i, positions, last,
                    n_blocks, lp)
            x = x + _ll._matw(o, lp["wo"])
        x, _ = _ffn(cfg, x, lp)
    return x, lat, kidx


def _attend_selected(cfg, q_nope, q_rope, qi, w, lat, kidx, layer: int,
                     positions, last, n_blocks, lp):
    """A piece's queries over the positions their index scores choose:
    [B, P, H * v]."""
    b, p = q_nope.shape[:2]
    tb = lat.shape[2]
    kb = min(KEY_BLOCK, tb)
    with jax.named_scope("attn.index"):
        def score(j, table):
            ki = jax.lax.dynamic_slice(
                kidx, (layer, 0, j * kb, 0), (1, b, kb, cfg.index_dim))[0]
            return jax.lax.dynamic_update_slice_in_dim(
                table, index_scores(qi, w, ki), j * kb, 2)

        table = jax.lax.fori_loop(
            0, n_blocks, score, jnp.zeros((b, p, tb), jnp.float32))
    with jax.named_scope("attn.select"):
        upto = jnp.minimum(positions[None, :], last[:, None])
        valid = jnp.arange(tb)[None, None, :] <= upto[..., None]
        sel = select_mask(table, valid, cfg.index_topk)
    with jax.named_scope("attn.sparse"):
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        bk = _kernel_key_block(cfg, p, kb, tb, lp)
        if not bk:
            return _sweep(cfg, q, lat, layer, sel, n_blocks, lp)
        from edl_tpu.ops.flash_attention import _INTERPRET
        from edl_tpu.ops.sparse_prefill_attention import \
            sparse_prefill_attention

        return sparse_prefill_attention(
            q, lat, lp["wkvb"], sel, jnp.int32(layer), n_blocks * kb // bk,
            rank=cfg.kv_rank, rope=cfg.qk_rope_dim,
            sm_scale=1.0 / float(np.sqrt(cfg.qk_dim)), block_k=bk,
            interpret=_INTERPRET.get())


def _kernel_key_block(cfg: GlmDsaConfig, p: int, kb: int, tb: int, lp: Dict):
    """Key positions of one block of ``edl_sparse_prefill_attn`` for
    pieces of ``p`` rows swept in blocks of ``kb``, or 0 where the
    sweep stays XLA's (``_sweep``): without ``use_flash``, with an int8
    record for ``Wkvb``, or where pieces and blocks are not whole
    tiles. The kernel's own block is as large as it likes them while a
    piece is whole blocks of it: every piece then ends on one, and the
    live blocks hold the same positions in either size."""
    from edl_tpu.ops import sparse_prefill_attention as spa

    if not cfg.use_flash or isinstance(lp["wkvb"], dict):
        return 0
    bk = spa.BLOCK_K if spa.BLOCK_K % kb == 0 and p % spa.BLOCK_K == 0 else kb
    return bk if spa.fits(p, bk, tb) else 0


def _run(params: Dict, tokens, last, cfg: GlmDsaConfig, every: bool):
    """A batch of END-padded prompts [B, Tb] in pieces: (x, lat [L, B,
    Tb, W], kidx [L, B, Tb, dI]); ``x`` is [B, Tb, d] (``every``) or the
    row at each prompt's ``last`` [B, d]."""
    b, tb = tokens.shape
    p = min(PREFILL_PIECE, tb)
    if tb % p:
        raise ValueError(f"a bucket of {tb} is not whole pieces of {p}")
    n = tb // p
    (lshape, dt), (ishape, _) = cfg.serve_cache_spec(b, tb)
    lat, kidx = jnp.zeros(lshape, dt), jnp.zeros(ishape, dt)
    rows = jnp.arange(b)

    def pick(x, start):
        """x [B, P, d] -> what the caller asked of this piece."""
        if every:
            return x
        at = jnp.clip(last - start, 0, p - 1)
        return x[rows, at]

    first, head = 0, []
    if p <= cfg.index_topk:
        x, lat, kidx = _piece(
            params, cfg, tokens[:, :p], last, 0, lat, kidx, dense=True)
        first, head = 1, [pick(x, 0)[None]]
    blank = jnp.zeros((b, p, cfg.d_model) if every else (b, cfg.d_model),
                      cfg.dtype)

    def walk(carry, j):
        lat, kidx = carry
        start = j * p

        def live():
            x, lt, kx = _piece(
                params, cfg,
                jax.lax.dynamic_slice_in_dim(tokens, start, p, 1),
                last, start, lat, kidx, dense=False)
            return pick(x, start), lt, kx

        # a piece that starts past every prompt's end has no row
        # anybody reads
        x, lat, kidx = jax.lax.cond(
            start <= jnp.max(last), live, lambda: (blank, lat, kidx))
        return (lat, kidx), x

    if first < n:
        (lat, kidx), rest = jax.lax.scan(
            walk, (lat, kidx), jnp.arange(first, n))
        head = head + [rest]
    xs = jnp.concatenate(head, axis=0)  # [n, B, P, d] or [n, B, d]
    if every:
        return jnp.moveaxis(xs, 0, 1).reshape(b, tb, -1), lat, kidx
    return xs[last // p, rows], lat, kidx


def _logits(params, x, cfg):
    """The head's product leaves its float32 accumulator as it is: a
    logit rounded to bfloat16 moves by up to 0.016 between 2 and 4, and
    the first choice among near-equal logits is what is served. (A
    v5e's compiler kept the accumulator through ``.astype(float32)``
    already: the chip's readings are the same with and without; the
    CPU's rounds.)"""
    with jax.named_scope("head"):
        x = _ll._rmsnorm(x, params["ln_f"], cfg.norm_eps)
        w = params["lm_head"]
        if isinstance(w, dict):  # weight-only int8: ``_ll._matw``'s form
            return jnp.matmul(x, w["q8"].astype(x.dtype),
                              preferred_element_type=jnp.float32) * w["s8"]
        return jnp.matmul(x, w.astype(x.dtype),
                          preferred_element_type=jnp.float32)


def forward(params: Dict, tokens: jnp.ndarray, cfg: GlmDsaConfig):
    """tokens [B, T] int32 -> logits [B, T, vocab] (float32)."""
    b, t = tokens.shape
    x, _, _ = _run(params, tokens, jnp.full((b,), t - 1, jnp.int32), cfg,
                   every=True)
    return _logits(params, x, cfg)


def prefill_padded(params: Dict, tokens: jnp.ndarray, last, cfg):
    """``llama.prefill_padded`` for this layer: an END-padded prompt
    batch [B, Tb] -> (logits [B, V] at each row's ``last`` index, (the
    latent rows [L, B, Tb, cache_width], the index keys [L, B, Tb,
    index_dim]))."""
    b = tokens.shape[0]
    last = jnp.broadcast_to(last, (b,)).astype(jnp.int32)
    x, lat, kidx = _run(params, tokens, last, cfg, every=False)
    return _logits(params, x, cfg), (lat, kidx)


# -- decode ------------------------------------------------------------------


def attention_selected(cfg: GlmDsaConfig, q_nope, q_rope, qi, w, lat, kidx,
                       layer: int, read_to, lp):
    """One new query a slot: index scores against the slot's index keys
    (this step's already written) up to ``read_to`` [B], the
    ``index_topk`` best as a mask, absorbed attention over the slot's
    live latent rows with the others masked out. q_nope [B, H, nope],
    q_rope [B, H, rope], qi [B, hI, dI], w [B, hI] float32, lat [L, B,
    S, W], kidx [L, B, S, dI]. Returns [B, H * v].

    A masked read of every live row, not a gather of the chosen: on a
    v5e XLA's gather of 32 x 2048 rows of 1280 bytes runs at 43 GB/s
    (1.94 ms a layer, and a sort of 32 x 32768 scores for the indices
    0.73 ms), and reading all ~10k live rows a slot in order costs less
    (PERF.md section 6, PR 41)."""
    b, s_len = lat.shape[1], lat.shape[2]
    kb = min(_DECODE_KEY_BLOCK, s_len)
    with jax.named_scope("attn.index"):
        def score(j, table):
            ki = jax.lax.dynamic_slice(
                kidx, (layer, 0, j * kb, 0), (1, b, kb, cfg.index_dim))[0]
            return jax.lax.dynamic_update_slice_in_dim(
                table, index_scores(qi[:, None], w[:, None], ki)[:, 0],
                j * kb, 1)

        table = jax.lax.fori_loop(
            0, jnp.max(read_to) // kb + 1, score,
            jnp.zeros((b, s_len), jnp.float32))
    with jax.named_scope("attn.select"):
        live = jnp.arange(s_len)[None, :] <= read_to[:, None]
        chosen = select_mask(
            table[:, None], live[:, None], cfg.index_topk)[:, 0]
    with jax.named_scope("attn.sparse"):
        q_lat, w_uv, s_uv = _absorb_q(cfg, q_nope, q_rope, lp)
        scale = 1.0 / float(np.sqrt(cfg.qk_dim))
        if cfg.use_flash:
            from edl_tpu.ops.decode_attention import decode_attention_latent
            from edl_tpu.ops.flash_attention import _INTERPRET

            o_lat = decode_attention_latent(
                q_lat, lat, read_to, jnp.int32(layer), rank=cfg.kv_rank,
                sm_scale=scale, interpret=_INTERPRET.get(), chosen=chosen)
        else:
            s = jnp.einsum("bhw,bsw->bhs", q_lat, lat[layer],
                           preferred_element_type=jnp.float32)
            s = jnp.where(chosen[:, None], s * scale, _NEG)
            p = jax.nn.softmax(s, axis=-1).astype(q_lat.dtype)
            o_lat = jnp.einsum(
                "bhs,bsr->bhr", p, lat[layer][..., :cfg.kv_rank])
        return _absorb_o(o_lat, w_uv, s_uv, q_nope.dtype)


def decode_step_slots(params: Dict, tok, pos, cache, cfg: GlmDsaConfig,
                      live=None):
    """``llama.decode_step_slots`` over the two arrays of the cache:
    (logits [B, V], cache, load). ``load`` is (experts_hit_share,
    expert_load_max_over_mean) of the ``live`` rows' routing over the
    experts held, each the mean over the expert layers."""
    lat, kidx = cache
    b = tok.shape[0]
    rows = jnp.arange(b)
    read_to = pos if live is None else jnp.where(live, pos, 0)
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tok[:, None], axis=0).astype(cfg.dtype)
    loads = []
    for i, lp in enumerate(_layers(params, cfg)):
        with jax.named_scope("attn"):
            a = _ll._rmsnorm(x, lp["ln1"], cfg.norm_eps)
            cq, q_nope, q_rope, row = _latent(cfg, a, lp, pos[:, None])
            with jax.named_scope("attn.index"):
                qi, ki, w = _indexer(cfg, a, cq, lp, pos[:, None])
            lat = lat.at[i, rows, pos].set(row[:, 0])
            kidx = kidx.at[i, rows, pos].set(ki[:, 0])
            o = attention_selected(
                cfg, q_nope[:, 0], q_rope[:, 0], qi[:, 0], w[:, 0], lat, kidx,
                i, read_to, lp)
            x = x + _ll._matw(o[:, None], lp["wo"])
        x, load = _ffn(cfg, x, lp, live)
        if load is not None:
            loads.append(load)
    logits = _logits(params, x[:, 0], cfg)
    load = tuple(jnp.mean(jnp.stack(v)) for v in zip(*loads)) if loads \
        else (jnp.float32(0), jnp.float32(0))
    return logits, (lat, kidx), load


def decode_horizon_slots(
    params, tok, pos, active, rem, eosv, cache, cfg: GlmDsaConfig,
    horizon: int, key=None, temperature=None, sampling: bool = False,
):
    """``llama.decode_horizon_slots`` over this model's cache tuple: the
    scan, the token choice and the freezing of finished rows are
    ``llama.horizon_scan``'s. Returns ``(toks [B, horizon], tok, pos,
    active, rem, cache, counters)``."""

    def step(tok, pos, cache, active):
        return decode_step_slots(params, tok, pos, cache, cfg, live=active)

    toks, tok, pos, active, rem, cache, (hit, skew) = _ll.horizon_scan(
        step, tok, pos, active, rem, eosv, cache, horizon,
        key=key, temperature=temperature, sampling=sampling)
    counters = {"experts_hit_share": jnp.mean(hit),
                "expert_load_max_over_mean": jnp.mean(skew)}
    return toks, tok, pos, active, rem, cache, counters
