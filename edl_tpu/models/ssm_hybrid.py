"""A decoder whose layers are of two kinds, served: what
``ibm-granite/granite-4.0-h-micro`` publishes as ``model_type:
granitemoehybrid`` with no experts: selective state-space layers
(Mamba-2, arXiv:2405.21060) beside a few softmax-attention layers, a
SwiGLU after each, one embedding that is also the head.

With ``e`` = ``embedding_multiplier``, ``r`` = ``residual_multiplier``,
RMSNorm everywhere:

    x0      = e * embed[token]
    layer l:  a = norm1(x);  x = x + r * mix_l(a)
              m = norm2(x);  x = x + r * W2 (silu(W1 m) * (W3 m))
    logits  = (norm_f(x_L) @ embed^T) / logits_scaling
    mix_l   = mamba2 where layer_types[l] == "mamba", else attention

*Attention* has no positional embedding and no bias: ``q = a Wq`` as
``H`` heads, ``k``, ``v`` as ``KV`` heads, ``softmax(attention_multiplier
* q k^T + causal) v``, then ``Wo``. The multiplier is the config's, NOT
``1 / sqrt(head_dim)``.

*Mamba-2* (``Hm`` heads of width ``P``, state width ``N``, one group,
``K`` convolution taps; ``ops/ssm.py`` has the recurrence):

    z | xBC = a @ in_proj ;  dt_raw = a @ dt_proj
    xBC_t   = silu(sum_j conv_w[j] * xBC_{t-K+1+j} + conv_b)
    x | B | C = xBC_t ;  dt = softplus(dt_raw + dt_bias) ;  A = -exp(A_log)
    S_t = exp(dt A) S_{t-1} + (dt x_t) B_t^T ;   y_t = S_t C_t + D x_t
    out = rmsnorm(y_t * silu(z), norm) @ out_proj

The layer kinds come from ``layer_types`` alone. The two kinds' weights
are two stacked trees, ``params["mamba"]`` and ``params["attn"]``; a run
of consecutive state-space layers is one loop over the stacked tree
(the program holds one body a run, not one a layer), an attention layer
is unrolled at its static index. The cache is one tuple of four arrays
of two depths and two kinds: ``S`` ``[Lm, slots, Hm, P, N]`` float32 and
the convolution's tail ``[Lm, slots, (K - 1) * C]`` (a state a slot), keys
and values ``[La, slots, max_len, KV / pack, pack * hd]`` (rows a
position). ``pack`` heads share a 128-lane row where the head is
narrower than a lane tile (two at 64): a cache whose minor dimension is
half a tile is stored positions-minor by the compiler and transposed
around every kernel; packed, the row is whole lanes, the bytes are the
same, and ``edl_decode_attn`` reads it as ``KV / pack`` heads of 128
with each query zero outside its own head's half.

Stored layouts that are this file's (the published checkpoint has one
``in_proj`` with columns ``z | xBC | dt`` and one ``input_linear``): the
``dt`` columns are a leaf of their own (``dt_proj``), the SwiGLU's two
halves ``w1`` / ``w3`` as in ``llama.py``, the convolution ``[K, C]``.

The dense parts are ``models/llama.py``'s, imported: ``_rmsnorm``,
``_matw``, ``_mlp``, ``_qkv`` / ``_qkv_cached`` (no RoPE: ``rope_theta``
is None), ``horizon_scan``. Serving goes through ``serving/engine.py``'s
model seam (the ``serve_*`` methods); the paged, quantized-cache,
chunked-prefill and verify programs are the dense decoder's. No loss:
the model is served, not trained.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from edl_tpu.models import llama as _ll
from edl_tpu.models.meta import dataclass_from_meta, dataclass_meta
from edl_tpu.ops import ssm as _ops

KINDS = ("mamba", "attention")


@dataclass(frozen=True)
class SSMHybridConfig:
    vocab: int = 100352
    d_model: int = 2048
    layer_types: Tuple[str, ...] = (
        ("mamba",) * 5 + ("attention",) + ("mamba",) * 4) * 4
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    d_ff: int = 8192
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    d_state: int = 128
    d_conv: int = 4
    # the prefill's chunk: the published ``mamba_chunk_size``; the same
    # function at any length, so the program's to choose
    chunk: int = 256
    norm_eps: float = 1e-5
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    dtype: Any = jnp.bfloat16
    # the Pallas kernels: ``edl_ssm_step`` and ``edl_decode_attn`` in
    # decode, ``edl_flash_fwd`` in prefill. Off: the plain lines.
    use_kernel: bool = False

    # ``llama._qkv`` and ``llama._mlp`` ask these of a config
    int8_mxu = False
    int8_wgrad_bf16 = False
    rope_theta = None  # no positional embedding
    # what the memory ledger files each array of the cache under
    serve_cache_kinds = ("state", "state", "kv", "kv")

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        bad = set(self.layer_types) - set(KINDS)
        if bad or not self.layer_types:
            raise ValueError(f"layer_types must name {KINDS}, got {bad}")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("heads must divide into kv heads")

    @classmethod
    def from_hf(cls, config: Dict, **overrides) -> "SSMHybridConfig":
        """From a published ``config.json`` of ``model_type:
        granitemoehybrid``. What this file does not implement is
        refused rather than ignored."""
        for key, want in (
            ("num_local_experts", 0), ("position_embedding_type", "nope"),
            ("attention_bias", False), ("mamba_proj_bias", False),
            ("mamba_conv_bias", True), ("mamba_n_groups", 1),
            ("tie_word_embeddings", True), ("hidden_act", "silu"),
            ("attention_dropout", 0.0),
        ):
            if config.get(key, want) != want:
                raise NotImplementedError(
                    f"granitemoehybrid with {key}={config[key]!r} "
                    f"(only {want!r})")
        d = config["hidden_size"]
        heads, p = config["mamba_n_heads"], config["mamba_d_head"]
        if heads * p != config["mamba_expand"] * d:
            raise NotImplementedError(
                f"mamba_n_heads x mamba_d_head = {heads * p} is not "
                f"mamba_expand x hidden_size = {config['mamba_expand'] * d}")
        return cls(**{**dict(
            vocab=config["vocab_size"], d_model=d,
            layer_types=tuple(config["layer_types"]),
            n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"],
            head_dim=config.get("head_dim") or d // config[
                "num_attention_heads"],
            d_ff=config["shared_intermediate_size"],
            mamba_heads=heads, mamba_head_dim=p,
            d_state=config["mamba_d_state"], d_conv=config["mamba_d_conv"],
            chunk=config["mamba_chunk_size"],
            norm_eps=float(config["rms_norm_eps"]),
            embedding_multiplier=float(config["embedding_multiplier"]),
            residual_multiplier=float(config["residual_multiplier"]),
            attention_multiplier=float(config["attention_multiplier"]),
            logits_scaling=float(config["logits_scaling"]),
        ), **overrides})

    def to_meta(self) -> Dict:
        """JSON-safe architecture record (rides export manifests so
        ``edl serve`` can rebuild the config; runtime/export.py)."""
        return dataclass_meta(self, "ssm_hybrid")

    @classmethod
    def from_meta(cls, meta: Dict) -> "SSMHybridConfig":
        return dataclass_from_meta(cls, meta, "ssm_hybrid")

    # -- sizes ---------------------------------------------------------------

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_mamba(self) -> int:
        return self.layer_types.count("mamba")

    @property
    def n_attn(self) -> int:
        return self.layer_types.count("attention")

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_width(self) -> int:
        """Channels the convolution runs over: ``x | B | C``."""
        return self.d_inner + 2 * self.d_state

    @property
    def runs(self):
        """``layer_types`` as runs of one kind: (kind, index of the
        run's first layer in its kind's stacked tree, layers)."""
        out, seen = [], dict.fromkeys(KINDS, 0)
        for kind in self.layer_types:
            if out and out[-1][0] == kind:
                out[-1][2] += 1
            else:
                out.append([kind, seen[kind], 1])
            seen[kind] += 1
        return tuple(tuple(r) for r in out)

    @property
    def kv_pack(self) -> int:
        """kv heads that share one 128-lane row of the cache."""
        pack, rest = divmod(_ops.LANES, self.head_dim)
        return pack if pack > 1 and not rest \
            and self.n_kv_heads % pack == 0 else 1

    def state_bytes_per_slot(self) -> int:
        """``S`` (float32) and the convolution's tail of one sequence,
        all state-space layers."""
        return self.n_mamba * (
            4 * self.d_inner * self.d_state
            + jnp.dtype(self.dtype).itemsize
            * (self.d_conv - 1) * self.conv_width)

    # -- what ``obs/costmodel.py`` asks a config that prices itself --------

    def _layer_params(self):
        d, ff = self.d_model, self.d_ff
        mlp = 3 * d * ff
        mamba = d * (self.d_inner + self.conv_width + self.mamba_heads) \
            + self.d_inner * d + mlp
        attn = 2 * d * self.n_heads * self.head_dim \
            + 2 * d * self.n_kv_heads * self.head_dim + mlp
        return mamba, attn

    def matmul_params(self) -> float:
        """Parameters a token multiplies, the (tied) head included; the
        state counts as two: each position enters it once and reads it
        once, ``Hm * P * N`` products each."""
        mamba, attn = self._layer_params()
        return (self.n_mamba * (mamba + 2 * self.d_inner * self.d_state)
                + self.n_attn * attn + self.d_model * self.vocab)

    def n_params(self) -> float:
        """Every parameter, the embedding counted once (it is the
        head)."""
        mamba, attn = self._layer_params()
        small = (self.d_conv + 1) * self.conv_width + 3 * self.mamba_heads \
            + self.d_inner
        norms = 2 * self.d_model
        return (self.n_mamba * (mamba + small + norms)
                + self.n_attn * (attn + norms)
                + self.vocab * self.d_model + self.d_model)

    def attn_width(self) -> float:
        """``h * hd`` of the attention products, averaged over ALL
        layers (the cost model multiplies by ``n_layers``; the
        attention layers are ``n_attn`` of them)."""
        return self.n_heads * self.head_dim * self.n_attn / self.n_layers

    def cache_numbers_per_token(self) -> int:
        """Keys and values one position holds, the attention layers'."""
        return 2 * self.n_attn * self.n_kv_heads * self.head_dim

    def cache_step_bytes_per_slot(self) -> int:
        """Bytes of per-slot state a decode step moves for one live
        slot: read once and written once."""
        return 2 * self.state_bytes_per_slot()

    # -- what ``serving/engine.py`` asks a config it serves (its comment
    # gives the contract)

    def serve_cache_spec(self, slots: int, max_len: int):
        """Four arrays of two depths: ``S`` and the convolution's tail,
        a state a slot; keys and values, rows a position, ``kv_pack``
        heads a 128-lane row."""
        pack = self.kv_pack
        kv = (self.n_attn, slots, max_len, self.n_kv_heads // pack,
              pack * self.head_dim)
        return (
            ((self.n_mamba, slots, self.mamba_heads, self.mamba_head_dim,
              self.d_state), jnp.float32),
            ((self.n_mamba, slots, (self.d_conv - 1) * self.conv_width),
             self.dtype),
            (kv, self.dtype), (kv, self.dtype))

    def serve_prefill(self, params, tokens, last):
        logits, *cache = prefill_padded(params, tokens, last, self)
        return logits, tuple(cache)

    def serve_decode_block(self, params, tok, pos, active, rem, eosv, cache,
                           **kw):
        toks, tok, pos, active, rem, cache = decode_horizon_slots(
            params, tok, pos, active, rem, eosv, cache, self, **kw)
        return toks, tok, pos, active, rem, cache, {}

    def serve_attn_block(self, max_len: int) -> int:
        """Positions of one S-block ``edl_decode_attn`` fetches of the
        packed cache; the dense read is one block of ``max_len``."""
        if not self.use_kernel:
            return max_len
        from edl_tpu.ops.decode_attention import block_positions

        pack = self.kv_pack
        return block_positions(
            self.n_kv_heads // pack, pack * self.head_dim,
            jnp.dtype(self.dtype).itemsize, max_len)

    def serve_cache_read(self, held, max_len: int, block: int):
        """Both kinds: a live slot's state is moved whole, its keys and
        values up to the block that holds its last token."""
        return {
            "kv_read_share": _ll.positional_read_share(held, max_len, block),
            "state_live_share": _ll.state_live_share(held),
        }


def init_params(key: jax.Array, cfg: SSMHybridConfig) -> Dict:
    """Two stacked trees, one a kind of layer, the embedding (which is
    the head) and the last norm. ``A``, ``dt_bias`` and ``D`` as the
    published model initialises them: ``A`` uniform in 1..16, the step
    sizes log-uniform in 1e-3..1e-1, ``D`` ones."""
    d, ff, di, cw = cfg.d_model, cfg.d_ff, cfg.d_inner, cfg.conv_width
    hm, lm, la = cfg.mamba_heads, cfg.n_mamba, cfg.n_attn
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    k = iter(jax.random.split(key, 24))

    def draw(*shape, scale):
        return jax.random.normal(next(k), shape, jnp.float32) * scale

    def mlp(n):
        return {"ln2": jnp.ones((n, d), jnp.float32),
                "w1": draw(n, d, ff, scale=d ** -0.5),
                "w3": draw(n, d, ff, scale=d ** -0.5),
                "w2": draw(n, ff, d, scale=ff ** -0.5)}

    step = jnp.exp(jax.random.uniform(
        next(k), (lm, hm), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "embed": draw(cfg.vocab, d, scale=0.02),
        "ln_f": jnp.ones((d,), jnp.float32),
        "mamba": {
            "ln1": jnp.ones((lm, d), jnp.float32),
            "in_proj": draw(lm, d, di + cw, scale=d ** -0.5),
            "dt_proj": draw(lm, d, hm, scale=d ** -0.5),
            "conv_w": draw(lm, cfg.d_conv, cw, scale=cfg.d_conv ** -0.5),
            "conv_b": jnp.zeros((lm, cw), jnp.float32),
            "A_log": jnp.log(jax.random.uniform(
                next(k), (lm, hm), jnp.float32, 1.0, 16.0)),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),  # softplus^-1
            "D": jnp.ones((lm, hm), jnp.float32),
            "norm": jnp.ones((lm, di), jnp.float32),
            "out_proj": draw(lm, di, d, scale=di ** -0.5),
            **mlp(lm),
        },
        "attn": {
            "ln1": jnp.ones((la, d), jnp.float32),
            "wq": draw(la, d, h * hd, scale=d ** -0.5),
            "wk": draw(la, d, kv * hd, scale=d ** -0.5),
            "wv": draw(la, d, kv * hd, scale=d ** -0.5),
            "wo": draw(la, h * hd, d, scale=(h * hd) ** -0.5),
            **mlp(la),
        },
    }


_INT8_WEIGHTS = ("in_proj", "out_proj", "wq", "wk", "wv", "wo",
                 "w1", "w3", "w2")


def quantize_params_int8(params: Dict) -> Dict:
    """``llama.quantize_params_int8`` for this tree: every matrix a
    decode step streams becomes an int8 record that ``llama._matw``
    multiplies; the head's is made of the embedding transposed (a leaf
    ``lm_head`` beside it: the lookup keeps the rows). ``dt_proj``, the
    convolution and the vectors stay."""
    from edl_tpu.ops.int8_matmul import absmax_quant

    def q(w):
        q8, s = absmax_quant(w, -2)
        return {"q8": q8, "s8": s[..., 0, :]}

    out = dict(params)
    for kind in ("mamba", "attn"):
        out[kind] = {name: (q(w) if name in _INT8_WEIGHTS else w)
                     for name, w in params[kind].items()}
    out["lm_head"] = q(params["embed"].T)
    return out


def _interpret(cfg: SSMHybridConfig) -> bool:
    """Whether the caller opened ``interpret_kernels`` (read while the
    program is traced, as the other models' kernels do)."""
    if not cfg.use_kernel:
        return False
    from edl_tpu.ops.flash_attention import _INTERPRET

    return _INTERPRET.get()


def _layer_of(tree: Dict, i):
    """Layer ``i`` (static or traced) of a stacked tree: each leaf
    sliced where it lies."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        tree)


def _embed(params: Dict, tokens, cfg: SSMHybridConfig):
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
        return x * cfg.embedding_multiplier


def _logits(params: Dict, x, cfg: SSMHybridConfig):
    """The head is the embedding, read where it lies: the product
    contracts the table's minor dimension."""
    with jax.named_scope("head"):
        x = _ll._rmsnorm(x, params["ln_f"], cfg.norm_eps)
        if "lm_head" in params:  # the int8 record of the control
            logits = _ll._matw(x, params["lm_head"])
        else:
            logits = jnp.einsum(
                "...d,vd->...v", x, params["embed"].astype(x.dtype))
        return logits.astype(jnp.float32) / cfg.logits_scaling


def _mamba_in(cfg: SSMHybridConfig, x, lp: Dict):
    """Norm and the input projections: (z, xBC, dt before its bias)."""
    a = _ll._rmsnorm(x, lp["ln1"], cfg.norm_eps)
    zx = _ll._matw(a, lp["in_proj"])
    dt_raw = _ll._matw(a, lp["dt_proj"]).astype(jnp.float32)
    return zx[..., :cfg.d_inner], zx[..., cfg.d_inner:], dt_raw


def _mamba_out(cfg: SSMHybridConfig, x, y, z, lp: Dict):
    """The gated norm, the output projection and the residual."""
    with jax.named_scope("ssm.gate_norm"):
        g = _ll._rmsnorm(
            y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32)),
            lp["norm"], cfg.norm_eps).astype(cfg.dtype)
    return x + cfg.residual_multiplier * _ll._matw(g, lp["out_proj"])


def _split_xbc(cfg: SSMHybridConfig, xbc):
    """silu(conv(xBC)) -> x [.., Hm, P], B [.., N], C [.., N]."""
    di, n = cfg.d_inner, cfg.d_state
    x = xbc[..., :di].reshape(
        xbc.shape[:-1] + (cfg.mamba_heads, cfg.mamba_head_dim))
    return x, xbc[..., di:di + n], xbc[..., di + n:]


def _dt(lp: Dict, dt_raw):
    return jax.nn.softplus(dt_raw + lp["dt_bias"].astype(jnp.float32))


def _a(lp: Dict):
    return -jnp.exp(lp["A_log"].astype(jnp.float32))


def _pack_kv(cfg: SSMHybridConfig, kv):
    """[.., KV, hd] -> [.., KV / pack, pack * hd]: the same bytes."""
    pack = cfg.kv_pack
    return kv.reshape(kv.shape[:-2] + (cfg.n_kv_heads // pack,
                                       pack * cfg.head_dim))


def _attention(cfg: SSMHybridConfig, q, k, v):
    """Causal attention over [B, T]: q [B, T, H, hd]; k, v [B, T, KV,
    hd]; the scale is the config's multiplier."""
    if cfg.use_kernel:
        from edl_tpu.ops.flash_attention import attention_auto

        return attention_auto(q, k, v, causal=True,
                              sm_scale=cfg.attention_multiplier)
    b, t, h, hd = q.shape
    groups = h // k.shape[2]
    k = jnp.repeat(k, groups, axis=2)
    v = jnp.repeat(v, groups, axis=2)
    scores = jnp.einsum("bthd,bshd->bhts", q, k) * cfg.attention_multiplier
    mask = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(mask[None, None], scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), -1).astype(q.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


def _run(params: Dict, tokens, cfg: SSMHybridConfig, valid, last):
    """Embedding and every layer over [B, T] rows that start their
    sequences: (x [B, T, d], S [Lm, B, Hm, P, N], tail [Lm, B, (K - 1) *
    C], keys and values [La, B, T, KV / pack, pack * hd]): the cache
    after each row's position ``last``."""
    b, t = tokens.shape
    r = cfg.residual_multiplier
    x = _embed(params, tokens, cfg)
    spec = cfg.serve_cache_spec(b, t)
    state, tail, kc, vc = (jnp.zeros(shape, dtype) for shape, dtype in spec)

    def mamba(i, carry):
        x, state, tail = carry
        lp = _layer_of(params["mamba"], i)
        with jax.named_scope("ssm"):
            z, xbc, dt_raw = _mamba_in(cfg, x, lp)
            xbc, tl = _ops.conv_prefill(xbc, lp["conv_w"], lp["conv_b"], last)
            xs, bm, cm = _split_xbc(cfg, xbc)
            y, s = _ops.ssd_chunked(
                xs, bm, cm, _dt(lp, dt_raw), _a(lp), lp["D"], valid,
                chunk=cfg.chunk, dtype=cfg.dtype)
            x = _mamba_out(cfg, x, y.reshape(b, t, -1), z, lp)
        set_at = jax.lax.dynamic_update_index_in_dim
        return (_ll._mlp(cfg, x, lp, residual=r),
                set_at(state, s, i, 0), set_at(tail, tl, i, 0))

    for kind, first, n in cfg.runs:
        if kind == "mamba":
            x, state, tail = jax.lax.fori_loop(
                first, first + n, mamba, (x, state, tail))
            continue
        for i in range(first, first + n):
            lp = _layer_of(params["attn"], i)
            with jax.named_scope("attn"):
                a = _ll._rmsnorm(x, lp["ln1"], cfg.norm_eps)
                q, k, v = _ll._qkv(cfg, a, lp)
                o = _attention(cfg, q, k, v).reshape(b, t, -1)
                x = x + r * _ll._matw(o, lp["wo"])
                kc = kc.at[i].set(_pack_kv(cfg, k))
                vc = vc.at[i].set(_pack_kv(cfg, v))
            x = _ll._mlp(cfg, x, lp, residual=r)
    return x, state, tail, kc, vc


def forward(params: Dict, tokens: jnp.ndarray, cfg: SSMHybridConfig):
    """tokens [B, T] int32 -> logits [B, T, vocab] (float32)."""
    b, t = tokens.shape
    x = _run(params, tokens, cfg, jnp.ones((b, t), bool),
             jnp.full((b,), t - 1, jnp.int32))[0]
    return _logits(params, x, cfg)


def prefill_padded(params: Dict, tokens: jnp.ndarray, last, cfg):
    """``llama.prefill_padded`` for this model: an END-padded prompt
    batch [B, Tb] -> (logits [B, V] at each row's ``last`` index, and
    the four cache arrays of :meth:`serve_cache_spec` for B slots of Tb
    positions). A positional cache ignores the rows past a prompt's
    end; a recurrence cannot, so a position past ``last`` neither
    decays ``S`` nor enters it, and the convolution's tail is the three
    inputs at ``last - 2 .. last`` (zeros before position 0)."""
    b, t = tokens.shape
    last = jnp.broadcast_to(last, (b,)).astype(jnp.int32)
    valid = jnp.arange(t)[None, :] <= last[:, None]
    x, *cache = _run(params, tokens, cfg, valid, last)
    return (_logits(params, x[jnp.arange(b), last], cfg), *cache)


def _qpack(cfg: SSMHybridConfig, q):
    """q [B, H, hd] -> [B, KV / pack, pack * groups, pack * hd]: each
    query in the lanes of its own kv head's share of the packed row,
    zeros in the others."""
    b = q.shape[0]
    pack, hd = cfg.kv_pack, cfg.head_dim
    kvp, groups = cfg.n_kv_heads // pack, cfg.n_heads // cfg.n_kv_heads
    q = q.reshape(b, kvp, pack, groups, hd)
    own = jnp.eye(pack, dtype=q.dtype)
    return jnp.einsum("bkpgd,pq->bkpgqd", q, own).reshape(
        b, kvp, pack * groups, pack * hd)


def _qunpack(cfg: SSMHybridConfig, o):
    """The inverse on the kernel's output: each head's own lanes."""
    b = o.shape[0]
    pack, hd = cfg.kv_pack, cfg.head_dim
    kvp, groups = cfg.n_kv_heads // pack, cfg.n_heads // cfg.n_kv_heads
    o = o.reshape(b, kvp, pack, groups, pack, hd)
    return jnp.einsum("bkpgqd,pq->bkpgd", o, jnp.eye(pack, dtype=o.dtype)
                      ).reshape(b, cfg.n_heads * hd)


def decode_step_slots(params: Dict, tok, pos, cache, cfg: SSMHybridConfig,
                      live: Optional[jnp.ndarray] = None):
    """``llama.decode_step_slots`` over this model's cache tuple: tok
    [B] each slot's previous token, pos [B] the position it writes.
    Returns (logits [B, V], cache). A row that is not ``live`` keeps
    its state and its tail (a recurrence re-run is not idempotent) and
    reads its keys at position 0 alone; its key write at its frozen
    ``pos`` lies past everything its finished request read."""
    state, tail, kc, vc = cache
    b = tok.shape[0]
    if live is None:
        live = jnp.ones((b,), bool)
    interpret = _interpret(cfg)
    r, hd = cfg.residual_multiplier, cfg.head_dim
    rows = jnp.arange(b)
    read_to = jnp.where(live, pos, 0)
    x = _embed(params, tok[:, None], cfg)

    def mamba(i, carry):
        x, state, tail = carry
        lp = _layer_of(params["mamba"], i)
        with jax.named_scope("ssm"):
            z, xbc, dt_raw = _mamba_in(cfg, x, lp)
            xbc, tail = _ops.conv_step(
                xbc[:, 0], tail, i, lp["conv_w"], lp["conv_b"], live)
            xs, bm, cm = _split_xbc(cfg, xbc)
            y, state = _ops.ssm_step(
                xs, bm, cm, _dt(lp, dt_raw[:, 0]), _a(lp), lp["D"], state,
                i, live, dtype=cfg.dtype, use_kernel=cfg.use_kernel,
                interpret=interpret)
            x = _mamba_out(cfg, x, y.reshape(b, 1, -1), z, lp)
        return _ll._mlp(cfg, x, lp, residual=r), state, tail

    for kind, first, n in cfg.runs:
        if kind == "mamba":
            x, state, tail = jax.lax.fori_loop(
                first, first + n, mamba, (x, state, tail))
            continue
        for i in range(first, first + n):
            lp = _layer_of(params["attn"], i)
            with jax.named_scope("attn"):
                a = _ll._rmsnorm(x, lp["ln1"], cfg.norm_eps)
                q, knew, vnew = _ll._qkv_cached(cfg, a, lp, None)
                kc = kc.at[i, rows, pos].set(_pack_kv(cfg, knew[:, 0]))
                vc = vc.at[i, rows, pos].set(_pack_kv(cfg, vnew[:, 0]))
                if cfg.use_kernel:
                    from edl_tpu.ops.decode_attention import decode_attention

                    # each slot's live prefix, read out of the stacked
                    # packed cache (never ``kc[i]``)
                    o = _qunpack(cfg, decode_attention(
                        _qpack(cfg, q[:, 0]), kc, vc, read_to, jnp.int32(i),
                        sm_scale=cfg.attention_multiplier,
                        interpret=interpret))
                else:
                    unpack = lambda c: c[i].reshape(
                        c.shape[1:3] + (cfg.n_kv_heads, hd))
                    o = _ll.slot_attention_dense(
                        q.reshape(b, cfg.n_kv_heads, -1, hd), unpack(kc),
                        unpack(vc), pos, sm_scale=cfg.attention_multiplier)
                x = x + r * _ll._matw(o.reshape(b, 1, -1), lp["wo"])
            x = _ll._mlp(cfg, x, lp, residual=r)
    return _logits(params, x[:, 0], cfg), (state, tail, kc, vc)


def decode_horizon_slots(
    params, tok, pos, active, rem, eosv, cache, cfg: SSMHybridConfig,
    horizon: int, key=None, temperature=None, sampling: bool = False,
):
    """``llama.decode_horizon_slots`` over the cache tuple: the scan,
    the token choice and the freezing of finished rows are
    ``llama.horizon_scan``'s. Returns ``(toks [B, horizon], tok, pos,
    active, rem, cache)``."""

    def step(tok, pos, cache, active):
        logits, cache = decode_step_slots(
            params, tok, pos, cache, cfg, live=active)
        return logits, cache, ()

    toks, tok, pos, active, rem, cache, _ = _ll.horizon_scan(
        step, tok, pos, active, rem, eosv, tuple(cache), horizon,
        key=key, temperature=temperature, sampling=sampling)
    return toks, tok, pos, active, rem, cache
