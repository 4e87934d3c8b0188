"""The power-retention decoder layer, served: what
``manifestai/Brumby-14B-Base`` publishes as ``model_type: brumby`` (a
dense decoder of Qwen3's shapes whose attention is replaced by power
retention; Manifest AI, "Scaling Context Requires Rethinking
Attention", arXiv:2507.04239). Its cache is not indexed by position:
a sequence's past is a fixed-size state a layer, read and written
whole every step.

The layer, with ``a_t = rmsnorm(x_t, ln1)``, head width ``d``, query
head ``h`` in the group ``j = h // (H / KV)`` of its kv head:

    q_t^h = rope(rmsnorm(a_t Wq^h, q_norm), t)    v_t^j = a_t Wv^j
    k_t^j = rope(rmsnorm(a_t Wk^j, k_norm), t)
    log g_t^j = logsigmoid(a_t Wg^j + gate_bias * b_g^j)
    attention form:  A_ts = (q_t^h . k_s^j / sqrt(d))^2
                            * exp(sum_{r=s+1..t} log g_r^j)   (s <= t)
                     y_t^h = sum_s A_ts v_s^j / (sum_s A_ts + eps)
    recurrent form:  S_t^j = g_t^j S_{t-1}^j + v_t^j phi(k_t^j)^T
                     z_t^j = g_t^j z_{t-1}^j + phi(k_t^j)
                     y_t^h = S_t^j phi(q_t^h) / (z_t^j . phi(q_t^h) + eps)
    x_t <- x_t + concat_h(y_t^h) Wo ;   x_t <- x_t + swiglu(rmsnorm(x_t, ln2))

with ``phi(a) . phi(b) = (a . b)^2 / d`` (``ops/retention.py`` says how
``phi`` and the state are laid out). The two forms are equal term by
term; :func:`forward` and :func:`prefill_padded` run the second in
chunks, :func:`decode_step_slots` a position at a time.

Not in the published ``config.json``, set by the family's convention
(``benchmark/configs/brumby-14b-L8.json`` lists them as ``assumed``):
the degree 2; one gate scalar a kv head (the only width at which the
query heads of a group can share a state) with a bias leaf scaled by
``gate_bias``; ``q_norm`` / ``k_norm`` (per-head RMSNorm) and RoPE kept
from the dense layer the model was initialised from; the output
normalised by the summed weights, ``eps`` 1e-6; ``S`` and ``z`` in
float32, everything else in ``dtype``.

Everything but the mixing is ``models/llama.py``'s and is imported:
the stacked ``params["layers"]`` tree, ``_rmsnorm``, ``_rope``,
``_qkv`` / ``_qkv_cached`` (given the two head norms), ``_mlp``, the
head, ``horizon_scan``. Serving goes through ``serving/engine.py``'s
model seam (the ``serve_*`` methods); the paged, quantized-cache,
chunked-prefill and verify programs are the dense decoder's. No loss:
the model is served, not trained.
"""

from __future__ import annotations

from dataclasses import dataclass, fields
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from edl_tpu.models import llama as _ll
from edl_tpu.ops import retention as _ops


# the power of ``q . k``: ``ops/retention.py``'s ``phi`` is the symmetric
# SQUARE, so nothing else can be served (``retention_degree`` of a
# config is checked against it)
DEGREE = 2


@dataclass(frozen=True)
class RetentionConfig:
    vocab: int = 151936
    d_model: int = 5120
    n_layers: int = 40
    n_heads: int = 40
    n_kv_heads: int = 8
    head_dim: int = 128
    d_ff: int = 17408
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    # log g = logsigmoid(a Wg + gate_bias * b_g): with b_g ones and a
    # small Wg the decay horizon 1 / (1 - g) lies around e ** gate_bias
    gate_bias: float = 6.93
    eps: float = 1e-6  # beside the summed weights under the output
    # the prefill's two loop sizes: no part of the architecture, so no
    # export carries them (``to_meta``). 128 positions a chunk measured
    # best of 128 / 256 on a v5e (512 overflows VMEM in the kernel);
    # 1024 rows a piece is what keeps a 4096 bucket's temporaries
    # beside 24 slots of state. The tests shrink both.
    chunk: int = 128
    piece: int = 1024
    dtype: Any = jnp.bfloat16
    # the Pallas kernels: ``edl_retention_step`` in decode,
    # ``edl_retention_chunk`` in prefill. Off: the plain lines.
    use_kernel: bool = False

    # ``llama._qkv`` and ``llama._mlp`` ask these of a config
    int8_mxu = False
    int8_wgrad_bf16 = False
    # what the memory ledger files each array of the cache under
    serve_cache_kinds = ("state", "state")

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads or self.head_dim % 2:
            raise ValueError("heads must divide into kv heads, and the "
                             "head width be even")

    @classmethod
    def from_hf(cls, config: Dict, **overrides) -> "RetentionConfig":
        """From a published ``config.json`` of ``model_type: brumby``.
        What this file does not implement is refused rather than
        ignored."""
        for key, want in (("rope_scaling", None), ("attention_bias", False),
                          ("use_sliding_window", False),
                          ("tie_word_embeddings", False),
                          ("retention_degree", DEGREE)):
            if config.get(key, want) != want:
                raise NotImplementedError(
                    f"brumby with {key}={config[key]!r} (only {want!r})")
        return cls(**{**dict(
            vocab=config["vocab_size"], d_model=config["hidden_size"],
            n_layers=config["num_hidden_layers"],
            n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"], d_ff=config["intermediate_size"],
            rope_theta=float(config["rope_theta"]),
            norm_eps=float(config["rms_norm_eps"]),
        ), **overrides})

    def to_meta(self) -> Dict:
        """JSON-safe architecture record (rides export manifests so
        ``edl serve`` can rebuild the config; runtime/export.py)."""
        meta = {f.name: getattr(self, f.name) for f in fields(self)
                if f.name not in ("chunk", "piece")}
        return {**meta, "family": "retention",
                "dtype": jnp.dtype(self.dtype).name}

    @classmethod
    def from_meta(cls, meta: Dict) -> "RetentionConfig":
        if meta.get("family") != "retention":
            raise ValueError(
                f"not a retention export: family={meta.get('family')!r}")
        known = {f.name for f in fields(cls)}
        kw = {k: v for k, v in meta.items() if k in known}
        return cls(**{**kw, "dtype": jnp.dtype(meta["dtype"])})

    @property
    def groups(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def state_width(self) -> int:
        """Numbers of ``phi`` of one key, as stored (8320 at 128)."""
        return _ops.phi_width(self.head_dim)

    def state_bytes_per_slot(self) -> int:
        """``S`` and ``z`` of one sequence, all layers, float32."""
        return 4 * self.n_layers * self.n_kv_heads * (
            self.head_dim + 1) * self.state_width

    # -- what ``obs/costmodel.py`` asks a config that prices itself --------

    def matmul_params(self) -> float:
        """Parameters a token multiplies, the head included; the state
        counts as one: ``(KV + H) * d * phi`` products a layer."""
        d, hd = self.d_model, self.head_dim
        kvh, h = self.n_kv_heads, self.n_heads
        return (self.n_layers * (
            2 * d * h * hd + 2 * d * kvh * hd + d * kvh + 3 * d * self.d_ff
            + (kvh + h) * hd * self.state_width) + d * self.vocab)

    def n_params(self) -> float:
        d, hd = self.d_model, self.head_dim
        kvh, h = self.n_kv_heads, self.n_heads
        per_layer = (2 * d * h * hd + 2 * d * kvh * hd + (d + 1) * kvh
                     + 3 * d * self.d_ff + 2 * d + 2 * hd)
        return 2 * self.vocab * d + d + self.n_layers * per_layer

    def attn_width(self) -> int:
        """Nothing grows with the context: the state's products are in
        :meth:`matmul_params`."""
        return 0

    def cache_numbers_per_token(self) -> int:
        """No position is held: the cache is a state a slot."""
        return 0

    def cache_step_bytes_per_slot(self) -> int:
        """Bytes of cache a decode step moves for one live slot: its
        state read once and written once."""
        return 2 * self.state_bytes_per_slot()

    # -- what ``serving/engine.py`` asks a config it serves (its comment
    # gives the contract)

    def serve_cache_spec(self, slots: int, max_len: int):
        """Two arrays, neither with a position axis: ``S`` [L, slots,
        KV, d, phi] and ``z`` [L, slots, KV, phi], float32. ``max_len``
        bounds a request, not the cache."""
        lead = (self.n_layers, slots, self.n_kv_heads)
        return ((lead + (self.head_dim, self.state_width), jnp.float32),
                (lead + (self.state_width,), jnp.float32))

    def serve_prefill(self, params, tokens, last):
        logits, s, z = prefill_padded(params, tokens, last, self)
        return logits, (s, z)

    def serve_decode_block(self, params, tok, pos, active, rem, eosv, cache,
                           **kw):
        toks, tok, pos, active, rem, s, z = decode_horizon_slots(
            params, tok, pos, active, rem, eosv, *cache, self, **kw)
        return toks, tok, pos, active, rem, (s, z), {}

    def serve_attn_block(self, max_len: int) -> int:
        """A slot's state is one block, whatever the slot holds."""
        return max_len

    def serve_cache_read(self, held, max_len: int, block: int):
        """A live slot's state is read whole whatever it holds, an idle
        one's not at all."""
        return {"state_live_share": _ll.state_live_share(held)}


def init_params(key: jax.Array, cfg: RetentionConfig) -> Dict:
    """``llama.init_params``'s stacked tree with the layer's four
    leaves more: unit head norms, a small gate projection (std 0.5 /
    sqrt(d): the gate's logit then lies within ``gate_bias`` +- 1.4 for
    nearly every position) and a ones gate bias."""
    d, kvh, L = cfg.d_model, cfg.n_kv_heads, cfg.n_layers
    params = _ll.init_params(key, cfg)
    params["layers"].update(
        q_norm=jnp.ones((L, cfg.head_dim), jnp.float32),
        k_norm=jnp.ones((L, cfg.head_dim), jnp.float32),
        wg=jax.random.normal(jax.random.fold_in(key, 11), (L, d, kvh),
                             jnp.float32) * 0.5 * d ** -0.5,
        bg=jnp.ones((L, kvh), jnp.float32),
    )
    return params


# every matrix a decode step streams (the gate's [d, KV] stays as it is)
quantize_params_int8 = _ll.quantize_params_int8


def _gate(cfg: RetentionConfig, a: jnp.ndarray, lp: Dict) -> jnp.ndarray:
    """log g of [B, T, d] normed inputs: [B, T, KV] float32."""
    logit = jnp.einsum("btd,dk->btk", a, lp["wg"].astype(a.dtype),
                       preferred_element_type=jnp.float32)
    return jax.nn.log_sigmoid(
        logit + cfg.gate_bias * lp["bg"].astype(jnp.float32))


def _norms(lp: Dict):
    return lp["q_norm"], lp["k_norm"]


def _interpret(cfg: RetentionConfig) -> bool:
    """Whether the caller opened ``interpret_kernels`` (read while the
    program is traced, as the other models' kernels do)."""
    if not cfg.use_kernel:
        return False
    from edl_tpu.ops.flash_attention import _INTERPRET

    return _INTERPRET.get()


def _piece(cfg: RetentionConfig, x, lp: Dict, valid, start):
    """One layer over [B, R, d] rows that follow the state ``start``:
    (x, (S, z))."""
    b, t, _ = x.shape
    with jax.named_scope("attn"):
        a = _ll._rmsnorm(x, lp["ln1"], cfg.norm_eps)
        # held behind the barrier like a cached step's: the head split
        # folded into the products makes XLA want the stacked wq / wk /
        # wv input-minor, and it copies all layers of them (560 MB)
        q, k, v = _ll._qkv_cached(
            cfg, a, lp, valid[1], qk_norm=_norms(lp))
        y, s, z = _ops.retention_chunked(
            q.reshape(b, t, cfg.n_kv_heads, cfg.groups, cfg.head_dim), k, v,
            _gate(cfg, a, lp), valid[0], start, chunk=cfg.chunk, eps=cfg.eps,
            dtype=cfg.dtype, use_kernel=cfg.use_kernel,
            interpret=_interpret(cfg))
        x = x + _ll._matw(y.reshape(b, t, -1), lp["wo"])
    return _ll._mlp(cfg, x, lp), (s, z)


def _run(params: Dict, tokens, cfg: RetentionConfig, valid=None, last=None):
    """Embedding and every layer over [B, T] in the chunked form: (x, S,
    z). ``x`` is [B, T, d], or [B, d] at each row's ``last`` position
    if that is given. The states [L, B, KV, d, phi] / [L, B, KV, phi]
    are those after each row's last valid position.

    A long prompt goes through the layers ``cfg.piece`` rows at a time,
    every layer's state carried from piece to piece: each temporary is
    then a piece's (at 4096 rows the SwiGLU's alone are 430 MB), for
    one more read of the weights a piece. The pieces are the outer loop
    and the layers the inner one, so that a layer's weights still enter
    their products as slices of the stacked tree: inside a loop of
    their own they are copied out first."""
    b, t = tokens.shape
    if valid is None:
        valid = jnp.ones((b, t), bool)
    at = jnp.broadcast_to(jnp.arange(t), (b, t))
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    L = cfg.n_layers
    empty = _ops.empty_state(b, cfg.n_kv_heads, cfg.head_dim)
    states = tuple(jnp.broadcast_to(e, (L,) + e.shape) for e in empty)

    def layers(x, ok, at, states, fresh):
        """Every layer over one piece; ``fresh``: the rows start the
        sequence (static True, or a traced flag)."""

        def body(carry, lp_i):
            x, *states = carry
            lp, i = lp_i
            if fresh is True:
                start = empty
            else:
                start = tuple(jnp.where(fresh, 0.0, a[i]) for a in states)
            x, new = _piece(cfg, x, lp, (ok, at), start)
            return (x, *(a.at[i].set(n) for a, n in zip(states, new))), None

        (x, *states), _ = jax.lax.scan(
            body, (x, *states), (params["layers"], jnp.arange(L)))
        return x, tuple(states)

    pick = lambda y, where: y[jnp.arange(b), where]
    r = cfg.piece
    if t <= r or t % r:
        x, states = layers(x, valid, at, states, True)
        return (x if last is None else pick(x, last)), *states
    split = lambda a: jnp.moveaxis(
        a.reshape((b, t // r, r) + a.shape[2:]), 1, 0)

    def pieces(carry, xs):
        states, found = carry
        p, xp, ok, at = xs
        y, states = layers(xp, ok, at, states, p == 0)
        if last is None:
            return (states, found), y
        found = jnp.where((last // r == p)[:, None], pick(y, last % r), found)
        return (states, found), None

    (states, found), ys = jax.lax.scan(
        pieces, (states, jnp.zeros((b, x.shape[-1]), x.dtype)),
        (jnp.arange(t // r), split(x), split(valid), split(at)))
    if last is None:
        found = jnp.moveaxis(ys, 0, 1).reshape(b, t, -1)
    return found, *states


def _logits(params: Dict, x, cfg: RetentionConfig):
    with jax.named_scope("head"):
        x = _ll._rmsnorm(x, params["ln_f"], cfg.norm_eps)
        return _ll._matw(x, params["lm_head"]).astype(jnp.float32)


def forward(params: Dict, tokens: jnp.ndarray, cfg: RetentionConfig):
    """tokens [B, T] int32 -> logits [B, T, vocab] (float32)."""
    return _logits(params, _run(params, tokens, cfg)[0], cfg)


def prefill_padded(params: Dict, tokens: jnp.ndarray, last, cfg):
    """``llama.prefill_padded`` for this layer: an END-padded prompt
    batch [B, Tb] -> (logits [B, V] at each row's ``last`` index, S [L,
    B, KV, d, phi], z [L, B, KV, phi]: the state AFTER position
    ``last``). A positional cache ignores the rows past a prompt's end;
    a recurrence cannot, so a position past ``last`` neither decays the
    state nor enters it."""
    b, t = tokens.shape
    last = jnp.broadcast_to(last, (b,))
    valid = jnp.arange(t)[None, :] <= last[:, None]
    x, s, z = _run(params, tokens, cfg, valid, last)
    return _logits(params, x, cfg), s, z


def decode_step_slots(
    params: Dict,
    tok: jnp.ndarray,
    pos: jnp.ndarray,
    state: jnp.ndarray,
    z: jnp.ndarray,
    cfg: RetentionConfig,
    live: Optional[jnp.ndarray] = None,
):
    """``llama.decode_step_slots`` over the states [L, B, KV, d, phi] /
    [L, B, KV, phi]: tok [B] each slot's previous token, pos [B] its
    position (for RoPE alone: the state has no position to write).
    Returns (logits [B, V], state, z). A row that is not ``live``
    keeps its state: a recurrence re-run is not idempotent, and nobody
    reads a finished request's state before the next prefill replaces
    it whole."""
    b = tok.shape[0]
    if live is None:
        live = jnp.ones((b,), bool)
    interpret = _interpret(cfg)
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tok[:, None], axis=0).astype(cfg.dtype)
    for i in range(cfg.n_layers):
        lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
        with jax.named_scope("attn"):
            a = _ll._rmsnorm(x, lp["ln1"], cfg.norm_eps)
            q, k, v = _ll._qkv_cached(
                cfg, a, lp, pos[:, None], qk_norm=_norms(lp))
            y, state, z = _ops.retention_step(
                q.reshape(b, cfg.n_kv_heads, cfg.groups, cfg.head_dim),
                k[:, 0], v[:, 0], _gate(cfg, a, lp)[:, 0], state, z, i, live,
                eps=cfg.eps, dtype=cfg.dtype, use_kernel=cfg.use_kernel,
                interpret=interpret)
            x = x + _ll._matw(y.reshape(b, 1, -1), lp["wo"])
        x = _ll._mlp(cfg, x, lp)
    with jax.named_scope("head"):
        x = _ll._rmsnorm(x, params["ln_f"], cfg.norm_eps)
        logits = _ll._matw(x[:, 0], params["lm_head"]).astype(jnp.float32)
    return logits, state, z


def decode_horizon_slots(
    params, tok, pos, active, rem, eosv, state, z, cfg: RetentionConfig,
    horizon: int, key=None, temperature=None, sampling: bool = False,
):
    """``llama.decode_horizon_slots`` over the states: the scan, the
    token choice and the freezing of finished rows are
    ``llama.horizon_scan``'s. Returns ``(toks [B, horizon], tok, pos,
    active, rem, state, z)``."""

    def step(tok, pos, cache, active):
        logits, s, zz = decode_step_slots(
            params, tok, pos, *cache, cfg, live=active)
        return logits, (s, zz), ()

    toks, tok, pos, active, rem, (state, z), _ = _ll.horizon_scan(
        step, tok, pos, active, rem, eosv, (state, z), horizon,
        key=key, temperature=temperature, sampling=sampling)
    return toks, tok, pos, active, rem, state, z
