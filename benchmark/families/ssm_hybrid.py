"""Family ``ssm_hybrid``: a decoder whose layers are of two kinds,
selective state-space layers (Mamba-2) beside a few softmax-attention
layers, a SwiGLU after each and one embedding that is also the head
(``model_type: granitemoehybrid`` with no experts), run by
``edl_tpu/models/ssm_hybrid.py`` on the serving path. The only file of
the benchmark that names that model code, its reference
(``benchmark/reference/ssm_hybrid.py``) or its arithmetic. Training is
not this family's: it gives no loss and no train steps.

``needed`` prices a decode step by what it MUST move: every parameter
once (the embedding once: it is the head), the state of each live slot
(``S`` in float32 and the convolution's tail) read once and written
once, and the keys and values of the positions resident.

**The draw.** ``harness.make_params`` draws a leaf normal at one std or
as ones. What the library initialises by rule and not by a normal draw
(``A``, the step sizes) is set by :func:`published_form`, the ONE
function that ``engine``, ``control_params`` and ``reference_logits``
all go through; it replaces leaves by constants, so applying it twice
is applying it once. The configuration's file states the rule and the
numbers (``assumed.draw``).
"""

from __future__ import annotations

import types
from typing import Dict

import jax
import jax.numpy as jnp

from benchmark.reference import ssm_hybrid as reference
from edl_tpu.models import ssm_hybrid
from edl_tpu.serving.engine import ContinuousBatchingEngine

# keys that must equal the published config's: every size, and every
# constant of the layer's arithmetic that the source publishes
widths = (
    "hidden_size", "intermediate_size", "shared_intermediate_size",
    "num_attention_heads", "num_key_value_heads", "vocab_size",
    "rms_norm_eps", "attention_multiplier", "embedding_multiplier",
    "residual_multiplier", "logits_scaling", "mamba_chunk_size",
    "mamba_d_conv", "mamba_d_head", "mamba_d_state", "mamba_expand",
    "mamba_n_groups", "mamba_n_heads", "mamba_conv_bias", "mamba_proj_bias",
    "attention_bias", "hidden_act", "normalization_function",
    "num_local_experts", "num_experts_per_tok", "position_embedding_type",
    "tie_word_embeddings",
)
# depth alone may be cut, and the pattern with it: a whole period of
# the published pattern and four more
reducible = {"num_hidden_layers": 14, "layer_types": 14}

# not in the source's config.json: the configuration's file states each
# of these under ``assumed``, with its reason, and carries the numbers
# as keys of its own so that program and reference read the same ones
ASSUMED = {"head_dim": 64, "dt_draw_lo": 1e-3, "dt_draw_hi": 1e-1,
           "a_draw_lo": 1.0, "a_draw_hi": 16.0}
# ``dt_proj`` is drawn at a quarter of the fan-in std: the bias and not
# the noise then sets a head's step (``dt`` varies by e ** +-0.25 with
# the token); the convolution's four taps of unit inputs at 0.5 sum to
# unit variance
DT_STD, CONV_STD = 0.25, 0.5


def rehearsal_config() -> Dict:
    """Tiny widths for --rehearse (CPU tests), the published keys: both
    kinds of layer in a pattern that is not the published one, two kv
    heads a 128-lane row as published. The draw is the published
    configuration's: of its heads' horizons (0.6 to 1000 positions) the
    short ones forget within the rehearsal's tens of positions and the
    long ones carry all of them."""
    return {
        "hidden_size": 64, "intermediate_size": 128,
        "shared_intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "vocab_size": 256, "rms_norm_eps": 1e-5,
        "attention_multiplier": 0.015625, "embedding_multiplier": 12,
        "residual_multiplier": 0.22, "logits_scaling": 8,
        "mamba_chunk_size": 8, "mamba_d_conv": 4, "mamba_d_head": 16,
        "mamba_d_state": 32, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 8, "mamba_conv_bias": True, "mamba_proj_bias": False,
        "attention_bias": False, "hidden_act": "silu",
        "normalization_function": "rmsnorm", "num_local_experts": 0,
        "num_experts_per_tok": 0, "position_embedding_type": "nope",
        "tie_word_embeddings": True, "num_hidden_layers": 5,
        "layer_types": ["mamba", "attention", "mamba", "mamba", "attention"],
        **ASSUMED,
    }


def _sizes(config: Dict):
    kinds = config["layer_types"]
    di = config["mamba_n_heads"] * config["mamba_d_head"]
    return types.SimpleNamespace(
        d=config["hidden_size"], h=config["num_attention_heads"],
        kv=config["num_key_value_heads"], hd=config["head_dim"],
        ff=config["shared_intermediate_size"], V=config["vocab_size"],
        Lm=kinds.count("mamba"), La=kinds.count("attention"),
        hm=config["mamba_n_heads"], p=config["mamba_d_head"],
        n=config["mamba_d_state"], k=config["mamba_d_conv"], di=di,
        cw=di + 2 * config["mamba_n_groups"] * config["mamba_d_state"])


def param_layout(config: Dict) -> Dict:
    """{path: (shape, std or None for a leaf of ones, stacked?)} of the
    program's tree: the embedding (which is the head), the last norm,
    and one stacked tree a kind of layer. ``A_log`` and ``dt_bias`` are
    drawn as ones and set by :func:`published_form`."""
    z = _sizes(config)
    # the embedding at 0.02 / embedding_multiplier: what enters the
    # first layer then has the std 0.02 of the other families'
    # embeddings. At 0.02 itself the tied head scores the token just
    # read 12 |e|^2 against the others' sqrt(d) |e|, 6.9 sigma over
    # them at the published widths, and every greedy answer repeats
    # its prompt's last token: no comparison would see the state
    out = {("embed",): ((z.V, z.d), 0.02 / config["embedding_multiplier"],
                        False),
           ("ln_f",): ((z.d,), None, False)}
    mlp = (("ln2", (z.d,), None), ("w1", (z.d, z.ff), z.d ** -0.5),
           ("w3", (z.d, z.ff), z.d ** -0.5), ("w2", (z.ff, z.d), z.ff ** -0.5))
    for name, shape, std in (
        ("ln1", (z.d,), None),
        ("in_proj", (z.d, z.di + z.cw), z.d ** -0.5),
        ("dt_proj", (z.d, z.hm), DT_STD * z.d ** -0.5),
        ("conv_w", (z.k, z.cw), CONV_STD), ("conv_b", (z.cw,), CONV_STD),
        ("A_log", (z.hm,), None), ("dt_bias", (z.hm,), None),
        ("D", (z.hm,), None), ("norm", (z.di,), None),
        ("out_proj", (z.di, z.d), z.di ** -0.5),
    ) + mlp:
        out[("mamba", name)] = ((z.Lm,) + shape, std, True)
    for name, shape, std in (
        ("ln1", (z.d,), None),
        ("wq", (z.d, z.h * z.hd), z.d ** -0.5),
        ("wk", (z.d, z.kv * z.hd), z.d ** -0.5),
        ("wv", (z.d, z.kv * z.hd), z.d ** -0.5),
        ("wo", (z.h * z.hd, z.d), (z.h * z.hd) ** -0.5),
    ) + mlp:
        out[("attn", name)] = ((z.La,) + shape, std, True)
    return out


def published_form(params: Dict) -> Dict:
    """The harness's draw in the published parametrisation: ``A_log``
    and ``dt_bias`` of every state-space layer as the library
    initialises them, spread evenly over the heads instead of drawn
    (head ``i`` of ``H``: ``A`` from ``a_draw_lo`` to ``a_draw_hi``,
    the step size log-uniform from ``dt_draw_lo`` to ``dt_draw_hi``,
    ``dt_bias`` its inverse softplus). Everything else is the draw's."""
    like = params["mamba"]["A_log"]  # [Lm, H]
    heads = like.shape[-1]
    i = jnp.arange(heads, dtype=jnp.float32) / max(heads - 1, 1)
    a = ASSUMED["a_draw_lo"] + (
        ASSUMED["a_draw_hi"] - ASSUMED["a_draw_lo"]) * i
    dt = ASSUMED["dt_draw_lo"] * (
        ASSUMED["dt_draw_hi"] / ASSUMED["dt_draw_lo"]) ** i
    spread = lambda v: jnp.broadcast_to(v, like.shape).astype(like.dtype)
    mamba = {**params["mamba"], "A_log": spread(jnp.log(a)),
             "dt_bias": spread(dt + jnp.log(-jnp.expm1(-dt)))}
    return {**params, "mamba": mamba}


def program_config(config: Dict, *, training: bool, control: bool = False):
    """The program's SSMHybridConfig for a published config (serving's
    control is :func:`control_params`)."""
    if training:
        raise NotImplementedError(
            "family ssm_hybrid is served, not trained: "
            "edl_tpu/models/ssm_hybrid.py has no loss")
    return ssm_hybrid.SSMHybridConfig.from_hf(
        config, head_dim=config["head_dim"], dtype=jnp.bfloat16,
        use_kernel=True)


# -- serving (kinds/serve.py) -------------------------------------------------


def engine(params, program_cfg, spec: Dict, metrics):
    """The engine ``edl serve`` runs, sized by the cell's ``engine``
    (``horizon``: decode steps a dispatch, ``edl serve --horizon``)."""
    return ContinuousBatchingEngine(
        published_form(params), program_cfg,
        max_slots=int(spec["max_slots"]), max_len=int(spec["max_len"]),
        horizon=int(spec.get("horizon", 1)), metrics=metrics)


def control_params(params):
    """The served tree in the program's own precision below bfloat16:
    int8 projection, SwiGLU and head weights. (``engine`` sets ``A_log``
    and ``dt_bias`` of what it is given, this tree too.)"""
    return jax.jit(ssm_hybrid.quantize_params_int8)(params)


def reference_logits(params, tokens, config: Dict):
    """tokens [T] of one sequence -> the plain reference's logits [T,
    V], from the weights the engine was given."""
    return reference.logits_row(published_form(params), tokens, config)


# -- needed bytes and operations (the readers' numerators) ---------------------


def n_params(config: Dict) -> int:
    """Every parameter, the embedding counted once (it is the head)."""
    z = _sizes(config)
    mlp = 3 * z.d * z.ff + 2 * z.d
    mamba = (z.d * (z.di + z.cw + z.hm) + (z.k + 1) * z.cw + 3 * z.hm
             + z.di + z.di * z.d + mlp)
    attn = 2 * z.d * z.h * z.hd + 2 * z.d * z.kv * z.hd + mlp
    return z.Lm * mamba + z.La * attn + z.V * z.d + z.d


def weight_bytes(config: Dict, bytes_per_param: int = 2) -> int:
    """Every parameter a decode step streams: the layers' and the head,
    which is the embedding, counted once."""
    return n_params(config) * bytes_per_param


def ssm_state_bytes_per_slot(config: Dict) -> int:
    """``S`` alone, one sequence, all state-space layers: float32."""
    z = _sizes(config)
    return z.Lm * z.hm * z.p * z.n * 4


def state_bytes_per_slot(config: Dict) -> int:
    """The state one sequence holds, all state-space layers: ``S`` in
    float32 and the convolution's tail (three inputs) in bfloat16."""
    z = _sizes(config)
    return ssm_state_bytes_per_slot(config) + z.Lm * (z.k - 1) * z.cw * 2


def kv_bytes_per_token(config: Dict) -> int:
    """Keys and values one position holds, the attention layers'."""
    z = _sizes(config)
    return z.La * 2 * z.kv * z.hd * 2


def decode_step_bytes(config: Dict, live_slots: float, live_tokens: float,
                      bytes_per_param: int = 2) -> float:
    """Bytes one decode step has to move: the weights once, each live
    slot's state read once and written once, and the keys and values of
    the positions resident read once."""
    return weight_bytes(config, bytes_per_param) \
        + 2 * live_slots * state_bytes_per_slot(config) \
        + live_tokens * kv_bytes_per_token(config)


def ssd_scan_flops(config: Dict, tokens: float) -> float:
    """Operations the recurrence's own products take for ``tokens``
    positions, all state-space layers: each position enters the state
    once and reads it once, ``2 * H * P * N`` each. A chunked form does
    more arithmetic than this and so reads lower."""
    z = _sizes(config)
    return tokens * z.Lm * 4 * z.hm * z.p * z.n


needed = types.SimpleNamespace(
    n_params=n_params, weight_bytes=weight_bytes,
    ssm_state_bytes_per_slot=ssm_state_bytes_per_slot,
    state_bytes_per_slot=state_bytes_per_slot,
    kv_bytes_per_token=kv_bytes_per_token,
    decode_step_bytes=decode_step_bytes, ssd_scan_flops=ssd_scan_flops)
