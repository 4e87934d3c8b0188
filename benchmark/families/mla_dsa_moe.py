"""Family ``mla_dsa_moe``: the ``glm_moe_dsa`` decoder layer (multi-head
latent attention with a low-rank query; a learned indexer that chooses
the ``index_topk`` cached positions each query attends; leading dense
layers, then sigmoid-routed SwiGLU experts that drop no token beside a
shared one), run by ``edl_tpu/models/glm_dsa.py`` on the serving path
as ONE CHIP'S SHARE of a deployment: the configuration's
``n_routed_experts`` is how many experts are held here, from
``first_routed_expert``, of the ``published.n_routed_experts`` the
router scores; its ``vocab_size`` is the slice of the vocabulary held.
The only file of the benchmark that names that model code, its
reference (``benchmark/reference/mla_dsa_moe.py``) or its arithmetic.
Training is not this family's: it gives no loss and no train steps.

``needed`` prices a decode step by what it MUST read, whatever
implements it: every weight outside the routed experts once, the
weights of the held experts its tokens hit, the index key of every live
position, and the latent rows of the positions chosen (``min(live,
index_topk)`` a slot), not of all that are live.
"""

from __future__ import annotations

import types
from typing import Dict

import jax
import jax.numpy as jnp

from benchmark.reference import mla_dsa_moe as reference
from edl_tpu.models import glm_dsa
from edl_tpu.serving.engine import ContinuousBatchingEngine

# keys that must equal the published config's: every size, and every
# constant of the layer's arithmetic
widths = (
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "index_n_heads", "index_head_dim",
    "index_topk", "n_shared_experts", "num_experts_per_tok",
    "routed_scaling_factor", "norm_topk_prob", "scoring_func",
    "rope_parameters", "rms_norm_eps",
)
# what may be cut, and the least that may be left (the guide's floors):
# the leading dense layers counted once and four expert layers; 8 routed
# experts held; an eighth of the vocabulary; the prediction module is a
# further pipeline stage's
reducible = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
             "n_routed_experts": 8, "vocab_size": 19360,
             "num_nextn_predict_layers": 0}

# not in the source's config.json: the configuration's file states each
# under ``assumed`` with its reason
ROUTER_BIAS_STD = 0.02  # e_score_correction_bias, as family mla_moe's
ROUTED_DOWN_STD = 0.25  # x fan-in std, as family mla_moe's (its comment)
INDEX_BIAS_STD = 0.02  # the index key's LayerNorm bias
# The embedding is drawn at std 1 and ``Wo`` at half the fan-in std.
# What the cell compares is how far a served token lies under the
# reference's best, so it wants (a) answers that do not repeat
# themselves, (b) a sound program's rounding small beside int8
# weights', (c) WHAT was attended still reaching the logits. With the
# embedding at 0.02 the residual stream of a position is its attention
# output (an average over 2048 rows, nearly the same for neighbouring
# positions) and not its token: greedy answers repeat themselves (at
# middle widths on the CPU 30-77 distinct tokens in an answer of 192),
# their near-ties come in bursts, and a run's mean gap spreads 35-fold
# over seeds (PR 41's first draw, ``Wo`` at 4 x: sound 8.5e-5..3.0e-3
# beside int8 8.1e-4..6.8e-3 by the cell). At std 1 the token carries
# the stream and an answer of 303 tokens holds 296 distinct ones (the
# chip). A sound run's gap is then mostly the choice of keys (it is
# twice as large where the indexer chooses as where every key is
# attended): bfloat16 flips near-tied positions at the 2048th place,
# which attention's output feels, so the smaller ``Wo`` the further
# sound lies under int8; and the smaller ``Wo`` the less a wrong
# choice of keys shows. Read on the chip over
# 8192 positions, every position compared (gap mean where the indexer
# chooses, two seeds; PERF.md section 2, PR 41): (embedding, Wqb, Wo) =
# (1, 1, 1) sound 3.1e-4 / 3.3e-4, int8 1.20e-3 / 1.31e-3, every key
# attended 0.0103 / 0.0099, the first 2048 for the best 0.022 / 0.021;
# (1, 1, 0.5) 2.5e-4 / 2.2e-4, 1.12e-3 / 1.05e-3, 2.8e-3 / 2.8e-3,
# 5.2e-3 / 5.1e-3: at 0.5 int8 is 4.5-4.9 x sound and the faults 2.5 and
# 4.7 x int8. Peaking the softmax instead (``Wqb`` at 2-4 x) lets
# bfloat16's rounding of the absorbed scores choose the next layer's
# keys: (0.02, 4, 1) sound 0.84 / int8 1.23; (0.02, 2, 1) 0.089 / 0.17
QUERY_UP_STD = 1.0  # x fan-in std of ``Wqb``
ATTN_OUT_STD = 0.5  # x fan-in std of ``Wo``
EMBED_STD = 1.0  # of the embedding's rows


def rehearsal_config() -> Dict:
    """Tiny widths for --rehearse (CPU tests), the published keys: two
    of eight experts held, ``index_topk`` several times shorter than
    the rehearsal's contexts."""
    return {
        "hidden_size": 64, "intermediate_size": 128,
        "moe_intermediate_size": 32, "num_attention_heads": 4,
        "q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "index_n_heads": 2,
        "index_head_dim": 16, "index_topk": 8, "n_routed_experts": 4,
        "first_routed_expert": 0, "published": {"n_routed_experts": 8},
        "n_shared_experts": 1, "num_experts_per_tok": 3,
        "first_k_dense_replace": 1, "routed_scaling_factor": 2.5,
        "norm_topk_prob": True, "scoring_func": "sigmoid",
        "num_hidden_layers": 3, "vocab_size": 256,
        "rope_parameters": {"rope_theta": 10000.0, "rope_type": "default"},
        "rms_norm_eps": 1e-5,
    }


def _sizes(config: Dict):
    d, h = config["hidden_size"], config["num_attention_heads"]
    return types.SimpleNamespace(
        d=d, h=h, L=config["num_hidden_layers"], V=config["vocab_size"],
        dense=config["first_k_dense_replace"], ff=config["intermediate_size"],
        f=config["moe_intermediate_size"], held=config["n_routed_experts"],
        E=config["published"]["n_routed_experts"],
        shared=config["n_shared_experts"], k=config["num_experts_per_tok"],
        qr=config["q_lora_rank"], r=config["kv_lora_rank"],
        rope=config["qk_rope_head_dim"],
        qk=config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        nv=config["qk_nope_head_dim"] + config["v_head_dim"],
        v=config["v_head_dim"], hi=config["index_n_heads"],
        di=config["index_head_dim"], topk=config["index_topk"])


def param_layout(config: Dict) -> Dict:
    """{path: (shape, std or None for a norm weight, drawn a leading
    index at a time?)} of the program's tree: per-layer leaves under
    ``("layers", "<nn>", ...)``, an expert layer's HELD experts stacked
    on the leaf's own leading axis."""
    z = _sizes(config)
    out = {
        ("embed",): ((z.V, z.d), EMBED_STD, False),
        ("ln_f",): ((z.d,), None, False),
        ("lm_head",): ((z.d, z.V), z.d ** -0.5, False),
    }
    for i in range(z.L):
        leaves = [
            ("ln1", (z.d,), None), ("ln2", (z.d,), None),
            ("wqa", (z.d, z.qr), z.d ** -0.5),
            ("q_norm", (z.qr,), None),
            ("wqb", (z.qr, z.h * z.qk), QUERY_UP_STD * z.qr ** -0.5),
            ("wkva", (z.d, z.r + z.rope), z.d ** -0.5),
            ("kv_norm", (z.r,), None),
            ("wkvb", (z.r, z.h * z.nv), z.r ** -0.5),
            ("wo", (z.h * z.v, z.d), ATTN_OUT_STD * (z.h * z.v) ** -0.5),
            ("wqi", (z.qr, z.hi * z.di), z.qr ** -0.5),
            ("wki", (z.d, z.di), z.d ** -0.5),
            ("ki_norm", (z.di,), None),
            ("ki_bias", (z.di,), INDEX_BIAS_STD),
            ("ww", (z.d, z.hi), z.d ** -0.5),
        ]
        if i < z.dense:
            leaves += [("w1", (z.d, z.ff), z.d ** -0.5),
                       ("w3", (z.d, z.ff), z.d ** -0.5),
                       ("w2", (z.ff, z.d), z.ff ** -0.5)]
        else:
            fs = z.shared * z.f
            leaves += [
                ("router", (z.d, z.E), z.d ** -0.5),
                ("router_bias", (z.E,), ROUTER_BIAS_STD),
                ("we1", (z.held, z.d, z.f), z.d ** -0.5),
                ("we3", (z.held, z.d, z.f), z.d ** -0.5),
                ("we2", (z.held, z.f, z.d), ROUTED_DOWN_STD * z.f ** -0.5),
                ("ws1", (z.d, fs), z.d ** -0.5),
                ("ws3", (z.d, fs), z.d ** -0.5),
                ("ws2", (fs, z.d), fs ** -0.5),
            ]
        for name, shape, std in leaves:
            out[("layers", f"{i:02d}", name)] = (
                shape, std, name in ("we1", "we3", "we2"))
    return out


def program_config(config: Dict, *, training: bool, control: bool = False):
    """The program's GlmDsaConfig for a configuration's file: the
    router as wide as published, the experts held as the file says
    (serving's control is :func:`control_params`)."""
    if training:
        raise NotImplementedError(
            "family mla_dsa_moe is served, not trained: "
            "edl_tpu/models/glm_dsa.py has no loss")
    return glm_dsa.GlmDsaConfig.from_hf(
        {**config,
         "n_routed_experts": config["published"]["n_routed_experts"]},
        experts_held=config["n_routed_experts"],
        first_expert=int(config.get("first_routed_expert", 0)),
        dtype=jnp.bfloat16, use_flash=True)


# -- serving (kinds/serve.py) -------------------------------------------------


def engine(params, program_cfg, spec: Dict, metrics):
    """The engine ``edl serve`` runs, sized by the cell's ``engine``
    (``horizon``: decode steps a dispatch, ``edl serve --horizon``)."""
    return ContinuousBatchingEngine(
        params, program_cfg, max_slots=int(spec["max_slots"]),
        max_len=int(spec["max_len"]), horizon=int(spec.get("horizon", 1)),
        metrics=metrics)


def control_params(params):
    """The served tree in the program's own precision below bfloat16:
    int8 attention, expert, shared and head weights."""
    return jax.jit(glm_dsa.quantize_params_int8)(params)


# tokens [T] of one sequence -> the plain reference's logits [T, V]
reference_logits = reference.logits_row


# -- needed operations and bytes (the readers' numerators) -------------------


def expert_layers(config: Dict) -> int:
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def expert_bytes(config: Dict, hit_share: float, bytes_per_param: int = 2):
    """Weights of the routed experts one decode step must read, all
    expert layers: those of the HELD experts its tokens hit."""
    z = _sizes(config)
    return (expert_layers(config) * hit_share * z.held * 3 * z.d * z.f
            * bytes_per_param)


def _always_params(config: Dict) -> int:
    """Parameters every decode step multiplies: attention, indexer,
    dense SwiGLU, shared experts, routers and the head."""
    z = _sizes(config)
    attn = (z.d * z.qr + z.qr * z.h * z.qk + z.d * (z.r + z.rope)
            + z.r * z.h * z.nv + z.h * z.v * z.d
            + z.qr * z.hi * z.di + z.d * z.di + z.d * z.hi)
    return (z.L * attn + z.dense * 3 * z.d * z.ff
            + expert_layers(config) * (z.d * z.E + 3 * z.d * z.shared * z.f)
            + z.d * z.V)


def weight_bytes(config: Dict, bytes_per_param: int = 2) -> int:
    """Every parameter held: what ``_always_params`` counts, the held
    experts whole, the embedding, norms and biases."""
    z = _sizes(config)
    small = z.d + z.L * (2 * z.d + z.qr + z.r + 2 * z.di) \
        + expert_layers(config) * z.E
    return int((_always_params(config) + z.V * z.d + small) * bytes_per_param
               + expert_bytes(config, 1.0, bytes_per_param))


def index_key_bytes(config: Dict, bytes_per_el: int = 2) -> int:
    """The index key one position holds, one layer."""
    return config["index_head_dim"] * bytes_per_el


def selected_row_bytes(config: Dict, bytes_per_el: int = 2) -> int:
    """The latent row (c | k_rope) of one chosen position, one layer."""
    return (config["kv_lora_rank"] + config["qk_rope_head_dim"]) * bytes_per_el


def selected_positions(config: Dict, live_tokens: float, live_slots: float):
    """Positions a step attends, all live slots: ``min(live,
    index_topk)`` a slot, at the slots' mean length."""
    if live_slots <= 0:
        return 0.0
    return live_slots * min(live_tokens / live_slots, config["index_topk"])


def decode_step_bytes(config: Dict, live_slots: float, live_tokens: float,
                      experts_hit: float = 1.0, bytes_per_param: int = 2):
    """Bytes one decode step has to read: the weights every step
    multiplies once, the weights of the held experts hit, the index
    keys of the ``live_tokens`` positions resident and the latent rows
    of the positions chosen. The embedding is a lookup; an expert
    nobody chose and a position not chosen are not read."""
    z = _sizes(config)
    return (_always_params(config) * bytes_per_param
            + expert_bytes(config, experts_hit, bytes_per_param)
            + z.L * (live_tokens * index_key_bytes(config)
                     + selected_positions(config, live_tokens, live_slots)
                     * selected_row_bytes(config)))


def index_score_flops(config: Dict, queries: float, keys: float) -> float:
    """``I[t, s]`` for ``queries`` queries against ``keys`` positions
    each, one layer: a dot product of ``index_head_dim`` a head."""
    z = _sizes(config)
    return 2.0 * queries * keys * z.hi * z.di


needed = types.SimpleNamespace(
    weight_bytes=weight_bytes, decode_step_bytes=decode_step_bytes,
    expert_bytes=expert_bytes, expert_layers=expert_layers,
    index_key_bytes=index_key_bytes, selected_row_bytes=selected_row_bytes,
    selected_positions=selected_positions,
    index_score_flops=index_score_flops)
