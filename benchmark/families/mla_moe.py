"""Family ``mla_moe``: the ``deepseek_v3`` decoder layer (multi-head
latent attention over a latent cache; a leading dense layer, then
sigmoid-routed SwiGLU experts that drop no token beside shared ones),
run by ``edl_tpu/models/deepseek_v3.py`` on the serving path. The only
file of the benchmark that names that model code, its reference
(``benchmark/reference/mla_moe.py``) or its arithmetic. Training is not
this family's: it gives no loss and no train steps.

``needed`` prices a decode step by what it MUST read: an expert nobody
chose is not read, so the experts' bytes follow the share of them that
the step's tokens hit (``experts_hit_share``, which the block program
counts on the device).
"""

from __future__ import annotations

import types
from typing import Dict

import jax
import jax.numpy as jnp

from benchmark.reference import mla_moe as reference
from edl_tpu.models import deepseek_v3
from edl_tpu.serving.engine import ContinuousBatchingEngine

# keys that must equal the published config's: every size, and every
# constant of the layer's arithmetic
widths = (
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "n_routed_experts",
    "n_shared_experts", "num_experts_per_tok", "first_k_dense_replace",
    "routed_scaling_factor", "norm_topk_prob", "scoring_func", "vocab_size",
    "rope_theta", "rms_norm_eps",
)
# depth alone may be cut: the dense layer and four expert layers at least
reducible = {"num_hidden_layers": 5}

# e_score_correction_bias is a trained buffer; drawn this wide it moves
# the choice of the sixth expert in a share of the tokens, as the
# checkpoint's does, and the weights never
ROUTER_BIAS_STD = 0.02
# the routed experts' down projection is drawn at a quarter of the
# fan-in std. bfloat16 flips a near-tied sixth choice in about every
# eighth token and layer whatever the weights are (so does this
# family's reference with its operands rounded to bfloat16), and at the
# fan-in std one flip swaps a sixth of a term as large as every other
# sublayer's: a third of the served tokens then leave the reference's
# first choice (gap mean 0.24 on the chip, PERF.md section 6, PR 29)
# and int8 reads 1.85 times bfloat16, coin flips both. At a quarter the
# experts still move every logit, a flip no longer decides it, and the
# comparison reads arithmetic again
ROUTED_DOWN_STD = 0.25


def rehearsal_config() -> Dict:
    """Tiny widths for --rehearse (CPU tests), the published keys."""
    return {
        "hidden_size": 64, "intermediate_size": 128,
        "moe_intermediate_size": 32, "num_attention_heads": 4,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "n_routed_experts": 8, "n_shared_experts": 2,
        "num_experts_per_tok": 3, "first_k_dense_replace": 1,
        "routed_scaling_factor": 2.448, "norm_topk_prob": True,
        "scoring_func": "sigmoid", "num_hidden_layers": 3, "vocab_size": 256,
        "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    }


def _sizes(config: Dict):
    d, h = config["hidden_size"], config["num_attention_heads"]
    return types.SimpleNamespace(
        d=d, h=h, L=config["num_hidden_layers"], V=config["vocab_size"],
        dense=config["first_k_dense_replace"], ff=config["intermediate_size"],
        f=config["moe_intermediate_size"], E=config["n_routed_experts"],
        shared=config["n_shared_experts"], k=config["num_experts_per_tok"],
        r=config["kv_lora_rank"], rope=config["qk_rope_head_dim"],
        qk=config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        nv=config["qk_nope_head_dim"] + config["v_head_dim"],
        v=config["v_head_dim"])


def param_layout(config: Dict) -> Dict:
    """{path: (shape, std or None for a norm weight, drawn a leading
    index at a time?)} of the program's tree: per-layer leaves under
    ``("layers", "<nn>", ...)``, an expert layer's experts stacked on
    the leaf's own leading axis."""
    z = _sizes(config)
    out = {
        ("embed",): ((z.V, z.d), 0.02, False),
        ("ln_f",): ((z.d,), None, False),
        ("lm_head",): ((z.d, z.V), z.d ** -0.5, False),
    }
    for i in range(z.L):
        leaves = [
            ("ln1", (z.d,), None), ("ln2", (z.d,), None),
            ("wq", (z.d, z.h * z.qk), z.d ** -0.5),
            ("wkva", (z.d, z.r + z.rope), z.d ** -0.5),
            ("kv_norm", (z.r,), None),
            ("wkvb", (z.r, z.h * z.nv), z.r ** -0.5),
            ("wo", (z.h * z.v, z.d), (z.h * z.v) ** -0.5),
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
                ("we1", (z.E, z.d, z.f), z.d ** -0.5),
                ("we3", (z.E, z.d, z.f), z.d ** -0.5),
                ("we2", (z.E, z.f, z.d), ROUTED_DOWN_STD * z.f ** -0.5),
                ("ws1", (z.d, fs), z.d ** -0.5),
                ("ws3", (z.d, fs), z.d ** -0.5),
                ("ws2", (fs, z.d), fs ** -0.5),
            ]
        for name, shape, std in leaves:
            out[("layers", f"{i:02d}", name)] = (
                shape, std, name in ("we1", "we3", "we2"))
    return out


def program_config(config: Dict, *, training: bool, control: bool = False):
    """The program's DeepseekV3Config for a published config (serving's
    control is :func:`control_params`)."""
    if training:
        raise NotImplementedError(
            "family mla_moe is served, not trained: "
            "edl_tpu/models/deepseek_v3.py has no loss")
    return deepseek_v3.DeepseekV3Config.from_hf(
        config, dtype=jnp.bfloat16, use_flash=True)


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
    return jax.jit(deepseek_v3.quantize_params_int8)(params)


# tokens [T] of one sequence -> the plain reference's logits [T, V]
reference_logits = reference.logits_row


# -- needed bytes (the readers' numerators) -----------------------------------


def latent_bytes_per_token(config: Dict, bytes_per_el: int = 2) -> int:
    """The latent row (c | k_rope) one position holds, one layer."""
    return (config["kv_lora_rank"] + config["qk_rope_head_dim"]) * bytes_per_el


def expert_layers(config: Dict) -> int:
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def expert_bytes(config: Dict, hit_share: float, bytes_per_param: int = 2):
    """Weights of the routed experts one decode step must read, all
    expert layers: those of the experts its tokens hit."""
    z = _sizes(config)
    return (expert_layers(config) * hit_share * z.E * 3 * z.d * z.f
            * bytes_per_param)


def decode_step_bytes(config: Dict, resident_tokens: float,
                      hit_share: float = 1.0, bytes_per_param: int = 2):
    """Bytes one decode step has to read: attention, router, shared and
    dense-layer weights and the head once, the weights of the experts
    hit, and the latent rows of the resident tokens. The embedding is a
    lookup; an expert nobody chose is not read."""
    z = _sizes(config)
    attn = z.d * z.h * z.qk + z.d * (z.r + z.rope) + z.r * z.h * z.nv \
        + z.h * z.v * z.d
    always = (z.L * attn + z.dense * 3 * z.d * z.ff
              + expert_layers(config) * (z.d * z.E + 3 * z.d * z.shared * z.f)
              + z.d * z.V)
    return (always * bytes_per_param
            + expert_bytes(config, hit_share, bytes_per_param)
            + resident_tokens * z.L * latent_bytes_per_token(config))


needed = types.SimpleNamespace(
    decode_step_bytes=decode_step_bytes, expert_bytes=expert_bytes,
    latent_bytes_per_token=latent_bytes_per_token,
    expert_layers=expert_layers)
