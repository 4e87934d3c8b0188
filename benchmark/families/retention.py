"""Family ``retention``: the power-retention decoder layer (a dense
decoder of Qwen3's shapes whose attention is ``(q . k) ** 2`` with a
per-head decay, kept as a fixed-size state a sequence instead of a
cache indexed by position), run by ``edl_tpu/models/retention.py`` on
the serving path. The only file of the benchmark that names that model
code, its reference (``benchmark/reference/retention.py``) or its
arithmetic. Training is not this family's: it gives no loss and no
train steps.

``needed`` prices a decode step by what it MUST move: every matmul
weight once, and the state of each live slot read once and written
once, counted at the packed symmetric width ``D = d (d + 1) / 2`` in
float32 whatever layout the program stores (its 65 rows of 128 are
0.8% wider).
"""

from __future__ import annotations

import types
from typing import Dict

import jax
import jax.numpy as jnp

from benchmark.reference import retention as reference
from edl_tpu.models import retention
from edl_tpu.serving.engine import ContinuousBatchingEngine

# keys that must equal the published config's: every size, and every
# constant of the layer's arithmetic that the source publishes
widths = (
    "hidden_size", "intermediate_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "vocab_size", "rope_theta",
    "rms_norm_eps", "max_window_layers", "tie_word_embeddings",
)
# depth alone may be cut; all layers are of one kind, four at least
reducible = {"num_hidden_layers": 4}

# not in the source's config.json: the configuration's file states each
# of these under ``assumed``, with its reason, and carries the numbers
# as keys of its own so that program and reference read the same ones
ASSUMED = {"retention_degree": 2, "gate_bias": 6.93, "retention_eps": 1e-6}
# the gate's projection is drawn at half the fan-in std: the logit is
# then gate_bias +- 1.4 for 99% of the heads and positions, a decay
# horizon 1 / (1 - g) of 256 to 4096 positions around e ** 6.93 = 1024.
# (At the plain fan-in std around 0 the gate is 0.5, the state forgets
# in three positions and no comparison would see what it carries.)
GATE_STD = 0.5


def rehearsal_config() -> Dict:
    """Tiny widths for --rehearse (CPU tests), the published keys. Five
    query heads a kv head, as published; a gate that forgets over tens
    of positions, the rehearsal's lengths."""
    return {
        "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 10, "num_key_value_heads": 2, "head_dim": 8,
        "num_hidden_layers": 2, "vocab_size": 256, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-6, "max_window_layers": 2,
        "tie_word_embeddings": False, **ASSUMED, "gate_bias": 3.0,
    }


def _sizes(config: Dict):
    return types.SimpleNamespace(
        d=config["hidden_size"], h=config["num_attention_heads"],
        kv=config["num_key_value_heads"], hd=config["head_dim"],
        ff=config["intermediate_size"], L=config["num_hidden_layers"],
        V=config["vocab_size"])


def param_layout(config: Dict) -> Dict:
    """{path: (shape, std or None for a leaf of ones, stacked?)} of the
    program's tree: the dense decoder's stacked leaves and the layer's
    four more (the head norms, the gate's projection and its bias of
    ones, which the program scales by ``gate_bias``)."""
    z = _sizes(config)
    out = {
        ("embed",): ((z.V, z.d), 0.02, False),
        ("ln_f",): ((z.d,), None, False),
        ("lm_head",): ((z.d, z.V), z.d ** -0.5, False),
    }
    for name, shape, std in (
        ("ln1", (z.d,), None), ("ln2", (z.d,), None),
        ("q_norm", (z.hd,), None), ("k_norm", (z.hd,), None),
        ("wq", (z.d, z.h * z.hd), z.d ** -0.5),
        ("wk", (z.d, z.kv * z.hd), z.d ** -0.5),
        ("wv", (z.d, z.kv * z.hd), z.d ** -0.5),
        ("wg", (z.d, z.kv), GATE_STD * z.d ** -0.5), ("bg", (z.kv,), None),
        ("wo", (z.h * z.hd, z.d), (z.h * z.hd) ** -0.5),
        ("w1", (z.d, z.ff), z.d ** -0.5), ("w3", (z.d, z.ff), z.d ** -0.5),
        ("w2", (z.ff, z.d), z.ff ** -0.5),
    ):
        out[("layers", name)] = ((z.L,) + shape, std, True)
    return out


def program_config(config: Dict, *, training: bool, control: bool = False):
    """The program's RetentionConfig for a published config (serving's
    control is :func:`control_params`)."""
    if training:
        raise NotImplementedError(
            "family retention is served, not trained: "
            "edl_tpu/models/retention.py has no loss")
    return retention.RetentionConfig.from_hf(
        config, gate_bias=config["gate_bias"], eps=config["retention_eps"],
        dtype=jnp.bfloat16, use_kernel=True)


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
    int8 projection, SwiGLU and head weights."""
    return jax.jit(retention.quantize_params_int8)(params)


# tokens [T] of one sequence -> the plain reference's logits [T, V]
reference_logits = reference.logits_row


# -- needed bytes and operations (the readers' numerators) ---------------------


def state_width(config: Dict) -> int:
    """Numbers of the symmetric second power of a key: d (d + 1) / 2."""
    hd = config["head_dim"]
    return hd * (hd + 1) // 2


def state_bytes_per_slot(config: Dict) -> int:
    """The state one sequence holds, all layers: ``S`` [KV, D, hd] in
    float32 (``z``, 1/128 of it, is not counted)."""
    z = _sizes(config)
    return z.L * z.kv * state_width(config) * z.hd * 4


def weight_bytes(config: Dict, bytes_per_param: int = 2) -> int:
    """Every matmul weight a decode step streams: the layers' (gate and
    norms among them) and the head. The embedding is a lookup."""
    z = _sizes(config)
    layer = (2 * z.d * z.h * z.hd + 2 * z.d * z.kv * z.hd + 3 * z.d * z.ff
             + z.d * z.kv + z.kv + 2 * z.d + 2 * z.hd)
    return (z.L * layer + z.d * z.V) * bytes_per_param


def decode_step_bytes(config: Dict, live_slots: float,
                      bytes_per_param: int = 2) -> float:
    """Bytes one decode step has to move: the weights once, and each
    live slot's state read once and written once."""
    return weight_bytes(config, bytes_per_param) \
        + 2 * live_slots * state_bytes_per_slot(config)


def retention_chunk_flops(config: Dict, tokens: float) -> float:
    """Operations the state's products take in a prefill of ``tokens``
    positions, all layers: each position's keys enter the state (``KV``
    heads) and each query head reads it (``H``), ``2 * D * hd`` each."""
    z = _sizes(config)
    return tokens * z.L * 2 * state_width(config) * z.hd * (z.kv + z.h)


needed = types.SimpleNamespace(
    state_bytes_per_slot=state_bytes_per_slot, weight_bytes=weight_bytes,
    decode_step_bytes=decode_step_bytes,
    retention_chunk_flops=retention_chunk_flops)
