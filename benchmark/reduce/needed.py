"""Operations and bytes a dense decoder needs, from shapes alone: the
``needed`` of the family ``decoder`` (benchmark/families/decoder.py), and
reached by the readers only through ``run["cell"].family.needed``, so
that a family whose step reads the top-k of its experts, or whose cache
is a window in some layers, prices its own step.

These are the numerators of every utilization the benchmark reports.
They count what the mathematics requires, not what a program happens to
execute: no recomputation, no padding, no masked-out half of a causal
attention. Copied in spirit from ``edl_tpu/obs/costmodel.py``
(``train_flops_per_token``) so that a later edit there cannot move the
yardstick; ``decode_step_bytes`` there prices the padded program and is
not copied.
"""

from typing import Dict


def dims(config: Dict):
    """(d, heads, kv heads, head dim, ff, layers, vocab) of a published
    ``config.json`` (the reference reads the same keys by itself)."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    return (d, h, config["num_key_value_heads"], d // h,
            config["intermediate_size"], config["num_hidden_layers"],
            config["vocab_size"])


def matmul_params(config: Dict) -> int:
    """Parameters that take part in a matrix product per token: every
    layer projection and the output head (the embedding is a lookup)."""
    d, h, kv, hd, ff, L, V = dims(config)
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff
    return L * per_layer + d * V


def total_params(config: Dict) -> int:
    d, _, _, _, _, L, V = dims(config)
    return matmul_params(config) + V * d + (2 * L + 1) * d


def attention_train_flops_per_token(config: Dict, seq: int) -> float:
    """Causal attention, forward and backward, per token: QK^T and PV
    are 2 * 2 * (seq / 2) * h * hd forward (a token attends to seq/2
    others on average) and twice that backward."""
    _, h, _, hd, _, L, _ = dims(config)
    return 12.0 * L * (seq / 2.0) * h * hd


def train_flops_per_token(config: Dict, seq: int) -> float:
    """Forward and backward per trained token: 6 per matmul parameter
    plus causal attention. Rematerialised work is not counted."""
    return (6.0 * matmul_params(config)
            + attention_train_flops_per_token(config, seq))


def kv_bytes_per_token(config: Dict, bytes_per_el: int = 2) -> int:
    """Keys and values one position holds across all layers."""
    _, _, kv, hd, _, L, _ = dims(config)
    return 2 * L * kv * hd * bytes_per_el


def decode_step_bytes(config: Dict, resident_tokens: float,
                      bytes_per_param: int = 2) -> float:
    """Bytes one decode step has to read: every matmul weight once, the
    embedding rows aside, plus the keys and values of the tokens that
    are resident (not of the padded cache)."""
    return (matmul_params(config) * bytes_per_param
            + resident_tokens * kv_bytes_per_token(config))
