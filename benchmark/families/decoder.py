"""Family ``decoder``: the dense pre-norm decoder (RMSNorm, rotary
MHA/GQA, SwiGLU, untied head) that Mistral-7B-v0.3 and DeepSeek-LLM-7B
publish, run by ``edl_tpu/models/llama.py``.

A configuration's file names its family (``"family": "decoder"``) and
``harness.Cell`` loads ``benchmark/families/<family>.py`` by that name.
What a family module gives is all the harness, the kinds and the
readers know of a model: its parameter tree, the program's config, the
engine and the trainer's hooks, the plain reference, the control, the
needed operations and bytes, and which keys of a published config are
widths and which may be cut. This is the only file of the benchmark
that names the program's model code, the decoder's reference or a dense
decoder's arithmetic.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from benchmark.reduce import needed  # noqa: F401  (the family's ``needed``)
from benchmark.reference import decoder as reference
from edl_tpu.models import llama
from edl_tpu.serving.engine import ContinuousBatchingEngine

# keys that must equal the published config's
widths = ("hidden_size", "intermediate_size", "num_attention_heads",
          "num_key_value_heads", "vocab_size", "rope_theta", "rms_norm_eps")
# keys that may stand in ``reduced``, each with the least it may be cut to
reducible = {"num_hidden_layers": 1}


def rehearsal_config() -> Dict:
    """Tiny widths for --rehearse (CPU tests): the same keys as a
    published config, so every code path reads them the same way."""
    return {
        "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 2, "vocab_size": 256, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-5,
    }


def param_layout(config: Dict) -> Dict:
    """{path: (shape, std or None for a norm weight, stacked?)} of the
    parameter tree, in the layout the program's model code takes (layer
    weights stacked on a leading axis)."""
    d, h, kv, hd, ff, L, V = reference.dims(config)
    out = {
        ("embed",): ((V, d), 0.02, False),
        ("ln_f",): ((d,), None, False),
        ("lm_head",): ((d, V), d ** -0.5, False),
    }
    for name, shape, std in (
        ("ln1", (d,), None), ("ln2", (d,), None),
        ("wq", (d, h * hd), d ** -0.5), ("wk", (d, kv * hd), d ** -0.5),
        ("wv", (d, kv * hd), d ** -0.5),
        ("wo", (h * hd, d), (h * hd) ** -0.5),
        ("w1", (d, ff), d ** -0.5), ("w3", (d, ff), d ** -0.5),
        ("w2", (ff, d), ff ** -0.5),
    ):
        out[("layers", name)] = ((L,) + shape, std, True)
    return out


def program_config(config: Dict, *, training: bool, control: bool = False):
    """The program's LlamaConfig for a published config. ``control``
    switches the training step's int8 path on (serving's control is
    :func:`control_params`)."""
    d, h, kv, _, ff, L, V = reference.dims(config)
    return llama.LlamaConfig(
        vocab=V, d_model=d, n_layers=L, n_heads=h, n_kv_heads=kv, d_ff=ff,
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]), dtype=jnp.bfloat16,
        use_flash=True, remat=training, int8_mxu=control and training,
    )


# -- serving (kinds/serve.py) -------------------------------------------------


def engine(params, program_cfg, spec: Dict, metrics):
    """The engine ``edl serve`` runs, sized by the cell's ``engine``."""
    return ContinuousBatchingEngine(
        params, program_cfg, max_slots=int(spec["max_slots"]),
        max_len=int(spec["max_len"]), metrics=metrics)


def control_params(params):
    """The served tree in the program's own precision below bfloat16:
    one program, so no float32 copy of a leaf is ever whole."""
    return jax.jit(llama.quantize_params_int8)(params)


# tokens [T] of one sequence -> the plain reference's logits [T, V]
reference_logits = reference.logits_row


# -- training (kinds/train.py, kinds/elastic.py) ------------------------------


# (program_cfg, plan) -> the tree of PartitionSpecs on that mesh plan
param_pspecs = llama.param_pspecs
# (program_cfg, plan, mesh) -> loss(params, batch), as ElasticTrainer takes it
make_loss = llama.make_loss_fn
# (params, batches, config, learning_rate, shardings, spread) -> (losses,
# the first gradient's sum of squares per leaf, the final parameters)
reference_train_steps = reference.train_steps
