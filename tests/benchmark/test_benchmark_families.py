"""The seam between the harness and a model family: the seed's weights
and the program's config are what they were before the decoder's shape
moved to ``benchmark/families/decoder.py`` (constants below were read on
the parent, commit cfb656e, from ``harness.make_params`` and
``harness.model_config``), and no file outside ``families/`` names a
model."""

import glob
import hashlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.families import decoder as family

BENCH = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))

# sha256 over every leaf's path, dtype, shape and bytes, in tree order,
# of the parent's make_params(seed, REHEARSAL_CONFIG, dtype) on the CPU
PARENT_WEIGHTS = {
    (11, "float32"):
        "663f7994bafee90132fd5917fdea6a14c9e9a538a6600ec0a59f809fd1907756",
    (11, "bfloat16"):
        "5537ad0dc6cd5712bcd88fc725c9190be578048232496f6819c8dcc7c8508d6b",
    (3000000019, "float32"):
        "dff77996a0b680fe6cf7d107a2546b8b10b43aae23fe8b8983c4cb6ff59e0fe5",
    (3000000019, "bfloat16"):
        "68a8deafee5624785ce779bf3843ee998b139df72ddef5f169c3f32627a7d935",
}
# the parent's REHEARSAL_CONFIG
PARENT_REHEARSAL = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 256,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
}
# the parent's model_config(...) of each configuration, field by field:
# (vocab, d_model, n_layers, n_heads, n_kv_heads, d_ff, rope_theta, norm_eps)
PARENT_PROGRAMS = {
    "mistral7b-L4": (32768, 4096, 4, 32, 8, 14336, 1e6, 1e-5),
    "deepseek7b-L12": (102400, 4096, 12, 32, 32, 11008, 1e4, 1e-6),
    "mistral7b-L16": (32768, 4096, 16, 32, 8, 14336, 1e6, 1e-5),
    "mistral7b-L9": (32768, 4096, 9, 32, 8, 14336, 1e6, 1e-5),
}


def digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed, dtype", sorted(PARENT_WEIGHTS))
def test_the_seeds_weights_are_the_parents_bit_for_bit(seed, dtype):
    assert family.rehearsal_config() == PARENT_REHEARSAL
    params = harness.make_params(
        seed, family.param_layout(PARENT_REHEARSAL), jnp.dtype(dtype))
    assert digest(params) == PARENT_WEIGHTS[seed, dtype]


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
@pytest.mark.parametrize("training", [True, False])
def test_program_config_is_the_parents(entry, training):
    """An equal config object is an equal compile-cache key: no program
    of a cell compiles anew for the move."""
    from edl_tpu.models import llama

    config = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
    v, d, n, h, kv, ff, theta, eps = PARENT_PROGRAMS[entry["name"]]
    for control in (False, True):
        parent = llama.LlamaConfig(
            vocab=v, d_model=d, n_layers=n, n_heads=h, n_kv_heads=kv,
            d_ff=ff, rope_theta=theta, norm_eps=eps, dtype=jnp.bfloat16,
            use_flash=True, remat=training, int8_mxu=control and training)
        ours = family.program_config(
            config, training=training, control=control)
        assert ours == parent and hash(ours) == hash(parent)


MODEL_NAMES = re.compile(
    r"edl_tpu\.models|edl_tpu import models|benchmark\.reference\.decoder|"
    r"benchmark\.reference import decoder|benchmark\.reduce\.needed|"
    r"benchmark\.reduce import [^\n]*\bneeded\b|LlamaConfig")
GENERIC = ["harness.py", "run.py", "readings.py", "kinds/*.py", "metrics/*.py",
           "reduce/trace.py", "reduce/program.py", "reduce/serving.py",
           "traffic/*.py"]


def generic_files():
    here = os.path.join(harness.ROOT, "benchmark")
    return sorted(p for pattern in GENERIC
                  for p in glob.glob(os.path.join(here, pattern)))


@pytest.mark.parametrize(
    "path", generic_files(),
    ids=lambda p: os.path.relpath(p, os.path.join(harness.ROOT, "benchmark")))
def test_nothing_outside_families_names_a_model(path):
    found = MODEL_NAMES.findall(open(path).read())
    assert not found, (
        f"{path} names {found}: what the harness knows of a model's shape "
        f"it asks of cell.family (benchmark/families/)")


def test_only_the_decoder_family_imports_the_decoders_files():
    here = os.path.join(harness.ROOT, "benchmark")
    naming = {os.path.relpath(p, here) for p in glob.glob(
        os.path.join(here, "**", "*.py"), recursive=True)
        if re.search(r"^(from|import) .*(edl_tpu\.models|reference\.decoder|"
                     r"reference import decoder|reduce\.needed|"
                     r"reduce import needed)", open(p).read(), re.M)}
    assert naming == {os.path.join("families", "decoder.py")}
    text = open(os.path.join(here, "harness.py")).read()
    for word in ("LlamaConfig", "hidden_size", "vocab_size", "lm_head",
                 '"layers"', "num_hidden_layers"):
        assert word not in text, f"harness.py holds {word}"
