"""The plain reference against the program's model code at tiny widths,
and the reference's layer-at-a-time gradient against ``jax.grad``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.families import decoder as family
from benchmark.reference import decoder

CONFIG = family.rehearsal_config()
LAYOUT = family.param_layout(CONFIG)
# float32 against float32 at highest precision: what separates them is
# the order of summation. bfloat16 activations miss this by two orders.
LOGIT_TOLERANCE = 2e-4


@pytest.fixture(scope="module")
def params():
    return harness.make_params(11, LAYOUT, jnp.float32)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, 256, (2, 2, 41), dtype=np.int32)


def program_logits(params, row, dtype):
    from edl_tpu.models import llama

    cfg = dataclasses.replace(
        family.program_config(CONFIG, training=False), dtype=dtype,
        use_flash=False)
    with jax.default_matmul_precision("highest"):
        return llama.forward(params, row[None], cfg)[0]


def test_reference_agrees_with_the_program_in_float32(params, tokens):
    row = tokens[0, 0, :40]
    ref = decoder.logits_row(params, jnp.asarray(row), CONFIG)
    err = float(jnp.max(jnp.abs(ref - program_logits(params, row, jnp.float32))))
    assert err < LOGIT_TOLERANCE


def test_a_bfloat16_run_fails_that_tolerance(params, tokens):
    row = tokens[0, 0, :40]
    ref = decoder.logits_row(params, jnp.asarray(row), CONFIG)
    err = float(jnp.max(jnp.abs(ref - program_logits(params, row, jnp.bfloat16))))
    assert err > 10 * LOGIT_TOLERANCE


def test_reference_loss_is_the_programs_loss_in_float32(params, tokens):
    from edl_tpu.models import llama

    cfg = dataclasses.replace(
        family.program_config(CONFIG, training=False), dtype=jnp.float32,
        use_flash=False)
    rows = tokens.reshape(4, 41)
    with jax.default_matmul_precision("highest"):
        prog = float(llama.make_loss_fn(cfg)(params, {"tokens": rows}))
    assert float(decoder.loss(params, jnp.asarray(tokens), CONFIG)) == \
        pytest.approx(prog, rel=1e-5)


def test_layer_at_a_time_gradient_is_the_gradient(params, tokens):
    tok = jnp.asarray(tokens)
    v1, g1 = jax.value_and_grad(lambda p: decoder.loss(p, tok, CONFIG))(params)
    v2, g2 = decoder.loss_and_grads(params, tok, CONFIG)
    assert float(v1) == pytest.approx(float(v2), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)):
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-5 * float(jnp.max(jnp.abs(a)))


def test_weights_are_the_seeds(params):
    again = harness.make_params(11, LAYOUT, jnp.float32)
    other = harness.make_params(2 ** 31 + 11, LAYOUT, jnp.float32)
    assert bool(jnp.all(again["layers"]["w1"] == params["layers"]["w1"]))
    assert not bool(jnp.all(other["embed"] == params["embed"]))


def test_worst_leaf_gap_is_measured_against_the_median_leaf():
    ref = {"a": 1.0, "b": 4.0, "c": 1e-12}
    gap, leaf = harness.worst_leaf_gap({"a": 1.0, "b": 4.0, "c": 4e-12}, ref)
    # c's norm doubled, but against the median leaf's norm that is nothing
    assert gap < 1e-5
    gap, leaf = harness.worst_leaf_gap({"a": 1.21, "b": 4.0, "c": 1e-12}, ref)
    assert leaf == "a" and gap == pytest.approx(0.1)
    gap, leaf = harness.worst_leaf_gap({"a": float("nan"), "b": 4.0, "c": 0.0}, ref)
    assert leaf == "a" and gap != gap
