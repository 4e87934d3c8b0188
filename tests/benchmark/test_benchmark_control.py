"""The control of the comparison that decides ``correct``, at a size a
test run can hold: the reference put in the program's place and computed
in a precision below the configuration's (operands rounded to float8,
one step under the configuration's bfloat16) fails the limits that the
rehearsal's cells carry, and the same in bfloat16 passes them. On the
chip, at the cells' own size, the control is the program's own int8
path (``--control``); PERF.md gives those readings."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.families import decoder as family
from benchmark.reference import decoder

CONFIG = family.rehearsal_config()
LAYOUT = family.param_layout(CONFIG)


def limits(cell):
    c = harness.Cell(cell)
    c.for_rehearsal()
    return c.limits


def training_readings(seed, dtype):
    """(worst loss gap, gradient norm gap, change norm gap) of the
    reference run with operands rounded to ``dtype`` against itself."""
    def follow(rounded):
        params = harness.make_params(seed, LAYOUT, jnp.float32)
        start = jax.tree_util.tree_map(lambda a: a + 0, params)
        rng = np.random.default_rng(seed)
        batches = [jnp.asarray(rng.integers(0, 256, (1, 2, 129), dtype=np.int32))
                   for _ in range(3)]
        if rounded is None:
            losses, grads, end = decoder.train_steps(params, batches, CONFIG, 1e-3)
        else:
            with decoder.operands_rounded_to(rounded):
                losses, grads, end = decoder.train_steps(
                    params, batches, CONFIG, 1e-3)
        change = decoder.leaf_sumsq(
            jax.tree_util.tree_map(lambda a, b: a - b, end, start))
        return losses, grads, change

    ref, low = follow(None), follow(dtype)
    return (max(abs(a - b) / abs(b) for a, b in zip(low[0], ref[0])),
            harness.worst_leaf_gap(low[1], ref[1])[0],
            harness.worst_leaf_gap(low[2], ref[2])[0])


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_training_control_fails_and_the_stated_precision_passes(seed):
    lim = limits("mistral7b.train-steady")
    names = ("loss_gap", "first_gradient_norm_gap", "parameter_change_norm_gap")
    sound = training_readings(seed, jnp.bfloat16)
    assert all(v <= lim[n] for n, v in zip(names, sound)), sound
    control = training_readings(seed, jnp.float8_e4m3fn)
    assert any(v > lim[n] for n, v in zip(names, control)), control
    # and by a margin: three times the limit, on the number meant to catch it
    assert control[1] > 3 * lim["first_gradient_norm_gap"]


def served_gaps(seed, dtype):
    """At each position of one sequence, how far the token that the
    lower precision puts first lies under the reference's best."""
    params = harness.make_params(seed, LAYOUT, jnp.float32)
    tokens = jnp.asarray(
        np.random.default_rng(seed).integers(0, 256, (64,), dtype=np.int32))
    ref = decoder.logits_row(params, tokens, CONFIG)
    with decoder.operands_rounded_to(dtype):
        low = jax.jit(lambda p, t: decoder.logits_row(p, t, CONFIG))(
            params, tokens)
    first = jnp.argmax(low, axis=-1)
    gap = jnp.max(ref, -1) - jnp.take_along_axis(ref, first[:, None], 1)[:, 0]
    return float(gap.max()), float(gap.mean())


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serving_control_fails_and_the_stated_precision_passes(seed):
    lim = limits("deepseek7b.decode-closed")
    worst, mean = served_gaps(seed, jnp.bfloat16)
    assert worst <= lim["served_token_gap_max"]
    assert mean <= lim["served_token_gap_mean"]
    worst, mean = served_gaps(seed, jnp.float8_e4m3fn)
    assert (worst > lim["served_token_gap_max"]
            or mean > lim["served_token_gap_mean"])
