"""``ops/ssm.py`` (the selective state-space recurrence of Mamba-2: a
decode step over the stacked per-slot state, the chunked prefill scan,
the causal convolution with its carried tail) against the recurrence
written position by position, at tiny sizes with seeded inputs. float32
against float32 differs by the order of summation alone (a chunk sums in
one product what the recurrence sums a position at a time): 1e-5 of the
largest entry holds every path (``err`` is relative to it: outputs and
states run to order 10 here)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.ops import ssm as ops

TOL = 1e-5
B, T, H, P, N, C, K = 2, 37, 4, 8, 16, 24, 4


def err(a, b):
    """The widest gap, as a share of the largest entry (of 1 at least)."""
    return float(jnp.max(jnp.abs(a - b))
                 / jnp.maximum(1.0, jnp.max(jnp.abs(b))))


@pytest.fixture(scope="module")
def inputs():
    k = iter(jax.random.split(jax.random.PRNGKey(5), 8))
    draw = lambda *shape: jax.random.normal(next(k), shape, jnp.float32)
    # steps from 0.01 to 1 and decays from 1 to 8: horizons of under a
    # position to a hundred, so the carried state matters at T = 37
    dt = jnp.exp(jax.random.uniform(next(k), (B, T, H), jnp.float32,
                                    np.log(0.01), np.log(1.0)))
    a = -jnp.linspace(1.0, 8.0, H)
    return dict(x=draw(B, T, H, P), bm=draw(B, T, N), cm=draw(B, T, N),
                dt=dt, a=a, d=draw(H))


def recurrence(x, bm, cm, dt, a, d, valid=None):
    """S_t = exp(dt A) S_{t-1} + (dt x_t) B_t^T; y_t = S_t C_t + D x_t,
    a position at a time. Returns (y [B, T, H, P], the state after each
    position [T, B, H, P, N])."""
    if valid is None:
        valid = jnp.ones(x.shape[:2], bool)

    def step(s, at):
        x_t, b_t, c_t, dt_t, ok = at
        new = jnp.exp(dt_t * a)[..., None, None] * s \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        s = jnp.where(ok[:, None, None, None], new, s)
        y = jnp.sum(s * c_t[:, None, None, :], -1) + d[None, :, None] * x_t
        return s, (y, s)

    s0 = jnp.zeros((B, H, P, N), jnp.float32)
    lead = lambda v: jnp.moveaxis(v, 1, 0)
    _, (ys, states) = jax.lax.scan(
        step, s0, (lead(x), lead(bm), lead(cm), lead(dt), lead(valid)))
    return jnp.moveaxis(ys, 0, 1), states


# chunk lengths that divide T = 37 (1, 37), that do not (4, 8, 16), and
# one longer than the sequence
@pytest.mark.parametrize("chunk", [1, 4, 8, 16, 37, 64])
def test_the_chunked_scan_is_the_recurrence(inputs, chunk):
    want, states = recurrence(**inputs)
    y, end = ops.ssd_chunked(**inputs, chunk=chunk, dtype=jnp.float32)
    assert err(y, want) < TOL
    assert err(end, states[-1]) < TOL


@pytest.mark.parametrize("chunk", [4, 16])
def test_a_position_that_does_not_exist_neither_decays_nor_enters(
        inputs, chunk):
    """Rows past each sequence's ``last`` (a padded bucket's tail) leave
    the state of ``last``, whatever they hold."""
    last = jnp.array([10, 29])
    valid = jnp.arange(T)[None, :] <= last[:, None]
    _, states = recurrence(**inputs)
    y, end = ops.ssd_chunked(**inputs, valid=valid, chunk=chunk,
                             dtype=jnp.float32)
    want, _ = recurrence(**inputs)
    for row in range(B):
        upto = int(last[row]) + 1
        assert err(end[row], states[int(last[row]), row]) < TOL
        assert err(y[row, :upto], want[row, :upto]) < TOL
    # and the fault the chip's comparison is given: the tail let in
    _, wrong = ops.ssd_chunked(**inputs, chunk=chunk, dtype=jnp.float32)
    assert err(wrong[0], states[10, 0]) > 100 * TOL


def test_a_scan_resumes_from_a_carried_state(inputs):
    """Two halves, the second from the first's state, are the whole."""
    want, end = ops.ssd_chunked(**inputs, chunk=8, dtype=jnp.float32)
    cut = 19
    part = lambda lo, hi: {
        k: (v[:, lo:hi] if k in ("x", "bm", "cm", "dt") else v)
        for k, v in inputs.items()}
    y1, s1 = ops.ssd_chunked(**part(0, cut), chunk=8, dtype=jnp.float32)
    y2, s2 = ops.ssd_chunked(**part(cut, T), start=s1, chunk=8,
                             dtype=jnp.float32)
    assert err(jnp.concatenate([y1, y2], 1), want) < TOL
    assert err(s2, end) < TOL


def stacked(layers, state):
    """[L, B, H, P, N] with ``state`` at layer 1 and noise elsewhere."""
    noise = jax.random.normal(jax.random.PRNGKey(9), (layers,) + state.shape)
    return noise.at[1].set(state)


@pytest.mark.parametrize("kernel", [False, True])
def test_decode_steps_are_the_recurrence(inputs, kernel):
    """A position at a time through the stacked cache, every slot live:
    the recurrence, and the other layers' states untouched."""
    want, states = recurrence(**inputs)
    cache = stacked(3, jnp.zeros((B, H, P, N), jnp.float32))
    before = cache
    live = jnp.ones((B,), bool)
    for t in range(12):
        y, cache = ops.ssm_step(
            inputs["x"][:, t], inputs["bm"][:, t], inputs["cm"][:, t],
            inputs["dt"][:, t], inputs["a"], inputs["d"], cache, 1, live,
            dtype=jnp.float32, use_kernel=kernel, interpret=True)
        assert err(y, want[:, t]) < TOL, t
        assert err(cache[1], states[t]) < TOL, t
    assert err(cache[0], before[0]) == 0.0 and err(cache[2], before[2]) == 0.0


def test_the_step_kernel_is_the_plain_lines_and_skips_an_idle_slot(inputs):
    """``edl_ssm_step`` in the Pallas interpreter against the plain
    lines, at a traced layer index, with one slot idle: the idle slot's
    state is not touched and its output reads zero."""
    t = 5
    state = jax.random.normal(jax.random.PRNGKey(3), (3, B, H, P, N))
    live = jnp.array([True, False])
    args = (inputs["x"][:, t], inputs["bm"][:, t], inputs["cm"][:, t],
            inputs["dt"][:, t], inputs["a"], inputs["d"], state)
    want = ops.ssm_step(*args, 2, live, dtype=jnp.float32, use_kernel=False)
    got = jax.jit(lambda layer: ops.ssm_step(
        *args, layer, live, dtype=jnp.float32, use_kernel=True,
        interpret=True))(jnp.int32(2))
    for a, b in zip(got, want):
        assert err(a, b) < TOL
    assert err(got[1][:2], state[:2]) == 0.0  # the other layers
    assert err(got[1][2, 1], state[2, 1]) == 0.0  # the idle slot
    assert err(got[1][2, 0], state[2, 0]) > 0.01
    assert float(jnp.max(jnp.abs(got[0][1]))) == 0.0


def test_the_skip_connection_is_in_the_step(inputs):
    """``D x`` left out (a planted fault of the chip's comparison) moves
    every output by ``D x``."""
    t = 0
    cache = jnp.zeros((1, B, H, P, N), jnp.float32)
    live = jnp.ones((B,), bool)
    args = (inputs["x"][:, t], inputs["bm"][:, t], inputs["cm"][:, t],
            inputs["dt"][:, t], inputs["a"])
    with_d, _ = ops.ssm_step(*args, inputs["d"], cache, 0, live,
                             dtype=jnp.float32, use_kernel=False)
    without, _ = ops.ssm_step(*args, jnp.zeros((H,)), cache, 0, live,
                              dtype=jnp.float32, use_kernel=False)
    assert err(with_d - without,
               inputs["d"][None, :, None] * inputs["x"][:, t]) < TOL


# -- the causal convolution ------------------------------------------------------


@pytest.fixture(scope="module")
def conv():
    k = iter(jax.random.split(jax.random.PRNGKey(6), 3))
    return dict(xbc=jax.random.normal(next(k), (B, T, C)),
                w=jax.random.normal(next(k), (K, C)) * 0.5,
                b=jax.random.normal(next(k), (C,)) * 0.5)


def conv_by_hand(xbc, w, b):
    """out_t = silu(sum_j w[j] * x_{t - K + 1 + j} + b), zeros before
    position 0, one position at a time."""
    out = np.zeros(xbc.shape, np.float32)
    x, w = np.asarray(xbc), np.asarray(w)
    for t in range(xbc.shape[1]):
        acc = np.asarray(b).copy()[None].repeat(xbc.shape[0], 0)
        for j in range(K):
            at = t - K + 1 + j
            if at >= 0:
                acc = acc + w[j] * x[:, at]
        out[:, t] = acc
    return jax.nn.silu(jnp.asarray(out))


def test_the_convolution_is_four_shifted_products(conv):
    got, _ = ops.conv_prefill(**conv, last=jnp.array([T - 1, T - 1]))
    assert err(got, conv_by_hand(**conv)) < TOL


# the tail's edge cases: fewer than three inputs exist before ``last``
@pytest.mark.parametrize("last", [0, 1, 2, 3, 17, T - 1])
def test_the_tail_is_the_three_inputs_at_last_and_before(conv, last):
    _, tail = ops.conv_prefill(**conv, last=jnp.array([last, last]))
    tail = tail.reshape(B, K - 1, C)
    for j in range(K - 1):
        at = last - (K - 2) + j
        want = conv["xbc"][:, at] if at >= 0 else jnp.zeros((B, C))
        assert err(tail[:, j], want) == 0.0, (last, j)


@pytest.mark.parametrize("last", [0, 1, 2, 9])
def test_steps_from_a_prefilled_tail_are_the_convolution(conv, last):
    """Prefill to ``last``, then a position at a time through the
    stacked tail: the whole convolution; an idle slot keeps its tail."""
    want = conv_by_hand(**conv)
    _, tail = ops.conv_prefill(**conv, last=jnp.array([last, last]))
    cache = jnp.zeros((2, B, (K - 1) * C)).at[1].set(tail)
    live = jnp.array([True, False])
    for t in range(last + 1, last + 6):
        held = cache[1, 1]
        out, cache = ops.conv_step(conv["xbc"][:, t], cache, 1, conv["w"],
                                   conv["b"], live)
        assert err(out[0], want[0, t]) < TOL, t
        assert err(cache[1, 1], held) == 0.0  # the idle slot
        assert err(cache[0], jnp.zeros_like(cache[0])) == 0.0
