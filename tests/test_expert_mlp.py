"""``ops/expert_mlp.py`` (``edl_expert_mlp``: the routed experts of a
decode step's few rows, no sort; ``edl_grouped_expert_mlp``: those of a
prefill's many, each expert its run of the sorted rows) under the
Pallas interpreter, against the grouped form they stand in for
(``parallel.moe.moe_dropless`` without ``kernel``:
``jax.lax.ragged_dot``) and the benchmark's float32 table. float32 at
``highest`` on both sides differs by the order of summation alone, so
1e-5 holds; bfloat16 rows and weights are held to bfloat16's step."""

import re

import jax
import jax.numpy as jnp
import pytest

from benchmark.reference import mla_moe as reference
from edl_tpu.ops import expert_mlp as em
from edl_tpu.ops.flash_attention import interpret_kernels
from edl_tpu.parallel import moe

D, F, E, K = 32, 24, 16, 3
CONFIG = {"num_experts_per_tok": K, "norm_topk_prob": True,
          "routed_scaling_factor": 2.448}


def err(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def layer(seed, e=E, d=D, f=F, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    tree = {"router": jax.random.normal(k[0], (d, e)) * d ** -0.5,
            "router_bias": jax.random.normal(k[1], (e,)) * 0.02,
            "we1": jax.random.normal(k[2], (e, d, f)) * d ** -0.5,
            "we3": jax.random.normal(k[3], (e, d, f)) * d ** -0.5,
            "we2": jax.random.normal(k[4], (e, f, d)) * f ** -0.5}
    return {name: leaf.astype(dtype) if name.startswith("we") else leaf
            for name, leaf in tree.items()}


def routed(lp, x, k=K):
    with jax.default_matmul_precision("highest"):
        return moe.route_sigmoid_topk(
            x, lp["router"], lp["router_bias"], k, 2.448)


def both(x, idx, w, lp, **kw):
    """(the kernel's layer, the grouped form's) on the same routing."""
    experts = (lp["we1"], lp["we3"], lp["we2"])
    with interpret_kernels(), jax.default_matmul_precision("highest"):
        got = moe.moe_dropless(x, idx, w, *experts, kernel=True, **kw)
        want = moe.moe_dropless(x, idx, w, *experts, **kw)
    return got, want


def jaxpr_text(fn, *args) -> str:
    return str(jax.make_jaxpr(fn)(*args))


@pytest.mark.parametrize("n", [1, 8, 96, 128])
def test_kernel_layer_is_the_grouped_form_and_the_references_table(n):
    lp = layer(n)
    x = jax.random.normal(jax.random.PRNGKey(100 + n), (n, D))
    idx, w = routed(lp, x)
    got, grouped = both(x, idx, w, lp)
    with jax.default_matmul_precision("highest"):
        table = reference.route(x, lp["router"], lp["router_bias"], CONFIG)
        want = reference.routed(x, table, lp["we1"], lp["we3"], lp["we2"])
    assert got.shape == (n, D) and got.dtype == x.dtype
    assert err(got, grouped) < 1e-5
    assert err(got, want) < 1e-5
    assert float(jnp.max(jnp.abs(want))) > 0.1


@pytest.mark.parametrize("n", [1, 8, 96, 128])
def test_bfloat16_rows_stay_within_bfloat16_of_the_float32_table(n):
    """bf16 rows and weights, float32 accumulation: no further from the
    float32 table than the grouped form, which rounds more often."""
    lp = layer(n, dtype=jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(200 + n), (n, D))
    idx, w = routed(lp, x)
    got, grouped = both(x.astype(jnp.bfloat16), idx, w, lp)
    with jax.default_matmul_precision("highest"):
        table = jnp.zeros((n, E)).at[jnp.arange(n)[:, None], idx].add(w)
        want = reference.routed(
            x.astype(jnp.bfloat16).astype(jnp.float32), table,
            *(lp[name].astype(jnp.float32) for name in ("we1", "we3", "we2")))
    assert got.dtype == jnp.bfloat16
    top = float(jnp.max(jnp.abs(want)))
    assert err(got, want) < 2.0 ** -6 * top
    assert err(got, want) <= err(grouped, want) + 2.0 ** -8 * top


@pytest.mark.parametrize("n", [1, 48, 128])
def test_every_token_sent_to_one_expert_still_gets_its_result(n):
    lp = layer(3)
    x = jax.random.normal(jax.random.PRNGKey(300 + n), (n, D))
    idx = jnp.tile(jnp.array([[2, 5]]), (n, 1))
    w = jnp.tile(jnp.array([[0.7, 0.3]]), (n, 1))
    got, _ = both(x, idx, w, lp)
    with jax.default_matmul_precision("highest"):
        one = lambda e: (jax.nn.silu(x @ lp["we1"][e]) * (x @ lp["we3"][e])
                         ) @ lp["we2"][e]
        want = 0.7 * one(2) + 0.3 * one(5)
    assert err(got, want) < 1e-5
    assert float(jnp.min(jnp.linalg.norm(got, axis=-1))) > 0


@pytest.mark.parametrize("n", [1, 8, 96])
def test_an_expert_with_no_row_is_skipped_not_multiplied_by_zero(n):
    """The weights of every expert nobody chose are NaN: one of them
    read into the sum, at whatever weight, would show."""
    lp = layer(4)
    x = jax.random.normal(jax.random.PRNGKey(400 + n), (n, D))
    idx, w = routed(lp, x)
    want, _ = both(x, idx, w, lp)
    unhit = ~jnp.any(idx[..., None] == jnp.arange(E), axis=(0, 1))
    assert n > 8 or int(jnp.sum(unhit)) > 0
    poisoned = {name: jnp.where(unhit[:, None, None], jnp.nan, lp[name])
                for name in ("we1", "we3", "we2")}
    got, _ = both(x, idx, w, poisoned)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert err(got, want) == 0.0


def test_a_row_that_chose_another_expert_is_masked_not_multiplied():
    """Expert 0 overflows on every row; row 1 did not choose it: ``0 *
    inf`` would be NaN there, ``where`` leaves it expert 1's term."""
    lp = layer(5)
    lp["we1"] = lp["we1"].at[0].mul(1e20)
    lp["we3"] = lp["we3"].at[0].mul(1e20)
    x = jax.random.normal(jax.random.PRNGKey(500), (2, D))
    idx = jnp.array([[0], [1]])
    got, want = both(x, idx, jnp.ones((2, 1)), lp)
    assert not bool(jnp.all(jnp.isfinite(got[0])))
    assert bool(jnp.all(jnp.isfinite(got[1])))
    assert err(got[1], want[1]) < 1e-5 and float(jnp.max(jnp.abs(got[1]))) > 0


@pytest.mark.parametrize("n", [8, 40])
def test_eight_shares_of_the_experts_add_up_to_the_uncut_layer(n):
    e, k = 128, 6
    lp = layer(6, e=e)
    x = jax.random.normal(jax.random.PRNGKey(600 + n), (n, D))
    idx, w = routed(lp, x, k)
    whole, grouped = both(x, idx, w, lp)
    total = jnp.zeros_like(x)
    for first in range(0, e, 16):
        share = {name: lp[name][first:first + 16]
                 for name in ("we1", "we3", "we2")}
        got, want = both(x, idx, w, share, first=first)
        assert err(got, want) < 1e-5
        total += got
    assert err(total, whole) < 1e-5 and err(total, grouped) < 1e-5
    # and one share alone is not the layer
    assert err(got, whole) > 0.1


def test_a_share_nobody_chose_is_zeros():
    lp = layer(7)
    x = jax.random.normal(jax.random.PRNGKey(700), (8, D))
    idx = jnp.full((8, 2), 3) + jnp.arange(2)
    got, want = both(x, idx, jnp.ones((8, 2)), lp, first=E)
    assert bool(jnp.all(got == 0.0)) and bool(jnp.all(want == 0.0))


@pytest.mark.parametrize("n", [20, 129])
@pytest.mark.parametrize("case", ["int8 record", "no kernels"])
def test_what_the_kernels_do_not_take_goes_through_the_grouped_matmul(case, n):
    """The control's int8 record and ``use_flash=False``, at a decode
    step's rows and at a prefill's."""
    from edl_tpu.models import deepseek_v3 as ds

    lp = layer(8)
    x = jax.random.normal(jax.random.PRNGKey(800), (n, D))
    idx, w = routed(lp, x)
    experts = {name: lp[name] for name in ("we1", "we3", "we2")}
    if case == "int8 record":
        experts = ds.quantize_params_int8(
            {"layers": {"00": experts}, "lm_head": jnp.ones((4, 4))}
        )["layers"]["00"]
        assert experts["we1"]["q8"].dtype == jnp.int8
    run = lambda x: moe.moe_dropless(
        x, idx, w, experts["we1"], experts["we3"], experts["we2"],
        kernel=case != "no kernels")
    text = jaxpr_text(run, x)
    assert text.count(" = ragged_dot") == 3
    assert "pallas_call" not in text


@pytest.mark.parametrize("n", [1, 128, 129, 1024])
def test_a_step_of_any_size_is_one_kernel_and_no_grouped_matmul(n):
    """Up to ``MAX_ROWS`` rows no sort either; past them the rows are
    sorted and the one kernel is the grouped one."""
    lp = layer(9)
    x = jax.random.normal(jax.random.PRNGKey(900), (n, D))
    idx, w = routed(lp, x)
    with interpret_kernels():
        text = jaxpr_text(lambda x: moe.moe_dropless(
            x, idx, w, lp["we1"], lp["we3"], lp["we2"], kernel=True), x)
    assert text.count(" = pallas_call[") == 1
    assert "ragged_dot" not in text
    assert (" sort[" in text) == (n > em.MAX_ROWS)
    assert ("edl_grouped_expert_mlp" in text) == (n > em.MAX_ROWS)


def test_more_rows_than_the_kernel_takes_are_refused_by_the_kernel():
    lp = layer(10)
    x = jnp.zeros((em.MAX_ROWS + 1, D))
    idx = jnp.zeros((em.MAX_ROWS + 1, K), jnp.int32)
    with pytest.raises(ValueError, match="at most"):
        em.expert_mlp(x, idx, idx.astype(jnp.float32), lp["we1"], lp["we3"],
                      lp["we2"], interpret=True)


def wide_shapes(d, f, rows=96):
    sds = jax.ShapeDtypeStruct
    return (sds((rows, d), jnp.bfloat16), sds((rows, 6), jnp.int32),
            sds((rows, 6), jnp.float32), sds((E, d, f), jnp.bfloat16),
            sds((E, d, f), jnp.bfloat16), sds((E, f, d), jnp.bfloat16))


def grouped_of_a_step(x, idx, w, w1, w3, w2):  # rows x 6 sorted rows
    rows = jnp.repeat(x, 6, axis=0)
    return em.grouped_expert_mlp(
        rows, jnp.zeros((E,), jnp.int32), w1, w3, w2, interpret=True)


@pytest.mark.parametrize("d, f, tiled", [
    (2048, 768, False),  # the other expert model's cell: whole, as it was
    (6144, 2048, True),  # 75 MB an expert: in tiles of f
    (7168, 2048, True),
])
def test_an_expert_that_does_not_fit_vmem_whole_passes_in_tiles_of_f(
        d, f, tiled):
    """The shapes that fit take the whole-expert kernels they took (one
    grid axis); a wider expert runs over a second axis of ``f`` tiles
    (traced, not run)."""
    run = lambda *a: em.expert_mlp(*a, interpret=True)
    for fn, rows in ((run, 96), (grouped_of_a_step, 576)):
        text = jaxpr_text(fn, *wide_shapes(d, f))
        assert jax.eval_shape(fn, *wide_shapes(d, f)).shape == (rows, d)
        two_axes = re.search(r"grid=\(\d+, (\d+)\)", text)
        assert bool(two_axes) == tiled
        assert not tiled or int(two_axes.group(1)) > 1


def test_a_width_no_tile_divides_is_refused():
    with pytest.raises(ValueError, match="no tile of f"):
        jax.eval_shape(lambda *a: em.expert_mlp(*a, interpret=True),
                       *wide_shapes(16384, 4000))


@pytest.fixture
def small_vmem(monkeypatch):
    """A VMEM in which an expert of 256 x 1024 float32 does not fit
    whole and a tile of 128 of its columns does: the tiled kernels at a
    size the interpreter runs."""
    monkeypatch.setattr(em, "VMEM_BYTES", 13 << 20)


@pytest.mark.parametrize("n", [5, 64, 128, 300])
def test_the_tiled_kernels_are_the_whole_expert_kernels_and_ragged_dot(
        n, small_vmem, monkeypatch):
    """The same routing through the tiled path (four or eight tiles of ``f``),
    through the whole-expert path (the VMEM as it is) and through the
    grouped matmuls: one result."""
    d, f, e = 256, 1024, 4
    lp = layer(40 + n, e=e, d=d, f=f)
    x = jax.random.normal(jax.random.PRNGKey(300 + n), (n, d))
    idx, w = routed(lp, x, k=2)
    tiled, grouped = both(x, idx, w, lp)
    with interpret_kernels():
        text = jaxpr_text(lambda x: moe.moe_dropless(
            x, idx, w, lp["we1"], lp["we3"], lp["we2"], kernel=True), x)
    assert int(re.search(r"grid=\(\d+, (\d+)\)", text).group(1)) >= 4
    monkeypatch.undo()
    # another row count: the whole-expert kernels are jitted by shape
    x2 = jnp.concatenate([x, x[:1]])
    idx2, w2 = jnp.concatenate([idx, idx[:1]]), jnp.concatenate([w, w[:1]])
    whole, _ = both(x2, idx2, w2, lp)
    assert err(tiled, grouped) < 2e-5
    assert err(tiled, whole[:n]) < 2e-5
    assert float(jnp.max(jnp.abs(grouped))) > 0.1


def test_the_tiled_kernel_takes_a_share_and_skips_an_expert_nobody_chose(
        small_vmem):
    """Experts 2 and 3 of 4 held here; expert 3's weights are NaN and
    nobody chooses it."""
    d, f = 256, 1024
    lp = layer(77, e=4, d=d, f=f)
    x = jax.random.normal(jax.random.PRNGKey(78), (24, d))
    idx = jnp.tile(jnp.array([[0, 2]], jnp.int32), (24, 1))
    w = jnp.full((24, 2), 0.5)
    held = {k: lp[k][2:].at[1].set(jnp.nan) for k in ("we1", "we3", "we2")}
    with interpret_kernels(), jax.default_matmul_precision("highest"):
        got = moe.moe_dropless(x, idx, w, held["we1"], held["we3"],
                               held["we2"], first=2, kernel=True)
        want = 0.5 * reference._swiglu(
            x, lp["we1"][2], lp["we3"][2], lp["we2"][2])
    assert err(got, want) < 2e-5


def test_a_weight_of_exactly_zero_reads_as_not_chosen():
    """Expert 0 overflows on every row. Row 0 chose it at weight 1, row
    1 at weight 0: the mask is the weight, so row 1 has expert 1's term
    alone where the grouped form's ``0 * inf`` leaves it NaN."""
    lp = layer(5)
    lp["we1"] = lp["we1"].at[0].mul(1e20)
    lp["we3"] = lp["we3"].at[0].mul(1e20)
    x = jax.random.normal(jax.random.PRNGKey(1100), (2, D))
    idx = jnp.array([[0, 1], [0, 1]])
    got, grouped = both(x, idx, jnp.array([[1.0, 1.0], [0.0, 1.0]]), lp)
    alone, _ = both(x[1:], jnp.array([[1]]), jnp.ones((1, 1)), lp)
    assert not bool(jnp.all(jnp.isfinite(got[0])))
    assert not bool(jnp.all(jnp.isfinite(grouped[1])))
    assert err(got[1], alone[0]) < 1e-5


def test_float32_weights_under_bfloat16_rows_are_cast_as_the_grouped_form_does():
    lp = layer(12)
    x = jax.random.normal(jax.random.PRNGKey(1200), (24, D)).astype(
        jnp.bfloat16)
    idx, w = routed(lp, x.astype(jnp.float32))
    cast = {name: lp[name].astype(jnp.bfloat16)
            for name in ("we1", "we3", "we2")}
    got, grouped = both(x, idx, w, lp)
    want, _ = both(x, idx, w, cast)
    assert got.dtype == jnp.bfloat16 and grouped.dtype == jnp.bfloat16
    assert err(got, want) == 0.0


def test_the_hit_list_is_the_hit_experts_ascending_then_the_last_again():
    idx = jnp.array([[9, 2], [2, 5], [11, 9]])
    w = jnp.array([[0.5, 0.25], [1.0, 2.0], [4.0, 8.0]])
    c, hit, n_hit = em.combine_weights(idx, w, 8, 4)
    # held experts 4..11: 5, 9, 11 are hit -> local 1, 5, 7
    assert n_hit.tolist() == [3]
    assert hit.tolist() == [1, 5, 7, 7, 7, 7, 7, 7]
    want = jnp.zeros((3, 8)).at[0, 5].set(0.5).at[1, 1].set(2.0) \
        .at[2, 7].set(4.0).at[2, 5].set(8.0)
    assert err(c, want) == 0.0
    # a row that names an expert twice adds its weights up
    c, _, _ = em.combine_weights(jnp.array([[3, 3]]), jnp.array([[1., 2.]]),
                                 4, 0)
    assert c.tolist() == [[0.0, 0.0, 0.0, 3.0]]


# -- edl_grouped_expert_mlp: more rows than MAX_ROWS ---------------------------


def skewed(seed, e=128):
    """A layer whose router bias makes a few experts popular and leaves
    some with no row at all (the cell's 2.5-3 x busiest over mean)."""
    lp = layer(seed, e=e)
    lp["router_bias"] = jax.random.normal(jax.random.PRNGKey(seed), (e,)) * 0.3
    return lp


# tokens, experts, choices a token, and what the case adds to "the
# kernel's layer is the ragged_dot form's and the float32 table's"
GROUPED = {
    "256 tokens": dict(n=256),
    "1024 tokens": dict(n=1024),
    "4096 tokens": dict(n=4096),
    "bfloat16 rows": dict(n=1024, dtype=jnp.bfloat16),
    "rows not a multiple of the tile": dict(n=129, e=16, k=3),
    "runs that end inside a tile": dict(n=192, e=16, k=2, to=(2, 5)),
    "experts nobody chose": dict(n=256, poison=True),
    "eight shares add up": dict(n=256, shares=8),
}


@pytest.mark.parametrize("case", sorted(GROUPED))
def test_grouped_kernel_layer_is_the_ragged_form_and_the_references_table(case):
    spec = dict(e=128, k=6, dtype=jnp.float32, to=None, poison=False,
                shares=1)
    spec.update(GROUPED[case])
    n, e, k, dtype = spec["n"], spec["e"], spec["k"], spec["dtype"]
    lp = skewed(len(case), e)
    x = jax.random.normal(jax.random.PRNGKey(n), (n, D))
    if spec["to"]:  # every token to the same experts: two runs of n rows
        idx = jnp.tile(jnp.array([spec["to"]]), (n, 1))
        w = jnp.tile(jnp.array([[0.7, 0.3]]), (n, 1))
    else:
        idx, w = routed(lp, x, k)
    sizes = jnp.sum(idx.reshape(-1)[:, None] == jnp.arange(e), axis=0)
    ends = jnp.cumsum(sizes)
    assert int(jnp.sum(ends[:-1] % em.GROUP_TILE != 0)) > 0
    assert (n * k % em.GROUP_TILE != 0) == (case.startswith("rows not"))
    if not spec["to"] and e == 128:
        assert float(jnp.max(sizes)) > 2.0 * n * k / e, "no skew"
    with jax.default_matmul_precision("highest"):
        table = jnp.zeros((n, e)).at[jnp.arange(n)[:, None], idx].add(w)
        x = x.astype(dtype)
        experts = {name: lp[name].astype(dtype)
                   for name in ("we1", "we3", "we2")}
        want = reference.routed(x.astype(jnp.float32), table, *(
            experts[name].astype(jnp.float32)
            for name in ("we1", "we3", "we2")))
    top = float(jnp.max(jnp.abs(want)))
    assert top > 0.1
    sound = experts
    if spec["poison"]:  # one of them read at whatever weight would show
        unhit = (sizes == 0)[:, None, None]
        assert int(jnp.sum(unhit)) > 0
        experts = {name: jnp.where(unhit, jnp.nan, leaf)
                   for name, leaf in experts.items()}
    held = e // spec["shares"]
    got = jnp.zeros((n, D), jnp.float32)
    ragged = jnp.zeros((n, D), jnp.float32)
    for first in range(0, e, held):
        share = {name: leaf[first:first + held]
                 for name, leaf in experts.items()}
        kw = dict(first=first) if spec["shares"] > 1 else {}
        part, grouped = both(x, idx, w, share, **kw)
        if spec["poison"]:  # XLA:CPU's ragged_dot multiplies every expert
            grouped = both(x, idx, w, sound, **kw)[1]
        assert part.shape == (n, D) and part.dtype == dtype
        got += part.astype(jnp.float32)
        ragged += grouped.astype(jnp.float32)
    if spec["shares"] > 1:  # one share alone is not the layer
        assert err(part, want) > 0.1
    if dtype == jnp.bfloat16:
        # no further from the table than the form that rounds more often
        assert err(got, want) < 2.0 ** -6 * top
        assert err(got, want) <= err(ragged, want) + 2.0 ** -8 * top
    else:
        assert err(got, ragged) < 1e-5
        assert err(got, want) < 1e-5


def test_the_visits_are_each_run_tile_by_tile_then_the_last_again():
    """Runs of 5, 0, 40, 3, 0, 0, 17, 30 rows in tiles of 16: a tile
    that a run's end crosses is visited once for each run in it."""
    sizes = jnp.array([5, 0, 40, 3, 0, 0, 17, 30])
    g, t, fresh, slot, ahead, edges, n = (
        a.tolist() for a in em.group_visits(sizes, 7, 16))
    assert n == [9] and len(g) == 7 + 8 - 1
    assert g[:9] == [0, 2, 2, 2, 3, 6, 6, 7, 7] and set(g[9:]) == {7}
    assert t[:9] == [0, 0, 1, 2, 2, 3, 4, 4, 5] and set(t[9:]) == {5}
    assert fresh[:9] == [1, 1, 0, 0, 1, 1, 0, 1, 0]
    # the experts with rows take the two buffers in turn; each names
    # the next one with rows, the last nobody
    assert slot[:9] == [0, 1, 1, 1, 0, 1, 1, 0, 0]
    assert ahead[:9] == [2, 3, 3, 3, 6, 7, 7, -1, -1]
    assert edges == [0, 5, 5, 45, 48, 48, 48, 65, 95]
    # nobody chose a held expert: no visit, and nothing is fetched
    assert em.group_visits(jnp.zeros((4,), jnp.int32), 2, 16)[-1].tolist() \
        == [0]
