"""Llama decoder: correctness, TP×FSDP sharded training, elastic reshard."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from edl_tpu.api.job import MeshSpec
from edl_tpu.models import llama
from edl_tpu.parallel.mesh import MeshPlan
from edl_tpu.runtime.elastic import ElasticTrainer
from edl_tpu.train.trainer import TrainState, global_batch, make_train_step, shard_state


def test_forward_shapes_and_causality():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    toks = np.arange(2 * 16, dtype=np.int32).reshape(2, 16) % cfg.vocab
    logits = llama.forward(params, jnp.asarray(toks), cfg)
    assert logits.shape == (2, 16, cfg.vocab)
    # causality: changing a future token must not affect earlier logits
    toks2 = toks.copy()
    toks2[:, 10:] = (toks2[:, 10:] + 7) % cfg.vocab
    logits2 = llama.forward(params, jnp.asarray(toks2), cfg)
    np.testing.assert_allclose(logits[:, :10], logits2[:, :10], atol=1e-5)
    assert not np.allclose(logits[:, 10:], logits2[:, 10:])


def test_tp_fsdp_training(cpu_devices):
    cfg = llama.LlamaConfig.tiny()
    plan = MeshPlan.create(dp=2, fsdp=2, tp=2)
    mesh = plan.build()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    pspecs = llama.param_pspecs(cfg, plan)
    tx = optax.adam(3e-3)
    state = shard_state(TrainState.create(params, tx), plan, mesh, pspecs)
    # tp really shards the head dim; fsdp really shards d_model
    wq = state.params["layers"]["wq"]
    wq_shard = (cfg.n_layers, cfg.d_model // 2, cfg.n_heads * cfg.head_dim // 2)
    assert {s.data.shape for s in wq.addressable_shards} == {wq_shard}
    # Adam moments must mirror the TP sharding of their params
    mu_wq = state.opt_state[0].mu["layers"]["wq"]
    assert {s.data.shape for s in mu_wq.addressable_shards} == {wq_shard}
    loss_fn = llama.make_loss_fn(cfg)
    step = make_train_step(loss_fn, tx, plan, mesh, param_pspecs=pspecs)
    rng = np.random.RandomState(0)
    losses = []
    for _ in range(15):
        b = llama.synthetic_tokens(rng, 16, 32, cfg.vocab)
        state, m = step(state, global_batch(b, plan, mesh))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5, losses


def test_tp_matches_unsharded(cpu_devices):
    # The sharding must be a layout choice: tp=2/fsdp=2 loss == dp loss.
    cfg = llama.LlamaConfig.tiny()
    rng_batches = [
        llama.synthetic_tokens(np.random.RandomState(i), 8, 16, cfg.vocab)
        for i in range(3)
    ]

    def run(plan, pspecs):
        mesh = plan.build()
        params = llama.init_params(jax.random.PRNGKey(1), cfg)
        tx = optax.sgd(1e-2)
        state = shard_state(TrainState.create(params, tx), plan, mesh, pspecs)
        step = make_train_step(llama.make_loss_fn(cfg), tx, plan, mesh, pspecs)
        out = []
        for b in rng_batches:
            state, m = step(state, global_batch(b, plan, mesh))
            out.append(float(m["loss"]))
        return out

    plan_tp = MeshPlan.create(dp=2, fsdp=2, tp=2)
    l_tp = run(plan_tp, llama.param_pspecs(cfg, plan_tp))
    plan_dp = MeshPlan.data_parallel(8)
    l_dp = run(plan_dp, None)
    np.testing.assert_allclose(l_tp, l_dp, rtol=1e-4, atol=1e-5)


from tests.llama_harness import loss_curve as _loss_curve  # noqa: E402
# (shared with test_int8_matmul.py via the non-test-module pattern —
# importing one test module from another double-imports it under
# pytest's prepend import mode)


def test_sp_ring_matches_dp(cpu_devices):
    """sp=2 (ring attention) — the long-context strategy as a TRAINABLE
    mesh axis: full train steps, loss == dp-only loss (SURVEY §2.5 SP,
    VERDICT r2 #1a)."""
    l_dp = _loss_curve(MeshPlan.data_parallel(8))
    l_sp = _loss_curve(MeshPlan.create(dp=4, sp=2))
    np.testing.assert_allclose(l_sp, l_dp, rtol=1e-4, atol=1e-5)


def test_sp_ulysses_matches_dp(cpu_devices):
    l_dp = _loss_curve(MeshPlan.data_parallel(8))
    l_ul = _loss_curve(MeshPlan.create(dp=4, sp=2), sp_impl="ulysses")
    np.testing.assert_allclose(l_ul, l_dp, rtol=1e-4, atol=1e-5)


def test_sp_with_fsdp_matches_dp(cpu_devices):
    """sp composes with fsdp+remat (the long-context production mesh)."""
    l_dp = _loss_curve(MeshPlan.data_parallel(8))
    l_mix = _loss_curve(MeshPlan.create(fsdp=2, sp=2, dp=2), remat=True)
    np.testing.assert_allclose(l_mix, l_dp, rtol=1e-4, atol=1e-5)


def test_pp_matches_dp(cpu_devices):
    """pp=2 (GPipe over ppermute) as a TRAINABLE mesh axis (VERDICT r2
    #1b): full train steps through pipeline_apply, loss == dp loss."""
    l_dp = _loss_curve(MeshPlan.data_parallel(8))
    l_pp = _loss_curve(MeshPlan.create(dp=4, pp=2))
    np.testing.assert_allclose(l_pp, l_dp, rtol=1e-4, atol=1e-5)
    # more microbatches than stages (the realistic bubble regime)
    l_pp4 = _loss_curve(MeshPlan.create(dp=2, pp=2), pp_microbatches=4)
    np.testing.assert_allclose(l_pp4, l_dp, rtol=1e-4, atol=1e-5)


def test_pp_fsdp_matches_dp(cpu_devices):
    """pp composes with fsdp (3D dp×pp×fsdp — VERDICT r3 weak #2): the
    pipeline shard_map gathers each stage's fsdp-sharded weights
    per step (ZeRO-style) while the microbatch rows stay split over
    fsdp, and the loss equals dp."""
    l_dp = _loss_curve(MeshPlan.data_parallel(8))
    l_mix = _loss_curve(MeshPlan.create(dp=2, pp=2, fsdp=2))
    np.testing.assert_allclose(l_mix, l_dp, rtol=1e-4, atol=1e-5)


def test_pp_tp_matches_dp(cpu_devices):
    """pp×tp: tp acts as memory sharding inside a pipeline stage (the
    stage gathers tp-sharded weights per step; stage compute is
    replicated over tp) — a layout choice, same loss as dp."""
    l_dp = _loss_curve(MeshPlan.data_parallel(8))
    l_mix = _loss_curve(MeshPlan.create(dp=2, pp=2, tp=2))
    np.testing.assert_allclose(l_mix, l_dp, rtol=1e-4, atol=1e-5)


def test_pp_fsdp_tp_matches_dp(cpu_devices):
    """The flagship 3D mesh pp×fsdp×tp trains: full train steps, loss
    == dp loss, with more microbatches than stages."""
    l_dp = _loss_curve(MeshPlan.data_parallel(8))
    l_3d = _loss_curve(MeshPlan.create(pp=2, fsdp=2, tp=2))
    np.testing.assert_allclose(l_3d, l_dp, rtol=1e-4, atol=1e-5)
    l_3d4 = _loss_curve(
        MeshPlan.create(pp=2, fsdp=2, tp=2), pp_microbatches=4
    )
    np.testing.assert_allclose(l_3d4, l_dp, rtol=1e-4, atol=1e-5)


def test_pp_fsdp_tp_shards_moments_per_stage(cpu_devices):
    """On the 3D mesh every big weight (and its Adam moments) is REALLY
    sharded along all three axes: layer dim over pp, d_model over fsdp,
    head dim over tp — at rest each device holds 1/8 of wq."""
    cfg = llama.LlamaConfig.tiny()
    plan = MeshPlan.create(pp=2, fsdp=2, tp=2)
    mesh = plan.build()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    pspecs = llama.param_pspecs(cfg, plan)
    tx = optax.adam(1e-3)
    state = shard_state(TrainState.create(params, tx), plan, mesh, pspecs)
    shard = (
        cfg.n_layers // 2,
        cfg.d_model // 2,
        cfg.n_heads * cfg.head_dim // 2,
    )
    wq = state.params["layers"]["wq"]
    assert {s.data.shape for s in wq.addressable_shards} == {shard}
    mu_wq = state.opt_state[0].mu["layers"]["wq"]
    assert {s.data.shape for s in mu_wq.addressable_shards} == {shard}


def test_pp_shards_layer_axis_and_moments(cpu_devices):
    """With a pp axis the scan-stacked layer dim is REALLY split across
    stages (each device holds only its stage's layers), and Adam
    moments follow."""
    cfg = llama.LlamaConfig.tiny()
    plan = MeshPlan.create(dp=4, pp=2)
    mesh = plan.build()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    pspecs = llama.param_pspecs(cfg, plan)
    tx = optax.adam(1e-3)
    state = shard_state(TrainState.create(params, tx), plan, mesh, pspecs)
    wq = state.params["layers"]["wq"]
    per_stage = (
        cfg.n_layers // 2,
        cfg.d_model,
        cfg.n_heads * cfg.head_dim,
    )
    assert {s.data.shape for s in wq.addressable_shards} == {per_stage}
    mu_wq = state.opt_state[0].mu["layers"]["wq"]
    assert {s.data.shape for s in mu_wq.addressable_shards} == {per_stage}


def test_sp_sequence_shards_activations(cpu_devices):
    """The sp program really sequence-shards the compute: logits come
    out split over sp on the T dim (no device saw the full sequence)."""
    cfg = llama.LlamaConfig.tiny()
    plan = MeshPlan.create(dp=2, sp=4)
    mesh = plan.build()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    toks = np.arange(4 * 16, dtype=np.int32).reshape(4, 16) % cfg.vocab

    fwd = jax.jit(
        lambda p, t: llama.forward(p, t, cfg, mesh=mesh, plan=plan)
    )
    logits = fwd(params, jnp.asarray(toks))
    spec = logits.sharding.spec
    assert spec[1] == "sp", spec
    # and the math still matches the unsharded oracle
    ref = llama.forward(params, jnp.asarray(toks), cfg)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref), atol=2e-4)


def test_sp_pp_combination_rejected(cpu_devices):
    cfg = llama.LlamaConfig.tiny()
    plan = MeshPlan.create(dp=2, sp=2, pp=2)
    mesh = plan.build()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.zeros((4, 16), jnp.int32)
    import pytest

    with pytest.raises(ValueError, match="sp and pp"):
        llama.forward(params, toks, cfg, mesh=mesh, plan=plan)


def _remat_loss_and_grads(cfg, t=16):
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    toks = llama.synthetic_tokens(np.random.RandomState(0), 4, t, cfg.vocab)
    loss_fn = llama.make_loss_fn(cfg)
    loss, grads = jax.value_and_grad(loss_fn)(params, toks)
    return float(loss), grads


def _assert_grads_close(g, g0, rtol=1e-4, atol=1e-6):
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=rtol, atol=atol
        ),
        g,
        g0,
    )


@pytest.mark.parametrize("policy", ["fit", "full", "mlp", "dots"])
def test_remat_policies_grad_and_match(policy):
    """Every remat policy produces the same loss and finite grads as
    the no-remat baseline (ADVICE r2: the policy dial had no coverage);
    "fit" with no trainer's offer open is "full"."""
    import dataclasses

    base = llama.LlamaConfig.tiny()
    l0, g0 = _remat_loss_and_grads(base)
    cfg = dataclasses.replace(base, remat=True, remat_policy=policy)
    l, g = _remat_loss_and_grads(cfg)
    np.testing.assert_allclose(l, l0, rtol=1e-6, err_msg=policy)
    _assert_grads_close(g, g0)


def _dots(fn, *args):
    """Matmuls in the optimized program (a scan's body counts once)."""
    import re

    text = jax.jit(fn).lower(*args).compile().as_text()
    return len(re.findall(r" dot\(", text))


# rungs of llama.KEEP_ORDER the room is sized for -> (names kept with
# the flash kernel in the program, names kept without it, matmuls of
# the optimized loss-and-gradient program without it that are spared)
_FIT_ROOMS = {
    "none": ((), (), 0),
    "flash_pair": (("flash_out", "flash_lse"), (), 0),
    "mlp_up": (("flash_out", "flash_lse", "mlp_up"), ("mlp_up",), 1),
    "unbounded": (
        ("flash_out", "flash_lse", "mlp_up", "mlp_gate",
         "attn_q", "attn_k", "attn_v"),
        ("mlp_up", "mlp_gate", "attn_q", "attn_k", "attn_v"), 5),
}


@pytest.mark.parametrize("flash", [True, False], ids=["flash", "dense"])
@pytest.mark.parametrize("room", list(_FIT_ROOMS))
def test_fit_keeps_the_richest_prefix_that_fits_the_offer(room, flash):
    """remat_policy="fit" under a trainer's offer: what is kept is what
    of KEEP_ORDER the room allows, here a prefix (sized from the model's
    own bytes, so the test follows a change of widths), a program
    without the flash kernel never keeps its names, the kept matmuls
    are really spared, and loss and gradients are "full"'s."""
    import dataclasses

    from edl_tpu.ops.flash_attention import interpret_kernels
    from edl_tpu.parallel import remat

    rows, t = 2, 128
    cfg = dataclasses.replace(
        llama.LlamaConfig.tiny(), remat=True, use_flash=flash)
    assert cfg.remat_policy == "fit"
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    toks = llama.synthetic_tokens(np.random.RandomState(0), rows, t, cfg.vocab)
    loss_and_grads = lambda c: (  # noqa: E731
        lambda p, b: jax.value_and_grad(llama.make_loss_fn(c))(p, b))
    # the room: the model's estimate of its step plus the bytes of the
    # candidates up to the named rung of the FLASH program's list, so
    # "flash_pair" is room the dense program cannot use for anything
    with_flash = llama.keep_candidates(cfg, rows * t, True)
    rungs = list(_FIT_ROOMS).index(room)
    working = llama.step_working_bytes(cfg, params, rows * t)
    room_bytes = (1 << 60) if room == "unbounded" else working + sum(
        b for _, b in with_flash[:rungs])
    want, want_dense, spared = _FIT_ROOMS[room]
    with interpret_kernels():
        l0, g0 = jax.jit(loss_and_grads(
            dataclasses.replace(cfg, remat_policy="full")))(params, toks)
        with remat.offer(room_bytes) as offer:
            l, g = jax.jit(loss_and_grads(cfg))(params, toks)
        assert offer.kept == (want if flash else want_dense)
        assert offer.kept_bytes == sum(
            b for names, b in with_flash if set(names) <= set(offer.kept))
        # one rung down is the prefix one shorter
        with remat.offer(room_bytes, back_off=1) as lower:
            jax.eval_shape(loss_and_grads(cfg), params, toks)
        assert lower.kept == offer.kept[:len(offer.kept) - len(
            next((n for n in reversed(llama.KEEP_ORDER)
                  if set(n) <= set(offer.kept)), ()))]
        if not flash:
            full = _dots(loss_and_grads(
                dataclasses.replace(cfg, remat_policy="full")), params, toks)
            with remat.offer(room_bytes):
                assert _dots(loss_and_grads(cfg), params, toks) == full - spared
    np.testing.assert_allclose(float(l), float(l0), rtol=1e-6)
    _assert_grads_close(g, g0, rtol=1e-5)


@pytest.mark.parametrize("room,back_off,want", [
    (0, 0, ()),
    (5, 0, ("a",)),              # b is too large, c after it still fits
    (7, 0, ("a", "c")),
    (7, 1, ("a",)),              # a rung down: the last one taken goes
    (15, 0, ("a", "b")),
    (17, 0, ("a", "b", "c")),
    (17, 2, ("a",)),
    (17, 5, ()),
])
def test_choose_takes_in_order_what_still_fits(room, back_off, want):
    from edl_tpu.parallel import remat

    candidates = [(("a",), 5), (("b",), 10), (("c",), 2)]
    assert remat.choose(candidates, 0) == ()  # no offer open
    with remat.offer(100 + room, back_off) as offer:
        assert remat.choose(candidates, 100) == want
    assert offer.kept == want and offer.rungs == len(want)
    assert offer.kept_bytes == sum(
        b for names, b in candidates if names[0] in want)


def test_fit_is_full_under_a_pipeline_and_with_no_offer():
    import dataclasses

    from edl_tpu.parallel import remat

    cfg = dataclasses.replace(llama.LlamaConfig.tiny(), remat=True)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    x = jnp.zeros((4, 16, cfg.d_model), cfg.dtype)
    assert llama._fit_kept(cfg, params, x, None, 1, 1) == ()
    with remat.offer(1 << 60) as offer:
        assert llama._fit_kept(cfg, params, x, None, 1, 2) == ()
        assert offer.kept == ()
        assert llama._fit_kept(cfg, params, x, None, 1, 1) == (
            "mlp_up", "mlp_gate", "attn_q", "attn_k", "attn_v")


def test_remat_attn_policy_runs_with_flash():
    """remat_policy="attn" with the flash kernel: traces, grads finite,
    loss matches the baseline (interpret-mode pallas on CPU)."""
    import dataclasses

    base = llama.LlamaConfig.tiny()
    # flash kernel block sizes need T >= the fitted block: use T=128
    cfg = dataclasses.replace(
        base, remat=True, remat_policy="attn", use_flash=True
    )
    from edl_tpu.ops.flash_attention import flash_supported

    from edl_tpu.ops.flash_attention import interpret_kernels

    t = 128
    assert flash_supported(t)
    # the model path never picks the interpreter itself: the test asks
    with interpret_kernels():
        l_attn, g = _remat_loss_and_grads(cfg, t=t)
        ref = dataclasses.replace(base, use_flash=True)
        l_ref, _ = _remat_loss_and_grads(ref, t=t)
    np.testing.assert_allclose(l_attn, l_ref, rtol=1e-4)
    assert all(
        np.isfinite(np.asarray(x)).all()
        for x in jax.tree_util.tree_leaves(g)
    )


def test_use_flash_never_degrades_silently():
    """use_flash=True either runs the kernel or raises: an unsupported
    length is a config error (no dense substitute), and on a CPU with
    no interpreter requested the kernel itself refuses."""
    import dataclasses

    import pytest

    cfg = dataclasses.replace(llama.LlamaConfig.tiny(), use_flash=True)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="not flash-supported"):
        llama.forward(params, jnp.zeros((1, 520), jnp.int32), cfg)
    with pytest.raises(ValueError, match="interpret mode"):
        llama.forward(params, jnp.zeros((1, 128), jnp.int32), cfg)


def test_remat_attn_policy_guards():
    """The attn policy refuses configurations where the flash residual
    names would not exist (silent degradation to full remat)."""
    import dataclasses

    import pytest

    base = llama.LlamaConfig.tiny()
    # no flash at all -> _remat_policy raises
    cfg = dataclasses.replace(base, remat=True, remat_policy="attn")
    with pytest.raises(ValueError, match="use_flash"):
        _remat_loss_and_grads(cfg)
    # flash on, but an unsupported sequence length -> forward raises
    # instead of silently taking the dense path (ADVICE r2)
    cfg = dataclasses.replace(
        base, remat=True, remat_policy="attn", use_flash=True
    )
    from edl_tpu.ops.flash_attention import flash_supported

    t_bad = 520  # > 512 and not a multiple of the 128-lane tile
    assert not flash_supported(t_bad)
    with pytest.raises(ValueError, match="not flash-supported"):
        _remat_loss_and_grads(cfg, t=t_bad)
    # sp mesh: ring/ulysses never run the flash kernel -> rejected
    plan = MeshPlan.create(dp=4, sp=2)
    mesh = plan.build()
    cfg = dataclasses.replace(
        base, remat=True, remat_policy="attn", use_flash=True
    )
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="flash kernel"):
        llama.forward(
            params, jnp.zeros((4, 128), jnp.int32), cfg, mesh=mesh, plan=plan
        )


def test_llama_elastic_sp_reshard(cpu_devices):
    """sp pinned in the in-process elastic runtime: the mesh-aware loss
    factory rebuilds the ring-attention program at every reshard while
    dp absorbs the worker change."""
    cfg = llama.LlamaConfig.tiny()
    tr = ElasticTrainer(
        None,
        optax.adam(1e-3),
        mesh_spec=MeshSpec(sp=2),
        chips_per_worker=2,
        per_chip_batch=4,
        param_pspecs=lambda plan: llama.param_pspecs(cfg, plan),
        make_loss=lambda plan, mesh: llama.make_loss_fn(cfg, plan, mesh),
    )
    tr.start(llama.init_params(jax.random.PRNGKey(0), cfg), n_workers=2)
    rng = np.random.RandomState(0)

    def data(bs):
        return llama.synthetic_tokens(rng, bs, 16, cfg.vocab)

    tr.train_steps(data, 3)
    tr.request_rescale(4)
    tr.train_steps(data, 3)
    assert tr.plan.describe() == {"dp": 4, "sp": 2}
    assert len(tr.report.reshards) == 1
    assert int(tr.state.step) == 6


def test_llama_elastic_fsdp_reshard(cpu_devices):
    # The BASELINE headline config in miniature: elastic FSDP llama.
    cfg = llama.LlamaConfig.tiny()
    plan_spec = MeshSpec(fsdp=2)
    tr = ElasticTrainer(
        llama.make_loss_fn(cfg),
        optax.adam(1e-3),
        mesh_spec=plan_spec,
        chips_per_worker=2,
        per_chip_batch=4,
        # plan-aware: evaluated once per distinct mesh
        param_pspecs=lambda plan: llama.param_pspecs(cfg, plan),
    )
    tr.start(llama.init_params(jax.random.PRNGKey(0), cfg), n_workers=2)
    rng = np.random.RandomState(0)

    def data(bs):
        return llama.synthetic_tokens(rng, bs, 16, cfg.vocab)

    tr.train_steps(data, 3)
    tr.request_rescale(4)
    tr.train_steps(data, 3)
    assert tr.n_workers == 4
    assert tr.plan.describe() == {"dp": 4, "fsdp": 2}
    assert len(tr.report.reshards) == 1
    assert int(tr.state.step) == 6
