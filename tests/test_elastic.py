"""ElasticTrainer: in-place reshard with zero restarts.

The north-star behavior (BASELINE.md): scale 2→4→1 workers mid-training
with state carried bit-exactly through each reshard, stall timed, and
the loss curve continuing as if nothing happened.
"""

import jax
import numpy as np
import optax
import pytest

from edl_tpu.api.job import MeshSpec
from edl_tpu.models import ctr, linreg
from edl_tpu.parallel import sharding as shd
from edl_tpu.runtime import checkpoint as ckpt
from edl_tpu.runtime.elastic import ElasticTrainer
from edl_tpu.train.trainer import TrainState


def linreg_data_fn():
    x, y = linreg.synthetic_dataset(4096)
    state = {"i": 0}

    def fn(batch_size):
        lo = state["i"] % (4096 - batch_size)
        state["i"] += batch_size
        return {"x": x[lo : lo + batch_size], "y": y[lo : lo + batch_size]}

    return fn


def test_elastic_rescale_preserves_training(cpu_devices):
    tr = ElasticTrainer(
        linreg.loss_fn,
        optax.sgd(0.05),
        chips_per_worker=2,
        per_chip_batch=16,
    )
    tr.start(linreg.init_params(jax.random.PRNGKey(0)), n_workers=2)
    assert tr.n_devices == 4
    data = linreg_data_fn()
    tr.train_steps(data, 10)
    params_before = shd.to_host(tr.state.params)

    tr.request_rescale(4)  # grow 2 -> 4 workers (8 devices)
    tr.train_steps(data, 1)
    assert tr.n_devices == 8
    assert tr.global_batch_size == 128
    # exactly one reshard, params carried over bit-exactly at the boundary
    assert len(tr.report.reshards) == 1
    ev = tr.report.reshards[0]
    assert (ev.from_workers, ev.to_workers) == (2, 4)
    assert ev.stall_s < 30.0  # the north-star bound
    assert ev.step == 10

    tr.train_steps(data, 9)
    tr.request_rescale(1)  # shrink 4 -> 1 (failure/squeeze)
    tr.train_steps(data, 10)
    assert tr.n_devices == 2
    assert len(tr.report.reshards) == 2
    # training made progress across all three mesh incarnations
    losses = tr.report.losses
    assert losses[-1] < losses[0] * 0.5
    assert tr.report.steps == 30
    assert int(tr.state.step) == 30  # no restart: step count never reset


def test_reshard_is_bitexact(cpu_devices):
    # Snapshot -> remesh -> restore must not change a single bit of state.
    tr = ElasticTrainer(linreg.loss_fn, optax.adam(1e-2), chips_per_worker=1)
    tr.start(linreg.init_params(jax.random.PRNGKey(1)), n_workers=4)
    data = linreg_data_fn()
    tr.train_steps(data, 5)
    before = ckpt.snapshot(tr.state)
    tr.request_rescale(8)
    tr._maybe_rescale()
    after = ckpt.snapshot(tr.state)
    jax.tree_util.tree_map(np.testing.assert_array_equal, before.params, after.params)
    jax.tree_util.tree_map(
        np.testing.assert_array_equal, before.opt_state, after.opt_state
    )


def test_elastic_fsdp_ctr(cpu_devices):
    # CTR with an fsdp axis: reshard re-slices the embedding across the
    # new mesh (the Llama-elastic-FSDP mechanism, at CTR scale).
    tr = ElasticTrainer(
        ctr.loss_fn,
        optax.adam(1e-2),
        mesh_spec=MeshSpec(fsdp=2),
        chips_per_worker=2,
        per_chip_batch=32,
    )
    tr.start(ctr.init_params(jax.random.PRNGKey(0), vocab=4096, emb=8), n_workers=2)
    rng = np.random.RandomState(0)

    def data(bs):
        return ctr.synthetic_batch(rng, bs, vocab=4096)

    tr.train_steps(data, 5)
    emb = tr.state.params["embedding"]
    assert {s.data.shape for s in emb.addressable_shards} == {(2048, 8)}
    tr.request_rescale(4)
    tr.train_steps(data, 5)
    emb = tr.state.params["embedding"]
    # fsdp stays 2, dp grew: vocab still sharded 2-way over fsdp
    assert tr.plan.describe() == {"dp": 4, "fsdp": 2}
    assert {s.data.shape for s in emb.addressable_shards} == {(2048, 8)}
    assert tr.report.reshards[0].stall_s < 30.0


def test_checkpoint_roundtrip(tmp_path, cpu_devices):
    params = linreg.init_params(jax.random.PRNGKey(0))
    tx = optax.adam(1e-2)
    state = TrainState.create(params, tx)
    host = ckpt.snapshot(state)
    ckpt.save(str(tmp_path / "c1"), host, {"job": "demo"})
    like = TrainState.create(linreg.init_params(jax.random.PRNGKey(42)), tx)
    loaded = ckpt.load(str(tmp_path / "c1"), like)
    jax.tree_util.tree_map(np.testing.assert_array_equal, host.params, loaded.params)
    assert ckpt.load_metadata(str(tmp_path / "c1")) == {"job": "demo"}


def test_staged_reshard_preserves_state_across_mesh_change(cpu_devices):
    """staged_reshard (overlapped host pipeline) must be value-identical
    to snapshot+restore when moving state onto a different-size mesh."""
    import numpy as np
    import optax

    from edl_tpu.models import ctr
    from edl_tpu.parallel.mesh import MeshPlan
    from edl_tpu.runtime import checkpoint as ckpt
    from edl_tpu.train.trainer import TrainState, shard_state

    import jax

    from edl_tpu.parallel import sharding as shd

    plan8 = MeshPlan.data_parallel(8)
    mesh8 = plan8.build()
    tx = optax.adam(1e-3)
    chunk = shd._CHUNK_BYTES
    try:
        shd._CHUNK_BYTES = 1 << 12  # 4 KB: exercise multi-piece path
        state = shard_state(
            TrainState.create(
                ctr.init_params(jax.random.PRNGKey(0), vocab=2048, emb=8), tx
            ),
            plan8,
            mesh8,
        )
        plan4 = MeshPlan.data_parallel(4)
        mesh4 = plan4.build(jax.devices()[:4])
        out = ckpt.staged_reshard(state, plan4, mesh4, stage="f32")  # pin: exactness test
        ref = ckpt.restore(ckpt.snapshot(state), plan4, mesh4)
        for a, b in zip(
            jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(ref)
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert int(out.step) == int(state.step)
    finally:
        shd._CHUNK_BYTES = chunk


def test_staged_reshard_onto_fsdp_mesh(cpu_devices):
    """Regression: pieces uploaded to an fsdp-sharded destination must
    split on the target's dim-0 partition count — ragged pieces make
    device_put raise (vocab 2048 / 8-way fsdp; tiny piece size forces
    many pieces whose raw ceil-rows would not divide by 8)."""
    import numpy as np
    import optax

    from edl_tpu.models import ctr
    from edl_tpu.parallel import sharding as shd
    from edl_tpu.parallel.mesh import MeshPlan
    from edl_tpu.runtime import checkpoint as ckpt
    from edl_tpu.train.trainer import TrainState, shard_state

    import jax

    src_plan = MeshPlan.data_parallel(1)  # single-device: pieces split
    src_mesh = src_plan.build(jax.devices()[:1])
    fsdp_plan = MeshPlan.fsdp_only(8)
    fsdp_mesh = fsdp_plan.build()
    tx = optax.adam(1e-3)
    chunk = shd._CHUNK_BYTES
    try:
        shd._CHUNK_BYTES = 3 << 10  # odd size: ceil-rows not % 8
        state = shard_state(
            TrainState.create(
                ctr.init_params(jax.random.PRNGKey(0), vocab=2048, emb=8), tx
            ),
            src_plan,
            src_mesh,
        )
        out = ckpt.staged_reshard(state, fsdp_plan, fsdp_mesh, stage="f32")  # pin: exactness test
        ref = ckpt.restore(ckpt.snapshot(state), fsdp_plan, fsdp_mesh)
        for a, b in zip(
            jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(ref)
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    finally:
        shd._CHUNK_BYTES = chunk


def test_reshard_event_flags_host_fallback(cpu_devices, monkeypatch):
    """When the direct device move fails, the reshard completes through
    host staging and the event is instrumented as a fallback (VERDICT
    r1 #7: measure when the slow path triggers)."""
    from edl_tpu.runtime import elastic as el

    def _boom(*a, **k):
        raise RuntimeError("transfer layer down")

    monkeypatch.setattr(el, "_device_reshard", _boom)
    tr = ElasticTrainer(
        linreg.loss_fn,
        optax.sgd(1e-2),
        mesh_spec=MeshSpec(),
        per_chip_batch=16,
    )
    tr.start(linreg.init_params(jax.random.PRNGKey(0)), 2)
    data = linreg_data_fn()
    tr.train_steps(data, 2)
    tr.request_rescale(4)
    rep = tr.train_steps(data, 2)
    assert [e.fallback for e in rep.reshards] == [True]
    assert tr.n_workers == 4
    # and the fast path reports fallback=False
    monkeypatch.undo()
    tr.request_rescale(2)
    rep = tr.train_steps(data, 2)
    assert rep.reshards[-1].fallback is False


def test_host_fallback_stall_model():
    # 17 GB on one host at 1 GiB/s: 17 s — inside the 30 s budget
    s = ckpt.host_fallback_stall_model(17 * (1 << 30), 1, 1 << 30)
    assert abs(s - 17.0) < 1e-9
    # spreading over 8 hosts divides the per-host bytes
    assert ckpt.host_fallback_stall_model(17 * (1 << 30), 8, 1 << 30) == s / 8
    import pytest as _pytest

    with _pytest.raises(ValueError):
        ckpt.host_fallback_stall_model(1, 0, 1.0)


def test_staged_reshard_int8_moment_staging(cpu_devices):
    """int8 moment staging (VERDICT r2 #4): params move EXACTLY, Adam
    moments within 1/127 of their block absmax, and wire bytes for the
    moments drop ~4x (ops/quant.py; stall measured on hardware by
    bench.py)."""
    import numpy as np
    import optax

    import jax

    from edl_tpu.models import ctr
    from edl_tpu.parallel.mesh import MeshPlan
    from edl_tpu.runtime import checkpoint as ckpt
    from edl_tpu.train.trainer import (
        TrainState,
        global_batch,
        make_train_step,
        shard_state,
    )

    plan = MeshPlan.data_parallel(4)
    mesh = plan.build(jax.devices()[:4])
    tx = optax.adam(1e-3)
    state = shard_state(
        TrainState.create(
            ctr.init_params(jax.random.PRNGKey(0), vocab=4096, emb=8), tx
        ),
        plan,
        mesh,
    )
    # one real step so moments are non-trivial
    step = make_train_step(ctr.make_loss_fn(), tx, plan, mesh, donate=False)
    b = ctr.synthetic_batch(np.random.RandomState(0), 64, vocab=4096)
    state, _ = step(state, global_batch(b, plan, mesh))

    plan2 = MeshPlan.create(dp=2, fsdp=4)
    mesh2 = plan2.build()
    out = ckpt.staged_reshard(state, plan2, mesh2, stage="int8")
    for a, bb in zip(
        jax.tree_util.tree_leaves(state.params),
        jax.tree_util.tree_leaves(out.params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(bb))
    mu0 = np.asarray(state.opt_state[0].mu["embedding"])
    mu1 = np.asarray(out.opt_state[0].mu["embedding"])
    denom = np.maximum(np.abs(mu0).max(axis=-1, keepdims=True), 1e-12)
    assert (np.abs(mu0 - mu1) / denom).max() <= 1 / 127 + 1e-6


def test_stall_model_staging_aware():
    """The 8B stall model charges compressed moments honestly: an
    Adam-shaped state halves, an adafactor-shaped state barely moves."""
    from edl_tpu.runtime import checkpoint as ckpt

    gb = 1 << 30
    bw = 1 * gb
    adam = ckpt.host_fallback_stall_model(
        30 * gb, 1, bw, moment_bytes=20 * gb, stage="int8"
    )
    assert abs(adam - (10 + 20 * 0.26)) < 1e-6
    adafactor = ckpt.host_fallback_stall_model(
        17 * gb, 1, bw, moment_bytes=1 * gb, stage="int8"
    )
    assert 16.2 < adafactor < 16.3
    raw = ckpt.host_fallback_stall_model(30 * gb, 1, bw)
    assert raw == 30.0


# ---------------------------------------------------------------------------
# a reshard back to a mesh the job has had reuses what was built for it


def _linreg_trainer(devices, tx=None, **kw):
    return ElasticTrainer(
        linreg.loss_fn, tx or optax.sgd(0.05), per_chip_batch=8,
        devices=devices, **kw
    )


def _run_schedule(tr, schedule, forget=False, steps=2):
    """``steps`` steps on each worker count of ``schedule`` in turn;
    ``forget`` empties the kept records before each rescale, which is
    what every reshard did before they were kept."""
    data = linreg_data_fn()
    tr.start(linreg.init_params(jax.random.PRNGKey(0)), schedule[0])
    tr.train_steps(data, steps)
    for workers in schedule[1:]:
        if forget:
            tr._built.clear()
        tr.request_rescale(workers)
        tr.train_steps(data, steps)
    return tr.report.losses, shd.to_host(tr.merged_state.params)


def _assert_trees_equal(a, b):
    jax.tree_util.tree_map(np.testing.assert_array_equal, a, b)


@pytest.mark.parametrize("sync_every", [1, 2])
def test_reused_mesh_trains_as_a_rebuilt_one_does(cpu_devices, sync_every):
    """4 -> 2 -> 4 -> 2 with the records kept against the same schedule
    with every mesh built afresh: the same losses and the same final
    parameters, bit for bit — with ``sync_every`` 2 through the kept
    ``LocalSyncStepper`` too."""
    schedule = [4, 2, 4, 2]
    kept = _linreg_trainer(cpu_devices[:4], sync_every=sync_every)
    fresh = _linreg_trainer(cpu_devices[:4], sync_every=sync_every)
    losses_kept, params_kept = _run_schedule(kept, schedule, steps=3)
    losses_fresh, params_fresh = _run_schedule(
        fresh, schedule, forget=True, steps=3)
    assert [e.step_reused for e in kept.report.reshards] == [False, True, True]
    assert [e.step_reused for e in fresh.report.reshards] == [False] * 3
    assert losses_kept == losses_fresh
    _assert_trees_equal(params_kept, params_fresh)
    assert (kept._stepper is not None) == (sync_every > 1)
    assert len(kept._built) == 2  # one record a distinct mesh


@pytest.mark.parametrize("path", ["device", "host"])
def test_reshard_onto_a_reused_mesh_is_bitexact(cpu_devices, monkeypatch, path):
    """Parameters and optimizer state come through a reshard onto a mesh
    the job has had bit for bit, by the device path and by the
    host-staged fallback alike."""
    from edl_tpu.runtime import elastic as el

    tr = _linreg_trainer(cpu_devices[:4], tx=optax.adam(1e-2))
    tr.start(linreg.init_params(jax.random.PRNGKey(1)), 4)
    data = linreg_data_fn()
    tr.train_steps(data, 3)
    tr.request_rescale(2)
    tr.train_steps(data, 3)
    before = ckpt.snapshot(tr.state)
    if path == "host":
        def _boom(*a, **k):
            raise RuntimeError("transfer layer down")

        monkeypatch.setattr(el, "_device_reshard", _boom)
    tr.request_rescale(4)
    tr._maybe_rescale()
    ev = tr.report.reshards[-1]
    assert ev.step_reused is True and ev.fallback is (path == "host")
    after = ckpt.snapshot(tr.state)
    _assert_trees_equal(before.params, after.params)
    _assert_trees_equal(before.opt_state, after.opt_state)
    # and the kept step takes the state the fallback placed
    rep = tr.train_steps(data, 2)
    assert np.isfinite(rep.losses[-1]) and int(tr.state.step) == 8
    assert tr.report.reshards[-1].recompile_s > 0.0


def test_mesh_factories_run_once_per_distinct_mesh(cpu_devices):
    """``make_loss`` and a callable ``param_pspecs`` are evaluated when a
    mesh is first built and not on a return to it; a pool replaced by
    other devices of the same count is another mesh."""
    calls = {"loss": [], "pspecs": []}

    def make_loss(plan, mesh):
        calls["loss"].append((plan.describe()["dp"], mesh.devices.size))
        return linreg.loss_fn

    def pspecs(plan):
        calls["pspecs"].append(plan.describe()["dp"])
        return None

    tr = ElasticTrainer(
        None, optax.sgd(0.05), per_chip_batch=8, devices=cpu_devices[:4],
        make_loss=make_loss, param_pspecs=pspecs,
    )
    _run_schedule(tr, [4, 2, 4, 2, 4])
    assert calls["loss"] == [(4, 4), (2, 2)]
    assert calls["pspecs"] == [4, 2]
    assert [e.step_reused for e in tr.report.reshards] == [
        False, True, True, True]

    # the same count over other devices: not the old record
    data = linreg_data_fn()
    old_mesh = tr.mesh
    tr.pool = list(cpu_devices[4:8])
    tr.request_rescale(2)
    tr.train_steps(data, 2)
    ev = tr.report.reshards[-1]
    assert ev.step_reused is False
    assert calls["loss"][-1] == (2, 2) and len(calls["loss"]) == 3
    assert list(tr.mesh.devices.flat) == list(cpu_devices[4:6])
    assert tr.mesh is not old_mesh
    assert {s.device for s in tr.state.params["w"].addressable_shards} == set(
        cpu_devices[4:6])
    # a replaced mesh_spec is another plan over the same devices
    tr.mesh_spec = MeshSpec(fsdp=2)
    tr.request_rescale(4)
    tr.train_steps(data, 2)
    assert tr.report.reshards[-1].step_reused is False
    assert tr.plan.describe() == {"dp": 2, "fsdp": 2}


def test_reshard_counters_say_how_often_the_step_was_reused(cpu_devices):
    from edl_tpu.obs import metrics as obs_metrics

    reg = obs_metrics.reset_default_registry()
    try:
        tr = _linreg_trainer(cpu_devices[:4])
        _run_schedule(tr, [4, 2, 4, 2])
        assert reg.get("edl_reshard_total").value(path="device") == 3
        assert reg.get("edl_reshard_total").value(path="host") == 0
        assert reg.get("edl_reshard_step_reused_total").value() == 2
        assert reg.get("edl_reshard_stall_seconds").stats()["count"] == 3
    finally:
        obs_metrics.reset_default_registry()


# ---------------------------------------------------------------------------
# what a step's rematerialised layers keep is fitted to the device when the
# step is first built, and filed with it (PR 40)


def _tiny_llama_trainer(devices, limit, monkeypatch, peak=None):
    """An ``ElasticTrainer`` over a toy dense decoder with ``remat`` on,
    fsdp 2, on devices that claim ``limit`` bytes each."""
    import dataclasses

    from edl_tpu.models import llama
    from edl_tpu.train import trainer as tr_mod

    monkeypatch.setattr(tr_mod, "device_bytes_limit", lambda mesh: limit)
    if peak is not None:
        monkeypatch.setattr(tr_mod, "_peak_bytes", peak)
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab=128), remat=True)
    rng = np.random.RandomState(0)
    trainer = ElasticTrainer(
        None, optax.adafactor(1e-3), mesh_spec=MeshSpec(fsdp=2),
        per_chip_batch=2, devices=devices,
        param_pspecs=lambda plan: llama.param_pspecs(cfg, plan),
        make_loss=lambda plan, mesh: llama.make_loss_fn(cfg, plan, mesh),
    )
    trainer.start(llama.init_params(jax.random.PRNGKey(0), cfg), len(devices))
    return trainer, (lambda rows: llama.synthetic_tokens(
        rng, rows, 16, cfg.vocab))


def test_a_meshs_step_is_fitted_once_and_filed_with_what_it_keeps(
        cpu_devices, monkeypatch):
    """4 -> 2 -> 4 on devices with room for everything: each mesh's step
    is compiled ONCE, on its first visit, with the whole of
    ``llama.KEEP_ORDER`` the dense program names; the return to four
    compiles nothing and reads the same record; the build's span and
    ``reshard.recompile`` carry what was kept, and the memory ledger
    files it under ``remat_kept`` beside ``params`` and ``opt``."""
    from edl_tpu.obs import compilewatch, memledger
    from edl_tpu.obs import metrics as obs_metrics
    from edl_tpu.utils import tracing

    reg = obs_metrics.reset_default_registry()
    memledger.reset_default_ledger(reg)
    tracing.tracer().clear()
    try:
        tr, data = _tiny_llama_trainer(cpu_devices[:4], 1 << 40, monkeypatch)
        compiles = reg.counter("edl_compiles_total", "", ("program",))
        steps_built = lambda: compiles.value(program="edl_train_step")  # noqa: E731
        before = steps_built()
        tr.train_steps(data, 2)
        assert steps_built() - before == 1
        on_four = tr._step_fn.kept
        assert on_four["remat_kept"] == "mlp_up,mlp_gate,attn_q,attn_k,attn_v"
        assert on_four["compiles"] == 1 and on_four["hbm_headroom_bytes"] > 0
        gauge = reg.get("edl_hbm_bytes")
        assert gauge.value(category="remat_kept") == (
            4 * on_four["remat_kept_bytes"])
        tr.request_rescale(2)
        tr.train_steps(data, 2)
        assert steps_built() - before == 2
        on_two = tr._step_fn.kept
        assert on_two is not on_four and on_two["compiles"] == 1
        assert gauge.value(category="remat_kept") == (
            2 * on_two["remat_kept_bytes"])
        tr.request_rescale(4)
        with compilewatch.Window() as built:
            tr.train_steps(data, 2)
        assert built.programs == 0 and steps_built() - before == 2
        assert tr._step_fn.kept is on_four
        assert gauge.value(category="remat_kept") == (
            4 * on_four["remat_kept_bytes"])
        spans = tracing.tracer().spans
        assert [s.attrs for s in spans("train.build_step")] == [
            on_four, on_two]
        recompiles = spans("reshard.recompile")
        assert [s.attrs["step_reused"] for s in recompiles] == [False, True]
        for span, kept in zip(recompiles, (on_two, on_four)):
            assert {k: span.attrs[k] for k in kept} == kept
        assert np.isfinite(tr.report.losses).all()
    finally:
        obs_metrics.reset_default_registry()
        memledger.reset_default_ledger()


def test_a_step_the_compiler_leaves_no_headroom_steps_down_a_rung(
        cpu_devices, monkeypatch):
    """Where the compiled peak leaves less than the headroom the step is
    traced again one rung lower, and only then: two compiles, the last
    entry of the first choice given up."""
    from edl_tpu.train import trainer as tr_mod

    limit = 1 << 40
    real, calls = tr_mod._peak_bytes, []

    def peak(compiled):
        calls.append(real(compiled))
        return limit if len(calls) == 1 else calls[-1]

    tr, data = _tiny_llama_trainer(cpu_devices[:4], limit, monkeypatch, peak)
    tr.train_steps(data, 1)
    kept = tr._step_fn.kept
    assert kept["compiles"] == 2 == len(calls)
    assert kept["remat_kept"] == "mlp_up,mlp_gate"
    assert np.isfinite(tr.report.losses).all()


def test_no_limit_no_offer_the_step_is_the_plain_jit(cpu_devices):
    """A backend that reports no memory limit (the CPU): nothing is
    offered, compiled ahead or recorded, and the step trains."""
    import dataclasses

    from edl_tpu.models import llama
    from edl_tpu.train import trainer as tr_mod

    assert tr_mod.device_bytes_limit(
        jax.sharding.Mesh(np.array(cpu_devices[:1]), ("dp",))) is None
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab=128), remat=True)
    tr = ElasticTrainer(
        llama.make_loss_fn(cfg), optax.adafactor(1e-3), per_chip_batch=2,
        devices=cpu_devices[:2])
    tr.start(llama.init_params(jax.random.PRNGKey(0), cfg), 2)
    rng = np.random.RandomState(0)
    rep = tr.train_steps(
        lambda rows: llama.synthetic_tokens(rng, rows, 16, cfg.vocab), 2)
    assert tr._step_fn.kept == {} and np.isfinite(rep.losses).all()
