"""ElasticTrainer — in-place mesh reshard instead of job restarts.

The genuinely new part of the framework (SURVEY §7 layer 4). The
reference achieves elasticity by killing/adding k8s pods and letting
Paddle's etcd runtime re-form (reference: pkg/autoscaler.go:361
retargets Parallelism; docker/paddle_k8s re-runs discovery). On TPU a
restart throws away compiled programs and device state, so the protocol
is instead:

    scale event → snapshot state to host RAM → rebuild the mesh over the
    new device set → re-shard state onto it → resume at the next step

The north-star metric (BASELINE.md) is the stall this costs: target
<30 s per reshard, zero restarts. The trainer times every reshard and
reports it via callback (feeding TrainingJobStatus.last_reshard_stall_s).

In-process, the device pool is the local ``jax.devices()`` list (tests:
8 virtual CPU devices). Multi-host, the same protocol runs with
``jax.distributed`` re-initialization between snapshot and rebuild —
the coordinator owns membership epochs (runtime/coordinator.py).
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import numpy as np
import optax

from edl_tpu.api.job import MeshSpec
from edl_tpu.parallel.mesh import MeshPlan
from edl_tpu.runtime import checkpoint as ckpt
from edl_tpu.train.trainer import (
    LocalSyncStepper,
    TrainState,
    global_batch,
    make_train_step,
    shard_state,
)
from edl_tpu.obs import compilewatch
from edl_tpu.obs import costmodel as _costmodel
from edl_tpu.obs import disttrace
from edl_tpu.obs import events as flight
from edl_tpu.obs import memledger
from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.utils import tracing
from edl_tpu.utils.logging import Timer, kv_logger

log = kv_logger("elastic")


def _obs_reshard(ev: "ReshardEvent") -> None:
    """Reshard telemetry (the BASELINE north-star, scrapeable): stall
    histogram + path-labeled counter — previously this lived only in
    tracing spans a human had to dump."""
    r = obs_metrics.default_registry()
    r.histogram(
        "edl_reshard_stall_seconds", "traffic-stopping reshard window"
    ).observe(ev.stall_s)
    r.counter("edl_reshard_total", "elastic reshards", ("path",)).inc(
        path="host" if ev.fallback else "device"
    )
    reused = r.counter(
        "edl_reshard_step_reused_total",
        "reshards back to a mesh the job has had, its built step reused",
    )
    if ev.step_reused:
        reused.inc()


def _device_reshard(state: TrainState, plan: MeshPlan, mesh, pspecs) -> TrainState:
    """Move a live device-resident TrainState onto a (different) mesh by
    direct ``jax.device_put`` — XLA routes shard movement device-to-device
    where device sets overlap, which is the elastic fast path. Same
    placement rule as initial placement (shard_state), plus a fence."""
    new_state = shard_state(state, plan, mesh, pspecs)
    jax.block_until_ready(new_state.params)
    return new_state


@dataclass
class ReshardEvent:
    """One elastic rescale, as observed by the runtime."""

    from_workers: int
    to_workers: int
    stall_s: float  # snapshot + remesh + reshard (the traffic-stopping window)
    # the first step on the new mesh, dispatch to loss (overlappable)
    recompile_s: float
    step: int
    # True when the direct device-to-device move failed and the reshard
    # went through host-RAM staging — the slow path whose cost scales
    # with per-host state bytes (see doc/reshard_stall.md for the bound)
    fallback: bool = False
    # recompile_s taken apart, from JAX's own compile events while the
    # first step ran (obs/compilewatch.py): Python to jaxpr, jaxpr to
    # MLIR, then the executable — compiled by XLA, or (cache_hit) found
    # in the persistent cache and loaded onto the new mesh's chips.
    # What is left of recompile_s after the three is the step running.
    trace_s: float = 0.0
    lower_s: float = 0.0
    load_s: float = 0.0
    cache_hit: bool = False
    # True when the job has had this mesh before and the step built for
    # it then was found again (ElasticTrainer._build): nothing is traced,
    # lowered or loaded, and the three durations above read 0.0
    step_reused: bool = False


@dataclass(frozen=True)
class _BuiltMesh:
    """What ``ElasticTrainer._build`` makes for one mesh: all of it a
    function of the device tuple and the plan alone. ``step_fn`` owns
    its ``jax.jit`` (and ``stepper`` its four), and with them the loaded
    executable: while the record lives, a call with the same shardings
    is found in jit's own cache."""

    plan: MeshPlan
    mesh: Any
    pspecs: Any
    step_fn: Callable
    stepper: Optional[LocalSyncStepper]


@dataclass
class TrainReport:
    steps: int = 0
    examples: int = 0
    losses: List[float] = field(default_factory=list)
    reshards: List[ReshardEvent] = field(default_factory=list)
    train_seconds: float = 0.0

    @property
    def examples_per_sec(self) -> float:
        return self.examples / self.train_seconds if self.train_seconds else 0.0


class ElasticTrainer:
    """Runs a sharded training loop that can rescale between steps.

    Parameters
    ----------
    loss_fn : ``f(params, batch) -> scalar``
    tx : optax optimizer
    mesh_spec : user parallelism plan; remaining device factor goes to dp
    chips_per_worker : devices driven by each worker (host) process
    per_chip_batch : per-device batch size — global batch scales with the
        worker count, the reference's elastic-DP throughput semantics
    param_pspecs : optional model-provided PartitionSpec tree, or a
        callable ``plan -> tree`` evaluated once per distinct mesh so TP
        layouts track the current mesh plan
    devices : device pool override (defaults to ``jax.devices()``)
    """

    def __init__(
        self,
        loss_fn: Callable,
        tx: optax.GradientTransformation,
        mesh_spec: Optional[MeshSpec] = None,
        chips_per_worker: int = 1,
        per_chip_batch: int = 32,
        param_pspecs=None,
        devices: Optional[Sequence[jax.Device]] = None,
        on_reshard: Optional[Callable[[ReshardEvent], None]] = None,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every_steps: int = 0,
        sync_every: int = 1,
        make_loss: Optional[Callable] = None,
        flops_per_example: Optional[float] = None,
        hbm_bytes_per_example: Optional[float] = None,
    ):
        self.loss_fn = loss_fn
        # mesh-aware loss factory ``(plan, mesh) -> loss_fn``, invoked
        # once per distinct mesh — required for strategies whose program
        # depends on the mesh layout (llama sp ring/Ulysses attention,
        # pp GPipe schedule), mirroring Workload.make_loss in the
        # process runtime. When given, ``loss_fn`` may be None.
        self.make_loss = make_loss
        self.tx = tx
        self.mesh_spec = mesh_spec or MeshSpec()
        self.chips_per_worker = chips_per_worker
        self.per_chip_batch = per_chip_batch
        self.param_pspecs = param_pspecs
        self._pspecs = None  # resolved per-plan in _build
        # every mesh the job has had, by (devices in order, plan): a
        # reshard back to one installs what was built for it then. At
        # most one record per feasible worker count of the pool.
        self._built: Dict[tuple, _BuiltMesh] = {}
        self.pool = list(devices) if devices is not None else list(jax.devices())
        self.on_reshard = on_reshard
        # periodic checkpointing (the reference's save_inference_model
        # cadence, example/ctr/ctr/train.py:169-180, made first-class)
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every_steps = checkpoint_every_steps
        # delayed-sync DP (local SGD): K local steps per dp group between
        # cross-group averages — the TPU analog of the reference's
        # --async_mode (example/ctr/ctr/train.py:75-79). 1 = fully sync.
        self.sync_every = max(int(sync_every), 1)
        self._stepper: Optional[LocalSyncStepper] = None

        self.n_workers = 0
        self.mesh = None
        self.plan: Optional[MeshPlan] = None
        self.state: Optional[TrainState] = None
        self._host_step = 0  # host mirror of state.step (avoids per-step syncs)
        self._step_fn = None
        self._scale_target: Optional[int] = None
        self.report = TrainReport()
        # hardware-efficiency observability (obs/costmodel.py): when
        # the workload declares its analytic cost per example, every
        # train_steps window publishes edl_mfu{phase="train"} /
        # edl_bw_util_ratio{phase="train"} from the measured
        # examples/sec — live roofline telemetry, not a bench-only
        # number. Per-DEVICE: the gauges are per-chip utilization.
        self.flops_per_example = flops_per_example
        self.hbm_bytes_per_example = hbm_bytes_per_example
        self._eff: Optional[_costmodel.EfficiencyMeter] = None
        # device memory ledger: this trainer's long-lived HBM (params
        # + optimizer moments), re-registered on every (re)placement
        # under stable keys so reshards replace rather than accumulate
        self._ledger = memledger.default_ledger()
        self._ledger_owner = f"trainer-{id(self)}"
        self._ledger_kept: Optional[Dict] = None  # the step's, once filed
        import weakref

        weakref.finalize(self, self._ledger.release_owner, self._ledger_owner)

    # -- lifecycle ---------------------------------------------------------

    @property
    def n_devices(self) -> int:
        return self.n_workers * self.chips_per_worker

    @property
    def global_batch_size(self) -> int:
        return self.per_chip_batch * self.n_devices

    def start(self, params, n_workers: int) -> None:
        """Initial mesh + state placement + step compile."""
        self._build(n_workers)
        host = TrainState.create(params, self.tx)
        self.state = shard_state(host, self.plan, self.mesh, self._pspecs)
        if self._stepper is not None:
            self.state = self._stepper.localize(self.state)
        self._ledger_register()
        self._host_step = 0
        log.info(
            "elastic trainer started",
            workers=n_workers,
            devices=self.n_devices,
            mesh=self.plan.describe(),
        )

    def resume(self, params, n_workers: int, checkpoint_path: str) -> None:
        """Start from a saved checkpoint (crash recovery / warm restart):
        ``params`` only provides the tree structure; values and the step
        counter come from disk and are sharded onto the fresh mesh."""
        self._build(n_workers)
        template = TrainState.create(params, self.tx)
        host = ckpt.load(checkpoint_path, template)
        self.state = ckpt.restore(host, self.plan, self.mesh, self._pspecs)
        if self._stepper is not None:
            self.state = self._stepper.localize(self.state)
        self._ledger_register()
        self._host_step = int(np.asarray(host.step))
        log.info(
            "elastic trainer resumed",
            workers=n_workers,
            step=int(np.asarray(host.step)),
            checkpoint=checkpoint_path,
        )

    def maybe_checkpoint(self, force: bool = False) -> Optional[str]:
        """Write ``checkpoint_dir/step-N`` when the cadence (or ``force``)
        says so; returns the path written."""
        if not self.checkpoint_dir or self.state is None:
            return None
        step = self._host_step  # host mirror: no device sync on the hot path
        if not force and (
            self.checkpoint_every_steps <= 0
            or step == 0
            or step % self.checkpoint_every_steps != 0
        ):
            return None
        path = os.path.join(self.checkpoint_dir, f"step-{step}")
        if os.path.exists(os.path.join(path, "state.npz")):
            return None  # already saved at this step
        # delayed-sync mode checkpoints the group AVERAGE (the consensus
        # model), not one group's drifted copy
        to_save = self.merged_state
        with tracing.span("checkpoint.save", step=step):
            ckpt.save(path, to_save, {"n_workers": self.n_workers})
        return path

    def _build(self, n_workers: int) -> bool:
        """Install the mesh for ``n_workers`` and its step; True when the
        job has had that mesh before and what was built then is reused."""
        n_dev = n_workers * self.chips_per_worker
        if n_dev > len(self.pool):
            raise ValueError(
                f"{n_workers} workers x {self.chips_per_worker} chips "
                f"exceed device pool ({len(self.pool)})"
            )
        devices = tuple(self.pool[:n_dev])
        plan = MeshPlan.from_spec(self.mesh_spec, n_dev)
        key = (devices, plan)
        built = self._built.get(key)
        reused = built is not None
        if not reused:
            mesh = plan.build(devices)
            pspecs = (
                self.param_pspecs(plan)
                if callable(self.param_pspecs)
                else self.param_pspecs
            )
            loss = (
                self.make_loss(plan, mesh)
                if self.make_loss is not None
                else self.loss_fn
            )
            built = self._built[key] = _BuiltMesh(
                plan,
                mesh,
                pspecs,
                make_train_step(loss, self.tx, plan, mesh, pspecs),
                LocalSyncStepper(loss, self.tx, plan, mesh)
                if self.sync_every > 1
                else None,
            )
        self.n_workers = n_workers
        self.plan, self.mesh, self._pspecs = built.plan, built.mesh, built.pspecs
        self._step_fn, self._stepper = built.step_fn, built.stepper
        return reused

    def _ledger_register(self) -> None:
        """(Re)register the live state's HBM in the memory ledger —
        params and optimizer moments, under stable per-trainer keys
        (replace semantics: reshards and restores cannot drift the
        edl_hbm_bytes gauges)."""
        if self.state is None:
            return
        self._ledger.register_tree(
            self._ledger_owner, "params", self.state.params, "params"
        )
        self._ledger.register_tree(
            self._ledger_owner, "opt", self.state.opt_state, "opt"
        )

    def _ledger_register_kept(self) -> None:
        """``edl_hbm_bytes{category="remat_kept"}``: what the installed
        step's rematerialised layers keep from forward to backward, over
        all devices like ``params`` and ``opt`` beside it. Known once
        the step's first call has built it (``make_train_step``), so
        this is asked after every dispatch and does something on the
        first of a mesh."""
        kept = getattr(self._step_fn, "kept", None)
        if not kept or kept is self._ledger_kept:
            return
        self._ledger_kept = kept
        self._ledger.register(
            self._ledger_owner, "remat_kept",
            kept["remat_kept_bytes"] * self.n_devices, "remat_kept",
        )

    @property
    def merged_state(self) -> Optional[TrainState]:
        """The consensus TrainState: in delayed-sync mode, the group
        average; otherwise the live state itself. Use for eval/export."""
        if self.state is not None and self._stepper is not None:
            return self._stepper.merge(self.state)
        return self.state

    # -- elastic surface ---------------------------------------------------

    def request_rescale(self, n_workers: int) -> None:
        """Signal from the control plane (autoscaler retarget); honored
        at the next step boundary — training never tears down."""
        if n_workers != self.n_workers:
            self._scale_target = n_workers

    def apply_chip_grant(self, total_chips: int) -> int:
        """Consume a chip-lease budget from the elasticity broker
        (edl_tpu/elasticity): retarget to as many whole workers as
        ``total_chips`` covers, floored at one worker — the trainer's
        end of the shared broker-grant interface. Returns the worker
        count requested."""
        if total_chips < 0:
            raise ValueError(f"total_chips must be >= 0, got {total_chips}")
        n_workers = max(1, total_chips // self.chips_per_worker)
        self.request_rescale(n_workers)
        return n_workers

    def _feasible(self, n_workers: int) -> bool:
        n_dev = n_workers * self.chips_per_worker
        if n_workers < 1 or n_dev > len(self.pool):
            return False
        try:
            MeshPlan.from_spec(self.mesh_spec, n_dev)
        except ValueError:
            return False
        return True

    def _resolve_target(self, target: int) -> Optional[int]:
        """Largest feasible worker count ≤ target (a retarget must never
        crash the loop — an infeasible count degrades to the nearest
        mesh-divisible one below it, or is ignored)."""
        for n in range(min(target, len(self.pool) // max(self.chips_per_worker, 1)), 0, -1):
            if self._feasible(n):
                return n
        return None

    def _maybe_rescale(self) -> None:
        target = self._scale_target
        if target is None:
            return
        self._scale_target = None
        target = self._resolve_target(target)
        if target is None or target == self.n_workers:
            if target is None:
                log.warn("ignoring infeasible rescale target")
            return
        prev = self.n_workers
        step_at = self._host_step
        # reshard_epoch: this trainer's reshard ordinal — the flight-
        # recorder correlation key tying begin/end/recompile together.
        # The whole rescale runs under a DERIVED trace root
        # ("reshard", ep): every reshard-phase span and event shares
        # trace id disttrace.derived_trace_id("reshard", ep), which is
        # how `edl trace --reshard-epoch N` selects the chain without
        # any id exchange.
        ep = len(self.report.reshards)
        log.info("reshard begin", from_workers=prev, to_workers=target)
        with disttrace.root("reshard", ep):
            self._rescale_traced(target, prev, step_at, ep)

    def _rescale_traced(self, target, prev, step_at, ep) -> None:
        used_fallback = False
        flight.emit("reshard.begin", reshard_epoch=ep, step=step_at,
                    from_workers=prev, to_workers=target)
        with Timer() as stall, tracing.span(
            "reshard", from_workers=prev, to_workers=target, step=step_at,
            reshard_epoch=ep,
        ):
            # delayed-sync groups are collapsed to their average before
            # the move: the new dp width means a new group count, and the
            # merge is the same one all-reduce a sync boundary costs
            old_state = self.merged_state
            with tracing.span("reshard.build_mesh", to_workers=target) as attrs:
                # new mesh over new device set, or one the job has had
                attrs["step_reused"] = reused = self._build(target)
            try:
                # fast path: direct device-to-device reshard (rides ICI on
                # real hardware; surviving shards move, no host round trip)
                with tracing.span("reshard.device_transfer"):
                    self.state = _device_reshard(
                        old_state, self.plan, self.mesh, self._pspecs
                    )
            except (ValueError, TypeError, RuntimeError) as e:
                # transfer-layer failures fall back to host-RAM staging;
                # deterministic spec bugs will fail again here and surface
                used_fallback = True
                log.warn("device reshard failed; staging via host", error=str(e))
                with tracing.span("reshard.host_staging"):
                    # overlapped down/up pipeline: ~max(d2h, h2d), not sum
                    self.state = ckpt.staged_reshard(
                        old_state, self.plan, self.mesh, self._pspecs
                    )
            if self._stepper is not None:
                self.state = self._stepper.localize(self.state)
            del old_state
            # stable keys: the re-placed state REPLACES the ledger
            # entries — N reshards leave exactly one state's bytes
            self._ledger_register()
        ev = ReshardEvent(
            from_workers=prev,
            to_workers=target,
            stall_s=stall.elapsed,
            recompile_s=0.0,  # filled after the first step on the new mesh
            step=step_at,
            fallback=used_fallback,
            step_reused=reused,
        )
        self.report.reshards.append(ev)
        _obs_reshard(ev)
        flight.emit(
            "reshard.end", reshard_epoch=ep, step=step_at,
            from_workers=prev, to_workers=target,
            stall_s=round(stall.elapsed, 6),
            path="host" if used_fallback else "device",
        )
        log.info(
            "reshard done",
            from_workers=prev,
            to_workers=target,
            stall_s=round(stall.elapsed, 4),
            fallback=used_fallback,
        )
        if self.on_reshard:
            self.on_reshard(ev)

    # -- training loop -----------------------------------------------------

    def train_steps(self, data_fn: Callable[[int], Any], n_steps: int) -> TrainReport:
        """Run ``n_steps`` updates; ``data_fn(global_batch_size)`` yields a
        host batch each step (task-queue readers plug in here).

        Every step records its wall time and the data-wait share into
        the process registry (edl_train_step_seconds /
        edl_train_data_wait_seconds); the end-of-call materialization
        is the host-block share. Pure host bookkeeping — nothing is
        synced that the loop didn't already sync."""
        reg = obs_metrics.default_registry()
        h_step = reg.histogram(
            "edl_train_step_seconds",
            "full step wall time (data + dispatch + sync)",
        )
        h_data = reg.histogram(
            "edl_train_data_wait_seconds",
            "host wait for the next batch (data stall)",
        )
        h_block = reg.histogram(
            "edl_train_host_block_seconds",
            "host blocked on device results (sync stall)",
        )
        c_examples = reg.counter(
            "edl_train_examples_total", "training rows consumed"
        )
        t0 = time.perf_counter()
        raw_losses = []  # device arrays; materialized once after the loop
        try:
            self._train_steps_inner(
                data_fn, n_steps, h_step, h_data, c_examples, raw_losses
            )
        except Exception as e:
            # the trainer's black-box escape hatch: record the failure
            # and dump the flight ring (EDL_BLACKBOX_DIR) BEFORE
            # re-raising, so the crash is explainable post-hoc
            flight.emit(
                "trainer.crash", severity="error", step=self._host_step,
                error=f"{type(e).__name__}: {e}",
            )
            flight.crash_dump("trainer", e)
            raise
        tb = time.perf_counter()
        with tracing.span("train.host_block"):
            jax.block_until_ready(self.state.params)
        h_block.observe(time.perf_counter() - tb)
        self.report.train_seconds += time.perf_counter() - t0
        self.report.losses.extend(float(x) for x in raw_losses)
        if raw_losses:
            reg.gauge("edl_train_loss", "most recent training loss").set(
                float(raw_losses[-1])
            )
        if self.report.train_seconds > 0:
            reg.gauge(
                "edl_train_examples_per_sec",
                "training throughput over the last report window",
            ).set(self.report.examples_per_sec)
        if self.flops_per_example and self.report.train_seconds > 0:
            # live roofline: measured examples/s × the workload's
            # analytic cost, per chip — the scrapeable twin of the
            # bench's MFU figure (obs/costmodel.py owns the formulas)
            # re-resolved per window (get-or-create is dict hits) so a
            # test's registry swap takes effect, like _record_dispatch
            self._eff = _costmodel.EfficiencyMeter(registry=reg)
            eps_per_dev = self.report.examples_per_sec / max(
                self.n_devices, 1
            )
            self._eff.set_rates(
                "train",
                eps_per_dev * self.flops_per_example,
                eps_per_dev * (self.hbm_bytes_per_example or 0.0),
            )
        return self.report

    def _train_steps_inner(
        self, data_fn, n_steps, h_step, h_data, c_examples, raw_losses
    ) -> None:
        for _ in range(n_steps):
            self._maybe_rescale()
            ts = time.perf_counter()
            with tracing.step_span("train.step", self._host_step):
                metrics = self._one_step(data_fn, ts, h_data)
            self.report.steps += 1
            self._host_step += 1
            self.report.examples += self.global_batch_size
            c_examples.inc(self.global_batch_size)
            raw_losses.append(metrics["loss"])
            self.maybe_checkpoint()
            h_step.observe(time.perf_counter() - ts)

    def _one_step(self, data_fn, ts: float, h_data):
        """Batch, dispatch and, on the first step of a mesh, the wait
        for its loss with the compile events of that step counted."""
        with tracing.span("train.data"):
            batch = data_fn(self.global_batch_size)
            dev_batch = global_batch(batch, self.plan, self.mesh)
        ev = self.report.reshards[-1] if self.report.reshards else None
        first_on_mesh = ev is not None and ev.recompile_s == 0.0
        tc = time.perf_counter()
        h_data.observe(tc - ts)
        with compilewatch.Window() as built:
            # on the first step of a mesh the job has not had, this is
            # where the program is traced, lowered and loaded
            with tracing.span("train.dispatch"):
                if self._stepper is not None:
                    self.state, metrics = self._stepper.step(
                        self.state, dev_batch
                    )
                    if (self._host_step + 1) % self.sync_every == 0:
                        self.state = self._stepper.sync(self.state)
                else:
                    self.state, metrics = self._step_fn(self.state, dev_batch)
            if first_on_mesh:
                jax.block_until_ready(metrics["loss"])
        self._ledger_register_kept()
        if first_on_mesh:
            ev.recompile_s = time.perf_counter() - tc
            ev.trace_s, ev.lower_s = built.trace_s, built.lower_s
            ev.load_s, ev.cache_hit = built.load_s, built.cache_hit
            tracing.tracer().record(
                "reshard.recompile", tc, ev.recompile_s,
                {"to_workers": self.n_workers, "trace_s": ev.trace_s,
                 "lower_s": ev.lower_s, "load_s": ev.load_s,
                 "cache_hit": ev.cache_hit, "step_reused": ev.step_reused,
                 # what this mesh's step keeps for its backward and the
                 # room beside it: chosen when the step was first built
                 # and filed with it, so a return reads the same
                 **getattr(self._step_fn, "kept", {})},
            )
            obs_metrics.default_registry().histogram(
                "edl_reshard_recompile_seconds",
                "first-step compile on the new mesh",
            ).observe(ev.recompile_s)
        return metrics
