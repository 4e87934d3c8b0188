"""Elastic multi-process worker program — real worker processes, zero restarts.

This is the process-level realization of the elastic protocol that
`runtime/elastic.py` implements in-process: each worker is a separate OS
process (one per TPU host in production; virtual-CPU JAX processes in
tests), peers are discovered through the job coordinator
(runtime/coordinator.py — the etcd/master analog, reference:
docker/paddle_k8s:14-32), and data comes from the coordinator's task
queue (reference: cloud_reader + master task queue,
example/fit_a_line/train_ft.py:105-114).

Lifecycle, per membership epoch ("incarnation" of the collective):

  1. rendezvous: wait until the coordinator's member list is stable,
     take the deterministic rank (reference: k8s_tools.py fetch_pod_id);
  2. the rank-0 member spawns the epoch's EXTERNAL coordination-service
     host (runtime/dist_service.py — outside the workers so leader death
     is survivable), which publishes the endpoint in coordinator KV;
     every worker connects as a pure client (world = live members);
  3. restore train state — from the in-RAM host snapshot if this worker
     survived the previous epoch, else from the job checkpoint
     (joiners), else fresh init (job start);
  4. lockstep training: every step the rank-0 worker publishes ONE
     decision — ``step`` / ``reshard`` / ``stop`` — in KV and all
     workers obey it. This is what keeps SPMD collectives aligned
     across membership change: a worker may only stop stepping after a
     published ``reshard``/``stop``, so nobody leaves a peer stranded
     inside an all-reduce. Data tasks are leased per step and acked
     after the optimizer update (lease timeout redelivers lost work —
     reference: -task-timout-dur=16s, docker/paddle_k8s:28-31).
  5. on ``reshard``: snapshot state to host RAM, write the job
     checkpoint (lowest-rank live worker), ``jax.distributed.shutdown``,
     clear XLA backends, and loop back to (1) — the process itself
     never restarts, which is the BASELINE north star ("zero job
     restarts", <30 s stall).

Scale-up: the controller just starts another worker process; its
registration bumps the membership epoch, rank 0 notices and publishes
``reshard``. Scale-down: the controller sends SIGTERM; the worker sets
a leaving flag but KEEPS stepping until rank 0 publishes ``reshard``
(graceful drain), then deregisters and exits 0. Crash: lease timeout +
member TTL expiry bump the epoch; survivors recover from the last
completed step (the train step does not donate its inputs, so state is
still live after a failed collective).

Env contract (EDL_*, reference: pkg/jobparser.go:263-311 PADDLE_INIT_*):
see ``WorkerConfig.from_env``.
"""

from __future__ import annotations

import os
import signal
import sys
import threading
import time
import dataclasses
from typing import Any, Callable, Dict, Optional

import numpy as np

from edl_tpu.obs import disttrace
from edl_tpu.runtime.coordinator import CoordinatorClient
from edl_tpu.runtime import entrypoint
from edl_tpu.utils import tracing
from edl_tpu.utils.logging import kv_logger

log = kv_logger("worker")

_POLL_S = 0.02


def _emit_worker_event(kind: str, worker: str, severity: str = "info", **attrs):
    """Flight-recorder emit keyed by worker (join/leave/heartbeat —
    the membership decisions a fleet postmortem reconstructs).
    Telemetry must never take the worker down."""
    try:
        from edl_tpu.obs import events

        events.emit(kind, severity, worker=worker, **attrs)
    # edl: no-lint[silent-failure] the event-emit wrapper itself: telemetry must never take the worker down, and logging from here could recurse into the sink
    except Exception:  # pragma: no cover - defensive
        pass


# --------------------------------------------------------------------------
# config: runtime/worker_config.py (re-exported: the EDL_* env contract)

from edl_tpu.runtime.worker_config import WorkerConfig  # noqa: E402



# --------------------------------------------------------------------------
# model registry: runtime/workloads.py (re-exported for existing
# consumers of the env contract)

from edl_tpu.runtime.workloads import WORKLOADS, Workload  # noqa: E402



# --------------------------------------------------------------------------
# platform / jax.distributed plumbing


def _setup_platform(cfg: WorkerConfig) -> None:
    """Platform/env setup only — must NOT query devices: the XLA backend
    may only initialize after jax.distributed.initialize."""
    import jax

    if cfg.local_devices > 0:
        from edl_tpu.utils.platform import prepare_virtual_cpu

        prepare_virtual_cpu(cfg.local_devices)
        # cross-process CPU collectives need gloo
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    else:
        # the real backend: its compiles are the expensive ones. (The
        # virtual-CPU platform above is tests and examples — toy
        # compiles, and XLA:CPU's cache loader warns on every hit.)
        from edl_tpu.utils import jaxcache

        jaxcache.configure()


def _initialize_distributed(
    addr: str, world: int, rank: int, timeout_s: int = 60
) -> None:
    """Client-only jax.distributed bring-up against an EXTERNAL
    coordination service (runtime/dist_service.py). Stock
    ``jax.distributed.initialize`` would make rank 0 host the service
    in-process, turning rank-0 death into an unrecoverable loss of the
    rendezvous plane. ``recoverable=True`` keeps a peer's death from
    being broadcast as a fatal job error to the survivors."""
    from jax._src import distributed as _dist
    from jax._src.lib import _jax

    state = _dist.global_state
    if state.client is not None:  # pragma: no cover - defensive
        raise RuntimeError("distributed state already initialized")
    state.client = _jax.get_distributed_runtime_client(
        addr,
        rank,
        init_timeout=timeout_s,
        heartbeat_timeout=10,
        shutdown_timeout=10,
        use_compression=True,
        recoverable=True,
    )
    state.client.connect()
    state.process_id = rank
    state.num_processes = world
    state.coordinator_address = addr


def _reset_distributed_state() -> None:
    """Drop jax.distributed's global state without a disconnect RPC, so
    a later initialize() starts clean (and jax's atexit shutdown
    becomes a no-op)."""
    from jax._src import distributed as _dist

    if _dist.global_state.client is not None:
        _dist.global_state.client = None
        _dist.global_state.service = None
        _dist.global_state.process_id = 0
        _dist.global_state.num_processes = 0


def _shutdown_distributed() -> None:
    """Tear down jax.distributed, tolerating a dead coordinator (the
    rank-0 peer may be the one that crashed)."""
    import jax

    done = threading.Event()

    def _go():
        try:
            jax.distributed.shutdown()
        except Exception as e:  # pragma: no cover - error-path logging
            log.warn("distributed shutdown error", error=str(e))
        finally:
            done.set()

    t = threading.Thread(target=_go, daemon=True)
    t.start()
    if not done.wait(timeout=15):  # pragma: no cover
        log.warn("distributed shutdown timed out; forcing state reset")
    _reset_distributed_state()


def _clear_backends() -> None:
    import jax.extend.backend

    jax.clear_caches()
    jax.extend.backend.clear_backends()


# --------------------------------------------------------------------------
# the worker


class ElasticWorker:
    def __init__(self, cfg: WorkerConfig):
        self.cfg = cfg
        self.client = CoordinatorClient(cfg.coord_host, cfg.coord_port, 30.0)
        self._leaving = False
        # last snapshot of THIS process's addressable shards (the RAM
        # half of the reshard protocol; disk holds the committed union)
        self._ram_snapshot = None  # checkpoint.LocalSnapshot
        self._pending_commit: Optional[threading.Thread] = None
        self._last_local: Optional[Dict[str, np.ndarray]] = None
        self._resharded = 0
        self._local_rows = 0  # batch rows this process feeds per step
        self._model_meta = None  # architecture record for exports
        # epoch-scoped KV (go/dist/disc keys) retired by past epochs,
        # GC'd one epoch later — keeps the coordinator KV (and its WAL
        # snapshots) O(live state), not O(job epochs). The two-phase
        # deferral semantics (and which keys MUST take the late lane)
        # live in runtime/epoch_gc.py.
        from edl_tpu.runtime.epoch_gc import EpochKeyGC
        from edl_tpu.runtime.eval_hook import ExportEvaluator
        from edl_tpu.runtime.p2p_restore import P2PRestorePlane

        self._gc = EpochKeyGC()
        # p2p shard plane brokering (server lifecycle, roster, restore
        # decision, veto, drain-window linger): runtime/p2p_restore.py
        self._p2p = P2PRestorePlane(
            cfg, self._k, self._gc, lambda: self._ram_snapshot
        )
        # commit-leader held-out eval: runtime/eval_hook.py
        self._eval = ExportEvaluator(cfg, self._k)
        self._incarnation = 0  # set at bootstrap; bumped to force regroup
        self._restore_failures = 0
        self._exporter = None  # obs.MetricsExporter when EDL_METRICS_PORT set
        self._pusher = None  # obs.MetricsPusher when metrics_push_s > 0
        self._hb_degraded = False  # heartbeat loop cut off from coordinator

    # -- keys ----------------------------------------------------------------
    def _k(self, *parts: str) -> str:
        return "/".join((self.cfg.job,) + parts)

    # -- telemetry (edl_tpu/obs) ---------------------------------------------
    def _telemetry_start(self) -> None:
        """Bring up this worker's observability surface: the process
        registry (full core catalog + tracer bridge, so reshard/
        checkpoint spans are scrapeable as histograms), the optional
        HTTP exporter, and the periodic snapshot push into coordinator
        KV that feeds the coordinator's fleet-aggregated /metrics.
        Telemetry failures degrade to warnings — never the job."""
        from edl_tpu import obs

        cfg = self.cfg
        obs.ensure_core_series()
        obs.bridge_tracer()
        # every flight-recorder event this process emits from here on
        # carries worker identity — the fleet log's correlation key
        obs.events.default_recorder().set_context(worker=cfg.worker_id)
        # clock alignment (obs/disttrace): bracket coordinator TIME
        # round trips to estimate this process's wall-clock offset
        # (NTP midpoint, min-RTT sample) and publish it so the fleet
        # merge lands every worker's spans/events on ONE axis. Refresh
        # rides the metrics-push cadence below, throttled.
        self._clock = obs.disttrace.ClockSync()
        clock_kv = obs.clock_key(cfg.job, cfg.worker_id)

        def _clock_publish():
            try:
                est = self._clock.maybe_sample(self.client.time)
                if est is not None:
                    self.client.kv_put(clock_kv, est.to_json())
            except Exception as e:  # telemetry must never take the job
                log.warn("clock sync failed", error=str(e))

        _clock_publish()
        # EDL_TSDB_DIR: one shared on-disk history (obs/tsdb.py) — the
        # pusher appends snapshots into it on its cadence, the exporter
        # serves it on /history, `edl watch DIR` replays it offline
        tsdb = obs.TSDB(cfg.tsdb_dir) if cfg.tsdb_dir else None
        if cfg.metrics_port >= 0:
            try:
                self._exporter = obs.start_exporter(
                    port=cfg.metrics_port, history=tsdb
                )
                # advertise the bound (possibly ephemeral) port so
                # `edl top` / scrapers can discover it through KV
                self.client.kv_put(
                    self._k("metrics_addr", cfg.worker_id),
                    f"127.0.0.1:{self._exporter.port}",
                )
            except OSError as e:
                log.warn("metrics exporter failed to bind", error=str(e))
        if cfg.metrics_push_s > 0:
            key = obs.metrics_key(cfg.job, cfg.worker_id)
            ekey = obs.events_key(cfg.job, cfg.worker_id)
            tkey = obs.trace_key(cfg.job, cfg.worker_id)
            # the main client is lock-serialized per roundtrip, so the
            # pusher thread can share it (same pattern would hold for a
            # dedicated connection; sharing avoids a third socket).
            # The flight-recorder window AND the recent tracer-span
            # window ride the same cadence so the coordinator's
            # /events shows the worker-labeled fleet log and /trace
            # merges every worker onto the coordinator's clock axis.
            self._pusher = obs.MetricsPusher(
                lambda payload: self.client.kv_put(key, payload),
                interval_s=cfg.metrics_push_s,
                events_publish=lambda payload: self.client.kv_put(
                    ekey, payload
                ),
                trace_publish=lambda payload: self.client.kv_put(
                    tkey, payload
                ),
                clock_refresh=_clock_publish,
                # the same snapshot also lands in the on-disk history
                # — and arms the memledger crosscheck gauge — at zero
                # extra RPCs
                tsdb=tsdb,
            ).start()

    def _telemetry_stop(self) -> None:
        if self._pusher is not None:
            try:
                self._pusher.stop(final_push=True)
            # edl: no-lint[silent-failure] teardown best-effort; a failing final push is already counted by the pusher's failure counter
            except Exception:  # pragma: no cover - teardown best-effort
                pass
            self._pusher = None
        if self._exporter is not None:
            try:
                self._exporter.stop()
            # edl: no-lint[silent-failure] teardown best-effort exporter stop
            except Exception:  # pragma: no cover
                pass
            self._exporter = None

    # -- SIGTERM: graceful drain --------------------------------------------
    def _on_sigterm(self, signum, frame):  # pragma: no cover - signal path
        # Python delivers signals on the main thread (same thread as
        # run()), and _leaving is a monotonic bool the beat thread only
        # polls — a stale read costs one extra heartbeat, never
        # correctness
        # edl: no-lint[lockset-race]
        self._leaving = True
        try:
            # separate connection: the main client may be mid-call
            c = CoordinatorClient(self.cfg.coord_host, self.cfg.coord_port, 5.0)
            c.kv_put(self._k("leaving", self.cfg.worker_id), "1")
            c.close()
        except Exception as e:
            # an unpublished leaving-mark downgrades the graceful drain
            # to a lease-expiry eviction — loud, not silent (edl check
            # silent-failure)
            log.warn("could not publish leaving mark", error=str(e))

    # -- rendezvous ----------------------------------------------------------
    def _stable_members(self):
        """Wait until membership is stable (same epoch + members across
        two reads, no pending leavers among them), then return
        (epoch, members)."""
        cl = self.client
        deadline = time.monotonic() + self.cfg.rendezvous_timeout_s
        while True:
            if time.monotonic() > deadline:
                raise TimeoutError("rendezvous: membership never stabilized")
            cl.expire()
            e1 = cl.epoch()
            ms = cl.members()
            names = [m.name for m in ms]
            if self.cfg.worker_id not in names or not names:
                time.sleep(_POLL_S)
                continue
            if any(cl.kv_get(self._k("leaving", n)) for n in names):
                time.sleep(_POLL_S)  # leaver still deregistering
                continue
            time.sleep(0.1)
            if cl.epoch() == e1 and [m.name for m in cl.members()] == names:
                return e1, ms

    def _spawn_dist_service(self, epoch: int, world: int) -> None:
        """Launch the external coordination-service host for this epoch
        (runtime/dist_service.py). Detached: it must outlive this worker
        so that rank-0 death cannot take the rendezvous plane with it."""
        import subprocess

        log_dir = os.environ.get("EDL_LOG_DIR", "")
        if log_dir:
            out = open(
                os.path.join(log_dir, f"dist_service_e{epoch}.log"), "ab"
            )
        else:
            out = subprocess.DEVNULL
        subprocess.Popen(
            [
                sys.executable,
                "-m",
                "edl_tpu.runtime.dist_service",
                "--job", self.cfg.job,
                "--epoch", str(epoch),
                "--world", str(world),
                "--coordinator",
                f"{self.cfg.coord_host}:{self.cfg.coord_port}",
            ],
            stdout=out,
            stderr=subprocess.STDOUT if log_dir else subprocess.DEVNULL,
            start_new_session=True,
        )
        if log_dir:
            out.close()  # child holds the fd

    def _rendezvous(self):
        """Agree on (epoch, rank, world, dist endpoint) with all live
        peers. The rank-0 member spawns the epoch's external service
        host, which publishes the endpoint; everyone polls for it.
        Restarts automatically if membership shifts underfoot."""
        cl = self.client
        while True:
            epoch, members = self._stable_members()
            me = next(m for m in members if m.name == self.cfg.worker_id)
            world = len(members)
            key = self._k("dist", str(epoch))
            if me.rank == 0 and cl.kv_get(key) is None:
                self._spawn_dist_service(epoch, world)
            addr = None
            deadline = time.monotonic() + self.cfg.rendezvous_timeout_s
            while addr is None:
                addr = cl.kv_get(key)
                if addr is None:
                    if cl.epoch() != epoch:
                        break  # membership moved: restart rendezvous
                    # (an orphan service host self-dismisses after its
                    # epoch goes stale — dist_service.py --orphan-grace)
                    if time.monotonic() > deadline:
                        raise TimeoutError("rendezvous: no dist endpoint")
                    time.sleep(_POLL_S)
            if addr is None:
                continue
            return epoch, me.rank, world, addr, members

    # -- state placement -----------------------------------------------------
    def _restore_state(self, wl, tx, plan, mesh, cl=None, epoch=0, rank=0,
                       members=()):
        """P2P peer pieces (rank-0-brokered decision; newest covered
        step) > committed sharded checkpoint (+RAM pieces when the step
        matches) > RAM-only (dp/single-process, no ckpt dir) > fresh
        sharded init. All processes restore the same step: the P2P
        decision key / the manifest is the agreed truth, so survivors
        whose RAM ran ahead of the last commit (fsdp crash) roll back
        with everyone else.

        Never materializes the full state on any host: restore builds
        only local shards (make_array_from_callback), fresh init runs
        jit-sharded (VERDICT r1 weak #2/#3).
        """
        import jax

        from edl_tpu.parallel import sharding as shd
        from edl_tpu.runtime import checkpoint as ckpt
        from edl_tpu.train.trainer import TrainState, state_pspecs

        pspecs = wl.pspecs(plan) if wl.pspecs is not None else None
        like = jax.eval_shape(lambda: TrainState.create(wl.init_params(), tx))
        state_sh = shd.named(state_pspecs(like, plan, pspecs), mesh)
        manifest = (
            ckpt.latest_manifest(self.cfg.ckpt_dir) if self.cfg.ckpt_dir else None
        )
        if self.cfg.p2p and cl is not None:
            state = self._p2p.restore(
                cl, epoch, rank, members, like, state_sh, manifest,
                self._ram_snapshot,
            )
            if state is not None:
                return state, pspecs
        if manifest is not None:
            state = ckpt.load_sharded(
                self.cfg.ckpt_dir,
                like,
                state_sh,
                ram=self._ram_snapshot,
                manifest=manifest,
            )
            log.info("restored", step=int(manifest["step"]))
        elif (
            self._ram_snapshot is not None and self._ram_snapshot.is_complete()
        ):
            state = ckpt.restore_local(like, state_sh, self._ram_snapshot)
        else:
            # job start — or an fsdp crash before ANY commit existed
            # (nothing restorable: the dead peer's shards are gone and
            # no manifest was written); restart the job's math from
            # step 0 rather than killing every survivor
            if self._ram_snapshot is not None:
                log.warn(
                    "no committed checkpoint and local snapshot is "
                    "partial; reinitializing from step 0"
                )
            state = jax.jit(
                lambda: TrainState.create(wl.init_params(), tx),
                out_shardings=state_sh,
            )()
        return state, pspecs


    def _join_pending_commit(self) -> None:
        """At most ONE background commit is in flight; the next commit,
        a crash rescue, or an epoch teardown serializes behind it."""
        t = self._pending_commit
        if t is None:
            return
        t.join(self.cfg.ckpt_commit_timeout_s + 30)
        if t.is_alive():  # pragma: no cover - hung storage
            log.error("background checkpoint commit did not finish in time")
        self._pending_commit = None

    def _coordinated_checkpoint(
        self, cl, epoch, state, rank, members, background=False
    ):
        """Commit the state as a sharded checkpoint: every member writes
        its primary shards, the leader (lowest live rank) awaits all
        marks and commits manifest.json last. A member dying mid-write
        aborts the commit (its primary shards are unrecoverable), and
        the previous committed step remains the restore point.

        ``background=True`` (the periodic "ckpt" verb): the host-RAM
        snapshot is taken synchronously — the device state mutates next
        step — but the disk write, mark posting, and the leader's
        mark-wait + manifest commit run on a writer thread with its own
        coordinator connection, so multi-GB shard writes overlap
        training instead of stalling it. Stop/reshard commits stay
        synchronous: teardown must not outrun the manifest."""
        from edl_tpu.runtime import checkpoint as ckpt

        cfg = self.cfg
        self._join_pending_commit()
        snap = ckpt.snapshot_local(state)
        self._ram_snapshot = snap
        if not cfg.ckpt_dir:
            return
        # A reshard/stop at the same step a background "ckpt" commit
        # just finished would re-commit an identical state — and the
        # finished commit's mark-cleanup can race the re-commit's fresh
        # marks (same (epoch, step, worker) keys), stranding the leader
        # in its mark wait. The leader's view of ckpt_step is
        # authoritative here: it joined the very thread that wrote it.
        if int(cl.kv_get(self._k("ckpt_step")) or "-1") >= snap.step:
            return
        world = len(members)

        def _write(client, own_client: bool) -> None:
            try:
                alive = {m.name for m in client.members()}
                leader = min(
                    (m.rank for m in members if m.name in alive), default=rank
                )
                own = os.path.join(
                    ckpt.step_dir(cfg.ckpt_dir, snap.step),
                    ckpt.shard_filename(rank, world),
                )
                if rank != leader and os.path.exists(own):
                    # a background commit of this exact step already
                    # wrote this rank's shards (atomic rename => the
                    # file is complete) but its manifest aborted; a
                    # non-leader's stale read of ckpt_step cannot see
                    # that — reuse the file, only re-post the mark
                    fname = os.path.basename(own)
                else:
                    fname = ckpt.save_shards(
                        cfg.ckpt_dir, snap, rank, world,
                        host_leaves=(rank == leader),
                    )
                mark = lambda n: self._k(  # noqa: E731
                    "ckmark", str(epoch), str(snap.step), n
                )
                client.kv_put(mark(cfg.worker_id), fname)
                if rank != leader:
                    # leak guard (ADVICE r2): the leader skips a commit
                    # when ITS ckpt_step read shows the step already
                    # committed — and since the skip is decided on this
                    # same shared KV, one fresh read here sees it too.
                    # In that case nobody will collect this mark:
                    # reclaim it now. The healthy path (leader waiting
                    # on marks) stays fire-and-forget.
                    if (
                        int(client.kv_get(self._k("ckpt_step")) or "-1")
                        >= snap.step
                    ):
                        client.kv_del(mark(cfg.worker_id))
                    return
                # scale the commit deadline with shard size is the
                # caller's job (EDL_CKPT_COMMIT_TIMEOUT_S); the default
                # must accommodate multi-GB writes to shared storage
                deadline = time.monotonic() + cfg.ckpt_commit_timeout_s
                files = None
                while time.monotonic() < deadline:
                    client.expire()
                    alive = {m.name for m in client.members()}
                    got, waiting, dead_unwritten = [], [], []
                    for m in members:
                        v = client.kv_get(mark(m.name))
                        if v:
                            got.append(v)
                        elif m.name in alive:
                            waiting.append(m.name)
                        else:
                            dead_unwritten.append(m.name)
                    if not waiting:
                        files = got if not dead_unwritten else None
                        break
                    time.sleep(_POLL_S)
                for m in members:  # marks served their purpose either way
                    client.kv_del(mark(m.name))
                if files:
                    ckpt.write_manifest(
                        cfg.ckpt_dir, snap, files, {"job": cfg.job}
                    )
                    # monotonic max-write: a commit thread that stalled
                    # past its join timeout must not regress the
                    # pointer a LATER commit already advanced
                    cur = int(client.kv_get(self._k("ckpt_step")) or "-1")
                    if snap.step > cur:
                        client.kv_put(self._k("ckpt_step"), str(snap.step))
                    ckpt.gc_step_dirs(cfg.ckpt_dir, keep=2)
                    if cfg.export_dir:
                        # servable params-only artifact on every commit
                        # (the save_inference_model cadence, reference
                        # example/ctr/ctr/train.py:169-180) — assembled
                        # from the shards just committed, so it works
                        # for fsdp states no single process holds
                        try:
                            from edl_tpu.runtime import export as exp

                            d = exp.export_from_checkpoint(
                                cfg.ckpt_dir,
                                cfg.export_dir,
                                dtype=cfg.export_dtype,
                                ram=snap,  # skip re-reading own shards
                                model_meta=self._model_meta,
                            )
                            if d:
                                log.info(
                                    "export published",
                                    dir=d,
                                    step=snap.step,
                                )
                                self._eval.evaluate(client, snap.step)
                        except Exception as e:  # pragma: no cover
                            log.error("export failed", error=str(e))
                else:  # pragma: no cover - crash-timing path
                    # surfaced as a counter so monitors can alarm on
                    # repeated aborts (a job silently training without
                    # restore points)
                    aborts = int(
                        client.kv_get(self._k("ckpt_aborts")) or "0"
                    ) + 1
                    client.kv_put(self._k("ckpt_aborts"), str(aborts))
                    log.error(
                        "checkpoint commit aborted "
                        "(peer died or write timed out)",
                        step=snap.step,
                        aborts=aborts,
                    )
            except Exception as e:  # pragma: no cover - storage faults
                log.error("checkpoint commit failed", error=str(e))
                try:
                    aborts = int(
                        client.kv_get(self._k("ckpt_aborts")) or "0"
                    ) + 1
                    client.kv_put(self._k("ckpt_aborts"), str(aborts))
                # edl: no-lint[silent-failure] abort-counter publish is best-effort; the commit failure itself was log.error'd just above
                except Exception:
                    pass
                if not own_client:
                    # synchronous (stop/reshard) commits must not be
                    # silently lost: the job would report success with
                    # a stale restore point
                    raise
            finally:
                if own_client:
                    try:
                        client.close()
                    # edl: no-lint[silent-failure] closing a one-shot client at teardown
                    except Exception:
                        pass

        if not background:
            _write(cl, own_client=False)
            return

        def _bg():
            try:
                client = CoordinatorClient(
                    cfg.coord_host, cfg.coord_port, 10.0
                )
            except Exception as e:  # pragma: no cover - coord hiccup
                log.error(
                    "background commit could not reach coordinator",
                    error=str(e),
                )
                return
            _write(client, own_client=True)

        t = threading.Thread(
            target=_bg, name="edl-ckpt-commit", daemon=True
        )
        t.start()
        self._pending_commit = t

    def _crash_checkpoint(self, cl, snap, rank, world) -> None:
        """After a failed collective any survivor may be the only one
        left. A survivor holding the COMPLETE state (dp-replicated)
        persists it solo if newer than the last commit (atomic manifest
        rename; content identical among lockstep peers, so racing
        writers are harmless). FSDP survivors cannot — the dead peer's
        primary shards died with it — so the job rolls back to the last
        committed step (cadence: cfg.ckpt_every)."""
        from edl_tpu.runtime import checkpoint as ckpt

        if not self.cfg.ckpt_dir:
            return
        self._join_pending_commit()  # serialize behind an in-flight commit
        known = int(cl.kv_get(self._k("ckpt_step")) or "-1")
        if snap.step <= known or not snap.is_complete():
            return
        fname = ckpt.save_shards(
            self.cfg.ckpt_dir, snap, rank, world,
            host_leaves=True, all_pieces=True,
        )
        ckpt.write_manifest(self.cfg.ckpt_dir, snap, [fname], {"job": self.cfg.job})
        cl.kv_put(self._k("ckpt_step"), str(snap.step))

    # -- the run -------------------------------------------------------------
    def run(self) -> int:
        cfg = self.cfg
        _setup_platform(cfg)
        import jax

        import optax

        from edl_tpu.parallel.mesh import MeshPlan

        wl = WORKLOADS[cfg.model](cfg)
        self._model_meta = wl.model_meta
        self._eval.eval_fn = wl.eval_fn
        # workload-declared analytic cost: lets the step loop publish
        # the live roofline gauge edl_mfu{phase="train"} (obs/costmodel)
        self._flops_per_example = wl.flops_per_example
        if cfg.eval_dir and wl.eval_fn is None:
            # surface the misconfiguration once: otherwise EDL_EVAL_DIR
            # on a workload without an eval hook is a silent no-op
            log.warn(
                "EDL_EVAL_DIR set but workload defines no eval_fn; "
                "no eval_metric will be published",
                model=cfg.model,
            )
        if cfg.data_dir:
            # real on-disk data: leased [start, end) ranges read shard
            # files instead of the workload's synthetic generator
            from edl_tpu.runtime.shards import FileShardSource

            source = FileShardSource(cfg.data_dir)
            wl = dataclasses.replace(wl, batch_fn=source.fetch_range)
            cfg.n_samples = source.n_samples
            log.info(
                "dataset attached", dir=cfg.data_dir, n_samples=cfg.n_samples
            )
        tx = optax.adam(1e-2 if cfg.model == "linreg" else 1e-3)

        if self._leaving:  # SIGTERM during startup: never joined
            return 0
        if cfg.slice_id >= 0:
            # published BEFORE registration so any peer that sees us in
            # membership can already read our slice id at rendezvous
            self.client.kv_put(
                self._k("slice", cfg.worker_id), str(cfg.slice_id)
            )
        # serve our host-RAM snapshot to peers (P2P reshard data plane);
        # published before registration like the slice id. Server
        # lifecycle, token, roster, and restore brokering:
        # runtime/p2p_restore.py.
        self._p2p.start(self.client)
        ctx = entrypoint.bootstrap(self.client)
        self._incarnation = ctx.incarnation
        heartbeat_stop = self._start_heartbeat(ctx.incarnation)
        self._telemetry_start()
        try:
            return self._epochs(cfg, jax, MeshPlan, wl, tx)
        except Exception as e:
            entrypoint.record_failure(self.client, cfg.job, f"exception: {e}")
            raise
        finally:
            heartbeat_stop.set()
            self._telemetry_stop()

    def _start_heartbeat(self, incarnation: int) -> threading.Event:
        """TTL keep-alive on its own connection (steps may outlast the
        member TTL). Survives transient coordinator hiccups by
        reconnecting, and re-registers if a missed TTL already evicted
        us — the re-registration bumps the epoch, which correctly shows
        up to the group as a membership change."""
        stop = threading.Event()
        interval = min(0.5, max(0.1, self.cfg.member_ttl_s / 4))

        def _beat():  # pragma: no cover - timing-dependent
            c = None
            while not stop.wait(interval):
                c = self._beat_tick(c, incarnation)
            if c is not None:
                try:
                    c.close()
                # edl: no-lint[silent-failure] closing the beat client at thread exit
                except Exception:
                    pass

        threading.Thread(target=_beat, daemon=True).start()
        return stop

    def _beat_tick(self, c, incarnation: int):
        """One heartbeat attempt; returns the (re)usable client or None
        after a failure. NEVER raises — a ConnectionError here (the
        client's reconnect window exhausted during a long coordinator
        outage) used to kill the beat thread, leaving the worker running
        but silently TTL-expiring out of membership. Instead the worker
        flips a degraded flag + gauge (``edl_worker_heartbeat_degraded``,
        scrapeable so the fleet view shows WHO is beating blind) and
        keeps retrying every tick until it departs; the first successful
        beat clears the flag (and re-registers if the TTL already
        evicted us)."""
        from edl_tpu.obs import metrics as obs_metrics

        cfg = self.cfg
        gauge = obs_metrics.default_registry().gauge(
            "edl_worker_heartbeat_degraded",
            "1 while the heartbeat loop cannot reach the coordinator",
        )
        try:
            if c is None:
                c = CoordinatorClient(cfg.coord_host, cfg.coord_port, 5.0)
            if not c.heartbeat(cfg.worker_id) and not self._leaving:
                log.warn("TTL-evicted while alive; re-registering")
                _emit_worker_event(
                    "worker.re_register", cfg.worker_id, severity="warn",
                )
                c.register(cfg.worker_id, incarnation)
            if self._hb_degraded:
                self._hb_degraded = False
                gauge.set(0)
                log.info("heartbeat recovered")
                _emit_worker_event(
                    "worker.heartbeat_recovered", cfg.worker_id
                )
            return c
        except Exception as e:
            if not self._hb_degraded:
                self._hb_degraded = True
                gauge.set(1)
                log.warn(
                    "heartbeat degraded; retrying until departure",
                    error=f"{type(e).__name__}: {e}",
                )
                _emit_worker_event(
                    "worker.heartbeat_degraded", cfg.worker_id,
                    severity="warn", error=f"{type(e).__name__}: {e}",
                )
            try:
                if c is not None:
                    c.close()
            # edl: no-lint[silent-failure] discarding the broken beat connection; the degraded heartbeat was already emitted above
            except Exception:
                pass
            return None

    def _epochs(self, cfg, jax, MeshPlan, wl, tx) -> int:
        from edl_tpu.train.trainer import make_train_step

        cl = self.client
        init_failures = 0
        while True:
            if self._leaving:
                return self._depart(code=0)
            epoch, rank, world, addr, members = self._rendezvous()
            log.info(
                "epoch up", epoch=epoch, rank=rank, world=world, dist=addr
            )
            _emit_worker_event(
                "worker.join", self.cfg.worker_id,
                epoch=epoch, rank=rank, world=world,
            )
            try:
                _initialize_distributed(addr, world, rank)
                init_failures = 0
            except Exception as e:
                # a peer died between rendezvous and connect (its TTL
                # expiry will bump the epoch) — or the service host
                # itself died with membership unchanged. Retract the
                # endpoint we failed against (guarded: only if still
                # current) so the next rendezvous respawns a fresh host
                # instead of spinning on the corpse.
                log.warn("distributed init failed; regrouping", error=str(e))
                _shutdown_distributed()
                if cl.kv_get(self._k("dist", str(epoch))) == addr:
                    cl.kv_del(self._k("dist", str(epoch)))
                    cl.kv_put(self._dist_done_key(epoch, addr), "1")
                    # a live host deletes its own mark; sweep up after a
                    # dead one so failed inits don't leak KV forever
                    self._gc.defer_late(self._dist_done_key(epoch, addr))
                init_failures += 1
                if init_failures >= 5:
                    raise RuntimeError(
                        f"distributed init failed {init_failures}x; giving up"
                    ) from e
                continue
            # jax.distributed installs a C++ SIGTERM preemption notifier
            # that would swallow our graceful-drain handler — take it back
            signal.signal(signal.SIGTERM, self._on_sigterm)
            devs = jax.devices()
            plan = MeshPlan.parse(cfg.mesh, len(devs))
            slices = self._device_slices(cl, members, devs)
            mesh = plan.build(devs, slices=slices)
            if rank == 0:
                # observability: the CURRENT epoch's mesh device order
                # by slice (slice-major by construction when multi —
                # inner axes intact, or build would have raised).
                # Re-published every epoch so a reshard back to one
                # slice doesn't leave a defunct layout advertised.
                # Consumed by tests/monitor.
                if slices is not None:
                    sl_of = {id(d): s for d, s in zip(devs, slices)}
                    val = ",".join(
                        str(sl_of[id(d)]) for d in mesh.devices.flatten()
                    )
                else:
                    val = ""  # slice-blind epoch
                cl.kv_put(self._k("mesh_slices"), val)
                # which backend this epoch's mesh really is — what a
                # launcher that stays off JAX (chip_smoke.py) reads to
                # know the workers trained on the chip
                cl.kv_put(
                    self._k("devices"),
                    f"{devs[0].platform}|{devs[0].device_kind}|{len(devs)}",
                )
            rows = cfg.per_device_batch * plan.batch_shards()
            if rows % world:
                raise ValueError(
                    f"batch rows {rows} (per_device_batch×batch_shards) do "
                    f"not divide across {world} processes — align tp/pp "
                    f"axes with chips per worker"
                )
            self._local_rows = rows // world
            try:
                state, pspecs = self._restore_state(
                    wl, tx, plan, mesh, cl=cl, epoch=epoch, rank=rank,
                    members=members,
                )
            except Exception as e:
                # a P2P source died between decision and fetch (or the
                # decision timed out). Peers who DID restore may already
                # be in the step loop with a world-size program that
                # includes us — quietly retrying would strand them in a
                # collective. Bump our incarnation: the epoch change
                # sends everyone back through reshard (their fresh
                # snapshots re-seed the next decision), and we regroup.
                restore_failures = getattr(self, "_restore_failures", 0) + 1
                self._restore_failures = restore_failures
                log.warn(
                    "state restore failed; regrouping",
                    error=str(e), failures=restore_failures,
                )
                _shutdown_distributed()
                _clear_backends()
                if restore_failures >= 3:
                    raise
                if cl.epoch() == epoch:
                    # membership hasn't moved on its own (e.g. a peer's
                    # server vanished without its TTL expiring yet):
                    # force the bump so nobody strands in a collective.
                    # The incarnation KV is the monotonic owner
                    # (entrypoint.bootstrap): write through it so a
                    # later process restart cannot reuse this value and
                    # silently fail to bump the epoch.
                    inc_key = self._k("incarnation", self.cfg.worker_id)
                    self._incarnation = (
                        max(self._incarnation, int(cl.kv_get(inc_key) or "0"))
                        + 1
                    )
                    cl.kv_put(inc_key, str(self._incarnation))
                    cl.register(self.cfg.worker_id, self._incarnation)
                continue
            self._restore_failures = 0
            # confirm the restore to any lingering leavers (they serve
            # P2P pieces until the new world is safely up). EVERY member
            # marks its own restore; rank 0 collects the marks before
            # advancing restored_step — publishing after only its own
            # restore would release the leavers while a slower peer is
            # still mid-fetch (connection reset, failed epoch).
            rmark = lambda n: self._k("restored", str(epoch), n)  # noqa: E731
            cl.kv_put(rmark(cfg.worker_id), "1")
            # the LATE lane, not defer(): this epoch's own GC drain runs
            # before rank 0 finishes collecting the marks (epoch_gc.py)
            self._gc.defer_late(rmark(cfg.worker_id))
            if rank == 0:
                deadline = time.monotonic() + cfg.rendezvous_timeout_s
                confirmed = False
                while time.monotonic() < deadline:
                    cl.expire()
                    alive = {m.name for m in cl.members()}
                    if all(
                        cl.kv_get(rmark(m.name)) or m.name not in alive
                        for m in members
                    ):
                        confirmed = True
                        break
                    if cl.epoch() != epoch:
                        break  # a peer died mid-restore: regrouping anyway
                    time.sleep(_POLL_S)
                if confirmed:
                    # leavers' linger is bounded by p2p_linger_s, so an
                    # unconfirmed epoch cannot strand them — but only a
                    # CONFIRMED restore may release them early
                    s = int(jax.device_get(state.step))
                    if s > int(cl.kv_get(self._k("restored_step")) or "-1"):
                        cl.kv_put(self._k("restored_step"), str(s))
            loss_fn = wl.loss_for(plan, mesh)
            # donate=False: after a failed collective (peer crash) the
            # pre-step buffers must still be alive to recover from.
            step = make_train_step(
                loss_fn, tx, plan, mesh, param_pspecs=pspecs, donate=False
            )
            stepper = None
            if cfg.sync_every > 1:
                from edl_tpu.train.trainer import LocalSyncStepper

                stepper = LocalSyncStepper(
                    loss_fn, tx, plan, mesh, donate=False
                )
                state = stepper.localize(state)

            # GC the epoch-scoped keys recorded at our own past
            # teardowns. Safe HERE (after _initialize_distributed):
            # every member has connected to this epoch's service, which
            # it only does after finishing the previous epoch's
            # teardown — nobody still reads those keys. EVERY worker
            # drains its own ledger (deletes are idempotent across
            # peers), so the keys go away even when rank 0 is a
            # freshly restarted process with no history. The two-lane
            # deferral semantics: runtime/epoch_gc.py.
            self._gc.drain(cl.kv_del)
            if rank == 0:
                self._ensure_queue(cl)
            outcome = self._train_epoch(
                cfg, jax, cl, epoch, rank, world, plan, mesh, state, step,
                wl.batch_fn, members, stepper=stepper,
            )
            self._teardown_epoch(cl, epoch, rank, members, addr)
            if outcome == "stop":
                return self._finish(rank)
            # outcome == "reshard": state already snapshotted
            self._resharded += 1
            # monotonic max-write: a late joiner's small private count
            # must not clobber the job-wide one
            if self._resharded > int(cl.kv_get(self._k("reshards")) or "0"):
                cl.kv_put(self._k("reshards"), str(self._resharded))
            _clear_backends()
            if self._leaving:
                return self._depart(code=0)

    def _device_slices(self, cl, members, devs):
        """Per-device slice ids for this epoch's global device list,
        from each member's published slice KV (runtime/worker_main.py
        run()). Returns None — mesh build falls back to the hardware's
        own ``device.slice_index`` — when this worker has no declared
        slice or any peer's is missing: a half-declared topology must
        not silently build a wrong slice-major order."""
        if self.cfg.slice_id < 0:
            return None
        by_rank = {}
        for m in members:
            v = cl.kv_get(self._k("slice", m.name))
            if v is None or int(v) < 0:
                log.warn(
                    "member without slice id; building slice-blind mesh",
                    member=m.name,
                )
                return None
            # member rank == jax.distributed process id (rendezvous
            # passes me.rank to _initialize_distributed)
            by_rank[m.rank] = int(v)
        if any(d.process_index not in by_rank for d in devs):
            return None
        return [by_rank[d.process_index] for d in devs]

    def _ensure_queue(self, cl) -> None:
        cfg = self.cfg
        if not cl.kv_get(self._k("queue_inited")):
            # one task = one process's per-step rows; constant across
            # rescales because the growth axis scales with world
            cl.queue_init(
                cfg.n_samples,
                self._local_rows,
                passes=cfg.passes,
                lease_timeout_s=cfg.lease_timeout_s,
            )
            cl.kv_put(self._k("queue_inited"), "1")

    def _chunk(self) -> int:
        return self._local_rows

    @staticmethod
    def _pad_to(batch: Dict[str, np.ndarray], n: int) -> Dict[str, np.ndarray]:
        """Wrap-pad every leaf's leading dim to exactly ``n`` samples.
        SPMD peers must contribute identical local shapes every step, so
        a ragged tail task (n_samples % chunk) is padded by repeating
        its own samples — coverage accounting stays exact via acks; the
        repeats only even out the tensor shape."""
        have = next(iter(batch.values())).shape[0]
        if have == n:
            return batch
        idx = np.resize(np.arange(have), n)
        return {k: v[idx] for k, v in batch.items()}

    def _local_batch(self, cl, batch_fn):
        """Lease one task; fall back to replaying the previous local
        batch when the queue has no task for us this step (tail rounds —
        coverage still exactly-once via acks; replay only pads the SPMD
        shape). Returns (local_np_batch, task_id_or_None).

        Every batch carries real-row weights ``_w`` (1 = leased row,
        0 = wrap-padding / replay / zero filler), consumed by the model
        losses (models/losses.py row_mean): filler rows keep the SPMD
        shapes aligned but contribute ZERO gradient, so the update at a
        ragged tail equals the sequential gradient over real rows."""
        chunk = self._chunk()
        task = cl.lease(self.cfg.worker_id)
        if task is not None:
            have = task.end - task.start
            local = self._pad_to(batch_fn(task.start, task.end), chunk)
            w = np.zeros(chunk, np.float32)
            w[:have] = 1.0
            local["_w"] = w
            self._last_local = local
            return local, task.task_id
        if self._last_local is not None:
            replay = dict(self._last_local)
            replay["_w"] = np.zeros(chunk, np.float32)
            return replay, None
        # first-ever step with no task: zero batch of chunk shape (probe
        # only what the dataset has — a file-backed source bounds-checks,
        # and the dataset may be smaller than one process's rows)
        probe = self._pad_to(
            batch_fn(0, min(chunk, self.cfg.n_samples)), chunk
        )
        zero = {k: np.zeros_like(v) for k, v in probe.items()}
        zero["_w"] = np.zeros(chunk, np.float32)
        return zero, None

    def _train_epoch(
        self, cfg, jax, cl, epoch, rank, world, plan, mesh, state, step,
        batch_fn, members, stepper=None,
    ):
        """Lockstep loop. Returns "stop" | "reshard" with
        self._ram_snapshot holding this process's shards of the last
        completed (or last committed, after a crash) step.

        With ``stepper`` (delayed-sync DP) the live state is grouped
        (leading dp axis); every peer syncs at the same K boundary
        (derived from the shared step counter), and commit points merge
        to the consensus average first — both are collectives, which is
        safe exactly where they run: on a healthy mesh under a rank-0
        verb. The crash path cannot merge (the mesh just failed), so it
        skips the RAM snapshot and rolls back to the last commit."""
        from edl_tpu.runtime import checkpoint as ckpt
        from edl_tpu.obs import metrics as obs_metrics

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
        g_loss = reg.gauge("edl_train_loss", "most recent training loss")
        eff = n_local = None
        if getattr(self, "_flops_per_example", None):
            from edl_tpu.obs import costmodel as _cm

            # per-CHIP roofline: this process's rows over its local
            # devices; the fleet view sums per-worker gauges
            eff = _cm.EfficiencyMeter(registry=reg)
            n_local = max(jax.local_device_count(), 1)

        go_key = self._k("go", str(epoch))
        sharding = plan.batch_sharding(mesh)
        first_loss_key = self._k("loss_first")
        while True:
            i = int(jax.device_get(state.step))
            # one DERIVED trace per lockstep decision: every process
            # independently opens trace ("step", job, epoch, i) — no
            # id exchange needed — so rank 0's publish span and each
            # follower's recv span land in one trace. The recv span
            # parents to the publish span through the go key's trace
            # side key, which is the cross-process client→server pair
            # the fleet merge links with a flow arrow.
            if rank == 0:
                step_tok = disttrace.enter_root("step", cfg.job, epoch, i)
                verb = self._decide(cl, epoch, i)
                with tracing.span("coord.go", step=i, verb=verb):
                    # ctx side key FIRST: a follower that can read the
                    # verb must already be able to fetch its context
                    disttrace.publish_ctx(cl.kv_put, go_key, tag=str(i))
                    cl.kv_put(go_key, f"{i}:{verb}")
            else:
                # the await poll runs OUTSIDE the trace root: polling
                # RPCs must not flood the span ring while rank 0 is
                # inside a long step
                verb = self._await_go(cl, go_key, i, members)
                step_tok = disttrace.enter_root("step", cfg.job, epoch, i)
                rctx = disttrace.fetch_ctx(cl.kv_get, go_key, tag=str(i))
                if rctx is not None:
                    tracing.tracer().record(
                        "coord.go.recv", time.perf_counter(), 0.0,
                        {"step": i, "verb": verb,
                         **disttrace.link_attrs(rctx)},
                    )
            try:
                verb = self._step_verb(
                    cfg, jax, cl, epoch, rank, world, members, state,
                    step, stepper, verb, i, go_key, first_loss_key,
                    sharding, batch_fn, h_step, h_data, h_block,
                    c_examples, g_loss, eff, n_local,
                )
            finally:
                disttrace.exit_root(step_tok)
            if isinstance(verb, tuple):  # (new state, keep looping)
                state = verb[0]
                continue
            return verb

    def _step_verb(
        self, cfg, jax, cl, epoch, rank, world, members, state, step,
        stepper, verb, i, go_key, first_loss_key, sharding, batch_fn,
        h_step, h_data, h_block, c_examples, g_loss, eff, n_local,
    ):
        """One published verb's work, inside the step's trace root.
        Returns ``(new_state,)`` to continue the lockstep loop or the
        epoch outcome string ("stop" | "reshard"). The whole verb runs
        under a ``train.step`` span so the fleet trace shows each
        worker's step duration beside the go decision that caused it
        (per-worker step skew is visible on one axis)."""
        from edl_tpu.runtime import checkpoint as ckpt

        with tracing.span(
            "train.step", step=i, verb=verb, worker=self.cfg.worker_id
        ):
            if verb in ("step", "ckpt"):
                t_iter = time.perf_counter()
                local, task_id = self._local_batch(cl, batch_fn)
                gbatch = jax.tree_util.tree_map(
                    lambda x: jax.make_array_from_process_local_data(
                        sharding, x
                    ),
                    local,
                )
                h_data.observe(time.perf_counter() - t_iter)
                try:
                    if stepper is not None:
                        new_state, metrics = stepper.step(state, gbatch)
                        if (i + 1) % cfg.sync_every == 0:
                            new_state = stepper.sync(new_state)
                    else:
                        new_state, metrics = step(state, gbatch)
                    t_sync = time.perf_counter()
                    loss = float(jax.device_get(metrics["loss"]))
                    h_block.observe(time.perf_counter() - t_sync)
                except Exception as e:
                    # peer died mid-collective: recover from last
                    # completed state (crash path; epoch will bump once
                    # the member TTL reaps the dead peer)
                    log.warn("step failed; recovering", step=i, error=str(e))
                    if task_id is not None:
                        cl.nack(task_id)
                    if stepper is None:
                        snap = ckpt.snapshot_local(state)
                        self._ram_snapshot = snap
                        self._crash_checkpoint(cl, snap, rank, world)
                    else:
                        # grouped state cannot move across a dp-width
                        # change and merging needs the (dead) mesh —
                        # keep the existing RAM snapshot untouched: it
                        # already holds the last MERGED commit
                        # (_coordinated_checkpoint), which is exactly
                        # the rollback point
                        log.warn(
                            "delayed-sync crash: rolling back to last commit"
                        )
                    self._await_peer_reaped(cl, epoch)
                    return "reshard"
                state = new_state
                c_examples.inc(self._local_rows)
                g_loss.set(loss)
                step_wall = time.perf_counter() - t_iter
                h_step.observe(step_wall)
                if eff is not None:
                    from edl_tpu.obs.costmodel import Cost

                    eff.observe(
                        "train",
                        Cost(
                            self._local_rows * self._flops_per_example
                            / n_local,
                            0.0,
                        ),
                        step_wall,
                    )
                if task_id is not None:
                    cl.ack(task_id)
                if cfg.step_sleep_s:
                    time.sleep(cfg.step_sleep_s)
                if rank == 0:
                    if not cl.kv_get(first_loss_key):
                        cl.kv_put(first_loss_key, repr(loss))
                    cl.kv_put(self._k("loss_last"), repr(loss))
                    cl.kv_put(self._k("progress"), str(i + 1))
                if verb == "ckpt":  # periodic commit of the NEW state,
                    # written behind the continuing step loop
                    self._coordinated_checkpoint(
                        cl, epoch,
                        stepper.merge(state) if stepper is not None else state,
                        rank, members, background=True,
                    )
            else:  # stop | reshard — commit the completed state
                self._coordinated_checkpoint(
                    cl, epoch,
                    stepper.merge(state) if stepper is not None else state,
                    rank, members,
                )
                return verb
        return (state,)

    def _await_peer_reaped(self, cl, failed_epoch: int) -> None:
        """A collective just failed, so some peer is dead but may not
        have TTL-expired yet. Re-rendezvousing before the coordinator
        reaps it would rebuild the world WITH the corpse — and a
        jax.distributed connect timeout is fatal. Wait for the epoch to
        move, then one extra TTL for any other silent deaths."""
        deadline = time.monotonic() + self.cfg.rendezvous_timeout_s
        while cl.epoch() == failed_epoch:
            cl.expire()
            if time.monotonic() > deadline:  # pragma: no cover
                raise TimeoutError("dead peer never reaped")
            time.sleep(0.1)
        time.sleep(self.cfg.member_ttl_s)
        cl.expire()

    def _decide(self, cl, epoch: int, i: int) -> str:
        cl.expire()
        if self._leaving or cl.epoch() != epoch:
            return "reshard"
        ms = cl.members()
        if any(cl.kv_get(self._k("leaving", m.name)) for m in ms):
            return "reshard"
        if cl.queue_done():
            return "stop"
        if (
            self.cfg.ckpt_every
            and self.cfg.ckpt_dir
            and (i + 1) % self.cfg.ckpt_every == 0
        ):
            return "ckpt"  # step, then commit the resulting state
        return "step"

    def _await_go(self, cl, go_key: str, i: int, members) -> str:
        """Wait for rank 0's decision for step ``i``. A published
        decision always wins (rank 0 may already be inside the step's
        collective). Only when there is NO decision yet AND rank 0 has
        left membership (crashed + TTL-reaped, or departed) can it
        never publish again — treat that as a reshard. Note: a mere
        epoch bump is NOT a bail-out signal; rank 0 may be alive and
        about to publish ``step``, and abandoning it then would strand
        it inside the collective."""
        deadline = time.monotonic() + self.cfg.rendezvous_timeout_s
        prefix = f"{i}:"
        rank0 = next(m.name for m in members if m.rank == 0)
        while True:
            v = cl.kv_get(go_key)
            if v and v.startswith(prefix):
                return v.split(":", 1)[1]
            cl.expire()
            if rank0 not in {m.name for m in cl.members()}:
                log.warn("rank-0 worker gone; resharding", step=i)
                return "reshard"
            if time.monotonic() > deadline:
                raise TimeoutError(f"no go decision for step {i}")
            time.sleep(_POLL_S)

    def _dist_done_key(self, epoch: int, addr: str) -> str:
        """Dismissal key scoped to one service instance's address, so
        dismissing a dead host cannot kill its respawn at the same
        epoch."""
        return self._k("dist_done", str(epoch), addr.rsplit(":", 1)[1])

    def _teardown_epoch(self, cl, epoch: int, rank: int, members, addr: str) -> None:
        """Ordered disconnect from this epoch's (external) coordination
        service. A live leader — the lowest-rank surviving member, since
        rank 0 itself may be the casualty — waits for every other live
        member's disconnect mark, disconnects last, and dismisses the
        service host via ``dist_done``. Dismissing it earlier would
        abort still-connected peers (their error pollers treat a dead
        service as fatal)."""
        me = self.cfg.worker_id
        disc = lambda name: self._k("disc", str(epoch), name)  # noqa: E731
        # retire this epoch's coordination keys at the NEXT rendezvous
        # (they must survive until every peer has left the epoch; the
        # dist_done mark must outlive the service host's dismissal poll)
        self._gc.defer(
            self._k("go", str(epoch)),
            self._k("dist", str(epoch)),
            *[disc(m.name) for m in members],
        )
        self._gc.defer_late(self._dist_done_key(epoch, addr))
        cl.expire()
        alive = {m.name for m in cl.members()}
        leader = min(
            (m.rank for m in members if m.name in alive), default=rank
        )
        if rank == leader:
            peers = [m.name for m in members if m.name != me]
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                cl.expire()
                live = {m.name for m in cl.members()}
                if all(cl.kv_get(disc(p)) or p not in live for p in peers):
                    break
                time.sleep(_POLL_S)
            _shutdown_distributed()
            cl.kv_put(self._dist_done_key(epoch, addr), "1")
            return
        _shutdown_distributed()
        cl.kv_put(disc(me), "1")

    def _finish(self, rank: int) -> int:
        cl = self.client
        if rank == 0:
            cl.kv_put(self._k("phase"), "succeeded")
        log.info("job complete", worker=self.cfg.worker_id)
        _emit_worker_event(
            "worker.leave", self.cfg.worker_id, reason="complete"
        )
        cl.leave(self.cfg.worker_id)
        cl.release_worker(self.cfg.worker_id)
        return 0

    def _depart(self, code: int) -> int:
        cl = self.client
        log.info("departing (scale-down)", worker=self.cfg.worker_id)
        _emit_worker_event(
            "worker.leave", self.cfg.worker_id, reason="scale-down"
        )
        cl.release_worker(self.cfg.worker_id)
        cl.leave(self.cfg.worker_id)
        cl.kv_del(self._k("leaving", self.cfg.worker_id))
        self._p2p.linger(cl)
        return code


def main(argv=None) -> int:
    import argparse

    # provisional shield: a scale-down SIGTERM that lands before the
    # worker has joined the job (registration happens inside run()) is
    # a clean no-op departure — exit 0 without touching membership.
    # The drain handler replaces this below. The only remaining window
    # is interpreter startup itself (same exposure as a pod deleted
    # during container start in the reference).
    signal.signal(signal.SIGTERM, lambda *a: sys.exit(0))

    # configuration comes from the EDL_* env contract injected by the
    # controller (api/parser.py pod_env); argv exists for --help only
    argparse.ArgumentParser(
        prog="edl-worker",
        description="elastic worker entrypoint; configured via the EDL_* "
        "environment contract (EDL_JOB_NAME, EDL_COORDINATOR, EDL_WORKER_ID, "
        "EDL_WORKERS_MIN/MAX, EDL_FAULT_TOLERANT, EDL_ENTRY, ...)",
    ).parse_args(argv)
    from edl_tpu.utils.logging import configure

    configure(os.environ.get("EDL_LOG_LEVEL", "info"))
    cfg = WorkerConfig.from_env()
    worker = ElasticWorker(cfg)
    # install BEFORE the heavy jax import: a scale-down SIGTERM can land
    # while the worker is still starting up
    signal.signal(signal.SIGTERM, worker._on_sigterm)
    try:
        return worker.run()
    except entrypoint.FailureGateError as e:
        log.error("failure gate", error=str(e))
        return 2


if __name__ == "__main__":
    sys.exit(main())
