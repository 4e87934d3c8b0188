"""Train-step factory: jit-compiled, mesh-sharded update steps.

Replaces the reference's external Paddle trainer/pserver loop
(reference: docker/paddle_k8s:145-228 launches it; the gradient math
lived outside the repo). Here the whole update is one XLA program:
params/optimizer state sharded per the mesh plan, gradients all-reduced
(dp) or reduce-scattered (fsdp) over ICI by the compiler.
"""

from __future__ import annotations

import itertools
import math
import time
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from edl_tpu.obs import compilewatch
from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.parallel import remat
from edl_tpu.parallel.mesh import MeshPlan
from edl_tpu.parallel import sharding as shd
from edl_tpu.utils import tracing

compilewatch.install()  # compile telemetry for every program built here


def _record_dispatch(dt_s: float, n_steps: int = 1) -> None:
    """Step-factory telemetry choke point: every compiled update path
    (per-step, scan-fused, delayed-sync) counts optimizer steps and
    times the DISPATCH (enqueue) — the async call itself, not device
    time; a blocking dispatch here means the pipeline is full, which
    is exactly the host-side signal worth scraping. Looked up per call
    so a test's registry swap takes effect immediately; cost is two
    dict hits."""
    r = obs_metrics.default_registry()
    r.histogram(
        "edl_train_dispatch_seconds",
        "train-step program dispatch (enqueue) time",
    ).observe(dt_s)
    r.counter("edl_train_steps_total", "optimizer steps completed").inc(n_steps)


@struct.dataclass
class TrainState:
    """Minimal train state pytree (flax.training analog without the
    apply_fn/tx statics, which live in the step closure)."""

    step: jnp.ndarray
    params: Any
    opt_state: Any

    @classmethod
    def create(cls, params, tx: optax.GradientTransformation) -> "TrainState":
        return cls(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=tx.init(params),
        )


def state_pspecs(state: TrainState, plan: MeshPlan, param_pspecs=None):
    """PartitionSpec tree matching a TrainState: params per the plan (or
    explicit model-provided specs), optimizer moments shard like their
    params (shape-matched — a TP-sharded weight gets TP-sharded Adam
    moments), scalars replicated."""
    p_specs = param_pspecs if param_pspecs is not None else shd.param_pspecs(
        state.params, plan
    )
    fsdp = plan.axis_size("fsdp")
    # Optimizer moment trees (optax mu/nu) are structurally identical to
    # the param tree — substitute the param spec tree for each such
    # subtree so every moment shards exactly like its parameter (shape
    # matching is NOT enough: wq [L,d,H] and wo [L,H,d] have equal shapes
    # when d == H but transposed specs). Non-param leaves (counts,
    # scalars) fall back to the fsdp rule.
    param_treedef = jax.tree_util.tree_structure(state.params)
    param_shapes = [
        getattr(x, "shape", ()) for x in jax.tree_util.tree_leaves(state.params)
    ]

    def _is_param_shaped(node) -> bool:
        try:
            if jax.tree_util.tree_structure(node) != param_treedef:
                return False
        # edl: no-lint[silent-failure] structure probe: "not param-shaped" is the answer, not an error
        except Exception:
            return False
        shapes = [
            getattr(x, "shape", ()) for x in jax.tree_util.tree_leaves(node)
        ]
        return shapes == param_shapes

    def _rec(node):
        if _is_param_shaped(node):
            return p_specs
        if isinstance(node, dict):
            return {k: _rec(v) for k, v in node.items()}
        if isinstance(node, tuple):
            vals = [_rec(v) for v in node]
            return type(node)(*vals) if hasattr(node, "_fields") else tuple(vals)
        if isinstance(node, list):
            return [_rec(v) for v in node]
        return shd.fsdp_pspec(getattr(node, "shape", ()), fsdp)

    opt_specs = _rec(state.opt_state)
    return TrainState(step=P(), params=p_specs, opt_state=opt_specs)


def _apply_update(loss_fn, tx, state: TrainState, batch):
    """One optimizer update — the single source of the update rule,
    shared by the per-step and scan-fused step factories."""
    loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
    with jax.named_scope("optimizer"):
        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
    return (
        TrainState(step=state.step + 1, params=params, opt_state=new_opt),
        loss,
    )


def _state_sharding(state: TrainState, plan: MeshPlan, mesh: Mesh, param_pspecs):
    # state_pspecs already returns a TrainState-shaped pspec tree
    return shd.named(state_pspecs(state, plan, param_pspecs), mesh)


def make_train_step(
    loss_fn: Callable[[Any, Any], jnp.ndarray],
    tx: optax.GradientTransformation,
    plan: MeshPlan,
    mesh: Mesh,
    param_pspecs=None,
    donate: bool = True,
):
    """Build a jit-compiled ``step(state, batch) -> (state, metrics)``.

    ``loss_fn(params, batch) -> scalar`` is traced once; XLA fuses the
    backward pass and inserts ICI collectives from the shardings alone —
    no hand-written all-reduce (the tpu-first replacement for the
    reference's pserver push/pull protocol).
    """

    # Sharding trees need a concrete state (opt_state structure is only
    # known then); build the jit lazily at first call. jax.jit itself
    # caches per input shape after that.
    cell: list = []
    kept: Dict[str, Any] = {}

    def build(state: TrainState, batch):
        # a function of its own for every jit: jit's cache of traces is
        # keyed by the function and does not see what ``_fit_to_device``
        # offers around a trace
        def edl_train_step(
            state: TrainState, batch
        ) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
            new_state, loss = _apply_update(loss_fn, tx, state, batch)
            return new_state, {"loss": loss}

        state_sh = _state_sharding(state, plan, mesh, param_pspecs)
        batch_sh = jax.tree_util.tree_map(
            lambda _: plan.batch_sharding(mesh), batch
        )
        metric_sh = NamedSharding(mesh, P())
        # the function's name is the program's: its build
        # lands in edl_compile_seconds{program="edl_train_step"}
        # and, post-warmup, on the flight-recorder timeline — a
        # steady-state loop that recompiles (the reshard
        # recompile aside, which re-enters here by design) is
        # paying seconds someone should see
        # edl: no-lint[recompile-hazard] built once a mesh by step()'s first call (and again only where the compiler refused the one before); step() keeps it
        return jax.jit(
            edl_train_step,
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, {"loss": metric_sh}),
            donate_argnums=(0,) if donate else (),
        )

    def step(state: TrainState, batch):
        if not cell:
            cell.append(_fit_to_device(build, state, batch, mesh, kept))
        t = time.perf_counter()
        out = cell[0](state, batch)
        _record_dispatch(time.perf_counter() - t)
        return out

    # the jitted program, once the first call has built it — so a
    # caller can lower it again and read what the compiler produced
    # (chip_smoke.py checks the kernel is really in there)
    step.program = cell
    # a jit of the step for a state and a batch (their shapes suffice):
    # what a described-device compile lowers without running anything
    step.build = build
    # what its rematerialised layers keep and the room left beside it,
    # once the first call has compiled it (``_fit_to_device``)
    step.kept = kept
    return step


# Of a device's memory, what a step's compiled peak has to leave free:
# the runtime's own reservations and what a caller holds beside the
# state. The estimate that picks the rung leaves twice that: it reads
# up to 0.3 GiB low, stepping down costs a compile, and near the limit
# the compiler trades speed for bytes (``_compiler_traded``).
HEADROOM_SHARE = 1 / 32


def device_bytes_limit(mesh: Mesh) -> Optional[int]:
    """The least ``bytes_limit`` over the mesh's devices, or None where
    the backend reports none (the CPU)."""
    limits = [
        (d.memory_stats() or {}).get("bytes_limit")
        for d in mesh.devices.flat
        if d.process_index == jax.process_index()
    ]
    return min(limits) if limits and all(limits) else None


def _device_nbytes(tree) -> int:
    """Bytes of a tree of arrays on ONE device under their shardings
    (a host array counts whole)."""
    total = 0
    for x in jax.tree_util.tree_leaves(tree):
        sharding = getattr(x, "sharding", None)
        shape = np.shape(x) if sharding is None else sharding.shard_shape(
            x.shape)
        total += math.prod(shape) * np.dtype(x.dtype).itemsize
    return total


def _fit_to_device(build, state, batch, mesh: Mesh, kept: Dict[str, Any]):
    """The jitted step of ``build``, compiled here so that what its
    model's rematerialised layers keep is fitted to the device: the
    room beside the state and the batch is offered to the model around
    the trace (``parallel/remat.py``), the step is compiled ONCE, and
    the compiler's ``memory_analysis()`` is held against the device's
    limit. Only where the compiler refuses the step
    (``RESOURCE_EXHAUSTED``), leaves less than the headroom or has
    started to rematerialise on its own (``_compiler_traded``) is it
    traced again with one rung less, in a ``jax.jit`` of its own. The
    call that follows finds the executable in jit's own caches. Fills
    ``kept`` and records the ``train.build_step`` span. Where the
    device reports no limit nothing is offered or compiled here."""
    limit = device_bytes_limit(mesh)
    if limit is None:
        return build(state, batch)
    t0 = time.perf_counter()
    held = _device_nbytes(state) + _device_nbytes(batch)
    headroom = int(limit * HEADROOM_SHARE)
    for back_off in itertools.count():
        jitted = build(state, batch)
        with remat.offer(limit - held - 2 * headroom, back_off) as offer:
            try:
                compiled = jitted.lower(state, batch).compile()
            except jax.errors.JaxRuntimeError as e:
                if "RESOURCE_EXHAUSTED" not in str(e) or not offer.rungs:
                    raise
                continue
        peak = _peak_bytes(compiled)
        if not offer.rungs or (
                limit - peak >= headroom and not _compiler_traded(compiled)):
            break
    kept.update(
        remat_kept=",".join(offer.kept),
        remat_kept_bytes=offer.kept_bytes,
        hbm_headroom_bytes=limit - peak,
        compiles=back_off + 1,
    )
    tracing.tracer().record(
        "train.build_step", t0, time.perf_counter() - t0, dict(kept))
    return jitted


def _compiler_traded(compiled) -> bool:
    """Whether the compiler, short of memory, rematerialised on its own:
    its pass names what it computes a second time ``<op>.remat``. It
    starts doing so well inside the limit (a v5e: with about a GiB
    still free), and what it recomputes cost more than the kept
    residuals spared (PERF.md section 6, PR 40: 963 ms a step against
    913 one rung lower), so a step that shows the sign is a rung too
    rich whatever its headroom reads."""
    return ".remat" in compiled.as_text()


def _peak_bytes(compiled) -> int:
    """A device's bytes at the compiled step's peak, arguments and
    donated outputs included, as the compiler holds them to the limit."""
    m = compiled.memory_analysis()
    peak = getattr(m, "peak_memory_in_bytes", 0)
    # a backend that reports no peak: the sum is its upper bound
    return peak or (
        m.argument_size_in_bytes + m.temp_size_in_bytes
        + m.output_size_in_bytes - m.alias_size_in_bytes
    )


def make_train_multistep(
    loss_fn: Callable[[Any, Any], jnp.ndarray],
    tx: optax.GradientTransformation,
    plan: MeshPlan,
    mesh: Mesh,
    param_pspecs=None,
    donate: bool = True,
):
    """Build ``multi(state, batches) -> (state, metrics)`` running a
    ``lax.scan`` over a leading steps axis of device-resident batches in
    ONE compiled program. K fused steps pay one dispatch instead of K
    (a per-dispatch overhead of ~1 ms is ~10% of a CTR step) and XLA
    can overlap the tail of step i with the head of step i+1. A caller
    that needs elastic rescale should check for membership changes
    between chunks: a scale event can only take effect at a chunk
    boundary (every K steps instead of every step).

    ``metrics["losses"]`` holds all K per-step losses; ``"loss"`` the
    last. Semantically identical to K calls of :func:`make_train_step`.
    """

    def edl_train_step_multi(state: TrainState, batches):
        state, losses = jax.lax.scan(
            lambda st, b: _apply_update(loss_fn, tx, st, b), state, batches
        )
        return state, {"loss": losses[-1], "losses": losses}

    cell: list = []

    def multi(state: TrainState, batches):
        if not cell:
            state_sh = _state_sharding(state, plan, mesh, param_pspecs)
            stacked = NamedSharding(
                mesh, P(None, *plan.batch_pspec())
            )  # leading steps axis unsharded
            batch_sh = jax.tree_util.tree_map(lambda _: stacked, batches)
            metric_sh = NamedSharding(mesh, P())
            cell.append(
                jax.jit(
                    edl_train_step_multi,
                    in_shardings=(state_sh, batch_sh),
                    out_shardings=(
                        state_sh,
                        {"loss": metric_sh, "losses": metric_sh},
                    ),
                    donate_argnums=(0,) if donate else (),
                )
            )
        t = time.perf_counter()
        out = cell[0](state, batches)
        k = jax.tree_util.tree_leaves(batches)[0].shape[0]
        _record_dispatch(time.perf_counter() - t, n_steps=k)
        return out

    return multi


class LocalSyncStepper:
    """K-step delayed-sync data parallelism (local SGD).

    The TPU translation of the reference's relaxed-consistency pserver
    mode (``--async_mode``, reference example/ctr/ctr/train.py:75-79):
    instead of trainers pushing gradients to pservers whenever they
    finish a step, each dp group keeps a PRIVATE copy of params and
    optimizer moments, takes K purely-local updates with zero cross-group
    traffic, and every K steps the copies are averaged (one all-reduce
    over the dp axis). With dp groups split across DCN this removes the
    per-step DCN collective entirely — the asynchrony budget K is the
    staleness bound, where the reference's pserver gave no bound at all.

    State layout: params/opt-state leaves carry a leading ``dp``-sized
    group axis sharded ``P("dp")``, so the local step is a ``vmap`` with
    no collectives (XLA sees only elementwise-along-sharded-axis work)
    and the sync is one mean over the sharded axis. ``step`` stays a
    replicated scalar. Restricted to dp-only meshes — the reference
    feature is pserver DP; sharded-param layouts (fsdp/tp) have no
    "private copy" to let drift.

    Usage::

        stepper = LocalSyncStepper(loss_fn, tx, plan, mesh)
        lstate = stepper.localize(state)          # replicated -> grouped
        for i in range(n):
            lstate, m = stepper.step(lstate, batch)   # no dp collective
            if (i + 1) % K == 0:
                lstate = stepper.sync(lstate)         # one all-reduce
        state = stepper.merge(lstate)             # grouped -> replicated
    """

    def __init__(
        self,
        loss_fn: Callable[[Any, Any], jnp.ndarray],
        tx: optax.GradientTransformation,
        plan: MeshPlan,
        mesh: Mesh,
        sync_moments: bool = True,
        donate: bool = True,
    ):
        busy = [
            a for a in ("pp", "fsdp", "sp", "ep", "tp") if plan.axis_size(a) > 1
        ]
        if busy:
            raise ValueError(
                f"local-sync (delayed-sync DP) requires a dp-only mesh; "
                f"axes {busy} shard parameters, which leaves no private "
                f"per-group copy to run ahead on"
            )
        self.plan = plan
        self.mesh = mesh
        self.dp = plan.axis_size("dp")
        self.sync_moments = sync_moments
        dp = self.dp

        grouped = TrainState(
            step=NamedSharding(mesh, P()),
            params=NamedSharding(mesh, P("dp")),
            opt_state=NamedSharding(mesh, P("dp")),
        )
        replicated = NamedSharding(mesh, P())
        batch_sh = plan.batch_sharding(mesh)

        def _localize(state: TrainState) -> TrainState:
            bc = lambda x: jnp.broadcast_to(x[None], (dp,) + jnp.shape(x))
            return TrainState(
                step=state.step,
                params=jax.tree_util.tree_map(bc, state.params),
                opt_state=jax.tree_util.tree_map(bc, state.opt_state),
            )

        def _avg(x):
            if jnp.issubdtype(x.dtype, jnp.floating):
                return jnp.mean(x, axis=0, dtype=jnp.float32).astype(x.dtype)
            return x[0]  # int leaves (adam counts) are identical per group

        def _merge(state: TrainState) -> TrainState:
            return TrainState(
                step=state.step,
                params=jax.tree_util.tree_map(_avg, state.params),
                opt_state=jax.tree_util.tree_map(_avg, state.opt_state),
            )

        def _sync(state: TrainState) -> TrainState:
            keep = lambda x: jnp.broadcast_to(
                _avg(x)[None], x.shape
            ) if jnp.issubdtype(x.dtype, jnp.floating) else x
            return TrainState(
                step=state.step,
                params=jax.tree_util.tree_map(keep, state.params),
                opt_state=jax.tree_util.tree_map(keep, state.opt_state)
                if sync_moments
                else state.opt_state,
            )

        def edl_train_step_localsync(state: TrainState, batch):
            # [B, ...] -> [dp, B/dp, ...]; the global batch's dp shards
            # become the per-group local batches (layout-preserving).
            bt = jax.tree_util.tree_map(
                lambda x: x.reshape((dp, x.shape[0] // dp) + x.shape[1:]), batch
            )

            def upd(p, o, b):
                st = TrainState(step=state.step, params=p, opt_state=o)
                new, loss = _apply_update(loss_fn, tx, st, b)
                return new.params, new.opt_state, loss

            params, opt, losses = jax.vmap(upd)(state.params, state.opt_state, bt)
            new = TrainState(step=state.step + 1, params=params, opt_state=opt)
            return new, {"loss": jnp.mean(losses)}

        self._localize = jax.jit(
            _localize, in_shardings=(replicated,), out_shardings=grouped
        )
        self._merge = jax.jit(
            _merge, in_shardings=(grouped,), out_shardings=replicated,
        )
        # donate=False callers (the crash-tolerant worker runtime) keep
        # pre-step buffers alive across a failed collective
        don = (0,) if donate else ()
        self._sync = jax.jit(
            _sync,
            in_shardings=(grouped,),
            out_shardings=grouped,
            donate_argnums=don,
        )
        self._step = jax.jit(
            edl_train_step_localsync,
            in_shardings=(grouped, batch_sh),
            out_shardings=(grouped, {"loss": replicated}),
            donate_argnums=don,
        )

    def localize(self, state: TrainState) -> TrainState:
        """Replicated TrainState -> grouped form (leading dp axis)."""
        return self._localize(state)

    def merge(self, lstate: TrainState) -> TrainState:
        """Grouped form -> replicated TrainState (group average)."""
        return self._merge(lstate)

    def sync(self, lstate: TrainState) -> TrainState:
        """Average params (and moments) across groups — the one
        all-reduce of a K-step round."""
        return self._sync(lstate)

    def step(self, lstate: TrainState, batch):
        """One local step on every group — no cross-group collectives."""
        t = time.perf_counter()
        out = self._step(lstate, batch)
        _record_dispatch(time.perf_counter() - t)
        return out


def stack_batches(batches, plan: MeshPlan, mesh: Mesh):
    """Stack host batches along a new leading steps axis and place them
    for :func:`make_train_multistep`."""
    import numpy as np

    stacked = jax.tree_util.tree_map(
        lambda *xs: np.stack(xs, axis=0), *batches
    )
    sh = NamedSharding(mesh, P(None, *plan.batch_pspec()))
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), stacked)


def shard_state(state: TrainState, plan: MeshPlan, mesh: Mesh, param_pspecs=None):
    """Place a host-resident TrainState onto the mesh (initial placement
    and the re-placement half of an elastic reshard)."""
    sp = state_pspecs(state, plan, param_pspecs)
    return TrainState(
        step=jax.device_put(state.step, NamedSharding(mesh, P())),
        params=shd.shard_tree(state.params, mesh, sp.params),
        opt_state=shd.shard_tree(state.opt_state, mesh, sp.opt_state),
    )


def global_batch(batch, plan: MeshPlan, mesh: Mesh):
    """Place a host batch onto the mesh, split over the batch axes."""
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, plan.batch_sharding(mesh)), batch
    )
