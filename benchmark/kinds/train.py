"""Kind ``train``: steady steps of ``ElasticTrainer`` on a fixed mesh.

Set-up builds the one trainer, drives it through its first steps on
fresh seeded rows (those are what the reference follows) and hands the
same object to the window; the window is back-to-back
``train_steps(feed, 1)`` calls, each ended by its loss on the host.
"""

from __future__ import annotations

import gc
import math
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
import optax

from benchmark import harness


class Kind:

    def __init__(self, ctx):
        self.ctx = ctx
        self.cell = ctx.cell
        t = self.cell.traffic
        self.seq = int(t["seq"])
        self.per_chip_batch = int(t["per_chip_batch"])
        self.check_steps = int(t["check_steps"])
        self.lr = float(t["learning_rate"])
        self.rng = np.random.default_rng(ctx.seed)
        self.vocab = self.cell.config["vocab_size"]
        self.first_batches: List[np.ndarray] = []
        self.spans: Dict[str, list] = {"step_s": []}
        self.counters: Dict[str, float] = {}
        self.attempted = self.failed = 0
        self.program: Dict = {}

    # -- the feed -----------------------------------------------------------

    def feed(self, rows: int) -> Dict[str, np.ndarray]:
        """Fresh rows on every call, all different, from the seed."""
        tokens = self.rng.integers(
            0, self.vocab, (rows, self.seq + 1), dtype=np.int32)
        if len(self.first_batches) < self.check_steps:
            self.first_batches.append(tokens)
        return {"tokens": tokens}

    def step(self) -> float:
        """One update through the trainer's own loop, ended by its loss
        on the host; the window's call and set-up's."""
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.train_steps"):
            report = self.trainer.train_steps(self.feed, 1)
        loss = report.losses[-1]
        self.spans["step_s"].append(time.perf_counter() - t0)
        self.attempted += 1
        if not math.isfinite(loss):
            self.failed += 1
        return loss

    # -- set-up -------------------------------------------------------------

    def make_trainer(self):
        from edl_tpu.api.job import MeshSpec
        from edl_tpu.runtime.elastic import ElasticTrainer

        family = self.cell.family
        cfg = family.program_config(
            self.cell.config, training=True, control=self.ctx.control)
        self.model_cfg = cfg
        return ElasticTrainer(
            None,
            optax.adafactor(self.lr),
            mesh_spec=MeshSpec(**self.cell.spec.get("mesh", {})),
            per_chip_batch=self.per_chip_batch,
            param_pspecs=lambda plan: family.param_pspecs(cfg, plan),
            make_loss=lambda plan, mesh: family.make_loss(cfg, plan, mesh),
            devices=self.ctx.devices,
        )

    def param_shardings(self):
        """Where the trainer will keep each parameter on its first mesh,
        so the weights are made in place and never whole on one chip."""
        from edl_tpu.parallel import sharding as shd
        from edl_tpu.parallel.mesh import MeshPlan

        tr = self.trainer
        plan = MeshPlan.from_spec(tr.mesh_spec, len(tr.pool))
        mesh = plan.build(tr.pool)
        return shd.named(
            self.cell.family.param_pspecs(self.model_cfg, plan), mesh)

    def setup(self) -> None:
        ctx = self.ctx
        self.trainer = self.make_trainer()
        t0 = time.perf_counter()
        params = harness.make_params(
            ctx.seed, self.cell.layout, jnp.float32, self.param_shardings())
        jax.block_until_ready(params)
        t1 = time.perf_counter()
        self.trainer.start(params, n_workers=len(ctx.devices))
        del params
        t2 = time.perf_counter()
        losses = []
        for i in range(self.check_steps):
            losses.append(self.step())
            if i == 0:
                self.program["grad_sumsq"] = first_gradient_sumsq(
                    self.trainer.state)
        self.program["losses"] = losses
        self.program["change_sumsq"] = self.change_sumsq(
            self.trainer.state.params)
        print(f"weights {t1 - t0:.1f}s, trainer start {t2 - t1:.1f}s, first "
              f"steps and their readings {time.perf_counter() - t2:.1f}s: "
              f"losses {losses} step_s "
              f"{[round(s, 3) for s in self.spans['step_s']]}", flush=True)
        self.after_first_steps()
        # set-up's steps are not the window's
        self.spans["step_s"].clear()
        self.attempted = self.failed = 0

    def after_first_steps(self) -> None:
        """Further warm-up of a kind built on this one."""

    def change_sumsq(self, params) -> Dict[str, float]:
        """Sum of squares of (params - the seed's initial draw) per leaf,
        in one program: each initial leaf is drawn again inside it, used
        and dropped, so no second copy of the tree is held."""
        layout = self.cell.layout

        def diff(key, params):
            return {
                jax.tree_util.keystr(path): jnp.sum(jnp.square(
                    leaf - harness.initial_leaf(
                        key, layout, [k.key for k in path], jnp.float32)))
                for path, leaf in
                jax.tree_util.tree_flatten_with_path(params)[0]}

        sums = jax.jit(diff)(harness.seed_key(self.ctx.seed), params)
        return {k: float(v) for k, v in sums.items()}

    # -- the window ---------------------------------------------------------

    def tokens_per_step(self) -> int:
        return self.trainer.global_batch_size * self.seq

    def window(self, seconds: float) -> None:
        tracer = self.ctx.tracer
        t0 = time.perf_counter()
        tokens = 0
        while True:
            now = time.perf_counter() - t0
            if now >= seconds:
                break
            tracer.tick(now)
            self.step()
            tokens += self.tokens_per_step()
        elapsed = time.perf_counter() - t0
        tracer.finish()
        steps = sorted(self.spans["step_s"])
        print(f"window: {len(steps)} steps, median "
              f"{steps[len(steps) // 2] * 1e3:.1f}ms, slowest "
              f"{[round(s * 1e3) for s in steps[-3:]]}ms", flush=True)
        self.counters.update(
            steady_tokens=tokens, steady_seconds=elapsed,
            steady_chips=len(self.ctx.devices),
            tokens_per_step_per_chip=self.per_chip_batch * self.seq)

    def end_to_end(self) -> Dict[str, float]:
        c = self.counters
        return {"train_tokens_per_s_per_chip":
                c["steady_tokens"] / c["steady_seconds"] / c["steady_chips"]}

    # -- correct ------------------------------------------------------------

    def release(self) -> None:
        self.trainer.state = None
        self.trainer._step_fn = None
        self.trainer = None
        gc.collect()

    def reference_shardings(self):
        """(parameter shardings, token and activation sharding) of the
        reference over this cell's chips, or (None, None) on one."""
        return None, None

    def check(self, compared) -> None:
        ctx, lim = self.ctx, self.cell.limits
        if self.failed:
            compared.add("non_finite_losses", float(self.failed), 0.0)
        p_sh, t_sh = self.reference_shardings()
        groups = len(ctx.devices)
        batches = [
            jax.device_put(
                b.reshape(groups, b.shape[0] // groups, b.shape[1]), t_sh)
            for b in self.first_batches
        ]
        t0 = time.perf_counter()
        params = harness.make_params(
            ctx.seed, self.cell.layout, jnp.float32, p_sh)
        losses, grad_sumsq, params = self.cell.family.reference_train_steps(
            params, batches, self.cell.config, self.lr, p_sh, t_sh)
        change = self.change_sumsq(params)
        del params
        print(f"reference: {len(batches)} steps in "
              f"{time.perf_counter() - t0:.1f}s, losses {losses}",
              flush=True)
        for i, (p, r) in enumerate(zip(self.program["losses"], losses)):
            compared.add(f"loss_gap.step{i + 1}", abs(p - r) / abs(r),
                         lim["loss_gap"])
        gap, leaf = harness.worst_leaf_gap(
            self.program["grad_sumsq"], grad_sumsq)
        print(f"worst gradient leaf: {leaf}", flush=True)
        compared.add("first_gradient_norm_gap", gap,
                     lim["first_gradient_norm_gap"])
        gap, leaf = harness.worst_leaf_gap(
            self.program["change_sumsq"], change)
        print(f"worst change leaf: {leaf}", flush=True)
        compared.add("parameter_change_norm_gap", gap,
                     lim["parameter_change_norm_gap"])


def first_gradient_sumsq(state) -> Dict[str, float]:
    """Per-leaf sum of squares of the gradient the optimizer was handed
    at its first update, worked out from adafactor's state after that
    update: its decay is 0 at the first step, so the second-moment
    estimate IS the squared gradient (its row means, for a factored
    leaf)."""
    factored = next(
        s for s in jax.tree_util.tree_leaves(
            state.opt_state, is_leaf=lambda x: hasattr(x, "v_row"))
        if hasattr(s, "v_row"))
    paths = [(jax.tree_util.keystr(path), p.shape, p.size) for path, p in
             jax.tree_util.tree_flatten_with_path(state.params)[0]]

    @jax.jit
    def sums(v_rows, v_full):
        return {
            name: (jnp.sum(v) if v.shape == shape
                   else jnp.sum(v_row) * (size / v_row.size))
            for (name, shape, size), v_row, v in zip(paths, v_rows, v_full)}

    out = sums(jax.tree_util.tree_leaves(factored.v_row),
               jax.tree_util.tree_leaves(factored.v))
    return {k: float(v) for k, v in out.items()}
