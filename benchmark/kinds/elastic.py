"""Kind ``elastic``: a training job that is rescaled without a restart.

The first ``steady_share`` of the window is steady steps on every chip
(the throughput sample: collectives included, reshards not). Then come
back-to-back cycles through ``ElasticTrainer.request_rescale``: down to
``small_workers``, ``steps_per_mesh`` steps, back up, the same again;
how many cycles is fixed by the window's length, so every run holds the
same work. Set-up runs one whole cycle, so both meshes' programs are
compiled and in the cache before the window, and checks there that the
parameters come through each reshard bit for bit.
"""

from __future__ import annotations

import statistics
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmark.kinds import train


class Kind(train.Kind):

    def __init__(self, ctx):
        super().__init__(ctx)
        t = self.cell.traffic
        self.big = len(ctx.devices)
        self.small = int(t["small_workers"])
        self.steps_per_mesh = int(t["steps_per_mesh"])
        self.spans.update(stall_s=[], transfer_s=[], recompile_s=[])
        self.changed_leaves = 0

    # -- one reshard --------------------------------------------------------

    def rescale(self, workers: int) -> float:
        """Ask for ``workers`` and take ``steps_per_mesh`` steps there.
        Returns the seconds from the end of the last step on the old
        mesh to the end of the first on the new."""
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.rescale"):
            self.trainer.request_rescale(workers)
            self.step()  # the trainer reshards at this step's boundary
        first = time.perf_counter() - t0
        self.spans["step_s"].pop()  # not a steady step
        if self.trainer.n_workers != workers:
            raise RuntimeError(f"rescale to {workers} did not happen")
        later = [self.timed_step(keep=False)
                 for _ in range(self.steps_per_mesh - 1)]
        self.steady.setdefault(workers, []).extend(later)
        return first

    def timed_step(self, keep: bool) -> float:
        """One step's seconds; ``keep`` leaves it among the steady
        four-chip steps that ``step_ms.train`` reads."""
        self.step()
        steps = self.spans["step_s"]
        return steps[-1] if keep else steps.pop()

    def cycle(self) -> List[float]:
        return [self.rescale(self.small), self.rescale(self.big)]

    def after_first_steps(self) -> None:
        """The warm cycle: compiles both meshes' programs, and checks the
        parameters bit for bit across each of its two reshards."""
        self.steady: Dict[int, List[float]] = {}
        for workers in (self.small, self.big):
            before = checksums(self.trainer.state.params)
            self.trainer.request_rescale(workers)
            self.trainer._maybe_rescale()  # train_steps' own first act
            after = checksums(self.trainer.state.params)
            self.changed_leaves += sum(
                1 for k in before if before[k] != after[k])
            for _ in range(self.steps_per_mesh):
                self.step()
        events = self.trainer.report.reshards
        print("warm cycle: " + "; ".join(
            f"{e.from_workers}->{e.to_workers} "
            f"{'host' if e.fallback else 'device'} path, transfer "
            f"{e.stall_s:.2f}s, first step {e.recompile_s:.2f}s"
            for e in events), flush=True)
        self.warm_reshards = len(events)
        self.steady.clear()

    # -- the window ---------------------------------------------------------

    def window(self, seconds: float) -> None:
        t = self.cell.traffic
        tracer = self.ctx.tracer
        steady_s = float(t["steady_share"]) * seconds
        cycles = max(1, int((seconds - steady_s) // float(t["cycle_every_s"])))
        t0 = time.perf_counter()
        tokens, spent = 0, 0.0

        def steady_until(limit: float) -> None:
            nonlocal tokens, spent
            while True:
                now = time.perf_counter() - t0
                if now >= limit:
                    return
                tracer.tick(now)
                spent += self.timed_step(keep=True)
                tokens += self.tokens_per_step()

        steady_until(steady_s)
        tracer.finish()
        firsts: List[List[float]] = [self.cycle() for _ in range(cycles)]
        steady_until(seconds)
        for down, up in firsts:
            self.spans["stall_s"] += [
                down - statistics.median(self.steady[self.small]),
                up - statistics.median(
                    self.steady[self.big] + self.spans["step_s"])]
        events = self.trainer.report.reshards[self.warm_reshards:]
        self.spans["transfer_s"] = [e.stall_s for e in events]
        self.spans["recompile_s"] = [e.recompile_s for e in events]
        self.counters.update(
            steady_tokens=tokens, steady_seconds=spent,
            steady_chips=self.big, cycles=cycles,
            host_path_reshards=sum(1 for e in events if e.fallback),
            tokens_per_step_per_chip=self.per_chip_batch * self.seq)
        print(f"window: {cycles} cycles, first steps after a reshard "
              f"{[[round(x, 2) for x in c] for c in firsts]}s, steady "
              f"{ {k: round(statistics.median(v), 3) for k, v in self.steady.items()} }",
              flush=True)

    def end_to_end(self) -> Dict[str, float]:
        out = super().end_to_end()
        out["reshard_stall_s"] = statistics.mean(self.spans["stall_s"])
        return out

    # -- correct ------------------------------------------------------------

    def reference_shardings(self):
        """The reference over the same chips: a one-axis mesh, every
        large leaf split along its longest axis that divides, rows
        split along the group axis."""
        from benchmark import harness

        n = len(self.ctx.devices)
        mesh = Mesh(np.array(self.ctx.devices), ("g",))

        def place(shape):
            axes = [i for i in range(len(shape)) if shape[i] % n == 0
                    and shape[i] >= 128]
            spec = [None] * len(shape)
            if axes:
                spec[max(axes, key=lambda i: shape[i])] = "g"
            return NamedSharding(mesh, P(*spec))

        tree = harness.layout_tree(
            self.cell.layout, lambda path, shape, *_: place(shape))
        return tree, NamedSharding(mesh, P("g"))

    def check(self, compared) -> None:
        compared.add("leaves_changed_by_a_reshard",
                     float(self.changed_leaves), 0.0)
        super().check(compared)


@jax.jit
def _checksum(x):
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32).reshape(-1)
    weight = jnp.arange(bits.size, dtype=jnp.uint32) % 65521 + 1
    return jnp.stack([jnp.sum(bits, dtype=jnp.uint32),
                      jnp.sum(bits * weight, dtype=jnp.uint32)])


def checksums(params) -> Dict[str, tuple]:
    """Two wrapping 32-bit sums of every leaf's bits, taken on the
    device: equal parameters give equal sums, and a changed or moved
    word changes them."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    sums = [(_checksum(leaf)) for _, leaf in flat]
    return {jax.tree_util.keystr(path): tuple(int(v) for v in np.asarray(s))
            for (path, _), s in zip(flat, sums)}
