"""What a training step gains and pays for each thing its rematerialised
layers keep (PERF.md section 6, PR 40).

For a training cell's step (Mistral-7B widths, adafactor, the state
donated) it walks ``llama.KEEP_ORDER`` rung by rung, 0 (``"full"``) to
all of it, and prints for each: what was kept and its bytes, the
compiler's ``memory_analysis()`` and the room left under the device's
limit, how often the optimized HLO mentions ``edl_flash_fwd``, and, on
the chip, the step's milliseconds and the ``edl_flash_fwd`` runs and
``mlp`` matmuls a step counted in one trace. Last comes ``auto``: the
trainer's own choice (``train.trainer.make_train_step``).

    PYTHONPATH=. python3 scripts/exp_train_remat.py --describe
    PYTHONPATH=. python3 scripts/exp_train_remat.py --describe \
        --cell elastic-424 --chips 4
    chiprun --timeout 1500 -- env PYTHONPATH=. python3 \
        scripts/exp_train_remat.py --steps 8

``--describe`` compiles for a DESCRIBED v5e (no chip: memory and HLO
only, as ``tests/test_tpu_compile.py`` does); without it the chips of
the machine are used. A rung the compiler refuses prints its
``RESOURCE_EXHAUSTED`` line and the walk goes on.
"""

from __future__ import annotations

import argparse
import gc
import glob
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import jax.numpy as jnp
import numpy as np
import optax

from edl_tpu.api.job import MeshSpec
from edl_tpu.models import llama
from edl_tpu.parallel import remat
from edl_tpu.parallel.mesh import MeshPlan
from edl_tpu.train import trainer as tr

GIB = 2 ** 30
V5E_LIMIT = int(15.75 * GIB)
# layers, rows a chip, fsdp: benchmark/workloads and benchmark/traffic
CELLS = {"train-steady": (4, 4, 1), "elastic-424": (9, 2, 2)}
SEQ = 4096


def config(layers: int) -> llama.LlamaConfig:
    return llama.LlamaConfig(
        vocab=32768, d_model=4096, n_layers=layers, n_heads=32,
        n_kv_heads=8, d_ff=14336, rope_theta=1e6, norm_eps=1e-5,
        dtype=jnp.bfloat16, use_flash=True, remat=True)


def setting(cell: str, devices):
    layers, rows, fsdp = CELLS[cell]
    cfg = config(layers)
    plan = MeshPlan.from_spec(
        MeshSpec(fsdp=fsdp) if fsdp > 1 else MeshSpec(), len(devices))
    mesh = plan.build(devices)
    tx = optax.adafactor(1e-3)
    pspecs = llama.param_pspecs(cfg, plan)
    shape = jax.eval_shape(lambda: tr.TrainState.create(
        llama.init_params(jax.random.PRNGKey(0), cfg), tx))
    state_sh = tr._state_sharding(shape, plan, mesh, pspecs)
    state_sds = jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        shape, state_sh)
    batch_sds = {"tokens": jax.ShapeDtypeStruct(
        (rows * len(devices), SEQ + 1), jnp.int32,
        sharding=plan.batch_sharding(mesh))}
    # the trainer's own jit of its step, a fresh one a call
    step = tr.make_train_step(
        llama.make_loss_fn(cfg, plan, mesh), tx, plan, mesh, pspecs)

    def build():
        return step.build(state_sds, batch_sds)

    def make_state():
        return jax.jit(
            lambda: tr.TrainState.create(
                llama.init_params(jax.random.PRNGKey(0), cfg), tx),
            out_shardings=state_sh)()

    return cfg, plan, mesh, build, make_state, state_sds, batch_sds


def compile_rung(build, state, batch, rung: int):
    """(compiled, offer) of the step with ``rung`` entries of
    ``KEEP_ORDER`` kept: an offer with room for everything, backed off
    to the rung."""
    with remat.offer(1 << 60, len(llama.KEEP_ORDER) - rung) as offer:
        compiled = build().lower(state, batch).compile()
    return compiled, offer


def memory_line(compiled, limit: int) -> str:
    m = compiled.memory_analysis()
    peak = tr._peak_bytes(compiled)
    return (f"peak {peak / GIB:.3f} GiB, room {(limit - peak) / GIB:.3f}, "
            f"temp_size {m.temp_size_in_bytes / GIB:.3f}, arguments "
            f"{m.argument_size_in_bytes / GIB:.3f}")


def flash_fwd_calls(text: str) -> int:
    return len(re.findall(r"custom-call\(.*edl_flash_fwd", text))


def counted_in_trace(trace_dir: str, steps: int) -> str:
    """``edl_flash_fwd`` runs and ``mlp`` matmuls a step in the trace."""
    from benchmark.reduce import program, trace

    path = trace.find_xplane(trace_dir)
    if path is None:
        return "no trace"
    planes = program.load(path)
    ops = program.device_lines(planes, trace.OPS_LINE)[0]
    flash = sum(1 for ev in ops if "edl_flash_fwd" in ev[0])
    dots = sum(
        1 for ev in ops
        if program.scope_of(ev[3].get(program.OP_NAME_STAT, "")) == "mlp"
        and "dot_general" in ev[3].get(program.OP_NAME_STAT, ""))
    return (f"edl_flash_fwd {flash / steps:g} a step, mlp dot_general "
            f"{dots / steps:g} a step")


def run_on_chip(compiled, make_state, batch_sds, steps: int, name: str):
    rng = np.random.default_rng(0)

    def batch():
        sds = batch_sds["tokens"]
        return {"tokens": jax.device_put(
            rng.integers(0, 32768, sds.shape, dtype=np.int32), sds.sharding)}

    state = make_state()
    times = []
    for _ in range(steps):
        b = batch()
        t0 = time.perf_counter()
        state, m = compiled(state, b)
        loss = float(m["loss"])
        times.append(time.perf_counter() - t0)
    trace_dir = os.path.join("chiprun_out", "exp_train_remat", name)
    jax.profiler.start_trace(trace_dir)
    for _ in range(2):
        state, m = compiled(state, batch())
    jax.block_until_ready(m)
    jax.profiler.stop_trace()
    del state
    gc.collect()
    warm = sorted(times[2:])
    print(f"    step_ms median {1e3 * warm[len(warm) // 2]:.1f} "
          f"(min {1e3 * warm[0]:.1f}, first {1e3 * times[0]:.1f}), loss "
          f"{loss:.4f}; {counted_in_trace(trace_dir, 2)}", flush=True)
    for f in glob.glob(os.path.join(trace_dir, "**", "*"), recursive=True):
        if os.path.isfile(f):
            os.remove(f)  # the numbers are printed; traces are large


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", choices=sorted(CELLS), default="train-steady")
    ap.add_argument("--describe", action="store_true")
    ap.add_argument("--chips", type=int, default=0,
                    help="devices of the mesh (default: the cell's: 1 or 4)")
    ap.add_argument("--rungs", default="",
                    help="comma list of rungs to walk (default: all)")
    ap.add_argument("--steps", type=int, default=8)
    args = ap.parse_args()
    n = args.chips or (4 if args.cell == "elastic-424" else 1)
    if args.describe:
        jax.config.update("jax_enable_compilation_cache", False)
        from jax.experimental import topologies

        devices = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[:n]
        limit = V5E_LIMIT
    else:
        devices = jax.devices()[:n]
        limit = tr.device_bytes_limit(
            MeshPlan.from_spec(MeshSpec(), n).build(devices)) or V5E_LIMIT
        print(f"device {devices[0].device_kind}, bytes_limit "
              f"{limit / GIB:.3f} GiB", flush=True)
    cfg, plan, mesh, build, make_state, state_sds, batch_sds = setting(
        args.cell, devices)
    tokens = batch_sds["tokens"].shape[0] // plan.batch_shards() * SEQ
    shards = plan.axis_size("fsdp") * plan.axis_size("tp")
    working = llama.step_working_bytes(
        cfg, state_sds.params, tokens, shards)
    held = tr._device_nbytes(state_sds) + tr._device_nbytes(batch_sds)
    print(f"{args.cell} on {n} chips: state and batch {held / GIB:.3f} GiB "
          f"a chip, the model's estimate of its step beside them "
          f"{working / GIB:.3f} (so a peak of {(held + working) / GIB:.3f} "
          f"with nothing kept); candidates "
          + ", ".join(f"{'+'.join(names)} {b / GIB:.3f}" for names, b in
                      llama.keep_candidates(cfg, tokens, True)), flush=True)
    rungs = ([int(r) for r in args.rungs.split(",")] if args.rungs
             else range(len(llama.KEEP_ORDER) + 1))
    for rung in rungs:
        t0 = time.perf_counter()
        try:
            compiled, offer = compile_rung(build, state_sds, batch_sds, rung)
        except jax.errors.JaxRuntimeError as e:
            line = next((l for l in str(e).splitlines() if "Used" in l),
                        str(e).splitlines()[0])
            print(f"rung {rung}: refused: {line.strip()[:200]}", flush=True)
            continue
        print(f"rung {rung}: kept {','.join(offer.kept) or 'nothing'} "
              f"({offer.kept_bytes / GIB:.3f} GiB): "
              f"{memory_line(compiled, limit)}; edl_flash_fwd calls in the "
              f"HLO {flash_fwd_calls(compiled.as_text())}; compile "
              f"{time.perf_counter() - t0:.1f}s", flush=True)
        if not args.describe:
            try:
                run_on_chip(compiled, make_state, batch_sds, args.steps,
                            f"rung{rung}")
            except jax.errors.JaxRuntimeError as e:
                print(f"    run refused: {str(e).splitlines()[0][:300]}",
                      flush=True)
        del compiled
        gc.collect()
    # the trainer's own choice, by the way its step is really built
    if args.describe:
        tr.device_bytes_limit = lambda mesh: V5E_LIMIT
    kept = {}
    jitted = tr._fit_to_device(
        lambda s, b: build(), state_sds, batch_sds, mesh, kept)
    print(f"auto: {kept}", flush=True)
    if not args.describe:
        run_on_chip(jitted.lower(state_sds, batch_sds).compile(), make_state,
                    batch_sds, args.steps, "auto")
    return 0


if __name__ == "__main__":
    sys.exit(main())
