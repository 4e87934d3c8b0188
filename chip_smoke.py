#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main paths once, through the entry points a user would call,
at the full width of the flagship model (depth and step counts are what
is cut), and checks what comes out by the repo's own means:

    python chip_smoke.py            one chip: train, serve, serve_h8, process
    python chip_smoke.py --chips 4  four chips: the elastic sharded trainer
                                    (dp2 x fsdp2, 4 -> 2 -> 4) and the
                                    one-device run it is compared with;
                                    no other phase

* train    — ``ElasticTrainer`` at flagship width (T2048, adafactor,
  per-chip batch 16): finite falling loss, the compiled step holds the
  Pallas kernel, checkpoint -> fresh trainer ``resume`` -> same loss as
  the uninterrupted trainer, bf16 ``export_params``.
* serve / serve_h8 — ``edl serve <that export>`` through
  ``edl_tpu.cli.main`` (defaults, then ``--horizon 8``; two processes,
  so the second finds the first's programs in the compile cache):
  every request done, zero recoveries, first tokens equal to
  ``llama.generate``'s on the same export.
* process  — one ``worker_main`` under ``ProcessJobLauncher`` and a
  coordinator built from source here, CTR at its production vocabulary:
  leased tasks, a checkpoint, an export, clean exit.

The parent never initialises a JAX backend: a chip belongs to one
process at a time, so each phase is one child process at a time and the
device line is what the children report. Without a TPU the first child
fails at the device gate and the script exits non-zero; no phase's
failure is caught while the script still exits 0.

``--rehearse`` walks the same control flow at toy size with the Pallas
interpreter, for a machine with no chip. It can never print
``"ok": true``.

Last line of stdout on success, and nothing else on that line:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import hashlib
import io
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
RESULT_TAG = "RESULT "
# the driver allows 1200 s, compilation included
TOTAL_BUDGET_S = 1140.0
PHASES = {1: ("train", "serve", "serve_h8", "process"), 4: ("elastic4",)}


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What a phase runs at. ``full`` is the contract's size; ``toy`` is
    the CPU rehearsal's."""

    seq: int
    batch: int  # per-chip batch of the one-chip train phase
    batch4: int  # per-chip batch of the four-chip phase
    mid_steps: int  # timed steps between the first and the last
    n_requests: int
    prompt_lo: int
    prompt_hi: int
    max_new: int
    max_len: int
    ctr_vocab: int
    ctr_batch: int
    ctr_steps: int

    @staticmethod
    def of(rehearse: bool) -> "Sizes":
        if rehearse:
            return Sizes(seq=128, batch=4, batch4=2, mid_steps=2,
                         n_requests=4, prompt_lo=8, prompt_hi=24, max_new=8,
                         max_len=64, ctr_vocab=4096, ctr_batch=64,
                         ctr_steps=4)
        return Sizes(seq=2048, batch=16, batch4=4, mid_steps=4,
                     n_requests=8, prompt_lo=64, prompt_hi=192, max_new=32,
                     max_len=256, ctr_vocab=2 ** 20, ctr_batch=8192,
                     ctr_steps=6)


def model_config(rehearse: bool):
    from edl_tpu.models import llama

    if rehearse:
        return dataclasses.replace(
            llama.LlamaConfig.tiny(vocab=512), use_flash=True, remat=True
        )
    return llama.LlamaConfig.flagship()


# ---------------------------------------------------------------------------
# what every JAX-holding child does first and last


def start_jax(args):
    """Compile cache on, then the device gate: the FIRST backend act is
    ``jax.devices()`` and anything but the expected TPUs is a failure.
    Returns the device record the parent prints."""
    import jax

    from edl_tpu.obs import costmodel
    from edl_tpu.utils import jaxcache

    cache_dir = jaxcache.configure()
    n_cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"  compile cache: {cache_dir} ({n_cached} entries at start)",
          flush=True)
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"  devices: {json.dumps(dev)}", flush=True)
    try:
        check(dev["platform"] == "tpu", "platform is tpu")
        check(dev["count"] == args.chips, f"{args.chips} device(s) visible")
        # an unknown kind raises here: no assumed peak on this path
        peak = costmodel.peak_for_device(devs[0])
        check(True, f"peak table knows {dev['kind']!r} ({peak.kind})")
    except (SmokeFailure, KeyError) as e:
        if not args.rehearse:
            raise SmokeFailure(f"device gate: {e}") from e
        print(f"  device gate FAILED ({e}); rehearsing on regardless",
              flush=True)
    return dev


def compile_seconds(program: str) -> float:
    """Seconds the process spent building (trace, lower, compile or
    cache load) the programs whose name starts with ``program``, from
    the repo's own compile watch."""
    from edl_tpu.obs import metrics as obs_metrics

    fam = obs_metrics.default_registry().get("edl_compile_seconds")
    if not fam:
        return 0.0
    at = fam.labelnames.index("program")
    return float(sum(s.sum for key, s in fam.samples()
                     if key[at].startswith(program)))


def device_memory_line() -> str:
    import jax

    parts = []
    for d in jax.devices():
        st = d.memory_stats() or {}
        parts.append(
            f"{d.id}: in_use={st.get('bytes_in_use', 'n/a')} "
            f"peak={st.get('peak_bytes_in_use', 'n/a')}"
        )
    return "; ".join(parts)


def kernels(args):
    """The interpreter, when — and only when — this is a rehearsal."""
    if args.rehearse:
        from edl_tpu.ops.flash_attention import interpret_kernels

        return interpret_kernels()
    return contextlib.nullcontext()


def make_trainer(cfg, per_chip_batch, mesh_spec=None, devices=None,
                 checkpoint_dir=None):
    import optax

    from edl_tpu.models import llama
    from edl_tpu.runtime.elastic import ElasticTrainer

    return ElasticTrainer(
        None,
        optax.adafactor(1e-3),
        mesh_spec=mesh_spec,
        per_chip_batch=per_chip_batch,
        param_pspecs=lambda plan: llama.param_pspecs(cfg, plan),
        make_loss=lambda plan, mesh: llama.make_loss_fn(cfg, plan, mesh),
        devices=devices,
        checkpoint_dir=checkpoint_dir,
    )


def one_step(trainer, batch):
    """One update through the trainer's own loop; (loss, seconds)."""
    t0 = time.perf_counter()
    report = trainer.train_steps(lambda n: batch, 1)
    return report.losses[-1], time.perf_counter() - t0


def compiled_step_text(trainer, batch) -> str:
    """Lower the trainer's own jitted step again and return what the
    compiler made of it."""
    from edl_tpu.train.trainer import global_batch

    jitted = trainer._step_fn.program[0]
    dev_batch = global_batch(batch, trainer.plan, trainer.mesh)
    return jitted.lower(trainer.state, dev_batch).compile().as_text()


def drop(trainer) -> None:
    """Free a trainer's device state (the flagship step leaves under a
    gigabyte beside it)."""
    import gc

    trainer.state = None
    trainer._step_fn = None
    trainer._built.clear()  # the step (and its executable) kept per mesh
    gc.collect()


# ---------------------------------------------------------------------------
# phase: train (one chip)


def check_kernel_against_oracle(args) -> None:
    """flash_attention against the dense f32 oracle on a small input at
    the flagship head layout — forward and gradients, at a tiled length
    and at an odd one (serving prefills whole-sequence blocks)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from edl_tpu.ops.flash_attention import attention_auto
    from edl_tpu.parallel.ring_attention import reference_attention

    h, kv, d = (4, 2, 16) if args.rehearse else (16, 8, 128)
    for t in (32, 24) if args.rehearse else (256, 150):
        ks = jax.random.split(jax.random.PRNGKey(args.seed + t), 3)
        q = jax.random.normal(ks[0], (1, t, h, d), jnp.bfloat16)
        k = jax.random.normal(ks[1], (1, t, kv, d), jnp.bfloat16)
        v = jax.random.normal(ks[2], (1, t, kv, d), jnp.bfloat16)

        def oracle(q, k, v):
            rep = lambda x: jnp.repeat(x.astype(jnp.float32), h // kv, 2)
            return reference_attention(q.astype(jnp.float32), rep(k), rep(v))

        def loss(f):
            return lambda q, k, v: jnp.sum(
                f(q, k, v).astype(jnp.float32) ** 2
            )

        with kernels(args):
            out = attention_auto(q, k, v)
            grads = jax.grad(loss(attention_auto), (0, 1, 2))(q, k, v)
        ref = oracle(q, k, v)
        ref_grads = jax.grad(loss(oracle), (0, 1, 2))(q, k, v)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
        check(np.isfinite(err) and err < 3e-2,
              f"flash kernel == oracle at T={t} (max abs err {err:.2e})")
        for name, g, rg in zip("qkv", grads, ref_grads):
            g = np.asarray(g.astype(jnp.float32))
            rg = np.asarray(rg)
            rel = float(np.max(np.abs(g - rg)) / (np.max(np.abs(rg)) + 1e-9))
            check(np.isfinite(rel) and rel < 5e-2,
                  f"  d{name} == oracle at T={t} (rel err {rel:.2e})")


def phase_train(args, sz: Sizes) -> dict:
    dev = start_jax(args)
    import jax
    import numpy as np

    from edl_tpu.models import llama
    from edl_tpu.runtime.export import export_params

    cfg = model_config(args.rehearse)
    check_kernel_against_oracle(args)
    rng = np.random.RandomState(args.seed)
    batches = [
        llama.synthetic_tokens(rng, sz.batch, sz.seq, cfg.vocab)
        for _ in range(sz.mid_steps + 1)
    ]
    key = jax.random.PRNGKey(args.seed)
    ckpt_dir = os.path.join(args.workdir, "ckpt")

    print(f"  model: {cfg.to_meta()} remat={cfg.remat}", flush=True)
    print(f"  batch {sz.batch}/chip x T{sz.seq}, adafactor(1e-3)", flush=True)
    a = make_trainer(cfg, sz.batch, checkpoint_dir=ckpt_dir)
    a.start(llama.init_params(key, cfg), n_workers=1)
    with kernels(args):
        first_loss, cold_s = one_step(a, batches[0])
    compile_cold = compile_seconds("edl_train_step")
    print(f"  step 0: loss={first_loss:.6f} seconds={cold_s:.2f} "
          f"(first call: trace+compile {compile_cold:.2f}s)", flush=True)
    if args.rehearse:
        print("  (rehearsal: interpreter, no tpu_custom_call to look for)")
    else:
        check("tpu_custom_call" in compiled_step_text(a, batches[0]),
              "compiled train step holds the Pallas kernel (tpu_custom_call)")
    losses, secs = [first_loss], []
    for i in range(1, sz.mid_steps + 1):
        loss, s = one_step(a, batches[i])
        losses.append(loss)
        secs.append(s)
        print(f"  step {i}: loss={loss:.6f} seconds={s:.3f}", flush=True)
    # the first batch again: same data, so "lower" is the optimizer's doing
    last_loss, s = one_step(a, batches[0])
    losses.append(last_loss)
    print(f"  step {sz.mid_steps + 1}: loss={last_loss:.6f} seconds={s:.3f} "
          f"(first batch again)", flush=True)
    check(all(np.isfinite(x) for x in losses), "loss finite every step")
    check(last_loss < first_loss,
          f"loss fell on the first batch ({first_loss:.4f} -> {last_loss:.4f})")
    step_s = float(np.median(secs))
    tok_s = sz.batch * sz.seq / step_s
    print(f"  steady step: median {step_s:.3f}s of {len(secs)} "
          f"({tok_s:,.0f} tokens/s/chip)", flush=True)
    print(f"  device memory after steps: {device_memory_line()}", flush=True)

    # the one-chip half of the elastic contract
    t0 = time.perf_counter()
    path = a.maybe_checkpoint(force=True)
    check(path is not None and os.path.exists(os.path.join(path, "state.npz")),
          f"checkpoint written ({time.perf_counter() - t0:.1f}s): {path}")
    loss_a, _ = one_step(a, batches[1])
    drop(a)
    b = make_trainer(cfg, sz.batch)
    t0 = time.perf_counter()
    b.resume(llama.init_params(key, cfg), n_workers=1, checkpoint_path=path)
    print(f"  fresh trainer resumed in {time.perf_counter() - t0:.1f}s",
          flush=True)
    with kernels(args):
        loss_b, warm_s = one_step(b, batches[1])
    compile_warm = compile_seconds("edl_train_step") - compile_cold
    print(f"  resumed step: loss={loss_b:.6f} seconds={warm_s:.2f} "
          f"(the same program's first call from a new trainer: trace+compile "
          f"{compile_warm:.2f}s, served by the compile cache)", flush=True)
    check(loss_b == loss_a,
          f"resumed trainer's loss == uninterrupted trainer's ({loss_a!r})")

    t0 = time.perf_counter()
    export_dir = os.path.join(args.workdir, "export")
    out = export_params(
        export_dir, b.merged_state.params, step=b._host_step,
        dtype="bfloat16", source="chip_smoke", model_meta=cfg.to_meta(),
    )
    check(os.path.exists(os.path.join(out, "params.npz")),
          f"bf16 export written ({time.perf_counter() - t0:.1f}s): {out}")
    print(f"  device memory at end: {device_memory_line()}", flush=True)
    return {
        "device": dev, "batch_per_chip": sz.batch,
        "first_step_cold_s": round(cold_s, 3),
        "first_step_warm_s": round(warm_s, 3),
        "compile_cold_s": round(compile_cold, 3),
        "compile_warm_s": round(compile_warm, 3),
        "step_s": round(step_s, 4), "tokens_per_s_per_chip": round(tok_s, 1),
        "loss_first": first_loss, "loss_last": last_loss,
    }


# ---------------------------------------------------------------------------
# phase: serve (one chip; two passes, two processes)


def make_requests(args, sz: Sizes, vocab: int):
    import numpy as np

    rng = np.random.RandomState(args.seed + 1)
    return [
        {"id": f"r{i}", "max_new": sz.max_new,
         "prompt": [int(t) for t in rng.randint(
             0, vocab, rng.randint(sz.prompt_lo, sz.prompt_hi + 1))]}
        for i in range(sz.n_requests)
    ]


def phase_serve(args, sz: Sizes, horizon: int) -> dict:
    """``edl serve`` on the train phase's export. The first pass (default
    horizon) also makes the reference with ``llama.generate``; the
    second (``--horizon 8``) is held to the same reference."""
    dev = start_jax(args)
    import jax
    import numpy as np

    from edl_tpu.cli.main import main as edl
    from edl_tpu.models import llama
    from edl_tpu.obs import metrics as obs_metrics
    from edl_tpu.runtime.export import export_status, load_export

    export_dir = os.path.join(args.workdir, "export")
    cfg = llama.LlamaConfig.from_meta(export_status(export_dir)["model"])
    reqs = make_requests(args, sz, cfg.vocab)
    feed = os.path.join(args.workdir, "requests.jsonl")
    with open(feed, "w") as f:
        f.write("".join(json.dumps(r) + "\n" for r in reqs))

    argv = ["serve", export_dir, "--requests", feed, "--max-slots", "8",
            "--max-len", str(sz.max_len)]
    if horizon != 1:
        argv += ["--horizon", str(horizon)]
    print(f"  edl {' '.join(argv)}", flush=True)
    out = io.StringIO()
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(out), kernels(args):
        rc = edl(argv)
    serve_s = time.perf_counter() - t0
    check(rc == 0, f"edl serve exit code 0 ({serve_s:.1f}s)")
    recs = {r["id"]: r for r in map(json.loads, out.getvalue().splitlines())}
    check(sorted(recs) == sorted(r["id"] for r in reqs),
          f"all {len(reqs)} requests answered")
    check(all(r["outcome"] == "done" and len(r["tokens"]) == sz.max_new
              for r in recs.values()),
          f"every request done with {sz.max_new} tokens "
          f"(no failed/timeout outcome)")
    reg = obs_metrics.default_registry()
    # the registry counter is what ServingMetrics.snapshot()["recoveries"]
    # counts: on_recovery() bumps both
    recoveries = reg.get("edl_serving_recoveries_total").value()
    check(recoveries == 0, "recoveries == 0 (no dispatch was refused)")
    tokens = sz.n_requests * sz.max_new
    c_pre, c_blk = compile_seconds("edl_serve_prefill"), compile_seconds("edl_serve_block")
    print(f"  {tokens} tokens in {serve_s:.2f}s incl. load+compile; "
          f"first calls: prefill {c_pre:.2f}s, decode block {c_blk:.2f}s; "
          f"ttft_s per request: "
          f"{[recs[r['id']]['ttft_s'] for r in reqs]}", flush=True)

    ref_path = os.path.join(args.workdir, "reference.json")
    if horizon == 1:
        t0 = time.perf_counter()
        ref = {}
        params = jax.device_put(load_export(export_dir)[0])
        with kernels(args):
            for r in reqs:
                toks = llama.generate(
                    params, np.asarray([r["prompt"]], np.int32), cfg,
                    max_new=sz.max_new,
                )
                ref[r["id"]] = [int(t) for t in np.asarray(toks)[0]]
        print(f"  llama.generate reference: {time.perf_counter() - t0:.1f}s "
              f"(first calls {compile_seconds('edl_llama_generate'):.2f}s)",
              flush=True)
        with open(ref_path, "w") as f:
            json.dump(ref, f)
    else:
        with open(ref_path) as f:
            ref = json.load(f)
    firsts = [recs[i]["tokens"][0] == ref[i][0] for i in ref]
    same = sum(
        a == b for i in ref for a, b in zip(recs[i]["tokens"], ref[i])
    )
    print(f"  identical to llama.generate: {same}/{tokens} tokens "
          f"({same / tokens:.1%}; printed, not gated)", flush=True)
    check(all(firsts), "first generated token == llama.generate's, "
          "every request")
    print(f"  device memory at end: {device_memory_line()}", flush=True)
    return {"device": dev, "horizon": horizon, "serve_s": round(serve_s, 3),
            "compile_prefill_s": round(c_pre, 3),
            "compile_block_s": round(c_blk, 3),
            "identical_token_share": round(same / tokens, 4)}


# ---------------------------------------------------------------------------
# phase: process runtime (one chip; this child stays off the backend)


def report_built(path: str, since: float) -> None:
    with open(path, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    check(os.path.getmtime(path) >= since - 1,
          f"built from source just now: {path} (sha256 {digest})")


def phase_process(args, sz: Sizes) -> dict:
    # a clean build directory, named before the bindings are imported:
    # they build into it from source on first use, so a stale .so that
    # came along with a copied tree cannot be what passes
    t0 = time.time()
    os.environ["EDL_NATIVE_BUILD_DIR"] = os.path.join(args.workdir, "native")
    from edl_tpu.runtime import coordinator
    from edl_tpu.runtime.checkpoint import latest_manifest
    from edl_tpu.runtime.export import export_status
    from edl_tpu.runtime.launcher import ProcessJobLauncher
    from edl_tpu.scheduler import native as native_sched

    check(coordinator.ensure_native_built(),
          f"native coordinator builds ({time.time() - t0:.1f}s)")
    report_built(coordinator._LIB_PATH, t0)
    report_built(coordinator._BIN_PATH, t0)
    check(native_sched.available(), "native scheduler builds and loads")
    report_built(native_sched._LIB_PATH, t0)
    check("jax" not in sys.modules or not _backend_initialised(),
          "launcher process holds no JAX backend")
    launcher = ProcessJobLauncher(
        job="smoke", model="ctr", mesh="dp", min_workers=1, max_workers=1,
        n_samples=sz.ctr_batch * sz.ctr_steps, per_device_batch=sz.ctr_batch,
        local_devices=0,  # the real backend, whatever the worker finds
        work_dir=os.path.join(args.workdir, "job"), ckpt_every=2, export=True,
        # a lease must outlive the worker's first compile on the chip
        lease_timeout_s=240.0, member_ttl_s=20.0, seed=args.seed,
        extra_env={"EDL_VOCAB": str(sz.ctr_vocab)},
    )
    with launcher:
        launcher.start(1)
        try:
            rcs = launcher.wait(timeout_s=420)
        finally:
            print("  --- worker log tail ---", flush=True)
            print(launcher.log_tail("w000", 3000), flush=True)
        check(rcs == {"w000": 0}, f"worker exit codes {rcs}")
        check(launcher.kv("phase") == "succeeded", "job phase == succeeded")
        platform, kind, count = launcher.kv("devices").split("|")
        dev = {"platform": platform, "kind": kind, "count": int(count)}
        print(f"  worker devices: {json.dumps(dev)}", flush=True)
        if not args.rehearse:
            check(platform == "tpu" and int(count) == args.chips,
                  "the worker trained on the chip")
        stats = launcher.client.queue_stats()
        check(stats["done"] == sz.ctr_steps and stats["todo"] == 0
              and stats["leased"] == 0 and stats["dead"] == 0,
              f"{sz.ctr_steps} leased tasks acked exactly once: {stats}")
        steps = launcher.progress()
        check(steps >= sz.ctr_steps, f"{steps} steps")
        l0, l1 = float(launcher.kv("loss_first")), float(launcher.kv("loss_last"))
        check(l0 == l0 and l1 == l1 and abs(l0) < 1e9 and abs(l1) < 1e9,
              f"loss finite ({l0:.5f} -> {l1:.5f})")
        man = latest_manifest(launcher.ckpt_dir)
        check(man is not None, f"checkpoint committed at step "
              f"{man and man['step']}")
        exp = export_status(launcher.export_dir)
        check(exp is not None and exp["model"]["family"] == "ctr"
              and exp["model"]["vocab"] == sz.ctr_vocab,
              f"export published at step {exp and exp['step']} "
              f"(vocab {sz.ctr_vocab}, emb {exp and exp['model']['emb']})")
    return {"device": dev, "steps": steps}


def _backend_initialised() -> bool:
    from jax._src import xla_bridge

    return bool(xla_bridge._backends)


# ---------------------------------------------------------------------------
# phase: elastic sharded training across four chips (--chips 4)


def phase_elastic4(args, sz: Sizes) -> dict:
    dev = start_jax(args)
    import jax
    import numpy as np

    from edl_tpu.api.job import MeshSpec
    from edl_tpu.models import llama

    cfg = model_config(args.rehearse)
    n = len(jax.devices())
    global_b = sz.batch4 * n
    rng = np.random.RandomState(args.seed)
    batch16 = llama.synthetic_tokens(rng, global_b, sz.seq, cfg.vocab)
    key = jax.random.PRNGKey(args.seed)

    # what it is compared with: the same first batch and seed on a
    # one-device pool, first — nothing else fits beside that step
    one = make_trainer(cfg, global_b, devices=[jax.devices()[0]])
    one.start(llama.init_params(key, cfg), n_workers=1)
    with kernels(args):
        loss_one, s = one_step(one, batch16)
    print(f"  one device, batch {global_b}: first-step loss={loss_one:.6f} "
          f"({s:.1f}s)", flush=True)
    drop(one)
    jax.clear_caches()

    events = []
    tr = make_trainer(cfg, sz.batch4, mesh_spec=MeshSpec(fsdp=2))
    tr.on_reshard = events.append
    tr.start(llama.init_params(key, cfg), n_workers=n)
    print(f"  mesh {tr.plan.describe()} over {n} devices, batch "
          f"{sz.batch4}/chip", flush=True)
    with kernels(args):
        loss_four, s = one_step(tr, batch16)
    print(f"  {n} devices: first-step loss={loss_four:.6f} ({s:.1f}s, "
          f"trace+compile {compile_seconds('edl_train_step'):.1f}s total so far)",
          flush=True)
    tol = 2.0 ** -8 * abs(loss_one)  # one bf16 ulp: another reduction order
    check(abs(loss_four - loss_one) <= tol,
          f"first-step losses agree to bf16 tolerance "
          f"(|{loss_four:.6f} - {loss_one:.6f}| <= {tol:.4f})")
    if not args.rehearse:
        check("tpu_custom_call" in compiled_step_text(tr, batch16),
              "sharded step holds the Pallas kernel")

    # proof the state is spread
    shard_shapes = {}
    for name in ("wq", "w1"):
        arr = tr.state.params["layers"][name]
        shards = arr.addressable_shards
        devs = {s.device.id for s in shards}
        shapes = {tuple(s.data.shape) for s in shards}
        want = tuple(arr.sharding.shard_shape(arr.shape))
        check(len(devs) == n and shapes == {want}
              and np.prod(want) * 2 == np.prod(arr.shape),
              f"{name} {tuple(arr.shape)}: {len(shards)} shards of {want} "
              f"on {len(devs)} distinct devices (fsdp2: half each)")
        shard_shapes[name] = want
    print(f"  device memory: {device_memory_line()}", flush=True)
    if not args.rehearse:
        used = [d.memory_stats()["bytes_in_use"] for d in jax.devices()]
        check(min(used) > 0 and max(used) < 1.5 * min(used),
              f"bytes_in_use non-zero and of similar size on all {n}: {used}")

    def params_digest():
        """sha256 of every parameter's bytes, gathered to the host."""
        return jax.tree_util.tree_map(
            lambda x: hashlib.sha256(
                np.ascontiguousarray(np.asarray(x)).view(np.uint8)
            ).hexdigest(),
            tr.merged_state.params,
        )

    losses = [loss_four]
    for target in (n // 2, n):
        before = params_digest()
        tr.request_rescale(target)
        tr._maybe_rescale()  # what train_steps does at the step boundary
        check(tr.n_workers == target,
              f"rescaled to {target} workers, mesh {tr.plan.describe()}")
        check(params_digest() == before,
              f"params bit-equal across the reshard to {target}")
        b = {"tokens": batch16["tokens"][: sz.batch4 * target]}
        with kernels(args):
            for _ in range(2):
                loss, s = one_step(tr, b)
                losses.append(loss)
                print(f"  {target} devices: loss={loss:.6f} seconds={s:.2f}",
                      flush=True)
    for ev in events:
        print(f"  ReshardEvent {ev.from_workers}->{ev.to_workers} "
              f"path={'host' if ev.fallback else 'device'} "
              f"stall_s={ev.stall_s:.3f} recompile_s={ev.recompile_s:.2f} "
              f"step_reused={ev.step_reused} at step {ev.step}", flush=True)
    check(len(events) == 2, "two reshards recorded")
    check(all(np.isfinite(x) for x in losses), "loss finite throughout")
    print(f"  device memory at end: {device_memory_line()}", flush=True)
    return {
        "device": dev, "loss_one_device": loss_one, "loss_sharded": loss_four,
        "reshards": [
            {"from": e.from_workers, "to": e.to_workers,
             "path": "host" if e.fallback else "device",
             "stall_s": round(e.stall_s, 4),
             "recompile_s": round(e.recompile_s, 3)} for e in events
        ],
    }


# ---------------------------------------------------------------------------
# child and parent


def run_phase(args) -> int:
    sz = Sizes.of(args.rehearse)
    t0 = time.perf_counter()
    fn = {
        "train": lambda: phase_train(args, sz),
        "serve": lambda: phase_serve(args, sz, horizon=1),
        "serve_h8": lambda: phase_serve(args, sz, horizon=8),
        "process": lambda: phase_process(args, sz),
        "elastic4": lambda: phase_elastic4(args, sz),
    }[args.phase]
    # no except: a phase's exception is the child's traceback and exit code
    result = fn()
    result["phase"] = args.phase
    result["seconds"] = round(time.perf_counter() - t0, 2)
    print(RESULT_TAG + json.dumps(result), flush=True)
    return 0


def run_child(args, phase: str, deadline: float):
    """One phase, one process, its output passed through as it comes.
    Returns the phase's RESULT record, or None if it did not deliver."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--workdir", args.workdir, "--chips", str(args.chips),
           "--seed", str(args.seed)]
    if args.rehearse:
        cmd.append("--rehearse")
    print(f"[{phase}] start", flush=True)
    t0 = time.perf_counter()
    proc = subprocess.Popen(cmd, cwd=REPO, stdout=subprocess.PIPE, text=True,
                            start_new_session=True)
    # the script's budget spent: the child's whole process group goes
    killer = threading.Timer(
        max(1.0, deadline - time.monotonic()), _kill_group, [proc]
    )
    killer.daemon = True
    killer.start()
    result = None
    try:
        for line in proc.stdout:
            if line.startswith(RESULT_TAG):
                result = json.loads(line[len(RESULT_TAG):])
            else:
                print(f"[{phase}] {line}", end="", flush=True)
        rc = proc.wait()
    finally:
        killer.cancel()
        _kill_group(proc)  # whatever the phase left behind
    dt = time.perf_counter() - t0
    if rc != 0 or result is None:
        print(f"[{phase}] FAILED after {dt:.1f}s (exit code {rc}"
              f"{', time limit' if time.monotonic() >= deadline else ''})",
              flush=True)
        return None
    print(f"[{phase}] passed in {dt:.1f}s", flush=True)
    return result


def _kill_group(proc) -> None:
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the cross-chip elastic phase and its "
                    "one-device comparison")
    ap.add_argument("--seed", type=int, default=0,
                    help="weights, data and prompts are made from it")
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes + Pallas interpreter, for a machine "
                    "with no chip; never reports ok")
    ap.add_argument("--phase", help=argparse.SUPPRESS)
    ap.add_argument("--workdir", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.phase:
        return run_phase(args)

    deadline = time.monotonic() + TOTAL_BUDGET_S
    args.workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    results, failed = [], None
    try:
        for phase in PHASES[args.chips]:
            res = run_child(args, phase, deadline)
            if res is None:
                failed = phase
                break
            results.append(res)
    finally:
        shutil.rmtree(args.workdir, ignore_errors=True)
    for r in results:
        print(f"summary {json.dumps(r)}", flush=True)
    devices = [r["device"] for r in results]
    if failed is None and any(d != devices[0] for d in devices):
        failed = f"phases disagree on the device: {devices}"
    if failed is None and args.rehearse:
        failed = "rehearsal (never a pass)"
    if failed is not None:
        print(json.dumps({"ok": False, "failed": failed}), flush=True)
        return 1
    print(json.dumps({"ok": True, "device": devices[0]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
