"""A name on everything the device runs, and compile telemetry from
JAX's own monitoring events: the names are metadata only (the programs
and their outputs are unchanged by them), they tell programs apart, and
the listener sees each program's stages under the program's own name."""

import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from edl_tpu.api.job import MeshSpec
from edl_tpu.models import llama
from edl_tpu.obs import compilewatch
from edl_tpu.obs import events as flight
from edl_tpu.obs import metrics as om
from edl_tpu.ops.flash_attention import interpret_kernels
from edl_tpu.parallel.mesh import MeshPlan
from edl_tpu.serving import engine as eng
from edl_tpu.train.trainer import TrainState, make_train_step

SCOPES = ("embed", "attn", "mlp", "head", "loss", "optimizer")
KERNELS = ("edl_flash_fwd", "edl_flash_bwd_dq", "edl_flash_bwd_dkv")


@pytest.fixture(autouse=True)
def _fresh_warmup():
    compilewatch.reset()
    yield
    compilewatch.reset()


def _tiny():
    return dataclasses.replace(
        llama.LlamaConfig.tiny(vocab=128), use_flash=True, remat=True)


def _train_step(cfg):
    plan = MeshPlan.from_spec(MeshSpec(), 1)
    mesh = plan.build(jax.devices()[:1])
    tx = optax.adafactor(1e-3)
    step = make_train_step(
        llama.make_loss_fn(cfg, plan, mesh), tx, plan, mesh,
        llama.param_pspecs(cfg, plan), donate=False)
    state = TrainState.create(
        llama.init_params(jax.random.PRNGKey(0), cfg), tx)
    batch = llama.synthetic_tokens(np.random.RandomState(0), 2, 128, cfg.vocab)
    return step, state, batch


def _scopes_in(text):
    """The named scopes in the locations of a lowered module."""
    found = set()
    for path in re.findall(r'loc\("([^"]+)"', text):
        # a component is a scope bare, or inside the marks of a
        # transformation: "mlp", "jvp(head)", "transpose(jvp(loss))"
        for part in path.split("/"):
            m = re.fullmatch(r"(?:[a-z]+\()*([a-z]+)\)*", part)
            if m and m.group(1) in SCOPES:
                found.add(m.group(1))
    return found


def test_train_step_carries_its_name_the_phases_and_the_kernels():
    step, state, batch = _train_step(_tiny())
    with interpret_kernels():
        step(state, batch)
        lowered = step.program[0].lower(state, batch)
    text = lowered.as_text(debug_info=True)
    assert "edl_train_step" in text
    assert _scopes_in(text) == set(SCOPES)
    for kernel in KERNELS:
        assert kernel in text, kernel
    # and they reach what the device's events are named by
    hlo = lowered.compile().as_text()
    assert hlo.startswith("HloModule jit_edl_train_step")
    ops = set(re.findall(r'op_name="([^"]+)"', hlo))
    for scope in SCOPES:
        assert any(re.search(rf"[/(]{scope}[/)]", o) for o in ops), scope


def test_names_are_metadata_only(monkeypatch):
    """The same step with every ``named_scope`` taken out again lowers
    to the same module, location info aside, and gives the same bits."""
    cfg = _tiny()

    def run():
        step, state, batch = _train_step(cfg)
        with interpret_kernels():
            new_state, metrics = step(state, batch)
            text = step.program[0].lower(state, batch).as_text()
        leaves = jax.tree_util.tree_leaves((new_state.params, metrics))
        return text, [np.asarray(x) for x in leaves]

    named_text, named = run()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    monkeypatch.setattr(llama, "_mlp", llama._mlp.__wrapped__)
    plain_text, plain = run()
    assert named_text == plain_text
    assert all((a == b).all() for a, b in zip(named, plain))
    # the comparison compared something: the patched run has no scopes
    step, state, batch = _train_step(cfg)
    with interpret_kernels():
        step(state, batch)
        text = step.program[0].lower(state, batch).as_text(debug_info=True)
    assert _scopes_in(text) == set()


def test_engine_programs_are_told_apart_by_module_name():
    cfg = llama.LlamaConfig.tiny(vocab=128)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    kv = (cfg.n_layers, 2, 32, cfg.n_kv_heads, cfg.head_dim)
    vec = lambda dt: jnp.zeros((2,), dt)  # noqa: E731
    state = (vec(jnp.int32), vec(jnp.int32), vec(bool), vec(jnp.int32),
             vec(jnp.int32), jnp.zeros(kv, cfg.dtype), jnp.zeros(kv, cfg.dtype))
    key, temp = jax.random.PRNGKey(0), jnp.float32(1.0)
    names = []
    for tb in (8, 16):
        prog = eng._prefill_program(cfg, tb, False)
        text = prog.lower(
            params, jnp.zeros((1, tb), jnp.int32), jnp.int32(tb - 1),
            jnp.int32(0), jnp.int32(4), jnp.int32(-1), *state, key, temp,
        ).as_text(debug_info=True)
        names.append(re.search(r"module @(\S+)", text).group(1))
        assert _scopes_in(text) >= {"embed", "attn", "mlp", "head"}
    block = eng._block_program(cfg, 2, 32, 1, False)
    tok, pos, act, rem, eosv, kc, vc = state
    text = block.lower(
        params, tok, pos, act, rem, eosv, kc, vc, key, temp
    ).as_text(debug_info=True)
    names.append(re.search(r"module @(\S+)", text).group(1))
    assert names == ["jit_edl_serve_prefill_8", "jit_edl_serve_prefill_16",
                     "jit_edl_serve_block"]
    assert _scopes_in(text) >= {"embed", "attn", "mlp", "head"}


# ---------------------------------------------------------------------------
# the compile listener


def _stage_counts(reg, program):
    fam = reg.get("edl_compile_seconds")
    return {stage: fam.stats(program=program, stage=stage)["count"]
            for stage in ("trace", "lower", "backend", "cache_load")}


def test_first_call_lands_under_the_functions_own_name_and_no_later_one():
    reg = om.reset_default_registry()

    @jax.jit
    def edl_test_program_a(x):
        return jnp.sin(x) * 2

    x = jnp.arange(4.0)
    x.block_until_ready()  # its own programs are not what is counted
    edl_test_program_a(x)
    first = _stage_counts(reg, "edl_test_program_a")
    assert first == {"trace": 1, "lower": 1, "backend": 1, "cache_load": 0}
    assert reg.get("edl_compiles_total").value(
        program="edl_test_program_a") == 1
    fam = reg.get("edl_compile_seconds")
    assert fam.stats(program="edl_test_program_a", stage="backend")["sum"] > 0
    edl_test_program_a(x)
    edl_test_program_a(x + 1)
    assert _stage_counts(reg, "edl_test_program_a") == first
    # another shape is another program under the same name
    edl_test_program_a(jnp.arange(8.0))
    assert reg.get("edl_compiles_total").value(
        program="edl_test_program_a") == 2
    om.reset_default_registry()


def test_a_new_program_after_mark_warm_is_an_obs_recompile():
    om.reset_default_registry()
    rec = flight.default_recorder()
    rec.clear()

    @jax.jit
    def edl_test_program_b(x):
        return x + 1

    @jax.jit
    def edl_test_program_c(x):
        return x - 1

    x = jnp.arange(4.0)
    edl_test_program_b(x)
    assert "obs.recompile" not in [r["kind"] for r in rec.records()]
    compilewatch.mark_warm()
    edl_test_program_b(x)  # built already: silent
    edl_test_program_c(x)
    evs = [r for r in rec.records() if r["kind"] == "obs.recompile"]
    assert [e["attrs"]["program"] for e in evs] == ["edl_test_program_c"]
    assert evs[0]["severity"] == "warn"
    assert evs[0]["attrs"]["seconds"] > 0
    assert evs[0]["attrs"]["cache_hit"] is False
    om.reset_default_registry()


def test_window_sums_the_stages_of_what_is_built_while_it_is_open():
    om.reset_default_registry()

    @jax.jit
    def edl_test_inner(x):
        return x * 3

    @jax.jit
    def edl_test_outer(x):
        return edl_test_inner(x) + 1

    x = jnp.arange(4.0)
    with compilewatch.Window() as before:
        pass
    with compilewatch.Window() as w:
        edl_test_outer(x)
    assert (before.programs, before.trace_s) == (0, 0.0)
    assert not before.cache_hit
    # the inner jit is traced inside the outer one: one program, and
    # its trace is counted once
    assert w.programs == 1 and w.cache_hits == 0
    assert w.trace_s > 0 and w.lower_s > 0 and w.load_s > 0
    reg = om.default_registry()
    assert _stage_counts(reg, "edl_test_outer")["trace"] == 1
    assert _stage_counts(reg, "edl_test_inner")["trace"] == 0
    with compilewatch.Window() as after:
        edl_test_outer(x)
    assert after.programs == 0
    om.reset_default_registry()
