"""The chip's compiler, asked without the chip: the main path's kernels
and one serving program are compiled at flagship widths for a DESCRIBED
v5e topology (on-chip-measurement guide §2, third rehearsal). What
interpret mode cannot show — tiling, VMEM, a kernel GSPMD cannot
partition — fails here, at no chip time. A compile that passes is not a
chip run; chip_smoke.py is.

Skipped where the topology cannot be described (no TPU compiler
installed). The persistent compile cache is switched off around the
compiles: an entry written for a described device cannot be read back.
"""

import dataclasses
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp
# describing a topology loads libtpu, which otherwise takes a machine-wide
# lock: test workers side by side (xdist) would skip all but one
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from edl_tpu.models import llama
from edl_tpu.ops.flash_attention import flash_attention
from edl_tpu.parallel.mesh import MeshPlan


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu / no compiler for this platform
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    return topo.devices


@pytest.fixture(autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# the flagship attention shape at T2048 and the long-context rung at
# T8192, each with the block setting attention_auto picks for it
@pytest.mark.parametrize("direction", ["fwd", "fwd_bwd"])
@pytest.mark.parametrize(
    "t,block_q,block_k", [(2048, 512, 1024), (8192, 1024, 1024)]
)
def test_flash_kernel_compiles_for_v5e(v5e, t, block_q, block_k, direction):
    one = SingleDeviceSharding(v5e[0])
    q = _sds((4, t, 16, 128), jnp.bfloat16, one)
    kv = _sds((4, t, 8, 128), jnp.bfloat16, one)

    def fwd(q, k, v):
        return flash_attention(q, k, v, block_q=block_q, block_k=block_k)

    fn = fwd if direction == "fwd" else jax.grad(
        lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(), (0, 1, 2)
    )
    compiled = jax.jit(fn).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _flagship_serving():
    cfg = dataclasses.replace(llama.LlamaConfig.flagship(), remat=False)
    params = jax.eval_shape(
        lambda: jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16),
            llama.init_params(jax.random.PRNGKey(0), cfg),
        )
    )
    return cfg, params


def test_fused_decode_block_compiles_for_v5e_and_donates_the_cache(v5e):
    """``edl serve --horizon 8`` at slots 8 x max_len 256: the engine's
    own block program, donation included (the cache must alias)."""
    from edl_tpu.serving import engine

    one = SingleDeviceSharding(v5e[0])
    cfg, params = _flagship_serving()
    params = jax.tree_util.tree_map(
        lambda x: _sds(x.shape, x.dtype, one), params
    )
    b, s = 8, 256
    i32 = _sds((b,), jnp.int32, one)
    kc = _sds(
        (cfg.n_layers, b, s, cfg.n_kv_heads, cfg.head_dim), cfg.dtype, one
    )
    program = engine._block_program(cfg, b, s, 8, False)
    compiled = program.lower(
        params, i32, i32, _sds((b,), jnp.bool_, one), i32, i32, kc, kc,
        _sds((2,), jnp.uint32, one), _sds((), jnp.float32, one),
    ).compile()
    mem = compiled.memory_analysis()
    cache_bytes = 2 * kc.size * jnp.dtype(cfg.dtype).itemsize
    assert mem.alias_size_in_bytes >= cache_bytes  # updated in place
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_flash_under_a_mesh_runs_per_shard(v5e):
    """GSPMD cannot partition a Mosaic kernel — the TPU compiler refuses
    a sharded program that calls one bare. With the mesh in hand the
    model runs the kernel per shard (llama._flash_per_shard); one
    flagship-width layer forward compiles on the 2x2 mesh the four-chip
    smoke uses, and without the mesh the refusal is loud."""
    cfg = dataclasses.replace(llama.LlamaConfig.flagship(), n_layers=1)
    plan = MeshPlan.create(dp=2, fsdp=2)
    mesh = plan.build(v5e)
    shapes = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg)
    )
    params = jax.tree_util.tree_map(
        lambda x, spec: _sds(x.shape, x.dtype, NamedSharding(mesh, spec)),
        shapes, llama.param_pspecs(cfg, plan),
        is_leaf=lambda x: isinstance(x, P),
    )
    tokens = _sds((8, 2048), jnp.int32, plan.batch_sharding(mesh))
    compiled = jax.jit(
        lambda p, t: llama.forward(p, t, cfg, mesh=mesh, plan=plan)
    ).lower(params, tokens).compile()
    assert "tpu_custom_call" in compiled.as_text()
    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.jit(lambda p, t: llama.forward(p, t, cfg)).lower(params, tokens)
