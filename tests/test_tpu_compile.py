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


# -- the ragged decode attention (ops/decode_attention.py) -------------------

# the two serving cells of BENCHMARK.json: DeepSeek-7B at 12 layers (MHA,
# 16 slots) and Mistral-7B at 16 (GQA-8, groups 4, 32 slots), 2048 long
DECODE_CELLS = {
    "deepseek7b-L12": (12, 16, 2048, 32, 1),
    "mistral7b-L16": (16, 32, 2048, 8, 4),
}


@pytest.mark.parametrize("cell", sorted(DECODE_CELLS))
def test_decode_attention_kernel_compiles_for_v5e(v5e, cell):
    """The kernel alone at a cell's real cache: it compiles (tiling,
    VMEM, the dynamic grid), takes the stacked cache as it is stored (the
    [S, KV, hd] -> [S * KV, hd] view is a bitcast, nothing cache-sized
    is a temporary) and is there under its name."""
    from edl_tpu.ops.decode_attention import decode_attention

    n_layers, b, s, kvh, groups = DECODE_CELLS[cell]
    one = SingleDeviceSharding(v5e[0])
    kc = _sds((n_layers, b, s, kvh, 128), jnp.bfloat16, one)
    compiled = jax.jit(decode_attention).lower(
        _sds((b, kvh, groups, 128), jnp.bfloat16, one), kc, kc,
        _sds((b,), jnp.int32, one), _sds((), jnp.int32, one),
    ).compile()
    text = compiled.as_text()
    assert "edl_decode_attn" in text and "tpu_custom_call" in text
    one_layer = b * s * kvh * 128 * 2
    assert compiled.memory_analysis().temp_size_in_bytes < one_layer // 16


def test_serve_open_block_reads_the_cache_through_the_kernel(v5e):
    """``edl_serve_block`` at serve-open's shape (Mistral-7B widths, 16
    layers, 32 slots x 2048, horizon 1, ``use_flash``): every layer's
    attention is ``edl_decode_attn``, the cache updates in place, and no
    operation produces a layer's worth of cache (the 32 ``slice``s of
    ``bf16[32,2048,8,128]`` that were 42.5% of this program's time)."""
    import re

    from edl_tpu.serving import engine

    one = SingleDeviceSharding(v5e[0])
    cfg = llama.LlamaConfig(
        vocab=32768, d_model=4096, n_layers=16, n_heads=32, n_kv_heads=8,
        d_ff=14336, rope_theta=1e6, norm_eps=1e-5, dtype=jnp.bfloat16,
        use_flash=True, remat=False,
    )
    params = jax.eval_shape(
        lambda: jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16),
            llama.init_params(jax.random.PRNGKey(0), cfg),
        )
    )
    params = jax.tree_util.tree_map(
        lambda x: _sds(x.shape, x.dtype, one), params
    )
    b, s = 32, 2048
    i32 = _sds((b,), jnp.int32, one)
    kc = _sds((cfg.n_layers, b, s, cfg.n_kv_heads, cfg.head_dim),
              cfg.dtype, one)
    compiled = engine._block_program(cfg, b, s, 1, False).lower(
        params, i32, i32, _sds((b,), jnp.bool_, one), i32, i32, kc, kc,
        _sds((2,), jnp.uint32, one), _sds((), jnp.float32, one),
    ).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == cfg.n_layers
    assert "edl_decode_attn" in text
    # result shapes of every instruction: `%name = bf16[..]{layout} op(`
    made = re.findall(r"= (bf16\[[\d,]+\])\S* ([\w\-]+)\(", text)
    layer = "bf16[32,2048,8,128]"
    assert not [op for shape, op in made if shape == layer], (
        "a layer of the cache is materialised")
    whole = "bf16[16,32,2048,8,128]"
    assert not [op for shape, op in made
                if shape == whole and op in ("copy", "slice", "transpose")]
    mem = compiled.memory_analysis()
    cache_bytes = 2 * kc.size * 2
    assert mem.alias_size_in_bytes >= cache_bytes  # updated in place
    assert mem.temp_size_in_bytes < cache_bytes // 8
