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

import collections
import dataclasses
import functools
import os
import re

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

# the two dense serving cells of BENCHMARK.json, by their configurations
# (benchmark/configs/<name>.json): (slots, the model's widths at the depth
# it is served at), the cache 2048 long. DeepSeek-7B is MHA, Mistral-7B
# GQA-8 (groups 4)
SERVING_CELLS = {
    "deepseek7b-L12": (16, dict(
        vocab=102400, d_model=4096, n_layers=12, n_heads=32, n_kv_heads=32,
        d_ff=11008, rope_theta=1e4, norm_eps=1e-6)),
    "mistral7b-L16": (32, dict(
        vocab=32768, d_model=4096, n_layers=16, n_heads=32, n_kv_heads=8,
        d_ff=14336, rope_theta=1e6, norm_eps=1e-5)),
}
CACHE_LEN = 2048


@pytest.mark.parametrize("cell", sorted(SERVING_CELLS))
def test_decode_attention_kernel_compiles_for_v5e(v5e, cell):
    """The kernel alone at a cell's real cache: it compiles (tiling,
    VMEM, the dynamic grid), takes the stacked cache as it is stored (the
    [S, KV, hd] -> [S * KV, hd] view is a bitcast, nothing cache-sized
    is a temporary) and is there under its name."""
    from edl_tpu.ops.decode_attention import decode_attention

    b, w = SERVING_CELLS[cell]
    n_layers, s, kvh = w["n_layers"], CACHE_LEN, w["n_kv_heads"]
    groups = w["n_heads"] // kvh
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


def _cell_block(v5e, cell):
    """``edl_serve_block`` compiled at a cell's shape (slots x 2048,
    horizon 1, ``use_flash``, a bf16 export): (cfg, cache spec,
    compiled)."""
    from edl_tpu.serving import engine

    one = SingleDeviceSharding(v5e[0])
    b, widths = SERVING_CELLS[cell]
    cfg = llama.LlamaConfig(
        dtype=jnp.bfloat16, use_flash=True, remat=False, **widths
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
    s = CACHE_LEN
    i32 = _sds((b,), jnp.int32, one)
    kc = _sds((cfg.n_layers, b, s, cfg.n_kv_heads, cfg.head_dim),
              cfg.dtype, one)
    compiled = engine._block_program(cfg, b, s, 1, False).lower(
        params, i32, i32, _sds((b,), jnp.bool_, one), i32, i32, kc, kc,
        _sds((2,), jnp.uint32, one), _sds((), jnp.float32, one),
    ).compile()
    return cfg, kc, compiled


@pytest.fixture(scope="module")
def cell_block(v5e):
    """:func:`_cell_block`, each cell's program compiled once a module."""
    return functools.lru_cache(maxsize=None)(
        functools.partial(_cell_block, v5e))


def test_serve_open_block_reads_the_cache_through_the_kernel(cell_block):
    """``edl_serve_block`` at serve-open's shape (Mistral-7B widths, 16
    layers, 32 slots x 2048, horizon 1, ``use_flash``): every layer's
    attention is ``edl_decode_attn``, the cache updates in place, and no
    operation produces a layer's worth of cache (the 32 ``slice``s of
    ``bf16[32,2048,8,128]`` that were 42.5% of this program's time)."""
    cfg, kc, compiled = cell_block("mistral7b-L16")
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


def _outside_fusions(text):
    """(name, result shapes, op, operands) of every instruction of an
    optimized module that is not in a fusion's body: the operations the
    device runs one by one (the entry computation and what it calls)."""
    bodies = set(re.findall(r"kind=k\w+, calls=%([\w.\-]+)", text))
    out, skip = [], False
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            skip = head.group(1) in bodies
        elif not skip:
            # a layout holds `T(8,128)` but no space: the op is the first
            # word after one that opens a bracket
            made = re.match(
                r"\s+(?:ROOT )?%([\w.\-]+) = (.*?) ([a-z][\w\-]*)\((.*)", line)
            if made:
                name, result, op, rest = made.groups()
                shapes = re.findall(r"\w+\[[\d,]*\]", result)
                out.append((name, shapes, op, rest))
    return out


@pytest.mark.parametrize("cell", sorted(SERVING_CELLS))
def test_serve_block_reads_the_projection_weights_where_they_lie(
    cell_block, cell
):
    """The dense serving cells' block: every leaf of ``params["layers"]``
    goes into fusions and nothing else (the slice of a layer is inside
    the fusion that reads it), and no operation outside a fusion makes a
    layer's ``wq`` / ``wk`` / ``wv`` (either way round) by ``copy``,
    ``transpose`` or a ``slice_bitcast_fusion``. With the head split
    folded into the dot (``llama._qkv`` bare) the compiler sliced each
    out, transposed it physically and only then read it: 48 weight-sized
    copies a step at serve-open's shape, 36 at decode-closed's (PERF.md
    section 6, PR 34); ``llama._qkv_cached`` keeps the split on the
    activation. This count is that mechanism's counter."""
    cfg, _, compiled = cell_block(cell)
    text = compiled.as_text()
    ops = _outside_fusions(text)
    assert sum(op == "custom-call" for _, _, op, _ in ops) >= cfg.n_layers
    d, kvd = cfg.d_model, cfg.n_kv_heads * cfg.head_dim
    weight = {f"bf16[{m},{n}]" for m, n in ((d, d), (d, kvd), (kvd, d))}
    relayouts = collections.Counter(
        (shapes[0], "slice_bitcast_fusion" if op == "fusion" else op)
        for name, shapes, op, _ in ops
        if shapes[:1] and shapes[0] in weight and (
            op in ("copy", "transpose", "slice")
            or name.startswith("slice_bitcast_fusion")
        )
    )
    assert not relayouts, dict(relayouts)
    readers = {
        (leaf, op) for _, _, op, rest in ops
        for leaf in re.findall(r"%params__layers____(\w+?)__[.\d]*\b", rest)
    }
    assert {leaf for leaf, _ in readers} == set(llama._INT8_WEIGHTS) | {
        "ln1", "ln2"}
    assert {op for _, op in readers} == {"fusion"}, sorted(readers)


# -- the latent-attention expert model (kanana2.decode-wide's shapes) --------


@pytest.mark.parametrize("block_s", [None, 512])
def test_latent_decode_kernel_compiles_for_v5e(v5e, block_s):
    """``edl_decode_attn_latent`` at 96 slots x 4096 positions x 640
    columns, 8 layers: one operand, no temporary the size of a layer."""
    from edl_tpu.ops.decode_attention import decode_attention_latent

    one = SingleDeviceSharding(v5e[0])
    b, s, w = 96, 4096, 640
    compiled = jax.jit(
        lambda q, c, p, l: decode_attention_latent(
            q, c, p, l, rank=512, sm_scale=192 ** -0.5, block_s=block_s)
    ).lower(
        _sds((b, 32, w), jnp.bfloat16, one),
        _sds((8, b, s, w), jnp.bfloat16, one),
        _sds((b,), jnp.int32, one), _sds((), jnp.int32, one),
    ).compile()
    text = compiled.as_text()
    assert "edl_decode_attn_latent" in text and "tpu_custom_call" in text
    assert compiled.memory_analysis().temp_size_in_bytes < b * s * w * 2 // 16


def test_flash_forward_compiles_with_192_wide_keys_and_128_wide_values(v5e):
    one = SingleDeviceSharding(v5e[0])
    qk = _sds((1, 2048, 32, 192), jnp.bfloat16, one)
    v = _sds((1, 2048, 32, 128), jnp.bfloat16, one)
    compiled = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, block_q=512, block_k=1024)
    ).lower(qk, qk, v).compile()
    assert "edl_flash_fwd" in compiled.as_text()


def test_decode_wide_block_fits_the_chip_and_updates_the_cache_in_place(v5e):
    """``edl_serve_block`` of ``kanana2.decode-wide`` (one dense + seven
    expert layers at published widths, 96 slots x 4096, one step a
    dispatch): weights and cache are 14.2 GB of the chip's 15.75,
    the latent cache aliases its output,
    nothing the size of the cache or of a layer of it is made (at 576
    columns the compiler kept the cache positions-minor and transposed
    all of it around every kernel), each layer's attention is
    ``edl_decode_attn_latent`` and each expert layer's routed experts
    are one ``edl_expert_mlp`` that reads the experts where they lie
    (three grouped matmuls a layer before PR 36). The temporaries are
    21 MB."""
    import re

    from benchmark import harness
    from benchmark.families import mla_moe as family
    from edl_tpu.serving import engine

    one = SingleDeviceSharding(v5e[0])
    config = harness.load_json(os.path.join(
        harness.ROOT, "benchmark", "configs", "kanana2-30b-a3b-L8.json"))
    cfg = family.program_config(config, training=False)
    params = harness.layout_tree(
        family.param_layout(config),
        lambda path, shape, std, stacked: _sds(shape, jnp.bfloat16, one))
    spec = harness.Cell("kanana2.decode-wide").spec["engine"]
    b, s, horizon = spec["max_slots"], spec["max_len"], spec["horizon"]
    assert (b, s, horizon) == (96, 4096, 1)
    i32 = _sds((b,), jnp.int32, one)
    cache = _sds((cfg.n_layers, b, s, cfg.cache_width), cfg.dtype, one)
    compiled = engine._block_program(cfg, b, s, horizon, False).lower(
        params, i32, i32, _sds((b,), jnp.bool_, one), i32, i32, cache,
        _sds((2,), jnp.uint32, one), _sds((), jnp.float32, one),
    ).compile()
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= b * s * cfg.n_layers * 640 * 2
    assert mem.temp_size_in_bytes < 64 << 20
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9
    assert text.count("edl_decode_attn_latent") >= cfg.n_layers
    assert len(re.findall(r"%ragged-dot[\w\-.]* = bf16", text)) == 0
    assert len(re.findall(
        r"custom_call_target=\"tpu_custom_call\"[^\n]*edl_expert_mlp", text)
    ) == 7, "edl_expert_mlp once an expert layer"
    # (the loop hands its operands on by get-tuple-element: no copy)
    made = re.findall(r"= (bf16\[[\d,]+\])\S* ([\w\-]+)\(", text)
    whole, layer = "bf16[8,96,4096,640]", "bf16[96,4096,640]"
    assert not [op for shape, op in made
                if shape in (whole, layer)
                and op not in ("fusion", "parameter", "scatter",
                              "get-tuple-element")], "a copy of the cache"
    assert not _expert_leaves_made(text), "experts copied"


def _expert_leaves_made(text):
    """Operations of an optimized decode-wide program that make an
    array as large as a layer's experts (a copy, a transpose)."""
    made = re.findall(r"= (bf16\[[\d,]+\])\S* ([\w\-]+)\(", text)
    return [op for shape, op in made
            if shape in ("bf16[128,2048,768]", "bf16[128,768,2048]")
            and op not in ("parameter", "get-tuple-element")]


@pytest.mark.parametrize("rows", [16, 96, 128])
def test_expert_mlp_kernel_compiles_for_v5e(v5e, rows):
    """``edl_expert_mlp`` at the cell's widths (128 experts of 2048 x
    768 bf16, six a token): a whole expert twice over in VMEM (18.9 MB,
    past the 16 MiB a kernel gets unasked), nothing expert-sized made
    beside it."""
    from edl_tpu.ops.expert_mlp import expert_mlp

    one = SingleDeviceSharding(v5e[0])
    e, d, f, k = 128, 2048, 768, 6
    compiled = jax.jit(expert_mlp).lower(
        _sds((rows, d), jnp.bfloat16, one), _sds((rows, k), jnp.int32, one),
        _sds((rows, k), jnp.float32, one),
        _sds((e, d, f), jnp.bfloat16, one), _sds((e, d, f), jnp.bfloat16, one),
        _sds((e, f, d), jnp.bfloat16, one),
    ).compile()
    text = compiled.as_text()
    assert "edl_expert_mlp" in text and "tpu_custom_call" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("tokens", [256, 2048, 4096])
def test_grouped_expert_mlp_kernel_compiles_for_v5e(v5e, tokens):
    """``edl_grouped_expert_mlp`` at the cell's widths over a prefill
    bucket's sorted rows (six a token): a whole expert twice over in
    VMEM, fetched by hand; the rows are written where they were read
    (aliased), so nothing row- or expert-sized is made beside them."""
    from edl_tpu.ops.expert_mlp import grouped_expert_mlp

    one = SingleDeviceSharding(v5e[0])
    e, d, f, k = 128, 2048, 768, 6
    compiled = jax.jit(grouped_expert_mlp, donate_argnums=0).lower(
        _sds((tokens * k, d), jnp.bfloat16, one), _sds((e,), jnp.int32, one),
        _sds((e, d, f), jnp.bfloat16, one), _sds((e, d, f), jnp.bfloat16, one),
        _sds((e, f, d), jnp.bfloat16, one),
    ).compile()
    text = compiled.as_text()
    assert "edl_grouped_expert_mlp" in text and "tpu_custom_call" in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == tokens * k * d * 2
    assert mem.temp_size_in_bytes < 1 << 20


# -- PR 35: a cache that is a state a slot --------------------------------------

# sha256 (first 16 hex digits) of the lowered text of each serving
# cell's block program and 1024 prefill, a Mosaic kernel's payload
# aside (it holds its callers' files and line numbers): recorded from
# the parent commit of PR 35 (546dfaa), whose seam change and
# ``llama._qkv``'s ``qk_norm`` must leave these programs as they were.
# PR 36 re-recorded decode-wide's block alone (``edl_expert_mlp`` for
# the grouped matmuls), PR 38 its prefill alone
# (``edl_grouped_expert_mlp`` for prefill's): the block's digest
# standing is the proof that a decode step bypasses the new kernel, the
# other pairs' that the dense decoders do.
# PR 37 added decode-state's pair from its own parent (36433bc): the
# seam widened for two kinds of cache, ``llama._qkv`` without RoPE and
# ``_mlp``'s residual multiplier leave all four as they were
PARENTS_TEXT = {
    "deepseek7b.decode-closed": ("1295e2201debccba", "2b18d84018a249e3"),
    "mistral7b.serve-open": ("f67a0903cf71b5a3", "9edf94936f77b1f2"),
    "kanana2.decode-wide": ("095d2ebfb11a760f", "10089310938df9f0"),
    "brumby14b.decode-state": ("5c42d86ecb8336c5", "aa4143d59a7dc56c"),
}


def _serving_programs(v5e, cell_name, bucket):
    """(cfg, lowered block program, lowered prefill of ``bucket``) of a
    serving cell at its own widths and engine sizes."""
    from benchmark import harness
    from edl_tpu.serving import engine

    one = SingleDeviceSharding(v5e[0])
    cell = harness.Cell(cell_name)
    cfg = cell.family.program_config(cell.config, training=False)
    params = harness.layout_tree(
        cell.family.param_layout(cell.config),
        lambda path, shape, std, stacked: _sds(shape, jnp.bfloat16, one))
    spec = cell.spec["engine"]
    b, s, h = spec["max_slots"], spec["max_len"], spec.get("horizon", 1)
    cache = [_sds(shape, dtype, one)
             for shape, dtype in cfg.serve_cache_spec(b, s)]
    i32, s0 = _sds((b,), jnp.int32, one), _sds((), jnp.int32, one)
    on = _sds((b,), jnp.bool_, one)
    tail = (_sds((2,), jnp.uint32, one), _sds((), jnp.float32, one))
    block = engine._block_program(cfg, b, s, h, False).lower(
        params, i32, i32, on, i32, i32, *cache, *tail)
    prefill = engine._prefill_program(cfg, bucket, False).lower(
        params, _sds((1, bucket), jnp.int32, one), s0, s0, s0, s0,
        i32, i32, on, i32, i32, *cache, *tail)
    return cfg, block, prefill


@pytest.mark.parametrize("cell", sorted(PARENTS_TEXT))
def test_serving_cells_programs_lower_to_the_parents_text(v5e, cell):
    import hashlib

    _, block, prefill = _serving_programs(v5e, cell, 1024)
    got = tuple(
        hashlib.sha256(re.sub(
            r'backend_config = "[^"]*"', 'backend_config = ""',
            lowered.as_text()).encode()).hexdigest()[:16]
        for lowered in (block, prefill))
    assert got == PARENTS_TEXT[cell]


def test_decode_wide_prefill_runs_the_grouped_kernel_once_an_expert_layer(v5e):
    """``edl_serve_prefill_2048`` of ``kanana2.decode-wide``, optimized:
    no ``ragged-dot`` (21 a prompt before PR 38), one
    ``edl_grouped_expert_mlp`` an expert layer, no expert leaf copied
    or transposed, and it fits beside the weights and the cache. The
    temporaries are 0.52 GB (0.46 before PR 38: XLA kept one more
    [12288, 2048] array in the other memory space; the 4096 bucket's,
    the largest, stayed 0.87-0.88)."""
    _, _, prefill = _serving_programs(v5e, "kanana2.decode-wide", 2048)
    compiled = prefill.compile()
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    assert len(re.findall(r"%ragged-dot[\w\-.]* = bf16", text)) == 0
    assert len(re.findall(
        r"custom_call_target=\"tpu_custom_call\"[^\n]*edl_grouped_expert_mlp",
        text)) == 7, "edl_grouped_expert_mlp once an expert layer"
    assert not _expert_leaves_made(text), "experts copied"
    assert mem.temp_size_in_bytes < 0.55e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


def _state_sized(text, cfg, slots):
    """Operations of the optimized text that make an array as large as
    the state of all slots, of one layer's, or of one slot's across the
    layers, outside a fusion: (shape, operation) pairs."""
    tail = f"{cfg.n_kv_heads},{cfg.head_dim},{cfg.state_width}]"
    sizes = {f"f32[{cfg.n_layers},{slots},{tail}", f"f32[{slots},{tail}",
             f"f32[1,{slots},{tail}", f"f32[{cfg.n_layers},1,{tail}"}
    return [(shape, op) for _, shapes, op, _ in _outside_fusions(text)
            for shape in shapes if shape in sizes]


@pytest.mark.parametrize("widths", ["tiny", "published"])
def test_retention_block_moves_the_state_once_and_in_place(v5e, widths):
    """``edl_serve_block`` of the power-retention model, at a tiny
    config (lane-wide heads) and at ``brumby14b.decode-state``'s (8
    layers, 24 slots): the state aliases its output, every layer's step
    is one ``edl_retention_step`` that reads a live slot's state once
    and writes it once, and nothing outside a fusion makes, copies or
    transposes an array the size of the state, of a layer of it or of
    a slot's share of it. The published block: 15.0 GB of arguments and
    5 MB of temporaries."""
    from edl_tpu.models import retention
    from edl_tpu.serving import engine

    one = SingleDeviceSharding(v5e[0])
    if widths == "published":
        cfg, lowered, _ = _serving_programs(
            v5e, "brumby14b.decode-state", 256)
        slots = 24
    else:
        cfg = retention.RetentionConfig(
            vocab=1024, d_model=256, n_layers=2, n_heads=10, n_kv_heads=2,
            head_dim=128, d_ff=512, use_kernel=True)
        slots = 4
        shapes = jax.eval_shape(
            lambda k: retention.init_params(k, cfg), jax.random.PRNGKey(0))
        params = jax.tree_util.tree_map(
            lambda s: _sds(s.shape, jnp.bfloat16, one), shapes)
        i32 = _sds((slots,), jnp.int32, one)
        cache = [_sds(shape, dtype, one)
                 for shape, dtype in cfg.serve_cache_spec(slots, 256)]
        lowered = engine._block_program(cfg, slots, 256, 1, False).lower(
            params, i32, i32, _sds((slots,), jnp.bool_, one), i32, i32,
            *cache, _sds((2,), jnp.uint32, one), _sds((), jnp.float32, one))
    compiled = lowered.compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    state = slots * cfg.state_bytes_per_slot()
    assert mem.alias_size_in_bytes >= state
    assert mem.temp_size_in_bytes < 64 << 20
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9
    assert text.count("edl_retention_step") >= cfg.n_layers
    made = _state_sized(text, cfg, slots)
    assert not [m for m in made if m[1] not in (
        "parameter", "get-tuple-element", "custom-call", "tuple")], made
    # the kernel call itself is the one operation that yields the state
    assert sum(op == "custom-call" for _, op in made) <= cfg.n_layers


def test_retention_prefill_fits_beside_24_slots_of_state(v5e):
    """The largest prefill program of ``brumby14b.decode-state`` (one
    4096 bucket) beside the weights and 24 slots of state, through the
    seam's plain ``serve_prefill`` and the engine's scatter: it fits
    the chip (the compiler's own count: 14.99 GB of arguments and 0.41
    GB of temporaries, a slot's stacked states, 272 MB, among them;
    15.75 GiB = 16.9 GB is the chip's), the scatter writes the slot's
    row into the donated cache in place, every chunk's work against
    the carried state is ``edl_retention_chunk`` and no ``phi`` of a
    chunk's queries is written out (170 MB a chunk of 256 in the plain
    lines)."""
    cfg, _, lowered = _serving_programs(v5e, "brumby14b.decode-state", 4096)
    compiled = lowered.compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 24 * cfg.state_bytes_per_slot()
    assert mem.temp_size_in_bytes < 512 << 20
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9
    assert "edl_retention_chunk" in text
    # nothing but the in-place scatter yields an array the size of the
    # whole cache
    whole = [op for shape, op in _state_sized(text, cfg, 24)
             if shape.startswith(f"f32[{cfg.n_layers},24,")]
    assert not [op for op in whole if op not in (
        "parameter", "get-tuple-element", "tuple", "fusion")], whole
    assert whole.count("fusion") <= 1
    rows = cfg.chunk * cfg.groups
    unwanted = (f"bf16[8,{rows},8320]", f"f32[8,{rows},8320]")
    assert not [(shape, op) for _, shapes, op, _ in _outside_fusions(text)
                for shape in shapes if shape in unwanted], "phi written out"


# -- PR 37: layers of two kinds, two kinds of cache in one tuple -----------------

HYBRID = "granite4h.decode-hybrid"


def _weight_sized(text, cfg):
    """Operations of the optimized text outside a fusion that make, by
    ``copy``, ``transpose`` or ``slice``, an array as large as a layer's
    matrix or as the embedding: (shape, operation) pairs."""
    d, ff, di = cfg.d_model, cfg.d_ff, cfg.d_inner
    mats = {(d, di + cfg.conv_width), (di, d), (d, ff), (ff, d),
            (d, cfg.n_heads * cfg.head_dim), (cfg.vocab, d)}
    shapes = {f"bf16[{m},{n}]" for a, b in mats for m, n in ((a, b), (b, a))}
    shapes |= {f"bf16[1,{s[5:]}" for s in shapes}
    return [(shape, op) for _, made, op, _ in _outside_fusions(text)
            for shape in made if shape in shapes
            and op in ("copy", "transpose", "slice", "dynamic-slice")]


def test_hybrid_block_moves_each_kind_of_cache_once_and_in_place(v5e):
    """``edl_serve_block`` of ``granite4h.decode-hybrid`` (all 40
    layers, 72 slots): it fits the chip (the compiler's own count: 14.30
    GB of arguments, 4 MB of temporaries), the whole cache tuple (7.92
    GB) aliases its output, the state-space layers are five loops over
    their stacked tree, one ``edl_ssm_step`` each, and the attention
    layers four ``edl_decode_attn``; nothing outside a fusion copies,
    transposes or slices out a layer's matrix or the embedding, which
    is also the head (411 MB), and nothing copies an array of the
    cache."""
    cfg, lowered, _ = _serving_programs(v5e, HYBRID, 256)
    compiled = lowered.compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    cache = sum(
        int(jnp.dtype(dtype).itemsize) * functools.reduce(
            lambda a, b: a * b, shape)
        for shape, dtype in cfg.serve_cache_spec(72, 4096))
    assert round(cache / 1e9, 2) == 7.92
    assert mem.alias_size_in_bytes >= cache
    assert mem.temp_size_in_bytes < 64 << 20
    assert 14.2e9 < mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 15.75e9
    ops = _outside_fusions(text)
    kernels = collections.Counter(
        name for _, _, op, rest in ops if op == "custom-call"
        for name in ("edl_ssm_step", "edl_decode_attn") if name in rest)
    runs = [r for r in cfg.runs if r[0] == "mamba"]
    assert kernels["edl_ssm_step"] == len(runs) == 5
    assert kernels["edl_decode_attn"] == cfg.n_attn == 4
    assert sum(op == "while" for _, _, op, _ in ops) >= len(runs)
    assert not _weight_sized(text, cfg), _weight_sized(text, cfg)
    sizes = {"f32[36,72,64,64,128]", "f32[72,64,64,128]",
             "bf16[36,72,13056]", "bf16[4,72,4096,4,128]"}
    made = [(shape, op) for _, shapes, op, _ in ops for shape in shapes
            if shape in sizes]
    assert not [m for m in made if m[1] not in (
        "parameter", "get-tuple-element", "custom-call", "tuple", "while",
        "fusion", "bitcast")], made


def test_hybrid_prefill_fits_beside_72_slots_of_cache(v5e):
    """The largest prefill program of the cell (one 4096 bucket) beside
    the weights and 72 slots of both kinds of cache, through the seam's
    ``serve_prefill`` and the engine's scatter: 14.30 GB of arguments
    and under 0.6 GB of temporaries; the attention layers' prefill is
    ``edl_flash_fwd`` at the config's scale."""
    cfg, _, lowered = _serving_programs(v5e, HYBRID, 4096)
    compiled = lowered.compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 7.9e9
    assert mem.temp_size_in_bytes < 600 << 20
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9
    assert "edl_flash_fwd" in text and "edl_ssm_step" not in text
    # the embedding is read where it lies by the lookup and by the head
    assert not [m for m in _weight_sized(text, cfg)
                if m[0].endswith(f"[{cfg.vocab},{cfg.d_model}]")
                or m[0].endswith(f"[{cfg.d_model},{cfg.vocab}]")]


@pytest.mark.parametrize("pack", [1, 2])
def test_decode_attention_at_the_hybrid_cells_cache(v5e, pack):
    """``edl_decode_attn`` at the caller's scale over the hybrid cell's
    keys and values, two heads of 64 a 128-lane row (4 x 128, blocks of
    512 positions), and as it would be unpacked (8 x 64: compiles, and
    the compiler stores such a cache positions-minor, which is why the
    model packs)."""
    from edl_tpu.ops.decode_attention import block_positions, decode_attention

    one = SingleDeviceSharding(v5e[0])
    kv, hd = 8 // pack, 64 * pack
    cache = _sds((4, 72, 4096, kv, hd), jnp.bfloat16, one)
    q = _sds((72, kv, 4 * pack, hd), jnp.bfloat16, one)
    assert block_positions(kv, hd, 2, 4096) == 512
    compiled = jax.jit(functools.partial(
        decode_attention, sm_scale=0.015625)).lower(
        q, cache, cache, _sds((72,), jnp.int32, one),
        _sds((), jnp.int32, one)).compile()
    assert "edl_decode_attn" in compiled.as_text()


# -- what the training step's rematerialised layers keep (PR 40) --------------

V5E_BYTES_LIMIT = int(15.75 * 2 ** 30)  # what the compiler holds a v5e to


def _train_steady_step(v5e):
    """``mistral7b.train-steady``'s step as ``ElasticTrainer`` builds it:
    Mistral-7B's widths at 4 layers, 4 x 4097 tokens, adafactor, the
    float32 state donated; (build, state, batch, mesh) of shapes alone."""
    import optax

    from edl_tpu.api.job import MeshSpec
    from edl_tpu.train import trainer as tr

    cfg = llama.LlamaConfig(
        **dict(SERVING_CELLS["mistral7b-L16"][1], n_layers=4),
        dtype=jnp.bfloat16, use_flash=True, remat=True)
    plan = MeshPlan.from_spec(MeshSpec(), 1)
    mesh = plan.build(v5e[:1])
    tx = optax.adafactor(1e-3)
    pspecs = llama.param_pspecs(cfg, plan)
    shape = jax.eval_shape(lambda: tr.TrainState.create(
        llama.init_params(jax.random.PRNGKey(0), cfg), tx))
    state_sh = tr._state_sharding(shape, plan, mesh, pspecs)
    state = jax.tree_util.tree_map(
        lambda x, s: _sds(x.shape, x.dtype, s), shape, state_sh)
    batch = {"tokens": _sds((4, 4097), jnp.int32, plan.batch_sharding(mesh))}
    step = tr.make_train_step(
        llama.make_loss_fn(cfg, plan, mesh), tx, plan, mesh, pspecs)
    return step, state, batch, mesh


def _flash_fwd_calls(text):
    return len(re.findall(r"custom-call\(.*edl_flash_fwd", text))


def test_train_steady_step_keeps_what_fits_the_chip_in_one_compile(
        v5e, monkeypatch):
    """The cell's step, fitted to the described chip by the trainer
    itself (``make_train_step``'s first call, up to the dispatch): ONE
    compile, the compiler's peak under the device's limit by the
    headroom, and the flash forward kernel once in the optimized
    program (the backward reuses what the layer kept) where
    ``remat_policy="full"`` has it twice."""
    from edl_tpu.obs import compilewatch
    from edl_tpu.train import trainer as tr

    monkeypatch.setattr(tr, "device_bytes_limit", lambda mesh: V5E_BYTES_LIMIT)
    step, state, batch, mesh = _train_steady_step(v5e)
    kept = {}
    with compilewatch.Window() as built:
        jitted = tr._fit_to_device(step.build, state, batch, mesh, kept)
    assert built.programs == 1 and kept["compiles"] == 1
    assert kept["remat_kept"].split(",")[:2] == ["flash_out", "flash_lse"]
    assert kept["hbm_headroom_bytes"] >= V5E_BYTES_LIMIT * tr.HEADROOM_SHARE
    # the call that follows finds this executable: lowering again under
    # no offer is the same trace, and its compile is jit's cached one
    with compilewatch.Window() as again:
        compiled = jitted.lower(state, batch).compile()
    assert again.programs == 0
    assert tr._peak_bytes(compiled) == (
        V5E_BYTES_LIMIT - kept["hbm_headroom_bytes"])
    assert _flash_fwd_calls(compiled.as_text()) == 1
    # and "full", the parent's program: the kernel twice, more room
    monkeypatch.setattr(tr, "device_bytes_limit", lambda mesh: None)
    full = tr._fit_to_device(step.build, state, batch, mesh, {})
    compiled_full = full.lower(state, batch).compile()
    assert _flash_fwd_calls(compiled_full.as_text()) == 2
    assert tr._peak_bytes(compiled_full) < tr._peak_bytes(compiled)


def test_a_step_the_compiler_refuses_or_squeezes_is_traced_again_a_rung_lower(
        v5e, monkeypatch):
    """A device that claims more memory than the described chip has: the
    estimate lets the whole of ``KEEP_ORDER`` through; the compiler
    refuses it (``RESOURCE_EXHAUSTED``) and the next rung too; the flash
    pair with ``mlp_up`` it compiles, 1.16 GiB inside the limit, but
    only by rematerialising on its own (``.remat`` in its text: on the
    chip that step is slower than "full"), so the trainer settles on
    the flash pair: four compiles, the rare path."""
    from edl_tpu.train import trainer as tr

    monkeypatch.setattr(tr, "device_bytes_limit", lambda mesh: 1 << 40)
    step, state, batch, mesh = _train_steady_step(v5e)
    kept = {}
    jitted = tr._fit_to_device(step.build, state, batch, mesh, kept)
    assert kept["compiles"] == 4
    assert kept["remat_kept"] == "flash_out,flash_lse"
    compiled = jitted.lower(state, batch).compile()
    assert tr._peak_bytes(compiled) < V5E_BYTES_LIMIT
    assert not tr._compiler_traded(compiled)


# -- PR 41: a learned indexer chooses the positions attended --------------------

LONG_SPARSE = "glm5.long-sparse"
CHIP_BYTES = 15.75 * 2 ** 30  # what the compiler holds a v5e program to


@pytest.mark.parametrize("rows, grouped", [(32, False), (2048 * 8, True)])
def test_an_expert_of_6144_x_2048_passes_in_tiles_of_f(v5e, rows, grouped):
    """Both expert kernels at the sparse latent-attention cell's widths
    (8 held experts of 6144 x 2048 bf16, 75 MB each: no expert fits
    VMEM whole): a decode step's 32 rows through ``edl_expert_mlp``, a
    prefill piece's 2048 x 8 sorted rows through
    ``edl_grouped_expert_mlp``, each over a second grid axis of ``f``
    tiles, nothing expert-sized made beside them."""
    from edl_tpu.ops import expert_mlp as em

    one = SingleDeviceSharding(v5e[0])
    e, d, f, k = 8, 6144, 2048, 8
    experts = (_sds((e, d, f), jnp.bfloat16, one),
               _sds((e, d, f), jnp.bfloat16, one),
               _sds((e, f, d), jnp.bfloat16, one))
    if grouped:
        lowered = jax.jit(em.grouped_expert_mlp, donate_argnums=0).lower(
            _sds((rows, d), jnp.bfloat16, one), _sds((e,), jnp.int32, one),
            *experts)
    else:
        lowered = jax.jit(em.expert_mlp).lower(
            _sds((rows, d), jnp.bfloat16, one),
            _sds((rows, k), jnp.int32, one),
            _sds((rows, k), jnp.float32, one), *experts)
    compiled = lowered.compile()
    text = compiled.as_text()
    name = "edl_grouped_expert_mlp" if grouped else "edl_expert_mlp"
    assert name in text and "tpu_custom_call" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    assert not re.findall(r"= bf16\[8,(?:6144,2048|2048,6144)\]\S* copy\(",
                          text)


def _cache_sized(text, cfg, b, s):
    """Operations of an optimized program that make an array as large
    as a cache array of the sparse latent-attention cell, or as a layer
    of one (a copy, a transpose, a slice)."""
    L = cfg.n_layers
    shapes = {f"bf16[{lead}{b},{s},{w}]" for w in (cfg.cache_width,
                                                  cfg.index_dim)
              for lead in ("", f"{L},")}
    made = re.findall(r"= (bf16\[[\d,]+\])\S* ([\w\-]+)\(", text)
    return [(shape, op) for shape, op in made if shape in shapes
            and op not in ("fusion", "parameter", "scatter",
                           "get-tuple-element", "dynamic-update-slice")]


def test_long_sparse_block_reads_the_live_rows_masked_and_in_place(v5e):
    """``edl_serve_block`` of ``glm5.long-sparse`` (one dense + four
    expert layers at published widths, 32 slots x 32768): weights and
    both cache arrays are 13.46 GB of the chip's 15.75 GiB and both
    arrays alias their outputs; a layer's attention is one
    ``edl_decode_attn_latent`` over the slot's live rows with the
    positions not chosen masked (no sort of the scores and no gather of
    rows: 0.73 and 1.94 ms a layer when there were, PR 41), an expert
    layer's routed experts one ``edl_expert_mlp``; nothing the size of
    a cache array or of a layer of one is made."""
    cfg, block, _ = _serving_programs(v5e, LONG_SPARSE, 2048)
    compiled = block.compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 8.05e9
    assert mem.temp_size_in_bytes < 128 << 20
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < CHIP_BYTES
    kernels = re.findall(
        r"custom_call_target=\"tpu_custom_call\"[^\n]*?kernel_name = \"(\w+)\"",
        text) or re.findall(r"(edl_decode_attn_latent|edl_expert_mlp)\"", text)
    assert text.count("edl_decode_attn_latent") >= cfg.n_layers
    assert text.count("edl_expert_mlp") >= cfg.n_layers - cfg.n_dense_layers
    assert not re.findall(r"f32\[32,32768\]\S* sort\(", text), kernels
    assert not re.findall(r"bf16\[32,2048,640\]\S* gather\(", text)
    assert not _cache_sized(text, cfg, 32, 32768)


def test_long_sparse_largest_prefill_fits_beside_32_long_slots(v5e):
    """``edl_serve_prefill_32768``, the largest bucket, in pieces of
    2048 rows inside the one program, beside the weights and 32 slots
    of 32768 positions: the compiler's peak stays under the chip's
    limit (15.4 GB; a bucket whole would hold 1.07 GB of queries and
    2.1 GB of expanded keys and values), the first piece runs
    ``edl_flash_fwd`` and an expert layer's rows one
    ``edl_grouped_expert_mlp`` a piece."""
    cfg, _, prefill = _serving_programs(v5e, LONG_SPARSE, 32768)
    compiled = prefill.compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 8.05e9
    assert mem.peak_memory_in_bytes < CHIP_BYTES
    assert mem.temp_size_in_bytes < 2.6e9
    assert "edl_flash_fwd" in text and "edl_grouped_expert_mlp" in text
    assert "edl_sparse_prefill_attn" in text
    assert not _cache_sized(text, cfg, 32, 32768)


def _kernel_calls(text, name):
    return [line for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            and re.match(rf"\s*%{name}[.\d]* = ", line)]


def test_long_sparse_prefill_attends_the_chosen_in_one_kernel_a_layer(v5e):
    """``edl_serve_prefill_8192`` (a dense piece and a scan over three
    more): the scan's body holds ``edl_sparse_prefill_attn`` once a
    layer and the dense piece none (it runs ``edl_flash_fwd``, once a
    layer), and nothing the size of a visit's scores (64 heads x 2048
    rows x 512 keys in float32, 268 MB, which ``_sweep`` wrote and read
    every visit: 125 such arrays in the program's text before PR 42)
    or of its accumulator is made."""
    cfg, _, prefill = _serving_programs(v5e, LONG_SPARSE, 8192)
    text = prefill.compile().as_text()
    assert len(_kernel_calls(text, "edl_sparse_prefill_attn")) == cfg.n_layers
    assert len(_kernel_calls(text, "edl_flash_fwd")) == cfg.n_layers
    assert not re.findall(r"f32\[(?:1,)?64,2048,(?:512|256)\]", text)
