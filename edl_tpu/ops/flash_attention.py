"""Pallas TPU flash attention — the hot op, hand-tiled for VMEM/MXU.

The reference has no custom kernels anywhere (SURVEY §2: "no C++/CUDA
in-repo"); on TPU the attention score matrix is the one op worth
hand-scheduling. Design:

- grid (batch*heads, q blocks, kv blocks), kv innermost: K/V stream
  through VMEM one [block_k, d] tile at a time — VMEM stays bounded at
  any sequence length;
- online-softmax accumulators (m, l, acc) live in VMEM scratch across
  the kv sweep, written back once on the last block;
- native GQA: the K/V BlockSpec maps head bh -> bh // groups, so grouped
  K/V heads are never materially repeated;
- matmuls keep the input dtype with ``preferred_element_type=float32``
  (bf16 MXU at full rate, f32 accumulation);
- causal upper-triangle blocks are skipped via ``pl.when``.

Differentiable: custom_vjp with FlashAttention-2-style backward — the
forward also emits the per-row logsumexp (lane-replicated [bh, T, 128]
layout, the Mosaic minimum f32 tile); the backward runs two pallas
sweeps, dQ (kv innermost) and dK/dV (q innermost, per-query-head then
group-summed for GQA), with delta = rowsum(dO*O) precomputed in XLA.

The kernel is VPU-bound at d=128 (softmax elementwise + cross-lane
reductions dwarf the MXU matmuls), so the causal mask's iota/compare/
select runs ONLY on diagonal-crossing blocks — fully-live blocks take
a mask-free code path (two ``pl.when`` branches per kernel).

Measured on v5e (fenced timing, 16 chained calls amortizing dispatch):
forward b=16 T=2048 h=16 d=128 — 8.3 ms/call (33 TF/s); fwd+bwd
21.5 ms/call (the r4 exp2-softmax fold cut fwd+bwd ~18% vs the exp
version's 26.4 ms). The jax.experimental reference pallas TPU kernel on
the same chip/shape: 27.1 ms forward, 40.8 ms fwd+bwd. In-model effect
of diagonal-skip + (512,1024) blocks: flagship MFU 0.502 -> 0.524.

In-model accounting (r4, scripts/exp_breakdown.py long): at T=8192 the
attention portion of a real remat train step runs at ~53 TF/s effective
— within 10% of the standalone kernel composite (55.7) — i.e. there is
NO standalone-vs-in-model integration gap; the long-context MFU ~0.50
is the honest mix of the ~55%-peak matmul chain with this ~27%-peak
VPU-bound kernel under mandatory full remat.

Off-TPU the kernel does not run unless the caller asks for the Pallas
interpreter: tests pass ``interpret=True`` to :func:`flash_attention`, or
open :func:`interpret_kernels` around a model call. No device probe
picks it — a production path that lands on a CPU fails loudly instead
of timing the interpreter.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128  # min f32 tile lane width: row vectors (lse, delta) are
# stored lane-replicated [bh, t, LANES] — Mosaic rejects (1, bq) blocks

# exp2 softmax: the VPU's transcendental unit computes exp(x) as
# exp2(x·log2e) anyway — folding log2e into the score SCALE (a multiply
# the kernel already does) deletes one full-tile VPU multiply per
# exp/rescale in the kernel's hottest loop. All softmax state (running
# max, lse residual) lives in the base-2 domain; gradients are
# unchanged (d/dx exp2(x·log2e) == exp'), and the backward consumes the
# base-2 lse with the same fold.
LOG2E = float(np.log2(np.e))


def _causal_live(q_start, k_start, block_q):
    """Whether a (q block, k block) pair intersects the causal triangle.
    Shared by all three kernels — the skip predicates must agree or the
    gradient desynchronizes from the forward."""
    return k_start <= q_start + block_q - 1


def _scores(q, k, sm_scale):
    """Scaled q·kᵀ block scores in f32 — the one matmul every kernel
    shares; any change here changes forward AND backward together."""
    return (
        jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        * sm_scale
    )


def _causal_rc(q_start, k_start, block_q, block_k):
    """(rows, cols) absolute-position iotas for the causal mask."""
    rows = q_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    cols = k_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    return rows, cols


def _flash_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    *rest,
    block_q: int,
    block_k: int,
    causal: bool,
    sm_scale: float,
    with_lse: bool,
):
    # lse is an output only on the residual-saving (training) path; the
    # plain forward skips it — pallas can't DCE an unused output and the
    # lane-replicated lse costs real HBM traffic
    if with_lse:
        lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        m_ref, l_ref, acc_ref = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)
    q_start = qi * block_q
    k_start = ki * block_k

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # causal: blocks entirely above the diagonal contribute nothing;
    # blocks entirely below it need no mask at all — the iota/compare/
    # select passes are real VPU time (the kernel is VPU-bound: softmax
    # elementwise dwarfs the MXU matmuls at d=128), so the mask runs
    # only on diagonal-crossing blocks
    live = True if not causal else _causal_live(q_start, k_start, block_q)
    crosses = causal and (k_start + block_k - 1 > q_start)

    def _compute_body(mask):
        q = q_ref[0]  # [bq, d] native dtype
        k = k_ref[0]  # [bk, d]
        v = v_ref[0]
        # scores arrive pre-scaled into the base-2 domain (LOG2E folded
        # into the score multiply): every exp below is a bare exp2
        s = _scores(q, k, sm_scale * LOG2E)  # [bq, bk] f32, base-2
        if mask:
            rows, cols = _causal_rc(q_start, k_start, block_q, block_k)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_ref[:]
        blk_m = jnp.max(s, axis=1, keepdims=True)  # [bq, 1]
        m_new = jnp.maximum(m_prev, blk_m)
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m_prev - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = m_new

    if not causal:
        pl.when(live)(lambda: _compute_body(False))
    else:
        pl.when(live & jnp.logical_not(crosses))(lambda: _compute_body(False))
        pl.when(live & crosses)(lambda: _compute_body(True))

    @pl.when(ki == n_k - 1)
    def _finish():
        o_ref[0] = (
            acc_ref[:] / jnp.maximum(l_ref[:], 1e-20)
        ).astype(o_ref.dtype)
        if with_lse:
            # log2-sum-exp2 per query row (base-2 domain end to end) —
            # the backward's softmax residual
            lse_ref[0] = jnp.broadcast_to(
                m_ref[:] + jnp.log2(jnp.maximum(l_ref[:], 1e-20)),
                lse_ref.shape[1:],
            )


def _fwd_call(
    qb, kb, vb, groups, block_q, block_k, causal, interpret, with_lse,
    sm_scale=None,
):
    """Forward pallas call in flattened [B*H, T, d] layout → out or
    (out, lse): lse is produced only when saving residuals for grad.
    ``vb`` may have a width of its own (``dv``: a latent-attention
    prefill has 192-wide queries and keys and 128-wide values); the
    output is then ``[B*H, T, dv]``."""
    bh, t, d = qb.shape
    dv = vb.shape[-1]
    kernel = functools.partial(
        _flash_kernel,
        block_q=block_q,
        block_k=block_k,
        causal=causal,
        sm_scale=1.0 / np.sqrt(d) if sm_scale is None else sm_scale,
        with_lse=with_lse,
    )
    o_spec = pl.BlockSpec((1, block_q, dv), lambda bh, qi, ki: (bh, qi, 0))
    o_shape = jax.ShapeDtypeStruct((bh, t, dv), qb.dtype)
    lse_spec = pl.BlockSpec(
        (1, block_q, LANES), lambda bh, qi, ki: (bh, qi, 0)
    )
    lse_shape = jax.ShapeDtypeStruct((bh, t, LANES), jnp.float32)
    return pl.pallas_call(
        kernel,
        grid=(bh, t // block_q, t // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            # GQA: grouped query heads share a kv head — no repeat
            pl.BlockSpec(
                (1, block_k, d), lambda bh, qi, ki, g=groups: (bh // g, ki, 0)
            ),
            pl.BlockSpec(
                (1, block_k, dv), lambda bh, qi, ki, g=groups: (bh // g, ki, 0)
            ),
        ],
        out_specs=[o_spec, lse_spec] if with_lse else o_spec,
        out_shape=[o_shape, lse_shape] if with_lse else o_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),  # running max
            pltpu.VMEM((block_q, 1), jnp.float32),  # running sum
            pltpu.VMEM((block_q, dv), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
        name="edl_flash_fwd",
    )(qb, kb, vb)


def _bwd_dq_kernel(
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    dq_ref,
    acc_ref,
    *,
    block_q: int,
    block_k: int,
    causal: bool,
    sm_scale: float,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)
    q_start = qi * block_q
    k_start = ki * block_k

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    live = True if not causal else _causal_live(q_start, k_start, block_q)
    crosses = causal and (k_start + block_k - 1 > q_start)

    def _compute_body(mask):
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        # base-2 scores against the base-2 lse: p is numerically the
        # same softmax; d(p)/d(q·kᵀ) still carries plain sm_scale
        s = _scores(q, k, sm_scale * LOG2E)
        p = jnp.exp2(s - lse_ref[0][:, :1])  # [bq, bk]
        if mask:
            rows, cols = _causal_rc(q_start, k_start, block_q, block_k)
            p = jnp.where(rows >= cols, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]
        ds = p * (dp - delta_ref[0][:, :1]) * sm_scale
        acc_ref[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if not causal:
        pl.when(live)(lambda: _compute_body(False))
    else:
        pl.when(live & jnp.logical_not(crosses))(lambda: _compute_body(False))
        pl.when(live & crosses)(lambda: _compute_body(True))

    @pl.when(ki == n_k - 1)
    def _finish():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    dk_ref,
    dv_ref,
    dk_acc_ref,
    dv_acc_ref,
    *,
    block_q: int,
    block_k: int,
    causal: bool,
    sm_scale: float,
):
    ki = pl.program_id(1)
    qi = pl.program_id(2)  # q innermost: dk/dv accumulate over the q sweep
    n_q = pl.num_programs(2)
    q_start = qi * block_q
    k_start = ki * block_k

    @pl.when(qi == 0)
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    live = True if not causal else _causal_live(q_start, k_start, block_q)
    crosses = causal and (k_start + block_k - 1 > q_start)

    def _compute_body(mask):
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = _scores(q, k, sm_scale * LOG2E)  # [bq, bk], base-2
        p = jnp.exp2(s - lse_ref[0][:, :1])
        if mask:
            rows, cols = _causal_rc(q_start, k_start, block_q, block_k)
            p = jnp.where(rows >= cols, p, 0.0)
        dv_acc_ref[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bk, d]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]
        ds = p * (dp - delta_ref[0][:, :1]) * sm_scale
        dk_acc_ref[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bk, d]

    if not causal:
        pl.when(live)(lambda: _compute_body(False))
    else:
        pl.when(live & jnp.logical_not(crosses))(lambda: _compute_body(False))
        pl.when(live & crosses)(lambda: _compute_body(True))

    @pl.when(qi == n_q - 1)
    def _finish():
        dk_ref[0] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[:].astype(dv_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(qb, kb, vb, groups, block_q, block_k, causal, interpret):
    # primal (no-grad) path: lse-free kernel — no residual HBM traffic
    return _fwd_call(
        qb, kb, vb, groups, block_q, block_k, causal, interpret,
        with_lse=False,
    )


def _flash_fwd(qb, kb, vb, groups, block_q, block_k, causal, interpret):
    if vb.shape[-1] != qb.shape[-1]:
        raise NotImplementedError(
            "flash backward needs values as wide as queries and keys; "
            f"got {vb.shape[-1]} and {qb.shape[-1]} (forward only)"
        )
    out, lse = _fwd_call(
        qb, kb, vb, groups, block_q, block_k, causal, interpret,
        with_lse=True,
    )
    # named so a rematerialization policy can KEEP these two residuals
    # (models/llama.py: the first entry of KEEP_ORDER, which
    # remat_policy="fit" keeps wherever the step has room, and "attn"
    # by name): the backward then reuses them instead of running this
    # kernel a second time; q/k/v are redone from the layer's input by
    # three matmuls. Measured at Mistral-7B's widths, 4 x 4096 tokens,
    # 4 layers (PERF.md section 6, PR 40): the pair is 8.1 KiB a token
    # a layer (0.51 GiB), the compiler's peak grows by 0.63 GiB, and
    # the optimized step calls edl_flash_fwd once where it called it
    # twice. The lse is kept COMPACT ([bh, t]: one lane of the kernel's
    # lane-replicated layout) so it is 4 bytes a row, not 512; the
    # backward rebroadcasts at XLA level. Nothing else is kept beside
    # them: the backward's float32 copy of ``out`` (for ``delta``) is
    # made and dropped inside each layer's backward.
    out = checkpoint_name(out, "flash_out")
    lse_c = checkpoint_name(lse[..., 0], "flash_lse")
    return out, (qb, kb, vb, out, lse_c)


def _flash_bwd(groups, block_q, block_k, causal, interpret, res, do):
    qb, kb, vb, out, lse_c = res
    bh, t, d = qb.shape
    lse = jnp.broadcast_to(lse_c[..., None], (bh, t, LANES))
    sm_scale = 1.0 / np.sqrt(d)
    # delta_i = Σ_d dO_i · O_i — cheap rowwise reduce, stays in XLA,
    # lane-replicated to match the lse layout
    delta = jnp.broadcast_to(
        jnp.sum(
            do.astype(jnp.float32) * out.astype(jnp.float32),
            axis=-1,
            keepdims=True,
        ),
        (bh, t, LANES),
    )

    qspec = pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0))
    rowspec = pl.BlockSpec((1, block_q, LANES), lambda bh, i, j: (bh, i, 0))
    kv_q = pl.BlockSpec(
        (1, block_k, d), lambda bh, i, j, g=groups: (bh // g, j, 0)
    )
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel,
            block_q=block_q,
            block_k=block_k,
            causal=causal,
            sm_scale=sm_scale,
        ),
        grid=(bh, t // block_q, t // block_k),
        in_specs=[qspec, kv_q, kv_q, qspec, rowspec, rowspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((bh, t, d), qb.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="edl_flash_bwd_dq",
    )(qb, kb, vb, do, lse, delta)

    # dk/dv: grid sweeps q innermost; outputs are per QUERY head, then
    # group-summed to the kv heads (GQA) in f32
    qspec2 = pl.BlockSpec((1, block_q, d), lambda bh, j, i: (bh, i, 0))
    rowspec2 = pl.BlockSpec((1, block_q, LANES), lambda bh, j, i: (bh, i, 0))
    kv_q2 = pl.BlockSpec(
        (1, block_k, d), lambda bh, j, i, g=groups: (bh // g, j, 0)
    )
    kvspec_out = pl.BlockSpec((1, block_k, d), lambda bh, j, i: (bh, j, 0))
    dk_full, dv_full = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel,
            block_q=block_q,
            block_k=block_k,
            causal=causal,
            sm_scale=sm_scale,
        ),
        grid=(bh, t // block_k, t // block_q),
        in_specs=[qspec2, kv_q2, kv_q2, qspec2, rowspec2, rowspec2],
        out_specs=[kvspec_out, kvspec_out],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, t, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name="edl_flash_bwd_dkv",
    )(qb, kb, vb, do, lse, delta)
    hkv = bh // groups
    dk = dk_full.reshape(hkv, groups, t, d).sum(axis=1).astype(kb.dtype)
    dv = dv_full.reshape(hkv, groups, t, d).sum(axis=1).astype(vb.dtype)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret", "sm_scale"),
)
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    block_q: int = 512,
    block_k: int = 1024,
    interpret: bool = False,
    sm_scale: float | None = None,
) -> jnp.ndarray:
    """q [B, T, H, d], k/v [B, T, KV, d] with H % KV == 0 (GQA) →
    [B, T, H, d]; v may be [B, T, KV, dv] of another width, forward
    only, and the result is then [B, T, H, dv]. T must divide by the (clamped) block sizes — check
    with :func:`flash_supported`, or pad upstream. Block defaults
    (512, 1024) measured fastest for train fwd+bwd on v5e at T=2048,
    d=128 (the kernel is VPU-bound; wider kv blocks amortize the
    running-max rescale). Differentiable:
    the FlashAttention-2-style backward (dQ sweep + dK/dV sweep pallas
    kernels, logsumexp residual) is wired via custom_vjp. ``sm_scale``
    multiplies the scores in place of ``1 / sqrt(d)``, forward only."""
    b, t, h, d = q.shape
    hk = k.shape[2]
    if h % hk:
        raise ValueError(f"query heads {h} not a multiple of kv heads {hk}")
    groups = h // hk
    block_q = _fit_block(block_q, t)
    block_k = _fit_block(block_k, t)
    if t % block_q or t % block_k:
        raise ValueError(
            f"seq len {t} is not flash-supported (flash_supported() is "
            f"False): it does not divide into blocks ({block_q},{block_k})"
            f" — pad T to a multiple of 128, or use dense attention"
        )

    qb = q.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    kb = k.transpose(0, 2, 1, 3).reshape(b * hk, t, d)
    dv = v.shape[3]
    vb = v.transpose(0, 2, 1, 3).reshape(b * hk, t, dv)
    if sm_scale is None:
        out = _flash(qb, kb, vb, groups, block_q, block_k, causal, interpret)
    else:
        # a model that publishes its own scale (``models/ssm_hybrid.py``):
        # the forward kernel alone, the backward kernels keep 1 / sqrt(d)
        out = _fwd_call(qb, kb, vb, groups, block_q, block_k, causal,
                        interpret, with_lse=False, sm_scale=sm_scale)
    return out.reshape(b, h, t, dv).transpose(0, 2, 1, 3)


def _fit_block(block: int, t: int) -> int:
    """Largest power-of-two block <= ``block`` that divides ``t`` (down
    to the 128-lane tile minimum) — a seq len divisible by 512 but not
    1024 (T=1536, 2560, ...) steps down instead of losing the kernel."""
    block = min(block, t)
    while block > 128 and t % block:
        block //= 2
    return block


def flash_supported(t: int, block_q: int = 512, block_k: int = 1024) -> bool:
    """True when :func:`flash_attention` accepts sequence length ``t``."""
    bq, bk = _fit_block(block_q, t), _fit_block(block_k, t)
    return t % bq == 0 and t % bk == 0


_INTERPRET = contextvars.ContextVar("edl_flash_interpret", default=False)


@contextlib.contextmanager
def interpret_kernels():
    """While open, :func:`attention_auto` traces the kernel for the
    Pallas interpreter — how a CPU test or rehearsal drives a
    ``use_flash`` model. Nothing in the program opens it. (jax's own
    ``pltpu.force_tpu_interpret_mode`` cannot stand in: its io_callback
    effects are rejected under ``jax.checkpoint``.) Read at TRACE time:
    open it around the first call of the jitted function."""
    token = _INTERPRET.set(True)
    try:
        yield
    finally:
        _INTERPRET.reset(token)


def attention_auto(q, k, v, causal: bool = True, sm_scale=None):
    """flash_attention with sequence-length-tuned block sizes — the
    model path's entry (``LlamaConfig.use_flash``, Ulysses). The
    compiled kernel unless the caller opened :func:`interpret_kernels`;
    never a device probe's choice. Blocks measured on v5e
    for BOTH directions: at T=2048 (512, 1024) is
    fastest (fwd 11.6 vs 10.7 TF/s for square blocks); at T=8192
    square 1024 blocks win fwd +12% (41.6 vs 37.1) and fwd+bwd +1.5%
    (46.1 vs 45.4), and the full T8192 train step (fwd x2 + bwd under
    remat) improves 13,945 -> 14,365 tok/s — longer rows amortize the
    per-block softmax reduces better."""
    t = q.shape[1]
    bq, bk = (1024, 1024) if t >= 4096 else (512, 1024)
    return flash_attention(
        q, k, v, causal=causal, block_q=bq, block_k=bk,
        interpret=_INTERPRET.get(), sm_scale=sm_scale,
    )
