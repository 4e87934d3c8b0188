"""Hardware-efficiency cost model (edl_tpu/obs/costmodel.py):

* the formula-dedup pin — bench.py, exp_mfu's peak lookup, and
  models/llama.py must all agree with the shared cost model on the r05
  flagship config (incl. the PUBLISHED 5637.1 MFLOPs/token figure);
* ground truth — analytic FLOPs vs XLA's own
  ``lower(...).cost_analysis()["flops"]`` for the train step and the
  decode-horizon block (tolerance-gated; skipped when the build's
  cost_analysis is unavailable);
* device-peak table semantics + env overrides;
* the EfficiencyMeter gauges (the compile watch's own tests are in
  tests/test_program_names.py);
* the ElasticTrainer live-MFU wiring (flops_per_example ->
  edl_mfu{phase="train"}).
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from edl_tpu.models import llama
from edl_tpu.obs import compilewatch
from edl_tpu.obs import costmodel as cm
from edl_tpu.obs import events as flight
from edl_tpu.obs import metrics as om


@pytest.fixture(autouse=True)
def _fresh_warmup():
    compilewatch.reset()
    yield
    compilewatch.reset()


def flagship_cfg():
    import bench

    return bench.flagship_train_config()


# ---------------------------------------------------------------------------
# formula dedup (ISSUE 8 satellite: three call sites, one formula)


def test_llama_train_flops_delegates_and_pins_published_figure():
    cfg = flagship_cfg()
    ours = cm.train_flops_per_token(cfg, 2048)
    assert llama.train_flops_per_token(cfg, 2048) == ours
    # BENCH_r02..r05 published llama_flops_per_token = 5637.1 MFLOPs
    assert round(ours / 1e6, 1) == 5637.1


def test_bench_decode_step_bytes_delegates():
    import bench

    cfg = bench.flagship_decode_config()
    pb = 2 * cm.n_params(cfg)  # bf16 export
    for b, s in ((1, 704), (8, 704), (32, 704)):
        assert bench._decode_step_bytes(cfg, pb, b, s) == cm.decode_step_bytes(
            cfg, pb, b, s
        )
    # the KV term is exactly the bench's original formula
    kv = 2 * cfg.n_layers * 8 * 704 * cfg.n_kv_heads * cfg.head_dim * 2
    assert cm.decode_step_bytes(cfg, pb, 8, 704) == pb + kv


def test_peak_table_matches_bench_values():
    import bench

    class D:
        def __init__(self, kind):
            self.device_kind = kind

    for kind, fl, bw in (
        ("TPU v5 lite", 197e12, 819e9),
        ("TPU v5e", 197e12, 819e9),
        ("TPU v5p", 459e12, 2765e9),
        ("TPU v5", 459e12, 2765e9),
        ("TPU v4", 275e12, 1228e9),
        ("TPU v6e", 918e12, 1640e9),
    ):
        assert bench._peak_flops(D(kind)) == fl, kind
        assert bench._peak_hbm_bw(D(kind)) == bw, kind
        assert cm.peak_for_kind(kind).flops == fl
        assert cm.peak_for_kind(kind).hbm_bytes_s == bw
    # a device the table does not know is an error, never a default
    for call in (bench._peak_flops, bench._peak_hbm_bw, cm.peak_for_device):
        with pytest.raises(KeyError, match="weird-backend"):
            call(D("weird-backend"))


def test_detect_peak_unknown_accelerator_raises_cpu_is_nominal(monkeypatch):
    class D:
        def __init__(self, platform, kind):
            self.platform, self.device_kind = platform, kind

    monkeypatch.delenv("EDL_PEAK_TFLOPS", raising=False)
    monkeypatch.delenv("EDL_PEAK_HBM_GBS", raising=False)
    assert cm.detect_peak(D("cpu", "cpu")).kind == "host-nominal"
    assert cm.detect_peak(D("tpu", "TPU v5 lite")).flops == 197e12
    with pytest.raises(KeyError):
        cm.detect_peak(D("tpu", "TPU v9 mystery"))
    # the escape hatch names BOTH rates for a kind the table predates
    monkeypatch.setenv("EDL_PEAK_TFLOPS", "100")
    monkeypatch.setenv("EDL_PEAK_HBM_GBS", "500")
    p = cm.detect_peak(D("tpu", "TPU v9 mystery"))
    assert (p.flops, p.hbm_bytes_s) == (100e12, 500e9)


def test_detect_peak_env_override(monkeypatch):
    monkeypatch.setenv("EDL_PEAK_TFLOPS", "123")
    monkeypatch.setenv("EDL_PEAK_HBM_GBS", "456")
    p = cm.detect_peak()
    assert p.flops == 123e12
    assert p.hbm_bytes_s == 456e9
    assert p.kind.endswith("+env")


def test_moe_activated_flops_counts_topk_not_all_experts():
    from edl_tpu.models.moe import MoEConfig

    dense_like = MoEConfig(n_experts=1, top_k=1)
    moe = MoEConfig(n_experts=8, top_k=2)
    # activated (per-token) params scale the ffn term by top_k=2 …
    assert cm.matmul_params(moe) < 3 * cm.matmul_params(dense_like)
    # … while the at-rest state counts ALL 8 experts
    assert cm.n_params(moe) > 6 * cm.n_params(dense_like) / 2
    ctr = cm.ctr_train_flops_per_example()
    assert ctr > 0 and math.isfinite(ctr)


# ---------------------------------------------------------------------------
# ground truth: XLA's own cost analysis (CPU; tolerance-gated)


def _xla_flops(lowered):
    try:
        ca = lowered.compile().cost_analysis()
    except Exception:  # noqa: BLE001 - capability probe, skip below
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    v = ca.get("flops")
    return float(v) if v and math.isfinite(v) and v > 0 else None


def test_train_flops_vs_xla_cost_analysis():
    # n_layers=1: jax's cost_analysis counts a lax.scan BODY once,
    # independent of trip count, so the layer scan must have trip
    # count 1 for the comparison to be apples-to-apples
    import dataclasses

    cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab=512), n_layers=1)
    B, T = 2, 64
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    loss_fn = llama.make_loss_fn(cfg)
    batch = {"tokens": jnp.zeros((B, T + 1), jnp.int32)}

    def fwd_bwd(p, b):
        return jax.value_and_grad(loss_fn)(p, b)

    flops = _xla_flops(jax.jit(fwd_bwd).lower(params, batch))
    if flops is None:
        pytest.skip("cost_analysis unavailable on this jax build")
    analytic = B * T * cm.train_flops_per_token(cfg, T)
    ratio = analytic / flops
    # the analytic model counts matmul+attention model FLOPs; XLA adds
    # norms/rope/softmax/CE and its per-op accounting differs in small
    # ways — the gate pins scale and exponents, not the last few %
    assert 0.6 < ratio < 1.5, (analytic, flops, ratio)


def test_decode_block_flops_vs_xla_cost_analysis():
    # horizon=1 for the same scan-body-counted-once reason; the layer
    # loop inside decode_step_slots is UNROLLED, so L=2 is fine here
    cfg = llama.LlamaConfig.tiny(vocab=512)
    B, S, H = 2, 32, 1
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    kvh, hd, L = cfg.n_kv_heads, cfg.head_dim, cfg.n_layers

    def block(p, tok, pos, active, rem, eosv, kc, vc):
        return llama.decode_horizon_slots(
            p, tok, pos, active, rem, eosv, kc, vc, cfg, horizon=H
        )

    args = (
        params,
        jnp.zeros(B, jnp.int32),
        jnp.zeros(B, jnp.int32),
        jnp.ones(B, bool),
        jnp.full((B,), 8, jnp.int32),
        jnp.full((B,), -1, jnp.int32),
        jnp.zeros((L, B, S, kvh, hd), jnp.float32),
        jnp.zeros((L, B, S, kvh, hd), jnp.float32),
    )
    flops = _xla_flops(jax.jit(block).lower(*args))
    if flops is None:
        pytest.skip("cost_analysis unavailable on this jax build")
    analytic = cm.CostModel(cfg, peak=cm.peak_for_kind("v5e")).decode_block(
        B, H, S
    ).flops
    ratio = analytic / flops
    assert 0.6 < ratio < 1.5, (analytic, flops, ratio)


def test_int8_kv_decode_block_flops_and_bytes_vs_xla():
    """The quantized-KV paged decode program prices like the float one
    on FLOPs (dequant is a few multiplies against the matmul bill) —
    pinned against XLA's own cost_analysis — while the analytic BYTE
    ledger takes the KV dtype width + scale planes into account."""
    cfg = llama.LlamaConfig.tiny(vocab=512)
    B, H, bs, nb, M = 2, 1, 8, 9, 4  # S = M*bs = 32
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    kvh, hd, L = cfg.n_kv_heads, cfg.head_dim, cfg.n_layers
    hdp = llama.kvq_packed_head_dim("int8", hd)

    def block(p, tok, pos, table, kc, vc, ks, vs):
        return llama.decode_step_slots_paged(
            p, tok, pos, table, (kc, vc, ks, vs), cfg, bs, kv_quant="int8",
        )

    args = (
        params,
        jnp.zeros(B, jnp.int32),
        jnp.zeros(B, jnp.int32),
        jnp.zeros((B, M), jnp.int32),
        jnp.zeros((L, nb, bs, kvh, hdp), jnp.int8),
        jnp.zeros((L, nb, bs, kvh, hdp), jnp.int8),
        jnp.zeros((L, nb, kvh), jnp.float32),
        jnp.zeros((L, nb, kvh), jnp.float32),
    )
    S = M * bs
    model = cm.CostModel(
        cfg, peak=cm.peak_for_kind("v5e"),
        kv_bytes_per_el=1.0, kv_block_size=bs,
    )
    flops = _xla_flops(jax.jit(block).lower(*args))
    if flops is None:
        pytest.skip("cost_analysis unavailable on this jax build")
    ratio = model.decode_block(B, H, S).flops / flops
    assert 0.6 < ratio < 1.5, (model.decode_block(B, H, S).flops, flops)
    # the byte ledger: int8 KV reads half the float figure + scales
    b_int8 = model.decode_block(B, H, S).hbm_bytes
    b_f = cm.CostModel(cfg, peak=cm.peak_for_kind("v5e")).decode_block(
        B, H, S
    ).hbm_bytes
    assert b_int8 < b_f
    assert b_int8 == H * cm.decode_step_bytes(
        cfg, model.param_bytes, B, S,
        kv_bytes_per_el=1.0, kv_block_size=bs,
    )


# ---------------------------------------------------------------------------
# EfficiencyMeter


def test_efficiency_meter_publishes_ratio_gauges():
    reg = om.MetricsRegistry()
    peak = cm.DevicePeak("test", 1e12, 1e11)
    meter = cm.EfficiencyMeter(peak, registry=reg)
    meter.observe("decode", cm.Cost(flops=5e11, hbm_bytes=5e10), seconds=1.0)
    assert reg.get("edl_mfu").value(phase="decode") == pytest.approx(0.5)
    assert reg.get("edl_bw_util_ratio").value(phase="decode") == pytest.approx(0.5)
    # cumulative: another second at zero work halves the rates
    meter.observe("decode", cm.Cost(0.0, 0.0), seconds=1.0)
    assert reg.get("edl_mfu").value(phase="decode") == pytest.approx(0.25)
    assert reg.get("edl_costmodel_flops_total").value(phase="decode") == 5e11
    # non-positive time is ignored, not a divide-by-zero
    meter.observe("decode", cm.Cost(1.0, 1.0), seconds=0.0)
    assert reg.get("edl_costmodel_flops_total").value(phase="decode") == 5e11
    meter.set_rates("train", 2.5e11, 2.5e10)
    assert reg.get("edl_mfu").value(phase="train") == pytest.approx(0.25)


def test_efficiency_snapshot_flattens_gauges():
    reg = om.MetricsRegistry()
    meter = cm.EfficiencyMeter(cm.DevicePeak("t", 1e12, 1e11), registry=reg)
    meter.set_rates("decode", 1e11, 1e10)
    snap = cm.efficiency_snapshot(reg)
    assert snap["mfu_decode"] == pytest.approx(0.1)
    assert snap["bw_util_decode"] == pytest.approx(0.1)


# ---------------------------------------------------------------------------
# trainer wiring: live train MFU


def test_elastic_trainer_publishes_train_mfu():
    import optax

    from edl_tpu.obs import memledger
    from edl_tpu.runtime.elastic import ElasticTrainer

    reg = om.reset_default_registry()
    # the default ledger binds its gauges at construction — pair the
    # registry swap with a ledger swap so they publish together
    memledger.reset_default_ledger(reg)
    try:
        cfg = llama.LlamaConfig.tiny(vocab=64)
        seq = 16
        trainer = ElasticTrainer(
            llama.make_loss_fn(cfg),
            optax.adam(1e-3),
            chips_per_worker=1,
            per_chip_batch=2,
            flops_per_example=seq * cm.train_flops_per_token(cfg, seq),
            hbm_bytes_per_example=cm.train_step_bytes(cfg, seq),
        )
        rng = np.random.RandomState(0)
        trainer.start(llama.init_params(jax.random.PRNGKey(0), cfg), 1)
        trainer.train_steps(
            lambda b: llama.synthetic_tokens(rng, b, seq, cfg.vocab), 2
        )
        assert reg.get("edl_mfu").value(phase="train") > 0
        assert reg.get("edl_bw_util_ratio").value(phase="train") > 0
        # the ledger carries the trainer's state
        assert reg.get("edl_hbm_bytes").value(category="params") > 0
        assert reg.get("edl_hbm_bytes").value(category="opt") > 0
    finally:
        memledger.reset_default_ledger(om.reset_default_registry())
