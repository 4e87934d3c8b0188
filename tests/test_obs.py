"""Unified telemetry (edl_tpu/obs): registry semantics, Prometheus
text exposition (golden), live exporter scrape, fleet push/aggregate,
tracer bridge, and the monitor-source round trips."""

import json
import threading
import urllib.request

import pytest

from edl_tpu import obs
from edl_tpu.monitor.collector import (
    MonitorSample,
    ServingSource,
    StoreSource,
)
from edl_tpu.obs.metrics import percentile_from_buckets
from edl_tpu.utils import tracing


# ---------------------------------------------------------------------------
# registry semantics


def test_counter_gauge_histogram_basics():
    r = obs.MetricsRegistry()
    c = r.counter("edl_t_total", "t", ("kind",))
    c.inc(kind="a")
    c.inc(2, kind="a")
    c.inc(kind="b")
    assert c.value(kind="a") == 3 and c.value(kind="b") == 1
    with pytest.raises(ValueError):
        c.inc(-1, kind="a")  # counters only go up
    g = r.gauge("edl_t_gauge", "g")
    g.set(7)
    g.set(3.5)
    assert g.value() == 3.5
    h = r.histogram("edl_t_seconds", "h", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0):
        h.observe(v)
    st = h.stats()
    assert st["count"] == 4 and st["sum"] == pytest.approx(6.05)
    # p50 lands inside the (0.1, 1.0] bucket
    assert 0.1 < h.percentile(0.5) <= 1.0
    # +Inf clamps to the largest finite edge
    h.observe(100.0)
    assert h.percentile(0.999) == 10.0


def test_get_or_create_and_schema_collision():
    r = obs.MetricsRegistry()
    a = r.counter("edl_same_total", "x", ("k",))
    b = r.counter("edl_same_total", "x", ("k",))
    assert a is b
    with pytest.raises(ValueError):
        r.gauge("edl_same_total", "x", ("k",))  # kind clash
    with pytest.raises(ValueError):
        r.counter("edl_same_total", "x", ("other",))  # label clash
    with pytest.raises(ValueError):
        a.inc(k="v", extra="nope")  # unknown label


def test_weighted_histogram_observations():
    r = obs.MetricsRegistry()
    h = r.histogram("edl_w_seconds", "w", buckets=(0.01, 0.1, 1.0))
    h.observe(0.05, n=7)  # one drain, 7 tokens at the per-token mean
    st = h.stats()
    assert st["count"] == 7 and st["sum"] == pytest.approx(0.35)


def test_registry_thread_safety_under_contention():
    r = obs.MetricsRegistry()
    c = r.counter("edl_race_total", "r")
    h = r.histogram("edl_race_seconds", "r")

    def work():
        for _ in range(1000):
            c.inc()
            h.observe(0.01)

    ts = [threading.Thread(target=work) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value() == 4000
    assert h.stats()["count"] == 4000


# ---------------------------------------------------------------------------
# exposition: golden text + parse round trip


def test_prometheus_text_golden():
    """Pin the exposition format: HELP/TYPE lines, label quoting,
    cumulative buckets, sum/count, value formatting."""
    r = obs.MetricsRegistry()
    r.counter("edl_req_total", "requests by event", ("event",)).inc(
        3, event="ok"
    )
    r.gauge("edl_depth", "queue depth").set(2)
    h = r.histogram("edl_lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    assert r.render() == (
        "# HELP edl_depth queue depth\n"
        "# TYPE edl_depth gauge\n"
        "edl_depth 2\n"
        "# HELP edl_lat_seconds latency\n"
        "# TYPE edl_lat_seconds histogram\n"
        'edl_lat_seconds_bucket{le="0.1"} 1\n'
        'edl_lat_seconds_bucket{le="1.0"} 2\n'
        'edl_lat_seconds_bucket{le="+Inf"} 3\n'
        "edl_lat_seconds_sum 5.55\n"
        "edl_lat_seconds_count 3\n"
        "# HELP edl_req_total requests by event\n"
        "# TYPE edl_req_total counter\n"
        'edl_req_total{event="ok"} 3\n'
    )


def test_label_escaping():
    r = obs.MetricsRegistry()
    r.counter("edl_esc_total", "e", ("path",)).inc(path='a"b\\c\nd')
    text = r.render()
    assert 'path="a\\"b\\\\c\\nd"' in text
    parsed = obs.parse_prometheus_text(text)
    (labels, v), = parsed["edl_esc_total"]
    assert v == 1 and labels["path"] == 'a"b\\c\nd'


def test_label_escaping_edge_cases_round_trip():
    """The pathological label values the ISSUE-5 satellite pins: a
    literal backslash followed by ``n`` (the old chained-replace parser
    turned the escaped backslash's tail into a newline), values ending
    in quotes/backslashes, lone escapes, and multi-label lines where an
    escaped quote must not terminate the value early."""
    cases = [
        "a\\nb",        # backslash + 'n' — NOT a newline
        "a\nb",         # a real newline
        'quote"end',
        'end"',
        "trail\\",
        "\\",
        '"',
        'mix\\"x\nand"more\\\\',
        "comma,inside",
        "",
    ]
    r = obs.MetricsRegistry()
    c = r.counter("edl_edge_total", "e", ("v", "other"))
    for i, v in enumerate(cases):
        c.inc(i + 1, v=v, other=f'p,"{i}\\')
    parsed = obs.parse_prometheus_text(r.render())
    got = {lv["v"]: (lv["other"], n) for lv, n in parsed["edl_edge_total"]}
    for i, v in enumerate(cases):
        assert v in got, f"case {i}: {v!r} lost in round trip: {sorted(got)}"
        other, n = got[v]
        assert other == f'p,"{i}\\' and n == i + 1, (v, other, n)


def test_empty_histogram_renders_inf_bucket_and_nan_free_percentiles():
    """An empty histogram still exposes its full cumulative schema
    (+Inf bucket, sum, count, all zero) and every percentile surface
    answers 0.0 — never NaN — through both the direct and the parsed
    paths."""
    import math

    r = obs.MetricsRegistry()
    h = r.histogram("edl_empty_seconds", "empty", buckets=(0.1, 1.0))
    text = r.render()
    assert 'edl_empty_seconds_bucket{le="+Inf"} 0' in text
    assert "edl_empty_seconds_sum 0" in text
    assert "edl_empty_seconds_count 0" in text
    for q in (0.5, 0.95, 0.99):
        direct = h.percentile(q)
        assert direct == 0.0 and not math.isnan(direct)
    parsed = obs.parse_prometheus_text(text)
    for q in (0.5, 0.95, 0.99):
        v = percentile_from_buckets(parsed["edl_empty_seconds_bucket"], q)
        assert v == 0.0 and not math.isnan(v)
    # no bucket samples at all (the degenerate consumer input)
    assert percentile_from_buckets([], 0.99) == 0.0
    # +Inf-only observations clamp to the largest finite edge
    h.observe(50.0)
    assert h.percentile(0.5) == 1.0
    parsed = obs.parse_prometheus_text(r.render())
    assert percentile_from_buckets(
        parsed["edl_empty_seconds_bucket"], 0.5
    ) == 1.0


def test_parse_and_percentile_round_trip():
    r = obs.MetricsRegistry()
    h = r.histogram("edl_rt_seconds", "rt")
    for v in (0.002, 0.004, 0.02, 0.3, 2.0):
        h.observe(v)
    parsed = obs.parse_prometheus_text(r.render())
    for q in (0.5, 0.95, 0.99):
        assert percentile_from_buckets(
            parsed["edl_rt_seconds_bucket"], q
        ) == pytest.approx(h.percentile(q))


def test_core_series_catalog_always_renders():
    """A scrape of any edl process shows the full unlabeled schema
    zero-valued before any observation (the acceptance criterion's
    'training, serving, and reshard series present')."""
    r = obs.ensure_core_series(obs.MetricsRegistry())
    text = r.render()
    for name in (
        "edl_train_step_seconds_count 0",
        "edl_serving_ttft_seconds_count 0",
        "edl_serving_queue_depth 0",
        "edl_reshard_stall_seconds_count 0",
        "# TYPE edl_serving_dispatch_total counter",
        "# TYPE edl_reshard_total counter",
    ):
        assert name in text, name


# ---------------------------------------------------------------------------
# snapshot / merge (fleet aggregation)


def _worker_snapshot(ttft: float, tokens: int) -> str:
    r = obs.MetricsRegistry()
    r.counter("edl_serving_tokens_total", "t").inc(tokens)
    r.histogram("edl_serving_ttft_seconds", "t").observe(ttft)
    r.gauge("edl_serving_queue_depth", "q").set(1)
    return r.snapshot_json()


def test_snapshot_merge_labels_by_worker():
    agg = obs.aggregate_snapshots(
        {"w0": _worker_snapshot(0.02, 10), "w1": _worker_snapshot(0.2, 30)}
    )
    text = agg.render()
    assert 'edl_serving_tokens_total{worker="w0"} 10' in text
    assert 'edl_serving_tokens_total{worker="w1"} 30' in text
    # fleet percentile sums buckets across the worker label
    parsed = obs.parse_prometheus_text(text)
    p99 = percentile_from_buckets(parsed["edl_serving_ttft_seconds_bucket"], 0.99)
    assert 0.1 < p99 <= 0.25  # the slow worker's bucket dominates the tail
    assert agg.gauge("edl_fleet_reporting_workers", "").value() == 0  # not set here


def test_aggregate_skips_corrupt_snapshot():
    agg = obs.aggregate_snapshots(
        {"good": _worker_snapshot(0.01, 5), "bad": "{not json"}
    )
    assert 'worker="good"' in agg.render()


def test_metrics_pusher_publishes_and_final_push():
    seen = []
    reg = obs.MetricsRegistry()
    reg.counter("edl_p_total", "p").inc(4)
    p = obs.MetricsPusher(seen.append, interval_s=3600, registry=reg)
    assert p.push_once()
    p.stop(final_push=True)
    assert len(seen) == 2
    snap = json.loads(seen[-1])
    fam = next(f for f in snap["families"] if f["name"] == "edl_p_total")
    assert fam["samples"][0]["value"] == 4


def test_collect_fleet_aggregates_member_and_extra_snapshots():
    """The coordinator-side scrape pass: live members' pushed
    snapshots + reserved non-member sources (dist_service), labeled
    per worker, counted in edl_fleet_reporting_workers."""
    from edl_tpu.runtime.coordinator import PyCoordinator

    c = PyCoordinator()
    c.register("w0", 1)
    c.register("w1", 1)
    c.kv_put(obs.metrics_key("job", "w0"), _worker_snapshot(0.01, 5))
    c.kv_put(obs.metrics_key("job", "w1"), _worker_snapshot(0.02, 7))
    svc = obs.MetricsRegistry()
    svc.gauge("edl_dist_service_up", "up", ("epoch",)).set(1, epoch="3")
    c.kv_put(obs.metrics_key("job", "dist_service"), svc.snapshot_json())
    reg = obs.collect_fleet(c, "job", ("dist_service",))
    text = reg.render()
    assert 'edl_serving_tokens_total{worker="w0"} 5' in text
    assert 'edl_serving_tokens_total{worker="w1"} 7' in text
    assert 'edl_dist_service_up{epoch="3",worker="dist_service"} 1' in text
    assert "edl_fleet_reporting_workers 3" in text
    # a member with no pushed snapshot yet just doesn't report
    c.register("w2", 1)
    reg = obs.collect_fleet(c, "job")
    assert "edl_fleet_reporting_workers 2" in reg.render()


def test_pusher_survives_failing_publish():
    def boom(_):
        raise ConnectionError("down")

    p = obs.MetricsPusher(boom, interval_s=3600)
    assert p.push_once() is False  # swallowed, telemetry never raises


def test_pusher_backoff_state_lock_guarded_under_contention():
    """push_once runs on the pusher thread AND from stop()'s last-gasp
    call while next_wait_s polls the streak — the backoff state is
    lock-guarded (`edl check` lockset-race finding). Hammer failing
    pushes from many threads: every increment must land (unlocked
    `+= 1` loses updates under bytecode interleaving), and one success
    must reset the streak for every observer."""
    import threading

    fail = {"on": True}

    def pub(_):
        if fail["on"]:
            raise ConnectionError("down")

    reg = obs.MetricsRegistry()
    p = obs.MetricsPusher(pub, interval_s=1.0, backoff_cap_s=64.0, registry=reg)
    n_threads, n_pushes = 8, 50
    start = threading.Barrier(n_threads)

    def hammer():
        start.wait()
        for _ in range(n_pushes):
            p.push_once()
            assert p.next_wait_s() >= 0.5  # jitter floor of the backoff

    threads = [threading.Thread(target=hammer) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert p._fail_streak == n_threads * n_pushes  # no lost increments
    fail["on"] = False
    assert p.push_once() is True
    assert p._fail_streak == 0
    assert p.next_wait_s() == 1.0  # healthy cadence restored


# ---------------------------------------------------------------------------
# live exporter scrape


def test_exporter_live_scrape_metrics_trace_healthz():
    reg = obs.MetricsRegistry()
    reg.counter("edl_live_total", "live").inc(5)
    tr = tracing.Tracer(max_spans=2)
    with tr.span("phase.one"):
        pass
    tr.record("x", 0.0, 0.1)
    tr.record("y", 0.0, 0.1)  # evicts phase.one -> dropped=1
    with obs.MetricsExporter(reg, port=0, tracer=tr) as exp:
        url = exp.url
        # /metrics: valid exposition with the core catalog + our series
        req = urllib.request.urlopen(f"{url}/metrics", timeout=5)
        assert req.status == 200
        assert "text/plain" in req.headers["Content-Type"]
        text = req.read().decode()
        assert "edl_live_total 5" in text
        assert "edl_serving_ttft_seconds_bucket" in text  # core catalog
        assert "edl_reshard_stall_seconds_count" in text
        parsed = obs.parse_prometheus_text(text)
        assert parsed["edl_live_total"] == [({}, 5.0)]
        # /trace: chrome-trace JSON with ring-buffer metadata
        doc = json.loads(obs.scrape(exp.url, "/trace"))
        assert doc["dropped"] == 1
        names = {e["name"] for e in doc["traceEvents"]}
        assert "y" in names and "edl_tracer" in names
        # /healthz
        hz = json.loads(obs.scrape(exp.url, "/healthz"))
        assert hz["status"] == "ok" and hz["uptime_s"] >= 0
        # unknown path -> 404
        with pytest.raises(urllib.error.HTTPError):
            obs.scrape(exp.url, "/nope")
    # server is down after stop
    with pytest.raises(OSError):
        obs.scrape(url, "/metrics", timeout_s=1)


def test_exporter_callable_source_reevaluates_per_scrape():
    calls = []

    def collect():
        r = obs.MetricsRegistry()
        calls.append(1)
        r.gauge("edl_n_scrapes", "n").set(len(calls))
        return r

    with obs.MetricsExporter(collect, port=0) as exp:
        assert "edl_n_scrapes 1" in obs.scrape(exp.url)
        assert "edl_n_scrapes 2" in obs.scrape(exp.url)


# ---------------------------------------------------------------------------
# tracer -> histogram bridge


def test_bridge_tracer_observes_spans_as_histograms():
    reg = obs.MetricsRegistry()
    tr = tracing.Tracer()
    listener = obs.bridge_tracer(reg, tr)
    try:
        with tr.span("reshard"):
            pass
        tr.record("checkpoint.save_shards", 0.0, 0.25)
        h = reg.get("edl_span_seconds")
        assert h.stats(name="reshard")["count"] == 1
        assert h.stats(name="checkpoint.save_shards")["sum"] == pytest.approx(0.25)
        text = reg.render()
        assert 'edl_span_seconds_bucket{name="reshard",le=' in text
    finally:
        tr.remove_listener(listener)


def test_bridge_sees_the_engine_steps_host_phases():
    """``serving.account`` and ``serving.replay`` reach the bridge like
    the engine's older spans, one histogram series a name; the older
    names count what they counted (one dispatch and one drain a block)."""
    import jax

    from edl_tpu.models import llama
    from edl_tpu.serving.engine import ContinuousBatchingEngine

    reg = obs.MetricsRegistry()
    listener = obs.bridge_tracer(reg, tracing.tracer())
    try:
        cfg = llama.LlamaConfig.tiny()
        eng = ContinuousBatchingEngine(
            llama.init_params(jax.random.PRNGKey(0), cfg), cfg,
            max_slots=2, max_len=64)
        eng.submit("r1", [2, 3, 4], 6)
        eng.run()
        h = reg.get("edl_span_seconds")
        blocks = h.stats(name="serving.dispatch")["count"]
        # the prefill gives the first of six tokens, and the double
        # buffer has one block more in flight when the last is drained
        assert blocks == 6
        assert h.stats(name="serving.drain")["count"] == blocks
        assert h.stats(name="serving.replay")["count"] == blocks
        # the step's gauges once a step, a dispatch's preparation once
        # a block
        steps = h.stats(name="serving.step")["count"]
        assert h.stats(name="serving.account")["count"] == steps + blocks
        assert h.stats(name="serving.replay")["sum"] < \
            h.stats(name="serving.step")["sum"]
    finally:
        tracing.tracer().remove_listener(listener)


# ---------------------------------------------------------------------------
# monitor-source round trips (StoreSource / ServingSource -> registry)


class _FakeStore:
    """Duck-typed JobStore: the StoreSource contract, no disk."""

    def read_cluster(self):
        return {
            "cpu_total_milli": 8000,
            "cpu_request_milli": 2000,
            "chip_total": 16,
            "chip_request": 8,
        }

    def list_keys(self):
        return [("default", "ctr")]

    def list_statuses(self):
        return {
            ("default", "ctr"): {
                "running": 3,
                "pending": 0,
                "parallelism": 4,
                "phase": "running",
                "reshard_count": 2,
                "last_reshard_stall_s": 1.25,
                "reshard_fallbacks": 1,
            }
        }


def test_store_source_snapshot_round_trip():
    sample = StoreSource(_FakeStore()).sample()
    reg = obs.registry_from_sample(sample)
    parsed = obs.parse_prometheus_text(reg.render())
    assert parsed["edl_fleet_chip_total"] == [({}, 16.0)]
    assert parsed["edl_fleet_chip_util_pct"] == [({}, 50.0)]
    (labels, v), = parsed["edl_job_workers"]
    assert labels == {"job": "ctr"} and v == 3
    (_, stall), = parsed["edl_job_last_reshard_stall_seconds"]
    assert stall == 1.25
    (_, resh), = parsed["edl_job_reshards"]
    assert resh == 2


def test_serving_source_snapshot_round_trip():
    from edl_tpu.serving.metrics import ServingMetrics

    t = [0.0]
    m = ServingMetrics(
        clock=lambda: t[0], registry=obs.MetricsRegistry()
    )
    m.on_submit("r1")
    t[0] = 0.5
    m.on_admit("r1", 4)
    m.on_token("r1")
    t[0] = 0.6
    m.on_tokens("r1", 4)
    m.on_step(1, 8, 2)
    sample = ServingSource(m).sample()
    reg = obs.registry_from_sample(sample)
    parsed = obs.parse_prometheus_text(reg.render())
    by_key = {
        lv["key"]: v for lv, v in parsed["edl_serving_snapshot"]
    }
    # every snapshot scalar round-trips through the registry exactly
    for k, v in m.snapshot().items():
        assert by_key[k] == pytest.approx(v), k
    assert by_key["queue_depth"] == 2
    assert by_key["tokens_out"] == 5
    assert by_key["ttft_p50_s"] > 0


def test_worker_telemetry_exporter_and_push(monkeypatch):
    """ElasticWorker telemetry bring-up: EDL_METRICS_PORT starts the
    exporter and advertises the bound address in coordinator KV;
    metrics_push_s pushes snapshots to {job}/metrics/{worker}; stop
    does a final push."""
    from edl_tpu.runtime.coordinator import (
        CoordinatorServer,
        ensure_native_built,
    )

    if not ensure_native_built():
        pytest.skip("no C++ toolchain")
    with CoordinatorServer(member_ttl_s=5.0) as srv:
        for k, v in {
            "EDL_JOB_NAME": "tj", "EDL_WORKER_ID": "w0",
            "EDL_COORDINATOR": f"127.0.0.1:{srv.port}",
            "EDL_METRICS_PORT": "0", "EDL_METRICS_PUSH_S": "30",
        }.items():
            monkeypatch.setenv(k, v)
        from edl_tpu.runtime.worker_config import WorkerConfig
        from edl_tpu.runtime.worker_main import ElasticWorker

        cfg = WorkerConfig.from_env()
        assert cfg.metrics_port == 0 and cfg.metrics_push_s == 30
        w = ElasticWorker(cfg)
        try:
            w._telemetry_start()
            addr = w.client.kv_get("tj/metrics_addr/w0")
            assert addr and addr.startswith("127.0.0.1:")
            text = obs.scrape(addr)
            assert "edl_train_step_seconds_count" in text
            assert "edl_serving_ttft_seconds_count" in text
        finally:
            w._telemetry_stop()
        snap = w.client.kv_get(obs.metrics_key("tj", "w0"))
        assert snap and "edl_train_steps_total" in snap  # final push
        w.client.close()


def test_monitor_sample_to_record_is_jsonable():
    s = MonitorSample(
        ts=1.0,
        submitted_jobs=["j"],
        running_workers={"j": 2},
        chip_total=8,
        chip_request=4,
        serving={"tokens_out": 3.0},
    )
    rec = json.loads(json.dumps(s.to_record()))
    assert rec["chip_util"] == 50.0
    assert rec["running_workers"] == {"j": 2}
    assert rec["serving"]["tokens_out"] == 3.0


# ---------------------------------------------------------------------------
# serving percentiles surface in the collector render


def test_serving_lines_render_percentiles():
    from edl_tpu.serving.metrics import ServingMetrics

    t = [0.0]
    m = ServingMetrics(clock=lambda: t[0], registry=obs.MetricsRegistry())
    m.on_submit("a")
    t[0] = 0.03
    m.on_admit("a", 2)
    m.on_token("a")
    sample = ServingSource(m).sample()
    text = sample.render()
    assert "latency: ttft p50/p95/p99=" in text
    assert "itl p50/p95/p99=" in text
    # ttft ~30ms lands in the (0.025, 0.05] bucket
    assert 0.025 <= m.snapshot()["ttft_p50_s"] <= 0.05
