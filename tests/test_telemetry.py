"""End-to-end telemetry (round 7): MetricsRegistry semantics, pipeline
stage recording across every ingest path, hostpool gauges, the service
/metrics endpoint + STATS frame, and warning-once capping.

The registry is process-global and cumulative, so assertions here are
DELTA-based (before/after), never absolute — other test modules feed the
same registry.
"""
import json
import logging
import time
import urllib.error
import urllib.request

import pytest

from _shared_parsers import shared_parser
from logparser_tpu.observability import (
    Histogram,
    MetricsRegistry,
    log_warning_once,
    metrics,
    pipeline_stage,
    reset_warning_once,
    suppressed_warning_counts,
)
from logparser_tpu.tools.metrics_smoke import validate_exposition

FIELDS = ["IP:connection.client.host", "BYTES:response.body.bytes"]
# Plausible-but-device-rejected: a referer ending in a backslash (raw
# bytes `\" "` — the escaped quote forms a separator occurrence of the
# NON-final referer field, ambiguous against the host regex's
# backtracking, so the device defers by design and the oracle rescues).
# (An escaped quote in the USER-AGENT no longer qualifies: the round-18
# escape-parity mask keeps that final-field class on device, like the
# round-9 full-int64 decoder did for 20-digit %b.)
RESCUE_LINE = (
    '5.6.7.8 - - [31/Dec/2012:23:49:41 +0100] '
    '"GET /big HTTP/1.1" 200 777 "r\\" "t/1.0"'
)
GOOD_LINE = (
    '1.2.3.4 - - [31/Dec/2012:23:49:40 +0100] '
    '"GET /i.html?x=1 HTTP/1.1" 200 512 "-" "t/1.0"'
)


def _parser():
    # view_fields=(): plain executor — stage accounting must not depend
    # on view emission being on.
    return shared_parser("combined", FIELDS, view_fields=())


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------


def test_registry_counters_gauges_and_labels():
    reg = MetricsRegistry()
    reg.increment("lines_total", 10)
    reg.increment("lines_total", 5)
    reg.increment("routed_total", 2, labels={"reason": "overflow"})
    reg.increment("routed_total", 3, labels={"reason": "host_fields"})
    assert reg.get("lines_total") == 15
    assert reg.get("routed_total", labels={"reason": "overflow"}) == 2
    assert reg.get("routed_total") == 0  # unlabeled series is distinct
    reg.gauge_set("depth", 4)
    reg.gauge_add("depth", -1)
    assert reg.gauge_get("depth") == 3
    snap = reg.snapshot()
    assert snap["counters"]['routed_total{reason="overflow"}'] == 2
    assert snap["gauges"]["depth"] == 3
    reg.reset()
    assert reg.get("lines_total") == 0
    assert reg.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}


def test_histogram_semantics_and_percentiles():
    h = Histogram("t", buckets=(0.001, 0.01, 0.1, 1.0))
    for v in (0.0005, 0.002, 0.003, 0.05, 0.5):
        h.observe(v)
    d = h.as_dict()
    assert d["count"] == 5
    assert abs(d["sum"] - 0.5555) < 1e-9
    assert d["min"] == 0.0005 and d["max"] == 0.5
    # p50's rank-2.5 observation lands in the (0.001, 0.01] bucket.
    assert 0.001 <= d["p50"] <= 0.01
    # p99 approaches the max, inside the (0.1, 1.0] bucket tightened by it.
    assert 0.1 <= d["p99"] <= 0.5
    # +Inf overflow bucket catches out-of-range observations.
    h.observe(5.0)
    assert h.as_dict()["buckets"][-1] == ["+Inf", 1]
    assert h.percentile(1.0) == 5.0


def test_registry_histogram_bucket_bounds_fixed_at_creation():
    reg = MetricsRegistry()
    first = reg.histogram("x", buckets=(1, 2, 3))
    again = reg.histogram("x", buckets=(9, 10))  # ignored: get-or-create
    assert again is first
    assert first.buckets == (1.0, 2.0, 3.0)


def test_prometheus_text_well_formed():
    reg = MetricsRegistry()
    reg.increment("lines_total", 3)
    reg.increment("routed", 1, labels={"reason": 'we"ird\\label'})
    reg.gauge_set("workers", 8)
    reg.observe("stage_seconds", 0.004, labels={"stage": "encode"})
    reg.observe("stage_seconds", 20.0, labels={"stage": "encode"})  # +Inf
    text = reg.prometheus_text()
    assert validate_exposition(text) == [], validate_exposition(text)
    assert "# TYPE logparser_tpu_lines_total counter" in text
    assert "# TYPE logparser_tpu_workers gauge" in text
    assert "# TYPE logparser_tpu_stage_seconds histogram" in text
    assert 'le="+Inf"' in text


def test_stage_breakdown_structure():
    reg = MetricsRegistry()
    reg.observe("stage_seconds", 0.002, labels={"stage": "encode"})
    reg.observe("stage_seconds", 0.004, labels={"stage": "encode"})
    reg.increment("stage_items_total", 128, labels={"stage": "encode"})
    bd = reg.stage_breakdown()
    assert set(bd) == {"encode"}
    e = bd["encode"]
    assert e["calls"] == 2 and e["items"] == 128
    assert 0 < e["p50_ms"] <= e["p99_ms"] <= 4.0 + 1e-6
    assert e["items_per_sec"] > 0


def test_pipeline_stage_context_feeds_registry_and_tracer():
    """One span feeds the inclusive histogram, the items counter and the
    self-time counter (the registry is the only stage recorder)."""
    reg = metrics()
    bd = reg.stage_breakdown().get("encode", {})
    calls0, items0 = bd.get("calls", 0), bd.get("items", 0)
    self0 = reg.get("stage_self_seconds_total", labels={"stage": "encode"})
    with pipeline_stage("encode", items=7):
        time.sleep(0.002)
    after = reg.stage_breakdown()["encode"]
    assert after["calls"] == calls0 + 1
    assert after["items"] == items0 + 7
    self1 = reg.get("stage_self_seconds_total", labels={"stage": "encode"})
    assert self1 - self0 >= 0.002


# ---------------------------------------------------------------------------
# hot-path stage recording, all three ingest paths
# ---------------------------------------------------------------------------

PARSE_STAGES = ("encode", "device", "fetch", "columns", "oracle_fallback")


def _stage_calls():
    bd = metrics().stage_breakdown()
    return {s: bd.get(s, {}).get("calls", 0) for s in PARSE_STAGES}


def test_parse_batch_records_stages_and_routing():
    parser = _parser()
    reg = metrics()
    before = _stage_calls()
    routed_before = reg.get(
        "oracle_routed_lines_total", labels={"reason": "device_reject"}
    )
    rescued_before = reg.get("oracle_rescued_lines_total")
    result = parser.parse_batch([GOOD_LINE, RESCUE_LINE, "garbage"])
    after = _stage_calls()
    for stage in PARSE_STAGES:
        assert after[stage] == before[stage] + 1, stage
    assert result.oracle_rows >= 1
    assert reg.get(
        "oracle_routed_lines_total", labels={"reason": "device_reject"}
    ) >= routed_before + 1
    assert reg.get("oracle_rescued_lines_total") >= rescued_before + 1
    # The rescued line delivered its byte count via the host.
    assert result.to_pylist("BYTES:response.body.bytes")[1] == 777


def test_parse_blob_records_stages():
    parser = _parser()
    before = _stage_calls()
    blob = (GOOD_LINE + "\n" + GOOD_LINE).encode("utf-8")
    result = parser.parse_blob(blob)
    assert result.lines_read == 2
    after = _stage_calls()
    for stage in ("encode", "device", "fetch", "columns"):
        assert after[stage] == before[stage] + 1, stage


def test_parse_batch_stream_records_stages():
    parser = _parser()
    before = _stage_calls()
    batches = [[GOOD_LINE] * 4, [GOOD_LINE] * 4, [GOOD_LINE] * 4]
    results = list(parser.parse_batch_stream(iter(batches), depth=2))
    assert [r.lines_read for r in results] == [4, 4, 4]
    after = _stage_calls()
    for stage in ("encode", "device", "fetch", "columns"):
        assert after[stage] == before[stage] + 3, stage


def test_batch_shape_accounting():
    parser = _parser()
    reg = metrics()
    pad_before = reg.get("pad_rows_total")
    lines_before = reg.get("parse_lines_total")
    parser.parse_batch([GOOD_LINE] * 65)  # bucket 128 -> 63 pad rows
    assert reg.get("parse_lines_total") == lines_before + 65
    assert reg.get("pad_rows_total") == pad_before + 63
    # Pad waste is derivable and sane: real bytes never exceed cells.
    assert reg.get("encoded_line_bytes_total") <= reg.get("buffer_cells_total")


# ---------------------------------------------------------------------------
# hostpool gauges / utilization under >= 2 workers
# ---------------------------------------------------------------------------


def test_hostpool_metrics_two_workers():
    from logparser_tpu.tpu.hostpool import AssemblyPool

    reg = metrics()
    tasks_before = reg.get("hostpool_tasks_total")
    busy_before = reg.get("hostpool_busy_seconds_total")
    wall_before = reg.get("hostpool_wall_seconds_total")
    hist_before = reg.histogram("hostpool_task_seconds").count

    pool = AssemblyPool(2)
    try:
        out = pool.run_all([lambda i=i: (time.sleep(0.01), i)[1]
                            for i in range(4)])
    finally:
        pool.close()
    assert out == [0, 1, 2, 3]
    assert reg.get("hostpool_tasks_total") == tasks_before + 4
    assert reg.histogram("hostpool_task_seconds").count == hist_before + 4
    busy = reg.get("hostpool_busy_seconds_total") - busy_before
    wall = reg.get("hostpool_wall_seconds_total") - wall_before
    assert busy >= 0.04 - 0.005  # 4 x 10 ms of sleep
    assert wall > 0
    # Utilization is a real fraction: busy time never exceeds workers*wall.
    assert busy <= pool.workers * wall * 1.5
    # Transient gauges drain back to zero once the run completes.
    assert reg.gauge_get("hostpool_queue_depth") == 0
    assert reg.gauge_get("hostpool_active_workers") == 0
    assert reg.gauge_get("hostpool_workers") == 2


def test_hostpool_serial_path_untouched():
    """The 1-wide pool is the bit-for-bit pre-pool baseline: it must not
    even touch the registry (parity contract)."""
    from logparser_tpu.tpu.hostpool import AssemblyPool

    reg = metrics()
    runs_before = reg.get("hostpool_runs_total")
    pool = AssemblyPool(1)
    assert pool.run_all([lambda: 1, lambda: 2]) == [1, 2]
    assert reg.get("hostpool_runs_total") == runs_before


# ---------------------------------------------------------------------------
# service: /metrics endpoint + STATS frame (parser pre-seeded, no compile)
# ---------------------------------------------------------------------------


def _preseed(svc):
    """Install the shared parser into the service cache under the exact
    key the CONFIG below resolves to, so no service-side compile runs."""
    key = ("combined", tuple(FIELDS), None, None)
    svc._server.parser_cache._parsers[key] = _parser()


def test_service_metrics_endpoint_and_stats_frame():
    from logparser_tpu.service import ParseService, ParseServiceClient

    with ParseService(metrics_port=0) as svc:
        _preseed(svc)
        # Plain v1 session first: no stats key, no trailing frame.
        with ParseServiceClient(svc.host, svc.port, "combined", FIELDS) as c:
            t = c.parse([GOOD_LINE, RESCUE_LINE])
            assert t.num_rows == 2
            assert c.last_stats is None
        # Stats session: ARROW frame + STATS frame per request.
        with ParseServiceClient(
            svc.host, svc.port, "combined", FIELDS, stats=True
        ) as c:
            t = c.parse([GOOD_LINE, RESCUE_LINE, GOOD_LINE])
            assert t.num_rows == 3
            stats = c.last_stats
            assert stats["v"] == 1
            assert stats["request"]["lines"] == 3
            assert stats["request"]["arrow_bytes"] > 0
            assert stats["request"]["oracle_lines"] >= 1
            assert "encode" in stats["stages"]
            assert "device" in stats["stages"]
            # Session survives: a second stats request frames correctly.
            t2 = c.parse([GOOD_LINE])
            assert t2.num_rows == 1
            assert c.last_stats["request"]["lines"] == 1

        url = f"http://{svc.host}:{svc.metrics_port}/metrics"
        with urllib.request.urlopen(url, timeout=10) as resp:
            assert resp.status == 200
            assert "text/plain" in resp.headers["Content-Type"]
            text = resp.read().decode("utf-8")
        # 404 for anything that is not /metrics.
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(url.replace("/metrics", "/nope"),
                                   timeout=10)
    assert validate_exposition(text) == [], validate_exposition(text)
    for needle in (
        'logparser_tpu_stage_seconds_bucket{stage="encode",le="+Inf"}',
        'logparser_tpu_stage_seconds_bucket{stage="assembly",le="+Inf"}',
        'logparser_tpu_stage_seconds_bucket{stage="ipc",le="+Inf"}',
        "logparser_tpu_service_requests_total",
        "logparser_tpu_oracle_routed_lines_total",
        "logparser_tpu_hostpool_workers",
    ):
        assert needle in text, needle


def test_stats_logger_line(caplog):
    from logparser_tpu.service import _StatsLogger

    metrics().increment("service_requests_total", 0)  # ensure key exists
    with caplog.at_level(logging.INFO, logger="logparser_tpu.service"):
        _StatsLogger.log_once()
    assert len(caplog.records) == 1
    message = caplog.records[0].getMessage()
    payload = json.loads(message.split("service stats: ", 1)[1])
    assert "counters" in payload and "stage_p99_ms" in payload


# ---------------------------------------------------------------------------
# warn-once capping (the BENCH_r05-tail localized-timestamp spam)
# ---------------------------------------------------------------------------

LOCALIZED_WARNING = "Only some parts of localized timestamps are supported"


def test_log_warning_once_caps_and_counts(caplog):
    reset_warning_once("repeated telemetry test warning")
    logger = logging.getLogger("test_warn_once")
    with caplog.at_level(logging.WARNING, logger="test_warn_once"):
        for _ in range(5):
            log_warning_once(logger, "repeated telemetry test warning")
    # One message + one suppression notice; the other four only counted.
    assert len(caplog.records) == 2
    assert suppressed_warning_counts()["repeated telemetry test warning"] == 4


def test_localized_timestamp_warning_logged_once(caplog):
    from logparser_tpu.httpd.parser import HttpdLoglineParser
    from logparser_tpu.tpu.batch import _CollectingRecord

    reset_warning_once()  # other suites may already have tripped it

    def build():
        p = HttpdLoglineParser(
            _CollectingRecord,
            '%h %l %u [%{%d/%b/%Y:%H:%M:%S %z}t] "%r" %>s %b',
        )
        p.add_parse_target(
            "set_value", ["TIME.EPOCH:request.receive.time.epoch"]
        )
        p.assemble_dissectors()

    with caplog.at_level(
        logging.WARNING, logger="logparser_tpu.dissectors.tokenformat"
    ):
        build()
        build()  # second assembly must NOT print the warning again
    hits = [r for r in caplog.records if LOCALIZED_WARNING in r.getMessage()]
    assert len(hits) == 1
    assert suppressed_warning_counts().get(LOCALIZED_WARNING, 0) >= 1


# ---------------------------------------------------------------------------
# feeder fabric telemetry (round 8, docs/FEEDER.md)
# ---------------------------------------------------------------------------


def test_feeder_queue_depth_gauge_rises_and_falls():
    """Under a slow consumer the bounded queue fills (producer-updated
    gauge in threads mode) and drains back to 0 on close."""
    from logparser_tpu.feeder import FeederPool

    blob = b"\n".join(b"line %d" % i for i in range(12))
    pool = FeederPool([blob], workers=1, shard_bytes=1 << 20,
                      batch_lines=2, line_len=64, queue_batches=2,
                      use_processes=False)
    stream = pool.batches()
    next(stream)  # start workers, take one batch; then stall
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        if metrics().gauge_get("feeder_queue_depth") >= 2:
            break
        time.sleep(0.01)
    assert metrics().gauge_get("feeder_queue_depth") >= 2, (
        "bounded queue never filled under a stalled consumer"
    )
    rest = list(stream)  # drain; generator exhaustion closes the pool
    assert 1 + len(rest) == 6
    assert metrics().gauge_get("feeder_queue_depth") == 0
    # The consumer's dequeue-time samples run one step behind the
    # producer-updated gauge (it samples right after taking an item),
    # so the recorded max only guarantees the queue was ever non-empty.
    assert pool.stats()["queue_depth_max"] >= 1


def test_feeder_starvation_counter_advances_when_workers_lag():
    """A throttled producer leaves the consumer blocked on an empty
    queue; the seconds counter and the pool stats both advance.  The
    pipeline-fill wait before the FIRST batch is startup, not
    starvation — only post-prime waits count."""
    from logparser_tpu.feeder import FeederPool

    before = metrics().get("feeder_starvation_seconds_total")
    blob = b"\n".join(b"line %d" % i for i in range(8))
    # Delay must exceed the consumer's 0.05 s poll window: starvation is
    # only counted in whole Empty windows (sub-window arrivals are free).
    pool = FeederPool([blob], workers=1, shard_bytes=1 << 20,
                      batch_lines=2, line_len=64, queue_batches=1,
                      use_processes=False, worker_delay_s=0.15)
    batches = list(pool.batches())
    assert len(batches) == 4
    stats = pool.stats()
    assert stats["starvation_s"] > 0.0
    assert stats["startup_s"] >= 0.0
    assert metrics().get("feeder_starvation_seconds_total") > before


def test_feeder_counters_and_stage_timings_accumulate():
    from logparser_tpu.feeder import FeederPool

    reg = metrics()
    before_bytes = reg.get("feeder_bytes_read_total")
    before_shards = reg.get("feeder_shards_total")
    blob = b"\n".join(b"line %d xx" % i for i in range(50))
    pool = FeederPool([blob], workers=2, shard_bytes=100, batch_lines=8,
                      line_len=64, use_processes=False)
    n = sum(eb.source_bytes for eb in pool.batches())
    assert reg.get("feeder_bytes_read_total") - before_bytes == n == len(blob)
    assert reg.get("feeder_shards_total") - before_shards == len(pool.shards)
    # Per-shard/per-batch stage timings flow through observe_stage into
    # the SAME stage_seconds family every other pipeline stage uses.
    breakdown = reg.stage_breakdown()
    for stage in ("feeder_read", "feeder_encode", "feeder_shard"):
        assert stage in breakdown, stage
        assert breakdown[stage]["calls"] > 0


def test_metrics_endpoint_exposes_feeder_families():
    """/metrics (the real HTTP scrape surface) carries the feeder_*
    families once a pool has run, and the exposition stays valid."""
    from logparser_tpu.feeder import FeederPool
    from logparser_tpu.service import MetricsEndpoint

    # Throttled 1-deep queue: guarantees post-prime waits, so the
    # starvation family exists even when this test runs alone.
    blob = b"\n".join(b"line %d" % i for i in range(20))
    list(FeederPool([blob], workers=1, shard_bytes=1 << 20, batch_lines=8,
                    line_len=64, queue_batches=1, use_processes=False,
                    worker_delay_s=0.15).batches())
    endpoint = MetricsEndpoint().start()
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{endpoint.port}/metrics", timeout=10
        ) as resp:
            text = resp.read().decode("utf-8")
    finally:
        endpoint.shutdown()
    for needle in (
        "logparser_tpu_feeder_bytes_read_total",
        "logparser_tpu_feeder_lines_total",
        "logparser_tpu_feeder_batches_total",
        "logparser_tpu_feeder_shards_total",
        'logparser_tpu_stage_seconds_bucket{stage="feeder_encode"',
        'logparser_tpu_stage_seconds_bucket{stage="feeder_read"',
        "logparser_tpu_feeder_queue_depth",
        "logparser_tpu_feeder_starvation_seconds_total",
    ):
        assert needle in text, f"/metrics missing {needle}"
    assert validate_exposition(text) == []


def test_metrics_endpoint_exposes_ring_families():
    """The ring transport's counter families (docs/OBSERVABILITY.md,
    round 10) reach /metrics once a ring pool has run: per-worker slot
    backpressure wait, in-place (pipe-bypassing) bytes, and — after a
    device-fed stream — the staged-H2D upload bytes."""
    import pytest

    from logparser_tpu.feeder import FeederPool, ring_available
    from logparser_tpu.service import MetricsEndpoint

    if not ring_available():
        pytest.skip("multiprocessing.shared_memory unavailable")
    blob = b"\n".join(b"line %d" % i for i in range(200))
    pool = FeederPool([blob], workers=1, shard_bytes=1 << 20, batch_lines=8,
                      line_len=64, use_processes=False, transport="ring",
                      ring_slots=2)
    drained = sum(eb.source_bytes for eb in pool.batches())
    assert drained == len(blob)
    assert pool.stats()["bytes_inplace"] > 0
    assert metrics().get("feeder_ring_bytes_inplace_total") > 0
    endpoint = MetricsEndpoint().start()
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{endpoint.port}/metrics", timeout=10
        ) as resp:
            text = resp.read().decode("utf-8")
    finally:
        endpoint.shutdown()
    for needle in (
        "logparser_tpu_feeder_ring_slot_wait_seconds_total",
        "logparser_tpu_feeder_ring_bytes_inplace_total",
    ):
        assert needle in text, f"/metrics missing {needle}"
    assert validate_exposition(text) == []


def test_metrics_endpoint_exposes_recovery_families():
    """The supervision layer's recovery ledger (round 11,
    docs/FEEDER.md "Failure model & recovery") reaches /metrics once a
    fault has been recovered: worker restarts, requeued shards, and —
    for a poison drill — the quarantine counter."""
    from logparser_tpu.feeder import FeederPool, SupervisorPolicy
    from logparser_tpu.service import MetricsEndpoint

    blob = b"\n".join(b"line %06d padding payload" % i for i in range(600))
    pool = FeederPool(
        [blob], workers=2, shard_bytes=3000, batch_lines=32, line_len=64,
        use_processes=False,
        chaos="poison_shard:shard=1:mode=soft",
        policy=SupervisorPolicy(backoff_base_s=0.001),
    )
    drained = b"".join(bytes(eb.payload) for eb in pool.batches())
    assert drained == blob
    assert pool.stats()["shards_quarantined"] == 1
    endpoint = MetricsEndpoint().start()
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{endpoint.port}/metrics", timeout=10
        ) as resp:
            text = resp.read().decode("utf-8")
    finally:
        endpoint.shutdown()
    for needle in (
        "logparser_tpu_feeder_worker_restarts_total",
        "logparser_tpu_feeder_shards_quarantined_total",
        "logparser_tpu_feeder_shards_requeued_total",
    ):
        assert needle in text, f"/metrics missing {needle}"
    assert validate_exposition(text) == []


def test_process_mode_queue_depth_gauge_is_live():
    """Round-10 satellite: process workers cannot update the parent's
    registry, so depth is exported via shared put-counters — the gauge
    must rise under a stalled process-mode consumer (the round-8 gap:
    qsize()-less platforms read a dead gauge)."""
    import pytest

    from logparser_tpu.feeder import FeederPool

    blob = b"\n".join(b"line %d" % i for i in range(64))
    pool = FeederPool([blob], workers=1, shard_bytes=1 << 20,
                      batch_lines=4, line_len=64, queue_batches=2,
                      use_processes=True, ring_slots=2)
    try:
        stream = pool.batches()
        try:
            next(stream)  # prime, then stall the consumer
        except Exception:
            pytest.skip("multiprocessing unavailable in this environment")
        assert pool.mode == "process"
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if pool._queue_depth() >= 1:
                break
            time.sleep(0.02)
        assert pool._queue_depth() >= 1, (
            "shared put-counter depth never rose under a stalled consumer"
        )
        list(stream)  # drain; exhaustion closes the pool
        assert metrics().gauge_get("feeder_queue_depth") == 0
    finally:
        pool.close()
