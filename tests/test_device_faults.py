"""Device-tier fault tolerance (docs/FAULTS.md "Device failure model").

The fault layer's contract is BYTE PARITY UNDER DEVICE FAILURE: a run
that hits a device OOM, a wedged execution, or a failed jit compile
must deliver exactly what an undisturbed run delivers — the OOM bisects
and retries on smaller buckets, the wedge expires on the abandonable
deadline and reroutes the batch to the batched oracle host path, the
compile failure demotes the parser key to the oracle outright — and
the SAME parser instance must keep serving every ingest surface
afterwards (no poisoned cached state).

Fast tier: the pure machines (breaker, classifier, chaos hooks, budget
estimator) + the parity drills on a cheap 2-field format.  Slow tier:
the parser-survives-fault matrix over the ``tests/_configs.py`` table.
"""
import time

import pytest

from logparser_tpu.observability import metrics
from logparser_tpu.tools.chaos import ChaosSpec, DeviceChaos, PodChaos
from logparser_tpu.tpu.batch import TpuBatchParser
from logparser_tpu.tpu.device_faults import (
    DeviceBreaker,
    DeviceBudgetError,
    DeviceCompileError,
    DeviceFaultPolicy,
    DeviceOomError,
    DeviceWedgeError,
    classify_device_error,
    resolve_budget,
    resolve_deadline,
    run_with_deadline,
)

from _configs import build_configs

FMT = "%h %u %>s"
FIELDS = ["IP:connection.client.host", "STRING:request.status.last"]


def _lines(n):
    return [
        b"10.0.%d.%d u%d %d" % ((i >> 8) % 256, i % 256, i, 200 + i % 7)
        for i in range(n)
    ]


def _counter(name):
    from logparser_tpu.observability import counter_sum

    return counter_sum(name)


# ---------------------------------------------------------------------------
# pure machines
# ---------------------------------------------------------------------------


class TestClassifier:
    def test_typed_faults_classify_by_type(self):
        assert classify_device_error(DeviceOomError("x")) == "oom"
        assert classify_device_error(DeviceCompileError("x")) == "compile"
        assert classify_device_error(DeviceWedgeError("x")) == "wedge"

    def test_xla_oom_message_markers(self):
        e = RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory allocating 123 bytes"
        )
        assert classify_device_error(e) == "oom"
        assert classify_device_error(
            ValueError("pjrt: failed to allocate buffer")) == "oom"

    def test_compile_markers(self):
        e = RuntimeError("UNIMPLEMENTED: cannot lower op")
        assert classify_device_error(e) == "compile"
        assert classify_device_error(
            RuntimeError("error during lowering of fused computation")
        ) == "compile"

    def test_unknown_errors_are_transient_execute(self):
        assert classify_device_error(
            RuntimeError("device halted unexpectedly")) == "execute"


class TestBreaker:
    def test_opens_after_threshold_and_cools_off(self):
        b = DeviceBreaker(threshold=2, cooloff_s=10.0)
        assert b.allow(now=0.0)
        assert not b.record_fault(now=0.0)
        assert b.record_fault(now=1.0)  # THIS fault opened it
        assert b.state == "open"
        assert not b.allow(now=5.0)
        assert b.allow(now=11.5)  # cool-off elapsed: half-open by time

    def test_success_closes_fault_reopens(self):
        b = DeviceBreaker(threshold=1, cooloff_s=10.0)
        b.record_fault(now=0.0)
        assert not b.allow(now=1.0)
        # Fault during the half-open window re-opens without a fresh
        # demotion signal (no double warn).
        assert not b.record_fault(now=12.0)
        assert not b.allow(now=13.0)
        b.record_success(now=30.0)
        assert b.state == "closed"
        assert b.allow(now=30.0)

    def test_permanent_demotion_latches(self):
        b = DeviceBreaker(threshold=3, cooloff_s=0.001)
        assert b.record_fault(permanent=True)
        assert not b.record_fault(permanent=True)  # warn exactly once
        assert b.state == "demoted"
        assert not b.allow(now=1e9)
        b.record_success()
        assert b.state == "demoted"  # success cannot un-demote a compile


class TestDeadlineRunner:
    def test_returns_value_and_relays_errors(self):
        assert run_with_deadline(lambda: 7, 5.0) == 7
        with pytest.raises(ValueError):
            run_with_deadline(lambda: (_ for _ in ()).throw(
                ValueError("boom")), 5.0)

    def test_expiry_raises_wedge(self):
        with pytest.raises(DeviceWedgeError):
            run_with_deadline(lambda: time.sleep(2.0), 0.05)


class TestChaosHooks:
    def test_oom_fires_by_min_lines_and_count(self):
        dc = DeviceChaos(ChaosSpec.parse("oom_batch:count=1:min_lines=100"))
        assert dc.on_execute(50) is None  # below threshold: no fire
        with pytest.raises(DeviceOomError):
            dc.on_execute(100)
        assert dc.on_execute(100) is None  # count exhausted
        assert dc.fired("oom_batch") == 1

    def test_wedge_returns_sleep_seconds(self):
        dc = DeviceChaos(ChaosSpec.parse("wedge_device:seconds=2.5"))
        assert dc.on_execute(1) == 2.5
        assert dc.on_execute(1) is None  # count default 1

    def test_after_skips_early_executions(self):
        """``after=K`` arms a device fault only from the K+1-th
        execution — what lets a drill aim PAST another fault's bisect
        retries instead of landing inside them."""
        dc = DeviceChaos(
            ChaosSpec.parse("wedge_device:seconds=1:count=1:after=2"))
        assert dc.on_execute(10) is None
        assert dc.on_execute(10) is None
        assert dc.on_execute(10) == 1.0
        assert dc.fired("wedge_device") == 1

    def test_compile_fault_and_inert_spec(self):
        dc = DeviceChaos(ChaosSpec.parse("fail_compile"))
        with pytest.raises(DeviceCompileError):
            dc.on_execute(1)
        assert not DeviceChaos(ChaosSpec.parse("kill_worker:after=1"))

    def test_pod_chaos_preempt_plan(self):
        pc = PodChaos(ChaosSpec.parse("preempt_host:host=1:after=3"))
        assert pc.preempt_plan() == {1: 3}
        assert not PodChaos(ChaosSpec.parse("oom_batch"))


class TestEnvResolution:
    def test_budget_env_fallback(self, monkeypatch):
        monkeypatch.delenv("LOGPARSER_TPU_DEVICE_BYTES_BUDGET",
                           raising=False)
        assert resolve_budget(None) is None
        assert resolve_budget(12345) == 12345
        monkeypatch.setenv("LOGPARSER_TPU_DEVICE_BYTES_BUDGET", "777")
        assert resolve_budget(None) == 777
        monkeypatch.setenv("LOGPARSER_TPU_DEVICE_BYTES_BUDGET", "0")
        assert resolve_budget(None) is None

    def test_deadline_env_fallback(self, monkeypatch):
        monkeypatch.delenv("LOGPARSER_TPU_DEVICE_DEADLINE_S",
                           raising=False)
        assert resolve_deadline(None) is None
        monkeypatch.setenv("LOGPARSER_TPU_DEVICE_DEADLINE_S", "1.5")
        assert resolve_deadline(None) == 1.5
        assert resolve_deadline(2.0) == 2.0


def test_estimate_device_bytes_matches_executor_shapes():
    """The budget estimator must cover the real staged input + packed
    output footprint (same arithmetic the executor's buffers resolve
    to), and grow monotonically with the batch."""
    from logparser_tpu.tpu.pipeline import (
        estimate_device_bytes,
        packed_row_count,
    )

    parser = TpuBatchParser(FMT, FIELDS)
    rows = packed_row_count(parser.units)
    assert rows >= 1
    small = estimate_device_bytes(parser.units, 0, 64, 128)
    big = estimate_device_bytes(parser.units, 0, 1024, 128)
    assert big > small
    assert small >= 64 * 128 + rows * 64 * 4


# ---------------------------------------------------------------------------
# parity drills (cheap format; parsers are fault-mutated, never shared)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def reference():
    lines = _lines(300)
    parser = TpuBatchParser(FMT, FIELDS)
    ref = parser.parse_batch(lines).to_dict()
    return lines, ref


def test_oom_bisects_and_recovers_byte_identical(reference):
    lines, ref = reference
    p = TpuBatchParser(FMT, FIELDS, device_chaos="oom_batch:count=1")
    before = _counter("device_oom_retries_total")
    assert p.parse_batch(lines).to_dict() == ref
    assert _counter("device_oom_retries_total") > before
    # Same instance keeps serving, back on the device path.
    r = p.parse_batch(lines)
    assert r.to_dict() == ref and r.oracle_rows == 0
    assert p.device_fault_stats()["state"] == "closed"


def test_repeated_oom_clamps_bucket_and_presplits(reference):
    lines, ref = reference
    p = TpuBatchParser(
        FMT, FIELDS,
        device_chaos="oom_batch:sticky=1:min_lines=129",
        fault_policy=DeviceFaultPolicy(oom_clamp_after=2),
    )
    assert p.parse_batch(lines).to_dict() == ref
    clamp = p.device_fault_stats()["oom_clamp"]
    assert clamp is not None and clamp <= 128
    # Pre-split now: executions stay at/below the clamp, so the sticky
    # injection (which only fires above it) never fires again.
    fired = p._device_chaos.fired("oom_batch")
    assert p.parse_batch(lines).to_dict() == ref
    assert p._device_chaos.fired("oom_batch") == fired
    assert metrics().gauge_get("device_bucket_clamped") == clamp


def test_oom_at_min_bucket_reroutes_to_oracle(reference):
    """An OOM that bisecting cannot save (fires at every size) must
    reroute the batch to the oracle — zero aborts, byte parity."""
    lines, ref = reference
    p = TpuBatchParser(
        FMT, FIELDS, device_chaos="oom_batch:sticky=1",
        fault_policy=DeviceFaultPolicy(oom_retries=2, oom_clamp_after=99),
    )
    before = _counter("device_fault_reroutes_total")
    r = p.parse_batch(lines)
    assert r.to_dict() == ref
    assert r.oracle_rows == len(lines)
    assert _counter("device_fault_reroutes_total") > before


def test_wedge_expires_and_reroutes(reference):
    lines, ref = reference
    p = TpuBatchParser(
        FMT, FIELDS, execute_deadline_s=0.2,
        device_chaos="wedge_device:seconds=1.5:count=1",
    )
    t0 = time.monotonic()
    r = p.parse_batch(lines)
    assert r.to_dict() == ref
    assert r.oracle_rows == len(lines)  # the wedged batch host-parsed
    assert time.monotonic() - t0 < 30.0
    # Same instance, next batch back on device.
    r2 = p.parse_batch(lines)
    assert r2.to_dict() == ref and r2.oracle_rows == 0


def test_repeated_wedges_demote_then_breaker_recovers(reference):
    lines, ref = reference
    p = TpuBatchParser(
        FMT, FIELDS, execute_deadline_s=0.2,
        fault_policy=DeviceFaultPolicy(
            breaker_threshold=2, breaker_cooloff_s=0.3),
        device_chaos="wedge_device:seconds=1.0:count=2",
    )
    for _ in range(2):
        assert p.parse_batch(lines).to_dict() == ref
    assert p.device_fault_stats()["state"] == "open"
    # While open every batch host-parses (still exact, no device touch).
    r = p.parse_batch(lines)
    assert r.to_dict() == ref and r.oracle_rows == len(lines)
    time.sleep(0.35)  # cool-off: the next batch is the half-open trial
    r = p.parse_batch(lines)
    assert r.to_dict() == ref and r.oracle_rows == 0
    assert p.device_fault_stats()["state"] == "closed"


def test_fail_compile_demotes_sticky_and_exact(reference):
    lines, ref = reference
    p = TpuBatchParser(FMT, FIELDS, device_chaos="fail_compile")
    before = _counter("device_compile_failures_total")
    r = p.parse_batch(lines)
    assert r.to_dict() == ref
    assert _counter("device_compile_failures_total") > before
    assert p.device_fault_stats()["state"] == "demoted"
    # Demotion is permanent: every later parse host-parses, exactly.
    r2 = p.parse_batch(lines)
    assert r2.to_dict() == ref and r2.oracle_rows == len(lines)


def test_stream_parity_and_ring_release_under_fault(reference):
    """parse_batch_stream under an injected mid-stream fault must yield
    every batch, in order, byte-identical — never abort the stream."""
    lines, ref = reference
    batches = [lines, lines[:150], lines]
    clean = TpuBatchParser(FMT, FIELDS)
    want = [r.to_dict() for r in clean.parse_batch_stream(batches)]
    p = TpuBatchParser(
        FMT, FIELDS, device_chaos="oom_batch:count=1:min_lines=200",
    )
    got = [r.to_dict() for r in p.parse_batch_stream(batches)]
    assert got == want


def test_budget_rejects_before_device_put(reference, monkeypatch):
    lines, ref = reference
    p = TpuBatchParser(FMT, FIELDS, device_bytes_budget=128)
    # The contract: the reject fires BEFORE any device placement.
    import jax

    def _no_put(*a, **k):  # pragma: no cover - would mean a real put
        raise AssertionError("device_put ran despite the budget reject")

    monkeypatch.setattr(jax, "device_put", _no_put)
    before = _counter("device_budget_rejects_total")
    with pytest.raises(DeviceBudgetError) as ei:
        p.parse_batch(lines)
    assert ei.value.estimated_bytes > ei.value.budget_bytes
    assert ei.value.lines == len(lines)
    assert _counter("device_budget_rejects_total") > before
    monkeypatch.undo()
    # A generous budget changes nothing.
    roomy = TpuBatchParser(FMT, FIELDS, device_bytes_budget=1 << 30)
    assert roomy.parse_batch(lines).to_dict() == ref


def test_artifact_roundtrip_drops_runtime_fault_state(reference):
    lines, ref = reference
    p = TpuBatchParser(FMT, FIELDS, device_chaos="fail_compile")
    p.parse_batch(lines)  # demote + (no) clamp
    assert p.device_fault_stats()["state"] == "demoted"
    loaded = TpuBatchParser.from_bytes(p.to_bytes())
    stats = loaded.device_fault_stats()
    assert stats["state"] == "closed" and stats["oom_clamp"] is None
    r = loaded.parse_batch(lines)
    assert r.to_dict() == ref and r.oracle_rows == 0  # device path back


# ---------------------------------------------------------------------------
# parser-survives-fault across the config table (slow tier)
# ---------------------------------------------------------------------------

N_CONFIG_LINES = 256


@pytest.mark.slow
@pytest.mark.parametrize("name", [
    "combined", "nginx_uri", "combinedio_strftime", "strftime_zonetext",
    "multiformat_mixed",
])
def test_parser_survives_fault_bench_configs(name):
    """After an injected device fault and oracle reroute, the SAME
    TpuBatchParser instance keeps serving parse_batch / parse_blob /
    parse_encoded with byte-identical results — no poisoned cached
    state, on every config of the table."""
    entry = next((c for c in build_configs() if c[0] == name), None)
    if entry is None:
        pytest.skip(f"config {name} unavailable on this host")
    _, fmt, fields, lines_fn, extra = entry
    lines = lines_fn(N_CONFIG_LINES)
    as_bytes = [
        ln.encode("utf-8") if isinstance(ln, str) else ln for ln in lines
    ]
    blob = b"\n".join(as_bytes)

    clean = TpuBatchParser(fmt, fields, extra_dissectors=extra)
    ref_batch = clean.parse_batch(lines).to_dict()
    ref_blob = clean.parse_blob(blob).to_dict()

    p = TpuBatchParser(
        fmt, fields, extra_dissectors=extra, execute_deadline_s=0.5,
        device_chaos="oom_batch:count=1;wedge_device:seconds=2:count=1",
    )
    # Fault 1 (OOM -> bisect) and fault 2 (wedge -> oracle reroute):
    assert p.parse_batch(lines).to_dict() == ref_batch
    assert p.parse_batch(lines).to_dict() == ref_batch
    # ... and the same instance serves every ingest surface exactly.
    assert p.parse_batch(lines).to_dict() == ref_batch
    assert p.parse_blob(blob).to_dict() == ref_blob

    from logparser_tpu.feeder.worker import EncodedBatch
    from logparser_tpu.native import encode_blob

    buf, lens, ovf = encode_blob(blob)
    eb = EncodedBatch(shard=0, index=0, payload=blob, buf=buf,
                      lengths=lens, overflow=list(ovf),
                      n_lines=buf.shape[0])
    assert p.parse_encoded(eb).to_dict() == ref_blob
    p.close()
    clean.close()
