"""Sidecar parse service: framing, Arrow IPC round trip, error relay,
parser caching (SURVEY §7.5 "sidecar service mode"), and the round-12
robustness tier: admission control / structured BUSY shedding, deadlines,
malformed-wire hardening, graceful drain (docs/SERVICE.md)."""
import json
import socket
import struct
import threading
import time
import urllib.error
import urllib.request

import pytest

from logparser_tpu.observability import metrics
from logparser_tpu.service import (
    ParseService,
    ParseServiceClient,
    ParseServiceError,
    ServiceBusyError,
    ServiceDeadlineError,
)
from logparser_tpu.tools.demolog import generate_combined_lines

from _configs import build_configs

FIELDS = [
    "IP:connection.client.host",
    "TIME.EPOCH:request.receive.time.epoch",
    "STRING:request.status.last",
    "BYTES:response.body.bytes",
]


@pytest.fixture(scope="module")
def service():
    with ParseService() as svc:
        yield svc


@pytest.mark.slow
def test_parse_round_trip(service):
    lines = generate_combined_lines(100, seed=41)
    with ParseServiceClient(
        service.host, service.port, "combined", FIELDS
    ) as client:
        table = client.parse(lines)
    assert table.num_rows == 100
    assert set(table.column_names) >= set(FIELDS) | {"__valid__"}
    ips = table.column("IP:connection.client.host").to_pylist()
    assert all(ip.count(".") == 3 for ip in ips)
    epochs = table.column("TIME.EPOCH:request.receive.time.epoch").to_pylist()
    assert all(isinstance(e, int) for e in epochs)


@pytest.mark.slow
def test_multiple_batches_one_session(service):
    with ParseServiceClient(
        service.host, service.port, "combined", FIELDS[:1]
    ) as client:
        for seed in (1, 2, 3):
            table = client.parse(generate_combined_lines(10, seed=seed))
            assert table.num_rows == 10


@pytest.mark.slow
def test_bytes_and_str_lines(service):
    line = '9.8.7.6 - - [01/Jan/2026:00:00:00 +0000] "GET / HTTP/1.1" 200 5 "-" "x"'
    with ParseServiceClient(
        service.host, service.port, "combined", FIELDS[:1]
    ) as client:
        t1 = client.parse([line])
        t2 = client.parse([line.encode("utf-8")])
    assert t1.column(FIELDS[0]).to_pylist() == t2.column(FIELDS[0]).to_pylist() == ["9.8.7.6"]


@pytest.mark.slow
def test_bad_config_relays_error(service):
    with pytest.raises(ParseServiceError, match="bad config"):
        ParseServiceClient(
            service.host, service.port, "combined", ["NOSUCH:field.path"]
        ).parse(["x"])


@pytest.mark.slow
def test_bad_lines_are_nulls_not_errors(service):
    lines = ["complete garbage", "more garbage"]
    with ParseServiceClient(
        service.host, service.port, "combined", FIELDS[:1]
    ) as client:
        table = client.parse(lines)
    assert table.num_rows == 2
    assert table.column("__valid__").to_pylist() == [False, False]
    assert table.column(FIELDS[0]).to_pylist() == [None, None]


@pytest.mark.slow
def test_parser_cache_shared_across_sessions(service):
    cache = service._server.parser_cache
    n_before = len(cache._parsers)
    for _ in range(3):
        with ParseServiceClient(
            service.host, service.port, "combined", FIELDS
        ) as client:
            client.parse(generate_combined_lines(5, seed=2))
    assert len(cache._parsers) == n_before  # same config -> same compiled parser


@pytest.mark.slow
def test_empty_batch_and_empty_line(service):
    # count-prefixed LINES framing: [] is a real (empty) batch, not
    # end-of-session, and an empty logline is a present-but-invalid row.
    with ParseServiceClient(
        service.host, service.port, "combined", FIELDS[:1]
    ) as client:
        t0 = client.parse([])
        assert t0.num_rows == 0
        t1 = client.parse([""])
        assert t1.num_rows == 1
        assert t1.column("__valid__").to_pylist() == [False]
        # the session survives both
        t2 = client.parse(generate_combined_lines(3, seed=7))
        assert t2.num_rows == 3


@pytest.mark.slow
def test_embedded_newline_rejected(service):
    with ParseServiceClient(
        service.host, service.port, "combined", FIELDS[:1]
    ) as client:
        with pytest.raises(ValueError, match="cannot contain"):
            client.parse(["a\nb"])


@pytest.mark.slow
def test_shutdown_before_start_does_not_hang():
    svc = ParseService()
    svc.shutdown()  # must not block on the never-started serve_forever loop


# ---------------------------------------------------------------------------
# feeder-session degradation (docs/FEEDER.md "Failure model & recovery"):
# a feeder failure mid-session must NEVER drop the connection — the
# request re-parses inline (error-free ARROW stream) or, for
# parse-shaped failures, relays a well-formed error frame, and the
# session survives on the degraded inline path either way.
# ---------------------------------------------------------------------------


def _feeder_session(monkeypatch, fail_with):
    """A service whose _feeder_parse fails once with ``fail_with``,
    counting calls; returns (service ctx entered by caller, calls)."""
    from logparser_tpu import service as service_mod

    monkeypatch.setattr(service_mod, "_FEEDER_MIN_LINES", 16)
    calls = []

    def exploding_feeder(parser, blob, count, workers):
        calls.append(count)
        raise fail_with

    monkeypatch.setattr(service_mod, "_feeder_parse", exploding_feeder)
    return calls


@pytest.mark.slow
def test_feeder_death_degrades_to_error_free_arrow(monkeypatch):
    """A dead feeder fabric (FeederError) yields the SAME ARROW frame
    the inline path produces — no error frame, no RST — and the session
    is demoted: its next LINES frame skips the feeder entirely."""
    from logparser_tpu.feeder import FeederError
    from logparser_tpu.observability import metrics

    calls = _feeder_session(
        monkeypatch, FeederError("all workers dead"))
    lines = generate_combined_lines(60, seed=9)
    before = metrics().get("service_feeder_demotions_total")
    with ParseService() as svc:
        with ParseServiceClient(
            svc.host, svc.port, "combined", FIELDS[:1]
        ) as plain:
            ref = plain.parse(lines)
        with ParseServiceClient(
            svc.host, svc.port, "combined", FIELDS[:1], feeder_workers=2,
        ) as client:
            got = client.parse(lines)          # feeder dies -> inline retry
            again = client.parse(lines)        # demoted: inline directly
    assert got.equals(ref) and again.equals(ref)
    assert calls == [60]  # the demoted session never re-entered the feeder
    assert metrics().get("service_feeder_demotions_total") == before + 1


@pytest.mark.slow
def test_feeder_parse_failure_relays_error_frame_and_survives(monkeypatch):
    """A parse-shaped failure inside the feeder path relays a
    WELL-FORMED error frame (the client raises ParseServiceError, the
    socket stays open), and the next LINES frame succeeds via the
    degraded inline path."""
    calls = _feeder_session(monkeypatch, RuntimeError("bad parse state"))
    lines = generate_combined_lines(40, seed=3)
    with ParseService() as svc:
        with ParseServiceClient(
            svc.host, svc.port, "combined", FIELDS[:1], feeder_workers=2,
        ) as client:
            with pytest.raises(ParseServiceError, match="bad parse state"):
                client.parse(lines)
            table = client.parse(lines)  # same socket, degraded inline
    assert table.num_rows == 40
    assert calls == [40]


# ---------------------------------------------------------------------------
# round 12 — serving-tier robustness (docs/SERVICE.md): admission control
# with structured BUSY sheds, deadlines, input hardening, graceful drain.
# ---------------------------------------------------------------------------


class _StubResult:
    oracle_rows = 0
    bad_lines = 0

    def __init__(self, n):
        self.n = n

    def to_arrow(self, include_validity=True, strings="copy"):
        import pyarrow as pa

        return pa.table({"x": list(range(self.n))})


class _StubParser:
    """Cache-injected parser double: no XLA compile, optional per-call
    delays (``first_delays`` pop per request, then ``delay``)."""

    def __init__(self, delay=0.0, first_delays=()):
        self.delay = delay
        self._first = list(first_delays)

    def _sleep(self):
        d = self._first.pop(0) if self._first else self.delay
        if d:
            time.sleep(d)

    def parse_batch(self, rows, emit_views=False):
        self._sleep()
        return _StubResult(len(rows))

    def parse_blob(self, blob, emit_views=False):
        self._sleep()
        return _StubResult(blob.count(b"\n") + 1)


def _install_stub(svc, delay=0.0, first_delays=()):
    parser = _StubParser(delay, first_delays)
    svc._server.parser_cache.get = lambda cfg: parser
    return parser


def _wait_admitted(svc, n=1, deadline_s=2.0):
    end = time.monotonic() + deadline_s
    while time.monotonic() < end:
        with svc._server.sessions_lock:
            if sum(1 for h in svc._server.sessions if h.admitted) >= n:
                return
        time.sleep(0.01)
    raise AssertionError(f"never saw {n} admitted sessions")


def _send_frame(sock, payload: bytes):
    sock.sendall(struct.pack(">I", len(payload)) + payload)


def _recv_exact(sock, n):
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            return bytes(buf)
        buf.extend(chunk)
    return bytes(buf)


def _recv_response(sock):
    """(kind, payload): 'arrow' | 'error' | 'eof' per PROTOCOL.md."""
    header = _recv_exact(sock, 4)
    if len(header) < 4:
        return "eof", b""
    (n,) = struct.unpack(">I", header)
    if n == 0xFFFFFFFF:
        (m,) = struct.unpack(">I", _recv_exact(sock, 4))
        return "error", _recv_exact(sock, m)
    return "arrow", _recv_exact(sock, n)


_RAW_CONFIG = json.dumps({
    "log_format": "combined", "fields": FIELDS[:1],
    "timestamp_format": None,
}).encode()


@pytest.mark.slow
def test_session_shed_is_structured_busy():
    """Over the session budget a connection gets a structured BUSY frame
    with the server's retry hint — never a reset — and the slot frees
    when the holder leaves."""
    before = metrics().get("service_shed_total",
                           labels={"reason": "sessions"})
    with ParseService(max_sessions=1, busy_retry_after_s=0.123) as svc:
        _install_stub(svc)
        holder = socket.create_connection((svc.host, svc.port))
        try:
            _wait_admitted(svc)
            with pytest.raises(ServiceBusyError) as ei:
                ParseServiceClient(
                    svc.host, svc.port, "combined", FIELDS[:1]
                ).parse(["x"])
            assert ei.value.reason == "sessions"
            assert ei.value.structured
            assert ei.value.retry_after_s == pytest.approx(0.123)
        finally:
            holder.close()
        # The freed slot admits the next session.
        end = time.monotonic() + 2.0
        while True:
            try:
                with ParseServiceClient(
                    svc.host, svc.port, "combined", FIELDS[:1]
                ) as client:
                    assert client.parse(["x"]).num_rows == 1
                break
            except ServiceBusyError:
                assert time.monotonic() < end, "slot never freed"
                time.sleep(0.02)
    assert metrics().get("service_shed_total",
                         labels={"reason": "sessions"}) > before


@pytest.mark.slow
def test_request_shed_inflight_session_survives():
    """Over the in-flight cap a REQUEST sheds BUSY but its session
    survives and the next request (after capacity frees) succeeds."""
    with ParseService(max_sessions=4, max_inflight=1) as svc:
        _install_stub(svc, delay=0.0, first_delays=[0.6])
        with ParseServiceClient(
            svc.host, svc.port, "combined", FIELDS[:1]
        ) as slow, ParseServiceClient(
            svc.host, svc.port, "combined", FIELDS[:1]
        ) as fast:
            t = threading.Thread(target=lambda: slow.parse(["a"] * 3))
            t.start()
            time.sleep(0.15)  # slow's request holds the one slot
            with pytest.raises(ServiceBusyError) as ei:
                fast.parse(["b"])
            assert ei.value.reason == "inflight"
            t.join(5)
            # Same socket, after the slot freed: served.
            assert fast.parse(["b"]).num_rows == 1


@pytest.mark.slow
def test_backpressure_signal_sheds_requests(monkeypatch):
    """A saturated feeder fabric (queue_backpressure >= threshold) sheds
    per-request with reason=backpressure."""
    import logparser_tpu.feeder as feeder_mod

    monkeypatch.setattr(feeder_mod, "queue_backpressure", lambda: 1.0)
    with ParseService() as svc:
        _install_stub(svc)
        with ParseServiceClient(
            svc.host, svc.port, "combined", FIELDS[:1]
        ) as client:
            with pytest.raises(ServiceBusyError) as ei:
                client.parse(["x"])
            assert ei.value.reason == "backpressure"


@pytest.mark.slow
def test_pool_backpressure_fraction():
    """FeederPool.backpressure(): 0 before start/after close, rises when
    the consumer stalls against the bounded queue, and feeds the
    process-wide queue_backpressure() aggregate."""
    from logparser_tpu.feeder import FeederPool, queue_backpressure

    blob = b"\n".join(f"line {i}".encode() for i in range(400))
    pool = FeederPool([blob], workers=1, shard_bytes=len(blob),
                      batch_lines=10, use_processes=False, queue_batches=2)
    assert pool.backpressure() == 0.0
    it = pool.batches()
    next(it)  # start the pool; the stalled consumer lets the queue fill
    end = time.monotonic() + 2.0
    while pool.backpressure() == 0.0 and time.monotonic() < end:
        time.sleep(0.02)
    assert pool.backpressure() > 0.0
    assert queue_backpressure() >= pool.backpressure()
    pool.close()
    assert pool.backpressure() == 0.0
    assert queue_backpressure() == 0.0


@pytest.mark.slow
def test_ring_backpressure_can_saturate():
    """Ring-transport occupancy is measured against REACHABLE capacity
    (slots, not the descriptor-queue bound + control slack), so a wedged
    fabric can actually cross the 0.95 shed threshold."""
    from logparser_tpu.feeder import FeederPool, ring_available

    if not ring_available():
        pytest.skip("shared memory unavailable")
    blob = b"\n".join(f"line {i}".encode() for i in range(400))
    pool = FeederPool([blob], workers=1, shard_bytes=len(blob),
                      batch_lines=10, use_processes=False,
                      transport="ring", queue_batches=2)
    it = pool.batches()
    next(it)  # start; the stalled consumer lets the worker lease all slots
    end = time.monotonic() + 2.0
    while pool.backpressure() < 0.95 and time.monotonic() < end:
        time.sleep(0.02)
    assert pool.backpressure() >= 0.95
    pool.close()


@pytest.mark.slow
def test_zero_timeouts_disable_not_nonblocking():
    """idle/frame timeout 0 means DISABLED (like every other 0-disables
    knob), never non-blocking sockets that kill every session."""
    with ParseService(idle_timeout_s=0.0, frame_timeout_s=0) as svc:
        assert svc.limits.idle_timeout_s is None
        assert svc.limits.frame_timeout_s is None
        _install_stub(svc)
        with ParseServiceClient(
            svc.host, svc.port, "combined", FIELDS[:1]
        ) as client:
            time.sleep(0.1)  # an instant-kill server would already be gone
            assert client.parse(["x"]).num_rows == 1


@pytest.mark.slow
def test_request_deadline_yields_deadline_frame_and_survives():
    """An expired request answers a structured DEADLINE frame; the
    session survives and its next request succeeds.  With continuous
    batching (round 14) an abandoned slow batch serializes the key's
    lane, so a follow-up inside the wedge window may ALSO answer
    DEADLINE (expired while queued — still structured, still
    session-surviving); once the lane clears, the same socket serves."""
    before = metrics().get("service_deadline_expired_total")
    with ParseService(request_deadline_s=0.15) as svc:
        _install_stub(svc, first_delays=[0.6])
        with ParseServiceClient(
            svc.host, svc.port, "combined", FIELDS[:1]
        ) as client:
            with pytest.raises(ServiceDeadlineError) as ei:
                client.parse(["a", "b"])
            assert ei.value.deadline_s == pytest.approx(0.15)
            end = time.monotonic() + 5.0
            while True:
                try:
                    assert client.parse(["a", "b"]).num_rows == 2
                    break
                except ServiceDeadlineError:
                    assert time.monotonic() < end, "lane never cleared"
                    time.sleep(0.05)
    assert metrics().get("service_deadline_expired_total") > before


@pytest.mark.slow
def test_idle_timeout_closes_cleanly():
    before = metrics().get("service_timeouts_total",
                           labels={"kind": "idle"})
    with ParseService(idle_timeout_s=0.2) as svc:
        sock = socket.create_connection((svc.host, svc.port))
        sock.settimeout(5)
        assert sock.recv(1) == b""  # clean EOF, not a reset
        sock.close()
    assert metrics().get("service_timeouts_total",
                         labels={"kind": "idle"}) == before + 1


@pytest.mark.slow
def test_mid_frame_stall_times_out():
    before = metrics().get("service_timeouts_total",
                           labels={"kind": "frame"})
    with ParseService(idle_timeout_s=5.0, frame_timeout_s=0.2) as svc:
        sock = socket.create_connection((svc.host, svc.port))
        sock.sendall(b"\x00\x00")  # half a header, then silence
        sock.settimeout(5)
        assert sock.recv(1) == b""
        sock.close()
    assert metrics().get("service_timeouts_total",
                         labels={"kind": "frame"}) == before + 1


@pytest.mark.slow
def test_client_busy_retry_with_backoff():
    """The BUSY-aware client absorbs session sheds: reconnect + jittered
    backoff honoring the retry hint, then success once a slot frees."""
    with ParseService(max_sessions=1, busy_retry_after_s=0.02) as svc:
        _install_stub(svc)
        holder = socket.create_connection((svc.host, svc.port))
        _wait_admitted(svc)
        threading.Timer(0.3, holder.close).start()
        with ParseServiceClient(
            svc.host, svc.port, "combined", FIELDS[:1],
            busy_retries=20, backoff_base_s=0.02,
        ) as client:
            assert client.parse(["x"]).num_rows == 1
            assert client.busy_seen >= 1


# -- malformed-wire fuzz: every case must end in an error frame or a clean
#    close — never a traceback escaping the handler, never a hang. ---------


@pytest.mark.slow
def test_fuzz_truncated_config_frame(service):
    sock = socket.create_connection((service.host, service.port))
    sock.sendall(struct.pack(">I", 100) + b"ten bytes!")
    sock.close()
    # The service survives: a fresh session on the same server parses.
    with ParseServiceClient(
        service.host, service.port, "combined", FIELDS[:1]
    ) as client:
        assert client.parse(["x"]).num_rows == 1


@pytest.mark.slow
def test_fuzz_oversized_length_prefix(service):
    """A hostile ~4 GiB length prefix costs one error frame (+ clean
    close), never an allocation."""
    sock = socket.create_connection((service.host, service.port))
    try:
        sock.sendall(struct.pack(">I", 0xF0000000))
        sock.settimeout(5)
        kind, payload = _recv_response(sock)
        assert kind == "error"
        assert b"cap" in payload
        assert _recv_response(sock)[0] == "eof"
    finally:
        sock.close()


@pytest.mark.slow
def test_fuzz_non_json_config(service):
    sock = socket.create_connection((service.host, service.port))
    try:
        _send_frame(sock, b"\x00\x01 this is not json {{{")
        _send_frame(sock, struct.pack(">I", 1) + b"x")  # pipelined LINES
        sock.settimeout(5)
        kind, payload = _recv_response(sock)
        assert kind == "error" and b"bad config" in payload
        kind2, _ = _recv_response(sock)
        assert kind2 == "error"
    finally:
        sock.close()


@pytest.mark.slow
def test_fuzz_mid_frame_disconnect(service):
    sock = socket.create_connection((service.host, service.port))
    _send_frame(sock, _RAW_CONFIG)
    sock.sendall(struct.pack(">I", 50) + b"five!")  # truncated LINES
    sock.close()
    with ParseServiceClient(
        service.host, service.port, "combined", FIELDS[:1]
    ) as client:
        assert client.parse(["x"]).num_rows == 1


@pytest.mark.slow
def test_fuzz_zero_length_lines_frame(service):
    """A LINES frame shorter than its count header errors; the session
    survives to parse the next frame."""
    sock = socket.create_connection((service.host, service.port))
    try:
        _send_frame(sock, _RAW_CONFIG)
        _send_frame(sock, b"\x00\x00")  # 2-byte LINES payload
        sock.settimeout(10)
        kind, payload = _recv_response(sock)
        assert kind == "error" and b"count header" in payload
        _send_frame(sock, struct.pack(">I", 1) + b"x")
        assert _recv_response(sock)[0] == "arrow"
        sock.sendall(struct.pack(">I", 0))
    finally:
        sock.close()


@pytest.mark.slow
def test_lines_payload_cap_discards_and_survives():
    """A LINES frame over the payload cap is consumed WITHOUT allocation,
    answered with an error frame, and the session survives."""
    before = metrics().get("service_rejected_frames_total",
                           labels={"reason": "lines_too_large"})
    with ParseService(max_lines_bytes=64) as svc:
        _install_stub(svc)
        with ParseServiceClient(
            svc.host, svc.port, "combined", FIELDS[:1]
        ) as client:
            with pytest.raises(ParseServiceError, match="cap"):
                client.parse(["y" * 200])
            assert client.parse(["tiny"]).num_rows == 1
    assert metrics().get("service_rejected_frames_total",
                         labels={"reason": "lines_too_large"}) == before + 1


@pytest.mark.slow
def test_config_payload_cap():
    with ParseService(max_config_bytes=32) as svc:
        client = ParseServiceClient(
            svc.host, svc.port, "combined", FIELDS  # > 32-byte CONFIG
        )
        with pytest.raises(ParseServiceError, match="bad config"):
            client.parse(["x"])
        client.close()


# -- graceful drain (acceptance): readyz flips, admitted work completes,
#    no leaked threads. ----------------------------------------------------


def _http_status(url):
    try:
        with urllib.request.urlopen(url, timeout=5) as resp:
            return resp.status
    except urllib.error.HTTPError as e:
        return e.code


@pytest.mark.slow
def test_graceful_drain_completes_admitted_requests():
    with ParseService(metrics_port=0, drain_deadline_s=10.0) as svc:
        _install_stub(svc, delay=0.3)
        base = f"http://{svc.host}:{svc.metrics_port}"
        assert _http_status(base + "/readyz") == 200
        assert _http_status(base + "/healthz") == 200
        client = ParseServiceClient(svc.host, svc.port, "combined",
                                    FIELDS[:1])
        results = []
        req = threading.Thread(
            target=lambda: results.append(client.parse(["a", "b", "c"]))
        )
        req.start()
        time.sleep(0.05)  # request in flight
        assert any(t.name.startswith("svc-sess-")
                   for t in threading.enumerate())
        drainer = threading.Thread(
            target=lambda: svc.shutdown(drain=True), daemon=True
        )
        drainer.start()
        # readyz flips to draining while the session is still in flight
        # (the flip happens BEFORE the listener closes).
        end = time.monotonic() + 3.0
        while _http_status(base + "/readyz") != 503:
            assert time.monotonic() < end, "/readyz never flipped"
            time.sleep(0.02)
        assert _http_status(base + "/healthz") == 200
        req.join(5)
        assert results and results[0].num_rows == 3
        # The admitted session keeps serving THROUGH the drain window.
        assert client.parse(["d"]).num_rows == 1
        # A NEW connection during the window sheds structured
        # BUSY(draining) — the listener stays up until admitted
        # sessions finish, so readiness propagation never turns into
        # ECONNREFUSED.
        with pytest.raises(ServiceBusyError) as ei:
            ParseServiceClient(
                svc.host, svc.port, "combined", FIELDS[:1]
            ).parse(["x"])
        assert ei.value.reason == "draining"
        client.close()
        drainer.join(15)
        assert not drainer.is_alive()
        # Listener is closed: new connections are refused, not shed.
        with pytest.raises(OSError):
            socket.create_connection((svc.host, svc.port), timeout=1)
    assert not [t for t in threading.enumerate()
                if t.name.startswith("svc-sess-") and t.is_alive()]


@pytest.mark.slow
def test_note_teardown_counts_and_warns_once():
    from logparser_tpu.observability import note_teardown
    import logging

    log = logging.getLogger("test.teardown")
    before = metrics().get("service_teardown_errors_total",
                           labels={"site": "unit_test"})
    note_teardown(log, "service_teardown_errors_total", "unit_test", "boom")
    note_teardown(log, "service_teardown_errors_total", "unit_test", "boom")
    assert metrics().get("service_teardown_errors_total",
                         labels={"site": "unit_test"}) == before + 2


# ---------------------------------------------------------------------------
# round 14 — continuous batching (docs/SERVICE.md "Continuous batching"):
# cross-session byte parity, deadline-expiry-while-queued, shed-while-
# queued, drain-with-queued-entries.
# ---------------------------------------------------------------------------


def _raw_parity_session(host, port, config_payload, payloads, barrier,
                        out, idx):
    """One raw-socket session: per round, rendezvous on the barrier then
    ship one LINES frame and capture the raw ARROW payload bytes."""
    sock = socket.create_connection((host, port))
    try:
        _send_frame(sock, config_payload)
        sock.settimeout(120)
        got = []
        for payload in payloads:
            barrier.wait(timeout=60)
            _send_frame(sock, payload)
            kind, body = _recv_response(sock)
            got.append((kind, body))
        out[idx] = got
        sock.sendall(struct.pack(">I", 0))
    finally:
        sock.close()


def _lines_payload(lines):
    blob = "\n".join(lines).encode()
    return struct.pack(">I", len(lines)) + blob


def _inject_parser(svc, config):
    """Share ONE compiled parser between the solo and coalescing
    services (and across runs, via the session parser cache) — the suite
    measures coalescing parity, not compile time."""
    from logparser_tpu.service import _ParserCache

    from _shared_parsers import shared_parser

    parser = shared_parser(config["log_format"], config["fields"],
                           view_fields=())
    svc._server.parser_cache._parsers[_ParserCache.key_of(config)] = parser


@pytest.mark.slow
def test_cross_session_coalesce_parity_bench_configs():
    """THE coalescing invariant (acceptance): for every wire-expressible
    config of the table, K concurrent sessions pushing interleaved mixed-size
    requests through the coalescer receive Arrow bytes IDENTICAL to the
    same requests parsed solo — and the drill must actually coalesce
    (>1 session in at least one shared batch)."""
    spb = metrics().histogram("service_coalesced_sessions_per_batch")
    count0, sum0 = spb.count, spb.sum
    sizes_by_session = [(1, 37, 8), (19, 3, 52), (7, 64, 2)]
    # extra_dissectors cannot ride a CONFIG frame: wire-expressible only.
    wire_configs = [(name, fmt, fields, lines_fn)
                    for name, fmt, fields, lines_fn, extra in build_configs()
                    if not extra]
    for name, fmt, fields, lines_fn in wire_configs:
        corpus = lines_fn(160)
        config = {"log_format": fmt, "fields": list(fields),
                  "timestamp_format": None}
        config_payload = json.dumps(config).encode()
        payload_sets = []
        cursor = 0
        for sizes in sizes_by_session:
            payloads = []
            for n in sizes:
                payloads.append(_lines_payload(
                    [corpus[(cursor + j) % len(corpus)] for j in range(n)]
                ))
                cursor += n
            payload_sets.append(payloads)
        # Solo reference: coalescing OFF, same injected parser.
        with ParseService(coalesce=False) as solo:
            _inject_parser(solo, config)
            refs = []
            for payloads in payload_sets:
                out = {}
                _raw_parity_session(solo.host, solo.port, config_payload,
                                    payloads,
                                    threading.Barrier(1), out, 0)
                refs.append(out[0])
        # Concurrent: coalescing ON, generous window so the sessions'
        # rounds land in shared batches deterministically.
        with ParseService(coalesce=True, coalesce_window_ms=50.0) as svc:
            _inject_parser(svc, config)
            barrier = threading.Barrier(len(payload_sets))
            out = {}
            threads = [
                threading.Thread(
                    target=_raw_parity_session,
                    args=(svc.host, svc.port, config_payload, payloads,
                          barrier, out, i),
                )
                for i, payloads in enumerate(payload_sets)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
        for i, ref in enumerate(refs):
            assert out.get(i) is not None, (name, i)
            for r, (kind, body) in enumerate(out[i]):
                assert kind == "arrow", (name, i, r)
                assert body == ref[r][1], (
                    f"{name}: session {i} round {r} coalesced bytes "
                    "differ from solo parse"
                )
    assert metrics().histogram(
        "service_coalesced_sessions_per_batch"
    ).sum - sum0 > metrics().histogram(
        "service_coalesced_sessions_per_batch"
    ).count - count0, "no batch ever coalesced >1 session"


@pytest.mark.slow
def test_deadline_expiry_while_queued():
    """An entry whose deadline expires while QUEUED behind a slow shared
    batch answers a structured DEADLINE (counted as a queue expiry) and
    never poisons the batch — and the abandoned batch RECYCLES its lane
    (round 15 head-of-line fix): the next request on the key is served
    by a fresh dispatcher WHILE the wedged parse still runs, so the
    follow-up parse below must succeed first try, no retry loop."""
    before = metrics().get("service_coalesce_expired_total")
    recycles0 = metrics().get("service_coalesce_lane_recycles_total")
    with ParseService(request_deadline_s=1.0,
                      coalesce_window_ms=0.0) as svc:
        # The wedge (6 s) dwarfs the deadline (1 s): if the lane did
        # NOT recycle, the follow-up request would sit behind it past
        # its own deadline — the success below is only reachable
        # through the recycled lane.  (1 s, not something tighter: the
        # recycled lane's parse is instant, but the box running the
        # whole suite is loaded.)
        started = _stub_with_start_signal(svc, [6.0])
        with ParseServiceClient(
            svc.host, svc.port, "combined", FIELDS[:1]
        ) as slow, ParseServiceClient(
            svc.host, svc.port, "combined", FIELDS[:1]
        ) as queued:
            errs = {}

            def drive(client, key):
                try:
                    client.parse(["a", "b"])
                except Exception as e:  # noqa: BLE001
                    errs[key] = e

            t1 = threading.Thread(target=drive, args=(slow, "slow"))
            t1.start()
            assert started.wait(5)  # slow's batch is claimed, in flight
            t2 = threading.Thread(target=drive, args=(queued, "queued"))
            t2.start()
            t1.join(10)
            t2.join(10)
            assert isinstance(errs.get("slow"), ServiceDeadlineError)
            assert isinstance(errs.get("queued"), ServiceDeadlineError)
            # Deterministic recovery: the recycled lane serves the key
            # immediately — one parse() call, while the abandoned batch
            # is still wedged in the background.
            assert queued.parse(["c"]).num_rows == 1
    assert metrics().get("service_coalesce_expired_total") >= before + 1
    assert metrics().get(
        "service_coalesce_lane_recycles_total") >= recycles0 + 1


def _stub_with_start_signal(svc, first_delays):
    """Install the stub parser and return an Event set when a parse
    BEGINS — the deterministic 'the batch is claimed and in flight'
    rendezvous the queue-bound drills need (sleeps race under load).
    The full response path (pyarrow/pandas import + IPC assembly) is
    warmed BEFORE the delays are armed: on a cold process that first
    import costs seconds and would eat any sub-second request deadline
    the drill sets."""
    started = threading.Event()
    parser = _install_stub(svc)
    end = time.monotonic() + 30.0
    with ParseServiceClient(svc.host, svc.port, "combined",
                            FIELDS[:1]) as warm:
        while True:
            try:
                warm.parse(["w"])
                break
            except ServiceDeadlineError:
                assert time.monotonic() < end, "warm-up never completed"
    parser._first = list(first_delays)
    orig = parser._sleep

    def sleep_and_signal():
        started.set()
        orig()

    parser._sleep = sleep_and_signal
    return started


def _wait_lane_queue(svc, depth, deadline_s=5.0):
    """Poll until some coalescer lane's submission queue holds exactly
    ``depth`` PENDING entries."""
    co = svc._server.coalescer
    end = time.monotonic() + deadline_s
    while time.monotonic() < end:
        with co._lock:
            lanes = list(co._batchers.values())
        if any(len(b.queue) == depth for b in lanes):
            return
        time.sleep(0.01)
    raise AssertionError(f"no lane ever held {depth} queued entries")


@pytest.mark.slow
def test_coalesce_queue_feeds_admission_backpressure():
    """The coalescer's queue occupancy feeds queue_backpressure(): a
    saturated submission queue makes the ADMISSION tier shed
    BUSY{backpressure} before the queue itself has to (docs/SERVICE.md
    — coalescing composes with admission, it does not bypass it)."""
    with ParseService(coalesce_queue_depth=1,
                      coalesce_window_ms=0.0) as svc:
        started = _stub_with_start_signal(svc, [0.8])
        clients = [
            ParseServiceClient(svc.host, svc.port, "combined", FIELDS[:1])
            for _ in range(3)
        ]
        try:
            results = {}

            def drive(i):
                try:
                    results[i] = clients[i].parse(["x"]).num_rows
                except Exception as e:  # noqa: BLE001
                    results[i] = e

            t0 = threading.Thread(target=drive, args=(0,))
            t0.start()
            assert started.wait(5)  # claimed into the in-flight batch
            t1 = threading.Thread(target=drive, args=(1,))
            t1.start()
            _wait_lane_queue(svc, 1)  # occupancy 1/1 >= the threshold
            with pytest.raises(ServiceBusyError) as ei:
                clients[2].parse(["y"])
            assert ei.value.reason == "backpressure"
            t0.join(10)
            t1.join(10)
            assert results[0] == 1 and results[1] == 1
        finally:
            for c in clients:
                c.close()


@pytest.mark.slow
def test_shed_while_queued_coalesce_queue():
    """At coalesce_queue_depth the submission queue itself sheds a
    STRUCTURED BUSY{coalesce_queue} — coalescing must never reintroduce
    the unbounded queue (docs/SERVICE.md).  The admission backpressure
    leg (which normally fires first, test above) is disabled so the
    drill reaches the queue's own bound."""
    before = metrics().get("service_shed_total",
                           labels={"reason": "coalesce_queue"})
    with ParseService(coalesce_queue_depth=1,
                      coalesce_window_ms=0.0,
                      backpressure_threshold=2.0) as svc:
        started = _stub_with_start_signal(svc, [0.8])
        clients = [
            ParseServiceClient(svc.host, svc.port, "combined", FIELDS[:1])
            for _ in range(3)
        ]
        try:
            results = {}

            def drive(i):
                try:
                    results[i] = clients[i].parse(["x"]).num_rows
                except Exception as e:  # noqa: BLE001
                    results[i] = e

            t0 = threading.Thread(target=drive, args=(0,))
            t0.start()
            assert started.wait(5)  # claimed into the in-flight batch
            t1 = threading.Thread(target=drive, args=(1,))
            t1.start()
            _wait_lane_queue(svc, 1)  # the 1-entry queue is now full
            with pytest.raises(ServiceBusyError) as ei:
                clients[2].parse(["y"])
            assert ei.value.reason == "coalesce_queue"
            assert ei.value.structured
            t0.join(10)
            t1.join(10)
            assert results[0] == 1 and results[1] == 1
        finally:
            for c in clients:
                c.close()
    assert metrics().get("service_shed_total",
                         labels={"reason": "coalesce_queue"}) == before + 1


@pytest.mark.slow
def test_drain_completes_queued_coalesce_entries():
    """A graceful drain finishes BOTH the in-flight shared batch and the
    entries still queued behind it — queued work belongs to admitted
    sessions, which the drain waits for."""
    with ParseService(drain_deadline_s=15.0,
                      coalesce_window_ms=0.0) as svc:
        started = _stub_with_start_signal(svc, [0.5])
        c1 = ParseServiceClient(svc.host, svc.port, "combined", FIELDS[:1])
        c2 = ParseServiceClient(svc.host, svc.port, "combined", FIELDS[:1])
        results = {}

        def drive(i, client, n):
            try:
                results[i] = client.parse(["r"] * n).num_rows
            except Exception as e:  # noqa: BLE001
                results[i] = e

        t1 = threading.Thread(target=drive, args=(1, c1, 2))
        t1.start()
        assert started.wait(5)   # claimed + parsing (0.5 s)
        t2 = threading.Thread(target=drive, args=(2, c2, 3))
        t2.start()
        _wait_lane_queue(svc, 1)  # queued behind the in-flight batch
        drainer = threading.Thread(
            target=lambda: svc.shutdown(drain=True), daemon=True
        )
        drainer.start()
        t1.join(10)
        t2.join(10)
        drainer.join(20)
        assert not drainer.is_alive()
        assert results.get(1) == 2, results.get(1)
        assert results.get(2) == 3, results.get(2)
        c1.close()
        c2.close()


# ---------------------------------------------------------------------------
# client batching hints: the coalesce_wait_ms CONFIG key (round 16,
# PROTOCOL.md) — a latency-critical session caps the straggler window
# its requests may hold a forming batch open; parsing, queue bounds, and
# shed behavior are untouched.
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_coalesce_window_end_takes_strictest_member():
    """Unit: the formation window is the configured end clamped by every
    claimed entry's own cap — the strictest session decides."""
    from logparser_tpu.service_batching import _Entry, _KeyBatcher

    now = time.monotonic()
    default_end = now + 1.0
    free = _Entry(b"a", 1, None)                      # no hint
    tight = _Entry(b"b", 1, None, max_wait_t=now + 0.01)
    zero = _Entry(b"c", 1, None, max_wait_t=now)
    assert _KeyBatcher._window_end([free], default_end) == default_end
    assert _KeyBatcher._window_end([free, tight], default_end) \
        == tight.max_wait_t
    assert _KeyBatcher._window_end([free, tight, zero], default_end) == now


@pytest.mark.slow
def test_coalesce_hint_submit_and_queue_bound():
    """Unit: submit() stamps the cap from max_wait_s, and the bounded
    queue sheds identically with or without the hint."""
    from logparser_tpu.service_batching import (
        BatchCoalescer,
        CoalesceQueueFull,
        _KeyBatcher,
    )

    co = BatchCoalescer(window_s=1.0, max_lines=64, queue_depth=2)
    try:
        b = _KeyBatcher(co, key="k", parser=None, seq=1)
        b._ensure_thread_locked = lambda: None  # keep entries queued
        e1 = b.submit(b"x", 1, None, max_wait_s=0.0)
        assert e1.max_wait_t is not None and e1.max_wait_t <= \
            time.monotonic()
        e2 = b.submit(b"y", 1, None)
        assert e2.max_wait_t is None
        with pytest.raises(CoalesceQueueFull):
            b.submit(b"z", 1, None, max_wait_s=0.0)
        # drain the gauge we bumped
        b.stop()
    finally:
        co.shutdown()


@pytest.mark.slow
def test_coalesce_wait_ms_zero_skips_straggler_window():
    """Wire: with a HUGE coalesce window and a second live session on
    the key (so the window would otherwise be paid), a session sending
    coalesce_wait_ms=0 gets its (byte-identical) answer without sitting
    out the window."""
    corpus = generate_combined_lines(48, seed=9)
    config = {"log_format": "combined", "fields": FIELDS,
              "timestamp_format": None}
    payload = _lines_payload(corpus)
    with ParseService(coalesce=False) as solo:
        _inject_parser(solo, config)
        out = {}
        _raw_parity_session(solo.host, solo.port,
                            json.dumps(config).encode(), [payload],
                            threading.Barrier(1), out, 0)
        ref = out[0][0]
    window_s = 6.0
    with ParseService(coalesce=True,
                      coalesce_window_ms=window_s * 1000.0) as svc:
        _inject_parser(svc, config)
        # A second idle session on the SAME parser key: should_wait()
        # now says the window is worth paying, so an unhinted request
        # would stall ~window_s for stragglers.
        idle = socket.create_connection((svc.host, svc.port))
        try:
            _send_frame(idle, json.dumps(config).encode())
            hinted = dict(config, coalesce_wait_ms=0)
            out = {}
            t0 = time.monotonic()
            _raw_parity_session(svc.host, svc.port,
                                json.dumps(hinted).encode(), [payload],
                                threading.Barrier(1), out, 0)
            elapsed = time.monotonic() - t0
        finally:
            idle.close()
    kind, body = out[0][0]
    assert kind == "arrow"
    assert body == ref[1], "hinted response diverged from solo parse"
    assert elapsed < window_s / 2, (
        f"coalesce_wait_ms=0 still paid the straggler window "
        f"({elapsed:.2f}s of {window_s}s)"
    )


@pytest.mark.slow
def test_coalesce_wait_ms_invalid_is_config_error():
    with ParseService() as svc:
        sock = socket.create_connection((svc.host, svc.port))
        try:
            _send_frame(sock, json.dumps({
                "log_format": "%h %u %>s",
                "fields": ["IP:connection.client.host"],
                "coalesce_wait_ms": -5,
            }).encode())
            _send_frame(sock, _lines_payload(["1.2.3.4 u 200"]))
            kind, body = _recv_response(sock)
            assert kind == "error"
            assert b"coalesce_wait_ms" in body
        finally:
            sock.close()



@pytest.mark.slow
def test_sidecar_process_survives_successive_sessions():
    """The sidecar CLI's first pyarrow import used to happen on a session
    thread, and pyarrow 25's mimalloc pool then segfaulted the process on
    its second session, once that thread had exited.  The service now
    loads pyarrow on the constructing thread."""
    import os
    import subprocess
    import sys

    from logparser_tpu.tools.demolog import HEADLINE_FIELDS

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.Popen(
        [sys.executable, "-m", "logparser_tpu.service", "--sidecar"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        cwd=root, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    try:
        line = proc.stdout.readline()
        while line and not line.startswith("SIDECAR_READY "):
            line = proc.stdout.readline()
        port = json.loads(line.split(" ", 1)[1])["port"]
        for seed in range(4):  # each session is a new, short-lived thread
            client = ParseServiceClient("127.0.0.1", port, "combined",
                                        HEADLINE_FIELDS, timeout=300)
            table = client.parse(generate_combined_lines(256, seed=seed))
            client.close()
            assert table.num_rows == 256
        assert proc.poll() is None
    finally:
        proc.kill()
        proc.wait(10)


# -- the served path of the benchmark's sidecar cell ------------------------
#
# NGINX's predefined ``combined`` log_format with the fields the served
# benchmark cell asks for (benchmark/configs/nginx_combined_sidecar.json).
NGINX_COMBINED = ('$remote_addr - $remote_user [$time_local] "$request" '
                  '$status $body_bytes_sent "$http_referer" "$http_user_agent"')
SERVED_FIELDS = [
    "IP:connection.client.host",
    "TIME.STAMP:request.receive.time",
    "HTTP.METHOD:request.firstline.method",
    "HTTP.PATH:request.firstline.uri.path",
    "HTTP.QUERYSTRING:request.firstline.uri.query",
    "STRING:request.status.last",
    "BYTES:response.body.bytes",
]


def _nginx_line(i, query_len, ua_len):
    query = "&".join(f"k{j}=v{i}" for j in range(query_len // 8))
    return (f'10.0.{i % 256}.{i // 256} - - [07/Mar/2026:10:00:00 +0000] '
            f'"GET /p/{i}?{query} HTTP/1.1" 200 {i} "-" "{"u" * ua_len}"')


def test_a_long_uri_keeps_every_warm_shape():
    """A URI longer than the old adaptive scan window (192 bytes, then
    twice that per CSR growth) used to rebuild the parser's executor:
    every warm shape compiled again on the request path, and a served
    sidecar stopped answering for seconds at the first long URI of a
    window.  The URI window is now fixed, so a request of a warm shape
    with a 600-byte URI compiles and loads nothing."""
    warm = [_nginx_line(i, 8, 640) for i in range(40)]
    long_uri = [_nginx_line(i, 600, 40) for i in range(40)]
    assert max(map(len, warm)) // 128 == max(map(len, long_uri)) // 128
    reg = metrics()

    def walks():
        return (reg.total("parser_prewarm_runs_total")
                + reg.total("parser_prewarm_errors_total"))

    walks0 = walks()
    with ParseService() as svc, ParseServiceClient(
        svc.host, svc.port, NGINX_COMBINED, SERVED_FIELDS, timeout=300
    ) as client:
        assert client.parse(warm).num_rows == len(warm)
        # The new parser's shape ladder compiles on the service's prewarm
        # thread, off the request path, and may still be walking when
        # the first answer lands: count from the end of that walk.
        deadline = time.monotonic() + 300
        while walks() == walks0 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert walks() > walks0
        compiles = reg.total("parser_compile_total")
        table = client.parse(long_uri)
        assert reg.total("parser_compile_total") == compiles
        assert reg.total("parser_csr_growths_total") == 0
    queries = table.column(SERVED_FIELDS[4]).to_pylist()
    assert [len(q) for q in queries] == [
        len(ln.split("?", 1)[1].split(" ", 1)[0]) + 1 for ln in long_uri]
    assert all(table.column("__valid__").to_pylist())


def test_a_coalesced_request_records_self_time_in_every_served_span(service):
    """One coalesced LINES request moves the served path's spans: the
    request (receipt to last byte), its wait in the coalescer queue,
    the dispatcher's parse of the formed batch, the scatter back to the
    entries and the socket write, each with self time of its own."""
    stages = ("serve_request", "coalesce_wait", "coalesce_parse", "scatter",
              "serve_send")
    reg = metrics()

    def self_s(stage):
        return reg.get("stage_self_seconds_total", labels={"stage": stage})

    def count(stage):
        return reg.histogram("stage_seconds", labels={"stage": stage}).count

    before = {s: (self_s(s), count(s)) for s in stages}
    with ParseServiceClient(
        service.host, service.port, "combined", FIELDS
    ) as client:
        assert client.parse(generate_combined_lines(64, seed=5)).num_rows == 64
    # The client can hold its answer before the server's threads have
    # closed scatter, serve_send and serve_request behind it.
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and any(
            self_s(s) <= before[s][0] or count(s) <= before[s][1]
            for s in stages):
        time.sleep(0.01)
    for s in stages:
        assert count(s) > before[s][1], s
        assert self_s(s) > before[s][0], s
    wait = reg.histogram("stage_seconds", labels={"stage": "coalesce_wait"})
    req = reg.histogram("stage_seconds", labels={"stage": "serve_request"})
    assert wait.sum < req.sum


def test_served_answers_equal_the_benchmark_reference():
    """Seeded weblog lines (the served benchmark cell's corpus, NGINX
    dialect, a tiny pool) sent by three concurrent sessions, so the
    coalescer shares batches among them: every answered row equals the
    benchmark's plain reference (benchmark/reference.py, which imports
    nothing of the program)."""
    import os
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    sys.path[:0] = [bench]
    try:
        import numpy as np
        from gen.weblog import Corpus
        from loadgen import compare
        from reference import Reference
    finally:
        sys.path.remove(bench)
    with open(os.path.join(bench, "corpora", "weblog.json")) as f:
        params = dict(json.load(f), pool_lines=400)
    corpus = Corpus(params, 2**35 + 7, "nginx")
    rng = np.random.default_rng(2**35 + 8)
    lines_of = {}
    for k in range(9):
        idx = corpus.draw(rng, 50)
        lines_of[k] = corpus.lines(idx, corpus.clock(
            params["start_epoch_s"] + k, 50, 50))
    answers = {}
    with ParseService() as svc:
        def session(ks):
            with ParseServiceClient(svc.host, svc.port, NGINX_COMBINED,
                                    SERVED_FIELDS, timeout=300) as client:
                for k in ks:
                    answers[k] = client.parse(lines_of[k]).to_pylist()

        threads = [threading.Thread(target=session, args=(range(j, 9, 3),))
                   for j in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
    ref = Reference(NGINX_COMBINED, SERVED_FIELDS)
    got = compare(ref, lines_of, answers, len(lines_of), len(answers))
    assert got["row_mismatches"] == 0, got["first_mismatch"]
    assert got["unanswered"] == 0 and got["compared_lines"] == 450
