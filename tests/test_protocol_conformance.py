"""Wire-protocol conformance: replay the frozen golden byte streams
(docs/PROTOCOL.md, tests/golden/protocol/) against a live ParseService
using RAW sockets and a self-contained framing implementation — no
ParseServiceClient, no service.py framing helpers.  This is exactly what a
third-party (JVM/Go/C++) client would do, so a pass here means the
protocol document + vectors are sufficient to implement one.
"""
import json
import os
import socket
import struct

import pytest

GOLDEN = os.path.join(os.path.dirname(__file__), "golden", "protocol")

ERROR_MARKER = 0xFFFFFFFF


def _recv_exact(sock, n):
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        assert chunk, "server closed mid-frame"
        buf.extend(chunk)
    return bytes(buf)


def recv_response(sock):
    """(kind, payload): kind is 'arrow' or 'error' per PROTOCOL.md."""
    (header,) = struct.unpack(">I", _recv_exact(sock, 4))
    if header == ERROR_MARKER:
        (n,) = struct.unpack(">I", _recv_exact(sock, 4))
        return "error", _recv_exact(sock, n)
    return "arrow", _recv_exact(sock, header)


@pytest.fixture(scope="module")
def service():
    from logparser_tpu.service import ParseService

    with ParseService() as svc:
        yield svc


def _connect_and_send(svc, vector):
    with open(os.path.join(GOLDEN, vector), "rb") as f:
        blob = f.read()
    sock = socket.create_connection((svc.host, svc.port))
    sock.sendall(blob)
    return sock


def _tupleless(values):
    """Arrow map rows decode as (key, value) tuples; golden JSON stores
    them as [key, value] lists."""
    if isinstance(values, tuple):
        return list(values)
    if isinstance(values, list):
        return [_tupleless(v) for v in values]
    return values


@pytest.mark.slow  # Full golden-vector session (service-side parser compile): slow tier (re-tier r06).
def test_01_session_vector(service):
    import pyarrow as pa

    with open(os.path.join(GOLDEN, "01_expected.json")) as f:
        expected = json.load(f)["batches"]
    sock = _connect_and_send(service, "01_session_request.bin")
    try:
        for want in expected:
            kind, payload = recv_response(sock)
            assert kind == "arrow"
            with pa.ipc.open_stream(pa.BufferReader(payload)) as reader:
                table = reader.read_all()
            # Column order: requested fields in request order + __valid__.
            assert table.column_names == list(want.keys())
            for col in table.column_names:
                assert _tupleless(table[col].to_pylist()) == want[col], col
        # After end-of-session the server closes the connection.
        assert sock.recv(1) == b""
    finally:
        sock.close()


@pytest.mark.slow  # Full golden-vector session (service-side parser compile): slow tier (re-tier r06).
def test_01_column_types(service):
    import pyarrow as pa

    sock = _connect_and_send(service, "01_session_request.bin")
    try:
        kind, payload = recv_response(sock)
        assert kind == "arrow"
        with pa.ipc.open_stream(pa.BufferReader(payload)) as reader:
            schema = reader.read_all().schema
        assert schema.field("IP:connection.client.host").type == pa.string()
        assert schema.field("BYTES:response.body.bytes").type == pa.int64()
        assert schema.field(
            "STRING:request.firstline.uri.query.*"
        ).type == pa.map_(pa.string(), pa.string())
        assert schema.field("__valid__").type == pa.bool_()
    finally:
        sock.close()


@pytest.mark.slow  # Full golden-vector session (service-side parser compile): slow tier.
def test_01_bytes_identical_with_telemetry(service):
    """Round-7 compatibility rule: a v1 session (no `stats` CONFIG key)
    replays the golden vector BYTE-identically whether or not telemetry
    is active in the process (registry populated, a concurrent
    stats-enabled session having run)."""
    import json as _json
    import struct as _struct

    import logparser_tpu

    def replay():
        sock = _connect_and_send(service, "01_session_request.bin")
        try:
            frames = [recv_response(sock) for _ in range(2)]
            assert sock.recv(1) == b""
        finally:
            sock.close()
        return frames

    baseline = replay()
    # Churn the registry with a stats session against the SAME server
    # (exercises the stats-enabled code path), then replay again.
    stages_before = logparser_tpu.metrics().stage_breakdown()
    sock = socket.create_connection((service.host, service.port))
    try:
        config = _json.dumps({
            "log_format": "combined",
            "fields": ["IP:connection.client.host"],
            "stats": True,
        }).encode()
        sock.sendall(_struct.pack(">I", len(config)) + config)
        line = (b'9.8.7.6 - - [01/Jan/2026:00:00:00 +0000] '
                b'"GET / HTTP/1.1" 200 5 "-" "x"')
        payload = _struct.pack(">I", 1) + line
        sock.sendall(_struct.pack(">I", len(payload)) + payload)
        kind, _arrow = recv_response(sock)
        assert kind == "arrow"
        kind2, stats_frame = recv_response(sock)
        assert kind2 == "arrow"  # a STATS frame is an ordinary frame
        assert _json.loads(stats_frame)["v"] == 1
        sock.sendall(_struct.pack(">I", 0))
    finally:
        sock.close()
    with_telemetry = replay()
    assert with_telemetry == baseline
    # The replay really ran through the instrumented stages.
    stages_after = logparser_tpu.metrics().stage_breakdown()
    assert (stages_after["encode"]["calls"]
            > stages_before.get("encode", {}).get("calls", 0))


def test_02_bad_config_vector(service):
    sock = _connect_and_send(service, "02_bad_config_request.bin")
    try:
        # The config error is relayed for the pipelined LINES frame too,
        # and the session drains instead of resetting.
        kind, payload = recv_response(sock)
        assert kind == "error"
        assert b"bad config" in payload
        kind2, payload2 = recv_response(sock)
        assert kind2 == "error"
    finally:
        sock.close()


def test_03_bad_lines_recovers(service):
    import pyarrow as pa

    sock = _connect_and_send(service, "03_bad_lines_request.bin")
    try:
        kind, payload = recv_response(sock)
        assert kind == "error"
        assert b"declared" in payload
        # The session stays usable: the next LINES frame parses.
        kind2, payload2 = recv_response(sock)
        assert kind2 == "arrow"
        with pa.ipc.open_stream(pa.BufferReader(payload2)) as reader:
            table = reader.read_all()
        assert table["IP:connection.client.host"].to_pylist() == ["1.2.3.4"]
        assert table["__valid__"].to_pylist() == [True]
    finally:
        sock.close()
