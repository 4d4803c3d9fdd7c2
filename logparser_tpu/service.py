"""Sidecar parse service: any-host interop over Arrow IPC.

SURVEY §7 step 5: "Java/any-host interop over Arrow IPC; sidecar service
mode".  The reference embeds the parser in-process in each engine (Hadoop,
Pig, Hive, ...); the TPU-native equivalent offers the same capability to
non-Python hosts by running the batch parser behind a socket: a JVM/Go/C++
data engine ships raw loglines to the sidecar and gets typed Arrow columns
back, so one TPU-attached process serves many engine workers.

Wire protocol (deliberately trivial to implement from any language):

    frame     := u32 big-endian length, then `length` payload bytes
    session   := CONFIG frame, then any number of
                 [LINES frame -> ARROW frame [-> STATS frame]]
    CONFIG    := JSON {"log_format": str, "fields": [str, ...],
                       "timestamp_format": str|null,
                       "assembly_workers": int|null (optional; host-side
                       Arrow assembly parallelism, default auto),
                       "feeder_workers": int|null (optional; >= 2 = frame
                       large LINES payloads through the sharded feeder
                       fabric — N threads frame disjoint byte-range shards
                       in parallel; the ARROW frame is unchanged in shape
                       and content, docs/FEEDER.md.  The fabric degrades,
                       never drops: a feeder failure re-parses the request
                       inline and demotes the session to inline parsing
                       for its remaining frames,
                       service_feeder_demotions_total),
                       "stats": bool (optional; true = one STATS JSON frame
                       after each ARROW frame — v1 sessions that omit the
                       key get byte-identical v1 behavior)}
    LINES     := u32 big-endian line count, then the loglines joined by '\n'
                 (UTF-8).  Loglines cannot contain '\n' — they are lines.
                 count=0 means an empty batch (an empty ARROW table comes
                 back); an empty logline is a present-but-empty row.
    ARROW     := one Arrow IPC stream (schema + one record batch) with the
                 requested columns plus the `__valid__` validity column
    STATS     := UTF-8 JSON telemetry frame (docs/PROTOCOL.md "stats" key):
                 per-request timing/sizes + process-cumulative stage
                 breakdown from the metrics registry
    error     := in place of an ARROW frame: 0xFFFFFFFF marker frame followed
                 by one frame of UTF-8 error text
    length 0  := end of session (client side); server closes the connection

Compiled parsers are cached per config, so successive sessions with the same
LogFormat skip recompilation (the service-side analogue of the reference's
"compile the Pattern only once", TokenFormatDissector.java:209-210).

Robustness contract (round 12, docs/SERVICE.md — the serving twin of the
feeder's "degrade, never drop" fault model):

- **Admission control & load shedding.**  Concurrent sessions are bounded
  by ``max_sessions`` and concurrently-parsing requests by
  ``max_inflight``; the per-request check is additionally wired to the
  feeder fabric's queue-backpressure signal
  (:func:`logparser_tpu.feeder.queue_backpressure`).  Over budget, the
  server answers with a STRUCTURED ``BUSY`` error frame carrying a
  retry-after hint — never a TCP reset — and counts the shed in
  ``service_shed_total{reason}``.
- **Deadlines everywhere.**  Per-frame socket read timeouts, a per-session
  idle timeout, and an optional per-request parse deadline
  (``request_deadline_s``): an expired request yields a ``DEADLINE`` error
  frame and the session SURVIVES.
- **Input hardening.**  Frame-length ceilings and CONFIG/LINES payload
  caps are enforced BEFORE allocation: a hostile 4 GiB length prefix or a
  junk CONFIG costs one error frame, not an OOM.
- **Graceful drain.**  ``shutdown(drain=True)`` (SIGTERM under the CLI)
  stops accepting, flips ``/readyz`` to draining so orchestrators stop
  routing, lets admitted sessions finish under ``drain_deadline_s``, then
  escalates force-close -> join — leaked threads are warned once and
  counted (``service_teardown_errors_total{site}``), never silent.

Throughput contract (round 14, docs/SERVICE.md "Continuous batching"):
concurrent sessions sharing a parser config COALESCE into shared device
batches (:mod:`logparser_tpu.service_batching`): per-batch fixed costs
(dispatch, pad waste, D2H round-trip) amortize across sessions, each
session scatters back its exact row window — BYTE-identical to solo
parsing, so nothing changes on the wire — and the coalescer's bounded
queue composes with the admission tier above (full queue = structured
``BUSY{coalesce_queue}``; queue occupancy feeds ``queue_backpressure()``;
request deadlines expire queued entries without poisoning shared
batches).  Knobs: ``coalesce`` / ``coalesce_window_ms`` /
``coalesce_max_lines`` / ``coalesce_queue_depth``.

Observability (docs/OBSERVABILITY.md): the service renders the process-wide
metrics registry as a Prometheus ``/metrics`` HTTP endpoint
(``metrics_port=``, or LOGPARSER_TPU_METRICS_PORT for the CLI) plus
``/healthz`` (liveness) and ``/readyz`` (readiness; 503 while draining),
and can log a periodic one-line stats summary (``stats_interval=`` /
LOGPARSER_TPU_STATS_INTERVAL).  ``python -m logparser_tpu.service`` runs
the sidecar standalone with all of it wired up.
"""
from __future__ import annotations

import itertools
import json
import logging
import os
import queue
import random
import socket
import socketserver
import struct
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

from .observability import (
    log_version_banner_once,
    log_warning_once,
    metrics,
    note_teardown,
    pipeline_stage,
    suppressed_warning_counts,
)
from .tracing import (
    child_span,
    flight_event,
    flightz_payload,
    parse_traceparent,
    tracez_payload,
)

LOG = logging.getLogger(__name__)

_ERROR_MARKER = 0xFFFFFFFF
_MAX_FRAME = 1 << 30  # 1 GiB absolute frame ceiling (protocol v1)
# Sharded-feeder engagement floor: below this many lines a LINES frame is
# parsed inline — splitting pays for itself only when the framing work
# dwarfs the per-shard setup (docs/FEEDER.md "worker sizing").
_FEEDER_MIN_LINES = 4096
# Bounds for the courtesy read-to-EOF after a terminal error response: the
# peer may still be mid-send, and closing with unread bytes in the receive
# buffer turns into an RST that can discard the very frame just written.
_LINGER_DRAIN_S = 1.0
_LINGER_DRAIN_BYTES = 4 << 20


# ---------------------------------------------------------------------------
# framing
# ---------------------------------------------------------------------------


def _read_exact(sock: socket.socket, n: int) -> Optional[bytes]:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            return None
        buf.extend(chunk)
    return bytes(buf)


def read_frame(sock: socket.socket) -> Optional[bytes]:
    """One length-prefixed frame; None on clean EOF or length-0 frame.
    Error responses raise the CLASSIFIED service error
    (:func:`classify_service_error`): plain :class:`ParseServiceError`,
    or its :class:`ServiceBusyError` / :class:`ServiceDeadlineError`
    structured subclasses."""
    header = _read_exact(sock, 4)
    if header is None:
        return None
    (length,) = struct.unpack(">I", header)
    if length == 0:
        return None
    if length == _ERROR_MARKER:
        payload = read_frame(sock)
        raise classify_service_error(
            (payload or b"(no error text)").decode("utf-8", errors="replace")
        )
    if length > _MAX_FRAME:
        raise ValueError(f"frame length {length} exceeds cap {_MAX_FRAME}")
    return _read_exact(sock, length)


def write_frame(sock: socket.socket, payload: bytes) -> None:
    # Two sendalls: no header+payload concatenation copy (Arrow responses
    # can be large).
    sock.sendall(struct.pack(">I", len(payload)))
    sock.sendall(payload)


def write_error(sock: socket.socket, message: str) -> None:
    sock.sendall(struct.pack(">I", _ERROR_MARKER))
    write_frame(sock, message.encode("utf-8"))


class ParseServiceError(RuntimeError):
    """Server-side failure relayed to the client."""


class ServiceClosedError(ParseServiceError):
    """The server closed the connection where a response frame was due —
    the one outcome the shedding/deadline machinery exists to prevent
    (an orderly server always answers with a structured frame first)."""


class ServiceBusyError(ParseServiceError):
    """Structured ``BUSY`` overload response (docs/PROTOCOL.md "Overload
    responses"): the request (reason ``inflight``/``backpressure``) or
    the whole connection (reason ``sessions``/``draining``/
    ``sidecar_failover``/``tenant_quota``) was SHED.  ``retry_after_s``
    is the server's backoff hint; ``structured`` is False only for a
    BUSY-prefixed frame whose JSON failed to parse."""

    def __init__(self, message: str, reason: str = "busy",
                 retry_after_s: float = 0.0, structured: bool = True):
        super().__init__(message)
        self.reason = reason
        self.retry_after_s = retry_after_s
        self.structured = structured


class ServiceUnavailableError(ParseServiceError):
    """The client exhausted its ``max_redirect_retries`` budget on
    connection-level sheds (``draining``/``sidecar_failover``/
    ``sessions``): every reconnect landed on a server that refused the
    whole connection again — the fleet (or the lone server) is
    UNAVAILABLE and the caller should fail fast, not keep spinning
    through reconnect/backoff cycles (docs/SERVICE.md "Client retry
    contract")."""


#: BUSY reasons that shed the whole CONNECTION (the server closes the
#: socket by contract): the client must reconnect before retrying, and
#: each one counts against ``max_redirect_retries``.  The single
#: source of truth — the front tier and loadgen reuse it.
#: ``tenant_quota`` is the SESSION-level tenant shed; the front's
#: request-level tenant shed is the distinct reason ``tenant_inflight``
#: (session survives, resend on the same connection) precisely so
#: clients never have to guess which kind they got.
RECONNECT_BUSY_REASONS = ("sessions", "draining", "sidecar_failover",
                          "tenant_quota")


class ServiceDeadlineError(ParseServiceError):
    """Structured ``DEADLINE`` response: the per-request parse deadline
    expired server-side.  The session survives — the next LINES frame is
    processed normally."""

    def __init__(self, message: str, deadline_s: float = 0.0):
        super().__init__(message)
        self.deadline_s = deadline_s


def busy_error_text(reason: str, retry_after_s: float) -> str:
    """The structured BUSY error-frame text (docs/PROTOCOL.md): the code
    word, one space, then a JSON object — trivially parseable from any
    client language, still readable as plain text by a v1 client."""
    return "BUSY " + json.dumps(
        {"reason": reason, "retry_after_ms": int(retry_after_s * 1000.0)},
        sort_keys=True, separators=(",", ":"),
    )


def deadline_error_text(deadline_s: float) -> str:
    """The structured DEADLINE error-frame text (docs/PROTOCOL.md)."""
    return "DEADLINE " + json.dumps(
        {"deadline_ms": int(deadline_s * 1000.0)},
        sort_keys=True, separators=(",", ":"),
    )


def classify_service_error(text: str) -> ParseServiceError:
    """Map error-frame text to the richest matching exception: the
    ``BUSY ``/``DEADLINE `` structured prefixes (round 12) become their
    typed subclasses, anything else the plain :class:`ParseServiceError`.
    A structured prefix with junk JSON still classifies (the code word is
    the contract; the JSON is the detail) but is flagged unstructured."""
    if text.startswith("BUSY"):
        try:
            detail = json.loads(text[4:].strip() or "{}")
            if not isinstance(detail, dict):
                raise TypeError("detail is not an object")
            return ServiceBusyError(
                text,
                reason=str(detail.get("reason", "busy")),
                retry_after_s=float(detail.get("retry_after_ms", 0)) / 1000.0,
            )
        except (ValueError, TypeError):
            return ServiceBusyError(text, structured=False)
    if text.startswith("DEADLINE"):
        try:
            detail = json.loads(text[8:].strip() or "{}")
            if not isinstance(detail, dict):
                raise TypeError("detail is not an object")
            return ServiceDeadlineError(
                text,
                deadline_s=float(detail.get("deadline_ms", 0)) / 1000.0,
            )
        except (ValueError, TypeError):
            return ServiceDeadlineError(text)
    return ParseServiceError(text)


# ---------------------------------------------------------------------------
# server-side frame reading: deadlines + pre-allocation ceilings
# ---------------------------------------------------------------------------


class _SessionTimeout(Exception):
    """A server-side read deadline fired: ``kind`` is ``"idle"`` (no
    frame started inside the idle window) or ``"frame"`` (a started
    frame stalled mid-transfer — unresyncable, the session closes)."""

    def __init__(self, kind: str):
        super().__init__(kind)
        self.kind = kind


class _FrameTooLarge(Exception):
    """A frame announced a length over a ceiling BEFORE any allocation.
    ``fatal=True``: over the absolute frame cap — the payload was not
    consumed and the session cannot resync (error frame, then close).
    ``fatal=False``: over a payload cap — the payload was READ AND
    DISCARDED in bounded chunks, so the session survives to the next
    frame."""

    def __init__(self, length: int, cap: int, fatal: bool):
        super().__init__(f"frame of {length} bytes exceeds the {cap}-byte cap")
        self.length = length
        self.cap = cap
        self.fatal = fatal


def _recv_exact_timed(sock: socket.socket, n: int,
                      first_s: Optional[float],
                      rest_s: Optional[float]) -> Optional[bytes]:
    """`_read_exact` with per-recv deadlines: the FIRST byte waits under
    ``first_s`` (the idle window when reading a header), later bytes
    under ``rest_s`` (the per-frame transfer window).  None on EOF at a
    clean boundary; ConnectionError on EOF mid-buffer (truncated frame);
    :class:`_SessionTimeout` when a window expires."""
    buf = bytearray()
    while len(buf) < n:
        sock.settimeout(first_s if not buf else rest_s)
        try:
            chunk = sock.recv(n - len(buf))
        except socket.timeout:
            raise _SessionTimeout("idle" if not buf else "frame") from None
        if not chunk:
            if not buf:
                return None
            raise ConnectionError(
                f"peer closed mid-frame ({len(buf)}/{n} bytes)"
            )
        buf.extend(chunk)
    return bytes(buf)


def _discard_exact(sock: socket.socket, n: int,
                   timeout_s: Optional[float]) -> None:
    """Consume exactly ``n`` payload bytes without retaining them (the
    over-cap skip path): bounded memory whatever the announced length."""
    remaining = n
    sock.settimeout(timeout_s)
    while remaining > 0:
        try:
            chunk = sock.recv(min(remaining, 1 << 16))
        except socket.timeout:
            raise _SessionTimeout("frame") from None
        if not chunk:
            raise ConnectionError(
                f"peer closed mid-frame ({n - remaining}/{n} bytes)"
            )
        remaining -= len(chunk)


def _linger_drain(sock: socket.socket, deadline_s: float = _LINGER_DRAIN_S,
                  max_bytes: int = _LINGER_DRAIN_BYTES) -> None:
    """Best-effort read-to-EOF before closing after a terminal error
    response: a peer mid-send must be allowed to finish (or go quiet) so
    close() doesn't RST away the buffered error frame.  Bounded by wall
    AND bytes — courtesy, not an obligation to a hostile peer."""
    end = time.monotonic() + deadline_s
    seen = 0
    try:
        sock.settimeout(0.1)
        while time.monotonic() < end and seen < max_bytes:
            try:
                chunk = sock.recv(1 << 16)
            except socket.timeout:
                continue
            if not chunk:
                return
            seen += len(chunk)
    except OSError:
        pass


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ServiceLimits:
    """Every serving-tier limit in one place (docs/SERVICE.md has the
    ops-facing table).  Defaults are production-sane: generous enough
    that a well-behaved client never notices them, finite enough that a
    hostile or wedged one cannot take the process down."""

    max_sessions: int = 64          # concurrent admitted sessions
    max_inflight: int = 0           # concurrent parsing requests (0 = sessions)
    frame_timeout_s: Optional[float] = 30.0   # per-recv mid-frame stall window
    idle_timeout_s: Optional[float] = 600.0   # between-frames session window
    request_deadline_s: Optional[float] = None  # per-request parse deadline
    max_frame_bytes: int = _MAX_FRAME         # absolute frame ceiling
    max_config_bytes: int = 1 << 20           # CONFIG payload cap (1 MiB)
    max_lines_bytes: int = 0                  # LINES payload cap (0 = frame cap)
    busy_retry_after_s: float = 0.25          # BUSY frame retry hint
    backpressure_threshold: float = 0.95      # feeder-queue shed fraction
    drain_deadline_s: float = 10.0            # graceful-drain budget
    # Continuous batching (docs/SERVICE.md "Continuous batching"):
    # cross-session device-batch coalescing, keyed per compiled-parser
    # config.  window = how long a forming batch waits for stragglers
    # (only when >1 session is live); max_lines = the shared batch
    # geometry ceiling; queue_depth = the bounded submission queue
    # (full = structured BUSY{coalesce_queue} shed).
    coalesce: bool = True
    coalesce_window_ms: float = 2.0
    coalesce_max_lines: int = 4096
    coalesce_queue_depth: int = 256

    @property
    def inflight(self) -> int:
        return self.max_inflight or self.max_sessions

    @property
    def lines_cap(self) -> int:
        return self.max_lines_bytes or self.max_frame_bytes


class _ParserCache:
    """LRU-bounded: each entry pins a compiled parser + XLA executables, so
    a long-lived sidecar serving many distinct configs must evict."""

    def __init__(self, max_entries: int = 32,
                 on_insert: Optional[Callable[[Any], None]] = None) -> None:
        self._lock = threading.Lock()
        self._max_entries = max_entries
        self._parsers: "OrderedDict[Tuple, Any]" = OrderedDict()
        self._building: Dict[Tuple, threading.Lock] = {}
        # Called once per freshly BUILT parser (cache hits skip it): the
        # serving tier hooks the background shape-bucket prewarmer here so
        # larger buckets — and the coalesced-batch shape — compile (or
        # load from the persistent compile cache, docs/COMPILE.md) off the
        # request path.
        self._on_insert = on_insert

    @staticmethod
    def key_of(config: Dict[str, Any]) -> Tuple:
        """The compiled-parser identity of a CONFIG: sessions with the
        same key share one parser — and one continuous-batching lane
        (requests coalesce ONLY within a key: a shared device batch must
        run exactly one compiled program)."""
        agg = config.get("aggregate")
        if agg is not None:
            # Analytics pushdown (PROTOCOL.md "aggregate"): per-session
            # specs key the parser cache, so an aggregate session never
            # shares a compiled-reduction cache — or a continuous-
            # batching lane — with a row session or a different spec.
            from .analytics.spec import parse_aggregate_config

            agg = parse_aggregate_config(agg).canonical_key()
        return (
            config["log_format"],
            tuple(config["fields"]),
            config.get("timestamp_format"),
            config.get("assembly_workers"),
            agg,
        )

    def get(self, config: Dict[str, Any]):
        from .tpu.batch import TpuBatchParser

        key = self.key_of(config)
        # Compile outside the global lock: a cold compile takes seconds and
        # must not stall sessions whose parser is already cached.  A per-key
        # lock still deduplicates concurrent compiles of the same config.
        with self._lock:
            parser = self._parsers.get(key)
            if parser is not None:
                self._parsers.move_to_end(key)
                return parser
            key_lock = self._building.setdefault(key, threading.Lock())
        with key_lock:
            with self._lock:
                parser = self._parsers.get(key)
                if parser is not None:
                    self._parsers.move_to_end(key)
            if parser is None:
                try:
                    parser = TpuBatchParser(
                        config["log_format"],
                        list(config["fields"]),
                        timestamp_format=config.get("timestamp_format"),
                        # The wire delivers copy-mode Arrow only, so the
                        # parser never needs device view rows.
                        view_fields=(),
                        assembly_workers=config.get("assembly_workers"),
                    )
                    with self._lock:
                        self._parsers[key] = parser
                        while len(self._parsers) > self._max_entries:
                            self._parsers.popitem(last=False)
                    if self._on_insert is not None:
                        # Outside the cache lock: the hook only ENQUEUES
                        # (the prewarm itself runs on the worker thread),
                        # and a hook failure must never fail the build
                        # that already succeeded.
                        try:
                            self._on_insert(parser)
                        except Exception:  # noqa: BLE001
                            LOG.warning("parser prewarm enqueue failed",
                                        exc_info=True)
                finally:
                    # Failed builds must also drop the per-key build lock:
                    # the parser LRU is bounded but _building is not, and a
                    # long-lived sidecar fed many invalid configs would
                    # otherwise grow it without bound.
                    with self._lock:
                        self._building.pop(key, None)
            return parser


class _PrewarmWorker:
    """Background shape-bucket prewarm (docs/COMPILE.md "Fleet prewarm").

    Every freshly built parser is walked up the bucket ladder — including
    the coalesced-batch shape when continuous batching is on — on ONE
    daemon thread, so no request ever waits on a compile for a bucket it
    did not itself need first.  With a warm compile cache
    (``compile_cache.cache_root()``), each rung is a disk deserialize (or
    an in-memory no-op) instead of an XLA compile; the per-rung source
    lands in
    ``parser_prewarm_shapes_total{source=memory|disk|compiled}``.

    Env knobs:

    - ``LOGPARSER_TPU_PREWARM=0``       disable entirely
    - ``LOGPARSER_TPU_PREWARM_BUCKETS`` comma-separated batch sizes
      (default: the compile cache's ``DEFAULT_BUCKET_LADDER``)
    - ``LOGPARSER_TPU_PREWARM_LINE_LEN`` line-length to warm at
      (bucketed; default 256 — the common access-log ballpark)
    """

    _STOP = object()

    @staticmethod
    def enabled() -> bool:
        return os.environ.get(
            "LOGPARSER_TPU_PREWARM", "1"
        ).strip().lower() not in ("0", "false", "no")

    def __init__(self, limits: ServiceLimits) -> None:
        self._limits = limits
        self._queue: "queue.Queue[Any]" = queue.Queue()
        self._thread = threading.Thread(
            target=self._run, name="logparser-tpu-prewarm", daemon=True
        )
        self._thread.start()

    def ladder(self) -> Tuple[int, ...]:
        raw = os.environ.get("LOGPARSER_TPU_PREWARM_BUCKETS", "").strip()
        if raw:
            buckets = [int(t) for t in raw.split(",") if t.strip()]
        else:
            from .tpu.compile_cache import DEFAULT_BUCKET_LADDER

            buckets = list(DEFAULT_BUCKET_LADDER)
        if self._limits.coalesce:
            # The coalescer dispatches full windows at coalesce_max_lines:
            # that shape is the steady-state hot path under load and must
            # never compile on a request's clock.
            buckets.append(self._limits.coalesce_max_lines)
        return tuple(sorted({int(b) for b in buckets if int(b) > 0}))

    @staticmethod
    def line_len() -> int:
        try:
            return max(1, int(os.environ.get(
                "LOGPARSER_TPU_PREWARM_LINE_LEN", "256")))
        except ValueError:
            return 256

    def enqueue(self, parser: Any) -> None:
        self._queue.put(parser)

    def shutdown(self, timeout_s: float = 2.0) -> None:
        """Best-effort stop: the thread is a daemon, so this only bounds
        how long a graceful shutdown waits for an in-flight warm rung."""
        self._queue.put(self._STOP)
        self._thread.join(timeout=timeout_s)

    def _run(self) -> None:
        while True:
            parser = self._queue.get()
            if parser is self._STOP:
                return
            try:
                t0 = time.perf_counter()
                sources = parser.prewarm(
                    batch_sizes=self.ladder(), max_line_len=self.line_len()
                )
                reg = metrics()
                for source in sources.values():
                    reg.increment("parser_prewarm_shapes_total", 1,
                                  labels={"source": source})
                reg.increment("parser_prewarm_seconds_total",
                              time.perf_counter() - t0)
                # One tick per completed parser walk: pollable by smokes
                # and the benchmark's sidecar driver ("is the ladder warm
                # yet?") where the
                # seconds/shapes counters alone cannot distinguish one
                # finished walk from one still in flight.
                reg.increment("parser_prewarm_runs_total", 1)
                LOG.info("prewarm: %d shapes ready in %.2fs (%s)",
                         len(sources), time.perf_counter() - t0,
                         ", ".join(f"{k}={v}"
                                   for k, v in sorted(sources.items())))
            except Exception:  # noqa: BLE001 — prewarm is an optimization;
                # a failure means first requests pay the compile, nothing
                # worse, and the error class is visible in the counter.
                metrics().increment("parser_prewarm_errors_total", 1)
                LOG.warning("background prewarm failed", exc_info=True)


class _ServiceServer(socketserver.ThreadingTCPServer):
    """The listener plus all shared serving-tier state the per-session
    handlers coordinate through: the session/in-flight budgets, the live
    session registry (the drain machinery's ledger), and the draining
    flag (readiness)."""

    allow_reuse_address = True
    daemon_threads = True

    def __init__(self, addr, handler, limits: ServiceLimits):
        super().__init__(addr, handler)
        self.limits = limits
        # Background shape-bucket prewarmer (docs/COMPILE.md): freshly
        # built parsers walk the bucket ladder off the request path.
        self.prewarmer: Optional[_PrewarmWorker] = (
            _PrewarmWorker(limits) if _PrewarmWorker.enabled() else None
        )
        self.parser_cache = _ParserCache(
            on_insert=(self.prewarmer.enqueue
                       if self.prewarmer is not None else None)
        )
        self.session_seq = itertools.count(1)
        self.session_slots = threading.BoundedSemaphore(limits.max_sessions)
        self.inflight_slots = threading.BoundedSemaphore(limits.inflight)
        self.sessions: Dict[Any, threading.Thread] = {}
        self.sessions_lock = threading.Lock()
        self.key_sessions: Dict[Any, int] = {}
        self.draining = False
        # Cross-session batch coalescer (service_batching.py), attached
        # by ParseService when limits.coalesce is on; None = every
        # request dispatches its own device batch (the pre-round-14
        # behavior).
        self.coalescer: Optional[Any] = None

    def admitted_sessions(self) -> int:
        with self.sessions_lock:
            return sum(1 for h in self.sessions if h.admitted)

    # Sessions per PARSER KEY (registered once the CONFIG resolves,
    # dropped when the session ends): the coalescer's window is only
    # worth paying when another session on the SAME key could
    # contribute — a global count would make a lone tenant on its own
    # format pay the window because an unrelated format has traffic.
    def key_session_enter(self, key: Any) -> None:
        with self.sessions_lock:
            self.key_sessions[key] = self.key_sessions.get(key, 0) + 1

    def key_session_exit(self, key: Any) -> None:
        with self.sessions_lock:
            n = self.key_sessions.get(key, 0) - 1
            if n > 0:
                self.key_sessions[key] = n
            else:
                self.key_sessions.pop(key, None)

    def sessions_on_key(self, key: Any) -> int:
        with self.sessions_lock:
            return self.key_sessions.get(key, 0)

    def admit_request(self) -> Optional[str]:
        """Per-request admission: None = admitted (ONE in-flight slot is
        now held by the caller); otherwise the shed reason.  The
        backpressure leg reads the feeder fabric's queue-occupancy
        signal (docs/FEEDER.md): framed batches waiting at/above the
        threshold fraction of bounded-queue capacity mean the parser is
        the bottleneck and queueing more requests only grows latency."""
        if not self.inflight_slots.acquire(blocking=False):
            return "inflight"
        from .feeder import queue_backpressure

        if queue_backpressure() >= self.limits.backpressure_threshold:
            self.release_request()
            return "backpressure"
        metrics().gauge_add("service_inflight_requests", 1)
        return None

    def release_request(self, gauged: bool = False) -> None:
        self.inflight_slots.release()
        if gauged:
            metrics().gauge_add("service_inflight_requests", -1)

    def handle_error(self, request, client_address) -> None:  # noqa: D102
        # socketserver's default prints a traceback to stderr; a hostile
        # wire must never be able to scribble on the operator's console.
        LOG.exception("unhandled session error from %s", client_address)


class _SessionHandler(socketserver.BaseRequestHandler):
    server: _ServiceServer  # narrowed for type checkers

    # -- lifecycle ------------------------------------------------------

    def setup(self) -> None:
        self.sid = next(self.server.session_seq)
        self.thread = threading.current_thread()
        # Named handler threads + sid-tagged logs: overload drills must be
        # debuggable from a thread dump / log tail alone.
        self.thread.name = f"svc-sess-{self.sid}"
        self.admitted = False
        with self.server.sessions_lock:
            self.server.sessions[self] = self.thread

    def finish(self) -> None:
        with self.server.sessions_lock:
            self.server.sessions.pop(self, None)
        if self.admitted:
            self.server.session_slots.release()
            metrics().gauge_add("service_sessions_active", -1)

    # -- helpers --------------------------------------------------------

    def _read_frame(self, payload_cap: int,
                    discard_over_cap: bool) -> Optional[bytes]:
        """One frame under the session's deadlines and ceilings; the
        length prefix is validated BEFORE any payload allocation."""
        lim = self.server.limits
        sock = self.request
        header = _recv_exact_timed(
            sock, 4, lim.idle_timeout_s, lim.frame_timeout_s
        )
        if header is None:
            return None
        (length,) = struct.unpack(">I", header)
        if length == 0:
            return None
        if length == _ERROR_MARKER:
            raise ParseServiceError("client sent an error marker frame")
        if length > lim.max_frame_bytes:
            raise _FrameTooLarge(length, lim.max_frame_bytes, fatal=True)
        if length > payload_cap:
            if not discard_over_cap:
                raise _FrameTooLarge(length, payload_cap, fatal=True)
            _discard_exact(sock, length, lim.frame_timeout_s)
            raise _FrameTooLarge(length, payload_cap, fatal=False)
        payload = _recv_exact_timed(
            sock, length, lim.frame_timeout_s, lim.frame_timeout_s
        )
        if payload is None:
            raise ConnectionError(f"peer closed mid-frame (0/{length} bytes)")
        return payload

    def _pre_write(self) -> None:
        """Arm the socket for a RESPONSE write: the per-frame READ window
        a prior ``_recv_exact_timed`` left on the socket must not govern
        ``sendall`` — a large Arrow frame on a slow link legitimately
        needs minutes, and CPython enforces the socket timeout as a
        TOTAL sendall deadline.  The idle window (generous, still
        bounded against a peer that stops reading entirely) applies to
        writes instead."""
        try:
            self.request.settimeout(self.server.limits.idle_timeout_s)
        except OSError:
            pass

    def _shed_session(self, reason: str) -> None:
        """Refuse this connection with a structured BUSY frame (never a
        reset): write the frame, let the peer finish/acknowledge, close."""
        lim = self.server.limits
        metrics().increment("service_shed_total", labels={"reason": reason})
        LOG.info("sess=%d shed (%s)", self.sid, reason)
        try:
            self._pre_write()
            write_error(
                self.request, busy_error_text(reason, lim.busy_retry_after_s)
            )
            _linger_drain(self.request)
        except OSError:
            pass

    def _timeout(self, kind: str) -> None:
        metrics().increment("service_timeouts_total", labels={"kind": kind})
        LOG.info("sess=%d %s timeout; closing session", self.sid, kind)

    def _reject_frame(self, reason: str, message: str,
                      fatal: bool) -> bool:
        """Answer an over-limit frame with one error frame; returns
        whether the session can continue (non-fatal = payload was
        consumed, resync is safe)."""
        metrics().increment(
            "service_rejected_frames_total", labels={"reason": reason}
        )
        LOG.warning("sess=%d rejected frame (%s): %s", self.sid, reason,
                    message)
        try:
            self._pre_write()
            write_error(self.request, message)
            if fatal:
                _linger_drain(self.request)
        except OSError:
            return False
        return not fatal

    # -- the session ----------------------------------------------------

    def handle(self) -> None:  # noqa: D102 — socketserver contract
        try:
            if self.server.draining:
                self._shed_session("draining")
                return
            if not self.server.session_slots.acquire(blocking=False):
                self._shed_session("sessions")
                return
            self.admitted = True
            metrics().gauge_add("service_sessions_active", 1)
            self._session()
        except Exception:  # noqa: BLE001 — a session must never kill/print
            LOG.exception("sess=%d unhandled session failure", self.sid)

    def _config_error_loop(self, message: str) -> None:
        """Relay a config error, then keep draining the session answering
        every subsequent frame with the same error: a client already
        mid-send of a large LINES frame would otherwise see ECONNRESET
        and the RST can discard the buffered error text."""
        sock = self.request
        lim = self.server.limits
        try:
            self._pre_write()
            write_error(sock, message)
            while True:
                try:
                    if self._read_frame(lim.lines_cap, True) is None:
                        return
                except _FrameTooLarge as e:
                    if e.fatal:
                        _linger_drain(sock)
                        return
                self._pre_write()
                write_error(sock, message)
        except (OSError, ValueError, ConnectionError, ParseServiceError):
            return
        except _SessionTimeout as e:
            self._timeout(e.kind)
            return

    def _session(self) -> None:
        sock = self.request
        lim = self.server.limits
        try:
            config_frame = self._read_frame(lim.max_config_bytes, True)
        except _SessionTimeout as e:
            self._timeout(e.kind)
            return
        except _FrameTooLarge as e:
            if e.fatal:
                self._reject_frame(
                    "frame_overflow", f"bad config: {e}", fatal=True
                )
            else:
                metrics().increment(
                    "service_rejected_frames_total",
                    labels={"reason": "config_too_large"},
                )
                self._config_error_loop(f"bad config: {e}")
            return
        except (ValueError, OSError, ParseServiceError) as e:
            if isinstance(e, OSError) and not isinstance(e, ConnectionError):
                # Our own force-close (shutdown/drain escalation) lands
                # here as EBADF/ENOTCONN on the blocked recv: routine.
                LOG.info("sess=%d socket closed during config read: %s",
                         self.sid, e)
            else:
                LOG.error("sess=%d bad config frame: %s", self.sid, e)
            return
        if config_frame is None:
            return
        send_stats = False
        feeder_workers = 0
        try:
            config = json.loads(config_frame)
            # Optional telemetry opt-in (PROTOCOL.md "stats" CONFIG key):
            # absent/falsy = byte-identical v1 session.  Not part of the
            # parser cache key — it changes framing, not parsing.
            send_stats = bool(config.get("stats")) if isinstance(
                config, dict) else False
            # Optional sharded-feeder framing (docs/FEEDER.md): >= 2 =
            # big LINES payloads are framed by that many feeder threads
            # over byte-range shards.  Session behavior, not parser
            # state — not part of the cache key either.
            if isinstance(config, dict) and config.get("feeder_workers"):
                feeder_workers = int(config["feeder_workers"])
            # Client batching hint (PROTOCOL.md "coalesce_wait_ms"): a
            # latency-critical session caps the coalescer's straggler
            # window for ITS requests (0 = dispatch immediately once
            # claimed).  Session behavior only — results are
            # byte-identical, so not part of the cache key either.
            coalesce_wait_s: Optional[float] = None
            if isinstance(config, dict) \
                    and config.get("coalesce_wait_ms") is not None:
                coalesce_wait_s = float(config["coalesce_wait_ms"]) / 1e3
                if coalesce_wait_s < 0:
                    raise ValueError(
                        "coalesce_wait_ms must be >= 0, got "
                        f"{config['coalesce_wait_ms']!r}"
                    )
            # Analytics pushdown (PROTOCOL.md "aggregate" / docs/
            # ANALYTICS.md): the session's responses become aggregate
            # frames instead of row Arrow.  Spec errors — bad JSON, an
            # unknown op, a field outside the parse config — relay
            # through the same "bad config:" loop as every other
            # config defect.
            agg_spec = None
            if isinstance(config, dict) \
                    and config.get("aggregate") is not None:
                from .analytics.spec import parse_aggregate_config

                agg_spec = parse_aggregate_config(config["aggregate"])
            # Distributed tracing context (PROTOCOL.md "traceparent"):
            # wire-invisible when absent (byte-identical v1 session);
            # a malformed value is silently DROPPED, never a config
            # error — the W3C contract is that bad trace plumbing must
            # not break the request.  Session behavior only — results
            # are identical either way, so not part of the cache key.
            trace_ctx = None
            if isinstance(config, dict) and config.get("traceparent"):
                trace_ctx = parse_traceparent(config.get("traceparent"))
            parser = self.server.parser_cache.get(config)
            if agg_spec is not None:
                agg_spec.validate_for(parser)
            metrics().increment("service_sessions_total")
        except Exception as e:  # noqa: BLE001 — relay config errors to client
            self._config_error_loop(f"bad config: {e}")
            return

        try:
            parser_key = _ParserCache.key_of(config)
        except Exception:  # noqa: BLE001 — doubles may bypass the schema
            parser_key = repr(config)
        state = {"feeder_workers": feeder_workers,
                 "parser_key": parser_key,
                 "coalesce_wait_s": coalesce_wait_s,
                 "aggregate": agg_spec,
                 "trace_ctx": trace_ctx}
        # Per-key session registry: the coalescer skips its straggler
        # window when this session is the key's only one.
        self.server.key_session_enter(parser_key)
        try:
            while True:
                try:
                    lines_frame = self._read_frame(lim.lines_cap, True)
                except _SessionTimeout as e:
                    self._timeout(e.kind)
                    return
                except _FrameTooLarge as e:
                    if not self._reject_frame(
                        "frame_overflow" if e.fatal else "lines_too_large",
                        f"rejected: {e}", fatal=e.fatal,
                    ):
                        return
                    continue
                except (ValueError, OSError, ParseServiceError) as e:
                    if isinstance(e, OSError) and not isinstance(
                            e, ConnectionError):
                        LOG.info("sess=%d socket closed between frames: %s",
                                 self.sid, e)
                    else:
                        LOG.error("sess=%d bad lines frame: %s", self.sid, e)
                    return
                if lines_frame is None:
                    return  # end of session
                # The request, from receipt to its last byte sent.
                with pipeline_stage("serve_request"):
                    served = self._serve_request(sock, parser, lines_frame,
                                                 state, send_stats)
                if not served:
                    return
        finally:
            self.server.key_session_exit(parser_key)

    # -- one request ----------------------------------------------------

    def _serve_request(self, sock, parser, lines_frame: bytes,
                       state: Dict[str, Any], send_stats: bool) -> bool:
        """One LINES frame -> one response frame (ARROW / BUSY / DEADLINE
        / error).  Returns False only when the socket died."""
        reg = metrics()
        lim = self.server.limits
        # Request span (docs/OBSERVABILITY.md "Tracing"): opened only
        # for sampled sessions; its context rides state["request_ctx"]
        # into the coalescer so the shared-batch span links back here.
        req_span = child_span("service_request", state.get("trace_ctx"),
                              attrs={"sid": self.sid})
        if req_span is not None:
            state["request_ctx"] = req_span.context
        # Every response write in this method (BUSY/DEADLINE/error/ARROW/
        # STATS) runs under the idle window, not the leftover read window.
        self._pre_write()
        shed_reason = self.server.admit_request()
        if shed_reason is not None:
            reg.increment("service_shed_total",
                          labels={"reason": shed_reason})
            flight_event("service_shed", reason=shed_reason, sid=self.sid)
            if req_span is not None:
                req_span.end(outcome="shed", reason=shed_reason)
            LOG.info("sess=%d request shed (%s)", self.sid, shed_reason)
            try:
                write_error(sock, busy_error_text(
                    shed_reason, lim.busy_retry_after_s))
            except OSError:
                return False
            return True

        t_request = time.perf_counter()
        done, outcome = self._run_admitted(
            lambda: self._parse_request(parser, lines_frame, state)
        )
        if not done:
            # Deadline expired: the parse keeps running in its worker
            # (releasing the in-flight slot when it truly finishes — a
            # stuck parse keeps its slot, which IS the backpressure);
            # the session answers and moves on.
            reg.increment("service_deadline_expired_total")
            flight_event("service_deadline_expired", sid=self.sid,
                         deadline_s=lim.request_deadline_s or 0.0)
            if req_span is not None:
                req_span.end(outcome="deadline")
            LOG.warning("sess=%d request deadline (%.3fs) expired",
                        self.sid, lim.request_deadline_s or 0.0)
            try:
                write_error(sock, deadline_error_text(
                    lim.request_deadline_s or 0.0))
            except OSError:
                return False
            return True
        if isinstance(outcome, Exception):
            from .service_batching import (
                CoalesceDeadline,
                CoalesceQueueFull,
            )

            if isinstance(outcome, CoalesceQueueFull):
                # The coalescer's bounded submission queue is full: shed
                # STRUCTURED, exactly like the admission legs — never an
                # opaque parse error (docs/SERVICE.md).
                reg.increment("service_shed_total",
                              labels={"reason": "coalesce_queue"})
                flight_event("service_shed", reason="coalesce_queue",
                             sid=self.sid)
                if req_span is not None:
                    req_span.end(outcome="shed", reason="coalesce_queue")
                LOG.info("sess=%d request shed (coalesce_queue)", self.sid)
                try:
                    write_error(sock, busy_error_text(
                        "coalesce_queue", lim.busy_retry_after_s))
                except OSError:
                    return False
                return True
            if isinstance(outcome, CoalesceDeadline):
                # Expired while QUEUED (dropped before batch formation):
                # the same structured DEADLINE answer an expired solo
                # parse gets, and the session survives.
                reg.increment("service_deadline_expired_total")
                flight_event("service_deadline_expired", sid=self.sid,
                             where="coalesce_queue",
                             deadline_s=lim.request_deadline_s or 0.0)
                if req_span is not None:
                    req_span.end(outcome="deadline",
                                 where="coalesce_queue")
                LOG.warning(
                    "sess=%d request deadline (%.3fs) expired in the "
                    "coalesce queue", self.sid,
                    lim.request_deadline_s or 0.0,
                )
                try:
                    write_error(sock, deadline_error_text(
                        lim.request_deadline_s or 0.0))
                except OSError:
                    return False
                return True
            from .tpu.device_faults import DeviceBudgetError

            if isinstance(outcome, DeviceBudgetError):
                # Pre-allocation device-byte ceiling (docs/FAULTS.md):
                # the batch was refused BEFORE any device_put — a
                # structured reject like the frame ceilings, not an
                # opaque parse failure (the session survives; the
                # client should split its payload).
                reg.increment("service_rejected_frames_total",
                              labels={"reason": "device_budget"})
                if req_span is not None:
                    req_span.end(outcome="rejected", reason="device_budget")
                LOG.warning("sess=%d request rejected (device_budget): "
                            "%s", self.sid, outcome)
                try:
                    write_error(sock, f"parse failed: {outcome}")
                except OSError:
                    return False
                return True
            LOG.error("sess=%d parse failed", self.sid, exc_info=outcome)
            reg.increment("service_request_errors_total")
            if req_span is not None:
                req_span.end(outcome="error",
                             error=f"{type(outcome).__name__}: {outcome}")
            try:
                write_error(sock, f"parse failed: {outcome}")
            except OSError:
                return False
            return True

        payload, count, oracle_rows, bad_lines = outcome
        try:
            with pipeline_stage("serve_send", items=count):
                write_frame(sock, payload)
        except OSError:
            return False
        dt = time.perf_counter() - t_request
        reg.increment("service_requests_total")
        reg.increment("service_lines_total", count)
        reg.observe("service_request_seconds", dt)
        if req_span is not None:
            req_span.end(outcome="ok", lines=count)
        if send_stats:
            # STATS frame: per-request figures + the SAME
            # process-cumulative stage breakdown /metrics reports
            # (one metric definition everywhere).
            stats = {
                "v": 1,
                "request": {
                    "lines": count,
                    "seconds": round(dt, 6),
                    "arrow_bytes": len(payload),
                    "oracle_lines": oracle_rows,
                    "bad_lines": bad_lines,
                },
                "stages": reg.stage_breakdown(),
                # as_dict(): counters only — snapshot() would build
                # every histogram's bucket view per request.
                "counters": dict(sorted(reg.as_dict().items())),
            }
            try:
                write_frame(
                    sock,
                    json.dumps(stats, separators=(",", ":"),
                               sort_keys=True).encode("utf-8"),
                )
            except OSError:
                return False
        return True

    def _run_admitted(self, fn: Callable[[], Any]) -> Tuple[bool, Any]:
        """Run one ADMITTED request under the parse deadline.  The
        in-flight slot is released when the WORK finishes — even after
        its deadline already expired — so abandoned parses keep counting
        against the budget until they actually stop consuming the host.
        Returns ``(completed, result-or-exception)``."""
        server = self.server
        deadline = server.limits.request_deadline_s
        if not deadline:
            try:
                return True, fn()
            except Exception as e:  # noqa: BLE001 — relayed as error frame
                return True, e
            finally:
                server.release_request(gauged=True)

        box: Dict[str, Any] = {}
        done = threading.Event()
        abandoned = threading.Event()

        def run() -> None:
            try:
                box["value"] = fn()
            except Exception as e:  # noqa: BLE001 — relayed / logged
                box["error"] = e
            finally:
                server.release_request(gauged=True)
                done.set()
                if abandoned.is_set():
                    LOG.debug(
                        "sess=%d abandoned request finished (%s)", self.sid,
                        "error" if "error" in box else "ok",
                    )

        worker = threading.Thread(
            target=run, name=f"svc-req-{self.sid}", daemon=True
        )
        worker.start()
        if not done.wait(deadline):
            abandoned.set()
            return False, None
        if "error" in box:
            return True, box["error"]
        return True, box["value"]

    def _parse_request(self, parser, lines_frame: bytes,
                       state: Dict[str, Any]):
        """The request body: LINES validation + parse + Arrow IPC bytes.
        Raises on anything relay-worthy; returns
        ``(ipc_payload, count, oracle_rows, bad_lines)``."""
        if len(lines_frame) < 4:
            raise ValueError("LINES frame shorter than its count header")
        (count,) = struct.unpack(">I", lines_frame[:4])
        if count == 0 and len(lines_frame) > 4:
            raise ValueError(
                "LINES frame declared 0 lines but carries "
                f"{len(lines_frame) - 4} payload bytes"
            )
        blob = lines_frame[4:]
        n_lines = (blob.count(b"\n") + 1) if count else 0
        if n_lines != count:
            raise ValueError(
                f"LINES frame declared {count} lines, payload has "
                f"{n_lines}"
            )
        blob_shape = count and blob and not blob.endswith(b"\n") \
            and b"\r" not in blob
        if state.get("aggregate") is not None:
            # Aggregate session (docs/ANALYTICS.md): the response is an
            # aggregate frame, not row Arrow, so the feeder's table
            # concatenation and the coalescer's row-window slicing
            # don't apply — aggregate requests keep their own
            # dispatch.  (They never coalesce wrongly either way:
            # the spec is part of the parser cache key, so an
            # aggregate session shares no lane with a row session.)
            spec = state["aggregate"]
            if blob_shape:
                agg_out = parser.aggregate_blob(blob, spec)
            else:
                agg_out = parser.aggregate_batch(
                    blob.split(b"\n") if count else [], spec
                )
            return (agg_out.state.to_ipc_bytes(), count,
                    agg_out.oracle_rows, agg_out.bad_lines)
        feeder_workers = state["feeder_workers"]
        table = None
        if blob_shape and feeder_workers >= 2 \
                and count >= _FEEDER_MIN_LINES:
            # Sharded-feeder framing: the blob splits into
            # byte-range shards framed by N threads in parallel;
            # result tables concatenate back in corpus order
            # (byte-identical to the inline blob path).
            try:
                table, oracle_rows, bad_lines = _feeder_parse(
                    parser, blob, count, feeder_workers
                )
                metrics().increment("service_feeder_requests_total")
            except Exception as e:  # noqa: BLE001 — degrade, not drop
                # ANY feeder-path failure demotes the SESSION:
                # its remaining LINES frames parse inline (the
                # fabric already self-heals worker crashes, so
                # reaching here means even quarantine failed —
                # don't re-enter it this session).
                from .feeder import FeederError

                state["feeder_workers"] = 0
                metrics().increment("service_feeder_demotions_total")
                log_warning_once(
                    LOG,
                    "service: sharded-feeder framing failed "
                    f"({type(e).__name__}); session demoted to "
                    "inline parsing",
                )
                if not isinstance(e, FeederError):
                    # A parse-shaped failure would fail inline
                    # too: relay it as a well-formed error frame
                    # (the session stays alive and its NEXT
                    # frame takes the inline path).
                    raise
                # A fabric failure with intact input: retry THIS
                # request inline below — the client sees an
                # error-free ARROW stream, not a dropped
                # connection or an error frame.
                LOG.error("sess=%d feeder fabric failed; request "
                          "re-parsed inline: %s", self.sid, e)
        if table is None:
            coalescer = getattr(self.server, "coalescer", None)
            if (
                coalescer is not None and blob_shape
                and count <= coalescer.max_lines
            ):
                # Continuous batching (docs/SERVICE.md): the payload
                # joins the parser key's shared submission queue and
                # comes back as this request's row window of a
                # coalesced device batch — byte-identical to the solo
                # parse below.  Oversize payloads (and the feeder path
                # above) keep their own dispatch; CR-carrying and
                # trailing-newline payloads need the exact-list
                # semantics of the split path.
                result = coalescer.parse(
                    state["parser_key"], parser, bytes(blob), count,
                    deadline_s=self.server.limits.request_deadline_s,
                    max_wait_s=state.get("coalesce_wait_s"),
                    trace_ctx=state.get("request_ctx"),
                )
            elif blob_shape:
                # (an empty blob is one empty LINE per the
                # protocol, which blob framing would drop —
                # split path below)
                # Common case: the payload IS the framer's input
                # shape (no trailing newline, no carriage
                # returns), so the blob ingest path applies — no
                # Python line list.  emit_views=False: the wire
                # ships copy-mode Arrow, so device view rows
                # would be wasted kernel + D2H.
                result = parser.parse_blob(blob, emit_views=False)
            else:
                result = parser.parse_batch(
                    blob.split(b"\n") if count else [],
                    emit_views=False,
                )
            # Copy mode for the wire: IPC does not dedupe shared
            # buffers, so string_view columns would each ship a
            # full copy of the batch buffer.
            table = result.to_arrow(include_validity=True,
                                    strings="copy")
            oracle_rows = result.oracle_rows
            bad_lines = result.bad_lines
        from .tpu.arrow_bridge import table_to_ipc_bytes

        return table_to_ipc_bytes(table), count, oracle_rows, bad_lines


def _feeder_parse(parser, blob: bytes, count: int, workers: int):
    """Parse one LINES blob through the sharded feeder fabric
    (docs/FEEDER.md): the payload splits into ``workers`` byte-range
    shards framed by feeder THREADS (a serving process must not fork,
    so the in-process ``inline`` hand-off applies — the shared-memory
    ring transport is for process pools), the parser consumes the
    encoded stream via ``parse_batch_stream`` (which also stages each
    next batch's H2D upload while the current one computes — the
    double-buffered device edge), and the per-batch tables concatenate
    back — in corpus order — into the single combined record batch the
    protocol promises.  Returns ``(table, oracle_rows, bad_lines)``."""
    import pyarrow as pa

    from .feeder import FeederPool, default_feeder_workers

    # The key is client-supplied: clamp to the host's own worker ceiling
    # so one CONFIG frame cannot spawn an arbitrary thread count.
    workers = max(2, min(workers, default_feeder_workers()))
    tables = []
    oracle_rows = 0
    bad_lines = 0
    with FeederPool(
        [blob],
        workers=workers,
        shard_bytes=max(1, -(-len(blob) // workers)),
        batch_lines=max(1024, -(-count // workers)),
        use_processes=False,
        # A per-request framing pool's full queue is its healthy steady
        # state, not fabric overload: it must not feed the process-wide
        # admission signal and shed every concurrent request.
        backpressure_signal=False,
    ) as pool:
        for result in pool.feed(parser, emit_views=False):
            tables.append(
                result.to_arrow(include_validity=True, strings="copy")
            )
            oracle_rows += result.oracle_rows
            bad_lines += result.bad_lines
    return pa.concat_tables(tables).combine_chunks(), oracle_rows, bad_lines


class _MetricsHandler(BaseHTTPRequestHandler):
    """GET /metrics -> Prometheus text exposition of the process registry;
    GET /tracez -> recent completed trace spans (JSON);
    GET /flightz -> the crash-safe flight recorder's event ring (JSON);
    GET /healthz -> liveness (200 while the process serves HTTP at all);
    GET /readyz -> readiness (200 ready, 503 once draining — the flip
    orchestrators key traffic removal on, docs/SERVICE.md)."""

    def do_GET(self) -> None:  # noqa: N802 — BaseHTTPRequestHandler contract
        path = self.path.split("?", 1)[0].rstrip("/") or "/metrics"
        if path == "/metrics":
            body = metrics().prometheus_text().encode("utf-8")
            self._respond(200, body,
                          "text/plain; version=0.0.4; charset=utf-8")
            return
        if path == "/tracez":
            # Recent completed spans (docs/OBSERVABILITY.md "Tracing").
            body = json.dumps(tracez_payload(),
                              sort_keys=True).encode("utf-8")
            self._respond(200, body, "application/json")
            return
        if path == "/flightz":
            # The flight recorder's live ring (docs/OBSERVABILITY.md
            # "Flight recorder") — same payload a crash dump writes.
            body = json.dumps(flightz_payload(),
                              sort_keys=True).encode("utf-8")
            self._respond(200, body, "application/json")
            return
        if path in ("/healthz", "/readyz"):
            state_fn = getattr(self.server, "state_fn", None)
            state = dict(state_fn()) if state_fn is not None else {}
            draining = bool(state.pop("draining", False))
            if path == "/healthz":
                status, code = "ok", 200
            elif draining:
                status, code = "draining", 503
            else:
                status, code = "ready", 200
            body = json.dumps(
                {"status": status, **state}, sort_keys=True
            ).encode("utf-8")
            self._respond(code, body, "application/json")
            return
        self.send_error(404)

    def _respond(self, code: int, body: bytes, content_type: str) -> None:
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, fmt: str, *args: Any) -> None:  # silence stderr
        LOG.debug("metrics http: " + fmt, *args)


class MetricsEndpoint:
    """Standalone /metrics + /healthz + /readyz HTTP endpoint.  Owned by
    :class:`ParseService` when ``metrics_port`` is given (which supplies
    ``state_fn`` so readiness tracks the drain state); usable on its own
    for non-sidecar processes (always ready)."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 state_fn: Optional[Callable[[], Dict[str, Any]]] = None):
        self._server = ThreadingHTTPServer((host, port), _MetricsHandler)
        self._server.daemon_threads = True
        self._server.state_fn = state_fn  # type: ignore[attr-defined]
        self._thread: Optional[threading.Thread] = None

    @property
    def port(self) -> int:
        return self._server.server_address[1]

    def start(self) -> "MetricsEndpoint":
        self._thread = threading.Thread(
            target=self._server.serve_forever,
            name="logparser-tpu-metrics", daemon=True,
        )
        self._thread.start()
        return self

    def shutdown(self) -> None:
        # Like ParseService.shutdown: BaseServer.shutdown() waits on an
        # event only a running serve_forever loop sets — never call it
        # for an endpoint that was constructed but not started.
        if self._thread is not None:
            self._server.shutdown()
        self._server.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5)
            if self._thread.is_alive():
                note_teardown(
                    LOG, "service_teardown_errors_total", "metrics_join",
                    "metrics endpoint thread outlived its 5 s join",
                )


class _StatsLogger:
    """Daemon thread logging a one-line telemetry summary every
    ``interval`` seconds: request/line counters, per-stage p99s, and
    suppressed-warning counts (the end-of-run summary CappedLogger/
    log_warning_once promise)."""

    def __init__(self, interval: float):
        self.interval = interval
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._run, name="logparser-tpu-stats", daemon=True
        )
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.wait(self.interval):
            self.log_once()

    @staticmethod
    def log_once() -> None:
        reg = metrics()
        snap = reg.snapshot()
        summary = {
            "counters": {
                k: v for k, v in snap["counters"].items()
                if not k.startswith("stage_items_total")
            },
            "stage_p99_ms": {
                stage: d["p99_ms"]
                for stage, d in reg.stage_breakdown().items()
            },
        }
        suppressed = suppressed_warning_counts()
        if suppressed:
            summary["suppressed_warnings"] = suppressed
        LOG.info("service stats: %s", json.dumps(summary, sort_keys=True))

    def shutdown(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            if self._thread.is_alive():
                note_teardown(
                    LOG, "service_teardown_errors_total", "stats_join",
                    "stats logger thread outlived its 5 s join",
                )


class ParseService:
    """The sidecar: `with ParseService() as svc: ... svc.port ...` or call
    `serve_forever()` from a main program.

    ``metrics_port`` (int, optional): also serve the process metrics
    registry as a Prometheus ``/metrics`` HTTP endpoint — plus
    ``/healthz`` and ``/readyz`` — on that port (0 = ephemeral; read
    back via :attr:`metrics_port`).
    ``stats_interval`` (seconds, optional): log a one-line telemetry
    summary periodically at INFO level.

    Every serving limit (admission budgets, deadlines, payload caps,
    drain budget — docs/SERVICE.md) is a keyword knob mirroring a
    :class:`ServiceLimits` field."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 metrics_port: Optional[int] = None,
                 stats_interval: Optional[float] = None,
                 *,
                 max_sessions: int = 64,
                 max_inflight: int = 0,
                 frame_timeout_s: Optional[float] = 30.0,
                 idle_timeout_s: Optional[float] = 600.0,
                 request_deadline_s: Optional[float] = None,
                 max_frame_bytes: int = _MAX_FRAME,
                 max_config_bytes: int = 1 << 20,
                 max_lines_bytes: int = 0,
                 busy_retry_after_s: float = 0.25,
                 backpressure_threshold: float = 0.95,
                 drain_deadline_s: float = 10.0,
                 coalesce: Optional[bool] = None,
                 coalesce_window_ms: Optional[float] = None,
                 coalesce_max_lines: Optional[int] = None,
                 coalesce_queue_depth: Optional[int] = None):
        def _window(v: Optional[float]) -> Optional[float]:
            # <= 0 means "disabled", like request_deadline_s/max_inflight:
            # settimeout(0.0) would mean NON-BLOCKING and instantly kill
            # every session — never let that spelling through.
            return float(v) if v and v > 0 else None

        # Load pyarrow on THIS thread, before any session thread does:
        # pyarrow 25's default (mimalloc) pool segfaults later
        # allocations when pyarrow was first imported on a thread that
        # has since exited — every sidecar crashed on its second request.
        try:
            import pyarrow  # noqa: F401
        except ImportError:  # optional extra: row tables need it, not
            pass             # the service itself
        defaults = ServiceLimits()
        if coalesce is None:
            # Env kill switch (docs/SERVICE.md): continuous batching is
            # ON by default — it is byte-transparent on the wire — but
            # an operator can hard-disable it without a code change.
            coalesce = os.environ.get(
                "LOGPARSER_TPU_COALESCE", "1"
            ).strip().lower() not in ("0", "false", "no")
        self.limits = ServiceLimits(
            max_sessions=int(max_sessions),
            max_inflight=int(max_inflight),
            frame_timeout_s=_window(frame_timeout_s),
            idle_timeout_s=_window(idle_timeout_s),
            request_deadline_s=_window(request_deadline_s),
            max_frame_bytes=int(max_frame_bytes),
            max_config_bytes=int(max_config_bytes),
            max_lines_bytes=int(max_lines_bytes),
            busy_retry_after_s=float(busy_retry_after_s),
            backpressure_threshold=float(backpressure_threshold),
            drain_deadline_s=float(drain_deadline_s),
            coalesce=bool(coalesce),
            coalesce_window_ms=float(
                defaults.coalesce_window_ms if coalesce_window_ms is None
                else coalesce_window_ms
            ),
            coalesce_max_lines=int(
                defaults.coalesce_max_lines if coalesce_max_lines is None
                else coalesce_max_lines
            ),
            coalesce_queue_depth=int(
                defaults.coalesce_queue_depth if coalesce_queue_depth is None
                else coalesce_queue_depth
            ),
        )
        self._server = _ServiceServer((host, port), _SessionHandler,
                                      self.limits)
        if self.limits.coalesce:
            from .service_batching import BatchCoalescer

            self._server.coalescer = BatchCoalescer(
                window_s=self.limits.coalesce_window_ms / 1000.0,
                max_lines=self.limits.coalesce_max_lines,
                queue_depth=self.limits.coalesce_queue_depth,
                live_sessions_fn=self._server.sessions_on_key,
            )
        self._thread: Optional[threading.Thread] = None
        self._serving = False
        self._closed = False
        self._close_lock = threading.Lock()
        self._teardown_done = threading.Event()
        self._metrics: Optional[MetricsEndpoint] = None
        if metrics_port is not None:
            self._metrics = MetricsEndpoint(host, metrics_port,
                                            state_fn=self._health_state)
        self._stats_logger: Optional[_StatsLogger] = None
        if stats_interval:
            self._stats_logger = _StatsLogger(float(stats_interval))

    @property
    def host(self) -> str:
        return self._server.server_address[0]

    @property
    def port(self) -> int:
        return self._server.server_address[1]

    @property
    def metrics_port(self) -> Optional[int]:
        """The bound /metrics HTTP port (None when not enabled)."""
        return self._metrics.port if self._metrics is not None else None

    @property
    def draining(self) -> bool:
        return self._server.draining

    def _health_state(self) -> Dict[str, Any]:
        # Admitted sessions only — matching the service_sessions_active
        # gauge and the max_sessions budget reported beside it.  Handlers
        # mid-BUSY-shed linger are refused connections, not sessions.
        with self._server.sessions_lock:
            active = sum(
                1 for h in self._server.sessions if h.admitted
            )
        return {
            "draining": self._server.draining,
            "sessions_active": active,
            "max_sessions": self.limits.max_sessions,
        }

    def _start_sidecars(self) -> None:
        log_version_banner_once(LOG)
        if self._metrics is not None:
            self._metrics.start()
            LOG.info("serving /metrics + /healthz + /readyz on port %d",
                     self._metrics.port)
        if self._stats_logger is not None:
            self._stats_logger.start()

    def start(self) -> "ParseService":
        self._serving = True
        self._start_sidecars()
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="logparser-tpu-service",
            daemon=True,
        )
        self._thread.start()
        return self

    def serve_forever(self) -> None:
        self._serving = True
        self._start_sidecars()
        self._server.serve_forever()

    # -- teardown -------------------------------------------------------

    def _session_snapshot(self) -> List[Tuple[Any, threading.Thread]]:
        with self._server.sessions_lock:
            return list(self._server.sessions.items())

    def _await_sessions(self, deadline_s: float) -> bool:
        """Wait (poll) until every ADMITTED session ends; False when the
        drain deadline expired with admitted sessions still live.  Only
        admitted sessions gate the drain: while it runs the listener is
        still up shedding BUSY{draining}, and those short-lived shed
        handlers must not be able to hold the drain open forever."""
        def admitted_live() -> bool:
            with self._server.sessions_lock:
                return any(h.admitted for h in self._server.sessions)

        end = time.monotonic() + deadline_s
        while time.monotonic() < end:
            if not admitted_live():
                return True
            time.sleep(0.02)
        return not admitted_live()

    def _force_close_sessions(self, site: str, count: bool) -> None:
        for handler, _thread in self._session_snapshot():
            # Only ADMITTED sessions count as drain-deadline leaks — a
            # transient shed handler mid-linger is a refused connection,
            # not work that outlived the drain (its socket still gets
            # closed below).
            if count and handler.admitted:
                note_teardown(
                    LOG, "service_teardown_errors_total", site,
                    f"session {handler.sid} outlived the drain deadline; "
                    "force-closing its socket",
                )
            for closer in (
                lambda: handler.request.shutdown(socket.SHUT_RDWR),
                handler.request.close,
            ):
                try:
                    closer()
                except OSError:
                    pass

    def _join_sessions(self, budget_s: float = 5.0) -> None:
        # ONE shared budget across all leaked sessions: per-thread
        # timeouts would stack (64 wedged sessions x 2 s each) far past
        # any drain deadline, stalling every concurrent shutdown() waiter.
        end = time.monotonic() + budget_s
        for _handler, thread in self._session_snapshot():
            thread.join(timeout=max(0.0, end - time.monotonic()))
            if thread.is_alive():
                note_teardown(
                    LOG, "service_teardown_errors_total", "session_join",
                    f"session thread {thread.name} outlived its join after "
                    "socket close",
                )

    def shutdown(self, drain: bool = False,
                 drain_deadline_s: Optional[float] = None) -> None:
        """Stop the service.  ``drain=False``: immediate — stop accepting
        and force-close any live session (clients mid-request see EOF).
        ``drain=True``: graceful — flip ``/readyz`` to draining FIRST
        (so orchestrators stop routing before the listener goes away),
        stop accepting, let admitted sessions finish under the drain
        deadline, then escalate force-close -> join.  Idempotent — and a
        DUPLICATE call BLOCKS until the first finishes: the CLI's
        SIGTERM drain runs on a daemon thread, and main()'s
        finally-shutdown must not let the interpreter exit (killing
        every daemon session thread mid-request) while that drain is
        still completing admitted work."""
        with self._close_lock:
            already = self._closed
            self._closed = True
        if already:
            # No timeout: every teardown phase is itself bounded (drain
            # deadline, per-join escalation windows), so the first call
            # always terminates — while a guessed timeout here could
            # elapse before a long drain finishes and let the
            # interpreter exit, killing daemon session threads
            # mid-request.
            self._teardown_done.wait()
            return
        try:
            self._shutdown_impl(drain, drain_deadline_s)
        finally:
            self._teardown_done.set()

    def _shutdown_impl(self, drain: bool,
                       drain_deadline_s: Optional[float]) -> None:
        if drain:
            # Readiness flips FIRST, and the listener stays up for the
            # whole drain window shedding BUSY{"reason":"draining"}: a
            # balancer needs real time to observe the 503 and stop
            # routing, and every connection that races in during that
            # propagation window must get the structured shed frame —
            # closing the listener immediately would turn them into
            # ECONNREFUSED, the unstructured refusal drain exists to
            # prevent.
            self._server.draining = True
            metrics().gauge_set("service_draining", 1)
            budget = (drain_deadline_s if drain_deadline_s is not None
                      else self.limits.drain_deadline_s)
            drained = self._await_sessions(budget)
        # BaseServer.shutdown() waits on an event only a running
        # serve_forever loop sets; calling it before start() blocks forever.
        if self._serving:
            self._server.shutdown()
        self._server.server_close()
        if drain:
            if not drained:
                self._force_close_sessions("drain_deadline", count=True)
        else:
            self._force_close_sessions("shutdown", count=False)
        self._join_sessions()
        # After the session join: queued coalescer entries belong to
        # admitted sessions, so by now the lanes are empty on a graceful
        # drain — shutdown() only has live work to fail when sessions
        # were force-closed past the drain deadline.
        if self._server.coalescer is not None:
            self._server.coalescer.shutdown()
        if self._server.prewarmer is not None:
            self._server.prewarmer.shutdown()
        if drain:
            # The drain is over (documented: "1 WHILE a graceful drain is
            # in progress") — a later service in this process must not
            # inherit a stuck-at-1 gauge.
            metrics().gauge_set("service_draining", 0)
        if self._metrics is not None:
            self._metrics.shutdown()
        if self._stats_logger is not None:
            self._stats_logger.shutdown()
        if self._thread is not None:
            self._thread.join(timeout=5)
            if self._thread.is_alive():
                note_teardown(
                    LOG, "service_teardown_errors_total", "server_join",
                    "service accept-loop thread outlived its 5 s join",
                )

    def __enter__(self) -> "ParseService":
        return self.start()

    def __exit__(self, *exc: Any) -> None:
        self.shutdown()


# ---------------------------------------------------------------------------
# client
# ---------------------------------------------------------------------------


class ParseServiceClient:
    """Python reference client (the wire protocol is the interop surface;
    a JVM/Go client implements the same five-line framing).

    Retry behavior (round 12, all OFF by default so the default client
    stays byte-exact v1):

    - ``connect_retries``: reconnect attempts on a refused/failed
      connect, with exponential backoff + full jitter.
    - ``busy_retries``: :meth:`parse` retries after a structured ``BUSY``
      response, honoring the server's retry-after hint as the backoff
      floor.  Session-level sheds (reason ``sessions``/``draining``/
      ``sidecar_failover``) reconnect first — the server closed that
      connection by contract; behind a front tier the reconnect is what
      lands the session on a LIVE sidecar (docs/SERVICE.md "Fleet").
    - ``max_redirect_retries``: per-:meth:`parse` bound on those
      connection-level sheds specifically — a DYING fleet (every
      reconnect shed again) fails fast with
      :class:`ServiceUnavailableError` instead of burning the whole
      (possibly large) ``busy_retries`` budget on reconnect loops.
    - ``tenant``: optional tenant identity carried in the CONFIG frame
      (the front tier's fairness quotas key on it; a plain sidecar
      ignores it).
    - ``timeout``: socket timeout for connect/send/recv (None = block).
    """

    def __init__(
        self,
        host: str,
        port: int,
        log_format: str,
        fields: Sequence[str],
        timestamp_format: Optional[str] = None,
        stats: bool = False,
        feeder_workers: Optional[int] = None,
        connect_retries: int = 0,
        busy_retries: int = 0,
        max_redirect_retries: int = 8,
        backoff_base_s: float = 0.05,
        backoff_max_s: float = 2.0,
        timeout: Optional[float] = None,
        tenant: Optional[str] = None,
        aggregate: Optional[Any] = None,
        traceparent: Optional[str] = None,
    ):
        self._addr = (host, port)
        self._stats = bool(stats)
        self._connect_retries = int(connect_retries)
        self._busy_retries = int(busy_retries)
        self._max_redirect_retries = int(max_redirect_retries)
        self._backoff_base_s = float(backoff_base_s)
        self._backoff_max_s = float(backoff_max_s)
        self._timeout = timeout
        #: Decoded STATS frame of the most recent parse() (stats sessions).
        self.last_stats: Optional[Dict[str, Any]] = None
        #: BUSY responses absorbed by retries (diagnosis/loadgen counter).
        self.busy_seen = 0
        config = {
            "log_format": log_format,
            "fields": list(fields),
            "timestamp_format": timestamp_format,
        }
        if feeder_workers:
            # Optional sharded-feeder framing for big batches
            # (docs/FEEDER.md); a v1 server ignores unknown keys.
            config["feeder_workers"] = int(feeder_workers)
        if tenant:
            # Tenant identity for the front tier's fairness quotas
            # (docs/SERVICE.md "Fleet"); a plain sidecar ignores it —
            # it is not part of the parser cache key.
            config["tenant"] = str(tenant)
        if stats:
            # Only stats sessions carry the key: a v1 server ignores it,
            # but omitting it keeps this client byte-exact v1 by default.
            config["stats"] = True
        if traceparent:
            # Distributed tracing head (PROTOCOL.md "traceparent"): the
            # session's requests join this trace.  A v1 server ignores
            # it; omitted, the CONFIG stays byte-exact v1.
            config["traceparent"] = str(traceparent)
        self._agg_spec = None
        if aggregate is not None:
            # Analytics pushdown (PROTOCOL.md "aggregate"): the session's
            # responses become aggregate frames; :meth:`parse` returns an
            # :class:`~logparser_tpu.analytics.AggregateState` instead of
            # a row table.  Parsed eagerly so a malformed spec fails at
            # construction, not as a server error frame.
            from .analytics.spec import parse_aggregate_config

            self._agg_spec = parse_aggregate_config(aggregate)
            config["aggregate"] = [op.as_dict()
                                   for op in self._agg_spec.ops]
        self._config_payload = json.dumps(config).encode("utf-8")
        self._sock = self._connect()

    # -- connection management ------------------------------------------

    def _connect(self) -> socket.socket:
        last: Optional[BaseException] = None
        for attempt in range(self._connect_retries + 1):
            sock: Optional[socket.socket] = None
            try:
                sock = socket.create_connection(
                    self._addr, timeout=self._timeout
                )
                sock.settimeout(self._timeout)
                write_frame(sock, self._config_payload)
                return sock
            except OSError as e:
                # A connect that made it to a socket but failed the
                # CONFIG write must not leak its fd across retries.
                if sock is not None:
                    sock.close()
                last = e
                if attempt >= self._connect_retries:
                    break
                self._backoff_sleep(attempt)
        assert last is not None
        raise last

    def _reconnect(self) -> None:
        try:
            self._sock.close()
        except OSError:
            pass
        self._sock = self._connect()

    def _backoff_sleep(self, attempt: int, floor_s: float = 0.0) -> None:
        """Exponential backoff with full jitter (and the server's
        retry-after hint as the floor): synchronized client herds must
        decorrelate, or every retry wave lands as one thundering herd."""
        ceiling = min(self._backoff_max_s,
                      self._backoff_base_s * (2 ** attempt))
        delay = random.uniform(0.0, ceiling)
        time.sleep(max(floor_s, delay))

    # -- requests --------------------------------------------------------

    def parse(self, lines: Sequence[Union[str, bytes]]):
        """Ship one batch; returns a pyarrow.Table.  On a stats session
        the trailing STATS frame is decoded into :attr:`last_stats`.
        With ``busy_retries`` set, structured BUSY responses are
        retried with backoff instead of raised."""
        encoded = [
            line.encode("utf-8") if isinstance(line, str) else line
            for line in lines
        ]
        for line in encoded:
            if b"\n" in line:
                raise ValueError(
                    "loglines cannot contain '\\n'; split them before parse()"
                )
        payload = struct.pack(">I", len(encoded)) + b"\n".join(encoded)
        redirects = 0
        for attempt in range(self._busy_retries + 1):
            try:
                return self._roundtrip(payload)
            except ServiceBusyError as e:
                self.busy_seen += 1
                if attempt >= self._busy_retries:
                    raise
                if e.reason in RECONNECT_BUSY_REASONS:
                    # Connection-level shed: the server closed this
                    # socket by contract — reconnect (after honoring
                    # the retry hint) before retrying.  A separate,
                    # tighter budget bounds these: a fleet where EVERY
                    # reconnect sheds again (rolling restart gone bad,
                    # cascading sidecar failures) must fail fast, not
                    # spin through busy_retries reconnect cycles.
                    redirects += 1
                    if redirects > self._max_redirect_retries:
                        raise ServiceUnavailableError(
                            f"{redirects} consecutive connection-level "
                            f"sheds (last: {e.reason!r}) — service "
                            "unavailable"
                        ) from e
                    self._backoff_sleep(attempt, floor_s=e.retry_after_s)
                    self._reconnect()
                else:
                    self._backoff_sleep(attempt, floor_s=e.retry_after_s)
        raise AssertionError("unreachable")  # pragma: no cover

    def _roundtrip(self, payload: bytes):
        import pyarrow as pa

        write_frame(self._sock, payload)
        response = read_frame(self._sock)
        if response is None:
            raise ServiceClosedError("server closed the connection")
        with pa.ipc.open_stream(pa.BufferReader(response)) as reader:
            table = reader.read_all()
        if self._agg_spec is not None:
            from .analytics.state import AggregateState

            table = AggregateState.from_arrow(table, self._agg_spec)
        if self._stats:
            stats_frame = read_frame(self._sock)
            if stats_frame is None:
                raise ServiceClosedError(
                    "server closed the connection before the STATS frame"
                )
            self.last_stats = json.loads(stats_frame)
        return table

    def close(self) -> None:
        try:
            self._sock.sendall(struct.pack(">I", 0))
        except OSError:
            pass
        self._sock.close()

    def __enter__(self) -> "ParseServiceClient":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()


# ---------------------------------------------------------------------------
# CLI: run the sidecar standalone with telemetry wired up
# ---------------------------------------------------------------------------


def main(argv: Optional[Sequence[str]] = None) -> int:
    """``python -m logparser_tpu.service``: serve the sidecar protocol,
    optionally with a Prometheus /metrics (+ /healthz, /readyz) endpoint
    and periodic stats logging.  SIGTERM triggers a graceful drain
    (docs/SERVICE.md).  Env fallbacks: LOGPARSER_TPU_METRICS_PORT,
    LOGPARSER_TPU_STATS_INTERVAL, LOGPARSER_TPU_MAX_SESSIONS,
    LOGPARSER_TPU_REQUEST_DEADLINE, LOGPARSER_TPU_DRAIN_DEADLINE."""
    import argparse
    import os
    import signal

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=None,
                    help="listen port (default 8123; --sidecar defaults "
                         "to 0 = ephemeral)")
    ap.add_argument(
        "--sidecar", action="store_true",
        help="supervised-sidecar run mode (docs/SERVICE.md \"Fleet\"): "
             "bind ephemeral service + metrics ports and print one "
             "machine-readable SIDECAR_READY JSON line on stdout so a "
             "front tier (logparser_tpu/front.py) can adopt, health-"
             "probe, and route to this process",
    )
    ap.add_argument(
        "--metrics-port", type=int, default=None,
        help="Prometheus /metrics HTTP port (0 = ephemeral; omit to "
             "disable; env fallback LOGPARSER_TPU_METRICS_PORT — "
             "ignored under --sidecar, where every fleet member must "
             "bind its own ephemeral port)",
    )
    ap.add_argument(
        "--stats-interval", type=float,
        default=_env_float("LOGPARSER_TPU_STATS_INTERVAL"),
        help="seconds between one-line telemetry summaries (omit to disable)",
    )
    ap.add_argument(
        "--max-sessions", type=int,
        default=_env_int("LOGPARSER_TPU_MAX_SESSIONS") or 64,
        help="admitted-session budget; over it, connections shed BUSY",
    )
    ap.add_argument(
        "--max-inflight", type=int, default=0,
        help="concurrent parsing requests (0 = same as --max-sessions)",
    )
    ap.add_argument(
        "--request-deadline", type=float,
        default=_env_float("LOGPARSER_TPU_REQUEST_DEADLINE"),
        help="per-request parse deadline in seconds (omit to disable)",
    )
    ap.add_argument(
        "--idle-timeout", type=float, default=600.0,
        help="per-session idle window between frames, seconds (0 disables)",
    )
    ap.add_argument(
        "--frame-timeout", type=float, default=30.0,
        help="mid-frame transfer stall window, seconds (0 disables)",
    )
    ap.add_argument(
        "--drain-deadline", type=float,
        default=_env_float("LOGPARSER_TPU_DRAIN_DEADLINE") or 10.0,
        help="graceful-drain budget before force-close escalation, seconds",
    )
    ap.add_argument(
        "--no-coalesce", action="store_true",
        help="disable cross-session continuous batching (also "
             "LOGPARSER_TPU_COALESCE=0)",
    )
    ap.add_argument(
        "--coalesce-window-ms", type=float,
        default=_env_float("LOGPARSER_TPU_COALESCE_WINDOW_MS"),
        help="how long a forming shared batch waits for more sessions "
             "(default 2 ms; only paid when >1 session is live)",
    )
    ap.add_argument(
        "--coalesce-max-lines", type=int,
        default=_env_int("LOGPARSER_TPU_COALESCE_MAX_LINES"),
        help="shared device batch geometry ceiling in lines (default 4096)",
    )
    ap.add_argument(
        "--coalesce-queue-depth", type=int,
        default=_env_int("LOGPARSER_TPU_COALESCE_QUEUE_DEPTH"),
        help="bounded coalesce submission queue; full = structured "
             "BUSY{coalesce_queue} shed (default 256)",
    )
    ap.add_argument("--log-level", default=os.environ.get(
        "LOGPARSER_TPU_LOG_LEVEL", "INFO"))
    args = ap.parse_args(argv)

    logging.basicConfig(
        level=getattr(logging, str(args.log_level).upper(), logging.INFO),
        format="%(asctime)s %(levelname)s %(name)s: %(message)s",
    )
    port = args.port if args.port is not None else (
        0 if args.sidecar else 8123)
    metrics_port = args.metrics_port
    if args.sidecar:
        # A sidecar without /readyz cannot be health-probed or drained
        # by the front tier: the metrics endpoint is mandatory — and
        # the env fallback is deliberately NOT consulted here (an
        # exported LOGPARSER_TPU_METRICS_PORT is inherited by every
        # spawned fleet member; a fixed port would EADDRINUSE all but
        # the first).  An explicit --metrics-port flag still wins.
        if metrics_port is None:
            metrics_port = 0
    elif metrics_port is None:
        metrics_port = _env_int("LOGPARSER_TPU_METRICS_PORT")
    svc = ParseService(
        args.host, port,
        metrics_port=metrics_port,
        stats_interval=args.stats_interval,
        max_sessions=args.max_sessions,
        max_inflight=args.max_inflight,
        request_deadline_s=args.request_deadline,
        idle_timeout_s=args.idle_timeout,
        frame_timeout_s=args.frame_timeout,
        drain_deadline_s=args.drain_deadline,
        coalesce=False if args.no_coalesce else None,
        coalesce_window_ms=args.coalesce_window_ms,
        coalesce_max_lines=args.coalesce_max_lines,
        coalesce_queue_depth=args.coalesce_queue_depth,
    )

    def _on_sigterm(signum, frame):  # noqa: ARG001 — signal contract
        # Flight dump FIRST: the drain may be escalated/killed, and the
        # last 60 s of silently-absorbed trouble must survive the
        # process (docs/OBSERVABILITY.md "Flight recorder").
        from .tracing import dump_flight

        flight_event("sigterm_drain",
                     drain_deadline_s=args.drain_deadline)
        dump_flight("sigterm")
        LOG.info("SIGTERM: draining (deadline %.1fs)", args.drain_deadline)
        threading.Thread(
            target=lambda: svc.shutdown(drain=True),
            name="logparser-tpu-drain", daemon=True,
        ).start()

    signal.signal(signal.SIGTERM, _on_sigterm)
    # SIGUSR2 -> non-fatal flight dump; fatal faults dump via excepthook.
    from .tracing import (
        arm_flight_signals,
        install_flight_excepthook,
        sweep_flight_dumps,
    )

    sweep_flight_dumps()
    arm_flight_signals()
    install_flight_excepthook()
    LOG.info("parse service listening on %s:%d", svc.host, svc.port)
    if args.sidecar:
        # The adoption handshake (docs/SERVICE.md "Fleet"): exactly one
        # line, flushed, so the spawning front tier can read the bound
        # ephemeral ports without racing the listen() — both sockets
        # are already bound by construction above.
        print("SIDECAR_READY " + json.dumps({
            "port": svc.port,
            "metrics_port": svc.metrics_port,
            "pid": os.getpid(),
        }, sort_keys=True), flush=True)
    try:
        svc.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        svc.shutdown()
    return 0


def _env_int(name: str) -> Optional[int]:
    import os

    raw = os.environ.get(name, "").strip()
    return int(raw) if raw else None


def _env_float(name: str) -> Optional[float]:
    import os

    raw = os.environ.get(name, "").strip()
    return float(raw) if raw else None


if __name__ == "__main__":  # pragma: no cover — CLI
    raise SystemExit(main())
