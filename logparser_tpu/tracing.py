"""Distributed request tracing + crash-safe flight recorder.

The second observability spine beside the metrics registry
(docs/OBSERVABILITY.md "Tracing" / "Flight recorder"):

- **TraceContext** — W3C-traceparent-compatible identity
  (``00-<32hex trace_id>-<16hex span_id>-<2hex flags>``) propagated on
  the wire via the CONFIG ``traceparent`` key (docs/PROTOCOL.md) and
  across process boundaries via the ``LOGPARSER_TPU_TRACEPARENT`` env.
- **Head-based sampling** — ``LOGPARSER_TPU_TRACE_SAMPLE`` (0..1,
  default 0 = off).  The sampling decision is made ONCE at the head of
  a trace (front session admit / job start / loadgen client) and rides
  the context; an unsampled process pays one cached float compare per
  span site and allocates nothing.
- **SpanBuffer** — bounded in-process ring of completed spans, exported
  as JSON at ``GET /tracez`` on the existing metrics endpoint, plus an
  optional JSON-lines span log (``LOGPARSER_TPU_TRACE_LOG``).
- **Flight recorder** — an always-on fixed-size ring of structured
  events fed by every site that recovers *silently* (device-fault
  absorption, feeder supervisor decisions, front failovers, service
  sheds), dumped to ``flight-<pid>.json`` on SIGTERM / SIGUSR2 / fatal
  fault and served at ``GET /flightz`` — the 60-second postmortem that
  survives the process.

Import discipline: this module imports :mod:`.observability` (stdlib
only); observability never imports tracing at module level — the stage
span sink is injected (:func:`observability.set_stage_span_sink`) and
only while a sampled batch scope is active, so the disabled hot path
keeps its exact pre-tracing instruction stream.
"""

from __future__ import annotations

import contextlib
import json
import os
import re
import secrets
import sys
import tempfile
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Dict, Iterator, List, Optional, Sequence

from . import observability
from .observability import metrics

__all__ = [
    "TraceContext",
    "Span",
    "parse_traceparent",
    "new_trace_context",
    "sample_rate",
    "set_sample_rate",
    "head_context",
    "root_span",
    "child_span",
    "batch_scope",
    "push_batch_span",
    "pop_batch_span",
    "span_buffer",
    "tracez_payload",
    "flight_event",
    "flightz_payload",
    "dump_flight",
    "flight_dump_path",
    "sweep_flight_dumps",
    "arm_flight_signals",
    "install_flight_excepthook",
    "reset_for_tests",
]

_TRACEPARENT_VERSION = "00"


# ---------------------------------------------------------------------------
# trace context (W3C traceparent)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class TraceContext:
    """One position in a trace: which trace, which span, sampled or not.

    Immutable; ``child()`` mints a fresh span identity inside the same
    trace with the same sampling decision (head-based: the flag never
    flips downstream)."""

    trace_id: str  # 32 lowercase hex chars, not all-zero
    span_id: str   # 16 lowercase hex chars, not all-zero
    sampled: bool

    def traceparent(self) -> str:
        flags = "01" if self.sampled else "00"
        return f"{_TRACEPARENT_VERSION}-{self.trace_id}-{self.span_id}-{flags}"

    def child(self) -> "TraceContext":
        return TraceContext(self.trace_id, _rand_hex(16), self.sampled)


def _rand_hex(n: int) -> str:
    return secrets.token_hex(n // 2)


def parse_traceparent(value: Any) -> Optional[TraceContext]:
    """Decode a ``traceparent`` header/CONFIG value; ``None`` on any
    malformation.  Invalid contexts are silently dropped (the W3C
    contract: a bad traceparent must not break the request)."""
    if not isinstance(value, str):
        return None
    parts = value.strip().lower().split("-")
    if len(parts) != 4:
        return None
    version, trace_id, span_id, flags = parts
    if version != _TRACEPARENT_VERSION:
        return None
    if len(trace_id) != 32 or len(span_id) != 16 or len(flags) != 2:
        return None
    try:
        int(trace_id, 16), int(span_id, 16), int(flags, 16)
    except ValueError:
        return None
    if set(trace_id) == {"0"} or set(span_id) == {"0"}:
        return None
    return TraceContext(trace_id, span_id, bool(int(flags, 16) & 0x01))


def new_trace_context(sampled: bool = True) -> TraceContext:
    return TraceContext(_rand_hex(32), _rand_hex(16), sampled)


# ---------------------------------------------------------------------------
# head-based sampling
# ---------------------------------------------------------------------------


def _env_rate() -> float:
    raw = os.environ.get("LOGPARSER_TPU_TRACE_SAMPLE", "").strip()
    if not raw:
        return 0.0
    try:
        return min(1.0, max(0.0, float(raw)))
    except ValueError:
        return 0.0


_SAMPLE_RATE = _env_rate()


def sample_rate() -> float:
    return _SAMPLE_RATE


def set_sample_rate(rate: float) -> None:
    """Programmatic override (tests); env is read once at
    import so sidecars inherit the smoke process's decision."""
    global _SAMPLE_RATE
    _SAMPLE_RATE = min(1.0, max(0.0, float(rate)))


def head_context(traceparent: Any = None) -> Optional[TraceContext]:
    """The one sampling decision point.  An incoming context is
    respected verbatim (sampled or not — the head already decided);
    with none, coin-flip at :func:`sample_rate`.  Returns ``None`` on
    a miss so every downstream span site is a single ``is None``."""
    ctx = parse_traceparent(traceparent)
    if ctx is not None:
        return ctx
    rate = _SAMPLE_RATE
    if rate <= 0.0:
        return None
    if rate < 1.0 and secrets.randbelow(1 << 30) >= int(rate * (1 << 30)):
        return None
    return new_trace_context(sampled=True)


# ---------------------------------------------------------------------------
# spans + bounded buffer
# ---------------------------------------------------------------------------


def _env_int(name: str, default: int) -> int:
    raw = os.environ.get(name, "").strip()
    try:
        return int(raw) if raw else default
    except ValueError:
        return default


class SpanBuffer:
    """Bounded thread-safe ring of completed spans (dicts).  Overflow
    drops the OLDEST span (recent history is the debugging surface) and
    counts ``trace_spans_dropped_total``."""

    def __init__(self, maxlen: int = 2048):
        self.maxlen = int(maxlen)
        self._spans: deque = deque(maxlen=self.maxlen)
        self._lock = threading.Lock()
        self.dropped = 0

    def record(self, span: Dict[str, Any]) -> None:
        with self._lock:
            if len(self._spans) == self.maxlen:
                self.dropped += 1
                metrics().increment("trace_spans_dropped_total")
            self._spans.append(span)

    def snapshot(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self.dropped = 0


_SPAN_BUFFER = SpanBuffer(_env_int("LOGPARSER_TPU_TRACE_BUFFER", 2048))


def span_buffer() -> SpanBuffer:
    return _SPAN_BUFFER


_SPAN_LOG_LOCK = threading.Lock()
_SPAN_LOG: Dict[str, Any] = {"path": None, "fh": None}


def _span_log_write(record: Dict[str, Any]) -> None:
    path = os.environ.get("LOGPARSER_TPU_TRACE_LOG", "").strip()
    if not path:
        return
    with _SPAN_LOG_LOCK:
        try:
            if _SPAN_LOG["path"] != path:
                if _SPAN_LOG["fh"] is not None:
                    _SPAN_LOG["fh"].close()
                _SPAN_LOG["fh"] = open(path, "a", encoding="utf-8")
                _SPAN_LOG["path"] = path
            _SPAN_LOG["fh"].write(json.dumps(record, sort_keys=True) + "\n")
            _SPAN_LOG["fh"].flush()
        except OSError:
            _SPAN_LOG["path"], _SPAN_LOG["fh"] = None, None


class Span:
    """A live span handle.  ``end()`` is idempotent and records the
    completed span into the process buffer (+ span log + metrics); an
    unsampled site never sees one of these (the factories return
    ``None`` instead, so the hot path is one branch)."""

    __slots__ = ("name", "context", "parent_span_id", "start_s",
                 "attrs", "links", "_ended")

    def __init__(self, name: str, context: TraceContext,
                 parent_span_id: Optional[str] = None,
                 attrs: Optional[Dict[str, Any]] = None,
                 links: Sequence[TraceContext] = ()):
        self.name = name
        self.context = context
        self.parent_span_id = parent_span_id
        self.start_s = time.time()
        self.attrs: Dict[str, Any] = dict(attrs) if attrs else {}
        self.links: List[TraceContext] = list(links)
        self._ended = False

    @property
    def traceparent(self) -> str:
        return self.context.traceparent()

    def add_link(self, ctx: Optional[TraceContext]) -> None:
        if ctx is not None:
            self.links.append(ctx)

    def end(self, **attrs: Any) -> None:
        if self._ended:
            return
        self._ended = True
        if attrs:
            self.attrs.update(attrs)
        end_s = time.time()
        record = {
            "name": self.name,
            "trace_id": self.context.trace_id,
            "span_id": self.context.span_id,
            "parent_span_id": self.parent_span_id,
            "start_s": self.start_s,
            "duration_ms": round((end_s - self.start_s) * 1000.0, 3),
            "attrs": self.attrs,
        }
        if self.links:
            record["links"] = [
                {"trace_id": c.trace_id, "span_id": c.span_id}
                for c in self.links
            ]
        _SPAN_BUFFER.record(record)
        metrics().increment("trace_spans_total", labels={"name": self.name})
        _span_log_write(record)


def root_span(name: str, traceparent: Any = None,
              attrs: Optional[Dict[str, Any]] = None) -> Optional[Span]:
    """Open a span at a trace head: continue an incoming context as its
    child, or head-sample a fresh trace.  ``None`` when unsampled."""
    ctx = head_context(traceparent)
    if ctx is None or not ctx.sampled:
        return None
    incoming = parse_traceparent(traceparent)
    if incoming is not None:
        return Span(name, incoming.child(),
                    parent_span_id=incoming.span_id, attrs=attrs)
    return Span(name, ctx, parent_span_id=None, attrs=attrs)


def child_span(name: str, parent: Optional[TraceContext],
               attrs: Optional[Dict[str, Any]] = None,
               links: Sequence[TraceContext] = ()) -> Optional[Span]:
    """Open a child span under ``parent``'s context; ``None`` when the
    parent is absent or unsampled (zero-cost pass-through)."""
    if parent is None or not parent.sampled:
        return None
    return Span(name, parent.child(),
                parent_span_id=parent.span_id, attrs=attrs, links=links)


# ---------------------------------------------------------------------------
# pipeline-stage child spans (the observe_stage sink)
# ---------------------------------------------------------------------------
#
# tpu/batch.py times its stages through observability.observe_stage; while
# a sampled batch scope is active the sink below turns each completed
# stage into a child span of the innermost batch span, so trace
# vocabulary == scrape vocabulary (PIPELINE_STAGES).  The sink is only
# installed while >=1 scope is live: an unsampled process never even
# loads this module from the hot path.

_BATCH_STACK: List[Span] = []
_BATCH_LOCK = threading.Lock()


def _stage_sink(name: str, seconds: float, items: int) -> None:
    with _BATCH_LOCK:
        parent = _BATCH_STACK[-1] if _BATCH_STACK else None
    if parent is None:
        return
    span = Span(name, parent.context.child(),
                parent_span_id=parent.context.span_id)
    span.start_s = time.time() - seconds
    if items:
        span.end(items=items)
    else:
        span.end()


def push_batch_span(span: Optional[Span]) -> None:
    """Make ``span`` the innermost stage-attribution target.  Explicit
    push/pop (vs only :func:`batch_scope`) because the coalescer's
    streamed formed-batches begin at formation and end after scatter —
    lifetimes that cross generator frames."""
    if span is None:
        return
    with _BATCH_LOCK:
        _BATCH_STACK.append(span)
        if len(_BATCH_STACK) == 1:
            observability.set_stage_span_sink(_stage_sink)


def pop_batch_span(span: Optional[Span]) -> None:
    if span is None:
        return
    with _BATCH_LOCK:
        try:
            _BATCH_STACK.remove(span)
        except ValueError:
            pass
        if not _BATCH_STACK:
            observability.set_stage_span_sink(None)


@contextlib.contextmanager
def batch_scope(span: Optional[Span]) -> Iterator[None]:
    """While active, completed pipeline stages become child spans of
    ``span``.  A ``None`` span is a no-op (unsampled batch)."""
    push_batch_span(span)
    try:
        yield
    finally:
        pop_batch_span(span)


def tracez_payload() -> Dict[str, Any]:
    """The ``GET /tracez`` body: recent completed spans, oldest first."""
    return {
        "pid": os.getpid(),
        "sample_rate": _SAMPLE_RATE,
        "buffer_maxlen": _SPAN_BUFFER.maxlen,
        "dropped": _SPAN_BUFFER.dropped,
        "spans": _SPAN_BUFFER.snapshot(),
    }


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


class FlightRecorder:
    """Always-on bounded ring of recent structured events from sites
    that recover silently.  Recording is a dict build + deque append
    under a lock — cheap enough for fault paths (which are off the
    per-line hot path by construction)."""

    def __init__(self, maxlen: int = 256):
        self.maxlen = int(maxlen)
        self._events: deque = deque(maxlen=self.maxlen)
        self._lock = threading.Lock()
        self.total = 0

    def record(self, kind: str, **fields: Any) -> None:
        event = {"t": time.time(), "kind": str(kind)}
        for k, v in fields.items():
            # "t"/"kind" are reserved envelope keys — a payload field
            # must never overwrite the event's identity.
            if v is not None and k not in ("t", "kind"):
                event[k] = v if isinstance(v, (int, float, bool)) else str(v)
        with self._lock:
            self._events.append(event)
            self.total += 1
        metrics().increment("flight_events_total", labels={"kind": str(kind)})

    def snapshot(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self.total = 0


_FLIGHT = FlightRecorder(_env_int("LOGPARSER_TPU_FLIGHT_EVENTS", 256))


def flight_recorder() -> FlightRecorder:
    return _FLIGHT


def flight_event(kind: str, **fields: Any) -> None:
    """Record one flight-recorder event (module-level convenience; the
    silent-recovery sites call exactly this)."""
    _FLIGHT.record(kind, **fields)


def flightz_payload(reason: Optional[str] = None) -> Dict[str, Any]:
    payload: Dict[str, Any] = {
        "pid": os.getpid(),
        "argv0": sys.argv[0] if sys.argv else "",
        "events_total": _FLIGHT.total,
        "ring_maxlen": _FLIGHT.maxlen,
        "events": _FLIGHT.snapshot(),
    }
    if reason is not None:
        payload["dump_reason"] = reason
    return payload


def flight_dir() -> str:
    """The dump directory: ``$LOGPARSER_TPU_FLIGHT_DIR``, defaulting to
    a per-machine run directory under the system temp root (dumps used
    to land in cwd, which litters whatever directory a CLI happened to
    start in)."""
    base = os.environ.get("LOGPARSER_TPU_FLIGHT_DIR", "").strip()
    return base or os.path.join(tempfile.gettempdir(), "logparser_tpu-flight")


def flight_dump_path(pid: Optional[int] = None) -> str:
    """Where a dump for ``pid`` (default: this process) lands:
    :func:`flight_dir` ``/flight-<pid>.json``."""
    return os.path.join(flight_dir(), f"flight-{pid or os.getpid()}.json")


def dump_flight(reason: str) -> Optional[str]:
    """Write the crash-safe dump; returns the path, or ``None`` if the
    write failed (a dying process must not die harder over telemetry).
    Atomic rename so a reader never sees a torn file."""
    path = flight_dump_path()
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump(flightz_payload(reason), fh, sort_keys=True)
        os.replace(tmp, path)
        return path
    except OSError:
        with contextlib.suppress(OSError):
            os.unlink(tmp)
        return None


_FLIGHT_NAME_RE = re.compile(r"^flight-(\d+)\.json$")

#: Dead-writer dumps retained after a sweep (most-recent first) —
#: post-mortem material for the runs that just crashed, without letting
#: a crash-looping fleet grow the directory without bound.
FLIGHT_KEEP_DEFAULT = 8


def sweep_flight_dumps(directory: Optional[str] = None,
                       keep: Optional[int] = None) -> List[str]:
    """Startup hygiene for the dump directory: unlink ``flight-<pid>.json``
    files whose writer pid is dead (the jobs-tier ``sweepable_temp_files``
    dead-pid rule — a live pid is a concurrent local process, an
    unkillable/unknowable one is left alone), keeping the ``keep``
    most-recently-modified dead dumps (``LOGPARSER_TPU_FLIGHT_KEEP``,
    default :data:`FLIGHT_KEEP_DEFAULT`).  Returns the removed paths."""
    base = directory if directory is not None else flight_dir()
    if keep is None:
        keep = _env_int("LOGPARSER_TPU_FLIGHT_KEEP", FLIGHT_KEEP_DEFAULT)
    try:
        names = os.listdir(base)
    except OSError:
        return []
    dead: List[tuple] = []
    for name in names:
        m = _FLIGHT_NAME_RE.match(name)
        if not m:
            continue
        pid = int(m.group(1))
        if pid == os.getpid():
            continue
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            pass            # dead writer: sweepable crash debris
        except OSError:
            continue        # unknowable (e.g. other uid): leave it
        else:
            continue        # alive: a concurrent local process
        path = os.path.join(base, name)
        try:
            mtime = os.stat(path).st_mtime
        except OSError:
            continue
        dead.append((mtime, path))
    dead.sort(reverse=True)
    removed = []
    for _, path in dead[max(0, keep):]:
        with contextlib.suppress(OSError):
            os.unlink(path)
            removed.append(path)
    return removed


def arm_flight_signals() -> None:
    """Install the SIGUSR2 dump trigger ("what was this process
    absorbing, without killing it"), chaining any prior handler.
    SIGTERM dumps are wired inside each CLI's existing graceful-drain
    handler (service.py / front.py) — not here — so drain semantics
    stay owned by the server."""
    import signal

    prev = signal.getsignal(signal.SIGUSR2)

    def _on_sigusr2(signum: int, frame: Any) -> None:  # noqa: ARG001
        flight_event("sigusr2_dump")
        dump_flight("sigusr2")
        if callable(prev) and prev not in (
            signal.SIG_IGN, signal.SIG_DFL, _on_sigusr2
        ):
            prev(signum, frame)

    try:
        signal.signal(signal.SIGUSR2, _on_sigusr2)
    except (ValueError, OSError, AttributeError):
        pass  # non-main thread / platform without SIGUSR2


def install_flight_excepthook() -> None:
    """Chain a sys.excepthook that dumps the flight ring on a fatal
    (uncaught) fault before the process dies — the last 60 s of
    silently-absorbed trouble usually explains the crash."""
    prev = sys.excepthook

    def _hook(exc_type: type, exc: BaseException, tb: Any) -> None:
        flight_event("fatal_fault", error=f"{exc_type.__name__}: {exc}")
        dump_flight("fatal_fault")
        prev(exc_type, exc, tb)

    if getattr(prev, "__name__", "") != "_hook":
        sys.excepthook = _hook


# ---------------------------------------------------------------------------
# test support
# ---------------------------------------------------------------------------


def reset_for_tests(sample_rate_value: Optional[float] = None) -> None:
    """Clear span buffer + flight ring and (optionally) re-pin the
    sample rate; re-reads the env when no explicit rate is given."""
    _SPAN_BUFFER.clear()
    _FLIGHT.clear()
    with _BATCH_LOCK:
        _BATCH_STACK.clear()
    observability.set_stage_span_sink(None)
    set_sample_rate(_env_rate() if sample_rate_value is None
                    else sample_rate_value)
