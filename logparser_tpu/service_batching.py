"""Cross-session continuous batching for the serving tier.

ROADMAP item 4's THROUGHPUT half (docs/SERVICE.md "Continuous
batching"): N concurrent sessions sending small LINES frames used to pay
N× the per-batch fixed cost — device dispatch, pad waste, one D2H
round-trip each — that ``tpu/batch.py`` amortizes so well at large batch
sizes.  This module coalesces line payloads ACROSS sessions into shared
device batches, keyed by the compiled-parser cache key (format + fields
config — and the aggregate spec, so analytics-pushdown sessions, whose
requests return aggregate frames and never enter the coalescer, can
never share a lane with row sessions even by key collision): the
LLM-serving continuous-batching trick applied to log lines,
and the device-program twin of CelerLog's route-by-format host
dispatching (PAPERS.md).

Shape:

- one :class:`_KeyBatcher` per parser cache key holds a bounded
  submission queue and a lazily-started dispatcher thread;
- session threads :meth:`BatchCoalescer.parse` → enqueue an entry and
  block on its event;
- the dispatcher claims queued entries into a formed batch (up to
  ``coalesce_max_lines``, waiting at most ``coalesce_window_ms`` for
  stragglers — and only when >1 session is live ON THIS PARSER KEY, so
  a lone client, or a format's only tenant, never pays the window),
  runs ONE device parse per formed batch, and
  scatters per-entry :meth:`~logparser_tpu.tpu.batch.BatchResult.slice`
  windows back.  Each waiting session assembles its own Arrow/IPC bytes
  from its slice, so host-side delivery still parallelizes across
  session threads;
- back-to-back formed batches run through ``parse_batch_stream`` (the
  framed payload adopted via ``parse_encoded``), so a backlog overlaps
  batch k+1's H2D upload with batch k's device work — the PR-5 staged
  edge, now engaged by serving bursts.

Robustness contract (composing with the PR-7 admission tier, never
replacing it):

- the submission queue is BOUNDED: at ``coalesce_queue_depth`` entries a
  submit raises :class:`CoalesceQueueFull` and the service sheds a
  structured ``BUSY {"reason":"coalesce_queue"}`` — coalescing must not
  reintroduce the unbounded queue admission control exists to prevent;
- the queue occupancy feeds the process-wide
  :func:`logparser_tpu.feeder.queue_backpressure` signal (the coalescer
  registers itself as a backpressure source), so the per-request
  admission leg sheds BEFORE the queue hard-fills;
- a request deadline expires a WAITING entry without poisoning the
  shared batch: the waiter (or the dispatcher, when it reaches an
  already-expired entry) cancels it under the batcher lock before batch
  formation and the session answers a structured ``DEADLINE`` frame; an
  entry already claimed into an in-flight batch delivers normally and
  the late result is discarded by the session's deadline machinery;
- an ABANDONED in-flight batch (every claimed entry's deadline expired
  while the shared parse is still running — a wedged or pathologically
  slow parse) RECYCLES the lane (round 15): the dispatcher epoch is
  bumped, a fresh dispatcher takes over the submission queue, and the
  stale dispatcher delivers its doomed batch in the background and
  exits — one wedged parse no longer head-of-line-blocks every session
  on that format key (``service_coalesce_lane_recycles_total``).  The
  abandoned requests' worker threads still hold their in-flight slots
  until the wedged parse truly stops, so the admission backpressure a
  wedge is supposed to exert is preserved;
- drain-safety: queued entries belong to admitted sessions, so a
  graceful drain's session wait inherently waits for the coalescer to
  finish them; :meth:`BatchCoalescer.shutdown` runs after the session
  join and fails any orphaned entry loudly instead of hanging it.

Parity invariant (the reason this is safe to default ON): the scattered
per-session results are BYTE-identical to what solo parsing would have
produced — guaranteed by ``BatchResult.slice``'s per-row independence
contract and locked by the cross-session parity suite in
tests/test_service.py, the golden protocol vectors, and
tools/coalesce_smoke.py.
"""
from __future__ import annotations

import logging
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Callable, Dict, List, Optional

from .observability import log_warning_once, metrics, pipeline_stage

LOG = logging.getLogger(__name__)

# Histogram bucket bounds (docs/OBSERVABILITY.md): occupancy is a 0-1
# fill fraction of the configured batch geometry; wait is the queue time
# an entry spent before claim; sessions/batch is the coalescing win.
OCCUPANCY_BUCKETS = (0.05, 0.1, 0.25, 0.5, 0.75, 0.9, 1.0)
WAIT_BUCKETS = (0.0005, 0.001, 0.002, 0.005, 0.01, 0.025, 0.05, 0.1,
                0.25, 1.0, 5.0)
SESSIONS_BUCKETS = (1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0, 32.0, 64.0)

# Dispatcher threads exit after this long idle; the next submit restarts
# one (a long-lived sidecar serving many historical configs must not
# keep a thread per cold parser key).
_IDLE_EXIT_S = 30.0
# Batcher registry bound: beyond it, idle (empty-queue) batchers are
# evicted LRU — mirrors the parser cache's own LRU bound.
_MAX_BATCHERS = 64


class CoalesceQueueFull(Exception):
    """The shared submission queue is at capacity: the request must SHED
    (structured ``BUSY {"reason":"coalesce_queue"}``) instead of queueing
    without bound behind the device — the admission contract
    (docs/SERVICE.md) extended to the coalescer's own queue."""


class CoalesceDeadline(Exception):
    """The request deadline expired while the entry was still QUEUED.
    The entry was cancelled BEFORE batch formation — the shared batch is
    not poisoned — and the session answers a structured ``DEADLINE``
    frame exactly as a solo slow parse would."""


class CoalesceShutdown(Exception):
    """The service shut down with this entry still queued (only possible
    for a session force-closed past the drain deadline — a graceful
    drain finishes queued entries before the coalescer stops)."""


class _Entry:
    """One session's queued request: the payload, its line count, and
    the rendezvous the session thread blocks on.  State transitions are
    guarded by the owning batcher's lock: PENDING -> CLAIMED (dispatcher
    took it into a formed batch) or PENDING -> CANCELLED (deadline /
    shutdown); CLAIMED entries always get ``result`` or ``error``.
    ``claimed`` is set once the entry leaves PENDING for good (claimed,
    or resolved while queued): the end of its ``coalesce_wait`` span."""

    __slots__ = ("blob", "count", "enq_t", "deadline_t", "max_wait_t",
                 "claimed", "event", "state", "result", "error",
                 "abandoned", "trace_ctx")

    PENDING, CLAIMED, CANCELLED = range(3)

    def __init__(self, blob: bytes, count: int,
                 deadline_t: Optional[float],
                 max_wait_t: Optional[float] = None,
                 trace_ctx: Any = None):
        self.blob = blob
        self.count = count
        self.enq_t = time.monotonic()
        self.deadline_t = deadline_t
        # The submitting request's TraceContext (or None): the formed
        # batch's span LINKS to every member's context — the fan-in IS
        # the signal (N sessions provably shared one device batch).
        # Never part of batching/parsing decisions.
        self.trace_ctx = trace_ctx
        # Client batching hint (PROTOCOL.md "coalesce_wait_ms"): the
        # absolute time by which a forming batch holding this entry must
        # stop waiting for stragglers — a latency-critical session caps
        # the straggler window it is willing to pay, without changing
        # parsing, sharing, shedding, or result bytes.
        self.max_wait_t = max_wait_t
        self.claimed = threading.Event()
        self.event = threading.Event()
        self.state = _Entry.PENDING
        self.result: Any = None
        self.error: Optional[BaseException] = None
        # CLAIMED entry whose waiter's deadline expired mid-flight: the
        # session already answered DEADLINE and will discard the late
        # result.  When EVERY in-flight entry is abandoned the lane
        # recycles (head-of-line-blocking fix, round 15).
        self.abandoned = False


class _FormedBatch:
    """Entries claimed into one shared device batch, in claim order.
    Row offsets are the running line counts — entry k's result is rows
    ``[offset_k, offset_k + count_k)`` of the combined parse."""

    __slots__ = ("entries", "total", "span")

    def __init__(self, entries: List[_Entry]):
        self.entries = entries
        self.total = sum(e.count for e in entries)
        # Live coalesce_batch trace span (or None): opened at formation,
        # closed after scatter — the stream path's lifetime crosses the
        # generator frame, so it rides the batch, not a with-block.
        self.span = None

    def blob(self) -> bytes:
        return b"\n".join(e.blob for e in self.entries)

    def encoded(self):
        """The combined payload framed exactly as ``parse_blob`` frames
        it (``native.encode_blob``), wrapped as a feeder
        :class:`~logparser_tpu.feeder.worker.EncodedBatch` so
        ``parse_batch_stream``/``parse_encoded`` adopt it without a
        re-scan — and so back-to-back formed batches ride the staged-H2D
        double buffer."""
        from .feeder.worker import EncodedBatch
        from .native import encode_blob

        blob = self.blob()
        buf, lengths, overflow = encode_blob(blob)
        return EncodedBatch(
            shard=0, index=0, payload=blob, buf=buf, lengths=lengths,
            overflow=list(overflow), n_lines=self.total,
        )


def _begin_batch_span(fb: _FormedBatch) -> Any:
    """Open the ONE shared-batch span (docs/OBSERVABILITY.md "Tracing"):
    parented on the first sampled member's context, span-LINKED to every
    member — N sessions provably share this device batch.  Pushed as the
    stage-attribution target so PIPELINE_STAGES become its children.
    Returns None (and touches nothing) when no member is sampled."""
    head = None
    for e in fb.entries:
        if e.trace_ctx is not None and getattr(e.trace_ctx, "sampled", False):
            head = e.trace_ctx
            break
    if head is None:
        return None
    from .tracing import child_span, push_batch_span

    span = child_span(
        "coalesce_batch", head,
        attrs={"sessions": len(fb.entries), "lines": fb.total},
    )
    for e in fb.entries:
        if e.trace_ctx is not None:
            span.add_link(e.trace_ctx)
    push_batch_span(span)
    return span


def _end_batch_span(span: Any) -> None:
    if span is None:
        return
    from .tracing import pop_batch_span

    pop_batch_span(span)
    span.end()


class _KeyBatcher:
    """The per-parser-cache-key coalescing lane: bounded submission
    queue + one dispatcher thread, started lazily and exiting when
    idle."""

    def __init__(self, co: "BatchCoalescer", key: Any, parser: Any,
                 seq: int):
        self.co = co
        self.key = key
        self.parser = parser
        self.seq = seq
        self.lock = threading.Lock()
        self.cond = threading.Condition(self.lock)
        self.queue: "deque[_Entry]" = deque()
        self.thread: Optional[threading.Thread] = None
        self.stopped = False
        self.last_used = time.monotonic()
        # Dispatcher ownership epoch: bumped by a lane recycle (every
        # in-flight entry abandoned).  A dispatcher whose captured epoch
        # is stale delivers its already-claimed batches and exits — it
        # must never claim fresh queue entries again.
        self.epoch = 0
        # Entries CLAIMED but not yet resolved, in claim order (the
        # recycle trigger reads it; guarded by ``lock``).
        self.inflight: List[_Entry] = []

    # -- submit side (session threads) ---------------------------------

    def submit(self, blob: bytes, count: int,
               deadline_s: Optional[float],
               max_wait_s: Optional[float] = None,
               trace_ctx: Any = None) -> _Entry:
        now = time.monotonic()
        entry = _Entry(blob, count,
                       now + deadline_s if deadline_s else None,
                       now + max_wait_s if max_wait_s is not None
                       else None,
                       trace_ctx=trace_ctx)
        with self.lock:
            if self.stopped:
                raise CoalesceShutdown("service is shutting down")
            if len(self.queue) >= self.co.queue_depth:
                raise CoalesceQueueFull(
                    f"coalesce queue at capacity "
                    f"({self.co.queue_depth} entries)"
                )
            self.queue.append(entry)
            self.last_used = now
            self._ensure_thread_locked()
            self.cond.notify_all()
        metrics().gauge_add("service_coalesce_queue_depth", 1)
        return entry

    def wait(self, entry: _Entry, deadline_s: Optional[float]):
        """Block the session thread until the entry's result/error; the
        time until a formed batch claims it is the ``coalesce_wait``
        span.  On deadline: cancel if still PENDING (the batch is not
        poisoned); if already CLAIMED the batch is in flight — mark the
        entry ABANDONED (recycling the lane once the whole in-flight
        batch is), then wait it out: the session's own deadline
        machinery answers the client and discards this late result, and
        this worker thread keeps its in-flight slot until the parse
        truly stops (wedge -> backpressure, docs/SERVICE.md)."""
        end = None if deadline_s is None else time.monotonic() + deadline_s
        with pipeline_stage("coalesce_wait", items=entry.count):
            entry.claimed.wait(deadline_s)
        left = None if end is None else max(0.0, end - time.monotonic())
        if not entry.event.wait(left):
            with self.lock:
                if entry.state == _Entry.PENDING:
                    entry.state = _Entry.CANCELLED
                    entry.error = CoalesceDeadline(
                        "request deadline expired in the coalesce queue"
                    )
                    metrics().increment("service_coalesce_expired_total")
                    metrics().gauge_add("service_coalesce_queue_depth", -1)
                    raise entry.error
            self._note_abandoned(entry)
            entry.event.wait()
        if entry.error is not None:
            raise entry.error
        return entry.result

    # -- dispatch side --------------------------------------------------

    def _ensure_thread_locked(self) -> None:
        if self.thread is None or not self.thread.is_alive():
            self.thread = threading.Thread(
                target=self._run, args=(self.epoch,),
                name=f"svc-coalesce-{self.seq}", daemon=True,
            )
            self.thread.start()

    def _note_abandoned(self, entry: _Entry) -> None:
        """A waiter's deadline expired on a CLAIMED entry.  When that
        leaves the ENTIRE in-flight population abandoned, nobody is
        waiting for the batch the dispatcher is stuck on — recycle the
        lane: bump the epoch (the stale dispatcher delivers its doomed
        batches and exits without ever touching the queue again) and
        hand the submission queue to a fresh dispatcher, so one wedged
        parse cannot stall every session on this format key."""
        recycled = False
        with self.lock:
            entry.abandoned = True
            if self.stopped or entry.event.is_set():
                return
            if not self.inflight or not all(
                e.abandoned or e.event.is_set() for e in self.inflight
            ):
                return
            self.epoch += 1
            self.thread = None
            if self.queue:
                self._ensure_thread_locked()
            self.cond.notify_all()
            recycled = True
        if recycled:
            metrics().increment("service_coalesce_lane_recycles_total")
            log_warning_once(
                LOG,
                "coalesce lane recycled around an abandoned in-flight "
                "batch (every waiter's deadline expired; the wedged "
                "parse finishes in the background)",
            )

    def _run(self, my_epoch: int) -> None:
        try:
            while True:
                with self.lock:
                    if self.epoch != my_epoch:
                        return  # recycled: a fresh dispatcher owns the queue
                    while not self.queue and not self.stopped:
                        if not self.cond.wait(timeout=_IDLE_EXIT_S):
                            if not self.queue and not self.stopped \
                                    and self.epoch == my_epoch:
                                # Idle exit: a later submit restarts one.
                                self.thread = None
                                return
                        if self.epoch != my_epoch:
                            return
                    if self.stopped and not self.queue:
                        return
                self._burst(my_epoch)
        except Exception as e:  # noqa: BLE001 — a lane must fail loudly
            # A dispatcher crash outside _burst's per-batch handling:
            # fail every queued entry (waiters get the error frame, not
            # a hang) and clear the thread slot so the lane can recover.
            log_warning_once(
                LOG,
                "coalesce dispatcher failed; queued entries answered "
                "with the error and the lane restarted "
                "(details at DEBUG)",
            )
            LOG.debug("coalesce dispatcher fault on key %r", self.key,
                      exc_info=True)
            with self.lock:
                if self.epoch != my_epoch:
                    # Recycled mid-crash: the queue belongs to the new
                    # dispatcher — only this incarnation's own claimed
                    # entries (already resolved by _burst's handlers)
                    # were affected.
                    return
                drained = list(self.queue)
                self.queue.clear()
                self.thread = None
                # State flips under the lock (the waiter-cancel path
                # races this); each PENDING->CANCELLED flip owns one
                # gauge decrement.
                cancelled = 0
                for entry in drained:
                    if entry.state == _Entry.PENDING:
                        entry.state = _Entry.CANCELLED
                        cancelled += 1
            if cancelled:
                metrics().gauge_add("service_coalesce_queue_depth",
                                    -cancelled)
            for entry in drained:
                self._finish(entry, error=e)

    def _claim_locked(self, claimed: List[_Entry], now: float) -> int:
        """Move eligible queue entries into ``claimed`` (respecting the
        line budget); expire already-dead ones.  Returns claimed line
        total.  Caller holds the lock."""
        reg = metrics()
        total = sum(e.count for e in claimed)
        while self.queue and total < self.co.max_lines:
            e = self.queue[0]
            if e.state == _Entry.CANCELLED:
                self.queue.popleft()
                continue
            if e.deadline_t is not None and now >= e.deadline_t:
                # Expired while queued: drop BEFORE batch formation so
                # the shared batch never carries a dead entry.
                self.queue.popleft()
                e.state = _Entry.CANCELLED
                e.error = CoalesceDeadline(
                    "request deadline expired in the coalesce queue"
                )
                reg.increment("service_coalesce_expired_total")
                reg.gauge_add("service_coalesce_queue_depth", -1)
                e.claimed.set()
                e.event.set()
                continue
            if claimed and total + e.count > self.co.max_lines:
                break  # keep the batch inside the configured geometry
            self.queue.popleft()
            e.state = _Entry.CLAIMED
            e.claimed.set()
            self.inflight.append(e)
            claimed.append(e)
            total += e.count
            reg.observe("service_coalesce_wait_seconds", now - e.enq_t,
                        buckets=WAIT_BUCKETS)
            reg.gauge_add("service_coalesce_queue_depth", -1)
        return total

    def _form(self, my_epoch: int) -> Optional[_FormedBatch]:
        """Form the next batch from the queue: claim what is there, then
        wait up to the coalesce window for stragglers (only when more
        than one session is live — a lone client must not pay the
        window, and an already-full batch never waits).  Inside a burst
        the window wait OVERLAPS the in-flight batch's async device
        work — dispatch is asynchronous, so filling batch k+1 while
        batch k computes costs nothing and roughly doubles occupancy
        (measured 2.2 -> 3.9 sessions/batch at 8 clients on the 2-core
        container, 1.37x -> 2.1x goodput over per-session dispatch).
        None (empty queue after the wait, or a stale dispatcher epoch —
        the lane was recycled) ends the burst."""
        claimed: List[_Entry] = []
        with self.lock:
            if self.epoch != my_epoch:
                return None
            total = self._claim_locked(claimed, time.monotonic())
            if (
                claimed and not self.stopped
                and self.co.window_s > 0.0
                and total < self.co.max_lines
                and self.co.should_wait(self.key)
            ):
                end = self._window_end(
                    claimed, time.monotonic() + self.co.window_s
                )
                while total < self.co.max_lines:
                    remaining = end - time.monotonic()
                    if remaining <= 0:
                        break
                    self.cond.wait(remaining)
                    if self.stopped or self.epoch != my_epoch:
                        break
                    total = self._claim_locked(claimed, time.monotonic())
                    # A newly claimed entry may carry a TIGHTER
                    # per-session wait cap (coalesce_wait_ms): the
                    # formation window shrinks to the strictest member.
                    end = min(end, self._window_end(claimed, end))
        if not claimed:
            return None
        return _FormedBatch(claimed)

    @staticmethod
    def _window_end(claimed: List[_Entry], default_end: float) -> float:
        """When the straggler wait over ``claimed`` must stop: the
        configured window end, clamped by every member's own
        ``coalesce_wait_ms`` cap (the strictest session in the batch
        decides — a 0 ms hint dispatches the batch immediately)."""
        end = default_end
        for e in claimed:
            if e.max_wait_t is not None and e.max_wait_t < end:
                end = e.max_wait_t
        return end

    def _burst(self, my_epoch: int) -> None:
        """Drain the backlog as one stream of formed batches: ONE device
        parse per formed batch, back-to-back batches overlapping upload
        with compute via ``parse_batch_stream``'s staged-H2D edge.
        Parser doubles without the streaming API take a plain
        ``parse_blob`` per formed batch.

        Spans on this thread: ``coalesce_parse`` around each step of the
        stream that yields one formed batch's result (the next batch's
        formation and framing, ``encode``, included: the stream pulls it
        to overlap its upload), holding the parser's shared stages; then
        ``scatter`` around handing that result's windows to the waiting
        entries."""
        parser = self.parser
        if not (hasattr(parser, "parse_batch_stream")
                and hasattr(parser, "parse_encoded")):
            fb = self._form(my_epoch)
            while fb is not None:
                fb.span = _begin_batch_span(fb)
                try:
                    with pipeline_stage("coalesce_parse", items=fb.total):
                        result = parser.parse_blob(fb.blob(),
                                                   emit_views=False)
                    with pipeline_stage("scatter", items=fb.total):
                        self._scatter(fb, result)
                except Exception as e:  # noqa: BLE001 — relayed per entry
                    self._fail(fb, e)
                finally:
                    _end_batch_span(fb.span)
                fb = self._form(my_epoch)
            return

        formed: "deque[_FormedBatch]" = deque()

        def gen():
            while True:
                fb = self._form(my_epoch)
                if fb is None:
                    return
                fb.span = _begin_batch_span(fb)
                formed.append(fb)
                with pipeline_stage("encode", items=fb.total):
                    enc = fb.encoded()
                yield enc

        try:
            stream = parser.parse_batch_stream(gen(), emit_views=False)
            while True:
                with pipeline_stage("coalesce_parse"):
                    result = next(stream, None)
                if result is None:
                    break
                fb = formed.popleft()
                try:
                    with pipeline_stage("scatter", items=fb.total):
                        self._scatter(fb, result)
                except Exception as e:  # noqa: BLE001 — relayed per entry
                    # A partial scatter (e.g. a slice fault mid-batch)
                    # must still resolve EVERY entry of the popped batch
                    # — _finish is first-write-wins, so already-delivered
                    # entries keep their results and only the unresolved
                    # tail gets the error.  An unresolved entry would
                    # hang its session thread and leak its in-flight
                    # slot forever.
                    self._fail(fb, e)
                finally:
                    _end_batch_span(fb.span)
        except Exception as e:  # noqa: BLE001 — relayed per entry
            # A mid-stream failure costs the formed-but-undelivered
            # batches their requests (each answered with the error
            # frame); entries still queued are untouched and retry on
            # the restarted lane.
            while formed:
                fb = formed.popleft()
                _end_batch_span(fb.span)
                self._fail(fb, e)

    def _finish(self, entry: _Entry, result: Any = None,
                error: Optional[BaseException] = None) -> None:
        # First write wins: a batch-level _fail after a partial scatter
        # must not overwrite an already-delivered entry's result with
        # the error (the waiter may already be reading it).
        if entry.event.is_set():
            return
        entry.result = result
        entry.error = error
        entry.claimed.set()
        with self.lock:
            # Off the recycle trigger's ledger BEFORE the event flips:
            # a resolved entry must never count toward "the whole
            # in-flight batch is abandoned".
            try:
                self.inflight.remove(entry)
            except ValueError:
                pass
        entry.event.set()

    def _fail(self, fb: _FormedBatch, error: BaseException) -> None:
        for entry in fb.entries:
            self._finish(entry, error=error)

    def _scatter(self, fb: _FormedBatch, result: Any) -> None:
        """Hand each claimed entry its row window of the shared result.
        Session threads do their own Arrow assembly from the slice, so
        delivery stays parallel across sessions."""
        reg = metrics()
        reg.increment("service_coalesce_batches_total")
        reg.increment("service_coalesced_requests_total", len(fb.entries))
        reg.observe("service_coalesced_sessions_per_batch",
                    float(len(fb.entries)), buckets=SESSIONS_BUCKETS)
        reg.observe("service_coalesce_batch_occupancy",
                    fb.total / max(1, self.co.max_lines),
                    buckets=OCCUPANCY_BUCKETS)
        if len(fb.entries) == 1:
            self._finish(fb.entries[0], result=result)
            return
        if not hasattr(result, "slice"):
            # Parser double / exotic result without the slicing contract:
            # re-parse each payload solo — slower, trivially
            # parity-correct (only reachable with injected test parsers).
            for entry in fb.entries:
                try:
                    self._finish(entry, result=self.parser.parse_blob(
                        entry.blob, emit_views=False))
                except Exception as e:  # noqa: BLE001
                    self._finish(entry, error=e)
            return
        row = 0
        for entry in fb.entries:
            self._finish(entry, result=result.slice(row, row + entry.count))
            row += entry.count

    # -- teardown -------------------------------------------------------

    def stop(self) -> "Optional[threading.Thread]":
        """Flag the lane stopped, fail queued entries, return the
        dispatcher thread (if any) for the caller to join."""
        with self.lock:
            self.stopped = True
            drained = []
            for entry in self.queue:
                # State flips under the lock (the waiter-cancel path
                # races this); each flip owns one gauge decrement.
                if entry.state == _Entry.PENDING:
                    entry.state = _Entry.CANCELLED
                    drained.append(entry)
            self.queue.clear()
            self.cond.notify_all()
            thread = self.thread
        if drained:
            metrics().gauge_add("service_coalesce_queue_depth",
                                -len(drained))
        for entry in drained:
            self._finish(entry, error=CoalesceShutdown(
                "service shut down with the request still queued"
            ))
        return thread


class BatchCoalescer:
    """The service-wide coalescer: one :class:`_KeyBatcher` per parser
    cache key, an aggregate :meth:`backpressure` signal registered with
    the feeder fabric's process-wide
    :func:`~logparser_tpu.feeder.queue_backpressure`, and a bounded
    batcher registry (idle lanes evict LRU)."""

    def __init__(self, *, window_s: float, max_lines: int,
                 queue_depth: int,
                 live_sessions_fn: Optional[Callable[[Any], int]] = None,
                 max_batchers: int = _MAX_BATCHERS):
        self.window_s = max(0.0, float(window_s))
        self.max_lines = max(1, int(max_lines))
        self.queue_depth = max(1, int(queue_depth))
        self._live_sessions_fn = live_sessions_fn
        self._max_batchers = max(1, int(max_batchers))
        self._lock = threading.Lock()
        self._batchers: "OrderedDict[Any, _KeyBatcher]" = OrderedDict()
        self._seq = 0
        self._closed = False
        from .feeder import register_backpressure_source

        register_backpressure_source(self)

    # -- the request path ----------------------------------------------

    def parse(self, key: Any, parser: Any, blob: bytes, count: int,
              deadline_s: Optional[float] = None,
              max_wait_s: Optional[float] = None,
              trace_ctx: Any = None):
        """Coalesce one request's payload into the key's shared batch
        stream; returns the session's own
        :class:`~logparser_tpu.tpu.batch.BatchResult` window (byte-
        identical to a solo parse of ``blob``).  ``max_wait_s`` is the
        session's ``coalesce_wait_ms`` hint: a cap on the straggler
        window any batch holding this request may pay (0 = dispatch as
        soon as claimed); parsing, queue bounds, and shed behavior are
        untouched.  Raises :class:`CoalesceQueueFull` (shed),
        :class:`CoalesceDeadline` (expired while queued),
        :class:`CoalesceShutdown`, or whatever the shared parse
        raised."""
        for _ in range(2):
            batcher = self._batcher(key, parser)
            try:
                entry = batcher.submit(blob, count, deadline_s,
                                       max_wait_s, trace_ctx=trace_ctx)
            except CoalesceShutdown:
                if self._closed:
                    raise
                # An LRU-evicted idle lane raced this submit: the key is
                # already out of the registry, so the next _batcher()
                # call builds a fresh one.
                continue
            return batcher.wait(entry, deadline_s)
        raise CoalesceShutdown("service is shutting down")

    def _batcher(self, key: Any, parser: Any) -> _KeyBatcher:
        with self._lock:
            if self._closed:
                raise CoalesceShutdown("service is shutting down")
            b = self._batchers.get(key)
            if b is None:
                self._seq += 1
                b = _KeyBatcher(self, key, parser, self._seq)
                self._batchers[key] = b
                self._evict_locked()
            else:
                # A recompiled parser for the same config (cache evict +
                # rebuild) produces identical results: adopt the fresh
                # object so the lane never pins a stale executor.
                b.parser = parser
                self._batchers.move_to_end(key)
            return b

    def _evict_locked(self) -> None:
        if len(self._batchers) <= self._max_batchers:
            return
        for key, b in list(self._batchers.items()):
            if len(self._batchers) <= self._max_batchers:
                return
            with b.lock:
                idle = not b.queue
                if idle:
                    b.stopped = True
                    b.cond.notify_all()
            if idle:
                del self._batchers[key]

    # -- signals --------------------------------------------------------

    def should_wait(self, key: Any) -> bool:
        """Whether the coalesce window is worth paying for ``key``'s
        lane: only when more than one session is live ON THAT PARSER
        KEY — a lone client (or the only tenant of a format, however
        busy the other formats are) has nobody to coalesce with, so
        waiting would be pure added latency."""
        fn = self._live_sessions_fn
        if fn is None:
            return True
        try:
            return fn(key) > 1
        except Exception:  # noqa: BLE001 — an unknown count must not stall
            return True

    def backpressure(self) -> float:
        """Worst per-key queue occupancy as a 0-1 fraction of the
        bounded depth — the coalescer's contribution to the process-wide
        :func:`~logparser_tpu.feeder.queue_backpressure` aggregate the
        admission tier sheds on (docs/SERVICE.md)."""
        if self._closed:
            return 0.0
        worst = 0.0
        with self._lock:
            batchers = list(self._batchers.values())
        for b in batchers:
            worst = max(worst, len(b.queue) / float(self.queue_depth))
        return min(1.0, worst)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "batchers": len(self._batchers),
                "queued_entries": sum(
                    len(b.queue) for b in self._batchers.values()
                ),
            }

    # -- teardown -------------------------------------------------------

    def shutdown(self, join_timeout_s: float = 5.0) -> None:
        """Stop every lane: fail still-queued entries loudly (see
        :class:`CoalesceShutdown` — a graceful drain finishes queued
        entries BEFORE this runs, because they belong to admitted
        sessions the drain waits for) and join dispatcher threads under
        one shared budget."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            batchers = list(self._batchers.values())
            self._batchers.clear()
        from .feeder import deregister_backpressure_source

        deregister_backpressure_source(self)
        threads = [t for t in (b.stop() for b in batchers) if t is not None]
        end = time.monotonic() + max(0.0, join_timeout_s)
        for t in threads:
            t.join(timeout=max(0.0, end - time.monotonic()))
            if t.is_alive():
                from .observability import note_teardown

                note_teardown(
                    LOG, "service_teardown_errors_total", "coalesce_join",
                    f"coalesce dispatcher {t.name} outlived its join",
                )
