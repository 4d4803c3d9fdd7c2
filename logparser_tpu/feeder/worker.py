"""Feeder worker loop: shard payloads -> framed, device-ready batches.

Each worker owns a deterministic subset of the shard plan (shard i goes
to worker ``i % N``) and pushes batch messages into its own queue.
Backpressure is transport-specific but always producer-blocking:

- **ring** transport (the default for process pools): the batch body is
  framed directly into a shared-memory slot (or, when it outgrows the
  slot, a segment of its own size leased under the slot) and only a tiny
  :class:`~logparser_tpu.feeder.ring.SlotFrame` descriptor crosses the
  queue — an exhausted free-slot queue blocks the worker until the
  consumer releases a slot;
- **pickle** transport (escape hatch / fallback): the whole
  :class:`EncodedBatch` is pickled through a BOUNDED queue — a full
  queue blocks the worker.

Either way the device consumer's drain rate caps host read rate;
nothing buffers unboundedly and nothing is ever dropped.

Framing is exactly ``TpuBatchParser.parse_blob``'s: the same
:func:`logparser_tpu.native.encode_blob` packs each batch's line bytes
into the padded ``[B, L]`` uint8 buffer (trailing-newline empty segment
dropped, one trailing ``\\r`` per line stripped), so feeder output is
byte-identical to single-process ``parse_blob`` over the same corpus —
on BOTH transports (the parity suite pins it).  The module is jax-free
and picklable — it runs inside ``spawn``ed worker processes that must
never acquire the device.
"""
from __future__ import annotations

import logging
import time
import traceback
from dataclasses import dataclass, field
from queue import Full
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .shards import Shard, _Source, read_shard_payload


def note_teardown_error(logger: logging.Logger, site: str,
                        exc: BaseException) -> None:
    """Teardown/cleanup failures must not be silent: count them
    (``feeder_teardown_errors_total{site=...}``) and warn once per
    distinct message — a leak-shaped failure (unlinkable arena, wedged
    queue) repeated across pools is exactly the drip a long-lived host
    needs to see.  Shared by pool.py and ring.py; in a WORKER process
    the counter lands in the child's registry (invisible to the
    consumer) but the warning still reaches its stderr."""
    from ..observability import log_warning_once, metrics

    metrics().increment(
        "feeder_teardown_errors_total", labels={"site": site}
    )
    log_warning_once(
        logger,
        f"feeder teardown: {site} failed "
        f"({type(exc).__name__}: {exc})",
    )

# Queue message kinds (worker -> consumer).
MSG_BATCH = "batch"          # pickled EncodedBatch body
MSG_SLOT = "slot"            # ring SlotFrame descriptor (body in shm)
MSG_SHARD_DONE = "shard_done"
MSG_DONE = "done"
MSG_ERROR = "error"


@dataclass
class EncodedBatch:
    """One framed batch: the raw line bytes (kept for lazy oracle rescue
    and byte-parity checks) plus the device-ready encoded buffers.

    ``TpuBatchParser.parse_encoded`` / ``parse_batch_stream`` adopt this
    directly — the consumer process never re-scans the payload.  The
    ring transport's :class:`~logparser_tpu.feeder.ring.RingBatch`
    subclass backs the same fields with shared-memory slot views."""

    shard: int                  # global shard index
    index: int                  # batch index within the shard
    payload: bytes              # the batch's raw line bytes (with '\n's)
    buf: np.ndarray             # [B, L] uint8 (unpadded batch dim)
    lengths: np.ndarray         # [B] int32
    overflow: List[int] = field(default_factory=list)
    n_lines: int = 0
    read_s: float = 0.0         # this batch's share of the shard read
    encode_s: float = 0.0       # framing wall time (worker-side)
    slot_wait_s: float = 0.0    # ring backpressure wait (0 for pickle)

    @property
    def source_bytes(self) -> int:
        return len(self.payload)

    @property
    def order_key(self) -> Tuple[int, int]:
        return (self.shard, self.index)

    def release(self) -> None:
        """Slot-lease hook: a plain (owned) batch holds no lease."""

    def detach(self) -> "EncodedBatch":
        """Owned-copy hook: a plain batch already owns its arrays."""
        return self


def split_batches(payload: bytes, batch_lines: int) -> List[Tuple[int, int]]:
    """Line-aligned (start, end) byte ranges of successive
    ``batch_lines``-line groups of ``payload`` (last group takes the
    remainder; a trailing newline ends the last line, it never starts an
    empty one — encode_blob's framing)."""
    if not payload:
        return []
    arr = np.frombuffer(payload, dtype=np.uint8)
    nl = np.flatnonzero(arr == 0x0A)
    # Line starts: 0 plus every newline+1 that still begins a line.
    starts = np.concatenate(([0], nl + 1))
    if payload.endswith(b"\n"):
        starts = starts[:-1]
    n = len(starts)
    out: List[Tuple[int, int]] = []
    for b0 in range(0, n, max(1, batch_lines)):
        b1 = b0 + max(1, batch_lines)
        end = int(starts[b1]) if b1 < n else len(payload)
        out.append((int(starts[b0]), end))
    return out


def run_worker(
    worker_id: int,
    sources: Sequence[_Source],
    shards: Sequence[Shard],
    out_q,
    batch_lines: int,
    line_len: int,
    stop_event,
    delay_s: float = 0.0,
    ring=None,
    puts=None,
    watch_parent: bool = False,
    resume: Optional[Dict[int, int]] = None,
    chaos=None,
) -> None:
    """Read + frame this worker's shards, in shard order, into ``out_q``.

    ``stop_event`` aborts blocked puts AND blocked slot acquires so an
    abandoned pool never leaks a worker wedged on a full queue or an
    exhausted ring.  ``delay_s`` sleeps after each batch — a
    shaping/test hook (slow-source simulation).  ``ring`` selects the
    shared-memory transport: a :class:`~logparser_tpu.feeder.ring.
    RingSpec` (process workers attach by name) or a ready
    ``SlotWriter`` (thread workers share the pool's mapping).  ``puts``
    is an optional shared put-counter (``multiprocessing.Value``) the
    parent reads to keep the ``feeder_queue_depth`` gauge live for
    process workers (a child process cannot touch the parent's metrics
    registry).  ``watch_parent`` arms the orphan watch — process
    workers only: there ``mp.parent_process()`` IS the consumer, while
    a thread worker's is whatever spawned the consumer, and that dying
    says nothing about the consumer's health.

    ``resume`` maps global shard index -> number of leading batches to
    SKIP — how a respawned worker replays a partially-delivered shard
    from the last delivered batch boundary (``split_batches`` is
    deterministic over (payload, batch_lines), so the replayed suffix
    is byte-identical to what the dead incarnation would have sent).
    Batch indices keep their original values.  ``chaos`` is an optional
    :class:`~logparser_tpu.tools.chaos.ChaosSpec` arming the
    fault-injection hooks (parsed by the pool — env vars do not reach
    forkserver children reliably)."""
    from ..native import encode_blob

    hard_exit: Tuple = ()
    if chaos is not None:
        from ..tools.chaos import WorkerChaos, _ChaosHardExit

        hard_exit = (_ChaosHardExit,)
        chaos = WorkerChaos(chaos, worker_id, is_process=watch_parent)

    writer = None
    if ring is not None:
        from .ring import SlotWriter

        writer = ring if isinstance(ring, SlotWriter) else SlotWriter(ring)

    stop = _StopWatch(stop_event, watch_parent=watch_parent)

    def put(item) -> bool:
        if chaos is not None:
            chaos.before_put()
        while True:
            if stop.is_set():
                return False
            try:
                out_q.put(item, timeout=0.1)
                if puts is not None:
                    with puts.get_lock():
                        puts.value += 1
                return True
            except Full:  # same class for both queue flavors
                continue

    def emit_batch(shard, bi, chunk, read_share) -> bool:
        """Frame + ship one batch over the active transport.  Returns
        False when the stop event cut a blocked wait short."""
        if writer is not None:
            from .ring import SlotOverflow

            got = writer.acquire(stop)
            if got is None:
                return False
            slot, wait_s = got
            t0 = time.perf_counter()
            try:
                if chaos is not None and chaos.force_overflow():
                    raise SlotOverflow("chaos: forced slot overflow")
                n, L, overflow = writer.frame(chunk, line_len, slot)
            except SlotOverflow:
                # No shared memory for this one batch (its segment could
                # not be reserved, or chaos forced it): give the slot
                # back and ship it pickled.
                writer.putback(slot)
            else:
                from .ring import SlotFrame

                desc = SlotFrame(
                    shard=shard.index, index=bi, slot=slot,
                    n_lines=n if len(chunk) else 0, line_len=L,
                    payload_len=len(chunk), overflow=overflow,
                    read_s=read_share,
                    encode_s=time.perf_counter() - t0,
                    slot_wait_s=wait_s,
                    generation=writer.next_generation(slot),
                    segment=writer.segment_of(slot),
                )
                if chaos is not None:
                    chaos.corrupt(desc)
                if not put((MSG_SLOT, desc)):
                    writer.putback(slot)
                    return False
                writer.note_sent(slot)
                return True
        else:
            wait_s = 0.0
        t0 = time.perf_counter()
        buf, lengths, overflow = encode_blob(chunk, line_len=line_len)
        encode_s = time.perf_counter() - t0
        n = int(buf.shape[0]) if len(chunk) else 0
        eb = EncodedBatch(
            shard=shard.index,
            index=bi,
            payload=chunk,
            buf=buf,
            lengths=lengths,
            overflow=list(overflow),
            n_lines=n,
            read_s=read_share,
            encode_s=encode_s,
            slot_wait_s=wait_s,
        )
        return put((MSG_BATCH, eb))

    try:
        for shard in shards:
            skip = resume.get(shard.index, 0) if resume else 0
            if chaos is not None:
                chaos.on_shard_start(shard.index)
            t_shard = time.perf_counter()
            t0 = time.perf_counter()
            payload = read_shard_payload(sources[shard.source], shard)
            read_s = time.perf_counter() - t0
            ranges = split_batches(payload, batch_lines)
            shard_lines = 0
            read_share = read_s / max(1, len(ranges))
            for bi, (p0, p1) in enumerate(ranges):
                if bi < skip:
                    continue  # replay: already delivered by a previous life
                if chaos is not None:
                    chaos.before_batch()
                chunk = payload[p0:p1]
                if not emit_batch(shard, bi, chunk, read_share):
                    return
                if chaos is not None:
                    chaos.after_emit()
                shard_lines += _count_lines(chunk)
                if delay_s:
                    time.sleep(delay_s)
            if chaos is not None and chaos.drop_done(shard.index):
                return  # injected protocol stall: vanish without DONE
            if not put((
                MSG_SHARD_DONE,
                shard.index,
                time.perf_counter() - t_shard,
                shard_lines,
                len(payload),
            )):
                return
        put((MSG_DONE, worker_id))
    except hard_exit:
        return  # injected hard crash (thread flavor): no relay, no DONE
    except Exception:  # noqa: BLE001 — relay to the consumer, never die silent
        try:
            put((MSG_ERROR, worker_id, traceback.format_exc()))
        except Exception:  # noqa: BLE001 — queue already torn down
            pass
    finally:
        if writer is not None:
            writer.close()


class _StopWatch:
    """``stop_event`` plus orphan detection: a worker whose logical
    parent (the pool's consumer process) died without close() — SIGKILL,
    test-harness timeout — must exit on its own.  Wedged orphans would
    otherwise spin on their put/acquire loops forever, holding the
    resource-tracker pipe open (so crashed-consumer arenas never get
    unlinked) and any inherited stdout/stderr pipes (so a harness
    waiting on the consumer's output hangs).  The parent sentinel is
    polled at most once per second.  Armed ONLY for process workers
    (``watch_parent=True``): for them ``mp.parent_process()`` is the
    consumer itself; a thread worker runs INSIDE the consumer, whose
    own parent dying is not the consumer dying."""

    __slots__ = ("_event", "_parent", "_next_check")

    def __init__(self, stop_event, watch_parent: bool = False):
        self._event = stop_event
        self._parent = None
        if watch_parent:
            try:
                import multiprocessing as mp

                self._parent = mp.parent_process()
            except Exception:  # noqa: BLE001 — detection is best-effort
                pass
        self._next_check = 0.0

    def is_set(self) -> bool:
        if self._event.is_set():
            return True
        if self._parent is not None:
            now = time.monotonic()
            if now >= self._next_check:
                self._next_check = now + 1.0
                if not self._parent.is_alive():
                    return True
        return False


def _count_lines(chunk: bytes) -> int:
    """encode_blob's line count without framing: a trailing newline
    ends the last line, it never starts a new one.  THE home of that
    counting rule — shard_done accounting here and ``_BlobLines``'s
    bytes branch (tpu/batch.py) both call it; keep any framing-rule
    change in one place."""
    if not chunk:
        return 0
    n = chunk.count(b"\n")
    return n if chunk.endswith(b"\n") else n + 1


# Threads-mode producers can update the shared queue-depth gauge on every
# put (the consumer only sees depth at get time); process-mode workers
# live in another registry, so the parent tracks depth with a shared
# put-counter (the ``puts`` arg of run_worker) minus its own get count.
def make_instrumented_queue(q, depth_cb: Optional[Callable[[], None]]):
    if depth_cb is None:
        return q

    class _Wrapped:
        def put(self, item, timeout=None):
            q.put(item, timeout=timeout)
            depth_cb()

        def get(self, timeout=None):
            return q.get(timeout=timeout)

        def qsize(self) -> int:
            return q.qsize()

    return _Wrapped()
