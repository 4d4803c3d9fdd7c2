"""Shared-memory ring transport: the zero-copy worker->consumer lane.

The pickled transport (PR 3) serializes every ``EncodedBatch`` crossing
a worker->consumer queue: the frame arrays and the retained rescue
payload are pickled, copied through the pipe in 64 KiB chunks, and
unpickled again — two-plus full memcpy passes (one of them with the
consumer's GIL held) per batch before the device sees a byte.  This
module replaces the payload lane with a per-worker
``multiprocessing.shared_memory`` arena carved into fixed slots:

- the worker ACQUIRES a free slot id from its ``free_q`` (an empty free
  queue blocks the worker — slot exhaustion IS the backpressure signal
  the bounded queues provide in pickle mode);
- it frames the batch DIRECTLY into slot-backed numpy views (the exact
  ``parse_blob`` framing via :func:`logparser_tpu.native.encode_blob`'s
  ``alloc`` hook) and memcpys the raw payload bytes beside it (kept for
  lazy oracle rescue, same contract as the pickled transport);
- the descriptor queue carries only a tiny :class:`SlotFrame` (slot id,
  shapes, sequence, timings) — the multi-MB batch body never touches a
  pipe;
- the consumer MAPS the slot zero-copy (``np.frombuffer`` views over
  the arena) into a :class:`RingBatch` and RELEASES the slot id back to
  ``free_q`` once the batch is done with it (after device upload and
  rescue-payload use — ``parse_batch_stream`` releases post-
  materialization; ``FeederPool.batches()`` detaches by default).

Slot layout (``slot_bytes``-aligned offsets, 8-byte slot alignment so
the int32 lengths view is aligned)::

    [0 .. 4*B)                lengths  int32[B]
    [align8(4*B) .. +B*L)     buf      uint8[B, L]
    [.. +payload_len)         payload  raw line bytes (with '\\n's)

Arenas are created sparse: a slot's tmpfs pages are faulted in by the
worker that first frames into it, in parallel with its framing, never
by a serial zero pass on the consumer.

A batch whose framed size exceeds ``slot_bytes`` (a large batch at a
long line bucket) is framed into a SEGMENT of its own: the worker
creates a shared-memory segment of exactly the frame's size, named
after the slot it acquired (:func:`segment_name`), reserves its pages
(``os.posix_fallocate``: a full tmpfs fails here with ENOSPC, never with
SIGBUS mid-frame) and frames into it with the same layout at offset 0.
The slot id stays the backpressure token — a worker never has more
than ``n_slots`` frames outstanding, whichever memory backs them — and
the generation ledger covers both.  The consumer maps the segment
zero-copy; releasing the slot unlinks the segment's name, and
:meth:`RingBatch.detach` keeps the mapping alive under owned views
instead of copying the frame.  Only a failed reservation (ENOSPC)
sends a batch over the pickled lane.

Cleanup: the consumer process CREATES the arenas and the resource
tracker holds their registrations, so a crashed consumer still gets
its segments unlinked.  Workers only attach — pre-3.13 that registers
with the tracker too, but forkserver/spawn children SHARE the parent's
tracker process, so the attach-side registration dedupes into the one
the consumer already holds (no premature unlink, no double-unregister;
see ``SlotWriter.__init__``) and the single unlink on pool close — or
on consumer crash, via the tracker — clears it.  Frame-sized segments
are created (and registered) by the worker on that same shared tracker
and unlinked by whoever ends their life: the consumer on release, the
worker when it gives an unsent slot back, and :meth:`SlotRing.close`
sweeps every slot's name (a worker that died between creating a segment
and sending its descriptor).  Orphaned workers
(consumer SIGKILLed) self-terminate via the parent-death watch in
``run_worker``, so nothing pins the tracker pipe open.  The module is
jax-free and import-cheap (worker processes load it).
"""
from __future__ import annotations

import errno
import mmap
import os
import secrets
import time
from dataclasses import dataclass, field
from queue import Empty
from typing import Any, List, Optional, Tuple

import numpy as np

import logging

from .worker import EncodedBatch, note_teardown_error

LOG = logging.getLogger(__name__)

#: Slot alignment: keeps every slot's int32 lengths view 4-byte aligned
#: (and leaves room for wider frame dtypes later).
SLOT_ALIGN = 8

#: /dev/shm segment name prefix — the leak checks in feeder_smoke and
#: tests key on it (arenas and frame-sized segments alike).
RING_NAME_PREFIX = "lpring"

try:  # POSIX shared memory: what multiprocessing.shared_memory uses
    import _posixshmem
    from multiprocessing import resource_tracker
except ImportError:  # pragma: no cover — no frame-sized segments here
    _posixshmem = None


def segment_name(ring_name: str, slot: int) -> str:
    """The name of the frame-sized segment backing ``slot`` of arena
    ``ring_name``.  One name per slot is enough: a segment lives only
    while its slot's token is leased, and every path that returns the
    token unlinks the name first."""
    return f"{ring_name}s{slot}"


def _create_segment(name: str, size: int) -> mmap.mmap:
    """Create segment ``name`` of ``size`` bytes with its pages reserved
    and map it.  Registered with the resource tracker (the consumer's,
    shared by forkserver/spawn workers) like an arena, so a crashed
    consumer's segments are unlinked too.  Raises ``OSError(ENOSPC)``
    when tmpfs cannot hold it."""
    path = "/" + name
    fd = _posixshmem.shm_open(path, os.O_CREAT | os.O_EXCL | os.O_RDWR,
                              mode=0o600)
    try:
        resource_tracker.register(path, "shared_memory")
        try:
            os.posix_fallocate(fd, 0, size)
            return mmap.mmap(fd, size)
        except BaseException:
            unlink_segment(name)
            raise
    finally:
        os.close(fd)


def _attach_segment(name: str, size: int) -> mmap.mmap:
    """Map an existing segment; the mapping alone keeps it alive (the
    views over it own it, the file descriptor is closed here)."""
    fd = _posixshmem.shm_open("/" + name, os.O_RDWR, mode=0o600)
    try:
        if os.fstat(fd).st_size < size:
            raise ValueError(f"segment {name} is smaller than {size}B")
        return mmap.mmap(fd, size)
    finally:
        os.close(fd)


def unlink_segment(name: str) -> bool:
    """Unlink segment ``name`` if it exists (and drop its tracker
    registration).  Live mappings stay valid.  Returns whether it did."""
    if _posixshmem is None:
        return False
    path = "/" + name
    try:
        _posixshmem.shm_unlink(path)
    except FileNotFoundError:
        return False
    resource_tracker.unregister(path, "shared_memory")
    return True


def ring_available() -> bool:
    """Can this platform back a shared-memory ring at all?"""
    try:
        from multiprocessing import shared_memory  # noqa: F401

        return True
    except ImportError:
        return False


def _shared_memory_cls():
    """SharedMemory with a close() tolerant of live exported views.

    The stream's tail batches materialize AFTER the pool (and its
    arenas) close — their payload views legitimately outlive close(),
    which makes ``mmap.close()`` raise BufferError both at close time
    and again from ``SharedMemory.__del__`` at GC.  The segment is
    still unlinked either way (names never leak); the mapping itself
    dies with the last view, so swallowing the BufferError is correct,
    not a leak."""
    from multiprocessing import shared_memory

    class _QuietSharedMemory(shared_memory.SharedMemory):
        def close(self) -> None:
            try:
                super().close()
            except BufferError:
                pass

    return _QuietSharedMemory


class SlotOverflow(Exception):
    """The framed batch has no shared memory to go to (fall back to
    pickle): its frame-sized segment could not be reserved."""


class RingFault(RuntimeError):
    """A descriptor failed map-time validation.  ``reason``:

    - ``"generation"``: the descriptor's slot-use generation does not
      match the consumer's ledger — a slot-reuse race or a stale/
      duplicated descriptor; the slot's contents cannot be trusted;
    - ``"descriptor"``: structurally invalid fields (slot id out of
      range, negative shapes, a layout that exceeds the slot).

    The supervised pool recovers per batch (in-process re-frame of the
    expected batch — delivery order makes it unambiguous) and demotes
    the worker off the ring past ``ring_fault_threshold`` faults; an
    unsupervised pool surfaces the fault as a FeederError instead of
    handing corrupt bytes downstream.

    ``stale`` marks the generation sub-case where the descriptor's
    generation is BEHIND the ledger: a replay of a send already mapped
    and delivered.  Re-framing that batch would duplicate it in the
    stream and releasing its slot would double-free a lease someone
    else may hold — the pool DROPS a stale descriptor instead."""

    def __init__(self, reason: str, detail: str = "", stale: bool = False):
        super().__init__(f"ring fault ({reason}): {detail}")
        self.reason = reason
        self.stale = stale


def slot_layout(n: int, line_len: int, payload_len: int) -> Tuple[int, int, int]:
    """(buf_offset, payload_offset, total_bytes) of one framed batch
    inside its slot — the single layout definition writer and reader
    share."""
    lengths_bytes = 4 * max(n, 1)
    buf_off = -(-lengths_bytes // SLOT_ALIGN) * SLOT_ALIGN
    payload_off = buf_off + max(n, 1) * line_len
    return buf_off, payload_off, payload_off + payload_len


@dataclass
class SlotFrame:
    """The descriptor that crosses the queue instead of the batch body.
    Everything here is a handful of ints/floats — pickling it is noise."""

    shard: int                  # global shard index
    index: int                  # batch index within the shard
    slot: int                   # slot id inside the worker's arena
    n_lines: int
    line_len: int               # framed L (buf is [n_lines, line_len])
    payload_len: int
    overflow: List[int] = field(default_factory=list)
    read_s: float = 0.0
    encode_s: float = 0.0
    slot_wait_s: float = 0.0    # time the worker blocked acquiring the slot
    #: Slot-use generation: how many descriptors have been SENT for this
    #: slot before this one.  The consumer keeps its own per-slot ledger
    #: of mapped descriptors; since every sent descriptor is mapped
    #: exactly once and in order, the two agree unless a reuse race, a
    #: duplicate, or corruption intervened — verified in SlotRing.map,
    #: counted as feeder_ring_generation_mismatch_total by the pool.
    generation: int = 0
    #: Name of the frame-sized segment holding this batch ("" = the
    #: slot itself holds it).
    segment: str = ""


@dataclass
class RingSpec:
    """Picklable handle a worker needs to attach one arena: segment
    name, geometry, and the free-slot queue (ForkingPickler ships
    mp.Queue through Process args)."""

    name: str
    slot_bytes: int
    n_slots: int
    free_q: Any


@dataclass
class RingBatch(EncodedBatch):
    """An EncodedBatch whose payload/buf/lengths are zero-copy views
    into a ring slot or its frame-sized segment.  The slot stays leased
    to this batch until :meth:`release` — ``parse_batch_stream``
    releases after the batch's materialization (device upload done,
    rescue payload consumed); :meth:`detach` converts to an owned plain
    batch and releases immediately (the ``FeederPool.batches()``
    default)."""

    ring: Any = None            # consumer-side SlotRing
    slot: int = -1
    released: bool = False
    segment: str = ""           # SlotFrame.segment

    def release(self) -> None:
        if self.ring is not None and not self.released:
            self.released = True
            self.ring.release(self.slot)

    def detach(self) -> EncodedBatch:
        if self.segment:
            # The segment's mapping is this batch's alone (no slot
            # recycles under it): the views become the owned arrays,
            # and release() drops only the name and the token.
            payload, buf, lengths = self.payload, self.buf, self.lengths
        else:
            payload = bytes(self.payload)
            buf = np.array(self.buf, copy=True)
            lengths = np.array(self.lengths, copy=True)
        eb = EncodedBatch(
            shard=self.shard,
            index=self.index,
            payload=payload,
            buf=buf,
            lengths=lengths,
            overflow=list(self.overflow),
            n_lines=self.n_lines,
            read_s=self.read_s,
            encode_s=self.encode_s,
        )
        eb.slot_wait_s = self.slot_wait_s
        self.release()
        return eb


class SlotWriter:
    """Worker-side arena access: acquire a slot, frame into it.

    In process mode the worker attaches by name from a :class:`RingSpec`
    (and drops its attach-side resource_tracker registration, see module
    docstring); in thread-ring mode the pool passes its own ``shm`` so
    all threads share one mapping."""

    def __init__(self, spec: RingSpec, shm: Any = None):
        self.spec = spec
        # Per-slot count of descriptors SENT (not merely acquired:
        # overflow/stop putbacks send nothing and must not advance the
        # generation the consumer's ledger expects).
        self._sent = [0] * spec.n_slots
        # Per-slot name of the segment framed for it and not yet sent
        # (the worker still owns it: putback unlinks it).
        self._segment = [""] * spec.n_slots
        self._owns_attach = shm is None
        if shm is None:
            # Attaching registers with the resource tracker too (pre-3.13
            # has no track=False) — harmless here: forkserver/spawn
            # children share the PARENT's tracker process, so the
            # registration dedupes into the one the creating consumer
            # already holds, and the single unlink on pool close (or on
            # consumer crash, via the tracker) clears it.
            shm = _shared_memory_cls()(name=spec.name)
        self.shm = shm

    def acquire(self, stop_event) -> Optional[Tuple[int, float]]:
        """Next free slot id, blocking until one is released (the
        backpressure wait) — ``(slot, waited_seconds)``, or None when
        ``stop_event`` fired first."""
        t0 = time.perf_counter()
        while True:
            if stop_event.is_set():
                return None
            try:
                slot = self.spec.free_q.get(timeout=0.1)
                return int(slot), time.perf_counter() - t0
            except Empty:
                continue

    def putback(self, slot: int) -> None:
        """Return an acquired-but-unsent slot (overflow/stop paths),
        unlinking the segment framed for it, if any."""
        if self._segment[slot]:
            unlink_segment(self._segment[slot])
            self._segment[slot] = ""
        self.spec.free_q.put(slot)

    def next_generation(self, slot: int) -> int:
        """The generation a descriptor for ``slot`` must carry NOW
        (descriptors sent so far); advance with :meth:`note_sent` only
        after the descriptor actually crossed the queue."""
        return self._sent[slot]

    def segment_of(self, slot: int) -> str:
        """The segment the last :meth:`frame` into ``slot`` used ("" =
        the slot itself): the descriptor's ``segment``."""
        return self._segment[slot]

    def note_sent(self, slot: int) -> None:
        self._sent[slot] += 1
        self._segment[slot] = ""  # the consumer owns it now

    def _reserve_segment(self, slot: int, size: int) -> mmap.mmap:
        if _posixshmem is None:
            raise SlotOverflow(f"batch needs {size}B > slot_bytes="
                               f"{self.spec.slot_bytes}, no POSIX shm")
        name = segment_name(self.spec.name, slot)
        try:
            mm = _create_segment(name, size)
        except OSError as e:
            if e.errno != errno.ENOSPC:
                raise
            raise SlotOverflow(f"no room for a {size}B segment: {e}") from e
        self._segment[slot] = name
        return mm

    def frame(self, chunk, line_len: int, slot: int):
        """Frame ``chunk`` (one batch's raw line bytes) directly into
        ``slot`` — or, when the frame outgrows the slot, into a segment
        of the frame's own size (:meth:`segment_of`): encode_blob packs
        the [B, L] buffer and lengths into shared-memory views, the
        payload is memcpy'd beside them.  Returns ``(n_lines, L,
        overflow)``; raises :class:`SlotOverflow` when the segment
        cannot be reserved."""
        from ..native import encode_blob

        carved: List[Any] = []  # [memory, base offset, payload offset]

        def alloc(n: int, L: int):
            buf_off, payload_off, total = slot_layout(n, L, len(chunk))
            if total <= self.spec.slot_bytes:
                mv, base = self.shm.buf, slot * self.spec.slot_bytes
            else:
                mv, base = self._reserve_segment(slot, total), 0
            carved[:] = [mv, base, payload_off]
            lengths = np.frombuffer(mv, dtype=np.int32, count=n, offset=base)
            buf = np.frombuffer(
                mv, dtype=np.uint8, count=n * L, offset=base + buf_off
            ).reshape(n, L)
            return buf, lengths

        buf, lengths, overflow = encode_blob(
            chunk, line_len=line_len, alloc=alloc
        )
        mv, base, payload_off = carved
        if len(chunk):
            dst = np.frombuffer(
                mv, dtype=np.uint8, count=len(chunk), offset=base + payload_off
            )
            dst[:] = np.frombuffer(chunk, dtype=np.uint8)
            del dst
        n, L = buf.shape
        del buf, lengths
        if self._segment[slot]:
            # The bytes live in the segment, not in this mapping: the
            # consumer maps the segment by name.
            try:
                mv.close()
            except BufferError:
                pass  # a view still pins it: it goes with the view
        return int(n), int(L), list(overflow)

    def close(self) -> None:
        for slot, name in enumerate(self._segment):
            if name:  # framed, never sent: nobody else will unlink it
                unlink_segment(name)
                self._segment[slot] = ""
        if self._owns_attach:
            try:
                self.shm.close()
            except Exception as e:  # noqa: BLE001 — teardown is best-effort
                note_teardown_error(LOG, "SlotWriter.close", e)


class SlotRing:
    """Consumer-side owner of one worker's arena: creates it (sparse),
    seeds the free queue, maps descriptors — slot or frame-sized
    segment — into :class:`RingBatch` views, recycles released slots,
    and unlinks on close."""

    def __init__(self, slot_bytes: int, n_slots: int, free_q: Any,
                 name_hint: str = ""):
        shm_cls = _shared_memory_cls()
        if slot_bytes % SLOT_ALIGN:
            slot_bytes += SLOT_ALIGN - slot_bytes % SLOT_ALIGN
        self.slot_bytes = int(slot_bytes)
        self.n_slots = int(n_slots)
        self.free_q = free_q
        shm = None
        for _ in range(8):
            name = (f"{RING_NAME_PREFIX}_{name_hint}_"
                    f"{secrets.token_hex(4)}").strip("_")
            try:
                shm = shm_cls(
                    name=name, create=True, size=self.slot_bytes * self.n_slots
                )
                break
            except FileExistsError:  # pragma: no cover — 32-bit token race
                continue
        if shm is None:  # pragma: no cover
            raise RuntimeError("could not allocate a uniquely-named arena")
        self.shm = shm
        self._closed = False
        # Consumer-side generation ledger: descriptors MAPPED per slot
        # (the counterpart of SlotWriter._sent — see SlotFrame.generation).
        self._gen = [0] * self.n_slots
        for slot in range(self.n_slots):
            free_q.put(slot)

    def spec(self) -> RingSpec:
        return RingSpec(self.shm.name, self.slot_bytes, self.n_slots,
                        self.free_q)

    def map(self, f: SlotFrame) -> RingBatch:
        """One descriptor -> zero-copy RingBatch over the slot's views.

        Validates the descriptor FIRST (:class:`RingFault`): a corrupt
        slot id or layout would otherwise read out of the arena, and a
        stale generation would silently deliver a recycled slot's bytes
        as this batch's."""
        if not (0 <= f.slot < self.n_slots):
            raise RingFault(
                "descriptor", f"slot {f.slot} outside [0, {self.n_slots})"
            )
        # A descriptor carrying generation >= the ledger is a SEND not
        # yet consumed — it advances the ledger whether it maps or
        # faults below, so a faulted slot's next legitimate descriptor
        # still maps cleanly once the pool releases the slot back.  One
        # carrying generation < the ledger is a replay of a send already
        # consumed (stale duplicate): its generation was counted when
        # the original mapped, so the ledger must NOT move again.
        expected = self._gen[f.slot]
        if f.generation >= expected:
            self._gen[f.slot] += 1
        if f.n_lines < 0 or f.line_len < 0 or f.payload_len < 0:
            raise RingFault(
                "descriptor",
                f"negative shape (n={f.n_lines}, L={f.line_len}, "
                f"payload={f.payload_len})",
            )
        n = max(f.n_lines, 1)
        buf_off, payload_off, total = slot_layout(
            n, f.line_len, f.payload_len
        )
        if f.segment:
            if f.segment != segment_name(self.shm.name, f.slot):
                raise RingFault(
                    "descriptor",
                    f"segment {f.segment!r} does not belong to slot "
                    f"{f.slot}",
                )
        elif total > self.slot_bytes:
            raise RingFault(
                "descriptor",
                f"layout needs {total}B > slot_bytes={self.slot_bytes}",
            )
        if f.generation != expected:
            raise RingFault(
                "generation",
                f"slot {f.slot} descriptor generation {f.generation} != "
                f"expected {expected} (slot-reuse race or stale "
                "descriptor)",
                stale=f.generation < expected,
            )
        if f.segment:
            try:
                mv, base = _attach_segment(f.segment, total), 0
            except (OSError, ValueError) as e:
                raise RingFault(
                    "descriptor", f"segment {f.segment!r}: {e}"
                ) from e
        else:
            mv, base = self.shm.buf, f.slot * self.slot_bytes
        lengths = np.frombuffer(
            mv, dtype=np.int32, count=n, offset=base
        )[: f.n_lines]
        buf = np.frombuffer(
            mv, dtype=np.uint8, count=n * f.line_len, offset=base + buf_off
        ).reshape(n, f.line_len)[: f.n_lines]
        payload = np.frombuffer(
            mv, dtype=np.uint8, count=f.payload_len, offset=base + payload_off
        )
        return RingBatch(
            shard=f.shard,
            index=f.index,
            payload=payload,
            buf=buf,
            lengths=lengths,
            overflow=list(f.overflow),
            n_lines=f.n_lines,
            read_s=f.read_s,
            encode_s=f.encode_s,
            slot_wait_s=f.slot_wait_s,
            ring=self,
            slot=f.slot,
            segment=f.segment,
        )

    def release(self, slot: int) -> None:
        """Take back ``slot``'s token, unlinking the segment framed for
        it, if any: whoever holds the token owns the slot's segment name
        (live mappings of it stay valid)."""
        unlink_segment(segment_name(self.shm.name, slot))
        if not self._closed:
            try:
                self.free_q.put(slot)
            except Exception as e:  # noqa: BLE001 — queue torn down mid-release
                note_teardown_error(LOG, "SlotRing.release", e)

    def inplace_bytes(self, f: SlotFrame) -> int:
        """Bytes this descriptor delivered through shared memory (arena
        or segment) instead of the pipe (the
        feeder_ring_bytes_inplace_total increment)."""
        return 4 * f.n_lines + f.n_lines * f.line_len + f.payload_len

    def close(self) -> None:
        """Unmap and unlink the arena, and unlink every slot's segment
        name.  Idempotent; outstanding RingBatch views die with the
        mapping — callers must detach batches that outlive the pool."""
        if self._closed:
            return
        self._closed = True
        for slot in range(self.n_slots):
            unlink_segment(segment_name(self.shm.name, slot))
        # mp.Queue's feeder thread would otherwise keep the process
        # alive waiting to flush released slot ids nobody will read.
        if hasattr(self.free_q, "cancel_join_thread"):
            self.free_q.cancel_join_thread()
        try:
            self.shm.close()
        except BufferError:
            # Live RingBatch views pin the mapping: the segment still
            # gets unlinked below (names never leak); the mapping itself
            # goes when the last view does.  Expected, not counted.
            pass
        except Exception as e:  # noqa: BLE001
            note_teardown_error(LOG, "SlotRing.close", e)
        try:
            self.shm.unlink()
        except FileNotFoundError:
            pass  # already unlinked (resource tracker beat us to it)
        except Exception as e:  # noqa: BLE001
            note_teardown_error(LOG, "SlotRing.unlink", e)
