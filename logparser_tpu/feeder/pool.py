"""FeederPool: the multi-process ingest fabric behind one iterator.

``FeederPool(sources).batches()`` turns raw log sources (file paths or
in-memory blobs) into a steady, ORDERED stream of framed
:class:`~logparser_tpu.feeder.worker.EncodedBatch` items:

- the shard planner tiles the sources into byte-range shards with
  newline-boundary healing (``feeder/shards.py`` — the reference's
  InputFormat split semantics);
- N workers (processes by default, threads as fallback or on request)
  read + frame their shards with the ``parse_blob`` framing and ship
  them over one of two TRANSPORTS:

  * ``"ring"`` (process default): each worker frames directly into a
    per-worker shared-memory slot arena (``feeder/ring.py``) and the
    queue carries only small slot descriptors — zero-copy bodies, with
    slot exhaustion as the backpressure signal.  Arenas are created
    sparse (the workers fault a slot's pages in as they first frame into
    it), and a frame larger than its slot goes to a shared-memory
    segment of its own size that the worker creates under the slot's
    token — only a segment that cannot be reserved (tmpfs full) ships
    that batch pickled;
  * ``"pickle"`` (escape hatch ``LOGPARSER_TPU_FEEDER_PICKLE=1``, or
    the fallback when shared memory is unavailable): whole batches
    pickle through BOUNDED per-worker queues — a full queue blocks its
    worker.  Thread workers default to the direct in-process hand-off
    (``"inline"``; nothing to serialize), but accept ``transport=
    "ring"`` explicitly (the ring mechanics are address-space agnostic
    — tests exercise wraparound/exhaustion without process spawns);

- the consumer drains shards in global order (shard i lives in worker
  ``i % N``'s queue), so delivery order equals single-process
  ``parse_blob`` order with no reorder buffer and no deadlock: each
  queue has exactly one producer and one consumer.

SUPERVISION (default ON; ``feeder/supervisor.py`` is the policy brain):
a crashed, errored, or deadline-stalled worker no longer aborts the run.
The pool reaps it, requeues its in-flight shard, and respawns it with
bounded per-rung restarts and exponential backoff — the respawned
incarnation REPLAYS the shard from the last fully-delivered batch
boundary (framing is deterministic, so recovered output is
byte-identical to an undisturbed run; respawns always get a fresh queue
and a fresh ring, so replay can never interleave with stale in-flight
messages).  A shard that kills its workers ``poison_threshold`` times
is QUARANTINED: re-framed in-process over the host (numpy) framer path
(``feeder_shards_quarantined_total``) so a poison shard costs its own
throughput, never the run.  Repeated transport faults walk a worker
down the demotion ladder ring -> pickle -> inline
(``feeder_transport_demotions_total``); ring descriptors are
generation-verified at map time, and a mismatch is recovered per batch
by re-framing the expected batch in-process
(``feeder_ring_generation_mismatch_total``) instead of delivering a
recycled slot's bytes.  ``supervise=False`` restores the fail-stop
PR-3/PR-5 behavior (one fault = FeederError).  The chaos harness
(``tools/chaos.py``, ``LOGPARSER_TPU_CHAOS``) injects these failures on
purpose; ``tests/test_faults.py`` and ``make chaos-smoke`` hold the
recovered output to byte parity.

``batches()`` DETACHES ring batches by default (owned copies, slot
released immediately) so callers may hold arbitrarily many; pass
``detach=False`` to receive zero-copy :class:`~logparser_tpu.feeder.
ring.RingBatch` views and call ``release()`` yourself.  ``feed(parser)``
pipes the zero-copy stream through ``TpuBatchParser.parse_batch_stream``
(which adopts pre-encoded batches without re-framing, stages the next
batch's H2D upload while the current one computes, and releases each
slot after the batch materializes), yielding one BatchResult per batch
in corpus order.

Telemetry (the PR-2 metrics registry, docs/OBSERVABILITY.md):
``feeder_bytes_read_total``, ``feeder_lines_total``,
``feeder_batches_total``, ``feeder_shards_total`` counters; the
``feeder_queue_depth`` gauge (producer-updated in threads mode, shared
put-counters minus consumer gets in process mode — live on every
platform, qsize-less or not); ``feeder_starvation_seconds_total`` (wall
time the consumer spent blocked on an empty queue after the first
batch — the "is the chip starving" number) and
``feeder_fill_seconds_total`` (the first dequeue: worker start, first
read and frame, first transfer); ring counters
``feeder_ring_slot_wait_seconds_total`` (worker backpressure wait,
shipped in descriptors),
``feeder_ring_bytes_inplace_total`` (bytes that crossed via shared
memory instead of a pipe), ``feeder_segment_batches_total`` (batches
mapped from a frame-sized segment) and
``feeder_ring_pickle_fallback_total`` (batches that found no shared
memory and crossed pickled); recovery counters ``feeder_worker_restarts_total``,
``feeder_shards_requeued_total``, ``feeder_shards_quarantined_total``,
``feeder_transport_demotions_total{from,to}``,
``feeder_ring_generation_mismatch_total``,
``feeder_ring_descriptor_faults_total``,
``feeder_teardown_errors_total{site}``; per-shard/per-batch stage
timings via ``observe_stage`` (``feeder_read``, ``feeder_encode``,
``feeder_shard``: other processes' seconds, carried in queue items); the
consumer's own phases as ``pipeline_stage`` spans (``feeder_start``,
``feeder_fill``, ``feeder_get``, ``feeder_detach``, ``feeder_close``).
"""
from __future__ import annotations

import os
import queue as _queue
import threading
import time
import weakref
from typing import Any, Dict, Iterator, List, Optional, Sequence

from ..observability import (
    log_warning_once,
    metrics,
    observe_stage,
    pipeline_stage,
)
from .ring import RingFault
from .shards import (
    DEFAULT_SHARD_BYTES,
    Shard,
    SourceT,
    normalize_sources,
    plan_shards,
    read_shard_payload,
)
from .supervisor import (
    FeederSupervisor,
    SupervisorPolicy,
    WorkerFault,
)
from .worker import (
    MSG_BATCH,
    MSG_ERROR,
    MSG_SHARD_DONE,
    MSG_SLOT,
    EncodedBatch,
    make_instrumented_queue,
    note_teardown_error,
    run_worker,
    split_batches,
)

import logging

LOG = logging.getLogger(__name__)

DEFAULT_BATCH_LINES = 16384

#: Escape hatch: force the pickled transport everywhere (parity suite
#: asserts both transports byte-identical; this is the rollback lever).
PICKLE_ENV = "LOGPARSER_TPU_FEEDER_PICKLE"

#: Fault-injection env var (tools/chaos.py grammar; single definition —
#: the spec is parsed HERE, in the consumer, and shipped to workers
#: through run_worker args: forkserver children inherit the
#: forkserver's env, not the pool's at spawn time).
from ..tools.chaos import CHAOS_ENV  # noqa: E402


class _QueuePump:
    """Consumer-side drainer for a PROCESS worker's queue.

    ``mp.Queue.get(timeout)`` only bounds the readiness poll(): once any
    bytes are buffered, ``recv_bytes()`` blocks until the whole
    length-prefixed frame arrives.  A worker that hard-dies MID-WRITE
    (os._exit / SIGKILL while its queue feeder thread flushes a
    multi-part pickled batch) leaves a partial frame that poll() calls
    ready but recv never completes — a consumer reading the queue
    directly would hang forever inside the very supervision layer meant
    to recover from that death.  The pump takes that risk instead: a
    daemon thread does the blocking gets and forwards messages — FIFO,
    1-deep buffer, so backpressure stays the mp queue bound plus one —
    onto a plain thread-safe buffer the consumer polls.  If the pump
    wedges on a truncated frame it is simply abandoned with its retired
    queue at reap time; the consumer's poll cadence never depends on
    it.  (Thread workers need no pump: queue.Queue hand-off is atomic.)
    """

    __slots__ = ("_q", "_buf", "_stop", "_thread")

    def __init__(self, q, name: str):
        self._q = q
        self._buf: _queue.Queue = _queue.Queue(maxsize=1)
        self._stop = False
        self._thread = threading.Thread(
            target=self._run, name=f"logparser-tpu-pump-{name}",
            daemon=True,
        )
        self._thread.start()

    def _run(self) -> None:
        while not self._stop:
            try:
                item = self._q.get(timeout=0.1)
            except _queue.Empty:
                continue
            except (EOFError, OSError):
                return  # queue torn down under us (pool close)
            while not self._stop:
                try:
                    self._buf.put(item, timeout=0.1)
                    break
                except _queue.Full:
                    continue

    def get(self, timeout=None):
        return self._buf.get(timeout=timeout)

    def get_nowait(self):
        return self._buf.get_nowait()

    def qsize(self) -> int:
        """Messages pulled off the mp queue but not yet consumed."""
        return self._buf.qsize()

    def retire(self) -> None:
        """Stop forwarding (best effort: a pump wedged in recv_bytes
        stays blocked — daemon, abandoned with its dead queue)."""
        self._stop = True


class FeederError(RuntimeError):
    """The feeder could not complete its corpus: with supervision OFF,
    any worker death; with supervision ON, only a shard that failed even
    the in-process quarantine path (i.e. the data itself is unreadable
    or unframeable in this process)."""


def default_feeder_workers() -> int:
    """Process-parallel framing saturates around the core count; capped
    like the assembly pool so a big host doesn't fork 64 readers."""
    return max(1, min(8, os.cpu_count() or 1))


# Live pools, registered at _start() and dropped at close(): the process-
# wide backpressure signal the serving tier's admission control reads
# (docs/SERVICE.md).  A WeakSet so an abandoned pool (consumer crashed
# between start and close) can never pin itself into the signal.
_LIVE_POOLS: "weakref.WeakSet[FeederPool]" = weakref.WeakSet()


def register_backpressure_source(source: Any) -> None:
    """Register any object exposing ``backpressure() -> float`` (a 0-1
    occupancy fraction) into the process-wide
    :func:`queue_backpressure` aggregate.  FeederPools self-register at
    ``_start``; the serving tier's cross-session batch coalescer
    (:mod:`logparser_tpu.service_batching`) registers here so its
    bounded submission queue feeds the SAME admission signal
    (docs/SERVICE.md "Continuous batching").  The WeakSet means an
    abandoned source can never pin itself into the signal."""
    _LIVE_POOLS.add(source)


def deregister_backpressure_source(source: Any) -> None:
    """Drop a :func:`register_backpressure_source` registration (no-op
    when absent)."""
    _LIVE_POOLS.discard(source)


def queue_backpressure() -> float:
    """Aggregate feeder-queue occupancy across every LIVE pool in this
    process as a 0.0–1.0 fraction (worst pool wins: one saturated ring
    means the fabric is not absorbing new work, however idle the
    others).  0.0 when no pool is running or depth is unknowable.  This
    is the signal ``ParseService`` wires its per-request admission
    control to: framed batches waiting at/above the configured fraction
    of the bounded-queue capacity mean the parser is the bottleneck and
    new requests should shed with a structured BUSY frame instead of
    queueing without bound."""
    worst = 0.0
    for pool in list(_LIVE_POOLS):
        try:
            worst = max(worst, pool.backpressure())
        except Exception:  # noqa: BLE001 — a pool mid-teardown reads as idle
            continue
    return worst


def resolve_transport(requested: Optional[str], mode: str) -> str:
    """The transport a (request, worker-mode) pair actually runs:
    ``LOGPARSER_TPU_FEEDER_PICKLE=1`` wins over everything (the
    emergency rollback must not be overridable per call site); explicit
    requests are honored next; process pools default to ``ring``
    (falling back to ``pickle`` when shared memory is unavailable) and
    thread pools to the direct ``inline`` hand-off.  The degradation
    counterpart — the ladder a SUPERVISED worker walks down after
    repeated faults — is :func:`~logparser_tpu.feeder.supervisor.
    demote_transport`."""
    from ..observability import _env_truthy
    from .ring import ring_available

    if _env_truthy(PICKLE_ENV):
        return "pickle" if mode == "process" else "inline"
    if requested:
        if requested not in ("ring", "pickle", "inline"):
            raise ValueError(f"unknown feeder transport {requested!r}")
        if requested == "ring" and not ring_available():
            return "pickle" if mode == "process" else "inline"
        return requested
    if mode == "process":
        return "ring" if ring_available() else "pickle"
    return "inline"


class FeederPool:
    """See module docstring.  Parameters:

    - ``sources``: file paths and/or bytes blobs, in corpus order.
    - ``workers``: feeder worker count (default
      :func:`default_feeder_workers`, clamped to the shard count).
    - ``shard_bytes``: raw shard size for the planner.
    - ``batch_lines``: lines per emitted batch (the device batch size).
    - ``line_len``: pin the framed ``L`` (0 = per-batch length bucket,
      exactly ``parse_blob``'s default).
    - ``queue_batches``: the backpressure window, in batches — the
      per-worker queue bound (pickle/inline) and the default ring slot
      count basis (``queue_batches + 2`` slots: the extra two cover the
      batch on device and the one materializing).
    - ``transport``: ``"ring"`` / ``"pickle"`` / ``"inline"`` / None
      (auto — see :func:`resolve_transport`).
    - ``ring_slots`` / ``slot_bytes``: ring geometry overrides (slots
      per worker arena; bytes per slot, default sized for
      ``batch_lines`` lines of generous length).
    - ``use_processes``: True/False forces the worker flavor; None
      prefers processes and falls back to threads when multiprocessing
      is unavailable.  Processes default to the ``forkserver`` context
      (``spawn`` where unavailable): the parent may hold an initialized
      device runtime, which plain ``fork`` would duplicate into
      children that must never touch the chip, and ``spawn`` re-runs
      ``__main__`` (driver scripts would re-import heavily).
    - ``worker_delay_s``: per-batch producer sleep (shaping/test hook).
    - ``supervise``: worker supervision (default True — crashes are
      recovered, poison shards quarantined, transports demoted; see
      module docstring).  False restores fail-stop FeederError.
    - ``policy``: a :class:`~logparser_tpu.feeder.supervisor.
      SupervisorPolicy` overriding restart/backoff/quarantine tunables.
    - ``chaos``: a :class:`~logparser_tpu.tools.chaos.ChaosSpec` (or its
      string grammar) arming fault injection; default: parse
      ``LOGPARSER_TPU_CHAOS`` when set.
    - ``shutdown_timeout_s``: per-stage close() wait before escalating
      join -> terminate -> kill on a stuck worker.
    """

    def __init__(
        self,
        sources: Sequence[SourceT],
        workers: Optional[int] = None,
        shard_bytes: int = DEFAULT_SHARD_BYTES,
        batch_lines: int = DEFAULT_BATCH_LINES,
        line_len: int = 0,
        queue_batches: int = 4,
        transport: Optional[str] = None,
        ring_slots: Optional[int] = None,
        slot_bytes: Optional[int] = None,
        use_processes: Optional[bool] = None,
        mp_context: Optional[str] = None,
        worker_delay_s: float = 0.0,
        supervise: bool = True,
        policy: Optional[SupervisorPolicy] = None,
        chaos: Any = None,
        shutdown_timeout_s: float = 5.0,
        backpressure_signal: bool = True,
        shard_plan: Optional[Sequence[Shard]] = None,
    ):
        if not sources:
            raise ValueError("FeederPool needs at least one source")
        self._sources = normalize_sources(sources)
        if shard_plan is not None:
            # Caller-supplied plan (the durable job runner resumes a
            # partially-committed corpus by feeding only the shards it
            # still owes).  Indices must be contiguous from 0: shard
            # ownership (``index % workers``) and the positional worker
            # split (``shards[w::workers]``) both assume index ==
            # position — a caller keeping its own identity for each
            # shard renumbers with dataclasses.replace and maps back by
            # position (logparser_tpu/jobs does exactly this).
            shards = list(shard_plan)
            for i, s in enumerate(shards):
                if s.index != i:
                    raise ValueError(
                        "shard_plan indices must be contiguous from 0 "
                        f"(shard at position {i} carries index {s.index})"
                    )
                if not 0 <= s.source < len(self._sources):
                    raise ValueError(
                        f"shard_plan references source {s.source} of "
                        f"{len(self._sources)}"
                    )
            self.shards: List[Shard] = shards
        else:
            self.shards = plan_shards(self._sources, shard_bytes)
        n_workers = workers if workers else default_feeder_workers()
        self.workers = max(1, min(int(n_workers), max(1, len(self.shards))))
        self.batch_lines = int(batch_lines)
        self.line_len = int(line_len)
        self.queue_batches = max(1, int(queue_batches))
        self._requested_transport = transport
        self.ring_slots = (
            max(2, int(ring_slots)) if ring_slots
            else self.queue_batches + 2
        )
        # Default slot: room for batch_lines lines at a generous L plus
        # the raw payload — a batch that still doesn't fit (a long line
        # bucket) is framed into a segment of its own size, so this is
        # the recycled fast path's size, not a bound.
        self.slot_bytes = (
            int(slot_bytes) if slot_bytes
            else max(1 << 20, self.batch_lines * 768)
        )
        self._use_processes = use_processes
        self._mp_context = mp_context
        self._worker_delay_s = float(worker_delay_s)
        self._supervise = bool(supervise)
        self.policy = policy or SupervisorPolicy()
        self._chaos_arg = chaos
        self._chaos_spec: Any = None
        self._shutdown_timeout_s = float(shutdown_timeout_s)
        # Whether this pool feeds the process-wide queue_backpressure()
        # admission signal.  A STANDING ingest pool (the fabric keeping
        # chips fed) should; a short-lived per-request framing pool (the
        # service's _feeder_parse) must NOT — its queue sitting full for
        # the length of one request is the healthy steady state of that
        # request, not overload, and exporting it would shed every
        # concurrent request whenever one feeder-framed request runs.
        self._backpressure_signal = bool(backpressure_signal)
        self.mode: Optional[str] = None  # "process" | "thread" once started
        self.transport: Optional[str] = None  # resolved at start
        self.supervisor: Optional[FeederSupervisor] = None
        self._ctx: Any = None           # mp context (process mode)
        self._queues: List[Any] = []
        self._pumps: List[Any] = []     # per-worker _QueuePump (process mode)
        self._procs: List[Any] = []
        self._rings: List[Any] = []
        self._puts: List[Any] = []      # shared put-counters (process mode)
        self._gets: List[int] = []      # local get-counters (process mode)
        self._stops: List[Any] = []     # per-worker stop events
        self._started = False
        self._closed = False
        self._payload_cache: Optional[tuple] = None
        self._pending_quarantine: set = set()
        self._last_sweep = 0.0
        self._stats: Dict[str, Any] = {
            "shards": len(self.shards),
            "workers": self.workers,
            "batches": 0,
            "lines": 0,
            "payload_bytes": 0,
            "read_s": 0.0,
            "encode_s": 0.0,
            "starvation_s": 0.0,
            "startup_s": 0.0,
            "wall_s": 0.0,
            "queue_depth_max": 0,
            "queue_depth_mean": 0.0,
            "slot_wait_s": 0.0,
            "bytes_inplace": 0,
            "pickle_fallback_batches": 0,
            "segment_batches": 0,
            "batches_reframed": 0,
        }
        self._depth_samples = 0
        self._depth_sum = 0
        self._primed = False  # first item delivered (pipeline filled)

    # -- lifecycle -------------------------------------------------------

    def _start(self) -> None:
        if self._started:
            raise RuntimeError("FeederPool.batches() can only run once")
        self._started = True
        if self._backpressure_signal:
            _LIVE_POOLS.add(self)
        if self._chaos_arg is not None or os.environ.get(CHAOS_ENV, "").strip():
            from ..tools.chaos import ChaosSpec

            self._chaos_spec = (
                ChaosSpec.parse(self._chaos_arg)
                if isinstance(self._chaos_arg, str)
                else self._chaos_arg or ChaosSpec.from_env()
            )
        shards_of = [self._worker_plan(self.shards[w :: self.workers])
                     for w in range(self.workers)]
        if self._use_processes is not False:
            try:
                self._start_processes(shards_of)
            except Exception as e:  # noqa: BLE001 — environment-dependent
                if self._use_processes:
                    raise
                self._abort_process_start()
                log_warning_once(
                    LOG,
                    "feeder: multiprocessing unavailable "
                    f"({type(e).__name__}); falling back to threads",
                )
                self._start_threads(shards_of)
        else:
            self._start_threads(shards_of)
        if self._supervise:
            self.supervisor = FeederSupervisor(
                self.policy, self.workers, self.mode or "thread",
                self.transport or "inline",
            )

    def _abort_process_start(self) -> None:
        """Roll back a half-built process start before the thread
        fallback: unlink any arenas already created (they would
        otherwise sit in /dev/shm until interpreter exit) and clear the
        process-mode depth counters (stale ``_puts`` would make
        ``_queue_depth`` read 0 for the whole thread-mode run)."""
        for r in self._rings:
            if r is not None:
                r.close()
        self._rings = []
        self._puts = []
        self._gets = []
        for pump in self._pumps:
            if pump is not None:
                pump.retire()
        self._pumps = []
        self._queues = []
        self._procs = []
        self._stops = []
        self._ctx = None
        self.transport = None

    def _worker_plan(self, shards: List[Shard]):
        """(sources, shards) restricted to what ONE worker touches: its
        shard subset with source indices remapped into a filtered source
        list — spawned workers must not each receive a pickled copy of
        every in-memory blob in the pool (shard indices stay GLOBAL;
        only source references are localized)."""
        from dataclasses import replace

        used = sorted({s.source for s in shards})
        remap = {g: l for l, g in enumerate(used)}
        return (
            [self._sources[g] for g in used],
            [replace(s, source=remap[s.source]) for s in shards],
        )

    def _build_rings(self, queue_factory) -> List[Any]:
        """One arena per worker, free queues seeded; ``queue_factory``
        makes the free queues (ctx.Queue or queue.Queue)."""
        from .ring import SlotRing

        rings = []
        try:
            for w in range(self.workers):
                rings.append(SlotRing(
                    self.slot_bytes, self.ring_slots, queue_factory(),
                    name_hint=f"{os.getpid()}_{w}",
                ))
        except Exception:
            for r in rings:
                r.close()
            raise
        return rings

    def _queue_bound(self, transport: Optional[str]) -> int:
        # Queue bound by transport: for pickle it IS the backpressure —
        # exactly the documented queue_batches window.  For the ring,
        # slot exhaustion backpressures and the queue only carries small
        # descriptors (at most one per leased slot) plus control
        # messages — sized to never stall a slot-holding worker.
        return (self.ring_slots + 2 if transport == "ring"
                else self.queue_batches)

    def _start_processes(self, shards_of) -> None:
        import multiprocessing as mp

        method = self._mp_context
        if method is None:
            method = ("forkserver"
                      if "forkserver" in mp.get_all_start_methods()
                      else "spawn")
        ctx = mp.get_context(method)
        if method == "forkserver":
            # Preload the worker module graph (numpy included) into the
            # forkserver ONCE: children then fork with it already
            # imported, so worker (re)spawns cost milliseconds instead
            # of a full interpreter import — the difference between a
            # supervised respawn that retains throughput and one that
            # stalls the consumer for seconds mid-corpus.  No-op if the
            # forkserver is already running (first pool in the process
            # wins).
            try:
                ctx.set_forkserver_preload(
                    ["logparser_tpu.feeder.worker"]
                )
            except Exception:  # noqa: BLE001 — best-effort fast path
                pass
        self._ctx = ctx
        self.transport = resolve_transport(self._requested_transport,
                                           "process")
        if self.transport == "ring":
            try:
                self._rings = self._build_rings(ctx.Queue)
            except Exception as e:  # noqa: BLE001 — no /dev/shm etc.
                log_warning_once(
                    LOG,
                    "feeder: shared-memory ring unavailable "
                    f"({type(e).__name__}); falling back to pickle",
                )
                self.transport = "pickle"
        q_bound = self._queue_bound(self.transport)
        self._queues = [ctx.Queue(maxsize=q_bound)
                        for _ in range(self.workers)]
        self._puts = [ctx.Value("l", 0) for _ in range(self.workers)]
        self._gets = [0] * self.workers
        self._stops = [ctx.Event() for _ in range(self.workers)]
        procs = []
        try:
            for w in range(self.workers):
                w_sources, w_shards = shards_of[w]
                p = ctx.Process(
                    target=run_worker,
                    args=(w, w_sources, w_shards, self._queues[w],
                          self.batch_lines, self.line_len, self._stops[w],
                          self._worker_delay_s,
                          self._rings[w].spec() if self._rings else None,
                          self._puts[w], True, None, self._chaos_spec),
                    name=f"logparser-tpu-feeder-{w}",
                    daemon=True,
                )
                p.start()
                procs.append(p)
        except Exception:
            for p in procs:
                try:
                    p.terminate()
                except Exception as e:  # noqa: BLE001 — rollback best-effort
                    note_teardown_error(LOG, "start.terminate", e)
            raise
        self._procs = procs
        # Pumps last: nothing to retire if anything above raised.
        self._pumps = [_QueuePump(q, str(w))
                       for w, q in enumerate(self._queues)]
        self.mode = "process"

    def _start_threads(self, shards_of) -> None:
        self.transport = resolve_transport(self._requested_transport,
                                           "thread")
        writers: List[Any] = [None] * self.workers
        if self.transport == "ring":
            try:
                self._rings = self._build_rings(_queue.Queue)
                from .ring import SlotWriter

                writers = [SlotWriter(r.spec(), shm=r.shm)
                           for r in self._rings]
            except Exception as e:  # noqa: BLE001
                log_warning_once(
                    LOG,
                    "feeder: shared-memory ring unavailable "
                    f"({type(e).__name__}); falling back to inline",
                )
                self.transport = "inline"
        q_bound = self._queue_bound(self.transport)
        raw = [_queue.Queue(maxsize=q_bound)
               for _ in range(self.workers)]
        # Producer-side gauge updates: only possible in-process.
        self._queues = raw
        instrumented = [
            make_instrumented_queue(q, self._publish_depth) for q in raw
        ]
        self._stops = [threading.Event() for _ in range(self.workers)]
        self._procs = []
        for w in range(self.workers):
            w_sources, w_shards = shards_of[w]
            t = threading.Thread(
                target=run_worker,
                args=(w, w_sources, w_shards, instrumented[w],
                      self.batch_lines, self.line_len, self._stops[w],
                      self._worker_delay_s, writers[w], None, False,
                      None, self._chaos_spec),
                name=f"logparser-tpu-feeder-{w}",
                daemon=True,
            )
            t.start()
            self._procs.append(t)
        self._pumps = [None] * self.workers  # queue.Queue gets are atomic
        self.mode = "thread"

    # -- recovery: reap / respawn / quarantine ---------------------------

    def _join_escalate(self, p, timeout: float) -> None:
        """join -> terminate -> kill: a worker that ignores SIGTERM (or
        cannot receive it — SIGSTOPped) must not hang close() or a
        respawn; SIGKILL reaches even a stopped process.  Threads can
        only be joined (daemon threads die with the process)."""
        p.join(timeout=timeout)
        if not hasattr(p, "terminate") or not p.is_alive():
            return
        try:
            p.terminate()
        except Exception as e:  # noqa: BLE001
            note_teardown_error(LOG, "worker.terminate", e)
        p.join(timeout=timeout)
        if p.is_alive() and hasattr(p, "kill"):
            try:
                p.kill()
            except Exception as e:  # noqa: BLE001
                note_teardown_error(LOG, "worker.kill", e)
            p.join(timeout=timeout)

    def _reap_worker(self, worker: int) -> None:
        """Make sure worker ``worker``'s old incarnation is gone and its
        transport lane is retired: stale in-flight messages are
        discarded (the respawn replays them deterministically), the old
        queue is dropped, and a ring arena is closed — the respawn gets
        a FRESH ring, so slots leaked by the dead incarnation (acquired
        but never shipped) can't shrink the new one's capacity."""
        self._stops[worker].set()
        q = self._queues[worker]
        pump = self._pumps[worker] if self._pumps else None
        if pump is not None:
            # Drain the pump's buffer only: a get on the mp queue itself
            # (even get_nowait) can block in recv_bytes on a partial
            # frame from a mid-write death — the very hazard the pump
            # isolates.  Whatever is still in the pipe dies with the
            # retired queue.
            pump.retire()
            q = pump
        try:
            while True:
                q.get_nowait() if hasattr(q, "get_nowait") else q.get(
                    timeout=0
                )
        except _queue.Empty:
            pass
        except Exception as e:  # noqa: BLE001
            note_teardown_error(LOG, "reap.drain", e)
        q = self._queues[worker]
        p = self._procs[worker]
        if hasattr(p, "terminate"):
            self._join_escalate(p, min(1.0, self._shutdown_timeout_s))
        else:
            # A wedged thread cannot be killed: abandon it (its stop
            # event is set, its queue is retired — it exits at its next
            # put/acquire poll, daemon either way).
            p.join(timeout=0.2)
        if hasattr(q, "cancel_join_thread"):
            q.cancel_join_thread()
        if self._rings and self._rings[worker] is not None:
            self._rings[worker].close()
            self._rings[worker] = None

    def _respawn_worker(self, worker: int, transport: str,
                        shards: List[Shard],
                        resume: Optional[Dict[int, int]]) -> None:
        """Start a fresh incarnation of worker ``worker`` over
        ``shards`` (its remaining subset), on ``transport`` — possibly a
        rung below the pool's (``"inline"`` = a thread in the consumer
        process, even for process pools).  ``resume`` maps the in-flight
        shard to its replay skip count."""
        w_sources, w_shards = self._worker_plan(shards)
        chaos = (self._chaos_spec.respawn_view()
                 if self._chaos_spec is not None else None)
        as_process = self.mode == "process" and transport != "inline"
        ring = None
        if transport == "ring":
            from .ring import SlotRing

            try:
                ring = SlotRing(
                    self.slot_bytes, self.ring_slots,
                    self._ctx.Queue() if as_process else _queue.Queue(),
                    name_hint=f"{os.getpid()}_{worker}r",
                )
            except Exception as e:  # noqa: BLE001 — arena gone mid-run
                log_warning_once(
                    LOG,
                    "feeder: ring rebuild failed on respawn "
                    f"({type(e).__name__}); worker continues on pickle",
                )
                transport = "pickle" if as_process else "inline"
                if self.supervisor is not None:
                    self.supervisor.transport_of[worker] = transport
        if self._rings:
            self._rings[worker] = ring
        q_bound = self._queue_bound(transport)
        if as_process:
            ctx = self._ctx
            q = ctx.Queue(maxsize=q_bound)
            stop = ctx.Event()
            puts = ctx.Value("l", 0)
            p = ctx.Process(
                target=run_worker,
                args=(worker, w_sources, w_shards, q, self.batch_lines,
                      self.line_len, stop, self._worker_delay_s,
                      ring.spec() if ring is not None else None,
                      puts, True, resume, chaos),
                name=f"logparser-tpu-feeder-{worker}",
                daemon=True,
            )
            p.start()
        else:
            q = _queue.Queue(maxsize=q_bound)
            stop = threading.Event()
            puts = None
            writer = None
            if ring is not None:
                from .ring import SlotWriter

                writer = SlotWriter(ring.spec(), shm=ring.shm)
            out_q = (make_instrumented_queue(q, self._publish_depth)
                     if self.mode == "thread" else q)
            p = threading.Thread(
                target=run_worker,
                args=(worker, w_sources, w_shards, out_q, self.batch_lines,
                      self.line_len, stop, self._worker_delay_s, writer,
                      None, False, resume, chaos),
                name=f"logparser-tpu-feeder-{worker}",
                daemon=True,
            )
            p.start()
        self._queues[worker] = q
        if self._pumps:
            self._pumps[worker] = (_QueuePump(q, f"{worker}r")
                                   if as_process else None)
        self._stops[worker] = stop
        self._procs[worker] = p
        if self._puts:
            self._puts[worker] = puts
            self._gets[worker] = 0

    def _shard_payload(self, shard: Shard) -> bytes:
        """The shard's healed payload, read in-process (quarantine and
        per-batch re-frame paths); cached per shard — ring-fault
        recovery may re-frame several batches of one shard."""
        if self._payload_cache and self._payload_cache[0] == shard.index:
            return self._payload_cache[1]
        payload = read_shard_payload(self._sources[shard.source], shard)
        self._payload_cache = (shard.index, payload)
        return payload

    def _frame_inproc(self, shard: Shard, index: int,
                      payload: bytes, ranges) -> EncodedBatch:
        """Frame batch ``index`` of ``shard`` in-process over the HOST
        (numpy) framer — byte-identical semantics to the native framer
        (the differential suite pins `_encode_blob_numpy` to it), but
        immune to whatever killed the worker, native framer included."""
        from ..native import _encode_blob_numpy

        p0, p1 = ranges[index]
        chunk = payload[p0:p1]
        t0 = time.perf_counter()
        buf, lengths, overflow = _encode_blob_numpy(
            chunk, self.line_len, 64, 8191, None
        )
        return EncodedBatch(
            shard=shard.index,
            index=index,
            payload=chunk,
            buf=buf,
            lengths=lengths,
            overflow=list(overflow),
            n_lines=int(buf.shape[0]) if len(chunk) else 0,
            encode_s=time.perf_counter() - t0,
        )

    def _reframe_batch(self, shard: Shard, index: int) -> EncodedBatch:
        """Recover ONE batch whose ring descriptor failed validation:
        delivery is ordered, so the next batch of the current shard is
        unambiguous regardless of what the corrupt descriptor claimed."""
        payload = self._shard_payload(shard)
        ranges = split_batches(payload, self.batch_lines)
        if index >= len(ranges):
            raise FeederError(
                f"shard {shard.index}: ring fault past the shard's last "
                f"batch (index {index} of {len(ranges)})"
            )
        self._stats["batches_reframed"] += 1
        return self._frame_inproc(shard, index, payload, ranges)

    def _quarantine_batches(
        self, shard: Shard, skip: int
    ) -> Iterator[EncodedBatch]:
        """The quarantine path: the rest of a poison shard, framed
        in-process from the last delivered batch boundary.  Raises
        FeederError only when the shard cannot even be read/framed in
        this process — the one case that still aborts a supervised run."""
        try:
            payload = self._shard_payload(shard)
            ranges = split_batches(payload, self.batch_lines)
            for bi in range(skip, len(ranges)):
                yield self._frame_inproc(shard, bi, payload, ranges)
        except FeederError:
            raise
        except Exception as e:
            raise FeederError(
                f"quarantined shard {shard.index} failed in-process too "
                f"({type(e).__name__}: {e}); the shard is unprocessable"
            ) from e

    def _owed_shards(self, worker: int, from_index: int,
                     inclusive: bool = True) -> List[Shard]:
        """The shards worker ``worker`` still owes the stream, at/after
        (``inclusive``) or strictly after ``from_index`` — the single
        home of the index-modulo ownership invariant every recovery
        path replays against."""
        lo = from_index if inclusive else from_index + 1
        return [s for s in self.shards
                if s.index % self.workers == worker and s.index >= lo]

    def _execute_decision(self, worker: int, decision, shards: List[Shard],
                          resume: Optional[Dict[int, int]], *,
                          backoff: bool = False, t0: float = 0.0) -> None:
        """The common mechanics of every supervised recovery: reap the
        old incarnation, count a transport demotion, optionally honor
        the decision's backoff, respawn over ``shards`` (skipped when
        the worker owes nothing) with ``resume`` replay and count the
        restart, then account recovery wall.  The three recovery paths
        (reactive fault, proactive sweep, ring demotion) differ only in
        the shard set / replay map / backoff they pass."""
        reg = metrics()
        self._reap_worker(worker)
        if decision.demoted_from:
            reg.increment(
                "feeder_transport_demotions_total",
                labels={"from": decision.demoted_from,
                        "to": decision.transport},
            )
        if backoff and decision.backoff_s:
            time.sleep(decision.backoff_s)
        if shards:
            self._respawn_worker(worker, decision.transport, shards, resume)
            reg.increment("feeder_worker_restarts_total")
            self.supervisor.total_restarts += 1
        self.supervisor.recovery_s += time.perf_counter() - t0

    def _handle_worker_fault(self, worker: int, shard: Shard,
                             delivered: int, fault: WorkerFault) -> str:
        """One dead/errored/stalled/protocol-breaking worker while
        ``shard`` was draining.  Unsupervised: the historical fail-stop
        FeederError.  Supervised: execute the supervisor's Decision —
        reap, then respawn with replay, or quarantine.  Returns the
        action taken ("respawned" | "quarantine")."""
        if self.supervisor is None:
            if fault.kind == "error":
                raise FeederError(
                    f"feeder worker {worker} failed:\n{fault.detail}"
                ) from None
            if fault.kind == "protocol":
                raise FeederError(
                    f"feeder protocol violation: {fault.detail}"
                ) from None
            if fault.kind == "stalled":
                raise FeederError(
                    f"feeder worker {worker} stalled past the "
                    f"{self.policy.worker_deadline_s}s deadline"
                ) from None
            raise FeederError(
                f"feeder worker {worker} exited without completing its "
                "shards"
            ) from None
        t0 = time.perf_counter()
        decision = self.supervisor.on_worker_fault(worker, shard.index)
        log_warning_once(
            LOG,
            f"feeder: worker {worker} fault ({fault.kind}) on shard "
            f"{shard.index}; supervised recovery: {decision.action}"
            + (f" (transport {decision.demoted_from} -> "
               f"{decision.transport})" if decision.demoted_from else ""),
        )
        if decision.action == "quarantine":
            self._note_quarantine(shard)
            remaining = self._owed_shards(worker, shard.index,
                                          inclusive=False)
            self._execute_decision(worker, decision, remaining, None, t0=t0)
            return "quarantine"
        remaining = self._owed_shards(worker, shard.index)
        self._execute_decision(worker, decision, remaining,
                               {shard.index: delivered}, backoff=True, t0=t0)
        metrics().increment("feeder_shards_requeued_total")
        return "respawned"

    def _note_quarantine(self, shard: Shard) -> None:
        self.supervisor.quarantined.append(shard.index)
        metrics().increment("feeder_shards_quarantined_total")

    def _sweep_dead_workers(self, current_worker: int) -> None:
        """Proactive supervision: while the consumer idles on the
        CURRENT worker's queue, look for OTHER workers that died early
        (dead, queue empty, shards still owed) and respawn them NOW —
        by the time the consumer reaches their shards, the replacement
        is already framing, so recovery wall overlaps delivery instead
        of serializing behind it.  Throttled; skipped where queue sizes
        are unobservable (the positional path still catches everything,
        just later)."""
        sup = self.supervisor
        if sup is None:
            return
        now = time.monotonic()
        if now - self._last_sweep < 0.05:
            return
        self._last_sweep = now
        current_index = getattr(self, "_current_shard_index", -1)
        for w in range(self.workers):
            if w == current_worker or self._procs[w].is_alive():
                continue
            try:
                buffered = self._queues[w].qsize()
            except (NotImplementedError, OSError):
                continue  # no qsize: leave it to positional detection
            pump = self._pumps[w] if self._pumps else None
            if pump is not None:
                buffered += pump.qsize()
            if buffered > 0:
                continue  # buffered work first; recheck next sweep
            owed = self._owed_shards(w, current_index, inclusive=False)
            if not owed:
                continue  # finished everything it owed: a normal exit
            t0 = time.perf_counter()
            decision = sup.on_worker_fault(w, owed[0].index)
            log_warning_once(
                LOG,
                f"feeder: worker {w} found dead ahead of its shards; "
                f"proactive {decision.action}",
            )
            respawn_shards = owed
            if decision.action == "quarantine":
                # Executed when the consumer reaches the shard (the
                # in-process re-frame must interleave at its ordered
                # position); the replacement skips it.
                self._pending_quarantine.add(owed[0].index)
                self._note_quarantine(owed[0])
                respawn_shards = owed[1:]
            # No backoff on the proactive path: the death already aged
            # while the consumer was busy elsewhere, and a crash loop
            # stays bounded by the restart budget.
            self._execute_decision(w, decision, respawn_shards, None, t0=t0)

    def _apply_demotion(self, worker: int, shard: Shard,
                        next_index: int, decision) -> None:
        """Execute a ring-lane demotion Decision: reap the (healthy but
        ring-compromised) worker and respawn it one rung down, replaying
        the current shard from ``next_index``."""
        log_warning_once(
            LOG,
            f"feeder: worker {worker} demoted off the ring "
            f"({decision.demoted_from} -> {decision.transport}) after "
            "repeated ring faults",
        )
        t0 = time.perf_counter()
        remaining = self._owed_shards(worker, shard.index)
        self._execute_decision(worker, decision, remaining,
                               {shard.index: next_index}, t0=t0)

    def close(self) -> None:
        """Stop workers, drop queues, unlink ring arenas.  Idempotent;
        also runs on normal exhaustion of :meth:`batches`.  Worker
        shutdown escalates join -> terminate -> kill (a SIGSTOPped or
        SIGTERM-deaf worker cannot hang close()); teardown failures are
        warned once + counted (``feeder_teardown_errors_total``), never
        silently swallowed."""
        if self._closed:
            return
        with pipeline_stage("feeder_close"):
            self._closed = True
            _LIVE_POOLS.discard(self)
            for stop in self._stops:
                stop.set()
            # Drain so workers blocked on a full queue observe the stop event
            # promptly instead of at their next 0.1 s put timeout.  Pumped
            # (process) lanes drain the pump buffer only — touching the mp
            # queue directly risks the partial-frame recv_bytes hang.
            for w, q in enumerate(self._queues):
                pump = self._pumps[w] if self._pumps else None
                if pump is not None:
                    pump.retire()
                    q = pump
                try:
                    while True:
                        q.get_nowait() if hasattr(q, "get_nowait") else q.get(
                            timeout=0
                        )
                except _queue.Empty:
                    pass
                except Exception as e:  # noqa: BLE001
                    note_teardown_error(LOG, "close.drain", e)
            for p in self._procs:
                self._join_escalate(p, self._shutdown_timeout_s)
            for q in self._queues:
                # mp.Queue feeder threads keep the process alive unless
                # cancelled; plain queue.Queue has no such method.
                if hasattr(q, "cancel_join_thread"):
                    q.cancel_join_thread()
            for r in self._rings:
                if r is not None:
                    r.close()
            metrics().gauge_set("feeder_queue_depth", 0)

    def __enter__(self) -> "FeederPool":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()

    # -- metrics helpers -------------------------------------------------

    def _queue_depth(self) -> int:
        if self._puts:
            # Process mode: shared put-counters minus this consumer's get
            # counts — live on every platform (macOS mp queues have no
            # qsize) and unaffected by pipe buffering.  A worker demoted
            # to an inline thread has no shared counter (None hole) —
            # its plain queue.Queue has a working qsize instead.
            total = 0
            for w in range(self.workers):
                if self._puts[w] is not None:
                    total += max(0, self._puts[w].value - self._gets[w])
                else:
                    try:
                        total += self._queues[w].qsize()
                    except (NotImplementedError, OSError):
                        pass
            return total
        total = 0
        for q in self._queues:
            try:
                total += q.qsize()
            except (NotImplementedError, OSError):
                return -1  # platform without qsize (macOS mp queues)
        return total

    def backpressure(self) -> float:
        """THIS pool's queue occupancy as a 0.0–1.0 fraction of its
        REACHABLE capacity.  For the ring that is ``workers x
        ring_slots`` — a saturated worker can have at most one
        descriptor per leased slot outstanding, so dividing by the
        descriptor-queue bound (``ring_slots + 2`` control slack) would
        cap the fraction at ~0.75 and a fully wedged fabric could never
        cross a 0.95 shed threshold.  For pickle/inline lanes the
        bounded queue itself is the capacity.  0.0 before start, after
        close, or on a platform where depth is unknowable — unknown
        must read as "admit", never as "shed".  The process-wide
        aggregate is :func:`queue_backpressure`."""
        if not self._started or self._closed:
            return 0.0
        depth = self._queue_depth()
        if depth < 0:
            return 0.0
        per_worker = (self.ring_slots if self.transport == "ring"
                      else self._queue_bound(self.transport))
        cap = self.workers * per_worker
        if cap <= 0:
            return 0.0
        return min(1.0, depth / cap)

    def _publish_depth(self) -> None:
        depth = self._queue_depth()
        if depth >= 0:
            metrics().gauge_set("feeder_queue_depth", depth)

    def _sample_depth(self) -> None:
        depth = self._queue_depth()
        if depth < 0:
            return
        metrics().gauge_set("feeder_queue_depth", depth)
        self._depth_samples += 1
        self._depth_sum += depth
        if depth > self._stats["queue_depth_max"]:
            self._stats["queue_depth_max"] = depth

    # -- consumption -----------------------------------------------------

    def _lane(self, worker: int):
        """The consumer-facing end of worker ``worker``'s message lane:
        its :class:`_QueuePump` for process workers (recv_bytes hazard
        isolation), the queue itself for thread/inline workers."""
        pump = self._pumps[worker] if self._pumps else None
        return pump if pump is not None else self._queues[worker]

    def _get(self, q, worker: int):
        """Blocking dequeue that accounts starvation and watches THIS
        queue's producer: a dead producer (crash/os._exit/silent thread
        return) raises WorkerFault("died") once its queue is empty, and
        an ALIVE but silent producer raises WorkerFault("stalled") past
        the policy's worker deadline (when one is set) — in both cases
        the supervised pool recovers; unsupervised, FeederError."""
        deadline = self.policy.worker_deadline_s
        t_enter = time.perf_counter()
        blocked = 0.0  # time spent in Empty waits only — a successful
        # get's own duration (pipe read + unpickling of a multi-MB
        # batch in pickle mode) is transfer, not starvation.
        while True:
            t0 = time.perf_counter()
            try:
                # Short poll: blocked time is only observable in whole
                # Empty windows, so the window is the accounting grain.
                msg = q.get(timeout=0.05)
                break
            except _queue.Empty:
                blocked += time.perf_counter() - t0
                if not self._procs[worker].is_alive():
                    # Producer gone with its queue empty: it died before
                    # reporting (e.g. SIGKILL).  One grace re-read in
                    # case its final messages were still in flight (a
                    # complete message already in the pipe reads back
                    # immediately; a partial pickle never completes, so
                    # a short timeout is the only thing that tells the
                    # two apart).
                    try:
                        msg = q.get(timeout=0.15)
                        break
                    except _queue.Empty:
                        raise WorkerFault("died", worker) from None
                if deadline is not None and blocked >= deadline:
                    raise WorkerFault(
                        "stalled", worker,
                        f"no output for {blocked:.1f}s "
                        f"(deadline {deadline}s)",
                    ) from None
                # The consumer is idle anyway: use the window to find
                # (and revive) dead NON-current workers before their
                # shards come up — recovery overlaps delivery instead
                # of serializing behind it.
                self._sweep_dead_workers(worker)
        if self._gets:
            self._gets[worker] += 1
        if not self._primed:
            # Pipeline fill — worker start, first read/frame AND the
            # first item's queue transfer — is startup latency, not
            # starvation: the chip wasn't waiting on a fabric that had
            # ever been ahead of it.  Post-prime gets only ever count
            # their Empty windows (the transfer itself is throughput).
            self._primed = True
            self._stats["startup_s"] = time.perf_counter() - t_enter
            metrics().increment("feeder_fill_seconds_total",
                                self._stats["startup_s"])
        elif blocked > 0:
            self._stats["starvation_s"] += blocked
            metrics().increment("feeder_starvation_seconds_total", blocked)
        self._sample_depth()
        return msg

    def _account_batch(self, eb: EncodedBatch) -> None:
        """Volume/stage accounting for one delivered batch — identical
        for worker-framed, re-framed and quarantined batches (recovered
        runs must report the same totals as undisturbed ones)."""
        reg = metrics()
        self._stats["batches"] += 1
        self._stats["lines"] += eb.n_lines
        self._stats["payload_bytes"] += eb.source_bytes
        self._stats["read_s"] += eb.read_s
        self._stats["encode_s"] += eb.encode_s
        reg.increment("feeder_bytes_read_total", eb.source_bytes)
        reg.increment("feeder_lines_total", eb.n_lines)
        reg.increment("feeder_batches_total")
        observe_stage("feeder_read", eb.read_s, items=eb.n_lines)
        observe_stage("feeder_encode", eb.encode_s, items=eb.n_lines)

    def batches(self, detach: bool = True) -> Iterator[EncodedBatch]:
        """The ordered batch stream (single use).  Yields every framed
        batch of every shard, in global shard order, then joins the
        workers and closes the pool.

        ``detach=True`` (default): ring batches are converted to owned
        copies and their slots released immediately — hold as many as
        you like.  ``detach=False``: ring batches arrive as ZERO-COPY
        slot views; the caller must ``release()`` each one (or the ring
        exhausts and the producers block) and must not touch a batch
        after releasing it.  ``feed()`` uses the zero-copy flavor with
        ``parse_batch_stream`` handling the releases.

        Under supervision (the default) the stream is FAULT-TRANSPARENT:
        worker deaths, stalls, ring faults and poison shards are
        recovered behind this iterator (replay is deterministic, so the
        delivered byte stream is identical to an undisturbed run's);
        only an in-process quarantine failure raises FeederError."""
        with pipeline_stage("feeder_start"):
            self._start()
        reg = metrics()
        sup = self.supervisor
        t_start = time.perf_counter()
        try:
            for shard in self.shards:
                worker = shard.index % self.workers
                self._current_shard_index = shard.index
                delivered = 0  # batches of THIS shard yielded so far
                quarantined = shard.index in self._pending_quarantine
                if quarantined:
                    # Decided by a proactive sweep (counters already
                    # bumped there); executed here, at stream order.
                    self._pending_quarantine.discard(shard.index)
                while not quarantined:
                    try:
                        with pipeline_stage("feeder_get" if self._primed
                                            else "feeder_fill"):
                            msg = self._get(self._lane(worker), worker)
                    except WorkerFault as fault:
                        if self._handle_worker_fault(
                            worker, shard, delivered, fault
                        ) == "quarantine":
                            quarantined = True
                            break
                        continue  # respawned onto a fresh queue: re-get
                    kind = msg[0]
                    if kind == MSG_SLOT:
                        desc = msg[1]
                        ring = (self._rings[worker] if self._rings
                                else None)
                        demote = None
                        try:
                            if ring is None:
                                raise RingFault(
                                    "descriptor",
                                    "slot descriptor from a worker with "
                                    "no ring",
                                )
                            eb: EncodedBatch = ring.map(desc)
                        except RingFault as rf:
                            if sup is None:
                                raise FeederError(
                                    f"feeder worker {worker}: {rf}"
                                ) from rf
                            reg.increment(
                                "feeder_ring_generation_mismatch_total"
                                if rf.reason == "generation"
                                else "feeder_ring_descriptor_faults_total"
                            )
                            if rf.stale:
                                # A replay of a send already mapped and
                                # delivered: re-framing would duplicate
                                # the batch in the stream, and the slot
                                # belongs to whoever legitimately holds
                                # its lease now — drop the descriptor
                                # (still a ring fault for the demotion
                                # ledger; resume stays at `delivered`:
                                # nothing was yielded).
                                log_warning_once(
                                    LOG,
                                    f"feeder: worker {worker} {rf}; "
                                    "stale duplicate dropped",
                                )
                                demote = sup.on_ring_fault(worker)
                                if demote is not None:
                                    self._apply_demotion(
                                        worker, shard, delivered, demote
                                    )
                                continue
                            log_warning_once(
                                LOG,
                                f"feeder: worker {worker} {rf}; batch "
                                "re-framed in-process",
                            )
                            if (ring is not None
                                    and 0 <= desc.slot < ring.n_slots):
                                # The worker holds a lease on this
                                # bounds-valid slot even though the
                                # descriptor failed validation: return
                                # it, or every sub-threshold fault
                                # shrinks the arena by one slot until
                                # producer (acquire) and consumer
                                # (empty queue) deadlock.
                                ring.release(desc.slot)
                            eb = self._reframe_batch(shard, delivered)
                            demote = sup.on_ring_fault(worker)
                        else:
                            reg.increment(
                                "feeder_ring_slot_wait_seconds_total",
                                desc.slot_wait_s,
                            )
                            inplace = ring.inplace_bytes(desc)
                            reg.increment("feeder_ring_bytes_inplace_total",
                                          inplace)
                            if desc.segment:
                                self._stats["segment_batches"] += 1
                                reg.increment("feeder_segment_batches_total")
                            self._stats["slot_wait_s"] += desc.slot_wait_s
                            self._stats["bytes_inplace"] += inplace
                        if demote is not None:
                            # Kill + respawn one rung down BEFORE the
                            # yield: the new incarnation replays from
                            # the batch after this (re-framed) one.
                            self._apply_demotion(worker, shard,
                                                 delivered + 1, demote)
                    elif kind == MSG_BATCH:
                        eb = msg[1]
                        worker_transport = (sup.transport_of[worker]
                                            if sup else self.transport)
                        if worker_transport == "ring":
                            # Pickle fallback batch: no shared memory
                            # could hold it (counted, not fatal: the
                            # ring degrades per batch).  Its
                            # slot-acquire wait still happened — keep the
                            # backpressure signal honest under overflow.
                            self._stats["pickle_fallback_batches"] += 1
                            reg.increment("feeder_ring_pickle_fallback_total")
                            self._stats["slot_wait_s"] += eb.slot_wait_s
                            reg.increment(
                                "feeder_ring_slot_wait_seconds_total",
                                eb.slot_wait_s,
                            )
                            demote = (sup.on_overflow_fallback(worker)
                                      if sup else None)
                            if demote is not None:
                                # An overflow STORM: the ring is mis-
                                # sized for this corpus — move the
                                # worker off it (batch in hand is fine).
                                self._apply_demotion(worker, shard,
                                                     delivered + 1, demote)
                    elif kind == MSG_SHARD_DONE:
                        _, sidx, wall_s, n_lines, _nbytes = msg
                        if sidx != shard.index:
                            fault = WorkerFault(
                                "protocol", worker,
                                f"shard_done for {sidx} while draining "
                                f"{shard.index}",
                            )
                            if self._handle_worker_fault(
                                worker, shard, delivered, fault
                            ) == "quarantine":
                                quarantined = True
                            continue
                        reg.increment("feeder_shards_total")
                        observe_stage("feeder_shard", wall_s, items=n_lines)
                        break
                    elif kind == MSG_ERROR:
                        fault = WorkerFault("error", msg[1], msg[2])
                        if self._handle_worker_fault(
                            worker, shard, delivered, fault
                        ) == "quarantine":
                            quarantined = True
                        continue
                    else:  # MSG_DONE out of order: worker finished early
                        fault = WorkerFault(
                            "protocol", worker,
                            f"{kind!r} before shard {shard.index} "
                            "completed",
                        )
                        if self._handle_worker_fault(
                            worker, shard, delivered, fault
                        ) == "quarantine":
                            quarantined = True
                        continue
                    if eb.shard != shard.index:
                        fault = WorkerFault(
                            "protocol", worker,
                            f"got shard {eb.shard}, expected "
                            f"{shard.index}",
                        )
                        eb.release()
                        if self._handle_worker_fault(
                            worker, shard, delivered, fault
                        ) == "quarantine":
                            quarantined = True
                        continue
                    self._account_batch(eb)
                    if detach:
                        with pipeline_stage("feeder_detach",
                                            items=eb.n_lines):
                            eb = eb.detach()
                    yield eb
                    delivered += 1
                if quarantined:
                    reg.increment("feeder_shards_total")
                    for eb in self._quarantine_batches(shard, delivered):
                        self._account_batch(eb)
                        yield eb
        finally:
            self._stats["wall_s"] = time.perf_counter() - t_start
            if self._depth_samples:
                self._stats["queue_depth_mean"] = round(
                    self._depth_sum / self._depth_samples, 3
                )
            self.close()

    def feed(self, parser, emit_views: Optional[bool] = None, depth: int = 1):
        """Drive ``parser`` (a TpuBatchParser) over the batch stream:
        yields one BatchResult per batch, in corpus order, with the
        host-side stages of batch k overlapping the device work of batch
        k+1 (``parse_batch_stream`` semantics).  Ring batches flow
        through ZERO-COPY: the stream stages each batch's H2D upload
        straight from (a bucket-padded adoption of) the slot frame and
        releases the slot once the batch materializes — after device
        upload and rescue-payload use."""
        return parser.parse_batch_stream(
            self.batches(detach=False), depth=depth, emit_views=emit_views
        )

    def stats(self) -> Dict[str, Any]:
        """Post-run (or mid-run) feed accounting.  Rates and the
        starvation fraction are computed over the STEADY window (wall
        minus pipeline-fill startup): the one-time worker start + first
        read/frame latency is reported as ``startup_s`` instead of
        polluting the sustained numbers.  ``slot_wait_fraction`` is the
        ring backpressure share: total worker slot-wait over the steady
        window summed across workers (1.0 = every worker blocked the
        whole time = the consumer is the bottleneck).  Supervised pools
        add the recovery ledger (restarts, quarantines, demotions, ring
        faults, recovery wall)."""
        out = dict(self._stats)
        out["mode"] = self.mode
        out["transport"] = self.transport
        out["ring_slots"] = self.ring_slots
        if self.supervisor is not None:
            out.update(self.supervisor.summary())
        else:
            out.update({"worker_restarts": 0, "shards_quarantined": 0,
                        "transport_demotions": 0, "ring_faults": 0,
                        "recovery_s": 0.0})
        steady = out["wall_s"] - out["startup_s"]
        if steady > 0:
            out["bytes_per_sec"] = round(out["payload_bytes"] / steady, 1)
            out["starvation_fraction"] = round(
                out["starvation_s"] / steady, 4
            )
            out["slot_wait_fraction"] = round(
                out["slot_wait_s"] / (steady * max(1, self.workers)), 4
            )
        return out
