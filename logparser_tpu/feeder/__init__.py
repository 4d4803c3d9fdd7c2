"""Sharded feeder subsystem: the multi-process ingest fabric.

Turns raw log sources into a steady, ordered stream of framed,
device-ready batches so the device tier is never input-starved
(docs/FEEDER.md).  Three layers:

- :mod:`~logparser_tpu.feeder.shards` — byte-range shard planning with
  newline-boundary healing (the reference InputFormat's split
  semantics: a line belongs to the shard where it starts);
- :mod:`~logparser_tpu.feeder.worker` — the jax-free worker loop that
  reads + frames shards with the ``parse_blob`` framing;
- :mod:`~logparser_tpu.feeder.ring` — the zero-copy shared-memory slot
  transport (per-worker arenas, descriptor queues, slot-exhaustion
  backpressure);
- :mod:`~logparser_tpu.feeder.pool` — :class:`FeederPool`, the consumer
  API: ``batches()`` (ordered EncodedBatch stream with backpressure)
  and ``feed(parser)`` (BatchResults via ``parse_batch_stream``);
- :mod:`~logparser_tpu.feeder.supervisor` — the fault-recovery policy:
  bounded worker respawn with shard replay, poison-shard quarantine,
  and the ring -> pickle -> inline transport demotion ladder (armed by
  default; exercised on purpose by ``tools/chaos.py``).
"""
from .pool import (  # noqa: F401
    CHAOS_ENV,
    DEFAULT_BATCH_LINES,
    PICKLE_ENV,
    FeederError,
    FeederPool,
    default_feeder_workers,
    deregister_backpressure_source,
    queue_backpressure,
    register_backpressure_source,
    resolve_transport,
)
from .ring import (  # noqa: F401
    RING_NAME_PREFIX,
    RingBatch,
    RingFault,
    SlotFrame,
    SlotOverflow,
    SlotRing,
    SlotWriter,
    ring_available,
    slot_layout,
)
from .supervisor import (  # noqa: F401
    Decision,
    FeederSupervisor,
    SupervisorPolicy,
    WorkerFault,
    demote_transport,
)
from .shards import (  # noqa: F401
    DEFAULT_SHARD_BYTES,
    Shard,
    healed_payload,
    healed_range,
    host_shard_range,
    line_start_at_or_after,
    normalize_sources,
    plan_shards,
    shards_for_host,
)
from .worker import EncodedBatch, split_batches  # noqa: F401
