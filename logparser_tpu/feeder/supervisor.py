"""Supervision policy for the feeder fabric: the decision state machine.

PR-3/PR-5 built an ingest fabric that was fail-stop: one crashed worker
raised :class:`~logparser_tpu.feeder.pool.FeederError` and aborted the
whole run, one wedged shard had no route around it, and a ring fault
meant silent corruption or a dead pipeline.  This module is the brain of
the recovery layer — a PURE state machine (no processes, no queues, no
sleeps) that :class:`~logparser_tpu.feeder.pool.FeederPool` consults on
every fault and whose :class:`Decision` the pool then executes:

- a crashed / errored / deadline-stalled worker is **respawned** with a
  bounded per-rung restart budget and exponential backoff; the pool
  replays the in-flight shard from the last fully-DELIVERED batch
  boundary (framing is deterministic, so recovered output is
  byte-identical to an undisturbed run);
- a shard that kills its workers ``poison_threshold`` times (default 2)
  is **quarantined**: the pool re-frames it in-process over the host
  (numpy) framer path instead of feeding it to yet another doomed
  worker — the run completes, the event is counted
  (``feeder_shards_quarantined_total``), and only a shard that cannot
  even be READ in-process aborts the run;
- repeated transport faults walk the worker down the **demotion
  ladder** — ``ring -> pickle -> inline`` for process pools,
  ``ring -> inline`` for thread pools (``demote_transport``, the
  degradation counterpart of ``resolve_transport``): ring descriptor /
  generation faults demote off the ring after ``ring_fault_threshold``,
  a slot-overflow storm after ``overflow_demotion_threshold``, and a
  worker that exhausts its restart budget carries its next incarnation
  one rung down (``feeder_transport_demotions_total``).  ``inline``
  means a THREAD in the consumer process — the rung below forking;
- a worker that still dies at the bottom of the ladder quarantines
  every shard it dies on — progress stays monotonic, the run always
  terminates.

The pool's one-producer/one-consumer queue discipline is what makes all
of this safe: respawns always get a FRESH queue (and a fresh ring), so
a replayed shard can never interleave with stale in-flight messages.
Everything here is jax-free; tests drive the machine directly
(``tests/test_faults.py``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple


class WorkerFault(RuntimeError):
    """One observed worker failure.  ``kind``:

    - ``"died"``: the producer vanished without reporting (SIGKILL,
      os._exit, a thread that returned mid-shard);
    - ``"error"``: the worker relayed MSG_ERROR (carries the traceback);
    - ``"stalled"``: the consumer waited past the worker deadline on an
      alive but silent producer;
    - ``"protocol"``: the worker broke the message protocol (wrong
      shard, DONE before its shards completed).
    """

    def __init__(self, kind: str, worker: int, detail: str = ""):
        super().__init__(
            f"feeder worker {worker} fault ({kind})"
            + (f":\n{detail}" if detail else "")
        )
        self.kind = kind
        self.worker = worker
        self.detail = detail


@dataclass
class SupervisorPolicy:
    """Tunables of the recovery layer (docs/FEEDER.md "Failure model").

    Defaults favor fast tests and fast production recovery: the backoff
    exists to stop a crash-looping worker from burning a core, not to
    ride out multi-second outages — quarantine/demotion handle those.
    """

    #: Restart budget PER WORKER PER LADDER RUNG; exceeding it demotes
    #: the worker's transport one rung (fresh budget at the new rung).
    max_restarts: int = 3
    #: A shard whose worker dies this many times is quarantined.
    poison_threshold: int = 2
    #: Exponential backoff before respawn k: base * 2**(k-1), capped.
    backoff_base_s: float = 0.05
    backoff_max_s: float = 2.0
    #: Ring descriptor/generation faults per worker before it is demoted
    #: off the ring (each fault is already recovered per batch by the
    #: in-process re-frame; the threshold stops the drip).
    ring_fault_threshold: int = 2
    #: Slot-overflow pickle fallbacks per worker before the ring is
    #: clearly mis-sized for this corpus and the worker leaves it.
    overflow_demotion_threshold: int = 16
    #: Consumer wait on an ALIVE but silent producer before it is
    #: declared stalled and respawned.  None disables (default): with a
    #: slow consumer holding slot leases, a stalled-looking worker may
    #: just be backpressured — enable it when the consumer is known to
    #: release promptly (the chaos harness does).
    worker_deadline_s: Optional[float] = None


@dataclass
class Decision:
    """What the pool should do about one fault."""

    action: str                      # "respawn" | "quarantine"
    transport: str                   # transport of the (re)spawned worker
    backoff_s: float = 0.0
    demoted_from: Optional[str] = None


def demote_transport(current: str, mode: str) -> Optional[str]:
    """The next rung DOWN from ``current`` for a pool in ``mode``
    (the degradation counterpart of ``resolve_transport``): process
    pools walk ring -> pickle -> inline (a consumer-side thread),
    thread pools ring -> inline; None below the bottom."""
    if mode == "process":
        return {"ring": "pickle", "pickle": "inline"}.get(current)
    return {"ring": "inline"}.get(current)


class FeederSupervisor:
    """Per-pool fault bookkeeping + the decision rules above.  The pool
    owns exactly one; every method is consumer-thread-only (no locks)."""

    def __init__(self, policy: SupervisorPolicy, workers: int, mode: str,
                 transport: str):
        self.policy = policy
        self.mode = mode
        self.transport_of: List[str] = [transport] * workers
        self._rung_restarts = [0] * workers
        #: Respawns EXECUTED (pool-incremented alongside
        #: feeder_worker_restarts_total, so stats() and /metrics agree);
        #: a fault whose worker owed nothing decides but never respawns.
        self.total_restarts = 0
        self.shard_kills: Dict[int, int] = {}
        self.ring_faults = [0] * workers
        self.overflow_fallbacks = [0] * workers
        self.quarantined: List[int] = []
        self.demotions: List[Tuple[int, str, str]] = []
        self.recovery_s = 0.0  # pool-accounted: backoff + respawn wall

    # -- worker death / error / stall -----------------------------------

    def on_worker_fault(self, worker: int, shard_index: int) -> Decision:
        """One dead/errored/stalled worker while shard ``shard_index``
        was draining.  Order of precedence: exhausted restart budget
        demotes (or, at the bottom rung, quarantines), then the shard's
        kill count may quarantine, else respawn with backoff."""
        kills = self.shard_kills[shard_index] = (
            self.shard_kills.get(shard_index, 0) + 1
        )
        self._rung_restarts[worker] += 1
        transport = self.transport_of[worker]
        demoted_from: Optional[str] = None
        if self._rung_restarts[worker] > self.policy.max_restarts:
            nxt = demote_transport(transport, self.mode)
            if nxt is None:
                # Bottom of the ladder and still dying: route around the
                # data instead of the worker.
                return self._record(
                    Decision("quarantine", transport),
                    worker=worker, shard=shard_index)
            demoted_from, transport = transport, nxt
            self._note_demotion(worker, nxt)
        if kills >= self.policy.poison_threshold:
            return self._record(
                Decision("quarantine", transport,
                         demoted_from=demoted_from),
                worker=worker, shard=shard_index)
        backoff = min(
            self.policy.backoff_max_s,
            self.policy.backoff_base_s
            * (2 ** (self._rung_restarts[worker] - 1)),
        )
        return self._record(
            Decision("respawn", transport, backoff, demoted_from),
            worker=worker, shard=shard_index)

    # -- ring-lane faults ------------------------------------------------

    def on_ring_fault(self, worker: int) -> Optional[Decision]:
        """One descriptor/generation fault (already recovered per batch
        by the pool's in-process re-frame).  Returns a demotion Decision
        once the per-worker threshold trips, else None (keep going)."""
        self.ring_faults[worker] += 1
        if (self.transport_of[worker] == "ring"
                and self.ring_faults[worker]
                >= self.policy.ring_fault_threshold):
            return self._demote_decision(worker)
        return None

    def on_overflow_fallback(self, worker: int) -> Optional[Decision]:
        """One slot-overflow pickle fallback (benign per batch); a storm
        of them means the ring is mis-sized — demote at the threshold."""
        self.overflow_fallbacks[worker] += 1
        if (self.transport_of[worker] == "ring"
                and self.overflow_fallbacks[worker]
                == self.policy.overflow_demotion_threshold):
            return self._demote_decision(worker)
        return None

    def _demote_decision(self, worker: int) -> Decision:
        current = self.transport_of[worker]
        nxt = demote_transport(current, self.mode) or "inline"
        self._note_demotion(worker, nxt)
        return self._record(
            Decision("respawn", nxt, demoted_from=current), worker=worker)

    @staticmethod
    def _record(decision: Decision, **fields: object) -> Decision:
        """Every supervisory decision is a flight-recorder event: the
        recovery itself is silent by design (byte-identical output), so
        the ring is the only per-incident record that survives a later
        crash (docs/OBSERVABILITY.md "Flight recorder")."""
        from ..tracing import flight_event

        flight_event("feeder_decision", action=decision.action,
                     transport=decision.transport,
                     demoted_from=decision.demoted_from, **fields)
        return decision

    def _note_demotion(self, worker: int, new_transport: str) -> None:
        self.demotions.append(
            (worker, self.transport_of[worker], new_transport)
        )
        self.transport_of[worker] = new_transport
        self._rung_restarts[worker] = 0  # fresh budget at the new rung

    # -- reporting -------------------------------------------------------

    def summary(self) -> Dict[str, object]:
        return {
            "worker_restarts": self.total_restarts,
            "shards_quarantined": len(self.quarantined),
            "quarantined_shards": list(self.quarantined),
            "transport_demotions": len(self.demotions),
            "ring_faults": int(sum(self.ring_faults)),
            "recovery_s": round(self.recovery_s, 4),
        }
