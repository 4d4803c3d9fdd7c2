"""Replicated front tier: one acceptor over a supervised sidecar fleet.

ROADMAP open item 2 ("Planet-facing serving"): round 14 made ONE process
serve many sessions well; this module multiplies processes.  A
:class:`FrontTier` is a TCP acceptor speaking the existing wire protocol
(docs/PROTOCOL.md — nothing changes for clients) that fans sessions out
to N supervised SIDECAR worker processes (each a
``python -m logparser_tpu.service --sidecar``, each owning its own
device/core budget), with:

- **Per-format affinity routing.**  A session is routed by its parser
  cache key (:meth:`~logparser_tpu.service._ParserCache.key_of` of its
  CONFIG) via rendezvous (highest-random-weight) hashing, so the same
  format lands on the same sidecar and that sidecar's compiled-parser
  cache, jit shape buckets, and coalescing lanes stay HOT — CelerLog's
  route-by-format dispatching and LogLSHD's bucket-by-signature idea
  (PAPERS.md) applied at fleet scale.  When the first choice's live
  coalesce-queue occupancy (scraped from its ``/metrics``) crosses
  ``spill_occupancy``, the session SPILLS to its second rendezvous
  choice (``front_spills_total``) — a hot format widens to two warm
  sidecars instead of melting one.
- **Supervision** (the serving twin of ``feeder/supervisor.py``, one
  level up): every sidecar is health-checked (``/readyz`` probe + a
  heartbeat deadline over its ``/metrics`` scrape); a crashed sidecar
  is respawned with a bounded restart budget and exponential backoff,
  a WEDGED one (alive but silent past ``heartbeat_deadline_s``) is
  killed first, and a FLAPPING one trips a circuit breaker
  (open -> half-open trial -> closed) so routing steers around it while
  it recovers.  The pure decision machine is :class:`FrontSupervisor` —
  no sockets, no sleeps; tests drive it directly.
- **Crash failover, never a reset.**  A session proxied to a sidecar
  that dies mid-flight is answered with a structured
  ``BUSY {"reason":"sidecar_failover"}`` frame (counted
  ``front_failovers_total``) and closed cleanly: a retrying client
  (``ParseServiceClient`` reconnects on that reason) lands on a live
  sidecar after one warmup.  Affinity is what makes this cheap — any
  sidecar can absorb a key after one compile.
- **Per-tenant fairness** on the front admission tier: a CONFIG may
  carry a ``tenant`` key; quotas bound one tenant's concurrent sessions
  (``tenant_max_sessions``) and in-flight lines
  (``tenant_max_inflight_lines``), shedding
  ``BUSY {"reason":"tenant_quota"}`` (``front_tenant_shed_total``)
  so one noisy tenant cannot starve the fleet.
- **Zero-downtime rolling restart.**  :meth:`FrontTier.roll` drains one
  sidecar at a time under the round-12 drain machinery (SIGTERM ->
  ``/readyz`` flip -> admitted sessions finish) while routing sends its
  keys to the rest, then respawns it and moves on — the config/version
  swap story with the listener never blinking.
- **Fleet observability.**  The front's HTTP endpoint merges every
  sidecar's ``/metrics`` exposition under a ``sidecar`` label alongside
  the front's own families (``front_sessions_routed_total{key,sidecar}``,
  ``front_failovers_total``, ``front_tenant_shed_total{tenant}``, ...),
  and registers fleet-wide sidecar occupancy as a process backpressure
  source (:func:`logparser_tpu.feeder.register_backpressure_source`) —
  the cross-process aggregation of the per-process signal the admission
  tier already sheds on.

Drilled by ``make fleet-smoke`` (``tools/fleet_smoke.py``: a 1-of-3
hard kill and a live rolling restart under loadgen traffic) and gated
in ``bench.py``'s ``fleet`` section (goodput scaling 1->N, kill-drill
retention); chaos primitives ``kill_sidecar``/``wedge_sidecar``/
``flap_sidecar`` (``tools/chaos.py``) produce the failures on purpose.
docs/SERVICE.md "Fleet" is the ops runbook.
"""
from __future__ import annotations

import hashlib
import itertools
import json
import logging
import os
import re
import signal
import socket
import socketserver
import struct
import subprocess
import sys
import threading
import time
import urllib.request
from dataclasses import dataclass
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from .observability import log_warning_once, metrics, note_teardown
from .service import (
    _ERROR_MARKER,
    _MAX_FRAME,
    RECONNECT_BUSY_REASONS,
    _FrameTooLarge,
    _ParserCache,
    _SessionTimeout,
    _linger_drain,
    _recv_exact_timed,
    busy_error_text,
    write_error,
    write_frame,
)
from .tracing import (
    flight_event,
    flightz_payload,
    root_span,
    tracez_payload,
)

LOG = logging.getLogger(__name__)

#: Bound on distinct client-controlled metric label values (parser-key
#: labels, tenant names) before the tail aggregates as ``overflow`` —
#: the registry keeps every series forever, so unbounded label spaces
#: are a memory leak an unauthenticated peer could drive.
_MAX_METRIC_LABELS = 256


# ---------------------------------------------------------------------------
# policy + the pure supervision machine
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class FrontPolicy:
    """Tunables of the front tier (docs/SERVICE.md "Fleet").  Defaults
    favor fast recovery and fast tests; production deployments mostly
    raise the budgets."""

    #: Faults per sidecar inside ``restart_budget_window_s`` before the
    #: slot is DISABLED (stops being respawned; routing skips it until
    #: the next :meth:`FrontTier.roll` revives it deliberately).
    max_restarts: int = 5
    restart_budget_window_s: float = 60.0
    #: Exponential backoff before respawn k of a window: base * 2**(k-1).
    backoff_base_s: float = 0.25
    backoff_max_s: float = 5.0
    #: Health probe period and the silence budget after which an ALIVE
    #: but unresponsive sidecar is declared wedged and killed.
    heartbeat_interval_s: float = 0.5
    heartbeat_deadline_s: float = 5.0
    #: Circuit breaker: ``circuit_threshold`` faults inside
    #: ``flap_window_s`` open the circuit for ``circuit_open_s`` (routing
    #: steers around the sidecar), then ONE half-open trial session
    #: probes it — success closes the circuit, a fault re-opens it.
    circuit_threshold: int = 3
    flap_window_s: float = 10.0
    circuit_open_s: float = 5.0
    #: First-choice coalesce-queue occupancy (0-1 fraction of the
    #: sidecar's bounded submission queue, scraped live from /metrics)
    #: at/above which a session spills to its second rendezvous choice.
    spill_occupancy: float = 0.5
    #: Per-tenant fairness quotas (0 = unlimited): concurrent sessions
    #: and in-flight lines per CONFIG ``tenant`` identity.
    tenant_max_sessions: int = 0
    tenant_max_inflight_lines: int = 0
    #: Front-wide admitted-session bound (the fleet's aggregate budget
    #: lives in the sidecars' own max_sessions; this one only stops a
    #: socket flood from exhausting front fds).
    max_sessions: int = 1024
    #: Fleet-wide occupancy fraction at/above which NEW sessions shed
    #: BUSY{"reason":"backpressure"} at the front door.
    backpressure_threshold: float = 0.95
    busy_retry_after_s: float = 0.25
    #: Socket windows (mirroring ServiceLimits semantics).
    connect_timeout_s: float = 2.0
    idle_timeout_s: Optional[float] = 600.0
    frame_timeout_s: Optional[float] = 30.0
    #: Upstream silence budget while a response is due: normally the
    #: prober kills a wedged sidecar long before this fires.
    upstream_timeout_s: Optional[float] = 300.0
    max_config_bytes: int = 1 << 20
    #: Sidecar spawn -> SIDECAR_READY budget (a cold jax import rides
    #: inside it) and the per-sidecar drain budget during a roll.
    ready_timeout_s: float = 120.0
    drain_timeout_s: float = 30.0


@dataclass
class FrontDecision:
    """What the fleet should do about one sidecar fault."""

    action: str                      # "respawn" | "disable"
    backoff_s: float = 0.0
    circuit_opened: bool = False


class FrontSupervisor:
    """Per-sidecar fault bookkeeping + circuit breaker — a PURE state
    machine (no processes, no sleeps, explicit ``now``), the fleet-level
    sibling of :class:`~logparser_tpu.feeder.supervisor.FeederSupervisor`.
    Circuit states per slot: ``closed`` (routable) -> ``open`` (faults >=
    ``circuit_threshold`` inside ``flap_window_s``; not routable) ->
    ``half_open`` (cool-off elapsed; exactly ONE trial session admitted)
    -> ``closed`` on trial success / ``open`` again on fault.  The
    restart budget is a sliding window: ``max_restarts`` faults inside
    ``restart_budget_window_s`` DISABLE the slot (quarantine, the
    route-around-the-data move one level up)."""

    CLOSED, OPEN, HALF_OPEN = "closed", "open", "half_open"

    def __init__(self, policy: FrontPolicy, n: int):
        self.policy = policy
        self.n = n
        self.state = [self.CLOSED] * n
        self.opened_at = [0.0] * n
        self.fault_times: List[List[float]] = [[] for _ in range(n)]
        self.disabled = [False] * n
        self.total_restarts = 0          # respawns EXECUTED (fleet-counted)
        self.circuit_opens = [0] * n

    # -- faults ----------------------------------------------------------

    def on_fault(self, idx: int, now: float) -> FrontDecision:
        """One observed sidecar failure (death, wedge, connect refusal).
        Returns the respawn/disable decision; flips the circuit open at
        the flap threshold so routing steers around the slot while its
        respawns churn."""
        faults = self.fault_times[idx]
        faults.append(now)
        window = self.policy.restart_budget_window_s
        self.fault_times[idx] = faults = [
            t for t in faults if now - t <= window
        ]
        opened = False
        recent = [t for t in faults if now - t <= self.policy.flap_window_s]
        if (self.state[idx] != self.OPEN
                and len(recent) >= self.policy.circuit_threshold):
            self.state[idx] = self.OPEN
            self.opened_at[idx] = now
            self.circuit_opens[idx] += 1
            opened = True
        elif self.state[idx] == self.HALF_OPEN:
            # The trial failed: straight back to cooling.
            self.state[idx] = self.OPEN
            self.opened_at[idx] = now
        if len(faults) > self.policy.max_restarts:
            self.disabled[idx] = True
            return FrontDecision("disable", circuit_opened=opened)
        backoff = min(
            self.policy.backoff_max_s,
            self.policy.backoff_base_s * (2 ** (len(recent) - 1)),
        )
        return FrontDecision("respawn", backoff, opened)

    # -- routing signal --------------------------------------------------

    def routable(self, idx: int, now: float) -> bool:
        """Whether the router may hand ``idx`` a NEW session right now.
        An open circuit past its cool-off transitions to half-open and
        admits exactly this one call's session as the trial.  A
        half-open slot whose trial went STALE (admitted here but never
        actually routed — rendezvous order sent that session elsewhere,
        or its client vanished — and no success/fault ever reported
        inside another cool-off window) re-admits a fresh trial:
        without the escape a recovered sidecar could sit HALF_OPEN
        forever, silently shrinking the fleet."""
        if self.disabled[idx]:
            return False
        st = self.state[idx]
        if st == self.CLOSED:
            return True
        # OPEN past the cool-off, or HALF_OPEN with a stale trial:
        # admit (another) trial and restart the window clock.
        if now - self.opened_at[idx] >= self.policy.circuit_open_s:
            self.state[idx] = self.HALF_OPEN
            self.opened_at[idx] = now
            return True
        return False

    def on_success(self, idx: int, now: float) -> None:
        """A routed session reached its sidecar (CONFIG forwarded on a
        live connection).  A half-open trial success closes the circuit
        and clears the flap window."""
        if self.state[idx] == self.HALF_OPEN:
            self.state[idx] = self.CLOSED
            self.fault_times[idx] = []

    def on_deliberate_restart(self, idx: int) -> None:
        """A rolling restart replaced this sidecar ON PURPOSE: fresh
        slate — deliberate churn must not trip the breaker or eat the
        crash budget (and a roll revives a disabled slot)."""
        self.state[idx] = self.CLOSED
        self.fault_times[idx] = []
        self.disabled[idx] = False

    def summary(self) -> Dict[str, Any]:
        return {
            "restarts": self.total_restarts,
            "circuit_opens": list(self.circuit_opens),
            "disabled": [i for i in range(self.n) if self.disabled[i]],
            "states": list(self.state),
        }


# ---------------------------------------------------------------------------
# sidecar handles: one supervised worker process (or an in-process
# stand-in for tests/bench)
# ---------------------------------------------------------------------------


class SidecarSpawnError(RuntimeError):
    """A sidecar process failed to reach SIDECAR_READY."""


class ProcessSidecar:
    """One ``python -m logparser_tpu.service --sidecar`` child process.
    The constructor blocks until the child prints its SIDECAR_READY
    handshake (bound service + metrics ports) or dies/times out."""

    def __init__(self, index: int, *, host: str = "127.0.0.1",
                 extra_args: Sequence[str] = (),
                 ready_timeout_s: float = 120.0,
                 env: Optional[Dict[str, str]] = None):
        self.index = index
        cmd = [
            sys.executable, "-m", "logparser_tpu.service",
            "--sidecar", "--host", host, *extra_args,
        ]
        child_env = dict(os.environ)
        if env:
            child_env.update(env)
        self._proc = subprocess.Popen(
            cmd, stdout=subprocess.PIPE, stderr=None, env=child_env,
            start_new_session=True, text=True,
        )
        ready: Dict[str, Any] = {}

        def read_ready() -> None:
            assert self._proc.stdout is not None
            for line in self._proc.stdout:
                if line.startswith("SIDECAR_READY "):
                    try:
                        ready.update(json.loads(line.split(" ", 1)[1]))
                    except ValueError:
                        pass
                    return

        reader = threading.Thread(target=read_ready, daemon=True)
        reader.start()
        reader.join(timeout=ready_timeout_s)
        if not ready:
            self.kill()
            raise SidecarSpawnError(
                f"sidecar {index} never reported SIDECAR_READY "
                f"(rc={self._proc.poll()})"
            )
        self.host = host
        self.port = int(ready["port"])
        self.metrics_port = int(ready["metrics_port"])
        # Keep the pipe drained so a chatty child can never block on a
        # full stdout buffer (logs ride stderr; this is belt-and-braces).
        threading.Thread(
            target=lambda: self._proc.stdout
            and self._proc.stdout.read(),
            daemon=True,
        ).start()

    @property
    def pid(self) -> int:
        return self._proc.pid

    def alive(self) -> bool:
        return self._proc.poll() is None

    def kill(self) -> None:
        """Hard death (SIGKILL): the crash-failover drill's primitive."""
        try:
            self._proc.kill()
        except OSError:
            pass

    def terminate(self) -> None:
        """SIGTERM: the sidecar CLI runs its graceful drain
        (docs/SERVICE.md) — readyz flips, admitted sessions finish."""
        try:
            self._proc.terminate()
        except OSError:
            pass

    def suspend(self, seconds: Optional[float] = None) -> None:
        """SIGSTOP — the WEDGE primitive: alive but silent, exactly what
        the heartbeat deadline exists to catch.  With ``seconds`` a
        timer SIGCONTs it back (the transient-stall shape)."""
        try:
            os.kill(self._proc.pid, signal.SIGSTOP)
        except OSError:
            return
        if seconds:
            def resume() -> None:
                try:
                    os.kill(self._proc.pid, signal.SIGCONT)
                except OSError:
                    pass
            t = threading.Timer(seconds, resume)
            t.daemon = True
            t.start()

    def wait(self, timeout_s: float) -> bool:
        try:
            self._proc.wait(timeout=timeout_s)
            return True
        except subprocess.TimeoutExpired:
            return False

    def close(self) -> None:
        if self.alive():
            self.terminate()
            if not self.wait(5.0):
                self.kill()
                self.wait(5.0)
        if self._proc.stdout is not None:
            try:
                self._proc.stdout.close()
            except OSError:
                pass


class LocalSidecar:
    """In-process sidecar stand-in (tests, and the bench's 1-sidecar
    reference): a real :class:`~logparser_tpu.service.ParseService` in
    THIS process, fronted over real sockets exactly like a child
    process would be.  ``kill()`` force-closes it (connections die
    mid-frame — the crash shape); ``suspend()`` stops its metrics
    endpoint (health probes go silent — the wedge shape)."""

    def __init__(self, index: int, **service_kwargs: Any):
        from .service import ParseService

        service_kwargs.setdefault("metrics_port", 0)
        self.index = index
        self._svc = ParseService(**service_kwargs).start()
        self.host = self._svc.host
        self.port = self._svc.port
        self.metrics_port = self._svc.metrics_port
        self._dead = False

    @property
    def pid(self) -> int:
        return os.getpid()

    @property
    def service(self):
        return self._svc

    def alive(self) -> bool:
        return not self._dead

    def kill(self) -> None:
        # Dead-by-flag first, teardown off-thread: a chaos kill fired
        # from a session thread must read as INSTANT death (the real
        # SIGKILL shape), not a blocking force-close join.
        self._dead = True
        threading.Thread(
            target=self._svc.shutdown,
            name=f"front-local-kill-{self.index}", daemon=True,
        ).start()

    def terminate(self) -> None:
        self._dead = True
        threading.Thread(
            target=lambda: self._svc.shutdown(drain=True),
            name=f"front-local-drain-{self.index}", daemon=True,
        ).start()

    def suspend(self, seconds: Optional[float] = None) -> None:
        if self._svc._metrics is not None:
            self._svc._metrics.shutdown()

    def wait(self, timeout_s: float) -> bool:
        end = time.monotonic() + timeout_s
        while time.monotonic() < end:
            if self._svc._teardown_done.is_set():
                return True
            time.sleep(0.02)
        return self._svc._teardown_done.is_set()

    def close(self) -> None:
        self._dead = True
        self._svc.shutdown()


def parse_sidecar_address(address: str) -> Tuple[str, int, int]:
    """``host:port:metrics_port`` -> its parts.  The metrics port is
    REQUIRED: the supervisor's health probes (readyz + occupancy
    scrape) are the only liveness signal the front has for a process it
    does not own."""
    parts = str(address).rsplit(":", 2)
    if len(parts) != 3:
        raise ValueError(
            f"sidecar address {address!r} must be host:port:metrics_port"
        )
    host, port_s, mport_s = parts
    try:
        port, mport = int(port_s), int(mport_s)
    except ValueError:
        raise ValueError(
            f"sidecar address {address!r}: ports must be integers"
        ) from None
    if not host or not (0 < port < 65536 and 0 < mport < 65536):
        raise ValueError(f"sidecar address {address!r} out of range")
    return host, port, mport


class AdoptedSidecar:
    """A sidecar this front did NOT spawn: an already-running
    ``--sidecar`` service at ``host:port:metrics_port`` — possibly on
    another machine (ROADMAP 2c: the per-host-front seam the pod story
    composes with).  Adoption sits behind the exact supervisor probes a
    spawned child gets: readyz + /metrics scrape each heartbeat,
    wedge/fault detection, and "respawn" = RE-ADOPT (the constructor
    re-probes the address; while the remote is down the respawn fails
    and the prober keeps re-deciding — when the remote operator brings
    it back, the slot rejoins warm).

    Process-control primitives are no-ops by design: the front does not
    own the remote process, so ``kill``/``terminate``/``suspend`` do
    nothing, ``alive()`` is always True (scrape silence, not waitpid,
    is the death signal), and a roll of an adopted slot is just a
    re-probe — rolling the actual process belongs to its own host's
    operator."""

    def __init__(self, index: int, address: str,
                 connect_timeout_s: float = 3.0):
        self.index = index
        self.address_spec = str(address)
        self.host, self.port, self.metrics_port = parse_sidecar_address(
            address)
        # Reachability probe — adopt-or-fail, mirroring spawn-or-fail:
        # a clean connect + close (no CONFIG frame; the service reads a
        # zero-length session, which its accept loop treats as EOF).
        try:
            probe = socket.create_connection(
                (self.host, self.port), timeout=connect_timeout_s)
            probe.close()
        except OSError as e:
            raise SidecarSpawnError(
                f"sidecar {index}: cannot adopt {self.address_spec} "
                f"({e})"
            ) from e
        metrics().increment("front_sidecar_adoptions_total")

    @property
    def pid(self) -> int:
        return -1  # not ours; there is no local pid

    def alive(self) -> bool:
        return True

    def kill(self) -> None:
        pass

    def terminate(self) -> None:
        pass

    def suspend(self, seconds: Optional[float] = None) -> None:
        pass

    def wait(self, timeout_s: float) -> bool:
        return True

    def close(self) -> None:
        pass


# ---------------------------------------------------------------------------
# /metrics aggregation
# ---------------------------------------------------------------------------

_SAMPLE_LINE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?( .+)$"
)
_COMMENT_FAMILY = re.compile(r"^# (?:TYPE|HELP) (\S+)")


def merge_expositions(own: str,
                      labeled: Sequence[Tuple[str, str]],
                      label: str = "sidecar") -> str:
    """One Prometheus text exposition for the whole fleet: the front's
    own families verbatim, then each sidecar's scrape with
    ``{label}="<name>"`` injected into every sample (docs/
    OBSERVABILITY.md "Fleet aggregation").  TYPE/HELP comments are
    emitted once per family across all sources (the validator requires
    a family's TYPE before its first sample; the declaration from the
    earliest source serves every later one)."""
    out: List[str] = []
    declared: set = set()
    for line in own.splitlines():
        m = _COMMENT_FAMILY.match(line)
        if m:
            declared.add(m.group(1))
        out.append(line)
    for name, text in labeled:
        inj = f'{label}="{name}"'
        for line in text.splitlines():
            if not line:
                continue
            if line.startswith("#"):
                m = _COMMENT_FAMILY.match(line)
                if m is None or m.group(1) in declared:
                    continue
                declared.add(m.group(1))
                out.append(line)
                continue
            m = _SAMPLE_LINE.match(line)
            if m is None:
                continue  # never relay a malformed sidecar line
            fam, labels, rest = m.group(1), m.group(2), m.group(3)
            if labels:
                out.append(f"{fam}{{{labels[1:-1]},{inj}}}{rest}")
            else:
                out.append(f"{fam}{{{inj}}}{rest}")
    return "\n".join(out) + "\n"


def _scrape(url: str, timeout_s: float = 3.0) -> str:
    with urllib.request.urlopen(url, timeout=timeout_s) as resp:
        return resp.read().decode("utf-8")


_GAUGE_RE_CACHE: Dict[str, re.Pattern] = {}


def _scrape_value(text: str, family: str) -> float:
    """Sum of one family's sample values in a scraped exposition."""
    pat = _GAUGE_RE_CACHE.get(family)
    if pat is None:
        pat = re.compile(
            r"^" + re.escape("logparser_tpu_" + family)
            + r"(?:\{[^}]*\})? (\S+)$", re.M,
        )
        _GAUGE_RE_CACHE[family] = pat
    return sum(float(v) for v in pat.findall(text))


# ---------------------------------------------------------------------------
# slots, tenants, routing
# ---------------------------------------------------------------------------


class _Slot:
    """One sidecar position in the fleet: the live handle plus the
    prober-maintained health/occupancy view the router reads."""

    def __init__(self, index: int):
        self.index = index
        self.name = f"sc{index}"
        self.handle: Optional[Any] = None
        self.generation = 0
        self.ready = False
        self.draining = False
        self.respawning = False
        self.last_ok = time.monotonic()
        self.occupancy = 0.0
        self.lock = threading.Lock()

    def address(self) -> Optional[Tuple[str, int]]:
        h = self.handle
        if h is None:
            return None
        return (h.host, h.port)


class _TenantLedger:
    """Per-tenant admission accounting (sessions + in-flight lines)."""

    def __init__(self, policy: FrontPolicy):
        self._policy = policy
        self._lock = threading.Lock()
        self._sessions: Dict[str, int] = {}
        self._lines: Dict[str, int] = {}

    def session_enter(self, tenant: str) -> bool:
        quota = self._policy.tenant_max_sessions
        with self._lock:
            n = self._sessions.get(tenant, 0)
            if quota and n >= quota:
                return False
            self._sessions[tenant] = n + 1
            return True

    def session_exit(self, tenant: str) -> None:
        with self._lock:
            n = self._sessions.get(tenant, 1) - 1
            if n > 0:
                self._sessions[tenant] = n
            else:
                self._sessions.pop(tenant, None)

    def lines_enter(self, tenant: str, n: int) -> bool:
        quota = self._policy.tenant_max_inflight_lines
        with self._lock:
            cur = self._lines.get(tenant, 0)
            if quota and cur + n > quota:
                return False
            self._lines[tenant] = cur + n
            return True

    def lines_exit(self, tenant: str, n: int) -> None:
        with self._lock:
            cur = self._lines.get(tenant, n) - n
            if cur > 0:
                self._lines[tenant] = cur
            else:
                self._lines.pop(tenant, None)


def key_label(parser_key: Any) -> str:
    """Short stable label for a parser cache key (metrics cardinality:
    8 hex chars, not the raw format string)."""
    return hashlib.blake2b(
        repr(parser_key).encode("utf-8"), digest_size=4
    ).hexdigest()


class _Router:
    """Rendezvous (HRW) affinity routing with occupancy spill: every
    (key, sidecar) pair gets a stable hash score; the ordered preference
    list only reshuffles the keys of a sidecar that LEAVES — exactly the
    property that keeps compiled-parser caches hot across membership
    churn."""

    def __init__(self, policy: FrontPolicy):
        self._policy = policy

    @staticmethod
    def _score(klabel: str, slot_name: str) -> bytes:
        return hashlib.blake2b(
            f"{klabel}:{slot_name}".encode("utf-8"), digest_size=8
        ).digest()

    def order(self, klabel: str, slots: Sequence[_Slot]) -> List[_Slot]:
        return sorted(
            slots, key=lambda s: self._score(klabel, s.name), reverse=True
        )

    def choose(self, klabel: str, candidates: Sequence[_Slot]
               ) -> Tuple[Optional[_Slot], bool]:
        """(chosen slot, spilled?) among routable candidates."""
        if not candidates:
            return None, False
        ordered = self.order(klabel, candidates)
        first = ordered[0]
        if (
            len(ordered) > 1
            and first.occupancy >= self._policy.spill_occupancy
            and ordered[1].occupancy < first.occupancy
        ):
            return ordered[1], True
        return first, False


def preferred_sidecar(parser_key: Any, n_sidecars: int) -> int:
    """Rendezvous first-choice sidecar INDEX for ``parser_key`` over a
    fully-healthy fleet of ``n_sidecars`` — computable statically
    (slot names are ``sc<i>``), which is how drills pick key sets that
    spread across the whole fleet deterministically."""
    kl = key_label(parser_key)
    best, best_score = 0, b""
    for i in range(n_sidecars):
        score = _Router._score(kl, f"sc{i}")
        if score > best_score:
            best, best_score = i, score
    return best


class _FleetPressure:
    """The fleet's aggregate occupancy as a process backpressure source:
    registered with :func:`logparser_tpu.feeder.register_backpressure_source`
    so the front's own admission leg (and anything else reading
    ``queue_backpressure()`` in this process) sees the sidecars'
    scraped coalesce-queue occupancy — backpressure aggregation ACROSS
    processes."""

    def __init__(self, front: "FrontTier"):
        self._front = front

    def backpressure(self) -> float:
        slots = [s for s in self._front._slots if s.ready]
        if not slots:
            return 0.0
        return min(1.0, min(s.occupancy for s in slots))

# ---------------------------------------------------------------------------
# the front tier
# ---------------------------------------------------------------------------


class _FrontServer(socketserver.ThreadingTCPServer):
    allow_reuse_address = True
    daemon_threads = True

    def __init__(self, addr, handler, front: "FrontTier"):
        super().__init__(addr, handler)
        self.front = front

    def handle_error(self, request, client_address) -> None:  # noqa: D102
        LOG.exception("front: unhandled session error from %s",
                      client_address)


def _read_raw_frame(sock: socket.socket, first_s: Optional[float],
                    rest_s: Optional[float],
                    max_frame: int = _MAX_FRAME,
                    payload_cap: Optional[int] = None
                    ) -> Tuple[str, bytes]:
    """One raw wire frame for RELAYING (never classifying):
    ``("eof", b"")`` on clean close or a length-0 frame,
    ``("error", text_bytes)`` for a marker + error-text pair,
    ``("data", payload)`` otherwise.  Raises :class:`_SessionTimeout` /
    ``ConnectionError`` / :class:`_FrameTooLarge` like the service's
    own reader — the proxy buffers whole frames so a mid-frame upstream
    death can still be answered with a STRUCTURED frame downstream."""
    header = _recv_exact_timed(sock, 4, first_s, rest_s)
    if header is None:
        return "eof", b""
    (length,) = struct.unpack(">I", header)
    if length == 0:
        return "eof", b""
    if length == _ERROR_MARKER:
        kind, payload = _read_raw_frame(sock, rest_s, rest_s, max_frame)
        if kind != "data":
            raise ConnectionError("error marker without its text frame")
        return "error", payload
    if length > max_frame:
        raise _FrameTooLarge(length, max_frame, fatal=True)
    if payload_cap is not None and length > payload_cap:
        raise _FrameTooLarge(length, payload_cap, fatal=True)
    payload = _recv_exact_timed(sock, length, rest_s, rest_s)
    if payload is None:
        raise ConnectionError(f"peer closed mid-frame (0/{length} bytes)")
    return "data", payload


class _FrontSessionHandler(socketserver.BaseRequestHandler):
    """One proxied session: CONFIG -> route by parser key -> relay
    frames, answering structured BUSY frames (never a reset) for every
    fleet-side failure mode."""

    server: _FrontServer

    def handle(self) -> None:  # noqa: D102 — socketserver contract
        front = self.server.front
        threading.current_thread().name = \
            f"front-sess-{next(front._session_seq)}"
        try:
            front._proxy_session(self.request)
        except Exception:  # noqa: BLE001 — a session must never kill/print
            LOG.exception("front: session failed")


class FrontTier:
    """The replicated front tier (module docstring; docs/SERVICE.md
    "Fleet").  ``spawner(index) -> handle`` builds one sidecar — the
    default spawns :class:`ProcessSidecar` children; tests and the
    bench inject :class:`LocalSidecar` (or stubs).  ``sidecar_args``
    ride every default-spawned child's CLI (version/config swaps roll
    through :meth:`roll`)."""

    def __init__(self, n_sidecars: int = 2, host: str = "127.0.0.1",
                 port: int = 0, metrics_port: Optional[int] = None,
                 policy: Optional[FrontPolicy] = None,
                 spawner: Optional[Callable[[int], Any]] = None,
                 sidecar_args: Sequence[str] = (),
                 sidecar_addresses: Sequence[str] = (),
                 warmup_fn: Optional[Callable[[Any], None]] = None,
                 chaos: Optional[Any] = None):
        self.policy = policy or FrontPolicy()
        # Remote sidecar ADOPTION (ROADMAP 2c): ``host:port:metrics_port``
        # addresses occupy the first len() slots (validated now, so a
        # typo fails construction, not a boot thread); any remaining
        # slots up to n_sidecars spawn local children as before.  The
        # supervisor treats both identically — probes, faults, circuit
        # breaking — except that "respawn" of an adopted slot re-probes
        # the address instead of forking a process.
        self._sidecar_addresses = [str(a) for a in sidecar_addresses]
        for a in self._sidecar_addresses:
            parse_sidecar_address(a)
        n_sidecars = max(n_sidecars, len(self._sidecar_addresses))
        # One chip per spawned sidecar (logparser_tpu/chips.py): the k-th
        # spawned slot is pinned to the host's k-th chip for every
        # respawn and roll (the old process is reaped first); a lone
        # sidecar keeps the host's chips.  More spawned sidecars than
        # chips fails here, not in a child.
        from .chips import assign_chips

        self._chip_envs = assign_chips(
            0 if spawner is not None
            else n_sidecars - len(self._sidecar_addresses))
        self.supervisor = FrontSupervisor(self.policy, n_sidecars)
        # The supervisor is a PURE machine; the fleet serializes every
        # consultation (session threads + the prober race otherwise —
        # two racing routable() calls must not both win the one
        # half-open trial).
        self._sup_lock = threading.Lock()
        # Metric-label bounds: parser keys and tenant names are
        # CLIENT-CONTROLLED, and every distinct label value is a
        # persistent series in the process registry — an unauthenticated
        # peer looping unique CONFIGs must not grow the front's memory
        # (and its merged exposition) without bound.  First N distinct
        # values keep their own label; the tail aggregates as
        # "overflow".
        self._label_lock = threading.Lock()
        self._key_label_set: set = set()
        self._tenant_label_set: set = set()
        self.router = _Router(self.policy)
        self._tenants = _TenantLedger(self.policy)
        self._slots = [_Slot(i) for i in range(n_sidecars)]
        self._sidecar_args = list(sidecar_args)
        # Optional post-spawn warmup (handle -> None): runs BEFORE a
        # sidecar is marked routable — at boot, after a crash respawn,
        # and during a roll — so a replacement sidecar re-enters the
        # fleet with its parsers compiled instead of paying the cold
        # compile inside a client's request ("any sidecar can absorb a
        # key after one warmup", and this is the one warmup).
        self._warmup_fn = warmup_fn
        self._session_seq = itertools.count(1)
        self._session_slots = threading.BoundedSemaphore(
            self.policy.max_sessions)
        self._host = host
        self._spawner = spawner or self._default_spawner
        self._server = _FrontServer((host, port), _FrontSessionHandler,
                                    self)
        self._thread: Optional[threading.Thread] = None
        self._probers: List[threading.Thread] = []
        self._stop = threading.Event()
        self.draining = False
        self._closed = False
        self._close_lock = threading.Lock()
        self._roll_lock = threading.Lock()
        self._serving = False
        self._pressure = _FleetPressure(self)
        self._http: Optional["_FrontEndpoint"] = None
        if metrics_port is not None:
            self._http = _FrontEndpoint(host, metrics_port, self)
        from .tools.chaos import ChaosSpec, FrontChaos

        spec = chaos if isinstance(chaos, ChaosSpec) else (
            ChaosSpec.parse(chaos) if isinstance(chaos, str)
            else chaos)
        if spec is None:
            spec = ChaosSpec.from_env()
        self.chaos = FrontChaos(spec) if spec is not None else None

    # -- lifecycle -------------------------------------------------------

    @property
    def host(self) -> str:
        return self._server.server_address[0]

    @property
    def port(self) -> int:
        return self._server.server_address[1]

    @property
    def metrics_port(self) -> Optional[int]:
        return self._http.port if self._http is not None else None

    def sidecars(self) -> List[Tuple[str, str, int, Optional[int]]]:
        """Live (name, host, port, metrics_port) per sidecar — the warm
        path for drills that must pre-compile a format on every
        sidecar without going through affinity routing."""
        out = []
        for slot in self._slots:
            h = slot.handle
            if h is not None:
                out.append((slot.name, h.host, h.port, h.metrics_port))
        return out

    def _bounded_label(self, pool: set, value: str) -> str:
        with self._label_lock:
            if value in pool:
                return value
            if len(pool) < _MAX_METRIC_LABELS:
                pool.add(value)
                return value
            return "overflow"

    def _key_metric_label(self, klabel: str) -> str:
        return self._bounded_label(self._key_label_set, klabel)

    def _tenant_label(self, tenant: str) -> str:
        return self._bounded_label(self._tenant_label_set, tenant)

    def _default_spawner(self, index: int) -> Any:
        if index < len(self._sidecar_addresses):
            return AdoptedSidecar(
                index, self._sidecar_addresses[index],
                connect_timeout_s=self.policy.connect_timeout_s,
            )
        return ProcessSidecar(
            index, host=self._host, extra_args=self._sidecar_args,
            ready_timeout_s=self.policy.ready_timeout_s,
            env=self.sidecar_env(index),
        )

    def sidecar_env(self, index: int) -> Dict[str, str]:
        """Environment overrides of the sidecar spawned for slot
        ``index``: its chip, or nothing on a host without chips."""
        return self._chip_envs[index - len(self._sidecar_addresses)]

    def _warm(self, handle: Any) -> None:
        if self._warmup_fn is None:
            return
        try:
            self._warmup_fn(handle)
        except Exception:  # noqa: BLE001 — a failed warmup is a slow
            # first request, not a dead sidecar.
            LOG.warning("front: warmup of sidecar %s failed; it joins "
                        "the fleet cold", getattr(handle, "index", "?"),
                        exc_info=True)

    def start(self) -> "FrontTier":
        """Spawn the fleet (in parallel — each sidecar pays a cold
        interpreter+jax start), then open the listener and the prober."""
        from .feeder import register_backpressure_source

        errors: List[BaseException] = []

        def boot(slot: _Slot) -> None:
            try:
                handle = self._spawner(slot.index)
                self._warm(handle)
                slot.handle = handle
                slot.ready = True
                slot.last_ok = time.monotonic()
                metrics().gauge_set("front_sidecar_ready", 1,
                                    labels={"sidecar": slot.name})
                if self.chaos is not None and self.chaos.on_ready(
                        slot.index):
                    handle.kill()  # flap_sidecar: die right at ready
            except BaseException as e:  # noqa: BLE001 — collected below
                errors.append(e)

        boots = [threading.Thread(target=boot, args=(s,), daemon=True)
                 for s in self._slots]
        for t in boots:
            t.start()
        for t in boots:
            t.join()
        if errors or not any(s.ready for s in self._slots):
            self.shutdown()
            raise SidecarSpawnError(
                f"fleet start failed: {errors or 'no sidecar ready'}"
            )
        register_backpressure_source(self._pressure)
        if self._http is not None:
            self._http.start()
        self._serving = True
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="front-accept",
            daemon=True,
        )
        self._thread.start()
        # One prober thread PER SLOT: a wedged sidecar's scrape blocks
        # its full timeout every beat, and a shared prober would let
        # one silent sidecar delay fault detection for the whole fleet.
        self._probers = [
            threading.Thread(
                target=self._probe_loop, args=(slot,),
                name=f"front-prober-{slot.name}", daemon=True,
            )
            for slot in self._slots
        ]
        for t in self._probers:
            t.start()
        LOG.info("front tier listening on %s:%d over %d sidecars",
                 self.host, self.port, len(self._slots))
        return self

    def __enter__(self) -> "FrontTier":
        return self.start()

    def __exit__(self, *exc: Any) -> None:
        self.shutdown()

    def shutdown(self) -> None:
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
        from .feeder import deregister_backpressure_source

        self._stop.set()
        self.draining = True
        deregister_backpressure_source(self._pressure)
        if self._serving:
            self._server.shutdown()
        self._server.server_close()
        if self._http is not None:
            self._http.shutdown()
        for slot in self._slots:
            h = slot.handle
            if h is not None:
                try:
                    h.close()
                except Exception:  # noqa: BLE001 — teardown must finish
                    note_teardown(
                        LOG, "front_teardown_errors_total",
                        "sidecar_close",
                        f"sidecar {slot.name} close failed",
                    )
        for prober in self._probers:
            prober.join(timeout=5)
        if self._thread is not None:
            self._thread.join(timeout=5)
            if self._thread.is_alive():
                note_teardown(
                    LOG, "front_teardown_errors_total", "accept_join",
                    "front accept loop outlived its 5 s join",
                )

    # -- supervision -----------------------------------------------------

    def _probe_loop(self, slot: _Slot) -> None:
        while not self._stop.wait(self.policy.heartbeat_interval_s):
            try:
                self._probe_slot(slot)
            except Exception:  # noqa: BLE001 — the prober must survive
                LOG.debug("front: probe of %s failed", slot.name,
                          exc_info=True)

    def _probe_slot(self, slot: _Slot) -> None:
        handle = slot.handle
        if handle is None or slot.respawning or slot.draining:
            return
        now = time.monotonic()
        if not handle.alive():
            self._on_sidecar_fault(slot, "died")
            return
        try:
            text = _scrape(
                f"http://{handle.host}:{handle.metrics_port}/metrics",
                timeout_s=min(3.0, self.policy.heartbeat_deadline_s),
            )
            ready = 200 == self._readyz(handle)
        except Exception:  # noqa: BLE001 — silence is the signal
            if slot.ready and \
                    now - slot.last_ok > self.policy.heartbeat_deadline_s:
                # Alive, IN the rotation, and unresponsive past the
                # deadline: WEDGED.  Kill first so the respawn never
                # races a zombie holding the ports.  (A slot that is
                # not ready — still warming, mid-respawn — gets the
                # spawn path's own budget instead.)
                handle.kill()
                self._on_sidecar_fault(slot, "wedged")
            return
        slot.last_ok = now
        slot.ready = ready
        depth = _scrape_value(text, "service_coalesce_queue_depth")
        slot.occupancy = min(1.0, depth / max(1.0, float(
            self._sidecar_queue_depth())))
        metrics().gauge_set("front_sidecar_ready", 1.0 if ready else 0.0,
                            labels={"sidecar": slot.name})
        metrics().gauge_set("front_sidecar_occupancy", slot.occupancy,
                            labels={"sidecar": slot.name})

    def _sidecar_queue_depth(self) -> int:
        """The coalesce submission-queue bound the fleet's sidecars run
        with (the front spawns them, so it knows): the denominator of
        the scraped occupancy fraction."""
        args = self._sidecar_args
        for i, a in enumerate(args):
            if a == "--coalesce-queue-depth" and i + 1 < len(args):
                try:
                    return int(args[i + 1])
                except ValueError:
                    break
        from .service import ServiceLimits

        return ServiceLimits().coalesce_queue_depth

    @staticmethod
    def _readyz(handle: Any) -> int:
        import urllib.error

        try:
            with urllib.request.urlopen(
                f"http://{handle.host}:{handle.metrics_port}/readyz",
                timeout=3,
            ) as resp:
                return resp.status
        except urllib.error.HTTPError as e:
            return e.code

    def _on_sidecar_fault(self, slot: _Slot, kind: str) -> None:
        with slot.lock:
            # A draining slot is DELIBERATE churn (mid-roll): its
            # session failovers must not spawn a racing replacement —
            # the roll itself installs the successor.
            if slot.respawning or slot.draining or self._stop.is_set():
                return
            slot.respawning = True
        slot.ready = False
        metrics().gauge_set("front_sidecar_ready", 0,
                            labels={"sidecar": slot.name})
        now = time.monotonic()
        with self._sup_lock:
            decision = self.supervisor.on_fault(slot.index, now)
        if decision.circuit_opened:
            metrics().increment("front_circuit_open_total",
                                labels={"sidecar": slot.name})
            flight_event("front_circuit_open", sidecar=slot.name)
            LOG.warning("front: circuit OPEN around flapping sidecar %s",
                        slot.name)
        flight_event("front_sidecar_fault", sidecar=slot.name, fault=kind,
                     action=decision.action,
                     backoff_s=round(decision.backoff_s, 3))
        LOG.warning("front: sidecar %s fault (%s) -> %s (backoff %.2fs)",
                    slot.name, kind, decision.action, decision.backoff_s)
        if decision.action == "disable":
            log_warning_once(
                LOG,
                f"front: sidecar slot {slot.name} exhausted its restart "
                "budget and is DISABLED (a rolling restart revives it)",
            )
            slot.respawning = False
            return
        threading.Thread(
            target=self._respawn, args=(slot, decision.backoff_s),
            name=f"front-respawn-{slot.name}", daemon=True,
        ).start()

    def _respawn(self, slot: _Slot, backoff_s: float) -> None:
        try:
            if backoff_s and self._stop.wait(backoff_s):
                return
            old = slot.handle
            if old is not None:
                try:
                    old.close()
                except Exception:  # noqa: BLE001 — a corpse may resist
                    pass
            handle = self._spawner(slot.index)
            self._warm(handle)
            slot.handle = handle
            slot.generation += 1
            slot.last_ok = time.monotonic()
            slot.ready = True
            self.supervisor.total_restarts += 1
            metrics().increment("front_restarts_total",
                                labels={"sidecar": slot.name})
            metrics().gauge_set("front_sidecar_ready", 1,
                                labels={"sidecar": slot.name})
            LOG.info("front: sidecar %s respawned (generation %d)",
                     slot.name, slot.generation)
            if self.chaos is not None and self.chaos.on_ready(slot.index):
                handle.kill()  # flap_sidecar: die again at ready
        except Exception:  # noqa: BLE001 — the prober re-decides next beat
            LOG.exception("front: respawn of %s failed", slot.name)
            slot.last_ok = time.monotonic()  # restart the wedge clock
        finally:
            slot.respawning = False

    # -- rolling restart -------------------------------------------------

    def roll(self, drain_timeout_s: Optional[float] = None,
             sidecar_args: Optional[Sequence[str]] = None) -> None:
        """Zero-downtime rolling restart (docs/SERVICE.md "Fleet"): one
        sidecar at a time — routing stops handing it NEW sessions, its
        process drains gracefully under the round-12 machinery (readyz
        flip, admitted sessions finish, deadline escalation), a fresh
        one (optionally with new ``sidecar_args`` — the config/version
        swap) takes the slot, and only then does the next sidecar
        start.  The rest of the fleet absorbs the drained keys: with a
        retrying client, zero failed requests."""
        budget = (drain_timeout_s if drain_timeout_s is not None
                  else self.policy.drain_timeout_s)
        with self._roll_lock:
            if sidecar_args is not None:
                self._sidecar_args = list(sidecar_args)
            for slot in self._slots:
                if self._stop.is_set():
                    return
                LOG.info("front: rolling sidecar %s", slot.name)
                slot.draining = True
                # A fault-respawn already mid-flight finishes first (it
                # owns slot.handle until it clears the flag).
                wait_end = time.monotonic() + 30.0
                while slot.respawning and time.monotonic() < wait_end:
                    time.sleep(0.05)
                try:
                    old = slot.handle
                    if old is not None and old.alive():
                        old.terminate()
                        if not old.wait(budget):
                            LOG.warning(
                                "front: sidecar %s outlived its drain "
                                "budget; killing", slot.name)
                            old.kill()
                            old.wait(5.0)
                    if old is not None:
                        try:
                            old.close()
                        except Exception:  # noqa: BLE001
                            pass
                    handle = self._spawner(slot.index)
                    self._warm(handle)
                    slot.handle = handle
                    slot.generation += 1
                    slot.last_ok = time.monotonic()
                    slot.ready = True
                    with self._sup_lock:
                        self.supervisor.on_deliberate_restart(slot.index)
                    metrics().increment("front_rolls_total",
                                        labels={"sidecar": slot.name})
                    metrics().gauge_set("front_sidecar_ready", 1,
                                        labels={"sidecar": slot.name})
                finally:
                    slot.draining = False
                LOG.info("front: sidecar %s rolled (generation %d)",
                         slot.name, slot.generation)

    # -- routing + the proxy ---------------------------------------------

    def _routable_slots(self, now: float) -> List[_Slot]:
        with self._sup_lock:
            return [
                s for s in self._slots
                if s.ready and not s.draining and not s.respawning
                and s.handle is not None and s.handle.alive()
                and self.supervisor.routable(s.index, now)
            ]

    def _shed(self, sock: socket.socket, reason: str,
              tenant: Optional[str] = None) -> None:
        metrics().increment("front_shed_total", labels={"reason": reason})
        flight_event("front_shed", reason=reason, tenant=tenant)
        if tenant is not None:
            metrics().increment("front_tenant_shed_total",
                                labels={"tenant": self._tenant_label(
                                    tenant)})
        try:
            sock.settimeout(self.policy.idle_timeout_s)
            write_error(sock, busy_error_text(
                reason, self.policy.busy_retry_after_s))
            _linger_drain(sock)
        except OSError:
            pass

    def _failover(self, sock: socket.socket, slot: _Slot,
                  kind: str) -> None:
        """A dead/unreachable sidecar under a live client session: the
        structured answer (never a reset), the fault report, and the
        connection-level close the reason implies."""
        metrics().increment("front_failovers_total")
        metrics().increment("front_shed_total",
                            labels={"reason": "sidecar_failover"})
        flight_event("front_failover", sidecar=slot.name, fault=kind)
        LOG.warning("front: session failover off sidecar %s (%s)",
                    slot.name, kind)
        try:
            sock.settimeout(self.policy.idle_timeout_s)
            write_error(sock, busy_error_text(
                "sidecar_failover", self.policy.busy_retry_after_s))
            _linger_drain(sock)
        except OSError:
            pass

    def _proxy_session(self, sock: socket.socket) -> None:
        metrics().increment("front_sessions_total")
        if self.draining:
            self._shed(sock, "draining")
            return
        if not self._session_slots.acquire(blocking=False):
            self._shed(sock, "sessions")
            return
        try:
            self._proxy_admitted(sock)
        finally:
            self._session_slots.release()

    def _proxy_admitted(self, sock: socket.socket) -> None:
        pol = self.policy
        try:
            kind, config_raw = _read_raw_frame(
                sock, pol.idle_timeout_s, pol.frame_timeout_s,
                payload_cap=pol.max_config_bytes,
            )
        except (_SessionTimeout, _FrameTooLarge, ConnectionError,
                OSError) as e:
            LOG.info("front: config read failed: %s", e)
            return
        if kind != "data":
            return
        tenant = "default"
        send_stats = False
        config: Any = None
        parser_key: Any = ("raw", hashlib.blake2b(
            config_raw, digest_size=8).hexdigest())
        try:
            config = json.loads(config_raw)
            if isinstance(config, dict):
                tenant = str(config.get("tenant") or "default")
                send_stats = bool(config.get("stats"))
                parser_key = _ParserCache.key_of(config)
        except Exception:  # noqa: BLE001 — junk config still routes; the
            pass           # sidecar answers the structured config error
        klabel = key_label(parser_key)

        # Root session span (docs/OBSERVABILITY.md "Tracing"): a sampled
        # session gets the front's root context injected into the
        # relayed CONFIG — the ONLY case the config is re-serialized.
        # Unsampled sessions forward the client's RAW bytes untouched,
        # so an untraced session stays byte-identical on the wire
        # (golden protocol vectors replay unchanged).
        span = None
        if isinstance(config, dict):
            span = root_span("front_session",
                             traceparent=config.get("traceparent"),
                             attrs={"tenant": tenant, "key": klabel})
            if span is not None:
                config["traceparent"] = span.traceparent
                config_raw = json.dumps(config).encode("utf-8")

        # Tenant fairness + fleet backpressure at the front door.
        if not self._tenants.session_enter(tenant):
            if span is not None:
                span.end(outcome="shed", reason="tenant_quota")
            self._shed(sock, "tenant_quota", tenant=tenant)
            return
        try:
            from .feeder import queue_backpressure

            if queue_backpressure() >= pol.backpressure_threshold:
                if span is not None:
                    span.end(outcome="shed", reason="backpressure")
                self._shed(sock, "backpressure")
                return
            self._proxy_routed(sock, config_raw, klabel, tenant,
                               send_stats)
        finally:
            self._tenants.session_exit(tenant)
            if span is not None:
                span.end()

    def _connect_upstream(self, sock: socket.socket, klabel: str,
                          config_raw: bytes
                          ) -> Optional[Tuple[_Slot, socket.socket]]:
        """Route + connect + forward CONFIG, walking the rendezvous
        order through connect failures (each one a reported fault)."""
        pol = self.policy
        tried: set = set()
        while True:
            now = time.monotonic()
            candidates = [s for s in self._routable_slots(now)
                          if s.index not in tried]
            slot, spilled = self.router.choose(klabel, candidates)
            if slot is None:
                return None
            if spilled:
                metrics().increment("front_spills_total")
            addr = slot.address()
            if addr is None:
                tried.add(slot.index)
                continue
            try:
                up = socket.create_connection(
                    addr, timeout=pol.connect_timeout_s)
                up.settimeout(pol.upstream_timeout_s)
                write_frame(up, config_raw)
            except OSError:
                tried.add(slot.index)
                self._on_sidecar_fault(slot, "connect")
                continue
            with self._sup_lock:
                self.supervisor.on_success(slot.index, now)
            metrics().increment(
                "front_sessions_routed_total",
                labels={"key": self._key_metric_label(klabel),
                        "sidecar": slot.name},
            )
            if self.chaos is not None:
                action = self.chaos.on_routed(slot.index)
                if action == "kill":
                    slot.handle.kill()
                elif action == "wedge":
                    slot.handle.suspend(self.chaos.wedge_seconds(
                        slot.index))
            return slot, up

    def _proxy_routed(self, sock: socket.socket, config_raw: bytes,
                      klabel: str, tenant: str,
                      send_stats: bool) -> None:
        pol = self.policy
        routed = self._connect_upstream(sock, klabel, config_raw)
        if routed is None:
            self._shed(sock, "sidecar_failover")
            return
        slot, up = routed
        try:
            while True:
                try:
                    kind, payload = _read_raw_frame(
                        sock, pol.idle_timeout_s, pol.frame_timeout_s,
                    )
                except _SessionTimeout:
                    metrics().increment("front_timeouts_total",
                                        labels={"side": "client"})
                    return
                except (_FrameTooLarge, ConnectionError, OSError):
                    return
                if kind == "eof":
                    try:
                        up.sendall(struct.pack(">I", 0))
                    except OSError:
                        pass
                    return
                if kind == "error":
                    return  # a client must not send marker frames
                # Tenant in-flight-lines quota: the count prefix is the
                # first 4 payload bytes of a LINES frame.
                n_lines = struct.unpack(">I", payload[:4])[0] \
                    if len(payload) >= 4 else 0
                if not self._tenants.lines_enter(tenant, n_lines):
                    # Request-level tenant shed: a DISTINCT reason from
                    # the session-level ``tenant_quota`` — this one
                    # keeps the session, so the client must not burn a
                    # reconnect (RECONNECT_BUSY_REASONS) on it.
                    metrics().increment(
                        "front_tenant_shed_total",
                        labels={"tenant": self._tenant_label(tenant)})
                    metrics().increment(
                        "front_shed_total",
                        labels={"reason": "tenant_inflight"})
                    try:
                        sock.settimeout(pol.idle_timeout_s)
                        write_error(sock, busy_error_text(
                            "tenant_inflight", pol.busy_retry_after_s))
                    except OSError:
                        return
                    continue
                try:
                    if not self._relay_request(sock, up, slot, payload,
                                               send_stats):
                        return
                finally:
                    self._tenants.lines_exit(tenant, n_lines)
        finally:
            try:
                up.close()
            except OSError:
                pass

    def _relay_request(self, sock: socket.socket, up: socket.socket,
                       slot: _Slot, payload: bytes,
                       send_stats: bool) -> bool:
        """Forward one request frame and relay its response frame(s).
        False = the session must end (socket died, or a
        connection-level shed was relayed)."""
        pol = self.policy
        try:
            write_frame(up, payload)
            kind, body = _read_raw_frame(
                up, pol.upstream_timeout_s, pol.frame_timeout_s)
        except (_SessionTimeout, ConnectionError, OSError,
                _FrameTooLarge) as e:
            self._failover(sock, slot, f"{type(e).__name__}: {e}")
            self._on_sidecar_fault(slot, "relay")
            return False
        try:
            sock.settimeout(pol.idle_timeout_s)
            if kind == "eof":
                # The sidecar closed where a response was due: the
                # crash-mid-request shape.
                self._failover(sock, slot, "eof mid-request")
                self._on_sidecar_fault(slot, "relay")
                return False
            if kind == "error":
                sock.sendall(struct.pack(">I", _ERROR_MARKER))
                write_frame(sock, body)
                text = body.decode("utf-8", errors="replace")
                if text.startswith("BUSY"):
                    try:
                        reason = json.loads(text[4:].strip()).get("reason")
                    except Exception:  # noqa: BLE001 — junk JSON: keep open
                        reason = None
                    if reason in RECONNECT_BUSY_REASONS:
                        # The sidecar is closing the upstream by
                        # contract; mirror it downstream.
                        _linger_drain(sock)
                        return False
                return True
            write_frame(sock, body)
            if send_stats:
                kind, stats_body = _read_raw_frame(
                    up, pol.upstream_timeout_s, pol.frame_timeout_s)
                if kind != "data":
                    self._failover(sock, slot, "eof before STATS")
                    self._on_sidecar_fault(slot, "relay")
                    return False
                write_frame(sock, stats_body)
            metrics().increment("front_requests_relayed_total")
            return True
        except OSError:
            return False

    def stats(self) -> Dict[str, Any]:
        return {
            "sidecars": [
                {
                    "name": s.name,
                    "generation": s.generation,
                    "ready": s.ready,
                    "draining": s.draining,
                    "occupancy": round(s.occupancy, 4),
                }
                for s in self._slots
            ],
            "supervisor": self.supervisor.summary(),
        }


# ---------------------------------------------------------------------------
# the fleet HTTP endpoint: merged /metrics + health + /rollz
# ---------------------------------------------------------------------------


class _FrontHttpHandler(BaseHTTPRequestHandler):
    """GET /metrics -> the MERGED fleet exposition (front families +
    every live sidecar's scrape under a ``sidecar`` label); GET
    /tracez, /flightz -> the front's spans / flight events plus every
    live sidecar's, keyed by slot name; GET /healthz -> front liveness;
    GET /readyz -> 200 while >= 1 sidecar is ready (503 otherwise /
    while draining); POST /rollz -> trigger a background rolling
    restart (the loadgen ``--roll`` hook)."""

    server: ThreadingHTTPServer

    def do_GET(self) -> None:  # noqa: N802 — BaseHTTPRequestHandler contract
        front: FrontTier = self.server.front  # type: ignore[attr-defined]
        path = self.path.split("?", 1)[0].rstrip("/") or "/metrics"
        if path == "/metrics":
            scraped: List[Tuple[str, str]] = []
            for name, host, _port, mport in front.sidecars():
                if mport is None:
                    continue
                try:
                    scraped.append(
                        (name, _scrape(f"http://{host}:{mport}/metrics"))
                    )
                except Exception:  # noqa: BLE001 — a dead sidecar scrapes empty
                    continue
            body = merge_expositions(
                metrics().prometheus_text(), scraped
            ).encode("utf-8")
            self._respond(200, body,
                          "text/plain; version=0.0.4; charset=utf-8")
            return
        if path in ("/tracez", "/flightz"):
            # The fleet's trace/flight view in one scrape: the front's
            # own payload plus each live sidecar's, keyed by slot name
            # (a dead sidecar reports its scrape error instead).
            own = tracez_payload() if path == "/tracez" \
                else flightz_payload()
            sidecars: Dict[str, Any] = {}
            for name, host, _port, mport in front.sidecars():
                if mport is None:
                    continue
                try:
                    sidecars[name] = json.loads(
                        _scrape(f"http://{host}:{mport}{path}"))
                except Exception as e:  # noqa: BLE001 — dead sidecar
                    sidecars[name] = {"error": str(e)}
            body = json.dumps({"front": own, "sidecars": sidecars},
                              sort_keys=True).encode("utf-8")
            self._respond(200, body, "application/json")
            return
        if path in ("/healthz", "/readyz"):
            ready = [s.name for s in front._slots if s.ready]
            if path == "/healthz":
                status, code = "ok", 200
            elif front.draining or not ready:
                status, code = "draining" if front.draining \
                    else "no_sidecar", 503
            else:
                status, code = "ready", 200
            body = json.dumps({
                "status": status,
                "sidecars_ready": len(ready),
                "sidecars": len(front._slots),
            }, sort_keys=True).encode("utf-8")
            self._respond(code, body, "application/json")
            return
        self.send_error(404)

    def do_POST(self) -> None:  # noqa: N802
        front: FrontTier = self.server.front  # type: ignore[attr-defined]
        path = self.path.split("?", 1)[0].rstrip("/")
        if path == "/rollz":
            threading.Thread(target=front.roll, name="front-roll",
                             daemon=True).start()
            body = json.dumps({"status": "rolling"}).encode("utf-8")
            self._respond(202, body, "application/json")
            return
        self.send_error(404)

    def _respond(self, code: int, body: bytes, content_type: str) -> None:
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, fmt: str, *args: Any) -> None:  # silence stderr
        LOG.debug("front http: " + fmt, *args)


class _FrontEndpoint:
    def __init__(self, host: str, port: int, front: FrontTier):
        self._server = ThreadingHTTPServer((host, port),
                                           _FrontHttpHandler)
        self._server.daemon_threads = True
        self._server.front = front  # type: ignore[attr-defined]
        self._thread: Optional[threading.Thread] = None

    @property
    def port(self) -> int:
        return self._server.server_address[1]

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="front-metrics",
            daemon=True,
        )
        self._thread.start()

    def shutdown(self) -> None:
        if self._thread is not None:
            self._server.shutdown()
        self._server.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def main(argv: Optional[Sequence[str]] = None) -> int:
    """``python -m logparser_tpu.front``: run the front tier over N
    spawned sidecar processes.  SIGTERM shuts the front down; SIGHUP
    triggers a rolling restart of the fleet (also POST /rollz on the
    metrics port)."""
    import argparse

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8123)
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="fleet /metrics + /readyz + POST /rollz port")
    ap.add_argument("--sidecars", type=int, default=2)
    ap.add_argument("--adopt", action="append", default=[],
                    metavar="HOST:PORT:METRICS_PORT",
                    help="adopt an already-running sidecar at this "
                         "address instead of spawning one (repeatable; "
                         "adopted addresses fill the first slots, "
                         "--sidecars still spawns the rest)")
    ap.add_argument("--tenant-max-sessions", type=int, default=0)
    ap.add_argument("--tenant-max-inflight-lines", type=int, default=0)
    ap.add_argument("--spill-occupancy", type=float, default=0.5)
    ap.add_argument("--heartbeat-deadline", type=float, default=5.0)
    ap.add_argument("--compile-cache", default=None, metavar="DIR",
                    help="persistent compile-cache directory "
                         "(docs/COMPILE.md) — exported as "
                         "JAX_COMPILATION_CACHE_DIR to every spawned "
                         "sidecar, so respawns and rolling restarts warm "
                         "up by DESERIALIZING cached executables instead "
                         "of recompiling")
    ap.add_argument("--log-level", default=os.environ.get(
        "LOGPARSER_TPU_LOG_LEVEL", "INFO"))
    ap.add_argument("sidecar_args", nargs="*",
                    help="extra args passed through to every sidecar "
                         "(e.g. -- --request-deadline 5)")
    args = ap.parse_args(argv)
    if args.compile_cache:
        # Spawned sidecars inherit the front's environment (ProcessSidecar
        # copies os.environ), so one export here covers the whole fleet —
        # including every future respawn and rolling-restart replacement.
        # (set directly: the tpu package would import JAX here).
        os.environ["JAX_COMPILATION_CACHE_DIR"] = args.compile_cache
    logging.basicConfig(
        level=getattr(logging, str(args.log_level).upper(), logging.INFO),
        format="%(asctime)s %(levelname)s %(name)s: %(message)s",
    )
    policy = FrontPolicy(
        tenant_max_sessions=args.tenant_max_sessions,
        tenant_max_inflight_lines=args.tenant_max_inflight_lines,
        spill_occupancy=args.spill_occupancy,
        heartbeat_deadline_s=args.heartbeat_deadline,
    )
    front = FrontTier(
        n_sidecars=args.sidecars, host=args.host, port=args.port,
        metrics_port=args.metrics_port, policy=policy,
        sidecar_args=args.sidecar_args,
        sidecar_addresses=args.adopt,
    )
    signal.signal(signal.SIGHUP,
                  lambda *_: threading.Thread(target=front.roll,
                                              daemon=True).start())
    stop = threading.Event()

    def _on_sigterm(*_: Any) -> None:
        # Crash-safe postmortem before the shutdown proceeds
        # (docs/OBSERVABILITY.md "Flight recorder").
        from .tracing import dump_flight

        flight_event("sigterm_shutdown")
        dump_flight("sigterm")
        stop.set()

    signal.signal(signal.SIGTERM, _on_sigterm)
    from .tracing import (
        arm_flight_signals,
        install_flight_excepthook,
        sweep_flight_dumps,
    )

    sweep_flight_dumps()
    arm_flight_signals()
    install_flight_excepthook()
    front.start()
    try:
        while not stop.wait(0.5):
            pass
    except KeyboardInterrupt:
        pass
    finally:
        front.shutdown()
    return 0


if __name__ == "__main__":  # pragma: no cover — CLI
    raise SystemExit(main())
