"""Replicated front tier (logparser_tpu/front.py, docs/SERVICE.md
"Fleet"): the pure supervision machine (circuit breaker, restart
budgets), rendezvous affinity routing + occupancy spill, exposition
merging, and the live proxy invariants — tenant quotas, structured
sidecar failover, rolling restart, and fleet-vs-solo byte parity."""
import json
import socket
import struct
import threading
import time

import pytest

from logparser_tpu.front import (
    FrontPolicy,
    FrontSupervisor,
    FrontTier,
    LocalSidecar,
    _Router,
    _Slot,
    key_label,
    merge_expositions,
    preferred_sidecar,
)
from logparser_tpu.observability import metrics
from logparser_tpu.service import (
    ParseServiceClient,
    ParseServiceError,
    ServiceBusyError,
    ServiceUnavailableError,
    _ParserCache,
)

FIELDS = ["IP:connection.client.host", "STRING:request.status.last"]
CONFIG = {"log_format": "combined", "fields": FIELDS,
          "timestamp_format": None}
KEY = _ParserCache.key_of(CONFIG)


# ---------------------------------------------------------------------------
# one chip per spawned sidecar (logparser_tpu/chips.py; no chip needed:
# the chip list is injected and no child is started)
# ---------------------------------------------------------------------------


class TestChipPinning:
    @pytest.fixture()
    def four_chips(self, monkeypatch):
        from logparser_tpu import chips

        monkeypatch.setattr(chips, "host_chips", lambda: [0, 1, 2, 3])

    @pytest.mark.parametrize("n", [2, 3, 4])
    def test_each_spawned_sidecar_gets_its_own_chip(self, four_chips, n):
        front = FrontTier(n_sidecars=n)
        try:
            envs = [front.sidecar_env(i) for i in range(n)]
        finally:
            front.shutdown()
        assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == [
            str(i) for i in range(n)]
        assert len({e["TPU_PROCESS_PORT"] for e in envs}) == n
        for e in envs:
            assert e["TPU_PROCESS_BOUNDS"] == "1,1,1"
            assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"

    def test_lone_sidecar_keeps_the_hosts_chips(self, four_chips):
        # One device-owning child fights nobody: it is not pinned, so a
        # machine that lets this process open only some of its chips
        # cannot be handed a chip index it cannot open.
        front = FrontTier(n_sidecars=1)
        front.shutdown()
        assert front.sidecar_env(0) == {}

    def test_more_sidecars_than_chips_refused_at_construction(
            self, four_chips):
        from logparser_tpu.chips import ChipOversubscribedError

        with pytest.raises(ChipOversubscribedError, match="4 TPU chip"):
            FrontTier(n_sidecars=5)

    def test_injected_spawner_is_not_pinned(self, four_chips):
        # Tests and the bench inject in-process sidecars: they own no
        # child process, so no chip is handed out (and 5 > 4 is fine).
        front = FrontTier(n_sidecars=5, spawner=lambda i: None)
        front.shutdown()

    @pytest.mark.parametrize("env, want", [
        ({"JAX_PLATFORMS": "cpu"}, []),
        ({"JAX_PLATFORMS": "tpu", "TPU_VISIBLE_CHIPS": "2,3"}, [2, 3]),
    ])
    def test_host_chips_follows_platform_and_own_pinning(
            self, monkeypatch, env, want):
        from logparser_tpu.chips import assign_chips, host_chips

        for k, v in env.items():
            monkeypatch.setenv(k, v)
        assert host_chips() == want
        envs = assign_chips(2)
        assert [e.get("TPU_VISIBLE_CHIPS") for e in envs] == (
            [str(c) for c in want] or [None] * 2)


# ---------------------------------------------------------------------------
# the pure supervision machine (fast tier: no sockets, no sleeps)
# ---------------------------------------------------------------------------


def _policy(**kw):
    base = dict(circuit_threshold=3, flap_window_s=10.0,
                circuit_open_s=5.0, max_restarts=5,
                restart_budget_window_s=60.0)
    base.update(kw)
    return FrontPolicy(**base)


class TestFrontSupervisor:
    def test_respawn_with_growing_backoff(self):
        sup = FrontSupervisor(_policy(), 2)
        d1 = sup.on_fault(0, now=0.0)
        d2 = sup.on_fault(0, now=1.0)
        assert d1.action == d2.action == "respawn"
        assert d2.backoff_s > d1.backoff_s
        assert sup.routable(1, now=1.0)  # the other slot is untouched

    def test_circuit_opens_at_flap_threshold(self):
        sup = FrontSupervisor(_policy(circuit_threshold=3), 1)
        assert not sup.on_fault(0, 0.0).circuit_opened
        assert not sup.on_fault(0, 1.0).circuit_opened
        d = sup.on_fault(0, 2.0)
        assert d.circuit_opened
        assert not sup.routable(0, now=2.1)  # open: routed around

    def test_half_open_trial_closes_on_success(self):
        sup = FrontSupervisor(_policy(circuit_open_s=5.0), 1)
        for t in (0.0, 1.0, 2.0):
            sup.on_fault(0, t)
        assert not sup.routable(0, now=4.0)       # still cooling
        assert sup.routable(0, now=8.0)           # the ONE trial
        assert not sup.routable(0, now=8.1)       # no second trial
        sup.on_success(0, now=8.2)
        assert sup.state[0] == FrontSupervisor.CLOSED
        assert sup.routable(0, now=8.3)

    def test_half_open_trial_failure_reopens(self):
        sup = FrontSupervisor(_policy(circuit_open_s=5.0), 1)
        for t in (0.0, 1.0, 2.0):
            sup.on_fault(0, t)
        assert sup.routable(0, now=8.0)           # trial admitted
        sup.on_fault(0, now=8.5)                  # trial died
        assert sup.state[0] == FrontSupervisor.OPEN
        assert not sup.routable(0, now=9.0)
        assert sup.routable(0, now=14.0)          # next cool-off, next trial

    def test_stale_half_open_trial_escapes(self):
        """A half-open trial that was admitted but never reported back
        (rendezvous routed the session elsewhere) must not park the
        slot HALF_OPEN forever: another cool-off window re-admits a
        fresh trial."""
        sup = FrontSupervisor(_policy(circuit_open_s=5.0), 1)
        for t in (0.0, 1.0, 2.0):
            sup.on_fault(0, t)
        assert sup.routable(0, now=8.0)      # trial 1 (never routed)
        assert not sup.routable(0, now=9.0)  # window still running
        assert sup.routable(0, now=13.5)     # stale: trial 2 admitted
        sup.on_success(0, now=13.6)
        assert sup.state[0] == FrontSupervisor.CLOSED

    def test_budget_exhaustion_disables(self):
        sup = FrontSupervisor(_policy(max_restarts=2), 1)
        assert sup.on_fault(0, 0.0).action == "respawn"
        assert sup.on_fault(0, 0.1).action == "respawn"
        d = sup.on_fault(0, 0.2)
        assert d.action == "disable"
        assert sup.disabled[0]
        assert not sup.routable(0, now=100.0)  # disabled outlives windows

    def test_budget_window_slides(self):
        sup = FrontSupervisor(_policy(max_restarts=2,
                                      restart_budget_window_s=10.0), 1)
        sup.on_fault(0, 0.0)
        sup.on_fault(0, 1.0)
        # Two old faults slid out of the window: a rare fault at t=100
        # is respawned, not disabled.
        assert sup.on_fault(0, 100.0).action == "respawn"

    def test_deliberate_restart_resets_everything(self):
        sup = FrontSupervisor(_policy(max_restarts=1), 1)
        sup.on_fault(0, 0.0)
        sup.on_fault(0, 0.1)          # disabled
        assert sup.disabled[0]
        sup.on_deliberate_restart(0)
        assert not sup.disabled[0]
        assert sup.routable(0, now=0.2)


class TestRouter:
    def _slots(self, n, occupancy=()):
        slots = []
        for i in range(n):
            s = _Slot(i)
            s.occupancy = occupancy[i] if i < len(occupancy) else 0.0
            slots.append(s)
        return slots

    def test_affinity_order_is_stable(self):
        r = _Router(FrontPolicy())
        slots = self._slots(4)
        o1 = [s.name for s in r.order("abcd1234", slots)]
        o2 = [s.name for s in r.order("abcd1234", slots)]
        assert o1 == o2

    def test_membership_change_moves_only_lost_keys(self):
        """THE rendezvous property: removing one sidecar reroutes ONLY
        the keys that lived on it — everyone else's compiled state
        stays hot."""
        r = _Router(FrontPolicy())
        slots = self._slots(4)
        keys = [f"key{i:03d}" for i in range(64)]
        before = {k: r.order(k, slots)[0].name for k in keys}
        survivors = [s for s in slots if s.name != "sc2"]
        after = {k: r.order(k, survivors)[0].name for k in keys}
        for k in keys:
            if before[k] != "sc2":
                assert after[k] == before[k], k

    def test_spill_on_occupancy(self):
        pol = FrontPolicy(spill_occupancy=0.5)
        r = _Router(pol)
        slots = self._slots(2)
        first = r.order("k", slots)[0]
        second = r.order("k", slots)[1]
        chosen, spilled = r.choose("k", slots)
        assert chosen is first and not spilled
        first.occupancy = 0.9
        chosen, spilled = r.choose("k", slots)
        assert chosen is second and spilled
        # No spill when the second choice is just as hot: affinity wins.
        second.occupancy = 0.95
        chosen, spilled = r.choose("k", slots)
        assert chosen is first and not spilled

    def test_preferred_sidecar_matches_router(self):
        r = _Router(FrontPolicy())
        slots = self._slots(3)
        for key in (("combined", ("a",), None, None), ("x", ("b",), 1, 2)):
            kl = key_label(key)
            assert slots[preferred_sidecar(key, 3)] is r.order(kl, slots)[0]


class TestMergeExpositions:
    def test_label_injection_and_validity(self):
        from logparser_tpu.tools.metrics_smoke import validate_exposition

        own = ("# TYPE front_failovers_total counter\n"
               "front_failovers_total 2\n")
        sc = ("# TYPE service_requests_total counter\n"
              "service_requests_total 5\n"
              '# TYPE service_shed_total counter\n'
              'service_shed_total{reason="sessions"} 1\n')
        merged = merge_expositions(own, [("sc0", sc), ("sc1", sc)])
        assert validate_exposition(merged) == []
        assert 'service_requests_total{sidecar="sc0"} 5' in merged
        assert ('service_shed_total{reason="sessions",sidecar="sc1"} 1'
                in merged)
        # TYPE declared once per family across sources.
        assert merged.count("# TYPE service_requests_total counter") == 1


# ---------------------------------------------------------------------------
# live integration (slow tier): LocalSidecar fleets with injected
# parsers — no XLA compile inside the drills.
# ---------------------------------------------------------------------------


def _shared(config=None):
    from _shared_parsers import shared_parser

    cfg = config or CONFIG
    return shared_parser(cfg["log_format"], cfg["fields"], view_fields=())


def _inject(svc, config=None):
    cfg = config or CONFIG
    svc._server.parser_cache._parsers[
        _ParserCache.key_of(cfg)] = _shared(cfg)


def _spawner(configs=None, **sidecar_kwargs):
    def spawn(index):
        sc = LocalSidecar(index, drain_deadline_s=2.0, **sidecar_kwargs)
        for cfg in (configs or [CONFIG]):
            _inject(sc.service, cfg)
        return sc
    return spawn


def _quick_policy(**kw):
    base = dict(heartbeat_interval_s=0.2, heartbeat_deadline_s=5.0,
                backoff_base_s=0.05, busy_retry_after_s=0.02,
                drain_timeout_s=8.0)
    base.update(kw)
    return FrontPolicy(**base)


LINES = [
    '9.8.7.6 - - [01/Jan/2026:00:00:00 +0000] "GET /a HTTP/1.1" 200 5 '
    '"-" "ua"',
    '1.2.3.4 - - [01/Jan/2026:00:00:01 +0000] "GET /b HTTP/1.1" 404 7 '
    '"-" "ua"',
]


@pytest.mark.slow
def test_affinity_same_key_same_sidecar():
    """Absent spill, every session of one parser key lands on the SAME
    sidecar (the compiled-state-stays-hot invariant)."""
    with FrontTier(n_sidecars=3, spawner=_spawner(),
                   policy=_quick_policy()) as front:
        kl = key_label(KEY)
        expected = front.router.order(kl, front._slots)[0].name
        before = {
            s.name: metrics().get("front_sessions_routed_total",
                                  labels={"key": kl, "sidecar": s.name})
            for s in front._slots
        }
        for _ in range(3):
            with ParseServiceClient(front.host, front.port, "combined",
                                    FIELDS) as c:
                assert c.parse(LINES).num_rows == 2
        for s in front._slots:
            routed = metrics().get(
                "front_sessions_routed_total",
                labels={"key": kl, "sidecar": s.name},
            ) - before[s.name]
            assert routed == (3 if s.name == expected else 0), s.name


@pytest.mark.slow
def test_spill_under_occupancy():
    """A hot first choice (live occupancy >= spill_occupancy) spills
    the session to its second rendezvous choice."""
    pol = _quick_policy(spill_occupancy=0.5, heartbeat_interval_s=30.0)
    before = metrics().get("front_spills_total")
    with FrontTier(n_sidecars=2, spawner=_spawner(), policy=pol) as front:
        kl = key_label(KEY)
        order = front.router.order(kl, front._slots)
        order[0].occupancy = 0.8  # the prober is parked (30 s interval)
        with ParseServiceClient(front.host, front.port, "combined",
                                FIELDS) as c:
            assert c.parse(LINES).num_rows == 2
        routed = metrics().get(
            "front_sessions_routed_total",
            labels={"key": kl, "sidecar": order[1].name})
        assert routed >= 1
    assert metrics().get("front_spills_total") >= before + 1


@pytest.mark.slow
def test_tenant_session_quota():
    """tenant_max_sessions bounds ONE tenant's concurrent sessions with
    a structured BUSY{tenant_quota}; other tenants stay unaffected."""
    pol = _quick_policy(tenant_max_sessions=1)
    before = metrics().get("front_tenant_shed_total",
                           labels={"tenant": "noisy"})
    with FrontTier(n_sidecars=2, spawner=_spawner(), policy=pol) as front:
        hold = ParseServiceClient(front.host, front.port, "combined",
                                  FIELDS, tenant="noisy")
        try:
            assert hold.parse(LINES).num_rows == 2
            with pytest.raises(ServiceBusyError) as ei:
                ParseServiceClient(front.host, front.port, "combined",
                                   FIELDS, tenant="noisy").parse(LINES)
            assert ei.value.reason == "tenant_quota"
            # A QUIET tenant is untouched by the noisy one's quota.
            with ParseServiceClient(front.host, front.port, "combined",
                                    FIELDS, tenant="quiet") as other:
                assert other.parse(LINES).num_rows == 2
        finally:
            hold.close()
        # The slot frees when the holder leaves.
        deadline = time.monotonic() + 5.0
        while True:
            try:
                with ParseServiceClient(front.host, front.port,
                                        "combined", FIELDS,
                                        tenant="noisy") as again:
                    assert again.parse(LINES).num_rows == 2
                break
            except ServiceBusyError:
                assert time.monotonic() < deadline
                time.sleep(0.05)
    assert metrics().get("front_tenant_shed_total",
                         labels={"tenant": "noisy"}) >= before + 1


@pytest.mark.slow
def test_tenant_inflight_lines_quota():
    """tenant_max_inflight_lines sheds an over-quota REQUEST with the
    request-level reason ``tenant_inflight`` (DISTINCT from the
    session-level ``tenant_quota``, which closes the connection): the
    session survives and the client resends on the same socket."""
    pol = _quick_policy(tenant_max_inflight_lines=4)
    with FrontTier(n_sidecars=1, spawner=_spawner(), policy=pol) as front:
        with ParseServiceClient(front.host, front.port, "combined",
                                FIELDS, tenant="bulk") as c:
            with pytest.raises(ServiceBusyError) as ei:
                c.parse(LINES * 3)  # 6 lines > the 4-line quota
            assert ei.value.reason == "tenant_inflight"
            from logparser_tpu.service import RECONNECT_BUSY_REASONS

            assert "tenant_inflight" not in RECONNECT_BUSY_REASONS
            # The session survives and a within-quota request works.
            assert c.parse(LINES).num_rows == 2


@pytest.mark.slow
def test_failover_structured_and_reroute():
    """A sidecar dying under a live session yields a structured
    BUSY{sidecar_failover} (never a reset); a retrying client lands on
    a live sidecar; the supervisor respawns the slot."""
    failovers0 = metrics().get("front_failovers_total")
    with FrontTier(n_sidecars=2, spawner=_spawner(),
                   policy=_quick_policy()) as front:
        kl = key_label(KEY)
        victim = front.router.order(kl, front._slots)[0]
        gen0 = victim.generation
        client = ParseServiceClient(front.host, front.port, "combined",
                                    FIELDS)
        try:
            assert client.parse(LINES).num_rows == 2
            victim.handle.kill()
            # The in-process "kill" closes asynchronously: keep sending
            # until the dead upstream surfaces — the answer must be the
            # structured failover shed, never an unstructured close.
            deadline = time.monotonic() + 10.0
            while True:
                try:
                    client.parse(LINES)
                except ServiceBusyError as e:
                    assert e.reason == "sidecar_failover"
                    break
                assert time.monotonic() < deadline, \
                    "dead sidecar never surfaced as a failover"
                time.sleep(0.02)
        finally:
            client.close()
        # A retrying client (the documented contract) lands on a LIVE
        # sidecar.
        with ParseServiceClient(front.host, front.port, "combined",
                                FIELDS, busy_retries=10,
                                connect_retries=5) as retry:
            assert retry.parse(LINES).num_rows == 2
        assert metrics().get("front_failovers_total") >= failovers0 + 1
        # The slot respawns (fresh generation).
        deadline = time.monotonic() + 10.0
        while victim.generation == gen0 or not victim.ready:
            assert time.monotonic() < deadline, "victim never respawned"
            time.sleep(0.05)
        assert front.supervisor.total_restarts >= 1


@pytest.mark.slow
def test_wedge_detection_respawns():
    """An ALIVE but silent sidecar (health endpoint gone) trips the
    heartbeat deadline: killed + respawned."""
    pol = _quick_policy(heartbeat_interval_s=0.1,
                        heartbeat_deadline_s=0.5)
    with FrontTier(n_sidecars=2, spawner=_spawner(), policy=pol) as front:
        slot = front._slots[0]
        gen0 = slot.generation
        slot.handle.suspend()  # metrics endpoint goes dark
        deadline = time.monotonic() + 15.0
        while slot.generation == gen0 or not slot.ready:
            assert time.monotonic() < deadline, "wedge never detected"
            time.sleep(0.05)


@pytest.mark.slow
def test_rolling_restart_under_traffic():
    """front.roll() replaces every sidecar one at a time while a
    retrying client keeps parsing: zero failed requests, every
    generation advances."""
    with FrontTier(n_sidecars=2, spawner=_spawner(),
                   policy=_quick_policy(drain_timeout_s=5.0)) as front:
        gens = [s.generation for s in front._slots]
        stop = threading.Event()
        failures = []
        oks = [0]

        def traffic():
            client = None
            while not stop.is_set():
                try:
                    if client is None:
                        client = ParseServiceClient(
                            front.host, front.port, "combined", FIELDS,
                            busy_retries=20, connect_retries=10,
                            timeout=10.0)
                    assert client.parse(LINES).num_rows == 2
                    oks[0] += 1
                except ServiceBusyError:
                    # Structured shed mid-roll: reconnect-class handled
                    # inside parse(); a leftover session-level shed just
                    # means a fresh client next loop.
                    client = None
                except Exception as e:  # noqa: BLE001 — the forbidden class
                    failures.append(e)
                    client = None

        t = threading.Thread(target=traffic, daemon=True)
        t.start()
        try:
            time.sleep(0.3)
            front.roll(drain_timeout_s=5.0)
            time.sleep(0.3)
        finally:
            stop.set()
            t.join(timeout=30)
        assert not failures, failures[:3]
        assert oks[0] > 0
        rolled = [s.generation for s in front._slots]
        assert all(b > a for a, b in zip(gens, rolled)), (gens, rolled)


@pytest.mark.slow
def test_client_fails_fast_on_dead_fleet():
    """max_redirect_retries: with every sidecar down and respawn
    disabled, a retrying client raises ServiceUnavailableError after
    the redirect budget instead of burning its whole busy_retries
    budget on reconnect loops."""
    pol = _quick_policy(max_restarts=0, heartbeat_interval_s=0.05,
                        circuit_threshold=1)
    with FrontTier(n_sidecars=2, spawner=_spawner(), policy=pol) as front:
        for slot in front._slots:
            slot.handle.kill()
        # Wait for the prober to disable both slots (budget 0).
        deadline = time.monotonic() + 10.0
        while not all(front.supervisor.disabled):
            assert time.monotonic() < deadline
            time.sleep(0.05)
        t0 = time.monotonic()
        with pytest.raises(ServiceUnavailableError):
            ParseServiceClient(
                front.host, front.port, "combined", FIELDS,
                busy_retries=1000, max_redirect_retries=3,
                backoff_base_s=0.01, backoff_max_s=0.05,
            ).parse(LINES)
        # Fails FAST: 3 redirects, not 1000 busy retries.
        assert time.monotonic() - t0 < 10.0


@pytest.mark.slow
def test_fleet_parity_bench_configs():
    """Byte parity (acceptance): for every wire-expressible bench
    config, a session served THROUGH the front returns ARROW payloads
    byte-identical to a solo ParseService session — the front is a
    pure relay whatever the routing did."""
    import bench
    from logparser_tpu.service import ParseService

    def payloads_for(corpus):
        out = []
        cursor = 0
        for n in (1, 23, 64):
            rows = [corpus[(cursor + j) % len(corpus)] for j in range(n)]
            out.append(struct.pack(">I", n)
                       + "\n".join(rows).encode())
            cursor += n
        return out

    def run_session(host, port, config_payload, payloads):
        sock = socket.create_connection((host, port))
        try:
            sock.settimeout(60)
            sock.sendall(struct.pack(">I", len(config_payload))
                         + config_payload)
            got = []
            for p in payloads:
                sock.sendall(struct.pack(">I", len(p)) + p)
                header = sock.recv(4, socket.MSG_WAITALL)
                (n,) = struct.unpack(">I", header)
                assert n != 0xFFFFFFFF, "error frame during parity run"
                buf = bytearray()
                while len(buf) < n:
                    chunk = sock.recv(n - len(buf))
                    assert chunk
                    buf.extend(chunk)
                got.append(bytes(buf))
            sock.sendall(struct.pack(">I", 0))
            return got
        finally:
            sock.close()

    wire_configs = [
        (name, fmt, fields, lines_fn)
        for name, fmt, fields, lines_fn, extra in bench.build_configs()
        if not extra
    ]
    for name, fmt, fields, lines_fn in wire_configs:
        corpus = lines_fn(96)
        cfg = {"log_format": fmt, "fields": list(fields),
               "timestamp_format": None}
        config_payload = json.dumps(cfg).encode()
        payloads = payloads_for(corpus)
        with ParseService(coalesce=False) as solo:
            _inject(solo, cfg)
            ref = run_session(solo.host, solo.port, config_payload,
                              payloads)
        with FrontTier(n_sidecars=2, spawner=_spawner(configs=[cfg]),
                       policy=_quick_policy()) as front:
            got = run_session(front.host, front.port, config_payload,
                              payloads)
        assert got == ref, f"{name}: fleet bytes differ from solo"


# ---------------------------------------------------------------------------
# remote sidecar ADOPTION (ROADMAP 2c): host:port:metrics_port slots
# behind the same supervisor probes as spawned children.
# ---------------------------------------------------------------------------


class TestAdoptedSidecar:
    def test_address_parsing(self):
        from logparser_tpu.front import parse_sidecar_address

        assert parse_sidecar_address("10.0.0.5:8123:9100") == \
            ("10.0.0.5", 8123, 9100)
        for bad in ("nope", "host:1", "host:0:9", "host:1:99999",
                    "host:x:y", ":1:2"):
            with pytest.raises(ValueError):
                parse_sidecar_address(bad)

    def test_adopt_probes_reachability(self):
        from logparser_tpu.front import AdoptedSidecar, SidecarSpawnError

        srv = socket.socket()
        srv.bind(("127.0.0.1", 0))
        srv.listen(1)
        host, port = srv.getsockname()
        try:
            sc = AdoptedSidecar(0, f"{host}:{port}:9100")
            # Process control is deliberately inert: the front does not
            # own the remote process.
            assert sc.alive() and sc.wait(0.0) and sc.pid == -1
            sc.kill(), sc.terminate(), sc.suspend(), sc.close()
            assert sc.alive()
        finally:
            srv.close()
        with pytest.raises(SidecarSpawnError):
            AdoptedSidecar(0, f"{host}:{port}:9100",
                           connect_timeout_s=0.2)

    def test_front_validates_addresses_at_construction(self):
        with pytest.raises(ValueError):
            FrontTier(n_sidecars=1, sidecar_addresses=["garbage"])

    def test_router_and_supervisor_treat_adopted_slot_normally(self):
        """An adopted handle sits in a _Slot exactly like a spawned one:
        routable while ready, faultable, circuit-breakable — the
        supervisor machine never looks at the handle type."""
        from logparser_tpu.front import AdoptedSidecar

        srv = socket.socket()
        srv.bind(("127.0.0.1", 0))
        srv.listen(1)
        host, port = srv.getsockname()
        try:
            slot = _Slot(0)
            slot.handle = AdoptedSidecar(0, f"{host}:{port}:9100")
            slot.ready = True
            sup = FrontSupervisor(_policy(), 1)
            assert sup.routable(0, now=0.0)
            assert slot.handle.alive()
            d = sup.on_fault(0, now=1.0)
            assert d.action == "respawn"
            sup.on_success(0, now=2.0)
            assert sup.routable(0, now=2.1)
        finally:
            srv.close()


@pytest.mark.slow
def test_adopted_sidecar_serves_and_dies_unroutable():
    """A front over ONE adopted in-process service: sessions route and
    parse through it (parity with the injected parser); when the remote
    dies, the slot leaves the rotation via the probe path and a re-adopt
    of the dead address keeps failing — new sessions get structured
    BUSY, never a reset."""
    from logparser_tpu.service import ParseService

    svc = ParseService(metrics_port=0).start()
    _inject(svc)
    addr = f"{svc.host}:{svc.port}:{svc.metrics_port}"
    adoptions0 = metrics().get("front_sidecar_adoptions_total")
    front = FrontTier(
        n_sidecars=1, sidecar_addresses=[addr],
        policy=_quick_policy(heartbeat_deadline_s=0.6,
                             connect_timeout_s=0.5),
    ).start()
    try:
        assert metrics().get("front_sidecar_adoptions_total") \
            > adoptions0
        with ParseServiceClient(front.host, front.port, "combined",
                                FIELDS) as c:
            table = c.parse(LINES)
            assert table.num_rows == 2
        # remote dies (operator's machine went away)
        svc.shutdown()
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if not front._routable_slots(time.monotonic()):
                break
            time.sleep(0.1)
        assert not front._routable_slots(time.monotonic()), \
            "dead adopted sidecar never left the rotation"
        with pytest.raises((ServiceBusyError, ServiceUnavailableError,
                            ParseServiceError)):
            with ParseServiceClient(front.host, front.port, "combined",
                                    FIELDS, busy_retries=0,
                                    connect_retries=0) as c:
                c.parse(LINES)
    finally:
        front.shutdown()
        svc.shutdown()
