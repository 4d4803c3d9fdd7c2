"""Loadgen + SLO harness for the sidecar parse service (docs/SERVICE.md).

ROADMAP item 4's measurement half: N concurrent clients drive a live
:class:`~logparser_tpu.service.ParseService` with bursty, open-loop-style
arrivals over MIXED formats, and every wire outcome is classified the way
an SLO cares about it:

- ``ok``            — ARROW frame back; latency recorded (p50/p99).
- ``busy``          — structured ``BUSY`` shed (the server refusing work
  the DEFINED way); ``busy_unstructured`` counts BUSY frames whose JSON
  detail failed to parse (must stay 0), ``busy_reasons`` breaks sheds
  down by the server's reason code.
- ``deadline``      — structured ``DEADLINE`` response (request expired
  server-side, session survived).
- ``errors``        — ordinary per-request error frames.
- ``resets``        — the FORBIDDEN outcome: a connection that died where
  a response frame was due (RST/EOF).  ``make service-smoke`` holds
  this at 0 under an overload burst.

Arrival model: each client schedules bursts of ``burst`` back-to-back
requests every ``interval_s`` on the wall clock.  When the service is
slower than the schedule the client is already late and fires
immediately — the backlog IS the overload — which is the open-loop
property closed-loop harnesses lack (they politely slow down with the
server and hide the melt).

Used two ways: ``tools/service_smoke.py`` (CI) and standalone::

    python -m logparser_tpu.tools.loadgen --port 8123 --clients 8
"""
from __future__ import annotations

import json
import math
import random
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..service import (
    RECONNECT_BUSY_REASONS,
    ParseServiceClient,
    ParseServiceError,
    ServiceBusyError,
    ServiceClosedError,
    ServiceDeadlineError,
)

#: (name, log_format, fields) triples the mixed-tenant drill rotates
#: through per client index — two real formats so the parser cache and
#: per-session compile reuse are part of what the SLO measures.
DEFAULT_FORMATS: Tuple[Tuple[str, str, List[str]], ...] = (
    ("combined", "combined",
     ["IP:connection.client.host", "STRING:request.status.last"]),
    ("common", '%h %l %u %t "%r" %>s %b',
     ["IP:connection.client.host", "BYTES:response.body.bytes"]),
)


def make_lines(format_name: str, n: int, seed: int = 7) -> List[str]:
    """A corpus for one of the DEFAULT_FORMATS entries."""
    from .demolog import generate_combined_lines, truncate_to_common

    lines = generate_combined_lines(n, seed=seed)
    if format_name == "common":
        lines = [truncate_to_common(ln) for ln in lines]
    return lines


@dataclass
class _ClientStats:
    requests: int = 0
    ok: int = 0
    busy: int = 0
    busy_unstructured: int = 0
    deadline: int = 0
    errors: int = 0
    resets: int = 0
    connect_errors: int = 0
    lines_ok: int = 0
    tenant: Optional[str] = None
    busy_reasons: Dict[str, int] = field(default_factory=dict)
    latencies: List[float] = field(default_factory=list)
    #: outcome class -> sampled trace ids (--trace; capped so a long
    #: window cannot bloat the record — enough to grep /tracez with).
    trace_ids: Dict[str, List[str]] = field(default_factory=dict)

    def note_trace(self, outcome: str, trace_id: Optional[str]) -> None:
        if not trace_id:
            return
        ids = self.trace_ids.setdefault(outcome, [])
        if len(ids) < _TRACE_IDS_CAP and trace_id not in ids:
            ids.append(trace_id)

    def merge(self, other: "_ClientStats") -> None:
        self.requests += other.requests
        self.ok += other.ok
        self.busy += other.busy
        self.busy_unstructured += other.busy_unstructured
        self.deadline += other.deadline
        self.errors += other.errors
        self.resets += other.resets
        self.connect_errors += other.connect_errors
        self.lines_ok += other.lines_ok
        for k, v in other.busy_reasons.items():
            self.busy_reasons[k] = self.busy_reasons.get(k, 0) + v
        self.latencies.extend(other.latencies)
        for k, ids in other.trace_ids.items():
            for tid in ids:
                self.note_trace(k, tid)


#: Per outcome class, how many example trace ids --trace keeps.
_TRACE_IDS_CAP = 8


#: Prometheus families the coalesce occupancy report reads
#: (docs/OBSERVABILITY.md "Continuous batching").
_COALESCE_PREFIX = "logparser_tpu_service_coalesce"


def scrape_metrics(url: str) -> Dict[str, float]:
    """Flat {series_name_with_labels: value} view of one Prometheus text
    exposition scrape (comment lines dropped)."""
    import urllib.request

    with urllib.request.urlopen(url, timeout=10) as resp:
        text = resp.read().decode("utf-8")
    out: Dict[str, float] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name, _, value = line.rpartition(" ")
        try:
            out[name] = float(value)
        except ValueError:
            continue
    return out


def coalesce_report(before: Dict[str, float],
                    after: Dict[str, float]) -> Dict[str, Any]:
    """The continuous-batching occupancy report for one loadgen window,
    from /metrics scrapes taken around it: formed batches, mean batch
    occupancy (fill fraction of the configured geometry), mean coalesced
    sessions per batch, mean queue wait — the server-side half of the
    SLO record (the outcome counts above are the client-side half)."""
    def delta(name: str) -> float:
        return after.get(name, 0.0) - before.get(name, 0.0)

    spb = _COALESCE_PREFIX + "d_sessions_per_batch"
    occ = _COALESCE_PREFIX + "_batch_occupancy"
    wait = _COALESCE_PREFIX + "_wait_seconds"
    batches = delta(spb + "_count")
    waits = delta(wait + "_count")
    return {
        "batches": int(batches),
        "mean_sessions_per_batch": round(delta(spb + "_sum") / batches, 3)
        if batches else None,
        "mean_batch_occupancy": round(delta(occ + "_sum") / batches, 4)
        if batches else None,
        "mean_wait_ms": round(delta(wait + "_sum") / waits * 1000.0, 3)
        if waits else None,
        "expired_in_queue": int(delta(
            "logparser_tpu_service_coalesce_expired_total")),
    }


def _drive_native(host: str, port: int, cfg: Tuple[str, str, List[str]],
                  lines: List[str], duration_s: float, timeout_s: float,
                  stats: _ClientStats, exe: str, workdir: str) -> None:
    """One client driven by the compiled C++ protocol client
    (native/svc_client.cc): closed-loop back-to-back requests for the
    window, outcomes merged from its JSON report.  The fast driver takes
    the Python client's GIL share out of the measurement loop — the
    loadgen process spends its cycles on the OTHER clients."""
    import json as _json
    import os
    import subprocess

    _name, log_format, fields = cfg
    config_path = os.path.join(workdir, f"config-{_name}.json")
    lines_path = os.path.join(workdir, f"lines-{_name}.txt")
    if not os.path.exists(config_path):
        with open(config_path, "w") as f:
            _json.dump({"log_format": log_format, "fields": fields,
                        "timestamp_format": None}, f)
    if not os.path.exists(lines_path):
        with open(lines_path, "w") as f:
            f.write("\n".join(lines))
    try:
        out = subprocess.run(
            [exe, "--host", host, "--port", str(port),
             "--config", config_path, "--lines", lines_path,
             "--duration", str(duration_s)],
            capture_output=True, text=True,
            timeout=duration_s + timeout_s + 10.0,
        )
        rec = _json.loads(out.stdout)
    except Exception:  # noqa: BLE001 — a dead driver reads as a reset
        stats.requests += 1
        stats.resets += 1
        return
    stats.ok += int(rec.get("ok", 0))
    stats.busy += int(rec.get("busy", 0))
    stats.deadline += int(rec.get("deadline", 0))
    stats.errors += int(rec.get("errors", 0))
    stats.resets += int(rec.get("resets", 0))
    stats.lines_ok += int(rec.get("lines_ok", 0))
    stats.requests += sum(int(rec.get(k, 0)) for k in
                          ("ok", "busy", "deadline", "errors", "resets"))
    stats.latencies.extend(
        ms / 1000.0 for ms in rec.get("latencies_ms", ())
    )


def _quiet_close(client: Optional[ParseServiceClient]) -> None:
    if client is not None:
        try:
            client.close()
        except OSError:
            pass


def _percentile_ms(latencies: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile in ms: rank ceil(q*n) (1-based), so p99 of
    100 samples is the 99th value, not the max.  The epsilon absorbs
    float noise in q*n (0.99 * 200 = 198.000...03 must stay rank 198)."""
    if not latencies:
        return None
    ordered = sorted(latencies)
    rank = math.ceil(q * len(ordered) - 1e-9)
    idx = max(0, min(len(ordered) - 1, rank - 1))
    return round(ordered[idx] * 1000.0, 3)


def _drive(host: str, port: int, cfg: Tuple[str, str, List[str]],
           lines: List[str], stop_at: float, interval_s: float, burst: int,
           timeout_s: float, rng: random.Random,
           stats: _ClientStats, trace: bool = False) -> None:
    _name, log_format, fields = cfg
    client: Optional[ParseServiceClient] = None
    trace_id: Optional[str] = None
    next_t = time.monotonic() + rng.uniform(0.0, interval_s)
    while time.monotonic() < stop_at:
        if client is None:
            traceparent = None
            if trace:
                # A fresh SAMPLED head per connection: the session's
                # requests join one trace, and the record names its id
                # under whichever outcome class the requests land in —
                # /tracez lookups start from here (docs/OBSERVABILITY.md
                # "Tracing").
                from ..tracing import new_trace_context

                ctx = new_trace_context(sampled=True)
                traceparent, trace_id = ctx.traceparent(), ctx.trace_id
            try:
                client = ParseServiceClient(
                    host, port, log_format, fields, timeout=timeout_s,
                    tenant=stats.tenant, traceparent=traceparent,
                )
            except OSError:
                stats.connect_errors += 1
                time.sleep(0.02)
                continue
        for _ in range(burst):
            if time.monotonic() >= stop_at:
                break
            stats.requests += 1
            t0 = time.monotonic()
            try:
                table = client.parse(lines)
            except ServiceBusyError as e:
                stats.busy += 1
                stats.note_trace("busy", trace_id)
                if not e.structured:
                    stats.busy_unstructured += 1
                stats.busy_reasons[e.reason] = (
                    stats.busy_reasons.get(e.reason, 0) + 1
                )
                if e.reason in RECONNECT_BUSY_REASONS:
                    # Connection-level shed: the server closes this
                    # socket by contract — reconnect (after the hint)
                    # to keep the overload pressure standing.  A
                    # failover reconnect is what lands the session on a
                    # LIVE sidecar behind a front tier (docs/SERVICE.md
                    # "Fleet").
                    _quiet_close(client)
                    client = None
                time.sleep(max(e.retry_after_s, 0.01) * rng.uniform(0.5, 1.5))
                break
            except ServiceDeadlineError:
                stats.deadline += 1
                stats.note_trace("deadline", trace_id)
            except ServiceClosedError:
                stats.resets += 1
                stats.note_trace("resets", trace_id)
                _quiet_close(client)
                client = None
                break
            except ParseServiceError:
                stats.errors += 1
                stats.note_trace("errors", trace_id)
            except OSError:
                stats.resets += 1
                stats.note_trace("resets", trace_id)
                _quiet_close(client)
                client = None
                break
            else:
                stats.ok += 1
                stats.note_trace("ok", trace_id)
                stats.lines_ok += table.num_rows
                stats.latencies.append(time.monotonic() - t0)
        # Open-loop pacing: the NEXT burst is due on the clock, not after
        # this one's responses; a late client fires immediately.
        next_t += interval_s
        now = time.monotonic()
        if next_t > now:
            time.sleep(min(next_t - now, max(0.0, stop_at - now)))
    _quiet_close(client)


def tenant_of(client_index: int, tenants: int) -> Optional[str]:
    """Skewed tenant assignment for the fairness drills: tenant ``t0``
    is the NOISY one (every even client), the rest share the odd
    clients round-robin — so quota enforcement visibly protects the
    quiet tenants from the loud one."""
    if tenants <= 0:
        return None
    if tenants == 1 or client_index % 2 == 0:
        return "t0"
    return f"t{1 + (client_index // 2) % (tenants - 1)}"


def run_loadgen(host: str, port: int, *, clients: int = 8,
                duration_s: float = 3.0, batch_lines: int = 128,
                burst: int = 4, interval_s: float = 0.05,
                formats: Optional[Sequence[Tuple[str, str, List[str]]]] = None,
                seed: int = 7, timeout_s: float = 30.0,
                metrics_url: Optional[str] = None,
                native: bool = False,
                tenants: int = 0,
                trace: bool = False,
                mid_run_fn: Optional[Any] = None,
                mid_run_at_s: Optional[float] = None) -> Dict[str, Any]:
    """Drive the service at ``host:port`` and return the SLO record:
    outcome counts, ok-request p50/p99 (ms), and goodput
    (ok lines per wall second).

    ``formats`` with a SINGLE entry is the many-small-clients shared-
    format scenario (every client on one parser cache key — the shape
    continuous batching coalesces, docs/SERVICE.md).  ``metrics_url``
    (the server's /metrics endpoint) adds a ``coalesce`` block with the
    server-side occupancy report for the window.  ``native=True`` runs
    each client through the compiled C++ protocol client
    (native/svc_client.cc) instead of the Python one — closed-loop
    back-to-back requests, no burst pacing — falling back to the Python
    driver when no toolchain is available.

    ``tenants`` > 0 assigns every client a tenant identity with SKEWED
    load (:func:`tenant_of`; the CONFIG ``tenant`` key the front
    tier's fairness quotas act on), and the record grows a per-tenant
    outcome table.  ``mid_run_fn`` runs ONCE on a helper thread at
    ``mid_run_at_s`` (default mid-window) — the rolling-restart-under-
    load trigger ``make fleet-smoke`` uses — and the record notes
    whether it completed inside the window."""
    fmts = list(formats or DEFAULT_FORMATS)
    corpora = {name: make_lines(name, batch_lines, seed=seed)
               for name, _lf, _f in fmts}
    per_client = [
        _ClientStats(tenant=tenant_of(i, tenants)) for i in range(clients)
    ]
    native_exe = None
    workdir = None
    if native:
        from ..native import svc_client_path

        native_exe = svc_client_path()
        if native_exe is not None:
            import tempfile

            workdir = tempfile.mkdtemp(prefix="loadgen-native-")
    before = scrape_metrics(metrics_url) if metrics_url else None
    t_start = time.monotonic()
    stop_at = t_start + duration_s
    mid_run: Optional[Dict[str, Any]] = None
    mid_timer: Optional[threading.Timer] = None
    if mid_run_fn is not None:
        at_s = (mid_run_at_s if mid_run_at_s is not None
                else duration_s / 2.0)
        mid_run = {"at_s": round(at_s, 3), "completed": False,
                   "error": None}

        def fire() -> None:
            try:
                mid_run_fn()
                mid_run["completed"] = True
            except Exception as e:  # noqa: BLE001 — recorded, not fatal
                mid_run["error"] = f"{type(e).__name__}: {e}"

        mid_timer = threading.Timer(at_s, fire)
        mid_timer.daemon = True
        mid_timer.start()
    threads = []
    for i in range(clients):
        cfg = fmts[i % len(fmts)]
        if native_exe is not None:
            t = threading.Thread(
                target=_drive_native,
                args=(host, port, cfg, corpora[cfg[0]], duration_s,
                      timeout_s, per_client[i], native_exe, workdir),
                name=f"loadgen-native-{i}", daemon=True,
            )
        else:
            t = threading.Thread(
                target=_drive,
                args=(host, port, cfg, corpora[cfg[0]], stop_at, interval_s,
                      burst, timeout_s, random.Random(seed * 1000 + i),
                      per_client[i], trace),
                name=f"loadgen-{i}", daemon=True,
            )
        t.start()
        threads.append(t)
    for t in threads:
        # Generous join slack: a client mid-request at stop_at finishes
        # that request (bounded by the socket timeout) before exiting.
        t.join(timeout=duration_s + timeout_s + 10.0)
    wall_s = time.monotonic() - t_start
    if mid_timer is not None:
        # Generous: a blocking mid-run action (a full fleet roll with
        # per-sidecar warmups) may legitimately outlive the window; the
        # join only lasts as long as the action actually takes.
        mid_timer.join(timeout=timeout_s + 600.0)
    total = _ClientStats()
    for s in per_client:
        total.merge(s)
    if workdir is not None:
        import shutil

        shutil.rmtree(workdir, ignore_errors=True)
    extra: Dict[str, Any] = {}
    if mid_run is not None:
        extra["mid_run"] = mid_run
    if tenants > 0:
        by_tenant: Dict[str, Dict[str, int]] = {}
        for s in per_client:
            t = by_tenant.setdefault(s.tenant or "default", {
                "clients": 0, "requests": 0, "ok": 0, "busy": 0,
                "tenant_quota_sheds": 0,
            })
            t["clients"] += 1
            t["requests"] += s.requests
            t["ok"] += s.ok
            t["busy"] += s.busy
            t["tenant_quota_sheds"] += s.busy_reasons.get(
                "tenant_quota", 0)
        extra["tenants"] = {k: by_tenant[k] for k in sorted(by_tenant)}
    if trace:
        # Example trace ids per outcome class (capped): the operator's
        # entry point into /tracez for exactly the requests that shed,
        # expired, or reset.
        extra["trace_ids"] = {
            k: total.trace_ids[k] for k in sorted(total.trace_ids)
        }
    if before is not None:
        extra["coalesce"] = coalesce_report(
            before, scrape_metrics(metrics_url))
    if native:
        extra["driver"] = "native" if native_exe is not None else "python"
    return {
        **extra,
        "clients": clients,
        "duration_s": round(wall_s, 3),
        "batch_lines": batch_lines,
        "burst": burst,
        "interval_s": interval_s,
        "formats": [name for name, _lf, _f in fmts],
        "requests": total.requests,
        "ok": total.ok,
        "busy": total.busy,
        "busy_unstructured": total.busy_unstructured,
        "busy_reasons": dict(sorted(total.busy_reasons.items())),
        "deadline": total.deadline,
        "errors": total.errors,
        "resets": total.resets,
        "connect_errors": total.connect_errors,
        "lines_ok": total.lines_ok,
        "goodput_lines_per_sec": round(total.lines_ok / wall_s, 1)
        if wall_s > 0 else 0.0,
        "p50_ms": _percentile_ms(total.latencies, 0.50),
        "p99_ms": _percentile_ms(total.latencies, 0.99),
    }


def main(argv: Optional[Sequence[str]] = None) -> int:
    """CLI: run one loadgen window against a live service and print the
    JSON record."""
    import argparse
    import os

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--duration", type=float, default=3.0)
    ap.add_argument("--batch-lines", type=int, default=128)
    ap.add_argument("--burst", type=int, default=4)
    ap.add_argument("--interval", type=float, default=0.05)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument(
        "--shared-format", action="store_true",
        help="many-small-clients scenario: every client on ONE format "
             "(one parser cache key), the shape continuous batching "
             "coalesces",
    )
    ap.add_argument(
        "--metrics-port", type=int, default=None,
        help="server /metrics port: adds the server-side coalesce "
             "occupancy report (batches, sessions/batch, occupancy, "
             "queue wait) to the record",
    )
    ap.add_argument(
        "--native", action="store_true",
        help="drive with the compiled C++ protocol client "
             "(native/svc_client.cc); falls back to the Python client "
             "when no toolchain is available",
    )
    ap.add_argument(
        "--tenants", type=int, default=0,
        help="assign clients skewed tenant identities (t0 = the noisy "
             "tenant); the record grows a per-tenant outcome table — "
             "the front tier's fairness-quota drill (docs/SERVICE.md "
             "\"Fleet\")",
    )
    ap.add_argument(
        "--trace", action="store_true",
        help="stamp a fresh SAMPLED traceparent on every client "
             "connection and report example trace ids per outcome "
             "class — the /tracez entry point for shed/expired/reset "
             "requests (Python driver only; docs/OBSERVABILITY.md "
             "\"Tracing\")",
    )
    ap.add_argument(
        "--roll", action="store_true",
        help="mid-run rolling-restart trigger: POST /rollz on "
             "--metrics-port (a front tier's fleet endpoint) at half "
             "the window — the zero-downtime restart-under-load drill",
    )
    args = ap.parse_args(argv)
    mid_run_fn = None
    if args.roll:
        if not args.metrics_port:
            ap.error("--roll needs --metrics-port (the front tier's "
                     "fleet endpoint serving POST /rollz)")

        def mid_run_fn() -> None:
            import urllib.request

            req = urllib.request.Request(
                f"http://{args.host}:{args.metrics_port}/rollz",
                method="POST", data=b"",
            )
            with urllib.request.urlopen(req, timeout=10) as resp:
                resp.read()

    record = run_loadgen(
        args.host, args.port, clients=args.clients,
        duration_s=args.duration, batch_lines=args.batch_lines,
        burst=args.burst, interval_s=args.interval, seed=args.seed,
        formats=DEFAULT_FORMATS[:1] if args.shared_format else None,
        metrics_url=(
            f"http://{args.host}:{args.metrics_port}/metrics"
            if args.metrics_port else None
        ),
        native=args.native,
        tenants=args.tenants,
        trace=args.trace,
        mid_run_fn=mid_run_fn,
    )
    print(json.dumps(record, indent=1, sort_keys=True))
    return 0


if __name__ == "__main__":  # pragma: no cover — CLI
    raise SystemExit(main())
