"""CI rescue smoke: the batched host-rescue pipeline on a dirty corpus.

Round 18 moved the escaped-quote class ON DEVICE (escape-parity mask in
``pipeline.compute_split``), so the smoke now drills BOTH sides of the
new boundary:

- leg 1 (the rescue machinery, still-host-rescued class): a small mixed
  stream with FORCED ~5% truncated >8k lines (the device judges only a
  prefix and always defers; the host parses the full line) plus the
  former overflow class (20-digit ``%b`` counters, on-device since
  round 9).  Asserts: truncated lines rescued byte-identically through
  the BATCHED rescue path, 20-digit values exact on device, rescue
  throughput/effective floors, ``oracle_routed_lines_total`` reasons on
  a live ``/metrics``.
- leg 2 (the escaped-quote class, device-decoded): a 5% forced
  escaped-quote corpus must route ZERO lines to the oracle
  (``oracle_routed_lines_total`` unchanged across the parse), deliver
  byte parity vs the per-line oracle, count every forced line in
  ``device_escaped_quote_lines_total``, and expose that counter on
  ``/metrics``.
- leg 3 (round 20, URI fields on device): with ``HTTP.PATH`` + a query
  key requested, a 5% forced repair-needing-URI corpus (fragment +
  ``;`` — repair stages the device cannot reproduce) must route EXACTLY
  those rows (reason ``device_reject``, zero ``host_fields`` — the
  covered URI set no longer forces whole-line oracle routing), move
  ``oracle_routed_lines_total`` by exactly that count, and deliver both
  URI fields byte-identically on the rescued AND the device-parsed rows.

Usage::

    make rescue-smoke
    python -m logparser_tpu.tools.rescue_smoke
"""
from __future__ import annotations

import os
import re
import sys
import time

# Rescue-pipeline throughput floor (rescued lines per rescue-wall
# second).  The truncated class carries ~8KB lines, so the floor is set
# below the escaped-quote era's 15k: the compiled+codegen oracle still
# clears ~8k of these on a weak CI core; a rescue path that
# re-serializes per line would trip it.
RESCUE_RATE_FLOOR = float(os.environ.get(
    "LOGPARSER_TPU_RESCUE_SMOKE_RATE_FLOOR", "5000"))
# Whole-batch effective floor — deliberately conservative: the smoke
# runs on CI CPUs.
EFFECTIVE_FLOOR = float(os.environ.get(
    "LOGPARSER_TPU_RESCUE_SMOKE_EFFECTIVE_FLOOR", "2000"))

N_LINES = 2048
TRUNC_LEN = 8300          # > runtime.DEFAULT_MAX_LINE_LEN (8191)
FIELDS = ["IP:connection.client.host", "BYTES:response.body.bytes",
          "HTTP.USERAGENT:request.user-agent"]


def build_corpus():
    """Leg-1 corpus: 5% truncated >8k (host-rescued), 5% 20-digit %b
    (on-device), 90% clean."""
    from logparser_tpu.tools.demolog import generate_combined_lines

    base = generate_combined_lines(N_LINES, seed=90)
    truncated, overflow = [], []
    for i, ln in enumerate(base):
        if i % 20 == 0:  # 5%: truncated >8k, device defers, host rescues
            pad = "x" * max(1, TRUNC_LEN - len(ln))
            base[i] = re.sub(r'"([^"]*)"$', f'"trunc {pad} \\1"', ln,
                             count=1)
            truncated.append(i)
        elif i % 20 == 10:  # 5%: the FORMER overflow reject class
            base[i] = re.sub(r'" (\d{3}) (\d+|-) ',
                             f'" \\1 {10**19 + i} ', ln, count=1)
            overflow.append(i)
    return base, truncated, overflow


def build_escaped_corpus():
    """Leg-2 corpus: 5% forced escaped-quote user-agents — the class
    that must now route ZERO lines (device escape-parity decode)."""
    from logparser_tpu.tools.demolog import generate_combined_lines

    base = generate_combined_lines(N_LINES, seed=91)
    forced = []
    for i in range(0, len(base), 20):
        base[i] = re.sub(r'"([^"]*)"$', r'"esc \\" quote \1"', base[i],
                         count=1)
        forced.append(i)
    return base, forced


URI_FIELDS = FIELDS + ["HTTP.PATH:request.firstline.uri.path",
                       "STRING:request.firstline.uri.query.q"]


def build_uri_corpus():
    """Leg-3 corpus: 5% repair-needing URIs — a fragment plus a ``;``
    (HTML-entity unescape + fragment-artifact rewrites the device cannot
    reproduce) — the rest clean demolog traffic whose path + query keys
    dissect fully on device."""
    from logparser_tpu.tools.demolog import generate_combined_lines

    base = generate_combined_lines(N_LINES, seed=92)
    forced = []
    for i in range(0, len(base), 20):
        base[i] = re.sub(
            r'"(\S+) \S+ HTTP',
            r'"\1 /account;v=2/search?q=caf%C3%A9+x#top HTTP',
            base[i], count=1,
        )
        forced.append(i)
    return base, forced


def _routed_total() -> float:
    """Sum of oracle_routed_lines_total across reason labels."""
    from logparser_tpu.observability import counter_sum

    return counter_sum("oracle_routed_lines_total")


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import urllib.request

    from logparser_tpu.core.exceptions import DissectionFailure
    from logparser_tpu.tpu.batch import TpuBatchParser, _CollectingRecord

    errors = []

    # ---- leg 1: the rescue machinery on the truncated class ----------
    lines, truncated, overflow = build_corpus()
    parser = TpuBatchParser("combined", FIELDS)
    parser.parse_batch(lines)  # warm: compile + caches

    t0 = time.perf_counter()
    result = parser.parse_batch(lines)
    wall = time.perf_counter() - t0

    reasons = result.rescue_reasons
    routed = result.oracle_rows
    # (a) widening guard: the 20-digit class must NOT route; the ONLY
    # routed lines are the truncated ones (reason "overflow").
    if reasons.get("device_reject", 0) or routed > len(truncated):
        errors.append(
            f"unexpected oracle routing: rows={routed} reasons={reasons} "
            f"(expected only the {len(truncated)} truncated lines)"
        )
    if reasons.get("overflow", 0) < len(truncated):
        errors.append(
            f"truncated lines not routed: {reasons} (expected >= "
            f"{len(truncated)} overflow)"
        )
    vals = result.to_pylist("BYTES:response.body.bytes")
    for i in overflow:
        if vals[i] != 10 ** 19 + i:
            errors.append(f"overflow row {i}: device value {vals[i]!r} != "
                          f"{10**19 + i}")
            break
    # (b) truncated lines rescued, bit-identical to the per-line oracle.
    ua = result.to_pylist("HTTP.USERAGENT:request.user-agent")
    for i in truncated[: 4]:
        try:
            rec = parser.oracle.parse(lines[i], _CollectingRecord())
            want = rec.values.get("HTTP.USERAGENT:request.user-agent")
        except DissectionFailure:
            errors.append(f"truncated line {i} not host-parseable")
            break
        if not result.valid[i] or ua[i] != want:
            errors.append(
                f"truncated row {i} not rescued bit-identically: "
                f"{(ua[i] or '')[:40]!r}... != {(want or '')[:40]!r}..."
            )
            break
    # (c) throughput floors.
    rescue_rate = (routed / result.rescue_wall_s
                   if result.rescue_wall_s else float("inf"))
    if rescue_rate < RESCUE_RATE_FLOOR:
        errors.append(
            f"rescue pipeline {rescue_rate:.0f} rescued-lines/s below "
            f"the {RESCUE_RATE_FLOOR:.0f} floor"
        )
    effective = len(lines) / wall if wall else float("inf")
    if effective < EFFECTIVE_FLOOR:
        errors.append(
            f"effective rate {effective:.0f} lines/s below the "
            f"{EFFECTIVE_FLOOR:.0f} smoke floor"
        )

    # ---- leg 2: the escaped-quote class must stay on device ----------
    esc_lines, forced = build_escaped_corpus()
    esc_parser = TpuBatchParser("combined", FIELDS)
    esc_parser.parse_batch(esc_lines)  # warm
    routed_before = _routed_total()
    esc_result = esc_parser.parse_batch(esc_lines)
    routed_after = _routed_total()
    if esc_result.oracle_rows or routed_after != routed_before:
        errors.append(
            "escaped-quote corpus routed lines to the oracle: "
            f"oracle_rows={esc_result.oracle_rows}, "
            f"oracle_routed_lines_total {routed_before} -> {routed_after} "
            "(must be unchanged — the class lives on device)"
        )
    if esc_result.escaped_quote_rows < len(forced):
        errors.append(
            f"device decoded {esc_result.escaped_quote_rows} < "
            f"{len(forced)} forced escaped-quote lines "
            "(device_escaped_quote_lines_total undercounts)"
        )
    esc_ua = esc_result.to_pylist("HTTP.USERAGENT:request.user-agent")
    for i in forced[: 8]:
        try:
            rec = esc_parser.oracle.parse(esc_lines[i], _CollectingRecord())
            want = rec.values.get("HTTP.USERAGENT:request.user-agent")
        except DissectionFailure:
            errors.append(f"escaped line {i} not host-parseable")
            break
        if not esc_result.valid[i] or esc_ua[i] != want:
            errors.append(
                f"escaped row {i} device decode not bit-identical to the "
                f"oracle: {esc_ua[i]!r} != {want!r}"
            )
            break

    # ---- leg 3: URI fields on device, repair-needing tail rescued ----
    uri_lines, uri_forced = build_uri_corpus()
    uri_parser = TpuBatchParser("combined", URI_FIELDS)
    uri_parser.parse_batch(uri_lines)  # warm
    uri_before = _routed_total()
    uri_result = uri_parser.parse_batch(uri_lines)
    uri_after = _routed_total()
    uri_reasons = uri_result.rescue_reasons
    if (uri_result.oracle_rows != len(uri_forced)
            or uri_reasons.get("host_fields", 0)
            or uri_reasons.get("device_reject", 0) != len(uri_forced)):
        errors.append(
            "URI leg routing off: "
            f"rows={uri_result.oracle_rows} reasons={uri_reasons} "
            f"(expected exactly the {len(uri_forced)} repair-needing "
            "URIs as device_reject, zero host_fields)"
        )
    if uri_after - uri_before != len(uri_forced):
        errors.append(
            f"oracle_routed_lines_total moved {uri_before} -> {uri_after} "
            f"(expected +{len(uri_forced)} for the forced URI rows)"
        )
    uri_cols = {f: uri_result.to_pylist(f) for f in URI_FIELDS[-2:]}
    # Byte parity on both sides of the boundary: rescued rows AND the
    # device-dissected neighbours.
    for i in uri_forced[: 6] + [j + 1 for j in uri_forced[: 6]]:
        try:
            rec = uri_parser.oracle.parse(uri_lines[i], _CollectingRecord())
        except DissectionFailure:
            errors.append(f"URI line {i} not host-parseable")
            break
        for fid, col in uri_cols.items():
            want = rec.values.get(fid)
            if not uri_result.valid[i] or col[i] != want:
                errors.append(
                    f"URI row {i} field {fid} not byte-identical: "
                    f"{col[i]!r} != {want!r}"
                )
                break
        else:
            continue
        break

    # (d) /metrics exposes the per-reason rescue counters AND the new
    # escaped-quote counter (live scrape, strict exposition grammar).
    from logparser_tpu.service import ParseService, ParseServiceClient
    from logparser_tpu.tools.metrics_smoke import validate_exposition

    with ParseService(metrics_port=0) as svc:
        with ParseServiceClient(svc.host, svc.port, "combined",
                                FIELDS) as client:
            client.parse(lines[: 256])
            client.parse(esc_lines[: 256])
        url = f"http://{svc.host}:{svc.metrics_port}/metrics"
        with urllib.request.urlopen(url, timeout=10) as resp:
            text = resp.read().decode("utf-8")
    errors += validate_exposition(text)
    if ('logparser_tpu_oracle_routed_lines_total{reason="overflow"}'
            not in text):
        errors.append(
            "/metrics missing per-reason rescue counter "
            "oracle_routed_lines_total{reason=\"overflow\"}"
        )
    if "logparser_tpu_device_escaped_quote_lines_total" not in text:
        errors.append(
            "/metrics missing device_escaped_quote_lines_total "
            "(the escaped-quote decode counter)"
        )

    if errors:
        print(f"rescue smoke FAILED ({len(errors)} problems):")
        for e in errors:
            print(f"  - {e}")
        return 1
    print(
        "rescue smoke OK: "
        f"{routed}/{len(lines)} routed ({reasons}), "
        f"rescue {rescue_rate:.0f} lines/s, "
        f"effective {effective:.0f} lines/s; "
        f"escaped-quote leg: 0 routed, "
        f"{esc_result.escaped_quote_rows} device-decoded; "
        f"URI leg: {uri_result.oracle_rows}/{len(uri_forced)} "
        "repair-needing rescued, 0 host_fields; "
        "/metrics well-formed"
    )
    return 0


if __name__ == "__main__":  # pragma: no cover — CLI
    sys.exit(main())
