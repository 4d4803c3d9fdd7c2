"""The parse-config table the cross-config tests run over, and two line
forgers that rewrite a corpus into a given host-routing class.

Importing this module resolves no fixtures and compiles nothing:
``build_configs()`` materializes the GeoIP test databases only when it is
called.  Each entry's corpus is seeded, so every test that draws from the
table parses the same bytes on every run.
"""
import os
import re

from logparser_tpu.tools.demolog import (
    HEADLINE_FIELDS,
    generate_combined_lines,
    truncate_to_common,
)


def build_configs():
    """The config table: ``(name, log_format, fields, lines_fn,
    extra_dissectors)`` per entry, where ``lines_fn(n)`` returns ``n``
    seeded lines.  ``geoip_chain`` is present only when the GeoIP test
    databases resolve."""
    from logparser_tpu.tools.geoip_testdata import ensure_test_databases

    def combined_lines(n, seed):
        return generate_combined_lines(n, seed=seed, garbage_fraction=0.01)

    configs = [
        ("combined", "combined", HEADLINE_FIELDS,
         lambda n: combined_lines(n, 42), None),
        ("combinedio_strftime",
         '%h %l %u [%{%d/%b/%Y:%H:%M:%S %z}t] "%r" %>s %b '
         '"%{Referer}i" "%{User-Agent}i" %I %O',
         ["IP:connection.client.host",
          "TIME.EPOCH:request.receive.time.epoch",
          "TIME.YEAR:request.receive.time.year",
          "STRING:request.status.last",
          "BYTES:request.bytes", "BYTES:response.bytes"],
         lambda n: [f"{ln} {100 + i} {5000 + i}" for i, ln in
                    enumerate(combined_lines(n, 43))],
         None),
        ("nginx_uri",
         '$remote_addr - $remote_user [$time_local] "$request" $status '
         '$body_bytes_sent "$http_referer" "$http_user_agent"',
         ["IP:connection.client.host", "TIME.STAMP:request.receive.time",
          "HTTP.METHOD:request.firstline.method",
          "HTTP.PATH:request.firstline.uri.path",
          "HTTP.QUERYSTRING:request.firstline.uri.query",
          "STRING:request.status.last", "BYTES:response.body.bytes"],
         # nginx $body_bytes_sent is strictly numeric ([0-9]+,
         # CoreLogModule.java:137) — rewrite the Apache-style CLF '-' byte
         # counts the generator emits, or 10% of the corpus exercises the
         # reject path instead of the parser.
         lambda n: [
             re.sub(r'" (\d{3}) - ', r'" \1 0 ', ln)
             for ln in combined_lines(n, 44)
         ],
         None),
    ]

    geo_data = ensure_test_databases()
    city = os.path.join(geo_data, "GeoIP2-City-Test.mmdb")
    asn = os.path.join(geo_data, "GeoLite2-ASN-Test.mmdb")
    if os.path.exists(city) and os.path.exists(asn):
        from logparser_tpu.geoip import GeoIPASNDissector, GeoIPCityDissector

        # IPs present in the generated GeoIP2 test databases (the
        # 80.100.47.0/24 Basjes test range hits both the City and the ASN
        # db) — the MaxMind official test IPs (81.2.69.142 etc.) are NOT
        # in these files, and a corpus of misses would exercise the join
        # machinery while delivering only nulls.
        known = ["80.100.47.45", "80.100.47.1", "80.100.47.254",
                 "80.100.47.13"]

        def geo_lines(n):
            base = combined_lines(n, 45)
            return [
                known[i % len(known)] + ln[ln.index(" "):]
                if (i % 3 == 0 and " " in ln) else ln
                for i, ln in enumerate(base)
            ]

        configs.append((
            "geoip_chain", "combined",
            ["IP:connection.client.host",
             "STRING:connection.client.host.country.name",
             "STRING:connection.client.host.city.name",
             "ASN:connection.client.host.asn.number",
             "STRING:request.status.last"],
            geo_lines,
            [GeoIPCityDissector(city), GeoIPASNDissector(asn)],
        ))

    def zonetext_lines(n):
        # %Z-bearing corpus over the DEVICE zone vocabulary (the device
        # claims every line: no oracle routing) — DST abbreviations,
        # fixed zones and region ids, resolved through the tzdata
        # transition tables on device.
        zones = ["CET", "EST", "UTC", "Europe/Paris", "America/New_York",
                 "Asia/Tokyo", "PST", "GMT", "Australia/Sydney", "CEST"]
        out = []
        for i, ln in enumerate(combined_lines(n, 48)):
            try:
                cut = ln.rindex(' "', 0, ln.rindex(' "'))
                ln = ln[:cut]
            except ValueError:
                pass
            out.append(re.sub(
                r"([+-]\d{4})\]", zones[i % len(zones)] + "]", ln, count=1
            ))
        return out

    configs.append((
        "strftime_zonetext",
        '%h %l %u [%{%d/%b/%Y:%H:%M:%S %Z}t] "%r" %>s %b',
        ["IP:connection.client.host",
         "TIME.EPOCH:request.receive.time.epoch",
         "TIME.HOUR:request.receive.time.hour_utc",
         "STRING:request.status.last"],
        zonetext_lines, None,
    ))

    def mixed_lines(n):
        combined = combined_lines(n // 2, 46)
        common = [truncate_to_common(ln) for ln in combined_lines(n // 2, 47)]
        return [v for pair in zip(combined, common) for v in pair]

    configs.append((
        "multiformat_mixed", 'combined\n%h %l %u %t "%r" %>s %b',
        ["IP:connection.client.host", "STRING:request.status.last",
         "BYTES:response.body.bytes", "HTTP.METHOD:request.firstline.method"],
        mixed_lines, None,
    ))
    return configs


def force_escaped_quote_lines(base, pct):
    """Copy of ``base`` with ``pct``% of lines rewritten to carry a
    backslash-escaped quote inside the user-agent — the one rescue class
    routinely present in real corpora.  The escape-parity mask in
    ``pipeline.compute_split`` decodes these ON DEVICE (final quoted
    field, exact vs the host's lazy regex).  Rewritten lines grow by only
    a few bytes (no >8k truncation, no line-length bucket change)."""
    step = max(1, round(100 / pct))
    out = list(base)
    for i in range(0, len(out), step):
        out[i] = re.sub(
            r'"([^"]*)"$', r'"esc \\" quote \1"', out[i], count=1
        )
    return out


def force_rescued_lines(base, pct):
    """Copy of ``base`` with ``pct``% of lines rewritten into a class
    that STAYS host-rescued: a referer value ending in a backslash (raw
    bytes ``\\" "`` — the escaped quote forms a separator occurrence of
    the NON-final referer field, which is ambiguous against the host
    regex's backtracking, so the device un-claims the line BY DESIGN and
    the oracle applies the reference semantics).  Keeps the batched
    rescue machinery under test now that the realistic escaped-quote
    class stays on device."""
    step = max(1, round(100 / pct))
    out = list(base)
    for i in range(0, len(out), step):
        out[i] = re.sub(
            r'"([^"]*)" "([^"]*)"$', r'"\1\\" "\2"', out[i], count=1
        )
    return out
