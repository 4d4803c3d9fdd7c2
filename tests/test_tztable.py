"""%Z zone text on device: tzdata transition tables vs the oracle.

Round-4 verdict item 4: DST abbreviations and region ids resolve on
device through host-compiled tzdata transition tables
(dissectors/tztable.py).  These tests pin (a) the fold=0 wall-clock
boundary rule against zoneinfo, (b) the device lookup against zoneinfo
around every transition of every vocabulary zone, and (c) end-to-end
device-vs-oracle parity over a zone-heavy corpus including DST gap and
ambiguous times.
"""
import datetime as dt
import random

import numpy as np
import pytest

import jax.numpy as jnp

from logparser_tpu.dissectors.tztable import (
    DEFAULT_DEVICE_ZONES,
    SPAN_MINUTES,
    default_zone_table,
    wall_table,
)

from _shared_parsers import shared_parser


def _probe(zobj, minute):
    local = dt.datetime(1970, 1, 1) + dt.timedelta(minutes=minute)
    return int(local.replace(tzinfo=zobj, fold=0).utcoffset().total_seconds())


def test_every_default_zone_compiles():
    table = default_zone_table()
    assert set(table.zones) == set(DEFAULT_DEVICE_ZONES)
    assert np.all(np.diff(table.keys.astype(np.int64)) > 0)


def test_fold0_boundaries_match_zoneinfo():
    """The max(o_prev, o_new) wall-boundary rule, probed +-1 minute
    around real transitions of DST-observing zones."""
    from zoneinfo import ZoneInfo

    for zone in ("CET", "EST5EDT", "Europe/London", "Australia/Sydney",
                 "Pacific/Auckland"):
        bounds, segs, valid_until = wall_table(zone)
        zobj = ZoneInfo(zone)
        rng = random.Random(1)
        idxs = list(range(1, len(bounds)))
        for i in rng.sample(idxs, min(40, len(idxs))):
            b = int(bounds[i])
            if b + 1 >= valid_until:
                continue
            assert _probe(zobj, b - 1) == int(segs[i - 1]), (zone, b)
            assert _probe(zobj, b) == int(segs[i]), (zone, b)


def test_device_lookup_matches_zoneinfo_random():
    from zoneinfo import ZoneInfo

    table = default_zone_table()
    rng = random.Random(7)
    zidx, minutes, want = [], [], []
    for z, zone in enumerate(table.zones):
        zobj = ZoneInfo(zone)
        vu = int(table.valid_until[z])
        for _ in range(20):
            m = rng.randrange(0, min(vu, SPAN_MINUTES - 1))
            zidx.append(z)
            minutes.append(m)
            want.append(_probe(zobj, m))
    off, ok = table.lookup(
        jnp.asarray(zidx, dtype=jnp.int32),
        jnp.asarray(minutes, dtype=jnp.int32),
    )
    off = np.asarray(off)
    ok = np.asarray(ok)
    assert ok.all()
    mismatch = np.nonzero(off != np.asarray(want))[0]
    assert mismatch.size == 0, [
        (table.zones[zidx[i]], minutes[i], int(off[i]), want[i])
        for i in mismatch[:5]
    ]


ZONE_FMT = '%h %l %u [%{%d/%b/%Y:%H:%M:%S %Z}t] "%r" %>s %b'
ZONE_FIELDS = [
    "TIME.EPOCH:request.receive.time.epoch",
    "TIME.HOUR:request.receive.time.hour",
    "TIME.HOUR:request.receive.time.hour_utc",
    "TIME.DATE:request.receive.time.date_utc",
]


def test_zone_format_compiles_fully_on_device():
    parser = shared_parser(ZONE_FMT, ZONE_FIELDS)
    assert parser._unit_oracle_fields == [[]]


@pytest.mark.slow  # Differential sweep over the full zone vocabulary: slow tier (re-tier r06).
def test_device_vs_oracle_zone_corpus():
    from logparser_tpu.tpu.batch import _CollectingRecord

    parser = shared_parser(ZONE_FMT, ZONE_FIELDS)
    rng = random.Random(3)
    zones = list(DEFAULT_DEVICE_ZONES) + [
        "EST", "CST", "PDT", "cet", "gmt", "Z", "UT",     # abbreviations
        "Unknown/Zone", "XYZ", "europe/paris",            # host-rejects
        "Etc/UTC",
    ]
    lines = []
    for i in range(160):
        zone = rng.choice(zones)
        y = rng.choice([1968, 1975, 1999, 2016, 2023, 2026, 2037, 2095])
        mo, d = rng.randrange(1, 13), rng.randrange(1, 29)
        h, mi, s = rng.randrange(24), rng.randrange(60), rng.randrange(60)
        lines.append(
            f'10.0.0.{i % 255} - - '
            f'[{d:02d}/{dt.date(2000, mo, 1):%b}/{y}:{h:02d}:{mi:02d}:{s:02d} '
            f'{zone}] "GET /{i} HTTP/1.0" 200 5'
        )
    # DST boundary adversaries (CET spring gap / autumn ambiguity).
    lines += [
        '1.1.1.1 - - [26/Mar/2023:02:30:00 CET] "GET /gap HTTP/1.0" 200 1',
        '1.1.1.2 - - [29/Oct/2023:02:30:00 CET] "GET /amb HTTP/1.0" 200 1',
        '1.1.1.3 - - [29/Oct/2023:02:30:00 CEST] "GET /amb2 HTTP/1.0" 200 1',
        '1.1.1.4 - - [31/Dec/2037:23:59:59 America/New_York] "GET /cap HTTP/1.0" 200 1',
    ]
    res = parser.parse_batch(lines)
    for fid in ZONE_FIELDS:
        got = res.to_pylist(fid)
        for i, line in enumerate(lines):
            try:
                want = parser.oracle.parse(
                    line, _CollectingRecord()).values.get(fid)
            except Exception:
                want = None
            assert str(got[i]) == str(want) or (got[i] is None
                                                and want is None), (
                fid, line, got[i], want)


def test_zone_vocabulary_corpus_stays_on_device():
    """A corpus using only device-vocabulary zones must not touch the
    oracle at all (the oracle_fraction 0.0 contract)."""
    parser = shared_parser(ZONE_FMT, ZONE_FIELDS)
    zones = ["CET", "EST", "UTC", "Europe/Paris", "America/New_York",
             "Asia/Tokyo", "Australia/Sydney", "PST", "GMT"]
    lines = [
        f'10.0.0.{i % 9} - - [15/Jun/202{i % 4}:10:3{i % 6}:00 '
        f'{zones[i % len(zones)]}] "GET /{i} HTTP/1.0" 200 5'
        for i in range(256)
    ]
    res = parser.parse_batch(lines)
    assert res.oracle_rows == 0
    assert res.bad_lines == 0


def test_bucketed_lookup_matches_searchsorted():
    """The device lookup resolves via the bucket table + chain steps; it
    must agree with the plain last-key<=query searchsorted semantics for
    every (zone, minute) — including bucket boundaries, exact transition
    minutes and the minute just before/after each transition."""
    import numpy as np

    from logparser_tpu.dissectors.tztable import (
        SPAN_MINUTES, default_zone_table,
    )

    tab = default_zone_table()
    assert len(tab.zones) > 10
    assert tab.chain >= 1
    rng = np.random.default_rng(7)
    Z = len(tab.zones)
    zi = rng.integers(0, Z, size=4096).astype(np.int32)
    mins = rng.integers(0, SPAN_MINUTES, size=4096).astype(np.int64)
    # Adversarial rows: transition boundaries +-1 and bucket edges.
    edge_keys = tab.keys[rng.integers(0, len(tab.keys), size=512)]
    edge_z = (edge_keys // SPAN_MINUTES).astype(np.int32)
    edge_m = (edge_keys % SPAN_MINUTES).astype(np.int64)
    for dm in (-1, 0, 1):
        zi = np.concatenate([zi, edge_z])
        mins = np.concatenate([mins, np.clip(edge_m + dm, 0,
                                             SPAN_MINUTES - 1)])
    bucket = 1 << tab.BUCKET_BITS
    zi = np.concatenate([zi, edge_z])
    mins = np.concatenate(
        [mins, np.clip((edge_m // bucket) * bucket, 0, SPAN_MINUTES - 1)]
    )

    import jax.numpy as jnp

    off, ok = tab.lookup(jnp.asarray(zi), jnp.asarray(mins))
    off = np.asarray(off)

    key = zi.astype(np.uint64) * np.uint64(SPAN_MINUTES) + mins.astype(
        np.uint64
    )
    pos = np.searchsorted(tab.keys.astype(np.uint64), key, side="right")
    want = tab.offsets_s[np.clip(pos - 1, 0, len(tab.keys) - 1)]
    assert np.array_equal(off, want)
