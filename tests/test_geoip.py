"""GeoIP subsystem tests.

Mirrors the reference's TestGeoIPDissectors.java (expected values come from
the checked-in MaxMind test databases generated by
GeoIP2-TestData/source-data/*.json) plus differential tests of the own mmdb
reader against those JSON sources, plus the device-side join path.
"""
import ipaddress
import json
import os

import numpy as np
import pytest

from logparser_tpu.core.exceptions import InvalidDissectorException
from logparser_tpu.geoip import (
    GeoDeviceTable,
    GeoIPASNDissector,
    GeoIPCityDissector,
    GeoIPCountryDissector,
    GeoIPISPDissector,
    MMDBReader,
    ipv4_to_u32,
)
from logparser_tpu.testing import DissectorTester

pytestmark = pytest.mark.slow

TEST_DATA = "/root/reference/GeoIP2-TestData/test-data"
SOURCE_DATA = "/root/reference/GeoIP2-TestData/source-data"
_HAVE_REFERENCE_DATA = os.path.isdir(TEST_DATA)
if not _HAVE_REFERENCE_DATA:
    # Self-contained fixtures: generated by our own MaxMind-DB writer
    # (tools/geoip_testdata.py) with the same Basjes test records the
    # suite asserts — the reference checkout is no longer a dependency.
    from logparser_tpu.tools.geoip_testdata import ensure_test_databases

    TEST_DATA = ensure_test_databases()
ASN_MMDB = os.path.join(TEST_DATA, "GeoLite2-ASN-Test.mmdb")
ISP_MMDB = os.path.join(TEST_DATA, "GeoIP2-ISP-Test.mmdb")
CITY_MMDB = os.path.join(TEST_DATA, "GeoIP2-City-Test.mmdb")
COUNTRY_MMDB = os.path.join(TEST_DATA, "GeoIP2-Country-Test.mmdb")

# Every .mmdb-backed test runs either way (generated fixtures when the
# reference checkout is absent); only the differential tests against the
# reference's JSON *source* files need its checkout.
needs_source_json = pytest.mark.skipif(
    not (_HAVE_REFERENCE_DATA and os.path.isdir(SOURCE_DATA)),
    reason="reference GeoIP source JSON not present",
)


def _tester(dissector):
    return (
        DissectorTester.create()
        .with_path_prefix("ip")
        .with_dissector(dissector)
    )


# -- mmdb reader differential tests vs the JSON sources ----------------------

@needs_source_json
@pytest.mark.parametrize(
    "name",
    ["GeoLite2-ASN-Test", "GeoIP2-City-Test", "GeoIP2-Country-Test", "GeoIP2-ISP-Test"],
)
def test_mmdb_reader_matches_source_json(name):
    reader = MMDBReader(os.path.join(TEST_DATA, f"{name}.mmdb"))
    with open(os.path.join(SOURCE_DATA, f"{name}.json")) as f:
        source = json.load(f)
    checked = 0
    for record in source:
        for network, expected in record.items():
            net = ipaddress.ip_network(network, strict=False)
            inside = net.network_address
            got = reader.lookup(str(inside))
            assert got is not None, f"{name}: no data for {inside} ({network})"
            # Spot-check a couple of scalar leaves rather than deep-equal:
            # the perl writer may coerce some types (e.g. geoname_id).
            for key in ("autonomous_system_number", "isp"):
                if key in expected:
                    assert got.get(key) == expected[key]
            if "country" in expected:
                assert (
                    got["country"]["iso_code"] == expected["country"]["iso_code"]
                )
            checked += 1
    assert checked >= 2


def test_mmdb_reader_miss_and_garbage():
    reader = MMDBReader(ASN_MMDB)
    assert reader.lookup("1.2.3.4") is None
    assert reader.lookup("not-an-ip") is None
    assert reader.lookup("") is None


# -- dissector tests (TestGeoIPDissectors.java expectations) ------------------

def test_geoip_asn():
    (
        _tester(GeoIPASNDissector(ASN_MMDB))
        .with_input("80.100.47.45")
        .expect("ASN:ip.asn.number", "4444")
        .expect("ASN:ip.asn.number", 4444)
        .expect("STRING:ip.asn.organization", "Basjes Global Network")
        .check_expectations()
    )


def test_geoip_asn_ipv6():
    (
        _tester(GeoIPASNDissector(ASN_MMDB))
        .with_input("2001:980:91c0:1:21c:c0ff:fe06:e580")
        .expect("ASN:ip.asn.number", "6666")
        .expect("ASN:ip.asn.number", 6666)
        .expect("STRING:ip.asn.organization", "Basjes Global Network IPv6")
        .check_expectations()
    )


def test_geoip_isp():
    (
        _tester(GeoIPISPDissector(ISP_MMDB))
        .with_input("80.100.47.45")
        .expect("ASN:ip.asn.number", "4444")
        .expect("STRING:ip.asn.organization", "Basjes Global Network")
        .expect("STRING:ip.isp.name", "Basjes ISP")
        .expect("STRING:ip.isp.organization", "Niels Basjes")
        .check_expectations()
    )


def test_geoip_country():
    (
        _tester(GeoIPCountryDissector(COUNTRY_MMDB))
        .with_input("80.100.47.45")
        .expect("STRING:ip.continent.name", "Europe")
        .expect("STRING:ip.continent.code", "EU")
        .expect("STRING:ip.country.name", "Netherlands")
        .expect("STRING:ip.country.iso", "NL")
        .expect("NUMBER:ip.country.getconfidence", "42")
        .expect("NUMBER:ip.country.getconfidence", 42)
        .expect("BOOLEAN:ip.country.isineuropeanunion", "1")
        .expect("BOOLEAN:ip.country.isineuropeanunion", 1)
        .check_expectations()
    )


def test_geoip_city():
    (
        _tester(GeoIPCityDissector(CITY_MMDB))
        .with_input("80.100.47.45")
        .expect("STRING:ip.continent.name", "Europe")
        .expect("STRING:ip.country.iso", "NL")
        .expect("STRING:ip.subdivision.name", "Noord Holland")
        .expect("STRING:ip.subdivision.iso", "NH")
        .expect("STRING:ip.city.name", "Amstelveen")
        .expect("NUMBER:ip.city.confidence", 1)
        .expect("NUMBER:ip.city.geonameid", 1234)
        .expect("STRING:ip.postal.code", "1187")
        .expect("NUMBER:ip.postal.confidence", 2)
        .expect("STRING:ip.location.latitude", "52.5")
        .expect("STRING:ip.location.latitude", 52.5)
        .expect("STRING:ip.location.longitude", "5.75")
        .expect("STRING:ip.location.longitude", 5.75)
        .expect("NUMBER:ip.location.accuracyradius", 4)
        .expect("NUMBER:ip.location.metrocode", 5)
        .expect("NUMBER:ip.location.averageincome", 6)
        .expect("NUMBER:ip.location.populationdensity", 7)
        .check_expectations()
    )


def test_geoip_miss_produces_nothing():
    (
        _tester(GeoIPCityDissector(CITY_MMDB))
        .with_input("1.2.3.4")
        .expect_absent_string("STRING:ip.continent.name")
        .expect_absent_string("STRING:ip.city.name")
        .check_expectations()
    )


def test_geoip_missing_database_raises():
    t = (
        _tester(GeoIPASNDissector("Does not exist"))
        .with_input("80.100.47.45")
        .expect("ASN:ip.asn.number", "4444")
    )
    with pytest.raises((InvalidDissectorException, AssertionError)) as e:
        t.check_expectations()
    assert "Does not exist" in str(e.value)


# -- device-side join ---------------------------------------------------------

def test_geo_device_table_matches_host_lookup():
    reader = MMDBReader(ASN_MMDB)
    table = GeoDeviceTable(reader, ["asn.number", "asn.organization"])

    ips = ["80.100.47.45", "80.100.47.0", "1.2.3.4", "255.255.255.255", "junk"]
    u32, ok = ipv4_to_u32(ips)
    rows = np.asarray(table.lookup_rows(u32))
    rows = np.where(ok, rows, 0)

    numbers = np.asarray(table.gather("asn.number", rows))
    orgs = table.decode_strings("asn.organization", rows)

    for i, ip in enumerate(ips):
        host = reader.lookup(ip) if ok[i] else None
        if host is None:
            assert rows[i] == 0
            assert numbers[i] == -1
            assert orgs[i] is None
        else:
            assert numbers[i] == host["autonomous_system_number"]
            assert orgs[i] == host["autonomous_system_organization"]


def test_geo_device_table_jit():
    import jax

    reader = MMDBReader(ASN_MMDB)
    table = GeoDeviceTable(reader, ["asn.number"])
    u32, _ = ipv4_to_u32(["80.100.47.45", "1.2.3.4"])

    @jax.jit
    def join(ips):
        rows = table.lookup_rows(ips)
        return table.gather("asn.number", rows)

    out = np.asarray(join(u32))
    assert out[0] == 4444
    assert out[1] == -1


# -- chained through the httpd parser -----------------------------------------

GEO_LINE = (
    '80.100.47.45 - - [01/Jan/2026:00:00:30 +0100] '
    '"GET /x HTTP/1.1" 200 5 "-" "ua"'
)
MISS_LINE = (
    '1.2.3.4 - - [01/Jan/2026:00:00:31 +0100] '
    '"GET /y HTTP/1.1" 200 5 "-" "ua"'
)
CHAIN_FIELDS = [
    "IP:connection.client.host",
    "STRING:connection.client.host.country.name",
    "STRING:connection.client.host.city.name",
    "ASN:connection.client.host.asn.number",
]


def test_geo_chained_through_httpd_parser():
    """The product shape: geo dissectors chained onto
    connection.client.host of a real Apache line must DELIVER values
    (a corpus of lookup misses once hid a silent all-null regression)."""
    from logparser_tpu.httpd import HttpdLoglineParser

    class Rec:
        def __init__(self):
            self.v = {}

        def set_value(self, n, val):
            self.v[n] = val

    p = HttpdLoglineParser(Rec, "combined")
    p.add_dissector(GeoIPCityDissector(CITY_MMDB))
    p.add_dissector(GeoIPASNDissector(ASN_MMDB))
    p.add_parse_target(Rec.set_value, CHAIN_FIELDS)
    r = Rec()
    p.parse(GEO_LINE, r)
    assert r.v["STRING:connection.client.host.country.name"] == "Netherlands"
    assert r.v["STRING:connection.client.host.city.name"] == "Amstelveen"
    assert r.v["ASN:connection.client.host.asn.number"] in (4444, "4444")


def test_geo_chained_through_batch_parser():
    from logparser_tpu.tpu.batch import TpuBatchParser

    p = TpuBatchParser(
        "combined", CHAIN_FIELDS,
        extra_dissectors=[GeoIPCityDissector(CITY_MMDB),
                          GeoIPASNDissector(ASN_MMDB)],
    )
    res = p.parse_batch([GEO_LINE, MISS_LINE])
    assert res.to_pylist("STRING:connection.client.host.country.name") == [
        "Netherlands", None,
    ]
    assert res.to_pylist("ASN:connection.client.host.asn.number") == [4444, None]
    table = res.to_arrow()
    assert table.column(
        "STRING:connection.client.host.city.name"
    ).to_pylist() == ["Amstelveen", None]
    import pyarrow as pa

    assert table.column(
        "STRING:connection.client.host.country.name"
    ).type == pa.string()


# -- the generated fixtures themselves ----------------------------------------

def test_generated_fixtures_roundtrip(tmp_path):
    """The self-contained MMDB writer's output must read back exactly
    through our own reader (whatever TEST_DATA the rest of the suite
    used) — networks, misses, IPv6, and the flattened device ranges."""
    from logparser_tpu.tools.geoip_testdata import write_test_databases

    paths = write_test_databases(str(tmp_path))
    city = MMDBReader(paths["GeoIP2-City-Test.mmdb"])
    rec = city.lookup("80.100.47.45")
    assert rec["city"]["names"]["en"] == "Amstelveen"
    assert rec["country"]["is_in_european_union"] is True
    assert rec["location"]["longitude"] == 5.75
    assert city.lookup("80.100.46.255") is None
    assert city.lookup("80.100.48.0") is None
    asn = MMDBReader(paths["GeoLite2-ASN-Test.mmdb"])
    assert asn.lookup("2001:980:91c0::1")["autonomous_system_number"] == 6666
    assert asn.lookup("2002::1") is None
    starts = [s for s, _, _ in asn.ipv4_ranges()]
    assert starts == sorted(starts)
