"""Guards for the shared config table and line forgers (``tests/_configs.py``).

Most of the table's users are slow-tier parity suites; these fast checks
keep the table itself honest in the counted tier: unique names, seeded
corpora of the requested size, and formats the host oracle actually
accepts.  No device program is compiled here.
"""
import pytest

from logparser_tpu.httpd import HttpdLoglineParser
from logparser_tpu.tools.demolog import generate_combined_lines
from logparser_tpu.tpu.batch import _CollectingRecord

from _configs import (
    build_configs,
    force_escaped_quote_lines,
    force_rescued_lines,
)

NAMES = ("combined", "combinedio_strftime", "nginx_uri", "geoip_chain",
         "strftime_zonetext", "multiformat_mixed")


def test_config_names_unique():
    names = [entry[0] for entry in build_configs()]
    assert len(set(names)) == len(names), names
    assert tuple(names) == NAMES


@pytest.mark.parametrize("name", NAMES)
def test_config_corpus_parses_on_host_oracle(name):
    _, fmt, fields, lines_fn, extra = next(
        entry for entry in build_configs() if entry[0] == name
    )
    lines = lines_fn(64)
    assert len(lines) == 64
    assert all(isinstance(ln, str) for ln in lines)

    oracle = HttpdLoglineParser(_CollectingRecord, fmt)
    oracle.apply_config(None, extra)
    oracle.add_parse_target("set_value", list(fields))
    oracle.assemble_dissectors()
    accepted = 0
    for ln in lines:
        try:
            oracle.parse(ln, _CollectingRecord())
        except Exception:
            continue
        accepted += 1
    assert accepted > len(lines) // 2, (name, accepted)


@pytest.mark.parametrize("forge,pct", [
    (force_escaped_quote_lines, 7),
    (force_rescued_lines, 10),
], ids=["escaped_quote", "rescued"])
def test_forger_rewrites_only_its_step(forge, pct):
    base = generate_combined_lines(160, seed=13)
    out = forge(base, pct)
    assert len(out) == len(base)
    step = max(1, round(100 / pct))
    for i, (before, after) in enumerate(zip(base, out)):
        if i % step:
            assert after == before, i
        else:
            assert after != before, i
            assert len(after) - len(before) <= 16, i
