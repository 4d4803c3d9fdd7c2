"""Codegen correctness harness: generated vs interpreted fastline.

core/fastline.py compiles the interpreted route closures into exec'd
per-format source (store-program codegen, round 9).  The contract is
byte-identical records AND byte-identical failure messages vs the
interpreted engine — this harness runs every config-table format through both
drivers over hostile corpora, and (when the reference checkout is
present) the full 3456-line hackers-access.log.

Escape hatch: ``LOGPARSER_TPU_FASTLINE_INTERP=1`` disables generation
entirely (documented in docs/README-Python.md); the last test pins it.
"""
import os

import pytest

from logparser_tpu.httpd import HttpdLoglineParser
from logparser_tpu.tools.demolog import HEADLINE_FIELDS, generate_combined_lines


class Rec:
    def __init__(self):
        self.values = {}

    def set_value(self, name, value):
        self.values[name] = value


NGINX = (
    '$remote_addr - $remote_user [$time_local] "$request" $status '
    '$body_bytes_sent "$http_referer" "$http_user_agent"'
)

# Every tests/_configs.py config's (format, fields) shape, plus the constructs the
# compiled path special-cases (URI chain, wildcards, multi-format).
BENCH_FORMATS = [
    ("combined", HEADLINE_FIELDS),
    ('%h %l %u [%{%d/%b/%Y:%H:%M:%S %z}t] "%r" %>s %b '
     '"%{Referer}i" "%{User-Agent}i" %I %O',
     ["IP:connection.client.host",
      "TIME.EPOCH:request.receive.time.epoch",
      "TIME.YEAR:request.receive.time.year",
      "STRING:request.status.last",
      "BYTES:request.bytes", "BYTES:response.bytes"]),
    (NGINX,
     ["IP:connection.client.host", "TIME.STAMP:request.receive.time",
      "HTTP.METHOD:request.firstline.method",
      "HTTP.PATH:request.firstline.uri.path",
      "HTTP.QUERYSTRING:request.firstline.uri.query",
      "STRING:request.status.last", "BYTES:response.body.bytes"]),
    ("combined",
     ["HTTP.PATH:request.firstline.uri.path",
      "STRING:request.firstline.uri.query.*"]),
    ('%h %l %u [%{%d/%b/%Y:%H:%M:%S %Z}t] "%r" %>s %b',
     ["IP:connection.client.host",
      "TIME.EPOCH:request.receive.time.epoch",
      "TIME.HOUR:request.receive.time.hour_utc",
      "STRING:request.status.last"]),
    ('combined\n%h %l %u %t "%r" %>s %b',
     ["IP:connection.client.host", "STRING:request.status.last",
      "BYTES:response.body.bytes",
      "HTTP.METHOD:request.firstline.method"]),
]


def build_parser(fmt, fields):
    parser = HttpdLoglineParser(Rec, fmt)
    parser.all_dissectors[0].stateless = True
    parser.add_parse_target("set_value", list(fields))
    parser.assemble_dissectors()
    return parser


def engine_of(parser):
    from logparser_tpu.core.fastline import compile_fastline
    from logparser_tpu.core.parser import _FASTLINE_UNSET

    engine = parser._fastline
    if engine is _FASTLINE_UNSET:
        engine = parser._fastline = compile_fastline(parser)
    return engine


def run_one(fn, line):
    rec = Rec()
    try:
        fn(line, rec)
        return ("ok", rec.values)
    except Exception as e:  # noqa: BLE001 — failure parity is the contract
        return (type(e).__name__, str(e))


def corpus():
    lines = generate_combined_lines(80, seed=23, garbage_fraction=0.2)
    lines += [
        "",
        "-",
        '1.2.3.4 - - [10/Oct/2023:13:55:36 -0700] "BROKEN" 200 - "-" "x"',
        '1.2.3.4 - - [10/Oct/2023:13:55:36 -0700] '
        '"GET /x?a=1&b=%41&c HTTP/1.0" 503 12 "-" "x"',
        # Long-overflow class (the round-9 rescue work's referee)
        '1.2.3.4 - - [10/Oct/2023:13:55:36 -0700] '
        '"GET /x HTTP/1.1" 200 9999999999999999999 "-" "x"',
        '1.2.3.4 - - [10/Oct/2023:13:55:36 -0700] '
        '"GET /x HTTP/1.1" 200 10000000000000000000 "-" "x"',
        # Escaped quote in the UA (device-decoded since round 18; still
        # a host-engine differential case here)
        '1.2.3.4 - - [10/Oct/2023:13:55:36 -0700] '
        '"GET /x HTTP/1.1" 200 5 "-" "esc \\" quote"',
        # The faithful upstream decode quirk: a VALUE literally equal to
        # "request.firstline" / starting with "request.header." runs the
        # Apache backslash-decode (utils_apache.py) — both drivers must
        # take the same branch with the same 1-arg decode.
        '1.2.3.4 - - [10/Oct/2023:13:55:36 -0700] '
        '"GET /x HTTP/1.1" 200 5 "request.firstline" "request.header.x\\t"',
        '5.6.7.8 - frank [10/Oct/2023:13:55:36 +0000] "GET / HTTP/1.0" 200 5',
    ]
    return lines


@pytest.mark.parametrize("fmt,fields", BENCH_FORMATS,
                         ids=[f"fmt{i}" for i in range(len(BENCH_FORMATS))])
def test_generated_matches_interpreted(fmt, fields):
    parser = build_parser(fmt, fields)
    engine = engine_of(parser)
    assert engine is not None, "fastline must compile for bench formats"
    assert engine.codegen_active, "codegen must attach for bench formats"
    for line in corpus():
        gen = run_one(engine.parse, line)
        interp = run_one(engine.interpreted_parse, line)
        assert gen == interp, f"divergence on {line!r}"


def test_interp_escape_hatch(monkeypatch):
    monkeypatch.setenv("LOGPARSER_TPU_FASTLINE_INTERP", "1")
    parser = build_parser("combined", HEADLINE_FIELDS)
    engine = engine_of(parser)
    assert engine is not None
    assert not engine.codegen_active
    rec = Rec()
    line = ('1.2.3.4 - - [10/Oct/2023:13:55:36 -0700] '
            '"GET /i HTTP/1.1" 200 5 "-" "ua"')
    engine.parse(line, rec)
    assert rec.values["IP:connection.client.host"] == "1.2.3.4"


def test_parse_many_matches_parse():
    parser = build_parser("combined", HEADLINE_FIELDS)
    lines = corpus()
    many = parser.parse_many(lines, Rec)
    for line, rec in zip(lines, many):
        one = run_one(parser.parse, line)
        if rec is None:
            assert one[0] != "ok" or one[1] is None
        else:
            assert one == ("ok", rec.values)


def test_generated_source_is_recorded():
    parser = build_parser("combined", HEADLINE_FIELDS)
    engine = engine_of(parser)
    assert engine.codegen_active
    src = engine.generated_source
    assert "_fmt_run_0" in src and "def _parse" in src
    # noop routes must be pruned, not emitted.
    assert "noop" not in src


@pytest.mark.slow
@pytest.mark.skipif(
    not os.path.exists("/root/reference/examples/demolog/hackers-access.log"),
    reason="reference hostile corpus not present",
)
def test_reference_corpus_differential():
    """Every bench format over the reference's 3456 hostile lines:
    generated == interpreted, record- and failure-message-exact."""
    with open("/root/reference/examples/demolog/hackers-access.log",
              "rb") as f:
        raw = f.read().split(b"\n")
    lines = [ln.decode("utf-8", "replace") for ln in raw if ln]
    assert len(lines) == 3456
    for fmt, fields in BENCH_FORMATS:
        parser = build_parser(fmt, fields)
        engine = engine_of(parser)
        if engine is None:
            continue
        diverged = [
            ln for ln in lines
            if run_one(engine.parse, ln)
            != run_one(engine.interpreted_parse, ln)
        ]
        assert not diverged, (fmt, diverged[:3])
