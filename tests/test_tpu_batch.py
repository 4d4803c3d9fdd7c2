"""TPU batch path tests: device split program vs. the host oracle.

The differential test is the core bit-exactness check: every field the device
path produces must equal what the per-line oracle engine (itself parity-tested
against the reference) produces — across a generated corpus including messy
and garbage lines.
"""
import numpy as np
import pytest

from logparser_tpu.core.exceptions import DissectionFailure
from logparser_tpu.httpd import HttpdLoglineParser
from logparser_tpu.tools.demolog import generate_combined_lines
from logparser_tpu.tpu import TpuBatchParser
from logparser_tpu.tpu.batch import _CollectingRecord
from logparser_tpu.tpu.program import compile_device_program
from logparser_tpu.tpu.runtime import encode_batch, run_program

from _configs import force_escaped_quote_lines, force_rescued_lines
from _shared_parsers import shared_parser

FIELDS = [
    "IP:connection.client.host",
    "STRING:connection.client.user",
    "TIME.EPOCH:request.receive.time.epoch",
    "HTTP.FIRSTLINE:request.firstline",
    "HTTP.METHOD:request.firstline.method",
    "HTTP.URI:request.firstline.uri",
    "STRING:request.status.last",
    "BYTES:response.body.bytes",
    "HTTP.URI:request.referer",
    "HTTP.USERAGENT:request.user-agent",
]


class _Rec:
    def __init__(self):
        self.values = {}

    def set_value(self, name: str, value):
        self.values[name] = value


def oracle_parse(lines, fields=FIELDS):
    p = HttpdLoglineParser(_Rec, "combined")
    p.add_parse_target("set_value", list(fields))
    out = []
    for line in lines:
        try:
            rec = p.parse(line, _Rec())
            out.append(rec.values)
        except DissectionFailure:
            out.append(None)
    return out


class TestSplitProgram:
    def test_compiles_combined(self):
        from logparser_tpu.httpd.apache import ApacheHttpdLogFormatDissector

        d = ApacheHttpdLogFormatDissector("combined")
        prog = compile_device_program(d)
        assert len(prog.tokens) == 9
        # combined ends with a literal quote, so every capture is until_lit.
        assert all(op.kind == "until_lit" for op in prog.ops)

    def test_run_program_valid_mask(self):
        from logparser_tpu.httpd.apache import ApacheHttpdLogFormatDissector

        d = ApacheHttpdLogFormatDissector("combined")
        prog = compile_device_program(d)
        lines = [
            '1.2.3.4 - - [31/Dec/2012:23:49:40 +0100] "GET / HTTP/1.1" 200 5 "-" "-"',
            "garbage",
            "",
        ]
        buf, lengths, _ = encode_batch(lines)
        res = run_program(prog, buf, lengths)
        valid = np.asarray(res["valid"])
        assert valid.tolist() == [True, False, False]


class TestDifferential:
    @pytest.mark.parametrize("garbage", [0.0, 0.05])
    def test_against_oracle(self, garbage):
        lines = generate_combined_lines(400, seed=7, garbage_fraction=garbage)
        batch = shared_parser("combined", FIELDS)
        result = batch.parse_batch(lines)
        expected = oracle_parse(lines)

        for fid in FIELDS:
            got = result.to_pylist(fid)
            for i, (g, exp_rec) in enumerate(zip(got, expected)):
                if exp_rec is None:
                    assert not result.valid[i], (
                        f"line {i} should be invalid: {lines[i]!r}"
                    )
                    continue
                e = exp_rec.get(fid)
                if isinstance(g, int) and isinstance(e, str):
                    e = int(e)
                assert g == e, (
                    f"field {fid} line {i}: device={g!r} oracle={e!r} "
                    f"line={lines[i]!r}"
                )

    def test_counters(self):
        lines = generate_combined_lines(200, seed=3, garbage_fraction=0.1)
        batch = shared_parser("combined", FIELDS)
        result = batch.parse_batch(lines)
        n_garbage = sum(
            1 for rec in oracle_parse(lines) if rec is None
        )
        assert result.bad_lines == n_garbage
        assert result.good_lines == 200 - n_garbage


class TestEdge:
    def test_quoted_quote_in_ua_falls_back(self):
        """A '" "' sequence inside a lazy-quoted field mis-splits the
        optimistic device pass; validation must catch it and the oracle must
        deliver the exact value."""
        line = (
            '1.2.3.4 - - [31/Dec/2012:23:49:40 +0100] "GET / HTTP/1.1" 200 5 '
            '"-" "weird" agent"'
        )
        batch = shared_parser("combined", FIELDS)
        result = batch.parse_batch([line])
        expected = oracle_parse([line])[0]
        ua = result.to_pylist("HTTP.USERAGENT:request.user-agent")[0]
        if expected is None:
            assert not result.valid[0]
        else:
            assert ua == expected.get("HTTP.USERAGENT:request.user-agent")

    def test_escaped_quote_in_ua_stays_on_device(self):
        """Round 18: a backslash-escaped quote in the FINAL quoted field
        is decoded by the escape-parity mask — zero oracle rows, the
        VERBATIM span delivered (the host decode never fires per the
        replicated upstream bug), and the decode counted."""
        lines = [
            '1.2.3.4 - - [31/Dec/2012:23:49:40 +0100] "GET / HTTP/1.1" '
            '200 5 "-" "esc \\" quote agent/1.0"',
            '1.2.3.4 - - [31/Dec/2012:23:49:40 +0100] "GET / HTTP/1.1" '
            '200 5 "-" "clean/1.0"',
        ]
        batch = shared_parser("combined", FIELDS)
        result = batch.parse_batch(lines)
        assert result.oracle_rows == 0
        assert list(result.valid) == [True, True]
        assert result.escaped_quote_rows == 1
        ua = result.to_pylist("HTTP.USERAGENT:request.user-agent")
        assert ua == ['esc \\" quote agent/1.0', "clean/1.0"]

    def test_escaped_quote_nonfinal_field_defers_to_oracle(self):
        """A skipped escaped-separator occurrence in a NON-final quoted
        field (referer ending in a backslash: raw `\\" "`) is ambiguous
        against the host regex's backtracking — the device must NOT
        claim it; the oracle referees, byte-identically."""
        line = (
            '1.2.3.4 - - [31/Dec/2012:23:49:40 +0100] "GET / HTTP/1.1" '
            '200 5 "r\\" "ua/1.0"'
        )
        batch = shared_parser("combined", FIELDS)
        result = batch.parse_batch([line])
        assert result.oracle_rows == 1
        assert result.escaped_quote_rows == 0
        expected = oracle_parse([line])[0]
        assert result.valid[0] == (expected is not None)
        if expected is not None:
            ua = result.to_pylist("HTTP.USERAGENT:request.user-agent")[0]
            assert ua == expected.get("HTTP.USERAGENT:request.user-agent")

    def test_long_line_device_resident(self):
        # Lines up to 8191 bytes fit the 13-bit span slots: no oracle.
        line = (
            '1.2.3.4 - - [31/Dec/2012:23:49:40 +0100] "GET /'
            + "a" * 8000
            + ' HTTP/1.1" 200 5 "-" "-"'
        )
        assert len(line) <= 8191
        batch = shared_parser("combined", FIELDS)
        result = batch.parse_batch([line])
        assert result.valid[0]
        assert result.oracle_rows == 0
        assert result.to_pylist("STRING:request.status.last")[0] == "200"
        uri = result.to_pylist("HTTP.URI:request.firstline.uri")[0]
        assert uri == "/" + "a" * 8000

    def test_long_line_overflow(self):
        line = (
            '1.2.3.4 - - [31/Dec/2012:23:49:40 +0100] "GET /'
            + "a" * 8300
            + ' HTTP/1.1" 200 5 "-" "-"'
        )
        batch = shared_parser("combined", FIELDS)
        result = batch.parse_batch([line])
        # Overflows the max device bucket -> host oracle handles it.
        assert result.oracle_rows == 1
        assert result.valid[0]
        assert result.to_pylist("STRING:request.status.last")[0] == "200"

    def test_bytes_numeric_vs_clf(self):
        lines = [
            '1.2.3.4 - - [31/Dec/2012:23:49:40 +0100] "GET / HTTP/1.1" 200 - "-" "-"',
            '1.2.3.4 - - [31/Dec/2012:23:49:40 +0100] "GET / HTTP/1.1" 200 123456789012 "-" "-"',
        ]
        batch = TpuBatchParser("combined", ["BYTES:response.body.bytes",
                                            "BYTESCLF:response.body.bytes"])
        result = batch.parse_batch(lines)
        assert result.to_pylist("BYTES:response.body.bytes") == [0, 123456789012]
        assert result.to_pylist("BYTESCLF:response.body.bytes") == [None, 123456789012]


class TestTimestampValidation:
    """Regression tests: device timestamp validation must agree with the host
    layout (day-in-month, leap years, leap-second clamp)."""

    def _epoch(self, ts):
        batch = TpuBatchParser("combined", ["TIME.EPOCH:request.receive.time.epoch"])
        line = f'1.2.3.4 - - [{ts}] "GET / HTTP/1.1" 200 5 "-" "-"'
        res = batch.parse_batch([line])
        return res.to_pylist("TIME.EPOCH:request.receive.time.epoch")[0], res

    def test_invalid_day_in_month_rejected(self):
        val, res = self._epoch("31/Feb/2024:10:00:00 +0000")
        # The host oracle also rejects this line; it must be counted bad.
        assert res.bad_lines == 1
        assert val is None

    def test_leap_day_accepted(self):
        val, res = self._epoch("29/Feb/2024:00:00:00 +0000")
        assert res.bad_lines == 0
        assert val == 1709164800000

    def test_leap_second_clamped_like_host(self):
        val, _ = self._epoch("27/Jan/2024:10:00:60 +0000")
        val59, _ = self._epoch("27/Jan/2024:10:00:59 +0000")
        assert val == val59


def test_negative_epoch_strftime():
    from logparser_tpu.dissectors.strftime_stamp import compile_strftime

    assert compile_strftime("%s").parse("-86400").epoch_millis == -86400000
    assert compile_strftime("%s").parse("86400").epoch_millis == 86400000


COMMON = '%h %l %u %t "%r" %>s %b'


def _common_lines(n, seed=11):
    """Common-format lines: combined lines with the quoted referer/UA cut."""
    out = []
    for line in generate_combined_lines(n, seed=seed):
        out.append(line.rsplit(' "', 2)[0])
    return out


class TestMultiFormat:
    """Vectorized multi-format: every registered format's automaton runs in
    the fused device computation; per-line winner by registration priority
    (the deterministic version of HttpdLogFormatDissector.java:174-204)."""

    FIELDS = [
        "IP:connection.client.host",
        "TIME.EPOCH:request.receive.time.epoch",
        "HTTP.METHOD:request.firstline.method",
        "HTTP.URI:request.firstline.uri",
        "STRING:request.status.last",
        "BYTES:response.body.bytes",
        "HTTP.URI:request.referer",
        "HTTP.USERAGENT:request.user-agent",
    ]

    def _mixed(self, n=32):
        a = generate_combined_lines(n, seed=3)
        b = _common_lines(n, seed=5)
        lines = [x for pair in zip(a, b) for x in pair]
        return lines

    def test_two_units_compiled(self):
        parser = shared_parser("combined\n" + COMMON, self.FIELDS)
        assert len(parser.units) == 2
        assert parser.units[1].row_offset == parser.units[0].layout.n_rows

    def test_winner_per_line(self):
        parser = shared_parser("combined\n" + COMMON, self.FIELDS)
        res = parser.parse_batch(self._mixed())
        # Interleaved combined/common lines -> alternating winners.
        assert list(res.format_index[:6]) == [0, 1, 0, 1, 0, 1]
        assert res.bad_lines == 0

    def test_matches_oracle(self):
        fmt = "combined\n" + COMMON
        lines = self._mixed() + [
            "garbage neither format accepts",
            '8.8.8.8 - - [01/Jan/2020:00:00:00 +0000] "GET / HTTP/1.1" 200 -',
        ]
        parser = TpuBatchParser(fmt, self.FIELDS)
        res = parser.parse_batch(lines)

        p = HttpdLoglineParser(_Rec, fmt)
        p.add_parse_target("set_value", list(self.FIELDS))
        for i, line in enumerate(lines):
            try:
                expected = p.parse(line, _Rec()).values
            except DissectionFailure:
                expected = None
            if expected is None:
                assert not res.valid[i], line
                continue
            assert res.valid[i], line
            for fid in self.FIELDS:
                got = res.to_pylist(fid)[i]
                exp = expected.get(fid)
                if isinstance(got, int) and exp is not None:
                    exp = int(exp)  # raw oracle stores strings; batch types them
                assert got == exp, (line, fid, got, exp)

    def test_clf_zero_semantics_per_line(self):
        """'-' bytes under Apache %b -> 0 (ConvertCLFIntoNumber); a format
        whose bytes token is a plain number never produces null."""
        fmt = "combined\n" + COMMON
        lines = [
            '1.1.1.1 - - [01/Jan/2020:00:00:00 +0000] "GET / HTTP/1.1" 200 - "-" "-"',
            '2.2.2.2 - - [01/Jan/2020:00:00:00 +0000] "GET / HTTP/1.1" 200 -',
        ]
        parser = TpuBatchParser(fmt, ["BYTES:response.body.bytes"])
        res = parser.parse_batch(lines)
        assert res.to_pylist("BYTES:response.body.bytes") == [0, 0]

    def test_priority_inversion_goes_to_oracle(self):
        """A line format 0's non-backtracking automaton false-rejects but
        format 1 accepts must NOT be claimed by format 1: the reference's
        lazy regex backtracks and accepts it under format 0 (registration
        priority).  The plausibility guard routes it to the oracle."""
        fmt0 = '"%{A}i" %h'
        fmt1 = '"%{A}i" %{C}i %h'
        line = '"x" y" 1.2.3.4'
        fields = ["HTTP.HEADER:request.header.a", "IP:connection.client.host"]
        parser = TpuBatchParser(fmt0 + "\n" + fmt1, fields)
        assert len(parser.units) == 2
        res = parser.parse_batch([line])

        p = HttpdLoglineParser(_Rec, fmt0 + "\n" + fmt1)
        p.add_parse_target("set_value", fields)
        expected = p.parse(line, _Rec()).values
        assert expected["HTTP.HEADER:request.header.a"] == 'x" y'
        assert res.valid[0]
        assert res.to_pylist("HTTP.HEADER:request.header.a")[0] == 'x" y'
        assert res.to_pylist("IP:connection.client.host")[0] == "1.2.3.4"


class TestTimestampGarbageParity:
    def test_nondigit_tz_rejected_identically_on_both_paths(self):
        """A timestamp whose tz-offset contains a non-digit ('+/000') must be
        rejected by the device program (routed to the oracle, which rejects
        it too) under BOTH executors.  Under uint8 the '/' wraps positive and
        under int32 it goes negative — without the explicit digit checks the
        two paths would disagree while both claiming ok."""
        line = (
            '1.2.3.4 - - [01/Jan/2024:00:00:00 +/000] '
            '"GET /x HTTP/1.1" 200 5 "-" "ua"'
        )
        good = (
            '1.2.3.4 - - [01/Jan/2024:00:00:00 +0000] '
            '"GET /x HTTP/1.1" 200 5 "-" "ua"'
        )
        fields = ["TIME.EPOCH:request.receive.time.epoch"]
        parser = TpuBatchParser("combined", fields)
        res = parser.parse_batch([line, good])
        valid, epochs = list(res.valid), res.to_pylist(fields[0])
        assert not valid[0]            # garbage tz -> invalid line
        assert valid[1]
        assert epochs[1] == 1704067200000


class TestMultiProducerFields:
    def test_duplicate_producers_route_to_oracle(self):
        """`%B ... %b` + translators gives BYTES/BYTESCLF two producers; the
        device must not silently pick one — the oracle's last-delivered
        value wins (graph order), typed by the producing casts."""
        p = TpuBatchParser("%B %b", ["BYTES:response.body.bytes",
                                     "BYTESCLF:response.body.bytes"])
        r = p.parse_batch(["123 456", "77 -"])
        assert list(r.valid) == [True, True]
        for fid in ("BYTES:response.body.bytes", "BYTESCLF:response.body.bytes"):
            got = r.to_pylist(fid)
            want = []
            for line in ["123 456", "77 -"]:
                rec = p.oracle.parse(line, _CollectingRecord())
                v = rec.values.get(fid)
                want.append(int(v) if v is not None else None)
            assert got == want, (fid, got, want)

    def test_multiformat_winner_host_field_stays_numeric(self):
        """A field that is multi-producer (host) under format 0 but
        device-numeric under format 1 must come out int64 for BOTH formats'
        lines (coercion follows the oracle casts, not another format's
        device plan)."""
        p = TpuBatchParser("%B %b\n%B", ["BYTES:response.body.bytes"])
        r = p.parse_batch(["123 123", "77", "0 -"])
        vals = r.to_pylist("BYTES:response.body.bytes")
        assert vals == [123, 77, 0]
        assert all(isinstance(v, int) for v in vals)
        t = r.to_arrow()
        assert str(t.column("BYTES:response.body.bytes").type) == "int64"


class TestZeroNullConverterDevice:
    """BYTES -> BYTESCLF (ConvertNumberIntoCLF) device route: the host
    compares the STRING to "0", so "00"/"007" pass through while "0" nulls —
    leading-zero spans must take the oracle, exact-"0" nulls on device."""

    def test_matches_oracle(self):
        from logparser_tpu.tpu.batch import TpuBatchParser, _CollectingRecord

        fid = "BYTESCLF:response.body.bytes"
        p = TpuBatchParser('%h %l %u %t "%r" %>s %B', [fid])
        assert p.plan_by_id[fid].null_mode == "zero_null"
        lines = [
            f'1.2.3.4 - - [07/Mar/2026:10:00:00 +0000] "GET /x HTTP/1.1" 200 {b}'
            for b in ("0", "00", "007", "123", "10")
        ]
        result = p.parse_batch(lines)
        got = result.to_pylist(fid)
        for i, line in enumerate(lines):
            want = p.oracle.parse(line, _CollectingRecord()).values.get(fid)
            if got[i] is None:
                assert want is None, (i, want)
            elif isinstance(got[i], int):
                assert got[i] == int(want), (i, got[i], want)
            else:
                assert got[i] == want, (i, got[i], want)


class TestDefinitelyBadFilter:
    """Implausible-for-every-format rejects skip the oracle entirely;
    plausible rejects still take it.  Validity must match the oracle in
    both cases (the differential fuzz asserts this across corpora; here
    the oracle_rows accounting itself is locked)."""

    def test_garbage_skips_oracle(self):
        batch = shared_parser("combined", FIELDS)
        lines = [
            '1.2.3.4 - - [31/Dec/2012:23:49:40 +0100] "GET /x HTTP/1.1" '
            '200 5 "-" "-"',
            "complete garbage with no structure",
            "",
            "a b c d e f g h i j k",
        ]
        result = batch.parse_batch(lines)
        assert list(result.valid) == [True, False, False, False]
        assert result.bad_lines == 3
        assert result.oracle_rows == 0

    def test_long_overflow_decodes_without_oracle(self):
        # Round 9: the full-int64 decoder keeps >19-digit runs on the
        # device path (reference FORMAT_NUMBER has no width bound); the
        # exact value is byte-patched host-side — NO oracle visit.
        batch = shared_parser("combined", FIELDS)
        lines = [
            '1.2.3.4 - - [31/Dec/2012:23:49:40 +0100] "GET /x HTTP/1.1" '
            '200 99999999999999999999 "-" "-"',
        ]
        result = batch.parse_batch(lines)
        assert result.oracle_rows == 0
        assert result.valid[0]
        assert result.to_pylist("BYTES:response.body.bytes") == [
            99999999999999999999
        ]

    def test_nondigit_overflow_tail_still_visits_oracle(self):
        # A >19-digit run whose tail (beyond the device's 19-byte digit
        # window) is NOT all digits cannot be byte-patched: the line is
        # demoted to the oracle, which rejects it like the reference.
        batch = shared_parser("combined", FIELDS)
        lines = [
            '1.2.3.4 - - [31/Dec/2012:23:49:40 +0100] "GET /x HTTP/1.1" '
            '200 9999999999999999999x9 "-" "-"',
        ]
        result = batch.parse_batch(lines)
        assert result.oracle_rows == 1
        assert not result.valid[0]

    def test_overflow_lines_always_oracle(self):
        # Truncated lines: the device's plausibility verdict covers only
        # the prefix, so overflow rows must keep their oracle visit.
        batch = shared_parser("combined", FIELDS)
        line = (
            '1.2.3.4 - - [31/Dec/2012:23:49:40 +0100] "GET /'
            + "a" * 8300
            + ' HTTP/1.1" 200 5 "-" "-"'
        )
        result = batch.parse_batch([line])
        assert result.oracle_rows == 1
        assert result.valid[0]

    def test_trailing_newline_matches_oracle(self):
        # Python '$' matches before a final '\n', so the oracle accepts a
        # newline-terminated line; the device path must agree (and stay
        # device-resident, not merely rescue via the oracle).
        base = (
            '1.2.3.4 - - [31/Dec/2012:23:49:40 +0100] "GET /x HTTP/1.1" '
            '200 5 "-" "ua"'
        )
        batch = shared_parser("combined", FIELDS)
        result = batch.parse_batch([base + "\n", base, base + "\n\n"])
        expected = oracle_parse([base + "\n", base, base + "\n\n"])
        assert [bool(v) for v in result.valid] == [
            rec is not None for rec in expected
        ]
        assert result.valid[0] and result.valid[1]
        assert result.oracle_rows == 0
        ua = result.to_pylist("HTTP.USERAGENT:request.user-agent")
        assert ua[0] == "ua" == ua[1]

    def test_uncompilable_format_gets_plausibility_probe(self):
        # A format the device cannot compile ("%h%m": adjacent value
        # tokens) contributes a plausibility-only probe unit; lines only
        # IT accepts must still reach the oracle.
        batch = TpuBatchParser("combined\n%h%m", ["IP:connection.client.host"])
        assert len(batch.units) == 2
        assert [u.plausibility_only for u in batch.units] == [False, True]
        lines = [
            '1.2.3.4 - - [31/Dec/2012:23:49:40 +0100] "GET /x HTTP/1.1" '
            '200 5 "-" "-"',
            "7.8.9.1GET",        # only the %h%m format accepts this
            "total garbage $$$",
        ]
        result = batch.parse_batch(lines)
        vals = result.to_pylist("IP:connection.client.host")
        for i, line in enumerate(lines):
            try:
                rec = batch.oracle.parse(line, _CollectingRecord())
                ok = True
            except Exception:
                rec, ok = None, False
            assert bool(result.valid[i]) == ok, (i, line)
            if ok:
                assert vals[i] == rec.values.get("IP:connection.client.host")
        assert result.valid[1]  # the %h%m line survived via the oracle

    def test_uncompilable_format_does_not_truncate_later_formats(self):
        # VERDICT round-2 item 3: a compilable format listed AFTER an
        # uncompilable one keeps its device path — only lines plausible
        # under the higher-priority uncompilable format go to the oracle.
        fields = ["IP:connection.client.host", "STRING:request.status.last",
                  "BYTES:response.body.bytes"]
        batch = TpuBatchParser('%h%l %u %t "%r" %>s %b\ncombined', fields)
        assert [u.plausibility_only for u in batch.units] == [True, False]
        assert batch._device_covers_all_formats

        combined = (
            '1.2.3.4 - frank [10/Oct/2026:13:55:36 -0700] '
            '"GET /x HTTP/1.1" 200 23 "-" "ua"'
        )
        first_only = (
            '1.2.3.4- frank [10/Oct/2026:13:55:36 -0700] '
            '"GET /x HTTP/1.1" 200 23'
        )
        lines = [combined, first_only, "garbage"]
        result = batch.parse_batch(lines)
        # The combined line is claimed ON DEVICE by format 1 (implausible
        # under format 0: its trailing %b wants a digits/'-' line end).
        assert result.format_index[0] == 1
        for i, line in enumerate(lines):
            try:
                want = batch.oracle.parse(line, _CollectingRecord()).values
                ok = True
            except Exception:
                want, ok = {}, False
            assert bool(result.valid[i]) == ok, (i, line)
            for f in fields:
                got = result.to_pylist(f)[i]
                w = want.get(f) if ok else None
                assert got == w or (w is not None and str(got) == str(w)), (
                    i, f, got, w,
                )

        # A pure combined corpus stays fully device-resident.
        pure = [combined] * 32
        assert batch.parse_batch(pure).oracle_rows == 0

    def test_line_plausible_under_uncompilable_format_takes_oracle(self):
        # Both formats could accept the line shape-wise; registration
        # priority belongs to the uncompilable format, so the device must
        # NOT claim it for the later format.
        fields = ["STRING:request.status.last"]
        batch = TpuBatchParser('%h%l %u %>s\n%h %u %>s', fields)
        assert [u.plausibility_only for u in batch.units] == [True, False]
        # Accepted by BOTH formats' regexes; format 0 wins by priority.
        line = "1.2.3.4 frank 200"
        result = batch.parse_batch([line])
        want = batch.oracle.parse(line, _CollectingRecord()).values
        assert result.oracle_rows == 1          # contested -> oracle
        assert bool(result.valid[0])
        assert result.to_pylist(fields[0])[0] == want.get(fields[0])


class TestModUniqueIdDevice:
    """mod_unique_id via type remapping: the device plan chase follows the
    remap edge and the fixed 24-char base64 variant decodes on device."""

    FMT = "%h %{unique_id}e %>s"
    REMAP = {"server.environment.unique_id": "MOD_UNIQUE_ID"}
    FIELDS = [
        "TIME.EPOCH:server.environment.unique_id.epoch",
        "IP:server.environment.unique_id.ip",
        "PROCESSID:server.environment.unique_id.processid",
        "COUNTER:server.environment.unique_id.counter",
        "THREAD_INDEX:server.environment.unique_id.threadindex",
        "MOD_UNIQUE_ID:server.environment.unique_id",
    ]

    def _parser(self):
        return TpuBatchParser(self.FMT, self.FIELDS,
                              type_remappings=self.REMAP)

    def test_resolves_to_device_plans(self):
        p = self._parser()
        kinds = {f.partition(":")[0]: p.plan_by_id[f].kind for f in self.FIELDS}
        assert kinds["TIME.EPOCH"] == "muid"
        assert kinds["IP"] == "muid"
        assert kinds["MOD_UNIQUE_ID"] == "span"  # the remapped raw value
        assert p._unit_oracle_fields == [[]]

    def test_differential(self):
        p = self._parser()
        tokens = [
            "VaGTKApid0AAALpaNo0AAAAC",   # known decode 1
            "Ucdv38CoEJwAAEusp6EAAADz",   # known decode 2
            "AAAAAAAAAAAAAAAAAAAAAAAA",   # all zero
            "____________------------",   # alphabet extremes
            "short",                      # wrong length: no delivery
            "VaGTKApid0AAALpaNo0AAA@C",   # '@': skipped char, no delivery
            "VaGTKApid0AAALpaNo0AAA+C",   # '+' -> '@': no delivery
            "VaGTKApid0AAALpaNo0AAA=C",   # '=' mid-token: no delivery
            "-",                          # CLF null token value
        ]
        lines = [f"9.9.9.9 {t} 200" for t in tokens]
        result = p.parse_batch(lines)
        assert result.oracle_rows == 0
        for f in self.FIELDS:
            got = result.to_pylist(f)
            for i, line in enumerate(lines):
                rec = p.oracle.parse(line, _CollectingRecord())
                want = rec.values.get(f)
                g = got[i]
                if isinstance(g, int) and want is not None:
                    want = int(want)
                assert g == want, (f, tokens[i], g, want)

    def test_known_values(self):
        p = self._parser()
        r = p.parse_batch(["9.9.9.9 VaGTKApid0AAALpaNo0AAAAC 200"])
        assert r.to_pylist(self.FIELDS[0]) == [1436652328000]
        assert r.to_pylist(self.FIELDS[1]) == ["10.98.119.64"]
        assert r.to_pylist(self.FIELDS[2]) == [47706]
        assert r.to_pylist(self.FIELDS[3]) == [13965]
        assert r.to_pylist(self.FIELDS[4]) == [2]


def test_single_char_token_width_enforced():
    """'.'-regex tokens ($pipe) match EXACTLY one byte: without the max
    bound the device accepted longer spans and SILENTLY diverged from the
    regex (a lazy token to the left absorbed the difference) instead of
    falling back.  Found by differential fuzz."""
    batch = TpuBatchParser(
        "$upstream_status $host $remote_user $pipe",
        ["STRING:connection.client.user", "STRING:connection.nginx.pipe"],
    )
    lines = [
        "404, - example.com - .",   # ambiguous: must go to the oracle
        "200 h.com bob p",          # clean: device-resident
    ]
    result = batch.parse_batch(lines)
    assert result.oracle_rows == 1
    user = result.to_pylist("STRING:connection.client.user")
    pipe = result.to_pylist("STRING:connection.nginx.pipe")
    for i, line in enumerate(lines):
        rec = batch.oracle.parse(line, _CollectingRecord())
        assert user[i] == rec.values.get("STRING:connection.client.user")
        assert pipe[i] == rec.values.get("STRING:connection.nginx.pipe")
    assert user[0] == "example.com -"  # the regex's greedy-backtrack answer


class TestParseBlob:
    """parse_blob: the list-free ingest path must deliver identically to
    parse_batch over the same framing."""

    def _parser(self):
        from logparser_tpu.tools.demolog import HEADLINE_FIELDS

        return TpuBatchParser("combined", HEADLINE_FIELDS)

    def test_blob_equals_batch(self):
        from logparser_tpu.tools.demolog import generate_combined_lines

        parser = self._parser()
        lines = generate_combined_lines(96, seed=31, garbage_fraction=0.05)
        blob = "\n".join(lines).encode("utf-8")
        rb = parser.parse_blob(blob)
        rl = parser.parse_batch(lines)
        assert rb.lines_read == rl.lines_read
        assert rb.to_dict() == rl.to_dict()
        tb = rb.to_arrow()
        tl = rl.to_arrow()
        assert tb.to_pylist() == tl.to_pylist()

    def test_blob_lazy_lines_and_oracle_rescue(self):
        from logparser_tpu.tools.demolog import generate_combined_lines

        parser = self._parser()
        lines = generate_combined_lines(32, seed=32)
        # >19-digit %b: decoded on the device path (round 9), with the
        # exact value byte-patched from the LAZY blob row — the patch
        # must materialize THAT line's span from the blob buffer.
        lines[9] = ('9.9.9.9 - x [10/Oct/2023:13:55:36 -0700] '
                    '"GET /r HTTP/1.0" 200 123456789012345678901 "-" "u"')
        # A garbage-but-plausible row keeps the lazy-rescue path covered.
        lines[11] = ('8.8.8.8 - - [10/Oct/2023:13:55:36 -0700] '
                     '"GET /broken HTTP/1.1" 200 oops "-" "u"')
        blob = "\n".join(lines).encode("utf-8")
        res = parser.parse_blob(blob)
        assert res.oracle_rows >= 1
        vals = res.to_pylist("BYTES:response.body.bytes")
        assert vals[9] == 123456789012345678901

    def test_blob_framing_edges(self):
        parser = self._parser()
        ok = ('1.2.3.4 - - [10/Oct/2023:13:55:36 +0000] '
              '"GET /x HTTP/1.1" 200 5 "-" "ua"')
        # Trailing newline: final empty segment dropped (encode_blob
        # semantics); \r stripped; empty middle line is a (bad) row.
        blob = (ok + "\r\n" + "\n" + ok + "\n").encode("utf-8")
        res = parser.parse_blob(blob)
        assert res.lines_read == 3
        ips = res.to_pylist("IP:connection.client.host")
        assert ips == ["1.2.3.4", None, "1.2.3.4"]
        assert parser.parse_blob(b"").lines_read == 0

    def test_blob_overflow_line(self):
        parser = self._parser()
        ok = ('1.2.3.4 - - [10/Oct/2023:13:55:36 +0000] '
              '"GET /x HTTP/1.1" 200 5 "-" "ua"')
        huge = ok[:-1] + "x" * 9000 + '"'
        blob = (ok + "\n" + huge).encode("utf-8")
        res = parser.parse_blob(blob)
        assert res.lines_read == 2
        # The overflow row re-parses from the FULL blob bytes on host.
        ua = res.to_pylist("HTTP.USERAGENT:request.user-agent")
        assert ua[1] is not None and ua[1].endswith("x" * 20 + '')


@pytest.mark.slow  # own parser compile (wildcard field): slow tier
class TestBatchSlice:
    """BatchResult.slice (round 14): the sub-batch windowing contract the
    serving tier's continuous batching stands on — every delivery surface
    of a slice must be BYTE-identical to parsing the window's lines
    alone, including oracle-rescued rows, wildcard CSR columns, and the
    invalid-row ledger."""

    FIELDS = [
        "IP:connection.client.host",
        "TIME.EPOCH:request.receive.time.epoch",
        "STRING:request.status.last",
        "BYTES:response.body.bytes",
        "HTTP.USERAGENT:request.user-agent",
        "STRING:request.firstline.uri.query.*",
    ]

    def _corpus(self):
        lines = generate_combined_lines(160, seed=13)
        lines = force_rescued_lines(lines, 10)  # ~10% oracle-rescued
        # ...and some device-decoded escaped quotes (round 18), so the
        # slice contract also covers escape-parity-claimed rows.
        lines = force_escaped_quote_lines(lines, 7)
        lines[5] = "complete garbage"                # definitely-bad row
        return lines

    def _ipc(self, result):
        from logparser_tpu.tpu.arrow_bridge import table_to_ipc_bytes

        return table_to_ipc_bytes(
            result.to_arrow(include_validity=True, strings="copy")
        )

    def test_slice_matches_solo_parse(self):
        parser = shared_parser("combined", self.FIELDS)
        lines = self._corpus()
        combined = parser.parse_blob("\n".join(lines).encode(),
                                     emit_views=False)
        for a, b in ((0, 23), (23, 64), (64, 65), (65, 160), (0, 160)):
            window = parser.parse_blob(
                "\n".join(lines[a:b]).encode(), emit_views=False
            )
            sl = combined.slice(a, b)
            assert self._ipc(sl) == self._ipc(window), (a, b)
            assert sl.oracle_rows == window.oracle_rows, (a, b)
            assert sl.bad_lines == window.bad_lines, (a, b)
            assert sl.good_lines == window.good_lines, (a, b)
            # Per-row ledgers rebase to window-local ids.
            assert sl.reject_reasons == window.reject_reasons, (a, b)

    def test_slice_pylist_and_raw_lines(self):
        parser = shared_parser("combined", self.FIELDS)
        lines = self._corpus()
        combined = parser.parse_blob("\n".join(lines).encode(),
                                     emit_views=False)
        sl = combined.slice(40, 90)
        solo = parser.parse_blob("\n".join(lines[40:90]).encode(),
                                 emit_views=False)
        for fid in self.FIELDS:
            assert sl.to_pylist(fid) == solo.to_pylist(fid), fid
        assert sl.raw_line(0) == lines[40].encode()
        assert sl.raw_line(49) == lines[89].encode()
        assert len(sl.lengths) == sl.lines_read == 50

    def test_slice_bounds_clamp(self):
        parser = shared_parser("combined", self.FIELDS)
        res = parser.parse_blob(
            "\n".join(generate_combined_lines(8, seed=2)).encode(),
            emit_views=False,
        )
        assert res.slice(-5, 100).lines_read == 8
        assert res.slice(6, 3).lines_read == 0
        assert res.slice(8, 8).to_arrow().num_rows == 0
