"""Vectorized post-stages: typed decoding of captured spans on device.

These replace the reference's per-line sub-dissectors on the hot path:
- :func:`parse_long_spans` — digit spans -> int64 (CLF '-' aware), replacing
  Value.getLong / ConvertCLFIntoNumber.
- :func:`parse_secmillis_spans` — ``"1483455396.639"`` decimal spans ->
  epoch-millis limbs, replacing ConvertSecondsWithMillisStringDissector
  (nginx ``$msec``/``$request_time``).
- :func:`split_firstline` — "GET /x HTTP/1.1" spans -> method/uri/protocol
  sub-spans (HttpFirstLineDissector.java:59-63 semantics: first space, last
  space, protocol validated as ``HTTP/``).

Timestamp layouts are handled generically by ``tpu/timeparse.py`` (any
fixed-width TimeLayout compiles to a device program); the epoch/derived
output math happens host-side in ``tpu/timefields.py``.
"""
from __future__ import annotations

import functools

from typing import Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

# Full int64 frame: every value of <= 19 digits decodes exactly on device
# (the widest int64, Long.MAX_VALUE = 9223372036854775807, has 19).  Runs
# LONGER than 19 digits stay device-valid too: parse_long_spans flags them
# ``big`` and the batch runtime patches their exact value from the byte
# buffer host-side (reference semantics: TokenParser FORMAT_NUMBER has no
# width bound; values beyond Long range deliver through the STRING cast).
MAX_LONG_DIGITS = 19
LONG_MAX = (1 << 63) - 1
# uint64 powers of ten for the host-side frame combine (10^19 overflows
# int64 but not uint64; mixed-dtype np.power would promote to float64).
_POW10_U64 = np.array([10 ** k for k in range(MAX_LONG_DIGITS + 1)],
                      dtype=np.uint64)


def pow10_weights(w: int) -> jnp.ndarray:
    """[w] descending powers of ten (10^(w-1) .. 10^0) for digit-window
    dot products.  Built from iota rather than a numpy constant; XLA
    folds it to a constant either way."""
    return jnp.int32(10) ** (
        w - 1 - jax.lax.broadcasted_iota(jnp.int32, (w,), 0)
    )


def shift_zero(x: jnp.ndarray, k: int) -> jnp.ndarray:
    """Left-shift columns by k, zero-filling the tail — the single shared
    shift primitive (pipeline re-exports it); every consumer masks bytes
    past the span/line end."""
    if k <= 0:
        return x
    B, L = x.shape
    if k >= L:
        return jnp.zeros_like(x)
    return jnp.concatenate([x[:, k:], jnp.zeros((B, k), x.dtype)], axis=1)


def _pad_cols(x: jnp.ndarray, w: int) -> jnp.ndarray:
    B, cur = x.shape
    if cur >= w:
        return x[:, :w]
    return jnp.pad(x, ((0, 0), (0, w - cur)))


def gather_span_bytes(buf: jnp.ndarray, start: jnp.ndarray, width: int) -> jnp.ndarray:
    """Extract `width` bytes per line beginning at start: [B, width].

    TPU gathers are scalar-slow, so this is a log-shift alignment instead:
    decompose the per-row shift into its bits and apply each power-of-two
    shift as a static slice + select.  The working width narrows as high bits
    are consumed, so total work is ~(width * log2(L) + L) elements — a couple
    of [B, L]-equivalent vector passes, no gather.  Bytes shifted in from
    beyond the row are 0 (callers' validity masks already exclude them)."""
    B, L = buf.shape
    width = min(width, L)
    x = buf
    for j in reversed(range(max(1, (L - 1).bit_length()))):
        k = 1 << j
        need = width + k - 1
        bit = ((start >> j) & 1) == 1
        x = jnp.where(bit[:, None], _pad_cols(x[:, k:], need), _pad_cols(x, need))
    return x[:, :width]


def parse_long_spans(
    buf: jnp.ndarray,
    start: jnp.ndarray,
    end: jnp.ndarray,
    clf: bool = False,
    extract=None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Spans of ASCII digits -> int64 limbs, fully vectorized.

    Returns ((hi, lo, d18, ndig), is_null, ok, big).  The limbs use a
    FIXED 19-wide left-aligned frame: ``hi`` is the dot product of window
    columns 0..8 with 10^(8-i), ``lo`` of columns 9..17 with 10^(17-i),
    ``d18`` the raw 19th digit (bytes past the span masked to digit 0),
    and ``ndig`` the span's digit count — so the host combine is one
    exact uint64 integer division (combine_long_limbs), and the device
    needs no per-column scalar rounds.  ``big`` marks runs longer than 19
    digits: the frame cannot carry them, so the caller either packs span
    coordinates for a host byte-patch (direct token numerics, reference
    Long-overflow semantics) or clears ok to route the line to the
    oracle.  For big rows only the first 19 bytes are digit-checked on
    device; the host patch validates the tail.  With ``clf`` a lone '-'
    yields is_null=True (the reference maps '-' to null,
    ApacheHttpdLogFormatDissector decodeExtractedValue :176-178).
    """
    extract = extract or gather_span_bytes
    n = end - start
    bytes_ = extract(buf, start, MAX_LONG_DIGITS)
    col = jax.lax.broadcasted_iota(jnp.int32, (buf.shape[0], MAX_LONG_DIGITS), 1)
    in_span = col < n[:, None]
    digits = (bytes_ - np.uint8(ord("0"))).astype(jnp.int32)
    digit_ok = (digits >= 0) & (digits <= 9)
    d = jnp.where(in_span, digits, 0)

    p9 = pow10_weights(9)
    hi = jnp.sum(d[:, :9] * p9, axis=1).astype(jnp.int32)
    lo = jnp.sum(d[:, 9:18] * p9, axis=1).astype(jnp.int32)
    d18 = d[:, 18].astype(jnp.int32)

    is_dash = (n == 1) & (bytes_[:, 0] == np.uint8(ord("-")))
    window_digits = jnp.all(digit_ok | ~in_span, axis=1)
    big = n > MAX_LONG_DIGITS
    ok = ((n > 0) & window_digits) | (is_dash if clf else False)
    is_null = is_dash & clf
    return (
        (hi, lo, d18, jnp.clip(n, 0, MAX_LONG_DIGITS)),
        is_null, ok, big,
    )


def combine_long_limbs(hi, lo, d18, ndig, is_null):
    """Host-side frame combine -> (int64 values, overflow mask, uint64
    frame values).

    The limbs are the fixed-frame dot products of parse_long_spans: the
    19-digit left-aligned value is hi*10^10 + lo*10 + d18 with
    (19 - ndig) trailing zero digits, so dividing by 10^(19-ndig) is
    exact.  The combine runs in uint64 (10^19-1 overflows int64);
    ``overflow`` marks rows whose exact value exceeds Long.MAX_VALUE —
    the caller delivers those through the reference's STRING-cast
    overflow path (the int64 column entry is clamped, never read).
    Null slots -1.  Rows the device flagged ``big`` carry span
    coordinates in ``hi`` and must be masked out by the caller."""
    hi_u = np.asarray(hi).astype(np.uint64)
    lo_u = np.asarray(lo).astype(np.uint64)
    d_u = np.asarray(d18).astype(np.uint64)
    frame = hi_u * np.uint64(10 ** 10) + lo_u * np.uint64(10) + d_u
    shift = MAX_LONG_DIGITS - np.asarray(ndig, dtype=np.int64)
    wide = frame // _POW10_U64[np.clip(shift, 0, MAX_LONG_DIGITS)]
    overflow = wide > np.uint64(LONG_MAX)
    value = np.where(overflow, np.uint64(LONG_MAX), wide).astype(np.int64)
    value[np.asarray(is_null)] = -1
    return value, overflow, wide


def parse_secmillis_spans(
    buf: jnp.ndarray,
    start: jnp.ndarray,
    end: jnp.ndarray,
    extract=None,
) -> Tuple[
    Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray],
    jnp.ndarray, jnp.ndarray, jnp.ndarray,
]:
    """``"<seconds>.<3-digit millis>"`` spans -> (seconds limbs, millis).

    Returns ((hi, lo, ndig), millis, is_null, ok): the seconds part goes
    through :func:`parse_long_spans` (fixed-frame limbs over the sub-span
    before the dot), the 3 millis digits decode from a fixed window at the
    span end; the host combines ``seconds * 1000 + millis``.  ok requires
    the exact ``[0-9]+\\.[0-9]{3}`` shape the host regex/converter accepts
    (ConvertSecondsWithMillisStringDissector semantics), incl. the old
    total-digits cap (w <= 19).
    """
    extract = extract or gather_span_bytes
    w = end - start
    sec_limbs, _, sec_ok, sec_big = parse_long_spans(
        buf, start, jnp.maximum(end - 4, start), extract=extract
    )
    # One width-4 window serves both the dot and the three millis digits.
    win = extract(buf, jnp.maximum(end - 4, 0), 4)
    dot = win[:, 0]
    md = (win[:, 1:4] - np.uint8(ord("0"))).astype(jnp.int32)
    m_ok = jnp.all((md >= 0) & (md <= 9), axis=1)
    millis = md[:, 0] * 100 + md[:, 1] * 10 + md[:, 2]
    ok = (
        # Total width cap unchanged from the 18-digit era (nd = w-1 <= 18):
        # seconds spans stay <= 15 digits, so seconds*1000+millis can
        # never overflow int64 and the big/overflow machinery of the
        # plain long path is unreachable here.
        (w >= 5)
        & (w <= 19)
        & sec_ok
        & ~sec_big
        & m_ok
        & (dot == np.uint8(ord(".")))
    )
    is_null = jnp.zeros(buf.shape[0], dtype=bool)
    return sec_limbs, millis, is_null, ok


def split_uri_fast(
    buf: jnp.ndarray,
    start: jnp.ndarray,
    end: jnp.ndarray,
    extract=None,
    dash=None,
    need_authority: bool = True,
    window: Optional[int] = None,
) -> Dict[str, jnp.ndarray]:
    """Fast-path URI split: repair-free URIs -> sub-spans on device.

    Mirrors HttpUriDissector (dissectors/uri.py; HttpUriDissector.java:52-63)
    for spans whose repair-chain outcome the device (plus per-row fix
    materialization) can model exactly: relative URIs, scheme-less paths,
    absolute URLs with a server-based or registry-based authority
    (IPv6 ``[...]`` literals included — see the inline note: the encode
    step makes them registry-based on the host too), and opaque
    scheme-URIs (``mailto:``).  ``clean`` is False only for rows whose
    repair stages the device cannot reproduce; those re-parse on the host
    oracle (the caller folds ``ok`` into line validity):

    - bytes >= 0x7F or < 0x20 (the host passes high bytes through
      byte-to-latin-1 mojibake-preserving, which a UTF-8 span decode
      cannot reproduce),
    - ``#`` (fragment handling, =#/#&/double-# artifacts rewrite),
    - ``;`` (sound over-approximation of the HTML-entity unescape:
      every entity needs a ``;``),
    - more than one ``?``, or a ``?`` that is not the first
      query-separator occurrence (the ?->& normalization would rewrite
      bytes inside the span),
    - a scheme that fails ``[A-Za-z][A-Za-z0-9+.-]*`` (raises on the
      host — the oracle rejects the line identically),
    - an absolute URL with an actual digits-only port longer than 19
      digits (the host parses it with arbitrary precision).

    Absolute URLs (JavaUri semantics, dissectors/uri.py:105-168): scheme =
    up to the first ``:`` when it precedes any ``/``/separator; a ``://``
    introduces an authority ending at the next ``/`` or query separator;
    the LAST ``@`` splits userinfo; the last ``:`` in the remainder splits
    a digits-only port.  A non-server authority (host charset outside
    ``[A-Za-z0-9.-]`` — which covers ``%`` and every encode-set byte, so
    IPv6 literals and %-escaped hosts land here — or a non-numeric port)
    is registry-based: userinfo/host/port are all null, path/query still
    deliver.  Opaque URIs deliver protocol + path (``first_colon+1`` to
    the first separator) + query; authority parts are null.  Scheme-less
    spans not starting with ``/`` ("example.com/x") have no authority:
    the whole head is path, protocol/userinfo/host/port null (exactly
    _URI_SPLIT's behavior).

    Percent signs and printable encode-set bytes in path/query/userinfo
    do NOT force the oracle: they only flag per-row host
    micro-materialization (orders of magnitude cheaper than a full oracle
    re-parse).  ``path_fix`` marks rows whose path contains ``%`` (the
    host delivers the path percent-DECODED after the encode + %25-repair
    steps; encode-set bytes alone are an encode->decode identity and need
    no fix).  ``query_fix`` marks rows whose query contains a bad escape
    (repaired to ``%25``) or an encode-set byte (delivered %-escaped).
    ``userinfo_fix`` marks rows with ``%`` in the userinfo (the host
    percent-decodes it).  The ``%``-repair inserts only digits and the
    encode step only ``%XX`` triples, so neither can create or destroy
    separators — span boundaries are unaffected.

    An empty span — or a lone ``-`` when the caller passes the token-level
    CLF ``dash`` mask — is clean: every output is null (the host dissector
    delivers nothing).  The query span keeps its leading separator byte;
    when that byte is ``?`` the host delivers it as ``&`` (the ?&
    normalization) — the ``amp`` flag tells the materializer to swap it.

    ``window`` bounds the scan domain exactly as in :func:`split_csr`: the
    URI span is cut into a compact [B, window] buffer by ``extract`` (the
    log-shift alignment, no per-byte gather), the split runs there, and
    every positional output is rebased by the span start.  Rows whose
    span exceeds the window raise ``overflow`` (with ``ok`` held True so
    they route as a capacity defer, not a device reject); the caller
    routes those rows to the oracle.  Outputs are bit-identical to the
    unwindowed split for every row that fits: the split reads no byte at
    or past the span's end, where the window holds zeros.
    """
    B, L = buf.shape
    extract = extract or gather_span_bytes
    if window is not None and int(window) < L:
        W = int(window)
        span = end - start
        res = split_uri_fast(
            extract(buf, start, W),
            jnp.zeros_like(start),
            jnp.minimum(span, W),
            extract=extract,
            dash=dash,
            need_authority=need_authority,
        )
        for name, v in list(res.items()):
            if name.endswith("_start") or name.endswith("_end"):
                res[name] = v + start
        over = span > W
        res["ok"] = res["ok"] | over
        res["overflow"] = over
        return res
    pos = jax.lax.broadcasted_iota(jnp.int32, (B, L), 1)
    in_span = (pos >= start[:, None]) & (pos < end[:, None])
    width = end - start
    empty = width == 0
    if dash is None:
        dash = jnp.zeros(B, dtype=bool)
    all_null = empty | dash

    is_q = (buf == np.uint8(ord("?"))) & in_span
    is_amp = (buf == np.uint8(ord("&"))) & in_span
    first_sep = jnp.min(
        jnp.where(is_q | is_amp, pos, L), axis=1
    ).astype(jnp.int32)
    first_sep = jnp.minimum(first_sep, end)

    # Oracle-only bytes: controls and >= 0x7F (the host chain passes raw
    # high bytes through byte-to-latin-1 — mojibake-preserving — which a
    # UTF-8 span decode cannot reproduce), '#' (fragment handling and the
    # =#/#&/double-# rewrites) and ';' (sound over-approximation of the
    # HTML-entity unescape: every entity needs a ';').
    bad = (buf < np.uint8(0x20)) | (buf >= np.uint8(0x7F))
    bad = bad | (buf == np.uint8(ord("#"))) | (buf == np.uint8(ord(";")))
    clean = ~jnp.any(bad & in_span, axis=1)

    # Printable encode-set bytes (URIUtil's escape set minus the oracle
    # bytes above).  These no longer force the oracle: the encode step
    # %-escapes them, after which (a) in an authority the host charset /
    # port-digit checks fail exactly as they do on the RAW bytes, so the
    # registry-based outcome is identical, (b) in the path (and userinfo)
    # the later percent-DECODE undoes the escape — a byte-identity round
    # trip — and (c) in the query they are delivered ESCAPED, which the
    # per-row fix materializer reproduces (fix modes run the encode step
    # first).
    from ..dissectors.uri import ENCODE_PRINTABLE

    enc = None
    for ch in ENCODE_PRINTABLE:
        m = (buf == np.uint8(ch)) & in_span
        enc = m if enc is None else (enc | m)

    # '?' discipline: at most one, and only at the first separator.
    q_count = jnp.sum(jnp.where(is_q, 1, 0), axis=1)
    first_q = jnp.min(jnp.where(is_q, pos, L), axis=1).astype(jnp.int32)
    clean = clean & (
        (q_count == 0) | ((q_count == 1) & (first_q == first_sep))
    )

    is_pct = (buf == np.uint8(ord("%"))) & in_span
    shift = shift_zero
    nxt1 = shift(buf, 1)
    nxt2 = shift(buf, 2)

    def _is_hex(x):
        return (
            ((x >= np.uint8(ord("0"))) & (x <= np.uint8(ord("9"))))
            | ((x >= np.uint8(ord("a"))) & (x <= np.uint8(ord("f"))))
            | ((x >= np.uint8(ord("A"))) & (x <= np.uint8(ord("F"))))
        )

    pct_bad = is_pct & ~(_is_hex(nxt1) & _is_hex(nxt2) & (pos + 2 < end[:, None]))

    lead = extract(buf, start, 1)[:, 0]
    relative = (~all_null) & (lead == np.uint8(ord("/")))

    # ---- absolute/scheme-less analysis (JavaUri semantics) -----------
    is_digit = (buf >= np.uint8(ord("0"))) & (buf <= np.uint8(ord("9")))
    is_alpha = (
        ((buf >= np.uint8(ord("A"))) & (buf <= np.uint8(ord("Z"))))
        | ((buf >= np.uint8(ord("a"))) & (buf <= np.uint8(ord("z"))))
    )
    is_colon = (buf == np.uint8(ord(":"))) & in_span
    is_slash = (buf == np.uint8(ord("/"))) & in_span

    first_colon = jnp.min(jnp.where(is_colon, pos, L), axis=1).astype(jnp.int32)
    first_slash = jnp.min(jnp.where(is_slash, pos, L), axis=1).astype(jnp.int32)
    limit = jnp.minimum(jnp.minimum(first_slash, first_sep), end)
    has_scheme = (first_colon < limit) & (first_colon > start)

    scheme_cs = (
        is_alpha | is_digit
        | (buf == np.uint8(ord("+")))
        | (buf == np.uint8(ord(".")))
        | (buf == np.uint8(ord("-")))
    )
    in_scheme = (pos > start[:, None]) & (pos < first_colon[:, None])
    lead_alpha = (
        ((lead >= np.uint8(ord("A"))) & (lead <= np.uint8(ord("Z"))))
        | ((lead >= np.uint8(ord("a"))) & (lead <= np.uint8(ord("z"))))
    )
    scheme_ok = lead_alpha & jnp.all(scheme_cs | ~in_scheme, axis=1)

    d2 = extract(buf, first_colon + 1, 2)
    dslash = (
        (d2[:, 0] == np.uint8(ord("/")))
        & (d2[:, 1] == np.uint8(ord("/")))
        & (first_colon + 3 <= end)
    )
    auth_start = first_colon + 3
    slash_a = jnp.min(
        jnp.where(is_slash & (pos >= auth_start[:, None]), pos, L), axis=1
    ).astype(jnp.int32)
    auth_end = jnp.minimum(jnp.minimum(slash_a, first_sep), end)
    if need_authority:
        in_auth = (pos >= auth_start[:, None]) & (pos < auth_end[:, None])
        at = jnp.max(
            jnp.where((buf == np.uint8(ord("@"))) & in_auth, pos, -1), axis=1
        ).astype(jnp.int32)
        has_at = at >= 0
        rest_start = jnp.where(has_at, at + 1, auth_start)
        colon2 = jnp.max(
            jnp.where(
                is_colon & (pos >= rest_start[:, None])
                & (pos < auth_end[:, None]),
                pos, -1,
            ),
            axis=1,
        ).astype(jnp.int32)
        has_pcolon = colon2 >= 0
        port_start = colon2 + 1
        port_len = auth_end - port_start
        port_empty = port_len <= 0
        in_port = has_pcolon[:, None] & (pos >= port_start[:, None]) & (
            pos < auth_end[:, None]
        )
        port_digits = jnp.all(is_digit | ~in_port, axis=1)
        host_end = jnp.where(
            has_pcolon & (port_empty | port_digits), colon2, auth_end
        )
        in_host = (pos >= rest_start[:, None]) & (pos < host_end[:, None])
        host_cs = (
            is_alpha | is_digit
            | (buf == np.uint8(ord(".")))
            | (buf == np.uint8(ord("-")))
        )
        host_ok_cs = jnp.all(host_cs | ~in_host, axis=1)
        registry = (~host_ok_cs) | (has_pcolon & ~port_empty & ~port_digits)

        # IPv6 '[...]' literals need no dedicated branch: the host chain
        # %-escapes '[' and ']' BEFORE java.net.URI ever sees the
        # authority, so "[::1]" can never take the URI IPv6-literal parse —
        # the escaped host fails the charset check and the authority is
        # registry-based (host/userinfo/port null).  On device the RAW
        # '['/':' bytes fail host_cs / port_digits the same way, landing
        # on the identical registry outcome.  A '%' in the host or port
        # region likewise survives the repair ('%25' keeps the '%') and
        # fails the same checks — no oracle needed.  Userinfo is the one
        # authority part the host percent-DECODES, so rows with '%' there
        # flag per-row fix materialization instead.
        ui_fix = jnp.any(
            is_pct & (pos >= auth_start[:, None]) & (pos < at[:, None]),
            axis=1,
        )
        # Only an actual >19-digit DIGITS port needs the oracle (the host
        # parses it with arbitrary precision); a non-digit port region of
        # any length is just registry-based.  A 19-digit port beyond
        # Long.MAX decodes on device and is demoted host-side by the
        # batch combine's overflow mask.
        abs_ok = (
            has_scheme & scheme_ok & dslash
            & ~(
                has_pcolon & ~port_empty & port_digits
                & (port_len > MAX_LONG_DIGITS)
            )
        )
    else:
        # Authority details (userinfo/host/port) are not requested: skip
        # their reductions.  Correct for path/query/protocol/ref because
        # the repair chain's %-insertions in the authority cannot change
        # the path/query SPAN CONTENTS (only shift the repaired copy), a
        # >19-digit port affects only the port parse, and registry-vs-
        # server validation affects only the authority outputs.
        false_v = jnp.zeros(B, dtype=bool)
        zero_v = jnp.zeros(B, dtype=jnp.int32)
        has_at = false_v
        at = rest_start = host_end = port_start = zero_v
        has_pcolon = port_empty = false_v
        registry = jnp.ones(B, dtype=bool)  # never deliver authority parts
        ui_fix = false_v
        abs_ok = has_scheme & scheme_ok & dslash
    is_abs = has_scheme & abs_ok & ~all_null
    # Opaque URIs (scheme but no '//': mailto:, urn:, news:): java.net.URI
    # leaves the authority None, so protocol + path (+ query past the
    # first separator) deliver and host/userinfo/port are null
    # (HttpUriDissector.java:190-199 via the _URI_SPLIT no-authority arm).
    opaque = has_scheme & scheme_ok & ~dslash & ~all_null
    # Scheme-less, not starting with '/': no authority possible — the whole
    # head is path (protocol/userinfo/host/port null).
    case3 = (~has_scheme) & (~relative) & (~all_null)
    handled = all_null | relative | case3 | is_abs | opaque
    ok = clean & handled

    zero_span = start
    show_auth = is_abs & ~registry
    path_begin = jnp.where(
        is_abs, auth_end, jnp.where(opaque, first_colon + 1, start)
    )
    path_fix = jnp.any(
        is_pct & (pos >= path_begin[:, None]) & (pos < first_sep[:, None]),
        axis=1,
    )
    # Query rows change under the host chain when they hold a bad escape
    # (repaired to %25) OR an encode-set byte (delivered %-ESCAPED — the
    # query, unlike the path, is never percent-decoded).
    query_fix = jnp.any(
        (pct_bad | enc) & (pos >= first_sep[:, None]), axis=1
    )
    has_query = (~all_null) & (first_sep < end)

    def span(show, s, e):
        return jnp.where(show, s, zero_span), jnp.where(show, e, zero_span)

    proto_s, proto_e = span(is_abs | opaque, start, first_colon)
    ui_show = show_auth & has_at
    ui_s, ui_e = span(ui_show, auth_start, at)
    host_s, host_e = span(show_auth, rest_start, host_end)
    port_show = show_auth & has_pcolon & ~port_empty
    port_s, port_e = span(port_show, port_start, auth_end)
    return {
        "ok": ok,
        "overflow": jnp.zeros(B, dtype=bool),
        "all_null": all_null,
        "path_start": jnp.where(all_null, zero_span, path_begin),
        "path_end": jnp.where(all_null, zero_span, jnp.maximum(first_sep, path_begin)),
        "path_null": all_null,
        "query_start": jnp.where(all_null, zero_span, first_sep),
        "query_end": jnp.where(all_null, zero_span, end),
        "query_null": all_null,
        "query_amp": has_query,
        "proto_start": proto_s,
        "proto_end": proto_e,
        "proto_null": all_null | ~(is_abs | opaque),
        "userinfo_start": ui_s,
        "userinfo_end": ui_e,
        "userinfo_null": all_null | ~ui_show,
        "userinfo_fix": ui_fix & ui_show,
        "host_start": host_s,
        "host_end": host_e,
        "host_null": all_null | ~show_auth,
        "port_start": port_s,
        "port_end": port_e,
        "path_fix": path_fix,
        "query_fix": query_fix,
    }


def parse_ipv4_spans(
    buf: jnp.ndarray,
    start: jnp.ndarray,
    end: jnp.ndarray,
    extract=None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Dotted-quad spans -> (u32, ok, has_colon).

    Mirrors ``ipaddress.ip_address`` strictness for IPv4 (exactly four
    octets, 0-255, no leading zeros — AbstractGeoIPDissector parses with it
    and silently delivers nothing on failure).  ``has_colon`` flags spans
    that look like IPv6 literals: the host DOES look those up, so the
    caller routes them to the oracle instead of treating them as misses.
    """
    extract = extract or gather_span_bytes
    B = buf.shape[0]
    MAX_IP = 15  # 255.255.255.255
    b = extract(buf, start, MAX_IP)
    w = end - start

    octet = jnp.zeros(B, dtype=jnp.int32)
    ndig = jnp.zeros(B, dtype=jnp.int32)
    lead0 = jnp.zeros(B, dtype=bool)
    ndots = jnp.zeros(B, dtype=jnp.int32)
    value = jnp.zeros(B, dtype=jnp.uint32)
    good = jnp.ones(B, dtype=bool)
    has_colon = jnp.zeros(B, dtype=bool)
    for i in range(MAX_IP):
        in_span = i < w
        byte = b[:, i]
        has_colon = has_colon | (in_span & (byte == np.uint8(ord(":"))))
        d = (byte - np.uint8(ord("0"))).astype(jnp.int32)
        is_digit = (d >= 0) & (d <= 9)
        is_dot = byte == np.uint8(ord("."))
        # Leading zero: an octet whose first digit is 0 and has more digits.
        lead0 = lead0 | (in_span & is_digit & (ndig == 1) & (octet == 0))
        octet = jnp.where(in_span & is_digit, octet * 10 + d, octet)
        ndig = jnp.where(in_span & is_digit, ndig + 1, ndig)
        good = good & (~in_span | is_digit | is_dot)
        good = good & ~(in_span & (octet > 255))
        close = in_span & is_dot
        good = good & ~(close & (ndig == 0))
        value = jnp.where(
            close, (value << 8) | octet.astype(jnp.uint32), value
        )
        ndots = jnp.where(close, ndots + 1, ndots)
        octet = jnp.where(close, 0, octet)
        ndig = jnp.where(close, 0, ndig)
    value = (value << 8) | octet.astype(jnp.uint32)
    ok = (
        good
        & (w >= 7) & (w <= MAX_IP)
        & (ndots == 3)
        & (ndig > 0)           # final octet non-empty
        & ~lead0
    )
    return value, ok, has_colon


@functools.lru_cache(maxsize=None)
def _csr_class_table(
    sep_byte: Optional[int], kv: int, uri_encoded: bool
) -> np.ndarray:
    """256-entry byte-class table for split_csr: bit 0 = value-decode
    trigger (%/+ and, uri_encoded, the printable encode set), bit 1 =
    name-escape trigger (% / encode set), bit 2 = high byte, bit 3 = the
    kv byte, bit 4 = a single-byte separator.  One gather through this
    table replaces ~20 per-byte compare/or passes over the span."""
    t = np.zeros(256, dtype=np.uint8)
    t[ord("%")] |= 1 | 2
    t[ord("+")] |= 1
    t[0x80:] |= 4
    if uri_encoded:
        from ..dissectors.uri import ENCODE_PRINTABLE

        for ch in ENCODE_PRINTABLE:
            t[ch] |= 1 | 2
    t[kv] |= 8
    if sep_byte is not None:
        t[sep_byte] |= 16
    return t


def split_csr(
    buf: jnp.ndarray,
    start: jnp.ndarray,
    end: jnp.ndarray,
    max_segments: int,
    sep: bytes = b"&",
    kv: int = ord("="),
    uri_encoded: bool = False,
    window: Optional[int] = None,
    extract=None,
) -> Dict[str, object]:
    """CSR segment split of spans on device: the vectorized core of the
    wildcard dissectors (QueryStringFieldDissector.java:76-108 splits on
    ``&`` then ``=``; cookies split on the two-byte ``"; "`` then ``=``).

    Locates up to ``max_segments`` separator-delimited segments per line and,
    per segment, the first ``kv`` byte.  Returns per-segment arrays (lists of
    [B] vectors) — segment k spans [seg_start[k], seg_end[k]); name/value
    split at eq_pos[k] (== seg_end[k] when no kv byte).  ``decode[k]`` marks
    values containing ``%`` or ``+`` (host applies resilientUrlDecode to
    exactly those).  ``overflow`` marks lines with more segments than slots —
    the caller routes them to the oracle.

    Empty segments keep their slot (the host skips them at materialization);
    compaction on a SIMD machine would cost a sort, skipping on host costs
    nothing.

    ``window`` bounds the scan domain: ``extract`` (the log-shift
    alignment of :func:`gather_span_bytes` unless the caller passes its
    own) cuts the span bytes into a compact [B, window] buffer, and every
    [.,L]-wide plane above shrinks to [., window] — the scans are the
    kernel cost, and spans (query strings, cookie headers) are tiny next
    to the padded line length.  Rows whose span exceeds the window raise
    ``overflow`` — the same exact capacity defer as running out of slots,
    and the caller's adaptive response (double the slots, which callers
    scale the window by) resolves both.  Windowed outputs are
    bit-identical to the unwindowed split for every row that fits: the
    core sees the same span bytes at a rebased origin.
    """
    B, L = buf.shape
    if window is not None and int(window) < L:
        W = int(window)
        span = end - start
        res = split_csr(
            (extract or gather_span_bytes)(buf, start, W),
            jnp.zeros_like(start),
            jnp.minimum(span, W),
            max_segments,
            sep=sep,
            kv=kv,
            uri_encoded=uri_encoded,
        )
        for name in ("seg_start", "seg_end", "eq_pos"):
            res[name] = [v + start for v in res[name]]
        res["overflow"] = res["overflow"] | (span > W)
        return res
    n_sep = len(sep)
    shift = shift_zero
    pos = jax.lax.broadcasted_iota(jnp.int32, (B, L), 1)
    in_span = (pos >= start[:, None]) & (pos < end[:, None])
    # Byte classes via ONE table gather (uri_encoded folds the printable
    # encode set into the dec/pct planes: query strings reach the host
    # dissector AFTER the URI encode step, so segments holding encode-set
    # bytes differ from the raw device span — names stay
    # %-escaped-and-lowercased, values escape then resilient-decode —
    # and take the per-row path alongside %/+).
    cls = jnp.asarray(
        _csr_class_table(sep[0] if n_sep == 1 else None, kv, uri_encoded)
    )[buf]
    if n_sep == 1:
        is_sep = (cls & 16) != 0
    else:
        is_sep = None
        for k, byte in enumerate(sep):
            part = (
                shift(buf, k) == np.uint8(byte) if k else (buf == np.uint8(byte))
            )
            is_sep = part if is_sep is None else (is_sep & part)
    is_sep = is_sep & in_span & (pos + n_sep <= end[:, None])
    is_kv = ((cls & 8) != 0) & in_span
    is_dec = ((cls & 1) != 0) & in_span
    is_pct = ((cls & 2) != 0) & in_span
    is_high = ((cls & 4) != 0) & in_span

    # Slot-invariant precomputation (the round-20 restructure): the
    # original per-slot scans rebuilt ~16 [B, L] masks + reductions per
    # slot (256 full-array passes at 16 slots — the dominant kernel cost
    # once concrete query keys made CSR groups routine).  Every per-slot
    # quantity is a "first occurrence at/after cursor" or a "count in a
    # sub-range", so ONE suffix-min per occurrence plane and ONE
    # exclusive prefix-count per flag plane replace them; each slot then
    # costs a handful of [B]-sized gathers.  Outputs are bit-identical
    # to the sequential scan by construction.
    masked_sep = jnp.where(is_sep, pos, L)
    suffix_sep = jax.lax.cummin(masked_sep, axis=1, reverse=True)
    masked_kv = jnp.where(is_kv, pos, L)
    suffix_kv = jax.lax.cummin(masked_kv, axis=1, reverse=True)

    def _excount(m):
        # c[:, i] = occurrences in [0, i) — exclusive prefix count.
        c = jnp.cumsum(m.astype(jnp.int32), axis=1)
        return jnp.pad(c, ((0, 0), (1, 0)))

    # The three flag planes pack into ONE scan when per-plane counts fit
    # 10 bits (always true under a window): every field of the packed
    # exclusive count is non-decreasing, so field-wise differences cannot
    # borrow across fields — one cumsum + four gathers replace three + six.
    packed = None
    if L < 1024:
        packed = _excount(
            is_dec.astype(jnp.int32)
            | (is_pct.astype(jnp.int32) << 10)
            | (is_high.astype(jnp.int32) << 20)
        )
    else:
        cum_dec = _excount(is_dec)
        cum_pct = _excount(is_pct)
        cum_high = _excount(is_high)

    def _gat(mat, idx, fill, width):
        v = jnp.take_along_axis(
            mat, jnp.clip(idx, 0, width - 1)[:, None], axis=1
        )[:, 0]
        return jnp.where(idx >= width, fill, v)

    seg_start: list = []
    seg_end: list = []
    eq_pos: list = []
    decode: list = []
    name_pct: list = []
    name_high: list = []
    cursor = start
    for _ in range(max_segments):
        # First separator at/after cursor; first kv byte at/after cursor
        # clamped into the segment (kv bytes of earlier segments are all
        # below cursor — it advances past each terminator).
        nxt = _gat(suffix_sep, cursor, L, L)
        s_end = jnp.minimum(nxt, end)
        eq = jnp.minimum(_gat(suffix_kv, cursor, L, L), s_end)
        # decode: any %/+ in the value range (eq, s_end); name flags:
        # any %-ish / high byte in the name range [cursor, eq).  Range
        # bounds are clamped so empty/trailing slots count zero.
        if packed is not None:
            val_d = (
                _gat(packed, s_end, 0, L + 1)
                - _gat(packed, jnp.minimum(eq + 1, s_end), 0, L + 1)
            )
            nam_d = (
                _gat(packed, eq, 0, L + 1)
                - _gat(packed, jnp.minimum(cursor, eq), 0, L + 1)
            )
            dec_cnt = val_d & 0x3FF
            np_cnt = (nam_d >> 10) & 0x3FF
            nh_cnt = nam_d >> 20
        else:
            dec_cnt = (
                _gat(cum_dec, s_end, 0, L + 1)
                - _gat(cum_dec, jnp.minimum(eq + 1, s_end), 0, L + 1)
            )
            np_cnt = (
                _gat(cum_pct, eq, 0, L + 1)
                - _gat(cum_pct, jnp.minimum(cursor, eq), 0, L + 1)
            )
            nh_cnt = (
                _gat(cum_high, eq, 0, L + 1)
                - _gat(cum_high, jnp.minimum(cursor, eq), 0, L + 1)
            )
        seg_start.append(cursor)
        seg_end.append(s_end)
        eq_pos.append(eq)
        decode.append(dec_cnt > 0)
        name_pct.append(np_cnt > 0)
        name_high.append(nh_cnt > 0)
        cursor = s_end + n_sep
    # One more separator past the last slot = segments we cannot ship.
    has_more = (_gat(suffix_sep, cursor, L, L) < L) | (cursor < end)
    return {
        "seg_start": seg_start,
        "seg_end": seg_end,
        "eq_pos": eq_pos,
        "decode": decode,
        "name_pct": name_pct,
        "name_high": name_high,
        "overflow": has_more,
    }


def _ci_literal_mask(buf, shift, lit: bytes, in_span):
    """[B, L] bool: case-insensitive `lit` match starting at this position
    (ASCII fold on letters only)."""
    m = None
    for k, ch in enumerate(lit):
        col = shift(buf, k) if k else buf
        if ord("a") <= ch <= ord("z"):
            part = (col | np.uint8(0x20)) == np.uint8(ch)
        else:
            part = col == np.uint8(ch)
        m = part if m is None else (m & part)
    return m & in_span


def split_setcookie_csr(
    buf: jnp.ndarray,
    start: jnp.ndarray,
    end: jnp.ndarray,
    max_segments: int,
) -> Dict[str, object]:
    """Device split of a Set-Cookie response header list: ``", "`` separated
    cookies with the expires-comma rejoin quirk
    (ResponseSetCookieListDissector.java:78-115, dissectors/cookies.py:120).

    A part whose FIRST (case-insensitive) ``expires=`` starts within 15
    bytes of its end is glued to the following part (the expires date
    itself contains ``", "``); the glued part is NOT re-checked.  Host
    quirks preserved exactly: a trailing held part is silently dropped
    (``emit`` False); a held part followed by another holding part is
    overwritten on the host — those rows (and parts starting with a
    case-insensitive ``set-cookie`` prefix, which the host name parser
    strips) set ``bad`` and take the oracle.

    Per segment k: the cookie name spans [seg_start[k], name_end[k])
    (host strips + lowercases it; empty names are skipped there), the
    delivered value is the RAW whole segment [seg_start[k], seg_end[k]).
    ``overflow`` marks lines with more cookies than slots.
    """
    # The shared quirk constant (len("expires=XXXXXXX")) — imported from
    # the host dissector so device and host can never diverge.
    from ..dissectors.cookies import _MINIMAL_EXPIRES_LENGTH

    B, L = buf.shape
    shift = shift_zero
    pos = jax.lax.broadcasted_iota(jnp.int32, (B, L), 1)
    in_span = (pos >= start[:, None]) & (pos < end[:, None])

    is_sep = (
        (buf == np.uint8(ord(",")))
        & (shift(buf, 1) == np.uint8(ord(" ")))
        & in_span
        & (pos + 2 <= end[:, None])
    )
    is_semi = (buf == np.uint8(ord(";"))) & in_span
    is_eq = (buf == np.uint8(ord("="))) & in_span
    exp_mask = _ci_literal_mask(buf, shift, b"expires=", in_span)
    prefix_mask = _ci_literal_mask(buf, shift, b"set-cookie", in_span)

    seg_start: list = []
    seg_end_l: list = []
    name_end_l: list = []
    emit_l: list = []
    bad = jnp.zeros(B, dtype=bool)
    cursor = start
    for _ in range(max_segments):
        usable = is_sep & (pos >= cursor[:, None])
        nxt = jnp.min(jnp.where(usable, pos, L), axis=1).astype(jnp.int32)
        s_end = jnp.minimum(nxt, end)
        exp_usable = exp_mask & (pos >= cursor[:, None]) & (
            pos + 8 <= s_end[:, None]
        )
        exp = jnp.min(jnp.where(exp_usable, pos, L), axis=1).astype(jnp.int32)
        hold = (exp < L) & (exp > s_end - _MINIMAL_EXPIRES_LENGTH)
        last = s_end >= end

        # Merged end: the separator after the held part's date fragment.
        usable2 = is_sep & (pos >= (s_end + 2)[:, None])
        nxt2 = jnp.min(jnp.where(usable2, pos, L), axis=1).astype(jnp.int32)
        s_end2 = jnp.minimum(nxt2, end)
        exp2_usable = exp_mask & (pos >= (s_end + 2)[:, None]) & (
            pos + 8 <= s_end2[:, None]
        )
        exp2 = jnp.min(jnp.where(exp2_usable, pos, L), axis=1).astype(jnp.int32)
        hold2 = (exp2 < L) & (exp2 > s_end2 - _MINIMAL_EXPIRES_LENGTH)

        merged = hold & ~last
        bad = bad | (merged & hold2)  # host overwrite quirk -> oracle
        drop = hold & last            # trailing held part: host drops it
        seg_e = jnp.where(merged, s_end2, s_end)

        semi = jnp.min(
            jnp.where(is_semi & (pos >= cursor[:, None]) & (pos < seg_e[:, None]),
                      pos, L),
            axis=1,
        ).astype(jnp.int32)
        eq_bound = jnp.minimum(semi, seg_e)
        eq = jnp.min(
            jnp.where(is_eq & (pos >= cursor[:, None]) & (pos < eq_bound[:, None]),
                      pos, L),
            axis=1,
        ).astype(jnp.int32)
        name_end = jnp.minimum(jnp.minimum(eq, semi), seg_e)
        nonempty = cursor < seg_e
        emit = nonempty & ~drop
        # The host name parser strips a (case-insensitive) set-cookie[2]:
        # prefix first — those rows go to the oracle.
        has_prefix = jnp.any(
            prefix_mask & (pos == cursor[:, None]), axis=1
        )
        bad = bad | (emit & has_prefix)

        seg_start.append(cursor)
        seg_end_l.append(seg_e)
        name_end_l.append(name_end)
        emit_l.append(emit)
        cursor = seg_e + 2
    usable = is_sep & (pos >= cursor[:, None])
    has_more = jnp.any(usable, axis=1) | (cursor < end)
    return {
        "seg_start": seg_start,
        "seg_end": seg_end_l,
        "name_end": name_end_l,
        "emit": emit_l,
        "bad": bad,
        "overflow": has_more,
    }


def parse_mod_unique_id(
    buf: jnp.ndarray,
    start: jnp.ndarray,
    end: jnp.ndarray,
    extract=None,
) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray]:
    """mod_unique_id spans -> decoded u32 words, vectorized.

    The host decoder (dissectors/mod_unique_id.py) delivers iff the token
    is EXACTLY 24 chars of ``[A-Za-z0-9_-]`` (any other byte — incl. the
    '@'-mapped '+'/'/' — is skipped by the lenient base64 decoder, leaving
    fewer than 18 bytes, so nothing is delivered).  24 chars x 6 bits =
    exactly 18 bytes: 32-bit epoch-seconds, 32-bit IPv4, 32-bit pid,
    16-bit counter, 32-bit thread index.

    Returns ({"time","ip","pid","counter","thread"}, ok): the u32 words
    bitcast to int32 (host re-widens with ``& 0xFFFFFFFF``), counter as a
    plain int32.
    """
    extract = extract or gather_span_bytes
    b = extract(buf, start, 24)
    w = end - start

    is_upper = (b >= np.uint8(ord("A"))) & (b <= np.uint8(ord("Z")))
    is_lower = (b >= np.uint8(ord("a"))) & (b <= np.uint8(ord("z")))
    is_digit = (b >= np.uint8(ord("0"))) & (b <= np.uint8(ord("9")))
    is_dash = b == np.uint8(ord("-"))
    is_under = b == np.uint8(ord("_"))
    ok = (w == 24) & jnp.all(
        is_upper | is_lower | is_digit | is_dash | is_under, axis=1
    )

    b32 = b.astype(jnp.int32)
    v = jnp.where(
        is_upper, b32 - ord("A"),
        jnp.where(
            is_lower, b32 - ord("a") + 26,
            jnp.where(
                is_digit, b32 - ord("0") + 52,
                jnp.where(is_dash, 62, 63),  # '-' -> '+', '_' -> '/'
            ),
        ),
    ).astype(jnp.uint32)

    # 4 chars -> one 24-bit group; 6 groups -> the 18 decoded bytes.
    g = [
        (v[:, i] << 18) | (v[:, i + 1] << 12) | (v[:, i + 2] << 6) | v[:, i + 3]
        for i in range(0, 24, 4)
    ]
    time_u = (g[0] << 8) | (g[1] >> 16)
    ip_u = ((g[1] & 0xFFFF) << 16) | (g[2] >> 8)
    pid_u = ((g[2] & 0xFF) << 24) | g[3]
    counter = (g[4] >> 8).astype(jnp.int32)
    thread_u = ((g[4] & 0xFF) << 24) | g[5]

    def cast(x):
        return jax.lax.bitcast_convert_type(x, jnp.int32)

    return (
        {
            "time": cast(time_u),
            "ip": cast(ip_u),
            "pid": cast(pid_u),
            "counter": counter,
            "thread": cast(thread_u),
        },
        ok,
    )


def split_firstline(
    buf: jnp.ndarray,
    lengths: jnp.ndarray,
    start: jnp.ndarray,
    end: jnp.ndarray,
    extract=None,
) -> Dict[str, jnp.ndarray]:
    """"METHOD URI PROTO" span -> method/uri/protocol sub-spans.

    Mirrors HttpFirstLineDissector: method = up to the first space, protocol =
    after the last space (only when it matches ``xxx/d.d`` shape — otherwise
    the truncated-line fallback applies: protocol absent, uri to the end).
    ``has_protocol`` distinguishes the two cases; fully garbage lines (no
    space at all) get ok=False.
    """
    extract = extract or gather_span_bytes
    B, L = buf.shape
    pos = jax.lax.broadcasted_iota(jnp.int32, (B, L), 1)
    in_span = (pos >= start[:, None]) & (pos < end[:, None])
    is_space = (buf == np.uint8(ord(" "))) & in_span

    first_space = jnp.min(jnp.where(is_space, pos, L), axis=1)
    last_space = jnp.max(jnp.where(is_space, pos, -1), axis=1)

    has_space = first_space < L
    method_start = start
    method_end = jnp.where(has_space, first_space, start)

    # Protocol candidate: after the last space; valid only when it matches
    # HTTP/[0-9]+\.[0-9]+ exactly (the 3-part regex arm; otherwise the
    # truncated-line fallback applies).
    proto_start = jnp.where(has_space, last_space + 1, end)
    head = extract(buf, proto_start, 5)
    head_ok = (
        (head[:, 0] == np.uint8(ord("H")))
        & (head[:, 1] == np.uint8(ord("T")))
        & (head[:, 2] == np.uint8(ord("T")))
        & (head[:, 3] == np.uint8(ord("P")))
        & (head[:, 4] == np.uint8(ord("/")))
    )
    ver = (pos >= (proto_start + 5)[:, None]) & (pos < end[:, None])
    is_digit = (buf >= np.uint8(ord("0"))) & (buf <= np.uint8(ord("9")))
    is_dot = buf == np.uint8(ord("."))
    ver_chars_ok = jnp.all(is_digit | is_dot | ~ver, axis=1)
    one_dot = jnp.sum(jnp.where(is_dot & ver, 1, 0), axis=1) == 1
    last_b = extract(buf, jnp.maximum(end - 1, 0), 1)[:, 0]
    first_ver = extract(buf, proto_start + 5, 1)[:, 0]
    ver_ok = (
        ((end - proto_start) >= 8)
        & ver_chars_ok
        & one_dot
        & (first_ver >= np.uint8(ord("0"))) & (first_ver <= np.uint8(ord("9")))
        & (last_b >= np.uint8(ord("0"))) & (last_b <= np.uint8(ord("9")))
    )
    has_protocol = has_space & (last_space > first_space) & head_ok & ver_ok

    uri_start = jnp.where(has_space, first_space + 1, end)
    uri_end = jnp.where(has_protocol, last_space, end)

    return {
        "method_start": method_start,
        "method_end": method_end,
        "uri_start": uri_start,
        "uri_end": uri_end,
        "proto_start": jnp.where(has_protocol, proto_start, end),
        "proto_end": end,
        "has_protocol": has_protocol,
        "ok": has_space,
    }


def split_protocol_version(
    buf: jnp.ndarray,
    start: jnp.ndarray,
    end: jnp.ndarray,
    dash: Optional[jnp.ndarray] = None,
) -> Dict[str, jnp.ndarray]:
    """HTTP.PROTOCOL_VERSION value span ("HTTP/1.1") -> protocol + version.

    Mirrors HttpFirstLineProtocolDissector exactly: a ``None``/``""``/``"-"``
    input delivers nothing (``dash`` carries the direct-token CLF null;
    sub-span chains never produce a lone dash); a value without ``/``
    delivers explicit nulls for both outputs; otherwise protocol is
    everything before the FIRST ``/`` (``value.split("/", 1)``) and version
    everything after it — either side may be the empty string.
    """
    B, L = buf.shape
    pos = jax.lax.broadcasted_iota(jnp.int32, (B, L), 1)
    in_span = (pos >= start[:, None]) & (pos < end[:, None])
    slash = jnp.min(
        jnp.where((buf == np.uint8(ord("/"))) & in_span, pos, L), axis=1
    )
    absent = start >= end
    if dash is not None:
        absent = absent | dash
    return {
        "proto_start": start,
        "proto_end": jnp.minimum(slash, end),
        "ver_start": jnp.minimum(slash + 1, end),
        "ver_end": end,
        "null": absent | (slash >= L),
    }


def unescape_compact_spans(
    buf: jnp.ndarray,
    start: jnp.ndarray,
    end: jnp.ndarray,
    width: int,
    extract=None,
):
    """Device-side inverse of Apache's ``ap_escape_logitem`` for the
    byte-DROPPING escape classes: ``\\"`` -> ``"`` and ``\\\\`` -> ``\\``
    inside a quoted-field span, as a compaction gather.

    Returns ``(out, out_len, exact)``: ``out`` is ``[B, width]`` span
    bytes with every ESCAPING backslash removed (bytes past ``out_len``
    zeroed), ``exact`` marks rows where this IS the reference decode
    (:func:`...dissectors.utils.decode_apache_httpd_log_value`) of the
    span.  Rows carrying a byte-SUBSTITUTING escape (``\\n``/``\\t``/
    ``\\xhh`` ... — C-escapes the reference maps to different bytes, and
    a bare trailing backslash, which it raises on) or a span longer than
    ``width`` are flagged inexact and left for the host decoder.

    NOTE this pass is NOT in the product delivery path: the reference
    compares the VALUE (not the token name) before applying its decode
    (ApacheHttpdLogFormatDissector.java:170-198 — see
    httpd/utils_apache.py), so the observable host semantics deliver
    quoted-field values VERBATIM, backslashes included, and the
    escape-parity split (pipeline.compute_split) already emits exactly
    those verbatim spans.  The pass exists for consumers that want the
    DECODED form on device (and as the executable spec of the escape
    geometry, differentially locked against the reference decoder in
    tests/test_fuzz_differential.py).  Cost: one [B, width] stable
    argsort — a cold-path utility, not a hot-path stage.

    Decode model (mirrors the reference's left-to-right pair scan): in a
    maximal backslash run of length n, the backslashes at even offsets
    0, 2, ... are the escaping bytes of ``\\\\`` pairs and are dropped;
    an odd run's trailing backslash is dropped only when it escapes a
    quote (``\\"``), kept verbatim before an unknown character (the
    reference's fall-through appends both bytes), and inexact before a
    substituting C-escape."""
    extract = extract or gather_span_bytes
    B = start.shape[0]
    width = min(width, buf.shape[1])  # extract clamps to the buffer width
    n = jnp.clip(end - start, 0, None)
    win = extract(buf, start, width).astype(jnp.int32)
    pos = jax.lax.broadcasted_iota(jnp.int32, (B, width), 1)
    in_span = pos < n[:, None]
    is_bs = (win == ord("\\")) & in_span

    # Offset of each backslash inside its maximal run: distance to the
    # last non-backslash before it (same running-max derivation as
    # pipeline.escaped_lead_positions, here within the span window).
    non_bs = ~is_bs
    last_non_bs = jax.lax.cummax(jnp.where(non_bs, pos, -1), axis=1)
    prev_last = jnp.concatenate(
        [jnp.full((B, 1), -1, dtype=jnp.int32), last_non_bs[:, :-1]],
        axis=1,
    )
    even_offset = ((pos - prev_last) & 1) == 1  # offset = pos-prev_last-1

    nxt = shift_zero(win, 1)
    nxt_in_span = (pos + 1) < n[:, None]
    last_of_run = is_bs & ~(shift_zero(is_bs, 1) & nxt_in_span)
    odd_tail = last_of_run & even_offset  # odd run length <=> last offset even
    escapes_quote = odd_tail & nxt_in_span & (nxt == ord('"'))
    # Substituting C-escapes (byte rewrite, not a drop) and a trailing
    # backslash with nothing after it: the reference decode diverges
    # from pure compaction there — flag the row.
    subst = jnp.zeros(odd_tail.shape, dtype=bool)
    for c in b"bnrtvx":
        subst = subst | (nxt == c)
    inexact_pos = odd_tail & ((subst & nxt_in_span) | ~nxt_in_span)

    drop = is_bs & even_offset & (~odd_tail | escapes_quote)
    keep = in_span & ~drop
    out_len = jnp.sum(jnp.where(keep, 1, 0), axis=1)
    # Stable compaction: kept bytes first, original order preserved.
    order = jnp.argsort(jnp.where(keep, 0, 1), axis=1, stable=True)
    out = jnp.take_along_axis(win, order, axis=1)
    out = jnp.where(pos < out_len[:, None], out, 0)
    exact = (n <= width) & ~jnp.any(inexact_pos, axis=1)
    return out, out_len, exact
