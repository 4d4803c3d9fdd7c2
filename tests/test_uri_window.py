"""The windowed URI and CSR splits (tpu/postproc.split_uri_fast and
split_csr with ``window=``) against the same splits over the whole line
(``window=None``), field by field.

Line buckets of 2,048 and 4,096 bytes; spans of 0, W-1, W and W+1
bytes starting at 0, at L-W and ending at the row's end; a lone ``-``,
``%`` escapes on the window's edge, a ``?`` query, an absolute URL.
Every byte outside a span is noise the splits react to, so a window
that leaked bytes past the span would show.  Rows that fit the window
match the unwindowed split exactly; ``overflow`` is set on exactly the
rows whose span passes the window (for CSR also where the unwindowed
split runs out of slots).  The windowed URI split also lowers with no
gather over the [B, W] window: TPUs run such a gather at scalar speed.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from logparser_tpu.tpu import postproc
from logparser_tpu.tpu.pipeline import CSR_WINDOW_PER_SLOT, URI_WINDOW

# Bytes every split reacts to (separators, escapes, oracle-only bytes,
# a high byte), used as the noise around each span.
NOISE = np.frombuffer(b"/?&=%:@;#-+ 0aZ9.~\xc3", dtype=np.uint8)


def _fill(pattern: bytes, n: int) -> bytes:
    return (pattern * (n // len(pattern) + 1))[:n]


def _edge(pattern: bytes, n: int, tail: bytes) -> bytes:
    """``pattern`` filled to n bytes, its last bytes replaced by tail."""
    return _fill(pattern, n - len(tail)) + tail


def _starts(L: int, W: int, n: int):
    """At 0, at L-W and ending at the row's end (distinct, in the row)."""
    return sorted({s for s in (0, L - W, L - n) if 0 <= s and s + n <= L})


def _batch(L, rows, seed):
    """(buf, start, end) with each (start, body) written into noise."""
    rng = np.random.default_rng(seed)
    buf = rng.choice(NOISE, size=(len(rows), L))
    for i, (s, body) in enumerate(rows):
        buf[i, s:s + len(body)] = np.frombuffer(body, dtype=np.uint8)
    start = np.array([s for s, _ in rows], dtype=np.int32)
    end = np.array([s + len(b) for s, b in rows], dtype=np.int32)
    return buf, start, end


def _uri_rows(L, W):
    bodies = []
    for n in (W - 1, W, W + 1, 37):
        bodies += [
            _fill(b"/p/seg/x%41y.", n),
            b"/q?" + _fill(b"k=v&a=%2F", n - 3),
            _fill(b"/d?a?", n),        # a second '?': the oracle's
            _fill(b"/h#", n),          # a fragment: the oracle's
            _edge(b"/a", n, b"?x=1"),
            b"http://u@host.example:8080/a?b=c" + _fill(b"d", n - 33),
            b"mailto:" + _fill(b"x.y", n - 7),
            _edge(b"/e", n, b"%4F"),   # a good escape ending on the edge
            _edge(b"/e", n, b"?%4"),   # an escape cut by the span's end
            _edge(b"/e", n, b"?q%"),
        ]
    rows = [(s, b) for b in bodies for s in _starts(L, W, len(b))]
    rows += [(s, b"-") for s in (0, L - W, L - 1)]
    rows += [(s, b"") for s in (0, L - W, L - 1, L)]
    rows += [(s, b"/x") for s in (0, L - W, L - 2)]
    return rows


def _call(fn, *args, **kw):
    return jax.device_get(jax.jit(functools.partial(fn, **kw))(*args))


@pytest.mark.parametrize("need_authority", [True, False])
@pytest.mark.parametrize("L", [2048, 4096])
def test_windowed_uri_split_matches_the_whole_line(L, need_authority):
    W = URI_WINDOW
    buf, start, end = _batch(L, _uri_rows(L, W), seed=L)
    dash = ((end - start) == 1) & (buf[np.arange(len(start)), np.minimum(
        start, L - 1)] == ord("-"))
    args = (jnp.asarray(buf), jnp.asarray(start), jnp.asarray(end))
    kw = dict(dash=jnp.asarray(dash), need_authority=need_authority)
    whole = _call(postproc.split_uri_fast, *args, window=None, **kw)
    win = _call(postproc.split_uri_fast, *args, window=W, **kw)
    fits = (end - start) <= W
    assert (~fits).any() and dash.any()
    assert np.array_equal(win["overflow"], ~fits)
    assert not whole["overflow"].any()
    assert win["ok"][~fits].all()
    assert set(win) == set(whole)
    for name in sorted(whole):
        if name != "overflow":
            np.testing.assert_array_equal(
                win[name][fits], whole[name][fits], err_msg=name)
    # The rows reach the outcomes the window could disturb.
    for name in ("ok", "path_fix", "query_fix", "query_amp", "all_null"):
        assert whole[name][fits].any() and not whole[name][fits].all(), name


def _csr_rows(L, W, sep):
    bodies = []
    for n in (W - 1, W, W + 1, 37):
        bodies += [
            _fill(b"a=1" + sep + b"bb=%20" + sep + b"c+d=x" + sep, n),
            _fill(b"k=v" + sep + b"n\xc3m=%4" + sep + b"n%41=v" + sep, n),
            _fill(b"z" + sep, n),       # more segments than slots
            _edge(b"kk=vv" + sep, n, b"e=%4F"),
            _edge(b"kk=vv" + sep, n, b"e=%4"),
            _edge(b"kk=vv" + sep, n, sep),
            _edge(b"kk=vv" + sep, n, sep[:1]),
        ]
    rows = [(s, b) for b in bodies for s in _starts(L, W, len(b))]
    rows += [(s, b"") for s in (0, L - W, L - 1, L)]
    rows += [(s, b"x=-") for s in (0, L - W, L - 3)]
    return rows


@pytest.mark.parametrize("sep", [b"&", b"; "], ids=["query", "cookie"])
@pytest.mark.parametrize("L, slots", [(2048, 16), (4096, 32)])
def test_windowed_csr_split_matches_the_whole_line(L, slots, sep):
    W = CSR_WINDOW_PER_SLOT * slots
    buf, start, end = _batch(L, _csr_rows(L, W, sep), seed=L + slots)
    args = (jnp.asarray(buf), jnp.asarray(start), jnp.asarray(end))
    kw = dict(max_segments=slots, sep=sep, uri_encoded=sep == b"&")
    whole = _call(postproc.split_csr, *args, window=None, **kw)
    win = _call(postproc.split_csr, *args, window=W, **kw)
    fits = (end - start) <= W
    assert (~fits).any()
    assert np.array_equal(win["overflow"], ~fits | whole["overflow"])
    assert (fits & whole["overflow"]).any()
    assert set(win) == set(whole)
    for name in sorted(whole):
        if name == "overflow":
            continue
        got, want = np.stack(win[name]), np.stack(whole[name])
        np.testing.assert_array_equal(got[:, fits], want[:, fits],
                                      err_msg=name)
    for name in ("decode", "name_pct", "name_high"):
        assert np.stack(whole[name])[:, fits].any(), name


def _result_elements(line: str) -> int:
    """Elements of the result tensor of one StableHLO op line."""
    dims = re.search(r"->\s*tensor<([0-9x]+)x[a-z]", line)
    if dims is None:
        return 0
    return int(np.prod([int(d) for d in dims.group(1).split("x")]))


@pytest.mark.parametrize("need_authority", [True, False])
def test_windowed_uri_split_lowers_with_no_window_gather(need_authority):
    B, L, W = 8, 2048, URI_WINDOW
    fn = jax.jit(functools.partial(
        postproc.split_uri_fast, window=W, need_authority=need_authority))
    text = fn.lower(
        jax.ShapeDtypeStruct((B, L), jnp.uint8),
        jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32),
    ).as_text()
    gathers = [ln for ln in text.splitlines() if "gather" in ln]
    assert not [ln for ln in gathers if _result_elements(ln) >= B * W]


def _line(i: int, ua_len: int) -> str:
    return (f'10.0.0.{i} - - [07/Mar/2026:10:00:00 +0000] '
            f'"GET /p/{i}?k={i} HTTP/1.1" 200 {i} "-" "{"u" * ua_len}"')


def test_a_batch_past_the_window_counts_its_lines():
    """``uri_window_lines_total`` moves by a dispatched batch's lines when
    its line bucket passes the URI window and the parser splits a URI,
    and by nothing otherwise."""
    from logparser_tpu.observability import metrics
    from logparser_tpu.tpu.batch import TpuBatchParser

    reg = metrics()
    short = [_line(i, 40) for i in range(5)]
    long = [_line(i, URI_WINDOW + 100) for i in range(7)]
    uri = TpuBatchParser("combined", ["HTTP.PATH:request.firstline.uri.path"])
    bare = TpuBatchParser("combined", ["STRING:request.status.last"])
    counts = []
    for parser, lines in ((uri, short), (uri, long), (bare, long)):
        before = reg.total("uri_window_lines_total")
        result = parser.parse_batch(lines)
        counts.append(reg.total("uri_window_lines_total") - before)
        assert result.valid.all() and result.oracle_rows == 0
    assert counts == [0, len(long), 0]
