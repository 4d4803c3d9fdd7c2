"""The batch parsing API: ``TpuBatchParser.parse_batch(lines) -> BatchResult``.

This is the product hot path (SURVEY §7: "compile the LogFormat to a static
field-extraction program, execute it over [B, L] uint8 batches on TPU").
Strings never leave the device as Python strings: string-typed fields are
(offset, length) span columns into the input buffer; numeric/epoch fields are
int32-limb columns decoded on device and combined to int64 on the host.

The split program AND all requested post-stages (numeric parse, timestamp ->
epoch, first-line split) trace into ONE jitted function per parser — a single
fused XLA computation per (B, L) shape bucket; batch and line length are both
padded to a bounded set of length buckets so recompilation is bounded.

Multi-format parsers run EVERY registered format's split automaton in the
same fused device computation and pick the per-line winner by registration
priority (the vectorized version of HttpdLogFormatDissector.java:174-204's
active/fallback switching — see pipeline.FormatUnit).  The host oracle (the
exact per-line engine in logparser_tpu.core/httpd) handles lines the
optimistic device split rejects and requested fields outside the winning
format's device-resolvable set (wildcards, URI repair, cookies, ...), so the
combined result is bit-exact with the reference semantics at batch
throughput for the common case.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

import os
import threading

from ..core.casts import Cast
from ..core.exceptions import DissectionFailure, OracleEngineError
from ..core.fields import cleanup_field_value

import logging as _logging

_LOG = _logging.getLogger(__name__)
from ..httpd.parser import HttpdLoglineParser
from .pipeline import (
    FieldPlan,
    FormatUnit,
    PackedLayout,
    assign_row_offsets,
    build_units_jnp_fn,
)
from .program import (
    CS_CLF_DIGITS,
    CS_DIGITS,
    DeviceProgram,
    UnsupportedFormatError,
    compile_device_program,
)
from .compile_cache import configure_jax_cache
from .runtime import encode_batch
from . import postproc, timefields

# Back-compat alias (plan resolution lives here; packing in pipeline.py).
_FieldPlan = FieldPlan

# Octet -> string vocab for vectorized dotted-quad formatting.
_OCTET_STRINGS = np.array([str(i) for i in range(256)], dtype=object)


def _apply_setter_casts(value, has_long: bool, has_double: bool):
    """LONG-then-DOUBLE setter-cast fallthrough (the reference's
    setter-signature dispatch, Parser.store's Long/Double/String setter
    preference).  SINGLE home for the ladder — used by both
    _coerce_casts (remapped sub-dissection deliveries) and the oracle
    delivery plan, which must type identical values identically."""
    if has_long:
        try:
            return int(value)
        except (TypeError, ValueError):
            pass
    if has_double:
        try:
            return float(value)
        except (TypeError, ValueError):
            pass
    return value


def _fix_uri_part(value: str, mode: str) -> str:
    """Per-row URI micro-materialization for device `fix` rows: the exact
    host repair semantics, applied to one sub-span instead of re-parsing
    the whole line (HttpUriDissector.java:111-121 encode, :166-167
    %-repair; java.net.URI path/userinfo decode).  The encode step is
    byte-local, so running it on the sub-span equals running it on the
    whole URI; the %-repair runs twice like the host (overlaps)."""
    from ..dissectors.uri import (
        _BAD_ESCAPE_PATTERN,
        _encode_bad_uri_chars,
        _percent_decode,
    )

    value = _encode_bad_uri_chars(value)
    value = _BAD_ESCAPE_PATTERN.sub(r"%25\1", value)
    value = _BAD_ESCAPE_PATTERN.sub(r"%25\1", value)
    if mode in ("path", "userinfo"):
        value = _percent_decode(value)
    return value


# Hex digit -> value (255 = not a hex digit), for the vectorized CSR
# value decode below.
_HEX_VAL = np.full(256, 255, dtype=np.uint8)
for _c in b"0123456789":
    _HEX_VAL[_c] = _c - ord("0")
for _c in b"abcdef":
    _HEX_VAL[_c] = _c - ord("a") + 10
for _c in b"ABCDEF":
    _HEX_VAL[_c] = _c - ord("A") + 10
del _c

# Label-bounded field names for host_field_lines_total{field}: the first
# _MAX_FIELD_LABELS distinct requested fields keep their own label, the
# tail collapses to "overflow" (same discipline as the front's key/tenant
# labels) so a hostile field list can't explode the registry.
_MAX_FIELD_LABELS = 64
_FIELD_LABEL_POOL: set = set()
_FIELD_LABEL_LOCK = threading.Lock()


def _bounded_field_label(fid: str) -> str:
    with _FIELD_LABEL_LOCK:
        if fid in _FIELD_LABEL_POOL:
            return fid
        if len(_FIELD_LABEL_POOL) < _MAX_FIELD_LABELS:
            _FIELD_LABEL_POOL.add(fid)
            return fid
        return "overflow"


def _qs_value_decode(bts, off):
    """Vectorized '+'/percent decode of concatenated value segments.

    ``bts`` is the raw bytes of n segments back to back; ``off`` the
    [n+1] int64 segment offsets.  Per byte: '+' -> 0x20, '%' followed by
    two same-segment hex digits -> the decoded byte (the two digits are
    consumed), anything else verbatim — the left-to-right rule of
    repair-then-URLDecode on a query value ('%' is not a hex digit, so
    escape starts can never overlap and the sequential scan vectorizes
    exactly).  Returns ``(decoded bytes, decoded offsets, bad)`` where
    ``bad[k]`` marks segments the rule does NOT cover for DIRECT token
    captures: a '%' without two in-segment hex digits (the un-repaired
    host decoder may chop it, raise ValueError, or read a %uXXXX UTF-16
    escape) or a raw byte >= 0x80 (URI-chain segments are clean ASCII by
    the split discipline; direct captures are not)."""
    n = len(off) - 1
    total = int(off[-1])
    if total == 0:
        return (np.zeros(0, dtype=np.uint8), np.zeros(n + 1, dtype=np.int64),
                np.zeros(n, dtype=bool))
    lens = np.diff(off)
    seg_id = np.repeat(np.arange(n, dtype=np.int64), lens)
    seg_end = np.repeat(off[1:], lens)
    pos = np.arange(total, dtype=np.int64)
    hexv = _HEX_VAL[bts]
    is_hex = hexv < 16
    is_pct = bts == 0x25
    i1 = np.minimum(pos + 1, total - 1)
    i2 = np.minimum(pos + 2, total - 1)
    start = is_pct & (pos + 2 < seg_end) & is_hex[i1] & is_hex[i2]
    consumed = np.zeros(total, dtype=bool)
    consumed[1:] |= start[:-1]
    consumed[2:] |= start[:-2]
    out = np.where(bts == 0x2B, np.uint8(0x20), bts)
    out = np.where(
        start, (hexv[i1].astype(np.uint8) << 4) | hexv[i2], out
    ).astype(np.uint8)
    bad_b = (is_pct & ~start) | (bts >= 0x80)
    bad = np.zeros(n, dtype=bool)
    if bad_b.any():
        bad = np.bincount(seg_id[bad_b], minlength=n) > 0
    keep = ~consumed
    new_off = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(seg_id[keep], minlength=n), out=new_off[1:])
    return out[keep], new_off, bad


def _latin1_to_utf8(bts, off):
    """Transcode decoded (latin-1 semantics) segment bytes to UTF-8 so
    they can ride the wildcard flat value buffer (whose consumers decode
    UTF-8): each byte < 0x80 passes through, each byte >= 0x80 expands
    to the two-byte UTF-8 form of U+0080..U+00FF."""
    hi = bts >= 0x80
    if not hi.any():
        return bts, off
    n = len(off) - 1
    lens = np.diff(off)
    seg_id = np.repeat(np.arange(n, dtype=np.int64), lens)
    width = 1 + hi.astype(np.int64)
    dst = np.cumsum(width) - width
    out = np.empty(int(dst[-1] + width[-1]) if len(dst) else 0,
                   dtype=np.uint8)
    out[dst] = np.where(hi, 0xC0 | (bts >> 6), bts)
    out[dst[hi] + 1] = 0x80 | (bts[hi] & 0x3F)
    extra = np.bincount(seg_id[hi], minlength=n)
    new_off = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(lens + extra, out=new_off[1:])
    return out, new_off


def _seg_scatter(dst, dst_off, src, src_off, lens):
    """Copy n variable-length segments src[src_off[k]:+lens[k]] ->
    dst[dst_off[k]:+lens[k]] with one gather/scatter pair."""
    total = int(lens.sum())
    if total == 0:
        return
    cum = np.zeros(len(lens) + 1, dtype=np.int64)
    np.cumsum(lens, out=cum[1:])
    ar = np.arange(total, dtype=np.int64)
    dst[np.repeat(dst_off - cum[:-1], lens) + ar] = (
        src[np.repeat(src_off - cum[:-1], lens) + ar]
    )


class _CollectingRecord:
    """Host-fallback record capturing every delivered value by field id."""

    def __init__(self) -> None:
        self.values: Dict[str, Any] = {}

    def set_value(self, name: str, value) -> None:
        self.values[name] = value


# ---------------------------------------------------------------------------
# Parallel oracle fallback: the per-line engine is pure Python, so large
# fallback sets (hostile batches, host-only fields) are fanned out over a
# persistent spawn pool — each worker holds ONE unpickled oracle parser (the
# reference's serialize-config-to-workers distribution contract, SURVEY §3.4,
# applied to the fallback path).
# ---------------------------------------------------------------------------

_WORKER_PARSER = None


def _oracle_worker_init(blob: bytes) -> None:
    global _WORKER_PARSER
    import pickle

    _WORKER_PARSER = pickle.loads(blob)
    _WORKER_PARSER.assemble_dissectors()


def _values_of(rec):
    """parse_many result -> delivery value: the record's values dict, or
    the None / OracleEngineError verdict passed through unchanged."""
    if rec is None or isinstance(rec, OracleEngineError):
        return rec
    return rec.values


def _oracle_worker_run(lines: List[str]) -> List[Optional[Dict[str, Any]]]:
    return [
        _values_of(rec)
        for rec in _WORKER_PARSER.parse_many(lines, _CollectingRecord)
    ]


class _LazyWildcard:
    """Override mapping for wildcard (``.*``) CSR fields.

    The flat CSR buffers (rows, per-segment name/value byte runs) are kept
    as-is; the per-row Python dicts the ``to_pylist`` contract requires
    materialize on first dict-style access.  The Arrow bridge reads the
    flat buffers directly (``to_arrow_map``) and never pays the per-row
    build.  ``eager`` holds dicts delivered individually (slow-path rows,
    oracle fallback); it always wins over chunk data for the same row.
    """

    __slots__ = ("eager", "chunks", "_dense", "dropped")

    def __init__(self) -> None:
        self.eager: Dict[int, Any] = {}
        # (vrows, seg_row, name_bytes, name_off, val_bytes, val_off, high)
        self.chunks: List[tuple] = []
        self._dense: Optional[Dict[int, Any]] = None
        # Tombstones: rows popped by the caller (csr_failed invalidation).
        # A row can be chunk-delivered by one CSR group and failed by
        # ANOTHER group on the same line, so pop must shadow chunk data
        # too, not just `eager`.
        self.dropped: set = set()

    def add_chunk(self, vrows, seg_row, nb, non, vb, nov, seg_high) -> None:
        self.chunks.append((vrows, seg_row, nb, non, vb, nov, seg_high))
        self._dense = None

    def _materialize(self) -> Dict[int, Any]:
        if self._dense is None:
            dense: Dict[int, Any] = {}
            for vrows, seg_row, nb, non, vb, nov, _hi in self.chunks:
                for r in vrows.tolist():
                    dense[r] = {}
                rl = seg_row.tolist()
                for j in range(len(rl)):
                    name = (
                        nb[non[j] : non[j + 1]]
                        .decode("utf-8", "replace").lower()
                    )
                    dense[rl[j]][name] = vb[nov[j] : nov[j + 1]].decode(
                        "utf-8", "replace"
                    )
            dense.update(self.eager)
            for i in self.dropped:
                dense.pop(i, None)
            self._dense = dense
        return self._dense

    def __contains__(self, i) -> bool:
        return i in self._materialize()

    def __getitem__(self, i):
        return self._materialize()[i]

    def __setitem__(self, i, value) -> None:
        self.eager[i] = value
        self.dropped.discard(i)
        if self._dense is not None:
            self._dense[i] = value

    def pop(self, i, default=None):
        self.dropped.add(i)
        if self._dense is not None:
            self._dense.pop(i, None)
        return self.eager.pop(i, default)

    def __bool__(self) -> bool:
        return bool(self.eager) or any(
            len(c[0]) for c in self.chunks
        ) or bool(self._dense)

    def sliced(self, start: int, stop: int) -> "_LazyWildcard":
        """Row-window copy for :meth:`BatchResult.slice`: eager rows and
        tombstones rebase to slice-local indices; each flat chunk is
        filtered to the window's segments with its byte runs re-packed —
        the SAME one-chunk construction a solo parse of those rows would
        have produced, so ``to_arrow_map``'s fast path (and its output
        bytes) are preserved across slicing."""
        out = _LazyWildcard()
        out.eager = {
            i - start: v for i, v in self.eager.items() if start <= i < stop
        }
        out.dropped = {
            i - start for i in self.dropped if start <= i < stop
        }
        for vrows, seg_row, nb, non, vb, nov, seg_high in self.chunks:
            vrows = np.asarray(vrows, dtype=np.int64)
            seg_row = np.asarray(seg_row, dtype=np.int64)
            vsel = (vrows >= start) & (vrows < stop)
            ssel = (seg_row >= start) & (seg_row < stop)
            if not vsel.any() and not ssel.any():
                continue
            name_lens = np.diff(np.asarray(non, dtype=np.int64))
            val_lens = np.diff(np.asarray(nov, dtype=np.int64))
            nb_np = np.frombuffer(nb, dtype=np.uint8)
            vb_np = np.frombuffer(vb, dtype=np.uint8)
            new_non = np.zeros(int(ssel.sum()) + 1, dtype=np.int64)
            np.cumsum(name_lens[ssel], out=new_non[1:])
            new_nov = np.zeros(int(ssel.sum()) + 1, dtype=np.int64)
            np.cumsum(val_lens[ssel], out=new_nov[1:])
            out.add_chunk(
                vrows[vsel] - start,
                seg_row[ssel] - start,
                nb_np[np.repeat(ssel, name_lens)].tobytes(),
                new_non,
                vb_np[np.repeat(ssel, val_lens)].tobytes(),
                new_nov,
                np.asarray(seg_high, dtype=bool)[ssel],
            )
        return out

    def to_arrow_map(self, B: int):
        """pyarrow MapArray built straight from the flat buffers; None when
        this needs the exact dict path (multi-chunk/multi-format results,
        non-ASCII names whose str.lower() differs from the byte fold,
        duplicate names within a row — the dict contract collapses those).
        Individually-delivered rows (``eager``: decode/repair/oracle rows)
        and popped rows (``dropped``) are PATCHED into the flat
        construction rather than disabling it — a single %-escaped value
        in a big batch must not cost the whole column its fast path."""
        if self._dense is not None or len(self.chunks) != 1:
            return None
        if len(self.eager) > max(64, B // 32):
            return None  # heavy fallback traffic: splicing stops paying
        import pyarrow as pa

        vrows, seg_row, nb, non, vb, nov, seg_high = self.chunks[0]
        seg_row = np.asarray(seg_row, dtype=np.int64)
        seg_high = np.asarray(seg_high, dtype=bool)
        n_seg = len(seg_row)
        name_lens = np.diff(non)
        val_lens = np.diff(nov)
        nb_np = np.frombuffer(nb, dtype=np.uint8)
        vb_np = np.frombuffer(vb, dtype=np.uint8)
        upper = (nb_np >= 0x41) & (nb_np <= 0x5A)
        folded = np.where(upper, nb_np | 0x20, nb_np)

        # Rows whose chunk segments must not be emitted: individually
        # delivered (eager wins) or popped.  Filter BEFORE the bail-out
        # checks so a shadowed row's segments (e.g. duplicate names on an
        # oracle-overridden line) cannot cost the column its fast path.
        shadow = set(self.dropped)
        shadow.update(self.eager)
        if shadow:
            shadow_arr = np.fromiter(shadow, dtype=np.int64)
            seg_keep = ~np.isin(seg_row, shadow_arr)
            if not seg_keep.all():
                byte_keep_n = np.repeat(seg_keep, name_lens)
                byte_keep_v = np.repeat(seg_keep, val_lens)
                folded = folded[byte_keep_n]
                vb_np = vb_np[byte_keep_v]
                seg_row = seg_row[seg_keep]
                seg_high = seg_high[seg_keep]
                name_lens = name_lens[seg_keep]
                val_lens = val_lens[seg_keep]
                n_seg = len(seg_row)

        if bool(seg_high.any()):
            return None
        # One shared pair of cumulative offsets over the filtered segment
        # lens (used by the duplicate check, the eager splice, and — when
        # no splice mutates the lens — the final StringArray offsets).
        nb_off = np.zeros(n_seg + 1, dtype=np.int64)
        np.cumsum(name_lens, out=nb_off[1:])
        vb_off = np.zeros(n_seg + 1, dtype=np.int64)
        np.cumsum(val_lens, out=vb_off[1:])
        if n_seg:
            # Duplicate-name detection by signature (row, len, sum, first,
            # last byte) over the FOLDED bytes — the emitted keys are
            # folded, so "A"/"a" must count as duplicates.  Any collision
            # — including a false positive — bails to the dict path,
            # which dedups exactly.
            sums = np.add.reduceat(folded.astype(np.int64), nb_off[:-1])
            sig = np.stack([
                seg_row, name_lens, sums,
                folded[nb_off[:-1]].astype(np.int64),
                folded[nb_off[1:] - 1].astype(np.int64),
            ])
            if np.unique(sig, axis=1).shape[1] != n_seg:
                return None

        counts = np.zeros(B, dtype=np.int64)
        left = np.searchsorted(seg_row, vrows, side="left")
        right = np.searchsorted(seg_row, vrows, side="right")
        counts[vrows] = right - left
        covered = np.zeros(B, dtype=bool)
        covered[vrows] = True
        for i in self.dropped:
            if 0 <= i < B:
                covered[i] = False
                counts[i] = 0

        # Splice the eager rows' items into row order (few rows: python
        # per ROW, still vectorized per segment everywhere else).
        spliced = False
        if self.eager:
            cut_bytes_n = cut_bytes_v = cut_seg = 0
            inserts = []
            for i in sorted(self.eager):
                if not (0 <= i < B) or i in self.dropped:
                    # Dropped wins over eager — matching _materialize's
                    # update-then-pop order.
                    continue
                d = self.eager[i]
                if d is None:
                    covered[i] = False
                    counts[i] = 0
                    continue
                covered[i] = True
                counts[i] = len(d)
                keys_b = [str(k).encode("utf-8") for k in d.keys()]
                vals_b = [str(v).encode("utf-8") for v in d.values()]
                inserts.append((i, keys_b, vals_b))
            if inserts:
                spliced = True
                name_pieces, val_pieces = [], []
                len_pieces_n, len_pieces_v = [], []
                for i, keys_b, vals_b in inserts:
                    at = int(np.searchsorted(seg_row, i, side="left"))
                    name_pieces.append(folded[cut_bytes_n:int(nb_off[at])])
                    val_pieces.append(vb_np[cut_bytes_v:int(vb_off[at])])
                    len_pieces_n.append(name_lens[cut_seg:at])
                    len_pieces_v.append(val_lens[cut_seg:at])
                    if keys_b:
                        name_pieces.append(
                            np.frombuffer(b"".join(keys_b), dtype=np.uint8)
                        )
                        val_pieces.append(
                            np.frombuffer(b"".join(vals_b), dtype=np.uint8)
                        )
                        len_pieces_n.append(
                            np.array([len(k) for k in keys_b], dtype=np.int64)
                        )
                        len_pieces_v.append(
                            np.array([len(v) for v in vals_b], dtype=np.int64)
                        )
                    cut_bytes_n, cut_bytes_v, cut_seg = (
                        int(nb_off[at]), int(vb_off[at]), at
                    )
                name_pieces.append(folded[cut_bytes_n:])
                val_pieces.append(vb_np[cut_bytes_v:])
                len_pieces_n.append(name_lens[cut_seg:])
                len_pieces_v.append(val_lens[cut_seg:])
                folded = np.concatenate(name_pieces)
                vb_np = np.concatenate(val_pieces)
                name_lens = np.concatenate(len_pieces_n)
                val_lens = np.concatenate(len_pieces_v)
                n_seg = len(name_lens)

        if spliced:  # the splice changed the lens: recompute offsets
            non32 = np.zeros(n_seg + 1, dtype=np.int64)
            np.cumsum(name_lens, out=non32[1:])
            nov32 = np.zeros(n_seg + 1, dtype=np.int64)
            np.cumsum(val_lens, out=nov32[1:])
        else:
            non32, nov32 = nb_off, vb_off
        if int(non32[-1]) > np.iinfo(np.int32).max or int(
            nov32[-1]
        ) > np.iinfo(np.int32).max:
            return None
        offsets64 = np.zeros(B + 1, dtype=np.int64)
        np.cumsum(counts, out=offsets64[1:])
        offsets = offsets64.astype(np.int32)
        mask = np.concatenate([~covered, [False]])
        try:
            keys = pa.StringArray.from_buffers(
                n_seg,
                pa.py_buffer(non32.astype(np.int32)),
                pa.py_buffer(np.ascontiguousarray(folded)),
            )
            items = pa.StringArray.from_buffers(
                n_seg,
                pa.py_buffer(nov32.astype(np.int32)),
                pa.py_buffer(np.ascontiguousarray(vb_np)),
            )
            arr = pa.MapArray.from_arrays(
                pa.array(offsets, type=pa.int32(), mask=mask), keys, items
            )
            arr.validate(full=True)  # UTF-8 check happens here
        except (pa.lib.ArrowException, TypeError, ValueError):
            # Anything the flat construction cannot express exactly falls
            # back to the dict path (which is always correct).
            return None
        return arr


class _BlobLines:
    """Lazy per-line view of a newline-delimited blob: the batch ingest
    path never builds a Python line list — rows materialize as bytes only
    when indexed (oracle-rescued rows, debugging).  Framing semantics are
    exactly :func:`logparser_tpu.native.encode_blob`'s: a final empty
    segment after a trailing newline is dropped and one trailing ``\\r``
    per line is stripped.

    ``blob`` may be bytes or any 1-D uint8 buffer (the feeder ring hands
    a shared-memory slot VIEW straight through — the payload is never
    copied unless a row is actually rescued)."""

    __slots__ = ("_blob", "_bytes", "_n", "_starts", "_ends")

    def __init__(self, blob):
        self._bytes = isinstance(blob, (bytes, bytearray))
        if not self._bytes:
            blob = np.frombuffer(blob, dtype=np.uint8)
        self._blob = blob
        # Cheap length only (one C-level count); the per-line index
        # arrays build lazily on first access — almost no row ever
        # materializes (only oracle-rescued ones).
        if self._bytes:
            from ..feeder.worker import _count_lines

            # The single home of the trailing-newline counting rule
            # (the ndarray branch below is its vectorized twin).
            self._n = _count_lines(blob)
        else:
            if not len(blob):
                self._n = 0
            else:
                nl = int(np.count_nonzero(blob == 0x0A))
                self._n = nl if blob[-1] == 0x0A else nl + 1
        self._starts = None
        self._ends = None

    def _index(self):
        if self._starts is None:
            blob = self._blob
            arr = (np.frombuffer(blob, dtype=np.uint8)
                   if self._bytes else blob)
            nl = np.flatnonzero(arr == 0x0A)
            starts = np.concatenate([[0], nl + 1]).astype(np.int64)
            ends = np.concatenate([nl, [len(blob)]]).astype(np.int64)
            if len(arr) and arr[-1] == 0x0A:
                starts = starts[:-1]
                ends = ends[:-1]
            cr = (arr[np.maximum(ends - 1, 0)] == 0x0D) & (ends > starts)
            self._starts = starts
            self._ends = ends - cr
        return self._starts, self._ends

    def __len__(self) -> int:
        return self._n

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [self[j] for j in range(*i.indices(len(self)))]
        starts, ends = self._index()
        raw = self._blob[starts[i]: ends[i]]
        return raw if self._bytes else raw.tobytes()

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]


class _SliceLines:
    """Row-window view of a parent lines sequence (list or
    :class:`_BlobLines`): the lines handle a sliced :class:`BatchResult`
    carries.  Rows materialize lazily through the parent — a blob-backed
    parent still only ever materializes the rows somebody indexes."""

    __slots__ = ("_parent", "_start", "_n")

    def __init__(self, parent, start: int, n: int):
        self._parent = parent
        self._start = start
        self._n = n

    def __len__(self) -> int:
        return self._n

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [self[j] for j in range(*i.indices(self._n))]
        if i < 0:
            i += self._n
        if not 0 <= i < self._n:
            raise IndexError(i)
        return self._parent[self._start + i]

    def __iter__(self):
        for i in range(self._n):
            yield self[i]


def _release_stream_item(item) -> None:
    """Give a stream item's ring slot back (zero-copy feeder batches);
    plain batches and line lists have no lease (no-op / absent)."""
    release = getattr(item, "release", None)
    if release is not None:
        release()


def _raw_line_bytes(line) -> bytes:
    """One line as ingested bytes — :meth:`BatchResult.raw_line`'s
    conversion for paths that carry no result object (the aggregate
    reject ledger)."""
    if isinstance(line, bytes):
        return line
    if isinstance(line, (bytearray, memoryview)):
        return bytes(line)
    return str(line).encode("utf-8", errors="surrogateescape")


class BatchResult:
    """Columnar parse result over one batch."""

    def __init__(self, lines, buf, lengths, valid, columns, overrides, good, bad,
                 format_index=None, oracle_rows=0, packed=None,
                 device_views=None, dirty_rows=None, assembly_pool=None):
        # Shared delivery-path worker pool (tpu/hostpool.py): to_arrow's
        # per-column assembly and the native memcpy fan-outs read their
        # parallelism from it.  None = serial (the pre-pool behavior).
        self.assembly_pool = assembly_pool
        # Device-emitted Arrow view rows: `packed` holds ONLY the trailing
        # view block (4 int32 rows per span field, copied out of the
        # device fetch); device_views maps field_id -> row index of its
        # merged span word inside that block (+1..+3 = LE-packed first-12
        # bytes); the Arrow bridge interleaves them natively.  dirty_rows
        # marks rows (overflow-truncated lines) whose device views must
        # be zeroed/patched on host.
        self.packed = packed
        self.device_views = device_views or {}
        self.dirty_view_rows = (
            dirty_rows if dirty_rows is not None
            else np.empty(0, dtype=np.int64)
        )
        # Lines the host oracle had to visit (device-invalid lines plus
        # lines whose winning format left requested fields device-unresolved)
        # — the number bench.py reports as oracle_fraction.
        self.oracle_rows = oracle_rows
        self._lines = lines
        self.buf = buf                  # np [B, L] uint8
        self.lengths = lengths
        self.valid = valid              # np [B] bool: overall line validity
        self._columns = columns         # field_id -> dict of arrays (per kind)
        self._overrides = overrides     # field_id -> {row: python value}
        self.lines_read = len(lines)
        self.good_lines = good
        self.bad_lines = bad
        # Rescue composition (filled by the materializer): routed-line
        # counts by reject reason and the wall seconds rescue added.
        self.rescue_reasons: Dict[str, int] = {}
        self.rescue_wall_s: float = 0.0
        # Lines the device claimed THROUGH the escape-parity mask (their
        # quoted-field split skipped a backslash-escaped separator
        # occurrence): the round-18 class that used to route to the host
        # rescue.  Filled by the materializer from the winning unit's
        # ESC_QUOTE_BIT; mirrors device_escaped_quote_lines_total.
        self.escaped_quote_rows: int = 0
        # Per-row reject ledger (filled by the materializer): row ->
        # stable reason ("implausible" | "oracle_reject" |
        # "oracle_error") for every row whose ``valid`` ended False —
        # the jobs reject channel reads it to build per-line error
        # tables instead of silently dropping bad lines.
        self.reject_reasons: Dict[int, str] = {}
        # Sorted row ids the host oracle visited (set by the
        # materializer; slices rebase it) — lets :meth:`slice` report the
        # EXACT per-window oracle_rows a solo parse would have counted.
        self.oracle_row_ids: Optional[np.ndarray] = None
        # Per-line index of the registered format that matched on device
        # (-1 = decided by the host oracle / no device match).  The columnar
        # analogue of the reference's "Switched to LogFormat" signal
        # (HttpdLogFormatDissector.java:162-165).
        self.format_index = (
            format_index
            if format_index is not None
            else np.full(self.lines_read, -1, dtype=np.int64)
        )
        self._ascii_only: Optional[bool] = None

    @property
    def ascii_only(self) -> bool:
        """True when every byte of the batch buffer is < 0x80 — then any
        gathered span is trivially valid UTF-8 and the Arrow bridge can
        skip its per-column validate pass.  One SIMD max over the buffer,
        computed lazily and cached for the batch."""
        if self._ascii_only is None:
            B = self.lines_read
            self._ascii_only = bool(
                B == 0 or int(self.buf[:B].max(initial=0)) < 0x80
            )
        return self._ascii_only

    def raw_line(self, i: int) -> bytes:
        """The raw bytes of line ``i`` exactly as ingested (lazy under
        blob ingest — only requested rows materialize).  String inputs
        encode UTF-8; the jobs reject channel stores these verbatim."""
        line = self._lines[i]
        if isinstance(line, bytes):
            return line
        if isinstance(line, (bytearray, memoryview)):
            return bytes(line)
        return str(line).encode("utf-8", errors="surrogateescape")

    def field_ids(self) -> List[str]:
        return list(self._columns.keys())

    def column(self, field_id: str) -> Dict[str, np.ndarray]:
        """Raw column arrays: spans have starts/ends; numerics have values +
        null mask."""
        return self._columns[cleanup_field_value(field_id)]

    def to_pylist(self, field_id: str) -> List[Any]:
        """Materialize one column as Python values (strings/ints/None)."""
        field_id = cleanup_field_value(field_id)
        col = self._columns[field_id]
        overrides = self._overrides.get(field_id, {})
        out: List[Any] = []
        kind = col["kind"]
        for i in range(self.lines_read):
            if i in overrides:
                out.append(overrides[i])
                continue
            if not self.valid[i] or not col["ok"][i]:
                out.append(None)
                continue
            if kind == "numeric":
                if col["null"][i]:
                    # Per-line CLF-zero semantics: the format that won the
                    # line decides whether '-' means 0 or null.
                    out.append(0 if col["null_zero"][i] else None)
                else:
                    out.append(int(col["values"][i]))
            elif kind == "obj":
                v = col["values"][i]
                out.append(v.item() if isinstance(v, np.generic) else v)
            else:
                if col["null"][i]:
                    # Device-computed null: CLF '-' token captures and
                    # undelivered URI parts.
                    out.append(None)
                    continue
                start, end = int(col["starts"][i]), int(col["ends"][i])
                raw = bytes(self.buf[i, start:end])
                if col.get("amp") is not None and col["amp"][i] and raw[:1] == b"?":
                    raw = b"&" + raw[1:]  # the ?& query normalization
                value = raw.decode("utf-8", errors="replace")
                if col.get("fix") is not None and col["fix"][i]:
                    value = _fix_uri_part(value, col["fix_mode"])
                out.append(value)
        return out

    def to_dict(self) -> Dict[str, List[Any]]:
        return {fid: self.to_pylist(fid) for fid in self._columns}

    def span_bytes(self, field_id: str, include_fix: bool = False,
                   threads: int = 0):
        """Flat-bytes view of a device span column for non-Arrow consumers:
        (data uint8, offsets int64, valid bool) — row r's raw value is
        ``data[offsets[r]:offsets[r+1]]`` when valid[r].  Uses the native
        threaded gather (numpy fallback inside).  Returns None when the
        column has host overrides or repair (`fix`) rows — unless
        ``include_fix`` (the Arrow bridge gathers repair rows raw and
        splices the repaired values afterwards); override columns always
        need the per-row path (:meth:`to_pylist`).  ``threads`` caps the
        native gather's fan-out (pooled per-column callers pass 1)."""
        from ..native import gather_spans

        inputs = self._span_flat_inputs(field_id, include_fix=include_fix)
        if inputs is None:
            return None
        starts, lens, valid = inputs
        B = self.lines_read
        data, offsets = gather_spans(self.buf[:B], starts, lens,
                                     threads=threads)
        self._amp_normalize(field_id, data, offsets, lens, valid)
        return data, offsets, valid

    def _span_flat_inputs(self, field_id: str, include_fix: bool = False):
        """(starts, lens, valid) for a flat-gather-eligible span column;
        None when the column needs the per-row path (overrides, repair
        rows unless ``include_fix`` — the Arrow bridge gathers those raw
        and splices the repaired values in afterwards)."""
        field_id = cleanup_field_value(field_id)
        col = self._columns[field_id]
        if col["kind"] != "span" or self._overrides.get(field_id):
            return None
        B = self.lines_read
        fix = col.get("fix")
        if not include_fix and fix is not None and fix[:B].any():
            return None
        valid = (
            np.asarray(self.valid[:B]).astype(bool)
            & np.asarray(col["ok"][:B]).astype(bool)
            & ~np.asarray(col["null"][:B]).astype(bool)
        )
        starts = np.asarray(col["starts"][:B], dtype=np.int32)
        lens = np.where(
            valid, np.asarray(col["ends"][:B]) - starts, 0
        ).astype(np.int64)
        return starts, lens, valid

    def _amp_normalize(self, field_id, data, offsets, lens, valid) -> None:
        """In-place ?& query normalization on gathered bytes (offsets are
        column-local, length B+1)."""
        col = self._columns[cleanup_field_value(field_id)]
        amp = col.get("amp")
        B = self.lines_read
        if amp is not None and amp[:B].any():
            swap = valid & np.asarray(amp[:B]).astype(bool) & (lens > 0)
            at = offsets[:-1][swap]
            at = at[data[at] == np.uint8(ord("?"))]
            data[at] = np.uint8(ord("&"))

    def span_bytes_many(self, field_ids, include_fix: bool = False,
                        threads: int = 0):
        """Gather several span columns in ONE native call.

        Returns {field_id: (data_view, offsets, valid)} covering the
        subset of ``field_ids`` eligible for the flat path (same
        eligibility as :meth:`span_bytes`, except repair rows when
        ``include_fix``); ineligible columns are simply absent.  The
        threaded memcpy fan-out is paid once per batch instead of once
        per column — the difference between ~3M and ~7M rows/s through
        the Arrow bridge at 16k-row batches.  ``threads`` defaults to
        the result's assembly pool budget when one is attached."""
        from ..native import gather_spans_multi

        if not threads and self.assembly_pool is not None:
            threads = self.assembly_pool.native_threads
        B = self.lines_read
        elig = []
        for fid in field_ids:
            inputs = self._span_flat_inputs(fid, include_fix=include_fix)
            if inputs is not None:
                elig.append((cleanup_field_value(fid), inputs))
        if not elig:
            return {}
        starts = np.stack([e[1][0] for e in elig])
        lens = np.stack([e[1][1] for e in elig])
        data, goff = gather_spans_multi(self.buf[:B], starts, lens,
                                        threads=threads)
        out = {}
        for k, (fid, (_s, lens_k, valid_k)) in enumerate(elig):
            base = goff[k * B]
            offsets = goff[k * B : k * B + B + 1] - base
            col_data = data[base : int(goff[(k + 1) * B])]
            self._amp_normalize(fid, col_data, offsets, lens_k, valid_k)
            out[fid] = (col_data, offsets, valid_k)
        return out

    def to_arrow(self, include_validity: bool = True, strings: str = "view"):
        """Materialize as a pyarrow.Table (see tpu/arrow_bridge.py).

        ``strings="view"`` (default): span columns are Arrow string_view
        arrays referencing this batch's byte buffer zero-copy (the table
        keeps the buffer alive; no value bytes are copied for clean
        rows).  ``strings="copy"``: classic contiguous StringArrays."""
        from .arrow_bridge import batch_to_arrow

        return batch_to_arrow(
            self, include_validity=include_validity, strings=strings
        )

    # Column-dict entries that are NOT per-row arrays (shared metadata /
    # vocab tables) and therefore must never be row-sliced.  Explicit
    # allowlist: a geo vocab array's length could coincide with the batch
    # size, so "slice every ndarray of length B" would silently corrupt.
    _NON_ROW_KEYS = frozenset(
        ("kind", "fix_mode", "mixed_fill", "typed_kind", "dict_values")
    )

    def slice(self, start: int, stop: int) -> "BatchResult":
        """Row-window VIEW ``[start, stop)`` of this result, without
        re-materializing anything: column arrays and the byte buffer are
        numpy views, override dicts rebase to window-local row ids, and
        wildcard CSR chunks re-pack to the window's segments.

        Delivery parity contract (locked by tests/test_tpu_batch.py and
        the service's cross-session suite): every delivery surface of the
        slice — ``to_arrow``/``to_pylist``/``span_bytes``/validity/
        ``oracle_rows``/``bad_lines`` — is byte-identical to parsing the
        window's lines ALONE, because every per-line verdict (automaton
        winner, oracle routing, overrides) is computed independently per
        row.  This is what lets the serving tier's continuous batching
        coalesce many sessions into one device batch and scatter each
        session its exact solo answer (docs/SERVICE.md).

        Two deliberate non-goals: device-emitted Arrow view rows are
        DROPPED (slices deliver copy-mode Arrow — the coalesced wire
        path never ships views; ``strings="view"`` still works through
        the host gather), and the parent's batch-level rescue
        composition stats (``rescue_reasons``/``rescue_wall_s``/
        ``escaped_quote_rows``) stay on the parent — they describe the
        shared batch, not any window."""
        B = self.lines_read
        start = max(0, min(int(start), B))
        stop = max(start, min(int(stop), B))
        n = stop - start
        columns: Dict[str, Dict[str, Any]] = {}
        for fid, col in self._columns.items():
            columns[fid] = {
                k: (v if k in self._NON_ROW_KEYS
                    or not isinstance(v, np.ndarray) else v[start:stop])
                for k, v in col.items()
            }
        overrides: Dict[str, Any] = {}
        for fid, ov in self._overrides.items():
            if isinstance(ov, _LazyWildcard):
                overrides[fid] = ov.sliced(start, stop)
            else:
                overrides[fid] = {
                    i - start: v for i, v in ov.items() if start <= i < stop
                }
        valid = self.valid[start:stop]
        bad = int(np.count_nonzero(~np.asarray(valid, dtype=bool)))
        out = BatchResult(
            _SliceLines(self._lines, start, n),
            self.buf[start:stop],
            self.lengths[start:stop],
            valid,
            columns,
            overrides,
            n - bad,
            bad,
            format_index=self.format_index[start:stop],
            assembly_pool=self.assembly_pool,
        )
        ids = self.oracle_row_ids
        if ids is not None:
            lo = int(np.searchsorted(ids, start, side="left"))
            hi = int(np.searchsorted(ids, stop, side="left"))
            out.oracle_row_ids = ids[lo:hi] - start
            out.oracle_rows = hi - lo
        out.reject_reasons = {
            i - start: r for i, r in self.reject_reasons.items()
            if start <= i < stop
        }
        return out


def _bucket_batch(b: int, minimum: int = 64) -> int:
    size = minimum
    while size < b:
        size *= 2
    return size


class TpuBatchParser:
    """Compiles one LogFormat + requested fields into a fused device function
    and a host-fallback parser."""

    def __init__(
        self,
        log_format: str,
        fields: Sequence[str],
        timestamp_format: Optional[str] = None,
        type_remappings: Optional[Dict[str, Any]] = None,
        extra_dissectors: Optional[Sequence[Any]] = None,
        locale: Optional[str] = None,
        view_fields: Optional[Sequence[str]] = None,
        assembly_workers: Optional[int] = None,
        data_parallel: Optional[int] = None,
        device_bytes_budget: Optional[int] = None,
        execute_deadline_s: Optional[float] = None,
        fault_policy: Optional[Any] = None,
        device_chaos: Any = None,
    ):
        self.log_format = log_format
        # Device-side data parallelism (docs/JOBS.md "Pod jobs"): lay
        # the fused parse over up to ``data_parallel`` local devices via
        # a jax.sharding Mesh ('data' axis; NamedSharding in/out) — the
        # dryrun_multichip idiom on the product hot path.  None/<=1 (or
        # a single-device host) keeps the unsharded executor.  The
        # effective width is the largest power of two that fits
        # (parallel.mesh.dp_device_count), so the power-of-two batch
        # buckets always divide evenly across devices.
        self.data_parallel = data_parallel
        self._mesh = self._build_mesh(data_parallel)
        configure_jax_cache()
        self._note_devices()
        self.requested = [cleanup_field_value(f) for f in fields]
        # Demand-driven view emission: the device emits Arrow view rows
        # only for span fields the consumer will actually deliver as
        # string_view columns.  None = all requested span fields (the
        # to_arrow default delivers every one); a sequence prunes to that
        # subset; an empty sequence disables view emission entirely
        # (equivalent to parse_batch(..., emit_views=False) per call).
        self._view_demand = (
            None if view_fields is None
            else frozenset(cleanup_field_value(f) for f in view_fields)
        )
        # One parallelism knob for the whole delivery path: Arrow column
        # assembly fan-out + the native memcpy thread budget.
        self.assembly_workers = assembly_workers
        self._assembly_pool = None

        # Host oracle parser (also the metadata source).  Pinned STATELESS:
        # the batch path guarantees deterministic per-line registration
        # priority across formats, so its fallback oracle must not carry
        # the reference's active-format state between lines (see
        # HttpdLogFormatDissector.stateless).
        self.oracle = HttpdLoglineParser(
            _CollectingRecord, log_format, timestamp_format, locale=locale
        )
        self.oracle.all_dissectors[0].stateless = True
        self.oracle.apply_config(type_remappings, extra_dissectors)
        self.oracle.add_parse_target("set_value", list(self.requested))
        self.oracle.assemble_dissectors()
        # Type remappings by complete name, used by the device plan chase.
        self._remaps = {
            k: tuple(sorted(v))
            for k, v in self.oracle.type_remappings.items()
        }

        # Consumer registry for device plan resolution: every non-root
        # dissector, keyed by input type, deduped per class in registration
        # order (mirroring the engine's one-instance-per-class-per-node rule
        # in Parser._find_useful_dissectors).  _resolve chases token outputs
        # through this registry, so EVERY producer of a requested field is
        # counted — fields with more than one producer in the oracle graph
        # (e.g. $time_local + $msec both feeding TIME.EPOCH:...epoch) must
        # resolve to "host": the oracle delivers every value in graph order
        # and the record keeps the last, which a single device route would
        # silently break.
        fmt_root = self.oracle.all_dissectors[0]
        self._consumers: Dict[str, List[Any]] = {}
        seen_consumer = set()
        for d in self.oracle.all_dissectors:
            if d is fmt_root:
                continue
            # No try/except around get_possible_output(): a raising
            # dissector would silently drop producer edges, letting a
            # device plan claim a multi-producer field — fail loudly.
            d.get_possible_output()
            key = (d.get_input_type(), type(d))
            if key in seen_consumer:
                continue
            seen_consumer.add(key)
            self._consumers.setdefault(d.get_input_type(), []).append(d)

        # Device programs: one FormatUnit per registered format, in
        # registration order (SURVEY §7.7 "run k format automata, pick the
        # per-line winner").  An UNCOMPILABLE format does not truncate the
        # list: it contributes a plausibility-only probe unit
        # (separator-order automaton, valid bit never set) at its ordinal,
        # so (a) later compilable formats still run on device, and (b) a
        # line is never claimed by format k while the uncompilable format
        # j < k is still plausible — those lines go to the oracle, which
        # applies the reference's registration-priority semantics
        # (HttpdLogFormatDissector.java:174-204) with the real regexes.
        fmt = self.oracle.all_dissectors[0]
        dissectors = getattr(fmt, "dissectors", [fmt])
        from .pipeline import CSR_SLOTS
        from .program import compile_plausibility_program

        self.csr_slots = CSR_SLOTS
        self.units: List[FormatUnit] = []
        for d in dissectors:
            try:
                prog = compile_device_program(d)
            except UnsupportedFormatError:
                self.units.append(FormatUnit(
                    compile_plausibility_program(d), [],
                    PackedLayout.for_plans([], self.csr_slots),
                    plausibility_only=True,
                ))
                continue
            plans = [self._resolve(prog, fid) for fid in self.requested]
            self.units.append(FormatUnit(
                prog, plans, PackedLayout.for_plans(plans, self.csr_slots)
            ))
        assign_row_offsets(self.units)
        # The definitely-bad filter (implausible for every format -> no
        # oracle visit) is sound because EVERY registered format has a
        # device automaton — full or plausibility-only probe.  Always True
        # for freshly-built parsers; kept as state (not an invariant)
        # because LOADED artifacts from pre-probe builds carry truncated
        # unit lists with the flag False (__setstate__).
        self._device_covers_all_formats = len(self.units) == len(dissectors)

        # Merged per-field plan: the first non-host kind across formats (used
        # for numeric coercion of oracle-delivered values).
        self.plan_by_id = {
            fid: self._merged_plan(fid) for fid in self.requested
        }
        # Fields that need the oracle for EVERY line (host under all formats).
        self.host_fields = [
            fid for fid, p in self.plan_by_id.items() if p.kind == "host"
        ]
        # Casts for EVERY requested field: any field can take the host path
        # on some line (host-only fields always; device fields when the
        # line's winning format resolves them as host — e.g. multi-producer
        # fields like `%B ... %b` — or when the line goes to the oracle).
        self._host_casts = {
            fid: self.oracle.get_casts(fid) for fid in self.requested
        }
        # Setter-cast dispatch flags (LONG, DOUBLE) per field: the single
        # source for both _coerce_casts and the oracle delivery plan.
        self._cast_flags = {
            f: (Cast.LONG in c, Cast.DOUBLE in c)
            for f, c in self._host_casts.items()
            if c is not None
        }
        self._overflow_delivery = self._build_overflow_delivery()
        # Per-unit: fields the oracle must supply for lines won by that unit
        # (host under it, or a kind-group mismatch with the merged column).
        self._unit_oracle_fields: List[List[str]] = [
            [
                fid
                for fid in self.requested
                if not self._unit_decodable(u, fid)
            ]
            for u in self.units
        ]
        # Device fault layer (docs/FAULTS.md): pre-allocation byte
        # budget, OOM bisect + bucket clamp, execution deadline on an
        # abandonable worker, per-parser circuit breaker demoting a
        # repeatedly-faulting kernel to the host oracle, and the chaos
        # injection hooks that drill all of it.
        self._init_fault_layer(
            device_bytes_budget, execute_deadline_s, fault_policy,
            device_chaos,
        )
        self._jitted = self._build_jitted()
        self._jitted_views = None  # lazily built by device_views_fn()
        # Aggregate-pushdown executors (docs/ANALYTICS.md): canonical
        # spec key -> (csr_slots at build, jitted reduction, op plans).
        # _agg_disabled holds spec keys whose reduction failed to
        # COMPILE — permanently demoted to the exact row-path fallback.
        self._agg_fns: Dict[str, tuple] = {}
        self._agg_disabled: set = set()

    def _init_fault_layer(self, budget, deadline, policy, chaos) -> None:
        """Device-tier fault state — shared by ``__init__`` and
        ``__setstate__``: artifacts never carry runtime fault state
        (breakers, clamps, chaos) — it re-arms on the loading host from
        the pickled knobs + the env fallbacks."""
        from .device_faults import (
            DeviceBreaker,
            DeviceFaultPolicy,
            resolve_budget,
            resolve_deadline,
        )

        self.fault_policy = policy or DeviceFaultPolicy()
        self.device_bytes_budget = resolve_budget(budget)
        self.execute_deadline_s = resolve_deadline(deadline)
        self._breaker = DeviceBreaker(
            self.fault_policy.breaker_threshold,
            self.fault_policy.breaker_cooloff_s,
        )
        self._oom_clamp: Optional[int] = None
        self._oom_events = 0
        self._device_chaos = None
        self.arm_device_chaos(chaos if chaos is not None else "env")

    def arm_device_chaos(self, chaos: Any) -> None:
        """Arm (or disarm with ``None``) device-tier fault injection:
        accepts a ``tools.chaos.DeviceChaos``, a ``ChaosSpec``, the
        grammar string, or ``"env"`` (the ``LOGPARSER_TPU_CHAOS``
        channel — also the construction-time default, so CLI drills arm
        the whole stack with one env var).  A spec carrying no device
        faults leaves the hot path untouched (no hook object at all)."""
        if chaos is None:
            self._device_chaos = None
            return
        from ..tools.chaos import ChaosSpec, DeviceChaos

        if isinstance(chaos, DeviceChaos):
            self._device_chaos = chaos or None
            return
        if chaos == "env":
            spec = ChaosSpec.from_env()
        elif isinstance(chaos, str):
            spec = ChaosSpec.parse(chaos)
        else:
            spec = chaos
        dc = DeviceChaos(spec) if spec is not None else None
        self._device_chaos = dc or None

    def device_fault_stats(self) -> Dict[str, Any]:
        """Fault-layer introspection for drills/ops: breaker state, the
        standing OOM clamp, and whether chaos is armed."""
        return {
            **self._breaker.stats(),
            "oom_clamp": self._oom_clamp,
            "oom_events": self._oom_events,
            "chaos_armed": self._device_chaos is not None,
        }

    @staticmethod
    def _build_mesh(data_parallel: Optional[int]):
        """The 'data'-axis mesh a data_parallel request resolves to on
        THIS host, or None for the unsharded executor (no request, one
        device, or a 1-wide resolution)."""
        if not data_parallel or int(data_parallel) <= 1:
            return None
        from ..observability import metrics
        from ..parallel.mesh import dp_device_count, make_mesh

        n = dp_device_count(int(data_parallel))
        if n <= 1:
            return None
        metrics().gauge_set("device_mesh_devices", n)
        return make_mesh(n_data=n)

    def _note_devices(self) -> None:
        """Publish the device(s) this parser runs on as the
        ``device_info`` gauge, with the chip this process was pinned to
        (``TPU_VISIBLE_CHIPS``, logparser_tpu/chips.py): a fleet's
        sidecars can then show that each one worked on its own chip."""
        from ..observability import metrics

        devs = (list(self._mesh.devices.flat) if self._mesh is not None
                else jax.devices()[:1])
        metrics().gauge_set("device_info", 1, labels={
            "platform": devs[0].platform,
            "kind": devs[0].device_kind,
            "ids": ",".join(str(d.id) for d in devs),
            "coords": " ".join(
                "x".join(map(str, getattr(d, "coords", ()))) for d in devs),
            "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS", "all"),
        })

    @property
    def mesh_devices(self) -> int:
        """How many devices the executor is laid out over (1 = no mesh)."""
        return self._mesh.devices.size if self._mesh is not None else 1

    def _bucket(self, b: int) -> int:
        """The padded batch size of a ``b``-line batch: the power-of-two
        bucket, floored at the mesh width so a sharded batch axis always
        divides evenly across devices."""
        size = _bucket_batch(b)
        if self._mesh is not None:
            size = max(size, self._mesh.devices.size)
        return size

    def _build_jitted(self):
        # No point running the device programs when every field is host-only.
        any_device_field = any(
            p.kind != "host" for u in self.units for p in u.plans
        )
        if self.units and any_device_field:
            return self._aot_wrap(
                build_units_jnp_fn(self.units, mesh=self._mesh), "plain"
            )
        return None

    def _aot_wrap(self, jit_fn, tag: str, specs=None):
        """Wrap a fresh jit executor in the AOT compile-cache layer (see
        tpu/compile_cache.py + docs/COMPILE.md).  Mesh-sharded executors
        stay in-memory only: their serialized form binds this process's
        device set."""
        from .compile_cache import AotExecutor

        return AotExecutor(
            jit_fn,
            self.executor_fingerprint(tag, specs),
            serializable=self._mesh is None,
            devices=(None if self._mesh is None
                     else list(self._mesh.devices.flat)),
        )

    def executor_fingerprint(self, tag: str, specs=None) -> str:
        """Content hash of everything that shapes the compiled executor:
        the device programs + field plans (which fold in format strings,
        requested fields, remappings, extra dissectors, geo tables), the
        CSR slot count (adaptive growth = new fingerprint), the mesh
        width, the executor variant (plain/views + its specs), and the
        pipeline code version.  Any drift is a cache MISS — a stale
        kernel can never load."""
        from .compile_cache import code_fingerprint, stable_hash

        return stable_hash((
            code_fingerprint(),
            tag,
            list(specs) if specs else [],
            self.csr_slots,
            self.mesh_devices,
            [
                (u.plausibility_only, u.row_offset, u.program, u.plans)
                for u in self.units
            ],
        ))

    def assembly_pool(self):
        """The shared delivery-path worker pool (lazily built; see
        tpu/hostpool.py).  BatchResults carry a reference so to_arrow
        inherits the knob wherever the result travels."""
        if self._assembly_pool is None:
            from .hostpool import AssemblyPool

            self._assembly_pool = AssemblyPool(self.assembly_workers)
        return self._assembly_pool

    def _view_specs(self):
        """Static spec for device-side Arrow view emission: span-group
        fields + the units the host would decode each from (the
        ``_unit_decodable`` rule — other units' lines deliver via oracle
        overrides, whose views the host patches anyway).  Pruned to the
        demand set when the parser was built with ``view_fields``."""
        specs = []
        for fid in self.requested:
            if fid.endswith(".*"):
                continue
            if self._view_demand is not None and fid not in self._view_demand:
                continue
            if self._plan_group(self.plan_by_id[fid]) != "span":
                continue
            unit_idx = [
                ui for ui, u in enumerate(self.units)
                if not u.plausibility_only and self._unit_decodable(u, fid)
            ]
            if unit_idx:
                specs.append((fid, tuple(unit_idx)))
        return specs

    def device_views_fn(self):
        """The executor variant that also emits Arrow view rows (4 int32
        rows per span field, appended after the unit rows) — the
        parse_batch product path.  Falls back to the plain executor when
        no span field is device-decodable."""
        if self._jitted is None:
            return None
        if self._jitted_views is None:
            specs = self._view_specs()
            if not specs:
                self._jitted_views = self._jitted
                self._views_fields = []
            else:
                self._jitted_views = self._aot_wrap(
                    build_units_jnp_fn(self.units, specs, mesh=self._mesh),
                    "views", specs,
                )
                self._views_fields = [fid for fid, _ in specs]
        return self._jitted_views

    def device_fn(self):
        """The fused plain-XLA device executor, or None when every field
        is host-only (shape-polymorphic jit; each [B, L] bucket compiles
        once).  XLA is the product path: a hand-written Pallas kernel of
        this pipeline measured ~4.5x slower on v5e and Mosaic cannot
        lower the chained stages — see the ADR in COMPONENTS.md."""
        return self._jitted

    def prewarm(
        self,
        batch_sizes: Optional[Sequence[int]] = None,
        max_line_len: int = 256,
        emit_views: Optional[bool] = None,
    ) -> Dict[str, str]:
        """Make the shape-bucket ladder executable OFF the request path:
        for each batch size, resolve the (padded-B, L-bucket) executable —
        in-memory map, then the persistent compile cache
        (``compile_cache.cache_root()``), then an explicit lower+compile
        written back to the cache.  ``max_line_len`` picks the line-length
        bucket to warm (the same ``runtime.bucket_length`` the encoder
        applies).  Returns ``{"BxL": "memory"|"disk"|"compiled"}`` per
        warmed shape; a no-device-field parser returns ``{}``.

        Sidecar boot and front-tier respawn warmup call this from a
        background thread (docs/SERVICE.md): a cache-warm fleet boots
        with zero compiles on the serving path."""
        from .compile_cache import DEFAULT_BUCKET_LADDER
        from .runtime import bucket_length

        executors = []
        if emit_views is None or emit_views:
            fn = self.device_views_fn()
            if fn is not None:
                executors.append(fn)
        if emit_views is None or not emit_views:
            fn = self.device_fn()
            if fn is not None and fn not in executors:
                executors.append(fn)
        if not executors:
            return {}
        line_len = bucket_length(max(1, max_line_len))
        out: Dict[str, str] = {}
        for b in batch_sizes or DEFAULT_BUCKET_LADDER:
            padded = self._bucket(int(b))
            for fn in executors:
                src = fn.warm(padded, line_len)
                shape = f"{padded}x{line_len}"
                # Report the coldest source across the executor variants.
                rank = {"memory": 0, "disk": 1, "compiled": 2}
                if rank[src] >= rank.get(out.get(shape, "memory"), 0):
                    out[shape] = src
        return out

    def _grow_csr_slots(self) -> bool:
        """Adaptive CSR: double the wildcard segment-slot count (bounded by
        CSR_SLOTS_MAX) and rebuild the packed layouts + executor.  Called
        when a batch flags CSR overflow, so query-heavy corpora cost a few
        recompiles instead of routing every long line to the per-line
        oracle.  Returns False at the cap (those lines stay oracle-bound)."""
        from .pipeline import CSR_SLOTS_MAX

        if self.csr_slots >= CSR_SLOTS_MAX:
            return False
        self.csr_slots *= 2
        for u in self.units:
            u.layout = PackedLayout.for_plans(u.plans, self.csr_slots)
        assign_row_offsets(self.units)
        self._jitted = self._build_jitted()
        self._jitted_views = None  # row offsets moved; rebuild lazily
        return True

    # ------------------------------------------------------------------

    def _merged_plan(self, field_id: str) -> _FieldPlan:
        for u in self.units:
            p = u.plan_for(field_id)
            if p.kind != "host":
                return p
        return _FieldPlan(field_id, "host")

    @staticmethod
    def _plan_group(plan: _FieldPlan) -> str:
        """Merge group: plans in the same group share column arrays."""
        if plan.kind == "span":
            return "span"
        if plan.kind in ("long", "secmillis"):
            return "numeric"
        if plan.kind == "ts":
            return "numeric" if timefields.is_numeric_output(plan.comp) else "obj"
        if plan.kind == "muid":
            return "obj" if plan.comp == "ip" else "numeric"
        if plan.kind == "ulist":
            return "span"
        if plan.kind == "qscsr":
            return "wild"
        if plan.kind == "geo":
            return "obj"
        return "host"

    def _unit_decodable(self, unit: FormatUnit, field_id: str) -> bool:
        """Can lines won by `unit` take this field from the device output?"""
        merged = self.plan_by_id[field_id]
        if merged.kind == "host":
            return False
        return self._plan_group(unit.plan_for(field_id)) == self._plan_group(
            merged
        )

    # -- device plan resolution ----------------------------------------

    def _resolve(self, program: DeviceProgram, field_id: str) -> _FieldPlan:
        """Map one requested field to its device plan by chasing every
        token output through the consumer registry (the device-compiler
        mirror of Parser._find_useful_dissectors).

        A field is device-resolvable only when EXACTLY ONE chase path
        produces it and every step of that path is device-modeled.  With
        multiple producers (e.g. `%B ... %b`: the direct BYTESCLF token
        plus the ConvertNumberIntoCLF edge from the BYTES token both feed
        BYTESCLF:response.body.bytes) the oracle delivers every value in
        graph order and the record keeps the last; a single-path device
        plan would silently pick one — so such fields go to the oracle."""
        ftype, _, path = field_id.partition(":")
        candidates: List[_FieldPlan] = []
        for tok in program.tokens:
            for out_type, out_name in tok.outputs:
                candidates.extend(
                    self._chase(
                        field_id, ftype, path, tok, out_type, out_name,
                        vctx=("", "", 1), steps=(), device_ok=True,
                        depth=6, visited=frozenset(),
                    )
                )
        if len(candidates) == 1 and candidates[0].kind != "host":
            return candidates[0]
        return _FieldPlan(field_id, "host")

    def _terminal_plan(
        self, field_id: str, tok, vctx, steps, device_ok
    ) -> _FieldPlan:
        """Build the plan for a chase path that reached the requested field.
        vctx = (parse, null_mode, scale) accumulated value conversions."""
        if not device_ok:
            return _FieldPlan(field_id, "host")
        parse, null_mode, scale = vctx
        if parse == "":
            # No value conversion: a raw (sub-)span.  Direct token captures
            # with a numeric charset deliver typed int64 (the reference
            # types them via Casts at the setter).
            if steps:
                return _FieldPlan(field_id, "span", tok.index, steps)
            # NARROW charsets under-approximate the regex (list tokens):
            # the host types those by casts (STRING), not by charset.
            if tok.charset == CS_DIGITS and not tok.narrow:
                return _FieldPlan(field_id, "long", tok.index)
            if tok.charset == CS_CLF_DIGITS and not tok.narrow:
                return _FieldPlan(
                    field_id, "long", tok.index, null_mode="dash_null"
                )
            return _FieldPlan(field_id, "span", tok.index)
        return _FieldPlan(
            field_id, parse, tok.index, steps, null_mode=null_mode, scale=scale
        )

    def _step_spec(self, d, oname: str, vctx, steps, device_ok):
        """How consumer dissector `d` transforms a chase path for output
        `oname`.  Returns (kind, new_vctx, new_steps, new_device_ok, comp,
        meta) where kind is "value" (value-level), "span" (span transform)
        or "ts" (terminal timestamp component)."""
        from ..dissectors.firstline import (
            HttpFirstLineDissector,
            HttpFirstLineProtocolDissector,
        )
        from ..dissectors.strftime_stamp import StrfTimeStampDissector
        from ..dissectors.timestamp import TimeStampDissector
        from ..dissectors.uri import HttpUriDissector
        from ..dissectors.translate import (
            ConvertCLFIntoNumber,
            ConvertMillisecondsIntoMicroseconds,
            ConvertNumberIntoCLF,
            ConvertSecondsWithMillisStringDissector,
        )
        from .timeparse import compile_layout_for_device

        parse, null_mode, scale = vctx
        if isinstance(d, ConvertCLFIntoNumber) and parse == "":
            return ("value", ("long", "dash_zero", scale), steps, device_ok)
        if isinstance(d, ConvertNumberIntoCLF) and parse == "":
            return ("value", ("long", "zero_null", scale), steps, device_ok)
        if isinstance(d, ConvertSecondsWithMillisStringDissector) and parse == "":
            return ("value", ("secmillis", "", scale), steps, device_ok)
        if isinstance(d, ConvertMillisecondsIntoMicroseconds):
            new_parse = parse or "long"
            return ("value", (new_parse, null_mode, scale * 1000), steps, device_ok)
        if isinstance(d, HttpFirstLineDissector) and parse == "":
            part = {"method": "method", "uri": "uri", "protocol": "protocol"}.get(
                oname
            )
            if part is not None:
                return ("span", vctx, steps + (("fl", part),), device_ok)
        if isinstance(d, HttpFirstLineProtocolDissector) and parse == "":
            # "HTTP/1.1" -> protocol ("" output name: keeps the input path)
            # + version.  A span split at the first '/', device-exact.
            if oname in ("", "version"):
                return (
                    "span", vctx,
                    steps + (("pv", "version" if oname else "protocol"),),
                    device_ok,
                )
        if isinstance(d, HttpUriDissector) and parse == "":
            if oname == "port":
                # Port is numeric on the host (uri.port int, STRING_OR_LONG
                # casts): terminal long parse over the device port span.
                return (
                    "value", ("long", null_mode, scale),
                    steps + (("uri", oname),), device_ok,
                )
            if oname in (
                "protocol", "userinfo", "host", "path", "query", "ref"
            ):
                return ("span", vctx, steps + (("uri", oname),), device_ok)
        from ..httpd.nginx_modules.upstream import UpstreamListDissector

        if isinstance(d, UpstreamListDissector) and parse == "":
            # Indexed upstream-list elements: device-eligible when the
            # output is STRING_ONLY (numeric-casted lists deliver typed
            # values through the oracle's casts dispatch).
            from ..core.casts import STRING_ONLY as _SO

            u_idx, _, u_which = oname.partition(".")
            if u_which in ("value", "redirected") and u_idx.isdigit():
                casts = (
                    d.output_original_casts if u_which == "value"
                    else d.output_redirected_casts
                )
                return (
                    "ulist", vctx, steps,
                    device_ok and casts == _SO,
                    oname, (int(u_idx), u_which),
                )
        from ..dissectors.mod_unique_id import ModUniqueIdDissector

        if isinstance(d, ModUniqueIdDissector) and parse == "":
            if oname in ("epoch", "ip", "processid", "counter", "threadindex"):
                return ("muid", vctx, steps, device_ok, oname, None)
        from ..geoip.dissectors import AbstractGeoIPDissector

        if isinstance(d, AbstractGeoIPDissector) and parse == "":
            table = self._geo_table_for(d) if device_ok else None
            if table is not None and oname in table.columns:
                tag = f"{type(d).__name__}:{d.database_file_name}"
                return ("geo", vctx, steps, device_ok, oname,
                        (tag, oname, table))
            return ("geo", vctx, steps, False, oname, None)
        if isinstance(d, (TimeStampDissector, StrfTimeStampDissector)) and parse == "":
            if oname in timefields.DEVICE_COMPONENTS:
                inner = (
                    d.timestamp_dissector
                    if isinstance(d, StrfTimeStampDissector)
                    else d
                )
                try:
                    dl = compile_layout_for_device(inner.get_layout())
                except ValueError:
                    dl = None  # pattern the layout compiler rejects: host
                if dl is not None:
                    return ("ts", vctx, steps, device_ok, oname, dl)
            return ("ts", vctx, steps, False, oname, None)
        # Not device-modeled: the path still counts as a producer.
        return ("value", vctx, steps, False)

    def _geo_table_for(self, d):
        """Build (once per database) the flattened device range-join table
        for a GeoIP dissector; None when the database cannot back one."""
        from ..geoip.device import _EXTRACTORS, GeoDeviceTable
        from ..geoip.mmdb import MMDBReader

        key = (type(d).__name__, d.database_file_name)
        if not hasattr(self, "_geo_tables"):
            self._geo_tables: Dict[tuple, Any] = {}
        if key not in self._geo_tables:
            try:
                columns = [
                    o.partition(":")[2]
                    for o in d.get_possible_output()
                    if o.partition(":")[2] in _EXTRACTORS
                ]
                reader = MMDBReader(d.database_file_name)
                self._geo_tables[key] = GeoDeviceTable(reader, columns)
            except Exception:
                self._geo_tables[key] = None
        return self._geo_tables[key]

    def _chase(
        self, field_id, ftype, path, tok, t, name,
        vctx, steps, device_ok, depth, visited, remapped=False,
    ) -> List[_FieldPlan]:
        """All ways field (t:name) — reachable from `tok` via `steps` and
        `vctx` — leads to the requested (ftype:path).  Device plans where
        every step is modeled; "host" placeholders otherwise (they count
        toward the multi-producer guard)."""
        plans: List[_FieldPlan] = []
        if t == ftype and name == path:
            plans.append(self._terminal_plan(field_id, tok, vctx, steps, device_ok))
            return plans
        if (t, name) in visited:
            return plans  # cycle: its producer paths are already counted
        relevant = name == "" or path == name or path.startswith(name + ".")
        if not relevant:
            return plans
        if depth == 0:
            # Fail SAFE on depth exhaustion: a truncated path may still be
            # a real producer — count it as host so the multi-producer
            # guard cannot be starved by deep chains.
            plans.append(_FieldPlan(field_id, "host"))
            return plans
        visited = visited | {(t, name)}
        # Type remappings re-type this name: the engine re-delivers the
        # same value under each mapped type (Parsable's remap recursion,
        # NOT nested — hence the `remapped` flag), so every consumer of a
        # mapped type is a producer path too, and the mapped field itself
        # is deliverable as a raw span (remapped targets are STRING_ONLY).
        if not remapped:
            for ntype in self._remaps.get(name, ()):
                if ntype == t:
                    continue
                plans.extend(self._chase(
                    field_id, ftype, path, tok, ntype, name,
                    vctx, steps, device_ok, depth - 1, visited,
                    remapped=True,
                ))
        for d in self._consumers.get(t, ()):
            for out in d.get_possible_output():
                ot, _, oname = out.partition(":")
                if oname == "*":
                    # Wildcard outputs (query-string/cookies): any requested
                    # path under this prefix is produced here.
                    if ot == ftype and path.startswith(name + "."):
                        from ..dissectors.cookies import (
                            RequestCookieListDissector,
                            ResponseSetCookieListDissector,
                        )
                        from ..dissectors.query import QueryStringFieldDissector

                        mode = None
                        if isinstance(d, QueryStringFieldDissector):
                            mode = "query"
                        elif isinstance(d, RequestCookieListDissector):
                            mode = "cookie"
                        elif isinstance(d, ResponseSetCookieListDissector):
                            mode = "setcookie"
                        if mode is not None and vctx[0] == "" and device_ok:
                            plans.append(_FieldPlan(
                                field_id, "qscsr", tok.index, steps,
                                comp=path[len(name) + 1:], meta=mode,
                            ))
                        else:
                            plans.append(_FieldPlan(field_id, "host"))
                    elif path.startswith(name + "."):
                        # Per-cookie ATTRIBUTE through the Set-Cookie
                        # wildcard: response.cookies.<cookie>.<attr> with
                        # the attr typed by ResponseSetCookieDissector
                        # (STRING value/path/domain/comment/expires;
                        # TIME.EPOCH expires).  The cookie name is
                        # everything before the last component (names may
                        # contain dots).
                        from ..dissectors.cookies import (
                            ResponseSetCookieListDissector,
                        )

                        rest = path[len(name) + 1:]
                        cname, _, attr = rest.rpartition(".")
                        typed = (
                            ftype == "STRING"
                            and attr in ("value", "path", "domain",
                                         "comment", "expires")
                        ) or (ftype == "TIME.EPOCH" and attr == "expires")
                        if (
                            isinstance(d, ResponseSetCookieListDissector)
                            and cname and typed
                        ):
                            if vctx[0] == "" and device_ok:
                                plans.append(_FieldPlan(
                                    field_id, "qscsr", tok.index, steps,
                                    comp=cname, meta="setcookie", attr=attr,
                                ))
                            else:
                                plans.append(_FieldPlan(field_id, "host"))
                    # A wildcard param REMAPPED to another type (the
                    # reference's query.res -> SCREENRESOLUTION demo): the
                    # engine re-types the delivered param value, so the
                    # remapped type's consumers become producers too.
                    if not remapped:
                        plans.extend(self._chase_wildcard_remaps(
                            field_id, ftype, path, tok, d, name,
                            vctx, steps, device_ok,
                        ))
                    continue
                if oname == "":
                    new_name = name
                else:
                    new_name = name + "." + oname if name else oname
                if not (path == new_name or path.startswith(new_name + ".")):
                    continue
                spec = self._step_spec(d, oname, vctx, steps, device_ok)
                kind = spec[0]
                if kind in ("ts", "geo", "muid", "ulist"):
                    _, nctx, nsteps, ndev, comp, meta = spec
                    if path == new_name and ot == ftype:
                        if ndev:
                            plans.append(_FieldPlan(
                                field_id, kind, tok.index, nsteps,
                                comp=comp, meta=meta,
                            ))
                        else:
                            plans.append(_FieldPlan(field_id, "host"))
                    # ts/geo outputs are terminal values; nothing deeper.
                    continue
                _, nctx, nsteps, ndev = spec
                if path == new_name and ot == ftype:
                    plans.append(
                        self._terminal_plan(field_id, tok, nctx, nsteps, ndev)
                    )
                else:
                    plans.extend(self._chase(
                        field_id, ftype, path, tok, ot, new_name,
                        nctx, nsteps, ndev, depth - 1, visited,
                    ))
        return plans

    def _chase_wildcard_remaps(
        self, field_id, ftype, path, tok, d, name, vctx, steps, device_ok,
    ) -> List[_FieldPlan]:
        """Producer paths through a remapped wildcard param.

        The wildcard delivers ``STRING:name.<param>``; a type remapping on
        that complete name re-delivers the value under the mapped type,
        whose consumers then sub-dissect it.  Device-modeled today: the
        remapped raw value itself (the CSR segment value span) and
        ScreenResolutionDissector's width/height (host-side split of the
        matched segment, like set-cookie attrs).  Anything else counts as
        a host producer."""
        from ..dissectors.cookies import RequestCookieListDissector
        from ..dissectors.query import QueryStringFieldDissector
        from ..dissectors.screenres import ScreenResolutionDissector

        mode = None
        if isinstance(d, QueryStringFieldDissector):
            mode = "query"
        elif isinstance(d, RequestCookieListDissector):
            mode = "cookie"
        plans: List[_FieldPlan] = []
        prefix = name + "."
        for remap_key, ntypes in self._remaps.items():
            if not remap_key.startswith(prefix):
                continue
            param = remap_key[len(prefix):]
            if path == remap_key:
                # The remapped raw value itself under one of its new types.
                for ntype in ntypes:
                    if ftype == ntype:
                        if mode is not None and vctx[0] == "" and device_ok:
                            plans.append(_FieldPlan(
                                field_id, "qscsr", tok.index, steps,
                                comp=param, meta=mode,
                            ))
                        else:
                            plans.append(_FieldPlan(field_id, "host"))
                continue
            if not path.startswith(remap_key + "."):
                continue
            sub = path[len(remap_key) + 1:]
            for ntype in ntypes:
                for d2 in self._consumers.get(ntype, ()):
                    for out2 in d2.get_possible_output():
                        ot2, _, oname2 = out2.partition(":")
                        if oname2 == sub and ot2 == ftype:
                            if (
                                isinstance(d2, ScreenResolutionDissector)
                                and oname2 in ("width", "height")
                                and mode is not None
                                and vctx[0] == "" and device_ok
                            ):
                                plans.append(_FieldPlan(
                                    field_id, "qscsr", tok.index, steps,
                                    comp=param, meta=mode,
                                    attr=("sres", d2.separator, oname2),
                                ))
                            else:
                                plans.append(_FieldPlan(field_id, "host"))
                        elif sub.startswith(oname2 + "."):
                            # Deeper chains through the remapped type are
                            # not modeled: count the producer, go host.
                            plans.append(_FieldPlan(field_id, "host"))
        return plans

    # ------------------------------------------------------------------

    @staticmethod
    def _geo_typed_fill(col, sel, typed, miss, kind_ch):
        """Carry a numeric geo column's raw values + miss mask alongside
        the object array so the Arrow bridge can build the typed column
        without per-element inference.  Mixed numeric kinds across fills
        disable the fast path (typed_kind=None)."""
        B = len(typed)
        if "typed_values" not in col:
            col["typed_values"] = np.zeros(
                B, dtype=np.float64 if kind_ch == "f" else np.int64
            )
            col["typed_miss"] = np.ones(B, dtype=bool)
            col["typed_kind"] = kind_ch
        if col.get("typed_kind") == kind_ch:
            col["typed_values"] = np.where(sel, typed, col["typed_values"])
            col["typed_miss"] = np.where(sel, miss, col["typed_miss"])
        else:
            col["typed_kind"] = None

    def parse_batch(
        self, lines: Sequence[Union[bytes, str]],
        emit_views: Optional[bool] = None,
    ) -> BatchResult:
        """``emit_views=False`` runs the plain executor (no device Arrow
        view rows): the demand knob for consumers that never deliver
        string_view columns — copy-mode Arrow (parse_to_ipc, the sidecar
        wire) and the per-record adapter paths — so they stop paying the
        view-emission kernel cost and the larger packed D2H.  Default
        (None/True): the product path with views."""
        return self._finish_batch(
            self._dispatch_batch(self._encode_batch(lines), emit_views)
        )

    def parse_blob(
        self, data: Union[bytes, bytearray, memoryview],
        emit_views: Optional[bool] = None,
    ) -> BatchResult:
        """Newline-delimited log bytes -> BatchResult without building a
        Python line list: the native framer packs the padded [B, L]
        buffer straight from the blob, and per-line bytes materialize
        lazily — only for oracle-rescued rows.  The product ingest path
        (the sidecar's LINES payload and file readers are exactly this
        shape; reference analogue: the Hadoop text input path hands raw
        line Writables to the parser,
        ApacheHttpdLogfileInputFormat.java:1).

        Framing semantics are encode_blob's: a final empty segment after
        a trailing newline is dropped, and one trailing ``\\r`` per line
        is stripped — callers needing exact list semantics for such
        inputs use :meth:`parse_batch`."""
        from ..native import encode_blob
        from ..observability import pipeline_stage, record_batch_shape

        data = bytes(data)
        lines = _BlobLines(data)
        B = len(lines)
        with pipeline_stage("encode", items=B):
            buf, lengths, overflow = encode_blob(data)
        if buf.shape[0] != B:  # framer/view disagreement: authoritative path
            return self.parse_batch(list(lines), emit_views=emit_views)
        padded_b = self._bucket(B)
        if padded_b != B:
            buf = np.pad(buf, ((0, padded_b - B), (0, 0)))
            lengths = np.pad(lengths, (0, padded_b - B))
        record_batch_shape(B, padded_b, buf.shape[1], int(lengths.sum()))
        enc = (lines, buf, lengths, overflow, B, padded_b)
        return self._finish_batch(self._dispatch_batch(enc, emit_views))

    def parse_encoded(
        self, batch, emit_views: Optional[bool] = None,
    ) -> BatchResult:
        """One feeder-framed batch (:class:`logparser_tpu.feeder.worker.
        EncodedBatch`) -> BatchResult, without re-scanning the payload:
        the feeder worker already ran the ``parse_blob`` framing
        (``encode_blob``) in its own process, so this path only pads the
        batch dimension to its bucket and dispatches.  Framing semantics
        and results are byte-identical to :meth:`parse_blob` over the
        same bytes — the feeder parity suite pins it."""
        return self._finish_batch(
            self._dispatch_batch(self._adopt_encoded(batch), emit_views)
        )

    def _adopt_encoded(self, batch):
        """EncodedBatch -> the in-flight enc tuple ``_dispatch_batch``
        consumes.  Lines stay lazy (``_BlobLines`` over the shipped
        payload — only oracle-rescued rows ever materialize).  A
        framer/count disagreement falls back to the authoritative
        per-line path, mirroring :meth:`parse_blob`.

        Ring batches (shared-memory slot views, feeder ring transport):
        the PAYLOAD stays a zero-copy slot view end to end — rescue rows
        read it in place during materialization, after which the stream
        releases the slot.  The frame arrays are adopted into owned
        buffers (the bucket pad does it for free on partial batches; an
        exact-bucket batch pays one memcpy) because ``BatchResult.buf``
        backs host span gathers and string_view tables for as long as
        the caller keeps the result — longer than a recycling slot may
        live."""
        from ..observability import pipeline_stage, record_batch_shape

        payload = batch.payload
        if not isinstance(payload, (bytes, bytearray, np.ndarray)):
            payload = bytes(payload)
        lines = _BlobLines(payload)
        B = len(lines)
        buf, lengths = batch.buf, batch.lengths
        if B != batch.n_lines or buf.shape[0] != B:
            return self._encode_batch(list(lines))
        leased = getattr(batch, "ring", None) is not None
        with pipeline_stage("encode", items=0):
            # Adoption cost only (row padding / lease copy): the real
            # encode ran in the feeder worker under feeder_encode.
            padded_b = self._bucket(B)
            if padded_b != B:
                buf = np.pad(buf, ((0, padded_b - B), (0, 0)))
                lengths = np.pad(lengths, (0, padded_b - B))
            elif leased:
                buf = np.array(buf, copy=True)
                lengths = np.array(lengths, copy=True)
        record_batch_shape(B, padded_b, buf.shape[1], int(lengths.sum()))
        return (lines, buf, lengths, list(batch.overflow), B, padded_b)

    def parse_batch_stream(
        self,
        batches,
        depth: int = 1,
        emit_views: Optional[bool] = None,
        stage_h2d: Optional[bool] = None,
    ):
        """Batches-in-flight streaming: yields one BatchResult per input
        batch, in order, overlapping the host-side stages with device
        work.  JAX dispatch is async, so per iteration the ENCODE of
        batch k+1 runs while batch k computes on device, and the
        MATERIALIZATION of batch k runs while batch k+1 computes.
        Counters stay exact: every result is materialized by the same
        code path as :meth:`parse_batch`.

        ``depth`` is the number of batches whose device work may be in
        flight simultaneously.  The default of 1 keeps the device link
        in natural order (H2D k, D2H k, H2D k+1, ...); deeper queues
        only pay where uploads and downloads overlap (full-duplex
        links).  Neither setting has been measured on a TPU v5e host.

        Adaptive-CSR interplay: growing the slot count rebuilds the
        executor, which invalidates in-flight dispatches — each pending
        batch snapshots the slot count at dispatch and transparently
        re-dispatches on mismatch (bounded, slots only ever double).

        Items may also be feeder-framed batches
        (:class:`logparser_tpu.feeder.worker.EncodedBatch`, e.g. from
        ``FeederPool.batches()``): those skip the host encode entirely —
        the framing already happened in the feeder worker.  Ring batches
        (``FeederPool.batches(detach=False)`` / ``feed()``) are RELEASED
        by the stream once their result materializes — device upload
        done, rescue payload consumed — so the zero-copy slots recycle
        exactly one materialization behind delivery.

        ``stage_h2d`` double-buffers the host->device edge: batch k+1's
        encoded frame is handed to ``jax.device_put`` BEFORE the stream
        blocks on batch k's D2H fetch, so the upload overlaps the
        in-flight device work instead of queueing behind the fetch (the
        gap ``observe_stage`` used to charge to ``encode``/``device``).
        Default (None): enabled unless ``LOGPARSER_TPU_STAGED_H2D=0`` —
        the opt-out exists because staging reorders the link to
        H2D(k+1)-before-D2H(k), which can hurt on a half-duplex link
        for the same reason depth>1 does (see above)."""
        from collections import deque

        from ..feeder.worker import EncodedBatch

        if stage_h2d is None:
            stage_h2d = os.environ.get(
                "LOGPARSER_TPU_STAGED_H2D", "1"
            ).strip().lower() not in ("0", "false", "no")
        depth = max(1, depth)
        pending = deque()
        inflight = deque()  # source items of `pending`, for slot release
        try:
            for lines in batches:
                enc = (
                    self._adopt_encoded(lines)
                    if isinstance(lines, EncodedBatch)
                    else self._encode_batch(lines)
                )
                if stage_h2d:
                    enc = self._stage_h2d(enc, emit_views)
                inflight.append(lines)
                if len(pending) >= depth:
                    # Drain the oldest D2H BEFORE enqueueing the next H2D
                    # (link order; the staged upload above is the deliberate
                    # exception), then materialize it while the new batch
                    # computes.
                    fetched = self._fetch_packed(pending.popleft())
                    pending.append(self._dispatch_batch(enc, emit_views))
                    result = self._materialize_packed(fetched)
                    _release_stream_item(inflight.popleft())
                    yield result
                else:
                    pending.append(self._dispatch_batch(enc, emit_views))
            while pending:
                result = self._finish_batch(pending.popleft())
                _release_stream_item(inflight.popleft())
                yield result
        finally:
            # Abandoned stream (close/throw/error): give every undelivered
            # ring slot back so the fabric can wind down instead of
            # wedging producers on an exhausted ring.
            while inflight:
                _release_stream_item(inflight.popleft())

    def _stage_h2d(self, enc, emit_views: Optional[bool]):
        """Begin the async H2D transfer of one encoded batch (double
        buffering: the upload overlaps whatever is already on device).
        Returns the enc tuple extended with the staged device arrays;
        a no-op for host-only parsers."""
        from ..observability import metrics, observe_stage

        if self._executor_for(emit_views) is None:
            return enc
        lines, buf, lengths, overflow, B, padded_b = enc[:6]
        if self._oom_clamp is not None and padded_b > self._oom_clamp:
            # Standing OOM clamp: this batch executes in clamp-sized
            # chunks at fetch time — staging the whole oversized frame
            # would re-create exactly the allocation the clamp forbids.
            return enc
        self._check_device_budget(buf, lengths, B, emit_views)
        t0 = time.perf_counter()
        try:
            if self._mesh is not None:
                # Per-device input sharding ON the H2D edge: each device
                # receives only its batch slice, so the upload fans out
                # across the mesh instead of landing whole on device 0 and
                # resharding inside the jit (the dryrun_multichip feeder
                # idiom promoted to the hot path).
                from ..parallel.mesh import dp_shardings

                (buf_sh, len_sh), _ = dp_shardings(self._mesh)
                staged = (jax.device_put(buf, buf_sh),
                          jax.device_put(lengths, len_sh))
            else:
                staged = (jax.device_put(buf), jax.device_put(lengths))
        except Exception as e:  # noqa: BLE001 — staging is an optimization
            # A staging failure (device OOM mid-upload, lost device)
            # defers placement to dispatch time, where the fault layer
            # classifies and absorbs it — never an abort here.  Still
            # counted + warned-once: a PERSISTENTLY failing staging
            # path silently costs the upload overlap fleet-wide, which
            # must not go dark (details at DEBUG).
            from ..observability import log_warning_once

            metrics().increment("device_stage_fallbacks_total")
            log_warning_once(
                _LOG,
                "device: staged H2D upload failed; batches fall back "
                "to dispatch-time placement "
                "(device_stage_fallbacks_total counts, details at "
                "DEBUG)",
            )
            _LOG.debug("staged H2D failed; deferring to dispatch: %s", e)
            return enc
        observe_stage("h2d_stage", time.perf_counter() - t0, items=B)
        metrics().increment(
            "h2d_staged_bytes_total", int(buf.nbytes + lengths.nbytes)
        )
        return (lines, buf, lengths, overflow, B, padded_b, staged)

    # ------------------------------------------------------------------
    # analytics pushdown (docs/ANALYTICS.md): aggregate queries fuse the
    # reduction into the device pass — the packed columns, view rows and
    # Arrow assembly never happen, and the D2H transfer is the per-batch
    # partial arrays (a few KB) plus one byte per row of fold/reject
    # classification.  Rows the device cannot finish exactly replay the
    # ordinary row path host-side, so every aggregate is bit-identical
    # to aggregating the row-path results.
    # ------------------------------------------------------------------

    def _resolve_agg_spec(self, spec):
        """Normalize a public ``spec`` argument: a built ``AggregateSpec``
        passes through untouched (the service/jobs boundary already
        validated it); an op list or JSON string parses AND validates
        against this parser's fields here, so the parser-level surface
        matches the CONFIG/CLI one."""
        from ..analytics.spec import AggregateSpec, parse_aggregate_config

        if isinstance(spec, AggregateSpec):
            return spec
        parsed = parse_aggregate_config(spec)
        if parsed is None:
            raise ValueError("aggregate: need a spec (op list, JSON "
                             "string, or AggregateSpec)")
        parsed.validate_for(self)
        return parsed

    def aggregate_batch(self, lines: Sequence[Union[bytes, str]], spec):
        """Parse + aggregate one batch entirely on device: returns an
        :class:`~logparser_tpu.analytics.state.AggregateOutcome` whose
        ``state`` holds this batch's partial aggregates (merge partials
        across batches with ``AggregateState.merge``).  ``spec`` is an
        ``AggregateSpec``, an op list, or a JSON string (validated
        against this parser's fields)."""
        spec = self._resolve_agg_spec(spec)
        return self._finish_aggregate(
            self._dispatch_aggregate(self._encode_batch(lines), spec), spec
        )

    def aggregate_blob(self, data: Union[bytes, bytearray, memoryview],
                       spec):
        """:meth:`parse_blob` framing, aggregate delivery (the jobs /
        sidecar ingest shape)."""
        from ..native import encode_blob
        from ..observability import pipeline_stage, record_batch_shape

        spec = self._resolve_agg_spec(spec)
        data = bytes(data)
        lines = _BlobLines(data)
        B = len(lines)
        with pipeline_stage("encode", items=B):
            buf, lengths, overflow = encode_blob(data)
        if buf.shape[0] != B:  # framer/view disagreement: authoritative path
            return self.aggregate_batch(list(lines), spec)
        padded_b = self._bucket(B)
        if padded_b != B:
            buf = np.pad(buf, ((0, padded_b - B), (0, 0)))
            lengths = np.pad(lengths, (0, padded_b - B))
        record_batch_shape(B, padded_b, buf.shape[1], int(lengths.sum()))
        enc = (lines, buf, lengths, overflow, B, padded_b)
        return self._finish_aggregate(
            self._dispatch_aggregate(enc, spec), spec
        )

    def aggregate_batch_stream(self, batches, spec, depth: int = 1):
        """Streamed aggregation: yields one AggregateOutcome per input
        batch, in order, overlapping host accumulation with device work
        (the :meth:`parse_batch_stream` discipline minus the packed D2H
        — there is nothing column-sized to drain).  Items may be line
        lists, or feeder-framed ``EncodedBatch``es (ring slots release
        one accumulation behind delivery, as in the row stream)."""
        from collections import deque

        from ..feeder.worker import EncodedBatch

        spec = self._resolve_agg_spec(spec)
        depth = max(1, depth)
        pending = deque()
        inflight = deque()
        try:
            for lines in batches:
                enc = (
                    self._adopt_encoded(lines)
                    if isinstance(lines, EncodedBatch)
                    else self._encode_batch(lines)
                )
                inflight.append(lines)
                pending.append(self._dispatch_aggregate(enc, spec))
                if len(pending) > depth:
                    outcome = self._finish_aggregate(
                        pending.popleft(), spec
                    )
                    _release_stream_item(inflight.popleft())
                    yield outcome
            while pending:
                outcome = self._finish_aggregate(pending.popleft(), spec)
                _release_stream_item(inflight.popleft())
                yield outcome
        finally:
            while inflight:
                _release_stream_item(inflight.popleft())

    def _agg_executor(self, spec):
        """The compiled aggregate reduction for this parser + spec:
        cached per (canonical spec, CSR slot generation) — a slot regrow
        rebuilds the units, so the reduction rebuilds with them.  None
        when the parser is host-only, the breaker is open, or the spec's
        reduction was compile-demoted (every batch then replays the
        exact row path)."""
        key = spec.canonical_key()
        if key in self._agg_disabled:
            return None
        cached = self._agg_fns.get(key)
        if cached is not None and cached[0] == self.csr_slots:
            return cached[1]
        from ..analytics.device import build_aggregate_fn

        fn, _ = build_aggregate_fn(self, spec)
        self._agg_fns[key] = (self.csr_slots, fn)
        return fn

    def _dispatch_aggregate(self, enc, spec):
        """Asynchronously dispatch the aggregate reduction for one
        encoded batch; faults ride the state tuple to
        :meth:`_finish_aggregate` (same discipline as the row path)."""
        from ..observability import metrics, pipeline_stage

        lines, buf, lengths, overflow, B, padded_b = enc[:6]
        out = None
        fault = None
        fn = self._agg_executor(spec) if self._breaker.allow() else None
        if fn is not None and self._oom_clamp is not None \
                and padded_b > self._oom_clamp:
            # Standing OOM clamp: the row-path fallback executes this
            # batch in clamp-sized chunks instead.
            fn = None
        if fn is not None:
            n_group_ops = sum(
                1 for op in spec.ops
                if op.op in ("count_by", "top_k", "time_bucket")
            )
            self._check_device_budget(
                buf, lengths, B, False, aggregate_group_ops=n_group_ops
            )
            host_kill = np.zeros(padded_b, dtype=bool)
            for i in overflow:
                # Truncated lines: the device saw a prefix — judged
                # host-side, exactly like the row path's overflow demote.
                host_kill[i] = True
            metrics().increment(
                "device_dispatch_total", labels={"views": "agg"}
            )
            with pipeline_stage("device", items=B):
                try:
                    out = fn(jnp.asarray(buf), jnp.asarray(lengths),
                             jnp.int32(B), jnp.asarray(host_kill))
                except Exception as e:  # noqa: BLE001 — absorbed at finish
                    out, fault = None, e
        return (lines, buf, lengths, overflow, B, padded_b, out,
                spec.canonical_key(), fault)

    def _finish_aggregate(self, state, spec):
        """Block on one in-flight aggregate dispatch: fetch the partials,
        accumulate them host-side, and replay every folded row through
        the ordinary row path so the outcome is exact.  Any device fault
        (or no executor at all) downgrades the WHOLE batch to the row
        path — which owns the central fault absorption — and aggregates
        its delivered rows; an aggregate stream never aborts on a device
        failure and never returns an approximate answer."""
        from ..analytics.device import accumulate_partials, fetch_partials
        from ..analytics.state import AggregateOutcome, AggregateState
        from ..observability import metrics, observe_stage

        (lines, buf, lengths, overflow, B, padded_b, out, key,
         fault) = state
        agg = AggregateState(spec)
        fetched = None
        nbytes = 0
        t0 = time.perf_counter()
        if out is not None and fault is None:
            try:
                fetched, nbytes = fetch_partials(out, spec, B, padded_b)
            except Exception as e:  # noqa: BLE001 — classified below
                fetched, fault = None, e
        if fault is not None:
            from ..observability import log_warning_once
            from .device_faults import classify_device_error

            if classify_device_error(fault) == "compile":
                # The REDUCTION does not compile (the row kernel may be
                # fine): demote this spec permanently, keep the parser.
                self._agg_disabled.add(key)
                metrics().increment("analytics_compile_demotions_total")
                log_warning_once(
                    _LOG,
                    "analytics: aggregate reduction failed to compile; "
                    "spec demoted to the exact row-path fallback "
                    "(analytics_compile_demotions_total counts, details "
                    "at DEBUG)",
                )
                _LOG.debug("aggregate compile fault for %s: %s", key, fault)
            else:
                _LOG.debug("aggregate device fault (row-path fallback "
                           "absorbs): %s", fault)
        if fetched is None:
            # Row-path fallback for the whole batch: a fresh dispatch —
            # NOT the ridden fault — so the row executor's own fault
            # layer (bisect/reroute/breaker) judges its own faults.
            result = self._finish_batch(
                (lines, buf, lengths, overflow, B, padded_b, None,
                 self.csr_slots, False, None)
            )
            metrics().increment("analytics_batches_total",
                                labels={"path": "fallback"})
            t1 = time.perf_counter()
            agg.update_from_result(result)
            metrics().observe("analytics_partial_merge_seconds",
                              time.perf_counter() - t1)
            reject_items = [
                (int(i), reason, result.raw_line(int(i)))
                for i, reason in sorted(result.reject_reasons.items())
            ]
            return AggregateOutcome(
                agg, B, result.good_lines, result.bad_lines,
                result.oracle_rows, reject_items,
                device_rows=0, d2h_bytes=0,
            )
        self._breaker.record_success()
        cls = fetched["cls"]
        accumulate_partials(agg, spec, fetched, buf)
        observe_stage("aggregate", time.perf_counter() - t0, items=B)
        metrics().increment("d2h_bytes_total", int(nbytes))
        metrics().increment("analytics_batches_total",
                            labels={"path": "device"})
        # What the row path would have transferred for this batch
        # (packed unit rows + the device-view block) minus what the
        # partials actually cost:
        from .pipeline import packed_row_count

        row_bytes = (
            packed_row_count(self.units) + 4 * self._view_field_count(None)
        ) * padded_b * 4
        metrics().increment(
            "analytics_d2h_bytes_saved_total",
            max(0, int(row_bytes) - int(nbytes)),
        )
        n_device = int(np.count_nonzero(cls == 0))
        fold_rows = np.nonzero(cls == 1)[0]
        reject_rows = np.nonzero(cls == 2)[0]
        reject_items = [
            (int(i), "implausible", _raw_line_bytes(lines[int(i)]))
            for i in reject_rows
        ]
        good = n_device
        bad = len(reject_rows)
        oracle_rows = 0
        if len(fold_rows):
            # Exactness fold: every row the device flagged replays the
            # ordinary row path (rescue, overflow patches, escaped-quote
            # and oracle semantics included) and aggregates from its
            # delivered values — per-row results are independent of
            # batch geometry, so the sub-batch parses identically.
            sub = self.parse_batch(
                [lines[int(i)] for i in fold_rows], emit_views=False
            )
            t1 = time.perf_counter()
            agg.update_from_result(sub)
            metrics().observe("analytics_partial_merge_seconds",
                              time.perf_counter() - t1)
            good += sub.good_lines
            bad += sub.bad_lines
            oracle_rows = sub.oracle_rows
            for j, reason in sub.reject_reasons.items():
                reject_items.append(
                    (int(fold_rows[int(j)]), reason, sub.raw_line(int(j)))
                )
            reject_items.sort(key=lambda item: item[0])
        return AggregateOutcome(
            agg, B, good, bad, oracle_rows, reject_items,
            device_rows=n_device, d2h_bytes=int(nbytes),
        )

    def _start_batch(self, lines: Sequence[Union[bytes, str]]):
        """Encode + pad + asynchronously dispatch the device program.
        Returns the in-flight state ``_finish_batch`` consumes."""
        return self._dispatch_batch(self._encode_batch(lines))

    def _executor_for(self, emit_views: Optional[bool]):
        """The executor an emit_views choice selects: the view-emitting
        product executor by default, the plain one when views are
        disabled (per call or by an empty parser-level demand set).
        None also when the fault layer's circuit breaker has demoted
        the kernel (open / compile-demoted): every batch then takes the
        batched oracle host path — the device twin of the feeder's
        transport demotion (docs/FAULTS.md)."""
        if not self._breaker.allow():
            return None
        if emit_views is None or emit_views:
            return self.device_views_fn()
        return self._jitted

    def _view_field_count(self, emit_views: Optional[bool]) -> int:
        """Trailing device-view rows the chosen executor will emit / 4
        (the budget estimator's input; 0 with views off)."""
        if not (emit_views is None or emit_views):
            return 0
        fields = getattr(self, "_views_fields", None)
        if fields is not None:
            return len(fields)
        return len(self._view_specs())

    def _check_device_budget(self, buf, lengths, B: int,
                             emit_views: Optional[bool],
                             aggregate_group_ops: Optional[int] = None,
                             ) -> None:
        """Pre-allocation device-memory ceiling: validate the padded
        batch's estimated footprint (staged H2D input + packed verdict
        output, ``pipeline.estimate_device_bytes``) against the
        configured budget BEFORE any ``device_put`` — over budget
        answers a structured :class:`DeviceBudgetError`, never an XLA
        RESOURCE_EXHAUSTED (the batch-tier twin of the serving tier's
        frame ceilings; docs/FAULTS.md).  ``aggregate_group_ops`` (the
        analytics pushdown) selects the aggregate-only footprint — no
        view rows, partial-sized D2H — so the budget stops over-
        rejecting aggregate batches that fit comfortably."""
        budget = self.device_bytes_budget
        if not budget:
            return
        from ..observability import metrics
        from .device_faults import DeviceBudgetError
        from .pipeline import estimate_device_bytes

        est = estimate_device_bytes(
            self.units, self._view_field_count(emit_views),
            buf.shape[0], buf.shape[1], lengths.dtype.itemsize,
            aggregate_group_ops=aggregate_group_ops,
        )
        if est > budget:
            metrics().increment("device_budget_rejects_total")
            raise DeviceBudgetError(est, budget, B)

    def _encode_batch(self, lines: Sequence[Union[bytes, str]]):
        from ..observability import pipeline_stage, record_batch_shape

        B = len(lines)
        with pipeline_stage("encode", items=B):
            buf, lengths, overflow = encode_batch(lines)
        # Pad the batch dimension to a bucket so jit recompiles stay bounded.
        padded_b = self._bucket(B)
        if padded_b != B:
            buf = np.pad(buf, ((0, padded_b - B), (0, 0)))
            lengths = np.pad(lengths, (0, padded_b - B))
        record_batch_shape(B, padded_b, buf.shape[1], int(lengths.sum()))
        return list(lines), buf, lengths, overflow, B, padded_b

    def _dispatch_batch(self, enc, emit_views: Optional[bool] = None):
        from ..observability import metrics, pipeline_stage, tracer

        # enc may carry a 7th element: device arrays already staged by
        # _stage_h2d (the overlapped-upload path).
        lines, buf, lengths, overflow, B, padded_b = enc[:6]
        staged = enc[6] if len(enc) > 6 else None
        out = None
        fault = None
        fn = self._executor_for(emit_views)
        if fn is not None and self._oom_clamp is not None \
                and padded_b > self._oom_clamp:
            # Standing OOM clamp: never dispatch above the safe bucket —
            # _fetch_packed executes this batch in clamp-sized chunks.
            fn = None
        if fn is not None:
            if staged is None:
                # (Staged batches were validated in _stage_h2d.)
                self._check_device_budget(buf, lengths, B, emit_views)
            # Label by the executor actually chosen, not the request: a
            # viewless parser's device_views_fn() falls back to the plain
            # executor, and that dispatch must not read as views="on".
            views_on = (
                (emit_views is None or emit_views)
                and bool(getattr(self, "_views_fields", None))
            )
            metrics().increment(
                "device_dispatch_total",
                labels={"views": "on" if views_on else "off"},
            )
            with pipeline_stage("device", items=B):
                try:
                    if staged is not None:
                        out = fn(*staged)
                    else:
                        out = fn(jnp.asarray(buf), jnp.asarray(lengths))
                    if tracer().enabled:
                        # Dispatch is async: make the device stage contain
                        # the actual kernel time instead of misattributing
                        # it to the fetch stage (only when someone is
                        # looking).
                        out = jax.block_until_ready(out)
                except Exception as e:  # noqa: BLE001 — absorbed at fetch
                    # Compile failures and allocation OOMs surface HERE
                    # (jit compiles synchronously at call); the fault
                    # rides the state tuple to _fetch_packed's central
                    # fault policy instead of raising out of the parse.
                    out, fault = None, e
        return (lines, buf, lengths, overflow, B, padded_b, out,
                self.csr_slots, emit_views, fault)

    def _finish_batch(self, state) -> BatchResult:
        return self._materialize_packed(self._fetch_packed(state))

    def _fetch_packed(self, state):
        """Block on the in-flight device result: returns the fetched
        verdicts (packed rows, per-line validity/winner/plausibility)
        ready for :meth:`_materialize_packed`.

        Every device-tier fault lands here — dispatch-time failures ride
        the state tuple, async execution errors surface in the guarded
        fetch — and is ABSORBED by the fault layer
        (:meth:`_absorb_device_fault`): OOMs bisect and retry, wedged or
        otherwise-failed executions reroute the batch to the batched
        oracle host path, compile failures demote the parser key.  The
        only raise left is the pre-allocation
        :class:`~.device_faults.DeviceBudgetError` (a structured reject
        by contract); a parse stream NEVER aborts on a device failure
        (docs/FAULTS.md)."""
        from ..observability import metrics, pipeline_stage

        (lines, buf, lengths, overflow, B, padded_b, out, out_slots,
         emit_views, fault) = state

        from .pipeline import CSR_OVERFLOW_BIT

        while True:
            packed = None
            if fault is None:
                try:
                    if out is not None and out_slots == self.csr_slots:
                        # ONE packed [sum K_i, B] int32 output -> ONE
                        # device->host fetch (one transfer round-trip
                        # per batch).
                        with pipeline_stage("fetch", items=B):
                            packed = self._guarded_get(out, B)
                    else:
                        # (Re-)dispatch: nothing in flight, a stale CSR
                        # slot layout (another batch's materialization
                        # grew the slots mid-stream), or a clamp/fault
                        # retry path.
                        packed = self._execute_packed(
                            buf, lengths, B, emit_views
                        )
                except Exception as e:  # noqa: BLE001 — classified below
                    fault = e
            out = None
            if fault is not None:
                packed = self._absorb_device_fault(
                    fault, buf, lengths, B, emit_views
                )
                fault = None
            if packed is None:
                valid = np.zeros(B, dtype=bool)
                winner = np.full(B, -1, dtype=np.int64)
                break
            self._breaker.record_success()
            metrics().increment("d2h_bytes_total", int(packed.nbytes))
            # Per-line winner: first registered format whose automaton
            # accepted the line (row_offset row: bit 0 = valid, bit 1 =
            # plausible).  A line is only CLAIMED by format i when no
            # earlier format is still plausible (its separators occur in
            # order) — those lines go to the oracle, which applies the
            # reference's registration-priority semantics with the real
            # backtracking regexes (HttpdLogFormatDissector.java:174-204).
            row0 = np.stack([packed[u.row_offset, :B] for u in self.units])
            # Adaptive CSR: any line with more wildcard segments than the
            # current layout's slots -> double the slots and re-run (a few
            # bounded recompiles replace a per-line oracle cliff).
            if ((row0 & CSR_OVERFLOW_BIT) != 0).any() and self._grow_csr_slots():
                continue
            validity = (row0 & 1) != 0
            plausible = (row0 & 2) != 0
            valid = validity.any(axis=0)
            winner = np.where(valid, validity.argmax(axis=0), -1)
            # Definitely-bad filter: regex-accept implies plausible, so a
            # line implausible for EVERY registered format cannot be
            # accepted by any format regex — the oracle would reject it
            # identically, so it never needs the per-line re-parse.
            plausible_any = plausible.any(axis=0)
            if len(self.units) > 1:
                earlier_plausible = np.cumsum(plausible, axis=0) - plausible
                contested = np.take_along_axis(
                    earlier_plausible,
                    np.maximum(winner, 0)[None, :],
                    axis=0,
                )[0] > 0
                winner = np.where(contested, -1, winner)
                valid = valid & ~contested
            break
        if packed is None or not self._device_covers_all_formats:
            # No device verdict — or formats beyond the compiled prefix
            # exist that the device cannot even judge plausibility for.
            plausible_any = np.ones(B, dtype=bool)
        for i in overflow:
            # Truncated lines: the device only saw a prefix, so its
            # plausibility verdict does not apply — always oracle.
            valid[i] = False
            winner[i] = -1
            plausible_any[i] = True
        return (lines, buf, lengths, B, packed, valid, winner,
                plausible_any, overflow)

    # ------------------------------------------------------------------
    # device fault layer (docs/FAULTS.md): guarded execution, OOM bisect
    # + bucket clamp, wedge deadlines, compile demotion, oracle reroute.
    # ------------------------------------------------------------------

    def _run_guarded(self, work, label: str):
        """Run one blocking device operation under the fault layer's
        guard: the execution deadline (abandonable worker — a wedged XLA
        call expires instead of hanging the pipeline) when armed, and
        raw-error classification into the DeviceFault vocabulary."""
        from .device_faults import (
            DeviceCompileError,
            DeviceExecutionError,
            DeviceFault,
            DeviceOomError,
            classify_device_error,
            run_with_deadline,
        )

        deadline = self.execute_deadline_s
        try:
            if deadline:
                return run_with_deadline(work, deadline, label)
            return work()
        except DeviceFault:
            raise
        except Exception as e:  # noqa: BLE001 — classified
            kind = classify_device_error(e)
            err = {
                "oom": DeviceOomError,
                "compile": DeviceCompileError,
            }.get(kind, DeviceExecutionError)
            raise err(f"{type(e).__name__}: {e}") from e

    def _guarded_get(self, out, n_lines: int):
        """Guarded blocking fetch of an in-flight async dispatch: async
        execution errors surface exactly here, classified like a
        synchronous invoke's; the chaos hook fires once per execution
        at this blocking point."""
        chaos = self._device_chaos
        wedge_s = chaos.on_execute(n_lines) if chaos is not None else None

        def work():
            if wedge_s:
                time.sleep(wedge_s)
            return np.asarray(jax.device_get(out))

        return self._run_guarded(work, "fetch")

    def _invoke_device(self, fn, buf, lengths, n_lines: int):
        """ONE guarded synchronous device execution (dispatch + packed
        fetch) of an already-padded frame.  ``n_lines`` is the REAL
        line count — chaos thresholds key on it."""
        chaos = self._device_chaos
        wedge_s = chaos.on_execute(n_lines) if chaos is not None else None

        def work():
            if wedge_s:
                time.sleep(wedge_s)
            out = fn(jnp.asarray(buf), jnp.asarray(lengths))
            return np.asarray(jax.device_get(out))

        return self._run_guarded(work, "execute")

    def _execute_packed(self, buf, lengths, B: int,
                        emit_views: Optional[bool]):
        """Fresh guarded execution of one encoded batch (the re-dispatch
        path: nothing staged or in flight).  Honors a standing OOM clamp
        by pre-splitting into safe chunks; returns None when every field
        is host-only or the breaker has demoted the kernel.  Raises
        classified DeviceFault errors (absorbed by the caller)."""
        from ..observability import pipeline_stage

        fn = self._executor_for(emit_views)
        if fn is None:
            return None
        clamp = self._oom_clamp
        with pipeline_stage("device", items=B):
            if clamp is not None and B > clamp:
                return self._execute_chunks(fn, buf, lengths, B, clamp)
            return self._invoke_device(fn, buf, lengths, B)

    def _execute_chunks(self, fn, buf, lengths, B: int, chunk: int):
        """Execute rows [0, B) in ``chunk``-sized pieces (the standing
        clamp path) and reassemble the packed verdict columns."""
        parts = [
            self._execute_range(fn, buf, lengths, lo, min(B, lo + chunk), 0)
            for lo in range(0, B, chunk)
        ]
        return np.concatenate(parts, axis=1)

    def _execute_range(self, fn, buf, lengths, lo: int, hi: int,
                       depth: int):
        """Execute rows [lo, hi) padded to their own bucket; on
        RESOURCE_EXHAUSTED, bisect with bounded depth (each retry
        counted on ``device_oom_retries_total``).  Raises DeviceOomError
        when even the policy's minimum bucket OOMs — the caller then
        reroutes the batch to the oracle.  Per-row outputs are
        independent of batch geometry (per-line automata), so the
        reassembled columns are bit-identical to a single-dispatch run —
        the property the device-fault parity drills pin."""
        from ..observability import metrics
        from .device_faults import DeviceOomError

        n = hi - lo
        pb = self._bucket(n)
        sub_buf = buf[lo:hi]
        sub_len = lengths[lo:hi]
        if pb != n:
            sub_buf = np.pad(sub_buf, ((0, pb - n), (0, 0)))
            sub_len = np.pad(sub_len, (0, pb - n))
        try:
            return self._invoke_device(fn, sub_buf, sub_len, n)[:, :n]
        except DeviceOomError:
            pol = self.fault_policy
            if n <= pol.min_bucket or depth >= pol.oom_retries:
                raise
            metrics().increment("device_oom_retries_total")
            self._note_oom(pb)
            mid = lo + (n + 1) // 2
            left = self._execute_range(fn, buf, lengths, lo, mid, depth + 1)
            right = self._execute_range(fn, buf, lengths, mid, hi, depth + 1)
            return np.concatenate([left, right], axis=1)

    def _note_oom(self, failed_bucket: int) -> None:
        """Clamp bookkeeping: after ``oom_clamp_after`` device OOMs the
        parser PERMANENTLY caps its executed bucket below the failing
        size — future batches pre-split before any device_put
        (``device_bucket_clamped`` gauge; warn-once)."""
        from ..observability import log_warning_once, metrics

        self._oom_events += 1
        if self._oom_events < self.fault_policy.oom_clamp_after:
            return
        new_clamp = max(self.fault_policy.min_bucket, failed_bucket // 2)
        if self._oom_clamp is None or new_clamp < self._oom_clamp:
            self._oom_clamp = new_clamp
            metrics().gauge_set("device_bucket_clamped", new_clamp)
            log_warning_once(
                _LOG,
                "device: repeated RESOURCE_EXHAUSTED — max executed "
                "bucket permanently clamped (device_bucket_clamped "
                "gauge; oversized batches now pre-split before "
                "device_put)",
            )

    def _absorb_compile_fault(self, e) -> None:
        """A deterministic compile/lowering failure: demote this parser
        key to the host oracle PERMANENTLY (retrying the same shape
        would fail identically), warn once, count — never raise out of
        the parse."""
        from ..observability import log_warning_once, metrics
        from ..tracing import flight_event

        reg = metrics()
        reg.increment("device_compile_failures_total")
        flight_event("device_compile_fault",
                     error=f"{type(e).__name__}: {e}"[:200])
        if self._breaker.record_fault(permanent=True):
            reg.increment("device_demotions_total",
                          labels={"reason": "compile"})
            log_warning_once(
                _LOG,
                "device: executor compile failed — parser demoted to "
                "the host oracle (results stay exact; "
                "device_compile_failures_total counts, details at "
                "DEBUG)",
            )
        _LOG.debug("device compile fault: %s", e)

    def _absorb_device_fault(self, e, buf, lengths, B: int,
                             emit_views: Optional[bool]):
        """Central device-fault policy (docs/FAULTS.md): classify,
        count, bisect OOMs, and score the circuit breaker — compile
        failures demote the key permanently, repeated transient faults
        demote it until the cool-off (the device twin of
        ``demote_transport``).  Returns the recovered packed block, or
        None to reroute the WHOLE batch to the batched oracle host path
        (byte-identical output either way — the oracle is the exactness
        referee).  Never raises: a device fault costs throughput, never
        the batch."""
        from ..observability import log_warning_once, metrics
        from ..tracing import flight_event
        from .device_faults import DeviceFault, classify_device_error

        reg = metrics()
        kind = classify_device_error(e)
        reg.increment("device_faults_total", labels={"kind": kind})
        # The flight recorder's primary feed: this absorption is
        # deliberately silent on the request path, so the ring is the
        # only per-incident record that survives the process
        # (docs/OBSERVABILITY.md "Flight recorder").
        flight_event("device_fault", fault=kind, batch_rows=B,
                     error=f"{type(e).__name__}: {e}"[:200])
        if kind == "compile":
            self._absorb_compile_fault(e)
            return None
        if kind == "oom" and B > self.fault_policy.min_bucket:
            fn = self._executor_for(emit_views)
            if fn is not None:
                reg.increment("device_oom_retries_total")
                self._note_oom(self._bucket(B))
                try:
                    mid = (B + 1) // 2
                    return np.concatenate([
                        self._execute_range(fn, buf, lengths, 0, mid, 1),
                        self._execute_range(fn, buf, lengths, mid, B, 1),
                    ], axis=1)
                except DeviceFault as e2:
                    if classify_device_error(e2) == "compile":
                        self._absorb_compile_fault(e2)
                        return None
                    kind = classify_device_error(e2)
                    e = e2  # the residual fault falls through to reroute
        # Wedge / transient execute / OOM beyond rescue: reroute this
        # batch to the host oracle and score the breaker.
        reg.increment("device_fault_reroutes_total",
                      labels={"kind": kind})
        if self._breaker.record_fault():
            reg.increment("device_demotions_total",
                          labels={"reason": kind})
            log_warning_once(
                _LOG,
                "device: repeated device faults — kernel demoted to the "
                "host oracle until the breaker cool-off (results stay "
                "exact; device_faults_total{kind} counts, details at "
                "DEBUG)",
            )
        _LOG.debug("device fault rerouted to oracle (%s): %s", kind, e)
        return None

    def _materialize_packed(self, fetched) -> BatchResult:
        from ..observability import metrics, observe_stage

        reg = metrics()
        (lines, buf, lengths, B, packed, valid, winner, plausible_any,
         overflow) = fetched
        columns: Dict[str, Dict[str, np.ndarray]] = {}
        zeros_null = np.zeros(B, dtype=bool)
        # (fid, plan, big_rows, ovf_rows, wide, hi_row) per numeric column
        # with Long-overflow traffic — applied after the overrides dicts
        # exist (see the patch pass below the column loop).
        overflow_patches: List[tuple] = []

        def unit_get(u: FormatUnit, fid: str, comp: str) -> np.ndarray:
            block = packed[u.row_offset : u.row_offset + u.layout.n_rows]
            return u.layout.get(block, fid, comp)[:B]

        # Timestamps are taken unconditionally (perf_counter is ~20ns against
        # a multi-ms batch) so a tracer enabled mid-batch still records real
        # durations; trace.add() itself no-ops when disabled.
        t_columns = time.perf_counter()
        ts_cache: Dict[tuple, tuple] = {}

        def unit_ts(u: FormatUnit, ui: int, plan: _FieldPlan):
            """Decoded timestamp component bundle, cached per (unit, token,
            steps) so N requested outputs of one timestamp decode it once."""
            from .pipeline import ts_group_key

            key = (ui, ts_group_key(plan))
            got = ts_cache.get(key)
            if got is None:
                block = packed[u.row_offset : u.row_offset + u.layout.n_rows]
                comp, ok = u.layout.get_ts_components(block, plan)
                # Third element: the derive() memo sharing epoch/UTC/ISO
                # intermediates across this bundle's requested outputs.
                got = ({k: v[:B] for k, v in comp.items()}, ok[:B], {})
                ts_cache[key] = got
            return got

        for fid in self.requested:
            merged = self.plan_by_id[fid]
            group = self._plan_group(merged)
            if packed is None or group in ("host", "wild"):
                # host: oracle-only.  wild wildcards (.*) deliver
                # exclusively through overrides; wild CONCRETE fields
                # (query.img) get their span column filled directly by
                # _materialize_csr — fresh arrays, it writes into them.
                concrete_wild = (
                    packed is not None
                    and group == "wild"
                    and merged.comp != "*"
                    and not getattr(merged, "attr", "")
                )
                columns[fid] = {
                    "kind": "span",
                    "starts": np.zeros(B, dtype=np.int32),
                    "ends": np.zeros(B, dtype=np.int32),
                    "ok": np.zeros(B, dtype=bool),
                    "null": np.zeros(B, dtype=bool) if concrete_wild
                    else zeros_null,
                }
                continue
            if group == "span":
                col = {
                    "kind": "span",
                    "starts": np.zeros(B, dtype=np.int32),
                    "ends": np.zeros(B, dtype=np.int32),
                    "ok": np.zeros(B, dtype=bool),
                    "null": np.zeros(B, dtype=bool),
                    "amp": np.zeros(B, dtype=bool),
                    "fix": np.zeros(B, dtype=bool),
                    # Which per-row micro-materialization `fix` rows need:
                    # the final uri chain step decides (path: %-repair +
                    # percent-decode; query: %-repair only).
                    "fix_mode": (
                        merged.steps[-1][1]
                        if merged.steps and merged.steps[-1][0] == "uri"
                        else ""
                    ),
                }
            elif group == "obj":
                col = {
                    "kind": "obj",
                    "values": np.full(B, None, dtype=object),
                    "ok": np.zeros(B, dtype=bool),
                    "null": zeros_null,
                }
            else:
                col = {
                    "kind": "numeric",
                    "values": np.zeros(B, dtype=np.int64),
                    "null": np.zeros(B, dtype=bool),
                    "null_zero": np.zeros(B, dtype=bool),
                    "ok": np.zeros(B, dtype=bool),
                }
            for ui, u in enumerate(self.units):
                plan = u.plan_for(fid)
                if not self._unit_decodable(u, fid):
                    continue  # lines won by this unit go through the oracle
                sel = winner == ui
                if not sel.any():
                    continue
                if group == "span":
                    starts_col = unit_get(u, fid, "start")
                    col["starts"] = np.where(sel, starts_col, col["starts"])
                    col["ends"] = np.where(
                        sel, starts_col + unit_get(u, fid, "len"), col["ends"]
                    )
                    col["ok"] = np.where(
                        sel, unit_get(u, fid, "ok") != 0, col["ok"]
                    )
                    col["null"] = np.where(
                        sel, unit_get(u, fid, "null") != 0, col["null"]
                    )
                    col["amp"] = np.where(
                        sel, unit_get(u, fid, "amp") != 0, col["amp"]
                    )
                    col["fix"] = np.where(
                        sel, unit_get(u, fid, "fix") != 0, col["fix"]
                    )
                elif plan.kind == "ts":
                    comp, ok, memo = unit_ts(u, ui, plan)
                    values = timefields.derive(
                        comp, plan.comp, memo,
                        locale=getattr(plan.meta, "locale", None),
                    )
                    # A non-geo fill on a (possibly geo-shared, mixed-
                    # format) obj column: the Arrow dict/typed fast paths
                    # only see geo-written state and would null these
                    # rows — disable them for this column, either order.
                    col["mixed_fill"] = True
                    col["values"] = np.where(sel, values, col["values"])
                    col["ok"] = np.where(sel, ok, col["ok"])
                elif plan.kind == "geo":
                    from .pipeline import geo_group_key

                    _, column, table = plan.meta
                    block = packed[u.row_offset : u.row_offset + u.layout.n_rows]
                    key = geo_group_key(plan)
                    rows_idx = u.layout.get(block, key, "row")[:B]
                    ok = (u.layout.get(block, key, "ok") != 0)[:B]
                    arr = table.arrays[column][rows_idx]
                    if column in table.vocabs:
                        vocab = table.vocab_arrays[column]
                        values = vocab[arr]
                        # Keep the vocab CODES for the Arrow bridge: geo
                        # strings are low-cardinality, so the column can
                        # build as dictionary.take(codes) with zero
                        # per-row inference.  A second fill from a
                        # DIFFERENT vocab (mixed-format batch over
                        # distinct .mmdb tables) disables the fast path.
                        if "dict_codes" not in col:
                            col["dict_codes"] = np.full(B, -1, dtype=np.int64)
                            col["dict_values"] = vocab
                        if col.get("dict_values") is vocab:
                            col["dict_codes"] = np.where(
                                sel, arr.astype(np.int64), col["dict_codes"]
                            )
                        else:
                            col["dict_values"] = None
                    elif arr.dtype.kind == "f":
                        values = arr.astype(object)
                        values[np.isnan(arr)] = None
                        self._geo_typed_fill(col, sel, arr.astype(np.float64),
                                             np.isnan(arr), "f")
                    else:
                        values = arr.astype(object)
                        values[arr < 0] = None
                        self._geo_typed_fill(col, sel, arr.astype(np.int64),
                                             arr < 0, "i")
                    col["values"] = np.where(sel, values, col["values"])
                    col["ok"] = np.where(sel, ok, col["ok"])
                elif plan.kind == "muid":
                    from .pipeline import muid_group_key

                    key = muid_group_key(plan)
                    ok = unit_get(u, key, "ok") != 0
                    col["mixed_fill"] = True  # see the ts branch
                    if plan.comp == "ip":
                        u32 = (
                            unit_get(u, key, "ip").astype(np.int64)
                            & 0xFFFFFFFF
                        )
                        # Vectorized dotted-quad: a 256-entry octet-string
                        # vocab + object-array concatenation (no per-row
                        # Python loop).
                        octs = _OCTET_STRINGS
                        dot = np.full(B, ".", dtype=object)
                        vals = (
                            octs[(u32 >> 24) & 255] + dot
                            + octs[(u32 >> 16) & 255] + dot
                            + octs[(u32 >> 8) & 255] + dot
                            + octs[u32 & 255]
                        )
                        values = np.where(ok, vals, None)
                        col["values"] = np.where(sel, values, col["values"])
                    else:
                        comp_row = {
                            "epoch": "time", "processid": "pid",
                            "counter": "counter", "threadindex": "thread",
                        }[plan.comp]
                        values = (
                            unit_get(u, key, comp_row).astype(np.int64)
                            & 0xFFFFFFFF
                        )
                        if plan.comp == "epoch":
                            values = values * 1000
                        col["values"] = np.where(sel, values, col["values"])
                    col["ok"] = np.where(sel, ok, col["ok"])
                else:  # long / secmillis
                    is_null = unit_get(u, fid, "null") != 0
                    big = unit_get(u, fid, "big") != 0
                    hi_row = unit_get(u, fid, "hi")
                    values, ovf, wide = postproc.combine_long_limbs(
                        hi_row,
                        unit_get(u, fid, "lo"),
                        unit_get(u, fid, "d18"),
                        unit_get(u, fid, "lo_digits"),
                        is_null,
                    )
                    # Overflow class (reference FORMAT_NUMBER has no width
                    # bound): 19-digit values beyond Long.MAX (exact in
                    # the uint64 frame) and >19-digit runs (hi row carries
                    # the span for a host byte-patch).  Both deliver via
                    # the post-loop patch, not the int64 column; is_null
                    # never overlaps (a dash is 1 byte).
                    ovf = ovf & ~big & ~is_null
                    row_ok = unit_get(u, fid, "ok") != 0
                    of_sel = sel & row_ok & valid & (ovf | big)
                    if of_sel.any():
                        overflow_patches.append((
                            fid, plan, of_sel & big, of_sel & ovf,
                            wide, hi_row,
                        ))
                    if plan.kind == "secmillis":
                        values = values * 1000 + unit_get(u, fid, "milli")
                    if plan.scale != 1:
                        values = values * plan.scale
                    if plan.null_mode == "zero_null":
                        is_null = is_null | (values == 0)
                    col["values"] = np.where(sel, values, col["values"])
                    col["null"] = np.where(sel, is_null, col["null"])
                    col["ok"] = np.where(sel, row_ok, col["ok"])
                    if plan.null_mode == "dash_zero":
                        col["null_zero"] = np.where(sel, True, col["null_zero"])
            columns[fid] = col
        observe_stage("columns", time.perf_counter() - t_columns, items=B)

        # Host fallback: invalid lines entirely; host-only fields for every line.
        # Numeric coercion follows the kind of the format that won the
        # line (a field can be numeric under one format and a plain
        # string under another); unknown winner -> merged kind.  A winner
        # that resolves the field as "host" (multi-producer) dispatches
        # on the producing dissector's setter casts instead — the
        # resolution is line-invariant per (fields, winner) and compiled
        # into delivery_plan below.
        overrides: Dict[str, Any] = {
            fid: (_LazyWildcard() if fid.endswith(".*") else {})
            for fid in columns
        }
        # Reference Long-overflow delivery (the former largest self-imposed
        # reject class): 19-digit values beyond Long.MAX deliver their
        # exact frame value, >19-digit runs are byte-patched from the
        # buffer — both as overrides, replaying what the oracle's
        # STRING-cast path would store, WITHOUT a per-line re-parse.
        # Ineligible plans (chained/scaled/zero_null/odd casts) and big
        # spans whose unchecked tail turns out non-digit demote to the
        # full oracle, which applies the exact semantics.
        from .pipeline import _SPAN_BITS

        demoted: set = set()
        span_mask = (1 << _SPAN_BITS) - 1
        for fid, plan, big_rows, ovf_rows, wide, hi_row in overflow_patches:
            mode = self._overflow_delivery.get(fid, "oracle")
            eligible = (
                plan.kind == "long" and not plan.steps and plan.scale == 1
                and plan.null_mode != "zero_null" and mode in ("int", "null")
            )
            if not eligible:
                demoted.update(
                    int(i) for i in np.nonzero(big_rows | ovf_rows)[0]
                )
                continue
            ov = overrides[fid]
            if mode == "null":
                # LONG-only casts: Long.parseLong fails beyond the range,
                # the null is delivered (policy ALWAYS), the record reads
                # None.
                for i in np.nonzero(big_rows | ovf_rows)[0]:
                    ov[int(i)] = None
                continue
            for i in np.nonzero(ovf_rows)[0]:
                ov[int(i)] = int(wide[i])
            for i in np.nonzero(big_rows)[0]:
                i = int(i)
                word = int(hi_row[i])
                raw = bytes(
                    buf[i, word & span_mask:
                        (word & span_mask) + (word >> _SPAN_BITS)]
                )
                if raw.isdigit():
                    ov[i] = int(raw)
                else:
                    # The tail beyond the 19-byte device window is not all
                    # digits: the token regex would reject — full oracle.
                    demoted.add(i)
                    ov.pop(i, None)
        for i in demoted:
            valid[i] = False
            winner[i] = -1
            plausible_any[i] = True
            for fid in self.requested:
                overrides[fid].pop(i, None)
        # Invalid AND implausible-for-all-formats: definitely bad, counted
        # without an oracle visit (the single biggest fallback cost on
        # hostile corpora — garbage lines are almost never plausible).
        inv = ~valid
        bad = int(np.count_nonzero(inv & ~plausible_any))
        invalid_rows = set(
            int(i) for i in np.nonzero(inv & plausible_any)[0]
        )
        # Per-row reject ledger: every row that ends the batch invalid
        # carries a stable reason (the jobs reject channel and the fuzz
        # suite both pin the vocabulary): "implausible" = no format even
        # plausible, rejected without an oracle visit; "oracle_reject" =
        # the oracle parsed and refused (DissectionFailure);
        # "oracle_error" = the oracle engine ITSELF failed on the line.
        reject_reasons: Dict[int, str] = {
            int(i): "implausible" for i in np.nonzero(inv & ~plausible_any)[0]
        }
        # Rows the oracle must visit: lines no automaton accepted (but some
        # format could still plausibly match), plus lines whose winning
        # format can't supply every requested field on device.
        need_oracle = set(invalid_rows)
        for ui, flds in enumerate(self._unit_oracle_fields):
            if flds:
                need_oracle.update(int(r) for r in np.nonzero(winner == ui)[0])
        # Batched rescue, started BEFORE the CSR materialization: the
        # rejected rows are framed once and parsed through the reused
        # per-format fastline program; on a multi-worker assembly pool
        # the parse runs on a pool thread and overlaps the numpy-heavy
        # CSR stage below (rescue no longer serializes behind the whole
        # materialization).  CSR-failed rows (rare) are parsed inline
        # afterwards.
        t_submit = time.perf_counter()
        engine_before = self._oracle_engine_tally()
        rescue_rows = sorted(need_oracle)
        collect_rescue = self._start_rescue(rescue_rows, lines)
        rescue_wall = time.perf_counter() - t_submit
        # Device CSR wildcards (query params): build the per-line override
        # values from the packed segment table; a resilientUrlDecode failure
        # is exactly a line the host engine fails, so those rows drop to
        # invalid and take the oracle (which rejects them identically).
        t_csr = time.perf_counter()
        csr_failed = self._materialize_csr(
            packed, winner, valid, overrides, columns, buf, B
        )
        extra_rows: List[int] = []
        for i in csr_failed:
            valid[i] = False
            winner[i] = -1
            for fid in self.requested:
                overrides[fid].pop(i, None)
            invalid_rows.add(i)
            if i not in need_oracle:
                need_oracle.add(i)
                extra_rows.append(i)
        observe_stage("csr_materialize", time.perf_counter() - t_csr, items=B)
        # Escaped-quote decode accounting (round 18): lines the device
        # claimed THROUGH the escape-parity mask — the winning unit's
        # ESC_QUOTE_BIT on rows that survived every demotion above.
        # These are exactly the lines that pre-round-18 routed to the
        # host rescue as device_reject.
        escaped_quote_rows = 0
        if packed is not None and self.units:
            from .pipeline import ESC_QUOTE_BIT

            esc_bits = np.stack([
                (packed[u.row_offset, :B] & ESC_QUOTE_BIT) != 0
                for u in self.units
            ])
            esc_won = np.take_along_axis(
                esc_bits, np.maximum(winner, 0)[None, :], axis=0
            )[0]
            escaped_quote_rows = int(np.count_nonzero(esc_won & valid))
            if escaped_quote_rows:
                reg.increment(
                    "device_escaped_quote_lines_total", escaped_quote_rows
                )
        # Routed-line accounting by reject class (batch granularity): WHY
        # each line left the device-only path.  overflow = truncated lines
        # the device judged on a prefix; device_reject = no automaton
        # accepted but some format stayed plausible; host_fields = the
        # winning format cannot supply every requested field on device.
        overflow_rows = {int(i) for i in overflow if 0 <= int(i) < B}
        rescue_reasons = {"overflow": 0, "device_reject": 0, "host_fields": 0}
        if bad:
            reg.increment("definitely_bad_lines_total", bad)
        if need_oracle:
            # Disjoint by construction (overflow rows are forced invalid
            # in _fetch_packed; the explicit exclusions keep the three
            # classes summing to len(need_oracle) even if that drifts).
            rescue_reasons["overflow"] = len(overflow_rows & need_oracle)
            rescue_reasons["device_reject"] = len(
                invalid_rows - overflow_rows
            )
            rescue_reasons["host_fields"] = len(
                need_oracle - invalid_rows - overflow_rows
            )
            for reason, n in rescue_reasons.items():
                if n:
                    reg.increment("oracle_routed_lines_total", n,
                                  labels={"reason": reason})
            # Per-field census of the host_fields residual: which requested
            # fields are still forcing whole-line oracle routing.  A row on
            # the host_fields path charges every oracle field of its winning
            # unit — the set the next device lane must cover to free it.
            if rescue_reasons["host_fields"]:
                hf = np.fromiter(
                    (need_oracle - invalid_rows - overflow_rows),
                    dtype=np.int64,
                )
                hf_win = winner[hf]
                cnt = np.bincount(
                    hf_win[hf_win >= 0], minlength=len(self.units)
                )
                for ui, flds in enumerate(self._unit_oracle_fields):
                    n_unit = int(cnt[ui]) if ui < cnt.shape[0] else 0
                    if not n_unit or not flds:
                        continue
                    for fid in flds:
                        reg.increment(
                            "host_field_lines_total", n_unit,
                            labels={"field": _bounded_field_label(fid)},
                        )
        t_oracle = time.perf_counter()
        oracle_rows_sorted = sorted(need_oracle)
        results_by_row = dict(zip(rescue_rows, collect_rescue()))
        if extra_rows:
            extra_rows.sort()
            results_by_row.update(zip(
                extra_rows,
                self._run_oracle_many([lines[i] for i in extra_rows]),
            ))
        oracle_results = [results_by_row[i] for i in oracle_rows_sorted]
        # Fully-resolved per-(fields, winner) delivery plan: field split,
        # override dict, and the coercion decision (device plan group +
        # setter casts) are all line-invariant — resolving them per VALUE
        # was ~40% of the rescue stage on top of the raw parses, which is
        # exactly the kind of drift the bench's rescue-model validation
        # (combined_rescue config) exists to catch.
        plan_cache: Dict[Tuple[bool, int], Tuple[list, list]] = {}

        def delivery_plan(fields, w, is_invalid):
            # Keyed on what DETERMINES the fields list ((is_invalid, w)),
            # not its identity — id() is only stable because both lists
            # happen to be parser-lifetime attributes today.
            key = (is_invalid, w)
            got = plan_cache.get(key)
            if got is None:
                concrete, wild = [], []
                for fid in fields:
                    if fid.endswith(".*"):
                        wild.append((fid, overrides[fid], fid[:-1]))
                        continue
                    plan = (
                        self.units[w].plan_for(fid) if w >= 0
                        else self.plan_by_id[fid]
                    )
                    flags = self._cast_flags.get(fid)
                    if self._plan_group(plan) == "numeric":
                        mode = "num"
                    elif flags and (flags[0] or flags[1]):
                        # LONG-then-DOUBLE fallthrough, like _coerce_casts
                        # (same _cast_flags source).
                        mode = flags
                    else:
                        mode = "plain"
                    concrete.append((fid, overrides[fid], mode))
                got = (concrete, wild)
                plan_cache[key] = got
            return got

        oracle_rescued = oracle_rejected = engine_errors = 0
        for i, values in zip(oracle_rows_sorted, oracle_results):
            is_invalid = i in invalid_rows
            fields_needed = (
                self.requested
                if is_invalid
                else self._unit_oracle_fields[winner[i]]
            )
            if values is None or isinstance(values, OracleEngineError):
                # None = the oracle parsed and refused (the reference's
                # bad-line verdict).  OracleEngineError = the oracle
                # ITSELF failed — surfaced as a counted, reasoned reject
                # (never a raise, never a silent None): a device-valid
                # line keeps its device columns with the host fields
                # unresolved; an invalid line rejects as oracle_error.
                oracle_rejected += 1
                if isinstance(values, OracleEngineError):
                    engine_errors += 1
                    from ..observability import log_warning_once

                    # STATIC warn-once key (per-line error text would
                    # grow the warn-once table without bound on a
                    # hostile corpus); the exact error rides the reject
                    # table and DEBUG.
                    log_warning_once(
                        _LOG,
                        "host oracle engine failed on one or more lines;"
                        " surfaced as oracle_error rejects (details at "
                        "DEBUG / in the reject channel)",
                    )
                    _LOG.debug("oracle engine fault on row %d: %s",
                               i, values.error)
                if is_invalid:
                    bad += 1
                    reject_reasons[i] = (
                        "oracle_error"
                        if isinstance(values, OracleEngineError)
                        else "oracle_reject"
                    )
                continue
            if is_invalid:
                valid[i] = True
                oracle_rescued += 1
            concrete, wild = delivery_plan(
                fields_needed, int(winner[i]), is_invalid
            )
            for fid, ov, mode in concrete:
                v = values.get(fid)
                if v is None or mode == "plain":
                    ov[i] = v
                elif mode == "num":
                    try:
                        ov[i] = int(v)
                    except (TypeError, ValueError):
                        ov[i] = None
                else:  # setter casts: LONG then DOUBLE then raw
                    ov[i] = _apply_setter_casts(v, mode[0], mode[1])
            for fid, ov, prefix in wild:
                # Wildcard target: deliver {relative.name: value} built
                # from every concrete field under the prefix (the oracle
                # stores them under their full TYPE:path names).
                ov[i] = {
                    k[len(prefix):]: v
                    for k, v in values.items()
                    if k.startswith(prefix)
                }
        # oracle_fallback measures the wall time rescue ADDED to the batch:
        # submit/framing cost plus the blocked wait + delivery — parse
        # time hidden under the CSR stage by the pool thread is excluded
        # (that overlap is the point of the batched rescue).
        rescue_wall += time.perf_counter() - t_oracle
        observe_stage("oracle_fallback", rescue_wall, items=len(need_oracle))
        if oracle_rescued:
            reg.increment("oracle_rescued_lines_total", oracle_rescued)
        if oracle_rejected:
            reg.increment("oracle_rejected_lines_total", oracle_rejected)
        if engine_errors:
            reg.increment("oracle_engine_errors_total", engine_errors)
        self._fold_oracle_engine_tally(engine_before)

        good = int(B - bad)
        reg.increment("good_lines_total", good)
        if bad:
            reg.increment("bad_lines_total", bad)
        # Device-emitted Arrow view rows (4 per span field, after the unit
        # rows): handed to the Arrow bridge, which interleaves them into
        # string_view structs without touching the byte buffer.  Overflow
        # rows are flagged dirty — the device judged a truncated prefix,
        # so its views for those rows are not trustworthy.
        device_views = None
        dirty_rows = None
        view_block = None
        view_fields = getattr(self, "_views_fields", None)
        if packed is not None and view_fields:
            k0 = (
                self.units[-1].row_offset + self.units[-1].layout.n_rows
                if self.units else 0
            )
            if packed.shape[0] >= k0 + 4 * len(view_fields):
                # Keep ONLY the trailing view block alive on the result
                # (contiguous copy): pinning the whole packed fetch would
                # retain several MB of unit rows the bridge never reads.
                view_block = packed[k0: k0 + 4 * len(view_fields)].copy()
                device_views = {
                    fid: 4 * i for i, fid in enumerate(view_fields)
                }
                dirty_rows = np.asarray(
                    [i for i in overflow if i < B], dtype=np.int64
                )
        result = BatchResult(
            # _encode_batch already listed the caller's lines; _BlobLines
            # stays lazy (its rows materialize only when indexed).
            lines, buf[:B], lengths[:B], valid, columns, overrides,
            good, bad, format_index=winner[:B], oracle_rows=len(need_oracle),
            packed=view_block, device_views=device_views,
            dirty_rows=dirty_rows, assembly_pool=self.assembly_pool(),
        )
        # Rescue composition for this batch: per-reason routed counts and
        # the wall seconds the rescue added (the bench's stdout
        # composition line and the smoke tool read these).
        result.rescue_reasons = rescue_reasons
        result.rescue_wall_s = rescue_wall
        result.escaped_quote_rows = escaped_quote_rows
        result.reject_reasons = reject_reasons
        result.oracle_row_ids = np.asarray(oracle_rows_sorted, dtype=np.int64)
        return result

    def _materialize_csr(
        self, packed, winner, valid, overrides, columns, buf, B
    ) -> set:
        """Materialize device CSR wildcard groups (query params / cookies /
        set-cookies) from the packed segment table.

        Vectorized: emitted segments are flattened with numpy gathers into
        one flat byte buffer per (names, values); per-segment Python work is
        one bytes-slice decode.  Concrete fields (``query.img``) are matched
        by name and written straight into their span COLUMN (no per-row
        objects at all); wildcard ``.*`` fields build their per-row dicts
        from the flat buffers.  Only rows that need per-value Python —
        resilientUrlDecode (``%``/``+`` values), uri-chain name %-repair,
        or whitespace/non-ASCII trimming at cookie name/value edges — take
        the per-row fallback loop.  Returns rows whose value decode failed
        (the host engine fails those lines; caller invalidates them so the
        oracle re-rejects identically)."""
        from .pipeline import csr_group_key

        failed: set = set()
        if packed is None:
            return failed
        L = buf.shape[1]
        buf_flat = buf.reshape(-1)
        for ui, u in enumerate(self.units):
            qs_plans = [
                (fid, u.plan_for(fid))
                for fid in self.requested
                if u.plan_for(fid).kind == "qscsr"
                and self._unit_decodable(u, fid)
            ]
            if not qs_plans:
                continue
            rows = np.nonzero((winner == ui) & valid)[0]
            if rows.size == 0:
                continue
            block = packed[u.row_offset : u.row_offset + u.layout.n_rows]
            by_key: Dict[str, List] = {}
            for fid, p in qs_plans:
                by_key.setdefault(csr_group_key(p), []).append((fid, p))
            for key, flist in by_key.items():
                ok = u.layout.get(block, key, "ok") != 0
                uri_chain = bool(flist[0][1].steps)
                cookie = flist[0][1].meta == "cookie"
                setcookie = flist[0][1].meta == "setcookie"
                K = u.layout.csr_slots

                def mat(comp: str) -> np.ndarray:
                    return np.stack([
                        u.layout.get(block, key, f"s{k}_{comp}")[:B][rows]
                        for k in range(K)
                    ])

                SS, NL, VS, VL = mat("start"), mat("nlen"), mat("vstart"), mat("vlen")
                HE = mat("eq").astype(bool)
                DC = mat("dec").astype(bool)
                ND = mat("ndec").astype(bool)
                ok_r = ok[rows]
                # A segment is emitted iff its name is non-empty: empty
                # slots pack nlen 0, and "=value" segments (empty name)
                # match nothing — QueryStringFieldDissector skips them.
                # Set-cookie additionally requires the device emit bit.
                emit = (NL > 0) & ok_r[None, :]
                if setcookie:
                    emit &= HE

                # Segments needing per-value Python: url-decode (%/+ in
                # value), uri-chain name %-repair, or cookie/set-cookie
                # whitespace-or-non-ASCII trim at name/value edges (host
                # str.strip() also eats \x1c-\x1f and unicode whitespace;
                # >= 0x80 edge bytes conservatively take the slow path).
                def edge(S, N):
                    has = N > 0
                    a = rows[None, :] * L + S
                    first = buf_flat[np.where(has, a, 0)]
                    last = buf_flat[np.where(has, a + N - 1, 0)]
                    e = (first <= 0x20) | (first >= 0x80)
                    e |= (last <= 0x20) | (last >= 0x80)
                    return has & e

                def direct_hard(fl):
                    # Direct-capture rows whose flagged values the
                    # vectorized left-to-right decode cannot prove: a
                    # '%' without two in-segment hex digits (the
                    # un-repaired host decoder may chop it, raise, or
                    # read %uXXXX as UTF-16) or a raw byte >= 0x80.
                    hard = np.zeros(fl.shape[1], dtype=bool)
                    fk, fj = np.nonzero(fl)
                    if fk.size == 0:
                        return hard
                    v_l = np.where(HE[fk, fj], VL[fk, fj], 0).astype(
                        np.int64
                    )
                    f_off = np.zeros(fk.size + 1, dtype=np.int64)
                    np.cumsum(v_l, out=f_off[1:])
                    gidx = np.repeat(
                        (rows[fj] * L + VS[fk, fj]).astype(np.int64)
                        - f_off[:-1], v_l,
                    ) + np.arange(int(f_off[-1]), dtype=np.int64)
                    _, _, bad = _qs_value_decode(buf_flat[gidx], f_off)
                    hard[fj[bad]] = True
                    return hard

                if setcookie:
                    flag = edge(SS, NL)
                elif cookie:
                    flag = DC | edge(SS, NL) | edge(VS, VL)
                elif uri_chain:
                    # Names needing %-repair keep the per-row loop;
                    # flagged VALUES decode in the vectorized lane below
                    # (device-valid uri-chain segments are clean ASCII
                    # by the split discipline, so the left-to-right
                    # rule is exact).
                    flag = ND
                else:
                    flag = DC & direct_hard(DC & emit)[None, :]
                flag &= emit
                row_flag = flag.any(axis=0)
                vrows = rows[~row_flag]
                py_rows = rows[row_flag]

                need_dicts = any(p.comp == "*" for _, p in flist)
                dicts: Dict[int, Optional[Dict[str, str]]] = {}

                # ---- vectorized path: flatten emitted segments ----------
                emv = emit[:, ~row_flag]
                pr, pk = np.nonzero(emv.T)  # row-major: slot order per row
                n_seg = pr.size
                nb, non = b"", np.zeros(1, dtype=np.int64)
                vb, nov = b"", np.zeros(1, dtype=np.int64)
                seg_high = np.zeros(0, dtype=bool)
                if n_seg:
                    sub = (pk, pr)
                    s_row = vrows[pr]
                    s_ss = SS[:, ~row_flag][sub]
                    s_nl = NL[:, ~row_flag][sub]
                    s_vs = VS[:, ~row_flag][sub]
                    s_vl = np.where(
                        HE[:, ~row_flag][sub] | setcookie,
                        VL[:, ~row_flag][sub], 0,
                    )

                    def flat(starts, lens):
                        off = np.zeros(len(lens) + 1, dtype=np.int64)
                        np.cumsum(lens, out=off[1:])
                        idx = np.repeat(
                            s_row * L + starts - off[:-1], lens
                        ) + np.arange(int(off[-1]), dtype=np.int64)
                        return buf_flat[idx].tobytes(), off

                    nb, non = flat(s_ss, s_nl)
                    nb_np = np.frombuffer(nb, dtype=np.uint8)
                    if nb_np.size:
                        seg_high = np.add.reduceat(
                            (nb_np >= 0x80).astype(np.int64), non[:-1]
                        ) > 0
                    else:
                        seg_high = np.zeros(n_seg, dtype=bool)
                    if need_dicts:
                        vb, nov = flat(s_vs, s_vl)
                else:
                    s_row = s_ss = s_nl = s_vs = s_vl = np.empty(
                        0, dtype=np.int64
                    )

                # ---- vectorized value decode: flagged (%/+/encode-set)
                # values of query chains decode here with compact
                # gathers — the exact fix+resilientUrlDecode result for
                # the segment classes proven above; only name repair,
                # cookie edge trims, and hard direct escapes still pay
                # the per-row loop.
                dec_pos = np.full(n_seg, -1, dtype=np.int64)
                darr = np.zeros(0, dtype=np.uint8)
                d_off = np.zeros(1, dtype=np.int64)
                if n_seg and not (cookie or setcookie):
                    s_dc = DC[:, ~row_flag][sub]
                    dec_idx = np.nonzero(s_dc)[0]
                    if dec_idx.size:
                        dec_pos[dec_idx] = np.arange(dec_idx.size)
                        fl_l = s_vl[dec_idx].astype(np.int64)
                        f_off = np.zeros(dec_idx.size + 1, dtype=np.int64)
                        np.cumsum(fl_l, out=f_off[1:])
                        gidx = np.repeat(
                            (s_row[dec_idx] * L + s_vs[dec_idx]).astype(
                                np.int64
                            ) - f_off[:-1], fl_l,
                        ) + np.arange(int(f_off[-1]), dtype=np.int64)
                        darr, d_off, _ = _qs_value_decode(
                            buf_flat[gidx], f_off
                        )
                        if need_dicts:
                            # Splice the decoded (UTF-8-transcoded)
                            # bytes into the flat wildcard value buffer
                            # in place of the raw spans.
                            uarr, u_off = _latin1_to_utf8(darr, d_off)
                            vb_np = np.frombuffer(vb, dtype=np.uint8)
                            lens = np.diff(nov)
                            lens2 = lens.copy()
                            lens2[dec_idx] = np.diff(u_off)
                            nov2 = np.zeros_like(nov)
                            np.cumsum(lens2, out=nov2[1:])
                            new_vb = np.empty(int(nov2[-1]), dtype=np.uint8)
                            keep_i = np.nonzero(~s_dc)[0]
                            _seg_scatter(new_vb, nov2[keep_i], vb_np,
                                         nov[keep_i], lens[keep_i])
                            _seg_scatter(new_vb, nov2[dec_idx], uarr,
                                         u_off[:-1], lens2[dec_idx])
                            vb, nov = new_vb.tobytes(), nov2

                def match_comp(comp: str) -> np.ndarray:
                    # Byte-wise name match with ASCII case fold; Python
                    # strings are never built for the common case.
                    # Segments containing ANY high byte decode individually
                    # regardless of byte length: host str.lower() can
                    # change the UTF-8 length (e.g. U+212A Kelvin sign,
                    # 3 bytes -> 'k', 1 byte), so a raw-length pre-filter
                    # would silently miss them.
                    comp_b = comp.encode("utf-8")
                    if n_seg == 0 or len(comp_b) == 0:
                        return np.empty(0, dtype=np.int64)
                    mlen = np.nonzero((s_nl == len(comp_b)) & ~seg_high)[0]
                    out = mlen
                    if mlen.size:
                        idx = (
                            (s_row * L + s_ss)[mlen][:, None]
                            + np.arange(len(comp_b))
                        )
                        g = buf_flat[idx]
                        upper = (g >= 0x41) & (g <= 0x5A)
                        folded = np.where(upper, g | 0x20, g)
                        target = np.frombuffer(comp_b, dtype=np.uint8)
                        out = mlen[(folded == target).all(axis=1)]
                    extra = [
                        j
                        for j in np.nonzero(seg_high)[0].tolist()
                        if nb[non[j] : non[j + 1]]
                        .decode("utf-8", "replace").lower() == comp
                    ]
                    if extra:
                        out = np.concatenate(
                            [out, np.asarray(extra, dtype=np.int64)]
                        )
                        out.sort()
                    return out

                match_cache: Dict[str, np.ndarray] = {}
                attrs_cache: Dict[str, dict] = {}
                for fid, p in flist:
                    if p.comp == "*":
                        continue
                    m = match_cache.get(p.comp)
                    if m is None:
                        m = match_cache[p.comp] = match_comp(p.comp)
                    if getattr(p, "attr", ""):
                        if isinstance(p.attr, tuple):
                            # Remapped screen-resolution param: split the
                            # matched segment's value host-side.
                            self._deliver_sres_attr(
                                fid, p, m, s_row, s_vs, s_vl, buf, overrides,
                                decoded=(dec_pos, darr, d_off),
                            )
                            continue
                        # Per-cookie attribute: parse the matched cookie's
                        # text once per row (host parse_attrs — the exact
                        # per-line semantics) and deliver via overrides.
                        self._deliver_setcookie_attr(
                            fid, p, m, s_row, s_vs, s_vl, buf, overrides,
                            attrs_cache,
                        )
                        continue
                    # Concrete field -> span column writes (duplicate rows:
                    # numpy fancy assignment keeps the LAST segment, the
                    # host's overwrite order).
                    col = columns[fid]
                    col["ok"][vrows] = True
                    col["null"][vrows] = True
                    if m.size:
                        mr = s_row[m]
                        col["starts"][mr] = s_vs[m]
                        col["ends"][mr] = s_vs[m] + s_vl[m]
                        col["null"][mr] = False
                        # Rows whose LAST matched segment was decoded
                        # deliver the decoded value via override — span
                        # columns can only point at raw buffer bytes.
                        last = np.ones(m.size, dtype=bool)
                        if m.size > 1:
                            last[:-1] = mr[:-1] != mr[1:]
                        for j in m[last & (dec_pos[m] >= 0)].tolist():
                            jj = int(dec_pos[j])
                            overrides[fid][int(s_row[j])] = bytes(
                                darr[d_off[jj]:d_off[jj + 1]]
                            ).decode("latin-1")

                # ---- per-row fallback: decode/repair/trim segments ------
                if py_rows.size:
                    self._materialize_csr_slow(
                        py_rows, rows, ok, SS, NL, HE, DC, ND, VS, VL,
                        uri_chain, cookie, setcookie, buf, dicts, failed,
                        need_dicts, flist, overrides, columns,
                    )

                if need_dicts:
                    for fid, p in flist:
                        if p.comp != "*":
                            continue
                        tgt = overrides[fid]
                        if isinstance(tgt, _LazyWildcard):
                            if vrows.size:
                                tgt.add_chunk(
                                    vrows, s_row, nb, non, vb, nov, seg_high
                                )
                            tgt.eager.update(dicts)
                        else:  # pragma: no cover — defensive
                            for i, d in dicts.items():
                                tgt[i] = d
        return failed

    def _coerce_casts(self, fid: str, value):
        """Type a host-materialized value by the producing dissector's
        casts (LONG > DOUBLE > STRING — the reference's setter-signature
        dispatch), shared by the oracle-override path and the remapped
        sub-dissection deliveries."""
        casts = self._host_casts.get(fid)
        if casts is not None and value is not None:
            has_long, has_double = self._cast_flags.get(fid, (False, False))
            return _apply_setter_casts(value, has_long, has_double)
        return value

    @staticmethod
    def _sres_value(attr, text):
        """ScreenResolutionDissector semantics for one remapped value:
        split on the configured separator; None when absent/empty (nothing
        delivered); parts beyond the second are ignored.  The single
        implementation shared by the vectorized and per-row paths."""
        _, sep, part = attr
        if text and sep in text:
            parts = text.split(sep)
            return parts[0] if part == "width" else parts[1]
        return None

    @staticmethod
    def _last_matched_texts(m, s_row, s_vs, s_vl, buf, decoded=None):
        """Yield (row, segment text) for the LAST matched segment per row
        — the host cache-overwrite rule shared by every qscsr attr
        delivery (duplicate same-name segments dissect only the last).
        ``decoded`` = (dec_pos, darr, d_off) supplies the vector-decoded
        value for segments the flat lane already url-decoded."""
        last: Dict[int, int] = {}
        for j in m.tolist():
            last[int(s_row[j])] = j
        for row, j in last.items():
            if decoded is not None and decoded[0][j] >= 0:
                dec_pos, darr, d_off = decoded
                jj = int(dec_pos[j])
                yield row, bytes(darr[d_off[jj]:d_off[jj + 1]]).decode(
                    "latin-1"
                )
                continue
            v0 = int(s_vs[j])
            yield row, bytes(buf[row, v0 : v0 + int(s_vl[j])]).decode(
                "utf-8", "replace"
            )

    def _deliver_sres_attr(
        self, fid, p, m, s_row, s_vs, s_vl, buf, overrides, decoded=None
    ) -> None:
        """Deliver a remapped screen-resolution width/height for matched
        segments."""
        tgt = overrides[fid]
        for row, value in self._last_matched_texts(
            m, s_row, s_vs, s_vl, buf, decoded
        ):
            out = self._sres_value(p.attr, value)
            if out is not None:
                tgt[row] = self._coerce_casts(fid, out)

    @staticmethod
    def _setcookie_attr_key(fid: str, attr: str) -> str:
        """parse_attrs key for a requested attr field: the TIME.EPOCH twin
        of expires reads the millis value, everything else its own name."""
        if attr == "expires" and fid.startswith("TIME.EPOCH:"):
            return "expires_epoch"
        return attr

    def _deliver_setcookie_attr(
        self, fid, p, m, s_row, s_vs, s_vl, buf, overrides, attrs_cache
    ) -> None:
        """Deliver one per-cookie attribute field for matched segments.
        With duplicate same-name cookies, the host dissects only the LAST
        delivery (the parsable cache entry is overwritten before the
        sub-dissector consumes it), so only the last matched segment per
        row is parsed; its absent attributes read None.  ``attrs_cache``
        memoizes parse_attrs by cookie text so N requested attributes of
        one cookie split/date-parse it once."""
        from ..dissectors.cookies import ResponseSetCookieDissector

        key = self._setcookie_attr_key(fid, p.attr)
        tgt = overrides[fid]
        for row, text in self._last_matched_texts(m, s_row, s_vs, s_vl, buf):
            attrs = attrs_cache.get(text)
            if attrs is None:
                attrs = attrs_cache[text] = (
                    ResponseSetCookieDissector.parse_attrs(text)
                )
            if key in attrs:
                tgt[row] = attrs[key]

    def _materialize_csr_slow(
        self, py_rows, rows, ok, SS, NL, HE, DC, ND, VS, VL,
        uri_chain, cookie, setcookie, buf, dicts, failed,
        need_dicts, flist, overrides, columns,
    ) -> None:
        """Per-row CSR materialization for rows with segments that need
        per-value Python (url-decode, %-repair, edge trimming) — the exact
        host semantics, including decode-failure -> failed row."""
        from ..dissectors.cookies import ResponseSetCookieDissector
        from ..dissectors.utils import resilient_url_decode

        attrs_cache: Dict[str, dict] = {}
        pos_of = {int(r): j for j, r in enumerate(rows.tolist())}
        for i in py_rows.tolist():
            i = int(i)
            j = pos_of[i]
            d: Optional[Dict[str, str]] = {}
            if ok[i]:
                for k in range(SS.shape[0]):
                    nlen = int(NL[k, j])
                    has_eq = bool(HE[k, j])
                    if setcookie:
                        if not has_eq:
                            continue
                        s0 = int(SS[k, j])
                        name = (
                            bytes(buf[i, s0 : s0 + nlen])
                            .decode("utf-8", "replace")
                            .strip()
                            .lower()
                        )
                        if name == "":
                            continue
                        v0 = int(VS[k, j])
                        d[name] = bytes(
                            buf[i, v0 : v0 + int(VL[k, j])]
                        ).decode("utf-8", "replace")
                        continue
                    if nlen == 0 and not has_eq:
                        continue  # empty slot / skipped empty segment
                    s0 = int(SS[k, j])
                    name = bytes(buf[i, s0 : s0 + nlen]).decode(
                        "utf-8", "replace"
                    )
                    if uri_chain and ND[k, j]:
                        name = _fix_uri_part(name, "")
                    if cookie:
                        name = name.strip()
                    name = name.lower()
                    if name == "":
                        # "=value": the empty relative name matches
                        # neither the wildcard nor any concrete target.
                        continue
                    if not has_eq:
                        d[name] = ""
                        continue
                    v0 = int(VS[k, j])
                    value = bytes(buf[i, v0 : v0 + int(VL[k, j])]).decode(
                        "utf-8", "replace"
                    )
                    if cookie:
                        value = value.strip()
                    if DC[k, j]:
                        if uri_chain:
                            value = _fix_uri_part(value, "")
                        try:
                            value = resilient_url_decode(value)
                        except ValueError:
                            failed.add(i)
                            d = None
                            break
                    d[name] = value
            if need_dicts and d is not None:
                dicts[i] = d
            for fid, p in flist:
                if p.comp == "*":
                    continue
                if getattr(p, "attr", ""):
                    # `d` keeps the last same-name segment — exactly the
                    # one the host's cache-overwrite semantics dissect.
                    text = d.get(p.comp) if d else None
                    if isinstance(p.attr, tuple):
                        out = self._sres_value(p.attr, text)
                        if out is not None:
                            overrides[fid][i] = self._coerce_casts(fid, out)
                        continue
                    if text:
                        key = self._setcookie_attr_key(fid, p.attr)
                        attrs = attrs_cache.get(text)
                        if attrs is None:
                            attrs = attrs_cache[text] = (
                                ResponseSetCookieDissector.parse_attrs(text)
                            )
                        if key in attrs:
                            overrides[fid][i] = attrs[key]
                    continue
                overrides[fid][i] = (d.get(p.comp) if d else None)

    def _oracle_engine_tally(self) -> Optional[Dict[str, int]]:
        """Snapshot of the oracle's compiled line engine tallies (None when
        no fastline engine is active).  Used to fold per-batch DELTAS into
        the metrics registry — per-line increments stay plain ints on the
        engine; the registry is only touched at batch granularity."""
        engine = getattr(self.oracle, "_fastline", None)
        tally = getattr(engine, "tally", None)
        return dict(tally) if isinstance(tally, dict) else None

    def _fold_oracle_engine_tally(self, before: Optional[Dict[str, int]]) -> None:
        """Fold the oracle engine's tally delta since ``before`` into the
        registry as oracle_engine_lines_total{outcome=...}.  The spawn-pool
        path runs engines in child processes, so only inline-parsed lines
        are covered — the routed/rescued/rejected counters above are the
        complete view."""
        after = self._oracle_engine_tally()
        if after is None:
            return
        from ..observability import metrics

        reg = metrics()
        for outcome, n in after.items():
            delta = n - (before or {}).get(outcome, 0)
            if delta > 0:
                reg.increment("oracle_engine_lines_total", delta,
                              labels={"outcome": outcome})

    def _build_overflow_delivery(self) -> Dict[str, str]:
        """Reference Long-overflow delivery per field (values beyond
        Long.MAX_VALUE / >19-digit runs): the oracle's collecting record
        resolves AUTO setters STRING-first, so a field with a STRING
        cast stores the raw digit string — which the numeric delivery
        plan types with int() (arbitrary precision), exactly what the
        host-side overflow patch replays.  A LONG-only field stores None
        on overflow (Long.parseLong fails, the null is skip-less-
        delivered).  Anything else (DOUBLE-only) is demoted to a full
        oracle parse — exactness over speed for a class no HTTPD token
        produces.  Single source for __init__ AND __setstate__ (loaded
        pre-round-9 artifacts must classify identically)."""
        out: Dict[str, str] = {}
        for fid, c in self._host_casts.items():
            if c is not None and Cast.STRING in c:
                out[fid] = "int"
            elif c is not None and Cast.LONG in c and Cast.DOUBLE not in c:
                out[fid] = "null"
            else:
                out[fid] = "oracle"
        return out

    def _start_rescue(self, rows: List[int], lines):
        """Begin the batched host rescue for ``rows`` (sorted row ids).

        The rows' lines are framed (materialized + decoded) once up
        front; the parse goes through the oracle's batched
        ``parse_many`` (one amortized fastline-program fetch for the
        whole set) — fanned out over the spawn pool for large sets, and
        run on an assembly-pool thread when one is available so it
        overlaps the caller's CSR/column materialization.  Returns a
        collector callable yielding List[Optional[values-dict]] in row
        order."""
        if not rows:
            return lambda: []
        batch_lines = [lines[i] for i in rows]
        pool = self.assembly_pool()
        if pool.workers > 1:
            fut = pool.submit(lambda: self._run_oracle_many(batch_lines))
            if fut is not None:
                return fut.result
        return lambda: self._run_oracle_many(batch_lines)

    def _run_oracle(self, line: Union[bytes, str]) -> Optional[Dict[str, Any]]:
        if isinstance(line, bytes):
            line = line.decode("utf-8", errors="replace")
        try:
            record = self.oracle.parse(line, _CollectingRecord())
        except DissectionFailure:
            return None
        return record.values

    # Fallback sets at least this large fan out over the process pool;
    # smaller ones run inline (pool startup is ~seconds once per parser).
    oracle_parallel_threshold = 512

    def _oracle_pool_get(self):
        if getattr(self, "_oracle_pool", None) is None:
            import multiprocessing as mp
            import pickle

            n = min(8, os.cpu_count() or 1)
            if n < 2 or os.environ.get("LOGPARSER_TPU_ORACLE_PROCS") == "0":
                self._oracle_pool = False
            else:
                # The workers run the pure-Python oracle only: hold them
                # to the CPU so none of them loads libtpu or touches the
                # chip this process owns.
                scrub = ("JAX_PLATFORMS", "XLA_FLAGS")
                saved = {v: os.environ.pop(v) for v in scrub if v in os.environ}
                os.environ["JAX_PLATFORMS"] = "cpu"
                try:
                    ctx = mp.get_context("spawn")
                    pool = ctx.Pool(
                        n,
                        initializer=_oracle_worker_init,
                        initargs=(pickle.dumps(self.oracle),),
                    )
                    # Readiness probe: a child-side initializer failure
                    # (e.g. the oracle references a __main__-defined
                    # dissector the spawn child cannot import) makes Pool
                    # respawn dying workers forever and map() would hang —
                    # probe with a timeout and fall back inline instead.
                    try:
                        pool.apply_async(_oracle_worker_run, ([],)).get(
                            timeout=120
                        )
                    except Exception:
                        pool.terminate()
                        pool.join()
                        raise
                    self._oracle_pool = pool
                    self._oracle_pool_n = n
                except Exception:
                    import logging

                    logging.getLogger(__name__).warning(
                        "oracle worker pool unavailable; falling back to "
                        "inline parsing", exc_info=True,
                    )
                    self._oracle_pool = False
                finally:
                    os.environ.pop("JAX_PLATFORMS", None)
                    os.environ.update(saved)
        return self._oracle_pool or None

    def _run_oracle_many(
        self, lines: List[Union[bytes, str]]
    ) -> List[Optional[Dict[str, Any]]]:
        """Oracle-parse many lines, fanning out over the worker pool when
        the set is large enough to amortize IPC.  The inline path uses
        the oracle's batched ``parse_many`` (one amortized fastline
        program fetch for the whole rescue set)."""
        decoded = [
            ln.decode("utf-8", errors="replace") if isinstance(ln, bytes) else ln
            for ln in lines
        ]
        pool = (
            self._oracle_pool_get()
            if len(decoded) >= self.oracle_parallel_threshold
            else None
        )
        if pool is None:
            return [
                _values_of(rec)
                for rec in self.oracle.parse_many(decoded, _CollectingRecord)
            ]
        n_chunks = self._oracle_pool_n * 4
        size = max(1, (len(decoded) + n_chunks - 1) // n_chunks)
        chunks = [decoded[i : i + size] for i in range(0, len(decoded), size)]
        out: List[Optional[Dict[str, Any]]] = []
        for part in pool.map(_oracle_worker_run, chunks):
            out.extend(part)
        return out

    def close(self) -> None:
        """Release the fallback worker pool (if one was started) and the
        Arrow assembly thread pool."""
        pool = getattr(self, "_oracle_pool", None)
        if pool:
            pool.terminate()
            pool.join()
        self._oracle_pool = None
        apool = getattr(self, "_assembly_pool", None)
        if apool is not None:
            apool.close()
        self._assembly_pool = None

    # ------------------------------------------------------------------
    # serialization — the compiled format program (token tables, split ops,
    # packed layouts, field plans) is a serializable, device-loadable
    # artifact.  The analogue of the reference's `Parser implements
    # Serializable` contract (Parser.java:91-97): engines serialize the
    # parser once and ship it to workers; jit executables are rebuilt on
    # load the way the reference re-resolves reflection Methods.
    #
    # SECURITY: the payload is a pickle (exactly as the reference's artifact
    # is a Java serialized object) — loading executes code from the blob.
    # Only load artifacts produced by your own pipeline over a trusted
    # channel; never feed user-uploaded files to from_bytes/load.
    # ------------------------------------------------------------------

    _ARTIFACT_MAGIC = b"LPTPU-PROGRAM-v1\n"
    # v2 wraps the v1 parser pickle with serialized AOT executables for
    # the shapes this process compiled (docs/COMPILE.md "Artifact
    # layout"): a fresh host loading the artifact executes its first
    # batch without lowering anything.  v1 artifacts stay loadable.
    _ARTIFACT_MAGIC_V2 = b"LPTPU-PROGRAM-v2\n"

    def __getstate__(self) -> Dict[str, Any]:
        state = self.__dict__.copy()
        state["_jitted"] = None
        state["_jitted_views"] = None
        state["_oracle_pool"] = None  # worker pools never ship in artifacts
        state["_assembly_pool"] = None  # rebuilt lazily from the knob
        # Device handles never ship: the mesh is re-resolved on the
        # LOADING host from the pickled data_parallel request (a
        # different host may have a different chip count).
        state["_mesh"] = None
        # Runtime fault state never ships either: a breaker/clamp
        # learned on one host's devices means nothing on another's, and
        # chaos re-arms from the loading process's env.
        state["_breaker"] = None
        state["_device_chaos"] = None
        state["_oom_clamp"] = None
        state["_oom_events"] = 0
        # Aggregate executors are jit handles (rebuilt lazily on load);
        # the compile-demote set is runtime fault state like the breaker.
        state["_agg_fns"] = {}
        state["_agg_disabled"] = set()
        return state

    def __setstate__(self, state: Dict[str, Any]) -> None:
        # Legacy artifact keys (use_pallas/_pallas_fns from pre-round-3
        # builds, when an experimental Pallas executor existed) are
        # dropped on load.
        for legacy in ("_pallas_fns", "use_pallas", "_use_pallas_explicit"):
            state.pop(legacy, None)
        self.__dict__.update(state)
        if "csr_slots" not in state:  # pre-adaptive-CSR artifacts
            from .pipeline import CSR_SLOTS

            self.csr_slots = CSR_SLOTS
        if "_device_covers_all_formats" not in state:  # pre-filter artifacts
            self._device_covers_all_formats = False  # conservatively off
        if "_cast_flags" not in state:  # pre-round-5 artifacts
            self._cast_flags = {
                f: (Cast.LONG in c, Cast.DOUBLE in c)
                for f, c in self._host_casts.items()
                if c is not None
            }
        if "_view_demand" not in state:  # pre-round-6 artifacts
            self._view_demand = None
        if "assembly_workers" not in state:
            self.assembly_workers = None
        if "_overflow_delivery" not in state:  # pre-round-9 artifacts
            self._overflow_delivery = self._build_overflow_delivery()
        if "data_parallel" not in state:  # pre-pod artifacts
            self.data_parallel = None
        if "_agg_fns" not in state:  # pre-analytics artifacts
            self._agg_fns = {}
            self._agg_disabled = set()
        # Fault layer rebuilds fresh on the loading host: pickled knobs
        # (budget/deadline/policy) are honored, env fallbacks re-read,
        # breaker/clamp/chaos start clean (pre-fault-layer artifacts
        # get the defaults).
        self._init_fault_layer(
            state.get("device_bytes_budget"),
            state.get("execute_deadline_s"),
            state.get("fault_policy"),
            "env",
        )
        # Re-resolve the mesh on THIS host (never pickled; the loading
        # host's device count decides the effective width).
        self._mesh = self._build_mesh(self.data_parallel)
        configure_jax_cache()
        # Pre-widening artifacts packed 18-digit limb layouts (no d18/big
        # aux slots).  Layouts are deterministic functions of the plans +
        # slot count, so rebuild them to the current frame format.
        needs_layout = any(
            p.kind in ("long", "secmillis") and "big" not in u.layout.slots.get(
                p.field_id, {"big": None}
            )
            for u in self.units for p in u.plans
        )
        if needs_layout:
            for u in self.units:
                u.layout = PackedLayout.for_plans(u.plans, self.csr_slots)
            assign_row_offsets(self.units)
        self._assembly_pool = None
        self._jitted = self._build_jitted()
        self._jitted_views = None

    def to_bytes(self, embed_executables: bool = True) -> bytes:
        """The compiled parser as a versioned artifact blob (a pickle — see
        the SECURITY note above: treat artifacts as executable).

        ``embed_executables`` (default) also ships the serialized AOT
        executables for every shape bucket this process has compiled or
        loaded — warm the ladder first (:meth:`prewarm`) to mint an
        artifact whose loading host never lowers anything.  A parser with
        nothing compiled yet (or a mesh-sharded executor, whose
        executables bind this process's device set) emits a plain v1
        blob."""
        import pickle

        execs = self._export_executables() if embed_executables else []
        if not execs:
            return self._ARTIFACT_MAGIC + pickle.dumps(self)
        from .compile_cache import backend_fingerprint

        return self._ARTIFACT_MAGIC_V2 + pickle.dumps({
            "parser": self,
            "backend": backend_fingerprint(),
            "execs": execs,
        })

    def _export_executables(self) -> List[Dict[str, Any]]:
        from .compile_cache import AotExecutor

        out: List[Dict[str, Any]] = []
        seen = set()
        for tag, fn in (("plain", self._jitted),
                        ("views", self._jitted_views)):
            if (not isinstance(fn, AotExecutor) or not fn.serializable
                    or id(fn) in seen):
                continue
            seen.add(id(fn))
            for (b, l), payload in fn.export_payloads().items():
                out.append({
                    "tag": tag, "b": b, "l": l, "payload": payload,
                    "fingerprint": fn.fingerprint,
                })
        return out

    def _preload_executables(self, execs: List[Dict[str, Any]],
                             backend: Optional[str]) -> int:
        """Install artifact-embedded executables into the rebuilt AOT
        executors.  Fingerprint or backend drift refuses the entry (the
        shape compiles fresh on first use — never a wrong kernel);
        returns how many shapes went live."""
        from ..observability import log_warning_once, metrics
        from .compile_cache import AotExecutor

        loaded = 0
        for e in execs:
            fn = (self._jitted if e.get("tag") == "plain"
                  else self.device_views_fn())
            if not isinstance(fn, AotExecutor):
                continue
            if e.get("fingerprint") != fn.fingerprint:
                metrics().increment("compile_cache_errors_total",
                                    labels={"kind": "fingerprint"})
                log_warning_once(
                    _LOG,
                    "artifact executable refused (fingerprint drift); "
                    "recompiling fresh",
                )
                continue
            if fn.preload(int(e["b"]), int(e["l"]), e["payload"], backend):
                loaded += 1
        return loaded

    @classmethod
    def from_bytes(cls, blob: bytes) -> "TpuBatchParser":
        """Load an artifact produced by :meth:`to_bytes`.  TRUSTED INPUT
        ONLY — the payload is a pickle and loading executes code."""
        import pickle

        if blob.startswith(cls._ARTIFACT_MAGIC_V2):
            d = pickle.loads(blob[len(cls._ARTIFACT_MAGIC_V2):])
            parser = d.get("parser") if isinstance(d, dict) else None
            if not isinstance(parser, cls):
                raise ValueError("artifact does not contain a TpuBatchParser")
            parser._preload_executables(
                d.get("execs") or [], d.get("backend")
            )
            return parser
        if not blob.startswith(cls._ARTIFACT_MAGIC):
            raise ValueError("not a logparser_tpu program artifact")
        parser = pickle.loads(blob[len(cls._ARTIFACT_MAGIC):])
        if not isinstance(parser, cls):
            raise ValueError("artifact does not contain a TpuBatchParser")
        return parser

    def save(self, path: str, embed_executables: bool = True) -> None:
        with open(path, "wb") as f:
            f.write(self.to_bytes(embed_executables))

    @classmethod
    def load(cls, path: str) -> "TpuBatchParser":
        with open(path, "rb") as f:
            return cls.from_bytes(f.read())
