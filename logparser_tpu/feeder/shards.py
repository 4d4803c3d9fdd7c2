"""Shard planner: byte-range shards with newline-boundary healing.

Feeding the device at its kernel rate takes far more than one reader
thread can frame, so the corpus must be split into
independent byte ranges that many workers can frame in parallel.  The
split semantics mirror the reference's Hadoop InputFormat
(ApacheHttpdLogfileInputFormat + LineRecordReader): raw shards tile the
byte space blindly, and healing assigns every LINE to exactly one shard:

    a shard [start, end) owns every line whose FIRST byte lies in
    [start, end).

A reader therefore skips forward from ``start`` to the first line start
(unless ``start`` is 0 or the previous byte is a newline), and reads
PAST ``end`` to finish the last line it owns — so a line spanning a
shard boundary belongs to the shard where it began, and a line longer
than a whole shard leaves the middle shards empty.  Healed payloads of
consecutive shards concatenate back to the original byte stream exactly
(the byte-parity contract tests/test_feeder.py pins).

Everything here is jax-free (workers must import it without touching
the device runtime).
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import IO, List, Optional, Sequence, Tuple, Union

SourceT = Union[str, bytes, bytearray, memoryview, "os.PathLike[str]"]

#: Default raw shard size: large enough that healing and per-shard setup
#: are noise, small enough that a handful of shards spread over few
#: workers (the reference's FileInputFormat defaults to the HDFS block).
DEFAULT_SHARD_BYTES = 8 << 20


@dataclass(frozen=True)
class Shard:
    """One raw byte range of one source.  ``index`` is the global shard
    order (across all sources) — delivery order and worker assignment
    both derive from it."""

    index: int          # global shard index (delivery order)
    source: int         # index into the pool's source list
    start: int          # raw range start (byte offset, pre-healing)
    end: int            # raw range end (exclusive, pre-healing)

    @property
    def raw_bytes(self) -> int:
        return self.end - self.start


class _Source:
    """Normalized input source: an in-memory blob or a file path."""

    __slots__ = ("kind", "blob", "path", "size")

    def __init__(self, src: SourceT):
        self.blob: bytes = b""
        self.path: Optional[str] = None
        if isinstance(src, (bytes, bytearray, memoryview)):
            self.kind = "blob"
            self.blob = bytes(src)
            self.size = len(self.blob)
        else:
            self.kind = "file"
            self.path = os.fspath(src)
            self.size = os.path.getsize(self.path)

    def describe(self) -> str:
        return self.path if self.kind == "file" else f"<blob {self.size}B>"


def normalize_sources(sources: Sequence[SourceT]) -> List[_Source]:
    return [_Source(s) for s in sources]


def plan_shards(
    sources: Sequence[_Source], shard_bytes: int = DEFAULT_SHARD_BYTES
) -> List[Shard]:
    """Tile every source into raw ``shard_bytes`` ranges (the last shard
    of a source takes the remainder).  Healing happens at read time —
    the plan itself never opens a file (the reference computes splits
    from file LENGTHS only, FileInputFormat.getSplits)."""
    if shard_bytes <= 0:
        raise ValueError(f"shard_bytes must be positive, got {shard_bytes}")
    shards: List[Shard] = []
    for si, src in enumerate(sources):
        start = 0
        while start < src.size:
            end = min(start + shard_bytes, src.size)
            shards.append(Shard(len(shards), si, start, end))
            start = end
    return shards


# ---------------------------------------------------------------------------
# pod-scale plan subsetting: disjoint per-host shard ranges
# ---------------------------------------------------------------------------


def host_shard_range(n_shards: int, n_hosts: int,
                     host_index: int) -> Tuple[int, int]:
    """The contiguous ``[start, end)`` slice of global shard indices that
    host ``host_index`` of an ``n_hosts`` pod owns — balanced (sizes
    differ by at most one), disjoint, and tiling ``range(n_shards)``
    exactly.  Contiguous ranges (not strided) keep each host's reads
    sequential within a source file and make a dead host's unfinished
    work one run of consecutive uncommitted shards (docs/JOBS.md "Pod
    jobs")."""
    if n_hosts <= 0:
        raise ValueError(f"n_hosts must be positive, got {n_hosts}")
    if not 0 <= host_index < n_hosts:
        raise ValueError(
            f"host_index {host_index} outside [0, {n_hosts})"
        )
    base, rem = divmod(n_shards, n_hosts)
    start = host_index * base + min(host_index, rem)
    end = start + base + (1 if host_index < rem else 0)
    return start, end


def shards_for_host(plan: Sequence[Shard], n_hosts: int,
                    host_index: int) -> List[Shard]:
    """The subset of a global shard plan one pod host owns (see
    :func:`host_shard_range`).  Shards keep their GLOBAL indices — the
    job runner renumbers for the feeder pool and maps back at commit
    time, so every host's manifest speaks the same global shard
    vocabulary and the manifests merge without translation."""
    start, end = host_shard_range(len(plan), n_hosts, host_index)
    return [s for s in plan if start <= s.index < end]


# ---------------------------------------------------------------------------
# healing: raw range -> owned line range
# ---------------------------------------------------------------------------


def line_start_at_or_after(blob: bytes, pos: int) -> int:
    """Offset of the first line START at or after ``pos`` (len(blob)
    when none): 0 stays 0, a position just after a newline is already a
    line start, anything else skips to just past the next newline."""
    if pos <= 0:
        return 0
    if pos >= len(blob):
        return len(blob)
    if blob[pos - 1 : pos] == b"\n":
        return pos
    j = blob.find(b"\n", pos)
    return len(blob) if j < 0 else j + 1


def healed_range(blob: bytes, start: int, end: int) -> Tuple[int, int]:
    """The line-owned byte range of raw shard [start, end): every line
    starting inside the raw range, whole.  Consecutive shards' healed
    ranges tile the blob exactly."""
    return (
        line_start_at_or_after(blob, start),
        line_start_at_or_after(blob, end),
    )


def healed_payload(blob: bytes, start: int, end: int) -> bytes:
    p0, p1 = healed_range(blob, start, end)
    return blob[p0:p1] if p1 > p0 else b""


def _file_line_start_at_or_after(
    f: IO[bytes], pos: int, size: int, chunk: int = 1 << 16
) -> int:
    """:func:`line_start_at_or_after` over an open binary file."""
    if pos <= 0:
        return 0
    if pos >= size:
        return size
    f.seek(pos - 1)
    if f.read(1) == b"\n":
        return pos
    off = pos
    while off < size:
        data = f.read(chunk)
        if not data:
            return size
        j = data.find(b"\n")
        if j >= 0:
            return off + j + 1
        off += len(data)
    return size


def read_shard_payload(src: _Source, shard: Shard) -> bytes:
    """The healed payload bytes of one shard (whole lines only; empty
    when the raw range contains no line start)."""
    if src.kind == "blob":
        return healed_payload(src.blob, shard.start, shard.end)
    with open(src.path, "rb") as f:  # type: ignore[arg-type]
        p0 = _file_line_start_at_or_after(f, shard.start, src.size)
        p1 = _file_line_start_at_or_after(f, shard.end, src.size)
        if p1 <= p0:
            return b""
        f.seek(p0)
        return f.read(p1 - p0)
