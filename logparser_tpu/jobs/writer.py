"""Shard output writer: Arrow tables -> durable files, atomically.

One shard produces up to two Arrow IPC stream files in the job
directory:

- ``shard-NNNNN.arrow`` — the data table (valid lines only, the
  parser's copy-mode Arrow schema), omitted when the shard has no
  valid line;
- ``shard-NNNNN.rejects.arrow`` — the reject table (one row per line
  that failed BOTH device parse and oracle rescue: shard, batch, line
  offset, stable reason, raw line bytes), omitted when clean.

Every file lands via temp-file -> flush -> fsync -> atomic rename (the
manifest commit happens AFTER, in the runner) so a crash at any byte
leaves either no file or a complete one — never a torn table.

Writer I/O faults (real ENOSPC/EIO, or injected through the chaos
grammar's ``io_error``/``enospc`` primitives) retry with bounded
exponential backoff; a shard that exhausts its retries raises
:class:`ShardWriteError`, which the runner records as a FAILED shard —
the job continues, the manifest stays consistent (no entry), and a
later resume retries the shard from the corpus.
"""
from __future__ import annotations

import hashlib
import logging
import os
import re
import time
from typing import Any, List, Optional, Tuple

from ..observability import log_warning_once, metrics, pipeline_stage
from .manifest import (
    JobManifest,
    ShardRecord,
    fsync_dir,
    host_token,
    temp_suffix,
)

LOG = logging.getLogger(__name__)

DATA_FILE = "shard-{index:05d}.arrow"
REJECT_FILE = "shard-{index:05d}.rejects.arrow"
# Aggregate-mode sidecar (docs/ANALYTICS.md): one partial-aggregate
# frame per shard, committed through the same temp->fsync->rename->
# manifest protocol — always written (even for an empty shard) so a
# committed aggregate shard's record always carries its sidecar.
AGG_FILE = "shard-{index:05d}.agg.arrow"

#: The writer's retryable operations (chaos injection points share the
#: names: ``io_error:op=write`` etc.).
WRITE_OPS = ("write", "fsync", "rename")


class ShardWriteError(RuntimeError):
    """One shard's output could not be durably written even after the
    bounded retry ladder.  Carries the shard index; the job survives."""

    def __init__(self, shard: int, message: str):
        super().__init__(message)
        self.shard = shard


def reject_schema():
    import pyarrow as pa

    return pa.schema([
        ("shard", pa.int64()),       # global shard index
        ("batch", pa.int32()),       # batch index within the shard
        ("line", pa.int64()),        # line offset within the shard
        ("reason", pa.string()),     # stable vocabulary (BatchResult)
        ("raw", pa.binary()),        # the line bytes, verbatim
    ])


def build_reject_table(rows: List[Tuple[int, int, int, str, bytes]]):
    """rows = [(shard, batch, line, reason, raw_bytes), ...] in line
    order -> the reject table (schema above)."""
    import pyarrow as pa

    schema = reject_schema()
    if not rows:
        return pa.table(
            {f.name: pa.array([], type=f.type) for f in schema}
        )
    cols = list(zip(*rows))
    return pa.table({
        "shard": pa.array(cols[0], type=pa.int64()),
        "batch": pa.array(cols[1], type=pa.int32()),
        "line": pa.array(cols[2], type=pa.int64()),
        "reason": pa.array(cols[3], type=pa.string()),
        "raw": pa.array(cols[4], type=pa.binary()),
    })


class JobWriter:
    """Durable shard writer for one job directory.  ``retries`` bounds
    the per-operation retry ladder (attempts = retries + 1), backoff
    doubling from ``backoff_base_s``; ``chaos`` is a
    :class:`~logparser_tpu.tools.chaos.WriterChaos` (or None)."""

    def __init__(self, out_dir: str, retries: int = 3,
                 backoff_base_s: float = 0.05, chaos: Any = None):
        self.out_dir = out_dir
        self.retries = max(0, int(retries))
        self.backoff_base_s = float(backoff_base_s)
        self.chaos = chaos

    # -- low-level: one durable file ------------------------------------

    def _attempt(self, path: str, data: bytes, shard: int) -> None:
        """One write->fsync->rename pass with chaos injection at each
        op.  Any OSError propagates to the retry ladder."""
        chaos = self.chaos
        tmp = path + temp_suffix()
        try:
            if chaos:
                chaos.check("write", shard)
            with open(tmp, "wb") as f:
                f.write(data)
                f.flush()
                if chaos:
                    chaos.check("fsync", shard)
                os.fsync(f.fileno())
            if chaos:
                chaos.check("rename", shard)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        fsync_dir(self.out_dir)

    def write_file(self, name: str, data: bytes, shard: int) -> None:
        """Durably land ``data`` at ``out_dir/name``, retrying transient
        I/O faults with bounded backoff.  Raises ShardWriteError once
        the ladder is exhausted — the caller fails the SHARD, never the
        job."""
        path = os.path.join(self.out_dir, name)
        last: Optional[BaseException] = None
        for attempt in range(self.retries + 1):
            if attempt:
                delay = self.backoff_base_s * (2 ** (attempt - 1))
                time.sleep(delay)
            try:
                self._attempt(path, data, shard)
                return
            except OSError as e:
                last = e
                metrics().increment(
                    "job_writer_retries_total",
                    labels={"op": _op_of(e)},
                )
                # Static warn-once key (per-file/per-error text would
                # grow the warn-once table shard by shard on a big
                # job); specifics ride the counter labels and DEBUG.
                log_warning_once(
                    LOG,
                    "job writer: transient I/O fault(s); retrying with "
                    "bounded backoff (job_writer_retries_total counts "
                    "them; details at DEBUG)",
                )
                LOG.debug("job writer: %s attempt %d failed (%s: %s)",
                          name, attempt + 1, type(e).__name__, e)
        raise ShardWriteError(
            shard,
            f"shard {shard}: {name} failed after "
            f"{self.retries + 1} attempts ({last})",
        )

    # -- shard commit ---------------------------------------------------

    def write_shard(self, shard, data_table, reject_rows, lines: int,
                    payload_bytes: int, agg_state: Any = None,
                    agg_rows: int = 0) -> ShardRecord:
        """Land one shard's outputs and return its (uncommitted)
        :class:`ShardRecord` — the runner appends it to the manifest,
        which is the actual commit point.  ``agg_state`` (aggregate-mode
        jobs) lands the shard's partial-aggregate sidecar instead of a
        data table; ``agg_rows`` records the shard's good-line count in
        the record's ``rows`` field (there is no data table to count)."""
        from ..tpu.arrow_bridge import table_to_ipc_bytes

        reg = metrics()
        data_file = data_hash = None
        reject_file = reject_hash = None
        agg_file = agg_hash = None
        if agg_state is not None:
            rows = int(agg_rows)
        else:
            rows = int(data_table.num_rows) if data_table is not None else 0
        with pipeline_stage("job_write", items=rows):
            if data_table is not None and data_table.num_rows:
                data = table_to_ipc_bytes(data_table.combine_chunks())
                data_file = DATA_FILE.format(index=shard.index)
                data_hash = hashlib.blake2b(data).hexdigest()
                self.write_file(data_file, data, shard.index)
                reg.increment("job_bytes_written_total", len(data))
            if agg_state is not None:
                data = agg_state.to_ipc_bytes()
                agg_file = AGG_FILE.format(index=shard.index)
                agg_hash = hashlib.blake2b(data).hexdigest()
                self.write_file(agg_file, data, shard.index)
                reg.increment("job_bytes_written_total", len(data))
            if reject_rows:
                reject = table_to_ipc_bytes(build_reject_table(reject_rows))
                reject_file = REJECT_FILE.format(index=shard.index)
                reject_hash = hashlib.blake2b(reject).hexdigest()
                self.write_file(reject_file, reject, shard.index)
                reg.increment("job_bytes_written_total", len(reject))
        reg.increment("job_rows_total", rows)
        return ShardRecord(
            shard=shard.index, source=shard.source,
            start=shard.start, end=shard.end,
            lines=lines, rows=rows, rejects=len(reject_rows),
            payload_bytes=payload_bytes,
            data_file=data_file, reject_file=reject_file,
            data_hash=data_hash, reject_hash=reject_hash,
            agg_file=agg_file, agg_hash=agg_hash,
        )


def _op_of(e: OSError) -> str:
    import errno

    if getattr(e, "errno", None) == errno.ENOSPC:
        return "enospc"
    return "io_error"


def merged_hash(out_dir: str, manifest: JobManifest) -> str:
    """Content hash of the job's durable output: every committed
    shard's data bytes then reject bytes, in global shard order — the
    byte-identity probe the kill-drill invariant is asserted with
    (docs/JOBS.md)."""
    h = hashlib.blake2b()
    for idx in manifest.committed_indices():
        rec = manifest.shards[idx]
        for name in (rec.data_file, rec.reject_file):
            if name is None:
                h.update(b"\0")
                continue
            with open(os.path.join(out_dir, name), "rb") as f:
                h.update(f.read())
        # Aggregate sidecars join the identity only when present, so a
        # pre-analytics job's hash is unchanged byte for byte.
        if rec.agg_file is not None:
            with open(os.path.join(out_dir, rec.agg_file), "rb") as f:
                h.update(f.read())
    return h.hexdigest()


def merged_job_aggregate(out_dir: str,
                         manifest: Optional[JobManifest] = None):
    """Merge every committed shard's partial-aggregate sidecar — in
    global shard order — into one
    :class:`~logparser_tpu.analytics.state.AggregateState`: the job-level
    aggregate answer (docs/ANALYTICS.md).  Order is cosmetic (the merge
    is associative and commutative) but fixed, so two resumed/pod runs
    of one job produce byte-identical merged frames."""
    from ..analytics.spec import AggregateSpec
    from ..analytics.state import AggregateState

    if manifest is None:
        manifest = JobManifest.load(out_dir)
        if manifest is None:
            raise ValueError(f"{out_dir}: no manifest to aggregate")
    key = manifest.job.get("aggregate")
    if not key:
        raise ValueError(f"{out_dir}: not an aggregate-mode job")
    spec = AggregateSpec.from_canonical(key)
    total = AggregateState(spec)
    for idx in manifest.committed_indices():
        rec = manifest.shards[idx]
        if rec.agg_file is None:
            continue
        with open(os.path.join(out_dir, rec.agg_file), "rb") as f:
            total.merge(AggregateState.from_ipc_bytes(f.read(), spec))
    return total


def leaked_temp_files(out_dir: str) -> List[str]:
    """``*.tmp`` debris in the job directory (crash leftovers; resume
    sweeps them, the smoke asserts none survive a completed run)."""
    try:
        return sorted(
            n for n in os.listdir(out_dir) if n.endswith(".tmp")
        )
    except FileNotFoundError:
        return []


_TMP_RE = re.compile(r"\.(?:([A-Za-z0-9_-]+)\.)?(\d+)\.tmp$")

#: A FOREIGN host's temp file (pod over a shared filesystem: its pid is
#: meaningless here) is only swept once it has sat untouched this long —
#: in-flight writes live milliseconds to seconds, so anything this old
#: is crash debris from a machine that went away.
FOREIGN_TMP_STALE_S = 900.0


def sweepable_temp_files(out_dir: str) -> List[str]:
    """The subset of :func:`leaked_temp_files` a (re)starting run may
    safely unlink.  In a POD directory a temp file can belong to
    another host's IN-FLIGHT write, so the rules are:

    - SAME machine (or a legacy name with no host token): sweep only
      when the embedded pid is dead — a live pid is a concurrent local
      host mid-write;
    - FOREIGN machine (shared-filesystem pod: the pid is meaningless
      here): sweep only when the file has sat untouched past
      :data:`FOREIGN_TMP_STALE_S` — a remote host's in-flight write is
      always fresh;
    - no parseable identity at all: legacy debris, sweepable."""
    local = host_token()
    out = []
    now = time.time()
    for name in leaked_temp_files(out_dir):
        m = _TMP_RE.search(name)
        if m:
            host, pid = m.group(1), int(m.group(2))
            if host is None or host == local:
                try:
                    os.kill(pid, 0)
                except ProcessLookupError:
                    pass        # dead local writer: crash debris
                except OSError:
                    continue    # unknowable: leave it alone
                else:
                    continue    # alive: a concurrent local host, or us
            else:
                try:
                    age = now - os.stat(
                        os.path.join(out_dir, name)).st_mtime
                except OSError:
                    continue    # vanished mid-scan: its owner is live
                if age < FOREIGN_TMP_STALE_S:
                    continue    # a remote host may be mid-write
        out.append(name)
    return out
