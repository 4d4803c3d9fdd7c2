"""Durable corpus -> sharded-Arrow job runner (docs/JOBS.md).

``run_job(JobSpec(...))`` drives the whole batch tier: the feeder
fabric's shard planner tiles the corpus (``feeder/shards.py`` — the
reference's InputFormat split semantics), a supervised
:class:`~logparser_tpu.feeder.pool.FeederPool` reads + frames shards in
parallel, ``TpuBatchParser.parse_batch_stream`` parses them on device
with host-stage overlap, and every shard's results land as Arrow IPC
files through the atomic :class:`~logparser_tpu.jobs.writer.JobWriter`,
committed one at a time into the JSON manifest
(:mod:`~logparser_tpu.jobs.manifest`).

Durability contract (the kill-drill invariant, drilled by ``make
job-smoke`` and tested in ``tests/test_jobs.py``):

- a shard is COMMITTED exactly when its manifest entry exists; its
  files were renamed into place (and fsynced) strictly before;
- ``run_job(..., resume=True)`` over an interrupted directory skips
  committed shards wholesale (they are never re-parsed) and replays
  only the rest from the corpus — parse and framing are deterministic,
  so the merged output (data + reject tables, global shard order) is
  BYTE-IDENTICAL to an undisturbed run's;
- a line that fails both device parse and oracle rescue is never
  dropped silently and never raises: it lands in the shard's reject
  table with a stable reason (``BatchResult.reject_reasons``) and
  counts ``job_rejected_lines_total{reason}``;
- writer I/O faults retry with bounded backoff, then fail the SHARD
  (recorded on the report, absent from the manifest — a later resume
  retries it); the job itself completes every other shard.
"""
from __future__ import annotations

import hashlib
import logging
import os
import time
from collections import deque
from dataclasses import dataclass, field, replace
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ..feeder.pool import FeederPool, default_feeder_workers
from ..feeder.shards import (
    DEFAULT_SHARD_BYTES,
    Shard,
    SourceT,
    normalize_sources,
    plan_shards,
    shards_for_host,
)
from ..observability import log_warning_once, metrics, pipeline_stage
from .manifest import (
    MANIFEST_NAME,
    JobManifest,
    ManifestError,
    committed_anywhere,
    host_manifest_name,
)
from .writer import JobWriter, ShardWriteError, sweepable_temp_files

LOG = logging.getLogger(__name__)

DEFAULT_JOB_BATCH_LINES = 16384

#: CLI exit code for a SIGTERM-clean (preempted) run: the current shard
#: boundary was committed and the manifest resumes exactly — the
#: cloud-TPU preemption notice's cheap exit (docs/JOBS.md "Preemption").
#: Distinct from 1 (failed shards) and 2 (config error): an orchestrator
#: relaunches a 3 unconditionally, resume re-parses zero committed
#: shards.
EXIT_PREEMPTED = 3


@dataclass
class JobSpec:
    """Everything that determines a job's output bytes, plus execution
    knobs that don't (worker count, transport) — only the former enter
    the manifest fingerprint."""

    sources: Sequence[SourceT]
    log_format: str
    fields: Sequence[str]
    out_dir: str
    shard_bytes: int = DEFAULT_SHARD_BYTES
    batch_lines: int = DEFAULT_JOB_BATCH_LINES
    # Execution-only knobs (not fingerprinted):
    workers: Optional[int] = None
    use_processes: Optional[bool] = None
    transport: Optional[str] = None
    # Pod placement (docs/JOBS.md "Pod jobs"): this run owns host
    # ``host_index``'s contiguous slice of the GLOBAL shard plan and
    # commits into its per-host manifest.  Execution-only — the shard
    # plan, and therefore the merged output bytes, are identical for
    # every n_hosts, which is exactly what makes an N-host pod's merged
    # output byte-comparable to a single-host run's.
    n_hosts: int = 1
    host_index: int = 0
    # Device-side data parallelism: lay the parse step over this many
    # local devices (``TpuBatchParser(data_parallel=...)``); None = the
    # parser default (single device).
    data_parallel: Optional[int] = None
    # Analytics pushdown (docs/ANALYTICS.md): an aggregation spec (op
    # list / JSON string / AggregateSpec) switches the job to aggregate
    # mode — each shard lands a partial-aggregate sidecar instead of a
    # data table (rejects still land).  FINGERPRINTED: the spec
    # determines the output bytes, so resuming a row job as an
    # aggregate job (or across two specs) is refused.
    aggregate: Optional[Any] = None

    def fingerprint(self, sources_norm) -> Dict[str, Any]:
        """The manifest's job block: resume refuses when any of this
        diverges (mixing configurations would corrupt the corpus)."""
        descr = []
        for s in sources_norm:
            if s.kind == "file":
                # path + size + mtime: a corpus rewritten IN PLACE to
                # the same byte size (rotate-and-refill) must refuse to
                # resume — mixing two corpora's shards would corrupt
                # the output with no crash at all.
                try:
                    mtime_ns = os.stat(s.path).st_mtime_ns
                except OSError:
                    mtime_ns = None
                descr.append({
                    "kind": "file",
                    "path": os.path.abspath(s.path),
                    "size": s.size,
                    "mtime_ns": mtime_ns,
                })
            else:
                descr.append({
                    "kind": "blob",
                    "size": s.size,
                    "hash": hashlib.blake2b(s.blob).hexdigest()[:32],
                })
        from ..analytics.spec import parse_aggregate_config, spec_tuple

        return {
            "log_format": self.log_format,
            "fields": list(self.fields),
            "shard_bytes": int(self.shard_bytes),
            "batch_lines": int(self.batch_lines),
            "sources": descr,
            # None for row jobs: a pre-analytics manifest's absent key
            # reads back as None too, so old row jobs still resume.
            "aggregate": spec_tuple(
                parse_aggregate_config(self.aggregate)
            ),
        }


@dataclass
class JobPolicy:
    """Runner tunables (all have safe defaults)."""

    io_retries: int = 3          # writer attempts = io_retries + 1
    io_backoff_s: float = 0.05   # backoff base, doubling per retry
    # Crash simulation for tests: abandon the run (WITHOUT
    # committing anything further) after this many shard commits this
    # run — models a kill landing on a commit boundary; the real
    # SIGKILL drill lives in tools/job_smoke.py.
    stop_after_shards: Optional[int] = None
    # Graceful preemption: an Event-like object (``is_set() -> bool``)
    # checked at every shard commit boundary — when set, the run
    # commits the shard in flight, marks the report ``preempted``, and
    # returns (the CLI installs its SIGTERM handler here and exits
    # EXIT_PREEMPTED; docs/JOBS.md "Preemption").  Cheaper than the
    # SIGKILL path by exactly one replayed shard.
    stop_event: Optional[Any] = None


@dataclass
class JobReport:
    """What one ``run_job`` call did (this run only; the manifest holds
    the cumulative truth)."""

    out_dir: str
    shards_total: int = 0
    committed: int = 0           # committed by THIS run
    skipped: int = 0             # committed before this run (resume)
    failed: List[Dict[str, Any]] = field(default_factory=list)
    lines: int = 0
    rows: int = 0
    rejects: int = 0
    reject_reasons: Dict[str, int] = field(default_factory=dict)
    payload_bytes: int = 0
    wall_s: float = 0.0
    stopped_early: bool = False  # JobPolicy.stop_after_shards tripped
    preempted: bool = False      # JobPolicy.stop_event fired (SIGTERM)
    n_hosts: int = 1             # pod placement (1 = single-host job)
    host_index: int = 0

    @property
    def complete(self) -> bool:
        return (not self.failed and not self.stopped_early
                and self.committed + self.skipped == self.shards_total)

    @property
    def bytes_per_sec(self) -> float:
        return self.payload_bytes / self.wall_s if self.wall_s > 0 else 0.0

    def as_dict(self) -> Dict[str, Any]:
        return {
            "out_dir": self.out_dir,
            "shards_total": self.shards_total,
            "committed": self.committed,
            "skipped": self.skipped,
            "failed": self.failed,
            "lines": self.lines,
            "rows": self.rows,
            "rejects": self.rejects,
            "reject_reasons": self.reject_reasons,
            "payload_bytes": self.payload_bytes,
            "wall_s": round(self.wall_s, 4),
            "bytes_per_sec": round(self.bytes_per_sec, 1),
            "complete": self.complete,
            "stopped_early": self.stopped_early,
            **({"preempted": True} if self.preempted else {}),
            **({"n_hosts": self.n_hosts, "host_index": self.host_index}
               if self.n_hosts > 1 else {}),
        }


class _ShardAccumulator:
    """Per-shard in-flight state: filtered data tables, reject rows,
    and volume counters, until the shard's last batch lands.  Reject
    REASON tallies stay here until the shard actually commits — report
    totals and ``job_rejected_lines_total`` must equal lines durably
    landed in reject tables (a failed or replayed shard's rejects are
    not double-counted)."""

    __slots__ = ("tables", "rejects", "reason_counts", "lines",
                 "payload_bytes", "agg", "rows")

    def __init__(self) -> None:
        self.tables: List[Any] = []
        self.rejects: List[tuple] = []
        self.reason_counts: Dict[str, int] = {}
        self.lines = 0
        self.payload_bytes = 0
        # Aggregate mode: the shard's merged partial state + good-line
        # count (there is no data table to count rows from).
        self.agg: Any = None
        self.rows = 0


def _split_chaos(chaos: Any):
    """(pool ChaosSpec or None, WriterChaos or None, DeviceChaos or
    None) from whatever the caller armed: a spec object, the string
    grammar, or the env var.  Worker faults go to the feeder fabric, io
    faults to the writer, device faults to the parser's fault layer;
    pod faults (``preempt_host``) are the pod runner's and inert here."""
    from ..tools.chaos import (
        DEVICE_FAULTS,
        IO_FAULTS,
        POD_FAULTS,
        ChaosSpec,
        DeviceChaos,
        WriterChaos,
    )

    if chaos is None:
        spec = ChaosSpec.from_env()
    elif isinstance(chaos, str):
        spec = ChaosSpec.parse(chaos)
    else:
        spec = chaos
    if spec is None:
        return None, None, None
    pool_faults = [
        f for f in spec.faults
        if f.kind not in IO_FAULTS | DEVICE_FAULTS | POD_FAULTS
    ]
    writer = WriterChaos(spec)
    device = DeviceChaos(spec)
    return (
        ChaosSpec(pool_faults) if pool_faults else None,
        writer if writer else None,
        device if device else None,
    )


def run_job(
    spec: JobSpec,
    resume: bool = True,
    parser: Any = None,
    chaos: Any = None,
    policy: Optional[JobPolicy] = None,
) -> JobReport:
    """Run (or resume) one durable job.  See module docstring.

    ``parser`` lets a caller reuse a compiled ``TpuBatchParser`` (its
    config must match the spec — the benchmark's ETL driver and the
    smokes reuse one parser to keep jit compiles out of timed
    windows).  ``chaos`` arms fault injection (``ChaosSpec`` / grammar
    string; default: the ``LOGPARSER_TPU_CHAOS`` env var)."""
    # Root span: every phase span below nests under it, so its self
    # time is the part of the job that no phase covers.  Kept off the
    # profiler track: a trace names each idle gap after the annotation
    # that overlaps it most, and a root would take every name.
    with pipeline_stage("job", annotate=False):
        return _run_job(spec, resume, parser, chaos, policy)


def _run_job(spec: JobSpec, resume: bool, parser: Any, chaos: Any,
             policy: Optional[JobPolicy]) -> JobReport:
    policy = policy or JobPolicy()
    t_start = time.perf_counter()
    reg = metrics()
    # The prologue, up to the stream: plan, manifest, sweep, committed
    # set, parser and pool construction.
    with pipeline_stage("job_plan"):
        if spec.n_hosts < 1 or not 0 <= spec.host_index < spec.n_hosts:
            raise ValueError(
                f"bad pod placement: host {spec.host_index} of "
                f"{spec.n_hosts}"
            )
        pod = spec.n_hosts > 1
        own_name = (host_manifest_name(spec.host_index) if pod
                    else MANIFEST_NAME)
        from ..analytics.spec import parse_aggregate_config

        agg_spec = parse_aggregate_config(spec.aggregate)
        sources_norm = normalize_sources(spec.sources)
        plan = plan_shards(sources_norm, spec.shard_bytes)
        out_dir = spec.out_dir
        os.makedirs(out_dir, exist_ok=True)
        fingerprint = spec.fingerprint(sources_norm)
        manifest = JobManifest.load(out_dir, own_name)
        if manifest is not None:
            if not resume:
                raise ManifestError(
                    f"{out_dir} already holds a job manifest; resume it "
                    "(the default) or clear the directory for a fresh run"
                )
            mismatch = manifest.mismatch(fingerprint)
            if mismatch:
                raise ManifestError(
                    f"refusing to resume {out_dir}: manifest belongs to a "
                    f"different job ({mismatch})"
                )
        else:
            manifest = JobManifest.fresh(fingerprint)
            manifest.save(out_dir, own_name)
        # Crash debris: tmp files can only be leftovers of an interrupted,
        # uncommitted write — safe to sweep (committed files were renamed).
        # Pod-safe: only debris whose writer pid is dead is swept — another
        # live host's mid-write temp file must not be yanked out from under
        # its fsync (sweepable_temp_files applies the dead-pid rule).
        for name in sweepable_temp_files(out_dir):
            try:
                os.unlink(os.path.join(out_dir, name))
                reg.increment("job_temp_files_swept_total")
            except OSError as e:
                log_warning_once(LOG, f"job: could not sweep {name}: {e}")

        # What to skip: every shard durably committed by ANYONE — this
        # host's earlier runs, the merged top-level manifest, and (pod) the
        # other hosts' manifests.  A fingerprint divergence in any commit
        # log refuses the run, exactly like the single-manifest resume.
        committed_before = set(committed_anywhere(
            out_dir, fingerprint, preloaded={own_name: manifest}))
        owned = (shards_for_host(plan, spec.n_hosts, spec.host_index)
                 if pod else plan)
        remaining = [s for s in owned if s.index not in committed_before]
        report = JobReport(out_dir=out_dir, shards_total=len(owned),
                           skipped=len(owned) - len(remaining),
                           n_hosts=spec.n_hosts, host_index=spec.host_index)
        if report.skipped:
            reg.increment("job_shards_skipped_total", report.skipped)
        pool_chaos, writer_chaos, device_chaos = _split_chaos(chaos)
        writer = JobWriter(out_dir, retries=policy.io_retries,
                           backoff_base_s=policy.io_backoff_s,
                           chaos=writer_chaos)
        reg.increment("job_runs_total")
        # One durable job = one connected trace (docs/OBSERVABILITY.md
        # "Tracing"): a pod launcher hands its context down via the
        # LOGPARSER_TPU_TRACEPARENT env; a standalone job head-samples
        # under LOGPARSER_TPU_TRACE_SAMPLE.  Feeder shards and shard
        # commits become child spans below.
        from ..tracing import child_span, root_span

        job_span = root_span(
            "job_run",
            traceparent=os.environ.get("LOGPARSER_TPU_TRACEPARENT"),
            attrs={"host_index": spec.host_index, "n_hosts": spec.n_hosts,
                   "shards": len(owned)},
        )
        job_ctx = job_span.context if job_span is not None else None
        if not remaining:
            report.wall_s = time.perf_counter() - t_start
            if job_span is not None:
                job_span.end(committed=0, skipped=report.skipped)
            return report

        own_parser = parser is None
        # A caller-supplied parser joins the drill too (device faults belong
        # to the parse step wherever the parser came from) but is handed
        # back with its PRIOR arming restored in the finally below — a
        # caller mid-drill of its own must not find its injections wiped.
        armed_caller_parser = (not own_parser) and device_chaos is not None
        prior_device_chaos = (
            getattr(parser, "_device_chaos", None) if armed_caller_parser
            else None
        )
        if own_parser:
            from ..tpu.batch import TpuBatchParser

            # Jobs deliver copy-mode IPC tables, never string_view columns:
            # device view emission would be pure kernel + D2H waste here.
            # data_parallel lays the fused parse over this host's local
            # chips (jax.sharding mesh; docs/JOBS.md "Pod jobs").
            parser = TpuBatchParser(
                spec.log_format, list(spec.fields), view_fields=(),
                data_parallel=spec.data_parallel,
                device_chaos=device_chaos,
            )
            if chaos is not None and device_chaos is None:
                # An EXPLICIT chaos arg with no device faults must override
                # the constructor's env fallback — the caller already chose
                # this run's whole fault plan.
                parser.arm_device_chaos(None)
        elif armed_caller_parser:
            parser.arm_device_chaos(device_chaos)
        if agg_spec is not None:
            # Field-level spec validation needs the built parser; a bad
            # spec must refuse the job BEFORE the pool spins up (and must
            # not leak a just-built parser's worker pools).
            try:
                agg_spec.validate_for(parser)
            except Exception:
                if own_parser:
                    parser.close()
                raise

        # The pool runs over a RENUMBERED plan (FeederPool requires index ==
        # position); remaining[pool_index] maps back to the global shard.
        pool_shards = [replace(s, index=i) for i, s in enumerate(remaining)]
        pool = FeederPool(
            spec.sources,
            workers=spec.workers or min(default_feeder_workers(),
                                        max(1, len(pool_shards))),
            shard_bytes=spec.shard_bytes,
            batch_lines=spec.batch_lines,
            transport=spec.transport,
            use_processes=spec.use_processes,
            chaos=pool_chaos,
            # A batch job's full queue is its healthy steady state, not
            # service overload — stay out of the admission signal.
            backpressure_signal=False,
            shard_plan=pool_shards,
        )

    meta: deque = deque()
    # One feeder_shard span per shard the fabric feeds: opened when the
    # shard's first batch arrives, closed when the next shard starts
    # (trailing span closed in the finally below).
    feed_state: Dict[str, Any] = {"shard": None, "span": None}

    def _tap(batches):
        for eb in batches:
            if job_ctx is not None and eb.shard != feed_state["shard"]:
                if feed_state["span"] is not None:
                    feed_state["span"].end()
                feed_state["shard"] = eb.shard
                feed_state["span"] = child_span(
                    "feeder_shard", job_ctx,
                    attrs={"shard": remaining[eb.shard].index},
                )
            meta.append((eb.shard, eb.index, eb.n_lines, eb.source_bytes))
            yield eb

    def _commit(pool_idx: int, acc: _ShardAccumulator) -> None:
        import pyarrow as pa

        shard = remaining[pool_idx]
        c_span = child_span("job_shard_commit", job_ctx,
                            attrs={"shard": shard.index})
        data_table = (
            pa.concat_tables(acc.tables) if acc.tables else None
        )
        def fail(e: ShardWriteError) -> None:
            report.failed.append({"shard": shard.index, "error": str(e)})
            reg.increment("job_shards_failed_total",
                          labels={"reason": "write_io"})
            if c_span is not None:
                c_span.end(outcome="failed")
            LOG.error("job: shard %d failed durably: %s", shard.index, e)

        agg_state = None
        if agg_spec is not None:
            # Always a sidecar, even for an empty shard: a committed
            # aggregate shard's record must carry its partial frame
            # (merged_job_aggregate folds records, not directory scans).
            from ..analytics.state import AggregateState

            agg_state = (acc.agg if acc.agg is not None
                         else AggregateState(agg_spec))
        try:
            record = writer.write_shard(
                shard, data_table, acc.rejects, acc.lines,
                acc.payload_bytes, agg_state=agg_state, agg_rows=acc.rows,
            )
        except ShardWriteError as e:
            fail(e)
            return
        # The manifest rewrite is the commit point, and it writes to the
        # same disk the shard files just hit — route it through the same
        # bounded retry ladder, and on exhaustion fail the SHARD (its
        # renamed files without an entry are exactly the not-committed
        # crash state resume already handles), never the job.
        try:
            manifest.commit(
                out_dir, record,
                write_bytes=lambda name, data: writer.write_file(
                    name, data, shard.index
                ),
                name=own_name,
            )
        except ShardWriteError as e:
            fail(e)
            return
        report.committed += 1
        report.lines += acc.lines
        report.rows += record.rows
        report.rejects += record.rejects
        report.payload_bytes += acc.payload_bytes
        reg.increment("job_shards_committed_total")
        if c_span is not None:
            c_span.end(outcome="committed", rows=record.rows,
                       lines=acc.lines)
        # Reject accounting lands at COMMIT time: the counter equals
        # lines durably present in reject tables, exactly — a failed
        # shard's rejects never count, a replayed shard's count once.
        for reason, n in acc.reason_counts.items():
            report.reject_reasons[reason] = (
                report.reject_reasons.get(reason, 0) + n
            )
            reg.increment("job_rejected_lines_total", n,
                          labels={"reason": reason})

    current: Optional[int] = None
    acc = _ShardAccumulator()
    commits_this_run = 0

    def _advance_to(pool_idx: Optional[int]) -> bool:
        """Commit the current shard and any EMPTY shards (no batches)
        between it and ``pool_idx`` (None = end of stream).  Returns
        False when the stop_after_shards budget ran out or the
        preemption stop_event fired — every commit boundary is a legal
        stopping point (the shard just committed stays committed; the
        manifest resumes exactly)."""
        nonlocal current, acc, commits_this_run
        end = pool_idx if pool_idx is not None else len(pool_shards)
        while current is not None and current < end:
            with pipeline_stage("job_commit"):
                _commit(current, acc)
            acc = _ShardAccumulator()
            commits_this_run += 1
            if (policy.stop_after_shards is not None
                    and commits_this_run >= policy.stop_after_shards):
                return False
            if (policy.stop_event is not None
                    and policy.stop_event.is_set()
                    # Only with work still pending: a notice landing on
                    # the FINAL commit must not turn a finished run
                    # into a preempted one (the relaunch would be a
                    # pure no-op and the report would read incomplete).
                    and (pool_idx is not None or current + 1 < end)):
                report.preempted = True
                reg.increment("job_preempted_total")
                LOG.warning(
                    "job: preemption stop (SIGTERM) honored at the "
                    "shard %d commit boundary — resume re-parses "
                    "nothing committed", remaining[current].index,
                )
                return False
            current += 1
        current = end if pool_idx is not None else None
        return True

    try:
        if agg_spec is not None:
            stream = parser.aggregate_batch_stream(
                _tap(pool.batches(detach=True)), agg_spec,
            )
        else:
            stream = parser.parse_batch_stream(
                _tap(pool.batches(detach=True)), emit_views=False,
            )
        for result in stream:
            pshard, bidx, n_lines, src_bytes = meta.popleft()
            if current is None:
                current = 0
            if pshard != current and not _advance_to(pshard):
                report.stopped_early = True
                return report
            with pipeline_stage("job_fold", items=n_lines):
                if agg_spec is not None:
                    _fold_outcome(remaining[pshard], bidx, src_bytes,
                                  result, acc)
                else:
                    _fold_result(remaining[pshard], bidx, src_bytes,
                                 result, acc, reg)
        if current is None and pool_shards:
            current = 0  # every shard was empty
        if not _advance_to(None):
            report.stopped_early = True
            return report
    finally:
        pool.close()
        if armed_caller_parser:
            # Hand the caller's parser back as received: the job's
            # injections must not outlive it, and a chaos plan the
            # caller had armed BEFORE the job must survive it.
            try:
                parser.arm_device_chaos(prior_device_chaos)
            except Exception as e:  # noqa: BLE001 — teardown best-effort
                log_warning_once(LOG, f"job: chaos disarm failed: {e}")
        if own_parser:
            # A parser built here is ours to release: its oracle worker
            # pool / assembly threads must not outlive the job (a
            # caller looping run_job would otherwise accumulate pools).
            try:
                parser.close()
            except Exception as e:  # noqa: BLE001 — teardown best-effort
                log_warning_once(LOG, f"job: parser close failed: {e}")
        report.wall_s = time.perf_counter() - t_start
        if feed_state["span"] is not None:
            feed_state["span"].end()
        if job_span is not None:
            job_span.end(committed=report.committed,
                         skipped=report.skipped,
                         preempted=report.preempted)
    return report


def _fold_outcome(shard: Shard, batch_index: int, src_bytes: int,
                  outcome, acc: _ShardAccumulator) -> None:
    """Aggregate-mode twin of :func:`_fold_result`: merge one
    :class:`~logparser_tpu.analytics.state.AggregateOutcome` into its
    shard's accumulator — partial state, good-line count, and the same
    reasoned reject ledger the row path lands (an aggregate job never
    silently drops a bad line either)."""
    line_base = acc.lines
    if acc.agg is None:
        acc.agg = outcome.state
    else:
        acc.agg.merge(outcome.state)
    acc.rows += outcome.good_lines
    for row, reason, raw in outcome.reject_items:
        acc.rejects.append((
            shard.index, batch_index, line_base + int(row), reason,
            bytes(raw),
        ))
        acc.reason_counts[reason] = acc.reason_counts.get(reason, 0) + 1
    acc.lines += outcome.lines_read
    acc.payload_bytes += int(src_bytes)


def _fold_result(shard: Shard, batch_index: int, src_bytes: int, result,
                 acc: _ShardAccumulator, reg) -> None:
    """Fold one BatchResult into its shard's accumulator: the valid
    rows' Arrow table (copy mode — the file outlives the batch buffers)
    and one reject row per invalid line, reasoned and raw."""
    import pyarrow as pa

    line_base = acc.lines
    valid = np.asarray(result.valid[:result.lines_read], dtype=bool)
    if result.lines_read:
        table = result.to_arrow(include_validity=False, strings="copy")
        if not valid.all():
            table = table.filter(pa.array(valid))
        if table.num_rows:
            acc.tables.append(table)
    for i in sorted(result.reject_reasons):
        reason = result.reject_reasons[i]
        acc.rejects.append((
            shard.index, batch_index, line_base + i, reason,
            bytes(result.raw_line(i)),
        ))
        acc.reason_counts[reason] = acc.reason_counts.get(reason, 0) + 1
    n_rej = int(np.count_nonzero(~valid))
    if n_rej != len(result.reject_reasons):
        # Defensive: every invalid row must carry a reason — a drift
        # here means a new reject path forgot the ledger.  Surface it
        # loudly (counted + warned, STATIC warn-once key; the counts
        # ride DEBUG), still never a raise.
        log_warning_once(
            LOG,
            "job: invalid rows without reject reasons in a batch "
            "(reject ledger drifted; job_reject_ledger_drift_total "
            "counts batches, details at DEBUG)",
        )
        LOG.debug("job: ledger drift on shard %d batch %d: %d invalid "
                  "rows, %d reasons", shard.index, batch_index, n_rej,
                  len(result.reject_reasons))
        reg.increment("job_reject_ledger_drift_total")
    acc.lines += result.lines_read
    acc.payload_bytes += int(src_bytes)
