"""Pod job runner: N per-host jobs + manifest merge (docs/JOBS.md).

``run_pod(PodSpec(...))`` drives one pod-level job:

1. the GLOBAL shard plan is computed once (``feeder/shards.py`` — the
   same plan every host computes independently from the same spec, so
   no plan ever travels over a wire);
2. each host runs its contiguous disjoint slice of that plan as an
   ordinary single-host job (``jobs/runner.py`` with
   ``n_hosts``/``host_index`` set), committing into its per-host
   manifest — subprocesses by default (the simulated-pod shape: real
   deployments run the same CLI on real hosts against a shared
   filesystem), or inline in-process for tests and the bench;
3. a host that dies or fails is relaunched up to
   ``PodPolicy.host_retries`` times — resume semantics make this free
   (its committed shards are skipped; only the uncommitted tail of its
   range replays);
4. the per-host manifests merge into the top-level ``manifest.json``
   (fingerprint-checked, duplicate-commit-checked), leaving a directory
   byte-indistinguishable from a single-host run over the same spec.

The kill-drill invariant, one level up from the single-host one: SIGKILL
any host mid-job, rerun ``run_pod`` (or resume the one host), and the
merged output is byte-identical to an undisturbed single-host run, with
committed shards never re-parsed — drilled live in
``tools/pod_smoke.py`` and gated in bench's ``pod`` section.
"""
from __future__ import annotations

import json
import logging
import os
import subprocess
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

from ..feeder.shards import (
    DEFAULT_SHARD_BYTES,
    SourceT,
    normalize_sources,
    plan_shards,
)
from ..jobs.manifest import ManifestError, host_manifest_name, merge_manifests
from ..jobs.runner import (
    DEFAULT_JOB_BATCH_LINES,
    EXIT_PREEMPTED,
    JobPolicy,
    JobSpec,
    run_job,
)
from ..observability import metrics

LOG = logging.getLogger(__name__)


@dataclass
class PodSpec:
    """One pod job: the output-determining geometry (identical to
    :class:`~logparser_tpu.jobs.runner.JobSpec`'s — n_hosts is
    EXECUTION-only, which is what makes an N-host merge byte-comparable
    to a 1-host run) plus pod execution knobs."""

    sources: Sequence[SourceT]
    log_format: str
    fields: Sequence[str]
    out_dir: str
    n_hosts: int = 2
    shard_bytes: int = DEFAULT_SHARD_BYTES
    batch_lines: int = DEFAULT_JOB_BATCH_LINES
    # Analytics pushdown (docs/ANALYTICS.md): aggregate-mode pod — each
    # host lands partial-aggregate sidecars, the merge step folds them
    # into the pod-level answer.  Output-determining (fingerprinted by
    # every host job).
    aggregate: Optional[Any] = None
    # Execution-only:
    workers: Optional[int] = None          # feeder workers per host
    use_processes: Optional[bool] = None
    transport: Optional[str] = None
    data_parallel: Optional[int] = None    # chips per host (mesh DP)
    host_env: Optional[Dict[str, str]] = None  # extra env per subprocess
    # Persistent compile cache (docs/COMPILE.md) — execution-only: cached
    # executables change when work starts, never what it produces.
    compile_cache: Optional[str] = None

    def host_job_spec(self, host_index: int) -> JobSpec:
        return JobSpec(
            sources=list(self.sources),
            log_format=self.log_format,
            fields=list(self.fields),
            out_dir=self.out_dir,
            shard_bytes=self.shard_bytes,
            batch_lines=self.batch_lines,
            workers=self.workers,
            use_processes=self.use_processes,
            transport=self.transport,
            n_hosts=self.n_hosts,
            host_index=host_index,
            data_parallel=self.data_parallel,
            aggregate=self.aggregate,
        )


@dataclass
class PodPolicy:
    """Pod runner tunables."""

    host_retries: int = 1        # relaunches per dead/failed host
    host_timeout_s: float = 3600.0
    io_retries: int = 3          # per-host writer retry ladder
    inline: bool = False         # run hosts sequentially in-process
    merge: bool = True           # merge manifests after the host wave


@dataclass
class HostResult:
    """One host's outcome across its launches."""

    host_index: int
    launches: int = 0
    returncode: Optional[int] = None
    report: Optional[Dict[str, Any]] = None  # the host job's as_dict()
    error: Optional[str] = None
    preempted: bool = False      # a launch exited EXIT_PREEMPTED

    @property
    def ok(self) -> bool:
        return (self.returncode == 0 and self.report is not None
                and self.report.get("complete", False))


@dataclass
class PodReport:
    """What one ``run_pod`` call did."""

    out_dir: str
    n_hosts: int
    shards_total: int = 0
    merged_shards: int = 0
    hosts: List[HostResult] = field(default_factory=list)
    wall_s: float = 0.0
    merge_error: Optional[str] = None
    # Aggregate-mode pods: the merged job-level aggregate summary
    # (None for row pods or before a successful merge).
    aggregate: Optional[List[Dict[str, Any]]] = None

    @property
    def complete(self) -> bool:
        return (self.merge_error is None
                and self.merged_shards == self.shards_total
                and all(h.ok for h in self.hosts))

    def as_dict(self) -> Dict[str, Any]:
        return {
            "out_dir": self.out_dir,
            "n_hosts": self.n_hosts,
            "shards_total": self.shards_total,
            "merged_shards": self.merged_shards,
            "complete": self.complete,
            "wall_s": round(self.wall_s, 4),
            **({"merge_error": self.merge_error}
               if self.merge_error else {}),
            **({"aggregate": self.aggregate}
               if self.aggregate is not None else {}),
            "hosts": [
                {
                    "host": h.host_index,
                    "launches": h.launches,
                    "returncode": h.returncode,
                    "ok": h.ok,
                    **({"preempted": True} if h.preempted else {}),
                    **({"error": h.error} if h.error else {}),
                    **({"committed": h.report.get("committed"),
                        "skipped": h.report.get("skipped"),
                        "rejects": h.report.get("rejects")}
                       if h.report else {}),
                }
                for h in self.hosts
            ],
        }


def host_argv(spec: PodSpec, host_index: int,
              policy: PodPolicy) -> List[str]:
    """The per-host CLI line — exactly what an operator runs on each
    real host of a shared-filesystem pod (the subprocess path and the
    documentation are the same command)."""
    argv = [sys.executable, "-m", "logparser_tpu.jobs",
            *[os.fspath(s) for s in spec.sources],
            "--format", spec.log_format,
            "--out", spec.out_dir,
            "--shard-bytes", str(spec.shard_bytes),
            "--batch-lines", str(spec.batch_lines),
            "--hosts", str(spec.n_hosts),
            "--host-index", str(host_index),
            "--io-retries", str(policy.io_retries)]
    for f in spec.fields:
        argv += ["--field", f]
    if spec.workers:
        argv += ["--workers", str(spec.workers)]
    if spec.use_processes is False:
        argv += ["--threads"]
    if spec.transport:
        argv += ["--transport", spec.transport]
    if spec.data_parallel:
        argv += ["--data-parallel", str(spec.data_parallel)]
    if spec.compile_cache:
        argv += ["--compile-cache", spec.compile_cache]
    if spec.aggregate is not None:
        # Canonical JSON on the wire: every host must fingerprint the
        # IDENTICAL spec string or the merge would refuse its manifests.
        from ..analytics.spec import parse_aggregate_config

        argv += ["--aggregate",
                 parse_aggregate_config(spec.aggregate).canonical_key()]
    return argv


def _launch_host(spec: PodSpec, host_index: int, policy: PodPolicy,
                 traceparent: Optional[str] = None,
                 chip_env: Optional[Dict[str, str]] = None
                 ) -> subprocess.Popen:
    env = dict(os.environ)
    if chip_env:
        env.update(chip_env)
    if spec.host_env:
        env.update(spec.host_env)
    if traceparent:
        # The host's job_run root span parents under this pod's trace;
        # the env var is the cross-process carrier (docs/OBSERVABILITY.md
        # "Tracing").
        env["LOGPARSER_TPU_TRACEPARENT"] = traceparent
    return subprocess.Popen(
        host_argv(spec, host_index, policy),
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        env=env, text=True, start_new_session=True,
    )


def _committed_in_host_manifest(out_dir: str, host_index: int) -> int:
    """Committed-shard count per the host's on-disk commit log."""
    from ..jobs.manifest import count_committed_shards

    return count_committed_shards(out_dir, host_manifest_name(host_index))


def _preemption_watcher(out_dir: str, host_index: int, after: int,
                        proc: subprocess.Popen,
                        poll_s: float = 0.05) -> None:
    """The ``preempt_host`` chaos drill: SIGTERM the host's jobs CLI
    once its commit log holds ``after`` shards — the CLI must finish
    the shard boundary in flight and exit EXIT_PREEMPTED, and the
    relaunch must resume with zero re-parsed shards (docs/JOBS.md
    "Preemption")."""
    while proc.poll() is None:
        if _committed_in_host_manifest(out_dir, host_index) >= after:
            try:
                proc.terminate()
            except OSError:
                pass
            return
        time.sleep(poll_s)


def _host_report_from_stdout(text: str) -> Optional[Dict[str, Any]]:
    for line in reversed((text or "").splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except ValueError:
                return None
    return None


def _run_host_inline(spec: PodSpec, host_index: int,
                     policy: PodPolicy, parser: Any) -> HostResult:
    hr = HostResult(host_index=host_index, launches=1)
    try:
        report = run_job(
            spec.host_job_spec(host_index), parser=parser,
            policy=JobPolicy(io_retries=policy.io_retries),
        )
        hr.report = report.as_dict()
        hr.returncode = 0 if not report.failed else 1
    except (ManifestError, ValueError) as e:
        hr.returncode = 2
        hr.error = str(e)
    return hr


def run_pod(spec: PodSpec, policy: Optional[PodPolicy] = None,
            parser: Any = None, chaos: Any = None) -> PodReport:
    """Run (or resume) one pod job end to end: host wave, bounded
    relaunch of dead/failed/preempted hosts, manifest merge.  ``parser``
    is only legal inline (subprocess hosts build their own).  ``chaos``
    arms pod-tier fault injection (``preempt_host`` — subprocess mode
    only; ChaosSpec / grammar string, default the LOGPARSER_TPU_CHAOS
    env var); see module docstring."""
    policy = policy or PodPolicy()
    if spec.n_hosts < 1:
        raise ValueError(f"n_hosts must be positive, got {spec.n_hosts}")
    # Subprocess hosts share this machine's chips: one chip per host
    # (logparser_tpu/chips.py), refused before anything starts when there
    # are fewer chips than hosts.  A 1-host pod keeps every chip, so its
    # data_parallel mesh spans the machine.
    from ..chips import assign_chips

    chip_envs = [] if policy.inline else assign_chips(spec.n_hosts)
    from ..tools.chaos import ChaosSpec, PodChaos

    if chaos is None:
        chaos_spec = ChaosSpec.from_env()
    elif isinstance(chaos, str):
        chaos_spec = ChaosSpec.parse(chaos)
    else:
        chaos_spec = chaos
    pod_chaos = PodChaos(chaos_spec) if chaos_spec is not None else None
    # host -> committed-shard trigger; popped as each fires (once per
    # pod run, so the relaunch completes clean — the recovery drill).
    preempt_plan = pod_chaos.preempt_plan() if pod_chaos else {}
    t0 = time.perf_counter()
    reg = metrics()
    reg.increment("pod_runs_total")
    from ..tracing import child_span, root_span

    pod_span = root_span(
        "pod_run",
        traceparent=os.environ.get("LOGPARSER_TPU_TRACEPARENT"),
        attrs={"hosts": spec.n_hosts},
    )
    pod_ctx = pod_span.context if pod_span is not None else None
    plan = plan_shards(normalize_sources(spec.sources), spec.shard_bytes)
    report = PodReport(out_dir=spec.out_dir, n_hosts=spec.n_hosts,
                       shards_total=len(plan))
    results = [HostResult(host_index=i) for i in range(spec.n_hosts)]
    report.hosts = results

    if policy.inline:
        for i in range(spec.n_hosts):
            h_span = child_span("pod_host_launch", pod_ctx,
                                attrs={"host": i, "inline": True})
            hr = _run_host_inline(spec, i, policy, parser)
            # Each failed LAUNCH counts once; a config refusal (rc 2)
            # never retries — resuming it would refuse identically.
            while (not hr.ok and hr.returncode != 2
                   and hr.launches <= policy.host_retries):
                reg.increment("pod_host_failures_total")
                retry = _run_host_inline(spec, i, policy, parser)
                retry.launches = hr.launches + 1
                hr = retry
            if not hr.ok:
                reg.increment("pod_host_failures_total")
            if h_span is not None:
                h_span.end(returncode=hr.returncode,
                           launches=hr.launches)
            results[i] = hr
    else:
        if parser is not None:
            raise ValueError("parser reuse requires PodPolicy(inline=True)")
        pending = list(range(spec.n_hosts))
        attempt = 0
        while pending and attempt <= policy.host_retries:
            procs = {}
            host_spans = {}
            for i in pending:
                results[i].launches += 1
                reg.increment("pod_hosts_launched_total")
                h_span = child_span(
                    "pod_host_launch", pod_ctx,
                    attrs={"host": i, "attempt": attempt})
                host_spans[i] = h_span
                procs[i] = _launch_host(
                    spec, i, policy,
                    traceparent=(h_span.traceparent
                                 if h_span is not None else None),
                    chip_env=chip_envs[i])
                after = preempt_plan.pop(i, None)
                if after is not None:
                    threading.Thread(
                        target=_preemption_watcher,
                        args=(spec.out_dir, i, after, procs[i]),
                        name=f"pod-preempt-{i}", daemon=True,
                    ).start()
            reg.gauge_set("pod_hosts_alive", len(procs))
            deadline = time.monotonic() + policy.host_timeout_s
            for i, p in procs.items():
                budget = max(0.0, deadline - time.monotonic())
                try:
                    out, _ = p.communicate(timeout=budget)
                except subprocess.TimeoutExpired:
                    p.kill()
                    out, _ = p.communicate()
                    results[i].error = (
                        f"host {i} exceeded its "
                        f"{policy.host_timeout_s:.0f}s budget (killed)"
                    )
                results[i].returncode = p.returncode
                results[i].report = _host_report_from_stdout(out)
                if host_spans.get(i) is not None:
                    host_spans[i].end(returncode=p.returncode)
                reg.gauge_set(
                    "pod_hosts_alive",
                    sum(1 for q in procs.values() if q.poll() is None),
                )
            failed = [i for i in pending if not results[i].ok
                      and results[i].returncode != 2]
            for i in failed:
                if results[i].returncode == EXIT_PREEMPTED:
                    # The clean preemption exit: the host honored
                    # SIGTERM at a commit boundary — a resume is free
                    # (zero re-parsed shards), so a relaunch is the
                    # whole recovery.
                    results[i].preempted = True
                    reg.increment("pod_host_preemptions_total")
                    LOG.warning(
                        "pod: host %d preempted (clean SIGTERM exit)%s",
                        i,
                        " — relaunching (resume re-parses zero "
                        "committed shards)"
                        if attempt < policy.host_retries else "",
                    )
                    continue
                reg.increment("pod_host_failures_total")
                LOG.warning("pod: host %d failed (rc=%s)%s", i,
                            results[i].returncode,
                            " — relaunching (resume skips its committed "
                            "shards)" if attempt < policy.host_retries
                            else "")
            pending = failed
            attempt += 1
        reg.gauge_set("pod_hosts_alive", 0)

    if policy.merge:
        try:
            merged = merge_manifests(spec.out_dir)
            report.merged_shards = len(merged.shards)
            reg.increment("pod_merge_runs_total")
            reg.increment("pod_merged_shards_total", len(merged.shards))
            if spec.aggregate is not None:
                # Pod-level aggregate: fold every committed shard's
                # partial sidecar — hosts merge exactly like manifests
                # (docs/ANALYTICS.md), and the answer over a partial
                # merge is the partial answer, never a wrong one.
                from ..jobs.writer import merged_job_aggregate

                t_m = time.perf_counter()
                report.aggregate = merged_job_aggregate(
                    spec.out_dir, merged).summary()
                reg.observe("analytics_partial_merge_seconds",
                            time.perf_counter() - t_m)
        except (ManifestError, ValueError, OSError) as e:
            report.merge_error = str(e)
            reg.increment("pod_merge_refusals_total")
    report.wall_s = time.perf_counter() - t0
    if pod_span is not None:
        pod_span.end(merged_shards=report.merged_shards,
                     wall_s=round(report.wall_s, 3))
    return report
