"""CLI for the durable job runner: ``python -m logparser_tpu.jobs``.

Examples::

    # parse a corpus into sharded Arrow files (resumable by default)
    python -m logparser_tpu.jobs access.log \\
        --format '%h %l %u %t "%r" %>s %b' \\
        --field IP:connection.client.host \\
        --field STRING:request.status.last \\
        --out /data/job1

    # after a crash: the same command resumes from the manifest,
    # skipping committed shards

Exit codes: 0 = job complete; 1 = one or more shards failed durably
(resume retries them); 2 = configuration error (manifest mismatch,
bad arguments); 3 = preempted — SIGTERM (the cloud-TPU preemption
notice) was honored at a shard commit boundary: the manifest resumes
exactly, an orchestrator should simply relaunch the same command
(docs/JOBS.md "Preemption").
"""
from __future__ import annotations

import argparse
import json
import signal
import sys
import threading

from .manifest import ManifestError, merge_manifests
from .writer import merged_job_aggregate
from .runner import (
    DEFAULT_JOB_BATCH_LINES,
    EXIT_PREEMPTED,
    JobPolicy,
    JobSpec,
    run_job,
)
from ..feeder.shards import DEFAULT_SHARD_BYTES


def build_arg_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m logparser_tpu.jobs",
        description="Durable corpus -> sharded-Arrow parse job "
                    "(docs/JOBS.md)",
    )
    ap.add_argument("sources", nargs="+",
                    help="input log files, in corpus order")
    ap.add_argument("--format", required=True, dest="log_format",
                    help="the Apache/NGINX LogFormat string")
    ap.add_argument("--field", action="append", required=True,
                    dest="fields", metavar="TYPE:path",
                    help="requested field id (repeatable)")
    ap.add_argument("--out", required=True, dest="out_dir",
                    help="job output directory (manifest + shard files)")
    ap.add_argument("--shard-bytes", type=int,
                    default=DEFAULT_SHARD_BYTES)
    ap.add_argument("--batch-lines", type=int,
                    default=DEFAULT_JOB_BATCH_LINES)
    ap.add_argument("--workers", type=int, default=None,
                    help="feeder worker count (default: auto)")
    ap.add_argument("--threads", action="store_true",
                    help="thread feeder workers instead of processes")
    ap.add_argument("--transport", choices=("ring", "pickle", "inline"),
                    default=None)
    ap.add_argument("--no-resume", action="store_true",
                    help="refuse to continue an existing manifest "
                         "(default: resume it)")
    ap.add_argument("--io-retries", type=int, default=3)
    ap.add_argument("--hosts", type=int, default=1,
                    help="pod size: partition the shard plan over this "
                         "many hosts (docs/JOBS.md 'Pod jobs')")
    ap.add_argument("--host-index", type=int, default=0,
                    help="which pod host THIS run is (0-based; commits "
                         "into manifest.host-NNN.json)")
    ap.add_argument("--merge", action="store_true",
                    help="after this host's share completes, merge all "
                         "per-host manifests into manifest.json "
                         "(run standalone with --merge-only)")
    ap.add_argument("--merge-only", action="store_true",
                    help="only merge per-host manifests into "
                         "manifest.json; parse nothing")
    ap.add_argument("--data-parallel", type=int, default=None,
                    help="lay the device parse over N local chips "
                         "(jax.sharding mesh; default: single device)")
    ap.add_argument("--aggregate", default=None, metavar="JSON",
                    help="aggregate mode (docs/ANALYTICS.md): a JSON "
                         "list of aggregation ops; shards land partial-"
                         "aggregate sidecars instead of data tables and "
                         "the completed job prints the merged aggregate "
                         "summary")
    ap.add_argument("--compile-cache", default=None, metavar="DIR",
                    help="persistent compile-cache directory "
                         "(docs/COMPILE.md): resumed/repeated jobs and "
                         "pod hosts deserialize cached executables "
                         "instead of recompiling "
                         "(exported as JAX_COMPILATION_CACHE_DIR)")
    ap.add_argument("--stop-after-shards", type=int, default=None,
                    help=argparse.SUPPRESS)  # crash-drill hook (smoke)
    return ap


def main(argv=None) -> int:
    args = build_arg_parser().parse_args(argv)
    if args.compile_cache:
        from ..tpu.compile_cache import set_cache_root

        set_cache_root(args.compile_cache)
    # SIGTERM = the cloud-TPU preemption notice: finish/commit the
    # current shard boundary, exit EXIT_PREEMPTED (resumable — cheaper
    # than the SIGKILL path by exactly one replayed shard).  An
    # immediate stop is SIGKILL, which the manifest already survives
    # (docs/JOBS.md "Preemption").  The previous disposition is
    # restored on the way out — an embedding process must not keep
    # swallowing SIGTERM into a dead Event after main() returns.
    stop = threading.Event()
    try:
        prev_sigterm = signal.signal(
            signal.SIGTERM, lambda signum, frame: stop.set()
        )
    except ValueError:
        prev_sigterm = None  # not the main thread: no handler, no stop
    try:
        return _main(args, stop)
    finally:
        if prev_sigterm is not None:
            try:
                signal.signal(signal.SIGTERM, prev_sigterm)
            except (ValueError, TypeError):
                pass


def _main(args, stop) -> int:
    spec = JobSpec(
        sources=list(args.sources),
        log_format=args.log_format,
        fields=list(args.fields),
        out_dir=args.out_dir,
        shard_bytes=args.shard_bytes,
        batch_lines=args.batch_lines,
        workers=args.workers,
        use_processes=False if args.threads else None,
        transport=args.transport,
        n_hosts=args.hosts,
        host_index=args.host_index,
        data_parallel=args.data_parallel,
        aggregate=args.aggregate,
    )
    policy = JobPolicy(io_retries=args.io_retries,
                       stop_after_shards=args.stop_after_shards,
                       stop_event=stop)
    try:
        if args.merge_only:
            merged = merge_manifests(args.out_dir)
            d = {
                "out_dir": args.out_dir,
                "merged_shards": len(merged.shards),
            }
            if merged.job.get("aggregate"):
                d["aggregate"] = merged_job_aggregate(
                    args.out_dir, merged).summary()
            print(json.dumps(d))
            return 0
        report = run_job(spec, resume=not args.no_resume, policy=policy)
        if args.merge and report.complete:
            merged = merge_manifests(args.out_dir)
            d = report.as_dict()
            d["merged_shards"] = len(merged.shards)
            if args.aggregate:
                d["aggregate"] = merged_job_aggregate(
                    args.out_dir, merged).summary()
            print(json.dumps(d))
            return 0  # complete implies no failed shards
    except (ManifestError, ValueError) as e:
        print(json.dumps({"error": str(e)}), file=sys.stderr)
        return 2
    d = report.as_dict()
    if args.aggregate and args.hosts == 1 and report.complete:
        # Single-host aggregate job: the merged answer is ready — print
        # it (a pod host's share is partial; --merge owns that case).
        try:
            d["aggregate"] = merged_job_aggregate(args.out_dir).summary()
        except (OSError, ValueError) as e:
            print(json.dumps({"error": str(e)}), file=sys.stderr)
            return 2
    print(json.dumps(d))
    if report.failed:
        return 1
    return EXIT_PREEMPTED if report.preempted else 0


if __name__ == "__main__":
    sys.exit(main())
