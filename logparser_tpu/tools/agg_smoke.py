"""CI analytics smoke: the pushdown exactness contract on live tiers.

Drills the on-device analytics pushdown (docs/ANALYTICS.md) end to end
and fails (exit 1) unless:

- a LIVE service session configured with an ``aggregate`` spec returns
  an aggregate frame EQUAL to a local host-oracle referee over the same
  lines (forced garbage + long-overflow fold rows included), while a
  row session on the same server keeps serving row frames;
- the pushdown accounting moved: ``analytics_batches_total{path=
  "device"}`` and ``analytics_d2h_bytes_saved_total`` (the D2H bytes
  the aggregate path did NOT ship vs the packed row payload) are
  positive;
- an aggregate JOB (the jobs CLI with ``--aggregate``), SIGKILLed (-9)
  mid-run from another process and resumed, merges BYTE-IDENTICAL
  aggregate output to a single-shot run — both the ``merged_hash`` over
  shard sidecars and the merged ``AggregateState`` wire bytes — with
  committed shards never re-parsed;
- no session thread, temp file, or shared-memory segment leaks, and the
  rendered Prometheus exposition stays structurally valid with the
  ``analytics_*`` families present.

Usage::

    make agg-smoke
    python -m logparser_tpu.tools.agg_smoke
"""
from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time

N_LINES = 60000
GARBAGE_EVERY = 997          # ~60 rejected lines across the corpus
OVERFLOW_EVERY = 1499        # ~40 forced 20-digit fold rows
SHARD_BYTES = 64 << 10       # 20+ shards: a wide mid-run kill window
BATCH_LINES = 1024
KILL_POLL_S = 0.05
KILL_TIMEOUT_S = 300.0
SHM_DIR = "/dev/shm"

FMT = "%h %u %>s %b"
FIELDS = [
    "IP:connection.client.host",
    "STRING:request.status.last",
    "BYTES:response.body.bytes",
]
OPS = [
    {"op": "count"},
    {"op": "count_by", "field": "STRING:request.status.last"},
    {"op": "top_k", "field": "IP:connection.client.host", "k": 5},
    {"op": "sum", "field": "BYTES:response.body.bytes"},
]


def _corpus(path: str) -> None:
    with open(path, "w") as f:
        for i in range(N_LINES):
            if i % GARBAGE_EVERY == 7:
                f.write(f"?? broken line {i} !! ::\n")
            elif i % OVERFLOW_EVERY == 11:
                # > int64 byte counter: the device must FOLD this row to
                # the host row path, and the merged sum must carry it.
                f.write(f"10.9.8.7 u{i} 200 {'9' * 20}\n")
            else:
                f.write(f"10.0.{(i >> 8) % 256}.{i % 256} u{i} "
                        f"{200 + i % 7} {100 + i % 9000}\n")


def _ring_segments():
    from logparser_tpu.feeder import RING_NAME_PREFIX

    if not os.path.isdir(SHM_DIR):
        return None
    return sorted(
        f for f in os.listdir(SHM_DIR) if f.startswith(RING_NAME_PREFIX)
    )


def _committed(out_dir: str) -> int:
    from logparser_tpu.jobs.manifest import count_committed_shards

    return count_committed_shards(out_dir)


def _service_leg(failures) -> None:
    from logparser_tpu.analytics import AggregateState
    from logparser_tpu.analytics.spec import parse_aggregate_config
    from logparser_tpu.observability import counter_sum
    from logparser_tpu.service import (
        ParseService,
        ParseServiceClient,
        ParseServiceError,
    )
    from logparser_tpu.tools.demolog import generate_combined_lines
    from logparser_tpu.tpu.batch import TpuBatchParser

    agg_fields = [
        "IP:connection.client.host",
        "STRING:request.status.last",
        "BYTES:response.body.bytes",
        "TIME.EPOCH:request.receive.time.epoch",
    ]
    ops = OPS + [{"op": "time_bucket",
                  "field": "TIME.EPOCH:request.receive.time.epoch",
                  "width_s": 3600}]
    lines = generate_combined_lines(2000, seed=23, garbage_fraction=0.01)
    lines[42] = ('9.8.7.6 - - [01/Jan/2026:00:00:00 +0000] '
                 f'"GET /big HTTP/1.1" 200 {"9" * 20} "-" "ua"')

    spec = parse_aggregate_config(ops)
    referee_parser = TpuBatchParser("combined", agg_fields)
    try:
        referee = AggregateState(spec)
        referee.update_from_result(referee_parser.parse_batch(lines))
    finally:
        referee_parser.close()

    threads_before = {t.ident for t in threading.enumerate()}
    d2h_saved_before = counter_sum("analytics_d2h_bytes_saved_total")
    device_batches_before = counter_sum(
        'analytics_batches_total{path="device"}')
    with ParseService() as svc:
        with ParseServiceClient(
            svc.host, svc.port, "combined", agg_fields, aggregate=ops
        ) as client:
            state = client.parse(lines)
        if not isinstance(state, AggregateState):
            failures.append(
                f"service aggregate session returned {type(state)!r}, "
                "not an AggregateState"
            )
        elif state != referee:
            failures.append(
                "service aggregate != local host-oracle referee:\n"
                f"  service: {state.summary()}\n"
                f"  referee: {referee.summary()}"
            )
        else:
            print("agg-smoke: service aggregate == referee over "
                  f"{len(lines)} lines (garbage + overflow folds "
                  "included)")
        # a row session on the same server still serves row frames
        with ParseServiceClient(
            svc.host, svc.port, "combined", agg_fields[:1]
        ) as client:
            table = client.parse(lines[:25])
        if getattr(table, "num_rows", None) != 25:
            failures.append("row session alongside the aggregate one "
                            f"returned {table!r}")
        # a bad spec must relay a structured config error
        try:
            ParseServiceClient(
                svc.host, svc.port, "combined", agg_fields,
                aggregate=[{"op": "sum",
                            "field": "STRING:request.status.last"}],
            ).parse(["x"])
            failures.append("bad aggregate spec was accepted")
        except ParseServiceError:
            pass

    d2h_saved = counter_sum(
        "analytics_d2h_bytes_saved_total") - d2h_saved_before
    device_batches = counter_sum(
        'analytics_batches_total{path="device"}') - device_batches_before
    if device_batches < 1:
        failures.append("analytics_batches_total{path=device} never "
                        "moved across the aggregate session")
    if d2h_saved <= 0:
        failures.append("analytics_d2h_bytes_saved_total never moved — "
                        "the aggregate path shipped as much as the row "
                        "path")
    else:
        print(f"agg-smoke: D2H saved {d2h_saved / 1e6:.2f} MB across "
              f"{int(device_batches)} device-aggregated batch(es)")

    time.sleep(0.5)
    leaked = [
        t.name for t in threading.enumerate()
        if t.ident not in threads_before and t.is_alive()
    ]
    if leaked:
        failures.append(f"leaked service threads: {leaked}")


def _jobs_leg(failures) -> None:
    from logparser_tpu.jobs import (
        JobManifest,
        JobSpec,
        leaked_temp_files,
        merged_hash,
        merged_job_aggregate,
        run_job,
    )

    tmp = tempfile.mkdtemp(prefix="logparser-agg-smoke-")
    corpus = os.path.join(tmp, "corpus.log")
    _corpus(corpus)
    agg_json = json.dumps(OPS)

    def spec(out_name):
        return JobSpec([corpus], FMT, FIELDS,
                       os.path.join(tmp, out_name),
                       shard_bytes=SHARD_BYTES, batch_lines=BATCH_LINES,
                       aggregate=agg_json)

    t0 = time.perf_counter()
    ref = run_job(spec("single-shot"))
    ref_wall = time.perf_counter() - t0
    if not ref.complete:
        failures.append(f"single-shot aggregate job incomplete: "
                        f"{ref.as_dict()}")
    if not ref.rejects:
        failures.append("single-shot aggregate job saw no rejects "
                        "(corpus has garbage lines)")
    ref_dir = spec("single-shot").out_dir
    ref_hash = merged_hash(ref_dir, JobManifest.load(ref_dir))
    ref_agg = merged_job_aggregate(ref_dir)
    print(f"agg-smoke: single-shot {ref.shards_total} shards, "
          f"count={ref_agg.data[0]}, {ref.rejects} rejects, "
          f"{ref.payload_bytes / max(ref_wall, 1e-9) / 1e6:.1f} MB/s")

    # ---- kill drill: SIGKILL the aggregate CLI mid-run, resume -------
    kill_dir = spec("killed").out_dir
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (
        repo_root + os.pathsep + env["PYTHONPATH"]
        if env.get("PYTHONPATH") else repo_root
    )
    argv = [sys.executable, "-m", "logparser_tpu.jobs", corpus,
            "--format", FMT, "--out", kill_dir,
            "--shard-bytes", str(SHARD_BYTES),
            "--batch-lines", str(BATCH_LINES),
            "--aggregate", agg_json]
    for f in FIELDS:
        argv += ["--field", f]
    proc = subprocess.Popen(argv, env=env,
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)
    deadline = time.monotonic() + KILL_TIMEOUT_S
    while time.monotonic() < deadline:
        if _committed(kill_dir) >= 2 or proc.poll() is not None:
            break
        time.sleep(KILL_POLL_S)
    if proc.poll() is None:
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
    else:
        print("agg-smoke: WARNING subprocess finished before the kill "
              "window (fast host) — resume still asserted below")
    committed_at_kill = _committed(kill_dir)
    print(f"agg-smoke: job stopped with {committed_at_kill} of "
          f"{ref.shards_total} shards committed")
    if committed_at_kill >= ref.shards_total and proc.returncode == -9:
        failures.append("kill drill never landed mid-run")
    time.sleep(2.0)

    resumed = run_job(spec("killed"))
    if not resumed.complete:
        failures.append(f"resume incomplete: {resumed.as_dict()}")
    if resumed.skipped != committed_at_kill:
        failures.append(
            f"resume re-parsed committed work: skipped "
            f"{resumed.skipped}, manifest had {committed_at_kill} at kill"
        )
    kill_hash = merged_hash(kill_dir, JobManifest.load(kill_dir))
    kill_agg = merged_job_aggregate(kill_dir)
    if kill_hash != ref_hash:
        failures.append(
            "kill-drill sidecar output is NOT byte-identical "
            f"({kill_hash[:16]} != {ref_hash[:16]})"
        )
    if kill_agg.to_ipc_bytes() != ref_agg.to_ipc_bytes():
        failures.append("kill-drill merged aggregate differs from the "
                        "single-shot run")
    elif kill_hash == ref_hash:
        print(f"agg-smoke: kill+resume aggregate byte-identical "
              f"({kill_hash[:16]}), skipped {resumed.skipped} committed "
              "shards")

    for out_name in ("single-shot", "killed"):
        debris = leaked_temp_files(spec(out_name).out_dir)
        if debris:
            failures.append(f"{out_name}: leaked temp files {debris}")


def main() -> int:
    from logparser_tpu.observability import metrics
    from logparser_tpu.tools.metrics_smoke import validate_exposition

    failures: list = []
    segments_before = _ring_segments()

    _service_leg(failures)
    _jobs_leg(failures)

    segments_after = _ring_segments()
    if segments_before is not None and segments_after is not None:
        leaked = sorted(set(segments_after) - set(segments_before))
        if leaked:
            failures.append(f"leaked shared-memory segments: {leaked}")

    text = metrics().prometheus_text()
    for needle in ("logparser_tpu_analytics_batches_total",
                   "logparser_tpu_analytics_d2h_bytes_saved_total",
                   "logparser_tpu_analytics_partial_merge_seconds"):
        if needle not in text:
            failures.append(f"/metrics exposition missing: {needle}")
    failures.extend(validate_exposition(text))

    if failures:
        print("AGG SMOKE FAILURES:")
        for f in failures:
            print(" -", f)
        return 1
    print("agg-smoke OK: live aggregate session == host-oracle referee, "
          "D2H savings recorded, SIGKILL/resume aggregate job "
          "byte-identical, no leaked threads/temp files/shm segments")
    return 0


if __name__ == "__main__":
    sys.exit(main())
