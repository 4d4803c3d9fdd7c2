"""Durable batch jobs (logparser_tpu/jobs, docs/JOBS.md): exactly-once
sharded output, crash-resumable runs, the per-line reject channel, and
writer I/O fault tolerance — plus the EOF/no-trailing-newline boundary
locks across the inputformat and feeder split paths.

The kill-drill invariant drilled here in-process (JobPolicy.
stop_after_shards models a crash landing on a commit boundary; the real
SIGKILL drill lives in tools/job_smoke.py and the bench ``jobs``
section): interrupted + resumed output must be BYTE-IDENTICAL to an
undisturbed run's, with committed shards never re-parsed.
"""
import json
import os

import pytest

from _shared_parsers import shared_parser
from logparser_tpu.core.exceptions import OracleEngineError
from logparser_tpu.jobs import (
    JobManifest,
    JobPolicy,
    JobSpec,
    ManifestError,
    ShardRecord,
    leaked_temp_files,
    merged_hash,
    run_job,
)
from logparser_tpu.observability import metrics

pa = pytest.importorskip("pyarrow")

FMT = "%h %u %>s"
FIELDS = ["IP:connection.client.host", "STRING:request.status.last"]

GARBAGE_LINES = [
    b"total garbage ! that & matches nothing ::",
    b"another \x01 bad line with weird bytes",
]


def make_corpus(n=240, trailing_newline=True):
    lines = [
        f"1.2.3.{i % 250} user{i} {200 + i % 3}".encode() for i in range(n)
    ]
    lines[17] = GARBAGE_LINES[0]
    lines[n - 40] = GARBAGE_LINES[1]
    blob = b"\n".join(lines)
    if trailing_newline:
        blob += b"\n"
    return lines, blob


def job_spec(tmp_path, corpus_file, out_name, **kw):
    kw.setdefault("shard_bytes", 700)
    kw.setdefault("batch_lines", 16)
    kw.setdefault("use_processes", False)
    return JobSpec([str(corpus_file)], FMT, FIELDS,
                   str(tmp_path / out_name), **kw)


@pytest.fixture()
def corpus_file(tmp_path):
    _, blob = make_corpus()
    p = tmp_path / "corpus.log"
    p.write_bytes(blob)
    return p


def parser():
    return shared_parser(FMT, FIELDS)


def run(spec, **kw):
    kw.setdefault("parser", parser())
    kw.setdefault("policy", JobPolicy(io_backoff_s=0.005))
    return run_job(spec, **kw)


# ---------------------------------------------------------------------------
# manifest
# ---------------------------------------------------------------------------


def test_manifest_roundtrip_and_atomic_save(tmp_path):
    m = JobManifest.fresh({"log_format": FMT, "fields": FIELDS})
    m.commit(str(tmp_path), ShardRecord(
        shard=3, source=0, start=0, end=100, lines=10, rows=9, rejects=1,
        payload_bytes=95, data_file="shard-00003.arrow",
        reject_file="shard-00003.rejects.arrow",
        data_hash="aa", reject_hash="bb",
    ))
    assert not leaked_temp_files(str(tmp_path))  # atomic: no tmp debris
    loaded = JobManifest.load(str(tmp_path))
    assert loaded.committed_indices() == [3]
    rec = loaded.shards[3]
    assert (rec.rows, rec.rejects, rec.data_file) == (
        9, 1, "shard-00003.arrow"
    )
    assert loaded.mismatch({"log_format": FMT, "fields": FIELDS}) is None
    assert "fields" in loaded.mismatch({"log_format": FMT, "fields": ["x"]})


def test_corrupt_manifest_refuses_not_ignores(tmp_path):
    (tmp_path / "manifest.json").write_text("{not json")
    with pytest.raises(ManifestError):
        JobManifest.load(str(tmp_path))


# ---------------------------------------------------------------------------
# the job itself: outputs, reject channel, determinism
# ---------------------------------------------------------------------------


def test_job_outputs_reject_channel_and_byte_identity(tmp_path, corpus_file):
    lines, _ = make_corpus()
    specA = job_spec(tmp_path, corpus_file, "outA")
    repA = run(specA)
    assert repA.complete and not repA.failed
    assert repA.lines == len(lines)
    assert repA.rows == len(lines) - 2
    assert repA.rejects == 2
    assert set(repA.reject_reasons) <= {
        "oracle_reject", "oracle_error", "implausible"
    }
    m = JobManifest.load(specA.out_dir)
    assert len(m.shards) == repA.shards_total
    # Reject tables carry the exact raw bytes + a stable reason.
    raws, reasons = [], set()
    for idx in m.committed_indices():
        rec = m.shards[idx]
        if not rec.reject_file:
            continue
        with open(os.path.join(specA.out_dir, rec.reject_file), "rb") as f:
            t = pa.ipc.open_stream(f).read_all()
        raws += t["raw"].to_pylist()
        reasons |= set(t["reason"].to_pylist())
        assert t["shard"].to_pylist() == [idx] * t.num_rows
    assert sorted(raws) == sorted(GARBAGE_LINES)
    assert reasons <= {"oracle_reject", "oracle_error", "implausible"}
    # Data rows: every valid line survives into the data tables.
    total_rows = sum(m.shards[i].rows for i in m.committed_indices())
    assert total_rows == len(lines) - 2
    # Determinism: a second fresh run is byte-identical.
    specB = job_spec(tmp_path, corpus_file, "outB")
    run(specB)
    assert merged_hash(specA.out_dir, m) == merged_hash(
        specB.out_dir, JobManifest.load(specB.out_dir)
    )
    assert metrics().get("job_rejected_lines_total",
                         {"reason": "oracle_reject"}) >= 2


def test_single_shard_reject_line_offsets(tmp_path, corpus_file):
    lines, _ = make_corpus()
    spec = job_spec(tmp_path, corpus_file, "out1", shard_bytes=1 << 20)
    run(spec)
    m = JobManifest.load(spec.out_dir)
    assert m.committed_indices() == [0]
    rec = m.shards[0]
    with open(os.path.join(spec.out_dir, rec.reject_file), "rb") as f:
        t = pa.ipc.open_stream(f).read_all()
    # line offsets are absolute within the shard == corpus line indices
    assert t["line"].to_pylist() == [17, len(lines) - 40]
    assert t["raw"].to_pylist() == GARBAGE_LINES


# ---------------------------------------------------------------------------
# resume: exactly-once, byte-identical
# ---------------------------------------------------------------------------


def test_crash_at_commit_boundary_resume_is_byte_identical(
    tmp_path, corpus_file
):
    specA = job_spec(tmp_path, corpus_file, "undisturbed")
    run(specA)
    href = merged_hash(specA.out_dir, JobManifest.load(specA.out_dir))

    specB = job_spec(tmp_path, corpus_file, "crashed")
    r1 = run(specB, policy=JobPolicy(stop_after_shards=3))
    assert r1.stopped_early and r1.committed == 3
    r2 = run(specB)
    # committed shards are NEVER re-parsed: the resume skipped exactly
    # the three committed shards and parsed only the rest.
    assert r2.skipped == 3
    assert r2.committed == r2.shards_total - 3
    assert r2.complete
    m = JobManifest.load(specB.out_dir)
    assert merged_hash(specB.out_dir, m) == href
    assert not leaked_temp_files(specB.out_dir)


def test_orphaned_rename_without_manifest_entry_is_overwritten(
    tmp_path, corpus_file
):
    """A crash BETWEEN the file rename and the manifest commit leaves a
    complete-looking orphan file — resume must re-parse that shard and
    overwrite it deterministically (the manifest is the only truth)."""
    spec = job_spec(tmp_path, corpus_file, "orphan")
    run(spec)
    m = JobManifest.load(spec.out_dir)
    href = merged_hash(spec.out_dir, m)
    victim = m.committed_indices()[1]
    del m.shards[victim]
    m.save(spec.out_dir)
    r = run(spec)
    assert r.committed == 1 and r.complete
    m2 = JobManifest.load(spec.out_dir)
    assert victim in m2.shards
    assert merged_hash(spec.out_dir, m2) == href


def test_resume_all_committed_is_a_noop(tmp_path, corpus_file):
    spec = job_spec(tmp_path, corpus_file, "noop")
    run(spec)
    r = run(spec, parser=None)  # no parser needed: nothing to parse
    assert r.skipped == r.shards_total and r.committed == 0 and r.complete


def test_modified_source_same_size_refuses_resume(tmp_path, corpus_file):
    """A corpus rewritten IN PLACE to the same byte size must refuse to
    resume (mtime enters the fingerprint): mixing two corpora's shards
    would corrupt the merged output without any crash."""
    import time as _time

    spec = job_spec(tmp_path, corpus_file, "mtime")
    run(spec, policy=JobPolicy(stop_after_shards=2, io_backoff_s=0.005))
    data = corpus_file.read_bytes()
    _time.sleep(0.02)
    corpus_file.write_bytes(b"X" + data[1:])  # same size, new content
    with pytest.raises(ManifestError, match="sources"):
        run(spec)


def test_manifest_write_fault_fails_shard_not_job(
    tmp_path, corpus_file, monkeypatch
):
    """The manifest rewrite is the commit point AND a disk write: when
    it exhausts its retries the shard fails (its renamed files without
    an entry are the ordinary not-committed state), the job continues,
    and resume heals byte-identically."""
    from logparser_tpu.jobs.writer import JobWriter

    real = JobWriter.write_file

    def flaky(self, name, data, shard):
        if name == "manifest.json" and shard == 1:
            from logparser_tpu.jobs.writer import ShardWriteError

            raise ShardWriteError(shard, "injected manifest write fault")
        return real(self, name, data, shard)

    monkeypatch.setattr(JobWriter, "write_file", flaky)
    spec = job_spec(tmp_path, corpus_file, "mwf")
    rep = run(spec)
    assert [f["shard"] for f in rep.failed] == [1]
    assert rep.committed == rep.shards_total - 1
    assert 1 not in JobManifest.load(spec.out_dir).shards
    monkeypatch.setattr(JobWriter, "write_file", real)
    r2 = run(spec)
    assert r2.complete and r2.committed == 1
    ref = job_spec(tmp_path, corpus_file, "mwf-ref")
    run(ref)
    assert merged_hash(
        spec.out_dir, JobManifest.load(spec.out_dir)
    ) == merged_hash(ref.out_dir, JobManifest.load(ref.out_dir))


def test_fingerprint_mismatch_refused(tmp_path, corpus_file):
    spec = job_spec(tmp_path, corpus_file, "fp")
    run(spec, policy=JobPolicy(stop_after_shards=1, io_backoff_s=0.005))
    other = job_spec(tmp_path, corpus_file, "fp", batch_lines=8)
    with pytest.raises(ManifestError, match="batch_lines"):
        run_job(other, parser=parser())
    with pytest.raises(ManifestError, match="manifest"):
        run_job(spec, resume=False, parser=parser())


# ---------------------------------------------------------------------------
# writer I/O faults (chaos io primitives)
# ---------------------------------------------------------------------------


def test_transient_io_fault_absorbed_by_retry(tmp_path, corpus_file):
    before = metrics().get("job_writer_retries_total",
                           {"op": "io_error"})
    specA = job_spec(tmp_path, corpus_file, "ioA")
    repA = run(specA, chaos="io_error:op=fsync:count=2")
    assert repA.complete and not repA.failed
    assert metrics().get("job_writer_retries_total",
                         {"op": "io_error"}) >= before + 2
    specB = job_spec(tmp_path, corpus_file, "ioB")
    run(specB)
    assert merged_hash(
        specA.out_dir, JobManifest.load(specA.out_dir)
    ) == merged_hash(specB.out_dir, JobManifest.load(specB.out_dir))


def test_sticky_enospc_fails_shard_not_job(tmp_path, corpus_file):
    spec = job_spec(tmp_path, corpus_file, "sticky")
    rep = run(spec, chaos="enospc:shard=2:sticky=1")
    assert [f["shard"] for f in rep.failed] == [2]
    assert rep.committed == rep.shards_total - 1
    m = JobManifest.load(spec.out_dir)
    assert 2 not in m.shards  # manifest stays consistent: no entry
    assert metrics().get("job_shards_failed_total",
                         {"reason": "write_io"}) >= 1
    # the failure healed (space back): resume completes just that shard
    r2 = run(spec)
    assert r2.committed == 1 and r2.skipped == rep.shards_total - 1
    ref = job_spec(tmp_path, corpus_file, "ref")
    run(ref)
    assert merged_hash(
        spec.out_dir, JobManifest.load(spec.out_dir)
    ) == merged_hash(ref.out_dir, JobManifest.load(ref.out_dir))


# ---------------------------------------------------------------------------
# feeder shard_plan hook
# ---------------------------------------------------------------------------


def test_feeder_shard_plan_subset_and_validation():
    from dataclasses import replace

    from logparser_tpu.feeder import FeederPool, plan_shards
    from logparser_tpu.feeder.shards import normalize_sources

    _, blob = make_corpus()
    srcs = normalize_sources([blob])
    plan = plan_shards(srcs, 700)
    subset = [s for s in plan if s.index % 2 == 0]
    renum = [replace(s, index=i) for i, s in enumerate(subset)]
    pool = FeederPool([blob], workers=2, shard_bytes=700,
                      batch_lines=16, use_processes=False,
                      shard_plan=renum)
    got = b"".join(bytes(eb.payload) for eb in pool.batches())
    from logparser_tpu.feeder.shards import read_shard_payload

    want = b"".join(read_shard_payload(srcs[0], s) for s in subset)
    assert got == want
    with pytest.raises(ValueError, match="contiguous"):
        FeederPool([blob], shard_plan=subset, use_processes=False)


# ---------------------------------------------------------------------------
# oracle-failure surfacing (satellite: rescue-failure audit)
# ---------------------------------------------------------------------------


def test_engine_error_becomes_marker_not_batch_abort():
    """A record setter raising mid-parse is an ENGINE failure, not a
    DissectionFailure: parse_many must mark that one line and keep
    parsing the rest (both oracle engine flavors route through here)."""

    class BoomRecord:
        def __init__(self):
            self.values = {}

        def set_value(self, name, value):
            raise ValueError("boom")

    out = parser().oracle.parse_many(
        ["1.2.3.4 bob 200", "total garbage ! ::"], BoomRecord
    )
    assert isinstance(out[0], OracleEngineError)
    assert "boom" in out[0].error
    assert out[1] is None  # ordinary reject stays None


def test_oracle_engine_failure_is_a_counted_reasoned_reject(monkeypatch):
    """When the oracle ITSELF fails on a routed line, the batch result
    must carry a counted oracle_error reject — never a raise, never a
    silent None (the jobs reject channel depends on this)."""
    p = parser()
    real = p.oracle.parse_many

    def failing(lines, record_factory):
        out = real(lines, record_factory)
        return [
            OracleEngineError("ValueError: injected engine fault")
            if (b"ENGINEBOOM" in (ln if isinstance(ln, bytes)
                                  else ln.encode()))
            else r
            for ln, r in zip(lines, out)
        ]

    monkeypatch.setattr(p.oracle, "parse_many", failing)
    before = metrics().get("oracle_engine_errors_total")
    result = p.parse_batch([
        b"1.2.3.4 bob 200",
        b"ENGINEBOOM garbage ! ::",   # invalid on device -> oracle
        b"5.6.7.8 al 404",
    ])
    assert list(result.valid) == [True, False, True]
    assert result.reject_reasons == {1: "oracle_error"}
    assert result.bad_lines == 1
    assert metrics().get("oracle_engine_errors_total") == before + 1


def test_reject_reasons_cover_every_invalid_row():
    p = parser()
    result = p.parse_batch([
        b"1.2.3.4 bob 200",
        b"total garbage ! that & matches nothing ::",
        b"",
        b"x",
    ])
    invalid = {i for i in range(result.lines_read) if not result.valid[i]}
    assert set(result.reject_reasons) == invalid
    assert set(result.reject_reasons.values()) <= {
        "implausible", "oracle_reject", "oracle_error"
    }
    assert result.raw_line(1) == b"total garbage ! that & matches nothing ::"


# ---------------------------------------------------------------------------
# EOF / no-trailing-newline boundary locks (inputformat + feeder + jobs)
# ---------------------------------------------------------------------------


class TestEofBoundary:
    CONTENT = (b"1.1.1.1 aa 200\n" * 7) + b"2.2.2.2 final 204"

    def _reader_lines(self, path, start, length):
        from logparser_tpu.adapters.inputformat import (
            FileSplit,
            LogfileRecordReader,
        )

        reader = object.__new__(LogfileRecordReader)
        reader.split = FileSplit(str(path), start, length)
        return list(reader._iter_split_lines())

    def test_inputformat_final_line_exactly_once(self, tmp_path):
        p = tmp_path / "nofinalnl.log"
        p.write_bytes(self.CONTENT)
        size = len(self.CONTENT)
        want = self.CONTENT.split(b"\n")
        for split_size in list(range(1, 40)) + [size - 1, size, size + 7]:
            splits, off = [], 0
            while off < size:
                ln = min(split_size, size - off)
                splits.append((off, ln))
                off += ln
            got = [
                ln for s, n in splits for ln in self._reader_lines(p, s, n)
            ]
            assert got == want, f"split_size={split_size}"

    def test_inputformat_strips_one_cr_like_the_framer(self, tmp_path):
        # "x\r\r\n" must yield "x\r" (one \n, then one \r) — exactly
        # encode_blob's framing; rstrip(b"\r\n") used to eat both.
        p = tmp_path / "cr.log"
        p.write_bytes(b"a\r\r\nb\r\nc")
        got = self._reader_lines(p, 0, 9)
        assert got == [b"a\r", b"b", b"c"]

    def test_feeder_shard_ending_at_eof_no_trailing_newline(self):
        from logparser_tpu.feeder import FeederPool

        size = len(self.CONTENT)
        for shard_bytes in (5, 15, size - 1, size, size + 3):
            pool = FeederPool([self.CONTENT], workers=2,
                              shard_bytes=shard_bytes, batch_lines=3,
                              use_processes=False)
            ebs = list(pool.batches())
            assert b"".join(bytes(eb.payload) for eb in ebs) == self.CONTENT
            assert sum(eb.n_lines for eb in ebs) == 8

    def test_job_delivers_final_line_exactly_once(self, tmp_path):
        p = tmp_path / "job-eof.log"
        p.write_bytes(self.CONTENT)
        # shard boundary landing ON EOF and mid-final-line both sweep
        for i, shard_bytes in enumerate((15, len(self.CONTENT),
                                         len(self.CONTENT) - 4)):
            spec = job_spec(tmp_path, p, f"eof{i}",
                            shard_bytes=shard_bytes, batch_lines=4)
            rep = run(spec)
            assert rep.complete
            assert rep.lines == 8 and rep.rows == 8 and rep.rejects == 0
            m = JobManifest.load(spec.out_dir)
            finals = 0
            for idx in m.committed_indices():
                rec = m.shards[idx]
                if not rec.data_file:
                    continue
                with open(os.path.join(spec.out_dir, rec.data_file),
                          "rb") as f:
                    t = pa.ipc.open_stream(f).read_all()
                finals += t[FIELDS[0]].to_pylist().count("2.2.2.2")
            assert finals == 1


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_cli_roundtrip(tmp_path, corpus_file, capsys, monkeypatch):
    from logparser_tpu.jobs.__main__ import main

    out = tmp_path / "cli-out"
    argv = [
        str(corpus_file), "--format", FMT, "--out", str(out),
        "--shard-bytes", "700", "--batch-lines", "16", "--threads",
    ]
    for f in FIELDS:
        argv += ["--field", f]
    assert main(argv) == 0
    rep = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rep["complete"] and rep["rejects"] == 2
    # resume via CLI: nothing left to do
    assert main(argv) == 0
    rep2 = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rep2["skipped"] == rep["shards_total"]
    # --no-resume refuses the existing manifest
    assert main(argv + ["--no-resume"]) == 2


# ---------------------------------------------------------------------------
# preemption (SIGTERM-clean stop at a shard commit boundary)
# ---------------------------------------------------------------------------


def test_stop_event_preempts_at_commit_boundary(tmp_path, corpus_file):
    """The runner half of the SIGTERM contract (docs/JOBS.md
    "Preemption"): a set stop_event stops the run at the FIRST commit
    boundary it reaches — the shard in flight commits, the report says
    preempted, and a resume re-parses ZERO committed shards, merging
    byte-identical to an undisturbed run."""
    import threading

    ref = run(job_spec(tmp_path, corpus_file, "ref"))
    assert ref.complete
    ref_hash = merged_hash(ref.out_dir, JobManifest.load(ref.out_dir))

    notice = threading.Event()
    notice.set()  # preemption notice already delivered
    before = metrics().get("job_preempted_total")
    r1 = run(job_spec(tmp_path, corpus_file, "pre"),
             policy=JobPolicy(stop_event=notice))
    assert r1.preempted and r1.stopped_early and not r1.complete
    assert r1.committed == 1  # the boundary in flight, nothing more
    assert r1.as_dict()["preempted"] is True
    assert metrics().get("job_preempted_total") > before

    r2 = run(job_spec(tmp_path, corpus_file, "pre"))
    assert r2.complete and r2.skipped == r1.committed
    assert merged_hash(r2.out_dir, JobManifest.load(r2.out_dir)) == ref_hash
    assert leaked_temp_files(r2.out_dir) == []


def test_run_job_hands_caller_parser_back_without_chaos(
    tmp_path, corpus_file
):
    """A drill must not keep injecting into unrelated parses: run_job
    arms device chaos on a caller-supplied parser for the job's
    duration only, and restores the PRIOR arming on the way out."""
    from logparser_tpu.tpu.batch import TpuBatchParser

    p = TpuBatchParser(FMT, FIELDS, device_chaos=None)
    rep = run_job(job_spec(tmp_path, corpus_file, "armed"), parser=p,
                  chaos="oom_batch:sticky=1:min_lines=1")
    assert rep.complete  # the injected OOMs were absorbed, not raised
    assert p._device_chaos is None  # handed back clean
    # A caller mid-drill of its own gets ITS plan back, not None.
    p.arm_device_chaos("wedge_device:seconds=0.01")
    mine = p._device_chaos
    run_job(job_spec(tmp_path, corpus_file, "armed2"), parser=p,
            chaos="oom_batch:count=1")
    assert p._device_chaos is mine
    p.arm_device_chaos(None)
    p.close()


def test_preemption_on_final_commit_is_a_clean_finish(
    tmp_path, corpus_file
):
    """A notice landing on the LAST shard's commit must not turn a
    finished run into a preempted one — the relaunch would be a pure
    no-op and the report would falsely read incomplete."""
    import threading

    notice = threading.Event()
    notice.set()
    # One-shard geometry: the first commit IS the final one.
    r = run(job_spec(tmp_path, corpus_file, "lastshard",
                     shard_bytes=1 << 20),
            policy=JobPolicy(stop_event=notice))
    assert r.complete and not r.preempted and r.shards_total == 1


def test_unset_stop_event_changes_nothing(tmp_path, corpus_file):
    import threading

    r = run(job_spec(tmp_path, corpus_file, "quiet"),
            policy=JobPolicy(stop_event=threading.Event()))
    assert r.complete and not r.preempted


def test_cli_sigterm_maps_to_preempted_exit_code(
    tmp_path, corpus_file, capsys, monkeypatch
):
    """The CLI half: the SIGTERM handler's stop_event reaches
    JobPolicy, and a preempted report exits EXIT_PREEMPTED (3) with the
    preempted flag on the JSON line — what an orchestrator keys its
    unconditional relaunch on.  (The live-signal drill — a real SIGTERM
    into a subprocess mid-run — runs in tools/device_chaos_smoke.py.)"""
    from logparser_tpu.jobs import EXIT_PREEMPTED
    from logparser_tpu.jobs.__main__ import main

    seen = {}
    real_run_job = run_job

    def preempting_run_job(spec, resume=True, parser=None, chaos=None,
                           policy=None):
        # The handler fires mid-run: model it as the notice arriving
        # before the first boundary (the earliest legal stop).
        assert policy is not None and policy.stop_event is not None
        seen["stop_event"] = policy.stop_event
        policy.stop_event.set()
        return real_run_job(spec, resume=resume, parser=parser,
                            chaos=chaos, policy=policy)

    monkeypatch.setattr("logparser_tpu.jobs.__main__.run_job",
                        preempting_run_job)
    out = tmp_path / "term-out"
    argv = [
        str(corpus_file), "--format", FMT, "--out", str(out),
        "--shard-bytes", "700", "--batch-lines", "16", "--threads",
    ]
    for f in FIELDS:
        argv += ["--field", f]
    assert main(argv) == EXIT_PREEMPTED
    rep = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rep["preempted"] is True and rep["stopped_early"] is True
    # The same command resumes to completion (exit 0), never re-parsing
    # the committed prefix.
    monkeypatch.setattr("logparser_tpu.jobs.__main__.run_job",
                        real_run_job)
    assert main(argv) == 0
    rep2 = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rep2["complete"] and rep2["skipped"] == rep["committed"]


# ---------------------------------------------------------------------------
# job-phase spans: every second of run_job on its thread lies under a span
# ---------------------------------------------------------------------------

# The spans that nest directly under the ``job`` root when a row job runs
# over feeder-framed batches (``encode`` is only the batch's adoption;
# assembly nests under job_fold, job_write under job_commit).
JOB_CHILD_STAGES = (
    "job_plan", "feeder_start", "feeder_fill", "feeder_get",
    "feeder_detach", "encode", "h2d_stage", "device", "h2d_wait", "fetch",
    "columns", "csr_materialize", "job_fold", "job_commit", "feeder_close",
)
JOB_PHASE_STAGES = (
    "job_plan", "feeder_start", "feeder_fill", "feeder_get",
    "feeder_detach", "h2d_wait", "job_fold", "job_commit", "feeder_close",
)


def test_run_job_phases_account_for_the_whole_job(tmp_path):
    reg = metrics()
    sources = []
    for i in range(2):
        _, blob = make_corpus(n=120)
        p = tmp_path / f"access.{i}.log"
        p.write_bytes(blob)
        sources.append(str(p))
    spec = JobSpec(sources, FMT, FIELDS, str(tmp_path / "out"),
                   shard_bytes=700, batch_lines=16, use_processes=False)

    def snap():
        return {s: (reg.histogram("stage_seconds", labels={"stage": s}).sum,
                    reg.histogram("stage_seconds",
                                  labels={"stage": s}).count,
                    reg.get("stage_self_seconds_total",
                            labels={"stage": s}))
                for s in ("job",) + JOB_CHILD_STAGES}

    before = snap()
    fill0 = reg.get("feeder_fill_seconds_total")
    report = run(spec)
    after = snap()
    assert report.complete and report.committed > 2

    def delta(stage, k):
        return after[stage][k] - before[stage][k]

    assert delta("job", 1) == 1
    for stage in JOB_PHASE_STAGES:
        assert delta(stage, 1) > 0, stage
    assert delta("feeder_fill", 1) == 1
    assert delta("feeder_close", 1) == 1
    assert reg.get("feeder_fill_seconds_total") > fill0
    job_s = delta("job", 0)
    children_s = sum(delta(s, 0) for s in JOB_CHILD_STAGES)
    assert abs(delta("job", 2) + children_s - job_s) <= 0.01 * job_s
