"""Pod-scale parse fabric (logparser_tpu/pod, docs/JOBS.md "Pod jobs"):
per-host plan subsetting, per-host manifests, the manifest MERGE step,
and the pod-level kill-drill invariant — a host lost mid-job is a run of
uncommitted shards; resume + merge is byte-identical to an undisturbed
single-host run, with committed shards never re-parsed.

The real-SIGKILL, real-subprocess drill lives in tools/pod_smoke.py and
the bench ``pod`` section; here the host-loss is modeled in-process
(JobPolicy.stop_after_shards — the same commit-boundary crash model
test_jobs.py uses one level down).
"""
import json
import os

import pytest

from _shared_parsers import shared_parser
from logparser_tpu.feeder.shards import (
    Shard,
    host_shard_range,
    plan_shards,
    shards_for_host,
)
from logparser_tpu.jobs import (
    JobManifest,
    JobPolicy,
    JobSpec,
    ManifestError,
    ShardRecord,
    committed_anywhere,
    host_manifest_name,
    leaked_temp_files,
    list_host_manifests,
    merge_manifests,
    merged_hash,
    run_job,
    sweepable_temp_files,
)
from logparser_tpu.pod import PodPolicy, PodSpec, run_pod

pa = pytest.importorskip("pyarrow")

FMT = "%h %u %>s"
FIELDS = ["IP:connection.client.host", "STRING:request.status.last"]


def make_corpus(n=240):
    lines = [
        f"1.2.3.{i % 250} user{i} {200 + i % 3}".encode() for i in range(n)
    ]
    lines[17] = b"total garbage ! that & matches nothing ::"
    lines[n - 40] = b"another \x01 bad line with weird bytes"
    return b"\n".join(lines) + b"\n"


@pytest.fixture()
def corpus_file(tmp_path):
    p = tmp_path / "corpus.log"
    p.write_bytes(make_corpus())
    return p


def job_spec(tmp_path, corpus_file, out_name, **kw):
    kw.setdefault("shard_bytes", 700)
    kw.setdefault("batch_lines", 16)
    kw.setdefault("use_processes", False)
    return JobSpec([str(corpus_file)], FMT, FIELDS,
                   str(tmp_path / out_name), **kw)


def parser():
    return shared_parser(FMT, FIELDS)


def run(spec, **kw):
    kw.setdefault("parser", parser())
    kw.setdefault("policy", JobPolicy(io_backoff_s=0.005))
    return run_job(spec, **kw)


def reference_hash(tmp_path, corpus_file):
    spec = job_spec(tmp_path, corpus_file, "reference")
    rep = run(spec)
    assert rep.complete
    return (merged_hash(spec.out_dir, JobManifest.load(spec.out_dir)),
            rep)


# ---------------------------------------------------------------------------
# plan subsetting
# ---------------------------------------------------------------------------


def test_host_ranges_tile_disjoint_and_balanced():
    for n_shards in (0, 1, 5, 8, 17):
        for n_hosts in (1, 2, 3, 8, 20):
            ranges = [host_shard_range(n_shards, n_hosts, h)
                      for h in range(n_hosts)]
            # tiling: concatenated ranges == range(n_shards), in order
            flat = [i for s, e in ranges for i in range(s, e)]
            assert flat == list(range(n_shards))
            sizes = [e - s for s, e in ranges]
            assert max(sizes) - min(sizes) <= 1


def test_host_range_validation():
    with pytest.raises(ValueError):
        host_shard_range(4, 0, 0)
    with pytest.raises(ValueError):
        host_shard_range(4, 2, 2)
    with pytest.raises(ValueError):
        host_shard_range(4, 2, -1)


def test_shards_for_host_keep_global_indices():
    class _Src:
        size = 10_000
    plan = plan_shards([_Src()], 1000)
    a = shards_for_host(plan, 3, 0)
    b = shards_for_host(plan, 3, 1)
    c = shards_for_host(plan, 3, 2)
    assert [s.index for s in a + b + c] == [s.index for s in plan]
    assert all(isinstance(s, Shard) for s in a)


# ---------------------------------------------------------------------------
# manifest merge
# ---------------------------------------------------------------------------


def _mk_manifest(fp, shards):
    m = JobManifest.fresh(fp)
    for i in shards:
        m.shards[i] = ShardRecord(
            shard=i, source=0, start=i * 10, end=i * 10 + 10,
            lines=5, rows=5, rejects=0, payload_bytes=50,
            data_file=f"shard-{i:05d}.arrow", reject_file=None,
            data_hash=f"h{i}", reject_hash=None,
        )
    return m


FP = {"log_format": FMT, "fields": FIELDS, "shard_bytes": 700,
      "batch_lines": 16, "sources": [{"kind": "blob", "size": 1}]}


def test_merge_disjoint_and_idempotent(tmp_path):
    d = str(tmp_path)
    _mk_manifest(FP, [0, 1]).save(d, host_manifest_name(0))
    _mk_manifest(FP, [2, 3]).save(d, host_manifest_name(1))
    merged = merge_manifests(d)
    assert sorted(merged.shards) == [0, 1, 2, 3]
    assert list_host_manifests(d) == [(0, host_manifest_name(0)),
                                      (1, host_manifest_name(1))]
    # idempotent: re-merge (now including the merged manifest.json)
    again = merge_manifests(d)
    assert sorted(again.shards) == [0, 1, 2, 3]
    # the merged file is a plain single-host manifest
    top = JobManifest.load(d)
    assert sorted(top.shards) == [0, 1, 2, 3]
    assert top.mismatch(FP) is None


def test_merge_partial_is_normal(tmp_path):
    d = str(tmp_path)
    _mk_manifest(FP, [0]).save(d, host_manifest_name(0))
    # host 1 never committed anything (dead host): merge still lands
    merged = merge_manifests(d)
    assert sorted(merged.shards) == [0]


def test_merge_overlap_identical_dedupes(tmp_path):
    d = str(tmp_path)
    _mk_manifest(FP, [0, 1]).save(d, host_manifest_name(0))
    # a rebalanced assignment re-committed shard 1 with the identical
    # record (deterministic replay): dedupe, don't refuse
    m1 = _mk_manifest(FP, [1, 2])
    m1.shards[1].committed_at = 123.0  # wall clock may differ
    m1.save(d, host_manifest_name(1))
    merged = merge_manifests(d)
    assert sorted(merged.shards) == [0, 1, 2]


def test_merge_overlap_conflicting_refused(tmp_path):
    d = str(tmp_path)
    _mk_manifest(FP, [0, 1]).save(d, host_manifest_name(0))
    m1 = _mk_manifest(FP, [1])
    m1.shards[1].data_hash = "DIVERGED"
    m1.save(d, host_manifest_name(1))
    with pytest.raises(ManifestError, match="DIVERGING"):
        merge_manifests(d)


def test_merge_fingerprint_mismatch_refused_across_hosts(tmp_path):
    d = str(tmp_path)
    _mk_manifest(FP, [0]).save(d, host_manifest_name(0))
    other = dict(FP, shard_bytes=999)
    _mk_manifest(other, [1]).save(d, host_manifest_name(1))
    with pytest.raises(ManifestError, match="different job"):
        merge_manifests(d)
    # committed_anywhere applies the same refusal on resume
    with pytest.raises(ManifestError):
        committed_anywhere(d, FP)


def test_merge_empty_dir_refused(tmp_path):
    with pytest.raises(ManifestError, match="no manifest"):
        merge_manifests(str(tmp_path))


def test_wide_host_indices_stay_visible(tmp_path):
    """host_manifest_name widens past 999 ({index:03d}); listing and
    merge must see those commit logs too, or a 1000+-host pod's tail
    silently never merges."""
    d = str(tmp_path)
    _mk_manifest(FP, [0]).save(d, host_manifest_name(7))
    _mk_manifest(FP, [1]).save(d, host_manifest_name(1000))
    assert [i for i, _ in list_host_manifests(d)] == [7, 1000]
    merged = merge_manifests(d)
    assert sorted(merged.shards) == [0, 1]
    assert sorted(committed_anywhere(d)) == [0, 1]


# ---------------------------------------------------------------------------
# pod host jobs: byte parity, host loss, resume
# ---------------------------------------------------------------------------


def test_two_host_pod_merge_is_byte_identical(tmp_path, corpus_file):
    ref_hash, ref = reference_hash(tmp_path, corpus_file)
    spec0 = job_spec(tmp_path, corpus_file, "pod", n_hosts=2, host_index=0)
    spec1 = job_spec(tmp_path, corpus_file, "pod", n_hosts=2, host_index=1)
    r0, r1 = run(spec0), run(spec1)
    assert r0.complete and r1.complete
    assert r0.shards_total + r1.shards_total == ref.shards_total
    assert r0.rejects + r1.rejects == ref.rejects
    merged = merge_manifests(spec0.out_dir)
    assert len(merged.shards) == ref.shards_total
    assert merged_hash(spec0.out_dir,
                       JobManifest.load(spec0.out_dir)) == ref_hash
    # post-merge, a single-host resume over the pod dir is a no-op
    rep = run(job_spec(tmp_path, corpus_file, "pod"))
    assert rep.skipped == ref.shards_total and rep.committed == 0
    # and hygiene: no temp debris anywhere
    assert leaked_temp_files(spec0.out_dir) == []


def test_host_loss_resume_byte_parity(tmp_path, corpus_file):
    """Kill one simulated host mid-run (commit-boundary crash model),
    resume it, merge: byte-identical, committed shards never
    re-parsed."""
    ref_hash, ref = reference_hash(tmp_path, corpus_file)
    spec0 = job_spec(tmp_path, corpus_file, "pod", n_hosts=2, host_index=0)
    spec1 = job_spec(tmp_path, corpus_file, "pod", n_hosts=2, host_index=1)
    r0 = run(spec0)
    assert r0.complete
    dead = run(spec1, policy=JobPolicy(stop_after_shards=1,
                                       io_backoff_s=0.005))
    assert dead.stopped_early and dead.committed == 1
    # a PARTIAL merge mid-loss is legal (the dead host's tail is absent)
    partial = merge_manifests(spec0.out_dir)
    assert len(partial.shards) == r0.committed + 1
    # resume the lost host: its committed shard is skipped, not re-parsed
    revived = run(spec1)
    assert revived.complete
    assert revived.skipped == 1
    assert revived.committed == dead.shards_total - 1
    merged = merge_manifests(spec0.out_dir)
    assert len(merged.shards) == ref.shards_total
    assert merged_hash(spec0.out_dir,
                       JobManifest.load(spec0.out_dir)) == ref_hash


def test_pod_host_count_change_respects_commits(tmp_path, corpus_file):
    """Re-running with a different host count (a shrunk pod) skips every
    shard any previous host committed — host geometry is execution-only."""
    ref_hash, ref = reference_hash(tmp_path, corpus_file)
    spec0 = job_spec(tmp_path, corpus_file, "pod", n_hosts=3, host_index=0)
    r0 = run(spec0)
    assert r0.complete
    # pod shrinks to 1 host: the survivor picks up everything else
    solo = run(job_spec(tmp_path, corpus_file, "pod"))
    assert solo.skipped == r0.committed
    assert solo.committed == ref.shards_total - r0.committed
    merge_manifests(spec0.out_dir)
    assert merged_hash(spec0.out_dir,
                       JobManifest.load(spec0.out_dir)) == ref_hash


def test_host_preemption_resume_byte_parity(tmp_path, corpus_file):
    """The SIGTERM-preemption model of host loss (docs/JOBS.md
    "Preemption"): a host stopped CLEANLY at a commit boundary
    (JobPolicy.stop_event — exactly what the jobs CLI's SIGTERM handler
    sets) resumes with ZERO re-parsed shards and merges
    byte-identical — the cheap exit the preemption notice buys over the
    SIGKILL crash path."""
    import threading

    ref_hash, ref = reference_hash(tmp_path, corpus_file)
    spec0 = job_spec(tmp_path, corpus_file, "pre", n_hosts=2, host_index=0)
    spec1 = job_spec(tmp_path, corpus_file, "pre", n_hosts=2, host_index=1)
    r0 = run(spec0)
    assert r0.complete
    notice = threading.Event()
    notice.set()
    pre = run(spec1, policy=JobPolicy(stop_event=notice,
                                      io_backoff_s=0.005))
    assert pre.preempted and pre.stopped_early and pre.committed == 1
    revived = run(spec1)
    assert revived.complete and revived.skipped == pre.committed
    merged = merge_manifests(spec0.out_dir)
    assert len(merged.shards) == ref.shards_total
    assert merged_hash(spec0.out_dir,
                       JobManifest.load(spec0.out_dir)) == ref_hash
    assert leaked_temp_files(spec0.out_dir) == []


def test_preemption_watcher_fires_on_commit_count(tmp_path):
    """The preempt_host chaos watcher SIGTERMs the host exactly when
    its commit log reaches the trigger count — driven with a fake
    process so the unit is deterministic."""
    import json as _json
    import threading

    from logparser_tpu.jobs.manifest import host_manifest_name
    from logparser_tpu.pod.runner import (
        _committed_in_host_manifest,
        _preemption_watcher,
    )

    out = str(tmp_path)
    assert _committed_in_host_manifest(out, 1) == 0  # absent = 0

    class FakeProc:
        def __init__(self):
            self.terminated = threading.Event()

        def poll(self):
            return 3 if self.terminated.is_set() else None

        def terminate(self):
            self.terminated.set()

    proc = FakeProc()
    t = threading.Thread(target=_preemption_watcher,
                         args=(out, 1, 2, proc, 0.01), daemon=True)
    t.start()
    # One commit: below the trigger, the watcher must keep waiting.
    path = tmp_path / host_manifest_name(1)
    path.write_text(_json.dumps({"shards": {"4": {}}}))
    assert not proc.terminated.wait(0.15)
    # Second commit: trigger reached -> SIGTERM.
    path.write_text(_json.dumps({"shards": {"4": {}, "5": {}}}))
    assert proc.terminated.wait(5.0)
    t.join(5.0)
    assert not t.is_alive()


def test_run_pod_inline(tmp_path, corpus_file):
    ref_hash, ref = reference_hash(tmp_path, corpus_file)
    spec = PodSpec(
        sources=[str(corpus_file)], log_format=FMT, fields=FIELDS,
        out_dir=str(tmp_path / "runpod"), n_hosts=2,
        shard_bytes=700, batch_lines=16, use_processes=False,
    )
    report = run_pod(spec, policy=PodPolicy(inline=True),
                     parser=parser())
    assert report.complete, report.as_dict()
    assert report.merged_shards == ref.shards_total
    assert merged_hash(spec.out_dir,
                       JobManifest.load(spec.out_dir)) == ref_hash
    d = report.as_dict()
    assert [h["ok"] for h in d["hosts"]] == [True, True]


def test_sweep_spares_live_writer_tmp(tmp_path, corpus_file):
    """The pod-safe debris rules: a LOCAL temp with a live pid (a
    concurrent local host mid-write) and a FRESH foreign-host temp (a
    remote host mid-write over the shared filesystem) are not
    sweepable; dead-local-pid, stale-foreign, and identity-less temps
    are."""
    from logparser_tpu.jobs.manifest import host_token, temp_suffix
    from logparser_tpu.jobs.writer import FOREIGN_TMP_STALE_S

    d = tmp_path / "sweep"
    d.mkdir()
    live_local = f"shard-00001.arrow{temp_suffix()}"
    (d / live_local).write_bytes(b"x")
    dead_local = f"shard-00002.arrow.{host_token()}.999999999.tmp"
    (d / dead_local).write_bytes(b"x")
    # legacy pid-only names follow the local rule
    legacy_live = f"shard-00003.arrow.{os.getpid()}.tmp"
    (d / legacy_live).write_bytes(b"x")
    foreign_fresh = "shard-00004.arrow.otherhost.123.tmp"
    (d / foreign_fresh).write_bytes(b"x")
    foreign_stale = "shard-00005.arrow.otherhost.456.tmp"
    p = d / foreign_stale
    p.write_bytes(b"x")
    old = p.stat().st_mtime - FOREIGN_TMP_STALE_S - 10
    os.utime(p, (old, old))
    (d / "manifest.json.tmp").write_bytes(b"x")
    assert len(leaked_temp_files(str(d))) == 6
    assert sorted(sweepable_temp_files(str(d))) == [
        "manifest.json.tmp",
        dead_local,
        foreign_stale,
    ]


def test_bad_pod_placement_rejected(tmp_path, corpus_file):
    with pytest.raises(ValueError):
        run(job_spec(tmp_path, corpus_file, "bad", n_hosts=2,
                     host_index=2))
    with pytest.raises(ValueError):
        run(job_spec(tmp_path, corpus_file, "bad", n_hosts=0))


def test_local_hosts_pinned_one_chip_each(tmp_path, corpus_file,
                                          monkeypatch):
    """Subprocess hosts share the machine's chips: host i's environment
    carries chip i, and more hosts than chips is refused before any host
    starts (read from the launcher; no chip and no child needed)."""
    from logparser_tpu import chips
    from logparser_tpu.chips import ChipOversubscribedError
    from logparser_tpu.pod import runner

    monkeypatch.setattr(chips, "host_chips", lambda: [0, 1])
    spec = PodSpec([corpus_file], "combined", FIELDS,
                   str(tmp_path / "pod"), n_hosts=3)
    launch_host = runner._launch_host
    launched = []
    monkeypatch.setattr(runner, "_launch_host",
                        lambda *a, **k: launched.append(a))
    with pytest.raises(ChipOversubscribedError):
        run_pod(spec)
    assert launched == []

    seen = {}

    class FakePopen:
        def __init__(self, argv, **kw):
            seen["argv"], seen["env"] = argv, kw["env"]

    monkeypatch.setattr(runner.subprocess, "Popen", FakePopen)
    launch_host(spec, 1, PodPolicy(), chip_env=chips.chip_env(1))
    assert seen["env"]["TPU_VISIBLE_CHIPS"] == "1"
    assert seen["argv"][seen["argv"].index("--host-index") + 1] == "1"


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_cli_pod_hosts_and_merge(tmp_path, corpus_file, capsys):
    from logparser_tpu.jobs.__main__ import main

    out = tmp_path / "cli-pod"
    base = [str(corpus_file), "--format", FMT, "--out", str(out),
            "--shard-bytes", "700", "--batch-lines", "16", "--threads"]
    for f in FIELDS:
        base += ["--field", f]
    assert main(base + ["--hosts", "2", "--host-index", "0"]) == 0
    rep0 = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rep0["complete"] and rep0["n_hosts"] == 2
    assert main(base + ["--hosts", "2", "--host-index", "1",
                        "--merge"]) == 0
    rep1 = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rep1["complete"]
    assert rep1["merged_shards"] == (rep0["shards_total"]
                                     + rep1["shards_total"])
    # --merge-only over the merged dir is a no-op re-merge
    assert main(base + ["--merge-only"]) == 0
    rep2 = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rep2["merged_shards"] == rep1["merged_shards"]
    # byte parity vs the single-host reference
    ref_hash, _ = reference_hash(tmp_path, corpus_file)
    assert merged_hash(str(out), JobManifest.load(str(out))) == ref_hash
