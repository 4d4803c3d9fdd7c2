"""Driver: batch ETL through ``logparser_tpu.jobs.run_job``.

Set-up writes the cell's rotated log files (the ``weblog`` corpus's
fixed pool in an order drawn from the seed), builds one ``TpuBatchParser`` and runs one whole job
over the files, which compiles or loads every (batch, line-length)
shape the window's jobs use: they read the same files.  The window
starts job after job over those files, each into a fresh output
directory, until the window's end; ``lines_per_s`` is the lines the
jobs committed (rows plus rejects) over the wall time from the first
job's start to the last job's end.

The check compares, for every job the window started: its line, row
and reject counts with the reference's scan of the files; every
rejected line; a sample of rows drawn from the seed (and the longest
lines); and, for an aggregate query, the merged job aggregate, key by
key, with the reference's.
"""
import json
import os
import time

import numpy as np

from check import Lines, compare_aggs, compare_rows, limits, scan_files
from gen.weblog import Corpus
from reference import Reference


class Driver:
    def __init__(self, config, traffic, seed, seconds, workdir,
                 rehearse=False):
        self.cfg = dict(config, **config["tiny"]) if rehearse else dict(config)
        self.traffic = traffic
        self.seed = seed
        self.seconds = seconds
        self.workdir = workdir
        self.rehearse = rehearse
        self.query = traffic["query"]
        self.parser = None
        self.jobs = []
        self._notes = []
        self._want = None

    def notes(self):
        return self._notes

    # -- set-up -------------------------------------------------------------

    def _corpus_params(self):
        with open(os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "corpora",
                self.traffic["corpus"] + ".json")) as f:
            p = json.load(f)
        if self.rehearse:
            p["pool_lines"] = self.cfg["pool_lines"]
        return p

    def write_corpus(self):
        """The cell's rotated log files, from the seed."""
        self.paths, blobs = write_files(self.cfg, self._corpus_params(),
                                        self.traffic["dialect"], self.seed,
                                        os.path.join(self.workdir, "logs"))
        self.lines = Lines(blobs)
        self.lens = np.concatenate([e - s for s, e in zip(self.lines.starts,
                                                          self.lines.ends)])
        self.longest = np.argsort(-self.lens, kind="stable")[:16]
        self.raw_bytes = sum(len(b) for b in blobs)

    def setup(self):
        from logparser_tpu.tpu.batch import TpuBatchParser

        self.write_corpus()
        self.parser = TpuBatchParser(self.cfg["log_format"],
                                     list(self.cfg["fields"]),
                                     view_fields=())
        report = self._job("warm")
        if not report.complete:
            raise RuntimeError(f"warm-up job incomplete: {report.as_dict()}")

    def _spec(self, out_dir):
        from logparser_tpu.jobs import JobSpec

        agg = self.query.get("aggregate")
        return JobSpec(sources=list(self.paths),
                       log_format=self.cfg["log_format"],
                       fields=list(self.cfg["fields"]), out_dir=out_dir,
                       shard_bytes=self.cfg["shard_bytes"],
                       batch_lines=self.cfg["batch_lines"],
                       workers=self.cfg["feeder_workers"],
                       aggregate=agg)

    def _job(self, name):
        from logparser_tpu.jobs import run_job

        out_dir = os.path.join(self.workdir, "out", name)
        return run_job(self._spec(out_dir), resume=False, parser=self.parser)

    # -- window -------------------------------------------------------------

    def window(self, seconds):
        t_end = time.monotonic() + seconds
        first = last = None
        job_s = []
        while True:
            t0 = time.monotonic()
            if t0 >= t_end:
                break
            report = self._job("job-%03d" % len(self.jobs))
            t1 = time.monotonic()
            first = t0 if first is None else first
            last = t1
            job_s.append(t1 - t0)
            self.jobs.append(report)
        self._notes.append("job seconds " + " ".join(
            "%.3f" % s for s in job_s))
        done = [r for r in self.jobs if r.complete]
        lines = sum(r.rows + r.rejects for r in done)
        return {
            "lines_per_s": lines / (last - first),
            "attempted": len(self.jobs),
            "failed": len(self.jobs) - len(done),
            "jobs": len(self.jobs),
            "lines": lines,
            "raw_bytes": self.raw_bytes * len(done),
            "wall_s": last - first,
        }

    def free(self):
        if self.parser is not None:
            self.parser.close()
            self.parser = None

    def close(self):
        self.free()

    # -- check --------------------------------------------------------------

    def reference_scan(self):
        """The reference over the whole files: (valid line count, set of
        rejected line numbers, [{key: count} per aggregate op])."""
        if self._want is None:
            self._want = scan_files(self.paths, self.cfg["log_format"],
                                    list(self.cfg["fields"]), self.ops(),
                                    min(8, os.cpu_count() or 1))
        return self._want

    def ops(self):
        return self.query.get("aggregate") or []

    def check(self, answers=None):
        """Every job the window started against the reference, or
        ``answers`` (the control's ``JobAnswers``, one per job) in their
        place through the same comparison."""
        fields = list(self.cfg["fields"])
        ref = Reference(self.cfg["log_format"], fields)
        ops = self.ops()
        want_valid, want_rejects, want_aggs = self.reference_scan()
        if answers is None:
            answers = [job_answers(r, fields, ops) if r.complete else None
                       for r in self.jobs]
        n = len(self.lines)
        count_gap = reject_bad = row_bad = agg_bad = incomplete = 0
        for k, job in enumerate(answers):
            if job is None:
                incomplete += 1
                continue
            count_gap += abs(job.lines - n) + abs(job.rows - want_valid)
            got = set(job.rejected)
            reject_bad += len(got ^ want_rejects) + sum(
                job.rejected[i] != self.lines[i] for i in got & want_rejects)
            if ops:
                agg_bad += compare_aggs(want_aggs, job.aggs)
                continue
            sample = check_sample(self.seed + k, n,
                                  np.array(sorted(got), dtype=np.int64),
                                  self.longest, self.cfg["sample_rows"])
            bad, first = compare_rows(ref, self.lines, sample, job.row)
            row_bad += bad
            if first is not None:
                self._notes.append(f"job {k}: first mismatch {first}")
        self._notes.append(
            f"jobs {len(answers)} lines/job {n} reference valid "
            f"{want_valid} rejects {len(want_rejects)} raw bytes/job "
            f"{self.raw_bytes}")
        out = dict(incomplete_jobs=incomplete, count_gaps=count_gap,
                   wrong_rejects=reject_bad)
        if ops:
            out["aggregate_key_gaps"] = agg_bad
        else:
            out["row_mismatches"] = row_bad
        return limits(**out)


class JobAnswers:
    """What one job answered, as plain data: the lines it committed, its
    row count, its reject table ({line number: raw line}), each line's
    row (``row(i)``: a field dict, None where it rejected the line) and,
    for an aggregate query, one {key: count} dict per op."""

    def __init__(self, lines, rows, rejected, row=None, aggs=None):
        self.lines, self.rows, self.rejected = lines, rows, rejected
        self.row, self.aggs = row, aggs


def job_answers(report, fields, ops):
    """The answers a job committed to its output directory."""
    import pyarrow as pa

    from logparser_tpu.jobs.manifest import JobManifest

    out_dir = report.out_dir
    man = JobManifest.load(out_dir)
    recs = [man.shards[i] for i in sorted(man.shards)]
    rejected, tables, base = {}, [], 0
    for rec in recs:
        if rec.reject_file:
            t = pa.ipc.open_stream(pa.memory_map(
                os.path.join(out_dir, rec.reject_file))).read_all()
            for line, raw in zip(t.column("line").to_pylist(),
                                 t.column("raw").to_pylist()):
                rejected[base + line] = raw.decode(errors="replace")
        if rec.data_file:
            tables.append(pa.ipc.open_stream(pa.memory_map(
                os.path.join(out_dir, rec.data_file))).read_all())
        base += rec.lines
    rows = sum(r.rows for r in recs)
    if ops:
        from logparser_tpu.jobs.writer import merged_job_aggregate

        return JobAnswers(base, rows, rejected,
                          aggs=program_aggs(merged_job_aggregate(out_dir), ops))
    data = pa.concat_tables(tables) if tables else None
    rej = np.array(sorted(rejected), dtype=np.int64)

    def row(i):
        if i in rejected:
            return None
        r = i - int(np.searchsorted(rej, i))
        if data is None or r >= data.num_rows:
            return {f: "<missing>" for f in fields}
        got = data.slice(r, 1).to_pylist()[0]
        return {f: got[f] for f in fields}

    return JobAnswers(base, rows, rejected, row=row)


def write_files(cfg, params, dialect, seed, indir):
    """The cell's rotated log files: (paths, blobs).  The lines are the
    fixed pool's (``pool_seed``), each file a stretch of one permutation
    of it drawn from the run seed, so every seed has the same lines in
    its own order; the seed also picks the day the files' clock starts."""
    corpus = Corpus(params, params["pool_seed"], dialect)
    rng = np.random.default_rng([seed & (2**63 - 1), 0x65746C])
    n = cfg["file_lines"]
    order = np.resize(rng.permutation(len(corpus.prefixes)), n * cfg["files"])
    start = params["start_epoch_s"] + 86400 * int(rng.integers(365))
    os.makedirs(indir)
    paths, blobs = [], []
    for k in range(cfg["files"]):
        # Each rotated file continues the clock of the one before.
        stamps = corpus.clock(start + k * -(-n // params["lines_per_s"]),
                              n, params["lines_per_s"])
        blob = corpus.blob(order[k * n:(k + 1) * n], stamps)
        # Rotation names: access.log.N, oldest first.
        path = os.path.join(indir, "access.log.%d" % (cfg["files"] - k))
        with open(path, "wb") as f:
            f.write(blob)
        paths.append(path)
        blobs.append(blob)
    return paths, blobs


def check_sample(seed, n, rejects, longest, size):
    """The lines whose rows are compared field by field: ``size`` drawn
    from the seed, the longest lines, the first rejects."""
    rng = np.random.default_rng([seed & (2**63 - 1), 0x636B])
    return np.unique(np.concatenate([
        rng.integers(0, n, size), longest, rejects[:64]])).astype(np.int64)


def program_aggs(state, ops):
    """The merged job aggregate as one {key: count} dict per op, keyed
    like the reference's scan."""
    import pyarrow as pa

    table = pa.ipc.open_stream(state.to_ipc_bytes()).read_all()
    out = [dict() for _ in ops]
    for op, key, value in zip(table.column("op").to_pylist(),
                              table.column("key").to_pylist(),
                              table.column("value").to_pylist()):
        kind = ops[op]["op"]
        k = int(key.decode()) if kind == "time_bucket" else key.decode()
        out[op][k] = int(value)
    return out
