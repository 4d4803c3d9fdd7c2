"""How ``correct`` is decided: what the timed path produced against the
plain reference (``reference.py``), answer by answer.

Every number compared is a count of answers that differ from the
reference, and every limit is 0: the system promises exact fields, an
exact reject table and exact aggregates (``configs/*.json``,
``guarantees``).  The functions here take the program's answers as
plain data, so the control (the reference in the program's place,
with one guarantee broken) goes through the same comparison.
"""
import os
from concurrent.futures import ProcessPoolExecutor
import multiprocessing

import numpy as np

from reference import Reference


def line_offsets(blob):
    """Start offset of every line of a newline-terminated blob."""
    ends = np.flatnonzero(np.frombuffer(blob, dtype=np.uint8) == 10)
    return np.concatenate([[0], ends[:-1] + 1]), ends


class Lines:
    """Random access to the lines of a list of newline-terminated blobs,
    numbered through all of them."""

    def __init__(self, blobs):
        self.blobs = blobs
        self.starts, self.ends, self.base = [], [], [0]
        for b in blobs:
            s, e = line_offsets(b)
            self.starts.append(s)
            self.ends.append(e)
            self.base.append(self.base[-1] + len(s))
        self.n = self.base[-1]

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        k = int(np.searchsorted(self.base, i, side="right")) - 1
        j = i - self.base[k]
        return self.blobs[k][self.starts[k][j]:self.ends[k][j]].decode()


def same(got, want, fields):
    if (got is None) != (want is None):
        return False
    return got is None or all(got[f] == want[f] for f in fields)


def compare_rows(ref, lines, sample, answer):
    """Mismatching answers among ``sample`` (line indices):
    ``answer(i)`` is the program's field dict for line ``i``, None where
    it rejected the line."""
    bad, first = 0, None
    for i in sample:
        want = ref.parse_line(lines[i])
        got = answer(i)
        if not same(got, want, ref.fields):
            bad += 1
            if first is None:
                first = (int(i), lines[i][:200], got, want)
    return bad, first


# -- whole-corpus reference scans (after the window, in worker processes)

def _scan_chunk(task):
    path, start, end, log_format, fields, ops = task
    ref = Reference(log_format, fields)
    with open(path, "rb") as f:
        f.seek(start)
        data = f.read(end - start).decode()
    lines = data.split("\n")[:-1]
    rejects = []
    aggs = [dict() for _ in ops]
    for j, line in enumerate(lines):
        row = ref.parse_line(line) if ops else (
            {} if ref.valid(line) else None)
        if row is None:
            rejects.append(j)
            continue
        for k, op in enumerate(ops):
            v = row[op["field"]]
            if v is None:
                continue
            if op["op"] == "time_bucket":
                v = v // (op["width_s"] * 1000)
            aggs[k][v] = aggs[k].get(v, 0) + 1
    return len(lines), rejects, aggs


def _chunks(path, n):
    size = os.path.getsize(path)
    cuts = [0]
    with open(path, "rb") as f:
        for k in range(1, n):
            f.seek(size * k // n)
            f.readline()
            cuts.append(max(cuts[-1], f.tell()))
    cuts.append(size)
    return [(a, b) for a, b in zip(cuts, cuts[1:]) if b > a]


def scan_files(paths, log_format, fields, ops, workers):
    """(valid line count, set of rejected line numbers, [dict per op])
    of the reference over whole files, lines numbered through all of
    them: the rows, reject table and aggregates a job must give."""
    tasks = [(p, a, b, log_format, list(fields), ops)
             for p in paths for a, b in _chunks(p, workers)]
    ctx = multiprocessing.get_context("spawn")
    with ProcessPoolExecutor(workers, mp_context=ctx) as ex:
        parts = list(ex.map(_scan_chunk, tasks))
    base, rejects = 0, set()
    aggs = [dict() for _ in ops]
    for n, rej, part in parts:
        rejects.update(base + j for j in rej)
        base += n
        for k, d in enumerate(part):
            for key, c in d.items():
                aggs[k][key] = aggs[k].get(key, 0) + c
    return base - len(rejects), rejects, aggs


def compare_aggs(want, got):
    """Keys whose count differs, over all ops."""
    bad = 0
    for w, g in zip(want, got):
        for key in set(w) | set(g):
            if w.get(key, 0) != g.get(key, 0):
                bad += 1
    return bad


def limits(**values):
    """{name: {"value", "limit"}} with every limit 0 (exact answers)."""
    return {k: {"value": int(v), "limit": 0} for k, v in values.items()}


def verdict(checks):
    """``correct``: every number compared within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())
