"""Open-loop load generator for the served cells: a child process that
never imports JAX (the run process holds the chip).

Adapted from the program's ``tools/loadgen.py`` with two changes: a
request is timed from the instant it was DUE, not from when it was sent
(so a stall that delays later requests counts against them, and there is
no coordinated omission), and the generator reports how late it sent.

Protocol on stdin/stdout, one line each:
    child  -> READY            payloads built
    parent -> GO               connect the sessions; the window starts
    child  -> DONE <json>      every request due in the window answered
    parent -> CHECK            the system's state is freed
    child  -> RESULT <json>    the sampled answers against the reference
"""
import argparse
import json
import os
import sys
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

from check import compare_rows  # noqa: E402
from gen.shippers import schedule  # noqa: E402
from gen.weblog import Corpus  # noqa: E402
from reference import Reference  # noqa: E402

#: An answer that never came within this many seconds of the window's
#: close is missing.
GRACE_S = 60.0


class Agent:
    """One shipper session: its requests, sent in due order on one
    connection."""

    def __init__(self, client_factory, requests):
        self.requests = requests  # [(k, due_s, lines)]
        self.client_factory = client_factory
        self.client = None

    def connect(self):
        self.client = self.client_factory()

    def run(self, t0, out, keep):
        from logparser_tpu.service import ParseServiceError

        for k, due, lines in self.requests:
            due_at = t0 + due
            now = time.monotonic()
            if due_at > now:
                time.sleep(due_at - now)
            sent = time.monotonic()
            try:
                if self.client is None:
                    self.connect()
                table = self.client.parse(lines)
            except ParseServiceError as e:
                out[k] = {"ok": False, "kind": type(e).__name__,
                          "late_s": sent - due_at}
                continue
            except OSError as e:
                out[k] = {"ok": False, "kind": "unanswered:" + type(e).__name__,
                          "late_s": sent - due_at}
                self.client = None
                continue
            done = time.monotonic()
            out[k] = {"ok": True, "latency_s": done - due_at,
                      "late_s": sent - due_at, "rows": table.num_rows}
            if k in keep:
                keep[k] = table

    def close(self):
        if self.client is not None:
            try:
                self.client.close()
            except OSError:
                pass


def percentile(values, q):
    """Nearest rank: the value with ceil(q * n) values at or below it."""
    ordered = sorted(values)
    rank = max(1, int(np.ceil(q * len(ordered) - 1e-9)))
    return ordered[rank - 1]


def build_requests(spec):
    """The window's requests: [(k, due_s, agent, payload)], the lines of
    each, and the requests whose answers are compared (drawn from the
    seed, and the largest)."""
    # The flush instants and sizes, and the pool of lines, are the
    # traffic's fixed work (the same for every seed); the run seed draws
    # which pool lines go into which request.
    seed = int(spec["seed"]) & (2**63 - 1)
    corpus = Corpus(spec["corpus"], spec["corpus"]["pool_seed"], spec["dialect"])
    sched = schedule(spec["arrivals"], seed, spec["seconds"])
    total = sum(n for _, _, n in sched)
    rng = np.random.default_rng([seed, 0x6C67])
    idx_all = corpus.draw(rng, total)
    start = spec["corpus"]["start_epoch_s"]
    requests, lines_of = [], {}
    pos = 0
    for k, (due, agent, n) in enumerate(sched):
        idx = idx_all[pos:pos + n]
        pos += n
        lines = corpus.lines(idx, corpus.clock(start + int(due), n, n))
        requests.append((k, due, agent, [ln.encode() for ln in lines]))
        lines_of[k] = lines
    n_req = len(requests)
    sample = set(rng.choice(n_req, min(spec["sample"], n_req),
                            replace=False).tolist())
    sample.add(max(range(n_req), key=lambda k: len(requests[k][3])))
    return requests, lines_of, sample


def compare(ref, lines_of, answers, n_req, n_ok):
    """The numbers the served check compares.  ``answers`` maps each
    sampled request to the rows it was answered with (dicts with
    ``__valid__``), or None where it got no rows.  ``row_mismatches``:
    lines of the sampled requests whose row differs from the
    reference's, every line of a request that got no rows counted;
    ``unanswered``: requests due in the window that got no rows, for
    whatever reason (never answered, refused, shed, expired)."""
    fields = ref.fields
    bad = compared = 0
    first = None
    for k, rows in answers.items():
        lines = lines_of[k]
        compared += len(lines)
        if rows is None:
            bad += len(lines)
            continue
        if len(rows) != len(lines):
            bad += abs(len(rows) - len(lines))

        def answer(i, rows=rows):
            if i >= len(rows):
                return {f: "<missing>" for f in fields}
            r = rows[i]
            return {f: r[f] for f in fields} if r["__valid__"] else None

        n_bad, f1 = compare_rows(ref, lines, range(len(lines)), answer)
        bad += n_bad
        first = first or f1
    return {"row_mismatches": bad, "unanswered": n_req - n_ok,
            "compared_lines": compared,
            "compared_requests": sum(r is not None for r in answers.values()),
            "first_mismatch": repr(first)[:500] if first else None}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--spec", required=True,
                    help="JSON: host, port, log_format, fields, arrivals, "
                         "corpus, dialect, seed, seconds, sample")
    args = ap.parse_args()
    spec = json.loads(args.spec)
    from logparser_tpu.service import ParseServiceClient

    requests, lines_of, sample = build_requests(spec)
    n_req = len(requests)
    keep = {k: None for k in sample}

    def factory():
        return ParseServiceClient(spec["host"], spec["port"],
                                  spec["log_format"], spec["fields"],
                                  timeout=GRACE_S + spec["seconds"])

    agents = {}
    for k, due, agent, payload in requests:
        agents.setdefault(agent, []).append((k, due, payload))
    agents = [Agent(factory, reqs) for _, reqs in sorted(agents.items())]
    print("READY", flush=True)
    if sys.stdin.readline().strip() != "GO":
        return 2
    # Sessions open at the window's start: a shipper's connection is
    # long-lived, and one opened before a long set-up would meet the
    # service's idle timeout.
    for a in agents:
        a.connect()
    out = {}
    t0 = time.monotonic() + 0.01
    threads = [threading.Thread(target=a.run, args=(t0, out, keep),
                                daemon=True) for a in agents]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=max(0.0, t0 + spec["seconds"] + GRACE_S
                           - time.monotonic()))
    t_end = time.monotonic()
    for a in agents:
        a.close()
    lat = [out[k]["latency_s"] * 1e3 if out.get(k, {}).get("ok")
           else float("inf") for k in range(n_req)]
    late = [out[k]["late_s"] for k in out]
    kinds = {}
    for k in range(n_req):
        kind = "ok" if out.get(k, {}).get("ok") else out.get(k, {}).get(
            "kind", "unanswered:timeout")
        kinds[kind] = kinds.get(kind, 0) + 1
    # Backlog: does the wait grow through the window?  Median latency of
    # the last quarter of requests (by due time) over the first quarter's.
    q = max(1, n_req // 4)
    summary = {
        "backlog_growth": percentile(lat[-q:], 0.5) / percentile(lat[:q], 0.5),
        "requests": n_req,
        "failed": n_req - kinds.get("ok", 0),
        "outcomes": kinds,
        "lines": int(sum(len(r[3]) for r in requests)),
        "request_p50_ms": percentile(lat, 0.50),
        "request_p95_ms": percentile(lat, 0.95),
        "late_p50_ms": 1e3 * percentile(late, 0.5) if late else None,
        "late_max_ms": 1e3 * max(late) if late else None,
        "span_s": t_end - t0,
    }
    print("DONE " + json.dumps(summary), flush=True)
    if sys.stdin.readline().strip() != "CHECK":
        return 2
    ref = Reference(spec["log_format"], spec["fields"])
    answers = {k: None if t is None else t.to_pylist() for k, t in keep.items()}
    print("RESULT " + json.dumps(compare(ref, lines_of, answers, n_req,
                                         kinds.get("ok", 0))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
