"""Closed-loop load generator for the backlog cell: a child process that
never imports JAX (the run process holds the chip).

The shippers of ``loadgen.py`` catching up after a sidecar restart or an
outage: each session resumes from its stored offset and sends its
flushes in order, the next one as soon as the last one is answered
(Fluent Bit's ``tail`` input with ``DB``, Filebeat's registry).  Every
session has a backlog from the window's first instant, so the sidecar
always has work queued; what is measured is how fast it answers.

The flushes, their lines and the sampled requests come from
``loadgen.build_requests`` over the traffic's schedule; the client and
the comparison with the reference are ``loadgen``'s too.  A session that
reaches the end of its backlog before the window closes starts it again,
so no session runs dry.

Reported (``DONE``): ``lines_per_s``, the lines of requests answered
with rows whose last byte arrived inside the window, over the window's
seconds (a request still in flight at the close is drained and checked,
not counted); ``attempted`` and ``failed``, requests sent and requests
answered without rows; ``answer_gap_max_s``, the longest interval inside
the window in which no answer arrived, and where it began.

Protocol on stdin/stdout, one line each (as ``loadgen.py``):
    child  -> READY            payloads built
    parent -> GO               connect the sessions; the window starts
    child  -> DONE <json>      the window closed, in-flight answers drained
    parent -> CHECK            the system's state is freed
    child  -> RESULT <json>    the sampled answers against the reference
"""
import argparse
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

from loadgen import GRACE_S, Agent, build_requests, compare  # noqa: E402
from reference import Reference  # noqa: E402


class BacklogAgent(Agent):
    """One shipper session replaying its backlog: its requests, in
    order, each sent when the last was answered, until the window
    closes."""

    def run_closed(self, t0, close, out, keep):
        """Append ``(k, sent, done, rows)`` to ``out`` for every request
        sent before ``close`` (``rows`` None for an answer without rows);
        keep the first answer of each request whose key is in ``keep``."""
        from logparser_tpu.service import ParseServiceError

        now = time.monotonic()
        if t0 > now:
            time.sleep(t0 - now)
        while True:
            for k, _due, lines in self.requests:
                sent = time.monotonic()
                if sent >= close:
                    return
                rows = None
                try:
                    if self.client is None:
                        self.connect()
                    table = self.client.parse(lines)
                    rows = table.num_rows
                except ParseServiceError:
                    table = None
                except OSError:
                    table = None
                    self.client = None
                out.append((k, sent, time.monotonic(), rows))
                if k in keep and keep[k] is None and table is not None:
                    keep[k] = table


def answer_gap(done, t0, close):
    """(longest interval in [t0, close] with no answer arriving, its
    start relative to ``t0``)."""
    edges = [t0] + sorted(t for t in done if t0 <= t <= close) + [close]
    best, at = 0.0, 0.0
    for a, b in zip(edges, edges[1:]):
        if b - a > best:
            best, at = b - a, a - t0
    return best, at


def summarize(records, lines_of_k, t0, close, seconds):
    """The window's numbers from the agents' records
    ``(k, sent, done, rows)``; ``lines_of_k(k)`` is request k's line
    count."""
    counted = sum(lines_of_k(k) for k, _, done, rows in records
                  if rows is not None and done <= close)
    failed = sum(rows is None for _, _, _, rows in records)
    gap, gap_at = answer_gap([done for _, _, done, _ in records], t0, close)
    per_s = [0] * max(1, int(round(seconds)))
    for k, _, done, rows in records:
        if rows is not None and t0 <= done <= close:
            per_s[min(len(per_s) - 1, int(done - t0))] += lines_of_k(k)
    return {
        "lines_per_s": counted / seconds,
        "attempted": len(records),
        "failed": failed,
        "answered_in_window": sum(done <= close for _, _, done, _ in records),
        "answer_gap_max_s": gap,
        "answer_gap_at_s": gap_at,
        "lines_each_second": per_s,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--spec", required=True,
                    help="JSON: host, port, log_format, fields, arrivals, "
                         "corpus, dialect, seed, seconds, sample")
    args = ap.parse_args()
    spec = json.loads(args.spec)
    from logparser_tpu.service import ParseServiceClient

    requests, lines_of, sample = build_requests(spec)
    keep = {k: None for k in sample}
    seconds = float(spec["seconds"])

    def factory():
        return ParseServiceClient(spec["host"], spec["port"],
                                  spec["log_format"], spec["fields"],
                                  timeout=GRACE_S + seconds)

    by_agent = {}
    for k, due, agent, payload in requests:
        by_agent.setdefault(agent, []).append((k, due, payload))
    agents = [BacklogAgent(factory, reqs) for _, reqs in sorted(by_agent.items())]
    print("READY", flush=True)
    if sys.stdin.readline().strip() != "GO":
        return 2
    for a in agents:
        a.connect()
    t0 = time.monotonic() + 0.01
    close = t0 + seconds
    outs = [[] for _ in agents]
    threads = [threading.Thread(target=a.run_closed,
                                args=(t0, close, out, keep), daemon=True)
               for a, out in zip(agents, outs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=max(0.0, close + GRACE_S - time.monotonic()))
    stuck = sum(t.is_alive() for t in threads)
    for a in agents:
        a.close()
    records = [r for out in outs for r in out]
    summary = summarize(records, lambda k: len(lines_of[k]), t0, close,
                        seconds)
    summary["sessions_stuck"] = stuck
    summary["backlog_wraps"] = sum(
        len(out) > len(a.requests) for a, out in zip(agents, outs))
    print("DONE " + json.dumps(summary), flush=True)
    if sys.stdin.readline().strip() != "CHECK":
        return 2
    # Compared: the sampled requests that were sent; unanswered: every
    # request sent that got no rows (a session still stuck counts one).
    sent = {k for k, _, _, _ in records}
    answers = {k: None if t is None else t.to_pylist()
               for k, t in keep.items() if k in sent}
    ref = Reference(spec["log_format"], spec["fields"])
    n_req = summary["attempted"] + stuck
    print("RESULT " + json.dumps(compare(
        ref, lines_of, answers, n_req,
        summary["attempted"] - summary["failed"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
