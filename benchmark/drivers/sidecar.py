"""Driver: one ``ParseService`` sidecar, continuous batching at its
defaults, in the run process (which holds the chip and can trace).

The load comes from ``benchmark/loadgen.py`` in a child process that
never imports JAX: one session per shipper agent, each sending its
flushes at their due instants (``gen/shippers.py``).  Set-up warms
every (batch, line-length) shape the traffic can form by sending one
request per shape through the service itself, then waits for the
service's own background prewarm.  ``request_p95_ms`` and
``request_p50_ms`` are over every request due in the window, each timed
from its due instant to its last response byte; a request that failed
or never came sorts as infinite.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np

from gen.weblog import Corpus

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
B_BUCKETS = (64, 128, 256, 512, 1024, 2048, 4096)
# The program's line-length buckets (native._bucket: powers of two to
# 128, steps of 128 to 512, of 256 to 1,024, then powers of two, capped
# at the device's 8,191-byte line).
L_BUCKETS = (64, 128, 256, 384, 512, 768, 1024, 2048, 4096, 8191)
#: A (batch, line) shape whose chance per formed batch is below this is
#: not warmed: one in a billion batches.
RARE = 1e-9


def warm_shapes(lengths, max_lines):
    """{line bucket: [batch buckets]} the traffic can form.  A batch of
    bucket B holds more than B/2 lines (any count up to 64 for the
    smallest), and its line bucket is that of its longest line, so the
    shape (B, L) needs a pool line in bucket L and comes with chance at
    most F(L)**(B/2+1), F the share of pool lines no longer than L."""
    lens = np.sort(np.asarray(lengths))
    out = {}
    for k, L in enumerate(L_BUCKETS):
        lo = L_BUCKETS[k - 1] if k else 0
        if not np.any((lens > lo) & (lens <= L)):
            continue
        F = np.searchsorted(lens, L, side="right") / len(lens)
        bs = [b for b in B_BUCKETS if b <= max_lines
              and F ** (1 if b == B_BUCKETS[0] else b // 2 + 1) >= RARE]
        if bs:
            out[L] = bs
    return out


def finite(v):
    return v if v != float("inf") else None


class Driver:
    def __init__(self, config, traffic, seed, seconds, workdir,
                 rehearse=False):
        self.cfg = dict(config, **config["tiny"]) if rehearse else dict(config)
        self.traffic = traffic
        self.seed = seed
        self.seconds = seconds
        self.workdir = workdir
        self.rehearse = rehearse
        self.service = None
        self.child = None
        self.summary = None
        self._notes = []
        with open(os.path.join(BENCH, "corpora", traffic["corpus"] + ".json")) as f:
            self.corpus_params = json.load(f)
        self.arrivals = dict(traffic["arrivals"])
        if rehearse:
            self.corpus_params["pool_lines"] = self.cfg["pool_lines"]
            self.arrivals["lines_per_s"] = self.cfg["lines_per_s"]

    def notes(self):
        return self._notes

    # -- set-up -------------------------------------------------------------

    def setup(self):
        from logparser_tpu.service import ParseService

        self.service = ParseService().start()
        self._start_child()
        self._warm()
        line = self.child.stdout.readline()
        if line.strip() != "READY":
            raise RuntimeError(f"load generator did not start: {line!r}")

    def load_spec(self):
        """What the load generator builds its requests from."""
        return {
            "log_format": self.cfg["log_format"],
            "fields": list(self.cfg["fields"]),
            "arrivals": self.arrivals, "corpus": self.corpus_params,
            "dialect": self.traffic["dialect"], "seed": self.seed,
            "seconds": self.seconds, "sample": self.cfg["sample_requests"],
        }

    def _start_child(self):
        spec = dict(self.load_spec(), host=self.service.host,
                    port=self.service.port)
        self.child = subprocess.Popen(
            [sys.executable, os.path.join(BENCH, "loadgen.py"),
             "--spec", json.dumps(spec)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))

    def _warm(self):
        """One request per (batch bucket, line bucket) the traffic can
        form, through the service: every executable the window can ask
        for is loaded (or compiled, the first time) before it starts."""
        from logparser_tpu.observability import metrics
        from logparser_tpu.service import ParseServiceClient

        corpus = Corpus(self.corpus_params, self.corpus_params["pool_seed"],
                        self.traffic["dialect"])
        lens = corpus.lengths
        order = np.argsort(lens, kind="stable")
        short = [corpus.lines([i], corpus.clock(0, 1, 1))[0]
                 for i in order[:64]]
        client = ParseServiceClient(self.service.host, self.service.port,
                                    self.cfg["log_format"],
                                    list(self.cfg["fields"]), timeout=1800)
        plan = warm_shapes(lens, self.arrivals["max_lines"])
        for L, bs in plan.items():
            fits = np.flatnonzero(lens <= L)
            top = corpus.lines([fits[np.argmax(lens[fits])]],
                               corpus.clock(0, 1, 1))[0]
            for B in bs:
                client.parse([top] + [short[k % len(short)]
                                      for k in range(B - 1)])
        client.close()
        # The coalescer can run a shape through the executor variant the
        # lone warm request did not use: load every variant of each shape.
        for parser in list(self.service._server.parser_cache._parsers.values()):
            for L, bs in plan.items():
                parser.prewarm(batch_sizes=bs, max_line_len=L)
        reg = metrics()
        deadline = time.monotonic() + 1800
        while reg.total("parser_prewarm_runs_total") < 1 and time.monotonic() < deadline:
            time.sleep(0.05)
        self.warm_shapes = self._shapes()

    def _shapes(self):
        """The (batch, line) shapes the service's parsers hold executables
        for: a shape that appears during the window compiled there."""
        out = set()
        for parser in list(self.service._server.parser_cache._parsers.values()):
            for fn in (parser.device_fn(), parser.device_views_fn()):
                if fn is not None and hasattr(fn, "shapes"):
                    out.update(fn.shapes())
        return out

    # -- window -------------------------------------------------------------

    def window(self, seconds):
        self.child.stdin.write("GO\n")
        self.child.stdin.flush()
        line = self.child.stdout.readline()
        if not line.startswith("DONE "):
            raise RuntimeError(f"load generator failed: {line!r}")
        s = json.loads(line[5:])
        self.summary = s
        self._notes.append("loadgen " + json.dumps(
            {k: s[k] for k in ("requests", "outcomes", "lines", "late_p50_ms",
                               "late_max_ms", "span_s", "backlog_growth")}))
        # A failed or unanswered request sorts as infinite; a tail that
        # reaches one is reported as missing, and the run's ``failed``
        # count says why.
        return {
            "request_p95_ms": finite(s["request_p95_ms"]),
            "request_p50_ms": finite(s["request_p50_ms"]),
            "attempted": s["requests"], "failed": s["failed"],
            "lines": s["lines"], "wall_s": s["span_s"],
        }

    def free(self):
        if self.service is not None:
            new = sorted(self._shapes() - self.warm_shapes)
            if new:
                self._notes.append(f"shapes first used in the window: {new}")
            self.service.shutdown()
            self.service = None

    def check(self, result=None):
        """The served check: what the load generator compared after the
        window, or ``result`` (the control's answers through the same
        comparison, ``loadgen.compare``)."""
        from check import limits

        if result is None:
            self.child.stdin.write("CHECK\n")
            self.child.stdin.flush()
            line = self.child.stdout.readline()
            if not line.startswith("RESULT "):
                raise RuntimeError(f"load generator check failed: {line!r}")
            result = json.loads(line[7:])
        self._notes.append("compared " + json.dumps(result))
        return limits(row_mismatches=result["row_mismatches"],
                      unanswered=result["unanswered"])

    def close(self):
        self.free()
        if self.child is not None:
            try:
                self.child.stdin.close()
            except OSError:
                pass
            try:
                self.child.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.child.kill()
                self.child.wait()
            self.child = None
