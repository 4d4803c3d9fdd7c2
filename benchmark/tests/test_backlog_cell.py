"""The backlog cell (``serve.nginx_combined.backlog``): its whole run
rehearses on the CPU at the configuration's tiny size and reads
``correct``; a planted fault reads ``correct`` false; the closed-loop
generator counts only the lines answered inside the window; and the
cell's span readers read their spans, or nothing from a program
without them."""
import importlib.util
import os
import time

import pytest

from test_faults import _halve_tables, _run

CELL = "serve.nginx_combined.backlog"
METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")


def test_the_cell_rehearses_correct(capsys):
    line = _run(capsys, CELL, 2**36 + 21)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"]["lines_per_s"]["value"] > 0
    assert set(line["checks"]) == {"row_mismatches", "unanswered"}


def test_halved_answers_read_incorrect(capsys, monkeypatch):
    _halve_tables(monkeypatch)
    line = _run(capsys, CELL, 2**36 + 21)
    assert line["correct"] is False
    assert line["checks"]["row_mismatches"]["value"] > 0


class _Table:
    def __init__(self, n):
        self.num_rows = n


class _SlowClient:
    """Answers every request with its rows after ``delay_s``."""

    def __init__(self, delay_s):
        self.delay_s = delay_s

    def parse(self, lines):
        time.sleep(self.delay_s)
        return _Table(len(lines))

    def close(self):
        pass


def test_only_lines_answered_inside_the_window_count():
    """Requests of 10, 20, 30, ... lines on one session whose answers
    take 0.1 s each, in a 0.25 s window: the first two answers land
    inside it, the third is sent inside and answered after the close
    (drained, attempted, not counted), and no fourth is sent."""
    from loadgen_backlog import BacklogAgent, summarize

    reqs = [(k, 0.0, ["x"] * (10 * (k + 1))) for k in range(6)]
    agent = BacklogAgent(lambda: _SlowClient(0.1), reqs)
    agent.connect()
    out, keep = [], {2: None}
    t0 = time.monotonic() + 0.02
    close = t0 + 0.25
    agent.run_closed(t0, close, out, keep)
    assert [k for k, _, _, _ in out] == [0, 1, 2]
    assert out[2][1] < close < out[2][2]
    assert keep[2] is not None  # a drained answer is still checked
    s = summarize(out, lambda k: len(reqs[k][2]), t0, close, 0.25)
    assert s["lines_per_s"] == pytest.approx((10 + 20) / 0.25)
    assert s["attempted"] == 3 and s["failed"] == 0
    assert s["answered_in_window"] == 2
    assert s["answer_gap_max_s"] == pytest.approx(0.1, abs=0.04)


def test_a_session_at_the_end_of_its_backlog_starts_it_again():
    from loadgen_backlog import BacklogAgent

    reqs = [(k, 0.0, ["x"]) for k in range(2)]
    agent = BacklogAgent(lambda: _SlowClient(0.02), reqs)
    agent.connect()
    out = []
    t0 = time.monotonic()
    agent.run_closed(t0, t0 + 0.15, out, {})
    assert [k for k, _, _, _ in out][:4] == [0, 1, 0, 1]


def test_the_answer_gap_is_the_longest_silence_in_the_window():
    from loadgen_backlog import answer_gap

    assert answer_gap([1.0, 1.5, 4.0, 9.0], 0.0, 5.0) == (2.5, 1.5)
    assert answer_gap([], 0.0, 5.0) == (5.0, 0.0)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"),
        os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _snap(stage_s, counters=(), count=1):
    hists = {'stage_seconds{stage="%s"}' % s: {"count": count, "sum": v}
             for s, v in stage_s.items()}
    return {"counters": dict(counters), "gauges": {}, "histograms": hists}


# Over the window: requests 40 s, queued 10 s of it; the dispatcher
# parsed 30 s of a 50 s window; IPC 2 s and socket writes 1 s for
# 1,000,000 lines answered.
WINDOW = {
    "before": _snap({"serve_request": 1.0, "coalesce_wait": 0.5,
                     "coalesce_parse": 1.0, "ipc": 1.0, "serve_send": 0.5},
                    {"service_lines_total": 10}),
    "after": _snap({"serve_request": 41.0, "coalesce_wait": 10.5,
                    "coalesce_parse": 31.0, "ipc": 3.0, "serve_send": 1.5},
                   {"service_lines_total": 1_000_010}, count=2),
    "facts": {"wall_s": 50.0},
}


@pytest.mark.parametrize("name, want", [
    ("coalesce_wait_share.serve", 10.0 / 40.0),
    ("dispatcher_busy_share.serve", 30.0 / 50.0),
    ("delivery_us_per_line.serve", 3.0),
])
def test_span_readers(name, want):
    assert _reader(name)(WINDOW) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "coalesce_wait_share.serve", "dispatcher_busy_share.serve",
    "delivery_us_per_line.serve",
])
def test_span_readers_read_nothing_without_the_served_spans(name):
    # A program without the served spans still has ``ipc``.
    bare = {"before": _snap({"ipc": 1.0}, {"service_lines_total": 10}),
            "after": _snap({"ipc": 3.0}, {"service_lines_total": 1000}, 2),
            "facts": {"wall_s": 50.0}}
    assert _reader(name)(bare) is None
