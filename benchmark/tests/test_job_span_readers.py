"""The job-span readers over hand-made registry snapshots: each divides
a window delta of span seconds by the ``job`` span's, and reads nothing
(None, no error) from a program that has no ``job`` span."""
import importlib.util
import os

import pytest

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"),
        os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _snap(stage_s, self_s):
    hists = {'stage_seconds{stage="%s"}' % s: {"count": 1, "sum": v}
             for s, v in stage_s.items()}
    counters = {'stage_self_seconds_total{stage="%s"}' % s: v
                for s, v in self_s.items()}
    return {"counters": counters, "gauges": {}, "histograms": hists}


BEFORE = _snap(
    {"job": 10.0, "feeder_start": 0.5, "feeder_fill": 1.0,
     "feeder_close": 0.25, "h2d_wait": 2.0, "job_commit": 1.0},
    {"job": 0.1, "job_commit": 0.2},
)
# Over the window: job 20 s, of which 0.4 s under no span; start 1 s,
# fill 3 s, close 1 s, H2D wait 6 s, commit 2 s.
AFTER = _snap(
    {"job": 30.0, "feeder_start": 1.5, "feeder_fill": 4.0,
     "feeder_close": 1.25, "h2d_wait": 8.0, "job_commit": 3.0,
     "job_fold": 5.0},
    {"job": 0.5, "job_commit": 0.7},
)


@pytest.mark.parametrize("name, want", [
    ("job_self_share.batch", 0.4 / 20.0),
    ("feed_startup_share.batch", 5.0 / 20.0),
    ("h2d_wait_share.batch", 6.0 / 20.0),
    ("writer_share.batch", 2.0 / 20.0),
])
def test_reader_divides_span_seconds_by_job_seconds(name, want):
    ctx = {"before": BEFORE, "after": AFTER, "facts": {}}
    assert _reader(name)(ctx) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "job_self_share.batch", "feed_startup_share.batch",
    "h2d_wait_share.batch", "writer_share.batch",
])
def test_reader_reads_nothing_without_a_job_span(name):
    # A program without the spans: the registry has other stages only.
    bare = _snap({"assembly": 1.0}, {})
    ctx = {"before": _snap({}, {}), "after": bare, "facts": {}}
    assert _reader(name)(ctx) is None


def test_missing_phase_spans_read_as_zero():
    # A window whose jobs all ran but never reached a phase (e.g. every
    # job had nothing left to do) reads 0, not an error.
    ctx = {"before": _snap({}, {}), "after": _snap({"job": 2.0}, {}),
           "facts": {}}
    assert _reader("h2d_wait_share.batch")(ctx) == 0.0
    assert _reader("job_self_share.batch")(ctx) == 0.0
