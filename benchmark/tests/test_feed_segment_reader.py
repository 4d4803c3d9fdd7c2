"""The feed-segment reader over hand-made registry snapshots: segment
batches over fed batches in the window, and nothing (None, no error)
from a program without the counter or a window that fed nothing."""
import importlib.util
import os

import pytest

READER = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics", "feed_segment_share.batch.py")


def _read(ctx):
    spec = importlib.util.spec_from_file_location("bench_metric_fss", READER)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def _ctx(before, after):
    return {"before": {"counters": before, "gauges": {}, "histograms": {}},
            "after": {"counters": after, "gauges": {}, "histograms": {}},
            "facts": {}}


def test_segment_batches_over_fed_batches():
    ctx = _ctx({"feeder_batches_total": 2, "feeder_segment_batches_total": 2},
               {"feeder_batches_total": 10, "feeder_segment_batches_total": 8})
    assert _read(ctx) == pytest.approx(6 / 8)


@pytest.mark.parametrize("before, after", [
    ({"feeder_batches_total": 2}, {"feeder_batches_total": 10}),
    ({"feeder_segment_batches_total": 2}, {"feeder_segment_batches_total": 2}),
])
def test_reads_nothing_without_the_counter_or_batches(before, after):
    assert _read(_ctx(before, after)) is None
