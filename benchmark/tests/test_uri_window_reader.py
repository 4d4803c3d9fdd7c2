"""The URI-window reader over hand-made registry snapshots: windowed
lines over parsed lines in the window, and nothing (None, no error)
from a program without the counter or a window that parsed nothing."""
import importlib.util
import os

import pytest

READER = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics", "uri_window_share.serve.py")


def _read(ctx):
    spec = importlib.util.spec_from_file_location("bench_metric_uws", READER)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def _ctx(before, after):
    return {"before": {"counters": before, "gauges": {}, "histograms": {}},
            "after": {"counters": after, "gauges": {}, "histograms": {}},
            "facts": {}}


@pytest.mark.parametrize("before, after, want", [
    ({"parse_lines_total": 100, "uri_window_lines_total": 40},
     {"parse_lines_total": 10_100, "uri_window_lines_total": 9_640}, 0.96),
    # The counter first moved inside the window.
    ({"parse_lines_total": 100},
     {"parse_lines_total": 4_100, "uri_window_lines_total": 1_000}, 0.25),
    # Present, but no windowed batch in the window.
    ({"parse_lines_total": 100, "uri_window_lines_total": 40},
     {"parse_lines_total": 900, "uri_window_lines_total": 40}, 0.0),
])
def test_windowed_lines_over_parsed_lines(before, after, want):
    assert _read(_ctx(before, after)) == pytest.approx(want)


@pytest.mark.parametrize("before, after", [
    ({"parse_lines_total": 100}, {"parse_lines_total": 900}),
    ({"parse_lines_total": 100, "uri_window_lines_total": 40},
     {"parse_lines_total": 100, "uri_window_lines_total": 40}),
])
def test_reads_nothing_without_the_counter_or_lines(before, after):
    assert _read(_ctx(before, after)) is None
