"""Observability subsystem: stage spans and self time, counters, capped
logging, banner (SURVEY §5.1/§5.5 — tracing is new work; counters/cap/
banner mirror the reference's Hadoop counters, RecordReader log cap, and
startup banner)."""
import ast
import logging
import pathlib
import time

import logparser_tpu
from _shared_parsers import shared_parser
from logparser_tpu.observability import (
    CappedLogger,
    CounterRegistry,
    metrics,
    pipeline_stage,
    version_banner,
)

COMBINED_FIELDS = ["IP:connection.client.host", "BYTES:response.body.bytes"]


def _inclusive_s(stage):
    return metrics().histogram("stage_seconds", labels={"stage": stage}).sum


def _self_s(stage):
    return metrics().get("stage_self_seconds_total", labels={"stage": stage})


def _calls(stage):
    return metrics().stage_breakdown().get(stage, {}).get("calls", 0)


def test_stage_self_time_is_inclusive_minus_children():
    names = ("t_self_outer", "t_self_mid", "t_self_leaf")
    inc0 = {n: _inclusive_s(n) for n in names}
    self0 = {n: _self_s(n) for n in names}
    with pipeline_stage("t_self_outer"):
        time.sleep(0.01)
        with pipeline_stage("t_self_mid"):
            time.sleep(0.01)
            for _ in range(2):
                with pipeline_stage("t_self_leaf"):
                    time.sleep(0.005)
    inc = {n: _inclusive_s(n) - inc0[n] for n in names}
    own = {n: _self_s(n) - self0[n] for n in names}
    assert own["t_self_leaf"] == inc["t_self_leaf"]
    assert abs(own["t_self_mid"]
               - (inc["t_self_mid"] - inc["t_self_leaf"])) < 1e-9
    assert abs(own["t_self_outer"]
               - (inc["t_self_outer"] - inc["t_self_mid"])) < 1e-9
    assert own["t_self_outer"] >= 0.01 and own["t_self_mid"] >= 0.01
    # Self times of a tree add up to its root's inclusive time.
    assert abs(sum(own.values()) - inc["t_self_outer"]) < 1e-9


def test_stage_in_generator_nests_under_the_consumers_stage():
    def produce():
        for _ in range(3):
            with pipeline_stage("t_gen_inner"):
                time.sleep(0.002)
            yield 1

    names = ("t_gen_inner", "t_gen_first", "t_gen_second")
    inc0 = {n: _inclusive_s(n) for n in names}
    self0 = {n: _self_s(n) for n in names}
    gen = produce()
    with pipeline_stage("t_gen_first"):
        next(gen)
        next(gen)
    with pipeline_stage("t_gen_second"):
        next(gen)
    inc = {n: _inclusive_s(n) - inc0[n] for n in names}
    own = {n: _self_s(n) - self0[n] for n in names}
    # Each inner span is charged to the span open at next(): two to the
    # first consumer span, one to the second, none left unaccounted.
    assert inc["t_gen_inner"] >= 0.006
    assert own["t_gen_first"] <= inc["t_gen_first"] - 0.004
    assert own["t_gen_second"] <= inc["t_gen_second"] - 0.002
    assert abs(sum(own.values())
               - inc["t_gen_first"] - inc["t_gen_second"]) < 1e-9


def _stage_blocks_with_yield(tree):
    """``with pipeline_stage(...)`` blocks whose own frame yields."""

    def is_stage(item):
        call = item.context_expr
        if not isinstance(call, ast.Call):
            return False
        f = call.func
        name = f.attr if isinstance(f, ast.Attribute) else getattr(f, "id", "")
        return name == "pipeline_stage"

    def yields(node):
        if isinstance(node, (ast.Yield, ast.YieldFrom)):
            return True
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda, ast.ClassDef)):
            return False  # another frame
        return any(yields(c) for c in ast.iter_child_nodes(node))

    bad = []
    for node in ast.walk(tree):
        if isinstance(node, ast.With) and any(is_stage(i) for i in node.items):
            if any(yields(stmt) for stmt in node.body):
                bad.append(node.lineno)
    return bad


def test_no_pipeline_stage_encloses_a_yield():
    root = pathlib.Path(logparser_tpu.__file__).parent
    offenders = []
    for path in sorted(root.rglob("*.py")):
        tree = ast.parse(path.read_text(), filename=str(path))
        offenders += [f"{path.relative_to(root)}:{n}"
                      for n in _stage_blocks_with_yield(tree)]
    assert offenders == []
    # The check itself sees a violation.
    planted = ast.parse(
        "def g():\n    with pipeline_stage('x'):\n        yield 1\n"
    )
    assert _stage_blocks_with_yield(planted) == [2]


def test_parse_batch_traces_pipeline_stages():
    from logparser_tpu.tools.demolog import generate_combined_lines

    stages = ("encode", "device", "fetch", "columns", "oracle_fallback")
    before = metrics().stage_breakdown()
    parser = shared_parser("combined", COMBINED_FIELDS)
    lines = generate_combined_lines(32, seed=23, garbage_fraction=0.1)
    # A PLAUSIBLE-but-device-rejected line (referer ending in a
    # backslash: the `\" "` bytes form an ambiguous non-final
    # separator occurrence the device defers on; the host regex
    # accepts), so it must visit the oracle.  (Pure garbage no
    # longer does — the implausible-for-all-formats filter counts it
    # bad without a per-line re-parse; 20-digit %b stays on device
    # since round 9, escaped-quote USER-AGENTS since round 18.)
    lines[3] = (
        '1.2.3.4 - - [31/Dec/2012:23:49:40 +0100] '
        '"GET /x HTTP/1.1" 200 17 "r\\" "esc quote"'
    )
    parser.parse_batch(lines)
    after = metrics().stage_breakdown()

    def delta(stage, key):
        return after[stage].get(key, 0) - before.get(stage, {}).get(key, 0)

    for stage in stages:
        assert delta(stage, "calls") > 0, stage
    assert delta("encode", "items") == 32
    # The plausible-but-rejected line forced an oracle visit.
    assert delta("oracle_fallback", "items") > 0


def test_fetch_packed_waits_on_each_staged_upload():
    from logparser_tpu.tools.demolog import generate_combined_lines

    parser = shared_parser("combined", COMBINED_FIELDS)
    batches = [generate_combined_lines(16, seed=s) for s in (1, 2, 3)]
    waits0, staged0 = _calls("h2d_wait"), _calls("h2d_stage")
    results = list(parser.parse_batch_stream(iter(batches), stage_h2d=True))
    assert len(results) == 3
    assert _calls("h2d_stage") - staged0 == 3
    assert _calls("h2d_wait") - waits0 == 3
    # Nothing staged, nothing to wait on.
    waits1 = _calls("h2d_wait")
    list(parser.parse_batch_stream(iter(batches), stage_h2d=False))
    assert _calls("h2d_wait") == waits1


def test_reader_feeds_global_counters(tmp_path):
    from logparser_tpu.adapters.inputformat import FileSplit, LogfileInputFormat
    from logparser_tpu.observability import counters
    from logparser_tpu.tools.demolog import write_demolog

    path = str(tmp_path / "access.log")
    write_demolog(path, n=50, seed=31, garbage_fraction=0.1)

    counters().reset()
    fmt = LogfileInputFormat("combined", ["IP:connection.client.host"])
    import os

    reader = fmt.create_record_reader(FileSplit(path, 0, os.path.getsize(path)))
    list(reader)
    agg = counters().as_dict()
    assert agg["Lines read"] == 50
    assert agg["Good lines"] + agg["Bad lines"] == 50
    assert agg["Bad lines"] > 0
    # Per-reader counters agree with the process-wide aggregate.
    assert reader.counters.as_dict() == agg


def test_counter_registry():
    c = CounterRegistry()
    c.increment("Lines read", 100)
    c.increment("Bad lines")
    assert c.get("Lines read") == 100
    assert c.as_dict() == {"Lines read": 100, "Bad lines": 1}
    c.reset()
    assert c.get("Lines read") == 0


def test_capped_logger(caplog):
    logger = logging.getLogger("test_capped")
    capped = CappedLogger(logger, cap=3)
    with caplog.at_level(logging.ERROR, logger="test_capped"):
        for i in range(10):
            capped.error("bad line %d", i)
    # 3 errors + 1 suppression notice; the other 7 only counted.
    assert len(caplog.records) == 4
    assert capped.suppressed == 7


def test_version_banner():
    banner = version_banner()
    assert logparser_tpu.__version__ in banner
    assert "JAX" in banner
