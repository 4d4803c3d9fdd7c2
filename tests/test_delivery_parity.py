"""Delivery-path parity suite (round 6).

Locks the demand-driven view emission + threaded Arrow assembly work
against silent drift:

1. ``to_arrow(strings="view")`` and the materialized-strings copy path
   must be column-for-column equal on every ``tests/_configs.py``
   config's corpus, with view emission FULL (every span field),
   DEMAND-PRUNED (a subset of span fields carried by device view rows,
   the rest host-built), and DISABLED (``emit_views=False`` — all views
   host-built).
2. ``parse_blob`` and ``parse_batch`` over the same payload must produce
   byte-identical Arrow IPC through ``parse_to_ipc``, with the assembly
   pool at 1 worker and >1 workers — delivery output must never depend
   on thread count.

The two heavy/fixture-dependent configs (geoip_chain needs the generated
MaxMind test databases; combinedio/zonetext/multiformat are extra
compiles) ride in the slow tier; combined + nginx_uri cover the fast
tier.
"""
import pytest

from logparser_tpu.tools.demolog import HEADLINE_FIELDS, generate_combined_lines
from logparser_tpu.tpu.batch import TpuBatchParser
from logparser_tpu.tpu.arrow_bridge import parse_to_ipc
from logparser_tpu.tpu.hostpool import AssemblyPool

from _configs import build_configs
from _shared_parsers import shared_parser

N_LINES = 384


FAST_CONFIGS = ("combined", "nginx_uri")


_EXTRA_CACHE = {}


def _config_case(name):
    entry = next((c for c in build_configs() if c[0] == name), None)
    if entry is None:
        pytest.skip(f"config {name} unavailable on this host")
    _, fmt, fields, lines_fn, extra = entry
    if extra:
        # extra_dissectors are unhashable: session-cache by config name.
        parser = _EXTRA_CACHE.get(name)
        if parser is None:
            parser = _EXTRA_CACHE[name] = TpuBatchParser(
                fmt, fields, extra_dissectors=extra
            )
    else:
        parser = shared_parser(fmt, fields)
    return parser, lines_fn(N_LINES), fmt, fields, extra


def _assert_view_matches_copy(res):
    tv = res.to_arrow()
    tc = res.to_arrow(strings="copy")
    assert tv.column_names == tc.column_names
    for name in tc.column_names:
        a = tv.column(name).to_pylist()
        b = tc.column(name).to_pylist()
        assert a == b, (name, [(x, y) for x, y in zip(a, b) if x != y][:3])


def _exercise_config(name):
    parser, lines, fmt, fields, extra = _config_case(name)
    # (a) full view emission — the parse_batch product default.
    res_full = parser.parse_batch(lines)
    _assert_view_matches_copy(res_full)
    full_table = res_full.to_arrow()

    # (b) view emission disabled: every view column host-built.
    res_off = parser.parse_batch(lines, emit_views=False)
    assert not res_off.device_views
    _assert_view_matches_copy(res_off)
    assert res_off.to_arrow().to_pylist() == full_table.to_pylist()

    # (c) demand-pruned: a fresh parser carrying device view rows for
    # only ONE span field; the other span columns host-build their
    # views.  Output must be identical to the full-emission table.
    span_fids = [
        fid for fid in parser.requested
        if not fid.endswith(".*")
        and parser._plan_group(parser.plan_by_id[fid]) == "span"
    ]
    if span_fids:
        pruned = _PRUNED_CACHE.get(name)
        if pruned is None:
            pruned = _PRUNED_CACHE[name] = TpuBatchParser(
                fmt, fields, view_fields=span_fids[:1],
                extra_dissectors=extra,
            )
        res_pruned = pruned.parse_batch(lines)
        assert set(res_pruned.device_views) <= set(span_fids[:1])
        _assert_view_matches_copy(res_pruned)
        assert res_pruned.to_arrow().to_pylist() == full_table.to_pylist()


_PRUNED_CACHE = {}


@pytest.mark.parametrize("name", FAST_CONFIGS)
def test_view_parity_fast_configs(name):
    _exercise_config(name)


@pytest.mark.slow
@pytest.mark.parametrize("name", [
    "combinedio_strftime", "strftime_zonetext", "multiformat_mixed",
    "geoip_chain",
])
def test_view_parity_slow_configs(name):
    _exercise_config(name)


# ---------------------------------------------------------------------------
# parse_blob vs parse_batch vs pool width: byte-identical IPC
# ---------------------------------------------------------------------------


def _rescue_corpus(n):
    """A corpus that exercises oracle overrides (>18-digit %b counters)
    and garbage lines alongside the clean fast path."""
    lines = generate_combined_lines(n, seed=31, garbage_fraction=0.03)
    lines[5] = ('9.9.9.9 - frank [10/Oct/2023:13:55:36 -0700] '
                '"GET /ov?a=%zz HTTP/1.0" 200 123456789012345678901 "-" "z"')
    return lines


def test_ipc_blob_batch_and_pool_width_identical(monkeypatch):
    # Drop the engage threshold so the POOLED per-column path really
    # runs on this small corpus (by default only >=32k-row batches pool).
    monkeypatch.setattr(
        "logparser_tpu.tpu.hostpool.MIN_POOLED_ROWS", 1
    )
    lines = _rescue_corpus(256)
    blob = "\n".join(lines).encode()
    payloads = {}
    for workers in (1, 4):
        parser = TpuBatchParser(
            "combined", HEADLINE_FIELDS, assembly_workers=workers
        )
        assert parser.assembly_pool().workers == workers
        ipc_batch = parse_to_ipc(parser, lines)
        ipc_blob = parse_to_ipc(parser, blob)
        assert ipc_batch == ipc_blob, (
            f"blob vs batch IPC diverged at {workers} workers"
        )
        payloads[workers] = ipc_batch
    assert payloads[1] == payloads[4], "IPC depends on assembly pool width"


def test_view_table_pool_width_identical(monkeypatch):
    """The string_view table (the non-IPC delivery surface) must also be
    value-identical across pool widths, including fix/amp/override
    rows."""
    monkeypatch.setattr(
        "logparser_tpu.tpu.hostpool.MIN_POOLED_ROWS", 1
    )
    parser = shared_parser("combined", HEADLINE_FIELDS)
    res = parser.parse_batch(_rescue_corpus(192))
    res.assembly_pool = AssemblyPool(4)  # >= VIEW_POOL_MIN_WORKERS
    wide = res.to_arrow()
    res.assembly_pool = AssemblyPool(1)
    res.__dict__.pop("_view_pre", None)
    narrow = res.to_arrow()
    assert wide.to_pylist() == narrow.to_pylist()


def test_demand_knob_drops_view_rows_from_packed_output():
    """emit_views=False must shrink the packed device output (the D2H
    payload) by exactly 4 int32 rows per demanded span field."""
    import jax
    import numpy as np

    parser = shared_parser("combined", HEADLINE_FIELDS)
    views_fn = parser.device_views_fn()
    plain_fn = parser.device_fn()
    buf = np.zeros((64, 128), dtype=np.uint8)
    lengths = np.zeros(64, dtype=np.int32)
    kv = jax.eval_shape(views_fn, buf, lengths).shape[0]
    kp = jax.eval_shape(plain_fn, buf, lengths).shape[0]
    n_span = len(parser._views_fields)
    assert n_span > 0
    assert kv == kp + 4 * n_span
