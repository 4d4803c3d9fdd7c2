"""Sharded feeder subsystem (round 8): planner contract, shard-boundary
framing edge cases, golden byte-/parse-parity with single-process
``parse_blob``, worker modes, the service ``feeder_workers`` key, and
(round 10) the zero-copy shared-memory ring transport: slot wraparound,
exhaustion backpressure, arena cleanup, transport selection, and golden
parity ring-vs-pickle.

The planner's contract is the reference InputFormat's split semantics:
a line belongs to the shard where its FIRST byte lies, healed payloads
of consecutive shards tile the corpus exactly, and per-shard framing is
byte-identical to one-shot framing of the whole corpus — on EVERY
transport.
"""
import os

import numpy as np
import pytest

from _shared_parsers import shared_parser
from logparser_tpu.feeder import (
    EncodedBatch,
    FeederError,
    FeederPool,
    RingBatch,
    healed_payload,
    line_start_at_or_after,
    normalize_sources,
    plan_shards,
    resolve_transport,
    ring_available,
    split_batches,
)
from logparser_tpu.native import encode_blob

FIELDS = ["IP:connection.client.host", "STRING:request.status.last",
          "BYTES:response.body.bytes"]


def _demolog(n, seed=5):
    from logparser_tpu.tools.demolog import generate_combined_lines

    return generate_combined_lines(n, seed=seed, garbage_fraction=0.02)


# ---------------------------------------------------------------------------
# shard planner contract
# ---------------------------------------------------------------------------


EDGE_BLOBS = {
    "plain": b"alpha\nbeta\ngamma\ndelta",
    "trailing_newline": b"alpha\nbeta\ngamma\n",
    "crlf": b"aaaa\r\nbbbb\r\ncccc\r\n",
    "empty_lines": b"\n\na\n\nb\n\n",
    "long_line": b"start\n" + b"X" * 300 + b"\nend",
    "single_no_newline": b"just-one-line-no-terminator",
}


@pytest.mark.parametrize("name", sorted(EDGE_BLOBS))
def test_healed_shards_tile_the_blob_exactly(name):
    """Every byte owned exactly once, for EVERY boundary position: the
    sweep drags the shard boundary through every offset, so it crosses
    lines mid-byte, lands exactly on '\\n', between '\\r' and '\\n', and
    leaves whole shards inside one long line (empty payloads)."""
    blob = EDGE_BLOBS[name]
    for shard_bytes in range(1, len(blob) + 2):
        srcs = normalize_sources([blob])
        shards = plan_shards(srcs, shard_bytes)
        payloads = [healed_payload(blob, s.start, s.end) for s in shards]
        assert b"".join(payloads) == blob, shard_bytes
        # Ownership: each payload is whole lines (it never starts
        # mid-line: its first byte is 0 or preceded by '\n').
        off = 0
        for p in payloads:
            if p:
                assert off == 0 or blob[off - 1 : off] == b"\n"
            off += len(p)


def test_line_start_at_or_after_semantics():
    blob = b"ab\ncd\nef"
    assert line_start_at_or_after(blob, 0) == 0
    assert line_start_at_or_after(blob, 1) == 3   # mid-line -> next line
    assert line_start_at_or_after(blob, 2) == 3   # ON the newline
    assert line_start_at_or_after(blob, 3) == 3   # already a line start
    assert line_start_at_or_after(blob, 7) == 8   # inside last line -> end
    assert line_start_at_or_after(blob, 8) == 8
    # A shard fully inside one long line owns nothing.
    long = b"Y" * 50
    assert healed_payload(long, 10, 20) == b""
    # ... and the line's owner reads it whole, past its own end.
    assert healed_payload(long, 0, 5) == long


def test_empty_shard_and_exact_newline_boundary():
    blob = b"aaaa\nbbbb\ncccc"
    # Boundary exactly ON a newline (index 4): the '\n' byte belongs to
    # the first shard's line; the next shard starts at 'bbbb'.
    assert healed_payload(blob, 0, 4) == b"aaaa\n"
    assert healed_payload(blob, 4, 14) == b"bbbb\ncccc"
    # Boundary exactly AFTER a newline (index 5 = a line start): the
    # line starting at the boundary belongs to the later shard.
    assert healed_payload(blob, 0, 5) == b"aaaa\n"
    assert healed_payload(blob, 5, 14) == b"bbbb\ncccc"


def test_file_and_blob_healing_agree(tmp_path):
    blob = EDGE_BLOBS["crlf"] + EDGE_BLOBS["long_line"] + b"\ntail"
    path = tmp_path / "corpus.log"
    path.write_bytes(blob)
    for shard_bytes in (1, 3, 7, 64, 1024):
        fsrcs = normalize_sources([str(path)])
        bsrcs = normalize_sources([blob])
        from logparser_tpu.feeder.shards import read_shard_payload

        fshards = plan_shards(fsrcs, shard_bytes)
        bshards = plan_shards(bsrcs, shard_bytes)
        assert [(s.start, s.end) for s in fshards] == [
            (s.start, s.end) for s in bshards
        ]
        for fs, bs in zip(fshards, bshards):
            assert read_shard_payload(fsrcs[0], fs) == read_shard_payload(
                bsrcs[0], bs
            )


def test_split_batches_line_aligned():
    payload = b"a\nbb\nccc\ndddd\neeeee"
    ranges = split_batches(payload, 2)
    chunks = [payload[a:b] for a, b in ranges]
    assert chunks == [b"a\nbb\n", b"ccc\ndddd\n", b"eeeee"]
    assert split_batches(b"", 4) == []
    # Trailing newline ends the last line, it never starts an empty one.
    tail = b"x\ny\n"
    assert [tail[a:b] for a, b in split_batches(tail, 10)] == [tail]


# ---------------------------------------------------------------------------
# shard-boundary framing edge cases (the parse_blob framing contract)
# ---------------------------------------------------------------------------


def _assert_framing_parity(blob, shard_bytes, batch_lines=3, line_len=64,
                           transport=None, ring_slots=None):
    """Sharded multi-worker framing must be byte-identical to one-shot
    encode_blob (parse_blob's framer) over the same corpus — on every
    transport (the ring variant reruns the boundary sweeps over
    shared-memory slots)."""
    ref_buf, ref_lengths, ref_overflow = encode_blob(blob, line_len=line_len)
    pool = FeederPool([blob], workers=2, shard_bytes=shard_bytes,
                      batch_lines=batch_lines, line_len=line_len,
                      use_processes=False, transport=transport,
                      ring_slots=ring_slots)
    ebs = list(pool.batches())
    assert [e.order_key for e in ebs] == sorted(e.order_key for e in ebs)
    assert b"".join(e.payload for e in ebs) == blob
    if not blob:
        assert ebs == []
        return
    buf = np.concatenate([e.buf for e in ebs])
    lengths = np.concatenate([e.lengths for e in ebs])
    np.testing.assert_array_equal(buf, ref_buf)
    np.testing.assert_array_equal(lengths, ref_lengths)
    # Batch-local overflow indices re-based to corpus rows.
    got_overflow = []
    row = 0
    for e in ebs:
        got_overflow.extend(row + i for i in e.overflow)
        row += e.n_lines
    assert got_overflow == list(ref_overflow)


def test_framing_empty_corpus():
    _assert_framing_parity(b"", shard_bytes=8)


def test_framing_shard_ends_exactly_on_newline():
    blob = b"aaaa\nbbbb\ncccc\ndddd"
    # 5 drags every shard edge onto a '\n'+1 boundary; 4 onto the '\n'.
    _assert_framing_parity(blob, shard_bytes=5)
    _assert_framing_parity(blob, shard_bytes=4)


@pytest.mark.parametrize("transport", [None, "ring"])
def test_framing_line_longer_than_a_shard(transport):
    blob = b"short\n" + b"L" * 200 + b"\nshort2\n" + b"M" * 90
    for shard_bytes in (16, 32, 64):
        # line_len=64 also forces overflow rows (200 > 64): truncation +
        # overflow-index parity across the sharded path (ring variant:
        # the in-place overflow-bit strip in the slot lengths).
        _assert_framing_parity(blob, shard_bytes=shard_bytes,
                               transport=transport, ring_slots=2)


@pytest.mark.parametrize("transport", [None, "ring"])
def test_framing_crlf_at_the_boundary(transport):
    blob = b"aaa\r\nbbb\r\nccc\r\nddd\r"
    for shard_bytes in range(1, len(blob) + 1):
        _assert_framing_parity(blob, shard_bytes=shard_bytes,
                               transport=transport, ring_slots=2)


@pytest.mark.parametrize("transport", [None, "ring"])
def test_framing_empty_lines_and_trailing_newline(transport):
    _assert_framing_parity(b"\n\nx\n\n", shard_bytes=2,
                           transport=transport, ring_slots=2)
    _assert_framing_parity(b"x\ny\n", shard_bytes=3,
                           transport=transport, ring_slots=2)


# ---------------------------------------------------------------------------
# FeederPool: golden parity with single-process parse_blob
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("workers", [1, 3])
@pytest.mark.parametrize("shard_bytes", [30_000, 1 << 20])
def test_feed_parity_with_parse_blob(workers, shard_bytes):
    """Acceptance bar: feeder output byte-identical to single-process
    parse_blob over the same corpus, >= 2 worker counts x >= 2 shard
    sizes — spans, typed columns, validity and counters."""
    import pyarrow as pa

    parser = shared_parser("combined", FIELDS)
    blob = "\n".join(_demolog(512)).encode()
    ref = parser.parse_blob(blob)
    ref_table = ref.to_arrow(include_validity=True, strings="copy")

    pool = FeederPool([blob], workers=workers, shard_bytes=shard_bytes,
                      batch_lines=512, use_processes=False)
    tables = []
    oracle_rows = bad_lines = lines_read = 0
    for result in pool.feed(parser):
        tables.append(result.to_arrow(include_validity=True, strings="copy"))
        oracle_rows += result.oracle_rows
        bad_lines += result.bad_lines
        lines_read += result.lines_read
    table = pa.concat_tables(tables).combine_chunks()
    assert table.equals(ref_table.combine_chunks())
    assert (lines_read, oracle_rows, bad_lines) == (
        ref.lines_read, ref.oracle_rows, ref.bad_lines
    )


def test_parse_encoded_single_batch_equals_parse_blob():
    parser = shared_parser("combined", FIELDS)
    blob = "\n".join(_demolog(64, seed=8)).encode()
    pool = FeederPool([blob], workers=1, shard_bytes=1 << 20,
                      batch_lines=1024, use_processes=False)
    (eb,) = list(pool.batches())
    assert isinstance(eb, EncodedBatch)
    got = parser.parse_encoded(eb)
    ref = parser.parse_blob(blob)
    assert got.to_arrow(strings="copy").equals(ref.to_arrow(strings="copy"))
    assert (got.good_lines, got.bad_lines) == (ref.good_lines, ref.bad_lines)


@pytest.mark.slow
def test_process_mode_parity(tmp_path):
    """The default (multi-process) worker flavor over a file source:
    same byte parity; slow tier — process start costs seconds."""
    blob = b"\n".join(b"line %d payload" % i for i in range(2000))
    path = tmp_path / "corpus.log"
    path.write_bytes(blob)
    ref_buf, ref_lengths, _ = encode_blob(blob, line_len=64)
    pool = FeederPool([str(path)], workers=2, shard_bytes=7_001,
                      batch_lines=256, line_len=64, use_processes=True)
    ebs = list(pool.batches())
    assert pool.stats()["mode"] == "process"
    assert b"".join(e.payload for e in ebs) == blob
    np.testing.assert_array_equal(
        np.concatenate([e.buf for e in ebs]), ref_buf
    )
    np.testing.assert_array_equal(
        np.concatenate([e.lengths for e in ebs]), ref_lengths
    )


def test_multiple_sources_concatenate_in_order(tmp_path):
    a = b"a1\na2\na3"
    b = b"b1\nb2"
    path = tmp_path / "b.log"
    path.write_bytes(b)
    pool = FeederPool([a, str(path)], workers=2, shard_bytes=4,
                      batch_lines=2, line_len=64, use_processes=False)
    ebs = list(pool.batches())
    assert b"".join(e.payload for e in ebs) == a + b
    assert pool.stats()["lines"] == 5


def test_empty_source_yields_no_batches():
    pool = FeederPool([b""], workers=2, use_processes=False)
    assert list(pool.batches()) == []
    assert pool.stats()["batches"] == 0


def test_worker_failure_surfaces_as_feeder_error(tmp_path):
    """Unsupervised pools keep the historical fail-stop contract; a
    SUPERVISED pool retries (bounded), quarantines the shard, and only
    aborts because the data is unreadable in-process too — the one
    fault class recovery cannot route around."""
    from logparser_tpu.feeder import SupervisorPolicy

    path = tmp_path / "gone.log"
    path.write_bytes(b"x\n" * 100)
    pool = FeederPool([str(path)], workers=1, shard_bytes=50,
                      use_processes=False, supervise=False)
    os.unlink(path)  # worker's open() will fail
    with pytest.raises(FeederError, match="worker 0 failed"):
        list(pool.batches())

    path.write_bytes(b"x\n" * 100)
    pool = FeederPool([str(path)], workers=1, shard_bytes=50,
                      use_processes=False,
                      policy=SupervisorPolicy(backoff_base_s=0.001))
    os.unlink(path)
    from logparser_tpu.observability import metrics

    before = metrics().get("feeder_shards_quarantined_total")
    with pytest.raises(FeederError, match="unprocessable"):
        list(pool.batches())
    assert metrics().get("feeder_shards_quarantined_total") == before + 1
    assert pool.stats()["worker_restarts"] >= 1


def test_batches_is_single_use():
    pool = FeederPool([b"x\ny"], workers=1, use_processes=False)
    list(pool.batches())
    with pytest.raises(RuntimeError, match="only run once"):
        list(pool.batches())


def test_parse_batch_stream_accepts_mixed_items():
    """EncodedBatch items and plain line lists interleave in one
    stream — adapters can mix feeder output with ad-hoc batches."""
    parser = shared_parser("combined", FIELDS)
    blob = "\n".join(_demolog(64, seed=8)).encode()
    pool = FeederPool([blob], workers=1, shard_bytes=1 << 20,
                      batch_lines=1024, use_processes=False)
    (eb,) = list(pool.batches())
    lines = _demolog(64, seed=8)
    results = list(parser.parse_batch_stream([eb, lines]))
    assert len(results) == 2
    assert results[0].lines_read == results[1].lines_read == 64
    assert results[0].good_lines == results[1].good_lines


# ---------------------------------------------------------------------------
# service: the optional feeder_workers CONFIG key
# ---------------------------------------------------------------------------


def test_service_feeder_workers_session_parity(monkeypatch):
    """A feeder_workers session returns the SAME single-record-batch
    ARROW frame as a plain session over the same lines."""
    from logparser_tpu import service as service_mod
    from logparser_tpu.service import ParseService, ParseServiceClient

    monkeypatch.setattr(service_mod, "_FEEDER_MIN_LINES", 64)
    lines = _demolog(200, seed=13)
    from logparser_tpu.observability import metrics

    before = metrics().get("service_feeder_requests_total")
    with ParseService() as svc:
        with ParseServiceClient(
            "127.0.0.1", svc.port, "combined", FIELDS
        ) as client:
            ref = client.parse(lines)
        with ParseServiceClient(
            "127.0.0.1", svc.port, "combined", FIELDS,
            feeder_workers=2, stats=True,
        ) as client:
            got = client.parse(lines)
            stats = client.last_stats
    assert got.equals(ref)
    # Protocol shape unchanged: one combined record batch.
    assert len(got.column(0).chunks) == 1
    assert metrics().get("service_feeder_requests_total") == before + 1
    assert stats["request"]["lines"] == 200


def test_service_small_batches_skip_the_feeder():
    """Below the engagement floor the inline path runs (no feeder
    counters move) even when the session asks for feeder_workers."""
    from logparser_tpu.observability import metrics
    from logparser_tpu.service import ParseService, ParseServiceClient

    before = metrics().get("service_feeder_requests_total")
    with ParseService() as svc:
        with ParseServiceClient(
            "127.0.0.1", svc.port, "combined", FIELDS, feeder_workers=2,
        ) as client:
            table = client.parse(_demolog(16, seed=13))
    assert table.num_rows == 16
    assert metrics().get("service_feeder_requests_total") == before


# ---------------------------------------------------------------------------
# ring transport (round 10): slot mechanics, backpressure, cleanup, parity
# ---------------------------------------------------------------------------

pytestmark_ring = pytest.mark.skipif(
    not ring_available(), reason="multiprocessing.shared_memory unavailable"
)


def _ring_pool(blob, **kw):
    kw.setdefault("workers", 2)
    kw.setdefault("shard_bytes", 3000)
    kw.setdefault("batch_lines", 64)
    kw.setdefault("line_len", 64)
    kw.setdefault("use_processes", False)
    kw.setdefault("transport", "ring")
    return FeederPool([blob], **kw)


def _ring_segments():
    from logparser_tpu.feeder import RING_NAME_PREFIX

    if not os.path.isdir("/dev/shm"):
        return None
    return {f for f in os.listdir("/dev/shm")
            if f.startswith(RING_NAME_PREFIX)}


@pytestmark_ring
def test_ring_slot_wraparound_byte_parity():
    """Far more batches than slots: every slot recycles many times and
    the delivered stream is still byte-identical to one-shot framing
    (stale slot contents never bleed into a recycled batch)."""
    blob = b"\n".join(b"row %06d with some filler text" % i
                      for i in range(2000))
    ref_buf, ref_lengths, _ = encode_blob(blob, line_len=64)
    pool = _ring_pool(blob, ring_slots=2, batch_lines=32)
    ebs = list(pool.batches())
    assert pool.stats()["transport"] == "ring"
    assert len(ebs) > 4 * pool.ring_slots * pool.workers  # real wraparound
    assert b"".join(bytes(e.payload) for e in ebs) == blob
    np.testing.assert_array_equal(
        np.concatenate([e.buf for e in ebs]), ref_buf
    )
    np.testing.assert_array_equal(
        np.concatenate([e.lengths for e in ebs]), ref_lengths
    )
    assert pool.stats()["pickle_fallback_batches"] == 0


@pytestmark_ring
def test_ring_exhaustion_blocks_producer_without_dropping():
    """Slot exhaustion IS the backpressure: with every slot leased the
    producer stalls (no drop, no error), and releasing one slot lets
    exactly the stream continue — all batches eventually arrive."""
    import threading

    blob = b"\n".join(b"line %04d" % i for i in range(400))
    pool = _ring_pool(blob, workers=1, shard_bytes=1 << 20, batch_lines=16,
                      ring_slots=2)
    it = pool.batches(detach=False)
    held = [next(it), next(it)]  # every slot in the (1-worker) ring leased
    assert all(isinstance(e, RingBatch) for e in held)

    got = []
    grabbed = threading.Event()

    def grab():
        got.append(next(it))
        grabbed.set()

    t = threading.Thread(target=grab, daemon=True)
    t.start()
    # The producer owns no free slot: the consumer side cannot advance.
    assert not grabbed.wait(0.4)
    held.pop(0).release()  # one slot back -> exactly one batch flows
    assert grabbed.wait(5.0)
    # Both slots are leased again (held[0] + got[0]) — give them back,
    # then drain releasing as we go: nothing was dropped, the whole
    # corpus crossed, in order, through 2 recycling slots.
    held.pop(0).release()
    got[0].release()
    rest = []
    for eb in it:
        rest.append(bytes(eb.payload))
        eb.release()
    from logparser_tpu.observability import metrics

    assert metrics().get("feeder_ring_slot_wait_seconds_total") > 0
    assert pool.stats()["payload_bytes"] == len(blob)
    assert pool.stats()["batches"] == len(rest) + 3


@pytestmark_ring
def test_ring_slot_overflow_goes_to_a_segment_per_batch():
    """A batch that outgrows its slot is framed into a segment of its
    own size — the stream stays complete and byte-identical, no batch
    crosses pickled, and the segment batches are counted."""
    from logparser_tpu.observability import metrics

    big = b"X" * 3000  # one line far beyond the tiny slot below
    blob = b"aaa\nbbb\n" + big + b"\nccc"
    ref_buf, ref_lengths, _ = encode_blob(blob, line_len=4096)
    before = metrics().get("feeder_segment_batches_total")
    pool = _ring_pool(blob, workers=1, shard_bytes=1 << 20, batch_lines=1,
                      line_len=4096, slot_bytes=4096, ring_slots=2)
    ebs = list(pool.batches())
    assert b"".join(bytes(e.payload) for e in ebs) == blob
    np.testing.assert_array_equal(
        np.concatenate([e.buf for e in ebs]), ref_buf
    )
    stats = pool.stats()
    assert stats["pickle_fallback_batches"] == 0
    assert stats["segment_batches"] == len(ebs) == 4
    assert metrics().get("feeder_segment_batches_total") == before + 4


def _own_segments():
    """This process's ring names in /dev/shm (arenas and segments: every
    name starts with the consumer's pid), so pools of other test
    processes running alongside never read as leaks."""
    from logparser_tpu.feeder import RING_NAME_PREFIX

    mine = f"{RING_NAME_PREFIX}_{os.getpid()}_"
    return {f for f in os.listdir("/dev/shm") if f.startswith(mine)}


def _big_frame_blob(n=300):
    """Lines framed at L = 4096: any batch of them, a shard's one-line
    tail included, outgrows the 4 KiB slots the segment tests give
    their rings."""
    return b"\n".join(b"line %05d " % i + b"q" * (i % 97) for i in range(n))


def _assert_frames_match(ebs, blob):
    ref_buf, ref_lengths, _ = encode_blob(blob, line_len=4096)
    assert b"".join(bytes(e.payload) for e in ebs) == blob
    np.testing.assert_array_equal(np.concatenate([e.buf for e in ebs]),
                                  ref_buf)
    np.testing.assert_array_equal(np.concatenate([e.lengths for e in ebs]),
                                  ref_lengths)


@pytestmark_ring
@pytest.mark.parametrize("use_processes", [False, True])
def test_segment_batches_byte_identical_to_encode_blob(use_processes):
    """Frames larger than their slot arrive through frame-sized segments
    as zero-copy RingBatches whose buf, lengths and payload equal
    encode_blob's, with no pickle fallback."""
    from logparser_tpu.observability import metrics

    blob = _big_frame_blob()
    before = metrics().get("feeder_segment_batches_total")
    pool = _ring_pool(blob, shard_bytes=8000, line_len=4096,
                      slot_bytes=4096, use_processes=use_processes)
    ebs = []
    for eb in pool.batches(detach=False):
        assert isinstance(eb, RingBatch) and eb.segment
        ebs.append(eb)
    assert pool.stats()["mode"] == ("process" if use_processes else "thread")
    assert pool.stats()["transport"] == "ring"
    _assert_frames_match(ebs, blob)
    for eb in ebs:
        eb.release()
    stats = pool.stats()
    assert stats["pickle_fallback_batches"] == 0
    assert stats["segment_batches"] == len(ebs) >= 2
    assert metrics().get("feeder_segment_batches_total") == \
        before + len(ebs)


@pytestmark_ring
def test_frame_that_fits_its_slot_takes_the_slot():
    """The bypass case: a frame that fits its slot is framed into the
    arena as before — no segment, none counted."""
    from logparser_tpu.observability import metrics

    blob = b"\n".join(b"short line %d" % i for i in range(500))
    before = metrics().get("feeder_segment_batches_total")
    pool = _ring_pool(blob, ring_slots=3)
    ebs = []
    for eb in pool.batches(detach=False):
        assert isinstance(eb, RingBatch) and eb.segment == ""
        ebs.append(bytes(eb.payload))
        eb.release()
    assert b"".join(ebs) == blob
    assert pool.stats()["segment_batches"] == 0
    assert metrics().get("feeder_segment_batches_total") == before


@pytestmark_ring
@pytest.mark.parametrize("event", ["close", "detach", "worker_crash"])
def test_segments_leave_no_names_behind(event):
    """No ring name survives: pool close; detached segment batches whose
    views outlive the pool (and still read right); a supervised process
    worker hard-killed mid-shard, whose replay keeps byte parity."""
    if not os.path.isdir("/dev/shm"):
        pytest.skip("no /dev/shm to observe")
    from logparser_tpu.feeder import SupervisorPolicy

    blob = _big_frame_blob(600)
    kw = dict(shard_bytes=8000, line_len=4096, slot_bytes=4096)
    before = _own_segments()
    if event == "close":
        pool = _ring_pool(blob, ring_slots=2, **kw)
        it = pool.batches(detach=False)
        next(it)  # one segment leased, the rest framed or in flight
        it.close()
        pool.close()
    elif event == "detach":
        pool = _ring_pool(blob, **kw)
        ebs = list(pool.batches())
        assert pool.stats()["segment_batches"] == len(ebs)
        assert _own_segments() - before == set()
        _assert_frames_match(ebs, blob)  # views outlive pool and names
    else:
        pool = _ring_pool(
            blob, use_processes=True, workers=2,
            chaos="kill_worker:worker=1:after=1:mode=hard",
            policy=SupervisorPolicy(backoff_base_s=0.001,
                                    backoff_max_s=0.01),
            **kw,
        )
        _assert_frames_match(list(pool.batches()), blob)
        assert pool.stats()["worker_restarts"] >= 1
    assert _own_segments() - before == set()


@pytestmark_ring
def test_unsent_segment_of_a_stalled_worker_is_unlinked():
    """A worker that framed a segment and stalls before sending it is
    reaped past the deadline: the reap sweeps the slot's name, the
    abandoned worker's give-back finds it gone, and the replay keeps
    byte parity."""
    if not os.path.isdir("/dev/shm"):
        pytest.skip("no /dev/shm to observe")
    from logparser_tpu.feeder import SupervisorPolicy

    blob = _big_frame_blob(600)
    before = _own_segments()
    pool = _ring_pool(
        blob, shard_bytes=8000, line_len=4096, slot_bytes=4096,
        chaos="delay_put:worker=1:seconds=0.6",
        policy=SupervisorPolicy(worker_deadline_s=0.15,
                                backoff_base_s=0.001, backoff_max_s=0.01),
    )
    _assert_frames_match(list(pool.batches()), blob)
    assert pool.stats()["worker_restarts"] >= 1
    import time

    time.sleep(0.8)  # the abandoned incarnation wakes and gives back
    assert _own_segments() - before == set()


@pytestmark_ring
def test_segments_outstanding_never_exceed_ring_slots():
    """The slot id stays the backpressure token for segments: with
    every token leased the worker stops framing, so at most ring_slots
    segments exist, and the stream completes once tokens come back."""
    import threading

    if not os.path.isdir("/dev/shm"):
        pytest.skip("no /dev/shm to observe")
    blob = _big_frame_blob(600)
    before = _own_segments()
    pool = _ring_pool(blob, workers=1, shard_bytes=1 << 20, line_len=4096,
                      slot_bytes=4096, ring_slots=2)
    it = pool.batches(detach=False)
    held = [next(it), next(it)]
    assert all(e.segment for e in held)
    got = []
    grabbed = threading.Event()

    def grab():
        got.append(next(it))
        grabbed.set()

    t = threading.Thread(target=grab, daemon=True)
    t.start()
    assert not grabbed.wait(0.4)  # no free token: the worker waits
    assert len(_own_segments() - before) == 1 + pool.ring_slots  # + arena
    held.pop(0).release()
    assert grabbed.wait(5.0)
    for eb in held + got:
        eb.release()
    n = 3
    for eb in it:
        assert len(_own_segments() - before) <= 1 + pool.ring_slots
        eb.release()
        n += 1
    assert n == pool.stats()["segment_batches"] == len(split_batches(blob, 64))
    assert _own_segments() - before == set()


@pytestmark_ring
def test_segment_reservation_enospc_takes_the_pipe(monkeypatch):
    """A full tmpfs (ENOSPC from the page reservation) sends that batch
    over the pickled lane, counted as a fallback, with parity kept and
    no name left behind."""
    import errno

    from logparser_tpu.observability import metrics

    def full(fd, offset, length):
        raise OSError(errno.ENOSPC, "No space left on device")

    monkeypatch.setattr(os, "posix_fallocate", full)
    blob = _big_frame_blob()
    before_seg = metrics().get("feeder_segment_batches_total")
    before_fb = metrics().get("feeder_ring_pickle_fallback_total")
    before = _own_segments() if os.path.isdir("/dev/shm") else None
    pool = _ring_pool(blob, shard_bytes=8000, line_len=4096,
                      slot_bytes=4096)
    ebs = list(pool.batches())
    _assert_frames_match(ebs, blob)
    stats = pool.stats()
    assert stats["pickle_fallback_batches"] == len(ebs) >= 2
    assert stats["segment_batches"] == 0
    assert metrics().get("feeder_ring_pickle_fallback_total") == \
        before_fb + len(ebs)
    assert metrics().get("feeder_segment_batches_total") == before_seg
    if before is not None:
        assert _own_segments() - before == set()


@pytestmark_ring
def test_ring_arena_cleanup_on_close():
    """Normal teardown unlinks every arena segment this pool created."""
    before = _ring_segments()
    if before is None:
        pytest.skip("no /dev/shm to observe")
    blob = b"\n".join(b"line %d" % i for i in range(100))
    pool = _ring_pool(blob)
    list(pool.batches())
    after = _ring_segments()
    assert after - before == set()


@pytestmark_ring
def test_ring_abandoned_stream_cleans_up():
    """An abandoned (not fully drained) feed stream still winds the
    fabric down: close() unlinks arenas even with slots leased."""
    before = _ring_segments()
    if before is None:
        pytest.skip("no /dev/shm to observe")
    blob = b"\n".join(b"line %04d" % i for i in range(600))
    pool = _ring_pool(blob, ring_slots=2, batch_lines=16)
    it = pool.batches(detach=False)
    next(it)  # lease one slot, then walk away
    it.close()
    pool.close()
    after = _ring_segments()
    assert after - before == set()


@pytest.mark.slow
@pytestmark_ring
def test_ring_consumer_crash_leaves_no_segments(tmp_path):
    """A consumer process that dies WITHOUT closing the pool must not
    leak /dev/shm segments: the resource tracker (which survives the
    crash) unlinks the arenas the consumer registered at create time."""
    import subprocess
    import sys

    if not os.path.isdir("/dev/shm"):
        pytest.skip("no /dev/shm to observe")
    script = tmp_path / "crash_consumer.py"
    script.write_text(
        "import os\n"
        "from logparser_tpu.feeder import FeederPool, RING_NAME_PREFIX\n"
        "if __name__ == '__main__':\n"  # forkserver re-imports __main__
        "    blob = b'\\n'.join(b'line %d' % i for i in range(2000))\n"
        "    pool = FeederPool([blob], workers=2, shard_bytes=3000,\n"
        "                      batch_lines=32, line_len=64,\n"
        "                      use_processes=True, transport='ring')\n"
        "    it = pool.batches(detach=False)\n"
        "    next(it)\n"
        "    segs = [f for f in os.listdir('/dev/shm')\n"
        "            if f.startswith(RING_NAME_PREFIX)]\n"
        "    assert segs, 'arenas should exist while the pool runs'\n"
        "    print('LIVE', len(segs), flush=True)\n"
        "    os._exit(42)\n"  # no close(), no atexit: a crash
    )
    before = _ring_segments()
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))))
    proc = subprocess.run(
        [sys.executable, str(script)], env=env, capture_output=True,
        text=True, timeout=180,
    )
    assert proc.returncode == 42, proc.stderr
    assert "LIVE" in proc.stdout
    # The tracker reaps asynchronously after the process dies.
    import time

    deadline = time.time() + 30
    while time.time() < deadline:
        leaked = _ring_segments() - before
        if not leaked:
            break
        time.sleep(0.5)
    assert _ring_segments() - before == set(), "leaked shm segments"


@pytestmark_ring
@pytest.mark.parametrize("workers", [1, 3])
@pytest.mark.parametrize("shard_bytes", [30_000, 1 << 20])
def test_ring_feed_parity_with_parse_blob_and_pickle(workers, shard_bytes):
    """Acceptance bar (round 10): feeder output over the RING transport
    is byte-identical to single-process parse_blob AND to the pickled
    transport, >= 2 worker counts x >= 2 shard sizes — spans, typed
    columns, validity, counters, and the retained rescue payload (the
    demolog garbage fraction forces oracle-rescued rows, which read the
    payload in place from the slot)."""
    import pyarrow as pa

    parser = shared_parser("combined", FIELDS)
    blob = "\n".join(_demolog(512)).encode()
    ref = parser.parse_blob(blob)
    ref_table = ref.to_arrow(include_validity=True, strings="copy")

    tallies = {}
    for transport in ("ring", "pickle"):
        pool = FeederPool([blob], workers=workers, shard_bytes=shard_bytes,
                          batch_lines=512, use_processes=False,
                          transport=transport, ring_slots=3)
        tables = []
        oracle_rows = bad_lines = lines_read = 0
        for result in pool.feed(parser):
            tables.append(
                result.to_arrow(include_validity=True, strings="copy")
            )
            oracle_rows += result.oracle_rows
            bad_lines += result.bad_lines
            lines_read += result.lines_read
        table = pa.concat_tables(tables).combine_chunks()
        assert table.equals(ref_table.combine_chunks()), transport
        tallies[transport] = (lines_read, oracle_rows, bad_lines)
    assert tallies["ring"] == tallies["pickle"] == (
        ref.lines_read, ref.oracle_rows, ref.bad_lines
    )


@pytestmark_ring
def test_ring_detach_and_parse_encoded():
    """batches() detaches by default: the yielded batches own their
    arrays (safe to hold all of them) and parse_encoded over a detached
    batch equals parse_blob."""
    parser = shared_parser("combined", FIELDS)
    blob = "\n".join(_demolog(64, seed=8)).encode()
    pool = FeederPool([blob], workers=1, shard_bytes=1 << 20,
                      batch_lines=1024, use_processes=False,
                      transport="ring")
    (eb,) = list(pool.batches())
    assert isinstance(eb, EncodedBatch) and not isinstance(eb, RingBatch)
    got = parser.parse_encoded(eb)
    ref = parser.parse_blob(blob)
    assert got.to_arrow(strings="copy").equals(ref.to_arrow(strings="copy"))


def test_transport_resolution_and_escape_hatch(monkeypatch):
    """LOGPARSER_TPU_FEEDER_PICKLE=1 wins over everything; otherwise
    explicit requests are honored and the defaults are ring (process) /
    inline (thread)."""
    from logparser_tpu.feeder import PICKLE_ENV

    monkeypatch.delenv(PICKLE_ENV, raising=False)
    if ring_available():
        assert resolve_transport(None, "process") == "ring"
    assert resolve_transport(None, "thread") == "inline"
    assert resolve_transport("pickle", "process") == "pickle"
    assert resolve_transport("ring", "thread") == (
        "ring" if ring_available() else "inline"
    )
    with pytest.raises(ValueError):
        resolve_transport("carrier-pigeon", "process")
    monkeypatch.setenv(PICKLE_ENV, "1")
    assert resolve_transport(None, "process") == "pickle"
    assert resolve_transport("ring", "process") == "pickle"
    assert resolve_transport("ring", "thread") == "inline"


def test_pickle_escape_hatch_end_to_end(monkeypatch):
    """The escape hatch selects the old transport and the parity suite's
    bar still holds over it (threads fallback keeps working unchanged)."""
    from logparser_tpu.feeder import PICKLE_ENV

    monkeypatch.setenv(PICKLE_ENV, "1")
    parser = shared_parser("combined", FIELDS)
    blob = "\n".join(_demolog(128, seed=3)).encode()
    pool = FeederPool([blob], workers=2, shard_bytes=4000, batch_lines=64,
                      use_processes=False, transport="ring")
    import pyarrow as pa

    tables = [r.to_arrow(include_validity=True, strings="copy")
              for r in pool.feed(parser)]
    assert pool.stats()["transport"] == "inline"
    table = pa.concat_tables(tables).combine_chunks()
    ref = parser.parse_blob(blob).to_arrow(
        include_validity=True, strings="copy"
    ).combine_chunks()
    assert table.equals(ref)


def test_stream_staged_h2d_parity():
    """The double-buffered H2D edge changes scheduling, never results:
    staged and unstaged streams produce identical tables over the same
    batches, and the staged path accounts its upload bytes."""
    import pyarrow as pa

    from logparser_tpu.observability import metrics

    parser = shared_parser("combined", FIELDS)
    lines = _demolog(256, seed=21)
    batches = [lines[i : i + 64] for i in range(0, len(lines), 64)]
    before = metrics().get("h2d_staged_bytes_total")
    staged = [r.to_arrow(strings="copy")
              for r in parser.parse_batch_stream(batches, stage_h2d=True)]
    assert metrics().get("h2d_staged_bytes_total") > before
    unstaged = [r.to_arrow(strings="copy")
                for r in parser.parse_batch_stream(batches, stage_h2d=False)]
    for a, b in zip(staged, unstaged):
        assert a.equals(b)
