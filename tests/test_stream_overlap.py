"""Batches-in-flight overlap validation, without a device.

How much ``parse_batch_stream`` gains over serialized ``parse_batch`` on
a chip depends on the host's link (not measured on a TPU v5e host yet).
This test validates the scheduler itself: a test double subclasses the REAL
parser and injects comparable transfer/compute delays — device compute
becomes an async "ready at" deadline stamped at dispatch time (the JAX
dispatch model: dispatch returns immediately, fetch blocks), host
materialization becomes a sleep.  If the stream loop's interleaving is
right (dispatch k+1 before materializing k), the compute deadline of
batch k+1 expires WHILE batch k materializes and the steady-state cost
per batch is max(compute, materialize) instead of their sum — ~2x when
they are comparable.  A reordering of the drain/enqueue logic collapses
the ratio to ~1x and fails the test.

Reference behavior being productized: the reference reads/parses
records inside engines that overlap IO with compute for free
(e.g. httpdlog-inputformat's RecordReader under MapReduce); here the
overlap is the framework's own responsibility.
"""
import time

import pytest

from logparser_tpu.tpu import TpuBatchParser

FIELDS = [
    "IP:connection.client.host",
    "STRING:request.status.last",
    "BYTES:response.body.bytes",
]


class _DelayedParser(TpuBatchParser):
    """Real parser + injected latencies.

    * device compute: async — ``_dispatch_batch`` stamps a deadline,
      ``_fetch_packed`` waits for it (background progress, like a real
      accelerator queue).
    * materialization: synchronous host work — a plain sleep.
    """

    def __init__(self, *args, compute_s: float, mat_s: float, **kw):
        super().__init__(*args, **kw)
        self._compute_s = compute_s
        self._mat_s = mat_s
        self._deadline = {}

    def _dispatch_batch(self, enc, emit_views=None):
        state = super()._dispatch_batch(enc, emit_views)
        self._deadline[id(state)] = time.monotonic() + self._compute_s
        return state

    def _fetch_packed(self, state):
        deadline = self._deadline.pop(id(state), 0.0)
        now = time.monotonic()
        if now < deadline:
            time.sleep(deadline - now)
        return super()._fetch_packed(state)

    def _materialize_packed(self, fetched):
        time.sleep(self._mat_s)
        return super()._materialize_packed(fetched)


def _lines(n):
    return [
        (
            '10.0.0.%d - - [25/Dec/2021:10:24:%02d +0100] '
            '"GET /i%d HTTP/1.1" 200 %d' % (i % 250 + 1, i % 60, i, 100 + i)
        ).encode()
        for i in range(n)
    ]


@pytest.mark.parametrize("compute_s,mat_s", [(0.05, 0.05)])
def test_stream_overlaps_compute_with_materialization(compute_s, mat_s):
    parser = _DelayedParser(
        "common", FIELDS, compute_s=compute_s, mat_s=mat_s,
    )
    n_batches, per = 10, 64
    batches = [_lines(per) for _ in range(n_batches)]

    # Warm the jit cache outside the timed region (and outside the
    # injected-delay accounting: one batch's delays hit both paths'
    # warmup equally hard, i.e. not at all — it is untimed).
    warm = parser.parse_batch(batches[0])
    assert warm.good_lines == per

    t0 = time.monotonic()
    serial = [parser.parse_batch(b) for b in batches]
    t_serial = time.monotonic() - t0

    t0 = time.monotonic()
    streamed = list(parser.parse_batch_stream(iter(batches), depth=1))
    t_stream = time.monotonic() - t0

    # Same results, same order, exact counters — the stream is not
    # allowed to trade correctness for overlap.
    assert len(streamed) == n_batches
    for rs, rq in zip(serial, streamed):
        assert rq.good_lines == rs.good_lines == per
        assert rq.to_dict() == rs.to_dict()

    # Serialized pays compute+materialize per batch; the stream pays
    # ~max(compute, materialize) in steady state.  With comparable
    # delays the ideal ratio is ~2x; require the VERDICT bar of 1.5x
    # with headroom for scheduler jitter and the real (small) parse
    # work that both paths share.
    ratio = t_serial / t_stream
    assert ratio >= 1.5, (
        f"stream overlap ratio {ratio:.2f} < 1.5 "
        f"(serialized {t_serial:.3f}s vs stream {t_stream:.3f}s)"
    )
