"""The traffic generators: seeded, shaped as their traffic files say,
and timed from the due instant."""
import json
import os
import threading
import time

import numpy as np
import pytest

from gen.shippers import agent_rates, schedule
from gen.weblog import Corpus

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def corpus_params(pool_lines=20000):
    with open(os.path.join(BENCH, "corpora", "weblog.json")) as f:
        p = json.load(f)
    return dict(p, pool_lines=pool_lines)


def arrivals():
    with open(os.path.join(BENCH, "traffic", "shippers.json")) as f:
        return json.load(f)["arrivals"]


@pytest.fixture(scope="module")
def corpus():
    return Corpus(corpus_params(), 2**40 + 17, "apache")


def test_same_seed_same_bytes_other_seed_other_bytes():
    p = corpus_params(3000)
    blobs = []
    for seed in (2**35 + 1, 2**35 + 1, 2**35 + 2):
        c = Corpus(p, seed, "nginx")
        rng = np.random.default_rng(seed)
        idx = c.draw(rng, 5000)
        blobs.append(c.blob(idx, c.clock(p["start_epoch_s"], 5000, 291)))
    assert blobs[0] == blobs[1] and blobs[0] != blobs[2]


def test_same_seed_same_schedule_every_seed_same_work():
    a = arrivals()
    one, other = schedule(a, 2**33 + 5, 10), schedule(a, 2**33 + 6, 10)
    assert one == schedule(a, 2**33 + 5, 10)
    assert one != other
    assert [(t, n) for t, _, n in one] == [(t, n) for t, _, n in other]


def _zipf_slope(counts):
    """Least-squares slope of log(count) against log(rank) over the
    ranks with enough hits to read."""
    c = np.sort(counts)[::-1]
    c = c[c >= 20]
    r = np.arange(1, len(c) + 1)
    return -np.polyfit(np.log(r), np.log(c), 1)[0]


def test_url_and_client_popularity_follow_their_zipf(corpus):
    p = corpus.p
    pools = corpus.pools
    path_of = {path: k for k, path in enumerate(pools.paths)}
    urls = np.zeros(len(pools.paths))
    clients = {}
    for pre, suf in zip(corpus.prefixes, corpus.suffixes):
        req = suf.split('"', 2)[1].split(" ")
        if len(req) == 3 and req[1].split("?")[0] in path_of:
            urls[path_of[req[1].split("?")[0]]] += 1
        host = pre.split(" ", 1)[0]
        clients[host] = clients.get(host, 0) + 1
    assert _zipf_slope(urls) == pytest.approx(p["url_zipf_alpha"], abs=0.12)
    assert _zipf_slope(np.array(list(clients.values()))) == pytest.approx(
        p["client_zipf_alpha"], abs=0.15)


def test_status_mix_query_share_and_length_bounds(corpus):
    p = corpus.p
    n = len(corpus.suffixes)
    status = {}
    with_query = 0
    for suf in corpus.suffixes:
        parts = suf.split('"')
        if len(parts) < 3:
            continue  # a line cut short
        code = parts[2].split()[0]
        status[code] = status.get(code, 0) + 1
        req = parts[1].split(" ")
        with_query += len(req) == 3 and "?" in req[1]
    total = sum(status.values())
    for code, share in p["status_mix"].items():
        want = share / sum(p["status_mix"].values())
        if code != "400":  # TLS junk lines log a 400 too
            assert status.get(code, 0) / total == pytest.approx(
                want, abs=4 * (want / total) ** 0.5 + 0.002)
    assert with_query / n == pytest.approx(p["query"]["share"], abs=0.02)
    assert corpus.lengths.max() <= p["line_limit"]
    for suf in corpus.suffixes:
        parts = suf.split('"')
        if len(parts) >= 3:
            assert len(parts[1]) <= p["request_line_limit"]


@pytest.mark.parametrize("key", ["query", "referer_query", "body_bytes"])
def test_lengths_follow_their_law(key):
    """The lengths the templates carry: the body's median and sigma, and
    a Pareto tail only where the file gives one, as heavy as it says."""
    from gen.weblog import _lengths

    spec = corpus_params()[key]
    lens = _lengths(np.random.default_rng(3), 400000, spec)
    # The body's quantiles, where a tail (all above them) holds a share.
    body = 100 * (1 - spec.get("tail_share", 0.0))
    logs = np.log(np.maximum(lens, 1))
    lo, mid, hi = np.percentile(logs, [0.16 * body, 0.5 * body, 0.84 * body])
    assert np.exp(mid) == pytest.approx(spec["median"], rel=0.05)
    assert (hi - lo) / 2 == pytest.approx(spec["sigma"], rel=0.1)
    # Beyond the body's reach (its 1-in-10**6 point): the tail's share,
    # or nothing where no tail is drawn.
    far = spec["median"] * np.exp(4.75 * spec["sigma"])
    share = np.mean(lens > far)
    if spec.get("tail_share"):
        want = spec["tail_share"] * (far / spec["tail_min"]) ** -spec["tail_alpha"]
        assert share == pytest.approx(want, rel=0.2)
    else:
        assert share < 1e-5
    assert lens.max() <= spec.get("cap", np.inf)


def test_shipper_rates_and_flush_sizes():
    a = arrivals()
    rates = agent_rates(a)
    assert rates.sum() == pytest.approx(a["lines_per_s"])
    assert np.all(rates == rates[0])
    sched = schedule(a, 2**33 + 9, 20)
    sizes = np.array([n for _, _, n in sched])
    assert sizes.min() >= 1 and sizes.max() <= a["max_lines"]
    offered = sizes.sum() / 20
    assert offered == pytest.approx(a["lines_per_s"], rel=0.1)
    assert all(t0 <= t1 for (t0, _, _), (t1, _, _) in zip(sched, sched[1:]))


class _SlowClient:
    """Answers each request after ``delay`` seconds."""

    def __init__(self, delay):
        self.delay = delay

    def parse(self, lines):
        time.sleep(self.delay)

        class T:
            num_rows = len(lines)
        return T()

    def close(self):
        pass


def test_latency_is_taken_from_the_due_instant():
    """Three requests due 10 ms apart on one session whose server takes
    100 ms each: the second and third wait behind the first, and that
    wait is theirs."""
    from loadgen import Agent

    agent = Agent(lambda: _SlowClient(0.1),
                  [(0, 0.00, ["a"]), (1, 0.01, ["b"]), (2, 0.02, ["c"])])
    agent.connect()
    out, keep = {}, {}
    t0 = time.monotonic() + 0.05
    th = threading.Thread(target=agent.run, args=(t0, out, keep))
    th.start()
    th.join(timeout=10)
    assert not th.is_alive()
    lat = [out[k]["latency_s"] for k in range(3)]
    assert lat[0] == pytest.approx(0.10, abs=0.03)
    assert lat[1] == pytest.approx(0.19, abs=0.03)
    assert lat[2] == pytest.approx(0.28, abs=0.03)
    assert out[2]["late_s"] == pytest.approx(0.18, abs=0.03)
