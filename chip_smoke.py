#!/usr/bin/env python
"""Bring-up check: drive logparser_tpu's main path on the TPU through the
entry points a user calls, and hold every result to the host oracle.

    python chip_smoke.py              # one chip (what the driver runs)
    python chip_smoke.py --chips 4    # the two 4-chip layouts, compared

One chip, in this order, from one process:

1. server: ``FrontTier(n_sidecars=1)`` spawns one ``logparser_tpu.service
   --sidecar`` child that owns the chip, while this process stays off JAX.
   ``ParseServiceClient`` requests of 4,096 combined lines come back as
   Arrow tables that must equal the per-line host oracle.
2. batch: 1,048,576 demolog combined lines (16 batches of 65,536, ~1%
   garbage) through ``TpuBatchParser.parse_batch_stream``; every garbage
   line and 2,048 evenly spaced lines per batch must equal the oracle.  A
   ``FeederPool`` (forkserver workers) feeds the first batch again and
   must give the same table.
3. aggregate: ``aggregate_batch`` with ``count_by`` on one batch must
   equal folding that batch's delivered Arrow column on the host.

Four chips: 4 pinned sidecars behind the front tier, then a
``data_parallel=4`` parser and a single-device parser in this process, on
one 65,536-line corpus; all three must give byte-identical Arrow IPC, and
4 distinct chips must have done the work.

Fails (non-zero exit, no result line) without a TPU, when any output
differs, when a device-fault, demotion or compile-cache-error counter
moves, when more lines go to the oracle than the corpus routes there, or
when a helper process (oracle pool, feeder) maps libtpu.  The last line
of a passing run is ``{"ok": true, "device": {...}}``.
"""
import argparse
import collections
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
FORMAT = "combined"
SEED = 20261015
FAULT_COUNTERS = (
    "device_compile_failures_total", "device_faults_total",
    "device_fault_reroutes_total", "device_demotions_total",
    "compile_cache_errors_total",
)
AGG_OPS = [{"op": "count_by", "field": "STRING:request.status.last"}]


class SmokeFailure(Exception):
    pass


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)


def log(msg):
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# the oracle side (pure Python; no JAX)
# ---------------------------------------------------------------------------


def garbage_rows(lines):
    from logparser_tpu.tools.demolog import _GARBAGE

    garbage = set(_GARBAGE)
    return [i for i, ln in enumerate(lines) if ln in garbage]


def sample_rows(lines, per_batch):
    n = len(lines)
    step = max(1, n // per_batch)
    return sorted(set(range(0, n, step)) | set(garbage_rows(lines)))


def check_against_oracle(oracle, fields, table, lines, rows, what):
    """Rows ``rows`` of an Arrow ``table`` (one column per field plus
    ``__valid__``) must equal the per-line host oracle."""
    from logparser_tpu.tpu.batch import _CollectingRecord

    got = table.take(rows).to_pydict()
    for k, i in enumerate(rows):
        try:
            want = oracle.parse(lines[i], _CollectingRecord()).values
        except Exception:  # noqa: BLE001 — the oracle rejects the line
            want = None
        check(got["__valid__"][k] == (want is not None),
              f"{what}: line {i} valid={got['__valid__'][k]} but the "
              f"oracle {'accepts' if want is not None else 'rejects'} it")
        if want is None:
            continue
        for f in fields:
            v, w = got[f][k], want.get(f)
            if isinstance(v, int) and w is not None:
                w = int(w)
            check(v == w, f"{what}: line {i} field {f}: {v!r} != {w!r}")
    return len(rows)


# ---------------------------------------------------------------------------
# the front tier (this process stays off JAX)
# ---------------------------------------------------------------------------


def _labels(block):
    return dict(re.findall(r'(\w+)="([^"]*)"', block))


def scrape_sidecars(front):
    """Per sidecar: its ``device_info`` labels and fault counters."""
    from logparser_tpu.tools.warm_smoke import _family_values, _scrape

    out = []
    for name, host, _port, mport in front.sidecars():
        text = _scrape(f"http://{host}:{mport}/metrics")
        info = [_labels(b) for b in _family_values(text, "device_info")]
        faults = {c: sum(_family_values(text, c).values())
                  for c in FAULT_COUNTERS}
        out.append({"sidecar": name, "devices": info, "faults": faults})
    return out


def run_front(n_sidecars, requests, concurrent=False):
    """``requests``: [(fields, lines)] through one front tier, in order or
    all at once.  Returns the Arrow tables and the sidecars' scraped
    facts, with every child reaped."""
    from concurrent.futures import ThreadPoolExecutor

    from logparser_tpu.front import FrontPolicy, FrontTier
    from logparser_tpu.service import ParseServiceClient

    def send(fields, lines):
        t1 = time.perf_counter()
        client = ParseServiceClient(front.host, front.port, FORMAT, fields,
                                    timeout=900)
        try:
            table = client.parse(lines)
        finally:
            client.close()
        log(f"server: {len(lines)} lines answered in "
            f"{time.perf_counter() - t1:.2f}s")
        return table

    t0 = time.perf_counter()
    front = FrontTier(n_sidecars=n_sidecars,
                      policy=FrontPolicy(ready_timeout_s=300.0))
    handles = []
    try:
        front.start()
        handles = [s.handle for s in front._slots]
        log(f"server: {n_sidecars} sidecar(s) ready in "
            f"{time.perf_counter() - t0:.1f}s, chips "
            f"{[front.sidecar_env(i).get('TPU_VISIBLE_CHIPS', 'none') for i in range(n_sidecars)]}")
        if concurrent:
            with ThreadPoolExecutor(len(requests)) as ex:
                tables = list(ex.map(lambda r: send(*r), requests))
        else:
            tables = [send(*r) for r in requests]
        facts = scrape_sidecars(front)
    finally:
        front.shutdown()
    for h in handles:
        check(h is None or h.wait(30.0), "a sidecar outlived shutdown")
    return tables, facts


def check_sidecar_facts(facts, rehearse):
    for f in facts:
        log(f"server: {f['sidecar']} devices {f['devices']} "
            f"faults {f['faults']}")
        check(not any(f["faults"].values()),
              f"{f['sidecar']}: a device-fault counter moved: {f['faults']}")
        check(f["devices"], f"{f['sidecar']} built no parser")
        if not rehearse:
            check(all(d["platform"] == "tpu" for d in f["devices"]),
                  f"{f['sidecar']} did not run on the TPU: {f['devices']}")


# ---------------------------------------------------------------------------
# in-process legs (JAX from here on)
# ---------------------------------------------------------------------------


def require_tpu(rehearse, count):
    import jax

    devs = jax.devices()
    if not rehearse:
        check(devs[0].platform == "tpu",
              f"JAX found no TPU (platform {devs[0].platform})")
        check(len(devs) == count,
              f"expected {count} TPU chip(s), JAX sees {len(devs)}")
    return devs


def counters():
    from logparser_tpu.observability import metrics

    reg = metrics()
    return {c: reg.total(c) for c in FAULT_COUNTERS}


def maps_libtpu(pid):
    try:
        with open(f"/proc/{pid}/maps") as f:
            return "libtpu" in f.read()
    except OSError:  # exited meanwhile: it holds nothing
        return False


def batch_leg(parser, lines, batch, per_batch, hf):
    batches = [lines[i:i + batch] for i in range(0, len(lines), batch)]
    t0 = time.perf_counter()
    warmed = parser.prewarm(batch_sizes=[batch],
                            max_line_len=max(len(ln) for ln in lines))
    log(f"batch: prewarm {warmed} in {time.perf_counter() - t0:.1f}s")
    oracle = parser.oracle
    checked = oracle_rows = n_garbage = n_lines = 0
    first_table = None
    t0 = time.perf_counter()
    for k, res in enumerate(parser.parse_batch_stream(batches)):
        part = batches[k]
        table = res.to_arrow(strings="copy")
        if k == 0:
            first_table = table
        check(table.num_rows == len(part), f"batch {k}: row count")
        checked += check_against_oracle(
            oracle, hf, table, part, sample_rows(part, per_batch),
            f"batch {k}")
        oracle_rows += res.oracle_rows
        n_garbage += len(garbage_rows(part))
        n_lines += len(part)
    log(f"batch: {n_lines} lines in {len(batches)} batches through "
        f"parse_batch_stream in {time.perf_counter() - t0:.1f}s; "
        f"{checked} rows checked against the oracle; oracle_rows "
        f"{oracle_rows}, garbage lines {n_garbage}")
    check(oracle_rows <= n_garbage,
          f"{oracle_rows} rows went to the oracle; the corpus routes only "
          f"its {n_garbage} garbage lines there")
    oracle_pool_leg(parser, lines[:parser.oracle_parallel_threshold * 2])
    return batches, first_table


def oracle_pool_leg(parser, lines):
    """The corpus routes too few lines to the oracle to start its
    process pool, so drive it directly: its answers must equal the
    inline oracle's, and its workers must not map libtpu."""
    pool = parser._oracle_pool_get()
    if pool is None:
        log("oracle pool: not available on this host (fewer than 2 CPUs)")
        return
    got = parser._run_oracle_many(lines)
    check(got == [parser._run_oracle(ln) for ln in lines],
          "oracle pool answers differ from the inline oracle")
    pids = [p.pid for p in pool._pool]
    check(not any(maps_libtpu(p) for p in pids),
          "an oracle pool worker loaded libtpu")
    log(f"oracle pool: {len(lines)} lines equal the inline oracle; workers "
        f"{pids} hold no libtpu")


def feeder_leg(parser, part, first_table):
    """The first batch again, framed by forkserver feeder workers."""
    import multiprocessing.forkserver as fs

    import pyarrow as pa

    from logparser_tpu.feeder import FeederPool

    blob = ("\n".join(part) + "\n").encode()
    pool = FeederPool([blob], workers=2, shard_bytes=max(1, len(blob) // 4),
                      batch_lines=len(part))
    tables, helper_pids = [], set()
    for res in pool.feed(parser):
        helper_pids |= {p.pid for p in pool._procs if p.is_alive()}
        tables.append(res.to_arrow(strings="copy"))
    stats = pool.stats()
    pool.close()
    fs_pid = getattr(fs._forkserver, "_forkserver_pid", None)
    if fs_pid:
        helper_pids.add(fs_pid)
    check(stats.get("mode") == "process",
          f"feeder ran in {stats.get('mode')} mode, not process mode")
    check(not any(maps_libtpu(p) for p in helper_pids),
          "a feeder process loaded libtpu")
    fed = pa.concat_tables(tables).combine_chunks()
    check(fed.equals(first_table.combine_chunks()),
          "feeder-fed batch differs from parse_batch_stream's")
    log(f"feeder: {fed.num_rows} lines over {len(tables)} batches equal; "
        f"feeder processes {sorted(helper_pids)} hold no libtpu")


def aggregate_leg(parser, part, table):
    t0 = time.perf_counter()
    out = parser.aggregate_batch(part, AGG_OPS)
    got = {k: v for k, v in out.state.summary()[0]["values"]}
    col = table.column(AGG_OPS[0]["field"]).to_pylist()
    valid = table.column("__valid__").to_pylist()
    want = collections.Counter(
        str(v) for v, ok in zip(col, valid) if ok and v is not None)
    check(got == dict(want), f"aggregate {got} != host fold {dict(want)}")
    check(out.device_rows > 0, "the aggregate ran no row on the device")
    log(f"aggregate: count_by status over {len(part)} lines = {got} "
        f"(device_rows {out.device_rows}, d2h_bytes {out.d2h_bytes}, "
        f"{time.perf_counter() - t0:.1f}s incl. compile)")


def report_compiles(devs):
    from logparser_tpu.observability import metrics

    reg = metrics()
    phases = {p: round(reg.get("parser_compile_seconds_total",
                               {"phase": p}), 2)
              for p in ("lower", "compile", "serialize", "deserialize")}
    log(f"compile seconds by phase {phases}; cache hits "
        f"{reg.get('compile_cache_hits_total')}, misses "
        f"{reg.get('compile_cache_misses_total')}")
    stats = devs[0].memory_stats() or {}
    log(f"device memory peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use', 'not reported')}")


def check_parser_health(parser, before):
    after = counters()
    check(after == before, f"fault counters moved: {before} -> {after}")
    state = parser.device_fault_stats()["state"]
    check(state == "closed", f"device fault breaker is {state}")


# ---------------------------------------------------------------------------
# the two paths
# ---------------------------------------------------------------------------


def one_chip(args):
    from logparser_tpu.tools.demolog import (
        HEADLINE_FIELDS,
        generate_combined_lines,
    )

    hf = list(HEADLINE_FIELDS)
    reqs = [generate_combined_lines(args.request_lines, seed=SEED + i,
                                    garbage_fraction=0.01)
            for i in range(3)]
    tables, facts = run_front(1, [(hf, r) for r in reqs])
    check_sidecar_facts(facts, args.rehearse)

    devs = require_tpu(args.rehearse, 1)
    from logparser_tpu.tpu.batch import TpuBatchParser

    before = counters()
    parser = TpuBatchParser(FORMAT, hf)
    for r, t in zip(reqs, tables):
        check_against_oracle(parser.oracle, hf, t, r, list(range(len(r))),
                             "server")
    log(f"server: {sum(map(len, reqs))} lines in {len(reqs)} requests "
        f"equal the oracle; sidecar reaped")
    t0 = time.perf_counter()
    lines = generate_combined_lines(args.batch * args.batches, seed=SEED,
                                    garbage_fraction=0.01)
    log(f"batch: generated {len(lines)} lines "
        f"({sum(map(len, lines)) + len(lines)} bytes) in "
        f"{time.perf_counter() - t0:.1f}s")
    batches, first = batch_leg(parser, lines, args.batch,
                               args.sample_per_batch, hf)
    feeder_leg(parser, batches[0], first)
    aggregate_leg(parser, batches[0], first)
    report_compiles(devs)
    check_parser_health(parser, before)
    parser.close()
    return devs


def spread_keys(fields, n):
    """``n`` orderings of ``fields`` whose parser keys rendezvous onto n
    distinct sidecars (the front routes by key)."""
    from itertools import permutations

    from logparser_tpu.front import preferred_sidecar
    from logparser_tpu.service import _ParserCache

    chosen = {}
    for perm in permutations(fields):
        key = _ParserCache.key_of({"log_format": FORMAT,
                                   "fields": list(perm),
                                   "timestamp_format": None})
        chosen.setdefault(preferred_sidecar(key, n), list(perm))
        if len(chosen) == n:
            return [chosen[i] for i in range(n)]
    raise SmokeFailure(f"no field orderings spread over {n} sidecars")


def four_chips(args):
    from logparser_tpu.tools.demolog import (
        HEADLINE_FIELDS,
        generate_combined_lines,
    )

    hf = list(HEADLINE_FIELDS)
    lines = generate_combined_lines(args.batch, seed=SEED,
                                    garbage_fraction=0.01)
    q = len(lines) // 4
    quarters = [lines[i * q:(i + 1) * q] for i in range(4)]
    orders = spread_keys(hf, 4)
    tables, facts = run_front(4, list(zip(orders, quarters)),
                              concurrent=True)
    check_sidecar_facts(facts, args.rehearse)
    chips = [d["visible_chips"] for f in facts for d in f["devices"]]
    log(f"front: sidecars ran on chips {chips}")
    if not args.rehearse:
        check(sorted(chips) == ["0", "1", "2", "3"],
              f"the 4 sidecars did not work on 4 distinct chips: {chips}")

    devs = require_tpu(args.rehearse, 4)
    from logparser_tpu.tpu.arrow_bridge import table_to_ipc_bytes
    from logparser_tpu.tpu.batch import TpuBatchParser

    before = counters()
    dp = TpuBatchParser(FORMAT, hf, data_parallel=4)
    ids = sorted(d.id for d in dp._mesh.devices.flat) if dp._mesh else []
    log(f"mesh: mesh_devices {dp.mesh_devices}, device ids {ids}")
    check(dp.mesh_devices == 4 and len(set(ids)) == 4,
          f"data_parallel=4 did not span 4 devices: {ids}")
    single = TpuBatchParser(FORMAT, hf)
    r_dp, r_one = dp.parse_batch(lines), single.parse_batch(lines)
    ipc_dp = table_to_ipc_bytes(r_dp.to_arrow(strings="copy"))
    ipc_one = table_to_ipc_bytes(r_one.to_arrow(strings="copy"))
    check(ipc_dp == ipc_one, "data_parallel=4 IPC differs from one device")
    cols = hf + ["__valid__"]
    for k, t in enumerate(tables):
        ipc_front = table_to_ipc_bytes(t.select(cols))
        ipc_ref = table_to_ipc_bytes(
            r_one.slice(k * q, (k + 1) * q).to_arrow(strings="copy"))
        check(ipc_front == ipc_ref,
              f"sidecar quarter {k} IPC differs from one device")
    check_against_oracle(single.oracle, hf, r_one.to_arrow(strings="copy"), lines,
                         sample_rows(lines, args.sample_per_batch),
                         "single device")
    log(f"compare: {len(lines)} lines byte-identical Arrow IPC from 4 "
        f"sidecars, data_parallel=4 and one device ({len(ipc_one)} bytes)")
    report_compiles(devs)
    check_parser_health(dp, before)
    check_parser_health(single, before)
    dp.close()
    single.close()
    return devs


# ---------------------------------------------------------------------------
# process hygiene: nothing this script starts outlives it
# ---------------------------------------------------------------------------


def become_subreaper():
    """Orphaned descendants (a sidecar's own children) reparent to this
    process, so ``reap_all`` can find and stop them."""
    import ctypes

    try:
        ctypes.CDLL(None, use_errno=True).prctl(36, 1, 0, 0, 0)  # PR_SET_CHILD_SUBREAPER
    except (OSError, AttributeError):
        pass


def child_pids():
    me, out = os.getpid(), []
    for d in os.listdir("/proc"):
        if not d.isdigit():
            continue
        try:
            with open(f"/proc/{d}/stat") as f:
                ppid = int(f.read().rsplit(")", 1)[1].split()[1])
        except (OSError, IndexError, ValueError):
            continue
        if ppid == me:
            out.append(int(d))
    return out


def reap_all(grace_s=10.0):
    """Stop multiprocessing's forkserver and resource tracker (they would
    otherwise outlive this process by a moment), then SIGTERM, and after
    ``grace_s`` SIGKILL, every child still here, and reap them all.
    Returns the pids that would not go."""
    import signal

    try:
        import multiprocessing.forkserver as fs
        import multiprocessing.resource_tracker as rt

        fs._forkserver._stop()
        rt._resource_tracker._stop()
    except Exception as e:  # noqa: BLE001 — fall through to the kill below
        log(f"cleanup: stopping multiprocessing helpers failed: {e!r}")
    # A child's own children can reparent here while it dies: go round
    # until none is left.
    for sig in (signal.SIGTERM, signal.SIGKILL, signal.SIGKILL):
        left = child_pids()
        if not left:
            break
        for pid in left:
            try:
                os.kill(pid, sig)
            except ProcessLookupError:
                pass
        deadline = time.monotonic() + grace_s
        while left and time.monotonic() < deadline:
            for pid in list(left):
                try:
                    if os.waitpid(pid, os.WNOHANG)[0]:
                        left.remove(pid)
                except ChildProcessError:
                    left.remove(pid)
            time.sleep(0.05)
    return child_pids()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="run at a tiny size on whatever JAX finds (the "
                         "CPU here); never prints a result line")
    args = ap.parse_args(argv)
    args.request_lines = 4096
    args.batch, args.batches, args.sample_per_batch = 65536, 16, 2048
    if args.rehearse:
        args.request_lines = 256
        args.batch, args.batches, args.sample_per_batch = 1024, 2, 64
    become_subreaper()
    failure = None
    try:
        sys.path.insert(0, ROOT)
        try:
            from logparser_tpu.chips import host_chips
        except ImportError as e:
            raise SmokeFailure(f"logparser_tpu is not next to this script: "
                               f"{e}")
        chips = host_chips()
        log(f"host: TPU chips {chips}")
        if not args.rehearse:
            check(len(chips) >= args.chips,
                  f"need {args.chips} TPU chip(s), this host has "
                  f"{len(chips)}")
        t0 = time.perf_counter()
        devs = four_chips(args) if args.chips == 4 else one_chip(args)
        log(f"all legs passed in {time.perf_counter() - t0:.1f}s")
    except SmokeFailure as e:
        failure = e
    finally:
        left = reap_all()
    if failure is None and left:
        failure = SmokeFailure(f"processes {left} could not be stopped")
    if failure is not None:
        log(f"FAILED: {failure}")
        return 1
    if args.rehearse:
        log(f"rehearsal passed on {devs[0].platform}; no result line")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
