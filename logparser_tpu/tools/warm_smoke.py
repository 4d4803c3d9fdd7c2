"""CI warm-boot smoke: the cold-compile tax is actually gone
(docs/COMPILE.md acceptance drill).

Boots a REAL sidecar process twice against one persistent compile-cache
directory (``JAX_COMPILATION_CACHE_DIR``):

1. **Cold boot** — empty cache: the first request pays lower + compile
   and the background prewarmer walks the bucket ladder (including the
   coalesced-batch shape), landing every rung in the cache.
2. **Warm boot** — same cache, fresh process: asserts the first request
   AND the full prewarm walk compile NOTHING (``parser_compile_total``
   ``{phase=lower}`` == 0 and ``{phase=compile}`` == 0 — deserialize
   only, counter-asserted over /metrics, never wall-clock), the prewarm
   covered every ladder rung including the coalesced shape with zero
   ``source="compiled"`` entries, the ARROW payload is byte-identical
   to the cold boot's, and the exposition validates
   (`metrics_smoke.validate_exposition`).

Usage::

    make warm-smoke
    python -m logparser_tpu.tools.warm_smoke
"""
from __future__ import annotations

import os
import re
import socket
import struct
import shutil
import sys
import time
import urllib.request
from typing import Any, Dict, List, Optional, Sequence

DRILL_FORMAT = "combined"
DRILL_FIELDS = [
    "IP:connection.client.host",
    "STRING:request.status.last",
    "BYTES:response.body.bytes",
]
DRILL_LINES = 64

# The exposition name prefix (observability.render_prometheus).
_PREFIX = "logparser_tpu_"


def _scrape(url: str) -> str:
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.read().decode("utf-8")


def _family_values(text: str, family: str) -> Dict[str, float]:
    """``{label-block-or-'': value}`` for one exposition family."""
    pat = re.compile(
        r"^" + re.escape(_PREFIX + family) + r"(\{[^}]*\})? (\S+)$", re.M)
    return {m.group(1) or "": float(m.group(2))
            for m in pat.finditer(text)}


def _labeled(values: Dict[str, float], **labels: str) -> float:
    want = {f'{k}="{v}"' for k, v in labels.items()}
    total = 0.0
    for block, v in values.items():
        parts = set(p for p in block.strip("{}").split(",") if p)
        if want <= parts:
            total += v
    return total


def _request_arrow(host: str, port: int, config: bytes,
                   lines: Sequence[str], timeout_s: float) -> bytes:
    """One CONFIG + LINES round over a raw socket; returns the ARROW
    payload bytes (raises on an error frame / reset)."""
    payload = struct.pack(">I", len(lines)) + "\n".join(lines).encode()
    sock = socket.create_connection((host, port), timeout=timeout_s)
    try:
        sock.settimeout(timeout_s)
        sock.sendall(struct.pack(">I", len(config)) + config)
        sock.sendall(struct.pack(">I", len(payload)) + payload)

        def recv_exact(n: int) -> bytes:
            buf = bytearray()
            while len(buf) < n:
                chunk = sock.recv(n - len(buf))
                if not chunk:
                    raise ConnectionError("sidecar reset mid-response")
                buf.extend(chunk)
            return bytes(buf)

        (n,) = struct.unpack(">I", recv_exact(4))
        if n == 0xFFFFFFFF:
            (m,) = struct.unpack(">I", recv_exact(4))
            raise RuntimeError(
                f"error frame: {recv_exact(m).decode('utf-8', 'replace')}")
        body = recv_exact(n)
        sock.sendall(struct.pack(">I", 0))
        return body
    finally:
        sock.close()


def boot_probe(cache_dir: str, *, lines: Sequence[str],
               log_format: str = DRILL_FORMAT,
               fields: Sequence[str] = tuple(DRILL_FIELDS),
               prewarm_buckets: Optional[str] = None,
               prewarm_line_len: Optional[int] = None,
               request_timeout_s: float = 300.0,
               prewarm_timeout_s: float = 300.0) -> Dict[str, Any]:
    """Boot one real sidecar against ``cache_dir``, time its first
    request, wait for the background prewarm walk to finish, scrape the
    compile/prewarm counters, and shut it down.

    Returns ``ready_s`` (spawn -> SIDECAR_READY), ``first_request_s``
    (CONFIG+LINES -> ARROW wall, parser build included), ``arrow`` (the
    payload bytes, for cross-boot parity), ``prewarm_done``, the counter
    dict, and the raw exposition text.
    """
    import json as _json

    from logparser_tpu.front import ProcessSidecar

    from ..tpu.compile_cache import ENV_CACHE_DIR

    env = {ENV_CACHE_DIR: cache_dir}
    if prewarm_buckets is not None:
        env["LOGPARSER_TPU_PREWARM_BUCKETS"] = prewarm_buckets
    if prewarm_line_len is not None:
        env["LOGPARSER_TPU_PREWARM_LINE_LEN"] = str(prewarm_line_len)
    t0 = time.perf_counter()
    handle = ProcessSidecar(0, extra_args=["--max-sessions", "8"], env=env)
    ready_s = time.perf_counter() - t0
    try:
        config = _json.dumps({
            "log_format": log_format, "fields": list(fields),
            "timestamp_format": None,
        }).encode()
        t0 = time.perf_counter()
        arrow = _request_arrow(handle.host, handle.port, config, lines,
                               request_timeout_s)
        first_request_s = time.perf_counter() - t0
        # The prewarm walk runs off the request path; wait for its
        # completion tick so the scraped counters cover the WHOLE ladder
        # (and so a later boot against this cache finds every rung).
        url = f"http://{handle.host}:{handle.metrics_port}/metrics"
        deadline = time.monotonic() + prewarm_timeout_s
        text = ""
        prewarm_done = False
        while time.monotonic() < deadline:
            text = _scrape(url)
            runs = _family_values(text, "parser_prewarm_runs_total")
            errs = _family_values(text, "parser_prewarm_errors_total")
            if sum(runs.values()) + sum(errs.values()) >= 1:
                prewarm_done = sum(runs.values()) >= 1
                break
            time.sleep(0.25)
        compile_totals = _family_values(text, "parser_compile_total")
        shapes = _family_values(text, "parser_prewarm_shapes_total")
        counters = {
            "lower": _labeled(compile_totals, phase="lower"),
            "compile": _labeled(compile_totals, phase="compile"),
            "deserialize": _labeled(compile_totals, phase="deserialize"),
            "cache_hits": sum(_family_values(
                text, "compile_cache_hits_total").values()),
            "cache_misses": sum(_family_values(
                text, "compile_cache_misses_total").values()),
            "cache_errors": sum(_family_values(
                text, "compile_cache_errors_total").values()),
            "load_traces": sum(_family_values(
                text, "compile_cache_load_traces_total").values()),
            "prewarm_shapes": sum(shapes.values()),
            "prewarm_compiled": _labeled(shapes, source="compiled"),
            "prewarm_errors": sum(_family_values(
                text, "parser_prewarm_errors_total").values()),
        }
        return {
            "ready_s": round(ready_s, 3),
            "first_request_s": round(first_request_s, 3),
            "arrow": arrow,
            "prewarm_done": prewarm_done,
            "counters": counters,
            "exposition": text,
        }
    finally:
        handle.terminate()


def main() -> int:
    # A boot-latency smoke, not a perf run: never acquire a TPU, and
    # every spawned sidecar inherits the same platform.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from logparser_tpu.tools.loadgen import make_lines
    from logparser_tpu.tools.metrics_smoke import validate_exposition

    problems: List[str] = []
    lines = make_lines(DRILL_FORMAT, DRILL_LINES, seed=7)
    t_all = time.monotonic()
    # A fixed, emptied directory under the cache root: the cold boot
    # must really be cold.
    from logparser_tpu.tpu.compile_cache import cache_root

    cache = os.path.join(cache_root(), "warm-smoke")
    shutil.rmtree(cache, ignore_errors=True)
    cold = boot_probe(cache, lines=lines)
    print(f"warm-smoke: cold boot ready {cold['ready_s']:.1f}s, "
          f"first request {cold['first_request_s']:.1f}s, "
          f"counters {cold['counters']}")
    if cold["counters"]["compile"] < 1:
        problems.append(
            "cold boot compiled nothing — the cache was not empty "
            "or the AOT path is not engaged")
    if not cold["prewarm_done"]:
        problems.append(
            "cold boot: background prewarm never completed "
            f"(errors={cold['counters']['prewarm_errors']})")

    warm = boot_probe(cache, lines=lines)
    print(f"warm-smoke: warm boot ready {warm['ready_s']:.1f}s, "
          f"first request {warm['first_request_s']:.1f}s, "
          f"counters {warm['counters']}")
    c = warm["counters"]
    # THE gate: a warm boot compiles nothing — counter-asserted,
    # deserialize is the only phase allowed to move.
    if c["lower"] or c["compile"]:
        problems.append(
            f"warm boot compiled: lower={c['lower']:.0f} "
            f"compile={c['compile']:.0f} (must both be 0)")
    if c["deserialize"] < 1:
        problems.append("warm boot deserialized nothing — the "
                        "first request did not come from the cache")
    if c["load_traces"]:
        problems.append(
            f"warm boot traced the program {c['load_traces']:.0f} times "
            "while loading executables (must be 0)")
    if not warm["prewarm_done"]:
        problems.append(
            "warm boot: background prewarm never completed "
            f"(errors={c['prewarm_errors']})")
    # Ladder coverage incl. the coalesced-batch shape: the default
    # ladder (DEFAULT_BUCKET_LADDER) + the coalesce_max_lines bucket
    # — all served from cache/memory, none compiled.
    from logparser_tpu.service import ServiceLimits
    from logparser_tpu.tpu.compile_cache import DEFAULT_BUCKET_LADDER
    expect = len(set(DEFAULT_BUCKET_LADDER)
                 | {ServiceLimits().coalesce_max_lines})
    if c["prewarm_shapes"] < expect:
        problems.append(
            f"warm boot prewarm covered {c['prewarm_shapes']:.0f} "
            f"shapes < {expect} (coalesced shape missing?)")
    if c["prewarm_compiled"]:
        problems.append(
            f"warm boot prewarm COMPILED "
            f"{c['prewarm_compiled']:.0f} shapes (must load them)")
    if warm["arrow"] != cold["arrow"]:
        problems.append("ARROW payload differs between cold and "
                        "warm boot (cache served a wrong kernel?)")
    expo_problems = validate_exposition(warm["exposition"])
    problems += [f"exposition: {p}" for p in expo_problems]

    wall = time.monotonic() - t_all
    if problems:
        print(f"warm-smoke: FAIL ({wall:.0f}s)")
        for p in problems:
            print(f"  - {p}")
        return 1
    print(f"warm-smoke: PASS ({wall:.0f}s) — warm boot compiled "
          "nothing, prewarm covered the coalesced shape, payloads "
          "byte-identical")
    return 0


if __name__ == "__main__":
    sys.exit(main())
