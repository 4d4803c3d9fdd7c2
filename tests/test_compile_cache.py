"""The AOT compile cache + zero-compile warm path (docs/COMPILE.md).

Three contracts, each counter-asserted (never wall-clock):

1. **Refusal, not wrong kernels**: a corrupted, truncated, key-renamed,
   backend-drifted or digest-broken cache entry is REFUSED (miss +
   ``compile_cache_errors_total{kind}`` + warn-once) and the parser
   falls back to a fresh compile with byte-identical output.
2. **Artifact warm path**: an artifact minted after a prewarm embeds the
   serialized executables; a FRESH PROCESS loading it parses its first
   batch with ``parser_compile_total{phase=lower|compile}`` both at 0
   (deserialize only).
3. **Device-native residuals** (round-21 satellites): the
   ``HTTP.PROTOCOL[.VERSION]`` split and the ``TIME.ZONE`` string table
   keep `combined` fully on device — no host plan, no oracle routing,
   values exact.
"""
import json
import logging
import os
import struct
import subprocess
import sys

import pytest

from logparser_tpu.observability import metrics
from logparser_tpu.tpu.compile_cache import (
    _ENTRY_MAGIC,
    CompileCache,
    backend_fingerprint,
    stable_hash,
)

# ---------------------------------------------------------------------------
# stable_hash: the cache key must be stable across processes
# ---------------------------------------------------------------------------

_HASH_SAMPLE = {
    "fields": ("IP:connection.client.host", "BYTES:response.body.bytes"),
    "nested": {"b": [1, 2.5, None], "a": {"x", "y"}},
    "flag": True,
}


def test_stable_hash_dict_order_insensitive():
    a = {"x": 1, "y": {"p": 2, "q": 3}}
    b = {"y": {"q": 3, "p": 2}, "x": 1}
    assert stable_hash(a) == stable_hash(b)
    assert stable_hash(a) != stable_hash({"x": 1, "y": {"p": 2, "q": 4}})


def test_stable_hash_cross_process():
    # PYTHONHASHSEED varies per process: set-iteration order and object
    # hashes differ, so this catches any hash()-dependence in the key.
    code = (
        "from logparser_tpu.tpu.compile_cache import stable_hash\n"
        f"print(stable_hash({_HASH_SAMPLE!r}))\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, check=True,
        env={**os.environ, "PYTHONHASHSEED": "12345"},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.stdout.strip() == stable_hash(_HASH_SAMPLE)


class _Slotted:
    # Mirrors dissectors.timelayout.LocaleData: __slots__, no __dict__.
    # Before the __slots__ branch these hashed by default repr — whose
    # memory address made every instance (and every process) unique,
    # silently defeating the cross-process cache for any parser whose
    # plan graph holds one (TIME fields carry locale tables).
    __slots__ = ("tag", "tables")

    def __init__(self, tag, tables):
        self.tag = tag
        self.tables = tables


def test_stable_hash_slots_is_content_not_identity():
    a = _Slotted("en", {"months": ("Jan", "Feb")})
    b = _Slotted("en", {"months": ("Jan", "Feb")})
    assert repr(a) != repr(b)  # default reprs differ (addresses) ...
    assert stable_hash(a) == stable_hash(b)  # ... the hash must not
    assert stable_hash(a) != stable_hash(_Slotted("fr", {"months": ("Jan", "Feb")}))
    assert stable_hash(a) != stable_hash(_Slotted("en", {"months": ("Jan", "Mar")}))


def test_timezone_parser_fingerprint_cross_process():
    # The end-to-end version of the __slots__ regression: a parser whose
    # field set pulls a DeviceTimeLayout (locale tables) into the plans
    # must fingerprint identically in another interpreter, or every
    # warm boot recompiles TIME-field parsers from scratch.
    from logparser_tpu.tpu import TpuBatchParser

    fields = ["TIME.ZONE:request.receive.time.timezone"]
    parser = TpuBatchParser("combined", fields)
    code = (
        "import os\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "from logparser_tpu.tpu import TpuBatchParser\n"
        f"p = TpuBatchParser('combined', {fields!r})\n"
        "print(p.executor_fingerprint('plain'))\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, check=True,
        env={**os.environ, "PYTHONHASHSEED": "54321",
             "JAX_PLATFORMS": "cpu"},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.stdout.strip() == parser.executor_fingerprint("plain")


# ---------------------------------------------------------------------------
# CompileCache: store semantics + the refusal matrix
# ---------------------------------------------------------------------------


def _errors(kind: str) -> float:
    return metrics().get("compile_cache_errors_total", {"kind": kind})


def test_cache_disabled_is_inert(tmp_path):
    cache = CompileCache(None)
    assert not cache.enabled
    assert cache.get("00" * 20) is None
    assert cache.put("00" * 20, b"payload") is False
    assert list(tmp_path.iterdir()) == []


def test_cache_round_trip(tmp_path):
    cache = CompileCache(str(tmp_path))
    key = "ab" + "cd" * 19
    assert cache.get(key) is None  # empty store: plain miss, no error
    assert cache.put(key, b"\x00\x01payload\xff", meta={"shape": [64, 256]})
    assert cache.get(key) == b"\x00\x01payload\xff"
    # One sharded file, atomic-write temp cleaned up.
    files = [p for p in tmp_path.rglob("*") if p.is_file()]
    assert [p.suffix for p in files] == [".xc"]


def _entry_path(cache: CompileCache, key: str) -> str:
    return cache._path(key)


def test_cache_refuses_bad_magic(tmp_path):
    cache = CompileCache(str(tmp_path))
    key = "11" * 20
    cache.put(key, b"payload")
    path = _entry_path(cache, key)
    blob = open(path, "rb").read()
    before = _errors("magic")
    with open(path, "wb") as f:
        f.write(b"GARBAGE" + blob)
    assert cache.get(key) is None
    assert _errors("magic") == before + 1


def test_cache_refuses_truncated_entry(tmp_path):
    cache = CompileCache(str(tmp_path))
    key = "22" * 20
    cache.put(key, b"payload-bytes")
    path = _entry_path(cache, key)
    before = _errors("corrupt")
    with open(path, "wb") as f:
        # Magic intact, header length field cut mid-word.
        f.write(_ENTRY_MAGIC + struct.pack("<I", 10 ** 6)[:2])
    assert cache.get(key) is None
    assert _errors("corrupt") == before + 1


def test_cache_refuses_payload_digest_mismatch(tmp_path):
    cache = CompileCache(str(tmp_path))
    key = "33" * 20
    cache.put(key, b"payload-bytes")
    path = _entry_path(cache, key)
    blob = bytearray(open(path, "rb").read())
    blob[-1] ^= 0xFF  # flip one payload byte; header digest now disagrees
    with open(path, "wb") as f:
        f.write(bytes(blob))
    before = _errors("digest")
    assert cache.get(key) is None
    assert _errors("digest") == before + 1


def test_cache_refuses_renamed_key(tmp_path):
    # A file copied under another key's name (header key disagrees) must
    # refuse — content addressing is only sound if the name IS the key.
    cache = CompileCache(str(tmp_path))
    src, dst = "44" * 20, "55" * 20
    cache.put(src, b"payload")
    os.makedirs(os.path.dirname(_entry_path(cache, dst)), exist_ok=True)
    with open(_entry_path(cache, src), "rb") as f:
        blob = f.read()
    with open(_entry_path(cache, dst), "wb") as f:
        f.write(blob)
    before = _errors("key_mismatch")
    assert cache.get(dst) is None
    assert _errors("key_mismatch") == before + 1


def test_cache_refuses_backend_drift(tmp_path):
    # Craft an entry whose header names another runtime: same wire format,
    # valid digest, wrong backend — the "copied between hosts" case.
    cache = CompileCache(str(tmp_path))
    key = "66" * 20
    cache.put(key, b"payload")
    path = _entry_path(cache, key)
    blob = open(path, "rb").read()
    off = len(_ENTRY_MAGIC)
    (hlen,) = struct.unpack("<I", blob[off:off + 4])
    header = json.loads(blob[off + 4:off + 4 + hlen])
    payload = blob[off + 4 + hlen:]
    assert header["backend"] == backend_fingerprint()
    header["backend"] = "jax=0.0.0;jaxlib=0.0.0;backend=tpu;kind=v9"
    hdr = json.dumps(header, sort_keys=True).encode()
    with open(path, "wb") as f:
        f.write(_ENTRY_MAGIC + struct.pack("<I", len(hdr)) + hdr + payload)
    before = _errors("backend")
    assert cache.get(key) is None
    assert _errors("backend") == before + 1


def test_cache_refusal_warns_once(tmp_path, caplog):
    cache = CompileCache(str(tmp_path))
    key = "77" * 20
    cache.put(key, b"payload")
    with open(_entry_path(cache, key), "wb") as f:
        f.write(b"not an entry at all")
    with caplog.at_level(logging.WARNING, logger="logparser_tpu.tpu.compile_cache"):
        assert cache.get(key) is None
        assert cache.get(key) is None
        assert cache.get(key) is None
    warned = [r for r in caplog.records if "refused" in r.getMessage()]
    assert len(warned) == 1  # warn-once; repeats only count


def test_cache_write_failure_degrades(tmp_path):
    # An unwritable root costs a warning + counter, never an exception.
    root = tmp_path / "blocked"
    root.write_text("a file where the cache dir should go")
    cache = CompileCache(str(root))
    before = _errors("io")
    assert cache.put("88" * 20, b"payload") is False
    assert _errors("io") == before + 1


# ---------------------------------------------------------------------------
# the warm path: prewarm sources, cross-process artifacts, fallback parity
# ---------------------------------------------------------------------------

FIELDS = [
    "IP:connection.client.host",
    "STRING:request.status.last",
    "BYTES:response.body.bytes",
]


@pytest.fixture()
def drill_lines():
    from logparser_tpu.tools.loadgen import make_lines

    return make_lines("combined", 48, seed=7)


@pytest.fixture()
def no_jax_cache():
    """JAX's persistent cache off: compiles here are real, so they land
    in the executable store (an executable JAX's cache answered is never
    re-serialized — see AotExecutor._from_jax_cache)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


@pytest.fixture()
def cache_env(tmp_path, monkeypatch, no_jax_cache):
    from logparser_tpu.tpu.compile_cache import ENV_CACHE_DIR

    root = str(tmp_path / "cc")
    monkeypatch.setenv(ENV_CACHE_DIR, root)
    return root


@pytest.mark.slow
def test_prewarm_sources_and_disk_reload(cache_env, drill_lines):
    from logparser_tpu.tpu.batch import TpuBatchParser

    reg = metrics()
    parser = TpuBatchParser("combined", FIELDS)
    first = parser.prewarm(batch_sizes=[64], max_line_len=256)
    assert first and set(first.values()) == {"compiled"}
    # Second walk on the same parser: everything already in memory.
    again = parser.prewarm(batch_sizes=[64], max_line_len=256)
    assert set(again.values()) == {"memory"}
    # A fresh parser (same fingerprint) must load from disk, not compile.
    lower0 = reg.get("parser_compile_total", {"phase": "lower"})
    fresh = TpuBatchParser("combined", FIELDS)
    reloaded = fresh.prewarm(batch_sizes=[64], max_line_len=256)
    assert set(reloaded.values()) == {"disk"}
    assert reg.get("parser_compile_total", {"phase": "lower"}) == lower0
    # And the loaded executable parses identically to the compiling one.
    ra, rb = parser.parse_batch(drill_lines), fresh.parse_batch(drill_lines)
    for fid in FIELDS:
        assert ra.to_pylist(fid) == rb.to_pylist(fid), fid


_CHILD_CODE = """
import json, os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from logparser_tpu.observability import metrics
from logparser_tpu.tpu.batch import TpuBatchParser

artifact, lines_json = sys.argv[1], sys.argv[2]
lines = json.loads(open(lines_json).read())
parser = TpuBatchParser.load(artifact)
r = parser.parse_batch(lines)
reg = metrics()
print(json.dumps({
    "lower": reg.get("parser_compile_total", {"phase": "lower"}),
    "compile": reg.get("parser_compile_total", {"phase": "compile"}),
    "deserialize": reg.get("parser_compile_total", {"phase": "deserialize"}),
    "values": {f: r.to_pylist(f) for f in %r},
}))
"""


@pytest.mark.slow
def test_artifact_round_trip_cross_process(tmp_path, drill_lines, monkeypatch,
                                           no_jax_cache):
    """The ship-to-worker contract: a fresh host loading a prewarmed
    artifact executes its first batch with ZERO lower/compile — asserted
    on the child's own counters, and the values must match the parent's."""
    from logparser_tpu.tpu.compile_cache import ENV_CACHE_DIR

    monkeypatch.setenv(ENV_CACHE_DIR, str(tmp_path / "parent-cc"))
    from logparser_tpu.tpu.batch import TpuBatchParser

    parser = TpuBatchParser("combined", FIELDS)
    parser.prewarm(batch_sizes=[64], max_line_len=256)
    expected = parser.parse_batch(drill_lines)
    artifact = str(tmp_path / "combined.lpprog")
    parser.save(artifact)

    lines_json = str(tmp_path / "lines.json")
    with open(lines_json, "w") as f:
        json.dump(list(drill_lines), f)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    # An empty cache dir: the artifact must carry the executables.
    env[ENV_CACHE_DIR] = str(tmp_path / "child-cc")
    out = subprocess.run(
        [sys.executable, "-c", _CHILD_CODE % (FIELDS,),
         artifact, lines_json],
        capture_output=True, text=True, timeout=420, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.splitlines()[-1])
    assert got["lower"] == 0, got
    assert got["compile"] == 0, got
    assert got["deserialize"] >= 1, got
    for fid in FIELDS:
        assert got["values"][fid] == expected.to_pylist(fid), fid


def test_executable_from_jax_cache_is_not_reserialized(
    tmp_path, monkeypatch, drill_lines
):
    """When JAX's persistent cache answers a compile, the executable is
    neither written to the store nor embedded in an artifact: an XLA:CPU
    executable loaded from that cache re-serializes without its function
    library, and every reload then faulted to the host oracle."""
    import jax

    from logparser_tpu.tpu.batch import TpuBatchParser
    from logparser_tpu.tpu.compile_cache import ENV_CACHE_DIR

    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        reg = metrics()
        first = TpuBatchParser("combined", FIELDS)
        first.prewarm(batch_sizes=[64], max_line_len=256)  # fills JAX's
        monkeypatch.setenv(ENV_CACHE_DIR, str(tmp_path / "store"))
        writes0 = reg.get("compile_cache_writes_total")
        again = TpuBatchParser("combined", FIELDS)
        again.prewarm(batch_sizes=[64], max_line_len=256)
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          floor)
    assert reg.get("compile_cache_writes_total") == writes0
    assert again._jitted._from_jax_cache
    assert again._jitted.export_payloads() == {}
    r = TpuBatchParser.from_bytes(again.to_bytes()).parse_batch(drill_lines)
    assert r.oracle_rows == 0


@pytest.mark.parametrize("source", ["artifact", "disk"])
def test_reloaded_executable_runs_on_a_multi_device_host(
    source, cache_env, drill_lines
):
    """A reloaded executable must run on the device it was compiled for.
    Left to JAX, deserialization loads onto every local device, and on
    the conftest's 8-device host the first call was refused and the whole
    batch rerouted to the host oracle (exact, so only counters showed)."""
    import jax

    from logparser_tpu.tpu.batch import TpuBatchParser

    assert len(jax.devices()) > 1
    reg = metrics()
    parser = TpuBatchParser("combined", FIELDS)
    parser.prewarm(batch_sizes=[64], max_line_len=256)
    expected = parser.parse_batch(drill_lines)
    assert expected.oracle_rows == 0
    watched = ("compile_cache_errors_total", "device_faults_total",
               "device_fault_reroutes_total", "device_demotions_total")
    before = {name: reg.total(name) for name in watched}
    deser0 = reg.get("parser_compile_total", {"phase": "deserialize"})
    if source == "artifact":
        loaded = TpuBatchParser.from_bytes(parser.to_bytes())
    else:
        loaded = TpuBatchParser("combined", FIELDS)
    got = loaded.parse_batch(drill_lines)
    assert reg.get("parser_compile_total", {"phase": "deserialize"}) > deser0
    assert got.oracle_rows == 0
    assert got.to_dict() == expected.to_dict()
    assert {name: reg.total(name) for name in watched} == before


@pytest.mark.slow
def test_artifact_fingerprint_drift_refused_with_identical_output(
    tmp_path, drill_lines, monkeypatch, no_jax_cache
):
    from logparser_tpu.tpu.batch import TpuBatchParser
    from logparser_tpu.tpu.compile_cache import ENV_CACHE_DIR
    import pickle

    monkeypatch.setenv(ENV_CACHE_DIR, str(tmp_path / "cc"))
    parser = TpuBatchParser("combined", FIELDS)
    parser.prewarm(batch_sizes=[64], max_line_len=256)
    expected = parser.parse_batch(drill_lines)
    blob = parser.to_bytes()
    assert blob.startswith(TpuBatchParser._ARTIFACT_MAGIC_V2)
    d = pickle.loads(blob[len(TpuBatchParser._ARTIFACT_MAGIC_V2):])
    assert d["execs"], "prewarmed artifact must embed executables"
    for e in d["execs"]:
        e["fingerprint"] = "not-the-real-fingerprint"
    forged = TpuBatchParser._ARTIFACT_MAGIC_V2 + pickle.dumps(d)

    reg = metrics()
    before = reg.get("compile_cache_errors_total", {"kind": "fingerprint"})
    loaded = TpuBatchParser.from_bytes(forged)
    assert reg.get(
        "compile_cache_errors_total", {"kind": "fingerprint"}
    ) > before
    # Every embedded executable was refused; the load still succeeds and
    # the parser recompiles fresh to byte-identical output.
    got = loaded.parse_batch(drill_lines)
    for fid in FIELDS:
        assert got.to_pylist(fid) == expected.to_pylist(fid), fid


@pytest.mark.slow
def test_corrupted_cache_falls_back_byte_identical(cache_env, drill_lines):
    from logparser_tpu.tpu.batch import TpuBatchParser

    seed_parser = TpuBatchParser("combined", FIELDS)
    seed_parser.prewarm(batch_sizes=[64], max_line_len=256)
    reference = seed_parser.parse_batch(drill_lines)
    entries = []
    for dirpath, _, names in os.walk(cache_env):
        entries += [os.path.join(dirpath, n)
                    for n in names if n.endswith(".xc")]
    assert entries, "prewarm must have written cache entries"
    for path in entries:
        blob = bytearray(open(path, "rb").read())
        blob[len(blob) // 2] ^= 0xFF
        with open(path, "wb") as f:
            f.write(bytes(blob))

    reg = metrics()
    errs0 = sum(
        reg.get("compile_cache_errors_total", {"kind": k})
        for k in ("digest", "corrupt", "magic")
    )
    compiles0 = reg.get("parser_compile_total", {"phase": "compile"})
    victim = TpuBatchParser("combined", FIELDS)
    warmed = victim.prewarm(batch_sizes=[64], max_line_len=256)
    assert set(warmed.values()) == {"compiled"}  # refused -> fresh compile
    errs1 = sum(
        reg.get("compile_cache_errors_total", {"kind": k})
        for k in ("digest", "corrupt", "magic")
    )
    assert errs1 > errs0
    assert reg.get("parser_compile_total", {"phase": "compile"}) > compiles0
    got = victim.parse_batch(drill_lines)
    for fid in FIELDS:
        assert got.to_pylist(fid) == reference.to_pylist(fid), fid


# ---------------------------------------------------------------------------
# round-21 device residuals: protocol split + timezone string table
# ---------------------------------------------------------------------------

RESIDUAL_FIELDS = [
    "HTTP.PROTOCOL:request.firstline.protocol",
    "HTTP.PROTOCOL.VERSION:request.firstline.protocol.version",
    "TIME.ZONE:request.receive.time.timezone",
]


@pytest.mark.slow
def test_protocol_and_zone_device_native_on_combined():
    from logparser_tpu.tpu.batch import TpuBatchParser

    parser = TpuBatchParser(
        "combined", RESIDUAL_FIELDS + ["IP:connection.client.host"]
    )
    # Plan-level: none of the residual fields is host-only any more.
    assert parser.host_fields == []
    for fid in RESIDUAL_FIELDS:
        assert parser.plan_by_id[fid].kind != "host", fid

    lines = [
        '1.2.3.4 - - [31/Dec/2012:23:49:40 +0100] '
        '"GET /a HTTP/1.1" 200 512 "-" "t/1.0"',
        '5.6.7.8 - - [01/Jan/2013:00:00:01 -0730] '
        '"POST /b HTTP/1.0" 302 7 "-" "t/1.0"',
        '9.9.9.9 - - [15/Jun/2014:12:30:00 +0000] '
        '"HEAD /c HTTP/2.0" 204 0 "-" "t/1.0"',
    ]
    reg = metrics()
    routed0 = reg.total("oracle_routed_lines_total")
    r = parser.parse_batch(lines)
    routed1 = reg.total("oracle_routed_lines_total")
    assert routed1 == routed0, "combined drill must stay fully on device"
    assert r.to_pylist(RESIDUAL_FIELDS[0]) == ["HTTP", "HTTP", "HTTP"]
    assert r.to_pylist(RESIDUAL_FIELDS[1]) == ["1.1", "1.0", "2.0"]
    # The reference's TIME.ZONE/TIME.TIMEZONE type-mismatch quirk
    # (TestTimeStampDissector.java:258): a requested timezone field is
    # None on every VALID line — what this test pins is that the None is
    # now produced ON DEVICE (zero oracle routing above), not by routing
    # the whole line to the host.
    assert r.to_pylist(RESIDUAL_FIELDS[2]) == [None, None, None]
