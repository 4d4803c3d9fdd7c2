"""AOT parser executables + a persistent cross-process compile cache.

Every tier of the system pays XLA compile latency at the worst possible
moment: a sidecar's first request on a fresh shape bucket, a front-tier
respawn, a pod host's first batch.  This module makes the compiled parser
executable a durable, shareable artifact instead of a per-process side
effect:

- :class:`AotExecutor` wraps the ``jax.jit`` executor built by
  ``pipeline.build_units_jnp_fn`` with an EXPLICIT per-shape
  lower -> compile path (``jit.lower(ShapeDtypeStruct...).compile()``),
  so compile cost is attributable (``parser_compile_seconds_total{phase}``)
  and the compiled object is serializable
  (``jax.experimental.serialize_executable``).
- :class:`CompileCache` is the content-addressed on-disk store under
  :func:`cache_root` (``JAX_COMPILATION_CACHE_DIR``, else
  ``<checkout>/.jax_cache``), beside JAX's own persistent cache.  Keys
  hash the parser program fingerprint, the (B, L) shape bucket, and the
  backend/jax version —
  a mismatch on ANY component is a miss and a fresh compile, never a
  wrong kernel.  The host oracle stays the exactness referee regardless:
  a cache bug can cost a compile, not a byte of output.
- Artifacts (``TpuBatchParser.to_bytes`` v2) embed serialized executables
  so a fresh host loading an artifact executes its first batch without
  lowering anything (phase=deserialize only).

The pytree structure of the executor's calling convention is FIXED
((buf [B, L] uint8, lengths [B] int32) -> packed [K, B] int32 array), so
cache entries carry only the serialized payload; the in/out treedefs are
rebuilt from that convention at load time (pickling PyTreeDefs is not
portable across processes), without tracing the program.  A loaded
executable whose own signature does not fit the convention is refused.
"""
from __future__ import annotations

import hashlib
import io
import json
import logging
import os
import struct
import threading
import time
from dataclasses import is_dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

logger = logging.getLogger(__name__)

# The ONE compile-cache setting: JAX's own persistent cache reads it at
# import, and the executable store lives in a subdirectory of it.  Unset,
# both go to a fixed path inside the checkout (a directory that moves
# between runs never hits).
ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)
_STORE_SUBDIR = "logparser-executables"

# Entry format version: bump when the on-disk layout changes.  Old entries
# then simply miss (refused by magic), they are never misread.
_ENTRY_MAGIC = b"LPTPU-EXEC-v1\n"

# Default shape-bucket ladder for prewarm/artifact embedding: the batch
# buckets serving traffic actually hits (service chunks, feeder chunks,
# coalesced batches all pad to powers of two >= 64).
DEFAULT_BUCKET_LADDER = (64, 256, 1024)


def cache_root() -> str:
    """The compile-cache directory: ``JAX_COMPILATION_CACHE_DIR`` when set
    (re-read per call, so CLIs and tests can repoint it), else the fixed
    in-checkout default."""
    return os.environ.get(ENV_CACHE_DIR) or DEFAULT_CACHE_DIR


def set_cache_root(path: str) -> None:
    """Place every compile cache of this process and its children under
    ``path`` (the ``--compile-cache`` flags).  Exported, so spawned
    children inherit it; JAX itself is only touched when this process
    already imported it (a front tier never does)."""
    import sys

    os.environ[ENV_CACHE_DIR] = path
    if "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_compilation_cache_dir", path)


def configure_jax_cache() -> None:
    """Point JAX's persistent compilation cache at :func:`cache_root` when
    nothing has placed it yet.  With ``JAX_COMPILATION_CACHE_DIR`` set at
    import JAX already did, and this sets nothing."""
    import jax

    if jax.config.jax_compilation_cache_dir is None:
        jax.config.update("jax_compilation_cache_dir", cache_root())


# ---------------------------------------------------------------------------
# fingerprints
# ---------------------------------------------------------------------------

_code_fp: Optional[str] = None
_code_fp_lock = threading.Lock()


def code_fingerprint() -> str:
    """Content hash of the device-pipeline sources.  Any edit to the code
    that shapes the compiled computation invalidates every cache key —
    coarse, but it can never reuse a stale kernel."""
    global _code_fp
    if _code_fp is None:
        with _code_fp_lock:
            if _code_fp is None:
                h = hashlib.blake2b(digest_size=12)
                root = os.path.dirname(os.path.abspath(__file__))
                for name in sorted(os.listdir(root)):
                    if not name.endswith(".py"):
                        continue
                    with open(os.path.join(root, name), "rb") as f:
                        h.update(name.encode())
                        h.update(f.read())
                _code_fp = h.hexdigest()
    return _code_fp


def backend_fingerprint() -> str:
    """jax/jaxlib version + backend platform + device kind: a serialized
    executable is only loadable into the exact runtime that produced it."""
    import jax
    import jaxlib

    try:
        devs = jax.devices()
        kind = devs[0].device_kind if devs else "none"
        platform = devs[0].platform if devs else jax.default_backend()
    except Exception:  # uninitialized backend: still a stable string
        kind, platform = "none", "unknown"
    return (f"jax={jax.__version__};jaxlib={jaxlib.__version__};"
            f"backend={platform};kind={kind}")


def _slot_names(x: Any) -> tuple:
    """All ``__slots__`` names across the MRO (``__slots__`` may be a
    bare string), minus the pseudo-slots."""
    names = []
    for klass in type(x).__mro__:
        slots = klass.__dict__.get("__slots__", ())
        if isinstance(slots, str):
            slots = (slots,)
        names.extend(s for s in slots if s not in ("__dict__", "__weakref__"))
    return tuple(names)


def stable_hash(obj: Any, digest_size: int = 16) -> str:
    """Deterministic cross-process content hash of a (mostly) pure-data
    object graph: primitives, containers, numpy arrays, dataclasses and
    plain ``__dict__``/``__slots__`` objects.  Sets are sorted by repr;
    anything opaque hashes by type name + repr — possibly
    process-unstable, which can only cost a cache miss, never a wrong
    hit."""
    h = hashlib.blake2b(digest_size=digest_size)

    def feed(x: Any, depth: int = 0) -> None:
        if depth > 24:
            h.update(b"<deep>")
            return
        if x is None or isinstance(x, (bool, int, float, str, bytes)):
            h.update(repr(x).encode())
        elif isinstance(x, np.ndarray):
            h.update(f"nd:{x.dtype}:{x.shape}".encode())
            h.update(np.ascontiguousarray(x).tobytes())
        elif isinstance(x, np.generic):
            h.update(repr(x.item()).encode())
        elif isinstance(x, (list, tuple)):
            h.update(f"seq{len(x)}(".encode())
            for item in x:
                feed(item, depth + 1)
                h.update(b",")
            h.update(b")")
        elif isinstance(x, dict):
            h.update(f"map{len(x)}(".encode())
            for k in sorted(x, key=repr):
                feed(k, depth + 1)
                h.update(b"=")
                feed(x[k], depth + 1)
                h.update(b",")
            h.update(b")")
        elif isinstance(x, (set, frozenset)):
            h.update(f"set{len(x)}(".encode())
            for item in sorted(x, key=repr):
                feed(item, depth + 1)
                h.update(b",")
            h.update(b")")
        elif is_dataclass(x) or hasattr(x, "__dict__") or _slot_names(x):
            # __slots__ classes have no __dict__; without this branch
            # they'd fall through to the default repr, whose memory
            # address makes the fingerprint process-unique and silently
            # defeats the cross-process cache for any parser whose plan
            # graph contains one (e.g. locale tables under TIME fields).
            h.update(type(x).__name__.encode())
            state = dict(getattr(x, "__dict__", {}))
            for slot in _slot_names(x):
                if hasattr(x, slot):
                    state[slot] = getattr(x, slot)
            feed(state, depth + 1)
        else:
            h.update(f"{type(x).__name__}:{x!r}".encode())

    feed(obj)
    return h.hexdigest()


# ---------------------------------------------------------------------------
# the on-disk store
# ---------------------------------------------------------------------------


def _metrics():
    from ..observability import metrics

    return metrics()


def _warn_once(message: str) -> None:
    from ..observability import log_warning_once

    log_warning_once(logger, message)


class CompileCache:
    """Content-addressed executable store: one file per (fingerprint,
    shape, backend) key under the cache root.  Writes are atomic
    (tmp + rename), reads verify magic + header + payload digest —
    a corrupted or version-mismatched entry is refused (miss + warn-once +
    ``compile_cache_errors_total``), never loaded."""

    def __init__(self, root: Optional[str]) -> None:
        self.root = root or None

    @classmethod
    def from_env(cls) -> "CompileCache":
        return cls(os.path.join(cache_root(), _STORE_SUBDIR))

    @property
    def enabled(self) -> bool:
        return self.root is not None

    def _path(self, key: str) -> str:
        assert self.root is not None
        return os.path.join(self.root, key[:2], f"{key}.xc")

    # -- read ------------------------------------------------------------

    def get(self, key: str) -> Optional[bytes]:
        """The serialized executable payload for ``key``, or None.  Every
        failure mode (missing, corrupt, version drift) is a miss."""
        if not self.enabled:
            return None
        path = self._path(key)
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except FileNotFoundError:
            return None
        except OSError as exc:
            _metrics().increment("compile_cache_errors_total",
                                 labels={"kind": "io"})
            _warn_once(f"compile cache read failed ({path}): {exc}")
            return None
        entry = self._decode(blob, key, path)
        return entry

    def _decode(self, blob: bytes, key: str, path: str) -> Optional[bytes]:
        reg = _metrics()
        if not blob.startswith(_ENTRY_MAGIC):
            reg.increment("compile_cache_errors_total",
                          labels={"kind": "magic"})
            _warn_once(f"compile cache entry refused (bad magic): {path}")
            return None
        try:
            off = len(_ENTRY_MAGIC)
            (hlen,) = struct.unpack("<I", blob[off:off + 4])
            header = json.loads(blob[off + 4:off + 4 + hlen])
            payload = blob[off + 4 + hlen:]
        except Exception:
            reg.increment("compile_cache_errors_total",
                          labels={"kind": "corrupt"})
            _warn_once(f"compile cache entry refused (corrupt): {path}")
            return None
        if header.get("key") != key:
            reg.increment("compile_cache_errors_total",
                          labels={"kind": "key_mismatch"})
            _warn_once(f"compile cache entry refused (key mismatch): {path}")
            return None
        if header.get("backend") != backend_fingerprint():
            # Same key hash can't collide across backends (the backend is
            # hashed into the key), so this only trips when a file was
            # copied around — refuse it like any other corruption.
            reg.increment("compile_cache_errors_total",
                          labels={"kind": "backend"})
            _warn_once(f"compile cache entry refused (backend drift): {path}")
            return None
        digest = hashlib.blake2b(payload, digest_size=16).hexdigest()
        if header.get("digest") != digest:
            reg.increment("compile_cache_errors_total",
                          labels={"kind": "digest"})
            _warn_once(f"compile cache entry refused (payload digest): {path}")
            return None
        return payload

    # -- write -----------------------------------------------------------

    def put(self, key: str, payload: bytes,
            meta: Optional[Dict[str, Any]] = None) -> bool:
        """Store a serialized executable.  IO failures are swallowed with a
        warn-once (the cache is an accelerator, not a correctness
        dependency)."""
        if not self.enabled:
            return False
        path = self._path(key)
        header = dict(meta or {})
        header.update({
            "key": key,
            "backend": backend_fingerprint(),
            "digest": hashlib.blake2b(payload, digest_size=16).hexdigest(),
            "created": time.time(),
        })
        hdr = json.dumps(header, sort_keys=True).encode()
        blob = _ENTRY_MAGIC + struct.pack("<I", len(hdr)) + hdr + payload
        tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)  # atomic: readers see old or new, whole
            _metrics().increment("compile_cache_writes_total")
            return True
        except OSError as exc:
            _metrics().increment("compile_cache_errors_total",
                                 labels={"kind": "io"})
            _warn_once(f"compile cache write failed ({path}): {exc}")
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return False


# ---------------------------------------------------------------------------
# the AOT executor
# ---------------------------------------------------------------------------


_tls = threading.local()
_listener_lock = threading.Lock()
_listening = False


# JAX brackets every jaxpr trace with this event: a scalar when it
# starts, a duration when it ends (jax._src.dispatch.JAXPR_TRACE_EVENT).
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


def _on_jax_event(event: str, **_: Any) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _tls.jax_cache_hits = getattr(_tls, "jax_cache_hits", 0) + 1


def _on_trace_start(event: str, value: float, **_: Any) -> None:
    if event == _TRACE_EVENT:
        _tls.trace_depth = getattr(_tls, "trace_depth", 0) + 1


def _on_trace_end(event: str, duration: float, **_: Any) -> None:
    if event == _TRACE_EVENT:
        depth = max(getattr(_tls, "trace_depth", 1) - 1, 0)
        _tls.trace_depth = depth
        if depth == 0:  # a nested trace is part of its outer one
            _tls.jax_traces = getattr(_tls, "jax_traces", 0) + 1


def _listen() -> None:
    global _listening
    if not _listening:
        with _listener_lock:
            if not _listening:
                import jax

                jax.monitoring.register_event_listener(_on_jax_event)
                jax.monitoring.register_scalar_listener(_on_trace_start)
                jax.monitoring.register_event_duration_secs_listener(
                    _on_trace_end)
                _listening = True


def _jax_cache_hits() -> int:
    """JAX persistent-cache hits on THIS thread so far (JAX records them
    on the compiling thread)."""
    _listen()
    return getattr(_tls, "jax_cache_hits", 0)


def _jax_traces() -> int:
    """Outermost jaxpr traces JAX has made on THIS thread so far: each
    is one whole program traced (JAX traces on the calling thread)."""
    _listen()
    return getattr(_tls, "jax_traces", 0)


def _check_fits(exe, avals) -> None:
    """Raise unless a loaded executable's own signature is the calling
    convention at this shape: (buf [B, L] uint8, lengths [B] int32) ->
    one [K, B] int32 array.  Read from the executable, not a trace; a
    payload with more or other outputs fails here (``out_info`` unflattens
    them into the one-leaf tree), before anything calls it."""
    import jax.numpy as jnp

    (buf, lengths), _ = exe.in_avals
    out = exe.out_info
    got = [(a.shape, a.dtype) for a in (buf, lengths)]
    want = [(a.shape, a.dtype) for a in avals]
    b = avals[1].shape[0]
    if (got != want or out.dtype != jnp.int32 or len(out.shape) != 2
            or out.shape[1] != b):
        raise ValueError(
            f"executable signature {got} -> {out.shape} {out.dtype} does "
            f"not fit {want} -> [K, {b}] int32"
        )


def _phase(reg, phase: str, seconds: float) -> None:
    reg.increment("parser_compile_total", labels={"phase": phase})
    reg.increment("parser_compile_seconds_total", seconds,
                  labels={"phase": phase})


class AotExecutor:
    """Drop-in callable for the ``jax.jit`` parser executor with explicit
    per-shape AOT compilation and a persistent executable cache.

    Resolution order per (B, L) shape bucket: in-memory map (artifact
    preloads land here) -> disk cache (:func:`cache_root`)
    -> explicit lower + compile (then written back to disk).  Each phase is
    timed into ``parser_compile_seconds_total{phase=lower|compile|
    serialize|deserialize}``.

    Compile/execute ERRORS propagate unchanged — the device fault layer
    (device_faults.classify_device_error) owns those semantics; only cache
    IO/corruption degrades, into a fresh compile."""

    def __init__(
        self,
        jit_fn: Callable,
        fingerprint: str,
        serializable: bool = True,
        cache: Optional[CompileCache] = None,
        devices: Optional[Sequence[Any]] = None,
    ) -> None:
        self._jit = jit_fn
        self.fingerprint = fingerprint
        # What a reloaded executable runs on: the mesh's devices, or None
        # for the default device.  Left to JAX, deserialization loads onto
        # EVERY local device and the first call then fails on a multi-chip
        # host (args sharded for 1 device, executable for N).
        self._devices = list(devices) if devices is not None else None
        # Mesh-sharded executors compile against THIS process's device
        # set; their serialized form is not portable, so they AOT-compile
        # in memory but skip the disk/artifact round-trip.
        self.serializable = serializable
        self._cache = cache
        self._execs: Dict[Tuple[int, int], Callable] = {}
        self._payloads: Dict[Tuple[int, int], bytes] = {}
        # Shapes whose executable JAX's persistent cache answered: never
        # re-serialized (an XLA:CPU executable loaded from that cache
        # serializes without its function library, and the reload then
        # faults on every call).  The next process gets the same hit.
        self._from_jax_cache: set = set()
        self._lock = threading.Lock()

    # -- plumbing --------------------------------------------------------

    def cache(self) -> CompileCache:
        # Env is re-read per resolution (cheap, and lets tests/tools
        # repoint the dir without process surgery) unless a cache was
        # injected explicitly.
        return self._cache if self._cache is not None else CompileCache.from_env()

    def _key(self, b: int, l: int) -> str:
        raw = f"{self.fingerprint}|{b}x{l}|{backend_fingerprint()}"
        return hashlib.blake2b(raw.encode(), digest_size=20).hexdigest()

    def _avals(self, b: int, l: int):
        import jax
        import jax.numpy as jnp

        return (
            jax.ShapeDtypeStruct((b, l), jnp.uint8),
            jax.ShapeDtypeStruct((b,), jnp.int32),
        )

    # -- resolution ------------------------------------------------------

    def __call__(self, buf, lengths):
        import jax

        if isinstance(buf, jax.core.Tracer) or isinstance(lengths, jax.core.Tracer):
            # Under a JAX transformation (eval_shape, grad-of, nested
            # jit): AOT executables reject tracers, so trace through the
            # plain jitted function instead.
            return self._jit(buf, lengths)
        b, l = int(buf.shape[0]), int(buf.shape[1])
        exe = self._execs.get((b, l))
        if exe is None:
            exe = self._resolve(b, l)
        return exe(buf, lengths)

    def warm(self, b: int, l: int) -> str:
        """Ensure shape (b, l) is executable without compiling on the
        request path.  Returns where it came from: ``"memory"`` | ``"disk"``
        | ``"compiled"``."""
        with self._lock:
            if (b, l) in self._execs:
                return "memory"
        before = _metrics().get("compile_cache_hits_total")
        self._resolve(b, l)
        after = _metrics().get("compile_cache_hits_total")
        return "disk" if after > before else "compiled"

    def shapes(self) -> List[Tuple[int, int]]:
        with self._lock:
            return sorted(self._execs)

    def _resolve(self, b: int, l: int) -> Callable:
        with self._lock:
            exe = self._execs.get((b, l))
            if exe is not None:
                return exe
            reg = _metrics()
            exe = self._try_load(b, l, reg)
            if exe is None:
                exe = self._compile(b, l, reg)
            self._execs[(b, l)] = exe
            return exe

    def _try_load(self, b: int, l: int, reg) -> Optional[Callable]:
        if not self.serializable:
            return None
        cache = self.cache()
        if not cache.enabled:
            return None
        key = self._key(b, l)
        payload = cache.get(key)
        if payload is None:
            reg.increment("compile_cache_misses_total")
            return None
        exe = self._deserialize(payload, b, l, reg)
        if exe is None:
            reg.increment("compile_cache_misses_total")
            return None
        reg.increment("compile_cache_hits_total")
        self._payloads[(b, l)] = payload
        return exe

    def _deserialize(self, payload: bytes, b: int, l: int, reg
                     ) -> Optional[Callable]:
        """Load a serialized executable; any failure is a refusal (fresh
        compile), counted and warned once — never an abort."""
        from jax.experimental import serialize_executable as se
        import jax
        import jax.tree_util as jtu

        t0 = time.perf_counter()
        traces0 = _jax_traces()
        try:
            avals = self._avals(b, l)
            in_tree = jtu.tree_structure((avals, {}))
            # One array out, whatever the shape: the output tree is the
            # calling convention's, so nothing here traces the program.
            out_tree = jtu.tree_structure(0)
            exe = se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=self._devices or jax.devices()[:1],
            )
            _check_fits(exe, avals)
        except Exception as exc:
            reg.increment("compile_cache_errors_total",
                          labels={"kind": "deserialize"})
            _warn_once(
                f"cached executable refused (deserialize failed, shape "
                f"{b}x{l}): {type(exc).__name__}: {exc}"
            )
            return None
        finally:
            reg.increment("compile_cache_load_traces_total",
                          _jax_traces() - traces0)
        _phase(reg, "deserialize", time.perf_counter() - t0)
        return exe

    def _compile(self, b: int, l: int, reg) -> Callable:
        """Explicit lower -> compile (errors propagate: the fault layer's
        compile-demotion semantics key on them), then serialize + write
        back when the executor is disk-eligible."""
        avals = self._avals(b, l)
        t0 = time.perf_counter()
        lowered = self._jit.lower(*avals)
        t1 = time.perf_counter()
        _phase(reg, "lower", t1 - t0)
        hits0 = _jax_cache_hits()
        compiled = lowered.compile()
        t2 = time.perf_counter()
        _phase(reg, "compile", t2 - t1)
        if _jax_cache_hits() > hits0:
            self._from_jax_cache.add((b, l))
        elif self.serializable:
            # Serialize only when there is a cache to write back to —
            # serialization costs a noticeable fraction of the compile
            # itself, and artifact export (export_payloads) serializes
            # lazily for shapes skipped here.
            cache = self.cache()
            if cache.enabled:
                payload = self._serialize(compiled, b, l, reg)
                if payload is not None:
                    self._payloads[(b, l)] = payload
                    cache.put(self._key(b, l), payload, meta={
                        "shape": [b, l], "fingerprint": self.fingerprint,
                    })
        return compiled

    def _serialize(self, compiled, b: int, l: int, reg) -> Optional[bytes]:
        from jax.experimental import serialize_executable as se

        t0 = time.perf_counter()
        try:
            payload, _, _ = se.serialize(compiled)
        except Exception as exc:
            reg.increment("compile_cache_errors_total",
                          labels={"kind": "serialize"})
            _warn_once(
                f"executable not serializable (shape {b}x{l}): "
                f"{type(exc).__name__}: {exc}"
            )
            return None
        _phase(reg, "serialize", time.perf_counter() - t0)
        return payload

    # -- artifact integration -------------------------------------------

    def export_payloads(self) -> Dict[Tuple[int, int], bytes]:
        """Serialized executables for every compiled/loaded shape (used by
        ``TpuBatchParser.to_bytes`` to embed them in the artifact)."""
        with self._lock:
            out = dict(self._payloads)
            missing = [s for s in self._execs
                       if s not in out and s not in self._from_jax_cache]
        reg = _metrics()
        for (b, l) in missing:
            payload = self._serialize(self._execs[(b, l)], b, l, reg)
            if payload is not None:
                with self._lock:
                    self._payloads[(b, l)] = payload
                out[(b, l)] = payload
        return out

    def preload(self, b: int, l: int, payload: bytes,
                backend: Optional[str] = None) -> bool:
        """Install an artifact-embedded executable for shape (b, l).
        Refused (False) on backend drift or a broken payload — the shape
        then simply compiles fresh on first use."""
        if not self.serializable:
            return False
        if backend is not None and backend != backend_fingerprint():
            _metrics().increment("compile_cache_errors_total",
                                 labels={"kind": "backend"})
            _warn_once(
                "artifact executable refused (backend drift): "
                f"{backend!r} != {backend_fingerprint()!r}"
            )
            return False
        reg = _metrics()
        exe = self._deserialize(payload, b, l, reg)
        if exe is None:
            return False
        with self._lock:
            self._execs[(b, l)] = exe
            self._payloads[(b, l)] = payload
        return True
