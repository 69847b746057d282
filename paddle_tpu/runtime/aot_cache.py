"""Persistent on-disk AOT executable cache, shared by training and serving.

The reference framework never recompiles: a ProgramDesc is interpreted
op-by-op, so a fresh process starts executing immediately. Our TPU-native
executor instead compiles the whole Program into one XLA executable —
which makes *cold start* (restarts, preemption recovery, CI, sweep
workers) pay a full trace + XLA compile before step 1. This module is the
warm-start store both `Executor` (training step + fused loop) and
`inference.Predictor` (serving) write their executables into, keyed so a
later process with the same program/feeds/toolchain deserializes instead
of recompiling.

Design rules (the "never a crash" contract):

- Keys are content hashes over (kind, program fingerprint + version, feed
  signature, fetch/state names, per-step feed set) PLUS the environment
  fingerprint (jax/jaxlib versions, backend, device kind, x64 flag,
  XLA_FLAGS, trace-affecting PADDLE_TPU_* knobs). A toolchain or backend
  change is therefore a plain MISS, never a deserialization attempt of an
  incompatible blob.
- Writes are atomic (tmp + `os.replace`); concurrent writers of the same
  key are idempotent (last rename wins, both blobs identical).
- A blob that fails to unpickle/deserialize anyway (truncation, foreign
  machine) is QUARANTINED (renamed `*.corrupt`) and treated as a miss —
  the caller recompiles; nothing raises through the executor.
- A read-only or unwritable cache directory degrades to compile-only
  (counted, not raised).
- Size is bounded by an mtime-LRU GC (`PADDLE_TPU_AOT_CACHE_MAX_BYTES`,
  default 1 GiB, 0 = unbounded); `load()`/use touches the entry so GC
  eviction order tracks traffic, not write time.

Layout (one format for serving and training): `<key>.xla` is the pickled
`(blob, in_tree, out_tree)` triple from
`jax.experimental.serialize_executable`; `<key>.sig` is a pickled metadata
dict (format version, kind, program fingerprint, feed signature, fetch
names, env fingerprint, creation time) that lets `Predictor` preload
executables without knowing their feed signatures up front and lets
`tools/aot_cache_ls.py` inspect entries without jax.

Where the caches live — ONE rule (`compile_cache_dir`), for this tier
and for jax's own persistent compilation cache (the second tier: XLA
output keyed on HLO, so even a *changed* program whose subcomputations
match compiles faster):

- ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself (this package
  then never touches ``jax_compilation_cache_dir``), and the AOT tier is
  the fixed-name subdirectory ``paddle_tpu_aot`` of it;
- unset: both live under ``<checkout>/.xla_cache``, resolved from this
  package's own path — the same directory in every process, because the
  path is part of jax's cache key and a directory that moves never hits.

`Predictor` / `DecodePredictor` keep their per-model
``<model_dir>/__aot_cache__`` for the AOT tier.

Env knobs:
- ``PADDLE_TPU_AOT_CACHE=0``        — kill switch (memory-only compiles)
- ``PADDLE_TPU_AOT_CACHE_DIR``      — AOT-tier override; exists for
  `tests/conftest.py` to isolate the suite from the checkout's cache
- ``PADDLE_TPU_AOT_CACHE_MAX_BYTES``— GC bound (default 1 GiB, 0 = off)
"""
from __future__ import annotations

import hashlib
import os
import pickle
import time
import warnings
from typing import Any, Dict, List, Optional, Tuple

from .. import observability as obs

__all__ = [
    "AotDiskCache", "compile_cache_dir", "default_cache_dir",
    "enable_compile_cache", "enabled_by_env",
    "max_bytes_from_env", "env_fingerprint", "trace_env_fingerprint",
    "serialize_executable", "deserialize_executable",
    "FORMAT_VERSION", "BLOB_SUFFIX",
    "META_SUFFIX", "QUARANTINE_SUFFIX", "DEFAULT_MAX_BYTES",
]

FORMAT_VERSION = 1
BLOB_SUFFIX = ".xla"
META_SUFFIX = ".sig"
QUARANTINE_SUFFIX = ".corrupt"
DEFAULT_MAX_BYTES = 1 << 30  # 1 GiB

# Env vars consumed INSIDE op lowering (trace time): they change the HLO
# without changing the Program fingerprint, so they must be part of the
# key or a cached executable could silently carry the wrong kernel
# selection into a process that set them otherwise. The two compile
# levers of docs/internals.md's table (tests/test_env_switches.py holds
# the two lists equal); what a kernel is given is otherwise an op
# attribute, which the Program fingerprint covers.
_TRACE_ENV = (
    "PADDLE_TPU_FORCE_PALLAS",
    "PADDLE_TPU_NO_PALLAS",
)


_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".xla_cache")
AOT_SUBDIR = "paddle_tpu_aot"


def compile_cache_dir() -> str:
    """The directory both compile-cache tiers live under (module doc)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _CHECKOUT_CACHE


def default_cache_dir() -> str:
    """The AOT tier's directory."""
    return (os.environ.get("PADDLE_TPU_AOT_CACHE_DIR")
            or os.path.join(compile_cache_dir(), AOT_SUBDIR))


def enable_compile_cache() -> str:
    """Point jax's persistent compilation cache at `compile_cache_dir()`
    and return that directory. With ``JAX_COMPILATION_CACHE_DIR`` set
    jax has already read it and nothing is updated here; thresholds stay
    jax's own (``JAX_PERSISTENT_CACHE_*``) either way. On a CPU-only
    process the jax tier stays off unless the variable asks for it:
    jaxlib 0.9.0 fails to dispatch some XLA:CPU executables reloaded
    from that cache ("Function ... not found"), and a CPU run is a
    rehearsal whose compiles are cheap; the AOT tier covers it."""
    d = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        if (jax.default_backend() != "cpu"
                and jax.config.jax_compilation_cache_dir != d):
            jax.config.update("jax_compilation_cache_dir", d)
    return d


def enabled_by_env() -> bool:
    return os.environ.get("PADDLE_TPU_AOT_CACHE", "1") != "0"


def max_bytes_from_env() -> int:
    raw = os.environ.get("PADDLE_TPU_AOT_CACHE_MAX_BYTES")
    if not raw:
        return DEFAULT_MAX_BYTES
    try:
        return int(raw)
    except ValueError:
        # cache management is best-effort, never a crash (the
        # PADDLE_TPU_PRELOAD_MAX precedent)
        warnings.warn(
            "PADDLE_TPU_AOT_CACHE_MAX_BYTES=%r is not an integer; using "
            "the default (%d)" % (raw, DEFAULT_MAX_BYTES))
        return DEFAULT_MAX_BYTES


def trace_env_fingerprint() -> Tuple[Tuple[str, str], ...]:
    """(name, value) for every SET trace-affecting env knob."""
    return tuple((k, os.environ[k]) for k in _TRACE_ENV if k in os.environ)


def env_fingerprint() -> Tuple:
    """Everything outside the Program that shapes the compiled
    executable. Two processes whose fingerprints differ can never share
    an entry — a version/backend mismatch is a key miss by construction,
    so stale blobs are unreachable rather than a deserialization risk."""
    import jax
    import jaxlib

    from ..framework.scope import current_device

    dev = current_device()
    return (
        "fmt%d" % FORMAT_VERSION,
        jax.__version__,
        jaxlib.__version__,
        dev.platform,
        dev.device_kind,
        bool(jax.config.jax_enable_x64),
        os.environ.get("XLA_FLAGS", ""),
        trace_env_fingerprint(),
    )


def serialize_executable(compiled) -> bytes:
    """jax Compiled -> bytes (the shared on-disk payload format)."""
    from jax.experimental import serialize_executable as se

    blob, in_tree, out_tree = se.serialize(compiled)
    return pickle.dumps((blob, in_tree, out_tree), protocol=4)


def deserialize_executable(payload: bytes):
    """bytes -> jax Compiled (raises on any corruption — callers go
    through AotDiskCache.load, which quarantines)."""
    from jax.experimental import serialize_executable as se

    from ..framework.scope import current_device

    blob, in_tree, out_tree = pickle.loads(payload)
    # pin execution to the one device the executable was compiled for
    # (the same `current_device` its key was derived from): the default
    # (all local devices) breaks under a multi-device runtime
    return se.deserialize_and_load(
        blob, in_tree, out_tree, execution_devices=[current_device()])


class AotDiskCache:
    """One cache directory: load/store/touch/GC with the module-docstring
    failure contract. Instances are cheap (env resolved at construction,
    no I/O until used); Executor and Predictor each hold their own."""

    def __init__(self, cache_dir: Optional[str] = None,
                 max_bytes: Optional[int] = None,
                 enabled: Optional[bool] = None):
        self.dir = (os.path.expanduser(cache_dir) if cache_dir
                    else default_cache_dir())
        self.max_bytes = (max_bytes_from_env() if max_bytes is None
                          else int(max_bytes))
        want = True if enabled is None else bool(enabled)
        self.enabled = want and enabled_by_env()

    # -- keys and paths ---------------------------------------------------
    @staticmethod
    def key(fields) -> str:
        """Stable 24-hex content key over a tuple of picklable/reprable
        key fields (repr of tuples/strings/ints is deterministic)."""
        return hashlib.sha1(repr(tuple(fields)).encode()).hexdigest()[:24]

    def blob_path(self, key: str) -> str:
        return os.path.join(self.dir, key + BLOB_SUFFIX)

    def meta_path(self, key: str) -> str:
        return os.path.join(self.dir, key + META_SUFFIX)

    def blob_bytes(self, key: str) -> Optional[int]:
        """Size of the stored executable under ``key``, None where there
        is none (what an acquisition record gives as ``blob_bytes``)."""
        try:
            return os.path.getsize(self.blob_path(key))
        except OSError:
            return None

    # -- load/store -------------------------------------------------------
    def load(self, key: str):
        """Deserialized executable, or None (miss / disabled / corrupt —
        corrupt blobs are quarantined and counted, never raised)."""
        if not self.enabled:
            return None
        path = self.blob_path(key)
        try:
            with open(path, "rb") as f:
                payload = f.read()
        except OSError:
            return None  # plain miss
        try:
            exe = deserialize_executable(payload)
        except Exception:
            self._quarantine(key)
            obs.AOT_CACHE_CORRUPT.inc(reason="blob")
            return None
        self.touch(key)
        return exe

    def store(self, key: str, compiled, meta: Optional[Dict] = None) -> bool:
        """Serialize + atomic write + sidecar + GC. Returns False (with a
        counter) instead of raising on ANY failure — an unwritable cache
        loses warm starts, not execution."""
        if not self.enabled:
            return False
        try:
            payload = serialize_executable(compiled)
        except Exception:
            # executable kind (or backend) without serialization support
            obs.AOT_CACHE_ERRORS.inc(op="serialize")
            return False
        tmp = None
        try:
            os.makedirs(self.dir, exist_ok=True)
            tmp = self.blob_path(key) + ".tmp.%d" % os.getpid()
            with open(tmp, "wb") as f:
                f.write(payload)
            os.replace(tmp, self.blob_path(key))
        except OSError:
            obs.AOT_CACHE_ERRORS.inc(op="store")
            if tmp is not None:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
            return False
        if meta is not None:
            self.write_meta(key, meta)
        obs.AOT_CACHE_WRITTEN_BYTES.inc(len(payload))
        self.gc()
        return True

    def _quarantine(self, key: str):
        """Move a bad blob aside (one postmortem copy per key; GC removes
        stale quarantines) and drop its sidecar so preload scans skip it."""
        try:
            os.replace(self.blob_path(key),
                       self.blob_path(key) + QUARANTINE_SUFFIX)
        except OSError:
            pass
        try:
            os.unlink(self.meta_path(key))
        except OSError:
            pass

    # -- sidecar metadata -------------------------------------------------
    def write_meta(self, key: str, meta: Dict) -> bool:
        try:
            os.makedirs(self.dir, exist_ok=True)
            tmp = self.meta_path(key) + ".tmp.%d" % os.getpid()
            with open(tmp, "wb") as f:
                pickle.dump(dict(meta, v=FORMAT_VERSION), f, protocol=4)
            os.replace(tmp, self.meta_path(key))
            return True
        except OSError:
            obs.AOT_CACHE_ERRORS.inc(op="store")
            return False

    def read_meta(self, key: str) -> Optional[Dict]:
        try:
            with open(self.meta_path(key), "rb") as f:
                meta = pickle.load(f)
        except OSError:
            return None
        except Exception:
            obs.AOT_CACHE_CORRUPT.inc(reason="sidecar")
            return None
        return meta if isinstance(meta, dict) else None

    def has_meta(self, key: str) -> bool:
        return os.path.exists(self.meta_path(key))

    def touch(self, key: str):
        """Refresh mtime so LRU eviction order tracks USE. Best-effort:
        a shared/read-only cache just doesn't update recency."""
        for p in (self.blob_path(key), self.meta_path(key)):
            try:
                os.utime(p, None)
            except OSError:
                pass

    # -- enumeration (preload + tools) -----------------------------------
    def entries(self) -> List[Dict[str, Any]]:
        """[{key, path, bytes, mtime, meta}] for every blob, newest
        first. meta is the sidecar dict or None; missing/corrupt sidecars
        do not hide their blob."""
        out = []
        try:
            names = os.listdir(self.dir)
        except OSError:
            return out
        for n in names:
            if not n.endswith(BLOB_SUFFIX):
                continue
            key = n[:-len(BLOB_SUFFIX)]
            p = os.path.join(self.dir, n)
            try:
                st = os.stat(p)
            except OSError:
                continue  # racing writer/GC: scan is best-effort
            out.append({"key": key, "path": p, "bytes": st.st_size,
                        "mtime": st.st_mtime, "meta": self.read_meta(key)})
        out.sort(key=lambda e: e["mtime"], reverse=True)
        return out

    def sidecars_by_recency(self) -> List[Tuple[str, Dict]]:
        """(key, meta) for every entry with a readable sidecar, newest
        first — the Predictor preload scan."""
        return [(e["key"], e["meta"]) for e in self.entries()
                if e["meta"] is not None]

    def total_bytes(self) -> int:
        total = 0
        try:
            for n in os.listdir(self.dir):
                try:
                    total += os.stat(os.path.join(self.dir, n)).st_size
                except OSError:
                    pass
        except OSError:
            pass
        return total

    # -- GC ---------------------------------------------------------------
    def gc(self, max_bytes: Optional[int] = None) -> List[str]:
        """mtime-LRU: evict oldest (blob, sidecar) pairs until the
        directory fits `max_bytes` (<= 0 = unbounded). Stale tmp files
        and quarantined blobs older than an hour are removed regardless
        (crashed writers / already-diagnosed corruption). Returns evicted
        keys; also refreshes the byte-size gauge."""
        limit = self.max_bytes if max_bytes is None else max_bytes
        evicted: List[str] = []
        try:
            names = os.listdir(self.dir)
        except OSError:
            return evicted
        now = time.time()
        total = 0
        blobs = []
        for n in names:
            p = os.path.join(self.dir, n)
            try:
                st = os.stat(p)
            except OSError:
                continue
            if ".tmp." in n or n.endswith(QUARANTINE_SUFFIX):
                if now - st.st_mtime > 3600:
                    try:
                        os.unlink(p)
                        continue
                    except OSError:
                        pass
            total += st.st_size
            if n.endswith(BLOB_SUFFIX):
                blobs.append((st.st_mtime, st.st_size, n[:-len(BLOB_SUFFIX)]))
        if limit > 0 and total > limit:
            blobs.sort()  # oldest first
            for _mt, size, key in blobs:
                if total <= limit:
                    break
                for p in (self.blob_path(key), self.meta_path(key)):
                    try:
                        sz = os.stat(p).st_size
                        os.unlink(p)
                        total -= sz
                    except OSError:
                        pass
                evicted.append(key)
                obs.AOT_CACHE_EVICTIONS.inc()
        obs.AOT_CACHE_BYTES.set(total, dir=self.dir)
        return evicted
