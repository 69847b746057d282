"""Engine: the compile/execute core shared by Executor and Predictor.

Before this module, the training ``Executor`` and the serving
``inference.Predictor`` each carried a private copy of the same three
things: a per-program-version feed-conversion plan (declared-variable
lookup + dtype coercion), the AOT disk-cache KEY derivation (what makes
a cached executable reachable), and the load-or-compile acquisition
path (disk hit -> deserialize, miss -> lower + XLA compile + store,
with the hit/miss/latency accounting). Divergence between the copies is
exactly how stale-cache bugs are born — a key field added on one side
but not the other silently serves the wrong executable or recompiles
forever.

``Engine`` owns those three things for ONE program:

- identity: the program, its content fingerprint (cached per version),
  and the environment fingerprint that completes every cache key;
- the AOT-cache handle (``runtime.aot_cache.AotDiskCache``);
- the feed plan: ``feed_var(name)`` (memoized per program version) and
  ``feed_plan(names)`` — the ``(name, declared var, numpy dtype)``
  triples the serving hot path converts feeds with;
- ``key(kind, feed_sig, fetch_names, *extra)`` — the ONE key-derivation
  function (field order is shared by training and serving, so the
  on-disk key space is identical to what PR 5 wrote);
- ``acquire(kind, key, lower, meta=...)`` — the ONE
  disk-load-or-compile path with the cold/warm metrics contract.

``Executor`` holds one Engine per program (weak-keyed);
``inference.Predictor`` and ``serving.sharded.ShardedPredictor`` hold
one for their loaded model — and a fleet replica is just an Engine (via
its Predictor) plus a channel loop (``serving.worker``).
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import observability as obs
from ..observability import scopes, tracing
from ..runtime import aot_cache as _aot

__all__ = ["Engine"]


class Engine:
    """Compile/execute core for one Program. Cheap to construct: no I/O
    and no trace until used; the feed plan materializes lazily per
    program version."""

    def __init__(self, program, disk: Optional[_aot.AotDiskCache] = None,
                 feed_names: Optional[Sequence[str]] = None,
                 fetch_names: Optional[Sequence[str]] = None):
        self.program = program
        self.disk = disk if disk is not None else _aot.AotDiskCache()
        self.feed_names = list(feed_names) if feed_names is not None else None
        self.fetch_names = (list(fetch_names) if fetch_names is not None
                            else None)
        # per-version memo: (version, {name: Variable}) — negative
        # lookups are NOT cached (create_var alone does not bump
        # program._version, same contract as the old
        # Executor._feed_var_for)
        self._feed_vars: Tuple = (None, {})
        # optimized-twin memo (the PADDLE_TPU_OPT step): (version, level,
        # feeds, fetches) -> optimized Program. Holding the clone here
        # keeps it alive exactly as long as its source program's Engine,
        # so the Executor's weak-keyed compile caches on the clone can't
        # see id reuse
        self._optimized: Dict = {}

    # -- identity ---------------------------------------------------------
    @property
    def version(self):
        """The program's process-local mutation counter."""
        return getattr(self.program, "_version", None)

    def fingerprint(self) -> str:
        """Short (8-hex) program fingerprint, cached per version."""
        return obs.program_fp(self.program)

    # -- optimizing transpiler --------------------------------------------
    _OPT_MEMO_MAX = 8

    def optimized(self, scope=None, feed_names: Sequence[str] = (),
                  fetch_names: Sequence[str] = (), level: int = 1):
        """The opt-in optimize step (PADDLE_TPU_OPT / explicit API): an
        optimized CLONE of this engine's program from the transpiler
        pass manager, memoized per (program version, level, feed set,
        fetch order). The clone fingerprints differently from the
        original, so its executables land under their own AOT-cache
        keys — optimized and original coexist on disk and in memory."""
        if level <= 0:
            return self.program
        import weakref

        key = (self.version, int(level), tuple(sorted(feed_names)),
               tuple(fetch_names))
        hit = self._optimized.get(key)
        if hit is not None:
            # the twin is only valid with the Scope its passes
            # materialized folded params into — a different scope must
            # re-optimize, not inherit state it doesn't hold
            ref, prog = hit
            same_scope = (scope is None and ref is None) or (
                ref is not None and ref() is scope)
            if same_scope:
                return prog
        from ..transpiler.passes import optimize_program

        prog, _ctx = optimize_program(
            self.program, scope=scope, level=level,
            feed_names=feed_names, fetch_names=fetch_names)
        if len(self._optimized) >= self._OPT_MEMO_MAX:
            self._optimized.pop(next(iter(self._optimized)))
        self._optimized[key] = (
            weakref.ref(scope) if scope is not None else None, prog)
        return prog

    # -- feed plan --------------------------------------------------------
    def feed_var(self, name: str):
        """Declared Variable behind a feed name, memoized per program
        version (the recursive block walk runs once per version, not
        once per call — the serving/training hot-path lookup)."""
        ver, cache = self._feed_vars
        if ver != self.version:
            cache = {}
            self._feed_vars = (self.version, cache)
        var = cache.get(name)
        if var is None:
            var = self.program.global_block()._find_var_recursive(name)
            if var is not None:
                cache[name] = var
        return var

    def feed_plan(self, feed_names: Optional[Sequence[str]] = None
                  ) -> List[Tuple[str, object, Optional[np.dtype]]]:
        """``[(name, declared var, numpy dtype or None)]`` for a frozen
        feed set — the conversion plan the Predictor walks per request
        instead of re-resolving declarations per call."""
        from ..framework.dtypes import as_numpy_dtype

        names = self.feed_names if feed_names is None else feed_names
        plan = []
        for name in names or ():
            var = self.feed_var(name)
            want = (np.dtype(as_numpy_dtype(var.dtype))
                    if var is not None else None)
            plan.append((name, var, want))
        return plan

    def convert_feeds(self, feed: Dict, plan=None) -> Dict[str, np.ndarray]:
        """Feed dict -> contiguous, declared-dtype arrays (the serving
        request path; KeyError names the missing feed)."""
        if plan is None:
            plan = self.feed_plan()
        out = {}
        for name, _var, want in plan:
            if name not in feed:
                raise KeyError("missing feed %r (model expects %s)"
                               % (name, [n for n, _, _ in plan]))
            arr = feed[name]
            if type(arr) is not np.ndarray:
                arr = np.asarray(arr)
            if want is not None and arr.dtype != want:
                arr = arr.astype(want)
            out[name] = arr
        return out

    # -- cache keys -------------------------------------------------------
    def key_fields(self, kind: str, feed_sig, fetch_names, *extra) -> Tuple:
        """The shared key-field layout: (kind, program content
        fingerprint, feed signature, fetch ORDER, <caller extras>,
        environment fingerprint). Training appends its state signature /
        per-step set as extras; serving appends nothing — both end with
        the env fingerprint so a toolchain change is a miss, never a
        stale load. program._version is deliberately absent: the content
        fingerprint already covers it, and a content-identical program
        rebuilt another way must still warm-start."""
        return ((kind, self.program.fingerprint(), feed_sig,
                 tuple(fetch_names)) + tuple(extra)
                + (_aot.env_fingerprint(),))

    def key(self, kind: str, feed_sig, fetch_names, *extra) -> str:
        return self.disk.key(self.key_fields(kind, feed_sig, fetch_names,
                                             *extra))

    def tier(self) -> str:
        """Transpile/quantization tier of this engine's program, from
        its stamps: "int8" (quantize stamp — serialized, so exported
        int8 models keep it), "O<level>" (the in-process marker
        optimize_program leaves on its clones), "O2" (a deserialized
        bucketize-stamped export), else "raw". Best-effort: an O1
        export carries no stamp and reloads as "raw"."""
        p = self.program
        if getattr(p, "_quantized", None):
            return "int8"
        lvl = getattr(p, "_opt_level", None)
        if lvl:
            return "O%d" % int(lvl)
        if getattr(p, "_bucketize", None):
            return "O2"
        return "raw"

    def meta(self, kind: str, feed_sig, fetch_names) -> Dict:
        """Sidecar metadata for preload scans and aot_cache_ls: the
        ``tier`` field is what distinguishes coexisting raw, optimized,
        and quantized executables of one model in the cache listing."""
        return {"kind": kind, "program": self.fingerprint(),
                "tier": self.tier(),
                "feed_sig": feed_sig, "fetch_names": tuple(fetch_names),
                "env": _aot.env_fingerprint(), "created": time.time()}

    # -- acquisition ------------------------------------------------------
    def acquire(self, kind: str, key: str, lower, meta: Optional[Dict] = None,
                describe=None, *, name: Optional[str] = None,
                build_ms: float = 0.0, cost: bool = False,
                counts_compile: bool = True):
        """THE load-or-compile path: disk hit deserializes (path=warm),
        miss runs ``lower()`` -> ``.compile()`` and stores the result
        (path=cold). Returns ``(compiled, path, timings)`` where path is
        ``"warm" | "cold"`` and timings is ``{"trace_ms", "xla_ms"}`` on
        the cold path (None on warm — a deserialize has no split).

        Every acquisition is ONE ``observability.observe_acquire`` call
        (the compile instruments and the timeline's record, whose fields
        it documents): the parts timed here, ``name`` (the executable's
        own; ``<kind>/<fingerprint>`` without one) and ``build_ms`` (what
        the caller spent on the program, its feed structs and the key
        before it called) from the caller, the ``tracing.phase`` it
        began under. ``describe(executable)`` gives fields read off the
        executable itself, on both paths; ``cost`` adds XLA's
        cost-analysis estimates on the cold one. ``counts_compile=False``
        leaves ``paddle_tpu_compile_total`` to the caller's first
        dispatch (the Executor: ``observe_run`` counts a first run
        whichever path its executable came by). While tracing samples,
        the acquisition is also a ``tracing.phase("acquire")`` with
        children ``acquire.load | .trace | .xla | .store``: a record of
        the flight recorder's process ring, and under a profiler session
        a host span on the device events' clock. The executable is
        registered under the record's name with ``observability.scopes``
        (a dict insert: its text is rendered when a reader asks
        ``scopes.maps()``, never here).

        ``lower`` may raise (program errors propagate exactly as the
        lazy-jit first call would); disk I/O failures are absorbed by
        AotDiskCache per its never-a-crash contract. ``lower=None`` asks
        for the disk tier alone (a preload, which compiles nothing):
        where the tier has no loadable blob under ``key`` the answer is
        ``(None, "absent", None)`` and nothing is recorded, since
        nothing was acquired."""
        fp = self.fingerprint()
        use_disk = self.disk.enabled
        began_under = tracing.current_phase()
        ts = time.time() - build_ms / 1e3
        clock = time.perf_counter
        parts = {"build_ms": build_ms or None}
        compiled = timings = None
        t0 = clock()
        with tracing.phase("acquire", executable=name or kind):
            blob = self.disk.blob_bytes(key) if use_disk else None
            if blob is not None:
                with tracing.phase("acquire.load", blob_bytes=blob):
                    t = clock()
                    compiled = self.disk.load(key)
                    load_ms = (clock() - t) * 1e3
            if compiled is None and lower is None:
                return None, "absent", None
            path = "warm" if compiled is not None else "cold"
            if path == "warm":
                parts.update(load_ms=load_ms, blob_bytes=blob)
            else:
                t1 = clock()
                with tracing.phase("acquire.trace"):
                    lowered = lower()
                t2 = clock()
                with tracing.phase("acquire.xla"):
                    compiled = lowered.compile()
                t3 = clock()
                with tracing.phase("acquire.store"):
                    stored = self.disk.store(key, compiled, meta=meta)
                timings = {"trace_ms": (t2 - t1) * 1e3,
                           "xla_ms": (t3 - t2) * 1e3}
                parts.update(timings, store_ms=(clock() - t3) * 1e3,
                             blob_bytes=(self.disk.blob_bytes(key)
                                         if stored else None))
            cost = cost and path == "cold"
            if describe or cost:
                t = clock()
                if cost:
                    parts.update(obs.hlo_cost_stats(compiled) or {})
                if describe:
                    parts.update(describe(compiled))
                parts["describe_ms"] = (clock() - t) * 1e3
        compile_ms = (t3 - t1) * 1e3 if timings else None
        record = name or "%s/%s" % (kind, fp)
        obs.observe_acquire(
            kind, path, build_ms + (clock() - t0) * 1e3, program=fp,
            name=record, ts=ts, phase=began_under,
            aot_ms=load_ms if path == "warm" else compile_ms,
            disk=use_disk,
            compile_ms=compile_ms if counts_compile else None, **parts)
        scopes.register(record, compiled)
        return compiled, path, timings
