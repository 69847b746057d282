"""Executor: runs Programs on TPU as single jitted XLA computations.

Reference: paddle/fluid/framework/executor.cc + python/paddle/fluid/
executor.py. The reference interprets a ProgramDesc op-by-op, launching one
device kernel per operator. Here `run()` compiles the whole main block into
ONE `jax.jit` function

    (feeds, state, rng_key) -> (fetches, new_state)

with the persistable state (parameters, optimizer accumulators, BN running
stats) donated, so parameter updates are in-place at the XLA buffer level —
the TPU-native equivalent of the reference's in-place Scope writes. Compiled
functions are cached on (program identity+version, feed signature, fetch
names), matching the reference's `use_program_cache` executor cache.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

import functools
import os
import time
import warnings

import itertools

from . import observability as obs
from . import profiler
from .runtime import aot_cache as _aot
from .framework.core import Program, Variable, default_main_program
from .framework.dtypes import as_numpy_dtype
from .framework.scope import Place, Scope, default_place, global_scope
from .framework.trace import RngStream, TraceError, trace_block
from .framework.verifier import verify_program

__all__ = ["Executor"]


def _as_feed_array(value, var: Optional[Variable]):
    if isinstance(value, jax.Array):
        # device-resident feed: pass through untouched — np.asarray would
        # round-trip it to host and re-upload every step (the reference's
        # double_buffer ops exist for the same
        # reason: keep steady-state batches off the feed path)
        if var is not None:
            want = as_numpy_dtype(var.dtype)
            # with x64 disabled JAX cannot hold an int64 array, so an int32
            # device array IS the canonical form of an int64 feed; only then
            # is skipping the cast correct
            exempt = (np.dtype(want) == np.int64 and value.dtype == jnp.int32
                      and not jax.config.jax_enable_x64)
            if np.dtype(value.dtype) != np.dtype(want) and not exempt:
                value = value.astype(want)
        return value
    arr = np.asarray(value)
    if var is not None:
        want = as_numpy_dtype(var.dtype)
        if arr.dtype != want:
            arr = arr.astype(want)
    return arr


def _fetch_name(f) -> str:
    return f.name if isinstance(f, Variable) else str(f)


_EXE_IDS = itertools.count()


class _Compiled:
    __slots__ = ("fn", "state_in_names", "state_out_names", "fetch_names",
                 "program", "fp", "hlo", "lazy")

    def __init__(self, fn, state_in_names, state_out_names, fetch_names,
                 program, fp=None, hlo=None, lazy=True):
        self.fn = fn
        self.state_in_names = state_in_names
        self.state_out_names = state_out_names
        self.fetch_names = fetch_names
        # strong ref: the cache key uses id(program), so the program must
        # stay alive for as long as the cache entry does (prevents id reuse)
        self.program = program
        self.fp = fp          # short program fingerprint (observability)
        self.hlo = hlo        # opt-in trace/lower timings + cost estimates
        # a bare jax.jit that traces and compiles inside its first call
        # (False: an executable Engine.acquire loaded or compiled, and
        # recorded)
        self.lazy = lazy


class _CompileCache:
    """LRU-bounded compile cache (cap via PADDLE_TPU_COMPILE_CACHE_MAX,
    default 256; 0 = unbounded). A long-lived server recompiling across
    many feed signatures must not grow executables without bound; each
    eviction is counted so cache thrash is visible in /metrics."""

    def __init__(self, cap: int):
        import collections

        self._cap = cap
        self._d = collections.OrderedDict()

    def get(self, key):
        c = self._d.get(key)
        if c is not None:
            self._d.move_to_end(key)
        return c

    def put(self, key, val):
        self._d[key] = val
        self._d.move_to_end(key)
        while self._cap > 0 and len(self._d) > self._cap:
            _, old = self._d.popitem(last=False)
            obs.CACHE_EVICTIONS.inc(program=getattr(old, "fp", None) or "?")

    def clear(self):
        self._d.clear()

    def __len__(self):
        return len(self._d)


def analyze_state(program: Program, feed_names):
    """Persistable vars read (state inputs) and written (state outputs)
    by the program's ops."""
    read, written = [], []
    seen_r, seen_w = set(), set()
    for block in program.blocks:
        for op in block.ops:
            for name in op.input_arg_names:
                var = block._find_var_recursive(name)
                if var is not None and var.persistable and name not in seen_r and name not in feed_names:
                    seen_r.add(name)
                    read.append(name)
            for name in op.output_arg_names:
                var = block._find_var_recursive(name)
                if var is not None and var.persistable and name not in seen_w:
                    seen_w.add(name)
                    written.append(name)
    return read, written


def build_step_fn(program: Program, fetch_names, state_in, state_out):
    """The pure traced step: (feeds, state, rng_key, step) -> (fetches,
    new_state). `step` is folded into the RNG INSIDE the jitted program —
    folding on the host would dispatch two device ops per step.

    Shared by Executor (jit, one device) and ParallelExecutor (jit over a
    Mesh with shardings) — the SAME computation, different partitionings.
    """
    block = program.global_block()

    def stepfn(feeds: Dict, state: Dict, rng_key, step=0):
        env: Dict = {}
        env.update(state)
        env.update(feeds)
        rng = RngStream(jax.random.fold_in(rng_key, jnp.asarray(step, jnp.uint32)))
        trace_block(block, env, rng)
        fetches = []
        for name in fetch_names:
            if name not in env:
                raise KeyError(
                    "fetch target %r was not produced by the program" % name
                )
            fetches.append(env[name])
        # Every donated state input must reappear as an output (XLA
        # aliases unchanged ones straight through); otherwise the Scope
        # would be left holding donated (invalidated) buffers.
        out_names = set(state_in) | set(state_out)
        new_state = {n: env[n] for n in out_names if n in env}
        return tuple(fetches), new_state

    return stepfn


def make_loop_fn(stepfn, slice_feeds=None):
    """First-step-unrolled fori_loop wrapper shared by Executor and
    ParallelExecutor: (feeds, state, rng_key, step0, n) -> the LAST
    step's (fetches, state), with n a traced int32. The first step runs
    outside the loop to fix the carry structure (fetch shapes/dtypes)
    without a separate trace; the per-step RNG folds step0+i exactly as
    n successive single-step calls would. `slice_feeds(feeds, i)`
    selects per-iteration feeds (reader windows); None = loop-invariant.
    """
    sf = slice_feeds if slice_feeds is not None else (lambda feeds, i: feeds)

    def loopfn(feeds, state, rng_key, step0, n):
        step0 = jnp.asarray(step0, jnp.uint32)
        fetches, st = stepfn(sf(feeds, 0), state, rng_key, step0)

        def body(i, carry):
            _, s = carry
            return stepfn(sf(feeds, i), s, rng_key,
                          step0 + jnp.asarray(i, jnp.uint32))

        return jax.lax.fori_loop(1, n, body, (fetches, st))

    return loopfn


def _on_place(method):
    """Run `method` with the executor's device as JAX's default, so what
    it traces, compiles, uploads and executes lands on the PLACE the
    executor was built with (and dispatch that asks
    ``framework.scope.current_device`` sees that device)."""

    @functools.wraps(method)
    def wrapped(self, *args, **kwargs):
        with jax.default_device(self._device):
            return method(self, *args, **kwargs)

    return wrapped


class Executor:
    """Runs Programs on ONE device: the one `place` names, resolved at
    construction — ``Executor(TPUPlace())`` raises where no TPU is
    visible, ``Executor(CPUPlace())`` runs on the host even where a chip
    is attached, and no place means JAX's default device.

    check_nan_inf=True (or env PADDLE_TPU_CHECK_NAN_INF=1) validates
    every fetch and updated state var for NaN/Inf after each run — the
    reference's FLAGS_check_nan_inf debug mode (framework/operator.cc)."""

    def __init__(self, place: Optional[Place] = None,
                 check_nan_inf: Optional[bool] = None,
                 opt_level: Optional[int] = None):
        self.place = place if place is not None else default_place()
        self._device = self.place.jax_device()
        if check_nan_inf is None:
            check_nan_inf = os.environ.get("PADDLE_TPU_CHECK_NAN_INF", "0") == "1"
        self.check_nan_inf = check_nan_inf
        # optimizing transpiler (transpiler/passes/): 0 = off, 1 = exact
        # structural passes, 2 = + conv_bn fold + feed bucketization.
        # Explicit arg wins over the PADDLE_TPU_OPT env knob.
        if opt_level is None:
            from .transpiler.passes import opt_level_from_env

            opt_level = opt_level_from_env(0)
        self.opt_level = int(opt_level)
        import weakref

        try:
            cache_cap = int(os.environ.get("PADDLE_TPU_COMPILE_CACHE_MAX",
                                           256))
        except ValueError:
            cache_cap = 256
        self._cache = _CompileCache(cache_cap)
        # persistent executable store (warm start): a fresh process
        # deserializes executables a previous run compiled instead of
        # paying trace + XLA compile before step 1. PADDLE_TPU_AOT_CACHE=0
        # turns this executor back into a memory-only compiler.
        self._disk = _aot.AotDiskCache()
        # second tier: jax's own persistent compilation cache, placed by
        # the same rule as the AOT tier (aot_cache.compile_cache_dir)
        _aot.enable_compile_cache()
        # label for this executor's prefetch-depth gauge series: the gauge
        # is process-global, so two executors writing an unlabeled series
        # would overwrite each other (sum the series for process truth)
        self._obs_exe = "exe%d" % next(_EXE_IDS)
        # weak keys for the same reason as _steps below: _cache entries
        # pin their program via _Compiled.program, but this cache holds
        # no such ref, so an id-keyed entry could outlive its program
        # and be served to a new one at the same address
        self._read_ops = weakref.WeakKeyDictionary()
        # per-program compile/execute core (serving.engine.Engine):
        # feed-conversion plan + AOT key derivation + the
        # load-or-compile acquisition path, SHARED with the inference
        # Predictor so the two can never diverge — weak keys for the
        # same id-reuse reason as _read_ops
        self._engines = weakref.WeakKeyDictionary()
        # per-PROGRAM step counters (the RNG stream fold): running one
        # program (e.g. startup) must not advance another program's
        # stochastic-op stream, or the same training program draws
        # different dropout masks depending on what else this Executor
        # ran before — and can never be parity-tested against a
        # ParallelExecutor, whose counter is program-bound from step 0.
        # Weak keys: a dead program's counter must die with it, never be
        # inherited by a new program allocated at the same address
        self._steps = weakref.WeakKeyDictionary()
        # per-program prefetched reader window (run_loop double-buffer):
        # the NEXT window's batches, already stacked and device_put, so
        # its host->device transfer overlaps the CURRENT window's device
        # execution — the device-side buffering the reference gets from
        # create_double_buffer_reader_op.cc. Raw batches ride along so a
        # mismatched next call (different steps / program version, or a
        # plain run()) can push them back and lose nothing. Staging only
        # pays when the next window's size is PREDICTABLE, so it engages
        # once two consecutive run_loop calls use the same `steps`
        # (_last_loop_steps below) — alternating sizes would waste a
        # full window transfer per call.
        self._reader_prefetch = weakref.WeakKeyDictionary()
        self._last_loop_steps = weakref.WeakKeyDictionary()
        self._last_step = 0  # most recent step index (error messages)
        self._seed = 0
        self._base_keys: Dict = {}

    # -- compilation -----------------------------------------------------
    @staticmethod
    def _check_feed_shapes(program: Program, feed_sig, only_names=None):
        """Fail fast with the variable name when a feed's shape can't
        match its declaration (wrong rank, or a static dim mismatch);
        otherwise the error surfaces deep inside some consuming op's
        trace. Runs only on compile (a changed shape is a cache miss).
        `only_names` restricts the check to user-supplied feeds —
        reader-op injected batches may legitimately diverge from their
        declared shape (a partial final batch just recompiles)."""
        gb = program.global_block()
        for name, shape, _dtype in feed_sig:
            if only_names is not None and name not in only_names:
                continue
            var = gb._find_var_recursive(name)
            declared = getattr(var, "shape", None) if var is not None else None
            if not declared:
                continue
            declared = tuple(declared)
            ok = len(declared) == len(shape) and all(
                d in (-1, None) or d == s for d, s in zip(declared, shape))
            if not ok:
                raise ValueError(
                    "feed %r has shape %s but the program declares %s "
                    "(-1 = any); fix the feed or the layers.data "
                    "declaration" % (name, tuple(shape), declared))

    def _verify_and_analyze(self, program: Program, feed_sig, scope: Scope,
                            user_feed_names=None, fetch_names=()):
        """Shared pre-compile prologue for _compile/_compile_loop: feed
        shape check, static program verification (SURVEY aux: race-
        detection equivalent — hard errors raise with op context, write-
        once findings only warn), state analysis, and the missing-
        persistable check.

        PADDLE_TPU_VERIFY=1 upgrades the def-use verifier to the FULL
        static analyzer (analysis/: whole-program shape/dtype inference,
        TPU static-shape + recompile-risk + dead-code lints) pre-trace:
        errors raise with op provenance, warnings warn.
        PADDLE_TPU_VERIFY=strict raises on warnings too."""
        feed_names = tuple(n for n, _, _ in feed_sig)
        self._check_feed_shapes(program, feed_sig, user_feed_names)
        from .analysis import analyze_program, enforce, verify_mode

        mode = verify_mode()
        if mode:
            enforce(analyze_program(program, feed_names=feed_names,
                                    fetch_names=fetch_names),
                    strict=(mode == "strict"))
        else:
            for kind, msg in verify_program(program, feed_names):
                if kind == "write-once":
                    warnings.warn("program verifier: " + msg)
        state_in, state_out = analyze_state(program, set(feed_names))
        # state vars written before ever being read (pure init, e.g. startup
        # programs) need no input value
        missing = [n for n in state_in if scope.find_var(n) is None]
        if missing:
            raise RuntimeError(
                "persistable variables %s have no value in scope; run the "
                "startup program first" % (missing,)
            )
        return state_in, state_out

    def _compile(self, program: Program, feed_sig, fetch_names, scope: Scope,
                 user_feed_names=None) -> _Compiled:
        began = time.perf_counter()
        state_in, state_out = self._verify_and_analyze(
            program, feed_sig, scope, user_feed_names,
            fetch_names=fetch_names)

        stepfn = build_step_fn(program, fetch_names, state_in, state_out)
        fn = jax.jit(stepfn, donate_argnums=(1,))
        exe, hlo = self._aot_compile(
            fn, program, feed_sig, fetch_names, state_in, state_out, scope,
            loop=False, kind="run", began=began)
        return _Compiled(exe, state_in, state_out, fetch_names, program,
                         fp=obs.program_fp(program), hlo=hlo,
                         lazy=exe is fn)

    def _compile_loop(self, program: Program, feed_sig, fetch_names,
                      scope: Scope, per_step_names: frozenset,
                      user_feed_names=None) -> _Compiled:
        """Like _compile, but the executable runs `n` training steps in ONE
        XLA while-loop: (feeds, state, rng_key, step0, n) -> (last fetches,
        final state). `n` is a traced int32, so one compilation serves any
        step count for feed-only programs. Feeds named in `per_step_names`
        carry a leading n-sized axis and are sliced per iteration (reader
        batches); that leading dim is a static shape, so reader programs
        compile once per distinct window length.

        Host<->device interaction per call is one dispatch + one fetch no
        matter how many steps run (the reference
        gets the same effect from double_buffer readers + multi-iteration
        C++ executor loops, e.g. ParallelExecutor::Run batches)."""
        began = time.perf_counter()
        state_in, state_out = self._verify_and_analyze(
            program,
            # per-step feeds are validated against their per-iteration shape
            [(n, s[1:] if n in per_step_names else s, d)
             for n, s, d in feed_sig],
            scope, user_feed_names, fetch_names=fetch_names)

        stepfn = build_step_fn(program, fetch_names, state_in, state_out)

        def slice_feeds(feeds, i):
            return {
                k: (jax.lax.dynamic_index_in_dim(v, i, keepdims=False)
                    if k in per_step_names else v)
                for k, v in feeds.items()
            }

        fn = jax.jit(make_loop_fn(stepfn, slice_feeds), donate_argnums=(1,))
        exe, hlo = self._aot_compile(
            fn, program, feed_sig, fetch_names, state_in, state_out, scope,
            loop=True, per_step_names=per_step_names, kind="loop",
            began=began)
        return _Compiled(exe, state_in, state_out, fetch_names, program,
                         fp=obs.program_fp(program), hlo=hlo,
                         lazy=exe is fn)

    @staticmethod
    def _avals_for(feed_sig, state_in, scope, loop=False):
        """Abstract call signature of the step/loop fn — what explicit
        ``fn.lower`` needs instead of concrete first-call args: feeds from
        the feed signature, state from the scope values' shapes/dtypes,
        the RNG key aval, the uint32 step, and (loop only) the traced
        int32 step count."""
        feeds_aval = {n: jax.ShapeDtypeStruct(tuple(s), np.dtype(d))
                      for n, s, d in feed_sig}
        state_aval = {}
        for n in state_in:
            val = scope.find_var(n)
            arr = (val if hasattr(val, "shape") and hasattr(val, "dtype")
                   else np.asarray(val))
            state_aval[n] = jax.ShapeDtypeStruct(tuple(arr.shape),
                                                 np.dtype(arr.dtype))
        args = [feeds_aval, state_aval,
                jax.eval_shape(lambda: jax.random.PRNGKey(0)),
                jax.ShapeDtypeStruct((), np.uint32)]
        if loop:
            args.append(jax.ShapeDtypeStruct((), np.int32))
        return args

    def _aot_compile(self, fn, program: Program, feed_sig, fetch_names,
                     state_in, state_out, scope, *, loop: bool, kind: str,
                     began: float,
                     per_step_names: frozenset = frozenset()):
        """Acquire the executable through the persistent disk tier:
        explicit ``lower → compile`` AOT (donation set on `fn` is
        preserved through lowering AND serialization), with the compiled
        executable stored under a key that covers everything that shapes
        it (see aot_cache.env_fingerprint). Returns ``(callable, hlo)``:
        an executable ``Engine.acquire`` loaded or compiled and recorded
        (``began``, the caller's clock when it started on the program,
        gives the record its ``build_ms``) and None, or the lazy `fn`
        itself, whose first call is its acquisition, and the `hlo` that
        ``observe_run`` adds to that record.

        Failure contract: a disabled cache or an un-abstractable
        signature falls back to the lazy ``jax.jit`` path unchanged;
        trace/compile errors PROPAGATE (they are the same program errors
        the lazy path would raise on first call); disk I/O problems are
        absorbed (counted) by AotDiskCache."""
        if not self._disk.enabled:
            return fn, self._hlo_compile_stats(fn, feed_sig, state_in,
                                               scope, loop=loop)
        eng = self._engine_for(program)
        try:
            args = self._avals_for(feed_sig, state_in, scope, loop=loop)
            # the state SIGNATURE (not just names) keys the cache: scope
            # values nearly always follow the program's declarations, but
            # an executable compiled against different state shapes/dtypes
            # must be unreachable, not a call-time XLA arity error
            state_sig = tuple(sorted(
                (n, tuple(a.shape), str(a.dtype))
                for n, a in args[1].items()))
            # key derivation lives in serving.engine.Engine (the layout —
            # incl. the deliberate ABSENCE of program._version — is
            # documented on Engine.key_fields and shared with Predictor)
            key = eng.key("loop" if loop else "step", feed_sig, fetch_names,
                          state_sig, tuple(state_out),
                          tuple(sorted(per_step_names)))
        except Exception:
            # an aval we can't build (exotic state value) must never
            # block execution: lazy jit handles it like before
            return fn, self._hlo_compile_stats(fn, feed_sig, state_in,
                                               scope, loop=loop)

        def lower():
            try:
                return fn.lower(*args)
            except TraceError as e:
                self._rethrow_with_provenance(
                    program, e, feed_names=tuple(n for n, _, _ in feed_sig),
                    fetch_names=tuple(fetch_names))

        # the trace/XLA split comes free on the explicit AOT path, and
        # the cost estimates for the asking (the lazy path needs opt-in
        # _hlo_compile_stats to pay for either)
        compiled, _path, _timings = eng.acquire(
            kind, key, lower,
            meta=eng.meta("loop" if loop else "step", feed_sig, fetch_names),
            cost=obs.TIMELINE.hlo_cost_enabled(), counts_compile=False,
            build_ms=(time.perf_counter() - began) * 1e3)
        return compiled, None

    def _hlo_compile_stats(self, fn, feed_sig, state_in, scope, loop=False):
        """Opt-in (``observability.TIMELINE.set_hlo_cost(True)``): lower +
        compile the jitted fn explicitly on abstract avals so the compile
        timeline event can split trace time from XLA compile time and
        carry the executable's cost-analysis FLOPs/bytes estimates. Only the
        LAZY-jit fallback path (disk tier disabled) uses this — it pays
        one extra compile per cache miss, which is why it is off by
        default; the AOT path gets the same split for free. Returns a
        dict for timeline.record_compile, or None."""
        if not obs.TIMELINE.hlo_cost_enabled():
            return None
        try:
            args = self._avals_for(feed_sig, state_in, scope, loop=loop)
            t0 = time.perf_counter()
            lowered = fn.lower(*args)
            t1 = time.perf_counter()
            compiled = lowered.compile()
            t2 = time.perf_counter()
            out = {"trace_ms": (t1 - t0) * 1e3, "xla_ms": (t2 - t1) * 1e3}
            cost = obs.hlo_cost_stats(compiled)
            if cost:
                out.update(cost)
            return out
        except Exception:  # measurement must never break compilation
            return None

    @staticmethod
    def _rethrow_with_provenance(program: Program, e: TraceError,
                                 feed_names=(), fetch_names=()):
        """Re-render a trace-time failure with the static analyzer's
        per-op provenance: the TraceError already names the failing op;
        the analyzer adds the statically-inferred input/output shapes and
        dtypes plus any findings it has for that op (and the rest of the
        program), so the user sees the IR-level cause instead of a bare
        JAX exception."""
        from .analysis import explain_trace_error

        try:
            note = explain_trace_error(program, e, feed_names=feed_names,
                                       fetch_names=fetch_names)
        except Exception:  # post-mortem must never mask the real error
            note = None
        if note:
            err = TraceError("%s\n%s" % (e, note))
            err.__dict__.update({k: v for k, v in e.__dict__.items()
                                 if k.startswith("pt_")})
            raise err from e
        raise e

    @staticmethod
    def _has_nan_inf(val) -> bool:
        arr = np.asarray(val)
        if np.issubdtype(arr.dtype, np.floating):
            return not np.isfinite(arr).all()
        if str(arr.dtype) in ("bfloat16", "float8_e4m3fn", "float8_e5m2"):
            # ml_dtypes extension floats are not np.floating subtypes
            return not np.isfinite(arr.astype(np.float32)).all()
        return False

    @staticmethod
    def _profiler_fence(fetches, new_state):
        """Wait until the dispatched step has really executed (measured
        on a v5e, PR 21: the step fenced this way and fenced by a host
        read take the same time)."""
        jax.block_until_ready((fetches, new_state))

    def _check_nan_inf(self, fetch_names, fetches, new_state):
        bad = []
        for name, val in zip(fetch_names, fetches):
            if self._has_nan_inf(val):
                bad.append("fetch %r" % name)
        for name, val in new_state.items():
            if self._has_nan_inf(val):
                bad.append("var %r" % name)
        if bad:
            raise FloatingPointError(
                "NaN/Inf detected after step %d in: %s (check_nan_inf mode)"
                % (self._last_step, ", ".join(bad)))

    # -- shared run plumbing ---------------------------------------------
    def _next_steps(self, program: Program, n: int) -> int:
        """Reserve `n` step indices on `program`'s OWN stream and return
        the first; see the _steps comment in __init__."""
        cur = self._steps.get(program, 0)
        self._steps[program] = cur + n
        self._last_step = cur + n - 1
        return cur

    def program_steps(self, program: Program) -> int:
        """Steps executed on `program`'s own stream — the per-step RNG
        fold position. Checkpoint it (checkpoint/ResumableLoop does) so
        a resumed run replays the exact stochastic-op stream (dropout
        masks, sampling) the uninterrupted run would have drawn."""
        return self._steps.get(program, 0)

    def set_program_steps(self, program: Program, n: int):
        """Restore `program`'s step stream position (the inverse of
        ``program_steps``, for sample-exact resume)."""
        self._steps[program] = int(n)

    def _read_ops_for(self, program: Program, gb):
        """(Static) read-op list, cached per program version so the hot
        path does not rescan every op each step."""
        entry = self._read_ops.get(program)
        if entry is None or entry[0] != program._version:
            entry = (program._version,
                     [op for op in gb.ops if op.type == "read"])
            self._read_ops[program] = entry
        return entry[1]

    def _engine_for(self, program: Program):
        """This program's shared compile/execute core (one per program,
        weak-keyed). The disk handle is refreshed on every access so a
        caller that swaps ``self._disk`` (tests point it at scratch
        dirs) is honored by engines built earlier."""
        from .serving.engine import Engine

        eng = self._engines.get(program)
        if eng is None:
            eng = Engine(program, disk=self._disk)
            self._engines[program] = eng
        eng.disk = self._disk
        return eng

    def _feed_var_for(self, program: Program, gb, name: str):
        """Declared Variable behind a feed name, memoized per (program,
        version) in the program's Engine (see Engine.feed_var for the
        negative-lookup contract) — feed dtype coercion needs the
        declaration every call, but it only changes when the program
        does, so on a steady serving/training loop this is a dict hit."""
        return self._engine_for(program).feed_var(name)

    def _maybe_optimize(self, program: Program, scope: Scope, feed_names,
                        fetch_names) -> Program:
        """The PADDLE_TPU_OPT step: swap in the Engine-memoized
        optimized twin. All downstream machinery (compile caches, AOT
        keys, RNG step streams, reader prefetch slots) keys on the twin
        itself, so optimized and original executables coexist."""
        if self.opt_level <= 0:
            return program
        return self._engine_for(program).optimized(
            scope=scope, feed_names=tuple(feed_names),
            fetch_names=tuple(fetch_names), level=self.opt_level)

    @staticmethod
    def _bucketize_feeds(program: Program, feed_arrays):
        """Apply a bucketize stamp (transpiler/passes/bucketize.py) at
        the feed boundary: pad every stamped feed's batch axis with zero
        rows up to the next power of two, so the feed SIGNATURE — what
        the compile/AOT caches key on — is the bucket, not the raw batch
        size. Returns the real row count to slice fetches back to, or
        None when the stamp doesn't apply to this call (feeds missing,
        row counts disagreeing across feeds — the call then runs at its
        raw signature, still correct)."""
        bkt = getattr(program, "_bucketize", None)
        if not bkt:
            return None
        names = bkt.get("feeds") or ()
        rows = set()
        for name in names:
            arr = feed_arrays.get(name)
            if arr is None or getattr(arr, "ndim", 0) < 1:
                return None
            rows.add(int(arr.shape[0]))
        if len(rows) != 1:
            return None
        from .transpiler.passes import next_pow2

        n = rows.pop()
        bucket = next_pow2(n)
        if bucket != n:
            for name in names:
                arr = np.asarray(feed_arrays[name])
                pad = np.zeros((bucket - n,) + arr.shape[1:], arr.dtype)
                feed_arrays[name] = np.concatenate([arr, pad], axis=0)
        return n

    @staticmethod
    def _slice_bucketized(program: Program, fetch_names, outs, n):
        """Slice batch-carrying fetches back to the real row count (the
        stamp lists which fetches carry the feed batch axis)."""
        if n is None:
            return outs
        sliced = set(getattr(program, "_bucketize", {}).get("fetches", ()))
        return [o[:n] if name in sliced else o
                for name, o in zip(fetch_names, outs)]

    @staticmethod
    def _holder_for(gb, op):
        rvar = gb._find_var_recursive(op.input("Reader")[0])
        holder = getattr(rvar, "_reader_holder", None)
        if holder is None:
            raise RuntimeError(
                "reader variable %r has no bound pipeline; build it "
                "with fluid.layers.py_reader/open_recordio_file"
                % op.input("Reader")[0])
        return holder

    @staticmethod
    def _next_batch(holder):
        """Pull the next reader batch, honoring batches a previous
        run_loop window pushed back (partial-shape boundary)."""
        buf = getattr(holder, "_ptpu_pushback", None)
        if buf:
            return buf.pop(0)
        # note: the executor does NOT auto-start the pipeline. File
        # readers lazy-start on first next(); py_reader requires the
        # explicit reader.start() per epoch (reference semantics).
        return holder.next()

    @staticmethod
    def _push_back(holder, batch):
        buf = getattr(holder, "_ptpu_pushback", None)
        if buf is None:
            buf = []
            holder._ptpu_pushback = buf
        buf.insert(0, batch)

    def _pull_reader_window(self, gb, read_ops, steps):
        """Pull up to `steps` aligned batches from every read op.
        Returns (op_windows, k, eof_exc): op_windows is a list of
        (op, holder, batches[:k], holder_epoch) — batches beyond the
        common window k are already pushed back (multi-reader skew
        realignment; k == 0 pushes ALL pulls back so an EOF on one
        reader costs the others nothing). holder_epoch snapshots the
        holder's reset/start generation so a later flush can tell these
        batches belong to the CURRENT epoch. eof_exc is the EOFException
        that closed the window early, or None."""
        from .io.reader import EOFException  # local: io imports executor

        t_pull = time.perf_counter()
        op_windows = []
        eof_exc = None
        for op in read_ops:
            holder = self._holder_for(gb, op)
            out_names = op.output("Out")
            batches = []
            for _ in range(steps):
                try:
                    b = self._next_batch(holder)
                except EOFException as e:
                    # tracebackless copy: the exception may be STORED in
                    # the prefetch slot until the next call raises it,
                    # and a live traceback pins the whole calling frame
                    # chain (run_loop's locals — including the consumed
                    # window's batch views) in a refcount CYCLE only the
                    # cyclic GC would free. A zero-copy DataLoader slot
                    # held hostage by that cycle starves its worker.
                    eof_exc = e.with_traceback(None)
                    break
                if batches and any(
                        np.shape(b[o]) != np.shape(batches[0][o])
                        for o in out_names):
                    # shape boundary (e.g. partial final batch): close
                    # the window here, keep the batch for the next call
                    self._push_back(holder, b)
                    break
                batches.append(b)
            op_windows.append((op, holder, batches,
                               getattr(holder, "_ptpu_epoch", 0)))
        k = min(len(b) for _, _, b, _e in op_windows) if op_windows else 0
        for _op, holder, batches, _e in op_windows:
            for b in reversed(batches[k:]):
                self._push_back(holder, b)
            del batches[k:]
        # input-starvation accounting: host time blocked on the reader
        # pipeline before this window could dispatch (compare against
        # step latency to tell input-bound from compute-bound)
        obs.READER_PULL_MS.inc((time.perf_counter() - t_pull) * 1e3,
                               kind="loop")
        return op_windows, k, eof_exc

    def _stack_reader_window(self, gb, op_windows, k, stage):
        """Stack each reader output into a (k, ...) per-step feed.
        int64/float64 are canonicalized the way jax would anyway (x64
        off), so the feed signature is identical whether the window is
        host numpy or device-staged. stage=True additionally device_puts
        each stack — an ASYNC transfer, which is the whole point: issued
        right after the current window's dispatch, it rides the link
        while the device is busy computing."""
        feeds = {}
        for op, _holder, batches, _epoch in op_windows:
            for out_name in op.output("Out"):
                var = gb._find_var_recursive(out_name)
                arr = np.stack(
                    [np.asarray(_as_feed_array(b[out_name], var))
                     for b in batches[:k]])
                if not jax.config.jax_enable_x64:
                    if arr.dtype == np.int64:
                        arr = arr.astype(np.int32)
                    elif arr.dtype == np.float64:
                        arr = arr.astype(np.float32)
                feeds[out_name] = (jax.device_put(arr, self._device) if stage
                                   else arr)
        return feeds

    def _flush_reader_prefetch(self, program, slot=None):
        """Return a consumed-but-unused prefetch window to its holders
        (raw batches, original order) — called whenever the prefetched
        shape can't be used: different steps, new program version, a
        plain run(), or cache-off mode. Pass `slot` when it was already
        popped. Batches from a holder whose reset()/start() epoch moved
        on are DROPPED, not pushed back: they belong to the finished
        epoch (same contract as the _ptpu_pushback clear in
        layers.io._make_reader_var)."""
        if slot is None:
            slot = self._reader_prefetch.pop(program, None)
        if slot is None:
            return
        obs.READER_PREFETCH_EVENTS.inc(event="flushed")
        obs.READER_PREFETCH_DEPTH.set(len(self._reader_prefetch),
                                          exe=self._obs_exe)
        for _op, holder, batches, epoch in reversed(slot["op_windows"]):
            if getattr(holder, "_ptpu_epoch", 0) != epoch:
                continue  # stale epoch: discard
            for b in reversed(batches):
                self._push_back(holder, b)

    def _gather_state(self, compiled, scope):
        state = {}
        for name in compiled.state_in_names:
            val = scope.find_var(name)
            if val is None:
                raise RuntimeError(
                    "persistable variable %r has no value in scope; run the "
                    "startup program first" % name
                )
            state[name] = val
        return state

    def _rng_for(self, program):
        seed = program.random_seed if program.random_seed else self._seed
        if seed not in self._base_keys:
            self._base_keys[seed] = jax.random.PRNGKey(seed)
        return self._base_keys[seed]

    def _finish(self, compiled, fetches, new_state, scope, return_numpy):
        for name, val in new_state.items():
            scope.set_var(name, val)
        if self.check_nan_inf:
            self._check_nan_inf(compiled.fetch_names, fetches, new_state)
        if return_numpy:
            return [np.asarray(v) for v in fetches]
        return list(fetches)

    # -- public API ------------------------------------------------------
    @_on_place
    def run(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict] = None,
        fetch_list: Optional[Sequence] = None,
        feed_var_name: str = "feed",
        fetch_var_name: str = "fetch",
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
        use_program_cache: bool = True,
    ):
        if program is None:
            program = default_main_program()
        if scope is None:
            scope = global_scope()
        feed = feed or {}
        fetch_list = list(fetch_list or [])
        fetch_names = tuple(_fetch_name(f) for f in fetch_list)
        program = self._maybe_optimize(program, scope, feed, fetch_names)

        gb = program.global_block()
        feed_arrays = {}
        for name, value in feed.items():
            var = self._feed_var_for(program, gb, name)
            feed_arrays[name] = _as_feed_array(value, var)
        # reader-op pipeline: pull the next staged batch for every `read`
        # op and inject its outputs as this step's feeds (reference:
        # operators/reader/read_op.cc pulling from the ReaderHolder).
        # Raises io.reader.EOFException when the pipeline is exhausted.
        # A window run_loop prefetched but never consumed goes back to
        # the holders first, so this step sees batches in pipeline order.
        self._flush_reader_prefetch(program)
        run_read_ops = self._read_ops_for(program, gb)
        if run_read_ops:
            t_pull = time.perf_counter()
            for op in run_read_ops:
                holder = self._holder_for(gb, op)
                batch = self._next_batch(holder)
                for out_name in op.output("Out"):
                    var = self._feed_var_for(program, gb, out_name)
                    feed_arrays[out_name] = _as_feed_array(batch[out_name],
                                                           var)
            obs.READER_PULL_MS.inc((time.perf_counter() - t_pull) * 1e3,
                                   kind="run")
        # bucketize stamp (opt level 2): pad the dynamic batch axis to
        # its pow2 bucket BEFORE the signature is derived — churny batch
        # sizes collapse onto one compile-cache/AOT-cache entry
        bkt_rows = self._bucketize_feeds(program, feed_arrays)
        feed_sig = tuple(
            (name, arr.shape, str(arr.dtype)) for name, arr in sorted(feed_arrays.items())
        )

        key = (id(program), program._version, feed_sig, fetch_names)
        compiled = self._cache.get(key) if use_program_cache else None
        if use_program_cache:
            profiler.record_cache(compiled is not None)
            (obs.CACHE_HITS if compiled is not None else obs.CACHE_MISSES
             ).inc(kind="run", tier="memory", program=obs.program_fp(program))
        first_run = compiled is None
        if compiled is None:
            compiled = self._compile(program, feed_sig, fetch_names, scope,
                                     user_feed_names=frozenset(feed))
            if use_program_cache:
                self._cache.put(key, compiled)

        state = self._gather_state(compiled, scope)
        rng_key = self._rng_for(program)
        step = np.uint32(self._next_steps(program, 1))

        profiling = profiler.is_profiling()
        # a device fence per step serializes the async dispatch pipeline,
        # so only the profiler window / opt-in timeline device-time mode
        # pays it; unfenced wall time is dispatch (+compile on first run)
        fence = profiling or obs.TIMELINE.device_time_enabled()
        t0 = time.perf_counter()
        try:
            fetches, new_state = compiled.fn(feed_arrays, state, rng_key,
                                             step)
        except TraceError as e:
            # lazy-jit path (disk tier off): the first call traces; give
            # its failures the same analyzer post-mortem as the AOT path
            self._rethrow_with_provenance(
                program, e, feed_names=tuple(feed_arrays),
                fetch_names=fetch_names)
        if fence:
            self._profiler_fence(fetches, new_state)
        wall = time.perf_counter() - t0
        if profiling:
            # jax.jit is lazy: trace + XLA compile all happen inside the
            # FIRST call, so bill that call to a separate event
            label = ("trace+compile+run" if first_run else "run")
            profiler.record_event(
                "%s/program_%x" % (label, id(program) & 0xFFFF), wall)
        obs.observe_run(
            "run", wall, steps=1, program=compiled.fp, compiled=first_run,
            lazy=compiled.lazy, hlo=compiled.hlo if first_run else None,
            feed_bytes=obs.nbytes_of(feed_arrays.values()),
            fetch_bytes=obs.nbytes_of(fetches),
            device_ms=wall * 1e3 if fence else None)
        outs = self._finish(compiled, fetches, new_state, scope,
                            return_numpy)
        return self._slice_bucketized(program, fetch_names, outs, bkt_rows)

    @_on_place
    def run_loop(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict] = None,
        fetch_list: Optional[Sequence] = None,
        steps: int = 1,
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
        use_program_cache: bool = True,
        per_step_feeds: Optional[Sequence[str]] = None,
    ) -> List:
        """Run up to `steps` consecutive training steps as ONE device-side
        XLA while-loop and return the LAST executed step's fetches.

        Semantically equivalent to calling run() `steps` times — same RNG
        sequence (the per-step seed folds the running step counter), same
        final state — but with exactly one host->device dispatch and one
        device->host fetch regardless of `steps`: it
        removes per-step dispatch overhead (the reference achieves the same
        with double_buffer readers feeding a C++ executor loop).

        Feeds are loop-invariant (the same batch every step), except names
        listed in `per_step_feeds`: those must carry a leading `steps`-sized
        axis (one stacked upload) and are sliced per iteration on device —
        the way to run a window of DIFFERENT batches per step. Programs with
        reader ops get the same treatment automatically: a window of batches
        is pulled up front, stacked, and sliced per iteration. The
        window closes early (k < steps, still trained and returned) when the
        pipeline hits EOF — the NEXT call then raises EOFException, so the
        usual catch-and-reset epoch loop sees every batch — or when a batch
        changes shape (partial final batch); the odd-shaped batch is pushed
        back for the next call. Each distinct window length k compiles its
        own executable (the stacked leading dim is a static shape); the
        feed-only path compiles once for any `steps`.

        Reader windows are DOUBLE-BUFFERED across calls: after dispatching
        window N, the executor pulls window N+1 and device_puts it
        asynchronously, so its host->device transfer overlaps window N's
        device execution (the reference's create_double_buffer_reader_op
        behavior). A next call with different `steps`, a changed program,
        or a plain run() pushes the prefetched batches back untouched.
        PADDLE_TPU_READER_PREFETCH=0 disables it.
        """
        if steps < 1:
            raise ValueError("run_loop needs steps >= 1, got %d" % steps)
        if program is None:
            program = default_main_program()
        if scope is None:
            scope = global_scope()
        feed = feed or {}
        fetch_list = list(fetch_list or [])
        fetch_names = tuple(_fetch_name(f) for f in fetch_list)
        # same optimize step as run(); the bucketize stamp stays dormant
        # here (run_loop windows are already shape-stable by contract)
        program = self._maybe_optimize(program, scope, feed, fetch_names)

        per_step_names = set(per_step_feeds or ())
        unknown = per_step_names - set(feed)
        if unknown:
            raise ValueError(
                "per_step_feeds %s are not in the feed dict" % sorted(unknown))
        gb = program.global_block()
        feed_arrays = {}
        for name, value in feed.items():
            var = self._feed_var_for(program, gb, name)
            if name in per_step_names:
                arr = np.asarray(value)
                if arr.ndim == 0 or arr.shape[0] != steps:
                    raise ValueError(
                        "per-step feed %r must carry a leading steps-sized "
                        "axis (%d), got shape %s"
                        % (name, steps, arr.shape))
                # validate/cast each slice against the declared var like a
                # normal feed, then restack
                feed_arrays[name] = np.stack(
                    [np.asarray(_as_feed_array(a, var)) for a in arr])
            else:
                feed_arrays[name] = _as_feed_array(value, var)

        # reader ops: a window of up to `steps` batches per reader, so the
        # whole window uploads in one transfer and the loop body slices it
        # on device. The window comes from the prefetch slot when the
        # previous run_loop call staged it (its device_put then overlapped
        # that call's execution), else from a fresh pull here.
        read_ops = self._read_ops_for(program, gb)
        if read_ops and per_step_names:
            # checked BEFORE any pull so a failed call consumes nothing
            raise NotImplementedError(
                "per_step_feeds cannot be combined with reader-op "
                "programs (the reader window length may truncate below "
                "`steps`, desynchronizing the stacked feeds)")
        eof_exc = None
        prefetch_on = (use_program_cache and os.environ.get(
            "PADDLE_TPU_READER_PREFETCH", "1") != "0"
            # stage ahead only once the window size proves stable: the
            # first call (or a size change) can't predict the next
            # window, and a wrong guess costs a full wasted transfer
            and self._last_loop_steps.get(program) == steps)
        self._last_loop_steps[program] = steps
        if read_ops:
            slot = self._reader_prefetch.pop(program, None)
            if slot is not None and (
                    slot["version"] != program._version
                    or slot["steps"] != steps or not prefetch_on
                    or any(getattr(h, "_ptpu_epoch", 0) != e
                           for _o, h, _b, e in slot["op_windows"])):
                # unusable (shape mismatch, or a reset() started a new
                # epoch): restore still-current batches, pull fresh below
                self._flush_reader_prefetch(program, slot)
                slot = None
            if slot is not None and slot["k"] == 0:
                raise slot["eof"]  # prefetch found the pipeline exhausted
            if slot is not None:
                obs.READER_PREFETCH_EVENTS.inc(event="used")
                obs.READER_PREFETCH_DEPTH.set(len(self._reader_prefetch),
                                          exe=self._obs_exe)
                window_feeds, k, eof_exc = (slot["feeds"], slot["k"],
                                            slot["eof"])
            else:
                op_windows, k, eof_exc = self._pull_reader_window(
                    gb, read_ops, steps)
                if k == 0:
                    raise eof_exc  # exhausted before the window started
                window_feeds = self._stack_reader_window(
                    gb, op_windows, k, stage=False)
            for out_name, arr in window_feeds.items():
                feed_arrays[out_name] = arr
                per_step_names.add(out_name)
            effective_steps = k
        else:
            effective_steps = steps
        # window-length distribution: mass below `steps` = truncation on
        # the reader path (EOF / shape boundary), the run_loop per-window
        # stat
        obs.RUN_LOOP_WINDOW_STEPS.observe(effective_steps)
        feed_sig = tuple(
            (name, arr.shape, str(arr.dtype))
            for name, arr in sorted(feed_arrays.items())
        )

        key = ("loop", id(program), program._version, feed_sig, fetch_names,
               frozenset(per_step_names))
        compiled = self._cache.get(key) if use_program_cache else None
        if use_program_cache:
            profiler.record_cache(compiled is not None)
            (obs.CACHE_HITS if compiled is not None else obs.CACHE_MISSES
             ).inc(kind="loop", tier="memory",
                   program=obs.program_fp(program))
        first_run = compiled is None
        if compiled is None:
            compiled = self._compile_loop(
                program, feed_sig, fetch_names, scope,
                frozenset(per_step_names), user_feed_names=frozenset(feed))
            if use_program_cache:
                self._cache.put(key, compiled)

        state = self._gather_state(compiled, scope)
        rng_key = self._rng_for(program)
        step0 = np.uint32(self._next_steps(program, effective_steps))

        profiling = profiler.is_profiling()
        fence = profiling or obs.TIMELINE.device_time_enabled()
        t0 = time.perf_counter()
        try:
            fetches, new_state = compiled.fn(
                feed_arrays, state, rng_key, step0,
                np.int32(effective_steps))
        except TraceError as e:
            self._rethrow_with_provenance(
                program, e, feed_names=tuple(feed_arrays),
                fetch_names=fetch_names)
        if fence:
            self._profiler_fence(fetches, new_state)
        wall = time.perf_counter() - t0
        if profiling:
            label = ("trace+compile+run_loop" if first_run else "run_loop")
            profiler.record_event(
                "%s/program_%x" % (label, id(program) & 0xFFFF), wall)
        obs.observe_run(
            "loop", wall, steps=effective_steps, program=compiled.fp,
            compiled=first_run, lazy=compiled.lazy,
            hlo=compiled.hlo if first_run else None,
            feed_bytes=obs.nbytes_of(feed_arrays.values()),
            fetch_bytes=obs.nbytes_of(fetches),
            device_ms=wall * 1e3 if fence else None)
        if read_ops and prefetch_on and eof_exc is None:
            # stage the NEXT window now, while the device is still
            # executing this one: the host pull/stack and the async
            # device_put transfer hide under the current window's compute
            # + the caller's fence instead of serializing before the next
            # dispatch. An EOF mid-pull is remembered: k>0 means the next
            # call trains the short window (contract), k==0 means the
            # next call must raise. ANY other reader error is deferred
            # the same way — window N already executed, and raising here
            # would lose its state update and fetches; the error belongs
            # to the call that would have consumed the broken batch.
            try:
                nwin, nk, neof = self._pull_reader_window(
                    gb, read_ops, steps)
                self._reader_prefetch[program] = {
                    "version": program._version, "steps": steps, "k": nk,
                    "eof": neof, "op_windows": nwin,
                    "feeds": (self._stack_reader_window(
                        gb, nwin, nk, stage=True) if nk else None),
                }
                obs.READER_PREFETCH_EVENTS.inc(event="staged")
            except Exception as e:  # noqa: BLE001 — deferred, not dropped
                import traceback as _tb

                # tracebackless for the same frame-cycle reason as the
                # _pull_reader_window EOF capture — but a REAL error's
                # diagnostics must survive the deferral, so the formatted
                # original traceback rides along as the __cause__ (plain
                # string payload: no frame objects, no cycle)
                if e.__traceback__ is not None and e.__cause__ is None:
                    e.__cause__ = RuntimeError(
                        "original traceback (deferred from reader "
                        "prefetch):\n" + "".join(_tb.format_exception(
                            type(e), e, e.__traceback__)).rstrip())
                self._reader_prefetch[program] = {
                    "version": program._version, "steps": steps, "k": 0,
                    "eof": e.with_traceback(None), "op_windows": [],
                    "feeds": None,
                }
                obs.READER_PREFETCH_EVENTS.inc(event="error")
            obs.READER_PREFETCH_DEPTH.set(len(self._reader_prefetch),
                                          exe=self._obs_exe)
        return self._finish(compiled, fetches, new_state, scope, return_numpy)

    def close(self):
        self._cache.clear()
        self._reader_prefetch.clear()
        self._engines.clear()
        # retire this executor's gauge series so executor churn in a
        # long-lived process doesn't grow the registry without bound
        obs.READER_PREFETCH_DEPTH.remove(exe=self._obs_exe)
