"""ShardedPredictor: one model larger than a single device, served
under ``pjit`` over a tensor-parallel mesh.

The single-device ``inference.Predictor`` pins params to one device; a
model that does not fit stops there. This predictor reuses the
TRAINING-side machinery at inference (ROADMAP item 1): a
``parallel.mesh`` Mesh over the tp devices, a ``ShardingPlan`` from
``parallel.sharding.infer_tp_plan`` (megatron column/row rules when the
naming matches, the same alternation derived structurally otherwise),
and one ``jax.jit`` with in/out shardings — GSPMD inserts the
all-reduce after each row-parallel matmul exactly as it does for the
training ``ParallelExecutor``.

Surface contract: ``run`` / ``warm`` / ``feed_names`` / ``fetch_names``
match ``Predictor``, so ``PredictorServer`` (and therefore a fleet
worker — ``examples/serve.py --shard K``) hosts either interchangeably.
Sharded executables stay MEMORY-only: ``serialize_executable``
round-trips single-device executables, and a mesh executable would need
per-topology keys (the ParallelExecutor carries the same note), so the
disk tier is disabled on this predictor's Engine.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import observability as obs
from ..framework.scope import Scope
from ..framework.trace import RngStream, trace_block
from ..framework import trace as trace_mod
from ..runtime import aot_cache as _aot
from .engine import Engine

__all__ = ["ShardedPredictor"]


class ShardedPredictor:
    """``Predictor`` over a tensor-parallel device mesh.

    predictor = ShardedPredictor(model_dir, shard=2)
    outs = predictor.run({"img": batch})   # same contract as Predictor
    """

    def __init__(self, model_dir: str, shard: Optional[int] = None,
                 mesh: Optional[Mesh] = None,
                 plan=None, mp_axis: str = "mp", place=None):
        from .. import io as fluid_io
        from ..executor import Executor
        from ..parallel.mesh import make_mesh
        from ..parallel.sharding import infer_tp_plan

        self.model_dir = model_dir
        self._scope = Scope()
        exe = Executor(place)
        # the loader executor's own compiles must not touch the
        # training-side default disk cache (same rule as
        # Predictor(aot_cache=False))
        exe._disk.enabled = False
        self._program, self._feed_names, self._fetch_targets = (
            fluid_io.load_inference_model(model_dir, exe, scope=self._scope))
        self._fetch_names = [t.name for t in self._fetch_targets]
        if mesh is None:
            n = int(shard) if shard else jax.device_count()
            if n > jax.device_count():
                raise ValueError(
                    "shard=%d needs %d devices, only %d available"
                    % (n, n, jax.device_count()))
            mesh = make_mesh((n,), axis_names=(mp_axis,),
                             devices=jax.devices()[:n])
        self.mesh = mesh
        self.mp_axis = mp_axis
        self._plan = (plan if plan is not None
                      else infer_tp_plan(mesh, self._program,
                                         mp_axis=mp_axis))
        # shared core: feed plan + identity (the disk tier stays off —
        # sharded executables are memory-only, see module docstring)
        self._engine = Engine(
            self._program,
            disk=_aot.AotDiskCache(enabled=False),
            feed_names=self._feed_names, fetch_names=self._fetch_names)
        self._feed_plan = self._engine.feed_plan()
        self._compiled: Dict = {}
        self.traces = 0
        self._state_names, self._state = self._load_state()

    # -- state -------------------------------------------------------------
    def _load_state(self):
        from ..executor import analyze_state

        state_in, _ = analyze_state(self._program, set(self._feed_names))
        state = {}
        for n in state_in:
            val = self._scope.find_var(n)
            if val is None:
                raise RuntimeError(
                    "inference model is missing persistable %r" % n)
            arr = np.asarray(val)
            sharding = self._plan.sharding(n, shape=tuple(arr.shape))
            # params are resident SHARDED device state from load time:
            # each device holds only its plan slice of every weight —
            # this is what lets the model exceed one device's memory
            state[n] = jax.device_put(arr, sharding)
        return state_in, state

    # -- compilation -------------------------------------------------------
    def _step_fn(self):
        program = self._program
        fetch_names = self._fetch_names

        def fn(feeds, state):
            self.traces += 1
            env = dict(state)
            env.update(feeds)
            rng = RngStream(jax.random.PRNGKey(0))
            trace_block(program.global_block(), env, rng)
            return tuple(env[n] for n in fetch_names)

        return fn

    def _get_executable(self, feed_arrays):
        feed_sig = tuple((n, tuple(a.shape), str(a.dtype))
                         for n, a in sorted(feed_arrays.items()))
        fp = self._engine.fingerprint()
        if feed_sig in self._compiled:
            obs.CACHE_HITS.inc(kind="predict_sharded", tier="memory",
                               program=fp)
            return self._compiled[feed_sig]
        obs.CACHE_MISSES.inc(kind="predict_sharded", tier="memory",
                             program=fp)
        ts, began_under = time.time(), obs.tracing.current_phase()
        t0 = time.perf_counter()
        from ..executor import Executor

        Executor._check_feed_shapes(self._program, feed_sig)
        rep = NamedSharding(self.mesh, P())
        # serving feeds are replicated (batches are small and dynamic);
        # only the params shard — GSPMD propagates the tp pattern from
        # the state shardings through the whole computation
        in_shardings = (
            {n: rep for n, _s, _d in feed_sig},
            {n: self._state[n].sharding for n in self._state_names},
        )
        out_shardings = tuple(rep for _ in self._fetch_names)
        fn = jax.jit(self._step_fn(), in_shardings=in_shardings,
                     out_shardings=out_shardings)
        t1 = time.perf_counter()
        with trace_mod.mesh_context(self.mesh, self._plan):
            lowered = fn.lower(
                {n: jax.ShapeDtypeStruct(s, np.dtype(d))
                 for n, s, d in feed_sig},
                {n: jax.ShapeDtypeStruct(a.shape, a.dtype)
                 for n, a in self._state.items()})
            t2 = time.perf_counter()
            compiled = lowered.compile()
        t3 = time.perf_counter()
        # no disk tier here: a mesh executable is compiled every process
        obs.observe_acquire(
            "predict_sharded", "cold", (t3 - t0) * 1e3, program=fp, ts=ts,
            phase=began_under, compile_ms=(t3 - t1) * 1e3,
            build_ms=(t1 - t0) * 1e3, trace_ms=(t2 - t1) * 1e3,
            xla_ms=(t3 - t2) * 1e3)
        self._compiled[feed_sig] = compiled
        return compiled

    # -- pre-warm ----------------------------------------------------------
    def warm(self, batch_rows: int) -> bool:
        """Same contract as ``Predictor.warm``: compile the executable
        for a ``batch_rows``-row batch of the declared feed shapes (the
        PredictorServer bucket pre-warm); False when a declared shape
        makes the bucket signature unknowable."""
        feed_arrays = {}
        for name, var, want in self._feed_plan:
            shape = tuple(getattr(var, "shape", None) or ())
            if (not shape or shape[0] not in (-1, None)
                    or any(d is None or d < 0 for d in shape[1:])):
                return False
            feed_arrays[name] = np.zeros(
                (batch_rows,) + shape[1:], want or np.float32)
        self._get_executable(feed_arrays)
        return True

    # -- prediction --------------------------------------------------------
    def run(self, feed, return_numpy: bool = True,
            _obs_path: str = "direct") -> List[np.ndarray]:
        t0 = time.perf_counter()
        if isinstance(feed, (list, tuple)):
            feed = dict(zip(self._feed_names, feed))
        feed_arrays = self._engine.convert_feeds(feed, self._feed_plan)
        exe = self._get_executable(feed_arrays)
        outs = exe(feed_arrays, self._state)
        outs = ([np.asarray(o) for o in outs] if return_numpy
                else list(outs))
        first = next(iter(feed_arrays.values())) if feed_arrays else None
        rows = (first.shape[0] if first is not None and first.ndim else 1)
        obs.PREDICT_LATENCY_MS.observe((time.perf_counter() - t0) * 1e3,
                                       path=_obs_path)
        obs.PREDICT_REQUESTS.inc(path=_obs_path)
        obs.PREDICT_BATCH_ROWS.observe(rows, path=_obs_path)
        return outs

    predict = run  # api parity sugar

    @property
    def feed_names(self) -> List[str]:
        return list(self._feed_names)

    @property
    def fetch_names(self) -> List[str]:
        return list(self._fetch_names)
