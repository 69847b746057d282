"""ParallelExecutor: one traced step, partitioned over a device Mesh.

Reference: python/paddle/fluid/parallel_executor.py + paddle/fluid/framework/
details/* — the reference clones the graph per GPU, scatters the feed,
runs per-device SSA graphs and all-reduces gradients with NCCL.

TPU-native there is exactly ONE program: the same step function the
single-device Executor traces, jitted with sharding annotations over a
``jax.sharding.Mesh``. Feeds are split on the batch ("dp") axis, state
follows the ShardingPlan (replicated by default; tensor/sequence-parallel
specs for mp/sp plans), and XLA's SPMD partitioner inserts the gradient
all-reduce (and any tp collectives) on ICI — the NCCL graph rewrite is a
compiler pass here, not framework code.

Multi-host (the reference's num_trainers/trainer_id NCCL bootstrap) comes
from ``parallel.init_distributed()``: the mesh then spans every process and
each process feeds its local shard (jax.make_array_from_process_local_data).
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import observability as obs
from ..executor import analyze_state, build_step_fn, _as_feed_array, _fetch_name
from ..framework import trace as trace_mod
from ..framework.core import Program, default_main_program
from ..framework.scope import Scope, global_scope
from .mesh import default_mesh
from .sharding import ShardingPlan

__all__ = ["ParallelExecutor", "ExecutionStrategy", "BuildStrategy"]


class ExecutionStrategy:
    """API parity (reference exposes num_threads etc. for the SSA executor;
    scheduling is XLA's job here so these are accepted and ignored)."""

    def __init__(self):
        self.num_threads = 0
        self.allow_op_delay = False
        self.num_iteration_per_drop_scope = 1
        self.use_cuda = False


class BuildStrategy:
    """Reference's graph-build knobs. reduce_strategy/gradient_scale map to
    sharding choices; the rest are XLA's concern.

    TPU-native extension — pipeline parallelism from the SAME Program:
    ``pipeline_stages=S`` (with a mesh carrying a ``pipeline_axis`` of
    size S) slices the program's repeated-layer region into S stages via
    ``parallel.pipeline_program.plan_pipeline`` and runs it GPipe-style;
    feeds then carry ``pipeline_microbatches ×`` the declared batch in
    dim 0. This is the graph-partitioning capability of the reference's
    distribute/pipeline transpiler (reference:
    transpiler/distribute_transpiler.py:159) done as a structural pass
    instead of a ProgramDesc rewrite."""

    class ReduceStrategy:
        AllReduce = "AllReduce"
        Reduce = "Reduce"  # maps to reduce-scatter state sharding (ZeRO-ish)

    class GradientScaleStrategy:
        CoeffNumDevice = "CoeffNumDevice"
        One = "One"

    def __init__(self):
        self.reduce_strategy = BuildStrategy.ReduceStrategy.AllReduce
        self.gradient_scale_strategy = BuildStrategy.GradientScaleStrategy.CoeffNumDevice
        self.debug_graphviz_path = ""
        self.pipeline_stages = 0
        self.pipeline_microbatches = 1
        self.pipeline_axis = "pp"
        # "gpipe" (fill-drain) or "interleaved" (circular: each device
        # holds every S-th layer group, K x smaller pipeline bubble)
        self.pipeline_schedule = "gpipe"


class _ParCompiled:
    __slots__ = ("fn", "state_in_names", "state_out_names", "fetch_names")

    def __init__(self, fn, state_in_names, state_out_names, fetch_names):
        self.fn = fn
        self.state_in_names = state_in_names
        self.state_out_names = state_out_names
        self.fetch_names = fetch_names


class ParallelExecutor:
    """
    Args mirror the reference; TPU-specific extras:
        mesh: jax Mesh (default: 1-D "dp" mesh over every device).
        plan: ShardingPlan for state vars (default: all replicated —
            classic data parallelism). Pass megatron_transformer_plan(...)
            etc. for tensor/sequence parallel runs.
    use_cuda is accepted for source compatibility and ignored (the
    accelerator is whatever mesh devices are).
    """

    def __init__(
        self,
        use_cuda: bool = False,
        loss_name: Optional[str] = None,
        main_program: Optional[Program] = None,
        share_vars_from: Optional["ParallelExecutor"] = None,
        exec_strategy: Optional[ExecutionStrategy] = None,
        build_strategy: Optional[BuildStrategy] = None,
        num_trainers: int = 1,
        trainer_id: int = 0,
        scope: Optional[Scope] = None,
        mesh: Optional[Mesh] = None,
        plan: Optional[ShardingPlan] = None,
    ):
        self._program = main_program if main_program is not None else default_main_program()
        self.loss_name = loss_name
        if share_vars_from is not None:
            if not isinstance(share_vars_from, ParallelExecutor):
                raise TypeError("share_vars_from must be a ParallelExecutor")
            scope = share_vars_from._scope
            mesh = mesh or share_vars_from._mesh
            plan = plan or share_vars_from._plan
        self._scope = scope if scope is not None else global_scope()
        self._mesh = mesh if mesh is not None else default_mesh("dp")
        self._exec_strategy = exec_strategy or ExecutionStrategy()
        self._build_strategy = build_strategy or BuildStrategy()
        if plan is None:
            if self._build_strategy.reduce_strategy == BuildStrategy.ReduceStrategy.Reduce:
                # each device owns a slice of the optimizer state (ZeRO-1)
                from .sharding import zero_plan

                plan = zero_plan(self._mesh, self._program, axis=self._mesh.axis_names[0])
            else:
                plan = ShardingPlan(self._mesh)
        self._plan = plan
        if num_trainers > 1 and jax.process_count() == 1:
            raise RuntimeError(
                "num_trainers>1 requires the multi-host runtime: call "
                "paddle_tpu.parallel.init_distributed() first (the mesh "
                "then spans all %d trainers)" % num_trainers
            )
        self.num_trainers = num_trainers
        self.trainer_id = trainer_id
        self._cache: Dict = {}
        self._step = 0
        self._base_keys: Dict = {}
        self._owned_state = {"dp_owned_state_bytes": 0, "state_bytes": 0}

    @property
    def device_count(self) -> int:
        return self._mesh.size

    # -- compilation -----------------------------------------------------
    def _compile(self, feed_sig, fetch_names, loop=False) -> _ParCompiled:
        from ..executor import Executor

        program = self._program
        feed_names = tuple(n for n, _, _ in feed_sig)
        bs = self._build_strategy
        pp_stages = int(getattr(bs, "pipeline_stages", 0) or 0)
        if pp_stages < 2:
            # same fail-fast shape validation as the single-device executor
            # (all ParallelExecutor feeds are user-supplied)
            Executor._check_feed_shapes(program, feed_sig)
        else:
            # pipelined feeds carry M x dp x the declared batch in dim 0;
            # ranks and trailing dims still validate fail-fast
            gb = program.global_block()
            for name, shape, _dtype in feed_sig:
                var = gb._find_var_recursive(name)
                declared = getattr(var, "shape", None) if var is not None else None
                if not declared:
                    continue
                declared = tuple(declared)
                ok = len(declared) == len(shape) and all(
                    d in (-1, None) or d == s
                    for d, s in zip(declared[1:], shape[1:]))
                if not ok:
                    raise ValueError(
                        "feed %r has shape %s but the program declares %s "
                        "(dim 0 carries num_microbatches x dp x the "
                        "declared per-device microbatch under pipeline "
                        "parallelism; trailing dims must match)"
                        % (name, tuple(shape), declared))
        state_in, state_out = analyze_state(program, set(feed_names))
        missing = [n for n in state_in if self._scope.find_var(n) is None]
        if missing:
            raise RuntimeError(
                "persistable variables %s have no value in scope; run the "
                "startup program first" % (missing,)
            )
        if pp_stages >= 2:
            from .pipeline_program import (build_pipeline_step_fn,
                                           plan_pipeline)

            pplan = plan_pipeline(program, pp_stages)
            batch_axis = next(
                (a for a in self._plan.batch_axes
                 if a != bs.pipeline_axis and self._mesh.shape[a] > 1),
                None)
            stepfn = build_pipeline_step_fn(
                program, fetch_names, state_in, state_out, self._mesh,
                pplan, int(bs.pipeline_microbatches),
                pp_axis=bs.pipeline_axis, batch_axis=batch_axis,
                schedule=bs.pipeline_schedule)
        else:
            stepfn = build_step_fn(program, fetch_names, state_in, state_out)

        # the traced step may return fewer state vars than analyze_state
        # guesses (e.g. a persistable written only under a lax control-flow
        # branch never lands in the top-level env): eval_shape gives the
        # TRUE output pytree, so out_shardings always matches.
        feeds_aval = {
            name: jax.ShapeDtypeStruct(shape, np.dtype(dt))
            for name, shape, dt in feed_sig
        }
        state_aval = {}
        for n in state_in:
            val = self._scope.find_var(n)
            arr = val if hasattr(val, "shape") and hasattr(val, "dtype") else np.asarray(val)
            state_aval[n] = jax.ShapeDtypeStruct(tuple(arr.shape), arr.dtype)
        key_aval = jax.eval_shape(lambda: jax.random.PRNGKey(0))
        step_aval = jax.ShapeDtypeStruct((), np.uint32)
        with trace_mod.mesh_context(self._mesh, self._plan):
            _, out_state_aval = jax.eval_shape(stepfn, feeds_aval, state_aval,
                                               key_aval, step_aval)

        plan = self._plan
        feed_shardings = {
            name: plan.feed_sharding(len(shape)) for name, shape, _ in feed_sig
        }
        in_state_shardings = {
            n: plan.sharding(n, shape=tuple(state_aval[n].shape)) for n in state_in
        }
        out_state_shardings = {
            n: plan.sharding(n, shape=tuple(a.shape))
            for n, a in out_state_aval.items()
        }
        rep = plan.replicated()
        self._observe_owned_state(state_aval, in_state_shardings)

        if loop:
            # device-side multi-step loop (see Executor.run_loop): the same
            # stepfn — plain, or even the pipelined one — runs n times in
            # ONE XLA while-loop, with a traced step count. Feeds are
            # loop-invariant; the fold of step0+i keeps the RNG sequence
            # identical to n successive run() calls.
            from ..executor import make_loop_fn

            fn = jax.jit(
                make_loop_fn(stepfn),
                in_shardings=(feed_shardings, in_state_shardings, rep, rep,
                              rep),
                out_shardings=(
                    tuple(rep for _ in fetch_names),
                    out_state_shardings,
                ),
                donate_argnums=(1,),
            )
        else:
            fn = jax.jit(
                stepfn,
                in_shardings=(feed_shardings, in_state_shardings, rep, rep),
                out_shardings=(
                    tuple(rep for _ in fetch_names),
                    out_state_shardings,
                ),
                donate_argnums=(1,),
            )
        return _ParCompiled(fn, state_in, state_out, fetch_names)

    def _observe_owned_state(self, state_aval, shardings):
        """How far the plan gave each update to ONE data-parallel rank:
        bytes of the step's persistable state that a batch axis wider than
        1 splits (the update of such a variable runs on 1/dp of it; dp
        twins else run the same update on the same summed gradient), of
        all its bytes. Read by ``run_stats()`` and the gauge."""
        wide = {a for a in self._plan.batch_axes if self._mesh.shape[a] > 1}
        total = owned = 0
        for n, aval in state_aval.items():
            nbytes = int(np.prod(aval.shape)) * np.dtype(aval.dtype).itemsize
            total += nbytes
            axes = {a for ax in shardings[n].spec if ax is not None
                    for a in (ax if isinstance(ax, tuple) else (ax,))}
            if axes & wide:
                owned += nbytes
        self._owned_state = {"dp_owned_state_bytes": owned,
                             "state_bytes": total}
        fp = obs.program_fp(self._program)
        obs.DP_OWNED_STATE_BYTES.set(owned, program=fp, of="owned")
        obs.DP_OWNED_STATE_BYTES.set(total, program=fp, of="state")

    # -- feed assembly ---------------------------------------------------
    def _assemble_feed(self, feed, feed_dict) -> Dict[str, np.ndarray]:
        if feed is None:
            feed = feed_dict
        feed = feed or {}
        if isinstance(feed, (list, tuple)):
            # reference semantics: list of per-device dicts -> concat along
            # the batch dim and let the dp sharding scatter it back
            merged: Dict[str, List[np.ndarray]] = {}
            for d in feed:
                for k, v in d.items():
                    merged.setdefault(k, []).append(np.asarray(v))
            feed = {k: np.concatenate(vs, axis=0) for k, vs in merged.items()}
        gb = self._program.global_block()
        out = {}
        for name, value in feed.items():
            var = gb._find_var_recursive(name)
            arr = _as_feed_array(value, var)
            if arr.ndim and self._plan.batch_axes:
                n = int(np.prod([self._mesh.shape[a] for a in self._plan.batch_axes]))
                if arr.shape[0] % n != 0:
                    raise ValueError(
                        "feed %r batch dim %d is not divisible by the %d-way "
                        "data-parallel mesh" % (name, arr.shape[0], n)
                    )
            out[name] = arr
        return out

    def _globalize(self, name: str, arr, sharding: NamedSharding,
                   full_value: bool = False):
        """Host numpy / single-device array -> mesh-sharded jax.Array.

        Multi-process semantics differ by source: FEEDS are process-local
        shards (each trainer supplies its slice of the global batch, the
        reference's per-trainer feed), while STATE from the scope is the
        FULL value on every process (startup ran identically everywhere).
        full_value=True therefore slices per-device — required when a
        model axis (mp/pp) spans the process boundary, where treating the
        full param as 'this process's block' would double-count it."""
        if isinstance(arr, jax.Array) and arr.sharding == sharding:
            return arr
        if jax.process_count() > 1:
            npv = np.asarray(arr)
            if full_value:
                return jax.make_array_from_callback(
                    npv.shape, sharding, lambda idx: npv[idx])
            return jax.make_array_from_process_local_data(sharding, npv)
        return jax.device_put(arr, sharding)

    # -- public API ------------------------------------------------------
    def run(self, fetch_list: Sequence, feed=None, feed_dict=None,
            return_numpy=True, _steps=None):
        loop = _steps is not None
        steps = int(_steps or 1)
        fetch_names = tuple(_fetch_name(f) for f in fetch_list)
        feed_arrays = self._assemble_feed(feed, feed_dict)
        feed_sig = tuple(
            (name, arr.shape, str(arr.dtype)) for name, arr in sorted(feed_arrays.items())
        )
        key = (id(self._program), self._program._version, feed_sig,
               fetch_names, loop)
        fp = obs.program_fp(self._program)
        compiled = self._cache.get(key)
        first_run = compiled is None
        # tier=memory: sharded multi-device executables stay memory-only
        # (serialize_executable round-trips single-device executables; the
        # mesh path would need per-topology keys — see runtime/aot_cache)
        (obs.CACHE_HITS if compiled is not None else obs.CACHE_MISSES
         ).inc(kind="parallel", tier="memory", program=fp)
        if compiled is None:
            compiled = self._compile(feed_sig, fetch_names, loop=loop)
            self._cache[key] = compiled

        plan = self._plan
        state = {}
        for name in compiled.state_in_names:
            val = self._scope.find_var(name)
            if val is None:
                raise RuntimeError(
                    "persistable variable %r has no value in scope; run the "
                    "startup program first" % name
                )
            state[name] = self._globalize(
                name, val, plan.sharding(name, shape=getattr(val, "shape", None)),
                full_value=True,
            )
        feeds = {
            name: self._globalize(name, arr, plan.feed_sharding(arr.ndim))
            for name, arr in feed_arrays.items()
        }

        seed = self._program.random_seed
        if seed not in self._base_keys:
            self._base_keys[seed] = jax.random.PRNGKey(seed)
        step = np.uint32(self._step)
        self._step += steps

        # jit traces lazily inside the first call: distributed-capable
        # kernels (ring_attention) read the mesh from this context
        t0 = time.perf_counter()
        with trace_mod.mesh_context(self._mesh, self._plan):
            if loop:
                fetches, new_state = compiled.fn(feeds, state,
                                                 self._base_keys[seed], step,
                                                 np.int32(steps))
            else:
                fetches, new_state = compiled.fn(feeds, state,
                                                 self._base_keys[seed], step)
        obs.observe_run(
            "parallel", time.perf_counter() - t0, steps=steps, program=fp,
            compiled=first_run,
            feed_bytes=obs.nbytes_of(feed_arrays.values()),
            fetch_bytes=obs.nbytes_of(fetches))
        for name, val in new_state.items():
            self._scope.set_var(name, val)

        if return_numpy:
            return [np.asarray(v) for v in fetches]
        return list(fetches)

    def run_stats(self):
        """Run statistics for the mesh-parallel path — see module-level
        ``run_stats()``; the registry series are process-global, so every
        instance reports the same aggregate. ``dp_owned_state_bytes`` /
        ``state_bytes`` are this executor's, of the program it compiled
        last (``_observe_owned_state``; 0 / 0 before the first run)."""
        return dict(run_stats(), **self._owned_state)

    def program_steps(self, program=None) -> int:
        """RNG step-fold position (Executor.program_steps twin; a
        ParallelExecutor is bound to ONE program, so the argument is
        accepted only for signature compatibility with the checkpoint
        resume surface)."""
        return self._step

    def set_program_steps(self, program, n: int):
        """Restore the RNG step-fold position (sample-exact resume)."""
        self._step = int(n)

    def run_loop(self, fetch_list: Sequence, feed=None, steps: int = 1,
                 return_numpy=True):
        """Run `steps` consecutive steps as ONE device-side XLA while-loop
        and return the LAST step's fetches — Executor.run_loop for the
        mesh-parallel path (feeds are loop-invariant; same RNG sequence
        and final state as `steps` successive run() calls). Composes with
        every ShardingPlan, including pipeline parallelism: the whole
        pp tick loop becomes the loop body."""
        if steps < 1:
            raise ValueError("run_loop needs steps >= 1, got %d" % steps)
        return self.run(fetch_list, feed=feed, return_numpy=return_numpy,
                        _steps=steps)


def run_stats():
    """Aggregate {'steps', 'dispatches', 'mean_step_ms'} over every
    ParallelExecutor in the process, read from the observability
    registry (the same counters Executor feeds, ``kind="parallel"``).
    mean_step_ms is wall dispatch time over steps executed, so run_loop
    windows amortize exactly as they do on the device."""
    lat = obs.STEP_LATENCY_MS.stats(kind="parallel")
    steps = obs.STEPS_TOTAL.value(kind="parallel")
    return {
        "steps": int(steps),
        "dispatches": int(lat["count"]),
        "mean_step_ms": (lat["sum"] / steps) if steps else 0.0,
    }
