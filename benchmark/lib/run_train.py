"""Runner for mixes of kind `train`: `Executor.run` (or, on a mesh,
`ParallelExecutor.run`) once per step over a pool of seeded batches that
lives on the device, fetches left there, at most two steps in flight."""
from __future__ import annotations

import collections
import gc
import json
import os
import shutil
import time

import numpy as np

from . import compare, trace_reduce, weights

IN_FLIGHT = 2


def _host(tree):
    return {k: np.asarray(v) for k, v in tree.items()}


class Trainer:
    """The cell's training program on its device or mesh: `reset(seed)`
    runs the startup program and puts the seed's weights in the scope,
    `step(feed, fetch)` runs one step, `put(a)` stages a feed."""

    def __init__(self, cfg, mix, chips, devices, model):
        import jax

        import paddle_tpu as fluid

        self.cfg, self.model = cfg, model
        self.built = built = model.build_train(cfg, mix)
        self.main_p, self.loss = built["main"], built["loss"]
        self.specs = [(p.name, tuple(p.shape), np.float32)
                      for p in self.main_p.all_parameters()]
        self.dev = dev = devices[0]
        self.place = (fluid.TPUPlace() if dev.platform == "tpu"
                      else fluid.CPUPlace())
        self.mesh = self.plan = self.exe = None
        if chips > 1:
            from paddle_tpu.parallel import make_mesh

            self.mesh = make_mesh(list(cfg["mesh"]["shape"]),
                                  tuple(cfg["mesh"]["axes"]),
                                  devices=list(devices))
            self.plan = model.plan(cfg, self.mesh)
            self.put = lambda a: jax.device_put(
                a, self.plan.feed_sharding(a.ndim))
        else:
            self.put = lambda a: jax.device_put(a, dev)

    def reset(self, seed: int):
        """A fresh scope: startup, then the seed's weights. Returns the
        weights (the scope holds the same buffers: use them before the
        first step donates them)."""
        import jax

        import paddle_tpu as fluid

        # let go of the last seed's state first: two copies do not fit
        if self.exe is not None and self.mesh is None:
            self.exe.close()
        self.exe = self.scope = self.step = None
        gc.collect()
        self.scope = scope = fluid.Scope()
        built, main_p, loss = self.built, self.main_p, self.loss
        with fluid.scope_guard(scope):
            if self.mesh is not None:
                from paddle_tpu.parallel import ParallelExecutor

                # the startup program as a mesh program: every state
                # variable is born sharded as the plan has it
                ParallelExecutor(main_program=built["startup"], scope=scope,
                                 mesh=self.mesh, plan=self.plan).run([])
                shard = {n: self.plan.sharding(n, shape=s)
                         for n, s, _ in self.specs}
                w = weights.seeded_weights(self.specs, seed,
                                           self.model.init_rule,
                                           shardings=shard)
                exe = self.exe = ParallelExecutor(
                    loss_name=loss.name, main_program=main_p, scope=scope,
                    mesh=self.mesh, plan=self.plan)
                self.step = lambda feed, fetch: exe.run(
                    fetch, feed=feed, return_numpy=False)
            else:
                exe = self.exe = fluid.Executor(self.place)
                exe.run(built["startup"])
                w = weights.seeded_weights(self.specs, seed,
                                           self.model.init_rule,
                                           device=self.dev)

                def step(feed, fetch):
                    with fluid.scope_guard(scope):
                        return exe.run(main_p, feed=feed, fetch_list=fetch,
                                       return_numpy=False)

                self.step = step
            for n in w:
                scope.set_var(n, w[n])
        jax.block_until_ready(w)
        return w


def run(ctx) -> dict:
    import jax

    cfg, mix = ctx.cfg, ctx.mix
    model = ctx.module("models", cfg["builder"])
    ref = ctx.module("reference", cfg["reference"])
    ann = jax.profiler.TraceAnnotation
    grads = model.check_grads(cfg)
    t0 = time.time()
    tr = Trainer(cfg, mix, ctx.cell["chips"], ctx.devices, model)
    built, loss, put = tr.built, tr.loss, tr.put
    w = tr.reset(ctx.seed)
    step = tr.step
    ctx.log("weights", seconds=time.time() - t0, params=sum(
        int(np.prod(s)) for _, s, _ in tr.specs))
    pool_np, check_np, ref_inputs = model.train_pool(cfg, mix, ctx.seed)
    pool = [{k: put(v) for k, v in f.items()} for f in pool_np]
    check_feed = {k: put(v) for k, v in check_np.items()}

    # -- correct: the reference first, on the seed's weights, before
    # the program's first step donates them
    t0 = time.time()
    depth = model.depth(cfg, "train")
    want = _host(ref.train_check(w, ref_inputs, cfg, depth, grads))
    del w
    ctx.log("reference", seconds=time.time() - t0,
            loss=float(want["loss"]))
    t0 = time.time()
    got = step(check_feed, [loss] + [g + "@GRAD" for g in grads])
    got = dict(zip(["loss"] + grads, (np.asarray(g) for g in got)))
    ctx.log("check_step", seconds=time.time() - t0,
            loss=float(got["loss"]))
    limits = cfg["check"]["train"]
    checks = compare.Checks()
    loss0 = float(np.asarray(got["loss"]).reshape(()))
    checks.add("loss_rel_err", abs(loss0 - float(want["loss"]))
               / abs(float(want["loss"])), limits["loss_rel_err"])
    for g in grads:
        checks.add("grad_rel_l2 " + g, compare.rel_l2(got[g], want[g]),
                   limits["grads"][g])
    del got, want

    # -- warm-up: the timed step's own executable (fetch: loss alone)
    t0 = time.time()
    n_warm = 2
    for i in range(n_warm):
        jax.block_until_ready(step(pool[i % len(pool)], [loss]))
    ctx.log("warmup", seconds=time.time() - t0)

    losses = []
    cursor = [n_warm]  # the pool goes on where the warm-up stopped

    def drive(seconds=None, steps=None):
        """Steps until `seconds` have passed or `steps` were queued;
        (steps, elapsed) with the last step finished."""
        pending = collections.deque()
        n, t_begin = 0, time.perf_counter()
        while True:
            if steps is not None and n >= steps:
                break
            if seconds is not None and (
                    time.perf_counter() - t_begin) >= seconds:
                break
            with ann("bench.exe_run"):
                out = step(pool[cursor[0] % len(pool)], [loss])
            cursor[0] += 1
            pending.append(out[0])
            losses.append(out[0])
            n += 1
            if len(pending) > IN_FLIGHT:
                with ann("bench.wait_step"):
                    jax.block_until_ready(pending.popleft())
            if n == 3:
                ctx.sample_memory()  # mid-stream, programs loaded
        with ann("bench.wait_last"):
            jax.block_until_ready(list(pending))
        return n, time.perf_counter() - t_begin

    trace_numbers, trace = {}, None
    trace_steps = int(mix.get("trace_steps", 6))
    ctx.watch.active = True
    t_window = time.time()
    n_steps, elapsed = drive(seconds=ctx.seconds)
    ctx.watch.active = False
    if ctx.trace:
        prof = os.path.join(ctx.work_dir, "profile")
        shutil.rmtree(prof, ignore_errors=True)
        os.makedirs(prof, exist_ok=True)
        # keep the device fed while the profiler starts
        warm = [step(pool[i % len(pool)], [loss])[0] for i in range(2)]
        jax.profiler.start_trace(prof)
        drive(steps=trace_steps)
        jax.profiler.stop_trace()
        del warm
        trace = trace_reduce.load_xplane(prof)
        trace_numbers = trace_reduce.reduce_trace(trace)
        with open(os.path.join(ctx.out_dir, "trace_summary.json"),
                  "w") as f:
            json.dump({"numbers": trace_numbers,
                       "lines": trace["lines"],
                       "host_events": len(trace["host"]),
                       "samples": trace_reduce.sample_events(trace)}, f, indent=1)
    with ann("bench.fetch_loss"):
        vals = np.array([float(np.asarray(v).reshape(()))
                         for v in losses])
    bad = int((~np.isfinite(vals)).sum())
    band = float(limits["window_loss_band"])
    off = float(np.nanmax(np.abs(vals - loss0))) if len(vals) else 0.0
    checks.add("window_loss_max_abs_from_step0", off, band)
    checks.add("window_nonfinite_losses", bad, 0)
    unit = built["units_per_step"]
    rate_name = mix["rate_metric"]
    return {
        "correct": checks.ok, "attempted": len(vals), "failed": bad,
        "end_to_end": {rate_name: n_steps * unit / elapsed,
                       "setup_s": t_window - ctx.t_start},
        "steps": n_steps, "elapsed_s": elapsed, "units_per_step": unit,
        "trace": trace, "trace_numbers": trace_numbers, "spans": [],
        "counts": {}, "notes": {"steps": n_steps, "elapsed_s": elapsed,
                                "loss_first": float(vals[0]),
                                "loss_last": float(vals[-1])},
    }
