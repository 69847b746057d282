"""Async-checkpoint overhead + preemption warm-restart time-to-first-step.

Two measurements per config, one JSON line per config (schema
``bench_resume/1``, pinned by tests/test_bench_resume_smoke.py):

1. **Overhead** (in-process, interleaved A/B): steps/s of a training
   loop with NO checkpointing vs the same loop with a ResumableLoop
   async-checkpointing every ``--step-interval`` batches
   (CheckpointManager background writer, max_pending=2). The timed
   window includes any save() blocking — a writer that can't keep up
   shows up as lost throughput, not as a hidden drain afterwards.
   ``overhead_frac`` = 1 - ckpt/plain (acceptance: < 0.05 at
   step_interval=10).

2. **Warm restart** (fresh subprocesses, the bench_coldstart
   methodology): a prime child trains + checkpoints (filling the AOT
   executable cache and the checkpoint dir), then interleaved restart
   children restore the newest checkpoint and run the first
   post-resume step — cold (EMPTY AOT cache: pays trace + XLA compile)
   vs warm (primed cache: deserializes). ``warm_restart_speedup`` =
   cold_median / warm_median (acceptance: >= 3x) — what a preempted
   job actually pays before its first post-resume step.

Usage:
    JAX_PLATFORMS=cpu python tools/bench_resume.py \
        [--configs mlp,deepfm] [--steps 60] [--step-interval 10] \
        [--replicates 3] [--restart-replicates 3]
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

SCHEMA = "bench_resume/1"

# config name -> builder parameters (see _build). Batches are sized like
# the bench.py training configs (production CTR/MLP batches), NOT toy
# sizes: the overhead measurement divides per-save cost by interval x
# step time, so an unrealistically light step overstates the overhead.
CONFIGS = {
    "mlp": {"kind": "mlp", "in_dim": 64, "widths": (512, 512, 512),
            "batch": 1024},
    "mlp-wide": {"kind": "mlp", "in_dim": 256,
                 "widths": (1024, 1024, 1024, 1024), "batch": 256},
    "deepfm": {"kind": "deepfm", "num_features": 10000, "num_fields": 10,
               "dense_dim": 13, "batch": 1024},
    "mlp-tiny": {"kind": "mlp", "in_dim": 8, "widths": (16,), "batch": 4},
}


def _build(config: str):
    """(main, startup, scope, feed, loss_name) for one config."""
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu import layers

    cfg = CONFIGS[config]
    rs = np.random.RandomState(0)
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope), fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            if cfg["kind"] == "mlp":
                x = layers.data(name="x", shape=[cfg["in_dim"]])
                y = layers.data(name="y", shape=[1])
                h = x
                for w in cfg["widths"]:
                    h = layers.fc(h, w, act="relu")
                loss = layers.mean(layers.square(layers.fc(h, 1) - y))
                feed = {"x": rs.rand(cfg["batch"], cfg["in_dim"])
                        .astype(np.float32),
                        "y": rs.rand(cfg["batch"], 1).astype(np.float32)}
            else:  # deepfm
                from paddle_tpu.models.deepfm import get_model

                loss, _prob, _feeds = get_model(
                    num_features=cfg["num_features"],
                    num_fields=cfg["num_fields"],
                    dense_dim=cfg["dense_dim"])
                feed = {
                    "feat_ids": rs.randint(
                        0, cfg["num_features"],
                        (cfg["batch"], cfg["num_fields"])).astype(np.int64),
                    "dense": rs.rand(cfg["batch"], cfg["dense_dim"])
                    .astype(np.float32),
                    "label": rs.randint(0, 2, (cfg["batch"], 1))
                    .astype(np.int64),
                }
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    return main, startup, scope, feed, loss.name


def _overhead(config: str, steps: int, step_interval: int,
              replicates: int):
    """Interleaved plain-vs-checkpointed steps/s, one pair per
    replicate. The two arms ALTERNATE order across replicates (CPU
    governors ramp frequency through a run, so a fixed order
    systematically flatters whichever arm goes second), and the async
    writer is drained UNTIMED between arms so a checkpoint tail never
    bleeds into the plain arm's window. Saves queued during the timed
    ckpt window still compete with the steps — that contention IS the
    overhead being measured."""
    import paddle_tpu as fluid
    from paddle_tpu.checkpoint import ResumableLoop

    main, startup, scope, feed, loss_name = _build(config)
    plain, ckpt, saves = [], [], 0
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for _ in range(5):  # compile + settle
            exe.run(main, feed=feed, fetch_list=[loss_name])

        def run_plain():
            t0 = time.perf_counter()
            for _ in range(steps):
                exe.run(main, feed=feed, fetch_list=[loss_name])
            plain.append(steps / (time.perf_counter() - t0))

        def run_ckpt():
            nonlocal saves
            ckdir = tempfile.mkdtemp(prefix="ptpu-bench-resume-ov-")
            try:
                loop = ResumableLoop(exe, main, ckdir, scope=scope,
                                     step_interval=step_interval,
                                     max_pending=2)
                t0 = time.perf_counter()
                for _ in range(steps):
                    exe.run(main, feed=feed, fetch_list=[loss_name])
                    loop.step_done()
                ckpt.append(steps / (time.perf_counter() - t0))
                loop.close()  # drain OUTSIDE the timed window
                saves = max(saves, loop.manager.latest() + 1)
            finally:
                shutil.rmtree(ckdir, ignore_errors=True)

        for rep in range(replicates):
            for arm in ((run_plain, run_ckpt) if rep % 2 == 0
                        else (run_ckpt, run_plain)):
                arm()
    return plain, ckpt, saves


# ---------------------------------------------------------------------------
# restart children
# ---------------------------------------------------------------------------


def _child(config: str, role: str, ckpt_dir: str, prime_steps: int,
           step_interval: int):
    """One fresh-process sample, one JSON line on stdout."""
    t_proc = time.perf_counter()
    import jax  # noqa: F401

    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu import observability as obs
    from paddle_tpu.checkpoint import ResumableLoop

    t_import = time.perf_counter()
    main, startup, scope, feed, loss_name = _build(config)
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        if role == "prime":
            exe.run(startup)
            loop = ResumableLoop(exe, main, ckpt_dir, scope=scope,
                                 step_interval=step_interval)
            for _ in range(prime_steps):
                exe.run(main, feed=feed, fetch_list=[loss_name])
                loop.step_done()
            loop.save_now(block=True)
            loop.close()
            out = {"role": role, "saved_serial": loop.manager.latest()}
        else:  # restart: restore newest checkpoint, run first step
            t0 = time.perf_counter()
            loop = ResumableLoop(exe, main, ckpt_dir, scope=scope,
                                 step_interval=step_interval)
            assert loop.resumed_meta is not None, "nothing to resume"
            t_restore = time.perf_counter()
            first = exe.run(main, feed=feed, fetch_list=[loss_name])[0]
            t_first = time.perf_counter()
            loop.close()
            warm = sum(obs.AOT_COMPILE_MS.stats(path="warm", kind=k)["count"]
                       for k in ("run", "loop"))
            cold = sum(obs.AOT_COMPILE_MS.stats(path="cold", kind=k)["count"]
                       for k in ("run", "loop"))
            out = {
                "role": role,
                "import_s": t_import - t_proc,
                "restore_s": t_restore - t0,
                "first_step_s": t_first - t_restore,
                "ttfs_s": t_first - t0,
                "first_loss": float(np.asarray(first).ravel()[0]),
                "resumed_global": loop.global_step,
                "warm_loads": warm,
                "cold_compiles": cold,
            }
    json.dump(out, sys.stdout)
    sys.stdout.write("\n")


def _run_child(config, role, ckpt_dir, cache_dir, prime_steps,
               step_interval):
    env = dict(os.environ,
               JAX_PLATFORMS=os.environ.get("JAX_PLATFORMS", "cpu"),
               PADDLE_TPU_AOT_CACHE_DIR=cache_dir,
               PADDLE_TPU_AOT_CACHE="1")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child",
         "--config", config, "--role", role, "--ckpt-dir", ckpt_dir,
         "--prime-steps", str(prime_steps),
         "--step-interval", str(step_interval)],
        capture_output=True, text=True, timeout=1200, env=env, cwd=_REPO)
    if proc.returncode != 0:
        raise RuntimeError("bench_resume child failed:\n"
                           + proc.stderr[-4000:])
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _median(xs):
    xs = sorted(xs)
    n = len(xs)
    return xs[n // 2] if n % 2 else 0.5 * (xs[n // 2 - 1] + xs[n // 2])


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--role", default="restart", help=argparse.SUPPRESS)
    ap.add_argument("--config", default="mlp", help=argparse.SUPPRESS)
    ap.add_argument("--ckpt-dir", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--configs", default="mlp,deepfm",
                    help="comma-separated config names (%s)"
                         % ",".join(sorted(CONFIGS)))
    ap.add_argument("--steps", type=int, default=60,
                    help="steps per overhead-measurement arm")
    ap.add_argument("--step-interval", type=int, default=10,
                    help="checkpoint cadence (batches)")
    ap.add_argument("--replicates", type=int, default=3,
                    help="interleaved plain/ckpt pairs (overhead)")
    ap.add_argument("--restart-replicates", type=int, default=3,
                    help="interleaved cold/warm restart pairs")
    ap.add_argument("--prime-steps", type=int, default=12,
                    help="training steps in the prime child")
    args = ap.parse_args()

    if args.child:
        _child(args.config, args.role, args.ckpt_dir, args.prime_steps,
               args.step_interval)
        return

    results = []
    for config in [c for c in args.configs.split(",") if c]:
        if config not in CONFIGS:
            raise SystemExit("unknown config %r (have: %s)"
                             % (config, ", ".join(sorted(CONFIGS))))
        plain, ckpt, saves = _overhead(config, args.steps,
                                       args.step_interval,
                                       args.replicates)
        plain_med, ckpt_med = _median(plain), _median(ckpt)
        # PAIRED per-replicate ratios: each (plain, ckpt) pair ran
        # back-to-back, so CPU frequency / load drift across the sweep
        # cancels inside the pair instead of polluting the medians
        paired_overhead = _median(
            [1.0 - c / p for p, c in zip(plain, ckpt)])

        work = tempfile.mkdtemp(prefix="ptpu-bench-resume-")
        ckpt_dir = os.path.join(work, "ck")
        warm_cache = os.path.join(work, "aot-warm")
        try:
            _run_child(config, "prime", ckpt_dir, warm_cache,
                       args.prime_steps, args.step_interval)
            cold, warm = [], []
            for i in range(args.restart_replicates):
                cold_cache = os.path.join(work, "aot-cold-%d" % i)
                cold.append(_run_child(config, "restart", ckpt_dir,
                                       cold_cache, args.prime_steps,
                                       args.step_interval))
                warm.append(_run_child(config, "restart", ckpt_dir,
                                       warm_cache, args.prime_steps,
                                       args.step_interval))
            cold_med = _median([c["ttfs_s"] for c in cold])
            warm_med = _median([w["ttfs_s"] for w in warm])
            line = {
                "bench": "resume",
                "schema": SCHEMA,
                "config": config,
                "steps": args.steps,
                "step_interval": args.step_interval,
                "replicates": args.replicates,
                "plain_steps_per_s": [round(v, 2) for v in plain],
                "ckpt_steps_per_s": [round(v, 2) for v in ckpt],
                "plain_median": round(plain_med, 2),
                "ckpt_median": round(ckpt_med, 2),
                "overhead_frac": round(paired_overhead, 4),
                "saves_per_arm": saves,
                "cold_ttfs_s": [round(c["ttfs_s"], 4) for c in cold],
                "warm_ttfs_s": [round(w["ttfs_s"], 4) for w in warm],
                "cold_median_s": round(cold_med, 4),
                "warm_median_s": round(warm_med, 4),
                "warm_restart_speedup": round(cold_med / warm_med, 3)
                if warm_med else None,
                "restore_median_s": round(_median(
                    [w["restore_s"] for w in warm]), 4),
                "warm_used_cache": all(w["warm_loads"] > 0 for w in warm),
                "resume_loaded_ckpt": all(
                    r["resumed_global"] > 0 for r in cold + warm),
            }
            results.append(line)
            print(json.dumps(line), flush=True)
        finally:
            shutil.rmtree(work, ignore_errors=True)

    if results:
        print(json.dumps({
            "bench": "resume_summary",
            "schema": SCHEMA,
            "configs": [r["config"] for r in results],
            "max_overhead_frac": max(r["overhead_frac"] for r in results),
            "min_warm_restart_speedup": min(
                r["warm_restart_speedup"] for r in results),
        }), flush=True)


if __name__ == "__main__":
    main()
