"""Cold-start vs warm-start: fresh-subprocess time-to-first-step.

Measures what the persistent AOT executable cache (runtime/aot_cache.py)
buys a FRESH process: each sample is a real subprocess that builds a
training program, runs the startup program, and executes the first
training step — cold (empty cache directory) or warm (directory primed
by a previous process). Cold and warm replicates are INTERLEAVED
(alternating A/B absorbs drift from CPU frequency/load), and one JSON line is emitted per config:

    {"bench": "coldstart", "config": "mlp", "cold_ttfs_s": [...],
     "warm_ttfs_s": [...], "cold_median_s": ..., "warm_median_s": ...,
     "warmstart_speedup": ..., ...}

``ttfs_s`` (time-to-first-step) = program build + startup run + first
training step, measured INSIDE the child after imports: interpreter +
jax import time is reported separately (``import_s``) because no
executable cache can help it and it would otherwise dilute the number
being measured. The fused-loop window compile (`run_loop`) is timed as
``loop_s`` on top.

Usage:
    JAX_PLATFORMS=cpu python tools/bench_coldstart.py \
        [--replicates 3] [--configs mlp,mlp-wide] [--loop-steps 4]

tests/test_bench_coldstart_smoke.py pins the line schema in tier-1.
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

SCHEMA = "bench_coldstart/1"

# config name -> (input dim, hidden widths, batch rows). Wider nets give
# XLA more to chew on, so the cold/warm gap grows with size.
CONFIGS = {
    "mlp": (64, (256, 256, 256), 32),
    "mlp-wide": (256, (1024, 1024, 1024, 1024), 64),
    "mlp-tiny": (8, (16,), 4),  # smoke-test sized
}


def _child(config: str, loop_steps: int):
    """One timed sample, printed as a single JSON line. Runs in a FRESH
    interpreter so every cost a restart pays (trace, XLA compile or
    deserialize, weight init) is inside the measurement."""
    t_proc = time.perf_counter()
    import jax  # noqa: F401 — the import being timed

    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu import layers, optimizer, observability as obs

    t_import = time.perf_counter()
    in_dim, widths, batch = CONFIGS[config]

    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope), fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = layers.data(name="x", shape=[in_dim])
            y = layers.data(name="y", shape=[1])
            h = x
            for w in widths:
                h = layers.fc(h, w, act="relu")
            loss = layers.mean(layers.square(layers.fc(h, 1) - y))
            optimizer.SGD(learning_rate=0.01).minimize(loss)
    t_build = time.perf_counter()

    rs = np.random.RandomState(0)
    feed = {"x": rs.rand(batch, in_dim).astype(np.float32),
            "y": rs.rand(batch, 1).astype(np.float32)}
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        t_startup = time.perf_counter()
        first = exe.run(main, feed=feed, fetch_list=[loss])[0]
        t_first = time.perf_counter()
        exe.run_loop(main, feed=feed, fetch_list=[loss], steps=loop_steps)
        t_loop = time.perf_counter()

    hits = sum(obs.CACHE_HITS.value(kind=k, tier="disk",
                                    program=obs.program_fp(p))
               for k in ("run", "loop") for p in (main, startup))
    misses = sum(obs.CACHE_MISSES.value(kind=k, tier="disk",
                                        program=obs.program_fp(p))
                 for k in ("run", "loop") for p in (main, startup))
    cold = sum(obs.AOT_COMPILE_MS.stats(path="cold", kind=k)["count"]
               for k in ("run", "loop"))
    warm = sum(obs.AOT_COMPILE_MS.stats(path="warm", kind=k)["count"]
               for k in ("run", "loop"))
    json.dump({
        "config": config,
        "import_s": t_import - t_proc,
        "build_s": t_build - t_import,
        "startup_s": t_startup - t_build,
        "first_step_s": t_first - t_startup,
        "loop_s": t_loop - t_first,
        "ttfs_s": t_first - t_import,
        "total_s": t_loop - t_proc,
        "first_loss": float(np.asarray(first).ravel()[0]),
        "disk_hits": hits,
        "disk_misses": misses,
        "cold_compiles": cold,
        "warm_loads": warm,
    }, sys.stdout)
    sys.stdout.write("\n")


def _run_child(config: str, cache_dir: str, loop_steps: int) -> dict:
    env = dict(os.environ,
               JAX_PLATFORMS=os.environ.get("JAX_PLATFORMS", "cpu"),
               PADDLE_TPU_AOT_CACHE_DIR=cache_dir,
               PADDLE_TPU_AOT_CACHE="1")
    # and keep jax's OWN persistent cache (the second tier) out of both
    # arms: an inherited JAX_COMPILATION_CACHE_DIR would warm the "cold"
    # children at the HLO level and understate the speedup
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child",
         "--config", config, "--loop-steps", str(loop_steps)],
        capture_output=True, text=True, timeout=1200, env=env, cwd=_REPO)
    if proc.returncode != 0:
        raise RuntimeError("coldstart child failed:\n" + proc.stderr[-4000:])
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _median(xs):
    xs = sorted(xs)
    n = len(xs)
    return xs[n // 2] if n % 2 else 0.5 * (xs[n // 2 - 1] + xs[n // 2])


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--config", default="mlp", help=argparse.SUPPRESS)
    ap.add_argument("--configs", default="mlp,mlp-wide",
                    help="comma-separated config names (%s)"
                         % ",".join(sorted(CONFIGS)))
    ap.add_argument("--replicates", type=int, default=3,
                    help="interleaved cold/warm pairs per config")
    ap.add_argument("--loop-steps", type=int, default=4,
                    help="run_loop window length timed after the first step")
    args = ap.parse_args()

    if args.child:
        _child(args.config, args.loop_steps)
        return

    results = []
    for config in [c for c in args.configs.split(",") if c]:
        if config not in CONFIGS:
            raise SystemExit("unknown config %r (have: %s)"
                             % (config, ", ".join(sorted(CONFIGS))))
        warm_dir = tempfile.mkdtemp(prefix="ptpu-coldstart-warm-")
        cold_dirs = []
        try:
            # prime the warm directory once (this sample is discarded:
            # it pays the compile that later warm runs reuse)
            prime = _run_child(config, warm_dir, args.loop_steps)
            cold, warm = [], []
            for _ in range(args.replicates):
                d = tempfile.mkdtemp(prefix="ptpu-coldstart-cold-")
                cold_dirs.append(d)
                cold.append(_run_child(config, d, args.loop_steps))
                warm.append(_run_child(config, warm_dir, args.loop_steps))
            bad_warm = [w for w in warm if w["warm_loads"] == 0]
            cold_med = _median([c["ttfs_s"] for c in cold])
            warm_med = _median([w["ttfs_s"] for w in warm])
            line = {
                "bench": "coldstart",
                "schema": SCHEMA,
                "config": config,
                "replicates": args.replicates,
                "loop_steps": args.loop_steps,
                "cold_ttfs_s": [round(c["ttfs_s"], 4) for c in cold],
                "warm_ttfs_s": [round(w["ttfs_s"], 4) for w in warm],
                "cold_median_s": round(cold_med, 4),
                "warm_median_s": round(warm_med, 4),
                "warmstart_speedup": round(cold_med / warm_med, 3)
                if warm_med else None,
                "cold_loop_median_s": round(
                    _median([c["loop_s"] for c in cold]), 4),
                "warm_loop_median_s": round(
                    _median([w["loop_s"] for w in warm]), 4),
                "import_median_s": round(_median(
                    [r["import_s"] for r in cold + warm]), 4),
                "prime_ttfs_s": round(prime["ttfs_s"], 4),
                "warm_used_cache": not bad_warm,
            }
            results.append(line)
            print(json.dumps(line), flush=True)
        finally:
            shutil.rmtree(warm_dir, ignore_errors=True)
            for d in cold_dirs:
                shutil.rmtree(d, ignore_errors=True)
    if results:
        speedups = [r["warmstart_speedup"] for r in results
                    if r["warmstart_speedup"]]
        print(json.dumps({
            "bench": "coldstart_summary",
            "schema": SCHEMA,
            "configs": [r["config"] for r in results],
            "min_speedup": min(speedups) if speedups else None,
            "max_speedup": max(speedups) if speedups else None,
        }), flush=True)


if __name__ == "__main__":
    main()
