"""The part of the benchmark that no cell owns: arguments, the cell's
files found by name, the device check, the compile watch, the result
line. A mix's `kind` names the runner (`run_train`, `run_serve`); a
configuration names its builder and its reference; a per-layer metric is
a file under `layer_metrics/` named after it."""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

# where a run writes what is too long for its output, and what it may
# throw away; benchmark/tests points both somewhere else
OUT_ROOT = os.path.join(ROOT, "chiprun_out", "benchmark")
WORK_ROOT = os.path.join(ROOT, ".bench_cache")

# the platform a run must find; benchmark/tests lifts it, run.py cannot
REQUIRE_PLATFORM = "tpu"


def setup_env(root: str) -> str:
    """One fixed cache directory inside the checkout, set before jax is
    imported: jax's persistent cache, and with it the program's AOT tier
    (`aot_cache.compile_cache_dir` follows JAX_COMPILATION_CACHE_DIR)."""
    cache = os.path.join(root, ".xla_cache")
    os.makedirs(cache, exist_ok=True)  # jax does not create it in time
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    # cache the small programs too (admission scatters, weight making):
    # every run is a new process and would compile them again
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    # no eviction: with a size limit in the environment jax's LRU scan
    # failed every write on the chip machine (a `-cache` file without
    # its `-atime` twin) and nothing was ever cached
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ["PADDLE_TPU_AOT_CACHE_MAX_BYTES"] = "0"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    return cache


def load_cell(root: str, name: str):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit("unknown workload %r; BENCHMARK.json has %s"
                         % (name, sorted(cells)))
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic",
                           cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    return bench, cell, cfg, mix


def metrics_of(bench: dict, group: str, cell_name: str):
    """Entries of `end_to_end` / `per_layer` that this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def load_layer_metric(name: str):
    path = os.path.join(BENCH_DIR, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark.layer_metrics." + name.replace(".", "_").replace(
            "-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class CompileWatch:
    """Counts programs jax builds or loads while `active`: a shape that
    was not warmed up. In the window that is an error of the run."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.active and event == self.EVENT:
            self.count += 1


class Ctx:
    """What a runner is given."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.memory_sampled = 0

    def sample_memory(self):
        """Live buffers plus what the loaded programs hold reserved for
        their temporaries, on the fullest chip, now. `peak_bytes_in_use`
        alone leaves the temporaries out (PERF.md, PR 21 and PR 23)."""
        for d in self.devices:
            st = d.memory_stats() or {}
            self.memory_sampled = max(self.memory_sampled, int(
                st.get("bytes_in_use", 0)) + int(st.get("bytes_reserved", 0)))

    def module(self, group: str, name: str):
        return importlib.import_module("benchmark.%s.%s" % (group, name))

    def log(self, msg, **fields):
        line = {"msg": msg}
        line.update(fields)
        print(json.dumps(line, sort_keys=True, default=str), flush=True)


def main(argv, t_start: float) -> int:
    ap = argparse.ArgumentParser(prog="benchmark/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cache_dir = setup_env(ROOT)
    bench, cell, cfg, mix = load_cell(ROOT, args.workload)

    import jax

    from . import peaks as peaks_mod

    devs = jax.devices()
    if REQUIRE_PLATFORM and devs[0].platform != REQUIRE_PLATFORM:
        print("benchmark: JAX's default device is %r, not a %s; refusing "
              "to run" % (devs[0], REQUIRE_PLATFORM), file=sys.stderr)
        return 2
    if len(devs) < cell["chips"]:
        print("benchmark: cell %s needs %d chip(s), JAX sees %d"
              % (cell["name"], cell["chips"], len(devs)), file=sys.stderr)
        return 2
    try:
        peaks = peaks_mod.peaks_for(devs[0].device_kind)
    except peaks_mod.UnknownDevice as e:
        print("benchmark: %s" % e, file=sys.stderr)
        return 2

    out_dir = os.path.join(OUT_ROOT, cell["name"])
    os.makedirs(out_dir, exist_ok=True)
    ctx = Ctx(root=ROOT, bench=bench, cell=cell, cfg=cfg, mix=mix,
              seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
              devices=devs[:cell["chips"]], peaks=peaks, t_start=t_start,
              cache_dir=cache_dir, out_dir=out_dir,
              work_dir=os.path.join(WORK_ROOT, cell["name"]),
              watch=CompileWatch())
    ctx.log("start", workload=cell["name"], seed=args.seed,
            seconds=args.seconds, trace=args.trace, jax=jax.__version__,
            device_kind=devs[0].device_kind, device_count=len(devs),
            cache_dir=cache_dir)
    kind = mix["kind"].split("_")[0]
    runner = importlib.import_module("benchmark.lib.run_" + kind)
    run = runner.run(ctx)
    run["memory_sampled"] = ctx.memory_sampled

    if ctx.watch.count:
        print("benchmark: %d program(s) were compiled or loaded inside the "
              "measured window; a shape was not warmed up"
              % ctx.watch.count, file=sys.stderr)
        return 3

    run.update(cell=cell, cfg=cfg, mix=mix, peaks=peaks, chips=cell["chips"])
    metrics = {}
    if args.trace:
        for m in metrics_of(bench, "per_layer", cell["name"]):
            val = load_layer_metric(m["name"]).read(run)
            if val is not None:
                metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
    else:
        for m in metrics_of(bench, "end_to_end", cell["name"]):
            metrics[m["name"]] = {"value": float(run["end_to_end"][m["name"]]),
                                  "unit": m["unit"]}
    peak_mem = int(run.get("memory_sampled", 0))
    for d in ctx.devices:
        stats = d.memory_stats() or {}
        ctx.log("memory_stats", device=str(d), stats=stats)
        peak_mem = max(peak_mem, int(stats.get("peak_bytes_in_use", 0)))
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak_mem}
    result = {"correct": bool(run["correct"]),
              "attempted": int(run["attempted"]),
              "failed": int(run["failed"]), "metrics": metrics,
              "device": device}
    if args.trace and run.get("trace_numbers", {}).get("devices"):
        tn = run["trace_numbers"]
        device["busy_s"] = tn["busy_s"]
        device["window_s"] = tn["window_s"]
        result["breakdown"] = {"device_ops": tn["device_ops"],
                               "idle_gaps": tn["idle_gaps"]}
    for k, v in sorted(run.get("notes", {}).items()):
        ctx.log("note", name=k, value=v)
    print(json.dumps(result), flush=True)
    return 0
