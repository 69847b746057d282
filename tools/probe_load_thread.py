"""Where is a load from the AOT disk tier slow? (PERF.md 7 i, PR 46.)

One serving cell's predictor, built as the benchmark builds it (weights
from a seed, export, `DecodePredictor` over the cell's own disk
directory `.xla_cache/decode_aot_<config>`), then the SAME prefill blobs
loaded again and again, each time by another way, the executable dropped
from the predictor's memory cache in between:

- `main`           the main thread, no server alive;
- `thread`         a fresh thread a load, no server alive;
- `thread.reused.0`, `.1`  ONE fresh thread, every shape, twice over;
- `thread.old`     a thread started with the round and parked until now;
- `main+slabs`     the main thread, a live server's loop parked on its
                   channel with its slabs resident;
- `thread+slabs`   a fresh thread beside the same;
- `main+own_slabs` the main thread, holding slabs it allocated itself
                   (no server thread at all);
- `loop.live`      that live server's loop thread, under
                   `decode.loop.admit`, at a bucket's first admission;
- `loop.fresh`     today's warm-up path: the last server stopped, a NEW
                   server's requests submitted before `start`, its loop
                   allocates its slabs and admits at once.

`load_ms` is the record's own (`Engine.acquire` around
`AotDiskCache.load`: read + deserialize; `deserialize_ms` the second
alone, timed by this script, with what `getrusage` says the loading
thread and the whole process spent inside it). Two processes in one call:

    python tools/probe_load_thread.py --workload <cell> --fill 1   # compile + store
    python tools/probe_load_thread.py --workload <cell>            # the table

so that the process that measures has compiled none of what it loads
(and a third time under `MALLOC_ARENA_MAX=1`: every way then reads as
the main thread does, which is how PR 46 named the cause).
Prints one JSON line a load and a table of medians; exits 2 without a
TPU (`--cpu 1 --tiny phi4flash-tiny.json,chat-tiny-any.json` is the
rehearsal on the CPU; a loop's admission takes one prompt a shape, so
the shapes are of batch 1)."""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import queue
import resource
import shutil
import statistics
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the checkout first, and not this directory: its `benchmark.py` would
# shadow the `benchmark` package
sys.path[:] = [ROOT] + [p for p in sys.path if os.path.abspath(
    p or ".") != os.path.dirname(os.path.abspath(__file__))]

import numpy as np  # noqa: E402

from benchmark.lib import harness, weights  # noqa: E402


def build_predictor(cfg, mix, seed, work):
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.serving import DecodePredictor, save_decode_model

    model = importlib.import_module("benchmark.models." + cfg["builder"])
    dev = jax.devices()[0]
    place = fluid.TPUPlace() if dev.platform == "tpu" else fluid.CPUPlace()
    kind = "serve_" + mix["kind"].split("_", 1)[1]
    w = weights.seeded_weights(model.parameter_specs(cfg, kind), seed,
                               model.init_rule, device=dev)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    scope = fluid.Scope()
    for n in w:
        scope.set_var(n, w[n])
    exe = fluid.Executor(place)
    with fluid.scope_guard(scope):
        save_decode_model(work, model.decode_config(cfg, kind), exe,
                          scope=scope)
    exe.close()
    del scope, exe, w
    gc.collect()
    return DecodePredictor(work, place=place, cache_dir=os.path.join(
        harness.ROOT, ".xla_cache", "decode_aot_" + cfg["name"]))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=3000000019)
    ap.add_argument("--shapes", default="1x256,1x512,1x1024")
    ap.add_argument("--tiny", default="",
                    help="CFG.json,MIX.json of benchmark/tests/tiny, for "
                    "a rehearsal on the CPU")
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--fill", type=int, default=0)
    ap.add_argument("--cpu", type=int, default=0)
    a = ap.parse_args()
    harness.setup_env(harness.ROOT)
    _, _cell, cfg, mix = harness.load_cell(harness.ROOT, a.workload)
    if a.tiny:
        cfg, mix = (json.load(open(os.path.join(
            harness.BENCH_DIR, "tests", "tiny", n)))
            for n in a.tiny.split(","))

    import jax

    from paddle_tpu import observability as obs
    from paddle_tpu.observability import tracing
    from paddle_tpu.serving import DecodeServer

    if jax.devices()[0].platform != "tpu" and not a.cpu:
        print("probe: no TPU", file=sys.stderr)
        return 2
    tracing.set_sample_rate(1.0)  # records carry their phase
    slots, seq = int(cfg["serve"]["slots"]), int(cfg["serve"]["max_seq"])
    shapes = [tuple(int(x) for x in s.split("x"))
              for s in a.shapes.split(",")]
    work = os.path.join(harness.ROOT, ".bench_cache", "probe_load")
    t0 = time.time()
    pred = build_predictor(cfg, mix, a.seed, work)
    print(json.dumps({"msg": "predictor", "seconds": time.time() - t0}),
          flush=True)

    def new_server():
        return DecodeServer(pred, slots=slots, max_seq=seq,
                            max_new_tokens=int(mix["max_new"]["max"]),
                            strategy="greedy")

    def request(srv, n, plen):
        return [srv.submit((np.ones((plen,), np.int64),
                            np.array([2], np.int64))) for _ in range(n)]

    if a.fill:
        # everything the measuring process will load, compiled and
        # stored: the shapes, and a server's own prewarm
        for b, s in shapes:
            pred.acquire("prefill", b, s)
        srv = new_server()
        srv.start()
        for f in request(srv, 1, 8):
            f.result(timeout=1200)
        srv.stop()
        for r in obs.TIMELINE.events("compile"):
            print(json.dumps({"msg": "fill", "name": r.get("name"),
                              "path": r.get("path"),
                              "wall_ms": r.get("wall_ms")}), flush=True)
        shutil.rmtree(work, ignore_errors=True)
        return 0

    rows = []
    # `load_ms` is read + deserialize: time the second alone, beside it
    from paddle_tpu.runtime import aot_cache as _aot

    deser = {"ms": None}
    real_deserialize = _aot.deserialize_executable

    def usage():
        # the loading thread's own CPU seconds, page faults and context
        # switches, and the whole process's CPU seconds (every thread's)
        t = resource.getrusage(resource.RUSAGE_THREAD)
        p = resource.getrusage(resource.RUSAGE_SELF)
        return (t.ru_utime, t.ru_stime, t.ru_minflt, t.ru_nvcsw,
                t.ru_nivcsw, p.ru_utime + p.ru_stime)

    def timed_deserialize(payload):
        u0, t = usage(), time.perf_counter()
        try:
            return real_deserialize(payload)
        finally:
            deser["ms"] = (time.perf_counter() - t) * 1e3
            d = [b - a for a, b in zip(u0, usage())]
            deser["usage"] = {
                "thread_user_ms": d[0] * 1e3, "thread_sys_ms": d[1] * 1e3,
                "thread_minflt": d[2], "thread_vol_switches": d[3],
                "thread_invol_switches": d[4], "process_cpu_ms": d[5] * 1e3,
                "thread": threading.current_thread().name}

    _aot.deserialize_executable = timed_deserialize

    def drop(b, s):
        with pred._lock:
            for ck in [ck for ck in pred._compiled
                       if ck[:3] == ("prefill", b, s)]:
                del pred._compiled[ck]
        gc.collect()

    def measure(way, b, s, load):
        """Drop the shape from memory, have `load()` acquire it again,
        and note the record that acquisition wrote."""
        name = "ptpu_prefill_b%d_s%d" % (b, s)

        def records():
            return [r for r in obs.TIMELINE.events("compile")
                    if r.get("name") == name]

        drop(b, s)
        n_before = len(records())
        load()
        recs = records()
        if len(recs) <= n_before:
            raise RuntimeError("no record for %s by way %s" % (name, way))
        r = recs[-1]
        st = jax.devices()[0].memory_stats() or {}
        row = {"way": way, "name": name, "path": r.get("path"),
               "load_ms": r.get("load_ms"), "deserialize_ms": deser["ms"],
               "wall_ms": r.get("wall_ms"),
               "blob_bytes": r.get("blob_bytes"), "phase": r.get("phase"),
               "bytes_in_use": st.get("bytes_in_use")}
        row.update(deser.get("usage") or {})
        rows.append(row)
        print(json.dumps(row), flush=True)

    def by_main(way, b, s):
        measure(way, b, s, lambda: pred.acquire("prefill", b, s))

    def by_thread(way, b, s):
        def load():
            t = threading.Thread(target=pred.acquire,
                                 args=("prefill", b, s), name="probe-thread")
            t.start()
            t.join()
        measure(way, b, s, load)

    def by_loop(way, srv, b, s, start=False):
        def load():
            futs = request(srv, b, s)
            if start:
                srv.start()
            for f in futs:
                f.result(timeout=1200)
        measure(way, b, s, load)

    def by_worker(way, jobs, b, s):
        def load():
            done = threading.Event()
            jobs.put((b, s, done))
            done.wait()
        measure(way, b, s, load)

    def worker(jobs):
        while True:
            job = jobs.get()
            if job is None:
                return
            pred.acquire("prefill", job[0], job[1])
            job[2].set()

    for rnd in range(a.rounds):
        order = shapes[rnd % len(shapes):] + shapes[:rnd % len(shapes)]
        # a thread as old as the round, parked until it is asked
        old_jobs = queue.Queue()
        old = threading.Thread(target=worker, args=(old_jobs,),
                               name="probe-old")
        old.start()
        for b, s in order:
            by_main("main", b, s)
        for b, s in order:
            by_thread("thread", b, s)
        # ONE fresh thread, every shape twice: the first load of a
        # thread, or every load of it?
        jobs = queue.Queue()
        one = threading.Thread(target=worker, args=(jobs,),
                               name="probe-reused")
        one.start()
        for i in range(2):
            for b, s in order:
                by_worker("thread.reused.%d" % i, jobs, b, s)
        jobs.put(None)
        one.join()
        for b, s in order:
            by_worker("thread.old", old_jobs, b, s)
        old_jobs.put(None)
        old.join()
        # a live server: prewarmed, one request through it, parked
        srv = new_server()
        srv.start()
        for f in request(srv, 1, 8):
            f.result(timeout=1200)
        for b, s in order:
            by_main("main+slabs", b, s)
        for b, s in order:
            by_thread("thread+slabs", b, s)
        for b, s in order:
            by_loop("loop.live", srv, b, s)
        srv.stop()
        del srv
        gc.collect()
        held = new_server()._fresh_slabs()
        jax.block_until_ready(held)
        for b, s in order:
            by_main("main+own_slabs", b, s)
        del held
        gc.collect()
        for b, s in order:
            srv = new_server()
            by_loop("loop.fresh", srv, b, s, start=True)
            srv.stop()
            del srv
        for b, s in order:
            by_main("main.after", b, s)

    print("%-16s %3s %10s %10s %10s  %s" % ("way", "n", "median_ms",
                                             "min_ms", "max_ms", "phase"))
    ways = []
    for r in rows:
        if r["way"] not in ways:
            ways.append(r["way"])
    for way in ways:
        ms = [r["load_ms"] for r in rows
              if r["way"] == way and r["load_ms"] is not None]
        ph = sorted({str(r["phase"]) for r in rows if r["way"] == way})
        if ms:
            print("%-16s %3d %10.1f %10.1f %10.1f  %s"
                  % (way, len(ms), statistics.median(ms), min(ms), max(ms),
                     ",".join(ph)))
    out = os.path.join(harness.ROOT, "chiprun_out", "probe_load_thread")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, a.workload + ".jsonl"), "a") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    shutil.rmtree(work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
