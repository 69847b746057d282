"""Serving-path measurements: the numbers behind
"usable in production", measured instead of asserted.

Reference counterpart: paddle/fluid/inference/api/api_impl.cc — the
NativePredictor whose cold-start/per-call costs this tool records for
our AOT predictor, PredictorServer, and (via runtime/capi_test.c's
bench mode) the pure-C ABI.

Prints one JSON line per phase / sweep config:
  {"phase": "predictor_cold_start", ...}
  {"phase": "predictor_latency", ...}
  {"phase": "server_sweep", "mode": "padmax"|"bucket", ...}   one per config
  {"phase": "server_speedup", ...}   best bucket config vs padmax baseline

The server sweep crosses PredictorServer's batching knobs — padding
policy (legacy pad-to-max vs power-of-two buckets), `max_wait_ms`
batching deadline, and in-flight pipeline depth — at a fixed submitter
count, reporting rows/s plus the pad-waste ratio (padded rows / device
rows) straight from the serving metrics.

With ``--fleet`` the tool instead measures the HORIZONTAL layer
(serving.Router): a single in-process PredictorServer (the PR-2
baseline) against an N-replica worker fleet behind the router, crossed
over replicas x submitters x batching deadline. Baseline and fleet
rounds are INTERLEAVED (base, fleet, base, fleet, ...) per config so
host noise hits both arms equally — the PR-2/3/5 A/B discipline — and
every config line carries its own ``fleet_speedup`` (median fleet
rows/s over median baseline rows/s):
  {"phase": "fleet_sweep", "replicas": N, ..., "fleet_speedup": ...}
  {"phase": "fleet_best", ...}   best config overall

Usage:
  python tools/bench_serving.py            # CPU (forced)
  python tools/bench_serving.py --fleet    # replica-scaling sweep
  BENCH_SERVING_PLATFORM=device python tools/bench_serving.py  # real chip

The model is the MLP the C ABI test embeds (16->128->10 softmax) at
SERVING_BATCH (default 8); adjust with SERVING_DIM / SERVING_HIDDEN.
Sweep grid: SERVING_SWEEP_BATCHES / SERVING_SWEEP_WAITS_MS /
SERVING_SWEEP_INFLIGHT (comma lists), SERVING_SUBMITTERS,
SERVING_REQUESTS. Fleet grid: FLEET_REPLICAS / FLEET_SUBMITTERS /
FLEET_WAITS_MS (comma lists), FLEET_ROUNDS, FLEET_MAX_BATCH,
FLEET_INFLIGHT, FLEET_REQUESTS.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if os.environ.get("BENCH_SERVING_PLATFORM", "cpu") == "cpu":
    os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402

import jax  # noqa: E402

if os.environ.get("BENCH_SERVING_PLATFORM", "cpu") == "cpu":
    jax.config.update("jax_platforms", "cpu")

import paddle_tpu as fluid  # noqa: E402


DIM = int(os.environ.get("SERVING_DIM", 16))
HIDDEN = int(os.environ.get("SERVING_HIDDEN", 128))
BATCH = int(os.environ.get("SERVING_BATCH", 8))


def _save_model(model_dir):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 17
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[DIM], dtype="float32")
        h = fluid.layers.fc(img, HIDDEN, act="relu")
        prob = fluid.layers.softmax(fluid.layers.fc(h, 10))
    scope = fluid.core.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(model_dir, ["img"], [prob], exe,
                                      main_program=main)


def _emit(obj):
    print(json.dumps(obj), flush=True)


def main():
    from paddle_tpu.inference import Predictor, PredictorServer

    tmp = tempfile.mkdtemp(prefix="ptpu_serving_")
    model_dir = os.path.join(tmp, "model")
    _save_model(model_dir)
    batch = np.random.RandomState(3).randn(BATCH, DIM).astype(np.float32)

    # -- cold start: construction + first predict, cache-cold vs warm ----
    t0 = time.perf_counter()
    p = Predictor(model_dir)
    t1 = time.perf_counter()
    p.run({"img": batch})
    t2 = time.perf_counter()
    cold_construct_ms = (t1 - t0) * 1e3
    cold_first_run_ms = (t2 - t1) * 1e3  # includes the XLA compile

    # second process-equivalent: fresh Predictor over the now-warm AOT
    # cache, preload on (default) vs off
    t0 = time.perf_counter()
    p2 = Predictor(model_dir)
    t1 = time.perf_counter()
    p2.run({"img": batch})
    t2 = time.perf_counter()
    warm_preload_construct_ms = (t1 - t0) * 1e3
    warm_preload_first_run_ms = (t2 - t1) * 1e3

    t0 = time.perf_counter()
    p3 = Predictor(model_dir, preload=False)
    t1 = time.perf_counter()
    p3.run({"img": batch})
    t2 = time.perf_counter()
    warm_lazy_construct_ms = (t1 - t0) * 1e3
    warm_lazy_first_run_ms = (t2 - t1) * 1e3

    _emit({"phase": "predictor_cold_start",
           "cold_construct_ms": round(cold_construct_ms, 1),
           "cold_first_run_ms": round(cold_first_run_ms, 1),
           "warm_preload_construct_ms": round(warm_preload_construct_ms, 1),
           "warm_preload_first_run_ms": round(warm_preload_first_run_ms, 3),
           "warm_lazy_construct_ms": round(warm_lazy_construct_ms, 1),
           "warm_lazy_first_run_ms": round(warm_lazy_first_run_ms, 1),
           "device": jax.devices()[0].device_kind})

    # -- steady-state latency -------------------------------------------
    iters = int(os.environ.get("SERVING_ITERS", 200))
    for _ in range(10):
        p2.run({"img": batch})
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out, = p2.run({"img": batch})  # return_numpy fences device->host
        times.append((time.perf_counter() - t0) * 1e3)
    times.sort()
    import math

    p99_idx = max(0, math.ceil(0.99 * len(times)) - 1)
    _emit({"phase": "predictor_latency", "batch": BATCH,
           "run_ms_min": round(times[0], 3),
           "run_ms_p50": round(times[len(times) // 2], 3),
           "run_ms_p99": round(times[p99_idx], 3),
           "iters": iters})

    # -- PredictorServer batching sweep: policy x deadline x in-flight ---
    from paddle_tpu import observability as obs

    n_req = int(os.environ.get("SERVING_REQUESTS", 2000))
    submitters = int(os.environ.get("SERVING_SUBMITTERS", 4))
    batches = _int_list("SERVING_SWEEP_BATCHES", "8,32")
    waits = _float_list("SERVING_SWEEP_WAITS_MS", "0,2")
    depths = _int_list("SERVING_SWEEP_INFLIGHT", "1,4")
    rows = [np.random.RandomState(i % 7).randn(DIM).astype(np.float32)
            for i in range(8)]

    # closed loop = each submitter waits for its row before the next one
    # (arrival-limited PARTIAL fill, where padding policy dominates);
    # open loop = submitters flood as fast as they can (full batches,
    # where the pipeline + zero-copy path dominates)
    loops = [v for v in os.environ.get("SERVING_LOOP_MODES",
                                       "closed,open").split(",") if v]
    baseline = {}
    best = {}
    for loop in loops:
        for max_batch in batches:
            configs = [("padmax", 0.0, 1)]  # pre-pipeline pad-to-max policy
            configs += [("bucket", w, d) for w in waits for d in depths]
            for mode, wait_ms, in_flight in configs:
                rec = _run_server_config(
                    PredictorServer, p2, obs, mode=mode, loop=loop,
                    max_batch=max_batch, wait_ms=wait_ms,
                    in_flight=in_flight, n_req=n_req,
                    submitters=submitters, rows=rows)
                _emit(rec)
                if mode == "padmax":
                    baseline[(loop, max_batch)] = rec
                if mode == "bucket" and (loop not in best
                                         or rec["rows_per_sec"]
                                         > best[loop]["rows_per_sec"]):
                    best[loop] = rec

    for loop in loops:
        top = best.get(loop)
        # compare against the padmax baseline at the SAME max_batch, so
        # the reported speedup isolates the padding policy instead of
        # conflating it with the batch-size choice
        base = baseline.get((loop, top["max_batch"])) if top else None
        if not (base and top):
            continue
        _emit({"phase": "server_speedup", "loop": loop,
               "baseline_rows_per_sec": base["rows_per_sec"],
               "best_rows_per_sec": top["rows_per_sec"],
               "speedup": round(top["rows_per_sec"]
                                / max(base["rows_per_sec"], 1e-9), 3),
               "baseline_pad_waste": base["pad_waste"],
               "best_pad_waste": top["pad_waste"],
               "best_config": {k: top[k] for k in
                               ("mode", "max_batch", "max_wait_ms",
                                "in_flight")}})


def _fleet_rows_per_sec(submit, n_req, submitters, rows, loop="closed",
                        timeout=600.0):
    """Serve n_req single-row requests from `submitters` threads through
    `submit`; returns rows/s. loop="closed": each thread waits for its
    row before the next (latency-bound — what an RPC frontend sees);
    loop="open": threads flood and futures are awaited at the end
    (aggregate CAPACITY — the front channel's backpressure bounds
    memory). The shared measurement body for the baseline-server and
    fleet-router arms."""
    import threading

    errs = []

    def feed_requests(k):
        try:
            futs = []
            for i in range(k * n_req // submitters,
                           (k + 1) * n_req // submitters):
                fut = submit((rows[i % len(rows)],))
                if loop == "closed":
                    fut.result(timeout=timeout)
                else:
                    futs.append(fut)
            for fut in futs:
                fut.result(timeout=timeout)
        except Exception as e:  # pragma: no cover - failure reporting
            errs.append(repr(e))

    threads = [threading.Thread(target=feed_requests, args=(k,))
               for k in range(submitters)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    if errs:
        raise RuntimeError("bench clients failed: %s" % errs[:3])
    return n_req / dt


def fleet_main():
    """--fleet: replicas x submitters x deadline sweep, interleaved A/B
    against a single PR-2 PredictorServer baseline."""
    from paddle_tpu.inference import Predictor, PredictorServer
    from paddle_tpu.serving import Router

    platform = os.environ.get("BENCH_SERVING_PLATFORM", "cpu")
    tmp = tempfile.mkdtemp(prefix="ptpu_fleet_")
    model_dir = os.path.join(tmp, "model")
    _save_model(model_dir)

    n_req = int(os.environ.get("FLEET_REQUESTS",
                               os.environ.get("SERVING_REQUESTS", 2000)))
    rounds = int(os.environ.get("FLEET_ROUNDS", 3))
    max_batch = int(os.environ.get("FLEET_MAX_BATCH", 32))
    in_flight = int(os.environ.get("FLEET_INFLIGHT", 4))
    replicas_grid = _int_list("FLEET_REPLICAS", "1,2,4")
    submitters_grid = _int_list("FLEET_SUBMITTERS", "8")
    waits_grid = _float_list("FLEET_WAITS_MS", "0")
    loops = [v for v in os.environ.get("FLEET_LOOP_MODES",
                                       "closed,open").split(",") if v]
    rows = [np.random.RandomState(i % 7).randn(DIM).astype(np.float32)
            for i in range(8)]

    # the baseline arm: one in-process pipelined server, PR-2 bucket
    # config — constructed once, reused in every interleaved round
    pred = Predictor(model_dir)
    base_server = PredictorServer(pred, max_batch=max_batch,
                                  in_flight=in_flight)
    base_server.start()
    # prime both arms' compiled buckets off the clock
    for f in [base_server.submit((rows[0],)) for _ in range(max_batch)]:
        f.result(timeout=600)

    best = None
    for replicas in replicas_grid:
        for wait_ms in waits_grid:
            router = Router(
                model_dir, replicas=replicas, max_batch=max_batch,
                max_wait_ms=wait_ms, in_flight=in_flight,
                jax_platform=("cpu" if platform == "cpu" else None))
            t_up = time.perf_counter()
            router.start()
            fleet_up_s = time.perf_counter() - t_up
            for submitters in submitters_grid:
                # warm the routed path off the clock
                for f in [router.submit((rows[0],))
                          for _ in range(max_batch)]:
                    f.result(timeout=600)
                for loop in loops:
                    base_rs, fleet_rs = [], []
                    t0 = time.perf_counter()
                    for _ in range(rounds):  # interleaved A/B per round
                        base_rs.append(_fleet_rows_per_sec(
                            base_server.submit, n_req, submitters, rows,
                            loop=loop))
                        fleet_rs.append(_fleet_rows_per_sec(
                            router.submit, n_req, submitters, rows,
                            loop=loop))
                    wall = time.perf_counter() - t0
                    base_med = sorted(base_rs)[len(base_rs) // 2]
                    fleet_med = sorted(fleet_rs)[len(fleet_rs) // 2]
                    rec = {
                        "phase": "fleet_sweep", "replicas": replicas,
                        "submitters": submitters, "loop": loop,
                        "max_wait_ms": wait_ms,
                        "shard": 1, "max_batch": max_batch,
                        "in_flight": in_flight, "requests": n_req,
                        "rounds": rounds,
                        "rows_per_sec": round(fleet_med, 1),
                        "baseline_rows_per_sec": round(base_med, 1),
                        "fleet_speedup": round(
                            fleet_med / max(base_med, 1e-9), 3),
                        "rows_per_sec_rounds": [round(v, 1)
                                                for v in fleet_rs],
                        "baseline_rounds": [round(v, 1) for v in base_rs],
                        "fleet_up_s": round(fleet_up_s, 2),
                        "wall_s": round(wall, 3),
                    }
                    _emit(rec)
                    if (best is None
                            or rec["fleet_speedup"] > best["fleet_speedup"]):
                        best = rec
            router.stop()
    base_server.stop()
    if best is not None:
        _emit({"phase": "fleet_best",
               "fleet_speedup": best["fleet_speedup"],
               "rows_per_sec": best["rows_per_sec"],
               "baseline_rows_per_sec": best["baseline_rows_per_sec"],
               "best_config": {k: best[k] for k in
                               ("replicas", "submitters", "loop",
                                "max_wait_ms", "max_batch", "in_flight")}})


def _int_list(env, default):
    return [int(v) for v in os.environ.get(env, default).split(",") if v]


def _float_list(env, default):
    return [float(v) for v in os.environ.get(env, default).split(",") if v]


def _run_server_config(server_cls, pred, obs, *, mode, loop, max_batch,
                       wait_ms, in_flight, n_req, submitters, rows):
    """One sweep point: serve n_req single-row requests from `submitters`
    concurrent threads and read the pad accounting back out of the
    serving metrics (registry delta over the timed window)."""
    import threading

    kwargs = dict(max_batch=max_batch, max_wait_ms=wait_ms,
                  in_flight=in_flight)
    if mode == "padmax":
        kwargs["buckets"] = [max_batch]  # every batch pads to max_batch
    server = server_cls(pred, **kwargs)
    server.start()
    # off the clock: fill the pipeline once (bucket signatures are
    # already pre-warmed by start(), this warms the thread handoff)
    for f in [server.submit((rows[0],)) for _ in range(max_batch)]:
        f.result(timeout=300)
    real0 = obs.SERVER_ROWS.value(kind="real")
    pad0 = obs.SERVER_ROWS.value(kind="pad")
    server.batch_size_counts.clear()
    futs = [[] for _ in range(submitters)]
    t0 = time.perf_counter()

    def feed_requests(k):
        local = futs[k]
        for i in range(k * n_req // submitters,
                       (k + 1) * n_req // submitters):
            fut = server.submit((rows[i % len(rows)],))
            local.append(fut)
            if loop == "closed":
                fut.result(timeout=300)

    threads = [threading.Thread(target=feed_requests, args=(k,))
               for k in range(submitters)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for chunk in futs:
        for f in chunk:
            f.result(timeout=300)
    dt = time.perf_counter() - t0
    real = obs.SERVER_ROWS.value(kind="real") - real0
    pad = obs.SERVER_ROWS.value(kind="pad") - pad0
    counts = dict(server.batch_size_counts)
    server.stop()
    n_batches = sum(counts.values())
    return {"phase": "server_sweep", "mode": mode, "loop": loop,
            "max_batch": max_batch,
            "max_wait_ms": wait_ms, "in_flight": in_flight,
            "submitters": submitters, "requests": n_req,
            "rows_per_sec": round(n_req / dt, 1), "wall_s": round(dt, 3),
            "real_rows": int(real), "pad_rows": int(pad),
            "pad_waste": round(pad / max(real + pad, 1), 4),
            "batches": n_batches,
            "mean_fill": round(sum(k * v for k, v in counts.items())
                               / n_batches, 2) if n_batches else 0.0}


if __name__ == "__main__":
    sys.exit(fleet_main() if "--fleet" in sys.argv[1:] else main())
