"""Runner for mixes of kind `serveany_closed` and `serveany_open`:
`run_serve.py`'s procedure (weights from the seed -> the reference's
logits -> export -> load -> prefill-then-decode rollout against the
reference -> warm-up servers -> server against the direct rollout ->
ramp, settle, window, traced sub-window, drain) for ANY model that
describes its caches: the rollout's arrays, feed names and scatter come
from `pred.cache_spec(slots, seq)` (K/V slabs of any head count,
fixed-size recurrent states, OPT's slabs as they were), and the
reference is called with the configuration, not with a head count.

`run_serve.py` builds `2 * n_layer` slabs of `(slots, seq, n_head,
d_head)` by hand and cannot run a model whose layers differ in kind; it
is an accepted benchmark file and stays as it is. `Loop` and `_bucket`
are its own. The returned `run` dictionary has the same keys, so the
`.serve` readers read a cell of this runner unchanged.
"""
from __future__ import annotations

import gc
import json
import os
import shutil
import time

import numpy as np

from . import compare, stats, trace_reduce, traffic, weights
from .run_serve import Loop, _bucket


def _reference_logits(ref, w, tokens, cfg, n_layer, precision, rows):
    """The reference's logits at `rows` of one sequence. A reference
    written for this runner takes the configuration (`serve_logits`);
    `reference/opt.py`, older than it, takes the head count."""
    if hasattr(ref, "serve_logits"):
        return ref.serve_logits(w, tokens, cfg, n_layer,
                                precision=precision, rows=rows)
    return ref.logits(w, tokens, n_layer, cfg["num_attention_heads"],
                      precision=precision, rows=rows)


def _direct_rollout(pred, prompts, steps, slots, seq, forced=None):
    """Rollout of `prompts` through the server's own executables
    (prefill at batch 1, the (slots, seq) decode step), by hand: the
    logits of the last prompt position and of each decoded position,
    and the tokens fed. Greedy, or teacher-forced with `forced[i]`.
    Caches as `pred.cache_spec` describes them: an entry of rows per
    position takes the prompt's rows, a fixed-size state is replaced."""
    import jax.numpy as jnp

    spec = pred.cache_spec(slots, seq)
    caches = [jnp.zeros(e.shape, e.dtype) for e in spec]
    lens = np.zeros((slots,), np.int32)
    cur = np.zeros((slots,), np.int64)
    rows = [[] for _ in prompts]
    toks = [[] for _ in prompts]
    for i, p in enumerate(prompts):
        sp = min(_bucket(len(p)), seq)
        pexe, _ = pred.acquire("prefill", 1, sp)
        tokens = np.zeros((1, sp), np.int64)
        tokens[0, :len(p)] = p
        outs = pexe({"tokens": tokens,
                     "lengths": np.array([len(p)], np.int32)}, pred._state)
        row = np.asarray(outs[0])[0]
        rows[i].append(row)
        for j, (e, sub) in enumerate(zip(spec, outs[1:])):
            # one array at a time: never two copies of all of them
            sub = jnp.asarray(sub)[0]
            caches[j] = (caches[j].at[i, :sp].set(sub) if e.per_position
                         else caches[j].at[i].set(sub))
        lens[i] = len(p)
        cur[i] = int(row.argmax()) if forced is None else forced[i][0]
        toks[i].append(int(cur[i]))
    dexe, _ = pred.acquire("decode", slots, seq, "greedy")
    names = [e.name for e in spec]
    for s in range(steps):
        feeds = {"tokens": cur.reshape(slots, 1), "lengths": lens.copy(),
                 "seed": np.array([s], np.int64)}
        if pred.config.positions:
            feeds["positions"] = lens.reshape(slots, 1).astype(np.int64)
        feeds.update(zip(names, caches))
        outs = dexe(feeds, pred._state)
        nxt = np.asarray(outs[0]).astype(np.int64)
        logits = np.asarray(outs[1])
        caches = list(outs[2:])
        for i in range(len(prompts)):
            rows[i].append(logits[i])
            lens[i] += 1
            cur[i] = nxt[i] if forced is None else forced[i][s + 1]
            toks[i].append(int(cur[i]))
    del caches
    return rows, toks


def run(ctx) -> dict:
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.observability import tracing
    from paddle_tpu.serving import (DecodePredictor, DecodeServer,
                                    save_decode_model)

    cfg, mix = ctx.cfg, ctx.mix
    model = ctx.module("models", cfg["builder"])
    ref = ctx.module("reference", cfg["reference"])
    dev = ctx.devices[0]
    place = fluid.TPUPlace() if dev.platform == "tpu" else fluid.CPUPlace()
    ann = jax.profiler.TraceAnnotation
    # what the builders are asked for: their depth and parameters when
    # serving, whatever this runner's mixes are called
    kind = "serve_" + mix["kind"].split("_", 1)[1]
    slots, seq = int(cfg["serve"]["slots"]), int(cfg["serve"]["max_seq"])
    chk = cfg["check"]["serve"]
    n_layer = model.depth(cfg, kind)
    if ctx.trace:
        tracing.set_sample_rate(1.0)

    # -- weights from the seed, the reference's logits, the export
    t0 = time.time()
    specs = model.parameter_specs(cfg, kind)
    w = weights.seeded_weights(specs, ctx.seed, model.init_rule, device=dev)
    jax.block_until_ready(w)
    ctx.log("weights", seconds=time.time() - t0)
    requests = traffic.serve_requests(mix, cfg["vocab_size"], ctx.seed)
    r = np.random.default_rng(np.random.SeedSequence([ctx.seed, 9]))
    probes = [r.integers(1, cfg["vocab_size"], n, dtype=np.int64)
              for n in chk["prompt_lens"]]
    k = int(chk["decode_steps"])
    forced = [r.integers(1, cfg["vocab_size"], k + 1, dtype=np.int64)
              for _ in probes]

    # -- correct, part 1a: the reference's logits at the last prompt
    # position and at k teacher-forced positions after it, from the
    # seed's weights, before anything of the program exists
    t0 = time.time()
    want = []
    for p, f in zip(probes, forced):
        full = np.concatenate([p, f[:k]])
        at = np.arange(len(p) - 1, len(p) + k)
        want.append(np.asarray(_reference_logits(
            ref, w, jax.numpy.asarray(full), cfg, n_layer,
            chk["reference_precision"], at)))
    ctx.log("reference", seconds=time.time() - t0)

    model_dir = os.path.join(ctx.work_dir, "decode_model")
    shutil.rmtree(model_dir, ignore_errors=True)
    os.makedirs(model_dir)
    t0 = time.time()
    scope = fluid.Scope()
    for n in w:
        scope.set_var(n, w[n])
    exe = fluid.Executor(place)
    with fluid.scope_guard(scope):
        save_decode_model(model_dir, model.decode_config(cfg, kind), exe,
                          scope=scope)
    exe.close()
    del scope, exe, w
    gc.collect()
    ctx.log("export", seconds=time.time() - t0)
    t0 = time.time()
    pred = DecodePredictor(model_dir, place=place, cache_dir=os.path.join(
        ctx.cache_dir, "decode_aot_" + cfg["name"]))
    ctx.log("load", seconds=time.time() - t0)

    # -- correct, part 1b: prefill, then decode through the cache, on
    # the same tokens, against those logits; then the greedy rollout the
    # server has to repeat
    t0 = time.time()
    rows, _ = _direct_rollout(pred, probes, k, slots, seq, forced=forced)
    checks = compare.Checks()
    worst = max(compare.rel_l2(np.stack(got), wnt)
                for got, wnt in zip(rows, want))
    checks.add("logits_rel_l2 prefill+decode vs reference", worst,
               chk["logits_rel_l2"])
    _, toks = _direct_rollout(pred, probes, k, slots, seq)
    del rows
    gc.collect()
    ctx.log("check_logits", seconds=time.time() - t0)

    def new_server():
        return DecodeServer(pred, slots=slots, max_seq=seq,
                            max_new_tokens=int(mix["max_new"]["max"]),
                            strategy="greedy")

    # -- warm-up through the server's own surface: one short-lived
    # server per (admission size, prompt bucket) the mix can produce;
    # requests are submitted before start(), so admission is one burst
    t0 = time.time()
    lo = _bucket(int(mix["prompt_len"]["min"]))
    hi = min(_bucket(int(mix["prompt_len"]["max"])), seq)
    buckets = []
    while lo <= hi:
        buckets.append(lo)
        lo *= 2
    for sp in buckets:
        plen = min(sp, int(mix["prompt_len"]["max"]))
        for n in mix["warm_admit_sizes"]:
            srv = new_server()
            futs = [srv.submit((np.ones((plen,), np.int64),
                                np.array([2], np.int64)))
                    for _ in range(int(n))]
            srv.start()
            for f in futs:
                f.result(timeout=1200)
            srv.stop()
    ctx.log("warm_servers", seconds=time.time() - t0, buckets=buckets)

    # -- correct, part 2: the server answers the probe prompts with the
    # tokens of the direct rollout, one at a time (same executables)
    srv = new_server()
    srv.start()
    mismatches = 0
    for p, tk in zip(probes, toks):
        out = srv.submit((p, np.array([k + 1], np.int64))).result(
            timeout=1200)
        got = np.asarray(out[0]).reshape(-1)
        mismatches += int(len(got) != k + 1
                          or (got != np.asarray(tk)).any())
    checks.add("server_vs_direct_rollout_mismatches", mismatches, 0)

    # -- the loop: ramp, settle, window, (traced sub-window), drain
    loop = Loop(srv, requests, ann)
    open_loop = kind == "serve_open"
    trace_s = float(mix.get("trace_seconds", 3.0)) if ctx.trace else 0.0
    window_s = max(ctx.seconds - trace_s, ctx.seconds / 2.0)
    settle = float(mix.get("settle_seconds", 3.0))
    if not open_loop:
        clients = int(mix["clients_per_slot"]) * slots
        group = int(mix.get("ramp_group", 4))
        while loop.next_i < slots:
            before = srv.prefill_executions
            for _ in range(min(group, slots - loop.next_i)):
                loop.submit()
            deadline = time.time() + 600
            while srv.prefill_executions == before and time.time() < deadline:
                time.sleep(0.002)
        while loop.next_i < clients:
            loop.submit()

        def pump(until):
            while True:
                left = until - time.perf_counter()
                if left <= 0:
                    return
                if loop.collect(min(left, 0.5)):
                    loop.submit()
    else:
        horizon = settle + window_s + trace_s
        due = traffic.arrival_times(mix, ctx.seed, horizon)
        state = {"i": 0, "t0": None, "late": []}

        def pump(until):
            if state["t0"] is None:
                state["t0"] = time.perf_counter()
            while True:
                now = time.perf_counter()
                if now >= until:
                    return
                i = state["i"]
                nxt = (state["t0"] + due[i]) if i < len(due) else until
                if now >= nxt:
                    state["late"].append(now - nxt)
                    loop.submit(t_due=nxt)
                    state["i"] += 1
                    continue
                loop.collect(min(nxt, until) - now)

    pump(time.perf_counter() + settle)
    steps_before = len(srv.step_active_counts)
    ctx.watch.active = True
    t_window = time.time()
    w0 = time.perf_counter()
    pump(w0 + window_s / 2)
    ctx.sample_memory()  # mid-window, programs loaded
    pump(w0 + window_s)
    w1 = w0 + window_s
    ctx.watch.active = False
    counts = list(srv.step_active_counts)[steps_before:]

    trace_numbers, trace = {}, None
    if ctx.trace:
        prof = os.path.join(ctx.work_dir, "profile")
        shutil.rmtree(prof, ignore_errors=True)
        os.makedirs(prof, exist_ok=True)
        jax.profiler.start_trace(prof)
        pump(time.perf_counter() + trace_s)
        jax.profiler.stop_trace()
    # drain: the server finishes what it has; the callbacks still fire
    srv.stop()
    while loop.in_flight and loop.collect(5.0):
        pass
    shutil.rmtree(model_dir, ignore_errors=True)  # gigabytes of disk
    if ctx.trace:
        trace = trace_reduce.load_xplane(prof)
        trace_numbers = trace_reduce.reduce_trace(trace)
        with open(os.path.join(ctx.out_dir, "trace_summary.json"), "w") as f:
            json.dump({"numbers": trace_numbers, "lines": trace["lines"],
                           "host_events": len(trace["host"]),
                           "samples": trace_reduce.sample_events(trace)},
                      f, indent=1)

    inside = [rec for rec in loop.records if w0 <= rec[1] <= w1]
    good = [rec for rec in inside if rec[3]]
    failed = len(inside) - len(good)
    lat_ms = [(rec[1] - rec[0]) * 1e3 for rec in good]
    tokens = sum(rec[2] for rec in good)
    checks.add("failed_requests_in_window", failed, 0)
    # the tail the mix names, or the run is no good: a lower percentile
    # under the same name would flatter a slower server
    p_want = float(mix["tail"])
    p_val, p_used = stats.tail(lat_ms, p_want)
    checks.add("tail_percentile_shortfall", p_want - p_used, 0)
    notes = {"completed_in_window": len(good), "window_s": window_s,
             "request_ms_p50": stats.quantile(lat_ms, 0.5),
             "tail_percentile_reported": p_used,
             "decode_steps_in_window": len(counts),
             "decode_tokens_per_s": sum(counts) / window_s,
             "requests_submitted": loop.next_i}
    if open_loop and state["late"]:
        notes["generator_late_ms_max"] = max(state["late"]) * 1e3
    return {
        "correct": checks.ok, "attempted": len(inside), "failed": failed,
        "end_to_end": {mix["rate_metric"]: tokens / window_s,
                       mix["tail_metric"]: p_val,
                       "setup_s": t_window - ctx.t_start},
        "trace": trace, "trace_numbers": trace_numbers,
        "spans": tracing.get_recorder().spans() if ctx.trace else [],
        "counts": {"step_active_counts": counts, "slots": slots,
                   "window_s": window_s},
        "window_wall": (t_window, t_window + window_s), "notes": notes,
    }
