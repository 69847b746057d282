"""Decode-serving measurements: the O(T^2)-vs-O(T) story, measured —
plus the PR-14 levers (shared-prefix KV, speculative decoding).

Interleaved A/B experiments over the same exported causal LM
(random-init weights — throughput does not care what the logits say):

1. **KV-cache incremental decode vs full-forward recompute**
   (``decode_ab``): generate DECODE_STEPS tokens per row at
   DECODE_BATCH. The kv arm is ``DecodePredictor.generate`` (one
   prefill + one single-query decode step per token); the full arm
   replays the serving status quo ante — re-running the SAME compiled
   prefill executable over the whole growing prefix for every token.
   Rounds interleave (kv, full, kv, full, ...) so host noise hits both
   arms equally — the PR-2/3/5/8 discipline. DECODE_STEPS accepts a
   comma ladder ("64,256,1024" — ROADMAP item 1b): one A/B pair + one
   speedup line per rung, showing the O(T^2)/O(T) divergence grow.

2. **Continuous vs static batching at mixed request lengths**
   (``batch_mode``): CONT_REQUESTS generations with alternating short/
   long ``max_new`` budgets through the same DecodeServer, once with
   continuous admission and once gang-scheduled. ``mean_active`` is the
   measured per-step slot occupancy.

3. **Speculative vs plain greedy decode** (``spec_ab``, opt-in via
   ``--speculative``): DECODE_DRAFT_LAYERS-deep self-drafting proposes
   SPEC_K tokens per round, ONE verify window call checks them.
   SPEC_FAVORABLE=1 (default when the arm runs) zeroes the out/fc2
   projections of layers >= DECODE_DRAFT_LAYERS at export, making the
   tail layers exact identities — the draft then agrees with the target
   everywhere (acceptance ~= 1), which measures the MECHANICS CEILING
   of the lever on this box the way a well-trained draft would behave;
   SPEC_FAVORABLE=0 keeps the random model (acceptance is luck) for the
   honest-floor number. ``acceptance_rate`` is emitted either way.

4. **Shared-prefix admission vs private prefills** (``prefix_ab``,
   opt-in via ``--prefix-share``): CONT_REQUESTS requests over
   PREFIX_GROUPS distinct prompts through two DecodeServers — prefix
   store on vs off. ``prefill_executions`` per arm shows the mechanism
   (PREFIX_GROUPS prefills vs one per request); tokens/s shows the
   admission wall-time win.

Prints one JSON line per config / phase; schema pinned by
tests/test_bench_decode_smoke.py.

Usage:
  python tools/bench_decode.py [--speculative] [--prefix-share]
  BENCH_DECODE_PLATFORM=device python tools/bench_decode.py  # real chip

Model: DECODE_LAYERS x DECODE_HEADS heads x DECODE_DMODEL (ffn
DECODE_DINNER) over DECODE_VOCAB tokens; prompts DECODE_PROMPT long.
Grid: DECODE_BATCH, DECODE_STEPS (comma ladder ok), DECODE_ROUNDS;
continuous phase: CONT_REQUESTS, CONT_SLOTS, CONT_MAXNEW_MIX,
CONT_ROUNDS; spec arm: DECODE_DRAFT_LAYERS, SPEC_K, SPEC_FAVORABLE;
prefix arm: PREFIX_GROUPS.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if os.environ.get("BENCH_DECODE_PLATFORM", "cpu") == "cpu":
    os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402

import jax  # noqa: E402

if os.environ.get("BENCH_DECODE_PLATFORM", "cpu") == "cpu":
    jax.config.update("jax_platforms", "cpu")

import paddle_tpu as fluid  # noqa: E402

LAYERS = int(os.environ.get("DECODE_LAYERS", 2))
HEADS = int(os.environ.get("DECODE_HEADS", 4))
DMODEL = int(os.environ.get("DECODE_DMODEL", 128))
DINNER = int(os.environ.get("DECODE_DINNER", 256))
VOCAB = int(os.environ.get("DECODE_VOCAB", 512))
PROMPT = int(os.environ.get("DECODE_PROMPT", 16))
BATCH = int(os.environ.get("DECODE_BATCH", 4))
STEPS_LIST = [int(x) for x in
              str(os.environ.get("DECODE_STEPS", "128")).split(",")]
ROUNDS = int(os.environ.get("DECODE_ROUNDS", 3))
CONT_REQUESTS = int(os.environ.get("CONT_REQUESTS", 24))
CONT_SLOTS = int(os.environ.get("CONT_SLOTS", 4))
CONT_MAXNEW_MIX = os.environ.get("CONT_MAXNEW_MIX", "")
CONT_ROUNDS = int(os.environ.get("CONT_ROUNDS", 5))
DRAFT_LAYERS = int(os.environ.get("DECODE_DRAFT_LAYERS", 1))
SPEC_K = int(os.environ.get("SPEC_K", 4))
SPEC_FAVORABLE = os.environ.get("SPEC_FAVORABLE", "1") == "1"
PREFIX_GROUPS = int(os.environ.get("PREFIX_GROUPS", 2))


def emit(rec):
    print(json.dumps(rec), flush=True)


def _export_model(model_dir, spec_favorable=False):
    from paddle_tpu import layers, optimizer  # noqa: F401
    from paddle_tpu.models import transformer as T
    from paddle_tpu.serving.decode import DecodeConfig, save_decode_model

    from paddle_tpu.serving.decode import _pow2_bucket

    max_len = _pow2_bucket(PROMPT + max(STEPS_LIST) + SPEC_K + 2,
                           floor=16)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 17
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        ids = layers.data(name="ids", shape=[2, 16], dtype="int64",
                          append_batch_size=False)
        lbl = layers.data(name="lbl", shape=[2, 16], dtype="int64",
                          append_batch_size=False)
        T.transformer_lm(ids, lbl, VOCAB, n_layer=LAYERS, n_head=HEADS,
                         d_model=DMODEL, d_inner=DINNER, dropout_rate=0.0,
                         max_len=max_len, fused_head=False)
    exe = fluid.Executor(fluid.CPUPlace() if os.environ.get(
        "JAX_PLATFORMS") == "cpu" else None)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        if spec_favorable:
            # acceptance-favorable: zero the residual-writing
            # projections of every post-draft layer, making them exact
            # identities — the DRAFT_LAYERS-deep draft then argmax-
            # agrees with the target everywhere (what a well-trained
            # draft approximates). Throughput is unaffected (the zeroed
            # matmuls still execute); only the logits change.
            for i in range(DRAFT_LAYERS, LAYERS):
                for name in ("lm.l%d.self.out" % i, "lm.l%d.ffn.fc2" % i):
                    for suffix in (".w", ".b"):
                        old = scope.find_var(name + suffix)
                        if old is not None:
                            scope.set_var(name + suffix,
                                          np.zeros_like(np.asarray(old)))
        save_decode_model(model_dir, DecodeConfig(
            vocab_size=VOCAB, n_layer=LAYERS, n_head=HEADS, d_model=DMODEL,
            d_inner=DINNER, max_len=max_len), exe, scope=scope)
    return max_len


def _prompts(n, rng, length=None):
    return [rng.randint(1, VOCAB, length or PROMPT).astype(np.int64)
            for _ in range(n)]


def _full_forward_rollout(pred, prompts, steps):
    """The no-cache baseline: one FULL prefill forward per generated
    token over the growing prefix (greedy), using the same compiled
    prefill executable family — and the same bucket policy
    (serving.decode._pow2_bucket) — the kv arm warms."""
    from paddle_tpu.serving.decode import _pow2_bucket

    b = len(prompts)
    bb = _pow2_bucket(b)
    s = _pow2_bucket(PROMPT + steps, floor=16)
    tokens = np.zeros((bb, s), np.int64)
    lens = np.ones((bb,), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
        lens[i] = len(p)
    rows = np.arange(bb)
    for _ in range(steps):
        # honest baseline: the full forward runs at the pow2 bucket of
        # the CURRENT prefix, not the final one (what a bucketed
        # full-forward server would actually pay per token)
        sc = min(_pow2_bucket(int(lens.max()), floor=16), s)
        pexe, _ = pred.acquire("prefill", bb, sc)
        outs = pexe({"tokens": tokens[:, :sc], "lengths": lens},
                    pred._state)
        nxt = np.asarray(outs[0]).argmax(axis=1)
        tokens[rows, np.minimum(lens, s - 1)] = nxt
        lens = np.minimum(lens + 1, s - 1)
    return tokens


def bench_decode_ab(pred, steps):
    rng = np.random.RandomState(0)
    prompts = _prompts(BATCH, rng)
    # one full untimed round per arm: EVERY signature either arm will
    # touch (all the growing full-forward buckets, the kv prefill + the
    # (B, S) decode step) compiles/loads outside the measured region
    pred.generate(prompts, max_new_tokens=steps)
    _full_forward_rollout(pred, prompts, steps)

    kv_rates, full_rates = [], []
    kv_wall = full_wall = 0.0
    for _ in range(ROUNDS):
        t0 = time.perf_counter()
        outs = pred.generate(prompts, max_new_tokens=steps)
        dt = time.perf_counter() - t0
        kv_wall += dt
        kv_rates.append(sum(len(o) for o in outs) / dt)

        t0 = time.perf_counter()
        _full_forward_rollout(pred, prompts, steps)
        dt = time.perf_counter() - t0
        full_wall += dt
        full_rates.append(BATCH * steps / dt)

    from paddle_tpu.serving.decode import _pow2_bucket

    s = _pow2_bucket(PROMPT + steps, floor=16)
    for mode, rates, wall in (("kv_cache", kv_rates, kv_wall),
                              ("full_forward", full_rates, full_wall)):
        emit({"phase": "decode_ab", "mode": mode, "batch": BATCH,
              "decode_steps": steps, "prompt_len": PROMPT,
              "seq_bucket": s, "rounds": ROUNDS,
              "tokens": BATCH * steps * ROUNDS,
              "tokens_per_sec": float(np.median(rates)),
              "tokens_per_sec_rounds": [float(r) for r in rates],
              "wall_s": float(wall)})
    kv, full = float(np.median(kv_rates)), float(np.median(full_rates))
    emit({"phase": "decode_speedup", "batch": BATCH,
          "decode_steps": steps, "kv_tokens_per_sec": kv,
          "full_tokens_per_sec": full, "speedup": kv / full})
    return kv / full


def bench_spec_ab(pred, steps):
    """Interleaved speculative-vs-plain greedy A/B on the same
    predictor; acceptance rate measured from the observability
    counters."""
    from paddle_tpu import observability as obs

    rng = np.random.RandomState(3)
    prompts = _prompts(BATCH, rng)
    # untimed warm round per arm (draft + verify signatures compile
    # here, outside the measured region) + the lossless check
    plain = pred.generate(prompts, max_new_tokens=steps)
    spec = pred.generate(prompts, max_new_tokens=steps, speculative=True,
                         spec_k=SPEC_K)
    assert all(np.array_equal(a, b) for a, b in zip(plain, spec)), \
        "speculative greedy diverged from plain greedy (lossless broken)"

    rates = {"speculative": [], "plain": []}
    walls = {"speculative": 0.0, "plain": 0.0}
    p0 = obs.DECODE_SPEC_PROPOSED.value()
    a0 = obs.DECODE_SPEC_ACCEPTED.value()
    for rnd in range(ROUNDS):
        order = (("speculative", "plain") if rnd % 2 == 0
                 else ("plain", "speculative"))
        for mode in order:
            t0 = time.perf_counter()
            outs = pred.generate(prompts, max_new_tokens=steps,
                                 speculative=(mode == "speculative"),
                                 spec_k=SPEC_K)
            dt = time.perf_counter() - t0
            walls[mode] += dt
            rates[mode].append(sum(len(o) for o in outs) / dt)
    proposed = obs.DECODE_SPEC_PROPOSED.value() - p0
    accepted = obs.DECODE_SPEC_ACCEPTED.value() - a0
    acceptance = float(accepted) / max(float(proposed), 1.0)
    for mode in ("speculative", "plain"):
        emit({"phase": "spec_ab", "mode": mode, "batch": BATCH,
              "decode_steps": steps, "spec_k": SPEC_K,
              "draft_layers": DRAFT_LAYERS, "rounds": ROUNDS,
              "favorable": bool(SPEC_FAVORABLE),
              "tokens_per_sec": float(np.median(rates[mode])),
              "tokens_per_sec_rounds": [float(r) for r in rates[mode]],
              "wall_s": float(walls[mode])})
    sp = float(np.median(rates["speculative"]))
    pl = float(np.median(rates["plain"]))
    emit({"phase": "spec_speedup", "batch": BATCH, "decode_steps": steps,
          "spec_k": SPEC_K, "draft_layers": DRAFT_LAYERS,
          "favorable": bool(SPEC_FAVORABLE),
          "acceptance_rate": acceptance,
          "spec_tokens_per_sec": sp, "plain_tokens_per_sec": pl,
          "speedup": sp / pl})
    return sp / pl


def bench_prefix_ab(model_dir):
    """Shared-prefix admission vs private prefills: CONT_REQUESTS
    requests over PREFIX_GROUPS distinct prompts through a prefix-
    cached and an uncached DecodeServer."""
    from paddle_tpu.serving.decode import DecodePredictor, DecodeServer

    rng = np.random.RandomState(4)
    steps = min(STEPS_LIST)
    groups = _prompts(PREFIX_GROUPS, rng, length=PROMPT)
    prompts = [groups[i % PREFIX_GROUPS] for i in range(CONT_REQUESTS)]
    max_new = max(4, steps // 4)

    pred = DecodePredictor(model_dir)
    servers = {}
    for mode in ("shared", "private"):
        srv = DecodeServer(pred, slots=CONT_SLOTS,
                           max_seq=PROMPT + max_new + SPEC_K + 1,
                           max_new_tokens=max_new,
                           prefix_cache=(mode == "shared"))
        srv.start()
        servers[mode] = srv

    def run_round(mode):
        srv = servers[mode]
        t0 = time.perf_counter()
        futs = [srv.submit((p,)) for p in prompts]
        outs = [f.result(timeout=600)[0] for f in futs]
        return outs, time.perf_counter() - t0

    results = {}
    for mode in ("shared", "private"):  # untimed warm round per arm
        results[mode], _ = run_round(mode)
    assert all(np.array_equal(a, b) for a, b in
               zip(results["shared"], results["private"])), \
        "prefix-shared admission diverged from private prefills"
    rates = {"shared": [], "private": []}
    walls = {"shared": 0.0, "private": 0.0}
    prefills = {}
    base = {m: servers[m].prefill_executions for m in servers}
    for rnd in range(CONT_ROUNDS):
        order = (("shared", "private") if rnd % 2 == 0
                 else ("private", "shared"))
        for mode in order:
            outs, dt = run_round(mode)
            rates[mode].append(sum(len(o) for o in outs) / dt)
            walls[mode] += dt
    for mode in ("shared", "private"):
        prefills[mode] = servers[mode].prefill_executions - base[mode]
        servers[mode].stop()
        emit({"phase": "prefix_ab", "mode": mode, "slots": CONT_SLOTS,
              "requests": CONT_REQUESTS, "groups": PREFIX_GROUPS,
              "max_new": max_new, "rounds": CONT_ROUNDS,
              "prefill_executions": int(prefills[mode]),
              "tokens_per_sec": float(np.median(rates[mode])),
              "tokens_per_sec_rounds": [float(r) for r in rates[mode]],
              "wall_s": float(walls[mode])})
    sh = float(np.median(rates["shared"]))
    pr = float(np.median(rates["private"]))
    emit({"phase": "prefix_speedup", "slots": CONT_SLOTS,
          "requests": CONT_REQUESTS, "groups": PREFIX_GROUPS,
          "shared_tokens_per_sec": sh, "private_tokens_per_sec": pr,
          "shared_prefills": int(prefills["shared"]),
          "private_prefills": int(prefills["private"]),
          "speedup": sh / pr})
    return sh / pr


def bench_batch_modes(model_dir):
    from paddle_tpu.serving.decode import DecodePredictor, DecodeServer

    steps = max(STEPS_LIST)
    rng = np.random.RandomState(1)
    prompts = _prompts(CONT_REQUESTS, rng)
    if CONT_MAXNEW_MIX:
        mix = [int(x) for x in CONT_MAXNEW_MIX.split(",")]
    else:
        mix = [max(4, steps // 16), steps // 2]
    budgets = [mix[i % len(mix)] for i in range(CONT_REQUESTS)]
    max_new = max(budgets)

    # ONE predictor (and its executable cache) behind both schedules:
    # the A/B measures the SCHEDULING policy, not who compiled first
    pred = DecodePredictor(model_dir)
    servers = {}
    for mode in ("continuous", "static"):
        srv = DecodeServer(pred, slots=CONT_SLOTS,
                           max_seq=PROMPT + max_new,
                           max_new_tokens=max_new,
                           continuous=(mode == "continuous"))
        srv.start()
        servers[mode] = srv

    def run_round(mode):
        srv = servers[mode]
        t0 = time.perf_counter()
        futs = [srv.submit((p, np.array([mn], np.int64)))
                for p, mn in zip(prompts, budgets)]
        outs = [f.result(timeout=600)[0] for f in futs]
        return [np.asarray(o) for o in outs], time.perf_counter() - t0

    results = {}
    rates = {"continuous": [], "static": []}
    walls = {"continuous": 0.0, "static": 0.0}
    active = {"continuous": [], "static": []}
    iters = {}
    for mode in ("continuous", "static"):  # untimed warm round per arm
        results[mode], _ = run_round(mode)
        servers[mode].step_active_counts.clear()
    for rnd in range(CONT_ROUNDS):
        # alternate which arm goes first so slow drifts (thermal, other
        # tenants of this box) hit both equally
        order = (("continuous", "static") if rnd % 2 == 0
                 else ("static", "continuous"))
        for mode in order:
            outs, dt = run_round(mode)
            toks = sum(len(o) for o in outs)
            rates[mode].append(toks / dt)
            walls[mode] += dt
    for mode in ("continuous", "static"):
        srv = servers[mode]
        if srv.step_active_counts:
            active[mode].append(float(np.mean(srv.step_active_counts)))
        # structural, noise-free half of the claim: decode iterations
        # per round — continuous needs fewer sweeps of the same (slots,
        # S) executable to emit the same tokens
        iters[mode] = len(srv.step_active_counts) / float(CONT_ROUNDS)
        srv.stop()
    # both schedules must produce identical tokens (greedy, same model)
    assert all(np.array_equal(a, b) for a, b in
               zip(results["continuous"], results["static"])), \
        "continuous and static batching diverged"
    for mode in ("continuous", "static"):
        emit({"phase": "batch_mode", "mode": mode, "slots": CONT_SLOTS,
              "requests": CONT_REQUESTS,
              "max_new_mix": ",".join(str(m) for m in mix),
              "rounds": CONT_ROUNDS,
              "tokens": sum(budgets),
              "tokens_per_sec": float(np.median(rates[mode])),
              "tokens_per_sec_rounds": [float(r) for r in rates[mode]],
              "mean_active": (float(np.mean(active[mode]))
                              if active[mode] else 0.0),
              "decode_iters_per_round": float(iters[mode]),
              "wall_s": float(walls[mode])})
    cont = float(np.median(rates["continuous"]))
    stat = float(np.median(rates["static"]))
    emit({"phase": "batching_speedup", "slots": CONT_SLOTS,
          "requests": CONT_REQUESTS,
          "continuous_tokens_per_sec": cont,
          "static_tokens_per_sec": stat, "speedup": cont / stat,
          "iters_ratio": float(iters["static"])
          / max(float(iters["continuous"]), 1.0)})
    return cont / stat


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--speculative", action="store_true",
                    help="add the speculative-vs-plain interleaved A/B")
    ap.add_argument("--prefix-share", action="store_true",
                    help="add the shared-prefix admission A/B")
    args = ap.parse_args(argv)

    from paddle_tpu.serving.decode import DecodePredictor

    with tempfile.TemporaryDirectory() as model_dir:
        _export_model(model_dir,
                      spec_favorable=args.speculative and SPEC_FAVORABLE)
        pred = DecodePredictor(model_dir, draft_n_layer=DRAFT_LAYERS)
        for steps in STEPS_LIST:
            bench_decode_ab(pred, steps)
        if args.speculative:
            bench_spec_ab(pred, max(STEPS_LIST))
        del pred
        bench_batch_modes(model_dir)
        if args.prefix_share:
            bench_prefix_ab(model_dir)


if __name__ == "__main__":
    main()
