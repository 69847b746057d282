"""Runner for mixes of kind `serveround_closed` and `serveround_open`:
a model that is served in ROUNDS (a multi-token-prediction layer as the
draft: two positions a slot a dispatch, `DecodePredictor.acquire(
"round", ...)`). It is `run_serveany.py`'s procedure, which runs next
and unchanged, behind three comparisons that runner cannot make:

* `slab_rows_rel_l2`: what the FIRST layer keeps of every position (its
  index keys and latent rows, as the prefill wrote them and as the
  rounds appended them) against the reference's. Layer 0 reads the
  embedding alone, so no choice of rows lies upstream: program and
  reference differ by the order of float32 sums, and a slab held one
  precision lower differs by its rounding. The logits cannot show that:
  every choice of 2,048 among thousands of scores amplifies a last-bit
  difference to ~0.01 of them, the program's rounding order and a
  lower storage precision alike (PERF.md, PR 58).
* `round_logits_rel_l2`: BOTH positions of each round against the
  reference's logits at those positions (`run_serveany` steps one token
  at a time: position 1 of a round never reaches its comparison).
* `draft_logits_rel_l2`: the prediction layer's logits at both
  positions of each round against `reference.draft_logits`, over the
  rows its prefill walk and its earlier rounds left.

The rounds are teacher-forced with the tokens `run_serveany` forces on
its steps (the same seed's draws), two a round: a round's hypothesis
row IS the next forced token's, so lengths advance by two whatever was
accepted. The prediction layer is handed the token the MODEL chose
after each position (a server's rounds do that), so its reference is
computed on the program's own choices, after the rollout.

The weights, the export and the predictor are made here and dropped
before `run_serveany.run` makes its own: the two never share a chip.
"""
from __future__ import annotations

import gc
import os
import shutil
import time

import numpy as np

from . import compare, run_serveany, weights
from .run_serve import _bucket

FIRST = ("index_0", "latent_0")  # the first layer's entries, by name


def probes_of(cfg, seed):
    """`run_serveany.run`'s probe prompts and forced tokens."""
    chk = cfg["check"]["serve"]
    r = np.random.default_rng(np.random.SeedSequence([seed, 9]))
    probes = [r.integers(1, cfg["vocab_size"], n, dtype=np.int64)
              for n in chk["prompt_lens"]]
    k = int(chk["decode_steps"])
    assert k % 2 == 0, "rounds take the forced tokens two at a time"
    return probes, [r.integers(1, cfg["vocab_size"], k + 1, dtype=np.int64)
                    for _ in probes], k


def rollout(pred, probes, forced, k, slots, seq):
    """Prefill each probe at batch 1, then k / 2 rounds at (slots, seq)
    on the forced tokens. Per probe: the k positions' logits and the
    prediction layer's, the token the model chose after the prompt's
    last position and after each round position (k + 1), and the first
    layer's kept rows over prompt + k positions, by entry name."""
    import jax.numpy as jnp

    spec = pred.cache_spec(slots, seq)
    names = [e.name for e in spec]
    caches = [jnp.zeros(e.shape, e.dtype) for e in spec]
    lens = np.zeros((slots,), np.int32)
    n = len(probes)
    chose = [[] for _ in probes]
    for i, p in enumerate(probes):
        sp = min(_bucket(len(p)), seq)
        pexe, _ = pred.acquire("prefill", 1, sp)
        tokens = np.zeros((1, sp), np.int64)
        tokens[0, :len(p)] = p
        outs = pexe({"tokens": tokens,
                     "lengths": np.array([len(p)], np.int32)}, pred._state)
        chose[i].append(int(np.asarray(outs[0])[0].argmax()))
        for j, sub in enumerate(outs[1:1 + len(spec)]):
            caches[j] = caches[j].at[i, :sp].set(jnp.asarray(sub)[0])
        lens[i] = len(p)
    rexe, _ = pred.acquire("round", slots, seq)
    logits = [[] for _ in probes]
    drafts = [[] for _ in probes]
    for r in range(k // 2):
        tokens = np.zeros((slots, 2), np.int64)
        for i in range(n):
            tokens[i] = forced[i][2 * r:2 * r + 2]
        feeds = {"tokens": tokens, "lengths": lens.copy()}
        feeds.update(zip(names, caches))
        outs = rexe(feeds, pred._state)
        ids, lg, dl = (np.asarray(o) for o in outs[:3])
        caches = list(outs[3:3 + len(spec)])
        for i in range(n):
            logits[i].extend(lg[i])
            drafts[i].extend(dl[i])
            chose[i].extend(int(t) for t in ids[i, :2])
        lens[:n] += 2
    kept = [{name: np.asarray(caches[names.index(name)][i, :lens[i]])
             for name in FIRST} for i in range(n)]
    return [{"logits": np.stack(logits[i]), "drafts": np.stack(drafts[i]),
             "chose": np.asarray(chose[i], np.int64), "kept": kept[i]}
            for i in range(n)]


def wanted(ref, w, cfg, n_layer, precision, probes, forced, k, chose):
    """The reference's side of `rollout`, a probe at a time, in
    `precision`: `logits` and `kept` from the weights alone, `drafts`
    on the tokens `chose[i]` that the program's model chose."""
    import jax.numpy as jnp

    out = []
    for p, f, c in zip(probes, forced, chose):
        text = jnp.asarray(np.concatenate([p, f[:k]]))
        at = np.arange(len(p), len(p) + k)
        keys, rows = ref.first_layer_rows(w, text, cfg, precision=precision)
        out.append({
            "kept": dict(zip(FIRST, (np.asarray(keys), np.asarray(rows)))),
            "logits": np.asarray(ref.serve_logits(
                w, text, cfg, n_layer, precision=precision, rows=at)),
            "drafts": np.asarray(ref.draft_logits(
                w, text, jnp.asarray(np.concatenate([p[1:], c])), cfg,
                n_layer, precision=precision, rows=at))})
    return out


def readings(got, want) -> dict:
    """The three numbers, each the worst over the probes."""
    pairs = list(zip(got, want))
    return {
        "slab_rows_rel_l2": max(compare.rel_l2(g["kept"][n], w["kept"][n])
                                for g, w in pairs for n in FIRST),
        "round_logits_rel_l2": max(compare.rel_l2(g["logits"], w["logits"])
                                   for g, w in pairs),
        "draft_logits_rel_l2": max(compare.rel_l2(g["drafts"], w["drafts"])
                                   for g, w in pairs)}


def program_side(ctx, model, kind):
    """Weights from the seed -> export -> load -> `rollout`; everything
    it made is dropped before it returns."""
    import paddle_tpu as fluid
    from paddle_tpu.serving import DecodePredictor, save_decode_model

    cfg = ctx.cfg
    dev = ctx.devices[0]
    place = fluid.TPUPlace() if dev.platform == "tpu" else fluid.CPUPlace()
    w = weights.seeded_weights(model.parameter_specs(cfg, kind), ctx.seed,
                               model.init_rule, device=dev)
    model_dir = os.path.join(ctx.work_dir, "round_model")
    shutil.rmtree(model_dir, ignore_errors=True)
    os.makedirs(model_dir)
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
    pred = DecodePredictor(model_dir, place=place, cache_dir=os.path.join(
        ctx.cache_dir, "decode_aot_" + cfg["name"]))
    probes, forced, k = probes_of(cfg, ctx.seed)
    got = rollout(pred, probes, forced, k, int(cfg["serve"]["slots"]),
                  int(cfg["serve"]["max_seq"]))
    del pred
    gc.collect()
    shutil.rmtree(model_dir, ignore_errors=True)
    return got


def reference_side(ctx, model, ref, kind, precision, chose):
    """Weights from the seed -> `wanted` in `precision`."""
    cfg = ctx.cfg
    w = weights.seeded_weights(model.parameter_specs(cfg, kind), ctx.seed,
                               model.init_rule, device=ctx.devices[0])
    probes, forced, k = probes_of(cfg, ctx.seed)
    return wanted(ref, w, cfg, model.depth(cfg, kind), precision, probes,
                  forced, k, chose)


def run(ctx) -> dict:
    cfg, mix = ctx.cfg, ctx.mix
    model = ctx.module("models", cfg["builder"])
    ref = ctx.module("reference", cfg["reference"])
    kind = "serve_" + mix["kind"].split("_", 1)[1]
    chk = cfg["check"]["serve"]

    t0 = time.time()
    got = program_side(ctx, model, kind)
    ctx.log("round_rollout", seconds=time.time() - t0)
    t0 = time.time()
    want = reference_side(ctx, model, ref, kind, chk["reference_precision"],
                          [g["chose"] for g in got])
    gc.collect()
    ctx.log("round_reference", seconds=time.time() - t0)
    checks = compare.Checks()
    for name, value in readings(got, want).items():
        checks.add(name + " rounds vs reference", value, chk[name])
    del got, want

    out = run_serveany.run(ctx)
    out["correct"] = bool(out["correct"] and checks.ok)
    return out
