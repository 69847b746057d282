"""Read, on the chip and at the cell's own size, the two numbers a limit
of `correct` is set from: what sound runs of the program give over many
seeds, and what the control gives (the reference put in the program's
place, computed in the nearest precision below the one the
configuration states). One process; prints one JSON line per seed and a
summary, and appends them to `chiprun_out/benchmark/calibrate.jsonl`.

    python benchmark/tools/calibrate.py --workload <cell> --seeds 12 --control-seeds 3
"""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np  # noqa: E402

from benchmark.lib import compare, harness, weights  # noqa: E402


def _emit(rec):
    line = json.dumps(rec, sort_keys=True)
    print(line, flush=True)
    out = os.path.join(harness.ROOT, "chiprun_out", "benchmark")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "calibrate.jsonl"), "a") as f:
        f.write(line + "\n")


def _diff(got, want, grads):
    d = {"loss_rel_err": abs(float(got["loss"]) - float(want["loss"]))
         / abs(float(want["loss"]))}
    for g in grads:
        d["grad_rel_l2 " + g] = compare.rel_l2(got[g], want[g])
    return d


def train(cell, cfg, mix, seeds, control_seeds, controls, grads):
    import jax

    from benchmark.lib import run_train

    model = importlib.import_module("benchmark.models." + cfg["builder"])
    ref = importlib.import_module("benchmark.reference." + cfg["reference"])
    tr = run_train.Trainer(cfg, mix, cell["chips"],
                           jax.devices()[:cell["chips"]], model)
    grads = grads or model.check_grads(cfg)
    depth = model.depth(cfg, "train")
    for si, seed in enumerate(seeds):
        t0 = time.time()
        w = tr.reset(seed)
        _, check_np, ref_in = model.train_pool(cfg, mix, seed)
        want = {k: np.asarray(v) for k, v in ref.train_check(
            w, ref_in, cfg, depth, grads).items()}
        rec = {"workload": cell["name"], "seed": seed}
        if si < control_seeds:
            for prec in controls:
                ctl = {k: np.asarray(v) for k, v in ref.train_check(
                    w, ref_in, cfg, depth, grads, precision=prec).items()}
                rec["control_" + prec] = _diff(ctl, want, grads)
                del ctl
        del w
        feed = {k: tr.put(v) for k, v in check_np.items()}
        out = tr.step(feed, [tr.loss] + [g + "@GRAD" for g in grads])
        got = dict(zip(["loss"] + grads, (np.asarray(o) for o in out)))
        rec["program"] = _diff(got, want, grads)
        rec["loss_ref"] = float(want["loss"])
        rec["seconds"] = time.time() - t0
        _emit(rec)
        del got, want, out, feed
        gc.collect()


def serve(cell, cfg, mix, seeds, control_seeds, controls):
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.serving import DecodePredictor, save_decode_model

    from benchmark.lib import run_serve

    model = importlib.import_module("benchmark.models." + cfg["builder"])
    ref = importlib.import_module("benchmark.reference." + cfg["reference"])
    dev = jax.devices()[0]
    place = fluid.TPUPlace() if dev.platform == "tpu" else fluid.CPUPlace()
    kind = mix["kind"]
    chk = cfg["check"]["serve"]
    slots, seq = int(cfg["serve"]["slots"]), int(cfg["serve"]["max_seq"])
    n_layer, n_head = model.depth(cfg, kind), cfg["num_attention_heads"]
    specs = model.parameter_specs(cfg, kind)
    k = int(chk["decode_steps"])
    work = os.path.join(harness.ROOT, ".bench_cache", "calibrate")
    for si, seed in enumerate(seeds):
        t0 = time.time()
        w = weights.seeded_weights(specs, seed, model.init_rule, device=dev)
        r = np.random.default_rng(np.random.SeedSequence([seed, 9]))
        probes = [r.integers(1, cfg["vocab_size"], n, dtype=np.int64)
                  for n in chk["prompt_lens"]]
        forced = [r.integers(1, cfg["vocab_size"], k + 1, dtype=np.int64)
                  for _ in probes]
        rec = {"workload": cell["name"], "seed": seed}
        precs = ["highest", "bf16_ops"] + (
            list(controls) if si < control_seeds else [])
        lg = {p: [] for p in precs}
        for p, f in zip(probes, forced):
            full = jax.numpy.asarray(np.concatenate([p, f[:k]]))
            at = np.arange(len(p) - 1, len(p) + k)
            for prec in precs:
                lg[prec].append(np.asarray(ref.logits(
                    w, full, n_layer, n_head, precision=prec, rows=at)))
        ref_all = {p: np.concatenate(v) for p, v in lg.items()}
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
        pred = DecodePredictor(work, place=place, cache_dir=os.path.join(
            harness.ROOT, ".xla_cache", "decode_aot_" + cfg["name"]))
        rows, _ = run_serve._direct_rollout(pred, probes, k, slots, seq,
                                            forced=forced)
        del pred
        gc.collect()
        got_all = np.concatenate([np.stack(g) for g in rows])
        for base in ("highest", "bf16_ops"):
            rec["program_vs_" + base] = compare.rel_l2(got_all,
                                                       ref_all[base])
            for prec in precs:
                if prec not in ("highest", base):
                    rec["control_%s_vs_%s" % (prec, base)] = compare.rel_l2(
                        ref_all[prec], ref_all[base])
        # how often the two references' greedy tokens part
        rec["argmax_agree_highest"] = float(
            (got_all.argmax(-1) == ref_all["highest"].argmax(-1)).mean())
        rec["seconds"] = time.time() - t0
        _emit(rec)
        del rows, got_all, ref_all, lg
        gc.collect()
    shutil.rmtree(work, ignore_errors=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3000000001)
    ap.add_argument("--controls", default="")
    ap.add_argument("--grads", default="")
    a = ap.parse_args()
    harness.setup_env(harness.ROOT)
    _, cell, cfg, mix = harness.load_cell(harness.ROOT, a.workload)
    seeds = [a.first_seed + 7919 * i for i in range(a.seeds)]
    kind = mix["kind"].split("_")[0]
    grads = [g for g in a.grads.split(",") if g]
    if kind == "train":
        controls = [c for c in a.controls.split(",") if c] or ["int8"]
        train(cell, cfg, mix, seeds, a.control_seeds, controls, grads)
    else:
        controls = [c for c in a.controls.split(",") if c] or ["bf16"]
        serve(cell, cfg, mix, seeds, a.control_seeds, controls)


if __name__ == "__main__":
    main()
