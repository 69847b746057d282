"""`tools/calibrate.py`'s serving half for the cells of
`lib/run_serveany.py`: read, on the chip and at the cell's own size,
the two numbers `check.serve.logits_rel_l2` is set from: what the
program gives over many seeds (prefill, then decode through the cache,
by the server's own executables and the runner's own rollout), and what
the control gives (the reference computed in the nearest precision
below the one the configuration states). One process; one JSON line a
seed, appended to `chiprun_out/benchmark/calibrate.jsonl`.

    python benchmark/tools/calibrate_serveany.py --workload <cell> --seeds 12 --control-seeds 3
"""
from __future__ import annotations

import argparse
import gc
import importlib
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np  # noqa: E402

from benchmark.lib import compare, harness, run_serveany, weights  # noqa: E402
from benchmark.tools.calibrate import _emit  # noqa: E402


def serve(cell, cfg, mix, seeds, control_seeds, controls):
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.serving import DecodePredictor, save_decode_model

    model = importlib.import_module("benchmark.models." + cfg["builder"])
    ref = importlib.import_module("benchmark.reference." + cfg["reference"])
    dev = jax.devices()[0]
    place = fluid.TPUPlace() if dev.platform == "tpu" else fluid.CPUPlace()
    kind = "serve_" + mix["kind"].split("_", 1)[1]
    chk = cfg["check"]["serve"]
    slots, seq = int(cfg["serve"]["slots"]), int(cfg["serve"]["max_seq"])
    n_layer = model.depth(cfg, kind)
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
                lg[prec].append(np.asarray(run_serveany._reference_logits(
                    ref, w, full, cfg, n_layer, prec, at)))
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
        rows, _ = run_serveany._direct_rollout(pred, probes, k, slots, seq,
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
        # the two probes apart: which of them sets the reading
        at = 0
        for p, g in zip(probes, rows):
            n = len(g)
            rec["program_vs_bf16_ops len%d" % len(p)] = compare.rel_l2(
                got_all[at:at + n], ref_all["bf16_ops"][at:at + n])
            at += n
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
    ap.add_argument("--controls", default="bf16")
    a = ap.parse_args()
    harness.setup_env(harness.ROOT)
    _, cell, cfg, mix = harness.load_cell(harness.ROOT, a.workload)
    seeds = [a.first_seed + 7919 * i for i in range(a.seeds)]
    serve(cell, cfg, mix, seeds, a.control_seeds,
          [c for c in a.controls.split(",") if c])


if __name__ == "__main__":
    main()
