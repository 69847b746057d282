"""Show that a serving cell's comparison sees each mechanism: the
PROGRAM's logits (the runner's own rollout, `lib/run_serveany.py`:
prefill, then decode through the caches by the server's executables at
the cell's slots and `max_seq`) against the reference with one part
left out (`reference_precision` + "+<variant>": what the cell's run
would compare with, were the program right and the equations short of
that part), by the runner's own `compare.rel_l2` and `Checks.add`
against the cell's own limit. A variant that reads `ok` is a fault the
cell cannot see. One process, one seed, the program built once; one
JSON line a (prompt, variant), appended to
`chiprun_out/benchmark/calibrate.jsonl`.

    python benchmark/tools/variants_serveany.py --workload <cell> \\
        --variants no_shared,no_routed --prompt-lens 300,3000,3073
"""
from __future__ import annotations

import argparse
import gc
import importlib
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np  # noqa: E402

from benchmark.lib import compare, harness, run_serveany, weights  # noqa: E402
from benchmark.tools.calibrate import _emit  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=3000000001)
    ap.add_argument("--variants", required=True)
    ap.add_argument("--prompt-lens", default="",
                    help="default: the cell's own check.serve.prompt_lens")
    a = ap.parse_args()
    harness.setup_env(harness.ROOT)
    _, cell, cfg, mix = harness.load_cell(harness.ROOT, a.workload)

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
    k = int(chk["decode_steps"])
    lens = ([int(n) for n in a.prompt_lens.split(",")] if a.prompt_lens
            else chk["prompt_lens"])
    w = weights.seeded_weights(model.parameter_specs(cfg, kind), a.seed,
                               model.init_rule, device=dev)
    r = np.random.default_rng(np.random.SeedSequence([a.seed, 9]))
    probes = [r.integers(1, cfg["vocab_size"], n, dtype=np.int64)
              for n in lens]
    forced = [r.integers(1, cfg["vocab_size"], k + 1, dtype=np.int64)
              for _ in probes]
    base = chk["reference_precision"]
    precs = [base] + [base + "+" + v for v in a.variants.split(",") if v]
    want = {}
    for p, f in zip(probes, forced):
        full = jax.numpy.asarray(np.concatenate([p, f[:k]]))
        at = np.arange(len(p) - 1, len(p) + k)
        for prec in precs:
            want[len(p), prec] = np.asarray(run_serveany._reference_logits(
                ref, w, full, cfg, n_layer, prec, at))
    work = os.path.join(harness.ROOT, ".bench_cache", "variants")
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
    shutil.rmtree(work, ignore_errors=True)
    checks = compare.Checks()
    for p, got in zip(probes, rows):
        for prec in precs:
            value = compare.rel_l2(np.stack(got), want[len(p), prec])
            ok = checks.add("len%d program vs %s" % (len(p), prec), value,
                            chk["logits_rel_l2"])
            _emit({"workload": cell["name"], "seed": a.seed,
                   "prompt_len": len(p), "reference": prec,
                   "program_vs_reference": value,
                   "limit": chk["logits_rel_l2"], "ok": ok})


if __name__ == "__main__":
    main()
