"""`tools/calibrate_serveany.py`'s counterpart for the three
comparisons `lib/run_serveround.py` adds: read, on the chip and at the
cell's own size, what the PROGRAM gives over seeds (the first layer's
kept rows, both positions of each round, the prediction layer's logits,
each against the reference in the arithmetic the configuration states)
and what each CONTROL gives by the same comparison: the reference one
storage precision lower (`bf16`: it has to fail `slab_rows_rel_l2`)
and the reference with a part of the prediction layer changed (it has
to fail `draft_logits_rel_l2`). One process; one JSON line a seed,
appended to `chiprun_out/benchmark/calibrate.jsonl`.

    python benchmark/tools/calibrate_serveround.py --workload <cell> --seeds 3
"""
from __future__ import annotations

import argparse
import gc
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import harness, run_serveround as rr  # noqa: E402
from benchmark.tools.calibrate import _emit  # noqa: E402

CONTROLS = ("bf16", "+mtp_concat_reversed", "+mtp_hidden_before_norm")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3000500001)
    ap.add_argument("--controls", default=",".join(CONTROLS))
    a = ap.parse_args()
    cache_dir = harness.setup_env(harness.ROOT)
    bench, cell, cfg, mix = harness.load_cell(harness.ROOT, a.workload)

    import jax

    base = cfg["check"]["serve"]["reference_precision"]
    for i in range(a.seeds):
        t0 = time.time()
        ctx = harness.Ctx(
            cfg=cfg, mix=mix, seed=a.first_seed + 7919 * i,
            devices=jax.devices()[:1], cache_dir=cache_dir,
            work_dir=os.path.join(harness.WORK_ROOT, "calibrate_round"))
        model = ctx.module("models", cfg["builder"])
        ref = ctx.module("reference", cfg["reference"])
        kind = "serve_" + mix["kind"].split("_", 1)[1]
        got = rr.program_side(ctx, model, kind)
        chose = [g["chose"] for g in got]
        want = rr.reference_side(ctx, model, ref, kind, base, chose)
        rec = {"workload": cell["name"], "seed": ctx.seed,
               "program": rr.readings(got, want)}
        for c in [c for c in a.controls.split(",") if c]:
            prec = base + c if c.startswith("+") else c
            ctl = rr.reference_side(ctx, model, ref, kind, prec, chose)
            rec["program_vs_" + prec] = rr.readings(got, ctl)
            rec[prec + "_vs_" + base] = rr.readings(ctl, want)
            del ctl
            gc.collect()
        rec["seconds"] = time.time() - t0
        _emit(rec)
        del got, want
        gc.collect()


if __name__ == "__main__":
    main()
