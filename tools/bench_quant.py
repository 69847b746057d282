"""Int8 quantization bench: serving rows/s A/B, parity, slab capacity.

Three measurements, one JSON line per config (schema ``bench_quant/1``,
pinned by tests/test_bench_quant_smoke.py):

1. **Serving rows/s** (``quant`` lines): the float serving path
   (optimize-level-2 export) vs the int8 export
   (``save_inference_model(quantize=calib_table)``) of the SAME
   trained-init model, both through ``Predictor.run`` — interleaved
   rounds with arm order alternated per round (the bench_transpile /
   bench_decode discipline), medians reported, ``rows_per_s_speedup``
   = quant / float.

2. **Parity** (embedded in every ``quant`` line): the
   ``quant.parity_report`` fields (max/mean abs logits diff, top-1
   agreement) on held-out batches — a run that breaks parity reports
   ``parity_ok: false`` instead of banking a bogus speedup.

3. **Slab capacity** (``quant_slab`` line): ``kv_slab_slots`` at a
   serving-realistic decode config and byte budget — how many
   continuous-batching sequences one KV slab budget holds at
   float32 / bfloat16 / int8, with ``capacity_ratio_vs_bf16`` the
   2x-sequences claim. Pure arithmetic plus (with ``--decode-roundtrip``)
   an actual int8-slab DecodeServer round trip at the computed slot
   count.

CPU honesty (the PR-8/PR-9 lesson): this box's XLA CPU GEMM has no
int8 fast path — the device-window claim (>=1.5x rows/s on MLP/DeepFM
at matched accuracy, int8 on the MXU) has not been measured on a
chip; the numbers here measure the
mechanism and the parity, not the silicon win.

Usage:
    JAX_PLATFORMS=cpu python tools/bench_quant.py \
        [--configs mlp,deepfm] [--rounds 3] [--batches 16] \
        [--batch-rows 256] [--decode-roundtrip]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import jax

jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])

import numpy as np

SCHEMA = "bench_quant/1"


def _build(config, batch_rows, rs):
    """(inference program, scope, feed_names, fetch_names, make_feed):
    initialized inference graphs for the serving benches."""
    import paddle_tpu as fluid
    from paddle_tpu import layers

    scope = fluid.Scope()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.scope_guard(scope), fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            if config in ("mlp", "mlp-tiny"):
                dim = 784 if config == "mlp" else 16
                x = layers.data(name="pixel", shape=[dim])
                if config == "mlp":
                    from paddle_tpu.models.mnist import mlp_model

                    predict = mlp_model(x)
                else:
                    predict = layers.fc(layers.fc(x, 8, act="relu"), 4,
                                        act="softmax")
                feed_names = ["pixel"]
                fetches = [predict.name]

                def make_feed():
                    return {"pixel": rs.rand(batch_rows, dim)
                            .astype(np.float32)}
            elif config == "deepfm":
                from paddle_tpu.models.deepfm import deepfm_net

                feat_ids = layers.data(name="feat_ids", shape=[10],
                                       dtype="int64")
                dense = layers.data(name="dense", shape=[13])
                label = layers.data(name="label", shape=[1],
                                    dtype="int64")
                _cost, prob = deepfm_net(feat_ids, dense, label,
                                         num_features=1000,
                                         num_fields=10)
                feed_names = ["feat_ids", "dense", "label"]
                fetches = [prob.name]

                def make_feed():
                    return {
                        "feat_ids": rs.randint(0, 1000, (batch_rows, 10))
                        .astype(np.int64),
                        "dense": rs.rand(batch_rows, 13)
                        .astype(np.float32),
                        "label": rs.randint(0, 2, (batch_rows, 1))
                        .astype(np.int64),
                    }
            else:
                raise SystemExit("unknown config %r" % config)
        exe = fluid.Executor()
        with fluid.scope_guard(scope):
            exe.run(startup)
    infer = main.clone(for_test=True)
    return infer, scope, feed_names, fetches, make_feed


def _rows_per_s(predictor, feeds):
    t0 = time.perf_counter()
    for f in feeds:
        predictor.run(f)
    dt = time.perf_counter() - t0
    rows = sum(next(iter(f.values())).shape[0] for f in feeds)
    return rows / dt


def bench_config(config, rounds, batches, batch_rows, calib_batches):
    import tempfile

    import paddle_tpu as fluid
    from paddle_tpu.inference import Predictor
    from paddle_tpu.quant import calibrate, parity_report

    rs = np.random.RandomState(0)
    infer, scope, feed_names, fetches, make_feed = _build(
        config, batch_rows, rs)
    calib_feeds = [make_feed() for _ in range(calib_batches)]
    table = calibrate(infer, scope, feed_names, calib_feeds,
                      max_batches=calib_batches)

    td = tempfile.mkdtemp(prefix="bench_quant_")
    float_dir = os.path.join(td, "float")
    quant_dir = os.path.join(td, "int8")
    exe = fluid.Executor()
    with fluid.scope_guard(scope):
        fluid.io.save_inference_model(
            float_dir, feed_names, fetches, exe, main_program=infer,
            scope=scope, optimize=2)
        fluid.io.save_inference_model(
            quant_dir, feed_names, fetches, exe, main_program=infer,
            scope=scope, quantize=table)

    p_float = Predictor(float_dir, aot_cache=False)
    p_quant = Predictor(quant_dir, aot_cache=False)
    bench_feeds = [make_feed() for _ in range(batches)]
    # warm both arms (compile outside the measured window)
    _rows_per_s(p_float, bench_feeds[:1])
    _rows_per_s(p_quant, bench_feeds[:1])

    f_rates, q_rates = [], []
    for rep in range(rounds):
        arms = [("float", p_float, f_rates), ("int8", p_quant, q_rates)]
        if rep % 2:
            arms.reverse()
        for _name, pred, acc in arms:
            acc.append(_rows_per_s(pred, bench_feeds))
    f_med = float(np.median(f_rates))
    q_med = float(np.median(q_rates))

    held_out = [make_feed() for _ in range(4)]
    par = parity_report(p_float, p_quant, held_out,
                        logits_tol=0.05, metric_tol=0.02)
    return {
        "bench": "quant", "schema": SCHEMA, "config": config,
        "rounds": rounds, "batches": batches, "batch_rows": batch_rows,
        "calib_batches": table.batches,
        "quantized_ops": int(
            (json.load(open(os.path.join(quant_dir, "__model__")))
             ["program"].get("quantized") or {}).get("ops", 0)),
        "rows_per_s_float": [round(r, 2) for r in f_rates],
        "rows_per_s_int8": [round(r, 2) for r in q_rates],
        "rows_per_s_float_median": round(f_med, 2),
        "rows_per_s_int8_median": round(q_med, 2),
        "rows_per_s_speedup": round(q_med / f_med, 4) if f_med else None,
        "parity_max_abs_diff": par["max_abs_diff"],
        "parity_mean_abs_diff": par["mean_abs_diff"],
        "parity_metric_agreement": par["metric_agreement"],
        "parity_ok": par["ok"],
    }


def bench_slab(decode_roundtrip: bool):
    """KV-slab capacity at a serving-realistic decode config: slots per
    byte budget by slab dtype (+ an int8 DecodeServer round trip at the
    computed slot count when requested)."""
    from paddle_tpu.serving.decode import DecodeConfig, kv_slab_slots

    cfg = DecodeConfig(vocab_size=32768, n_layer=12, n_head=8,
                       d_model=1024, d_inner=4096, max_len=2048)
    seq = 1024
    budget = 256 << 20  # 256 MiB of slab per replica
    slots = {dt: kv_slab_slots(budget, cfg, seq, dt)
             for dt in ("float32", "bfloat16", "int8")}
    line = {
        "bench": "quant_slab", "schema": SCHEMA,
        "config": "lm-%dx%d" % (cfg.n_layer, cfg.d_model),
        "seq": seq, "budget_bytes": budget,
        "slots_float32": slots["float32"],
        "slots_bfloat16": slots["bfloat16"],
        "slots_int8": slots["int8"],
        "capacity_ratio_vs_bf16": round(
            slots["int8"] / max(slots["bfloat16"], 1), 4),
        "decode_roundtrip": None,
    }
    if decode_roundtrip:
        line["decode_roundtrip"] = _decode_roundtrip()
    return line


def _decode_roundtrip():
    """Tiny-LM int8-slab DecodeServer round trip: at one slab byte
    budget the int8 server admits 2x the bf16 slot count and completes
    every sequence."""
    import tempfile

    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.models import transformer as _T
    from paddle_tpu.serving.decode import (
        DecodeConfig, DecodePredictor, DecodeServer, kv_slab_slots,
        save_decode_model)

    cfg = DecodeConfig(vocab_size=64, n_layer=2, n_head=2, d_model=32,
                       d_inner=64, max_len=64)
    seq = 32
    scope = fluid.Scope()
    mdir = os.path.join(tempfile.mkdtemp(prefix="bench_quant_kv_"), "m")
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                tokens = layers.data(name="tokens", shape=[2, 16],
                                     dtype="int64",
                                     append_batch_size=False)
                lengths = layers.data(name="lengths", shape=[2],
                                      dtype="int32",
                                      append_batch_size=False)
                _T.transformer_lm_prefill(
                    tokens, lengths, cfg.vocab_size, n_layer=cfg.n_layer,
                    n_head=cfg.n_head, d_model=cfg.d_model,
                    d_inner=cfg.d_inner, max_len=cfg.max_len)
        exe.run(startup)
        save_decode_model(mdir, cfg, exe, scope=scope)
    # a budget sized to 4 int8 slots -> 2 bf16 slots
    budget = 4 * 2 * cfg.n_layer * seq * (cfg.n_head * cfg.d_head + 4)
    slots_i8 = kv_slab_slots(budget, cfg, seq, "int8")
    slots_bf = kv_slab_slots(budget, cfg, seq, "bfloat16")
    pred = DecodePredictor(mdir, aot_cache=False)
    srv = DecodeServer(pred, slots=slots_i8, max_seq=seq,
                       max_new_tokens=4, strategy="greedy",
                       prewarm=False, kv_dtype="int8")
    srv.start()
    prompts = [np.arange(1, 4 + i) % 60 + 1 for i in range(slots_i8)]
    futs = [srv.submit((p,)) for p in prompts]
    outs = [f.result(timeout=240)[0] for f in futs]
    srv.stop()
    return {
        "slots_int8": slots_i8, "slots_bf16": slots_bf,
        "sequences_served": len(outs),
        "all_completed": all(len(o) == 4 for o in outs),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--configs", default="mlp,deepfm")
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--batches", type=int, default=16)
    ap.add_argument("--batch-rows", type=int, default=256)
    ap.add_argument("--calib-batches", type=int, default=4)
    ap.add_argument("--decode-roundtrip", action="store_true",
                    help="run the int8-slab DecodeServer round trip "
                         "inside the quant_slab line")
    args = ap.parse_args(argv)

    lines = []
    for config in [c for c in args.configs.split(",") if c]:
        line = bench_config(config, args.rounds, args.batches,
                            args.batch_rows, args.calib_batches)
        lines.append(line)
        print(json.dumps(line), flush=True)
    slab = bench_slab(args.decode_roundtrip)
    print(json.dumps(slab), flush=True)

    summary = {
        "bench": "quant_summary", "schema": SCHEMA,
        "configs": [ln["config"] for ln in lines],
        "min_speedup": min(ln["rows_per_s_speedup"] for ln in lines),
        "max_speedup": max(ln["rows_per_s_speedup"] for ln in lines),
        "max_parity_abs_diff": max(ln["parity_max_abs_diff"]
                                   for ln in lines),
        "all_parity_ok": all(ln["parity_ok"] for ln in lines),
        "capacity_ratio_vs_bf16": slab["capacity_ratio_vs_bf16"],
    }
    print(json.dumps(summary), flush=True)
    return 0 if summary["all_parity_ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
