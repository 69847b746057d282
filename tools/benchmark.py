"""Unified model-zoo benchmark CLI (reference:
benchmark/fluid/fluid_benchmark.py — one harness running any model with
--model/--batch_size/--iterations/--device).

Usage:
    python tools/benchmark.py --model resnet50 --batch-size 64 --iters 10
    JAX_PLATFORMS=cpu python tools/benchmark.py --model mnist --cpu

Prints one JSON line per run: {model, batch, examples_per_sec, step_ms,
loss}. For the headline LM/ResNet numbers with MFU accounting use
bench.py; this harness is for breadth across the zoo.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _imagenet_feed(r, b, size=224, classes=1000, img="data"):
    return {img: r.randn(b, 3, size, size).astype(np.float32),
            "label": r.randint(0, classes, (b, 1)).astype(np.int64)}


# model -> (build(batch) -> (avg_cost, feeds), make_feed(rng, batch))
def _registry():
    from paddle_tpu import models

    return {
        "mnist": (
            lambda b: models.mnist.get_model()[0],
            lambda r, b: {"pixel": r.randn(b, 1, 28, 28).astype(np.float32),
                          "label": r.randint(0, 10, (b, 1)).astype(np.int64)}),
        "resnet50": (
            lambda b: models.resnet.get_model(dataset="imagenet",
                                              depth=50)[0],
            _imagenet_feed),
        "vgg16": (
            lambda b: models.vgg.get_model()[0],
            _imagenet_feed),
        "mobilenet": (
            lambda b: models.mobilenet.get_model()[0],
            lambda r, b: _imagenet_feed(r, b, img="image")),
        "se_resnext": (
            lambda b: models.se_resnext.get_model(batch_size=b)[0],
            _imagenet_feed),
        "stacked_lstm": (
            lambda b: models.stacked_lstm.get_model(dict_dim=10000,
                                                    seq_len=80)[0],
            lambda r, b: {
                "words": r.randint(0, 10000, (b, 80)).astype(np.int64),
                "lengths": r.randint(8, 81, b).astype(np.int32),
                "label": r.randint(0, 2, (b, 1)).astype(np.int64)}),
        "transformer_lm": (
            lambda b: _lm(b),
            lambda r, b: {
                "ids": r.randint(0, 8192, (b, 256)).astype(np.int64),
                "labels": r.randint(0, 8192, (b, 256)).astype(np.int64)}),
        "seq2seq": (
            lambda b: models.seq2seq.get_model(dict_size=8000)[0],
            lambda r, b: {
                "src_word_id": r.randint(2, 8000, (b, 16)).astype(np.int64),
                "src_len": np.full(b, 16, np.int32),
                "target_language_word": r.randint(2, 8000, (b, 16)).astype(np.int64),
                "trg_len": np.full(b, 16, np.int32),
                "target_language_next_word": r.randint(2, 8000, (b, 16)).astype(np.int64)}),
        "deepfm": (
            lambda b: models.deepfm.get_model()[0],
            lambda r, b: {
                "feat_ids": r.randint(0, 1000, (b, 10)).astype(np.int64),
                "dense": r.randn(b, 13).astype(np.float32),
                "label": r.randint(0, 2, (b, 1)).astype(np.int64)}),
    }


def _lm(b):
    from paddle_tpu import layers, models

    ids = layers.data(name="ids", shape=[b, 256], dtype="int64",
                      append_batch_size=False)
    lbl = layers.data(name="labels", shape=[b, 256], dtype="int64",
                      append_batch_size=False)
    loss, _ = models.transformer.transformer_lm(
        ids, lbl, vocab_size=8192, n_layer=4, n_head=8, d_model=256,
        d_inner=1024, max_len=256)
    return loss


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", required=True)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--amp", action="store_true")
    ap.add_argument("--loop", action="store_true",
                    help="time a device-side run_loop window (one "
                         "dispatch/fetch total) instead of per-step runs")
    args = ap.parse_args()

    if args.cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")

    import paddle_tpu as fluid
    from paddle_tpu import optimizer

    registry = _registry()
    if args.model not in registry:
        raise SystemExit("unknown model %r; choose from %s"
                         % (args.model, ", ".join(sorted(registry))))
    build, make_feed = registry[args.model]

    prog, startup = fluid.Program(), fluid.Program()
    prog.random_seed = startup.random_seed = 1
    with fluid.program_guard(prog, startup):
        with fluid.unique_name.guard():
            avg_cost = build(args.batch_size)
            optimizer.Adam(learning_rate=1e-4).minimize(avg_cost)
        if args.amp:
            prog.enable_mixed_precision()

    exe = fluid.Executor(fluid.CPUPlace() if args.cpu else fluid.TPUPlace())
    scope = fluid.Scope()
    r = np.random.RandomState(0)
    feed = make_feed(r, args.batch_size)
    with fluid.scope_guard(scope):
        exe.run(startup)
        if args.loop:
            # device-side window: one dispatch + one fetch per call (the
            # numpy return is the sync), robust to host latency
            exe.run_loop(prog, feed=feed, fetch_list=[avg_cost],
                         steps=max(1, args.warmup))
            t0 = time.perf_counter()
            out = exe.run_loop(prog, feed=feed, fetch_list=[avg_cost],
                               steps=args.iters)
            dt = (time.perf_counter() - t0) / args.iters
        else:
            exe.run(prog, feed=feed, fetch_list=[])
            # always warm the [avg_cost] fetch variant too (it is its own
            # compile-cache entry) so --warmup 0 cannot push a compile
            # into the timed window
            for _ in range(max(1, args.warmup)):
                exe.run(prog, feed=feed, fetch_list=[avg_cost])
            t0 = time.perf_counter()
            for _ in range(args.iters - 1):
                exe.run(prog, feed=feed, fetch_list=[])
            out = exe.run(prog, feed=feed, fetch_list=[avg_cost])
            dt = (time.perf_counter() - t0) / args.iters

    print(json.dumps({
        "model": args.model,
        "batch": args.batch_size,
        "examples_per_sec": round(args.batch_size / dt, 2),
        "step_ms": round(dt * 1e3, 2),
        "loss": float(np.asarray(out[0]).reshape(-1)[0]),
    }))


if __name__ == "__main__":
    main()
