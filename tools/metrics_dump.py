"""Observability smoke: tiny CPU train loop -> Prometheus + JSON dump.

Runs a few Executor.run steps and one run_loop window on the CPU backend,
a Predictor round-trip when --predict is given (or by default), then
prints the paddle_tpu.observability registry twice: the Prometheus text
exposition (what a scrape of PredictorServer's /metrics returns) and the
JSON snapshot including the step timeline. tests/test_metrics_dump.py
runs this in tier-1, so an exposition-format regression fails CI before
it reaches a real scrape job.

``--merge a.json b.json ...`` instead aggregates several previously
captured JSON dumps (a worker's ``/metrics.json``, or this tool's own
``--json`` output) into ONE snapshot via
``observability.export.merge_json_snapshots``: series with identical
label sets sum (counters/gauges/histogram buckets; summaries merge
min/max), distinct label sets stay distinct — so fleet workers exporting
with a ``replica`` label (PADDLE_TPU_REPLICA / ``--replica``) merge
collision-free. No jax import, no train loop.

Usage:
    JAX_PLATFORMS=cpu python tools/metrics_dump.py [--steps 4] [--json]
    python tools/metrics_dump.py --merge w0.json w1.json > fleet.json
"""
from __future__ import annotations

import argparse
import os
import sys

# CPU by default: this is a format smoke, not a perf measurement, and it
# must run in CI / on laptops with no accelerator attached
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def tiny_train_loop(steps: int):
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu import layers, optimizer

    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope), fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = layers.data(name="x", shape=[8])
            y = layers.data(name="y", shape=[1])
            h = layers.fc(x, 16, act="relu")
            pred = layers.fc(h, 1)
            loss = layers.mean(layers.square(pred - y))
            optimizer.SGD(learning_rate=0.01).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        rs = np.random.RandomState(0)
        xs = rs.rand(4, 8).astype(np.float32)
        ys = rs.rand(4, 1).astype(np.float32)
        for _ in range(steps):
            exe.run(main, feed={"x": xs, "y": ys}, fetch_list=[loss])
        # one device-side while-loop window so the loop-kind series and
        # the window-length histogram have samples too
        exe.run_loop(main, feed={"x": xs, "y": ys}, fetch_list=[loss],
                     steps=2)


def predict_roundtrip(tmpdir: str):
    """Predictor round trip PLUS the quant tier's calibrate ->
    quantized-export -> parity flow, so the
    ``paddle_tpu_quant_{calib_batches,quantized_ops,parity_max_abs_diff}``
    series ship samples through the same pinned exposition."""
    import os

    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.inference import Predictor
    from paddle_tpu.quant import calibrate, parity_report

    raw_dir = os.path.join(tmpdir, "raw")
    quant_dir = os.path.join(tmpdir, "quant")
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope), fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = layers.data(name="x", shape=[8])
            out = layers.fc(x, 3, act="softmax")
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feeds = [{"x": np.random.RandomState(i).rand(2, 8)
                  .astype(np.float32)} for i in range(2)]
        table = calibrate(main, scope, ["x"], feeds, max_batches=2)
        fluid.io.save_inference_model(raw_dir, ["x"], [out], exe,
                                      main_program=main, scope=scope)
        fluid.io.save_inference_model(quant_dir, ["x"], [out], exe,
                                      main_program=main, scope=scope,
                                      quantize=table)
    p = Predictor(raw_dir, aot_cache=False)
    p.run({"x": np.ones((2, 8), np.float32)})
    q = Predictor(quant_dir, aot_cache=False)
    parity_report(p, q, feeds, logits_tol=0.1)


def decode_round(tmpdir: str):
    """Exercise the PR-14 decode levers so their series ship through
    the pinned exposition: a REAL micro speculative generate (draft +
    verify executables over a 2-layer toy LM) ticks
    ``paddle_tpu_decode_spec_{proposed,accepted}_total``, and a real
    PrefixStore miss -> insert -> hit round ticks
    ``paddle_tpu_decode_prefix_{queries,hits}_total`` and the
    ``..._prefix_bytes`` gauge."""
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.models import transformer as T
    from paddle_tpu.serving.decode import (DecodeConfig, DecodePredictor,
                                           save_decode_model)
    from paddle_tpu.serving.prefix import PrefixStore

    model_dir = os.path.join(tmpdir, "decode")
    V, L = 13, 1  # minimal: 3 tiny compiles (prefill, draft, verify)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    scope = fluid.Scope()
    with fluid.scope_guard(scope), fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            ids = layers.data(name="ids", shape=[2, 8], dtype="int64",
                              append_batch_size=False)
            lbl = layers.data(name="lbl", shape=[2, 8], dtype="int64",
                              append_batch_size=False)
            T.transformer_lm(ids, lbl, V, n_layer=L, n_head=1, d_model=8,
                             d_inner=16, dropout_rate=0.0, max_len=32,
                             fused_head=False)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        save_decode_model(model_dir, DecodeConfig(
            vocab_size=V, n_layer=L, n_head=1, d_model=8, d_inner=16,
            max_len=32), exe, scope=scope)
    pred = DecodePredictor(model_dir, aot_cache=False, draft_n_layer=1)
    pred.generate([np.array([3, 1, 4], np.int64)], max_new_tokens=3,
                  speculative=True, spec_k=1)

    store = PrefixStore(max_bytes=1 << 20)
    prompt = np.arange(1, 9, dtype=np.int64)
    store.lookup(prompt)  # miss
    store.insert(prompt, [np.zeros((8, 1, 8), np.float32)
                          for _ in range(2 * L)],
                 np.zeros((V,), np.float32))
    store.lookup(prompt)  # full hit


def stream_round(tmpdir: str):
    """Exercise the ISSUE-15 online-learning hardening so its series
    ship through the pinned exposition: a real ``StreamingTrainer``
    step skips ONE NaN-poisoned batch through the in-graph sentinel
    (``paddle_tpu_train_skipped_batches_total{reason="nonfinite"}``,
    quarantine included), and a tolerant recordio read skips ONE
    corrupt chunk (``reason="corrupt_chunk"``)."""
    import numpy as np

    from paddle_tpu import layers, optimizer
    from paddle_tpu.training import StreamingTrainer

    def train_func():
        x = layers.data(name="x", shape=[4])
        y = layers.data(name="y", shape=[1])
        pred = layers.fc(x, 1)
        loss = layers.mean(layers.square(pred - y))
        return [loss, pred]

    st = StreamingTrainer(train_func,
                          lambda: optimizer.SGD(learning_rate=0.01))
    good = {"x": np.ones((2, 4), np.float32),
            "y": np.ones((2, 1), np.float32)}
    bad = {"x": np.full((2, 4), np.nan, np.float32),
           "y": np.ones((2, 1), np.float32)}
    st.run(lambda: iter([good, bad, good]), restart_source=False,
           quarantine_dir=os.path.join(tmpdir, "quarantine"))

    # corrupt-chunk skip through the tolerant recordio reader
    from paddle_tpu.runtime.recordio import (RecordIOReader,
                                             RecordIOWriter)

    path = os.path.join(tmpdir, "stream.rio")
    with RecordIOWriter(path, compressor=0, max_chunk_records=1) as w:
        for i in range(3):
            w.write(b"rec%d" % i)
    blob = bytearray(open(path, "rb").read())
    blob[len(blob) // 2] ^= 0xFF  # flip one payload byte mid-file
    open(path, "wb").write(bytes(blob))
    list(RecordIOReader(path, tolerant=True))


def swap_round():
    """One REJECTED hot swap through the real controller admission
    path (a nonexistent export dir fails validation before any worker
    spawns — same no-process trick as shed_round), so
    ``paddle_tpu_swap_total{result="rollback"}`` and the
    ``paddle_tpu_swap_ms`` histogram ride the pinned exposition. Plus
    one wedge sweep over a fabricated stuck replica handle — the REAL
    ``Router._wedge_sweep`` code, no processes — for
    ``paddle_tpu_fleet_wedged_total``."""
    import numpy as np

    from paddle_tpu.inference import _encode_sample
    from paddle_tpu.serving import Router, SwapController, SwapError

    router = Router("/nonexistent-model-dir", replicas=1,
                    wedge_timeout_s=0.01)
    try:
        SwapController(router).swap("/nonexistent-new-version")
    except SwapError:
        pass

    import time as _time

    from paddle_tpu.serving.router import _Worker

    w = _Worker(0, "replica-wedged")
    w.state = "ready"
    req = router._parse_request(
        _encode_sample(7, (np.zeros(2, np.float32),)))
    w.outstanding[7] = (req, None, _time.perf_counter() - 10.0)
    w.last_progress = _time.monotonic() - 10.0
    router._workers.append(w)
    assert router._wedge_sweep() == ["replica-wedged"]


def shed_round():
    """One load-shed through the REAL admission path (Router.submit with
    an already-expired deadline needs no worker processes), so the
    ``paddle_tpu_fleet_shed_total{class=...}`` exposition line ships
    through the same pinned format — a rename or label change fails
    tier-1 before it breaks a fleet dashboard."""
    import numpy as np

    from paddle_tpu.serving import RejectedError, Router

    router = Router("/nonexistent-model-dir", replicas=1)
    try:
        router.submit((np.zeros(2, np.float32),), slo="interactive",
                      deadline_ms=0).result(timeout=1)
    except RejectedError:
        pass


def trace_round():
    """One fully-sampled request through the REAL client edge + shed
    path (the shed_round no-process trick), so the ISSUE-16 tracing
    exposition ships through the same pinned format: exactly one
    ``paddle_tpu_trace_spans_total`` tick each for phase="client.submit"
    and phase="router.shed", and exactly one
    ``paddle_tpu_request_phase_ms`` sample in phase="queue" (a shed
    request's whole life). Submitted under class "batch" so the
    shed_round's pinned ``{class="interactive"} 1`` line stays exact.
    Sampling is forced to 1.0 for this round only — every other round
    runs untraced, as a default-config process would."""
    import numpy as np

    from paddle_tpu.observability import tracing
    from paddle_tpu.serving import RejectedError, Router

    tracing.set_sample_rate(1.0)
    try:
        router = Router("/nonexistent-model-dir", replicas=1)
        fut = router.submit((np.zeros(2, np.float32),), slo="batch",
                            deadline_ms=5000)
        # drive the dispatch-side parse + shed by hand: no worker
        # processes, same real code paths the fleet runs
        msgs = router._chan.recv_batch(1, 1.0)
        req = router._parse_request(msgs[0])
        assert req.trace_id is not None, "sampled request lost its id"
        router._shed(req, "expired")
        try:
            fut.result(timeout=1)
        except RejectedError:
            pass
    finally:
        tracing.set_sample_rate(0.0)


def merge_dumps(paths):
    """Load each JSON dump and print the aggregated snapshot. Stays off
    the jax import path ENTIRELY: merging is pure dict arithmetic
    (export.merge_json_snapshots) and the observability subtree is
    jax-free, so the parent package's heavy __init__ is stubbed out —
    a scrape sidecar pays ~ms, not a framework import."""
    import json
    import types

    if "paddle_tpu" not in sys.modules:
        # import ONLY paddle_tpu.observability: a bare namespace module
        # with the right __path__ stands in for the parent package so
        # its jax-importing __init__ never runs
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        stub = types.ModuleType("paddle_tpu")
        stub.__path__ = [os.path.join(root, "paddle_tpu")]
        sys.modules["paddle_tpu"] = stub
    from paddle_tpu.observability.export import merge_json_snapshots

    snaps = []
    for p in paths:
        with open(p) as f:
            snap = json.load(f)
        if "metrics" not in snap:
            raise SystemExit(
                "%s is not a metrics snapshot (expected a top-level "
                "'metrics' key, i.e. /metrics.json or --json output)" % p)
        snaps.append(snap)
    merged = merge_json_snapshots(snaps)
    sys.stdout.write(json.dumps(merged, indent=2, sort_keys=True))
    sys.stdout.write("\n")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", type=int, default=4,
                    help="Executor.run steps in the tiny loop")
    ap.add_argument("--no-predict", action="store_true",
                    help="skip the Predictor round-trip")
    ap.add_argument("--json", action="store_true",
                    help="print ONLY the JSON snapshot (no Prometheus text)")
    ap.add_argument("--merge", nargs="+", metavar="DUMP.json",
                    help="aggregate previously captured JSON dumps "
                         "(fleet workers) instead of running the smoke")
    ap.add_argument("--replica", default=None,
                    help="label this process's exports replica=<value> "
                         "(same effect as PADDLE_TPU_REPLICA)")
    args = ap.parse_args()

    if args.merge:
        merge_dumps(args.merge)
        return
    if args.replica:
        from paddle_tpu import observability as obs

        obs.set_replica(args.replica)
    tiny_train_loop(args.steps)
    shed_round()
    swap_round()
    trace_round()
    if not args.no_predict:
        import tempfile

        with tempfile.TemporaryDirectory() as td:
            predict_roundtrip(td)
        with tempfile.TemporaryDirectory() as td:
            decode_round(td)
        with tempfile.TemporaryDirectory() as td:
            stream_round(td)

    from paddle_tpu.observability import export

    if not args.json:
        sys.stdout.write(export.to_prometheus())
        sys.stdout.write("\n")
    sys.stdout.write(export.dumps_json(indent=2))
    sys.stdout.write("\n")


if __name__ == "__main__":
    main()
