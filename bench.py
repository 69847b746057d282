"""Headline benchmarks: transformer LM + ResNet-50 training throughput.

Prints ONE JSON line. Primary metric: transformer LM tokens/sec/chip with
"vs_baseline" = achieved_MFU / 0.50 (the north-star 50% MFU target from
BASELINE.json; the reference publishes no numbers). The same line carries
secondary phase objects covering the rest of BASELINE.json's configs:
- "resnet50": images/sec/chip + conv MFU (BASELINE.json configs[1],
  reference benchmark/fluid/models/resnet.py:1); BENCH_RESNET=0 skips.
- "stacked_lstm": words/sec/chip for the scan-heavy RNN workload
  (reference benchmark/fluid/models/stacked_dynamic_lstm.py:1);
  BENCH_LSTM=0 skips.
- "deepfm": rows/sec/chip for the embedding-bound CTR workload
  (reference paddle/fluid/operators/lookup_table_op.cc:1);
  BENCH_DEEPFM=0 skips.
BENCH_LM=0 skips the LM phase itself (sweep rows that only need a
secondary phase; the headline value is then null by design).

The whole training step (fwd + bwd + optimizer) is one donated jax.jit
XLA computation produced by tracing the Program — see executor.py.
"""
from __future__ import annotations

import json
import os as _os
import sys as _sys
import time

import numpy as np

def _apply_platform():
    """BENCH_PLATFORM=cpu runs the bench on the host CPU on purpose (the
    scaffold smoke tests). Without it the bench needs a TPU and refuses
    to run on anything else."""
    plat = _os.environ.get("BENCH_PLATFORM")
    if plat:
        import jax

        jax.config.update("jax_platforms", plat)


def _place():
    """The place every phase's Executor is built on: the TPU, unless
    BENCH_PLATFORM=cpu asked for the host."""
    import paddle_tpu as fluid

    return (fluid.CPUPlace() if _os.environ.get("BENCH_PLATFORM") == "cpu"
            else fluid.TPUPlace())


# Sweep winners as DEFAULTS, applied inside main() (a mere `import bench`
# must not mutate the process env — pytest imports this module):
# - BENCH_AMP_LEVEL=O2 scopes to the LM phase ONLY (O2 made ResNet 35%
#   slower — secondary phases take BENCH_RN/LSTM/DFM_AMP_LEVEL, default
#   O1).
# - PADDLE_TPU_FLASH_FUSED_BWD=1: the fused flash backward. Its numerics
#   are asserted by chip_smoke.py and tests, not gated here.

# LM config. Default batch 16: flash attention + the fused LM head freed
# the HBM the (T, T) scores and (N, V) logits used to occupy, and MFU at
# the measured batch-8 steady state (~0.42) was still injection-limited —
# bench_lm falls back down the ladder on RESOURCE_EXHAUSTED, so a chip
# where 16 does not fit still reports the batch-8 number instead of dying.
BATCH = int(_os.environ.get("BENCH_BATCH", 16))
SEQ = int(_os.environ.get("BENCH_SEQ", 1024))
VOCAB = int(_os.environ.get("BENCH_VOCAB", 32768))
N_LAYER = int(_os.environ.get("BENCH_LAYERS", 12))
# n_head 16 -> d_head 64; BENCH_HEADS=8 gives d_head 128 = the MXU's full
# 128-lane contraction depth on the attention score/context matmuls
N_HEAD = int(_os.environ.get("BENCH_HEADS", 16))
D_MODEL, D_INNER = 1024, 4096
WARMUP, STEPS = int(_os.environ.get("BENCH_WARMUP", 3)), int(_os.environ.get("BENCH_STEPS", 12))
AMP = _os.environ.get("BENCH_AMP", "1") == "1"

# ResNet-50 config
RN_BATCH = int(_os.environ.get("BENCH_RN_BATCH", 128))
RN_STEPS = int(_os.environ.get("BENCH_RN_STEPS", 10))
RN_WARMUP = int(_os.environ.get("BENCH_RN_WARMUP", 2))
# fwd matmul+conv FLOPs for ResNet-50 @224 (4.09 GMACs, fvcore-style count)
RN_FWD_FLOPS_PER_IMG = 2 * 4.089e9

# Stacked dynamic LSTM config
# batch 64 measured +15% words/s over 32 on-chip (r5 third session:
# 360,417 vs 312,896 at seq 512) — the scan step is small-matmul bound,
# so doubling rows per step is nearly free until HBM fills
LSTM_BATCH = int(_os.environ.get("BENCH_LSTM_BATCH", 64))
LSTM_SEQ = int(_os.environ.get("BENCH_LSTM_SEQ", 512))
LSTM_DICT = int(_os.environ.get("BENCH_LSTM_DICT", 30000))
LSTM_EMB = 512
LSTM_HID = int(_os.environ.get("BENCH_LSTM_HID", 512))
LSTM_STACK = int(_os.environ.get("BENCH_LSTM_STACK", 3))
LSTM_STEPS = int(_os.environ.get("BENCH_LSTM_STEPS", 10))
LSTM_WARMUP = int(_os.environ.get("BENCH_LSTM_WARMUP", 2))

# DeepFM CTR config.
# Batch 16384 won the on-chip ladder (r5 s4, same-session controls:
# 338.6k @ 4096, 336.3k @ 8192, 382.1k @ 16384, 347.1k @ 32768 rows/s).
DFM_BATCH = int(_os.environ.get("BENCH_DFM_BATCH", 16384))
DFM_FEATURES = int(_os.environ.get("BENCH_DFM_FEATURES", 1000000))
DFM_FIELDS = int(_os.environ.get("BENCH_DFM_FIELDS", 26))
DFM_DENSE = int(_os.environ.get("BENCH_DFM_DENSE", 13))
DFM_STEPS = int(_os.environ.get("BENCH_DFM_STEPS", 10))
DFM_WARMUP = int(_os.environ.get("BENCH_DFM_WARMUP", 2))

_PEAK_FLOPS = {
    # bf16 peak matmul FLOP/s per chip
    "TPU v5 lite": 197e12,   # v5e
    "TPU v4": 275e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,   # v6e / Trillium
}


def _peak_flops(device) -> float:
    kind = getattr(device, "device_kind", "")
    for k, v in _PEAK_FLOPS.items():
        if kind.startswith(k):
            return v
    raise ValueError(
        "no peak FLOP/s on record for device kind %r; add it to "
        "_PEAK_FLOPS with its source" % kind)


def _stage_feed(feed, dev):
    import jax

    return {k: jax.device_put(v, dev) for k, v in feed.items()}


def _train_flops_per_step(batch) -> float:
    """Analytic matmul FLOPs for fwd+bwd (bwd = 2x fwd)."""
    tokens = batch * SEQ
    # per-layer matmul params: qkv+out (4 d^2) + mlp (2 d d_inner)
    p_layer = 4 * D_MODEL * D_MODEL + 2 * D_MODEL * D_INNER
    p_mm = N_LAYER * p_layer + VOCAB * D_MODEL  # + lm head
    fwd = 2.0 * tokens * p_mm
    # attention scores + context: 2 * (2 B H T^2 Dh) per layer
    fwd += N_LAYER * 4.0 * batch * SEQ * SEQ * D_MODEL
    return 3.0 * fwd


def _looks_oom(exc) -> bool:
    text = repr(exc)
    return ("RESOURCE_EXHAUSTED" in text or "Out of memory" in text
            or "out of memory" in text or "OOM" in text)


def _timed_loop(run_loop, warmup, steps):
    """Device-loop timing scaffold (default, BENCH_LOOP=1): `run_loop(k)`
    executes k training steps inside ONE XLA while-loop via
    Executor.run_loop and returns the last fetch list; reading it to the
    host is the fence. Per-step time is the SLOPE between a k-step and a
    2k-step call: fixed per-call costs (feed upload, dispatch) cancel,
    leaving the marginal device step time.
    BENCH_PROFILE=1 captures a k-step jax.profiler trace on a separate,
    UNtimed call so trace overhead cannot skew the slope.
    Returns (dt_per_step, last_loss)."""
    out = run_loop(max(1, warmup))  # trace + compile + warm (n is traced:
    _ = float(np.asarray(out[0]).reshape(-1)[0])  # same executable for any k)
    if _os.environ.get("BENCH_PROFILE", "0") == "1":
        import jax
        jax.profiler.start_trace(
            _os.environ.get("BENCH_PROFILE_DIR", "/tmp/jaxprof"))
        try:
            out = run_loop(steps)
            _ = float(np.asarray(out[0]).reshape(-1)[0])
        finally:
            jax.profiler.stop_trace()
    t0 = time.perf_counter()
    out = run_loop(steps)
    _ = float(np.asarray(out[0]).reshape(-1)[0])
    t1 = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = run_loop(2 * steps)
    loss_val = float(np.asarray(out[0]).reshape(-1)[0])
    t2 = time.perf_counter() - t0
    dt = (t2 - t1) / steps
    if dt <= 0:
        # timing noise ate the slope (can only happen when per-call fixed
        # cost dwarfs step time); fall back to the conservative average
        dt = t2 / (2 * steps)
    return dt, loss_val


def _timed_steps(step, warmup, steps):
    """Per-dispatch timing scaffold (fallback, BENCH_LOOP=0): `step()`
    dispatches ONE async training step (return_numpy=False — fetches stay
    device futures so steps chain on-device) and returns the fetch list.
    First call traces + compiles the single variant; warmup drains; the
    timed loop syncs only at the end of the chain. BENCH_PROFILE=1 wraps
    the timed steps in a jax.profiler trace. Returns
    (dt_per_step, last_loss)."""
    import jax

    out = step()  # trace + compile
    for _ in range(warmup):
        out = step()
    jax.block_until_ready(out)  # drain warmup before timing starts
    profiling = _os.environ.get("BENCH_PROFILE", "0") == "1"
    if profiling:
        jax.profiler.start_trace(
            _os.environ.get("BENCH_PROFILE_DIR", "/tmp/jaxprof"))
    try:
        t0 = time.perf_counter()
        for _ in range(steps):
            out = step()
        loss_val = float(np.asarray(out[0]).reshape(-1)[0])  # end-of-chain sync
        dt = (time.perf_counter() - t0) / steps
    finally:
        # an exception mid-trace (e.g. OOM at the sync) must still stop the
        # trace, or the ladder's retry at a smaller batch would hit
        # "trace already started" and lose the OOM-fallback contract
        if profiling:
            jax.profiler.stop_trace()
    return dt, loss_val


def _timed_exec(exe, program, feed, fetch, warmup, steps):
    """Dispatch to the device-loop scaffold (default) or the per-step
    scaffold (BENCH_LOOP=0)."""
    if _os.environ.get("BENCH_LOOP", "1") == "1":
        return _timed_loop(
            lambda k: exe.run_loop(program, feed=feed, fetch_list=[fetch],
                                   steps=k, return_numpy=False),
            warmup, steps)
    return _timed_steps(
        lambda: exe.run(program, feed=feed, fetch_list=[fetch],
                        return_numpy=False),
        warmup, steps)


def bench_lm_ladder(dev):
    """Default run: 8 heads (d_head 128 fills the MXU's 128-lane
    contraction and takes the transpose-free BTHD pallas layout) at the
    largest per-chip batch that fits — OOM retries down the batch
    ladder. EXPLICIT BENCH_BATCH / BENCH_HEADS run exactly that config.
    Anything but an OOM propagates: a kernel that fails is a failed run,
    never a quiet measurement of another config."""
    heads = N_HEAD if _os.environ.get("BENCH_HEADS") is not None else 8
    if _os.environ.get("BENCH_BATCH") is not None:
        return bench_lm(dev, BATCH, heads)
    oom_err = None
    for b in dict.fromkeys([BATCH, 16, 8]):
        if b > BATCH:
            continue
        try:
            return bench_lm(dev, b, heads)
        except Exception as e:  # noqa: BLE001 — OOM shapes vary
            if not _looks_oom(e):
                raise
            oom_err = e
    raise oom_err


def _bench_phase(dev, build, feed, warmup, steps, stage=True,
                 amp_level=None):
    """Shared phase scaffold (every bench phase differs only in its model
    builder and feed): seeded Program/Scope, `build()` under the program
    guards returning the loss var (the builder also calls minimize), AMP
    + optional remat transpilation, startup init, optional device staging
    of the feed, slope timing. Returns (dt_per_step, last_loss).

    amp_level: the phase's AMP level; None reads BENCH_AMP_LEVEL (the LM
    knob). O2 is the measured LM winner but made ResNet 35% SLOWER
    (bf16 batchnorm stats lose the conv-epilogue fusions), so each
    secondary phase passes its own default instead of inheriting."""
    import paddle_tpu as fluid

    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = 1
    scope = fluid.Scope()
    with fluid.scope_guard(scope), fluid.program_guard(main_p, startup):
        with fluid.unique_name.guard():
            loss = build()
        if AMP:
            # bf16 matmuls, fp32 master weights; O2 keeps the
            # elementwise path (residual stream) in bf16 too
            main_p.enable_mixed_precision(
                level=amp_level if amp_level is not None
                else _os.environ.get("BENCH_AMP_LEVEL", "O1"))
        if _os.environ.get("BENCH_REMAT", "0") == "1":
            # rematerialize the backward: frees activation HBM so larger
            # per-chip batches fit (sweep lever for batch 24/32)
            fluid.memory_optimize(main_p)

        exe = fluid.Executor(_place())
        exe.run(startup)
        if stage:
            feed = _stage_feed(feed, dev)
        return _timed_exec(exe, main_p, feed, loss, warmup, steps)


def bench_lm(dev, batch, n_head=None):
    from paddle_tpu import layers, models, optimizer

    def build():
        ids = layers.data(name="ids", shape=[batch, SEQ], dtype="int64",
                          append_batch_size=False)
        labels = layers.data(name="labels", shape=[batch, SEQ],
                             dtype="int64", append_batch_size=False)
        loss, _ = models.transformer.transformer_lm(
            ids, labels, vocab_size=VOCAB, n_layer=N_LAYER,
            n_head=n_head if n_head is not None else N_HEAD,
            d_model=D_MODEL, d_inner=D_INNER, max_len=SEQ,
            fused_qkv=_os.environ.get("PADDLE_TPU_FUSED_QKV", "0") == "1",
            tie_embeddings=_os.environ.get("BENCH_TIE", "0") == "1")
        optimizer.Adam(learning_rate=1e-4).minimize(loss)
        return loss

    r = np.random.RandomState(0)
    feed = {
        "ids": r.randint(0, VOCAB, (batch, SEQ)).astype(np.int64),
        "labels": r.randint(0, VOCAB, (batch, SEQ)).astype(np.int64),
    }
    # the LM feed stays numpy (128 KB/step is cheap; one upload per
    # run_loop call in the default device-loop mode)
    dt, loss_val = _bench_phase(dev, build, feed, WARMUP, STEPS, stage=False)

    mfu = _train_flops_per_step(batch) / dt / _peak_flops(dev)
    return {
        "value": round(batch * SEQ / dt, 1),
        "mfu": round(mfu, 4),
        "step_ms": round(dt * 1e3, 2),
        "loss": loss_val,
        "batch": batch,
        "n_head": n_head if n_head is not None else N_HEAD,
    }


def bench_resnet(dev):
    from paddle_tpu import models, optimizer

    def build():
        avg_cost, acc, feeds = models.resnet.get_model(
            dataset="imagenet", depth=50,
            layout=_os.environ.get("BENCH_RN_LAYOUT", "NCHW"))
        optimizer.Momentum(learning_rate=0.1, momentum=0.9).minimize(
            avg_cost)
        return avg_cost

    r = np.random.RandomState(0)
    feed = {
        "data": r.randn(RN_BATCH, 3, 224, 224).astype(np.float32),
        "label": r.randint(0, 1000, (RN_BATCH, 1)).astype(np.int64),
    }
    # the image batch (~77 MB at batch 128) must live on device (staged):
    # re-uploading it every step would put the host link on the step's
    # critical path
    dt, loss_val = _bench_phase(
        dev, build, feed, RN_WARMUP, RN_STEPS,
        amp_level=_os.environ.get("BENCH_RN_AMP_LEVEL", "O1"))

    mfu = 3.0 * RN_FWD_FLOPS_PER_IMG * RN_BATCH / dt / _peak_flops(dev)
    res = {
        "images_per_sec": round(RN_BATCH / dt, 1),
        "mfu": round(mfu, 4),
        "step_ms": round(dt * 1e3, 2),
        "batch": RN_BATCH,
        "loss": loss_val,
    }
    if _os.environ.get("BENCH_RN_LAYOUT", "NCHW") != "NCHW":
        res["layout"] = _os.environ["BENCH_RN_LAYOUT"]
    if _os.environ.get("BENCH_RESNET_INPUT", "synthetic") == "reader":
        try:
            res["reader"] = _bench_resnet_reader(dev, res)
        except Exception as e:  # the comparison row must not cost the bench
            res["reader"] = {"error": repr(e)[:200]}
    return res


def _bench_resnet_reader(dev, synthetic):
    """the same ResNet step fed through the FULL input
    pipeline — recordio file -> C++ chunk reader/channel/arena ->
    batch/double_buffer reader ops -> run_loop windows (one stacked
    upload per window) — timed with the same slope method. If
    input_overhead_pct is small, input is overlapped/amortized, not
    serial (reference design:
    operators/reader/create_double_buffer_reader_op.cc:1)."""
    import tempfile

    import paddle_tpu as fluid
    from paddle_tpu import optimizer
    from paddle_tpu.models.resnet import resnet_imagenet

    steps = int(_os.environ.get("BENCH_RN_READER_STEPS", 4))
    timed_windows = int(_os.environ.get("BENCH_RN_READER_WINDOWS", 3))
    # wire dtype: uint8 by default — images travel host->device as raw
    # bytes (4x less traffic than f32) and are cast+normalized in-graph,
    # the layout a production image pipeline uses anyway. f32 via
    # BENCH_RN_READER_WIRE=float32 for the old apples-to-apples row.
    wire = _os.environ.get("BENCH_RN_READER_WIRE", "uint8")
    # UNIFORM windows (training-loop shape: Trainer's steps_per_loop is
    # fixed): 2 warmups (first compiles; second engages the executor's
    # stable-size window prefetch) + timed windows + one window the
    # prefetch holds staged at the end
    batches_needed = (2 + timed_windows + 2) * steps + 2
    n_samples = 2 * RN_BATCH  # 2 distinct batches on disk, replayed
    pass_num = batches_needed * RN_BATCH // n_samples + 2
    path = _os.path.join(tempfile.gettempdir(),
                         "ptpu_rn_%d_%s.recordio" % (RN_BATCH, wire))
    if not _os.path.exists(path):
        r = np.random.RandomState(0)

        def samples():
            for _ in range(n_samples):
                if wire == "uint8":
                    img = r.randint(0, 256, (3, 224, 224)).astype(np.uint8)
                else:
                    img = r.randn(3, 224, 224).astype(np.float32)
                yield (img, r.randint(0, 1000, (1,)).astype(np.int64))

        fluid.recordio_convert(samples, path)

    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = 1
    scope = fluid.Scope()
    with fluid.scope_guard(scope), fluid.program_guard(main_p, startup):
        with fluid.unique_name.guard():
            reader = fluid.layers.open_recordio_file(
                path, shapes=[(3, 224, 224), (1,)],
                dtypes=[wire, "int64"], pass_num=pass_num)
            reader = fluid.layers.batch(reader, batch_size=RN_BATCH)
            reader = fluid.layers.double_buffer(reader)
            data, label = fluid.layers.read_file(reader)
            if wire == "uint8":
                # cast + [0,255] -> [-1,1] normalize on DEVICE: the host
                # ships bytes, the chip does the float conversion
                data = fluid.layers.scale(fluid.layers.cast(data, "float32"),
                                          scale=1.0 / 127.5, bias=-1.0)
            predict = resnet_imagenet(
                data, 1000, depth=50,
                layout=_os.environ.get("BENCH_RN_LAYOUT", "NCHW"))
            avg_cost = fluid.layers.mean(
                fluid.layers.cross_entropy(input=predict, label=label))
            optimizer.Momentum(learning_rate=0.1, momentum=0.9).minimize(
                avg_cost)
        if AMP:
            main_p.enable_mixed_precision(
                level=_os.environ.get("BENCH_RN_AMP_LEVEL", "O1"))
        exe = fluid.Executor(_place())
        exe.run(startup)

        def window(k):
            out = exe.run_loop(main_p, fetch_list=[avg_cost], steps=k,
                               return_numpy=False)
            return float(np.asarray(out[0]).reshape(-1)[0])

        # uniform windows, mean-timed: the per-window fixed costs (pull,
        # stack, transfer, fence) are REAL training-loop costs here, so
        # no slope trick — warm twice (compile, then prefetch engages on
        # the stable size), then average the steady state
        window(steps)
        window(steps)
        t0 = time.perf_counter()
        for _ in range(timed_windows):
            loss_val = window(steps)
        dt = (time.perf_counter() - t0) / (timed_windows * steps)

        # drain the window the executor prefetched during the last timed
        # call: its async device_put is still riding the link, and the
        # upload control below must not time its own transfer queued
        # behind it
        slot = exe._reader_prefetch.get(main_p)
        for a in ((slot or {}).get("feeds") or {}).values():
            np.asarray(a[tuple(0 for _ in a.shape[:-1])][:1])

    # upload CONTROL: host->device transfer of the exact bytes/step the
    # reader window ships, with nothing else attached (PCIe on a TPU
    # host). pipeline_overhead_pct is the honest
    # reader cost: time beyond transfer + compute (≈0 when decode and
    # batching fully overlap the step; the transfer itself is environment
    # physics, not pipeline design).
    import jax

    import jax.numpy as jnp

    wire_np = np.uint8 if wire == "uint8" else np.float32
    shape = (RN_BATCH, 3, 224, 224)  # ONE batch = one step's wire bytes
    # pre-compile the fence slice for this exact shape with a
    # device-materialized array (zeros never cross the host link), so the
    # timed region below is pure host->device transfer — no XLA compile,
    # and O(1) in BENCH_RN_READER_STEPS
    np.asarray(jnp.zeros(shape, wire_np)[0, 0, 0, :1])
    r = np.random.RandomState(1)
    buf = (r.randint(0, 256, shape).astype(np.uint8) if wire == "uint8"
           else r.randn(*shape).astype(np.float32))
    t0 = time.perf_counter()
    x = jax.device_put(buf, dev)
    # fence = device->host read of ONE element (a full np.asarray would
    # re-ship the whole batch back to the host); the device-side
    # slice can't run until the put lands
    np.asarray(x[0, 0, 0, :1])
    up_dt = time.perf_counter() - t0
    # round-trip control: one dispatch + one 4-byte fetch — every window
    # pays ~2 of these (dispatch, loss fence) regardless of size.
    tiny = jax.device_put(np.zeros((1,), np.float32), dev)
    np.asarray(tiny * 1)  # warm the trivial executable
    t0 = time.perf_counter()
    np.asarray(tiny * 1)
    rtt = time.perf_counter() - t0
    # the double_buffer design OVERLAPS transfer with compute, so the
    # ideal reader step is max(transfer, compute) plus the per-window
    # round trips, not their sum — pipeline_overhead_pct is the cost
    # ABOVE that ideal (≈0 when the pipeline overlaps perfectly; the
    # transfer floor and RTTs are link physics: ~14 MB/s and ~1 s here,
    # GB/s PCIe and µs dispatches on a real host)
    ideal = (max(up_dt, synthetic["step_ms"] / 1e3)
             + 2.0 * rtt / max(1, steps))
    return {
        "step_ms": round(dt * 1e3, 2),
        "images_per_sec": round(RN_BATCH / dt, 1),
        "synthetic_step_ms": synthetic["step_ms"],
        "wire_dtype": wire,
        "upload_ms_per_step": round(up_dt * 1e3, 2),
        "rtt_ms": round(rtt * 1e3, 2),
        "input_overhead_pct": round(
            100.0 * (dt * 1e3 / synthetic["step_ms"] - 1.0), 1),
        "pipeline_overhead_pct": round(100.0 * (dt / ideal - 1.0), 1),
        "loss": loss_val,
        "window_steps": steps,
    }


def _lstm_train_flops_per_step() -> float:
    """Analytic matmul FLOPs for the stacked LSTM step (fwd gate/fc
    matmuls; bwd = 2x fwd). Embedding gathers and pools are not matmul
    FLOPs — the MFU here measures how well lax.scan keeps the MXU busy
    on the per-timestep (B, hid) x (hid, 4*hid) gate matmuls."""
    tokens = LSTM_BATCH * LSTM_SEQ
    g = 4 * LSTM_HID
    p = LSTM_EMB * g + LSTM_HID * g  # fc1 + lstm1 recurrent
    # stacked layers: fc over concat(fc_prev, lstm_prev) + recurrent
    p += (LSTM_STACK - 1) * ((g + LSTM_HID) * g + LSTM_HID * g)
    return 3.0 * 2.0 * tokens * p


def bench_stacked_lstm(dev):
    """Stacked dynamic LSTM training throughput (words/s/chip). The whole
    step is one jitted XLA computation whose RNN layers are lax.scan
    loops — exactly the path whose TPU cost a CUDA-per-op design never
    predicts."""
    from paddle_tpu import models, optimizer

    def build():
        avg_cost, acc, feeds = models.stacked_lstm.get_model(
            dict_dim=LSTM_DICT, seq_len=LSTM_SEQ, emb_dim=LSTM_EMB,
            hid_dim=LSTM_HID, stacked_num=LSTM_STACK)
        optimizer.Adam(learning_rate=1e-3).minimize(avg_cost)
        return avg_cost

    r = np.random.RandomState(0)
    feed = {
        "words": r.randint(0, LSTM_DICT,
                           (LSTM_BATCH, LSTM_SEQ)).astype(np.int64),
        # full lengths: every padded position is a real word, so
        # words/s counts the tokens actually computed
        "lengths": np.full((LSTM_BATCH,), LSTM_SEQ, np.int32),
        "label": r.randint(0, 2, (LSTM_BATCH, 1)).astype(np.int64),
    }
    dt, loss_val = _bench_phase(
        dev, build, feed, LSTM_WARMUP, LSTM_STEPS,
        amp_level=_os.environ.get("BENCH_LSTM_AMP_LEVEL", "O1"))

    mfu = _lstm_train_flops_per_step() / dt / _peak_flops(dev)
    return {
        "words_per_sec": round(LSTM_BATCH * LSTM_SEQ / dt, 1),
        "mfu": round(mfu, 4),
        "step_ms": round(dt * 1e3, 2),
        "loss": loss_val,
        "batch": LSTM_BATCH,
        "seq": LSTM_SEQ,
        "hid": LSTM_HID,
        "stacked": LSTM_STACK,
    }


def bench_deepfm(dev):
    """DeepFM CTR training throughput (rows/s/chip). Embedding-bound:
    the step gathers (B*F) rows of a 1M x K table forward and
    scatter-adds the same rows backward — the path where a TPU rebuild
    of a SelectedRows/pserver design can silently be 10x off
   ."""
    from paddle_tpu import models, optimizer

    def build():
        avg_cost, prob, feeds = models.deepfm.get_model(
            num_features=DFM_FEATURES, num_fields=DFM_FIELDS,
            dense_dim=DFM_DENSE)
        optimizer.Adam(learning_rate=1e-3).minimize(avg_cost)
        return avg_cost

    r = np.random.RandomState(0)
    feed = {
        "feat_ids": r.randint(0, DFM_FEATURES,
                              (DFM_BATCH, DFM_FIELDS)).astype(np.int64),
        "dense": r.rand(DFM_BATCH, DFM_DENSE).astype(np.float32),
        "label": r.randint(0, 2, (DFM_BATCH, 1)).astype(np.int64),
    }
    dt, loss_val = _bench_phase(
        dev, build, feed, DFM_WARMUP, DFM_STEPS,
        amp_level=_os.environ.get("BENCH_DFM_AMP_LEVEL", "O1"))

    return {
        "rows_per_sec": round(DFM_BATCH / dt, 1),
        "step_ms": round(dt * 1e3, 2),
        "loss": loss_val,
        "batch": DFM_BATCH,
        "features": DFM_FEATURES,
        "fields": DFM_FIELDS,
    }


def _input_pipeline_metric():
    """Host-side input-pipeline throughput (tools/bench_dataloader.py
    quick_metric): batches/s through the multiprocess shared-memory
    DataLoader on a decode-heavy synthetic workload, with the threaded
    xmap_readers rate as baseline. Pure host measurement — no device
    required. BENCH_INPUT_PIPELINE=0 skips."""
    import sys as _s

    tools_dir = _os.path.join(
        _os.path.dirname(_os.path.abspath(__file__)), "tools")
    if tools_dir not in _s.path:
        _s.path.insert(0, tools_dir)
    import bench_dataloader

    return bench_dataloader.quick_metric(
        workers=int(_os.environ.get("BENCH_IP_WORKERS", 0)) or None,
        sample_kb=int(_os.environ.get("BENCH_IP_SAMPLE_KB", 16)),
        batch=int(_os.environ.get("BENCH_IP_BATCH", 16)),
        n_batches=int(_os.environ.get("BENCH_IP_BATCHES", 48)))


def _emit_input_pipeline():
    """Measure + print the input-pipeline metric as its OWN JSON line
    (never the last line: the driver parses the final line as the device
    metric). Returns the phase dict to attach to the main result."""
    if _os.environ.get("BENCH_INPUT_PIPELINE", "1") != "1":
        return None
    ip = _input_pipeline_metric()
    line = {"metric": "input_pipeline_batches_per_sec",
            "value": ip.get("batches_per_sec"), "unit": "batches/s"}
    line.update({k: v for k, v in ip.items() if k != "batches_per_sec"})
    print(json.dumps(line), flush=True)
    return ip


def _effective_fused_bwd(n_head):
    """What the attention dispatch will ACTUALLY run for this config:
    env opt-in AND the kernel's VMEM-footprint gate (which silently
    falls back to the split backward at long sequence — the recorded
    config must not label split-kernel numbers as fused)."""
    if _os.environ.get("PADDLE_TPU_FLASH_FUSED_BWD", "0") != "1":
        return "0"
    try:
        from paddle_tpu.ops.attention import _fused_bwd_fits

        # attention inputs are bf16 under both AMP levels (fused_attention
        # is in the AMP bf16 op set), hence itemsize 2
        return "1" if _fused_bwd_fits(SEQ, D_MODEL // n_head, 2) else "0"
    except Exception:  # pragma: no cover — labeling must never kill a run
        return "1"


def main():
    # sweep-winner defaults (see the comment block above the LM config)
    _os.environ.setdefault("BENCH_AMP_LEVEL", "O2")
    _os.environ.setdefault("PADDLE_TPU_FLASH_FUSED_BWD", "1")
    _apply_platform()
    import jax

    dev = jax.devices()[0]
    want = _os.environ.get("BENCH_PLATFORM") or "tpu"
    if dev.platform != want:
        print("bench: JAX's default device is %r, not a %s device; "
              "refusing to run (BENCH_PLATFORM=cpu asks for the host on "
              "purpose)" % (dev, want), file=_sys.stderr)
        return 2
    if _os.environ.get("BENCH_NO_CACHE", "0") != "1":
        from paddle_tpu.runtime import aot_cache

        aot_cache.enable_compile_cache()
    device = {"platform": dev.platform,
              "kind": getattr(dev, "device_kind", dev.platform),
              "count": len(jax.devices())}
    if _os.environ.get("BENCH_LM", "1") == "1":
        obs_before = _obs_counters()
        lm = bench_lm_ladder(dev)
        result = {
            "metric": "transformer_lm_train_tokens_per_sec_per_chip",
            "value": lm["value"],
            "unit": "tokens/s",
            "vs_baseline": round(lm["mfu"] / 0.50, 4),
            "mfu": lm["mfu"],
            "step_ms": lm["step_ms"],
            "loss": lm["loss"],
            "device": device,
            "config": {"batch": lm["batch"], "seq": SEQ, "vocab": VOCAB,
                       "layers": N_LAYER, "d_model": D_MODEL,
                       "n_head": lm["n_head"],
                       "attn_bthd": _os.environ.get("PADDLE_TPU_ATTN_BTHD", "1"),
                       "fused_bwd": _effective_fused_bwd(lm["n_head"]),
                       "amp_level": _os.environ.get("BENCH_AMP_LEVEL", "O1"),
                       "tie_emb": _os.environ.get("BENCH_TIE", "0")},
        }
        delta = _obs_delta(obs_before)
        if delta:
            result["metrics"] = delta
    else:
        # sweep rows measuring only a secondary phase skip the LM compile;
        # the headline stays null so a driver parsing this line can't
        # mistake it for an LM number
        result = {
            "metric": "transformer_lm_train_tokens_per_sec_per_chip",
            "value": None, "unit": "tokens/s", "vs_baseline": None,
            "note": "BENCH_LM=0 (secondary-phase row)",
            "device": device,
        }
    ip = _emit_input_pipeline()
    if ip is not None:
        result["input_pipeline"] = ip
    for name, phase in _phase_list():
        # flush what we have before each phase: if it is killed at a time
        # limit, the flushed line is still the last complete JSON line.
        # A phase that RAISES ends the run non-zero.
        print(json.dumps(result), flush=True)
        obs_before = _obs_counters()
        result[name] = phase(dev)
        delta = _obs_delta(obs_before)
        if delta:
            result[name]["metrics"] = delta
    print(json.dumps(result))
    return 0


def _phase_list():
    """Secondary phases, cheapest compile first: stacked_lstm's 3-deep
    scan-of-scans backward is by far the longest compile, so it goes
    last and every earlier phase's result is already flushed."""
    phases = []
    if _os.environ.get("BENCH_RESNET", "1") == "1":
        phases.append(("resnet50", bench_resnet))
    if _os.environ.get("BENCH_DEEPFM", "1") == "1":
        phases.append(("deepfm", bench_deepfm))
    if _os.environ.get("BENCH_LSTM", "1") == "1":
        phases.append(("stacked_lstm", bench_stacked_lstm))
    return phases


def _obs_counters():
    """Registry before-image for one bench phase. Phases diff against it
    (export.delta_state) instead of resetting, so the emitted "metrics"
    object carries only what THIS phase moved and the process-wide
    registry stays intact for later phases."""
    try:
        from paddle_tpu.observability import export
        return export.counters_state()
    except Exception:  # metrics must never break a bench capture
        return None


def _obs_delta(before):
    """Nonzero registry movement since ``before``, rounded for the JSON
    line; None when observability was unavailable at phase start."""
    if before is None:
        return None
    try:
        from paddle_tpu.observability import export
        return {k: round(v, 4) for k, v in export.delta_state(before).items()}
    except Exception:
        return None


if __name__ == "__main__":
    _sys.exit(main())
