import os, sys; sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import os, glob
import numpy as np, jax
import paddle_tpu as fluid
from paddle_tpu.runtime.aot_cache import enable_compile_cache
enable_compile_cache()  # the one compile-cache rule, as bench.py
from paddle_tpu import layers, models, optimizer

_e = os.environ.get
# Default to the r5 baked-winner LM config (batch 16, heads 8, BTHD layout,
# fused flash backward) so the trace captures the graph bench.py actually
# times. bench.main() never runs here, so the kernel levers are
# setdefault'd — export PADDLE_TPU_ATTN_BTHD=0 etc. to profile another path.
os.environ.setdefault("PADDLE_TPU_ATTN_BTHD", "1")
os.environ.setdefault("PADDLE_TPU_FLASH_FUSED_BWD", "1")
B,S,V,L,D,F,H = (int(_e("BENCH_BATCH", 16)), int(_e("BENCH_SEQ", 1024)),
                 int(_e("BENCH_VOCAB", 32768)), int(_e("BENCH_LAYERS", 12)),
                 int(_e("BENCH_DMODEL", 1024)), int(_e("BENCH_DINNER", 4096)),
                 int(_e("BENCH_HEADS", 8)))
main_p, startup = fluid.Program(), fluid.Program()
main_p.random_seed = startup.random_seed = 1
scope = fluid.Scope()
MODEL = _e("PROFILE_MODEL", "transformer")
if MODEL not in ("transformer", "resnet"):
    raise SystemExit("PROFILE_MODEL must be 'transformer' or 'resnet', got %r" % MODEL)
with fluid.scope_guard(scope), fluid.program_guard(main_p, startup):
    with fluid.unique_name.guard():
        if MODEL == "resnet":
            RB = int(_e("BENCH_RN_BATCH", 128))
            loss, _acc, _feeds = models.resnet.get_model(
                dataset="imagenet", depth=50,
                layout=_e("BENCH_RN_LAYOUT", "NCHW"))
            optimizer.Momentum(learning_rate=0.1, momentum=0.9).minimize(loss)
        else:
            ids = layers.data(name="ids", shape=[B,S], dtype="int64", append_batch_size=False)
            lbl = layers.data(name="labels", shape=[B,S], dtype="int64", append_batch_size=False)
            loss, _ = models.transformer.transformer_lm(ids, lbl, vocab_size=V, n_layer=L, n_head=H, d_model=D, d_inner=F, max_len=S)
            optimizer.Adam(learning_rate=1e-4).minimize(loss)
    if _e("BENCH_AMP", "1") == "1":
        # mirror bench.py main()'s per-phase AMP defaults: the trace must
        # capture the SAME graph the bench times — LM at O2 (the r5
        # sweep winner bench.main() bakes), ResNet pinned O1 (O2
        # measured 35% slower there). bench.main() never runs here, so
        # the defaults are restated per phase.
        main_p.enable_mixed_precision(
            level=_e("BENCH_RN_AMP_LEVEL", "O1") if MODEL == "resnet"
            else _e("BENCH_AMP_LEVEL", "O2"))
    exe = fluid.Executor(fluid.CPUPlace() if _e("BENCH_PLATFORM", "") == "cpu"
                         else fluid.TPUPlace())
    exe.run(startup)
    r = np.random.RandomState(0)
    if MODEL == "resnet":
        # stage the ~77 MB image batch on device (bench.py's own helper):
        # re-uploading it per step would dwarf compute
        from bench import _stage_feed
        feed = _stage_feed({"data": r.randn(RB,3,224,224).astype(np.float32),
                            "label": r.randint(0,1000,(RB,1)).astype(np.int64)},
                           jax.devices()[0])
    else:
        feed = {"ids": r.randint(0,V,(B,S)).astype(np.int64),
                "labels": r.randint(0,V,(B,S)).astype(np.int64)}
    # warm + compile the loop executable, then trace one 6-step window,
    # fenced by reading the loss back to the host.
    out = exe.run_loop(main_p, feed=feed, fetch_list=[loss],
                       steps=2, return_numpy=False)
    float(np.asarray(out[0]).reshape(-1)[0])
    with jax.profiler.trace("/tmp/jaxprof"):
        out = exe.run_loop(main_p, feed=feed, fetch_list=[loss],
                           steps=6, return_numpy=False)
        float(np.asarray(out[0]).reshape(-1)[0])
print(glob.glob("/tmp/jaxprof/**/*.xplane.pb", recursive=True))
