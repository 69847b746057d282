"""Child of the cross-process tests of the AOT disk tier (run via
subprocess by tests/test_aot_cache.py).

A FRESH interpreter builds a tiny MLP training program, runs its
startup program, one training step and a fused ``run_loop`` window of 2
steps, and prints one JSON line: ``cold_compiles`` and ``warm_loads``
(executables compiled here against deserialized from the cache directory
that ``PADDLE_TPU_AOT_CACHE_DIR`` names), ``first_loss`` and ``ttfs_s``
(program build + startup + first step, imports not counted).
"""
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

IN_DIM, WIDTH, BATCH, LOOP_STEPS = 8, 16, 4, 2


def main():
    import jax

    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu import layers, optimizer, observability as obs

    t_import = time.perf_counter()
    main_p, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope), fluid.program_guard(main_p, startup):
        with fluid.unique_name.guard():
            x = layers.data(name="x", shape=[IN_DIM])
            y = layers.data(name="y", shape=[1])
            h = layers.fc(x, WIDTH, act="relu")
            loss = layers.mean(layers.square(layers.fc(h, 1) - y))
            optimizer.SGD(learning_rate=0.01).minimize(loss)

    rs = np.random.RandomState(0)
    feed = {"x": rs.rand(BATCH, IN_DIM).astype(np.float32),
            "y": rs.rand(BATCH, 1).astype(np.float32)}
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        first = exe.run(main_p, feed=feed, fetch_list=[loss])[0]
        t_first = time.perf_counter()
        exe.run_loop(main_p, feed=feed, fetch_list=[loss], steps=LOOP_STEPS)

    def count(path):
        return sum(obs.AOT_COMPILE_MS.stats(path=path, kind=k)["count"]
                   for k in ("run", "loop"))

    print(json.dumps({
        "cold_compiles": count("cold"),
        "warm_loads": count("warm"),
        "first_loss": float(np.asarray(first).ravel()[0]),
        "ttfs_s": t_first - t_import,
    }))


if __name__ == "__main__":
    main()
