"""Compile a cell's step at full size for a DESCRIBED v5e, no chip
attached, and print what `memory_analysis()` says (rehearsal 3 of the
`on-chip-measurement` guide). Nothing runs; a compile that passes is not
a chip run.

    JAX_PLATFORMS=cpu python benchmark/tools/rehearse_v5e.py <cell> [--layers N] [--batch B]

Training cells: the whole training step (one chip, or the config's mesh).
Serving cells: the decode step at the config's slots and the prefill at
`--batch` x `--seq`.
"""
from __future__ import annotations

import argparse
import importlib
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["PADDLE_TPU_FORCE_PALLAS"] = "1"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import Mesh, SingleDeviceSharding  # noqa: E402

from benchmark.lib import harness  # noqa: E402

GB = 1024.0 ** 3


def report(tag, compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    text = compiled.as_text()
    print("%s: args %.2f GB, temps %.2f GB, out %.2f GB, alias %.2f GB, "
          "total %.2f GB per device; tpu_custom_call %d, all-reduce %d"
          % (tag, m.argument_size_in_bytes / GB, m.temp_size_in_bytes / GB,
             m.output_size_in_bytes / GB, m.alias_size_in_bytes / GB,
             total / GB, text.count("tpu_custom_call"),
             text.count("all-reduce(") + text.count("all-reduce-start(")),
          flush=True)


def train(cfg, mix, topo):
    from paddle_tpu.executor import analyze_state, build_step_fn
    from paddle_tpu.framework import trace as trace_mod

    model = importlib.import_module(
        "benchmark.models." + cfg["builder"])
    built = model.build_train(cfg, mix)
    main_p, startup, loss = built["main"], built["startup"], built["loss"]
    sds = jax.ShapeDtypeStruct
    if cfg.get("mesh"):
        mesh = Mesh(np.array(topo.devices).reshape(cfg["mesh"]["shape"]),
                    tuple(cfg["mesh"]["axes"]))
        plan = model.plan(cfg, mesh)

        def state_sh(name, shape):
            return plan.sharding(name, shape=shape)

        def other_sh(ndim, batch):
            return plan.feed_sharding(ndim) if batch else plan.replicated()
    else:
        mesh = plan = None
        one = SingleDeviceSharding(topo.devices[0])

        def state_sh(name, shape):
            return one

        def other_sh(ndim, batch):
            return one

    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    step = sds((), np.uint32)
    _, init_out = analyze_state(startup, set())
    _, init = jax.eval_shape(build_step_fn(startup, (), [], init_out),
                             {}, {}, key, step)
    feed_names = tuple(v for v in built.get("feed_names", ("ids", "labels")))
    state_in, state_out = analyze_state(main_p, set(feed_names))
    stepfn = build_step_fn(main_p, (loss.name,), state_in, state_out)
    gb = main_p.global_block()
    feeds = {}
    for n in feed_names:
        var = gb.var(n)
        shape = tuple(mix["batch"] if d in (-1, None) else d
                      for d in var.shape)
        dt = np.float32 if "float" in str(var.dtype).lower() else np.int32
        feeds[n] = sds(shape, dt, sharding=other_sh(len(shape), True))
    state = {n: sds(init[n].shape, init[n].dtype,
                    sharding=state_sh(n, init[n].shape)) for n in state_in}
    args = (feeds, state, sds(key.shape, key.dtype,
                              sharding=other_sh(key.ndim, False)),
            sds((), np.uint32, sharding=other_sh(0, False)))
    ctxm = (trace_mod.mesh_context(mesh, plan) if mesh is not None
            else trace_mod.mesh_context(None, None))
    with ctxm:
        compiled = jax.jit(stepfn, donate_argnums=(1,)).lower(
            *args).compile()
    report("train step", compiled)


def serve(cfg, mix, topo, batch, seq):
    from paddle_tpu.executor import analyze_state
    from paddle_tpu.framework.trace import RngStream, trace_block
    from paddle_tpu.ops import kv_cache as KV
    from paddle_tpu.serving.decode import DecodePredictor

    KV._use_pallas_decode = (
        lambda s, d: d % 128 == 0 and s % 128 == 0 and s >= 128)
    model = importlib.import_module(
        "benchmark.models." + cfg["builder"])
    one = SingleDeviceSharding(topo.devices[0])
    pred = DecodePredictor.__new__(DecodePredictor)  # graph builder only
    pred.config = model.decode_config(cfg, mix["kind"])
    pred.sample_k, pred.sample_p, pred.temperature = 40, 0.9, 1.0
    pred.draft_n_layer = 1
    sds = jax.ShapeDtypeStruct
    for kind, b, s in (("decode", cfg["serve"]["slots"],
                        cfg["serve"]["max_seq"]), ("prefill", batch, seq)):
        program, feed_names, fetch_names = pred._build(kind, b, s, "greedy")
        feeds = {n: sds(a.shape, a.dtype, sharding=one) for n, a in
                 pred._feed_structs(program, feed_names).items()}
        gb = program.global_block()
        state = {}
        for n in analyze_state(program, set(feed_names))[0]:
            var = gb._find_var_recursive(n)
            state[n] = sds(tuple(var.shape), np.float32, sharding=one)

        def step_fn(feeds, state):
            env = dict(state)
            env.update(feeds)
            trace_block(gb, env, RngStream(jax.random.PRNGKey(0)))
            return tuple(env[n] for n in fetch_names)

        compiled = jax.jit(step_fn, donate_argnums=(0,)).lower(
            feeds, state).compile()
        report("%s %dx%d" % (kind, b, s), compiled)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--layers", type=int)
    ap.add_argument("--batch", type=int)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--slots", type=int)
    a = ap.parse_args()
    _, cell, cfg, mix = harness.load_cell(harness.ROOT, a.cell)
    kind = mix["kind"].split("_")[0]
    if a.layers:
        cfg["num_hidden_layers"][kind] = a.layers
    if a.slots:
        cfg["serve"]["slots"] = a.slots
    if a.batch and kind == "train":
        mix["batch"] = a.batch
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    print("cell %s: %s layers=%s" % (cell["name"], kind,
                                     cfg.get("num_hidden_layers")))
    if kind == "train":
        train(cfg, mix, topo)
    else:
        serve(cfg, mix, topo, a.batch or 4, a.seq)


if __name__ == "__main__":
    main()
