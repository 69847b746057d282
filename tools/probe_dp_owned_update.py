"""The mesh training cell's own step under the layouts of its state over
``dp`` that PR 53 chose between, each built by hand from the PARENT's
specs, so the three stay comparable whatever ``megatron_transformer_plan``
gives today:

- ``whole``: every ``dp`` twin holds and updates its whole ``mp`` slice
  (the plan until PR 52);
- ``moments``: the Adam moments of the matrices and tables split over
  ``dp`` on the dimension ``mp`` leaves whole (ZeRO-1);
- ``weights``: the float32 weights at rest split the same way too;
- ``plan``: what ``megatron_transformer_plan`` gives in this tree.

On the chip (four of them), one layout a process:

    python tools/probe_dp_owned_update.py --form weights --steps 12

prints the step time, ``peak_bytes_in_use`` of device 0 and, from a traced
window, the device's operations grouped by the TEXT of their instruction
in the step's own compiled program (``classify_text``: `all-reduce` over
the ``dp`` or the ``mp`` pairs, fusions that call an ``all-reduce-scatter``,
plain ``all-gather``, the asynchronous collectives' ``start`` / ``done``,
the optimizer's update fusions), in ms a step; the table, every operation
of 0.05 ms a step and up, and the compiled text go to
``chiprun_out/probe_dp_owned_update/<form>.json`` / ``.hlo.txt``.

Without a chip, ``--compile`` compiles the same step for a DESCRIBED
``v5e:2x2`` and prints the collectives of the compiled text and the bytes a
device holds (``--layers`` / ``--config`` cut it to size; nothing runs):

    python tools/probe_dp_owned_update.py --compile --form moments --layers 2
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# `tests/hlo_text.py` reads compiled texts for the tests and for this
sys.path[:0] = [HERE, os.path.join(HERE, "tests")]
from hlo_text import _computations, replica_groups  # noqa: E402

FORMS = ("whole", "moments", "weights", "plan")
# the matrices and tables whose update one dp rank can own, with the
# parent's spec of each (`mp` on one dimension) and the dimension left whole
_OWNED = [
    (r"\.(q|k|v|qkv|fc1)\.w", 0),
    (r"\.(out|fc2)\.w", 1),
    (r"pos_emb", 0),
    (r"tok_emb", 1),  # tied alone: rows over mp, so the columns
]
_MOMENT = r"_moment[12]_acc"


def build_plan(form, mesh, tied):
    """The layout `form` as a ShardingPlan over `mesh` ("dp", "mp")."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.parallel import megatron_transformer_plan

    if form == "plan":
        return megatron_transformer_plan(mesh, tied=tied)
    # the parent's specs: what the plan gives where it sees no batch axis
    plan = megatron_transformer_plan(mesh, tied=tied, batch_axes=())
    plan.batch_axes = ("dp",)
    if form == "whole":
        return plan
    front = []
    for pat, whole_dim in _OWNED:
        if pat == "tok_emb" and not tied:
            continue
        spec = P("dp", "mp") if whole_dim == 0 else P("mp", "dp")
        front.append((re.compile(pat + _MOMENT), spec, False))
        if form == "weights":
            front.append((re.compile(pat + "$"), spec, False))
    plan._regex[:0] = front
    return plan


def load_cell(args):
    """(configuration, traffic mix): each a name under `benchmark/` or a
    file (the tiny ones of `benchmark/tests/tiny`, for a rehearsal)."""
    def read(name, where):
        path = name if os.path.exists(name) else os.path.join(
            HERE, "benchmark", where, name + ".json")
        with open(path) as f:
            return json.load(f)

    cfg, mix = read(args.config, "configs"), read(args.traffic, "traffic")
    if args.layers:
        cfg["num_hidden_layers"]["train"] = args.layers
    return cfg, mix


# -- the compiled text -------------------------------------------------------

_SHAPE = re.compile(r"(bf16|f32|f16|s32|u32|s8|u8|pred)\[([\d,]*)\]")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
          "u8": 1, "pred": 1}
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*)$")
_MOVE = re.compile(r"^(.*?) (all-reduce|all-gather|reduce-scatter|all-to-all"
                   r"|collective-permute)(?:-start)?\(")
_COPIES = re.compile(r" (copy|copy-start|copy-done|slice|dynamic-slice|"
                     r"dynamic-update-slice|bitcast|concatenate|pad|"
                     r"slice-start|slice-done)\(")


def classify_text(text, mesh_ids):
    """instruction name -> (row, MB it moves) for every instruction of a
    compiled text, outside the computations fusions call. The row is read
    off the instruction's own TEXT, never its name: `all-reduce dp bf16`,
    `reduce-scatter dp f32` (a fusion that calls an `all-reduce-scatter`;
    the MB are the gradient that goes in), `async all-gather dp bf16
    start` / `... done` (an `async_collective_fusion` runs between them),
    `adam update` (the metadata names an `fl.adam` scope), `matmul
    fusions`, `mosaic kernels`, `copies and slices`, `other fusions`."""
    axes = {
        frozenset(frozenset(int(i) for i in mesh_ids[:, j])
                  for j in range(mesh_ids.shape[1])): "dp",
        frozenset(frozenset(int(i) for i in mesh_ids[i, :])
                  for i in range(mesh_ids.shape[0])): "mp",
        frozenset([frozenset(int(i) for i in mesh_ids.ravel())]): "all",
    }
    comps = _computations(text)

    def move(line):
        """(opcode, axis, types, MB) of a collective's line, or None."""
        m = _MOVE.match(line.split(" = ", 1)[-1])
        if not m:
            return None
        shapes = _SHAPE.findall(m.group(1))
        mb = sum(_BYTES[t] * int(np.prod([int(d) for d in dims.split(",")
                                          if d] or [1]))
                 for t, dims in shapes) / 1e6
        return (m.group(2), axes.get(replica_groups(line), "?"),
                ",".join(sorted({t for t, _ in shapes})), mb)

    def inner(callee):
        """The largest collective inside a called computation."""
        found = [mv for mv in map(move, comps.get(callee, ())) if mv]
        return max(found, key=lambda mv: mv[3]) if found else None

    called = set(re.findall(r"calls=%([\w.\-]+)", text))
    out = {}
    for name, lines in comps.items():
        if name in called:
            continue
        for line in lines:
            m = _INSTR.match(line)
            if not m:
                continue
            inst, rest = m.groups()
            callee = re.search(r"calls=%([\w.\-]+)", rest)
            callee = callee.group(1) if callee else ""
            mv = move(line)
            if mv:
                out[inst] = ("%s %s %s" % mv[:3], mv[3])
            elif callee.startswith("all-reduce-scatter"):
                mv = inner(callee) or ("", "?", "?", 0.0)
                out[inst] = ("reduce-scatter %s %s" % mv[1:3], mv[3])
            elif inst.startswith("async-collective-"):
                mv = inner(callee) or ("collective", "?", "?", 0.0)
                end = "start" if "-start" in inst else "done"
                out[inst] = ("async %s %s %s %s" % (mv[:3] + (end,)),
                             mv[3] if end == "start" else 0.0)
            elif "tpu_custom_call" in rest:
                out[inst] = ("mosaic kernels", 0.0)
            elif "/fl.adam:" in rest:
                out[inst] = ("adam update", 0.0)
            elif " fusion(" in rest and any(
                    " convolution(" in ln for ln in comps.get(callee, ())):
                out[inst] = ("matmul fusions", 0.0)
            elif _COPIES.search(" " + rest.split("(", 1)[0] + "("):
                out[inst] = ("copies and slices", 0.0)
            else:
                out[inst] = ("other fusions", 0.0)
    return out


def print_text_rows(text, mesh_ids):
    """The collectives of a compiled text: row, count, MB."""
    rows = collections.defaultdict(lambda: [0, 0.0])
    for row, mb in classify_text(text, mesh_ids).values():
        if mb:
            rows[row][0] += 1
            rows[row][1] += mb
    for row, (n, mb) in sorted(rows.items()):
        if mb >= 1.0:  # norms, biases and row statistics left out
            print("   text %-36s %4d  %9.1f MB" % (row, n, mb))


def compile_described(args):
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    os.environ["PADDLE_TPU_FORCE_PALLAS"] = "1"
    import jax
    from jax.experimental import topologies
    from jax.sharding import Mesh

    cfg, mix = load_cell(args)
    from benchmark.models import opt_lm
    from paddle_tpu.executor import analyze_state, build_step_fn
    from paddle_tpu.framework import trace as trace_mod

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "mp"))
    mesh_ids = np.array([[d.id for d in row] for row in mesh.devices])
    built = opt_lm.build_train(cfg, mix)
    main_p, startup, loss = built["main"], built["startup"], built["loss"]
    sds = jax.ShapeDtypeStruct
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    step = sds((), np.uint32)
    _, init_out = analyze_state(startup, set())
    _, init = jax.eval_shape(build_step_fn(startup, (), [], init_out),
                             {}, {}, key, step)
    state_in, state_out = analyze_state(main_p, {"ids", "labels"})
    stepfn = build_step_fn(main_p, (loss.name,), state_in, state_out)
    for form in args.form:
        plan = build_plan(form, mesh, bool(cfg["tie_word_embeddings"]))
        rep = plan.replicated()
        feeds = {n: sds((mix["batch"], mix["seq"]), np.int32,
                        sharding=plan.feed_sharding(2))
                 for n in ("ids", "labels")}
        state = {n: sds(init[n].shape, init[n].dtype,
                        sharding=plan.sharding(n, shape=init[n].shape))
                 for n in state_in}
        t0 = time.time()
        with trace_mod.mesh_context(mesh, plan):
            _, out_aval = jax.eval_shape(stepfn, feeds, state, key, step)
            out_sh = {n: plan.sharding(n, shape=tuple(a.shape))
                      for n, a in out_aval.items()}
            compiled = jax.jit(
                stepfn, donate_argnums=(1,),
                out_shardings=((rep,), out_sh)).lower(
                feeds, state, sds(key.shape, key.dtype, sharding=rep),
                sds((), np.uint32, sharding=rep)).compile()
        text = compiled.as_text()
        mem = compiled.memory_analysis()
        total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        print("== %s: compiled in %.0f s; a device holds %.2f GB of "
              "arguments, %.2f GB in all" % (
                  form, time.time() - t0,
                  mem.argument_size_in_bytes / 1e9, total / 1e9))
        print_text_rows(text, mesh_ids)
        if args.text:
            os.makedirs(args.text, exist_ok=True)
            with open(os.path.join(args.text, form + ".txt"), "w") as f:
                f.write(text)


# -- the chip ----------------------------------------------------------------

_WAITS = re.compile(r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
                    r"collective-permute|async .* done)")


def run_on_chip(args):
    import shutil

    import jax

    cfg, mix = load_cell(args)
    from benchmark.lib import run_train, trace_reduce
    from benchmark.models import opt_lm
    from paddle_tpu.framework import trace as trace_mod

    form = args.form[0]
    devices = jax.devices()
    assert len(devices) >= 4 and (
        args.cpu or devices[0].platform == "tpu"), devices
    tied = bool(cfg["tie_word_embeddings"])
    model = type("M", (), dict(vars(opt_lm)))
    model.plan = staticmethod(lambda c, mesh: build_plan(form, mesh, tied))
    tr = run_train.Trainer(cfg, mix, 4, devices[:4], model)
    tr.reset(args.seed)
    pool_np, _, _ = opt_lm.train_pool(
        cfg, dict(mix, pool_batches=args.steps + 8), args.seed)
    pool = [{k: tr.put(v) for k, v in f.items()} for f in pool_np]
    loss = tr.loss
    t1 = time.time()
    first = float(np.asarray(tr.step(pool[0], [loss])[0]).reshape(()))
    t_compile = time.time() - t1
    jax.block_until_ready(tr.step(pool[1], [loss]))
    # timed: two steps in flight, as the cell drives it
    pending = collections.deque()
    t1 = time.perf_counter()
    for i in range(args.steps):
        pending.append(tr.step(pool[(2 + i) % len(pool)], [loss])[0])
        if len(pending) > 2:
            jax.block_until_ready(pending.popleft())
    jax.block_until_ready(list(pending))
    step_ms = (time.perf_counter() - t1) / args.steps * 1e3
    last = float(np.asarray(pending[-1]).reshape(()))
    stats = devices[0].memory_stats() or {}
    out_dir = os.path.join(HERE, "chiprun_out", "probe_dp_owned_update")
    os.makedirs(out_dir, exist_ok=True)
    prof = os.path.join(out_dir, "profile_" + form)
    jax.profiler.start_trace(prof)
    outs = [tr.step(pool[i % len(pool)], [loss])[0]
            for i in range(args.trace_steps)]
    jax.block_until_ready(outs)
    jax.profiler.stop_trace()
    trace = (trace_reduce.load_xplane(
        prof, lambda n: n == "/host:CPU",
        ("tf_XLAPjRtCpuClient", "tf_XLAEigen")) if args.cpu
        else trace_reduce.load_xplane(prof))
    shutil.rmtree(prof, ignore_errors=True)
    numbers = trace_reduce.reduce_trace(trace)

    # the step's own executable, compiled once more for its text: an
    # executed event is told by the text of the instruction it names
    exe = tr.exe
    comp = [c for k, c in exe._cache.items() if k[3] == (loss.name,)][0]
    state = {k: exe._scope.find_var(k) for k in comp.state_in_names}
    with trace_mod.mesh_context(tr.mesh, tr.plan):
        text = comp.fn.lower(pool[0], state, exe._base_keys[1],
                             np.uint32(0)).compile().as_text()
    with open(os.path.join(out_dir, form + ".hlo.txt"), "w") as f:
        f.write(text)
    mesh_ids = np.array([[d.id for d in row] for row in tr.mesh.devices])
    row_of = classify_text(text, mesh_ids)

    plane = sorted(p for p, e in trace["devices"].items() if e)[0]
    n = args.trace_steps
    rows = collections.defaultdict(lambda: [0, 0.0])
    by_op = collections.defaultdict(lambda: [0, 0.0, ""])
    waiting, working = [], []  # intervals; containers in neither
    for name, start, dur, info in trace["devices"][plane]:
        if trace_reduce.CONTAINER.match(name):
            continue
        row = row_of.get(name, ("not in the text", 0.0))[0]
        (waiting if _WAITS.match(row) else working).append(
            (start, start + dur))
        rows[row][0] += 1
        rows[row][1] += dur / 1e6
        o = by_op[name]
        o[0] += 1
        o[1] += dur / 1e6
        o[2] = info[:300]
    # collectives BY TEXT during which nothing else runs (the reader of
    # `collective_exposed_pct.lm` goes by the event's NAME and counts a
    # `while` as something else running)
    alone_ms = trace_reduce.total(trace_reduce.subtract(
        trace_reduce.union(waiting), trace_reduce.union(working))) / 1e6 / n
    table = {k: {"events_a_step": v[0] / n, "ms_a_step": v[1] / n}
             for k, v in rows.items()}
    ops = sorted(by_op.items(), key=lambda kv: -kv[1][1])
    result = {
        "form": form, "seed": args.seed, "steps": args.steps,
        "step_ms": step_ms, "compile_and_first_step_s": t_compile,
        "loss_first": first, "loss_last": last,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "device": {"kind": devices[0].device_kind, "n": len(devices)},
        "trace_numbers": {k: v for k, v in numbers.items()
                          if not isinstance(v, (list, dict))},
        "collectives_by_text_alone_ms_a_step": alone_ms,
        "op_table_ms_a_step": table,
        "ops": [{"name": k, "row": row_of.get(k, ("?",))[0],
                 "events_a_step": v[0] / n, "ms_a_step": v[1] / n,
                 "text": v[2]} for k, v in ops if v[1] / n >= 0.05],
        "run_stats": exe.run_stats(),
    }
    with open(os.path.join(out_dir, form + ".json"), "w") as f:
        json.dump(result, f, indent=1)
    print("== %s: step %.2f ms (%d steps), peak %.3f GB a device, first "
          "step with its compile %.1f s, loss %.4f -> %.4f" % (
              form, step_ms, args.steps,
              (stats.get("peak_bytes_in_use") or 0) / 1e9, t_compile,
              first, last))
    print("   run_stats: %r" % (result["run_stats"],))
    print_text_rows(text, mesh_ids)
    print("   traced %d steps on %s: window %.1f ms a step, busy %.1f; the "
          "reader's, by event NAME: collective %.1f, exposed %.1f" % (
              n, plane, numbers.get("window_s", 0) / n * 1e3,
              numbers.get("busy_s", 0) / n * 1e3,
              numbers.get("collective_s", 0) / n * 1e3,
              numbers.get("collective_exposed_s", 0) / n * 1e3))
    print("   collectives by TEXT with nothing else running: %.2f ms a step"
          % alone_ms)
    for k, v in sorted(table.items(), key=lambda kv: -kv[1]["ms_a_step"]):
        print("   %-40s %7.1f events  %8.2f ms a step" % (
            k, v["events_a_step"], v["ms_a_step"]))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--form", default="plan",
                    help="one of %s (with --compile: a comma list)"
                    % (FORMS,))
    ap.add_argument("--config", default="opt-6.7b-tp2")
    ap.add_argument("--traffic", default="lm-pretrain-2048")
    ap.add_argument("--layers", type=int, default=0,
                    help="train this many layers, not the configuration's")
    ap.add_argument("--compile", action="store_true",
                    help="no chip: compile for a described v5e:2x2")
    ap.add_argument("--text", help="with --compile: keep the compiled "
                    "texts in this directory")
    ap.add_argument("--cpu", action="store_true",
                    help="a rehearsal on 4 virtual CPU devices: no number "
                    "of it means anything")
    ap.add_argument("--seed", type=int, default=2147483953)
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--trace-steps", type=int, default=4)
    args = ap.parse_args(argv)
    args.form = args.form.split(",")
    assert all(f in FORMS for f in args.form), args.form
    if args.compile:
        compile_described(args)
    else:
        assert len(args.form) == 1, "one layout a process on the chip"
        run_on_chip(args)


if __name__ == "__main__":
    main()
