"""Train the flagship decoder-only LM (single chip or a multi-chip mesh).

Run:
  python examples/train_lm.py                       # single device
  python examples/train_lm.py --mesh dp=2,mp=4      # 8-chip tensor parallel
  python examples/train_lm.py --mesh dp=1,sp=8 --ring --seq 8192  # long ctx
  python examples/train_lm.py --mesh dp=2,pp=4 --pp-microbatches 4 \
      --pp-schedule interleaved   # pipeline parallel from the same Program
      # (--batch then declares the PER-DEVICE microbatch; the global batch
      #  is batch * dp * microbatches)

On CPU smoke-test with:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/train_lm.py --cpu --mesh dp=2,mp=4 --layers 2 \
      --d-model 128 --seq 256 --steps 3
"""
import os as _os, sys as _sys
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))  # run from anywhere
import argparse
import time

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers, models, optimizer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--vocab", type=int, default=32768)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--d-model", type=int, default=1024)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--loop", action="store_true",
                    help="run the timed steps as ONE device-side XLA loop "
                         "(Executor.run_loop) — one dispatch/fetch total")
    ap.add_argument("--mesh", type=str, default=None,
                    help="axis=size pairs, e.g. dp=2,mp=4")
    ap.add_argument("--ring", action="store_true",
                    help="sequence-parallel ring attention")
    ap.add_argument("--pp-microbatches", type=int, default=4,
                    help="microbatches per step when the mesh has pp")
    ap.add_argument("--pp-schedule", choices=["gpipe", "interleaved"],
                    default="gpipe")
    ap.add_argument("--cpu", action="store_true",
                    help="run on the host CPU (the default place is the "
                         "TPU, and fails without one)")
    ap.add_argument("--amp", action=argparse.BooleanOptionalAction,
                    default=True, help="bf16 mixed precision (--no-amp off)")
    args = ap.parse_args()

    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = 1
    with fluid.program_guard(main_p, startup):
        with fluid.unique_name.guard():
            ids = layers.data(name="ids", shape=[args.batch, args.seq],
                              dtype="int64", append_batch_size=False)
            labels = layers.data(name="labels", shape=[args.batch, args.seq],
                                 dtype="int64", append_batch_size=False)
            loss, _ = models.transformer.transformer_lm(
                ids, labels, vocab_size=args.vocab, n_layer=args.layers,
                n_head=16, d_model=args.d_model, d_inner=4 * args.d_model,
                max_len=args.seq, use_ring_attention=args.ring)
            optimizer.Adam(learning_rate=1e-4).minimize(loss)
        if args.amp:
            main_p.enable_mixed_precision()

    # pp mode: the Program declares the per-device microbatch and feeds
    # carry microbatches x dp x that in dim 0 (rows = global batch)
    mesh_axes = (dict(kv.split("=") for kv in args.mesh.split(","))
                 if args.mesh else {})
    scale = (args.pp_microbatches * int(mesh_axes.get("dp", 1))
             if "pp" in mesh_axes else 1)
    rows = scale * args.batch
    r = np.random.RandomState(0)
    feed = {
        "ids": r.randint(0, args.vocab, (rows, args.seq), np.int64),
        "labels": r.randint(0, args.vocab, (rows, args.seq), np.int64),
    }

    place = fluid.CPUPlace() if args.cpu else fluid.TPUPlace()
    fluid.Executor(place).run(startup)  # init params in the global scope
    if args.mesh:
        from paddle_tpu.parallel import (ParallelExecutor, make_mesh,
                                         megatron_transformer_plan,
                                         seq_parallel_plan)

        mesh = make_mesh([int(v) for v in mesh_axes.values()],
                         tuple(mesh_axes))
        kw = {}
        if "pp" in mesh_axes:
            if args.ring or "sp" in mesh_axes:
                raise SystemExit(
                    "pipeline parallelism composes with dp and mp today; "
                    "drop sp/--ring from --mesh when using pp")
            from paddle_tpu.parallel import BuildStrategy

            bs = BuildStrategy()
            bs.pipeline_stages = int(mesh_axes["pp"])
            bs.pipeline_microbatches = args.pp_microbatches
            bs.pipeline_schedule = args.pp_schedule
            kw["build_strategy"] = bs
            if "mp" in mesh_axes:
                # tensor parallelism rides the auto mp axis inside the
                # pipeline's manual (dp, pp) region
                kw["plan"] = megatron_transformer_plan(
                    mesh, mp_axis="mp",
                    batch_axes=("dp",) if "dp" in mesh_axes else ())
        elif args.ring:
            kw["plan"] = seq_parallel_plan(mesh)
        elif "mp" in mesh_axes:
            kw["plan"] = megatron_transformer_plan(mesh)
        elif "sp" in mesh_axes:
            kw["plan"] = seq_parallel_plan(mesh)
        # pure-dp meshes use ParallelExecutor's default data-parallel plan
        pexe = ParallelExecutor(loss_name=loss.name, main_program=main_p,
                                mesh=mesh, **kw)
        run = lambda fetch: pexe.run(feed=feed, fetch_list=fetch)
    else:
        sexe = fluid.Executor(place)
        run = lambda fetch: sexe.run(main_p, feed=feed, fetch_list=fetch)

    if args.loop:
        if args.mesh:
            looper = lambda fetch_list, steps: pexe.run_loop(
                fetch_list=fetch_list, feed=feed, steps=steps)
        else:
            looper = lambda fetch_list, steps: sexe.run_loop(
                main_p, feed=feed, fetch_list=fetch_list, steps=steps)
        looper([loss], 1)  # compile + warm
        t0 = time.perf_counter()
        out = looper([loss], args.steps)  # numpy return = synced
        dt = (time.perf_counter() - t0) / args.steps
    else:
        # warm BOTH compiled variants (the cache keys on the fetch set):
        # the timed loop mixes no-fetch steps with one final loss fetch
        run([loss])
        run([])
        t0 = time.perf_counter()
        for _ in range(args.steps - 1):
            run([])
        out = run([loss])
        dt = (time.perf_counter() - t0) / args.steps
    toks = rows * args.seq / dt
    print("loss %.4f  |  %.0f tokens/s  |  %.1f ms/step"
          % (float(np.asarray(out[0]).reshape(-1)[0]), toks, dt * 1e3))


if __name__ == "__main__":
    main()
