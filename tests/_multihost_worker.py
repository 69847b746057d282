"""Worker for the two-process multi-host tests (run via subprocess).

Usage: python _multihost_worker.py <proc_id> <n_proc> <port> <out.npz> [mode]

Each process owns 2 virtual CPU devices; jax.distributed joins them into
one 4-device job. Modes:

  dp      — data parallel across hosts (default): each process feeds its
            LOCAL batch shard, params replicated.
  mp_ici  — hybrid placement: dp spans the process boundary over DCN,
            the Megatron mp axis stays INSIDE each host's ICI
            (make_hybrid_mesh ici mp — the placement the constructor
            exists for).
  mp_dcn  — the mp axis itself SPANS the process boundary: params are
            sharded across processes (each host owns half of every
            col/row-parallel weight), batch replicated.
  pp      — a 4-stage PIPELINE axis spans the process boundary:
            stages 0-1 live on host 0, stages 2-3 on host 1,
            so every inter-stage ppermute hop at the 1->2 boundary
            crosses DCN. The same Program-level plan_pipeline/
            BuildStrategy path as the single-process tests — the
            reference's multi-trainer pipeline capability
            (distribute_transpiler.py:336).

The worker trains an MLP (a 4-layer decoder LM for `pp`) for 3 steps
through ParallelExecutor, then process 0 writes losses + final
(allgathered) params.
"""
import os
import sys

# pp-mode model config, shared with the parent test's single-process
# reference so both build the IDENTICAL program (same auto param names)
PP_VOCAB, PP_D_MODEL, PP_N_HEAD, PP_D_INNER, PP_T = 64, 32, 2, 64, 16
PP_LAYERS, PP_STAGES, PP_MICRO, PP_MB = 4, 4, 4, 2


def build_pp_lm(batch, seed=13, lr=0.1):
    """(main, startup, loss) for the cross-process pipeline LM. Module
    level so the parent test constructs the identical program for its
    sequential reference. Imports stay inside the function: importing
    this module must not pull jax before the worker sets platform env."""
    import paddle_tpu as fluid
    from paddle_tpu.models.transformer import transformer_lm

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        ids = fluid.layers.data(name="ids", shape=[batch, PP_T],
                                dtype="int64", append_batch_size=False)
        lbl = fluid.layers.data(name="lbl", shape=[batch, PP_T],
                                dtype="int64", append_batch_size=False)
        loss, _ = transformer_lm(
            ids, lbl, PP_VOCAB, n_layer=PP_LAYERS, n_head=PP_N_HEAD,
            d_model=PP_D_MODEL, d_inner=PP_D_INNER, dropout_rate=0.0,
            max_len=PP_T, fused_head=False)
        fluid.optimizer.SGD(learning_rate=lr).minimize(loss)
    return main, startup, loss


def main():
    proc_id, n_proc, port, out_path = (int(sys.argv[1]), int(sys.argv[2]),
                                       sys.argv[3], sys.argv[4])
    mode = sys.argv[5] if len(sys.argv) > 5 else "dp"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    os.environ["JAX_PLATFORMS"] = "cpu"

    import numpy as np

    import jax

    jax.config.update("jax_platforms", "cpu")

    import paddle_tpu as fluid
    from paddle_tpu.parallel import (ParallelExecutor, init_distributed,
                                     make_hybrid_mesh)

    init_distributed("127.0.0.1:%s" % port, num_processes=n_proc,
                     process_id=proc_id)
    assert jax.process_count() == n_proc, jax.process_count()
    assert jax.device_count() == 2 * n_proc, jax.device_count()
    assert jax.local_device_count() == 2

    from jax.sharding import PartitionSpec as P

    from paddle_tpu.parallel.sharding import ShardingPlan

    if mode == "dp":
        # dp spans hosts over DCN; devices must enumerate host-major
        # (process 0's devices first)
        mesh = make_hybrid_mesh(("dp",), ici_shape=(2,),
                                dcn_shape=(n_proc,))
        flat = list(mesh.devices.flat)
        assert [d.process_index for d in flat] == sorted(
            d.process_index for d in flat), (
            "hybrid mesh is not host-major: %s" % flat)
    elif mode == "mp_ici":
        # dp across the process boundary (DCN), mp inside each host (ICI)
        mesh = make_hybrid_mesh(("dp", "mp"), ici_shape=(1, 2),
                                dcn_shape=(n_proc, 1))
        assert mesh.shape == {"dp": n_proc, "mp": 2}
        # every mp pair lives inside ONE process
        for row in mesh.devices:
            assert len({d.process_index for d in row}) == 1, (
                "mp axis crosses a process boundary in mp_ici mode")
    elif mode == "mp_dcn":
        # ONE mp axis built dcn x ici: spans both processes
        mesh = make_hybrid_mesh(("mp",), ici_shape=(2,),
                                dcn_shape=(n_proc,))
        assert mesh.shape == {"mp": 2 * n_proc}
        assert len({d.process_index for d in mesh.devices.flat}) == n_proc
    elif mode == "pp":
        # ONE pipeline axis built dcn x ici: stage k on device k, so the
        # stage 1 -> 2 activation hop crosses the process boundary
        mesh = make_hybrid_mesh(("pp",), ici_shape=(2,),
                                dcn_shape=(n_proc,))
        assert mesh.shape == {"pp": 2 * n_proc}
        assert len({d.process_index for d in mesh.devices.flat}) == n_proc
    else:
        raise SystemExit("unknown mode %r" % mode)

    if mode == "pp":
        _run_pp(proc_id, n_proc, mesh, out_path)
        jax.distributed.shutdown()
        return

    main_prog, startup = fluid.Program(), fluid.Program()
    main_prog.random_seed = startup.random_seed = 13
    with fluid.unique_name.guard(), fluid.program_guard(main_prog, startup):
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(x, 32, act="relu")
        pred = fluid.layers.fc(h, 1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)

    plan = None
    if mode != "dp":
        # Megatron split of the MLP: hidden fc column-parallel, output fc
        # row-parallel — GSPMD inserts the all-reduce after the row matmul
        w1, b1, w2, b2 = [p.name for p in main_prog.all_parameters()]
        plan = ShardingPlan(
            mesh, batch_axes=("dp",) if mode == "mp_ici" else ())
        plan.set(w1, P(None, "mp"))
        plan.set(b1, P("mp"))
        plan.set(w2, P("mp", None))
        plan.set(b2, P())

    scope = fluid.core.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        pexe = ParallelExecutor(
            loss_name=loss.name, main_program=main_prog, scope=scope,
            mesh=mesh, plan=plan, num_trainers=n_proc, trainer_id=proc_id)
        rs = np.random.RandomState(0)
        losses = []
        dp_n = n_proc if mode in ("dp", "mp_ici") else 1
        for step in range(3):
            xb = rs.randn(8, 16).astype(np.float32)
            yb = (xb[:, :1] * 0.5 + 0.1).astype(np.float32)
            # batch sharded over dp -> feed the local shard; mp_dcn has
            # no data axis -> every process feeds the full batch
            lo = 8 // dp_n * proc_id if dp_n > 1 else 0
            hi = 8 // dp_n * (proc_id + 1) if dp_n > 1 else 8
            lv, = pexe.run(feed={"x": xb[lo:hi], "y": yb[lo:hi]},
                           fetch_list=[loss])
            losses.append(float(np.squeeze(lv)))
        params = {}
        for p in main_prog.all_parameters():
            val = scope.find_var(p.name)
            if isinstance(val, jax.Array) and not val.is_fully_addressable:
                # mp shards live on both processes: gather to host numpy
                from jax.experimental import multihost_utils

                val = multihost_utils.process_allgather(
                    val, tiled=True)
            params[p.name] = np.asarray(val)
    if proc_id == 0:
        np.savez(out_path, losses=np.asarray(losses), **params)
    jax.distributed.shutdown()


def _run_pp(proc_id, n_proc, mesh, out_path):
    """Train the 4-layer LM pipelined over the cross-process pp mesh and
    write process 0's losses + allgathered params."""
    import numpy as np

    import jax

    import paddle_tpu as fluid
    from paddle_tpu.parallel.parallel_executor import (BuildStrategy,
                                                       ParallelExecutor)

    main_prog, startup, loss = build_pp_lm(batch=PP_MB)
    bs = BuildStrategy()
    bs.pipeline_stages = PP_STAGES
    bs.pipeline_microbatches = PP_MICRO

    scope = fluid.core.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        pexe = ParallelExecutor(
            loss_name=loss.name, main_program=main_prog, scope=scope,
            mesh=mesh, build_strategy=bs, num_trainers=n_proc,
            trainer_id=proc_id)
        rs = np.random.RandomState(0)
        losses = []
        B = PP_MICRO * PP_MB  # no dp axis: every process feeds the full batch
        for _ in range(3):
            xb = rs.randint(0, PP_VOCAB, (B, PP_T)).astype(np.int64)
            yb = rs.randint(0, PP_VOCAB, (B, PP_T)).astype(np.int64)
            lv, = pexe.run(feed={"ids": xb, "lbl": yb},
                           fetch_list=[loss])
            losses.append(float(np.squeeze(lv)))
        params = {}
        for p in main_prog.all_parameters():
            val = scope.find_var(p.name)
            if isinstance(val, jax.Array) and not val.is_fully_addressable:
                from jax.experimental import multihost_utils

                val = multihost_utils.process_allgather(val, tiled=True)
            params[p.name] = np.asarray(val)
    if proc_id == 0:
        np.savez(out_path, losses=np.asarray(losses), **params)


if __name__ == "__main__":
    main()
