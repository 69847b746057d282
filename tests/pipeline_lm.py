"""What the `test_pipeline_program*.py` files share: the tiny decoder-only
LM they pipeline, its sequential single-device reference, and the one
comparison of a pipelined training step against it."""
from __future__ import annotations

import numpy as np

import jax

import paddle_tpu as fluid
from paddle_tpu.framework.core import Program, program_guard
from paddle_tpu.models.transformer import transformer_lm
from paddle_tpu.parallel import make_mesh
from paddle_tpu.parallel.parallel_executor import (BuildStrategy,
                                                   ParallelExecutor)

VOCAB, D_MODEL, N_HEAD, D_INNER, T = 64, 32, 2, 64, 16


def build_lm(batch, n_layer, seed=7, lr=0.1):
    """(main, startup, loss) for a decoder-only LM at `batch`. A fresh
    unique_name scope keeps auto-named params (layer_norm) identical
    between the microbatch-sized and full-batch constructions."""
    main, startup = Program(), Program()
    main.random_seed = seed
    startup.random_seed = seed
    with fluid.unique_name.guard(), program_guard(main, startup):
        ids = fluid.layers.data(name="ids", shape=[batch, T], dtype="int64",
                                append_batch_size=False)
        lbl = fluid.layers.data(name="lbl", shape=[batch, T], dtype="int64",
                                append_batch_size=False)
        loss, _ = transformer_lm(
            ids, lbl, VOCAB, n_layer=n_layer, n_head=N_HEAD,
            d_model=D_MODEL, d_inner=D_INNER, dropout_rate=0.0,
            max_len=T, fused_head=False)
        fluid.optimizer.SGD(learning_rate=lr).minimize(loss)
    return main, startup, loss


def run_sequential_reference(n_layer, xs, ys, p0, lr):
    """Single-device full-batch step on an identically-named program."""
    B = xs.shape[0]
    main, startup, loss = build_lm(batch=B, n_layer=n_layer, lr=lr)
    scope = fluid.core.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        for k, v in p0.items():  # start from the SAME initial params
            scope.set_var(k, v)
        lv, = exe.run(main, feed={"ids": xs, "lbl": ys},
                      fetch_list=[loss])
    params = {k: np.asarray(scope.find_var(k)) for k in p0}
    return float(lv), params


def param_names(program):
    return [p.name for p in program.all_parameters()]


def pipeline_vs_sequential(mesh_shape, axes, *, n_layer, stages,
                           microbatches, seed, schedule=None, plan=None,
                           what="pp"):
    """One pipelined training step of the LM over `mesh_shape` (B_mb = 2
    a device a microbatch; feeds carry M x dp x that in dim 0) against
    sequential full-batch execution from the SAME initial parameters:
    the loss within 2e-4 and every updated parameter within rtol 2e-3 /
    atol 2e-5. `plan(mesh)` gives a sharding plan for the axes the tick
    loop leaves automatic. Returns (updated, initial) parameters."""
    M, B_mb, lr = microbatches, 2, 0.1
    B = M * dict(zip(axes, mesh_shape)).get("dp", 1) * B_mb
    rs = np.random.RandomState(seed)
    xs = rs.randint(0, VOCAB, (B, T)).astype(np.int64)
    ys = rs.randint(0, VOCAB, (B, T)).astype(np.int64)

    main, startup, loss = build_lm(batch=B_mb, n_layer=n_layer, lr=lr)
    scope = fluid.core.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    p0 = {k: np.asarray(scope.find_var(k)) for k in param_names(main)}

    mesh = make_mesh(list(mesh_shape), axes,
                     devices=jax.devices()[:int(np.prod(mesh_shape))])
    bs = BuildStrategy()
    bs.pipeline_stages = stages
    bs.pipeline_microbatches = M
    if schedule is not None:
        bs.pipeline_schedule = schedule
    pe = ParallelExecutor(loss_name=loss.name, main_program=main,
                          build_strategy=bs, scope=scope, mesh=mesh,
                          plan=plan(mesh) if plan else None)
    lv_pp, = pe.run(feed={"ids": xs, "lbl": ys}, fetch_list=[loss])
    p_pp = {k: np.asarray(scope.find_var(k)) for k in p0}

    lv_ref, p_ref = run_sequential_reference(n_layer, xs, ys, p0, lr)
    np.testing.assert_allclose(float(np.squeeze(lv_pp)), lv_ref,
                               rtol=2e-4)
    for k in sorted(p0):
        np.testing.assert_allclose(
            p_pp[k], p_ref[k], rtol=2e-3, atol=2e-5,
            err_msg="param %s diverged (%s vs sequential)" % (k, what))
    return p_pp, p0
