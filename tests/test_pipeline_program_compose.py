"""Program-level pipeline parallelism, beside
`test_pipeline_program.py`: the interleaved schedule, and what a
pipelined step composes with: AMP and dropout, a Megatron `mp` axis
left automatic inside the manual tick loop, `run_loop`, and all three
axes in one mesh. `pipeline_lm.py` holds the LM and the comparison
against sequential execution."""
from __future__ import annotations

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu.framework.core import Program, program_guard
from paddle_tpu.models.transformer import transformer_lm
from paddle_tpu.parallel import make_mesh, megatron_transformer_plan
from paddle_tpu.parallel.parallel_executor import (BuildStrategy,
                                                   ParallelExecutor)
from paddle_tpu.parallel.pipeline_program import (PipelineError,
                                                  plan_pipeline)

from pipeline_lm import (D_INNER, D_MODEL, N_HEAD, T, VOCAB, build_lm,
                         param_names, pipeline_vs_sequential)


@pytest.mark.parametrize("mesh_shape,axes", [
    ((4,), ("pp",)),
    ((2, 4), ("dp", "pp")),
])
def test_interleaved_schedule_parity(mesh_shape, axes):
    """The circular schedule (each device holds every S-th layer group,
    K x smaller bubble) computes exactly the same step as sequential
    full-batch execution."""
    pipeline_vs_sequential(mesh_shape, axes, n_layer=12, stages=4,
                           microbatches=4, seed=13, schedule="interleaved",
                           what="interleaved")


def test_interleaved_needs_enough_microbatches():
    from paddle_tpu.parallel.pipeline_program import (
        build_pipeline_step_fn)

    main, _, _ = build_lm(batch=2, n_layer=8)
    plan = plan_pipeline(main, num_stages=4)
    mesh = make_mesh([4], ("pp",), devices=jax.devices()[:4])
    with pytest.raises(PipelineError, match="num_microbatches >="):
        build_pipeline_step_fn(main, (), [], [], mesh, plan,
                               num_microbatches=2, schedule="interleaved")
    with pytest.raises(PipelineError, match="unknown pipeline schedule"):
        build_pipeline_step_fn(main, (), [], [], mesh, plan,
                               num_microbatches=4, schedule="1f1b")


def test_pipeline_amp_and_dropout_run():
    """Mixed precision and dropout both work through the pipelined step:
    bf16 carries hop stages, per-(microbatch, repeat) RNG keys draw
    inside the tick loop. (Numeric parity with sequential execution is
    not defined under dropout — different draw order — so this checks
    training behavior: finite loss, params move.)"""
    main, startup = Program(), Program()
    main.random_seed = startup.random_seed = 9
    with fluid.unique_name.guard(), program_guard(main, startup):
        ids = fluid.layers.data(name="ids", shape=[2, T], dtype="int64",
                                append_batch_size=False)
        lbl = fluid.layers.data(name="lbl", shape=[2, T], dtype="int64",
                                append_batch_size=False)
        loss, _ = transformer_lm(
            ids, lbl, VOCAB, n_layer=4, n_head=N_HEAD, d_model=D_MODEL,
            d_inner=D_INNER, dropout_rate=0.1, max_len=T, fused_head=False)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    main.enable_mixed_precision()

    scope = fluid.core.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    p0 = {p.name: np.asarray(scope.find_var(p.name))
          for p in main.all_parameters()}
    mesh = make_mesh([4], ("pp",), devices=jax.devices()[:4])
    bs = BuildStrategy()
    bs.pipeline_stages = 4
    bs.pipeline_microbatches = 2
    pe = ParallelExecutor(loss_name=loss.name, main_program=main,
                          build_strategy=bs, scope=scope, mesh=mesh)
    rs = np.random.RandomState(21)
    xs = rs.randint(0, VOCAB, (4, T)).astype(np.int64)
    ys = rs.randint(0, VOCAB, (4, T)).astype(np.int64)
    l0, = pe.run(feed={"ids": xs, "lbl": ys}, fetch_list=[loss])
    l1, = pe.run(feed={"ids": xs, "lbl": ys}, fetch_list=[loss])
    assert np.isfinite(float(np.squeeze(l0)))
    assert np.isfinite(float(np.squeeze(l1)))
    moved = sum(float(np.abs(np.asarray(scope.find_var(k)) - p0[k]).sum())
                for k in p0)
    assert moved > 0.0


def test_pipeline_composes_with_tensor_parallel():
    """pp x mp: the tick loop is manual over (dp?, pp) while the Megatron
    mp axis stays automatic — GSPMD shards the template matmuls over mp
    inside the manual region. Loss + updated params must still match
    sequential full-batch execution."""
    pipeline_vs_sequential(
        (2, 2), ("pp", "mp"), n_layer=4, stages=2, microbatches=2, seed=17,
        plan=lambda mesh: megatron_transformer_plan(mesh, mp_axis="mp",
                                                    batch_axes=()),
        what="pp x mp")


def test_pipeline_run_loop_matches_stepwise():
    """ParallelExecutor.run_loop composes with pipeline parallelism: the
    whole pp tick loop becomes the while-loop body. 2 loop steps == 2
    stepwise run() calls."""
    n_layer, M, B_mb, lr = 4, 2, 2, 0.1
    B = M * B_mb
    rs = np.random.RandomState(5)
    xs = rs.randint(0, VOCAB, (B, T)).astype(np.int64)
    ys = rs.randint(0, VOCAB, (B, T)).astype(np.int64)

    def train(mode):
        main, startup, loss = build_lm(batch=B_mb, n_layer=n_layer, lr=lr)
        scope = fluid.core.Scope()
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(scope):
            exe.run(startup)
        mesh = make_mesh([2], ("pp",), devices=jax.devices()[:2])
        bs = BuildStrategy()
        bs.pipeline_stages = 2
        bs.pipeline_microbatches = M
        pe = ParallelExecutor(loss_name=loss.name, main_program=main,
                              build_strategy=bs, scope=scope, mesh=mesh)
        if mode == "step":
            for _ in range(2):
                lv, = pe.run(feed={"ids": xs, "lbl": ys}, fetch_list=[loss])
        else:
            lv, = pe.run_loop(fetch_list=[loss],
                              feed={"ids": xs, "lbl": ys}, steps=2)
        params = {k: np.asarray(scope.find_var(k))
                  for k in param_names(main)}
        return float(np.squeeze(lv)), params

    lv_s, p_s = train("step")
    lv_l, p_l = train("loop")
    np.testing.assert_allclose(lv_l, lv_s, rtol=2e-5)
    for k in sorted(p_s):
        np.testing.assert_allclose(p_l[k], p_s[k], rtol=2e-4, atol=2e-6,
                                   err_msg=k)


def test_pipeline_composes_dp_pp_mp():
    """the full 3-axis hybrid — manual tick loop over
    (dp, pp) with the Megatron mp axis left automatic for GSPMD — in ONE
    [2,2,2] mesh. Loss + updated params must match sequential full-batch
    execution, proving the 'hybrid mesh' story end to end."""
    p_pp, p0 = pipeline_vs_sequential(
        (2, 2, 2), ("dp", "pp", "mp"), n_layer=4, stages=2, microbatches=2,
        seed=23,
        plan=lambda mesh: megatron_transformer_plan(mesh, mp_axis="mp",
                                                    batch_axes=("dp",)),
        what="dp x pp x mp")
    moved = sum(float(np.abs(p_pp[k] - p0[k]).sum()) for k in p0)
    assert moved > 0.0
