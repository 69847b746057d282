"""Program-level pipeline parallelism: the SAME fluid
Program that trains dp/tp runs pipelined — no hand-written stage_fn.
plan_pipeline's stage cut is exercised on the flagship transformer LM and
a dp×pp training step checks loss + updated-parameter parity against
single-device sequential execution of an identically-parameterized
full-batch program, on the 8-virtual-device CPU mesh. The interleaved
schedule and what the pipeline composes with (AMP, dropout, tensor
parallelism, `run_loop`, dp x pp x mp) are beside this file, in
`test_pipeline_program_compose.py`; `pipeline_lm.py` holds the LM and
the comparison both use."""
from __future__ import annotations

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu.framework.core import Program, program_guard
from paddle_tpu.parallel import make_mesh
from paddle_tpu.parallel.parallel_executor import (BuildStrategy,
                                                   ParallelExecutor)
from paddle_tpu.parallel.pipeline_program import (PipelineError,
                                                  plan_pipeline)

from pipeline_lm import (D_INNER, D_MODEL, N_HEAD, T, VOCAB, build_lm,
                         pipeline_vs_sequential)


def test_plan_detects_transformer_layers():
    main, _, _ = build_lm(batch=2, n_layer=4)
    plan = plan_pipeline(main, num_stages=4)
    assert plan.repeats == 4 and plan.repeats_per_stage == 1
    # carry is the (B, T, D) hidden state
    from paddle_tpu.parallel.pipeline_program import _var_shape
    assert _var_shape(plan.block, plan.carry_tpl_in) == (2, T, D_MODEL)
    # every repeat owns its own parameter set, mapped onto the template
    names = set(plan.param_map[0].values())
    for m in plan.param_map[1:]:
        assert set(m.values()).isdisjoint(names) or set(m.values()) == names
    assert "pipeline plan" in plan.describe()


def test_plan_groups_repeats_into_stages():
    main, _, _ = build_lm(batch=2, n_layer=6)
    plan = plan_pipeline(main, num_stages=2)
    assert plan.repeats == 6 and plan.repeats_per_stage == 3


def test_plan_rejects_unrepeated_program():
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4, 8],
                              append_batch_size=False)
        h = fluid.layers.fc(x, 16, act="relu")
        y = fluid.layers.fc(h, 3)
        loss = fluid.layers.mean(y)
        fluid.optimizer.SGD(0.1).minimize(loss)
    with pytest.raises(PipelineError):
        plan_pipeline(main, num_stages=2)


def test_plan_rejects_too_many_stages():
    main, _, _ = build_lm(batch=2, n_layer=4)
    with pytest.raises(PipelineError, match="reduce pipeline_stages"):
        plan_pipeline(main, num_stages=8)


@pytest.mark.parametrize("mesh_shape,axes", [
    ((4,), ("pp",)),
    ((2, 4), ("dp", "pp")),
])
def test_transformer_pipeline_parity(mesh_shape, axes):
    """12 layers / 4 stages / microbatched: loss and updated params match
    sequential full-batch execution. The
    Program declares the PER-DEVICE microbatch; feeds carry
    M x dp x that in dim 0."""
    p_pp, p0 = pipeline_vs_sequential(mesh_shape, axes, n_layer=12,
                                      stages=4, microbatches=4, seed=3)
    # and the pp step actually trained (params moved)
    moved = sum(float(np.abs(p_pp[k] - p0[k]).sum()) for k in p0)
    assert moved > 0.0


def test_pipeline_carry_fed_directly():
    """No prologue: the first repeated layer consumes the feed itself, so
    the pipeline carry IS the feed (code-review regression)."""
    def build(batch):
        main, startup = Program(), Program()
        main.random_seed = startup.random_seed = 5
        with fluid.unique_name.guard(), program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[batch, 8],
                                  append_batch_size=False)
            h = x
            for _ in range(4):
                h = fluid.layers.fc(h, 8, act="tanh", num_flatten_dims=1)
            loss = fluid.layers.mean(h)
            fluid.optimizer.SGD(0.1).minimize(loss)
        return main, startup, loss

    M, B_mb = 2, 2
    main, startup, loss = build(B_mb)
    plan = plan_pipeline(main, 2)
    assert not plan.prologue and plan.carry_in_names[0] == "x"

    xs = np.random.RandomState(11).randn(M * B_mb, 8).astype(np.float32)
    scope = fluid.core.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    p0 = {p.name: np.asarray(scope.find_var(p.name))
          for p in main.all_parameters()}
    mesh = make_mesh([2], ("pp",), devices=jax.devices()[:2])
    bs = BuildStrategy()
    bs.pipeline_stages = 2
    bs.pipeline_microbatches = M
    pe = ParallelExecutor(loss_name=loss.name, main_program=main,
                          build_strategy=bs, scope=scope, mesh=mesh)
    lv_pp, = pe.run(feed={"x": xs}, fetch_list=[loss])

    fmain, fstartup, floss = build(M * B_mb)
    fscope = fluid.core.Scope()
    with fluid.scope_guard(fscope):
        exe.run(fstartup)
        for k, v in p0.items():
            fscope.set_var(k, v)
        lv_ref, = exe.run(fmain, feed={"x": xs}, fetch_list=[floss])
    np.testing.assert_allclose(float(np.squeeze(lv_pp)),
                               float(np.squeeze(lv_ref)), rtol=1e-5)


def test_pipeline_transpiler_api():
    from paddle_tpu.transpiler import PipelineTranspiler

    main, _, _ = build_lm(batch=2, n_layer=4)
    t = PipelineTranspiler(num_stages=2, num_microbatches=4)
    plan = t.transpile(main)
    assert plan.repeats == 4
    bs = t.build_strategy()
    assert bs.pipeline_stages == 2 and bs.pipeline_microbatches == 4


def test_plan_rejects_batch_dependent_side_inputs():
    """Encoder layers read the per-batch lengths feed -> the planner must
    name the offending variable and suggest the restructure."""
    from paddle_tpu.models.transformer import transformer_encoder

    main, startup = Program(), Program()
    with fluid.unique_name.guard(), program_guard(main, startup):
        src = fluid.layers.data(name="src", shape=[2, T], dtype="int64",
                                append_batch_size=False)
        lens = fluid.layers.data(name="lens", shape=[2], dtype="int32",
                                 append_batch_size=False)
        enc = transformer_encoder(src, lens, VOCAB, n_layer=4,
                                  n_head=N_HEAD, d_model=D_MODEL,
                                  d_inner=D_INNER, dropout_rate=0.0,
                                  max_len=T)
        loss = fluid.layers.mean(enc)
        fluid.optimizer.SGD(0.1).minimize(loss)
    with pytest.raises(PipelineError, match="batch-dependent side input"):
        plan_pipeline(main, num_stages=2)


def test_plan_alignment_survives_ambiguous_prologue():
    """At microbatch 1 the embed's tok+pos add fingerprints identically
    to the layers' residual adds, so the periodic-run start lands one op
    early; the planner must retry intra-period shifts until the carry
    validates (stress-found regression)."""
    main, _, _ = build_lm(batch=1, n_layer=6)
    plan = plan_pipeline(main, num_stages=3)
    assert plan.repeats == 6 and plan.repeats_per_stage == 2
    from paddle_tpu.parallel.pipeline_program import _var_shape
    assert _var_shape(plan.block, plan.carry_tpl_in) == (1, T, D_MODEL)
