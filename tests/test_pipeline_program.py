"""Program-level pipeline parallelism: the SAME fluid
Program that trains dp/tp runs pipelined — no hand-written stage_fn.
plan_pipeline's stage cut is exercised on the flagship transformer LM and
a dp×pp training step checks loss + updated-parameter parity against
single-device sequential execution of an identically-parameterized
full-batch program, on the 8-virtual-device CPU mesh."""
from __future__ import annotations

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu.framework.core import Program, program_guard
from paddle_tpu.models.transformer import transformer_lm
from paddle_tpu.parallel import make_mesh
from paddle_tpu.parallel.parallel_executor import (BuildStrategy,
                                                   ParallelExecutor)
from paddle_tpu.parallel.pipeline_program import (PipelineError,
                                                  plan_pipeline)

VOCAB, D_MODEL, N_HEAD, D_INNER, T = 64, 32, 2, 64, 16


def _build_lm(batch, n_layer, seed=7, lr=0.1):
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


def test_plan_detects_transformer_layers():
    main, _, _ = _build_lm(batch=2, n_layer=4)
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
    main, _, _ = _build_lm(batch=2, n_layer=6)
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
    main, _, _ = _build_lm(batch=2, n_layer=4)
    with pytest.raises(PipelineError, match="reduce pipeline_stages"):
        plan_pipeline(main, num_stages=8)


def _run_sequential_reference(n_layer, xs, ys, p0, lr):
    """Single-device full-batch step on an identically-named program."""
    B = xs.shape[0]
    main, startup, loss = _build_lm(batch=B, n_layer=n_layer, lr=lr)
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


def _param_names(program):
    return [p.name for p in program.all_parameters()]


@pytest.mark.parametrize("mesh_shape,axes", [
    ((4,), ("pp",)),
    ((2, 4), ("dp", "pp")),
])
def test_transformer_pipeline_parity(mesh_shape, axes):
    """12 layers / 4 stages / microbatched: loss and updated params match
    sequential full-batch execution. The
    Program declares the PER-DEVICE microbatch; feeds carry
    M x dp x that in dim 0."""
    n_layer, M, B_mb, lr = 12, 4, 2, 0.1
    dp = dict(zip(axes, mesh_shape)).get("dp", 1)
    B = M * dp * B_mb
    rs = np.random.RandomState(3)
    xs = rs.randint(0, VOCAB, (B, T)).astype(np.int64)
    ys = rs.randint(0, VOCAB, (B, T)).astype(np.int64)

    main, startup, loss = _build_lm(batch=B_mb, n_layer=n_layer, lr=lr)
    scope = fluid.core.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    p0 = {k: np.asarray(scope.find_var(k)) for k in _param_names(main)}

    mesh = make_mesh(list(mesh_shape), axes,
                     devices=jax.devices()[:int(np.prod(mesh_shape))])
    bs = BuildStrategy()
    bs.pipeline_stages = 4
    bs.pipeline_microbatches = M
    pe = ParallelExecutor(loss_name=loss.name, main_program=main,
                          build_strategy=bs, scope=scope, mesh=mesh)
    lv_pp, = pe.run(feed={"ids": xs, "lbl": ys}, fetch_list=[loss])
    p_pp = {k: np.asarray(scope.find_var(k)) for k in p0}

    lv_ref, p_ref = _run_sequential_reference(n_layer, xs, ys, p0, lr)
    np.testing.assert_allclose(float(np.squeeze(lv_pp)), lv_ref,
                               rtol=2e-4)
    for k in sorted(p0):
        np.testing.assert_allclose(
            p_pp[k], p_ref[k], rtol=2e-3, atol=2e-5,
            err_msg="param %s diverged between pp and sequential" % k)
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


@pytest.mark.parametrize("mesh_shape,axes", [
    ((4,), ("pp",)),
    ((2, 4), ("dp", "pp")),
])
def test_interleaved_schedule_parity(mesh_shape, axes):
    """The circular schedule (each device holds every S-th layer group,
    K x smaller bubble) computes exactly the same step as sequential
    full-batch execution."""
    n_layer, M, B_mb, lr = 12, 4, 2, 0.1
    dp = dict(zip(axes, mesh_shape)).get("dp", 1)
    B = M * dp * B_mb
    rs = np.random.RandomState(13)
    xs = rs.randint(0, VOCAB, (B, T)).astype(np.int64)
    ys = rs.randint(0, VOCAB, (B, T)).astype(np.int64)

    main, startup, loss = _build_lm(batch=B_mb, n_layer=n_layer, lr=lr)
    scope = fluid.core.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    p0 = {k: np.asarray(scope.find_var(k)) for k in _param_names(main)}

    mesh = make_mesh(list(mesh_shape), axes,
                     devices=jax.devices()[:int(np.prod(mesh_shape))])
    bs = BuildStrategy()
    bs.pipeline_stages = 4
    bs.pipeline_microbatches = M
    bs.pipeline_schedule = "interleaved"
    pe = ParallelExecutor(loss_name=loss.name, main_program=main,
                          build_strategy=bs, scope=scope, mesh=mesh)
    lv_pp, = pe.run(feed={"ids": xs, "lbl": ys}, fetch_list=[loss])
    p_pp = {k: np.asarray(scope.find_var(k)) for k in p0}

    lv_ref, p_ref = _run_sequential_reference(n_layer, xs, ys, p0, lr)
    np.testing.assert_allclose(float(np.squeeze(lv_pp)), lv_ref,
                               rtol=2e-4)
    for k in sorted(p0):
        np.testing.assert_allclose(
            p_pp[k], p_ref[k], rtol=2e-3, atol=2e-5,
            err_msg="param %s diverged (interleaved vs sequential)" % k)


def test_interleaved_needs_enough_microbatches():
    from paddle_tpu.parallel.pipeline_program import (
        build_pipeline_step_fn)

    main, _, _ = _build_lm(batch=2, n_layer=8)
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


def test_pipeline_transpiler_api():
    from paddle_tpu.transpiler import PipelineTranspiler

    main, _, _ = _build_lm(batch=2, n_layer=4)
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


def test_pipeline_composes_with_tensor_parallel():
    """pp x mp: the tick loop is manual over (dp?, pp) while the Megatron
    mp axis stays automatic — GSPMD shards the template matmuls over mp
    inside the manual region. Loss + updated params must still match
    sequential full-batch execution."""
    from paddle_tpu.parallel import megatron_transformer_plan

    n_layer, M, B_mb, lr = 4, 2, 2, 0.1
    B = M * B_mb
    rs = np.random.RandomState(17)
    xs = rs.randint(0, VOCAB, (B, T)).astype(np.int64)
    ys = rs.randint(0, VOCAB, (B, T)).astype(np.int64)

    main, startup, loss = _build_lm(batch=B_mb, n_layer=n_layer, lr=lr)
    scope = fluid.core.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    p0 = {k: np.asarray(scope.find_var(k)) for k in _param_names(main)}

    mesh = make_mesh([2, 2], ("pp", "mp"), devices=jax.devices()[:4])
    bs = BuildStrategy()
    bs.pipeline_stages = 2
    bs.pipeline_microbatches = M
    plan = megatron_transformer_plan(mesh, mp_axis="mp", batch_axes=())
    pe = ParallelExecutor(loss_name=loss.name, main_program=main,
                          build_strategy=bs, scope=scope, mesh=mesh,
                          plan=plan)
    lv_pp, = pe.run(feed={"ids": xs, "lbl": ys}, fetch_list=[loss])
    p_pp = {k: np.asarray(scope.find_var(k)) for k in p0}

    lv_ref, p_ref = _run_sequential_reference(n_layer, xs, ys, p0, lr)
    np.testing.assert_allclose(float(np.squeeze(lv_pp)), lv_ref,
                               rtol=2e-4)
    for k in sorted(p0):
        np.testing.assert_allclose(
            p_pp[k], p_ref[k], rtol=2e-3, atol=2e-5,
            err_msg="param %s diverged (pp x mp vs sequential)" % k)


def test_plan_alignment_survives_ambiguous_prologue():
    """At microbatch 1 the embed's tok+pos add fingerprints identically
    to the layers' residual adds, so the periodic-run start lands one op
    early; the planner must retry intra-period shifts until the carry
    validates (stress-found regression)."""
    main, _, _ = _build_lm(batch=1, n_layer=6)
    plan = plan_pipeline(main, num_stages=3)
    assert plan.repeats == 6 and plan.repeats_per_stage == 2
    from paddle_tpu.parallel.pipeline_program import _var_shape
    assert _var_shape(plan.block, plan.carry_tpl_in) == (1, T, D_MODEL)


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
        main, startup, loss = _build_lm(batch=B_mb, n_layer=n_layer, lr=lr)
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
                  for k in _param_names(main)}
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
    from paddle_tpu.parallel import megatron_transformer_plan

    n_layer, M, B_mb, lr = 4, 2, 2, 0.1
    dp = 2
    B = M * dp * B_mb
    rs = np.random.RandomState(23)
    xs = rs.randint(0, VOCAB, (B, T)).astype(np.int64)
    ys = rs.randint(0, VOCAB, (B, T)).astype(np.int64)

    main, startup, loss = _build_lm(batch=B_mb, n_layer=n_layer, lr=lr)
    scope = fluid.core.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    p0 = {k: np.asarray(scope.find_var(k)) for k in _param_names(main)}

    mesh = make_mesh([2, 2, 2], ("dp", "pp", "mp"),
                     devices=jax.devices()[:8])
    bs = BuildStrategy()
    bs.pipeline_stages = 2
    bs.pipeline_microbatches = M
    plan = megatron_transformer_plan(mesh, mp_axis="mp",
                                     batch_axes=("dp",))
    pe = ParallelExecutor(loss_name=loss.name, main_program=main,
                          build_strategy=bs, scope=scope, mesh=mesh,
                          plan=plan)
    lv_pp, = pe.run(feed={"ids": xs, "lbl": ys}, fetch_list=[loss])
    p_pp = {k: np.asarray(scope.find_var(k)) for k in p0}

    lv_ref, p_ref = _run_sequential_reference(n_layer, xs, ys, p0, lr)
    np.testing.assert_allclose(float(np.squeeze(lv_pp)), lv_ref,
                               rtol=2e-4)
    for k in sorted(p0):
        np.testing.assert_allclose(
            p_pp[k], p_ref[k], rtol=2e-3, atol=2e-5,
            err_msg="param %s diverged (dp x pp x mp vs sequential)" % k)
    moved = sum(float(np.abs(p_pp[k] - p0[k]).sum()) for k in p0)
    assert moved > 0.0
