"""PADDLE_TPU_MUL_DWT (sweep lever): transposed-form dW backward for the
`mul` op is a pure schedule change — same forward, same gradients
(kernel: paddle_tpu/ops/math.py _mm2d_dwt; motivation: the FFN-hidden
relayout copies on the LM step's profile)."""
import numpy as np

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.ops.math import _mm2d, _mm2d_dwt


def test_mm2d_dwt_matches_standard_fwd_and_grad():
    r = np.random.RandomState(0)
    for dt in (jnp.float32, jnp.bfloat16):
        x = jnp.asarray(r.randn(24, 16), dt)
        w = jnp.asarray(r.randn(16, 32) * 0.1, dt)

        np.testing.assert_array_equal(
            np.asarray(_mm2d_dwt(x, w), np.float32),
            np.asarray(_mm2d(x, w), np.float32))

        def f_std(x, w):
            return jnp.sum(jnp.sin(_mm2d(x, w).astype(jnp.float32)))

        def f_dwt(x, w):
            return jnp.sum(jnp.sin(_mm2d_dwt(x, w).astype(jnp.float32)))

        gs = jax.grad(f_std, argnums=(0, 1))(x, w)
        gd = jax.grad(f_dwt, argnums=(0, 1))(x, w)
        tol = 1e-6 if dt == jnp.float32 else 3e-2
        for a, e in zip(gd, gs):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(e, np.float32),
                                       rtol=tol, atol=tol)


def test_mul_dwt_program_trajectory_parity(monkeypatch):
    """A small fc MLP trained with the lever ON matches OFF step for
    step (the reduction order of each dW is transposed, so allclose,
    not bit-equal)."""
    r = np.random.RandomState(1)
    feed = {"x": r.randn(8, 12).astype(np.float32),
            "y": r.randn(8, 1).astype(np.float32)}

    def run(flag):
        monkeypatch.setenv("PADDLE_TPU_MUL_DWT", flag)
        main, start = fluid.Program(), fluid.Program()
        main.random_seed = start.random_seed = 3
        scope = fluid.Scope()
        with fluid.scope_guard(scope), fluid.program_guard(main, start):
            with fluid.unique_name.guard():
                x = layers.data(name="x", shape=[8, 12], dtype="float32",
                                append_batch_size=False)
                y = layers.data(name="y", shape=[8, 1], dtype="float32",
                                append_batch_size=False)
                h = layers.fc(x, 16, act="relu")
                pred = layers.fc(h, 1)
                loss = layers.mean(layers.square_error_cost(pred, y))
                fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(start)
            return [float(exe.run(main, feed=feed, fetch_list=[loss])[0])
                    for _ in range(5)]

    off, on = run("0"), run("1")
    np.testing.assert_allclose(on, off, rtol=1e-5, atol=1e-7)
    assert off[-1] < off[0]


def test_mul_dwt_shard_map_pipeline_parity(monkeypatch):
    """The lever must hold under shard_map parallelism (the pipeline
    executor runs every op inside one shard_map over the dp x pp mesh):
    the bwd's transposed dW is dp-varying while the weight is
    replicated, so the cotangent needs the _grad_vma_like psum —
    without it this trace fails with 'mismatched varying manual axes'
    (code-review regression). Lever on == off, loss and params."""
    import jax

    from paddle_tpu.models.transformer import transformer_lm
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.parallel.parallel_executor import (BuildStrategy,
                                                       ParallelExecutor)

    VOCAB, T, B_mb, M = 64, 16, 2, 2
    rs = np.random.RandomState(4)
    xs = rs.randint(0, VOCAB, (M * 2 * B_mb, T)).astype(np.int64)
    ys = rs.randint(0, VOCAB, (M * 2 * B_mb, T)).astype(np.int64)

    def run(flag):
        monkeypatch.setenv("PADDLE_TPU_MUL_DWT", flag)
        main, start = fluid.Program(), fluid.Program()
        main.random_seed = start.random_seed = 7
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            with fluid.unique_name.guard(), \
                    fluid.program_guard(main, start):
                ids = layers.data(name="ids", shape=[B_mb, T],
                                  dtype="int64", append_batch_size=False)
                lbl = layers.data(name="lbl", shape=[B_mb, T],
                                  dtype="int64", append_batch_size=False)
                loss, _ = transformer_lm(
                    ids, lbl, VOCAB, n_layer=4, n_head=2, d_model=32,
                    d_inner=64, dropout_rate=0.0, max_len=T,
                    fused_head=False)
                fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
            fluid.Executor(fluid.CPUPlace()).run(start)
            mesh = make_mesh([2, 2], ("dp", "pp"),
                             devices=jax.devices()[:4])
            bs = BuildStrategy()
            bs.pipeline_stages = 2
            bs.pipeline_microbatches = M
            pe = ParallelExecutor(loss_name=loss.name, main_program=main,
                                  build_strategy=bs, scope=scope,
                                  mesh=mesh)
            lv, = pe.run(feed={"ids": xs, "lbl": ys}, fetch_list=[loss])
            params = {p.name: np.asarray(scope.find_var(p.name))
                      for p in main.all_parameters()}
        return float(np.squeeze(lv)), params

    loss_off, p_off = run("0")
    loss_on, p_on = run("1")
    np.testing.assert_allclose(loss_on, loss_off, rtol=1e-5)
    for k in sorted(p_off):
        np.testing.assert_allclose(p_on[k], p_off[k], rtol=1e-4,
                                   atol=1e-6, err_msg=k)
