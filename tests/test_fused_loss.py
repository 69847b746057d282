"""fused_lm_head_loss: chunked logsumexp head == naive fc + softmax-xent,
forward and gradients (kernel: paddle_tpu/ops/fused_loss.py)."""
import numpy as np

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.ops.fused_loss import lm_head_loss


def _naive(x, w, b, labels):
    logits = x @ w + b
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1)


def test_lm_head_loss_matches_naive_fwd_and_grad():
    r = np.random.RandomState(0)
    n, d, v = 12, 16, 100  # v not a multiple of block_v: exercises padding
    x = jnp.asarray(r.randn(n, d), jnp.float32)
    w = jnp.asarray(r.randn(d, v) * 0.1, jnp.float32)
    b = jnp.asarray(r.randn(v) * 0.1, jnp.float32)
    labels = jnp.asarray(r.randint(0, v, (n,)), jnp.int32)

    out = lm_head_loss(32, x, w, b, labels)
    ref = _naive(x, w, b, labels)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)

    def f_fused(x, w, b):
        return jnp.mean(lm_head_loss(32, x, w, b, labels))

    def f_naive(x, w, b):
        return jnp.mean(_naive(x, w, b, labels))

    gf = jax.grad(f_fused, argnums=(0, 1, 2))(x, w, b)
    gn = jax.grad(f_naive, argnums=(0, 1, 2))(x, w, b)
    for a, e in zip(gf, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=1e-4, atol=1e-5)


def test_lm_head_loss_transpose_w_matches_naive():
    """transpose_w=True reads a (V, D) table in place: same loss and
    grads as the naive x @ w^T head (the tied-embedding layout)."""
    r = np.random.RandomState(3)
    n, d, v = 12, 16, 100  # v not a multiple of block_v: exercises padding
    x = jnp.asarray(r.randn(n, d), jnp.float32)
    wt = jnp.asarray(r.randn(v, d) * 0.1, jnp.float32)  # (V, D) table
    b = jnp.asarray(r.randn(v) * 0.1, jnp.float32)
    labels = jnp.asarray(r.randint(0, v, (n,)), jnp.int32)

    out = lm_head_loss(32, x, wt, b, labels, transpose_w=True)
    ref = _naive(x, wt.T, b, labels)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)

    def f_fused(x, wt, b):
        return jnp.mean(lm_head_loss(32, x, wt, b, labels,
                                     transpose_w=True))

    def f_naive(x, wt, b):
        return jnp.mean(_naive(x, wt.T, b, labels))

    gf = jax.grad(f_fused, argnums=(0, 1, 2))(x, wt, b)
    gn = jax.grad(f_naive, argnums=(0, 1, 2))(x, wt, b)
    for a, e in zip(gf, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=1e-4, atol=1e-5)


def test_lm_head_loss_shared_table_sums_both_grad_paths():
    """When the same (V, D) table feeds an embedding lookup AND the head
    (weight tying), d(table) is the sum of both contributions."""
    r = np.random.RandomState(4)
    n, d, v = 8, 12, 64
    ids = jnp.asarray(r.randint(0, v, (n,)), jnp.int32)
    table = jnp.asarray(r.randn(v, d) * 0.1, jnp.float32)
    b = jnp.zeros((v,), jnp.float32)
    labels = jnp.asarray(r.randint(0, v, (n,)), jnp.int32)

    def f_fused(table):
        x = table[ids]
        return jnp.mean(lm_head_loss(16, x, table, b, labels,
                                     transpose_w=True))

    def f_naive(table):
        x = table[ids]
        return jnp.mean(_naive(x, table.T, b, labels))

    np.testing.assert_allclose(float(f_fused(table)), float(f_naive(table)),
                               rtol=1e-5, atol=1e-6)
    gf = jax.grad(f_fused)(table)
    gn = jax.grad(f_naive)(table)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gn),
                               rtol=1e-4, atol=1e-5)


def test_transformer_lm_tied_fused_matches_unfused_and_shares():
    """tie_embeddings=True: fused and unfused heads give the same SGD
    trajectory, no separate head weight exists, and training moves."""
    from paddle_tpu import models, optimizer

    r = np.random.RandomState(5)
    feed = {
        "ids": r.randint(0, 64, (2, 16)).astype(np.int64),
        "labels": r.randint(0, 64, (2, 16)).astype(np.int64),
    }
    traj = {}
    for fused in (True, False):
        main, start = fluid.Program(), fluid.Program()
        main.random_seed = start.random_seed = 7
        scope = fluid.Scope()
        with fluid.scope_guard(scope), fluid.program_guard(main, start):
            with fluid.unique_name.guard():
                ids = layers.data(name="ids", shape=[2, 16], dtype="int64",
                                  append_batch_size=False)
                labels = layers.data(name="labels", shape=[2, 16],
                                     dtype="int64", append_batch_size=False)
                loss, _ = models.transformer.transformer_lm(
                    ids, labels, 64, n_layer=1, n_head=2, d_model=16,
                    d_inner=32, max_len=16, fused_head=fused,
                    tie_embeddings=True)
                optimizer.SGD(learning_rate=0.5).minimize(loss)
            assert "lm.head.w" not in main.global_block().vars
            assert "lm.tok_emb" in main.global_block().vars
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(start)
            traj[fused] = [
                float(exe.run(main, feed=feed, fetch_list=[loss])[0])
                for _ in range(4)
            ]
    np.testing.assert_allclose(traj[True], traj[False], rtol=1e-4, atol=1e-5)
    assert traj[True][-1] < traj[True][0]  # tied grads flow; training moves


def test_fused_head_rejects_reused_param_with_wrong_layout():
    """Naming an existing (V, D) table without transpose_w=True must be
    a clear ValueError, not garbage logits (create_parameter reuses by
    name, ignoring the requested shape)."""
    import pytest
    from paddle_tpu.param_attr import ParamAttr

    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start):
        with fluid.unique_name.guard():
            ids = layers.data(name="ids", shape=[2, 8], dtype="int64",
                              append_batch_size=False)
            labels = layers.data(name="labels", shape=[2, 8],
                                 dtype="int64", append_batch_size=False)
            emb = layers.embedding(input=ids, size=[64, 16],
                                   param_attr=ParamAttr(name="table"))
            with pytest.raises(ValueError, match="transpose_w"):
                layers.fused_lm_head_loss(
                    emb, labels, 64, param_attr=ParamAttr(name="table"))


def test_transformer_lm_fused_head_matches_unfused():
    """Same params/seed: fused and unfused heads give the same loss and
    the same loss trajectory under Adam."""
    from paddle_tpu import models, optimizer

    r = np.random.RandomState(1)
    feed = {
        "ids": r.randint(0, 64, (2, 16)).astype(np.int64),
        "labels": r.randint(0, 64, (2, 16)).astype(np.int64),
    }
    traj = {}
    for fused in (True, False):
        main, start = fluid.Program(), fluid.Program()
        main.random_seed = start.random_seed = 7
        scope = fluid.Scope()
        with fluid.scope_guard(scope), fluid.program_guard(main, start):
            with fluid.unique_name.guard():
                ids = layers.data(name="ids", shape=[2, 16], dtype="int64",
                                  append_batch_size=False)
                labels = layers.data(name="labels", shape=[2, 16],
                                     dtype="int64", append_batch_size=False)
                loss, _ = models.transformer.transformer_lm(
                    ids, labels, 64, n_layer=1, n_head=2, d_model=16,
                    d_inner=32, max_len=16, fused_head=fused)
                # unfused head param names differ (lm.head.w vs fc w) but
                # both draw from the same seeded initializer stream
                optimizer.SGD(learning_rate=0.5).minimize(loss)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(start)
            traj[fused] = [
                float(exe.run(main, feed=feed, fetch_list=[loss])[0])
                for _ in range(4)
            ]
    np.testing.assert_allclose(traj[True], traj[False], rtol=1e-4, atol=1e-5)
