"""Flagship long-context LM: transformer_lm(use_ring_attention=True) on a
sequence-parallel mesh matches the single-device model exactly (same seed),
and trains. SURVEY §2 models commitment."""
from __future__ import annotations

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu import layers, models, optimizer
from paddle_tpu.parallel import ParallelExecutor, make_mesh, seq_parallel_plan


def _build(use_ring, seed=13, batch=2, seq=32, vocab=64, dropout_rate=0.0):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    scope = fluid.Scope()
    with fluid.scope_guard(scope), fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            ids = layers.data(name="ids", shape=[batch, seq], dtype="int64",
                              append_batch_size=False)
            labels = layers.data(name="labels", shape=[batch, seq],
                                 dtype="int64", append_batch_size=False)
            loss, _ = models.transformer.transformer_lm(
                ids, labels, vocab_size=vocab, n_layer=2, n_head=2,
                d_model=16, d_inner=32, max_len=seq,
                use_ring_attention=use_ring, dropout_rate=dropout_rate)
            optimizer.SGD(0.1).minimize(loss)
    return main, startup, scope, loss


def _feed(batch=2, seq=32, vocab=64, seed=0):
    r = np.random.RandomState(seed)
    return {"ids": r.randint(0, vocab, (batch, seq)).astype(np.int64),
            "labels": r.randint(0, vocab, (batch, seq)).astype(np.int64)}


def test_ring_lm_matches_single_device():
    feed = _feed()

    # single-device reference (ring op falls back to full attention)
    main, startup, scope, loss = _build(use_ring=True)
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        ref = [float(exe.run(main, feed=feed, fetch_list=[loss])[0])
               for _ in range(3)]

    # sp mesh: sequence sharded over 4 devices, ring attention active
    mesh = make_mesh([4], ("sp",), devices=jax.devices()[:4])
    main, startup, scope, loss = _build(use_ring=True)
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        pexe = ParallelExecutor(
            loss_name=loss.name, main_program=main, scope=scope, mesh=mesh,
            plan=seq_parallel_plan(mesh, sp_axis="sp", batch_axes=()))
        got = [float(pexe.run(feed=feed, fetch_list=[loss])[0])
               for _ in range(3)]

    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
    assert ref[2] < ref[0]  # it actually trains


def test_ring_lm_dp_x_sp():
    feed = _feed(batch=4)
    main, startup, scope, loss = _build(use_ring=True, batch=4)
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        ref = [float(exe.run(main, feed=feed, fetch_list=[loss])[0])
               for _ in range(2)]

    mesh = make_mesh([2, 4], ("dp", "sp"), devices=jax.devices()[:8])
    main, startup, scope, loss = _build(use_ring=True, batch=4)
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        pexe = ParallelExecutor(
            loss_name=loss.name, main_program=main, scope=scope, mesh=mesh,
            plan=seq_parallel_plan(mesh, sp_axis="sp", batch_axes=("dp",)))
        got = [float(pexe.run(feed=feed, fetch_list=[loss])[0])
               for _ in range(2)]
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)


def test_ring_lm_with_dropout_matches_single_device():
    """the flagship long-context path must train the
    SAME model as the single-device path even with attention dropout on.
    The ring op's dropout mask is a pure function of (seed, global q,
    global k) — independent of the sp shard count — and both executors
    derive identical per-op RNG streams from program.random_seed, so the
    losses must agree step for step."""
    feed = _feed()

    main, startup, scope, loss = _build(use_ring=True, dropout_rate=0.2)
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        ref = [float(exe.run(main, feed=feed, fetch_list=[loss])[0])
               for _ in range(3)]

    mesh = make_mesh([4], ("sp",), devices=jax.devices()[:4])
    main, startup, scope, loss = _build(use_ring=True, dropout_rate=0.2)
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        pexe = ParallelExecutor(
            loss_name=loss.name, main_program=main, scope=scope, mesh=mesh,
            plan=seq_parallel_plan(mesh, sp_axis="sp", batch_axes=()))
        got = [float(pexe.run(feed=feed, fetch_list=[loss])[0])
               for _ in range(3)]

    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
    assert ref[2] < ref[0]  # it actually trains under dropout


def test_ring_lm_clone_for_test_disables_attention_dropout():
    """clone(for_test=True) must flip is_test on ring_attention ops
    (code-review regression: the op was missing from _TRAIN_TEST_OPS):
    eval runs are deterministic while training draws fresh masks.
    Reference idiom: clone BEFORE minimize (framework.py clone docs)."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 13
    scope = fluid.Scope()
    with fluid.scope_guard(scope), fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            ids = layers.data(name="ids", shape=[2, 32], dtype="int64",
                              append_batch_size=False)
            labels = layers.data(name="labels", shape=[2, 32],
                                 dtype="int64", append_batch_size=False)
            loss, _ = models.transformer.transformer_lm(
                ids, labels, vocab_size=64, n_layer=2, n_head=2,
                d_model=16, d_inner=32, max_len=32,
                use_ring_attention=True, dropout_rate=0.5)
            test_prog = main.clone(for_test=True)
            optimizer.SGD(0.1).minimize(loss)
    ring_ops = [op for b in test_prog.blocks for op in b.ops
                if op.type == "ring_attention"]
    assert ring_ops and all(op.attr("is_test") for op in ring_ops)

    feed = _feed()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        e1 = float(exe.run(test_prog, feed=feed, fetch_list=[loss])[0])
        e2 = float(exe.run(test_prog, feed=feed, fetch_list=[loss])[0])
        assert e1 == e2  # no stochastic op left in the eval graph
        # training program DOES draw masks: same feed, different losses
        t1 = float(exe.run(main, feed=feed, fetch_list=[loss])[0])
        assert t1 != e1


def _run_sp(chunk, seed=3):
    """One seeded training step on the 4-device sp mesh with the ring
    ops' `chunk` attribute set: the value must reach the CHUNKED ring
    path (on a plain single-device Executor the ring op falls back to
    full_attention and the chunk is never consumed)."""
    main, startup, scope, loss = _build(use_ring=True, seed=seed)
    rings = [op for op in main.global_block().ops
             if op.type == "ring_attention"]
    assert rings
    for op in rings:
        op.set_attr("chunk", chunk)
    mesh = make_mesh([4], ("sp",), devices=jax.devices()[:4])
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        pexe = ParallelExecutor(
            loss_name=loss.name, main_program=main, scope=scope, mesh=mesh,
            plan=seq_parallel_plan(mesh, sp_axis="sp", batch_axes=()))
        return float(pexe.run(feed=_feed(), fetch_list=[loss])[0])


def test_ring_chunk_attribute():
    """The ring op's `chunk` on an sp mesh: None and 0 mean auto (not a
    crash), an explicit chunk is numerically invisible."""
    v0 = _run_sp(None)     # auto, as the layer leaves it
    assert np.isfinite(v0)
    assert _run_sp(0) == v0
    v8 = _run_sp(8)        # T_local for seq 32 over 4 devices
    np.testing.assert_allclose(v8, v0, rtol=1e-5)  # chunking is invisible
    v4 = _run_sp(4)        # genuine sub-chunking (2 per block)
    np.testing.assert_allclose(v4, v0, rtol=1e-5)
