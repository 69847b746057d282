"""Parallel execution tests on the 8-device virtual CPU mesh (conftest)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.parallel import (
    ParallelExecutor,
    ShardingPlan,
    all_gather,
    all_reduce,
    broadcast,
    default_mesh,
    full_attention,
    make_mesh,
    reduce_scatter,
    ring_self_attention,
)

try:
    shard_map = jax.shard_map
except AttributeError:
    from jax.experimental.shard_map import shard_map


def test_mesh_has_8_devices():
    assert jax.device_count() == 8
    mesh = default_mesh("dp")
    assert mesh.size == 8


def _smap(fn, mesh, in_specs, out_specs):
    return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


def _ring_run(q, k, v, mesh, *, grads, **attention_kwargs):
    """`ring_self_attention` and, where asked, the q/k/v gradients of
    `sum(sin(out))`, as ONE compiled program: the way the product runs
    the ring (its caller, `ops/attention.py`, sits inside a traced
    Program). Called eagerly the `shard_map` body runs primitive by
    primitive over the eight devices, forward and backward: minutes a
    case on the CPU for work no user's program does."""
    def loss(q, k, v):
        out = ring_self_attention(q, k, v, mesh, "sp", **attention_kwargs)
        return jnp.sum(jnp.sin(out)), out

    if not grads:
        return jax.jit(lambda q, k, v: loss(q, k, v)[1])(q, k, v), None
    (_, out), g = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    return out, g


def _ring_vs_full(q, k, v, mesh, *, grads, out_tol, grad_tol=None,
                  **attention_kwargs):
    """The ring (one compiled program) against `full_attention` (the
    plain reference, eager as it always was) on the same inputs: outputs
    within `out_tol`, and where `grads` the q/k/v gradients of
    `sum(sin(out))`, finite and within `grad_tol`. Returns both outputs
    for what else a case asserts."""
    out, gr = _ring_run(q, k, v, mesh, grads=grads, **attention_kwargs)
    ref = full_attention(q, k, v, **attention_kwargs)
    assert np.isfinite(np.asarray(ref)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **out_tol)
    if grads:
        gf = jax.grad(lambda q, k, v: jnp.sum(jnp.sin(full_attention(
            q, k, v, **attention_kwargs))), argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", gr, gf):
            assert np.isfinite(np.asarray(a)).all(), "d%s not finite" % name
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       err_msg="d%s diverged" % name,
                                       **grad_tol)
    return out, ref


def test_collectives():
    mesh = default_mesh("dp")
    x = np.arange(8, dtype=np.float32)

    out = _smap(lambda v: all_reduce(v, "dp"), mesh, (P("dp"),), P("dp"))(x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, x.sum()))

    out = _smap(lambda v: all_gather(v, "dp"), mesh, (P("dp"),), P(None))(x)
    np.testing.assert_allclose(np.asarray(out), x)

    # replicated input -> psum_scatter: device i gets 8 * (i-th chunk)
    big = np.arange(64, dtype=np.float32)
    out = _smap(lambda v: reduce_scatter(v, "dp"), mesh, (P(None),), P("dp"))(big)
    np.testing.assert_allclose(np.asarray(out), 8 * big)

    out = _smap(lambda v: broadcast(v, "dp", root=3), mesh, (P("dp"),), P("dp"))(x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, 3.0))


def test_parallel_executor_matches_single_device():
    """8-way dp training step == single-device step (same seed/feeds)."""
    rng = np.random.RandomState(0)
    xs = rng.randn(32, 16).astype(np.float32)
    ys = (rng.randn(32, 1) > 0).astype(np.int64)

    def build():
        x = layers.data(name="x", shape=[16])
        y = layers.data(name="y", shape=[1], dtype="int64")
        h = layers.fc(input=x, size=32, act="relu")
        logits = layers.fc(input=h, size=2)
        loss = fluid.layers.mean(
            layers.softmax_with_cross_entropy(logits, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        return loss

    # single device
    main_a, start_a = fluid.Program(), fluid.Program()
    main_a.random_seed = start_a.random_seed = 7
    scope_a = fluid.Scope()
    with fluid.scope_guard(scope_a), fluid.program_guard(main_a, start_a):
        with fluid.unique_name.guard():
            loss_a = build()
        exe = fluid.Executor()
        exe.run(start_a)
        single = [exe.run(main_a, feed={"x": xs, "y": ys},
                          fetch_list=[loss_a])[0] for _ in range(3)]

    # 8-way data parallel
    main_b, start_b = fluid.Program(), fluid.Program()
    main_b.random_seed = start_b.random_seed = 7
    scope_b = fluid.Scope()
    with fluid.scope_guard(scope_b), fluid.program_guard(main_b, start_b):
        with fluid.unique_name.guard():
            loss_b = build()
        fluid.Executor().run(start_b)
        pexe = ParallelExecutor(loss_name=loss_b.name, main_program=main_b,
                                scope=scope_b)
        par = [pexe.run(feed={"x": xs, "y": ys},
                        fetch_list=[loss_b])[0] for _ in range(3)]

    for a, b in zip(single, par):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)
    assert single[0] > single[-1]  # actually training


def test_parallel_executor_feed_list_of_dicts():
    x = layers.data(name="x", shape=[4])
    out = layers.reduce_sum(x)
    fluid.Executor().run(fluid.default_startup_program())
    pexe = ParallelExecutor(main_program=fluid.default_main_program())
    feeds = [{"x": np.full((1, 4), float(i), np.float32)} for i in range(8)]
    (val,) = pexe.run(feed=feeds, fetch_list=[out])
    assert float(val) == sum(4.0 * i for i in range(8))


def test_tensor_parallel_matmul_parity():
    """Column+row parallel matmul pair under pjit == dense computation."""
    mesh = make_mesh([1, 8], ("dp", "mp"))
    rng = np.random.RandomState(1)
    x = rng.randn(4, 32).astype(np.float32)
    w1 = rng.randn(32, 64).astype(np.float32)
    w2 = rng.randn(64, 16).astype(np.float32)

    def f(x, w1, w2):
        return jnp.maximum(x @ w1, 0) @ w2

    from jax.sharding import NamedSharding
    jf = jax.jit(
        f,
        in_shardings=(
            NamedSharding(mesh, P()),
            NamedSharding(mesh, P(None, "mp")),  # column parallel
            NamedSharding(mesh, P("mp", None)),  # row parallel
        ),
        out_shardings=NamedSharding(mesh, P()),
    )
    np.testing.assert_allclose(np.asarray(jf(x, w1, w2)), f(x, w1, w2),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    mesh = default_mesh("sp")
    rng = np.random.RandomState(2)
    B, H, T, D = 2, 4, 64, 16  # T sharded 8 ways -> 8 per device
    q, k, v = (jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
               for _ in range(3))
    _ring_vs_full(q, k, v, mesh, grads=False,
                  out_tol=dict(rtol=2e-5, atol=2e-5), causal=causal)


def test_zero_reduce_strategy_trains_and_shards_state():
    """BuildStrategy.Reduce -> optimizer accumulators sharded over dp."""
    from paddle_tpu.parallel import BuildStrategy

    x = layers.data(name="x", shape=[16])
    y = layers.data(name="y", shape=[1], dtype="int64")
    h = layers.fc(input=x, size=64, act="relu")
    loss = layers.mean(
        layers.softmax_with_cross_entropy(layers.fc(input=h, size=2), y))
    fluid.optimizer.Adam(learning_rate=1e-2).minimize(loss)
    fluid.Executor().run(fluid.default_startup_program())

    bs = BuildStrategy()
    bs.reduce_strategy = BuildStrategy.ReduceStrategy.Reduce
    pexe = ParallelExecutor(loss_name=loss.name, build_strategy=bs)
    # plan shards the fc accumulators ((16,64) divisible by 8 on dim 0)
    wname = next(p.name for p in fluid.default_main_program().all_parameters()
                 if "w" in p.name and p.shape[0] % 8 == 0)
    assert pexe._plan.spec(wname + "_moment1_acc")[0] == "dp"
    assert pexe._plan.spec(wname) == P()

    rng = np.random.RandomState(3)
    xs = rng.randn(32, 16).astype(np.float32)
    ys = (rng.rand(32, 1) > 0.5).astype(np.int64)
    losses = [pexe.run(feed={"x": xs, "y": ys}, fetch_list=[loss])[0]
              for _ in range(10)]
    assert losses[-1] < losses[0]


def test_sharding_plan_prefix_and_regex():
    mesh = make_mesh([2, 4], ("dp", "mp"))
    plan = ShardingPlan(mesh)
    plan.set("fc_0.w_0", P(None, "mp"))
    plan.set_regex(r"\.q\.w", P(None, "mp"))
    assert plan.spec("fc_0.w_0") == P(None, "mp")
    # accumulator inherits via prefix
    assert plan.spec("fc_0.w_0_moment_acc") == P(None, "mp")
    assert plan.spec("enc.l0.attn.q.w.w_0") == P(None, "mp")
    assert plan.spec("other") == P()
    # ndim clamp
    assert plan.spec("fc_0.w_0_beta1_pow_acc", ndim=1) == P(None)


def test_parallel_executor_rnn_model_parity():
    """8-way dp on a scan-based RNN model (GRU over time) == single
    device: exercises lax.scan + embedding + sequence masking under
    GSPMD, not just dense fc stacks."""
    rng = np.random.RandomState(3)
    B, T, V, D = 16, 12, 50, 24
    xs = rng.randint(0, V, (B, T)).astype(np.int64)
    lens = rng.randint(3, T + 1, B).astype(np.int32)
    ys = rng.randint(0, 2, (B, 1)).astype(np.int64)

    def build():
        words = layers.data(name="w", shape=[T], dtype="int64")
        lengths = layers.data(name="lens", shape=[], dtype="int32")
        label = layers.data(name="y", shape=[1], dtype="int64")
        emb = layers.embedding(words, size=[V, D])
        proj = layers.fc(emb, size=D * 3, num_flatten_dims=2)
        h = layers.dynamic_gru(proj, size=D, sequence_length=lengths)
        pooled = layers.sequence_pool(h, "last", sequence_length=lengths)
        logits = layers.fc(pooled, size=2)
        loss = fluid.layers.mean(
            layers.softmax_with_cross_entropy(logits, label))
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
        return loss

    feed = {"w": xs, "lens": lens, "y": ys}

    main_a, start_a = fluid.Program(), fluid.Program()
    main_a.random_seed = start_a.random_seed = 11
    scope_a = fluid.Scope()
    with fluid.scope_guard(scope_a), fluid.program_guard(main_a, start_a):
        with fluid.unique_name.guard():
            loss_a = build()
        exe = fluid.Executor()
        exe.run(start_a)
        single = [exe.run(main_a, feed=feed, fetch_list=[loss_a])[0]
                  for _ in range(3)]

    main_b, start_b = fluid.Program(), fluid.Program()
    main_b.random_seed = start_b.random_seed = 11
    scope_b = fluid.Scope()
    with fluid.scope_guard(scope_b), fluid.program_guard(main_b, start_b):
        with fluid.unique_name.guard():
            loss_b = build()
        fluid.Executor().run(start_b)
        pexe = ParallelExecutor(loss_name=loss_b.name, main_program=main_b,
                                scope=scope_b)
        par = [pexe.run(feed=feed, fetch_list=[loss_b])[0]
               for _ in range(3)]

    for a, b in zip(single, par):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)
    assert single[0] > single[-1]


@pytest.mark.parametrize("fused_qkv,tied", [
    (False, False), (True, False), (False, True)])
def test_transformer_lm_dp_x_mp_parity(fused_qkv, tied):
    """Flagship path: the transformer LM trained under a dp=2 x mp=4 mesh
    with the Megatron plan must match single-device training exactly
    (same seed/feeds) — embedding/attention/ffn/vocab-parallel-head
    shardings change the partitioning, never the math. Covers the
    separate q/k/v projections, the fused head-grouped .qkv layout the
    plan's column split was extended for, and the tied embed/head table
    under the plan's tied=True rules (the table split by vocabulary rows
    over mp; the fused head runs a rank over its own 32 rows under a
    shard_map and only row statistics and one dx cross mp). The untied
    head's (D, V) weight takes the same path on its column split."""
    from paddle_tpu import models
    from paddle_tpu.parallel import make_mesh, megatron_transformer_plan

    B, T, V = 8, 32, 128
    rng = np.random.RandomState(0)
    ids = rng.randint(0, V, (B, T)).astype(np.int64)
    lbl = rng.randint(0, V, (B, T)).astype(np.int64)
    feed = {"ids": ids, "labels": lbl}

    def build():
        i = layers.data(name="ids", shape=[B, T], dtype="int64",
                        append_batch_size=False)
        l = layers.data(name="labels", shape=[B, T], dtype="int64",
                        append_batch_size=False)
        loss, _ = models.transformer.transformer_lm(
            i, l, vocab_size=V, n_layer=2, n_head=4, d_model=32,
            d_inner=64, max_len=T, fused_qkv=fused_qkv,
            tie_embeddings=tied)
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
        return loss

    main_a, start_a = fluid.Program(), fluid.Program()
    main_a.random_seed = start_a.random_seed = 13
    scope_a = fluid.Scope()
    with fluid.scope_guard(scope_a), fluid.program_guard(main_a, start_a):
        with fluid.unique_name.guard():
            loss_a = build()
        exe = fluid.Executor()
        exe.run(start_a)
        single = [exe.run(main_a, feed=feed, fetch_list=[loss_a])[0]
                  for _ in range(3)]

    main_b, start_b = fluid.Program(), fluid.Program()
    main_b.random_seed = start_b.random_seed = 13
    scope_b = fluid.Scope()
    with fluid.scope_guard(scope_b), fluid.program_guard(main_b, start_b):
        with fluid.unique_name.guard():
            loss_b = build()
        fluid.Executor().run(start_b)
        mesh = make_mesh([2, 4], ("dp", "mp"))
        pexe = ParallelExecutor(loss_name=loss_b.name, main_program=main_b,
                                scope=scope_b, mesh=mesh,
                                plan=megatron_transformer_plan(mesh,
                                                               tied=tied))
        par = [pexe.run(feed=feed, fetch_list=[loss_b])[0]
               for _ in range(3)]

    for a, b in zip(single, par):
        np.testing.assert_allclose(a, b, rtol=3e-5, atol=3e-6)
    assert single[0] > single[-1]


def test_ring_attention_bf16_tracks_f32():
    """Under bf16 inputs the ring path runs bf16 MXU matmuls with f32
    accumulation (the flash-kernel recipe); outputs must track the f32
    reference within bf16 noise."""
    mesh = default_mesh("sp")
    r = np.random.RandomState(5)
    q, k, v = (r.randn(2, 2, 64, 16).astype(np.float32) * 0.5
               for _ in range(3))
    ref = np.asarray(full_attention(jnp.asarray(q), jnp.asarray(k),
                                    jnp.asarray(v), causal=True))
    out16, _ = _ring_run(
        jnp.asarray(q, jnp.bfloat16), jnp.asarray(k, jnp.bfloat16),
        jnp.asarray(v, jnp.bfloat16), mesh, grads=False, causal=True)
    np.testing.assert_allclose(np.asarray(out16.astype(jnp.float32)), ref,
                               atol=3e-2)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_grads_match_full(causal):
    """The ring custom VJP (re-rotating K/V, O(T_local) residuals) must
    produce the same q/k/v gradients as autodiff of full attention."""
    mesh = default_mesh("sp")
    r = np.random.RandomState(9)
    q, k, v = (jnp.asarray(r.randn(2, 2, 64, 16), jnp.float32) * 0.5
               for _ in range(3))
    _ring_vs_full(q, k, v, mesh, grads=True,
                  out_tol=dict(rtol=2e-5, atol=2e-5),
                  grad_tol=dict(rtol=2e-4, atol=2e-5), causal=causal)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_kv_lengths_matches_full(causal):
    """Global KV-length masking (the reference's padded-batch attention
    semantics) must agree between the ring and the full fallback — the
    lengths tensor is global, each rotation step masks by global key
    position. Includes a zero-length batch row (fully-masked: output 0,
    finite grads — the backward's lse guard)."""
    mesh = default_mesh("sp")
    r = np.random.RandomState(11)
    q, k, v = (jnp.asarray(r.randn(3, 2, 64, 16), jnp.float32) * 0.5
               for _ in range(3))
    lengths = jnp.asarray([40, 64, 0], jnp.int32)
    out, _ = _ring_vs_full(q, k, v, mesh, grads=True,
                           out_tol=dict(rtol=2e-5, atol=2e-5),
                           grad_tol=dict(rtol=2e-4, atol=2e-5),
                           causal=causal, lengths=lengths)
    # fully-masked batch row -> exactly zero, not mean-of-V
    np.testing.assert_array_equal(np.asarray(out[2]), 0.0)


def test_ring_attention_dropout_matches_full():
    """Attention-probability dropout (reference:
    python/paddle/fluid/nets.py scaled_dot_product_attention dropout_rate)
    on the ring path: the mask is a pure function of (seed, b, h, global
    q, global k) — independent of shard count — so ring == full EXACTLY
    for the same seed, values and gradients."""
    mesh = default_mesh("sp")
    r = np.random.RandomState(13)
    q, k, v = (jnp.asarray(r.randn(2, 2, 64, 16), jnp.float32) * 0.5
               for _ in range(3))
    lengths = jnp.asarray([64, 40], jnp.int32)
    seed = jax.random.key_data(jax.random.PRNGKey(21)).astype(jnp.uint32)
    _, ref = _ring_vs_full(q, k, v, mesh, grads=True,
                           out_tol=dict(rtol=2e-5, atol=2e-5),
                           grad_tol=dict(rtol=2e-4, atol=2e-5),
                           causal=True, lengths=lengths, dropout_rate=0.3,
                           dropout_seed=seed)
    # dropout actually dropped something
    ref_nodrop = full_attention(q, k, v, causal=True, lengths=lengths)
    assert float(jnp.abs(ref - ref_nodrop).max()) > 1e-3


def test_ring_attention_dropout_mask_statistics():
    """The lowbias32 position-hash must behave like Bernoulli(1-rate):
    empirical drop fraction within 3 sigma on a 64k-element mask."""
    from paddle_tpu.parallel.ring_attention import _dropout_keep_scale

    seed = jax.random.key_data(jax.random.PRNGKey(3)).astype(jnp.uint32)
    rate = 0.25
    ks = np.asarray(_dropout_keep_scale(
        seed, 4, 4, jnp.arange(64), jnp.arange(64), rate))
    dropped = float((ks == 0.0).mean())
    n = ks.size
    sigma = (rate * (1 - rate) / n) ** 0.5
    assert abs(dropped - rate) < 3 * sigma, (dropped, rate)
    # kept entries carry the 1/(1-rate) inverted-dropout scale
    kept = ks[ks != 0.0]
    np.testing.assert_allclose(kept, 1.0 / (1 - rate), rtol=1e-6)


@pytest.mark.parametrize("chunk", [8, 16, 32])
def test_ring_attention_chunked_matches_unchunked(chunk):
    """KV sub-chunking (the transient-memory bound for 100k+ sequences)
    is numerically invisible: same values and grads as the whole-block
    path, with causal + ragged lengths + dropout all on — the masks and
    dropout are keyed on GLOBAL positions, so blocking can't shift them.
    T_local = 32, so chunk=8/16 split each visiting block and chunk=32
    degenerates to whole-block."""
    mesh = default_mesh("sp")  # 8 shards
    r = np.random.RandomState(29)
    T = 256  # T_local = 32
    q, k, v = (jnp.asarray(r.randn(2, 2, T, 16), jnp.float32) * 0.5
               for _ in range(3))
    lengths = jnp.asarray([T, 200], jnp.int32)
    seed = jax.random.key_data(jax.random.PRNGKey(31)).astype(jnp.uint32)

    def run(chunk_):
        o, grads = _ring_run(q, k, v, mesh, grads=True, causal=True,
                             lengths=lengths, dropout_rate=0.25,
                             dropout_seed=seed, chunk=chunk_)
        return np.asarray(o), [np.asarray(g) for g in grads]

    o_ref, g_ref = run(None)  # T_local=32 < auto threshold: whole-block
    o_c, g_c = run(chunk)
    np.testing.assert_allclose(o_c, o_ref, rtol=2e-6, atol=2e-6)
    for name, a, b in zip("qkv", g_c, g_ref):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6,
                                   err_msg="d%s diverged (chunk=%d)"
                                   % (name, chunk))


def test_ring_attention_chunk_validation():
    from paddle_tpu.parallel.ring_attention import _pick_chunk

    assert _pick_chunk(32, None) == (1, 32)          # small: whole block
    assert _pick_chunk(4096, None) == (2, 2048)      # auto split
    assert _pick_chunk(8192, None) == (4, 2048)
    assert _pick_chunk(96, 32) == (3, 32)            # explicit divisor
    with pytest.raises(ValueError, match="divide"):
        _pick_chunk(100, 32)
    # odd big block with no pow2 divisor >=128: stays whole
    assert _pick_chunk(2049 * 3, None) == (1, 2049 * 3)
