"""The vocabulary-parallel fused head: under a trace mesh whose plan has a
tensor axis, `lm_head_loss` runs its chunk loop per rank over V / ways
rows inside a shard_map (ops/fused_loss.py), and
`megatron_transformer_plan(tied=True)` shards the tied table by rows.

Numbers against the one-device op, the plan's rules, and the STRUCTURE of
the compiled step: no collective inside a loop body, no gather of the
table. 8 virtual CPU devices (conftest)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import paddle_tpu as fluid
from paddle_tpu import layers, models, observability as obs
from paddle_tpu.framework import trace as trace_mod
from paddle_tpu.ops.fused_loss import lm_head_loss
from paddle_tpu.parallel import (ParallelExecutor, make_mesh,
                                 megatron_transformer_plan)

from hlo_text import collectives, while_bodies

BLOCK_V = 128


def _inputs(v, ways, transpose_w, n=16, d=32, seed=0):
    r = np.random.RandomState(seed)
    x = jnp.asarray(r.randn(n, d).astype(np.float32))
    w = r.randn(d, v).astype(np.float32) * 0.3
    w = jnp.asarray(w.T.copy() if transpose_w else w)
    b = jnp.asarray(r.randn(v).astype(np.float32))
    per = v // ways
    # a label in every rank's slice: its first row (the id the previous
    # rank's padded tail would number its first padded column with), its
    # last, and one inside; the rest anywhere
    edge = [k * per + o for k in range(ways) for o in (0, per - 1, per // 2)]
    labels = np.concatenate([edge, r.randint(0, v, n)])[:n]
    return x, w, b, jnp.asarray(labels.astype(np.int32))


def _loss_and_grads(x, w, b, labels, transpose_w):
    def f(x, w, b):
        per_row = lm_head_loss(BLOCK_V, x, w, b, labels,
                               transpose_w=transpose_w)
        # uneven row weights: a wrong row shows in every gradient
        return jnp.sum(per_row[:, 0] * jnp.linspace(0.5, 1.5, x.shape[0])), \
            per_row

    (_, per_row), grads = jax.jit(
        jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True))(x, w, b)
    return (per_row,) + grads


@pytest.mark.parametrize("ways", [2, 4])
@pytest.mark.parametrize("per_rank", [
    pytest.param(2 * BLOCK_V, id="whole-chunks"),
    pytest.param(BLOCK_V + 64, id="ragged-chunk"),
    pytest.param(BLOCK_V + 37, id="not-128"),
])
@pytest.mark.parametrize("transpose_w", [False, True],
                         ids=["d-by-v", "v-by-d"])
def test_vocab_parallel_matches_one_device(transpose_w, per_rank, ways):
    """Loss, dx, dW and db of the mesh path equal the one-device op's: the
    ranks' row statistics meet once, a label is picked by the rank that
    owns its row and by no padded tail, dx is summed over the tensor
    axis, dW and db over the batch axis."""
    v = per_rank * ways
    args = _inputs(v, ways, transpose_w)
    want = _loss_and_grads(*args, transpose_w)
    mesh = make_mesh([2, ways], ("dp", "mp"))
    before = obs.FUSED_HEAD_TRACES.value(path="vocab_parallel", ways=ways)
    with trace_mod.mesh_context(mesh, megatron_transformer_plan(mesh)):
        got = _loss_and_grads(*args, transpose_w)
    assert obs.FUSED_HEAD_TRACES.value(
        path="vocab_parallel", ways=ways) > before
    for name, a, g in zip(("loss", "dx", "dw", "db"), want, got):
        np.testing.assert_allclose(np.asarray(g), np.asarray(a), rtol=2e-5,
                                   atol=2e-6, err_msg=name)


def test_vocab_parallel_refuses_a_vocabulary_the_axis_does_not_divide():
    x, w, b, labels = _inputs(4 * BLOCK_V + 2, 2, False)
    mesh = make_mesh([2, 4], ("dp", "mp"))
    with trace_mod.mesh_context(mesh, megatron_transformer_plan(mesh)):
        with pytest.raises(ValueError, match=r"\(32, 514\).*'mp'"):
            jax.jit(lambda *a: lm_head_loss(BLOCK_V, *a))(x, w, b, labels)


@pytest.mark.parametrize("case", ["no-mesh", "mp-of-one", "no-tensor-axis",
                                  "inside-shard-map"])
def test_local_path_holds_no_shard_map_and_no_collective(case):
    """Without a mesh, with a tensor axis of one device, under a plan that
    names none, or inside an enclosing shard_map, the op is today's
    program: no shard_map and no collective in its jaxpr."""
    from paddle_tpu.parallel import seq_parallel_plan

    x, w, b, labels = _inputs(2 * BLOCK_V, 2, False)
    fn = jax.grad(
        lambda x, w, b, y: jnp.sum(lm_head_loss(BLOCK_V, x, w, b, y)),
        argnums=(0, 1, 2))
    before = obs.FUSED_HEAD_TRACES.value(path="local", ways=1)
    if case == "no-mesh":
        text = str(jax.make_jaxpr(fn)(x, w, b, labels))
    else:
        shape, plan_of = {
            "mp-of-one": ([8, 1], megatron_transformer_plan),
            "no-tensor-axis": ([2, 4], lambda m: seq_parallel_plan(
                m, sp_axis="mp")),
            "inside-shard-map": ([2, 4], megatron_transformer_plan),
        }[case]
        mesh = make_mesh(shape, ("dp", "mp"))
        if case == "inside-shard-map":
            # a dp-mapped step: the op sees manual axes and stays local
            fn = jax.shard_map(
                fn, mesh=mesh, in_specs=(P("dp", None), P(), P(), P("dp")),
                out_specs=(P("dp", None), P(), P()))
        with trace_mod.mesh_context(mesh, plan_of(mesh)):
            text = str(jax.make_jaxpr(fn)(x, w, b, labels))
    assert obs.FUSED_HEAD_TRACES.value(path="local", ways=1) > before
    if case == "inside-shard-map":
        # the enclosing map is the test's own; the op adds none, and its
        # only collective is the dp sum of the replicated weight's grad
        assert text.count("shard_map") == 1
        assert "pmax" not in text and "axis_index" not in text
    else:
        for word in ("shard_map", "psum", "pmax", "all_gather",
                     "axis_index"):
            assert word not in text, word


def test_megatron_plan_tied_shards_the_table_by_rows():
    mesh = make_mesh([2, 4], ("dp", "mp"))
    plan = megatron_transformer_plan(mesh, tied=True)
    # rows over mp; the columns over the batch axis, wider than 1 here, so
    # that one dp rank owns the table's update (tests/test_dp_owned_update)
    rows = P("mp", "dp")
    assert plan.spec("lm.tok_emb", shape=(128, 32)) == rows
    assert megatron_transformer_plan(mesh, tied=True, batch_axes=()).spec(
        "lm.tok_emb", shape=(128, 32)) == P("mp", None)
    # the moments inherit the table's rule; a (1,) power accumulator
    # cannot be split and stays whole, quietly
    for acc in ("lm.tok_emb_moment1_acc", "lm.tok_emb_moment2_acc"):
        assert plan.spec(acc, shape=(128, 32)) == rows
    assert plan.spec("lm.tok_emb_beta1_pow_acc", shape=(1,)) == P(None)
    assert plan.spec("lm.head.b", shape=(128,)) == P("mp")
    assert plan.spec("lm.head.b_moment1_acc", shape=(128,)) == P("mp")
    # a vocabulary mp does not divide is an error naming the shape
    with pytest.raises(ValueError, match=r"lm\.tok_emb.*\(130, 32\)"):
        plan.spec("lm.tok_emb", shape=(130, 32))
    with pytest.raises(ValueError, match=r"lm\.head\.b.*\(130,\)"):
        plan.sharding("lm.head.b", shape=(130,))
    # the untied rules stand: hidden-sharded table, vocabulary-sharded
    # head, and a dim the axis does not divide left whole without a word
    untied = megatron_transformer_plan(mesh)
    assert untied.spec("lm.tok_emb", shape=(128, 32)) == P(None, "mp")
    assert untied.spec("lm.head.w", shape=(32, 128)) == P(None, "mp")
    assert untied.spec("lm.head.w", shape=(32, 130)) == P(None, None)


def _mesh_step_text(tied, vocab=3 * BLOCK_V * 2, d_model=32):
    """Compiled text of a tiny transformer_lm + Adam step on a 2x2
    dp x mp mesh under the megatron plan, as ParallelExecutor jits it;
    three vocabulary chunks a rank (the head op's `block_v` set to
    BLOCK_V)."""
    from paddle_tpu.executor import analyze_state, build_step_fn

    b, t = 4, 16
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = 3
    with fluid.program_guard(main_p, startup), fluid.unique_name.guard():
        ids = layers.data(name="ids", shape=[b, t], dtype="int64",
                          append_batch_size=False)
        lbl = layers.data(name="labels", shape=[b, t], dtype="int64",
                          append_batch_size=False)
        loss, _ = models.transformer.transformer_lm(
            ids, lbl, vocab_size=vocab, n_layer=1, n_head=2,
            d_model=d_model, d_inner=64, max_len=t, tie_embeddings=tied)
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    head, = [op for op in main_p.global_block().ops
             if op.type == "fused_lm_head_loss"]
    head.set_attr("block_v", BLOCK_V)
    mesh = make_mesh([2, 2], ("dp", "mp"))
    plan = megatron_transformer_plan(mesh, tied=tied)
    sds = jax.ShapeDtypeStruct
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    step = sds((), np.uint32)
    _, init_out = analyze_state(startup, set())
    _, init = jax.eval_shape(build_step_fn(startup, (), [], init_out),
                             {}, {}, key, step)
    state_in, state_out = analyze_state(main_p, {"ids", "labels"})
    stepfn = build_step_fn(main_p, (loss.name,), state_in, state_out)
    feeds = {n: sds((b, t), np.int32, sharding=plan.feed_sharding(2))
             for n in ("ids", "labels")}
    state = {n: sds(init[n].shape, init[n].dtype,
                    sharding=plan.sharding(n, shape=init[n].shape))
             for n in state_in}
    with trace_mod.mesh_context(mesh, plan):
        _, out_aval = jax.eval_shape(stepfn, feeds, state, key, step)
        out_sh = ((plan.replicated(),),
                  {n: plan.sharding(n, shape=tuple(a.shape))
                   for n, a in out_aval.items()})
        text = jax.jit(stepfn, out_shardings=out_sh,
                       donate_argnums=(1,)).lower(
            feeds, state, sds(key.shape, key.dtype,
                              sharding=plan.replicated()),
            sds((), np.uint32, sharding=plan.replicated())
        ).compile().as_text()
    return text, (vocab, d_model)


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_mesh_step_keeps_collectives_out_of_the_head_loops(tied):
    """The compiled 2x2 step as ParallelExecutor jits it: both chunk loops
    are there, no all-reduce, all-gather or reduce-scatter lies inside a
    loop body, and nothing gathers the (V, D) table (or its transpose):
    each mp rank reads and updates its own rows."""
    text, (v, d) = _mesh_step_text(tied)
    assert len(while_bodies(text)) >= 2, "the chunk loops were unrolled"
    found = collectives(text)
    assert found, "a 2x2 step with no collective at all was not partitioned"
    in_loops = [c for c in found if c[3] is not None]
    assert not in_loops, in_loops
    table = ("[%d,%d]" % (v, d), "[%d,%d]" % (d, v))
    gathers = [c for c in found if c[0] == "all-gather"
               and any(s in c[1] for s in table)]
    assert not gathers, gathers


def test_tied_mesh_training_counts_a_vocab_parallel_trace():
    """Through ParallelExecutor: the tied LM under the tied plan compiles
    its head on the vocabulary-parallel path (the counter says so), its
    table and moments live row-sharded, and training moves."""
    b, t, v = 4, 16, 256
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = 5
    scope = fluid.Scope()
    r = np.random.RandomState(0)
    feed = {"ids": r.randint(0, v, (b, t)).astype(np.int64),
            "labels": r.randint(0, v, (b, t)).astype(np.int64)}
    with fluid.scope_guard(scope), fluid.program_guard(main_p, startup):
        with fluid.unique_name.guard():
            ids = layers.data(name="ids", shape=[b, t], dtype="int64",
                              append_batch_size=False)
            lbl = layers.data(name="labels", shape=[b, t], dtype="int64",
                              append_batch_size=False)
            loss, _ = models.transformer.transformer_lm(
                ids, lbl, vocab_size=v, n_layer=1, n_head=2, d_model=32,
                d_inner=64, max_len=t, tie_embeddings=True)
            fluid.optimizer.Adam(learning_rate=1e-2).minimize(loss)
        fluid.Executor().run(startup)
        mesh = make_mesh([2, 2], ("dp", "mp"))
        before = obs.FUSED_HEAD_TRACES.value(path="vocab_parallel", ways=2)
        pexe = ParallelExecutor(
            loss_name=loss.name, main_program=main_p, scope=scope,
            mesh=mesh, plan=megatron_transformer_plan(mesh, tied=True))
        losses = [float(np.asarray(
            pexe.run(feed=feed, fetch_list=[loss])[0]).reshape(()))
            for _ in range(3)]
        assert obs.FUSED_HEAD_TRACES.value(
            path="vocab_parallel", ways=2) > before
        assert losses[-1] < losses[0]
        names = [n for n in main_p.global_block().vars
                 if n == "lm.tok_emb" or n.startswith("lm.tok_emb_moment")]
        assert len(names) == 3, names
        for name in names:
            arr = scope.find_var(name)
            # rows over mp as the head reads them; columns over dp, whose
            # one rank owns the update (tests/test_dp_owned_update.py)
            assert arr.sharding.spec == P("mp", "dp"), (name, arr.sharding)
            assert arr.addressable_shards[0].data.shape == (v // 2, 16)
