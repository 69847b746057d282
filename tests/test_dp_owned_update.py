"""`megatron_transformer_plan` on a mesh whose batch axis is wider than 1:
one dp rank owns the update of each matrix and table (the dimension mp
leaves whole goes over dp, weight and accumulators alike), and a mesh
without such an axis keeps the specs it always had, letter for letter.
On the 8-device virtual CPU mesh (conftest)."""
import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

import paddle_tpu as fluid
from paddle_tpu import layers, models
from paddle_tpu import observability as obs
from paddle_tpu.parallel import (ParallelExecutor, ShardingPlan, make_mesh,
                                 megatron_transformer_plan)
from paddle_tpu.parallel.sharding import infer_tp_plan

D, F, V, T = 32, 64, 128, 16
# (name, shape): one column weight, one row weight, the table, positions,
# and what stays whole over dp whatever the mesh
SHAPES = {
    "lm.l0.self.q.w": (D, D), "lm.l1.ffn.fc1.w": (D, F),
    "lm.l1.ffn.fc2.w": (F, D), "lm.l0.self.out.w": (D, D),
    "lm.tok_emb": (V, D), "lm.pos_emb": (T, D),
    "lm.l0.self.q.b": (D,), "lm.l1.ffn.fc2.b": (D,), "lm.head.b": (V,),
    "lm.head.w": (D, V), "layer_norm_0.w_0": (D,),
}
# the specs of every plan until PR 52, and of a mesh with no wide batch axis
WHOLE = {
    "lm.l0.self.q.w": P(None, "mp"), "lm.l1.ffn.fc1.w": P(None, "mp"),
    "lm.l1.ffn.fc2.w": P("mp", None), "lm.l0.self.out.w": P("mp", None),
    "lm.tok_emb": {True: P("mp", None), False: P(None, "mp")},
    "lm.pos_emb": P(None, "mp"),
    "lm.l0.self.q.b": P("mp"), "lm.l1.ffn.fc2.b": P(), "lm.head.b": P("mp"),
    "lm.head.w": P(None, "mp"), "layer_norm_0.w_0": P(),
}
OWNED = {
    "lm.l0.self.q.w": P("dp", "mp"), "lm.l1.ffn.fc1.w": P("dp", "mp"),
    "lm.l1.ffn.fc2.w": P("mp", "dp"), "lm.l0.self.out.w": P("mp", "dp"),
    # the untied table keeps its rows whole (the plan's docstring)
    "lm.tok_emb": {True: P("mp", "dp"), False: P(None, "mp")},
    "lm.pos_emb": P("dp", "mp"),
}
ACCS = ("", "_moment1_acc_0", "_moment2_acc_0")


def _want(table, name, tied):
    s = table[name]
    return s[tied] if isinstance(s, dict) else s


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
@pytest.mark.parametrize("name", sorted(OWNED))
def test_wide_batch_axis_owns_each_matrix_and_its_moments(name, tied):
    mesh = make_mesh([2, 2], ("dp", "mp"), devices=jax.devices()[:4])
    plan = megatron_transformer_plan(mesh, tied=tied)
    shape = SHAPES[name]
    for acc in ACCS:
        got = plan.spec(name + acc, shape=shape)
        assert got == _want(OWNED, name, tied), (name + acc, got)
    # a (1,) power accumulator cannot be split and stays whole, quietly
    assert plan.spec(name + "_beta1_pow_acc_acc_0", shape=(1,)) == P(None)


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_vectors_and_the_untied_head_stay_whole_over_dp(tied):
    mesh = make_mesh([2, 2], ("dp", "mp"), devices=jax.devices()[:4])
    plan = megatron_transformer_plan(mesh, tied=tied)
    for name in set(SHAPES) - set(OWNED):
        for acc in ACCS:
            got = plan.spec(name + acc, shape=SHAPES[name])
            assert got == _want(WHOLE, name, tied), (name + acc, got)


def _plans_without_a_wide_batch_axis(tied):
    """name -> plan: each must give the specs of a mesh with no dp."""
    dp1 = make_mesh([1, 4], ("dp", "mp"), devices=jax.devices()[:4])
    dp2 = make_mesh([2, 2], ("dp", "mp"), devices=jax.devices()[:4])
    only_mp = make_mesh([4], ("mp",), devices=jax.devices()[:4])
    prog = fluid.Program()
    with fluid.program_guard(prog, fluid.Program()), \
            fluid.unique_name.guard():
        ids = layers.data(name="ids", shape=[2, T], dtype="int64",
                          append_batch_size=False)
        models.transformer.transformer_lm(
            ids, ids, vocab_size=V, n_layer=1, n_head=2, d_model=D,
            d_inner=F, max_len=T, tie_embeddings=tied)
    out = {
        "dp-of-one": megatron_transformer_plan(dp1, tied=tied),
        "no-batch-axes": megatron_transformer_plan(dp2, tied=tied,
                                                   batch_axes=()),
        "axis-not-in-mesh": megatron_transformer_plan(only_mp, tied=tied),
    }
    if not tied:  # the serving plan knows no tied rule
        out["infer_tp_plan"] = infer_tp_plan(dp2, prog)
    return out


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_no_wide_batch_axis_gives_the_specs_it_always_had(tied):
    for what, plan in _plans_without_a_wide_batch_axis(tied).items():
        for name, shape in SHAPES.items():
            for acc in ACCS:
                got = plan.spec(name + acc, shape=shape)
                assert got == _want(WHOLE, name, tied), (what, name + acc)
            # and with no shape to look at
            assert plan.spec(name) == _want(WHOLE, name, tied), (what, name)


def test_a_dimension_the_batch_axis_does_not_divide_stays_whole():
    mesh = make_mesh([2, 2], ("dp", "mp"), devices=jax.devices()[:4])
    plan = megatron_transformer_plan(mesh, tied=True)
    assert plan.spec("lm.l0.ffn.fc1.w", shape=(33, 64)) == P(None, "mp")
    assert plan.spec("lm.l0.ffn.fc2.w_moment1_acc_0",
                     shape=(64, 33)) == P("mp", None)
    # the tied table: D whole where dp does not divide it, without a
    # word; a V that mp does not divide is still an error
    assert plan.spec("lm.tok_emb", shape=(128, 33)) == P("mp", None)
    with pytest.raises(ValueError, match=r"lm\.tok_emb.*\(129, 32\)"):
        plan.spec("lm.tok_emb", shape=(129, 32))
    # two batch axes own it together, and each must be wider than 1
    mesh3 = make_mesh([2, 2, 2], ("dp", "sp", "mp"))
    both = megatron_transformer_plan(mesh3, batch_axes=("dp", "sp"))
    assert both.spec("lm.l0.ffn.fc1.w", shape=(32, 64)) == P(
        ("dp", "sp"), "mp")
    assert both.spec("lm.l0.ffn.fc1.w", shape=(34, 64)) == P(None, "mp")
    mesh1 = make_mesh([2, 1, 4], ("dp", "sp", "mp"))
    one = megatron_transformer_plan(mesh1, batch_axes=("dp", "sp"))
    assert one.spec("lm.l0.ffn.fc2.w", shape=(64, 32)) == P("mp", "dp")


def _whole_plan(mesh, tied):
    """The plan until PR 52, every rule written out by hand."""
    plan = ShardingPlan(mesh, batch_axes=("dp",))
    plan.tensor_axis = "mp"
    for pat, spec in [
        (r"\.(q|k|v|qkv|fc1)\.w", P(None, "mp")),
        (r"\.(q|k|v|qkv|fc1)\.b", P("mp")),
        (r"\.(out|fc2)\.w", P("mp", None)),
        (r"\.(out|fc2)\.b", P()),
        (r"pos_emb", P(None, "mp")),
        (r"tok_emb", P("mp", None) if tied else P(None, "mp")),
        (r"\.head\.w", P(None, "mp")),
        (r"\.head\.b", P("mp")),
    ]:
        plan.set_regex(pat, spec)
    return plan


def _train(plan_of, tied, steps=3):
    """(losses, scope values, executor) of the tiny LM trained `steps`
    steps with Adam on a 2x2 dp x mp mesh under `plan_of(mesh)`."""
    B = 8
    rng = np.random.RandomState(0)
    feed = {"ids": rng.randint(0, V, (B, T)).astype(np.int64),
            "labels": rng.randint(0, V, (B, T)).astype(np.int64)}
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = 13
    scope = fluid.Scope()
    with fluid.scope_guard(scope), fluid.program_guard(main_p, startup):
        with fluid.unique_name.guard():
            i = layers.data(name="ids", shape=[B, T], dtype="int64",
                            append_batch_size=False)
            l = layers.data(name="labels", shape=[B, T], dtype="int64",
                            append_batch_size=False)
            loss, _ = models.transformer.transformer_lm(
                i, l, vocab_size=V, n_layer=2, n_head=4, d_model=D,
                d_inner=F, max_len=T, tie_embeddings=tied)
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
        fluid.Executor().run(startup)
        mesh = make_mesh([2, 2], ("dp", "mp"), devices=jax.devices()[:4])
        pexe = ParallelExecutor(loss_name=loss.name, main_program=main_p,
                                scope=scope, mesh=mesh, plan=plan_of(mesh))
        losses = [float(np.squeeze(pexe.run(feed=feed,
                                            fetch_list=[loss])[0]))
                  for _ in range(steps)]
        names = ["lm.l1.ffn.fc2.w", "lm.l0.self.q.w", "lm.tok_emb",
                 "lm.l1.ffn.fc2.w_moment1_acc_0",
                 "lm.l0.self.q.w_moment2_acc_0", "lm.tok_emb_moment1_acc_0"]
        vals = {n: scope.find_var(n) for n in names}
    return losses, vals, pexe


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_owned_update_trains_as_the_whole_one(tied):
    """Three Adam steps under the plan against the specs until PR 52 set
    by hand: the same float32 program, partitioned another way. Not
    bitwise: a dp pair's gradients are summed by a reduce-scatter where
    the whole plan all-reduces them, and XLA:CPU orders the partial
    products of a matmul by its partitioning; 1e-6 of the value holds."""
    l_new, v_new, pexe = _train(
        lambda mesh: megatron_transformer_plan(mesh, tied=tied), tied)
    l_old, v_old, _ = _train(lambda mesh: _whole_plan(mesh, tied), tied)
    np.testing.assert_allclose(l_new, l_old, rtol=1e-6)
    assert l_new[0] > l_new[-1]
    for n in v_old:
        np.testing.assert_allclose(np.asarray(v_new[n]),
                                   np.asarray(v_old[n]),
                                   rtol=1e-6, atol=1e-6, err_msg=n)
    # at rest a device holds a quarter of an owned matrix, half of it
    # under the whole plan
    w = v_new["lm.l1.ffn.fc2.w"]
    assert w.sharding.shard_shape(w.shape) == (F // 2, D // 2)
    w = v_old["lm.l1.ffn.fc2.w"]
    assert w.sharding.shard_shape(w.shape) == (F // 2, D)
    m = v_new["lm.tok_emb_moment1_acc_0"]
    assert m.sharding.shard_shape(m.shape) == (
        (V // 2, D // 2) if tied else (V, D // 2))


def test_run_stats_say_how_much_state_one_dp_rank_owns():
    _, _, whole = _train(lambda mesh: _whole_plan(mesh, True), True, steps=1)
    stats = whole.run_stats()
    assert stats["dp_owned_state_bytes"] == 0 and stats["state_bytes"] > 0
    fp = obs.program_fp(whole._program)
    assert obs.DP_OWNED_STATE_BYTES.value(program=fp, of="owned") == 0
    assert obs.DP_OWNED_STATE_BYTES.value(
        program=fp, of="state") == stats["state_bytes"]

    _, _, owned = _train(
        lambda mesh: megatron_transformer_plan(mesh, tied=True), True,
        steps=1)
    stats = owned.run_stats()
    # every matrix and table with its two moments: all but the vectors
    share = stats["dp_owned_state_bytes"] / stats["state_bytes"]
    assert 0.9 < share < 1.0, stats
    fp = obs.program_fp(owned._program)
    assert obs.DP_OWNED_STATE_BYTES.value(
        program=fp, of="owned") == stats["dp_owned_state_bytes"]
    assert {"steps", "dispatches", "mean_step_ms"} <= set(stats)

    # a replicated plan on a dp mesh, and the plan on a mesh of dp = 1
    _, _, rep = _train(lambda mesh: ShardingPlan(mesh), True, steps=1)
    assert rep.run_stats()["dp_owned_state_bytes"] == 0
