"""Kimi Delta Attention's three forms (`paddle_tpu/ops/kda.py`): the
CHUNKED scan of a prefill == the recurrence a token at a time == one
step after another, at lengths that are no multiple of the chunk, with
padding, with a log-decay AT the gate's bound for whole chunks (where a
chunk's decay cannot be factored from its start), and by the guarded
path of a gate with no bound; the gate's two kinds; the triangular
inverse; the ops through a Program. The chunked form's kernel:
`test_kda_kernel.py`."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import kda

H, DK, DV = 2, 16, 8


def kda_recurrent(q, k, v, g, beta, lengths=None, qk_norm=True):
    """The recurrence token by token, a ``lax.scan`` of the op's own
    one-token update: what the chunked form has to equal. Shapes as
    ``kda_scan``. Lives here: no serving path runs it."""
    from jax import lax

    bsz, t, h, dk = q.shape
    q, k = kda._prepare(jnp.asarray(q), jnp.asarray(k), qk_norm)
    lens = (jnp.full((bsz,), t, jnp.int32) if lengths is None
            else jnp.asarray(lengths).reshape(-1).astype(jnp.int32))

    def body(s, inp):
        i, q_t, k_t, v_t, g_t, b_t = inp
        o, new = kda._update(s, q_t, k_t, v_t, g_t, b_t)
        live = (i < lens)[:, None, None, None]
        return jnp.where(live, new, s), o

    xs = (jnp.arange(t, dtype=jnp.int32),) + tuple(
        jnp.swapaxes(jnp.asarray(a, jnp.float32), 0, 1)
        for a in (q, k, v, g, beta))
    state, os = lax.scan(
        body, jnp.zeros((bsz, h, dk, v.shape[-1]), jnp.float32), xs)
    return jnp.swapaxes(os, 0, 1), state


def _inputs(bsz, t, seed=0, decay="mixed", h=H, dk=DK, dv=DV):
    r = np.random.default_rng(seed)
    q, k = (r.normal(size=(bsz, t, h, dk)).astype(np.float32)
            for _ in range(2))
    v = r.normal(size=(bsz, t, h, dv)).astype(np.float32)
    if decay == "bound":      # every channel at the bound, every token
        g = np.full((bsz, t, h, dk), -4.999, np.float32)
    elif decay == "deep":     # far below what 16 tokens could factor
        g = (-30.0 * r.uniform(size=(bsz, t, h, dk))).astype(np.float32)
    elif decay == "softplus":  # Kimi Linear's gate from its published
        # initialisation (a head's rate in [1, 16], a channel's step
        # log-uniform in [1e-3, 0.1]) through `kda_gate`, and a ROW of
        # decays below e^-40 a token
        g = np.asarray(kda.kda_gate(
            r.normal(size=(bsz, t, h * dk)).astype(np.float32),
            np.zeros((bsz, t, h), np.float32),
            np.log(r.uniform(1.0, 16.0, h)).astype(np.float32),
            np.log(np.expm1(np.exp(r.uniform(
                np.log(1e-3), np.log(0.1), h * dk)))).astype(np.float32),
            "softplus")[0]).copy()
        g[:, t // 3] = r.uniform(-100.0, -40.0, size=(bsz, h, dk))
    else:                     # fast and slow channels side by side
        g = (-5.0 * r.uniform(size=(bsz, t, h, dk)) ** 3).astype(np.float32)
    # beta in (0, 2) under the unbounded gate (`KDA_BETA_MAX`)
    top = 1.95 if decay == "softplus" else 0.95
    beta = r.uniform(0.05, top, size=(bsz, t, h)).astype(np.float32)
    return q, k, v, g, beta


def _both(args, lens, bound):
    with jax.default_matmul_precision("highest"):
        o, s = jax.jit(lambda *a: kda.kda_scan(*a, lower_bound=bound))(
            *args, lens)
        o_ref, s_ref = jax.jit(kda_recurrent)(*args, lens)
    t = args[0].shape[1]
    live = (np.arange(t)[None, :] < np.asarray(lens)[:, None])[
        :, :, None, None]
    return (np.where(live, o, 0.0), np.asarray(s),
            np.where(live, o_ref, 0.0), np.asarray(s_ref))


@pytest.mark.parametrize("t,lens,decay,bound", [
    (150, [150, 113], "mixed", -5.0),    # 2.3 chunks; padding mid-chunk
    (150, [150, 113], "bound", -5.0),    # the overflow case, factored
    (64, [64, 1], "mixed", -5.0),        # one whole chunk; one token
    (70, [70, 64], "bound", -5.0),
    (1100, [1100, 1030], "mixed", -5.0),  # > one block of 16 chunks
    (150, [150, 113], "mixed", None),    # the guarded path
    (150, [150, 40], "bound", None),
    (150, [150, 113], "deep", None),     # log-decays no bound could hold
    (150, [150, 113], "mixed", -8.0),    # a bound too low to factor: guarded
    # the softplus gate, beta in (0, 2), a row of decays below e^-40, a
    # prompt that ends inside a chunk and one inside a sub-chunk
    (150, [150, 113], "softplus", None),
    (1100, [1100, 70], "softplus", None),
], ids=["mixed", "at_bound", "one_chunk", "at_bound_70", "blocks",
        "guarded", "guarded_at_bound", "guarded_deep", "low_bound",
        "softplus_beta_2", "softplus_beta_2_blocks"])
def test_chunked_equals_token_by_token(t, lens, decay, bound):
    args = _inputs(2, t, seed=t, decay=decay)
    o, s, o_ref, s_ref = _both(args, np.array(lens, np.int32), bound)
    np.testing.assert_allclose(o, o_ref, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(s, s_ref, atol=1e-5, rtol=1e-5)
    assert np.isfinite(o).all() and np.isfinite(s).all()


def test_padding_leaves_the_state_alone():
    """Rows past a length decay nothing and write nothing: the state is
    the state after the last real token, whatever the padding holds."""
    args = _inputs(1, 100, seed=5)
    lens = np.array([37], np.int32)
    _, s, _, _ = _both(args, lens, -5.0)
    short = tuple(a[:, :37] for a in args)
    _, s_short, _, s_ref = _both(short, lens, -5.0)
    np.testing.assert_allclose(s, s_short, atol=1e-6)
    np.testing.assert_allclose(s, s_ref, atol=1e-5)
    noisy = tuple(np.concatenate([a[:, :37], 100.0 * a[:, 37:]], axis=1)
                  for a in args[:3]) + args[3:]
    _, s_noisy, _, _ = _both(noisy, lens, -5.0)
    np.testing.assert_allclose(s_noisy, s, atol=1e-6)


@pytest.mark.parametrize("decay", ["mixed", "bound", "softplus"])
def test_step_after_step_equals_the_scan(decay):
    """A prefill's state handed to the step, then steps: the same
    numbers as one scan over everything (under the softplus gate with
    beta in (0, 2): the guarded scan, then the same step)."""
    args = _inputs(2, 90, seed=9, decay=decay)
    o_all, s_all = kda_recurrent(*args)
    with jax.default_matmul_precision("highest"):
        _, state = kda.kda_scan(*(a[:, :70] for a in args), None,
                                None if decay == "softplus" else -5.0)
    outs = []
    for i in range(70, 90):
        o, state = kda.kda_step(*(a[:, i:i + 1] for a in args), state)
        outs.append(o)
    np.testing.assert_allclose(np.concatenate(outs, axis=1), o_all[:, 70:],
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(state, s_all, atol=1e-5, rtol=1e-5)


def test_qk_norm_is_part_of_the_op():
    args = _inputs(1, 20, seed=2)
    q, k = args[0], args[1]
    o, _ = kda_recurrent(*args)
    unit = lambda x: x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)  # noqa
    o2, _ = kda_recurrent(unit(q) * DK ** -0.5 * DK ** 0.5, unit(k),
                              *args[2:], qk_norm=False)
    np.testing.assert_allclose(o, o2, atol=1e-6)
    o3, _ = kda_recurrent(*args, qk_norm=False)
    assert np.abs(np.asarray(o3) - np.asarray(o)).max() > 1e-2


@pytest.mark.parametrize("kind", kda.KDA_GATES)
def test_gate_kinds(kind):
    r = np.random.default_rng(1)
    f = r.normal(size=(2, 5, H * DK)).astype(np.float32) * 3
    b = r.normal(size=(2, 5, H)).astype(np.float32)
    a_log = r.normal(size=(H,)).astype(np.float32) * 0.3
    dt = r.normal(size=(H * DK,)).astype(np.float32)
    g, beta = kda.kda_gate(f, b, a_log, dt, kind, -5.0)
    x = (f + dt).reshape(2, 5, H, DK) * 1.0
    a = np.exp(a_log)[None, None, :, None]
    want = (-5.0 / (1 + np.exp(-a * x)) if kind == "lower_bound_sigmoid"
            else -a * np.logaddexp(0.0, x))
    np.testing.assert_allclose(g, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(beta, 1 / (1 + np.exp(-b)), rtol=1e-5)
    # the write strength's factor: (0, 2) where the transition may have
    # a negative eigenvalue; the decay is the same
    g2, beta2 = kda.kda_gate(f, b, a_log, dt, kind, -5.0, beta_max=2.0)
    np.testing.assert_allclose(beta2, 2 / (1 + np.exp(-b)), rtol=1e-5)
    np.testing.assert_array_equal(g2, g)
    assert (np.asarray(g) <= 0).all()
    if kind == "lower_bound_sigmoid":
        assert (np.asarray(g) > -5.0).all()


@pytest.mark.parametrize("n", [1, 2, 16, 64])
def test_inverse_of_a_unit_lower_triangle(n):
    r = np.random.default_rng(n)
    m = np.tril(r.normal(size=(3, n, n)), -1) + np.eye(n)
    with jax.default_matmul_precision("highest"):
        inv = kda._inv_unit_lower(jnp.asarray(m, jnp.float32))
    np.testing.assert_allclose(inv, np.linalg.inv(m), atol=2e-4, rtol=2e-4)
    assert np.abs(np.triu(np.asarray(inv), 1)).max() == 0.0


def test_ops_through_a_program():
    """`kda_gate` -> `kda_scan` and `kda_step` as Program ops, against
    the functions."""
    import paddle_tpu as fluid
    from paddle_tpu import layers

    q, k, v, g, beta = _inputs(2, 40, seed=4)
    lens = np.array([40, 22], np.int32)
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        names = ("q", "k", "v", "g", "beta")
        vs = [layers.data(name=n, shape=list(a.shape), dtype="float32",
                          append_batch_size=False)
              for n, a in zip(names, (q, k, v, g, beta))]
        ln = layers.data(name="lens", shape=[2], dtype="int32",
                         append_batch_size=False)
        o, state = layers.kda_scan(*vs, ln, lower_bound=-5.0)
        one = [layers.slice(x, axes=[1], starts=[0], ends=[1]) for x in vs]
        o1, s1 = layers.kda_step(*one, state)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    feed = dict(zip(names, (q, k, v, g, beta)), lens=lens)
    got = exe.run(main_p, feed=feed, fetch_list=[o, state, o1, s1])
    o_ref, s_ref = kda_recurrent(q, k, v, g, beta, lens)
    live = (np.arange(40)[None, :] < lens[:, None])[:, :, None, None]
    np.testing.assert_allclose(np.where(live, got[0], 0),
                               np.where(live, o_ref, 0), atol=2e-3)
    np.testing.assert_allclose(got[1], s_ref, atol=2e-3)
    o1_ref, s1_ref = kda.kda_step(q[:, :1], k[:, :1], v[:, :1], g[:, :1],
                                  beta[:, :1], jnp.asarray(got[1]))
    np.testing.assert_allclose(got[2], o1_ref, atol=1e-5)
    np.testing.assert_allclose(got[3], s1_ref, atol=1e-5)


def test_a_gate_no_graph_builds_is_refused():
    with pytest.raises(ValueError, match="gate 'tanh' is not built"):
        kda.kda_gate(jnp.zeros((1, 1, 8)), jnp.zeros((1, 1, 2)),
                     jnp.zeros((2,)), jnp.zeros((8,)), "tanh")
