"""The ops a latent-attention (MLA) block adds (`paddle_tpu/ops/mla.py`,
the interleaved rotation and the query scale of `ops/rope.py`), each
against a plain statement of it: the ABSORBED path against the EXPANDED
one on the same latent rows, the rotation of the pairs (2i, 2i+1)
against a rotation of complex numbers, the position-dependent query
scale at an original context small enough that positions pass it, the
softmax scale `a`, the one-row append, and the ops through the layers
API."""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu import observability as obs
from paddle_tpu.ops import decode_stream as DS
from paddle_tpu.ops import kv_cache as KV
from paddle_tpu.ops import mla, rope

B, T, D, H = 2, 12, 32, 4
RQ, RK, DN, DR, DV = 16, 8, 4, 4, 8
YARN = {"factor": 8.0, "original_max_position": 4, "beta_fast": 32.0,
        "beta_slow": 1.0}
ROT = {"theta": 10000.0, "yarn": YARN, "attention_factor": 1.0,
       "interleave": True, "scale_beta": 0.1}


def _weights(seed=0):
    r = np.random.default_rng(seed)

    def m(*shape):
        return jnp.asarray(r.normal(size=shape) * 0.3, jnp.float32)

    return {"u": m(B, T, D), "q_a": m(D, RQ),
            "g_q": jnp.asarray(r.normal(1.0, 0.1, RQ), jnp.float32),
            "q_b": m(RQ, H * (DN + DR)), "kv_a": m(D, RK + DR),
            "g_kv": jnp.asarray(r.normal(1.0, 0.1, RK), jnp.float32),
            "kv_b": m(RK, H * (DN + DV))}


def _causal(q, k, v, a):
    s = jnp.einsum("bthd,bshd->bhts", q, k) * a
    seen = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p, v)


def test_absorbed_equals_expanded_on_the_same_latent_rows():
    """A decode step at every position t of a sequence, over the slab
    that holds the prefill's latent rows [0, t], against row t of the
    expanded causal attention: the same numbers to float32 rounding,
    and no row past a slot's length is read (the slab is longer than
    the text and holds garbage there)."""
    w = _weights()
    q = mla.mla_q(w["u"], w["q_a"], w["g_q"], w["q_b"], None, H, DR, 1e-6,
                  ROT)
    rows = mla.mla_kv(w["u"], w["kv_a"], w["g_kv"], None, DR, 1e-6, ROT)
    assert q.shape == (B, T, H, DN + DR) and rows.shape == (B, T, RK + DR)
    k, v = mla.mla_expand(rows, w["kv_b"], H, DN)
    assert k.shape == (B, T, H, DN + DR) and v.shape == (B, T, H, DV)
    # k_r is ONE row for all heads
    np.testing.assert_array_equal(k[:, :, 0, DN:], k[:, :, H - 1, DN:])
    a = 0.37
    want = _causal(q, k, v, a)
    slab = jnp.full((B, 16, RK + DR), 1e6, jnp.float32).at[:, :T].set(rows)
    for t in (0, 3, T - 1):
        got = mla.mla_decode(q[:, t:t + 1], slab,
                             jnp.asarray([t + 1] * B), w["kv_b"], a)
        assert got.shape == (B, 1, H, DV)
        np.testing.assert_allclose(got[:, 0], want[:, t], rtol=2e-5,
                                   atol=2e-6)


def test_decode_rows_at_the_slots_own_positions_match_the_prefill():
    """A decode step's q and latent row, made at positions handed in
    (each slot its own), are the prefill's at those positions."""
    w = _weights(1)
    q = mla.mla_q(w["u"], w["q_a"], w["g_q"], w["q_b"], None, H, DR, 1e-6,
                  ROT)
    rows = mla.mla_kv(w["u"], w["kv_a"], w["g_kv"], None, DR, 1e-6, ROT)
    at = np.array([5, 9])
    one = jnp.stack([w["u"][i, at[i]] for i in range(B)])[:, None]
    q1 = mla.mla_q(one, w["q_a"], w["g_q"], w["q_b"], jnp.asarray(at), H,
                   DR, 1e-6, ROT)
    r1 = mla.mla_kv(one, w["kv_a"], w["g_kv"], jnp.asarray(at), DR, 1e-6,
                    ROT)
    for i in range(B):
        np.testing.assert_allclose(q1[i, 0], q[i, at[i]], rtol=1e-5,
                                   atol=1e-6)
        np.testing.assert_allclose(r1[i, 0], rows[i, at[i]], rtol=1e-5,
                                   atol=1e-6)


@pytest.mark.parametrize("yarn", [None, YARN], ids=["plain", "yarn"])
def test_interleaved_rotation_is_a_rotation_of_complex_numbers(yarn):
    """Channel 2i is the real and 2i+1 the imaginary part of the i-th
    complex number, turned by p * inv_freq_i; the half-split convention
    on the de-interleaved row gives the same numbers, de-interleaved."""
    r = np.random.default_rng(2)
    x = r.normal(size=(2, 9, 3, 8)).astype(np.float32)
    inv = rope.rope_inv_freq(8, 10000.0, yarn)
    got = np.asarray(rope.rope(jnp.asarray(x), None, inv, interleave=True))
    z = x[..., 0::2].astype(np.float64) + 1j * x[..., 1::2]
    ang = np.arange(9)[None, :, None, None] * inv[None, None, None, :]
    zr = z * np.exp(1j * ang)
    np.testing.assert_allclose(got[..., 0::2], zr.real, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got[..., 1::2], zr.imag, rtol=1e-5, atol=1e-6)
    halves = np.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    split = np.asarray(rope.rope(jnp.asarray(halves), None, inv))
    np.testing.assert_allclose(split[..., :4], got[..., 0::2], rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(split[..., 4:], got[..., 1::2], rtol=1e-5,
                               atol=1e-6)
    # a rotation keeps each pair's length
    np.testing.assert_allclose(got[..., 0::2] ** 2 + got[..., 1::2] ** 2,
                               x[..., 0::2] ** 2 + x[..., 1::2] ** 2,
                               rtol=1e-4)


def test_query_scale_passes_one_past_the_original_context():
    """1 below `original_max_position`, 1 + beta ln(1 + floor(p / it))
    from there on: positions 0..11 at an original context of 4."""
    got = np.asarray(rope.query_scale(None, 12, 0.1, 4))[0]
    want = [1.0] * 4 + [1 + 0.1 * math.log(2)] * 4 + [
        1 + 0.1 * math.log(3)] * 4
    np.testing.assert_allclose(got, want, rtol=1e-6)
    at = np.asarray(rope.query_scale(jnp.asarray([3, 8]), 1, 0.1, 4))
    np.testing.assert_allclose(at[:, 0], [1.0, 1 + 0.1 * math.log(3)],
                               rtol=1e-6)
    # the published sizes: 1.0693 at position 12,000 of 8,192
    big = float(rope.query_scale(jnp.asarray([12000]), 1, 0.1, 8192)[0, 0])
    assert abs(big - 1.0693147) < 1e-6
    # mla_q applies it to the WHOLE query row, after the rotation
    w = _weights(3)
    with_scale = mla.mla_q(w["u"], w["q_a"], w["g_q"], w["q_b"], None, H,
                           DR, 1e-6, ROT)
    without = mla.mla_q(w["u"], w["q_a"], w["g_q"], w["q_b"], None, H, DR,
                        1e-6, dict(ROT, scale_beta=0.0))
    np.testing.assert_allclose(
        with_scale, without * np.asarray(rope.query_scale(
            None, T, 0.1, 4))[:, :, None, None], rtol=1e-6)
    np.testing.assert_array_equal(with_scale[:, :4], without[:, :4])


def test_softmax_scale_is_the_yarn_rule():
    """a = qk^-0.5 x (0.1 mscale_all_dim ln(factor) + 1)^2: 0.1950 at the
    published sizes; qk^-0.5 with no YaRN factor or no mscale_all_dim."""
    assert abs(mla.softmax_scale(128, 128.0, 1.0) - 0.19497) < 1e-5
    assert mla.softmax_scale(128) == 128 ** -0.5
    assert mla.softmax_scale(128, 128.0, 0.0) == 128 ** -0.5
    assert mla.softmax_scale(128, 1.0, 1.0) == 128 ** -0.5


def test_append_writes_one_row_a_slot_at_its_position():
    slab = jnp.arange(2 * 6 * 3, dtype=jnp.float32).reshape(2, 6, 3)
    row = jnp.asarray([[[-1.0, -2.0, -3.0]], [[-4.0, -5.0, -6.0]]])
    got = np.asarray(mla.mla_append(slab, row, jnp.asarray([4, 0])))
    want = np.asarray(slab).copy()
    want[0, 4], want[1, 0] = [-1, -2, -3], [-4, -5, -6]
    np.testing.assert_array_equal(got, want)
    # a position past the slab's end is held to its last row
    got = np.asarray(mla.mla_append(slab, row, jnp.asarray([9, 5])))
    np.testing.assert_array_equal(got[0, 5], [-1, -2, -3])
    with pytest.raises(ValueError, match="ONE row"):
        mla.mla_append(slab, jnp.zeros((2, 2, 3)), jnp.asarray([0, 0]))


def test_ops_through_the_layers_api_and_the_traces_counter():
    """The five ops in a Program, prefill and one decode step, against
    the functions; `paddle_tpu_mla_traces_total` counts a trace of each
    path."""
    w = _weights(4)
    rot = dict(ROT)
    before = {k["path"]: v for k, v in obs.MLA_TRACES.samples()}

    def data(name, a):
        return layers.data(name=name, shape=list(a.shape), dtype=str(a.dtype),
                           append_batch_size=False)

    lens = np.array([T - 1, T - 3], np.int32)
    slab0 = np.zeros((B, 16, RK + DR), np.float32)
    one = np.stack([np.asarray(w["u"])[i, lens[i]] for i in range(B)])[:, None]
    feed = {"u": np.asarray(w["u"]), "one": one, "lens": lens,
            "slab": slab0}
    feed.update({n: np.asarray(w[n]) for n in
                 ("q_a", "g_q", "q_b", "kv_a", "g_kv", "kv_b")})
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        v = {n: data(n, a) for n, a in feed.items()}
        q = layers.mla_q(v["u"], v["q_a"], v["g_q"], v["q_b"], H, DR, rot)
        rows = layers.mla_kv(v["u"], v["kv_a"], v["g_kv"], DR, rot)
        k, val = layers.mla_expand(rows, v["kv_b"], H, DN)
        assert tuple(q.shape) == (B, T, H, DN + DR)
        assert tuple(rows.shape) == (B, T, RK + DR)
        assert tuple(k.shape) == (B, T, H, DN + DR)
        assert tuple(val.shape) == (B, T, H, DV)
        q1 = layers.mla_q(v["one"], v["q_a"], v["g_q"], v["q_b"], H, DR, rot,
                          positions=v["lens"])
        r1 = layers.mla_kv(v["one"], v["kv_a"], v["g_kv"], DR, rot,
                           positions=v["lens"])
        slab = layers.mla_append(v["slab"], r1, v["lens"])
        out = layers.mla_decode(q1, slab, v["lens"], v["kv_b"], 0.5)
        assert tuple(out.shape) == (B, 1, H, DV)
    exe = fluid.Executor(fluid.CPUPlace())
    got = exe.run(main, feed=feed, fetch_list=[q, rows, k, val, slab, out])
    want_q = mla.mla_q(w["u"], w["q_a"], w["g_q"], w["q_b"], None, H, DR,
                       1e-6, rot)
    want_rows = mla.mla_kv(w["u"], w["kv_a"], w["g_kv"], None, DR, 1e-6, rot)
    np.testing.assert_allclose(got[0], want_q, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got[1], want_rows, rtol=1e-5, atol=1e-6)
    want_k, want_v = mla.mla_expand(want_rows, w["kv_b"], H, DN)
    np.testing.assert_allclose(got[2], want_k, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got[3], want_v, rtol=1e-5, atol=1e-6)
    # the appended row is the prefill's row at that position
    for i in range(B):
        np.testing.assert_allclose(got[4][i, lens[i]],
                                   np.asarray(want_rows)[i, lens[i]],
                                   rtol=1e-5, atol=1e-6)
    after = {k["path"]: v for k, v in obs.MLA_TRACES.samples()}
    for path in ("expanded", "absorbed"):
        assert after[path] - before.get(path, 0) >= 1


# -- a query with no bottleneck, a query/key head wider than the value head -----

def test_query_without_a_bottleneck_is_one_projection():
    w = _weights(3)
    r = np.random.default_rng(4)
    w_q = jnp.asarray(r.normal(size=(D, H * (DN + DR))) * 0.3, jnp.float32)
    rot = {"theta": 6e6, "interleave": True}
    q = mla.mla_q(w["u"], None, None, w_q, None, H, DR, 1e-6, rot)
    assert q.shape == (B, T, H, DN + DR)
    plain = jnp.matmul(w["u"], w_q).reshape(B, T, H, DN + DR)
    np.testing.assert_allclose(q[..., :DN], plain[..., :DN], rtol=1e-6)
    # position 0 is not turned; every later one is, pair by pair
    np.testing.assert_allclose(q[:, 0], plain[:, 0], rtol=1e-6, atol=1e-7)
    want = rope.rope(plain[..., DN:], None, rope.rope_inv_freq(DR, 6e6),
                     1.0, True)
    np.testing.assert_allclose(q[..., DN:], want, rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("dq,dv", [(24, 16), (192, 128), (16, 24)],
                         ids=["24/16", "192/128", "16/24"])
def test_unlike_width_expanded_attention_is_plain_softmax_attention(dq, dv):
    """`mla_attend` (zero channels up to one lane-aligned width, the
    flash dispatch, the first `dv` channels kept) against the softmax
    written out."""
    r = np.random.default_rng(dq)
    q, k = (jnp.asarray(r.normal(size=(2, 20, 3, dq)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(r.normal(size=(2, 20, 3, dv)), jnp.float32)
    a = float(dq) ** -0.5
    got = mla.mla_attend(q, k, v, a)
    assert got.shape == (2, 20, 3, dv)
    with jax.default_matmul_precision("highest"):
        want = _causal(q, k, v, a)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_absorbed_equals_expanded_at_unlike_widths():
    """A head of 8 + 4 query/key channels over 6 value channels, no
    query bottleneck: a decode step over the slab == the row of the
    expanded attention."""
    r = np.random.default_rng(8)
    dn, dr, dv, rk = 8, 4, 6, 8

    def m(*shape):
        return jnp.asarray(r.normal(size=shape) * 0.3, jnp.float32)

    u, w_q, w_kva, w_kvb = (m(B, T, D), m(D, H * (dn + dr)), m(D, rk + dr),
                            m(rk, H * (dn + dv)))
    rot = {"theta": 6e6, "interleave": True}
    q = mla.mla_q(u, None, None, w_q, None, H, dr, 1e-6, rot)
    rows = mla.mla_kv(u, w_kva, jnp.ones((rk,)), None, dr, 1e-6, rot)
    k, v = mla.mla_expand(rows, w_kvb, H, dn)
    assert k.shape == (B, T, H, dn + dr) and v.shape == (B, T, H, dv)
    a = float(dn + dr) ** -0.5
    want = mla.mla_attend(q, k, v, a)
    slab = jnp.full((B, 16, rk + dr), 1e6, jnp.float32).at[:, :T].set(rows)
    for t in (0, 5, T - 1):
        got = mla.mla_decode(q[:, t:t + 1], slab, jnp.asarray([t + 1] * B),
                             w_kvb, a)
        np.testing.assert_allclose(got[:, 0], want[:, t], rtol=2e-5,
                                   atol=2e-6)


# -- the absorbed attention's kernel (`ptpu.mla_latent_attn`) ------------------
# (its parity with the lax form, through `mla_decode` and at the Ling
# cell's row of 576: `test_decode_stream.py`, the `latent-` cases)

_RULE_CASES = [
    # id, (s, h, row, rank, dtype), lanes a block or None
    ("the-cell", (16384, 32, 320, 256, "float32"), mla._LATENT_BLOCK_LANES),
    ("short-slab", (256, 32, 320, 256, "float32"), 256),
    ("bfloat16", (16384, 32, 320, 256, "bfloat16"), None),
    ("row-no-sublane-tiles", (16384, 32, 321, 256, "float32"), None),
    ("rank-no-sublane-tiles", (16384, 32, 320, 250, "float32"), None),
    ("no-128-lane-block", (192, 32, 320, 256, "float32"), None),
    ("scores-over-budget", (16384, 128, 320, 256, "float32"), None),
]


@pytest.mark.parametrize("shape,lanes", [c[1:] for c in _RULE_CASES],
                         ids=[c[0] for c in _RULE_CASES])
def test_latent_kernel_rule_is_the_slabs_shape_type_and_device(
        monkeypatch, shape, lanes):
    """`block_positions` of `latent_view` answers from shape and type
    alone; `decode_stream_rows` adds the device: the CPU takes the lax path at
    every shape, a TPU (stood in for by the decode kernels' own device
    rule, lifted) the kernel wherever the shape allows one."""
    view = mla.latent_view(*shape)
    assert DS.block_positions(view) == lanes
    assert KV.decode_stream_rows(view) is None  # the CPU
    monkeypatch.setattr(
        KV, "_use_pallas_decode",
        lambda s, d: d % 128 == 0 and s % 128 == 0 and s >= 128)
    assert KV.decode_stream_rows(view) == lanes
    if lanes is None:
        with pytest.raises(ValueError, match="the lax path attends it"):
            mla.pallas_latent_attend(
                jnp.zeros((1, shape[1], shape[2]), jnp.float32),
                jnp.zeros((1, shape[0], shape[2]), shape[4]),
                jnp.zeros((1,), jnp.int32), shape[3])
