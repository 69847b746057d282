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
    # a window of rows lands at pos..pos + T - 1 (since PR 58: a round of
    # a model with a prediction layer); a ring still takes ONE row
    got = np.asarray(mla.mla_append(slab, jnp.ones((2, 2, 3)),
                                    jnp.asarray([0, 3])))
    np.testing.assert_array_equal(got[0, :2], np.ones((2, 3)))
    np.testing.assert_array_equal(got[1, 3:5], np.ones((2, 3)))
    with pytest.raises(ValueError, match="ONE row"):
        mla.mla_append(slab, jnp.zeros((2, 2, 3)), jnp.asarray([0, 0]),
                       ring=True)


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
    # id, (s, h, row, rank, dtype), lanes a block or None, whether ONE
    # array of that shape is read once (a slot's `rank` rows kept in VMEM)
    ("the-cell", (16384, 32, 320, 256, "float32"), mla._LATENT_BLOCK_LANES,
     True),
    ("short-slab", (256, 32, 320, 256, "float32"), 256, True),
    ("bfloat16", (16384, 32, 320, 256, "bfloat16"), None, False),
    ("row-no-sublane-tiles", (16384, 32, 321, 256, "float32"), None, False),
    ("rank-no-sublane-tiles", (16384, 32, 320, 250, "float32"), None, False),
    ("no-128-lane-block", (192, 32, 320, 256, "float32"), None, False),
    ("scores-over-budget", (16384, 128, 320, 256, "float32"), None, False),
    ("the-ling-cell", (16384, 32, 576, 512, "float32"),
     mla._LATENT_BLOCK_LANES, True),
    # 64 MiB of kept rows alone: the kernel, each live block read a pass
    ("kept-rows-over-the-cap", (32768, 32, 576, 512, "float32"),
     mla._LATENT_BLOCK_LANES, False),
]


@pytest.mark.parametrize("shape,lanes,once", [c[1:] for c in _RULE_CASES],
                         ids=[c[0] for c in _RULE_CASES])
def test_latent_kernel_rule_is_the_slabs_shape_type_and_device(
        monkeypatch, shape, lanes, once):
    """`block_positions` of `latent_view` answers from shape and type
    alone, and so does `kept_vmem_bytes` (which body attends ONE array:
    the one that reads it once, where a slot's summed rows fit);
    `decode_stream_rows` adds the device: the CPU takes the lax path at
    every shape, a TPU (stood in for by the decode kernels' own device
    rule, lifted) the kernel wherever the shape allows one."""
    view = mla.latent_view(*shape)
    assert DS.block_positions(view) == lanes
    assert (DS.kept_vmem_bytes(view) is not None) == once
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


@pytest.mark.parametrize("s,chosen,path", [
    (16384, False, "absorbed_kernel_once"),
    (32768, False, "absorbed_kernel"),   # kept rows over the cap
    (16384, True, "absorbed_kernel"),    # a choice of rows: one pass of its own
], ids=["kept", "over-the-cap", "chosen"])
def test_traces_counter_names_the_body_a_step_holds(monkeypatch, s, chosen,
                                                    path):
    """`paddle_tpu_mla_traces_total{path}` of a traced `mla_decode` at
    the Ling cell's widths (the device rule lifted, nothing run): once
    for the program, under the body its kernel has."""
    monkeypatch.setattr(KV, "_use_pallas_decode", lambda s, d: True)
    h, row, rank, dn, dv = 32, 576, 512, 128, 128

    def sd(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype)

    def counts():
        return {k["path"]: v for k, v in obs.MLA_TRACES.samples()}

    before = counts()
    out = jax.eval_shape(
        lambda q, slab, n, w, c: mla.mla_decode(q, slab, n, w, 0.1, c),
        sd(2, 1, h, dn + row - rank), sd(2, s, row), sd(2, dtype=jnp.int32),
        sd(rank, h * (dn + dv)), sd(2, s, dtype=jnp.bool_) if chosen else None)
    assert out.shape == (2, 1, h, dv)
    after = counts()
    moved = {k: after[k] - before.get(k, 0) for k in after
             if after[k] != before.get(k, 0)}
    assert moved == {path: 1}


# -- many heads, a group at a time; under a mask; over a window; a ring ---------
# (a latent layer under an indexer and a latent layer over a window:
# `tests/test_dots3_decode.py` has the model)

def _grouped_inputs(seed, t=24, h=H):
    r = np.random.default_rng(seed)

    def m(*shape):
        return jnp.asarray(r.normal(size=shape) * 0.3, jnp.float32)

    return {"c_q": m(B, t, RQ), "rows": m(B, t, RK + DR),
            "q_b": m(RQ, h * (DN + DR)), "kv_b": m(RK, h * (DN + DV)),
            "gate": jax.nn.sigmoid(m(B, t, h)), "o": m(h * DV, D),
            "mask": jnp.asarray(np.tril(r.random((B, t, t)) < 0.5)
                                | np.eye(t, dtype=bool)[None], jnp.int8)}


def _expanded(w, rot, a, seen, h=H):
    """`mla_q`'s up-projection, `mla_expand`, softmax attention over the
    keys `seen` (B | 1, T, T), the gate and the projection, all heads at
    once."""
    q = mla.mla_q(w["c_q"], None, None, w["q_b"], None, h, DR, 1e-6, rot)
    k, v = mla.mla_expand(w["rows"], w["kv_b"], h, DN)
    s = jnp.einsum("bthd,bshd->bhts", q, k) * a
    p = jax.nn.softmax(jnp.where(seen[:, None], s, -jnp.inf), axis=-1)
    ctx = jnp.einsum("bhts,bshd->bthd", p, v) * w["gate"][..., None]
    return jnp.matmul(ctx.reshape(ctx.shape[:2] + (-1,)), w["o"])


_GROUPED = [
    # id, heads a pass, window, mask, through the kernel (interpreted)
    ("causal-4-heads-at-once", 4, 0, False, False),
    ("causal-2-heads-a-pass", 2, 0, False, False),
    ("causal-3-does-not-divide-4", 3, 0, False, False),
    ("window-5", 2, 5, False, False),
    ("mask", 2, 0, True, False),
    ("kernel-causal", 2, 0, False, True),
    ("kernel-window-130", 2, 130, False, True),
    ("kernel-mask", 2, 0, True, True),
    ("kernel-mask-lengths", 2, 0, True, True, (300, 1024)),
    ("kernel-window-130-lengths", 2, 130, False, True, (1, 700)),
    ("lax-mask-lengths", 2, 0, True, False, (5, 24)),
]


@pytest.mark.parametrize("heads,window,masked,kernel,lengths",
                         [(c[1:] + (None,))[:5] for c in _GROUPED],
                         ids=[c[0] for c in _GROUPED])
def test_latent_prefill_is_the_expanded_attention_a_group_at_a_time(
        heads, window, masked, kernel, lengths):
    """`latent_prefill` (q, k and v of `heads` heads at a time, the
    group's rows of W_o added up) against all heads at once: causal,
    over a window, under a (query, key) mask; by the lax form and
    through the flash kernel (interpreted), its mask operand at 1,024
    positions: q-blocks of 256 against two key blocks of 512. Given
    the rows' `lengths`, every live row is what it was and the kernel
    leaves the q-blocks wholly past a row's length zeros."""
    t = (1024 if masked or lengths else 256) if kernel else 24
    w = _grouped_inputs(heads + window, t)
    rot = {"theta": 5e4, "interleave": True}
    a = float(DN + DR) ** -0.5
    at = np.arange(t)
    seen = (at[None, :] <= at[:, None])[None]
    if window:
        seen = seen & (at[None, :] > at[:, None] - window)[None]
    if masked:
        seen = seen & (np.asarray(w["mask"]) != 0)
    got = mla.latent_prefill(
        w["c_q"], w["rows"], w["q_b"], w["kv_b"], w["gate"], w["o"], H, DN,
        a, rot, window=window, mask=w["mask"] if masked else None,
        lengths=None if lengths is None else jnp.asarray(lengths, jnp.int32),
        heads=heads, interpret=kernel)
    assert got.shape == (B, t, D)
    with jax.default_matmul_precision("highest"):
        want = _expanded(w, rot, a, jnp.asarray(seen))
    if lengths is not None:
        live = (at[None, :] < np.asarray(lengths)[:, None])[..., None]
        if kernel:
            q_rows = 256 if masked else 512  # a q-block of the kernel
            dead = -(-np.asarray(lengths) // q_rows) * q_rows
            for b_, d0 in enumerate(dead):
                assert not np.asarray(got[b_, d0:]).any()
                assert np.asarray(got[b_, :d0]).all()
        got, want = got * live, want * live
    if kernel:  # bfloat16 operands: a key wrongly seen or hidden is 1e-1
        err = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert err < 1e-2, err
        return
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


_CHOSEN_KERNEL = [
    # id, slots' lengths, positions a slot, lanes a block, share chosen
    ("one-block", (0, 1, 100, 128), 128, 128, 0.5),
    ("blocks-of-128", (0, 130, 512, 257), 512, 128, 0.4),
    ("blocks-of-256-few-chosen", (512, 3, 256, 300), 512, 256, 0.02),
    ("all-chosen", (512, 511, 129, 0), 512, 128, 1.0),
]


@pytest.mark.parametrize("lens,s,lanes,share",
                         [c[1:] for c in _CHOSEN_KERNEL],
                         ids=[c[0] for c in _CHOSEN_KERNEL])
def test_chosen_rows_kernel_is_the_lax_form_under_the_mask(lens, s, lanes,
                                                           share):
    """`pallas_chosen_attend` (interpreted: one pass over a slot's live
    blocks, an online softmax, the rows not chosen masked inside)
    against `_latent_attend_lax` under the same choice: slots of no live
    row, of part of a block, of every block; a chosen row past a slot's
    length is not seen; a slot that chose nothing gives zeros."""
    r = np.random.default_rng(s + lanes)
    h, row, rank = 16, 80, 64
    q = jnp.asarray(r.normal(size=(len(lens), h, row)) * 0.3, jnp.float32)
    slab = jnp.asarray(r.normal(size=(len(lens), s, row)), jnp.float32)
    chosen = r.random((len(lens), s)) < share
    chosen[-1, :] = share == 1.0  # the last slot chose nothing, or all
    lens = jnp.asarray(lens, jnp.int32)
    want = mla._latent_attend_lax(q, slab, lens, rank, jnp.asarray(chosen))
    got = mla.pallas_chosen_attend(q, slab, lens, jnp.asarray(chosen), rank,
                                   "ptpu.test_step", block_s=lanes,
                                   interpret=True)
    assert got.shape == want.shape == (len(lens), h, rank)
    for i in range(len(lens)):
        if not (chosen[i, :int(lens[i])]).any():
            assert not np.asarray(got[i]).any()
    # bfloat16 operands, as the TPU's default precision rounds the lax form's
    err = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert err < 1e-2, err


def test_chosen_rows_kernel_rule(monkeypatch):
    """Under a choice the absorbed attention of ANY number of heads has
    a kernel wherever the slab's layout has one (no scores wait between
    passes: the two-pass rule refuses 128 heads on 16,384 positions),
    and `mla_decode` takes it on the device that has it."""
    shape = (16384, 128, 576, 512, "float32")
    assert DS.block_positions(mla.latent_view(*shape)) is None
    assert DS.block_positions(mla.chosen_view(*shape)) == 1024
    assert DS.block_positions(mla.chosen_view(513, 64, 1088, 1024,
                                               "float32")) is None
    assert KV.decode_stream_rows(mla.chosen_view(*shape)) is None  # the CPU
    with pytest.raises(ValueError, match="no kernel for a slab"):
        mla.pallas_chosen_attend(
            jnp.zeros((1, 4, 80)), jnp.zeros((1, 192, 80)),
            jnp.zeros((1,), jnp.int32), jnp.zeros((1, 192), bool), 64, "x")


@pytest.mark.parametrize("lens,lanes", [((0, 130, 512), 128),
                                        ((512, 1, 256), 256),
                                        ((3, 300, 0), 512)])
def test_step_scores_kernel_is_the_lax_products(lens, lanes):
    """`pallas_step_scores` (interpreted: a slot's live blocks of index
    keys read once, all heads' products of a block at once) against
    `index_scores` of one query row a slot, on the live blocks; zeros
    past them."""
    from paddle_tpu.ops import dsa

    r = np.random.default_rng(lanes)
    b, s, j, d = len(lens), 512, 8, 128
    q_i = jnp.asarray(r.normal(size=(b, 1, j, d)), jnp.float32)
    w = jnp.asarray(r.normal(size=(b, 1, j)), jnp.float32)
    keys = jnp.asarray(r.normal(size=(b, s, d)), jnp.float32)
    want = np.asarray(dsa.index_scores(q_i, w, keys)[:, 0])
    got = np.asarray(dsa.pallas_step_scores(
        q_i[:, 0], w[:, 0], keys, jnp.asarray(lens, jnp.int32),
        block_s=lanes, interpret=True))
    assert got.shape == (b, s)
    for i, n in enumerate(lens):
        live = -(-n // lanes) * lanes
        assert not got[i, live:].any()
        if live:  # bfloat16 operands, float32 sums
            np.testing.assert_allclose(got[i, :live], want[i, :live],
                                       atol=0.01 * np.abs(want[i]).max())
    assert dsa.step_block(16384, 128, "float32") is None  # the CPU


@pytest.mark.parametrize("lengths", [(40,), (64,), (1,), (17, 33)])
def test_prefill_mask_leaves_the_rows_past_the_longest_prompt(lengths):
    """`prefill_mask` given the prompts' lengths: the blocks of query
    rows that hold a live row of any prompt are what they are without
    the lengths, the blocks past the longest prompt stay 0."""
    from paddle_tpu.ops import dsa

    r = np.random.default_rng(sum(lengths))
    b, t, j, d, rows, k = len(lengths), 64, 4, 8, 16, 12
    q_i = jnp.asarray(r.normal(size=(b, t, j, d)), jnp.float32)
    w = jnp.asarray(r.normal(size=(b, t, j)), jnp.float32)
    k_i = jnp.asarray(r.normal(size=(b, t, d)), jnp.float32)
    whole = np.asarray(dsa.prefill_mask(q_i, w, k_i, k, rows=rows))
    got = np.asarray(dsa.prefill_mask(
        q_i, w, k_i, k, jnp.asarray(lengths, jnp.int32), rows=rows))
    live = -(-max(lengths) // rows) * rows
    np.testing.assert_array_equal(got[:, :live], whole[:, :live])
    assert not got[:, live:].any()
    assert whole[:, -1].sum() == b * k


@pytest.mark.parametrize("case", ["chosen", "ring-not-full", "ring-wrapped"])
def test_absorbed_attention_under_a_choice_and_over_a_ring(case):
    """`mla_decode` over the CHOSEN rows of a slab == the expanded
    attention over those rows; over a RING (the last rows at position
    mod window, `mla_append(ring=True)`) == over the last `window`
    rows, whatever their order."""
    r = np.random.default_rng(len(case))
    w = _grouped_inputs(3, T)
    rot = {"theta": 5e4, "interleave": True}
    a = float(DN + DR) ** -0.5
    q = mla.mla_q(w["c_q"], None, None, w["q_b"], None, H, DR, 1e-6, rot)
    k, v = mla.mla_expand(w["rows"], w["kv_b"], H, DN)
    at = np.arange(T)
    if case == "chosen":
        t = T - 2
        chosen = r.random((B, 16)) < 0.5
        chosen[:, t] = True
        slab = jnp.zeros((B, 16, RK + DR)).at[:, :T].set(w["rows"])
        got = mla.mla_decode(q[:, t:t + 1], slab, jnp.asarray([t + 1] * B),
                             w["kv_b"], a, chosen=jnp.asarray(chosen))
        seen = chosen[:, :T] & (at <= t)[None]
    else:
        window, t = 5, (3 if case == "ring-not-full" else T - 1)
        ring = jnp.full((B, window, RK + DR), 1e6, jnp.float32)
        for p in range(t + 1):
            ring = mla.mla_append(ring, w["rows"][:, p:p + 1],
                                  jnp.asarray([p] * B), ring=True)
        for p in range(max(t + 1 - window, 0), t + 1):
            np.testing.assert_array_equal(ring[:, p % window],
                                          w["rows"][:, p])
        got = mla.mla_decode(q[:, t:t + 1], ring, jnp.asarray([t + 1] * B),
                             w["kv_b"], a)
        seen = np.broadcast_to((at <= t) & (at > t - window), (B, T))
    s = jnp.einsum("bhd,bshd->bhs", q[:, t], k) * a
    p = jax.nn.softmax(jnp.where(jnp.asarray(seen)[:, None], s, -jnp.inf),
                       axis=-1)
    want = jnp.einsum("bhs,bshd->bhd", p, v)
    np.testing.assert_allclose(got[:, 0], want, rtol=2e-5, atol=2e-6)


def test_rescaled_latent_rows_and_the_new_ops_through_the_layers_api():
    """`mla_kv(rescale=)` multiplies the normalised latent alone; and
    `latent_prefill`, `dsa_index_keys`, `dsa_mask` in a Program against
    the functions."""
    from paddle_tpu.ops import dsa

    w = _weights(5)
    rot = {"theta": 5e4, "interleave": True}
    plain = mla.mla_kv(w["u"], w["kv_a"], w["g_kv"], None, DR, 1e-6, rot)
    scaled = mla.mla_kv(w["u"], w["kv_a"], w["g_kv"], None, DR, 1e-6, rot,
                        rescale=2.0)
    np.testing.assert_allclose(scaled[..., :RK], 2.0 * plain[..., :RK],
                               rtol=1e-6)
    np.testing.assert_array_equal(scaled[..., RK:], plain[..., RK:])
    g = _grouped_inputs(9, T)
    r = np.random.default_rng(1)
    j, di, topk = 2, 8, 4
    irot = {"theta": 1e4, "rotary_dim": 4}
    feed = {"u": np.asarray(w["u"]), "c_q": np.asarray(g["c_q"]),
            "rows": np.asarray(g["rows"]), "q_b": np.asarray(g["q_b"]),
            "kv_b": np.asarray(g["kv_b"]), "gate": np.asarray(g["gate"]),
            "o": np.asarray(g["o"]),
            "w_ik": r.normal(size=(D, di)).astype(np.float32),
            "gain": np.ones((di,), np.float32),
            "bias": np.zeros((di,), np.float32),
            "w_iq": r.normal(size=(RQ, j * di)).astype(np.float32),
            "w_iw": r.normal(size=(D, j)).astype(np.float32)}
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        v = {n: layers.data(name=n, shape=list(a.shape), dtype=str(a.dtype),
                            append_batch_size=False)
             for n, a in feed.items()}
        keys = layers.dsa_index_keys(v["u"], v["w_ik"], v["gain"], v["bias"],
                                     irot)
        mask = layers.dsa_mask(v["c_q"], v["u"], v["w_iq"], v["w_iw"], keys,
                               j, topk, irot)
        out = layers.latent_prefill(
            v["c_q"], v["rows"], v["q_b"], v["kv_b"], v["o"], H, DN, 0.3, rot,
            gate=v["gate"], mask=mask, scope="ptpu.dsa_attend")
        assert tuple(keys.shape) == (B, T, di)
        assert tuple(mask.shape) == (B, T, T)
        assert tuple(out.shape) == (B, T, D)
    got = fluid.Executor(fluid.CPUPlace()).run(
        main, feed=feed, fetch_list=[keys, mask, out])
    want_keys = dsa.index_keys(w["u"], feed["w_ik"], feed["gain"],
                               feed["bias"], None, 1e-5, irot)
    q_i, iw = dsa.index_queries(g["c_q"], w["u"], feed["w_iq"], feed["w_iw"],
                                None, j, irot)
    want_mask = dsa.prefill_mask(q_i, iw, want_keys, topk)
    np.testing.assert_allclose(got[0], want_keys, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(got[1], want_mask)
    assert (np.asarray(got[1]).sum(-1)
            == np.minimum(np.arange(T) + 1, topk)[None]).all()
    np.testing.assert_allclose(got[2], mla.latent_prefill(
        g["c_q"], g["rows"], g["q_b"], g["kv_b"], g["gate"], g["o"], H, DN,
        0.3, rot, mask=want_mask), rtol=1e-5, atol=1e-6)
