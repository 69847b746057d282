"""Differential attention and the gated memory unit (`ops/diff_attn.py`)
against plain `jax.numpy` statements of them, written from the papers'
equations with NO pairing trick (heads split even/odd, two softmaxes, a
subtraction): the full-sequence op, causal and at a window of 8; one
token over a slab and over a ring that has wrapped; cross attention over
another layer's rows; the GMU; the Mamba mixer's memory; infer rules
and scopes."""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.ops import decode_stream as DS
from paddle_tpu.ops import diff_attn as D
from paddle_tpu.ops import kv_cache as KV

B, T, H, HKV, DH = 2, 21, 8, 4, 8
LAM0 = D.lambda_init(3)


def _rng(*shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=shape).astype(np.float32))


@pytest.fixture(scope="module")
def parts():
    q = _rng(B, T, H, DH, seed=1)
    k = _rng(B, T, HKV * DH, seed=2)
    v = _rng(B, T, HKV * DH, seed=3)
    lams = [0.3 * _rng(DH, seed=4 + i) for i in range(4)]
    gain = 1.0 + 0.1 * _rng(2 * DH, seed=9)
    return q, k, v, lams, gain


def plain(q, k, v, lams, gain, seen, lam0=LAM0, eps=1e-5):
    """q (B, Tq, H, dh), k/v (B, S, Hkv, dh), seen (B, Tq, S) bool ->
    (B, Tq, H/2, 2 dh): the equations as the paper writes them."""
    lq1, lk1, lq2, lk2 = lams
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam0
    q1, q2 = q[:, :, 0::2], q[:, :, 1::2]
    k1, k2, v1, v2 = k[:, :, 0::2], k[:, :, 1::2], v[:, :, 0::2], v[:, :, 1::2]
    g = q1.shape[2] // k1.shape[2]
    outs = []
    for j in range(q1.shape[2]):
        p = j // g
        vv = jnp.concatenate([v1[:, :, p], v2[:, :, p]], axis=-1)
        a = []
        for qq, kk in ((q1[:, :, j], k1[:, :, p]), (q2[:, :, j], k2[:, :, p])):
            s = jnp.einsum("btd,bsd->bts", qq, kk,
                           precision="highest") / math.sqrt(q.shape[-1])
            a.append(jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1))
        o = jnp.einsum("bts,bsd->btd", a[0] - lam * a[1], vv,
                       precision="highest")
        o = gain * o * jax.lax.rsqrt(
            jnp.mean(jnp.square(o), -1, keepdims=True) + eps) * (1 - lam0)
        outs.append(o)
    return jnp.stack(outs, axis=2)


def _heads(x):
    return x.reshape(x.shape[:2] + (HKV, DH))


def _lam(lams):
    return D.diff_lambda(*lams, LAM0)


@pytest.mark.parametrize("window", [0, 8])
def test_full_sequence_matches_the_equations(parts, window):
    q, k, v, lams, gain = parts
    row = jnp.arange(T)[:, None]
    col = jnp.arange(T)[None, :]
    seen = col <= row
    if window:
        seen = seen & (col > row - window)
    want = plain(q, _heads(k), _heads(v), lams, gain, seen[None])
    with jax.default_matmul_precision("highest"):
        got = D.diff_attention(q, k, v, _lam(lams), gain, LAM0,
                               window=window)
    assert got.shape == (B, T, H // 2, 2 * DH)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_pairing_is_not_grouped_queries(parts):
    """Query head 2j reads key 2(j // g), head 2j + 1 key 2(j // g) + 1:
    against keys whose odd heads are zeroed only the odd queries'
    softmaxes go flat."""
    q, k, v, lams, gain = parts
    qp = D.pair_queries(q)
    assert qp.shape == (B, T, H, 2 * DH)
    np.testing.assert_array_equal(qp[:, :, 0::2, DH:], 0)
    np.testing.assert_array_equal(qp[:, :, 1::2, :DH], 0)
    np.testing.assert_array_equal(qp[:, :, 0::2, :DH], q[:, :, 0::2])
    np.testing.assert_array_equal(qp[:, :, 1::2, DH:], q[:, :, 1::2])


@pytest.mark.parametrize("lens", [(5, 21), (0, 13)])
def test_one_token_over_a_slab(parts, lens):
    """The last query of each row against a slab of S = 32 rows of which
    `lens` are live (a free slot gives zeros, not the mean of garbage)."""
    q, k, v, lams, gain = parts
    s = 32
    pad = lambda a: jnp.pad(a, [(0, 0), (0, s - T), (0, 0)],
                            constant_values=7.0)
    lens = jnp.asarray(lens, jnp.int32)
    q1 = jnp.stack([q[b, max(int(n) - 1, 0)] for b, n in
                    enumerate(lens)])[:, None]
    with jax.default_matmul_precision("highest"):
        got = D.diff_decode_attention(q1, pad(k), pad(v), lens, _lam(lams),
                                      gain, LAM0)
    seen = (jnp.arange(s)[None, None, :] < lens[:, None, None])
    want = plain(q1, _heads(pad(k)), _heads(pad(v)), lams, gain,
                 jnp.where(lens[:, None, None] > 0, seen, True))
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=2e-5, atol=2e-6)
    assert np.isfinite(np.asarray(got)).all()


def test_one_token_over_a_ring_that_has_wrapped(parts):
    """Rows written one at a time into a ring of 8 (`ring_append`), 21
    positions: the ring has wrapped twice; the last query sees the last 8
    positions, whatever their order in the ring. Also `ring_pack`ed."""
    q, k, v, lams, gain = parts
    w = 8
    kr = jnp.zeros((B, w, HKV * DH))
    vr = jnp.zeros((B, w, HKV * DH))
    for t in range(T):
        pos = jnp.full((B,), t, jnp.int32)
        kr = KV.ring_append(kr, k[:, t:t + 1], pos)
        vr = KV.ring_append(vr, v[:, t:t + 1], pos)
    lens = jnp.full((B,), T, jnp.int32)
    np.testing.assert_array_equal(kr, KV.ring_pack(k, lens, w))
    with jax.default_matmul_precision("highest"):
        got = D.diff_decode_attention(q[:, -1:], kr, vr, lens, _lam(lams),
                                      gain, LAM0, ring=True)
    seen = (jnp.arange(T) > T - 1 - w)[None, None, :]
    want = plain(q[:, -1:], _heads(k), _heads(v), lams, gain, seen)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_cross_reads_rows_it_does_not_own(parts):
    """One query row against another layer's keys and values, rows [0,
    len) seen; nothing is returned but the context."""
    q, k, v, lams, gain = parts
    lens = jnp.asarray([21, 9], jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = D.attn_cross(q[:, 4:5], k, v, lens, _lam(lams), gain, LAM0)
    seen = (jnp.arange(T)[None, None, :] < lens[:, None, None])
    want = plain(q[:, 4:5], _heads(k), _heads(v), lams, gain, seen)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_gmu_is_the_gated_memory():
    u, m = _rng(B, T, 16, seed=1), _rng(B, T, 24, seed=2)
    w_in, w_out = _rng(16, 24, seed=3), _rng(24, 16, seed=4)
    with jax.default_matmul_precision("highest"):
        got = D.gmu(u, m, w_in, w_out)
        g = u @ w_in
        want = (m * g * jax.nn.sigmoid(g)) @ w_out
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_lambda_init_by_depth():
    assert D.lambda_init(0) == pytest.approx(0.2)
    assert D.lambda_init(9) == pytest.approx(0.8 - 0.6 * math.exp(-2.7))


def _program(build):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            return build(), main


def _data(name, shape, dtype="float32"):
    return layers.data(name=name, shape=list(shape), dtype=dtype,
                       append_batch_size=False)


def _diff_args():
    lams = tuple(_data("l%d" % i, [DH]) for i in range(4))
    return lams, _data("gain", [2 * DH])


def test_layers_infer_their_shapes():
    def build():
        q = _data("q", [B, T, H, DH])
        k, v = _data("k", [B, T, HKV * DH]), _data("v", [B, T, HKV * DH])
        lams, gain = _diff_args()
        a = layers.diff_attention(q, k, v, lams, gain, LAM0, window=8)
        q1 = _data("q1", [B, 1, H, DH])
        slab = _data("slab", [B, 32, HKV * DH])
        lens = _data("lens", [B], "int32")
        d = layers.diff_decode_attention(q1, slab, slab, lens, lams, gain,
                                         LAM0, ring=True)
        c = layers.attn_cross(q1, slab, slab, lens, lams, gain, LAM0)
        g = layers.gmu(_data("u", [B, T, 16]), _data("m", [B, T, 24]),
                       _data("wi", [16, 24]), _data("wo", [24, 16]))
        return a, d, c, g
    (a, d, c, g), main = _program(build)
    assert tuple(a.shape) == (B, T, H // 2, 2 * DH)
    assert tuple(d.shape) == tuple(c.shape) == (B, 1, H // 2, 2 * DH)
    assert tuple(g.shape) == (B, T, 16)
    from paddle_tpu.analysis import infer_program

    assert not infer_program(main).report.errors


@pytest.mark.parametrize("build,match", [
    (lambda: layers.diff_attention(
        _data("q", [B, T, H, DH]), _data("k", [B, T, HKV, DH]),
        _data("v", [B, T, HKV, DH]), *_diff_args(), lam_init=LAM0),
     "rank 3"),
    (lambda: layers.diff_decode_attention(
        _data("q", [B, 1, H, DH]), _data("k", [B, 32, 3 * DH]),
        _data("v", [B, 32, 3 * DH]), _data("n", [B], "int32"),
        *_diff_args(), lam_init=LAM0), "PAIRS"),
    (lambda: layers.attn_cross(
        _data("q", [B, 1, 6, DH]), _data("k", [B, 32, HKV * DH]),
        _data("v", [B, 32, HKV * DH]), _data("n", [B], "int32"),
        *_diff_args(), lam_init=LAM0), "do not divide"),
    (lambda: layers.gmu(_data("u", [B, T, 16]), _data("m", [B, T, 20]),
                        _data("wi", [16, 24]), _data("wo", [24, 16])),
     "not as wide"),
])
def test_infer_rules_name_the_mismatch(build, match):
    from paddle_tpu.analysis import infer_program

    _, main = _program(build)
    errors = infer_program(main).report.errors
    assert errors and any(match in e.message for e in errors), errors


def test_ops_carry_their_scopes(parts):
    q, k, v, lams, gain = parts
    lens = jnp.full((B,), T, jnp.int32)

    def text(fn, *a):
        return jax.jit(fn).lower(*a).as_text(debug_info=True)

    lam = _lam(lams)
    assert D.DIFF_ATTN in text(
        lambda *a: D.diff_attention(*a, lam, gain, LAM0), q, k, v)
    assert D.DIFF_ATTN_SLAB in text(
        lambda *a: D.diff_decode_attention(*a, lens, lam, gain, LAM0),
        q[:, :1], k, v)
    assert D.DIFF_ATTN_RING in text(
        lambda *a: D.diff_decode_attention(*a, lens, lam, gain, LAM0,
                                           ring=True), q[:, :1], k, v)
    assert D.ATTN_CROSS in text(
        lambda *a: D.attn_cross(*a, lens, lam, gain, LAM0), q[:, :1], k, v)
    assert D.GMU in text(D.gmu, _rng(B, T, 16), _rng(B, T, 24),
                         _rng(16, 24), _rng(24, 16))


# -- the kernel over a slab of flat rows ---------------------------------------
# (its parity with the lax path: `test_decode_stream.py`, the `rows-` cases)

def test_shape_and_dtype_choose_the_rows_path(monkeypatch, parts):
    """Float32 rows a multiple of 128 lanes wide whose scores fit beside
    the blocks take the kernel, in blocks of at most 2 MiB; a 16-bit
    type, a narrow row or scores past the budget take the lax path; so
    does every ring, and every device but a TPU."""
    def block(*shape):
        return DS.block_positions(D.rows_view(*shape))

    assert block(4096, 40, 1280, 128, jnp.float32) == 256
    assert block(2048, 16, 512, 128, jnp.float32) == 512
    assert block(512, 40, 1280, 128, jnp.float32) == 256
    assert block(4096, 40, 1280, 128, jnp.bfloat16) is None
    assert block(4096, 8, 64, 64, jnp.float32) is None
    assert block(32768, 40, 1280, 128, jnp.float32) is None
    cell = D.rows_view(4096, 40, 1280, 128, jnp.float32)
    assert KV.decode_stream_rows(cell) is None  # CPU
    calls = []
    monkeypatch.setattr(D._KV, "_use_pallas_decode", lambda s, d: True)
    monkeypatch.setattr(D, "pallas_attend_rows",
                        lambda *a, **kw: calls.append(a) or D._attend_rows_lax(*a))
    assert KV.decode_stream_rows(cell) == 256
    q, k, v, lams, gain = parts
    big = lambda a: jnp.tile(a, (1, 1, 8))[:, :16]          # rows of 256
    qq = jnp.tile(q[:, :1], (1, 1, 1, 8))                    # heads of 64
    lens = jnp.full((B,), 9, jnp.int32)
    D.diff_decode_attention(qq, big(k), big(v), lens, _lam(lams),
                            jnp.ones((128,)), LAM0)
    assert len(calls) == 1
    D.attn_cross(qq, big(k), big(v), lens, _lam(lams), jnp.ones((128,)), LAM0)
    assert len(calls) == 2
    D.diff_decode_attention(qq, big(k), big(v), lens, _lam(lams),
                            jnp.ones((128,)), LAM0, ring=True)
    assert len(calls) == 2  # a ring keeps the lax path
