"""State-space op battery (ops/ssm.py): the selective scan against a
step-by-step numpy recurrence, padded against unpadded (state and
window taken at each row's length), scan-then-step against a scan one
token longer, the scan's Pallas kernel in interpret mode against the
lax form and the gate that chooses between them, the causal
convolution and its step, RMS normalization,
and decode attention with fewer key/value heads than query heads. Each
op's infer rule is cross-checked against the traced shapes."""
import math
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import observability as obs
from paddle_tpu.ops import kv_cache as KV
from paddle_tpu.ops import ssm as S
from tests.op_test import check_infer, run_op

B, T, DI, N, K = 3, 21, 12, 4, 4


def _rand(shape, seed=0, scale=1.0):
    return (np.random.RandomState(seed).randn(*shape) * scale).astype(
        np.float32)


def _ssm_inputs(t=T, seed=0):
    return {"X": _rand((B, t, DI), seed),
            "Delta": np.abs(_rand((B, t, DI), seed + 1, 0.3)) + 0.01,
            "A": -np.exp(_rand((DI, N), seed + 2, 0.3)),
            "B": _rand((B, t, N), seed + 3),
            "C": _rand((B, t, N), seed + 4),
            "D": _rand((DI,), seed + 5)}


def _np_scan(inp, lens):
    """The recurrence as the reference states it, a token at a time in
    float64: y (B, T, Di), the state after each row's last real token."""
    x, dt, a, b, c, d = (inp[k].astype(np.float64)
                         for k in ("X", "Delta", "A", "B", "C", "D"))
    bsz, t, di = x.shape
    y = np.zeros((bsz, t, di))
    state = np.zeros((bsz, di, a.shape[1]))
    for bi in range(bsz):
        s = np.zeros((di, a.shape[1]))
        for ti in range(int(lens[bi])):
            s = (np.exp(dt[bi, ti][:, None] * a) * s
                 + (dt[bi, ti] * x[bi, ti])[:, None] * b[bi, ti][None, :])
            y[bi, ti] = s @ c[bi, ti] + d * x[bi, ti]
        state[bi] = s
    return y, state


def _scan(inp, lens=None):
    feeds = dict(inp)
    if lens is not None:
        feeds["Lengths"] = np.asarray(lens, np.int32)
    out = run_op("ssm_scan", feeds, outs=("Y", "State"))
    return np.asarray(out["Y"]), np.asarray(out["State"])


@pytest.mark.parametrize("lens", [None, [T, 5, 1], [9, T, 17]],
                         ids=["full", "short_rows", "odd_lengths"])
def test_ssm_scan_matches_numpy_recurrence(lens):
    """21 positions cross the scan's unroll of 8 twice, with a
    remainder; rows shorter than the batch stop where they end."""
    inp = _ssm_inputs()
    real = [T] * B if lens is None else lens
    y, state = _scan(inp, lens)
    want_y, want_state = _np_scan(inp, real)
    for bi, n in enumerate(real):
        np.testing.assert_allclose(y[bi, :n], want_y[bi, :n],
                                   rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(state, want_state, rtol=2e-5, atol=2e-5)
    assert np.isfinite(y).all()  # padded positions: meaningless, finite


def test_ssm_scan_padding_does_not_advance_the_state():
    """A row of 7 tokens in a batch padded to 21 ends in the state of
    the unpadded 7-token scan, whatever the padding holds."""
    inp = _ssm_inputs()
    _, padded = _scan(inp, [7, 7, 7])
    short = {k: (v[:, :7] if v.ndim == 3 else v) for k, v in inp.items()}
    _, alone = _scan(short)
    np.testing.assert_allclose(padded, alone, rtol=1e-6, atol=1e-6)


def _kernel_inputs(bsz, t, di=128, n=8, seed=0):
    """Operands of a shape the kernel takes (``S._kernel_blocks``)."""
    r = np.random.RandomState(seed)
    return (r.randn(bsz, t, di).astype(np.float32),
            (np.abs(r.randn(bsz, t, di)) * 0.3 + 0.01).astype(np.float32),
            -np.exp(r.randn(di, n) * 0.3).astype(np.float32),
            r.randn(bsz, t, n).astype(np.float32),
            r.randn(bsz, t, n).astype(np.float32),
            r.randn(di).astype(np.float32))


def _traces():
    got = {k["path"]: v for k, v in obs.SSM_SCAN_TRACES.samples()}
    return got.get("kernel", 0), got.get("lax", 0)


@pytest.mark.parametrize("path", ["lax", "kernel"])
def test_ssm_scan_then_step_is_a_scan_one_token_longer(path):
    """The state a scan hands to ``ssm_step`` is the state of a scan
    one token longer, whichever form scanned: the lax form over 21 and
    22 positions, the kernel over a bucket of 256 stopped at 128 (a
    block's last row) and at 129."""
    if path == "lax":
        inp = _ssm_inputs(t=T + 1)
        y_all, state_all = _scan(inp)
        head = {k: (v[:, :T] if v.ndim == 3 else v) for k, v in inp.items()}
        _, state = _scan(head)
        at, di = T, DI
    else:
        at, di = S._KERNEL_BLOCK_T, 128
        ops = _kernel_inputs(B, 2 * at, di)
        inp = dict(zip(("X", "Delta", "A", "B", "C", "D"), ops))
        y_all, state_all = S.ssm_scan(
            *ops, jnp.full((B,), at + 1, jnp.int32), interpret=True)
        _, state = S.ssm_scan(*ops, jnp.full((B,), at, jnp.int32),
                              interpret=True)
        y_all, state_all = np.asarray(y_all), np.asarray(state_all)
    feeds = {k: (v[:, at:at + 1] if v.ndim == 3 else v)
             for k, v in inp.items()}
    feeds["State"] = state
    out = run_op("ssm_step", feeds, outs=("Y", "StateOut"))
    assert np.asarray(out["Y"]).shape == (B, 1, di)
    np.testing.assert_allclose(np.asarray(out["Y"])[:, 0], y_all[:, at],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(out["StateOut"]), state_all,
                               rtol=2e-5, atol=2e-5)


_BT = S._KERNEL_BLOCK_T
_KERNEL_CASES = [
    # id, bucket positions, lengths (None: every position)
    ("every_position", 2 * _BT, None),
    ("ragged", 2 * _BT, [2 * _BT, _BT + 3, 57]),
    ("block_boundary", 2 * _BT, [_BT, _BT - 1, _BT + 1]),
    ("zero_beside_full", 2 * _BT, [0, 2 * _BT]),
    ("one_block", _BT, [_BT, 5]),
    ("many_blocks", 4 * _BT, [4 * _BT, 2 * _BT + 44, 1]),
    ("past_the_bucket", _BT, [_BT + 9, 8]),
]


@pytest.mark.parametrize("t,lens", [c[1:] for c in _KERNEL_CASES],
                         ids=[c[0] for c in _KERNEL_CASES])
def test_ssm_scan_kernel_matches_the_lax_form(t, lens):
    """The Pallas kernel (interpret mode) against the lax form: the
    state at each row's length, y at every live position, y finite
    everywhere (zeros past a row's length, where the lax form's is
    finite and meaningless); the counter says which form was traced."""
    bsz = 2 if lens is None else len(lens)
    ops = _kernel_inputs(bsz, t)
    ln = None if lens is None else jnp.asarray(lens, jnp.int32)
    k0, l0 = _traces()
    want_y, want_state = S.ssm_scan(*ops, ln)
    assert _traces() == (k0, l0 + 1)
    got_y, got_state = S.ssm_scan(*ops, ln, interpret=True)
    assert _traces() == (k0 + 1, l0 + 1)
    assert got_y.shape == want_y.shape == (bsz, t, 128)
    assert got_state.shape == want_state.shape == (bsz, 128, 8)
    np.testing.assert_allclose(got_state, want_state, rtol=1e-5, atol=1e-6)
    got_y = np.asarray(got_y)
    assert np.isfinite(got_y).all()
    for bi, n in enumerate([t] * bsz if lens is None else lens):
        n = min(n, t)
        np.testing.assert_allclose(got_y[bi, :n], np.asarray(want_y)[bi, :n],
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(got_y[bi, n:], 0.0)


def test_ssm_scan_kernel_path_differentiates_as_the_lax_form():
    """No cell trains through a scan, and the op must not start to
    raise: the kernel path's backward is the lax form's."""
    ops = _kernel_inputs(2, 2 * _BT, seed=3)
    ln = jnp.asarray([2 * _BT, _BT + 3], jnp.int32)
    w = np.random.RandomState(4).randn(2, 2 * _BT, 128).astype(np.float32)
    w[1, _BT + 3:] = 0.0  # a padded position's y means nothing

    def loss(interpret, x, delta, a, b, c, d):
        y, state = S.ssm_scan(x, delta, a, b, c, d, ln, interpret=interpret)
        return jnp.sum(y * w) + jnp.sum(state * state)

    args = tuple(range(1, 7))
    want = jax.grad(loss, argnums=args)(False, *ops)
    got = jax.grad(loss, argnums=args)(True, *ops)
    for g, wnt in zip(got, want):
        np.testing.assert_allclose(g, wnt, rtol=2e-4, atol=2e-4)


_GATE_CASES = [
    # id, (t, d_inner, d_state), blocks or None
    ("the-cells-2048", (2048, 5120, 16), (_BT, S._KERNEL_BLOCK_D)),
    ("one-block", (_BT, 5120, 16), (_BT, S._KERNEL_BLOCK_D)),
    ("d-inner-of-twelve-lane-tiles", (256, 1536, 16), (_BT, 512)),
    ("under-one-block", (64, 5120, 16), None),
    ("not-whole-blocks", (_BT + 8, 5120, 16), None),
    ("d-inner-no-lane-tiles", (256, 5000, 16), None),
    ("d-state-no-sublane-tiles", (256, 5120, 4), None),
]


@pytest.mark.parametrize("shape,blocks", [c[1:] for c in _GATE_CASES],
                         ids=[c[0] for c in _GATE_CASES])
def test_ssm_scan_gate_answers_from_shape_and_device(shape, blocks,
                                                     monkeypatch):
    """``_kernel_blocks`` answers from the shape; ``_use_kernel`` adds
    the device a step is bound for: never the CPU."""
    assert S._kernel_blocks(*shape) == blocks
    assert not S._use_kernel(*shape)
    monkeypatch.setattr(KV, "current_device",
                        lambda: types.SimpleNamespace(platform="tpu"))
    assert S._use_kernel(*shape) == (blocks is not None)
    monkeypatch.setenv("PADDLE_TPU_NO_PALLAS", "1")
    assert not S._use_kernel(*shape)


def test_ssm_scan_a_refused_shape_takes_the_lax_path(monkeypatch):
    """A bucket under one block of positions, on a device the kernel
    runs on: the lax form, and the counter says so; the kernel's own
    entry refuses it by name."""
    monkeypatch.setattr(KV, "current_device",
                        lambda: types.SimpleNamespace(platform="tpu"))
    ops = _kernel_inputs(2, _BT // 2)
    k0, l0 = _traces()
    y, state = S.ssm_scan(*ops, jnp.asarray([_BT // 2, 3], jnp.int32))
    assert _traces() == (k0, l0 + 1)
    assert y.shape == (2, _BT // 2, 128) and state.shape == (2, 128, 8)
    with pytest.raises(ValueError, match="the lax form runs it"):
        S.pallas_ssm_scan(*ops, jnp.asarray([1, 1], jnp.int32),
                          interpret=True)


def _np_conv(x, w, bias):
    bsz, t, ch = x.shape
    k = w.shape[1]
    xp = np.concatenate([np.zeros((bsz, k - 1, ch), x.dtype), x], axis=1)
    return bias + sum(w[:, j] * xp[:, j:j + t] for j in range(k)), xp


@pytest.mark.parametrize("lens", [[T, T, T], [1, 2, 10]],
                         ids=["full", "shorter_than_window"])
def test_causal_conv1d_and_its_window(lens):
    x, w, bias = _rand((B, T, DI), 1), _rand((DI, K), 2), _rand((DI,), 3)
    out = run_op("causal_conv1d",
                 {"X": x, "W": w, "Bias": bias,
                  "Lengths": np.asarray(lens, np.int32)},
                 outs=("Y", "Window"))
    want, xp = _np_conv(x, w, bias)
    np.testing.assert_allclose(np.asarray(out["Y"]), want,
                               rtol=1e-5, atol=1e-5)
    # the K - 1 inputs before each row's length, zeros before the start
    for bi, n in enumerate(lens):
        np.testing.assert_array_equal(np.asarray(out["Window"])[bi],
                                      xp[bi, n:n + K - 1])


def test_causal_conv1d_step_carries_on_from_the_window():
    x, w, bias = _rand((B, T, DI), 1), _rand((DI, K), 2), _rand((DI,), 3)
    n = 10
    pre = run_op("causal_conv1d",
                 {"X": x, "W": w, "Bias": bias,
                  "Lengths": np.full((B,), n, np.int32)},
                 outs=("Y", "Window"))
    step = run_op("causal_conv1d_step",
                  {"X": x[:, n:n + 1], "Window": pre["Window"], "W": w,
                   "Bias": bias}, outs=("Y", "WindowOut"))
    want, xp = _np_conv(x, w, bias)
    np.testing.assert_allclose(np.asarray(step["Y"])[:, 0], want[:, n],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(step["WindowOut"]),
                                  xp[:, n + 1:n + K])


def test_rms_norm_matches_numpy():
    x, g = _rand((B, T, DI), 4), _rand((DI,), 5)
    got = np.asarray(run_op("rms_norm", {"X": x, "Scale": g},
                            attrs={"epsilon": 1e-6})["Out"])
    x64 = x.astype(np.float64)
    want = g * x64 / np.sqrt((x64 ** 2).mean(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("hkv", [1, 2], ids=["one_kv_head", "two_kv_heads"])
def test_decode_attention_with_fewer_kv_heads(hkv):
    """4 query heads on 1 (or 2) key/value heads equal full-head
    attention against the slab with each K/V head repeated for the
    query heads that share it (query head h reads K/V head h // g)."""
    h, d, s = 4, 8, 32
    q = _rand((B, 1, h, d), 6)
    k, v = _rand((B, s, hkv, d), 7), _rand((B, s, hkv, d), 8)
    lens = np.array([5, s, 0], np.int32)
    got = np.asarray(run_op("decode_attention",
                            {"Q": q, "KCache": k, "VCache": v,
                             "Lengths": lens})["Out"])
    full = run_op("decode_attention",
                  {"Q": q, "KCache": np.repeat(k, h // hkv, axis=2),
                   "VCache": np.repeat(v, h // hkv, axis=2),
                   "Lengths": lens})["Out"]
    np.testing.assert_allclose(got, np.asarray(full), rtol=1e-5, atol=1e-5)
    assert not got[2].any()  # a free slot: zeros, not garbage
    scale = 1.0 / math.sqrt(d)
    s0 = (q[0, 0, 3] @ k[0, :5, 3 // (h // hkv)].T) * scale
    p = np.exp(s0 - s0.max())
    np.testing.assert_allclose(got[0, 0, 3],
                               (p / p.sum()) @ v[0, :5, 3 // (h // hkv)],
                               rtol=1e-5, atol=1e-5)


def test_fused_attention_repeats_shared_kv_heads():
    """Prefill's side of the same: k and v of 1 head against q of 4."""
    h, d, t = 4, 8, 16
    q, k, v = (_rand((2, t, h, d), 9), _rand((2, t, 1, d), 10),
               _rand((2, t, 1, d), 11))
    attrs = {"causal": True, "layout": "bthd"}
    got = run_op("fused_attention", {"Q": q, "K": k, "V": v}, attrs)["Out"]
    want = run_op("fused_attention",
                  {"Q": q, "K": np.repeat(k, h, axis=2),
                   "V": np.repeat(v, h, axis=2)}, attrs)["Out"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


INFER = {
    "rms_norm": (lambda: {"X": _rand((B, T, DI)), "Scale": _rand((DI,))},
                 ("Out",)),
    "ssm_scan": (lambda: dict(_ssm_inputs(),
                              Lengths=np.array([3, 2, 1], np.int32)),
                 ("Y", "State")),
    "ssm_step": (lambda: dict(
        {k: (v[:, :1] if v.ndim == 3 else v)
         for k, v in _ssm_inputs().items()},
        State=_rand((B, DI, N))), ("Y", "StateOut")),
    "causal_conv1d": (lambda: {"X": _rand((B, T, DI)), "W": _rand((DI, K)),
                               "Bias": _rand((DI,))}, ("Y", "Window")),
    "causal_conv1d_step": (lambda: {
        "X": _rand((B, 1, DI)), "Window": _rand((B, K - 1, DI)),
        "W": _rand((DI, K)), "Bias": _rand((DI,))}, ("Y", "WindowOut")),
    "decode_attention": (lambda: {
        "Q": _rand((B, 1, 4, 8)), "KCache": _rand((B, 32, 1, 8)),
        "VCache": _rand((B, 32, 1, 8)),
        "Lengths": np.array([1, 2, 3], np.int32)}, ("Out",)),
}


@pytest.mark.parametrize("op", sorted(INFER))
def test_infer_rules_match_traced_shapes(op):
    inputs, outs = INFER[op]
    check_infer(op, inputs(), outs=outs)


def test_decode_attention_refuses_heads_that_do_not_divide():
    """4 query heads on 3 key/value heads: the kernel says so (the
    infer rule raises the same for a Program that is only analyzed)."""
    with pytest.raises(Exception, match="not divide"):
        run_op("decode_attention",
               {"Q": _rand((B, 1, 4, 8)), "KCache": _rand((B, 32, 3, 8)),
                "VCache": _rand((B, 32, 3, 8)),
                "Lengths": np.array([1, 2, 3], np.int32)})
