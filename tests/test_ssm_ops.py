"""State-space op battery (ops/ssm.py): the selective scan against a
step-by-step numpy recurrence, padded against unpadded (state and
window taken at each row's length), scan-then-step against a scan one
token longer, the causal convolution and its step, RMS normalization,
and decode attention with fewer key/value heads than query heads. Each
op's infer rule is cross-checked against the traced shapes."""
import math

import numpy as np
import pytest

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


def test_ssm_scan_then_step_is_a_scan_one_token_longer():
    inp = _ssm_inputs(t=T + 1)
    y_all, state_all = _scan(inp)
    head = {k: (v[:, :T] if v.ndim == 3 else v) for k, v in inp.items()}
    _, state = _scan(head)
    feeds = {k: (v[:, T:] if v.ndim == 3 else v) for k, v in inp.items()}
    feeds["State"] = state
    out = run_op("ssm_step", feeds, outs=("Y", "StateOut"))
    assert np.asarray(out["Y"]).shape == (B, 1, DI)
    np.testing.assert_allclose(np.asarray(out["Y"])[:, 0], y_all[:, T],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(out["StateOut"]), state_all,
                               rtol=2e-5, atol=2e-5)


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
