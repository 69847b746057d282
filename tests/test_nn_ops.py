"""Numeric checks for the conv/pool/norm/dropout/interp/random nn kernels.
Reference: paddle/fluid/operators/{conv,pool,batch_norm,layer_norm,lrn,
norm,dropout,bilinear_interp,nearest_interp,im2sequence,roi_pool}_op.cc.
"""
from __future__ import annotations

import numpy as np
import pytest

from op_test import check_grad, run_op


def rs(seed):
    return np.random.RandomState(seed)


# ---------------------------------------------------------------------------
# convolution (naive numpy loops on small shapes)
# ---------------------------------------------------------------------------


def np_conv2d(x, w, stride=(1, 1), pad=(0, 0), dilation=(1, 1), groups=1):
    n, cin, h, wd = x.shape
    cout, cin_g, kh, kw = w.shape
    xp = np.pad(x, [(0, 0), (0, 0), (pad[0], pad[0]), (pad[1], pad[1])])
    eh = (kh - 1) * dilation[0] + 1
    ew = (kw - 1) * dilation[1] + 1
    oh = (h + 2 * pad[0] - eh) // stride[0] + 1
    ow = (wd + 2 * pad[1] - ew) // stride[1] + 1
    out = np.zeros((n, cout, oh, ow))
    cpg = cin // groups
    opg = cout // groups
    for b in range(n):
        for o in range(cout):
            g = o // opg
            for i in range(oh):
                for j in range(ow):
                    acc = 0.0
                    for c in range(cpg):
                        for ki in range(kh):
                            for kj in range(kw):
                                acc += (xp[b, g * cpg + c,
                                           i * stride[0] + ki * dilation[0],
                                           j * stride[1] + kj * dilation[1]]
                                        * w[o, c, ki, kj])
                    out[b, o, i, j] = acc
    return out


def test_conv2d():
    x = rs(0).randn(2, 3, 5, 5).astype(np.float32)
    w = rs(1).randn(4, 3, 3, 3).astype(np.float32)
    got = np.asarray(run_op("conv2d", {"Input": x, "Filter": w},
                            attrs={"strides": [1, 1], "paddings": [1, 1]},
                            outs=("Output",))["Output"])
    np.testing.assert_allclose(got, np_conv2d(x, w, pad=(1, 1)), rtol=1e-4,
                               atol=1e-4)
    got = np.asarray(run_op("conv2d", {"Input": x, "Filter": w},
                            attrs={"strides": [2, 2], "paddings": [0, 0],
                                   "dilations": [2, 2]},
                            outs=("Output",))["Output"])
    np.testing.assert_allclose(got, np_conv2d(x, w, stride=(2, 2),
                                              dilation=(2, 2)),
                               rtol=1e-4, atol=1e-4)


def test_conv2d_groups_depthwise():
    x = rs(2).randn(1, 4, 5, 5).astype(np.float32)
    w = rs(3).randn(4, 2, 3, 3).astype(np.float32)
    got = np.asarray(run_op("conv2d", {"Input": x, "Filter": w},
                            attrs={"paddings": [1, 1], "groups": 2},
                            outs=("Output",))["Output"])
    np.testing.assert_allclose(got, np_conv2d(x, w, pad=(1, 1), groups=2),
                               rtol=1e-4, atol=1e-4)
    wd = rs(4).randn(4, 1, 3, 3).astype(np.float32)
    got = np.asarray(run_op("depthwise_conv2d", {"Input": x, "Filter": wd},
                            attrs={"paddings": [1, 1], "groups": 4},
                            outs=("Output",))["Output"])
    np.testing.assert_allclose(got, np_conv2d(x, wd, pad=(1, 1), groups=4),
                               rtol=1e-4, atol=1e-4)


def test_conv2d_grad():
    x = rs(5).randn(1, 2, 4, 4).astype(np.float32)
    w = rs(6).randn(2, 2, 3, 3).astype(np.float32)
    check_grad("conv2d", {"Input": x, "Filter": w}, "Input",
               attrs={"paddings": [1, 1]}, outs=("Output",))
    check_grad("conv2d", {"Input": x, "Filter": w}, "Filter",
               attrs={"paddings": [1, 1]}, outs=("Output",))


def test_conv3d():
    x = rs(7).randn(1, 2, 4, 4, 4).astype(np.float32)
    w = rs(8).randn(3, 2, 2, 2, 2).astype(np.float32)
    got = np.asarray(run_op("conv3d", {"Input": x, "Filter": w},
                            attrs={}, outs=("Output",))["Output"])
    want = np.zeros((1, 3, 3, 3, 3))
    for o in range(3):
        for i in range(3):
            for j in range(3):
                for k in range(3):
                    want[0, o, i, j, k] = (
                        x[0, :, i:i + 2, j:j + 2, k:k + 2] * w[o]).sum()
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def np_conv2d_transpose(x, w, stride=(1, 1), pad=(0, 0)):
    n, cin, h, wd = x.shape
    cin2, cout, kh, kw = w.shape
    oh = (h - 1) * stride[0] + kh - 2 * pad[0]
    ow = (wd - 1) * stride[1] + kw - 2 * pad[1]
    full = np.zeros((n, cout, oh + 2 * pad[0], ow + 2 * pad[1]))
    for b in range(n):
        for c in range(cin):
            for i in range(h):
                for j in range(wd):
                    full[b, :, i * stride[0]:i * stride[0] + kh,
                         j * stride[1]:j * stride[1] + kw] += (
                        x[b, c, i, j] * w[c])
    if pad[0] or pad[1]:
        full = full[:, :, pad[0]:full.shape[2] - pad[0],
                    pad[1]:full.shape[3] - pad[1]]
    return full


def test_conv2d_transpose():
    x = rs(9).randn(1, 3, 3, 3).astype(np.float32)
    w = rs(10).randn(3, 2, 3, 3).astype(np.float32)  # IOHW
    for stride, pad in [((1, 1), (0, 0)), ((2, 2), (1, 1))]:
        got = np.asarray(run_op(
            "conv2d_transpose", {"Input": x, "Filter": w},
            attrs={"strides": list(stride), "paddings": list(pad)},
            outs=("Output",))["Output"])
        np.testing.assert_allclose(got, np_conv2d_transpose(x, w, stride,
                                                            pad),
                                   rtol=1e-4, atol=1e-4)


def test_depthwise_conv2d_transpose():
    """Reference conv_transpose_op.cc:338: each input
    channel deconvolves independently — groups == C_in, paddle filter
    layout (C, 1, kh, kw) — so the per-channel numpy transpose-conv is
    the reference."""
    x = rs(13).randn(2, 3, 4, 4).astype(np.float32)
    w = rs(14).randn(3, 1, 3, 3).astype(np.float32)
    for stride, pad in [((1, 1), (0, 0)), ((2, 2), (1, 1))]:
        got = np.asarray(run_op(
            "depthwise_conv2d_transpose", {"Input": x, "Filter": w},
            attrs={"strides": list(stride), "paddings": list(pad),
                   "groups": 3},
            outs=("Output",))["Output"])
        want = np.concatenate(
            [np_conv2d_transpose(x[:, c:c + 1], w[c:c + 1], stride, pad)
             for c in range(3)], axis=1)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_depthwise_conv2d_transpose_grad():
    x = rs(15).randn(1, 2, 3, 3).astype(np.float32)
    w = (0.4 * rs(16).randn(2, 1, 2, 2)).astype(np.float32)
    check_grad("depthwise_conv2d_transpose", {"Input": x, "Filter": w},
               "Input", attrs={"groups": 2}, outs=("Output",))
    check_grad("depthwise_conv2d_transpose", {"Input": x, "Filter": w},
               "Filter", attrs={"groups": 2}, outs=("Output",))


def test_conv3d_transpose():
    x = rs(11).randn(1, 2, 2, 2, 2).astype(np.float32)
    w = rs(12).randn(2, 3, 2, 2, 2).astype(np.float32)
    got = np.asarray(run_op("conv3d_transpose", {"Input": x, "Filter": w},
                            attrs={}, outs=("Output",))["Output"])
    want = np.zeros((1, 3, 3, 3, 3))
    for c in range(2):
        for i in range(2):
            for j in range(2):
                for k in range(2):
                    want[0, :, i:i + 2, j:j + 2, k:k + 2] += (
                        x[0, c, i, j, k] * w[c])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# pooling
# ---------------------------------------------------------------------------


def test_pool2d():
    x = rs(13).randn(2, 3, 6, 6).astype(np.float32)
    got = np.asarray(run_op("pool2d", {"X": x},
                            attrs={"ksize": [2, 2], "strides": [2, 2],
                                   "pooling_type": "max"})["Out"])
    want = x.reshape(2, 3, 3, 2, 3, 2).max(axis=(3, 5))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    got = np.asarray(run_op("pool2d", {"X": x},
                            attrs={"ksize": [2, 2], "strides": [2, 2],
                                   "pooling_type": "avg"})["Out"])
    want = x.reshape(2, 3, 3, 2, 3, 2).mean(axis=(3, 5))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    got = np.asarray(run_op("pool2d", {"X": x},
                            attrs={"ksize": [6, 6], "global_pooling": True,
                                   "pooling_type": "avg"})["Out"])
    np.testing.assert_allclose(got.reshape(2, 3),
                               x.mean(axis=(2, 3)), rtol=1e-5, atol=1e-6)


def test_pool2d_grad():
    x = rs(14).randn(1, 1, 4, 4).astype(np.float32)
    check_grad("pool2d", {"X": x}, "X",
               attrs={"ksize": [2, 2], "strides": [2, 2],
                      "pooling_type": "avg"})
    # max pool gradient: make entries well-separated so argmax is stable
    x2 = (np.arange(16).reshape(1, 1, 4, 4) * 0.37 + 0.1).astype(np.float32)
    check_grad("pool2d", {"X": x2}, "X",
               attrs={"ksize": [2, 2], "strides": [2, 2],
                      "pooling_type": "max"})


def test_pool3d():
    x = rs(15).randn(1, 2, 4, 4, 4).astype(np.float32)
    got = np.asarray(run_op("pool3d", {"X": x},
                            attrs={"ksize": [2, 2, 2], "strides": [2, 2, 2],
                                   "pooling_type": "max"})["Out"])
    want = x.reshape(1, 2, 2, 2, 2, 2, 2, 2).max(axis=(3, 5, 7))
    np.testing.assert_allclose(got, want, rtol=1e-5)


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------


def test_batch_norm_train_and_test():
    x = rs(16).randn(4, 3, 5, 5).astype(np.float32)
    scale = rs(17).rand(3).astype(np.float32) + 0.5
    bias = rs(18).randn(3).astype(np.float32)
    mean = rs(19).randn(3).astype(np.float32)
    var = rs(20).rand(3).astype(np.float32) + 0.5
    eps, mom = 1e-5, 0.9
    got = run_op("batch_norm",
                 {"X": x, "Scale": scale, "Bias": bias, "Mean": mean,
                  "Variance": var},
                 attrs={"epsilon": eps, "momentum": mom},
                 outs=("Y", "MeanOut", "VarianceOut", "SavedMean"))
    mu = x.mean(axis=(0, 2, 3))
    sig2 = x.var(axis=(0, 2, 3))
    want = ((x - mu[None, :, None, None])
            / np.sqrt(sig2[None, :, None, None] + eps)
            * scale[None, :, None, None] + bias[None, :, None, None])
    np.testing.assert_allclose(np.asarray(got["Y"]), want, rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(got["MeanOut"]),
                               mom * mean + (1 - mom) * mu, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(got["VarianceOut"]),
                               mom * var + (1 - mom) * sig2, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(got["SavedMean"]), mu, rtol=1e-5,
                               atol=1e-6)
    # test mode: uses running stats
    got = run_op("batch_norm",
                 {"X": x, "Scale": scale, "Bias": bias, "Mean": mean,
                  "Variance": var},
                 attrs={"epsilon": eps, "is_test": True}, outs=("Y",))
    want = ((x - mean[None, :, None, None])
            / np.sqrt(var[None, :, None, None] + eps)
            * scale[None, :, None, None] + bias[None, :, None, None])
    np.testing.assert_allclose(np.asarray(got["Y"]), want, rtol=1e-4,
                               atol=1e-4)


def test_batch_norm_grad():
    x = rs(21).randn(2, 2, 3, 3).astype(np.float32)
    scale = np.array([1.2, 0.7], np.float32)
    bias = np.array([0.1, -0.2], np.float32)
    mean = np.zeros(2, np.float32)
    var = np.ones(2, np.float32)
    check_grad("batch_norm",
               {"X": x, "Scale": scale, "Bias": bias, "Mean": mean,
                "Variance": var},
               "X", outs=("Y",), rtol=2e-2, atol=2e-3)


def test_batch_norm_nhwc():
    x = rs(22).randn(4, 5, 5, 3).astype(np.float32)
    scale = np.ones(3, np.float32)
    bias = np.zeros(3, np.float32)
    mean = np.zeros(3, np.float32)
    var = np.ones(3, np.float32)
    got = np.asarray(run_op(
        "batch_norm",
        {"X": x, "Scale": scale, "Bias": bias, "Mean": mean,
         "Variance": var},
        attrs={"data_layout": "NHWC"}, outs=("Y",))["Y"])
    mu = x.mean(axis=(0, 1, 2))
    sig2 = x.var(axis=(0, 1, 2))
    want = (x - mu) / np.sqrt(sig2 + 1e-5)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_layer_norm():
    x = rs(23).randn(3, 4, 5).astype(np.float32)
    scale = rs(24).rand(20).astype(np.float32) + 0.5
    bias = rs(25).randn(20).astype(np.float32)
    got = run_op("layer_norm", {"X": x, "Scale": scale, "Bias": bias},
                 attrs={"begin_norm_axis": 1}, outs=("Y", "Mean"))
    flat = x.reshape(3, 20)
    mu = flat.mean(1, keepdims=True)
    sig = flat.var(1, keepdims=True)
    want = ((flat - mu) / np.sqrt(sig + 1e-5) * scale + bias).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(got["Y"]), want, rtol=1e-4,
                               atol=1e-4)
    check_grad("layer_norm", {"X": x[:2, :2, :2],
                              "Scale": scale[:4], "Bias": bias[:4]},
               "X", attrs={"begin_norm_axis": 1}, outs=("Y",),
               rtol=2e-2, atol=2e-3)


def test_lrn():
    x = rs(26).rand(2, 6, 3, 3).astype(np.float32)
    n, k, alpha, beta = 5, 2.0, 1e-3, 0.75
    got = np.asarray(run_op("lrn", {"X": x},
                            attrs={"n": n, "k": k, "alpha": alpha,
                                   "beta": beta})["Out"])
    want = np.zeros_like(x, dtype=np.float64)
    for c in range(6):
        lo, hi = max(0, c - n // 2), min(6, c + n // 2 + 1)
        sq = (x[:, lo:hi] ** 2).sum(axis=1)
        want[:, c] = x[:, c] / (k + alpha * sq) ** beta
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_norm_op():
    x = rs(27).randn(2, 3, 4).astype(np.float32)
    got = run_op("norm", {"X": x}, attrs={"axis": 1, "epsilon": 1e-10},
                 outs=("Out", "Norm"))
    nrm = np.sqrt((x ** 2).sum(1, keepdims=True) + 1e-10)
    np.testing.assert_allclose(np.asarray(got["Out"]), x / nrm, rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(got["Norm"]), nrm, rtol=1e-4,
                               atol=1e-5)


def test_prelu():
    x = rs(28).randn(2, 3, 4).astype(np.float32)
    a = np.array([0.25], np.float32)
    got = np.asarray(run_op("prelu", {"X": x, "Alpha": a},
                            attrs={"mode": "all"})["Out"])
    np.testing.assert_allclose(got, np.where(x > 0, x, 0.25 * x), rtol=1e-5)
    ac = np.array([0.1, 0.2, 0.3], np.float32)
    got = np.asarray(run_op("prelu", {"X": x, "Alpha": ac},
                            attrs={"mode": "channel"})["Out"])
    np.testing.assert_allclose(
        got, np.where(x > 0, x, ac[None, :, None] * x), rtol=1e-5)


# ---------------------------------------------------------------------------
# dropout & random ops (statistical / structural checks)
# ---------------------------------------------------------------------------


def test_dropout():
    x = np.ones((200, 50), np.float32)
    got = np.asarray(run_op("dropout", {"X": x},
                            attrs={"dropout_prob": 0.3})["Out"])
    # train: masked, unscaled (downgrade_in_infer)
    kept = got != 0
    assert abs(kept.mean() - 0.7) < 0.03
    np.testing.assert_allclose(got[kept], 1.0)
    got = np.asarray(run_op("dropout", {"X": x},
                            attrs={"dropout_prob": 0.3,
                                   "dropout_implementation":
                                       "upscale_in_train"})["Out"])
    kept = got != 0
    np.testing.assert_allclose(got[kept], 1.0 / 0.7, rtol=1e-5)
    got = np.asarray(run_op("dropout", {"X": x},
                            attrs={"dropout_prob": 0.3, "is_test": True})["Out"])
    np.testing.assert_allclose(got, 0.7, rtol=1e-5)
    got = np.asarray(run_op("dropout", {"X": x},
                            attrs={"dropout_prob": 0.3, "is_test": True,
                                   "dropout_implementation":
                                       "upscale_in_train"})["Out"])
    np.testing.assert_allclose(got, 1.0, rtol=1e-6)


def test_random_ops_statistics():
    got = np.asarray(run_op("uniform_random", {}, attrs={
        "shape": [2000], "min": -1.0, "max": 3.0, "dtype": "float32"})["Out"])
    assert got.min() >= -1.0 and got.max() <= 3.0
    assert abs(got.mean() - 1.0) < 0.1
    got = np.asarray(run_op("gaussian_random", {}, attrs={
        "shape": [4000], "mean": 2.0, "std": 0.5, "dtype": "float32"})["Out"])
    assert abs(got.mean() - 2.0) < 0.05 and abs(got.std() - 0.5) < 0.05
    got = np.asarray(run_op("truncated_gaussian_random", {}, attrs={
        "shape": [4000], "mean": 0.0, "std": 1.0, "dtype": "float32"})["Out"])
    assert np.abs(got).max() <= 2.0 + 1e-6
    assert abs(got.mean()) < 0.08


def test_sampling_id_random_crop():
    p = np.zeros((50, 4), np.float32)
    p[:, 2] = 1.0  # degenerate distribution -> always index 2
    got = np.asarray(run_op("sampling_id", {"X": p})["Out"])
    np.testing.assert_array_equal(got.reshape(-1), np.full(50, 2))
    x = rs(29).randn(2, 3, 8, 8).astype(np.float32)
    got = np.asarray(run_op("random_crop", {"X": x},
                            attrs={"shape": [3, 5, 5]})["Out"])
    assert got.shape == (2, 3, 5, 5)
    # crop content must be a contiguous window of the source
    found = False
    for i in range(4):
        for j in range(4):
            if np.allclose(got[0], x[0, :, i:i + 5, j:j + 5]):
                found = True
    assert found


# ---------------------------------------------------------------------------
# interpolation / patches / roi
# ---------------------------------------------------------------------------


def test_nearest_interp():
    x = rs(30).randn(1, 2, 4, 4).astype(np.float32)
    got = np.asarray(run_op("nearest_interp", {"X": x},
                            attrs={"out_h": 8, "out_w": 8})["Out"])
    assert got.shape == (1, 2, 8, 8)
    # corners match
    np.testing.assert_allclose(got[..., 0, 0], x[..., 0, 0])


def test_bilinear_interp():
    x = rs(31).randn(1, 1, 3, 3).astype(np.float32)
    got = np.asarray(run_op("bilinear_interp", {"X": x},
                            attrs={"out_h": 5, "out_w": 5})["Out"])
    # align-corners: corners exact, center of a 2x-ish grid interpolates
    np.testing.assert_allclose(got[0, 0, 0, 0], x[0, 0, 0, 0], rtol=1e-5)
    np.testing.assert_allclose(got[0, 0, 4, 4], x[0, 0, 2, 2], rtol=1e-5)
    np.testing.assert_allclose(got[0, 0, 2, 2], x[0, 0, 1, 1], rtol=1e-5)
    np.testing.assert_allclose(
        got[0, 0, 0, 1], 0.5 * (x[0, 0, 0, 0] + x[0, 0, 0, 1]), rtol=1e-5)


def test_im2sequence():
    x = rs(32).randn(2, 3, 4, 4).astype(np.float32)
    got = np.asarray(run_op("im2sequence", {"X": x},
                            attrs={"kernels": [2, 2],
                                   "strides": [2, 2]})["Out"])
    assert got.shape == (2 * 2 * 2, 3 * 2 * 2)
    # first patch of first image: channels-major patch flattening
    want = x[0, :, 0:2, 0:2].reshape(-1)
    np.testing.assert_allclose(got[0], want, rtol=1e-5)


def test_roi_pool():
    x = np.arange(64, dtype=np.float32).reshape(1, 1, 8, 8)
    rois = np.array([[0, 0, 0, 3, 3]], np.float32)  # batch 0, 4x4 region
    got = np.asarray(run_op("roi_pool", {"X": x, "ROIs": rois},
                            attrs={"pooled_height": 2, "pooled_width": 2,
                                   "spatial_scale": 1.0})["Out"])
    want = np.array([[[9., 11.], [25., 27.]]])  # max of each 2x2 sub-bin
    np.testing.assert_allclose(got[0], want, rtol=1e-5)
    # reference bins OVERLAP (floor start / ceil end): a max sitting on the
    # shared boundary row appears in BOTH bins
    x2 = np.zeros((1, 1, 8, 8), np.float32)
    x2[0, 0, 2, 4] = 100.0
    rois2 = np.array([[0, 0, 0, 4, 4]], np.float32)  # 5x5 region
    got = np.asarray(run_op("roi_pool", {"X": x2, "ROIs": rois2},
                            attrs={"pooled_height": 2, "pooled_width": 2,
                                   "spatial_scale": 1.0})["Out"])
    # (2,4): row 2 is in BOTH row-bins ([0,ceil(2.5)) and [floor(2.5),5));
    # col 4 only in col-bin 1
    np.testing.assert_allclose(got[0, 0], [[0., 100.], [0., 100.]])
    # C-style rounding: coordinate 8 at scale 1/16 rounds to 1, not 0
    rois3 = np.array([[0, 0, 0, 8, 8]], np.float32)
    got = np.asarray(run_op("roi_pool", {"X": x, "ROIs": rois3},
                            attrs={"pooled_height": 1, "pooled_width": 1,
                                   "spatial_scale": 1.0 / 16})["Out"])
    # region rows/cols 0..1 inclusive -> max of x[:2,:2] = 9
    np.testing.assert_allclose(got[0, 0], [[9.]])


def test_mean_iou():
    preds = np.array([0, 1, 1, 2, 2, 0], np.int32)
    labels = np.array([0, 1, 2, 2, 1, 0], np.int32)
    got = run_op("mean_iou", {"Predictions": preds, "Labels": labels},
                 attrs={"num_classes": 3},
                 outs=("OutMeanIou", "OutWrong", "OutCorrect"))
    # class0: inter 2, union 2 -> 1.0; class1: inter 1, union 3; class2 same
    want = (1.0 + 1 / 3 + 1 / 3) / 3
    np.testing.assert_allclose(float(np.asarray(got["OutMeanIou"])), want,
                               rtol=1e-5)


def test_conv2d_transpose_groups_matches_per_group_composition():
    """Grouped transpose conv == running each group's transpose conv
    separately and concatenating the outputs (the reference semantic the
    groups attr was previously silently dropping)."""
    from tests.op_test import run_op

    r = np.random.RandomState(0)
    C, M, G, S = 4, 6, 2, 5
    x = r.randn(2, C, S, S).astype(np.float32)
    w = r.randn(C, M // G, 3, 3).astype(np.float32)
    got = np.asarray(run_op(
        "conv2d_transpose", {"Input": x, "Filter": w},
        attrs={"strides": [2, 2], "paddings": [1, 1], "groups": G},
        outs=("Output",))["Output"])

    parts = []
    for g in range(G):
        xg = x[:, g * C // G:(g + 1) * C // G]
        wg = w[g * C // G:(g + 1) * C // G]
        parts.append(np.asarray(run_op(
            "conv2d_transpose", {"Input": xg, "Filter": wg},
            attrs={"strides": [2, 2], "paddings": [1, 1], "groups": 1},
            outs=("Output",))["Output"]))
    want = np.concatenate(parts, axis=1)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
