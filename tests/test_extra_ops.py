"""Numeric tests for the round-2 extra kernels: small losses/norms,
proximal optimizers, ranking/precision-recall metrics, pooling-with-index /
unpool / spp, and ctc_align (reference C++-only operators)."""
import numpy as np
import pytest

from tests.op_test import check_forward, check_grad, run_op

R = np.random.RandomState(42)


def test_minus():
    x = R.randn(3, 4).astype(np.float32)
    y = R.randn(3, 4).astype(np.float32)
    check_forward("minus", {"X": x, "Y": y}, lambda: x - y)
    check_grad("minus", {"X": x, "Y": y}, "X")


def test_hinge_loss():
    logits = R.randn(8, 1).astype(np.float32)
    labels = (R.rand(8, 1) > 0.5).astype(np.float32)
    want = np.maximum(0.0, 1.0 - (2.0 * labels - 1.0) * logits)
    check_forward("hinge_loss", {"Logits": logits, "Labels": labels},
                  lambda: want, outs=("Loss",))


def test_log_loss():
    p = R.rand(8, 1).astype(np.float32) * 0.9 + 0.05
    y = (R.rand(8, 1) > 0.5).astype(np.float32)
    eps = 1e-4
    want = -y * np.log(p + eps) - (1 - y) * np.log(1 - p + eps)
    check_forward("log_loss", {"Predicted": p, "Labels": y},
                  lambda: want, attrs={"epsilon": eps}, outs=("Loss",))
    check_grad("log_loss", {"Predicted": p, "Labels": y}, "Predicted",
               attrs={"epsilon": eps}, outs=("Loss",))


def test_margin_rank_loss():
    x1 = R.randn(6, 1).astype(np.float32)
    x2 = R.randn(6, 1).astype(np.float32)
    lbl = np.where(R.rand(6, 1) > 0.5, 1.0, -1.0).astype(np.float32)
    margin = 0.1
    raw = margin - lbl * (x1 - x2)
    check_forward("margin_rank_loss", {"X1": x1, "X2": x2, "Label": lbl},
                  lambda: (np.maximum(0, raw), (raw > 0).astype(np.float32)),
                  attrs={"margin": margin}, outs=("Out", "Activated"))


def test_modified_huber_loss():
    x = np.linspace(-3, 3, 13).astype(np.float32).reshape(-1, 1)
    y = (R.rand(13, 1) > 0.5).astype(np.float32)
    z = (2 * y - 1) * x
    want = np.where(z < -1, -4 * z, np.where(z < 1, (1 - z) ** 2, 0.0))
    check_forward("modified_huber_loss", {"X": x, "Y": y},
                  lambda: (want, z), outs=("Out", "IntermediateVal"))


def test_squared_l2_distance_and_norms():
    x = R.randn(4, 5).astype(np.float32)
    y = R.randn(4, 5).astype(np.float32)
    check_forward("squared_l2_distance", {"X": x, "Y": y},
                  lambda: ((x - y) ** 2).sum(1, keepdims=True))
    # broadcast row
    y1 = R.randn(1, 5).astype(np.float32)
    check_forward("squared_l2_distance", {"X": x, "Y": y1},
                  lambda: ((x - y1) ** 2).sum(1, keepdims=True))
    # rank-3 input still reduces to the reference's (N, 1)
    x3 = R.randn(4, 2, 3).astype(np.float32)
    y3 = R.randn(4, 2, 3).astype(np.float32)
    check_forward("squared_l2_distance", {"X": x3, "Y": y3},
                  lambda: ((x3 - y3) ** 2).reshape(4, -1).sum(
                      1, keepdims=True))
    check_forward("squared_l2_norm", {"X": x},
                  lambda: np.array([(x ** 2).sum()]))
    check_forward("l1_norm", {"X": x}, lambda: np.array([np.abs(x).sum()]))
    check_grad("squared_l2_norm", {"X": x}, "X")


def _prox(p, l1, l2, lr):
    return np.sign(p) * np.maximum(np.abs(p) - lr * l1, 0.0) / (1 + lr * l2)


def test_proximal_gd():
    p = R.randn(6).astype(np.float32)
    g = R.randn(6).astype(np.float32)
    lr = np.array([0.1], np.float32)
    out = run_op("proximal_gd",
                 {"Param": p, "Grad": g, "LearningRate": lr},
                 attrs={"l1": 0.05, "l2": 0.01}, outs=("ParamOut",))
    want = _prox(p - 0.1 * g, 0.05, 0.01, 0.1)
    np.testing.assert_allclose(np.asarray(out["ParamOut"]), want, rtol=1e-5)


def test_proximal_adagrad():
    p = R.randn(6).astype(np.float32)
    g = R.randn(6).astype(np.float32)
    m = np.abs(R.randn(6)).astype(np.float32)
    lr = np.array([0.1], np.float32)
    out = run_op("proximal_adagrad",
                 {"Param": p, "Grad": g, "Moment": m, "LearningRate": lr},
                 attrs={"l1": 0.05, "l2": 0.01},
                 outs=("ParamOut", "MomentOut"))
    m_new = m + g ** 2
    # per-element lr only scales the gradient step; the l1/l2 proximal
    # factors use the scalar lr (reference proximal_adagrad_op.h)
    prox = p - 0.1 * g / np.sqrt(m_new)
    want = (np.sign(prox) * np.maximum(np.abs(prox) - 0.1 * 0.05, 0.0)
            / (1 + 0.1 * 0.01))
    np.testing.assert_allclose(np.asarray(out["MomentOut"]), m_new, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out["ParamOut"]), want, rtol=1e-4)


def _pnpair_ref(score, label, query, weight=None, acc=(0.0, 0.0, 0.0)):
    n = len(score)
    w = weight if weight is not None else np.ones(n)
    pos, neg, neu = acc
    for i in range(n):
        for j in range(i + 1, n):
            if query[i] != query[j] or label[i] == label[j]:
                continue
            pw = (w[i] + w[j]) * 0.5
            if score[i] == score[j]:
                neu += pw
            if (score[i] - score[j]) * (label[i] - label[j]) > 0:
                pos += pw
            else:
                neg += pw
    return pos, neg, neu


def test_positive_negative_pair():
    n = 12
    score = R.randint(0, 4, (n, 1)).astype(np.float32)  # ties likely
    label = R.randint(0, 3, (n, 1)).astype(np.float32)
    query = np.repeat(np.arange(3), 4).reshape(n, 1).astype(np.int64)
    out = run_op("positive_negative_pair",
                 {"Score": score, "Label": label, "QueryID": query},
                 outs=("PositivePair", "NegativePair", "NeutralPair"))
    pos, neg, neu = _pnpair_ref(score[:, 0], label[:, 0], query[:, 0])
    np.testing.assert_allclose(np.asarray(out["PositivePair"]), [pos])
    np.testing.assert_allclose(np.asarray(out["NegativePair"]), [neg])
    np.testing.assert_allclose(np.asarray(out["NeutralPair"]), [neu])
    # accumulation + weights
    wgt = R.rand(n, 1).astype(np.float32)
    out2 = run_op("positive_negative_pair",
                  {"Score": score, "Label": label, "QueryID": query,
                   "Weight": wgt,
                   "AccumulatePositivePair": np.array([10.0], np.float32),
                   "AccumulateNegativePair": np.array([5.0], np.float32),
                   "AccumulateNeutralPair": np.array([1.0], np.float32)},
                  outs=("PositivePair", "NegativePair", "NeutralPair"))
    pos2, neg2, neu2 = _pnpair_ref(score[:, 0], label[:, 0], query[:, 0],
                                   wgt[:, 0], (10.0, 5.0, 1.0))
    np.testing.assert_allclose(np.asarray(out2["PositivePair"]), [pos2],
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out2["NegativePair"]), [neg2],
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out2["NeutralPair"]), [neu2],
                               rtol=1e-5)


def _pr_states_ref(ids, labels, w, c):
    st = np.zeros((c, 4))  # TP FP TN FN
    for i in range(len(ids)):
        idx, lbl, wi = ids[i], labels[i], w[i]
        if idx == lbl:
            st[idx, 0] += wi
            st[:, 2] += wi
            st[idx, 2] -= wi
        else:
            st[lbl, 3] += wi
            st[idx, 1] += wi
            st[:, 2] += wi
            st[idx, 2] -= wi
            st[lbl, 2] -= wi
    return st


def _pr_metrics_ref(st):
    def prec(tp, fp):
        return tp / (tp + fp) if tp > 0 or fp > 0 else 1.0

    def rec(tp, fn):
        return tp / (tp + fn) if tp > 0 or fn > 0 else 1.0

    def f1(p, r):
        return 2 * p * r / (p + r) if p > 0 or r > 0 else 0.0

    c = st.shape[0]
    mp = np.mean([prec(st[i, 0], st[i, 1]) for i in range(c)])
    mr = np.mean([rec(st[i, 0], st[i, 3]) for i in range(c)])
    tp, fp, fn = st[:, 0].sum(), st[:, 1].sum(), st[:, 3].sum()
    up, ur = prec(tp, fp), rec(tp, fn)
    return np.array([mp, mr, f1(mp, mr), up, ur, f1(up, ur)])


def test_precision_recall():
    c, n = 4, 20
    ids = R.randint(0, c, n).astype(np.int32)
    labels = R.randint(0, c, n).astype(np.int32)
    w = R.rand(n).astype(np.float32)
    states = np.abs(R.rand(c, 4)).astype(np.float32) * 3
    out = run_op("precision_recall",
                 {"Indices": ids.reshape(-1, 1),
                  "Labels": labels.reshape(-1, 1),
                  "Weights": w.reshape(-1, 1), "StatesInfo": states},
                 attrs={"class_number": c},
                 outs=("BatchMetrics", "AccumMetrics", "AccumStatesInfo"))
    st = _pr_states_ref(ids, labels, w, c)
    np.testing.assert_allclose(np.asarray(out["BatchMetrics"]),
                               _pr_metrics_ref(st), rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out["AccumStatesInfo"]),
                               st + states, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out["AccumMetrics"]),
                               _pr_metrics_ref(st + states.astype(np.float64)),
                               rtol=1e-4, atol=1e-6)


def _ref_pool_with_index(x, k, s, p):
    n, c, h, w = x.shape
    oh = (h - k + 2 * p) // s + 1
    ow = (w - k + 2 * p) // s + 1
    out = np.zeros((n, c, oh, ow), x.dtype)
    mask = np.zeros((n, c, oh, ow), np.int32)
    for ni in range(n):
        for ci in range(c):
            for i in range(oh):
                for j in range(ow):
                    best, bidx = -np.inf, -1
                    for di in range(k):
                        for dj in range(k):
                            r, cc = i * s - p + di, j * s - p + dj
                            if 0 <= r < h and 0 <= cc < w \
                                    and x[ni, ci, r, cc] > best:
                                best = x[ni, ci, r, cc]
                                bidx = r * w + cc
                    out[ni, ci, i, j] = best
                    mask[ni, ci, i, j] = bidx
    return out, mask


def test_max_pool2d_with_index_and_unpool():
    x = R.randn(2, 3, 6, 6).astype(np.float32)
    attrs = {"ksize": [2, 2], "strides": [2, 2], "paddings": [0, 0]}
    got = run_op("max_pool2d_with_index", {"X": x}, attrs=attrs,
                 outs=("Out", "Mask"))
    want_out, want_mask = _ref_pool_with_index(x, 2, 2, 0)
    np.testing.assert_allclose(np.asarray(got["Out"]), want_out, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(got["Mask"]), want_mask)

    up = run_op("unpool", {"X": np.asarray(got["Out"]),
                           "Indices": np.asarray(got["Mask"])},
                attrs=attrs)["Out"]
    up = np.asarray(up)
    assert up.shape == x.shape
    # every pooled max lands back at its original position
    flat_x, flat_up = x.reshape(6, 36), up.reshape(6, 36)
    flat_m = want_mask.reshape(6, -1)
    for r in range(6):
        np.testing.assert_allclose(flat_up[r, flat_m[r]],
                                   flat_x[r, flat_m[r]], rtol=1e-6)
        zero_pos = np.setdiff1d(np.arange(36), flat_m[r])
        assert np.all(flat_up[r, zero_pos] == 0)


def _ref_pool3d_with_index(x, k, s, p):
    n, c, d, h, w = x.shape
    od = (d - k + 2 * p) // s + 1
    oh = (h - k + 2 * p) // s + 1
    ow = (w - k + 2 * p) // s + 1
    out = np.zeros((n, c, od, oh, ow), x.dtype)
    mask = np.zeros((n, c, od, oh, ow), np.int32)
    for ni in range(n):
        for ci in range(c):
            for a in range(od):
                for i in range(oh):
                    for j in range(ow):
                        best, bidx = -np.inf, -1
                        for da in range(k):
                            for di in range(k):
                                for dj in range(k):
                                    dd = a * s - p + da
                                    r = i * s - p + di
                                    cc = j * s - p + dj
                                    if (0 <= dd < d and 0 <= r < h
                                            and 0 <= cc < w
                                            and x[ni, ci, dd, r, cc] > best):
                                        best = x[ni, ci, dd, r, cc]
                                        bidx = dd * h * w + r * w + cc
                        out[ni, ci, a, i, j] = best
                        mask[ni, ci, a, i, j] = bidx
    return out, mask


def test_max_pool3d_with_index():
    """the 3-D sibling of max_pool2d_with_index
    (reference pool_with_index_op.cc:276), incl. a padded config where
    the argmax must never land in the padding."""
    x = R.randn(2, 2, 4, 4, 4).astype(np.float32)
    got = run_op("max_pool3d_with_index", {"X": x},
                 attrs={"ksize": [2, 2, 2], "strides": [2, 2, 2],
                        "paddings": [0, 0, 0]}, outs=("Out", "Mask"))
    want_out, want_mask = _ref_pool3d_with_index(x, 2, 2, 0)
    np.testing.assert_allclose(np.asarray(got["Out"]), want_out, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(got["Mask"]), want_mask)

    got = run_op("max_pool3d_with_index", {"X": x},
                 attrs={"ksize": [3, 3, 3], "strides": [2, 2, 2],
                        "paddings": [1, 1, 1]}, outs=("Out", "Mask"))
    want_out, want_mask = _ref_pool3d_with_index(x, 3, 2, 1)
    np.testing.assert_allclose(np.asarray(got["Out"]), want_out, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(got["Mask"]), want_mask)

    got = run_op("max_pool3d_with_index", {"X": x},
                 attrs={"ksize": [2, 2, 2], "global_pooling": True},
                 outs=("Out", "Mask"))
    np.testing.assert_allclose(
        np.asarray(got["Out"])[:, :, 0, 0, 0], x.max(axis=(2, 3, 4)),
        rtol=1e-6)


def test_spp():
    x = R.randn(2, 3, 7, 9).astype(np.float32)
    out = np.asarray(run_op("spp", {"X": x},
                            attrs={"pyramid_height": 3,
                                   "pooling_type": "max"})["Out"])
    assert out.shape == (2, 3 * (1 + 4 + 16))
    # level 0 is global max pooling
    np.testing.assert_allclose(out[:, :3], x.max(axis=(2, 3)), rtol=1e-6)
    # avg level 0 is the global mean (exclusive padding)
    out_avg = np.asarray(run_op("spp", {"X": x},
                                attrs={"pyramid_height": 1,
                                       "pooling_type": "avg"})["Out"])
    np.testing.assert_allclose(out_avg, x.mean(axis=(2, 3)), rtol=1e-5)


def test_ctc_align():
    inp = np.array([[0, 1, 1, 0, 2, 2, 2, 0, 3],
                    [4, 4, 0, 5, 5, 5, 6, 0, 0]], np.int32)
    got = run_op("ctc_align", {"Input": inp},
                 attrs={"blank": 0, "merge_repeated": True},
                 outs=("Output", "OutLengths"))
    out = np.asarray(got["Output"])
    lens = np.asarray(got["OutLengths"])
    np.testing.assert_array_equal(lens, [3, 3])
    np.testing.assert_array_equal(out[0, :3], [1, 2, 3])
    np.testing.assert_array_equal(out[1, :3], [4, 5, 6])
    assert np.all(out[0, 3:] == 0) and np.all(out[1, 3:] == 0)

    # no merge: repeats survive, blanks still dropped
    got2 = run_op("ctc_align", {"Input": inp},
                  attrs={"blank": 0, "merge_repeated": False},
                  outs=("Output", "OutLengths"))
    np.testing.assert_array_equal(np.asarray(got2["OutLengths"]), [6, 6])
    np.testing.assert_array_equal(np.asarray(got2["Output"])[0, :6],
                                  [1, 1, 2, 2, 2, 3])

    # lengths mask the tail
    lens_in = np.array([4, 2], np.int32)
    got3 = run_op("ctc_align", {"Input": inp, "Lengths": lens_in},
                  attrs={"blank": 0, "merge_repeated": True},
                  outs=("Output", "OutLengths"))
    np.testing.assert_array_equal(np.asarray(got3["OutLengths"]), [1, 1])
    np.testing.assert_array_equal(np.asarray(got3["Output"])[0, 0], 1)
    np.testing.assert_array_equal(np.asarray(got3["Output"])[1, 0], 4)


def test_fake_quantize_abs_max():
    x = np.array([[0.5, -2.0], [1.0, 0.25]], np.float32)
    got = run_op("fake_quantize", {"X": x},
                 attrs={"quantize_type": "abs_max", "bit_length": 8},
                 outs=("Out", "OutMovingScale"))
    scale = 2.0
    want = np.round(127.0 / scale * np.clip(x, -scale, scale))
    np.testing.assert_allclose(np.asarray(got["Out"]), want)
    np.testing.assert_allclose(np.asarray(got["OutMovingScale"]), [2.0])
    # round-trip through dequantize recovers x up to quantization error
    deq = run_op("fake_dequantize_max_abs",
                 {"X": np.asarray(got["Out"]),
                  "Scale": np.array([scale], np.float32)},
                 attrs={"max_range": 127.0})["Out"]
    np.testing.assert_allclose(np.asarray(deq), x, atol=scale / 127.0)
    # abs_max with the window state wired (as reference QAT
    # graphs declare it) zero-fills OutScales/OutCurrentIter
    got = run_op("fake_quantize",
                 {"X": x, "InScales": np.ones(4, np.float32),
                  "InCurrentIter": np.array([7], np.int64)},
                 attrs={"quantize_type": "abs_max", "bit_length": 8},
                 outs=("Out", "OutScales", "OutCurrentIter"))
    np.testing.assert_allclose(np.asarray(got["OutScales"]), np.zeros(4))
    np.testing.assert_array_equal(np.asarray(got["OutCurrentIter"]), [0])


def test_fake_quantize_moving_average():
    x = np.array([3.0, -1.0], np.float32)
    got = run_op("fake_quantize",
                 {"X": x, "InMovingScale": np.array([1.0], np.float32)},
                 attrs={"quantize_type": "moving_average_abs_max",
                        "bit_length": 8},
                 outs=("Out", "OutMovingScale"))
    scale = 0.9 * 3.0 + 0.1 * 1.0  # reference coefficient order
    np.testing.assert_allclose(np.asarray(got["OutMovingScale"]), [scale],
                               rtol=1e-6)
    want = np.round(127.0 / scale * np.clip(x, -scale, scale))
    np.testing.assert_allclose(np.asarray(got["Out"]), want)
    # is_test: the stored scale is used unchanged
    got_t = run_op("fake_quantize",
                   {"X": x, "InMovingScale": np.array([5.0], np.float32)},
                   attrs={"quantize_type": "moving_average_abs_max",
                          "is_test": True},
                   outs=("Out", "OutMovingScale"))
    np.testing.assert_allclose(np.asarray(got_t["OutMovingScale"]), [5.0])


def test_fake_quantize_range_abs_max():
    window = 4
    scales = np.zeros(window, np.float32)
    moving = np.array([0.0], np.float32)
    it = np.array([0], np.int32)
    seen = []
    for step, mx in enumerate([1.0, 3.0, 2.0, 0.5, 0.25, 0.1]):
        x = np.array([mx, -mx / 2], np.float32)
        got = run_op("fake_quantize",
                     {"X": x, "InScales": scales, "InMovingScale": moving,
                      "InCurrentIter": it},
                     attrs={"quantize_type": "range_abs_max",
                            "window_size": window, "bit_length": 8},
                     outs=("Out", "OutScales", "OutMovingScale",
                           "OutCurrentIter"))
        scales = np.asarray(got["OutScales"])
        moving = np.asarray(got["OutMovingScale"])
        it = np.asarray(got["OutCurrentIter"])
        seen.append(float(moving[0]))
    # running max grows to 3.0 and stays until 3.0 leaves the window
    # (slot 1 is overwritten at step 5 -> rescan of [0.25, 0.1, 2.0, 0.5])
    assert seen[:4] == [1.0, 3.0, 3.0, 3.0]
    assert seen[4] == 3.0
    assert abs(seen[5] - 2.0) < 1e-6
    assert int(it[0]) == 6


def test_fake_quantize_straight_through_grad_and_rounding():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.math import _ste_quantize

    # straight-through: d/dx sum(quantize(x)) == 1 everywhere
    x = jnp.array([0.3, -1.7, 0.9], jnp.float32)
    g = jax.grad(lambda v: jnp.sum(_ste_quantize(v, 2.0, 127.0)))(x)
    np.testing.assert_allclose(np.asarray(g), np.ones(3))

    # half-away-from-zero rounding (C++ std::round), not half-to-even
    v = np.asarray(_ste_quantize(jnp.array([0.5, -0.5, 1.5], jnp.float32),
                                 1.0, 1.0))
    np.testing.assert_allclose(v, [1.0, -1.0, 1.0])

    # is_test with an uninitialized (zero) scale must stay finite
    out = run_op("fake_quantize",
                 {"X": np.array([1.0, -1.0], np.float32),
                  "InMovingScale": np.array([0.0], np.float32)},
                 attrs={"quantize_type": "moving_average_abs_max",
                        "is_test": True})["Out"]
    assert np.isfinite(np.asarray(out)).all()


def test_fusion_lstm_matches_projection_plus_lstm():
    B, T, M, D = 2, 5, 3, 4
    x = R.randn(B, T, M).astype(np.float32)
    wx = R.randn(M, 4 * D).astype(np.float32)
    wh = R.randn(D, 4 * D).astype(np.float32) * 0.3
    b = R.randn(1, 4 * D).astype(np.float32)
    lens = np.array([5, 3], np.int32)
    fused = run_op("fusion_lstm",
                   {"X": x, "WeightX": wx, "WeightH": wh, "Bias": b,
                    "Lengths": lens},
                   outs=("Hidden", "Cell", "XX"))
    xx = x.reshape(-1, M) @ wx
    np.testing.assert_allclose(np.asarray(fused["XX"]).reshape(-1, 4 * D),
                               xx, rtol=1e-5)
    plain = run_op("lstm", {"Input": xx.reshape(B, T, 4 * D),
                            "Weight": wh, "Bias": b, "Lengths": lens},
                   outs=("Hidden", "Cell"))
    np.testing.assert_allclose(np.asarray(fused["Hidden"]),
                               np.asarray(plain["Hidden"]), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(fused["Cell"]),
                               np.asarray(plain["Cell"]), rtol=1e-5)


def test_fusion_gru_matches_projection_plus_gru():
    B, T, M, D = 2, 4, 3, 5
    x = R.randn(B, T, M).astype(np.float32)
    wx = R.randn(M, 3 * D).astype(np.float32)
    wh = R.randn(D, 3 * D).astype(np.float32) * 0.3
    b = R.randn(1, 3 * D).astype(np.float32)
    fused = run_op("fusion_gru", {"X": x, "WeightX": wx, "WeightH": wh,
                                  "Bias": b}, outs=("Hidden", "XX"))
    xx = (x.reshape(-1, M) @ wx).reshape(B, T, 3 * D)
    plain = run_op("gru", {"Input": xx, "Weight": wh, "Bias": b},
                   outs=("Hidden",))
    np.testing.assert_allclose(np.asarray(fused["Hidden"]),
                               np.asarray(plain["Hidden"]), rtol=1e-5)


def _sigmoid(v):
    return 1.0 / (1.0 + np.exp(-v))


def test_attention_lstm():
    B, T, M, D = 2, 4, 3, 2
    x = R.randn(B, T, M).astype(np.float32)
    c0 = R.randn(B, D).astype(np.float32) * 0.2
    h0 = R.randn(B, D).astype(np.float32) * 0.2
    aw = R.randn(M + D, 1).astype(np.float32)
    ab = R.randn(1, 1).astype(np.float32)
    lw = (R.randn(D + M, 4 * D) * 0.4).astype(np.float32)
    lb = R.randn(1, 4 * D).astype(np.float32)
    got = run_op("attention_lstm",
                 {"X": x, "C0": c0, "H0": h0, "AttentionWeight": aw,
                  "AttentionBias": ab, "LSTMWeight": lw, "LSTMBias": lb},
                 outs=("Hidden", "Cell"))

    # numpy replay (reference gate layout: [forget, input, output, tilde])
    h, c = h0.copy(), c0.copy()
    want_h = np.zeros((B, T, D))
    want_c = np.zeros((B, T, D))
    for t in range(T):
        score = x.reshape(B, T, M) @ aw[:M, 0] + ab[0, 0] \
            + (c @ aw[M:, 0])[:, None]
        score = np.maximum(score, 0)
        attn = np.exp(score - score.max(1, keepdims=True))
        attn /= attn.sum(1, keepdims=True)
        lstm_x = np.einsum("bt,btm->bm", attn, x)
        gates = np.concatenate([h, lstm_x], 1) @ lw + lb[0]
        f = _sigmoid(gates[:, :D])
        i = _sigmoid(gates[:, D:2 * D])
        o = _sigmoid(gates[:, 2 * D:3 * D])
        tilde = np.tanh(gates[:, 3 * D:])
        c = f * c + i * tilde
        h = np.tanh(c) * o
        want_h[:, t] = h
        want_c[:, t] = c
    np.testing.assert_allclose(np.asarray(got["Hidden"]), want_h, rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(got["Cell"]), want_c, rtol=1e-4,
                               atol=1e-5)


def test_fusion_seqexpand_concat_fc():
    B, T, M0, M1, DD = 2, 3, 4, 2, 5
    seq = R.randn(B, T, M0).astype(np.float32)
    vec = R.randn(B, M1).astype(np.float32)
    w = R.randn(M0 + M1, DD).astype(np.float32)
    b = R.randn(DD).astype(np.float32)
    got = run_op("fusion_seqexpand_concat_fc",
                 {"X": [seq, vec], "FCWeight": w, "FCBias": b},
                 attrs={"fc_activation": "relu"}, outs=("Out", "FCOut"))
    cat = np.concatenate(
        [seq, np.repeat(vec[:, None, :], T, axis=1)], axis=-1)
    fcout = cat @ w + b
    np.testing.assert_allclose(np.asarray(got["FCOut"]), fcout, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got["Out"]),
                               np.maximum(fcout, 0), rtol=1e-5)


def test_attention_lstm_scalar_and_lengths():
    B, T, M, D = 2, 4, 3, 2
    x = R.randn(B, T, M).astype(np.float32)
    c0 = R.randn(B, D).astype(np.float32) * 0.2
    aw = R.randn(M + D, 1).astype(np.float32)
    scal = np.array([[1.7]], np.float32)
    scal_b = np.array([[-0.2]], np.float32)
    lw = (R.randn(D + M, 4 * D) * 0.4).astype(np.float32)
    lb = R.randn(1, 4 * D).astype(np.float32)
    lens = np.array([4, 2], np.int32)
    got = run_op("attention_lstm",
                 {"X": x, "C0": c0, "AttentionWeight": aw,
                  "AttentionScalar": scal, "AttentionScalarBias": scal_b,
                  "LSTMWeight": lw, "LSTMBias": lb, "Lengths": lens},
                 outs=("Hidden", "Cell"))

    h, c = np.zeros((B, D), np.float32), c0.copy()
    want_h = np.zeros((B, T, D))
    for t in range(T):
        score = x @ aw[:M, 0] + (c @ aw[M:, 0])[:, None]
        score = np.maximum(score, 0)
        score = np.maximum(score * scal[0, 0] + scal_b[0, 0], 0)
        # padded positions leave the softmax entirely
        score = np.where(np.arange(T)[None, :] < lens[:, None], score,
                         -np.inf)
        attn = np.exp(score - score.max(1, keepdims=True))
        attn /= attn.sum(1, keepdims=True)
        lstm_x = np.einsum("bt,btm->bm", attn, x)
        gates = np.concatenate([h, lstm_x], 1) @ lw + lb[0]
        f, i = _sigmoid(gates[:, :D]), _sigmoid(gates[:, D:2 * D])
        o, tilde = _sigmoid(gates[:, 2 * D:3 * D]), np.tanh(gates[:, 3 * D:])
        c_new = f * c + i * tilde
        h_new = np.tanh(c_new) * o
        keep = (t < lens)[:, None]
        h = np.where(keep, h_new, h)
        c = np.where(keep, c_new, c)
        want_h[:, t] = h
    np.testing.assert_allclose(np.asarray(got["Hidden"]), want_h,
                               rtol=1e-4, atol=1e-5)


def test_fill_op():
    got = np.asarray(run_op("fill", {}, attrs={
        "value": [1.0, 2.0, 3.0, 4.0], "shape": [2, 2],
        "dtype": "float32"})["Out"])
    np.testing.assert_allclose(got, [[1, 2], [3, 4]])
    assert got.dtype == np.float32
    got_i = np.asarray(run_op("fill", {}, attrs={
        "value": [7, 8], "shape": [2], "dtype": "int32"})["Out"])
    assert got_i.dtype == np.int32 and list(got_i) == [7, 8]


def test_fused_elemwise_activation():
    x = R.randn(3, 4).astype(np.float32)
    y = R.randn(3, 4).astype(np.float32)
    # Out = X + scale(Y)
    got = run_op("fused_elemwise_activation", {"X": x, "Y": y},
                 attrs={"functor_list": ["elementwise_add", "scale"],
                        "scale": 0.5},
                 outs=("Out", "IntermediateOut"))
    np.testing.assert_allclose(np.asarray(got["IntermediateOut"]), y * 0.5,
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got["Out"]), x + y * 0.5,
                               rtol=1e-6)
    # Out = relu(X + Y)
    got2 = run_op("fused_elemwise_activation", {"X": x, "Y": y},
                  attrs={"functor_list": ["relu", "elementwise_add"]},
                  outs=("Out", "IntermediateOut"))
    np.testing.assert_allclose(np.asarray(got2["Out"]),
                               np.maximum(x + y, 0), rtol=1e-6)
    # broadcast along axis like elementwise_add
    y1 = R.randn(4).astype(np.float32)
    got3 = run_op("fused_elemwise_activation", {"X": x, "Y": y1},
                  attrs={"functor_list": ["elementwise_add", "relu"],
                         "axis": 1})["Out"]
    np.testing.assert_allclose(np.asarray(got3), x + np.maximum(y1, 0),
                               rtol=1e-6)


def test_average_accumulates():
    shape = (3,)
    p = np.full(shape, 2.0, np.float32)
    s1 = np.zeros(shape, np.float32)
    s2 = np.zeros(shape, np.float32)
    s3 = np.zeros(shape, np.float32)
    na = np.array([0], np.int64)
    oa = np.array([0], np.int64)
    nu = np.array([0], np.int64)
    attrs = {"average_window": 0.5, "max_average_window": 4,
             "min_average_window": 2}
    outs = ("out_sum_1", "out_sum_2", "out_sum_3", "out_num_accumulates",
            "out_old_num_accumulates", "out_num_updates")
    for step in range(1, 6):
        got = run_op("average_accumulates",
                     {"param": p, "in_sum_1": s1, "in_sum_2": s2,
                      "in_sum_3": s3, "in_num_accumulates": na,
                      "in_old_num_accumulates": oa, "in_num_updates": nu},
                     attrs=attrs, outs=outs)
        s1 = np.asarray(got["out_sum_1"])
        s2 = np.asarray(got["out_sum_2"])
        s3 = np.asarray(got["out_sum_3"])
        na = np.asarray(got["out_num_accumulates"])
        oa = np.asarray(got["out_old_num_accumulates"])
        nu = np.asarray(got["out_num_updates"])
    # windows roll at steps 2 and 4 (num_acc >= min(4, updates*0.5) and
    # >= min_window 2), so after 5 steps: one fresh accumulation in s1,
    # s3 holds the 2-step window sum (2 params * 2.0 = 4.0 each)
    assert int(nu[0]) == 5
    assert int(oa[0]) == 2
    assert int(na[0]) == 1
    np.testing.assert_allclose(s3, np.full(shape, 4.0))
    np.testing.assert_allclose(s1, np.full(shape, 2.0))


def test_average_accumulates_default_window():
    # the default max_average_window must not overflow int32 (x64 off)
    shape = (2,)
    got = run_op("average_accumulates",
                 {"param": np.ones(shape, np.float32),
                  "in_sum_1": np.zeros(shape, np.float32),
                  "in_sum_2": np.zeros(shape, np.float32),
                  "in_sum_3": np.zeros(shape, np.float32),
                  "in_num_accumulates": np.array([0], np.int64),
                  "in_old_num_accumulates": np.array([0], np.int64),
                  "in_num_updates": np.array([0], np.int64)},
                 attrs={"average_window": 0.1},
                 outs=("out_sum_1", "out_num_updates"))
    np.testing.assert_allclose(np.asarray(got["out_sum_1"]), np.ones(shape))
    assert int(np.asarray(got["out_num_updates"])[0]) == 1


def test_fea_intermediate_keeps_y_shape():
    import jax.numpy as jnp

    x = R.randn(3, 4).astype(np.float32)
    y1 = R.randn(4).astype(np.float32)
    got = run_op("fused_elemwise_activation", {"X": x, "Y": y1},
                 attrs={"functor_list": ["elementwise_add", "scale"],
                        "scale": 2.0, "axis": 1},
                 outs=("Out", "IntermediateOut"))
    assert np.asarray(got["IntermediateOut"]).shape == (4,)
    np.testing.assert_allclose(np.asarray(got["Out"]), x + 2.0 * y1,
                               rtol=1e-6)

    # jax arrays bind as factory inputs too (lowercase slot)
    from paddle_tpu.op import Operator

    out = Operator("scale", X=jnp.arange(3, dtype=jnp.float32),
                   scale=2.0).run()["Out"]
    np.testing.assert_allclose(out, [0.0, 2.0, 4.0])
