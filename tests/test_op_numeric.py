"""Per-op numeric sweep (OpTest): forward vs numpy, gradient vs finite
differences, for the registered kernels. Reference model:
python/paddle/fluid/tests/unittests/op_test.py + the per-op test files.

Ops with dedicated numeric tests elsewhere (control flow, CRF/CTC/beam,
detection, attention, fused loss, RNN layers) are listed in COVERED_ELSEWHERE
and counted by the coverage gate at the bottom.
"""
from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from op_test import check_forward, check_grad, run_op


def rs(seed=0):
    return np.random.RandomState(seed)


def away(x, points, margin=0.12):
    """Push values of x away from non-smooth points (for finite diffs)."""
    x = x.copy()
    for p in points:
        close = np.abs(x - p) < margin
        x[close] = p + margin * np.where(x[close] >= p, 1.0, -1.0) * 1.5
    return x


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


# ---------------------------------------------------------------------------
# unary activations: name -> (numpy ref(attrs), attrs, input, grad_ok)
# ---------------------------------------------------------------------------

_X = rs(1).uniform(-2.5, 2.5, (3, 4)).astype(np.float32)
_XPOS = (np.abs(_X) + 0.5).astype(np.float32)
_XSAFE = away(_X, [0.0])  # away from 0 for |x|-style kinks

UNARY = {
    "sigmoid": (lambda x: _sigmoid(x), {}, _X, True),
    "logsigmoid": (lambda x: np.log(_sigmoid(x)), {}, _X, True),
    "exp": (np.exp, {}, _X, True),
    "relu": (lambda x: np.maximum(x, 0), {}, _XSAFE, True),
    "tanh": (np.tanh, {}, _X, True),
    "tanh_shrink": (lambda x: x - np.tanh(x), {}, _X, True),
    "sqrt": (np.sqrt, {}, _XPOS, True),
    "abs": (np.abs, {}, _XSAFE, True),
    "ceil": (np.ceil, {}, _X, False),
    "floor": (np.floor, {}, _X, False),
    "cos": (np.cos, {}, _X, True),
    "sin": (np.sin, {}, _X, True),
    "round": (np.round, {}, _X, False),
    "reciprocal": (lambda x: 1.0 / x, {}, _XPOS, True),
    "square": (np.square, {}, _X, True),
    "softplus": (lambda x: np.log1p(np.exp(x)), {}, _X, True),
    "softsign": (lambda x: x / (1 + np.abs(x)), {}, _XSAFE, True),
    "log": (np.log, {}, _XPOS, True),
    "sign": (np.sign, {}, _XSAFE, False),
    "relu6": (lambda x: np.minimum(np.maximum(x, 0), 2.0),
              {"threshold": 2.0}, away(_X, [0.0, 2.0]), True),
    "leaky_relu": (lambda x: np.where(x >= 0, x, 0.1 * x),
                   {"alpha": 0.1}, _XSAFE, True),
    "elu": (lambda x: np.where(x >= 0, x, 1.2 * (np.exp(x) - 1)),
            {"alpha": 1.2}, _XSAFE, True),
    "brelu": (lambda x: np.clip(x, -1.0, 1.5),
              {"t_min": -1.0, "t_max": 1.5}, away(_X, [-1.0, 1.5]), True),
    "soft_relu": (lambda x: np.log1p(np.exp(np.clip(x, -2.0, 2.0))),
                  {"threshold": 2.0}, away(_X, [-2.0, 2.0]), True),
    "pow": (lambda x: np.power(x, 3.0), {"factor": 3.0}, _X, True),
    "stanh": (lambda x: 1.7159 * np.tanh(0.67 * x),
              {"scale_a": 0.67, "scale_b": 1.7159}, _X, True),
    "hard_sigmoid": (lambda x: np.clip(0.2 * x + 0.5, 0, 1),
                     {"slope": 0.2, "offset": 0.5},
                     away(_X, [-2.5, 2.5]), True),
    "swish": (lambda x: x * _sigmoid(1.5 * x), {"beta": 1.5}, _X, True),
    "thresholded_relu": (lambda x: np.where(x > 0.3, x, 0.0),
                         {"threshold": 0.3}, away(_X, [0.3]), True),
    "hard_shrink": (lambda x: np.where(np.abs(x) > 0.5, x, 0.0),
                    {"threshold": 0.5}, away(_X, [-0.5, 0.5]), True),
    "softshrink": (
        lambda x: np.where(x > 0.5, x - 0.5, np.where(x < -0.5, x + 0.5, 0.0)),
        {"lambda": 0.5}, away(_X, [-0.5, 0.5]), True),
}


@pytest.mark.parametrize("name", sorted(UNARY))
def test_unary_forward(name):
    ref, attrs, x, _ = UNARY[name]
    check_forward(name, {"X": x}, lambda: ref(x.astype(np.float64)),
                  attrs=attrs, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("name", sorted(k for k in UNARY if UNARY[k][3]))
def test_unary_grad(name):
    _, attrs, x, _ = UNARY[name]
    check_grad(name, {"X": x[:2, :3]}, "X", attrs=attrs)


# ---------------------------------------------------------------------------
# elementwise binary + axis broadcast
# ---------------------------------------------------------------------------

_A = rs(2).uniform(0.5, 2.0, (2, 3, 4)).astype(np.float32)
_B = rs(3).uniform(0.5, 2.0, (2, 3, 4)).astype(np.float32)
_BROW = rs(4).uniform(0.5, 2.0, (3,)).astype(np.float32)

BINARY = {
    "elementwise_add": (np.add, True),
    "elementwise_sub": (np.subtract, True),
    "elementwise_mul": (np.multiply, True),
    "elementwise_div": (np.divide, True),
    "elementwise_max": (np.maximum, True),
    "elementwise_min": (np.minimum, True),
    "elementwise_pow": (np.power, True),
    "elementwise_mod": (np.mod, False),
}


@pytest.mark.parametrize("name", sorted(BINARY))
def test_binary_forward(name):
    ref, _ = BINARY[name]
    check_forward(name, {"X": _A, "Y": _B},
                  lambda: ref(_A.astype(np.float64), _B.astype(np.float64)),
                  rtol=1e-5, atol=1e-5)
    # paddle axis broadcast: Y spans X dims starting at axis
    check_forward(name, {"X": _A, "Y": _BROW},
                  lambda: ref(_A.astype(np.float64),
                              _BROW.astype(np.float64).reshape(1, 3, 1)),
                  attrs={"axis": 1}, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("name", ["elementwise_add", "elementwise_mul",
                                  "elementwise_div", "elementwise_sub"])
@pytest.mark.parametrize("wrt", ["X", "Y"])
def test_binary_grad(name, wrt):
    # max/min kinks: use well-separated values for those
    check_grad(name, {"X": _A[0, :2, :3], "Y": _B[0, :2, :3]}, wrt)


def test_elementwise_max_min_grad():
    x = np.array([[1.0, 5.0], [2.0, 0.5]], np.float32)
    y = np.array([[3.0, 1.0], [4.0, 2.5]], np.float32)
    for op in ("elementwise_max", "elementwise_min"):
        check_grad(op, {"X": x, "Y": y}, "X")


# ---------------------------------------------------------------------------
# logical / comparison
# ---------------------------------------------------------------------------

_LA = rs(5).rand(3, 4) > 0.5
_LB = rs(6).rand(3, 4) > 0.5
_CA = rs(7).randint(0, 3, (3, 4)).astype(np.float32)
_CB = rs(8).randint(0, 3, (3, 4)).astype(np.float32)

LOGICAL = {
    "logical_and": lambda: np.logical_and(_LA, _LB),
    "logical_or": lambda: np.logical_or(_LA, _LB),
    "logical_xor": lambda: np.logical_xor(_LA, _LB),
}
COMPARE = {
    "equal": lambda: _CA == _CB,
    "not_equal": lambda: _CA != _CB,
    "less_than": lambda: _CA < _CB,
    "less_equal": lambda: _CA <= _CB,
    "greater_than": lambda: _CA > _CB,
    "greater_equal": lambda: _CA >= _CB,
}


@pytest.mark.parametrize("name", sorted(LOGICAL))
def test_logical(name):
    got = run_op(name, {"X": _LA, "Y": _LB})["Out"]
    np.testing.assert_array_equal(np.asarray(got), LOGICAL[name]())


def test_logical_not():
    got = run_op("logical_not", {"X": _LA})["Out"]
    np.testing.assert_array_equal(np.asarray(got), ~_LA)


@pytest.mark.parametrize("name", sorted(COMPARE))
def test_compare(name):
    got = run_op(name, {"X": _CA, "Y": _CB})["Out"]
    np.testing.assert_array_equal(np.asarray(got), COMPARE[name]())


def test_isfinite():
    x = np.array([1.0, np.inf, -np.inf, np.nan, 2.0], np.float32)
    got = np.asarray(run_op("isfinite", {"X": x})["Out"])
    # reference isfinite_op reduces to a single bool: "contains only finite"
    assert got.reshape(-1).shape[0] in (1, 5)
    if got.size == 1:
        assert not bool(got.reshape(()))
    else:
        np.testing.assert_array_equal(got, np.isfinite(x))


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

_RX = rs(9).uniform(0.5, 1.5, (2, 3, 4)).astype(np.float32)

REDUCE = {
    "reduce_sum": np.sum,
    "reduce_mean": np.mean,
    "reduce_max": np.max,
    "reduce_min": np.min,
    "reduce_prod": np.prod,
}


@pytest.mark.parametrize("name", sorted(REDUCE))
def test_reduce_forward(name):
    ref = REDUCE[name]
    x64 = _RX.astype(np.float64)
    check_forward(name, {"X": _RX}, lambda: ref(x64, axis=1),
                  attrs={"dim": [1], "keep_dim": False}, rtol=1e-5, atol=1e-5)
    check_forward(name, {"X": _RX}, lambda: ref(x64, axis=1, keepdims=True),
                  attrs={"dim": [1], "keep_dim": True}, rtol=1e-5, atol=1e-5)
    check_forward(name, {"X": _RX}, lambda: np.asarray(ref(x64)),
                  attrs={"reduce_all": True}, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("name", ["reduce_sum", "reduce_mean", "reduce_prod"])
def test_reduce_grad(name):
    check_grad(name, {"X": _RX[:, :2, :2]}, "X", attrs={"dim": [1]})


def test_mean_op():
    check_forward("mean", {"X": _RX},
                  lambda: np.asarray(_RX.astype(np.float64).mean()))
    check_grad("mean", {"X": _RX[0, :2, :2]}, "X")


def test_sum_op():
    xs = [rs(i).randn(2, 3).astype(np.float32) for i in (10, 11, 12)]
    check_forward("sum", {"X": xs},
                  lambda: sum(x.astype(np.float64) for x in xs))


# ---------------------------------------------------------------------------
# shape manipulation
# ---------------------------------------------------------------------------

_SX = rs(13).randn(2, 3, 4).astype(np.float32)


def test_reshape():
    check_forward("reshape", {"X": _SX}, lambda: _SX.reshape(2, 12),
                  attrs={"shape": [2, 12]})
    check_forward("reshape", {"X": _SX}, lambda: _SX.reshape(6, 4),
                  attrs={"shape": [-1, 4]})
    check_grad("reshape", {"X": _SX[:, :2, :2]}, "X", attrs={"shape": [2, 4]})


def test_squeeze_unsqueeze():
    x = _SX[:, :1]
    check_forward("squeeze", {"X": x}, lambda: x.squeeze(1),
                  attrs={"axes": [1]})
    check_forward("unsqueeze", {"X": _SX}, lambda: _SX[:, None],
                  attrs={"axes": [1]})


def test_transpose():
    check_forward("transpose", {"X": _SX}, lambda: _SX.transpose(2, 0, 1),
                  attrs={"axis": [2, 0, 1]})
    check_grad("transpose", {"X": _SX[:, :2, :2]}, "X",
               attrs={"axis": [1, 0, 2]})


def test_concat_split_stack_unstack():
    a, b = _SX, _SX + 1
    check_forward("concat", {"X": [a, b]},
                  lambda: np.concatenate([a, b], axis=1), attrs={"axis": 1})
    got = run_op("split", {"X": _SX}, attrs={"axis": 2, "num": 2},
                 outs=("Out",))
    # split returns a list bound to multiple outputs; with one declared
    # output var the first section lands there
    parts = np.split(_SX, 2, axis=2)
    np.testing.assert_allclose(np.asarray(got["Out"]), parts[0], rtol=1e-6)
    check_forward("stack", {"X": [a, b]}, lambda: np.stack([a, b], axis=0),
                  outs=("Y",))
    got = run_op("unstack", {"X": _SX}, attrs={"axis": 0}, outs=("Y",))
    np.testing.assert_allclose(np.asarray(got["Y"]), _SX[0], rtol=1e-6)


def test_flatten():
    check_forward("flatten", {"X": _SX}, lambda: _SX.reshape(6, 4),
                  attrs={"axis": 2})
    check_forward("flatten", {"X": _SX}, lambda: _SX.reshape(1, 24),
                  attrs={"axis": 0})


def test_pad_crop_reverse_expand():
    check_forward("pad", {"X": _SX[0]},
                  lambda: np.pad(_SX[0], [(1, 0), (0, 2)],
                                 constant_values=0.5),
                  attrs={"paddings": [1, 0, 0, 2], "pad_value": 0.5})
    y = np.zeros((5, 6), np.float32)
    check_forward("pad_constant_like", {"X": y, "Y": _SX[0]},
                  lambda: np.pad(_SX[0], [(0, 2), (0, 2)]),
                  attrs={"pad_value": 0.0})
    check_forward("crop", {"X": _SX[0]},
                  lambda: _SX[0][1:3, 1:4],
                  attrs={"offsets": [1, 1], "shape": [2, 3]})
    check_forward("reverse", {"X": _SX}, lambda: _SX[:, ::-1],
                  attrs={"axis": [1]})
    check_forward("expand", {"X": _SX[0]}, lambda: np.tile(_SX[0], (2, 3)),
                  attrs={"expand_times": [2, 3]})


def test_slice_shape():
    check_forward("slice", {"Input": _SX},
                  lambda: _SX[:, 1:3, 0:2],
                  attrs={"axes": [1, 2], "starts": [1, 0], "ends": [3, 2]})
    got = np.asarray(run_op("shape", {"Input": _SX})["Out"])
    np.testing.assert_array_equal(got, [2, 3, 4])


# ---------------------------------------------------------------------------
# indexing / gathering
# ---------------------------------------------------------------------------


def test_gather_scatter():
    gx = rs(60).randn(5, 3).astype(np.float32)
    idx = np.array([2, 0, 4, 2], np.int64)
    check_forward("gather", {"X": gx, "Index": idx}, lambda: gx[idx])
    x = np.zeros((4, 3), np.float32)
    upd = rs(14).randn(2, 3).astype(np.float32)
    ids = np.array([1, 3], np.int64)
    want = x.copy()
    want[ids] = upd
    check_forward("scatter", {"X": x, "Ids": ids, "Updates": upd},
                  lambda: want, attrs={"overwrite": True})
    want2 = x.copy()
    np.add.at(want2, ids, upd)
    check_forward("scatter", {"X": x, "Ids": ids, "Updates": upd},
                  lambda: want2, attrs={"overwrite": False})


def test_lookup_table():
    w = rs(15).randn(10, 4).astype(np.float32)
    ids = np.array([[1], [7], [0]], np.int64)
    check_forward("lookup_table", {"W": w, "Ids": ids},
                  lambda: w[ids.reshape(-1)].reshape(3, 4))


def test_one_hot():
    x = np.array([[1], [0], [3]], np.int64)
    got = np.asarray(run_op("one_hot", {"X": x}, attrs={"depth": 4})["Out"])
    want = np.eye(4, dtype=np.float32)[x.reshape(-1)]
    np.testing.assert_array_equal(got.reshape(3, 4), want)


def test_multiplex():
    xs = [rs(i).randn(4, 3).astype(np.float32) for i in (16, 17)]
    ids = np.array([[0], [1], [1], [0]], np.int64)
    want = np.stack([xs[ids[i, 0]][i] for i in range(4)])
    check_forward("multiplex", {"X": xs, "Ids": ids}, lambda: want)


def test_topk_argmax_argsort():
    x = rs(18).randn(3, 5).astype(np.float32)
    got = run_op("top_k", {"X": x}, attrs={"k": 2}, outs=("Out", "Indices"))
    order = np.argsort(-x, axis=1)[:, :2]
    np.testing.assert_allclose(np.asarray(got["Out"]),
                               np.take_along_axis(x, order, 1), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(got["Indices"]), order)
    np.testing.assert_array_equal(
        np.asarray(run_op("arg_max", {"X": x}, attrs={"axis": 1})["Out"]),
        np.argmax(x, 1))
    np.testing.assert_array_equal(
        np.asarray(run_op("arg_min", {"X": x}, attrs={"axis": 0})["Out"]),
        np.argmin(x, 0))
    got = run_op("argsort", {"X": x}, attrs={"axis": 1},
                 outs=("Out", "Indices"))
    np.testing.assert_allclose(np.asarray(got["Out"]), np.sort(x, 1),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(got["Indices"]),
                                  np.argsort(x, 1))


def test_cast_assign_fills():
    x = rs(19).randn(2, 3).astype(np.float32)
    got = np.asarray(run_op("cast", {"X": x},
                            attrs={"out_dtype": "int32"})["Out"])
    np.testing.assert_array_equal(got, x.astype(np.int32))
    check_forward("assign", {"X": x}, lambda: x)
    got = np.asarray(run_op("assign_value", {}, attrs={
        "shape": [2, 2], "dtype": "float32",
        "values": [1.0, 2.0, 3.0, 4.0]})["Out"])
    np.testing.assert_allclose(got, [[1, 2], [3, 4]])
    got = np.asarray(run_op("fill_constant", {}, attrs={
        "shape": [2, 3], "dtype": "float32", "value": 2.5})["Out"])
    np.testing.assert_array_equal(got, np.full((2, 3), 2.5, np.float32))
    got = np.asarray(run_op("fill_constant_batch_size_like", {"Input": x},
                            attrs={"shape": [5, 7], "dtype": "float32",
                                   "value": 1.5, "input_dim_idx": 0,
                                   "output_dim_idx": 0})["Out"])
    np.testing.assert_array_equal(got, np.full((2, 7), 1.5, np.float32))
    check_forward("fill_zeros_like", {"X": x}, lambda: np.zeros_like(x))
    check_forward("increment", {"X": np.array([3.0], np.float32)},
                  lambda: np.array([4.5]), attrs={"step": 1.5})


def test_cumsum():
    x = rs(20).randn(2, 4).astype(np.float32)
    check_forward("cumsum", {"X": x}, lambda: np.cumsum(x, 1),
                  attrs={"axis": 1})
    ex = np.concatenate([np.zeros((2, 1)), np.cumsum(x, 1)[:, :-1]], 1)
    check_forward("cumsum", {"X": x}, lambda: ex,
                  attrs={"axis": 1, "exclusive": True}, rtol=1e-5, atol=1e-5)
    rev = np.flip(np.cumsum(np.flip(x, 1), 1), 1)
    check_forward("cumsum", {"X": x}, lambda: rev,
                  attrs={"axis": 1, "reverse": True}, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# matmul family / scaling
# ---------------------------------------------------------------------------


def test_mul_matmul():
    x = rs(21).randn(3, 4).astype(np.float32)
    y = rs(22).randn(4, 5).astype(np.float32)
    check_forward("mul", {"X": x, "Y": y}, lambda: x @ y)
    x4 = rs(23).randn(2, 3, 4, 5).astype(np.float32)
    y2 = rs(24).randn(20, 6).astype(np.float32)
    # reference mul_op: out shape = x.shape[:x_ncd] + y.shape[y_ncd:]
    check_forward("mul", {"X": x4, "Y": y2},
                  lambda: (x4.reshape(6, 20) @ y2).reshape(2, 3, 6),
                  attrs={"x_num_col_dims": 2, "y_num_col_dims": 1})
    check_forward("matmul", {"X": x, "Y": y}, lambda: x @ y)
    check_forward("matmul", {"X": x, "Y": y.T}, lambda: x @ y,
                  attrs={"transpose_Y": True})
    b1 = rs(25).randn(2, 3, 4).astype(np.float32)
    b2 = rs(26).randn(2, 4, 5).astype(np.float32)
    check_forward("matmul", {"X": b1, "Y": b2},
                  lambda: np.einsum("bij,bjk->bik", b1, b2))
    check_grad("matmul", {"X": x[:2, :3], "Y": y[:3, :2]}, "X")
    check_grad("mul", {"X": x[:2, :3], "Y": y[:3, :2]}, "Y")


def test_scale_clip():
    x = rs(27).randn(3, 4).astype(np.float32)
    check_forward("scale", {"X": x}, lambda: 2.0 * x + 1.0,
                  attrs={"scale": 2.0, "bias": 1.0})
    check_forward("scale", {"X": x}, lambda: 2.0 * (x + 1.0),
                  attrs={"scale": 2.0, "bias": 1.0,
                         "bias_after_scale": False})
    check_forward("clip", {"X": x}, lambda: np.clip(x, -0.5, 0.5),
                  attrs={"min": -0.5, "max": 0.5})
    nrm = np.sqrt((x ** 2).sum())
    check_forward("clip_by_norm", {"X": x},
                  lambda: x * (1.0 / max(nrm, 1.0)),
                  attrs={"max_norm": 1.0})


def test_l2_normalize_cos_sim():
    x = rs(28).randn(3, 4).astype(np.float32)
    y = rs(29).randn(3, 4).astype(np.float32)
    want = x / np.sqrt((x ** 2).sum(1, keepdims=True) + 1e-10)
    check_forward("l2_normalize", {"X": x}, lambda: want,
                  attrs={"axis": 1, "epsilon": 1e-10},
                  rtol=1e-4, atol=1e-5)
    cs = (x * y).sum(1) / (np.sqrt((x ** 2).sum(1)) * np.sqrt((y ** 2).sum(1)))
    check_forward("cos_sim", {"X": x, "Y": y},
                  lambda: cs.reshape(3, 1), rtol=1e-4, atol=1e-5)


def test_bilinear_tensor_product():
    x = rs(30).randn(3, 4).astype(np.float32)
    y = rs(31).randn(3, 5).astype(np.float32)
    w = rs(32).randn(6, 4, 5).astype(np.float32)
    b = rs(33).randn(1, 6).astype(np.float32)
    want = np.einsum("bi,oij,bj->bo", x, w, y) + b
    check_forward("bilinear_tensor_product",
                  {"X": x, "Y": y, "Weight": w, "Bias": b}, lambda: want,
                  rtol=1e-4, atol=1e-4)


def test_conv_shift():
    x = rs(34).randn(2, 6).astype(np.float32)
    y = rs(35).randn(2, 3).astype(np.float32)
    n = 6
    half = 1  # (3-1)//2
    want = np.zeros_like(x)
    for b in range(2):
        for i in range(n):
            for j in range(3):
                want[b, i] += x[b, (i + j - half) % n] * y[b, j]
    check_forward("conv_shift", {"X": x, "Y": y}, lambda: want,
                  rtol=1e-4, atol=1e-5)


def test_row_conv():
    # dense batch variant: (B, T, D) with future-context filter (k, D)
    x = rs(36).randn(2, 5, 3).astype(np.float32)
    f = rs(37).randn(2, 3).astype(np.float32)
    want = np.zeros_like(x)
    for b in range(2):
        for t in range(5):
            for j in range(2):
                if t + j < 5:
                    want[b, t] += x[b, t + j] * f[j]
    check_forward("row_conv", {"X": x, "Filter": f}, lambda: want,
                  rtol=1e-4, atol=1e-5)


def test_maxout():
    x = rs(38).randn(2, 6, 3, 3).astype(np.float32)
    want = x.reshape(2, 3, 2, 3, 3).max(axis=2)
    check_forward("maxout", {"X": x}, lambda: want, attrs={"groups": 2})


# ---------------------------------------------------------------------------
# losses / softmax
# ---------------------------------------------------------------------------


def _np_softmax(x, axis=-1):
    e = np.exp(x - x.max(axis=axis, keepdims=True))
    return e / e.sum(axis=axis, keepdims=True)


def test_softmax_ops():
    x = rs(39).randn(3, 5).astype(np.float32)
    check_forward("softmax", {"X": x}, lambda: _np_softmax(x))
    check_forward("log_softmax", {"X": x},
                  lambda: np.log(_np_softmax(x)), rtol=1e-4, atol=1e-5)
    check_grad("softmax", {"X": x[:2, :3]}, "X")


def test_cross_entropy():
    p = _np_softmax(rs(40).randn(4, 5)).astype(np.float32)
    lbl = np.array([[1], [0], [4], [2]], np.int64)
    want = -np.log(p[np.arange(4), lbl.reshape(-1)]).reshape(4, 1)
    check_forward("cross_entropy", {"X": p, "Label": lbl}, lambda: want,
                  outs=("Y",), rtol=1e-4, atol=1e-5)
    soft = _np_softmax(rs(41).randn(4, 5)).astype(np.float32)
    want = -(soft * np.log(p)).sum(1, keepdims=True)
    check_forward("cross_entropy", {"X": p, "Label": soft}, lambda: want,
                  outs=("Y",), attrs={"soft_label": True},
                  rtol=1e-4, atol=1e-5)


def test_softmax_with_cross_entropy():
    logits = rs(42).randn(4, 5).astype(np.float32)
    lbl = np.array([[1], [0], [4], [2]], np.int64)
    p = _np_softmax(logits)
    want = -np.log(p[np.arange(4), lbl.reshape(-1)]).reshape(4, 1)
    check_forward("softmax_with_cross_entropy",
                  {"Logits": logits, "Label": lbl}, lambda: want,
                  outs=("Loss",), rtol=1e-4, atol=1e-5)
    check_grad("softmax_with_cross_entropy",
               {"Logits": logits[:2, :3], "Label": lbl[:2]}, "Logits",
               outs=("Loss",))


def test_square_error_huber_rank():
    x = rs(43).randn(3, 4).astype(np.float32)
    y = rs(44).randn(3, 4).astype(np.float32)
    check_forward("square_error_cost", {"X": x, "Y": y},
                  lambda: (x - y) ** 2)
    d = y - x
    delta = 0.8
    want = np.where(np.abs(d) <= delta, 0.5 * d * d,
                    delta * (np.abs(d) - 0.5 * delta))
    check_forward("huber_loss", {"X": x, "Y": y}, lambda: want,
                  attrs={"delta": delta}, rtol=1e-4, atol=1e-5)
    left = rs(45).rand(3, 1).astype(np.float32)
    right = rs(46).rand(3, 1).astype(np.float32)
    lbl = (rs(47).rand(3, 1) > 0.5).astype(np.float32)
    dd = left - right
    want = np.log1p(np.exp(dd)) - lbl * dd
    check_forward("rank_loss",
                  {"Left": left, "Right": right, "Label": lbl},
                  lambda: want, rtol=1e-4, atol=1e-5)


def test_smooth_l1():
    x = rs(48).randn(3, 4).astype(np.float32)
    y = rs(49).randn(3, 4).astype(np.float32)
    sigma = 1.0
    d = x - y
    s2 = sigma * sigma
    l = np.where(np.abs(d) < 1.0 / s2, 0.5 * s2 * d * d,
                 np.abs(d) - 0.5 / s2)
    want = l.sum(1).reshape(3, 1)
    check_forward("smooth_l1_loss", {"X": x, "Y": y}, lambda: want,
                  attrs={"sigma": sigma}, rtol=1e-4, atol=1e-5)


def test_label_smooth_dice():
    x = _np_softmax(rs(50).randn(3, 4)).astype(np.float32)
    eps = 0.1
    check_forward("label_smooth", {"X": x},
                  lambda: (1 - eps) * x + eps / 4.0,
                  attrs={"epsilon": eps}, rtol=1e-5, atol=1e-6)
    prior = _np_softmax(rs(51).randn(4,)).astype(np.float32)
    check_forward("label_smooth", {"X": x, "PriorDist": prior},
                  lambda: (1 - eps) * x + eps * prior,
                  attrs={"epsilon": eps}, rtol=1e-5, atol=1e-6)
    lbl = np.array([[1], [3], [0]], np.int64)
    onehot = np.eye(4, dtype=np.float64)[lbl.reshape(-1)]
    inter = (x * onehot).sum(1)
    union = x.sum(1) + onehot.sum(1)
    de = 1e-5
    want = np.mean(1 - (2 * inter + de) / (union + de))
    check_forward("dice_loss", {"X": x, "Label": lbl},
                  lambda: np.asarray(want),
                  attrs={"epsilon": de}, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# coverage gate (extended by the other numeric test files)
# ---------------------------------------------------------------------------

# ops with dedicated numeric tests in other test files
COVERED_ELSEWHERE = {
    # control flow: tests/test_control_flow.py
    "while", "conditional_block", "switch", "static_rnn", "dynamic_rnn",
    "create_array", "write_to_array", "read_from_array", "lod_array_length",
    "array_stack", "select", "print", "is_empty", "increment",
    # decode/structured: tests/test_decode.py
    "linear_chain_crf", "crf_decoding", "ctc_greedy_decoder", "warpctc",
    "edit_distance", "chunk_eval", "nce", "hierarchical_sigmoid",
    "beam_search", "beam_search_decode",
    # detection: tests/test_detection.py
    "iou_similarity", "box_coder", "bipartite_match", "target_assign",
    "mine_hard_examples", "multiclass_nms", "detection_map", "prior_box",
    "polygon_box_transform",
    # RPN: tests/test_rpn.py
    "anchor_generator", "rpn_target_assign", "generate_proposals",
    # attention/fused: tests/test_attention.py, tests/test_fused_loss.py
    "fused_attention", "fused_lm_head_loss",
    # transpiler-emitted fusion: tests/test_passes.py
    # (test_fused_fc_numeric_matches_unfused pins it against the
    # unfused mul+elementwise_add+relu chain bit-for-bit)
    "fused_fc",
    # KV-cache decode ops: tests/test_kv_cache_ops.py
    "decode_attention", "cache_append", "cache_gather",
    # speculative verify window (PR 14): tests/test_speculative.py
    # (window append, staircase attention, accept against the plain
    # decode loop); shapes and rules in tests/test_kv_cache_ops.py
    "cache_append_window", "decode_attention_window", "spec_accept",
    # a prediction layer's next tokens and the next draft's pick
    # (tests/test_glm5_decode.py::test_next_tokens_and_the_pick)
    "mtp_next_tokens", "spec_pick",
    # state-space layers and RMS norm: tests/test_ssm_ops.py (numpy
    # recurrence, padded vs unpadded, scan-then-step, infer rules)
    "rms_norm", "ssm_scan", "ssm_step", "causal_conv1d",
    "causal_conv1d_step",
    # rotary positions, routed experts that drop nothing, window
    # attention and its ring: tests/test_rope_moe_ops.py (against the
    # plain reference, closed forms, both sides of the window, the four
    # shares of an expert-parallel deployment, infer rules)
    "rope", "moe_route", "moe_experts", "moe_shared", "prefill_attention",
    "ring_append", "ring_pack", "decode_attn_ring",
    # a slab of flat rows, K's wider than V's, of heads that fill no
    # tile: tests/test_mimo_v2_decode.py (the kernel in interpret mode
    # against the lax path over lengths inside, at and past a block;
    # through the server against the plain reference)
    "decode_attention_uneven",
    # differential attention (full, windowed, one token over a slab and
    # over a wrapped ring, cross) and the gated memory unit against
    # plain statements of them: tests/test_diff_attn_ops.py
    "diff_attention", "diff_decode_attention", "attn_cross", "gmu",
    # latent attention (MLA): tests/test_mla_ops.py (absorbed against
    # expanded on the same latent rows, the rotation of pairs against
    # complex numbers, the query scale, the scale a, the append)
    "mla_q", "mla_kv", "mla_expand", "mla_decode", "mla_append",
    "mla_attend",
    # Kimi Delta Attention: tests/test_kda_ops.py (the chunked scan
    # against the recurrence a token at a time and one step after
    # another, at the gate's bound, with padding; both gates)
    "kda_gate", "kda_scan", "kda_step",
    # many heads a group at a time, the learned indexer's keys and
    # choice: tests/test_mla_ops.py (the grouped cases) and
    # tests/test_dots3_decode.py (select against lax.top_k)
    "latent_prefill", "dsa_index_keys", "dsa_mask",
    # EVA (pooled chunks, windows under one softmax, the entry's packing,
    # a step's appends and its live range): tests/test_evabyte_decode.py
    # (against the plain reference through the server, the kernel's view
    # against the lax path)
    "eva_summaries", "eva_prefill", "eva_pack", "eva_append", "eva_decode",
    # in-graph sampling: tests/test_sampling_ops.py
    "greedy_sample", "top_k_sample", "top_p_sample",
    # metrics: tests/test_aux.py
    "accuracy", "auc",
    # sequence (dense+lengths): tests/test_sequence_ops.py
    "sequence_pool", "sequence_softmax", "sequence_mask", "sequence_expand",
    "sequence_expand_as", "sequence_conv", "sequence_reshape",
    "sequence_pad", "sequence_unpad", "sequence_slice", "sequence_concat",
    "sequence_erase",
    # rnn: tests/test_rnn_ops.py
    "lstm", "gru", "lstmp", "lstm_unit", "gru_unit",
    # nn: tests/test_nn_ops.py
    "conv2d", "conv3d", "conv2d_transpose", "conv3d_transpose",
    "depthwise_conv2d", "depthwise_conv2d_transpose",
    "pool2d", "pool3d", "batch_norm", "layer_norm",
    "lrn", "norm", "dropout", "im2sequence", "roi_pool", "bilinear_interp",
    "nearest_interp", "random_crop", "sampling_id", "gaussian_random",
    "uniform_random", "truncated_gaussian_random", "prelu", "mean_iou",
    # optimizers: tests/test_optim_ops.py
    "sgd", "momentum", "adam", "adamax", "adagrad", "decayed_adagrad",
    "adadelta", "rmsprop", "ftrl",
    # round-2 small-op sweep: tests/test_small_ops.py
    "sigmoid_cross_entropy_with_logits", "uniform_random_batch_size_like",
    "gaussian_random_batch_size_like", "lod_reset",
    # round-2 extra kernels: tests/test_extra_ops.py
    "minus", "hinge_loss", "log_loss", "margin_rank_loss",
    "modified_huber_loss", "squared_l2_distance", "squared_l2_norm",
    "l1_norm", "proximal_gd", "proximal_adagrad", "positive_negative_pair",
    "precision_recall", "max_pool2d_with_index", "max_pool3d_with_index",
    "unpool", "spp",
    "ctc_align", "fake_quantize", "fake_dequantize_max_abs",
    "fusion_lstm", "fusion_gru", "attention_lstm",
    "fusion_seqexpand_concat_fc", "fill", "fused_elemwise_activation",
    "average_accumulates",
    # beam_gather: tests/test_contrib_decoder.py
    "beam_gather",
    # parallel kernels: tests/test_moe.py, tests/test_ring_lm.py (and
    # ring-vs-full parity in tests/test_attention.py)
    "moe_ffn", "ring_attention",
    # int8 quantization tier: tests/test_quant.py (integer-reference
    # batteries) + tests/test_quant_decode.py (slab ops)
    "quantize_linear", "dequantize_linear", "quantized_matmul",
    "quantized_conv2d", "cache_append_quant", "decode_attention_quant",
}

# covered directly in this file
COVERED_HERE = (
    set(UNARY) | set(BINARY) | set(LOGICAL) | set(COMPARE) | set(REDUCE) | {
        "logical_not", "isfinite", "mean", "sum", "reshape", "squeeze",
        "unsqueeze", "transpose", "concat", "split", "stack", "unstack",
        "flatten", "pad", "pad_constant_like", "crop", "reverse", "expand",
        "slice", "shape", "gather", "scatter", "lookup_table", "one_hot",
        "multiplex", "top_k", "arg_max", "arg_min", "argsort", "cast",
        "assign", "assign_value", "fill_constant",
        "fill_constant_batch_size_like", "fill_zeros_like", "increment",
        "cumsum", "mul", "matmul", "scale", "clip", "clip_by_norm",
        "l2_normalize", "cos_sim", "bilinear_tensor_product", "conv_shift",
        "row_conv", "maxout", "softmax", "log_softmax", "cross_entropy",
        "softmax_with_cross_entropy", "square_error_cost", "huber_loss",
        "rank_loss", "smooth_l1_loss", "smooth_l1", "label_smooth",
        "dice_loss", "load_file", "reorder_lod_tensor_by_rank",
    })


def test_registry_coverage():
    from paddle_tpu.ops.registry import registered_ops

    ops = set(registered_ops())
    covered = (COVERED_HERE | COVERED_ELSEWHERE) & ops
    missing = sorted(ops - COVERED_HERE - COVERED_ELSEWHERE)
    frac = len(covered) / len(ops)
    assert frac == 1.0, (
        "numeric coverage %.0f%% below 100%%; uncovered: %s"
        % (100 * frac, missing))


# ---------------------------------------------------------------------------
# extended gradient sweep (round 2): every differentiable op family gets a
# finite-difference check beyond the core set above
# ---------------------------------------------------------------------------

_GX = rs(70).uniform(0.5, 1.5, (2, 3)).astype(np.float32)


def test_grad_losses():
    x = rs(71).randn(2, 3).astype(np.float32)
    y = rs(72).randn(2, 3).astype(np.float32)
    check_grad("huber_loss", {"X": x, "Y": y}, "X", attrs={"delta": 5.0})
    check_grad("square_error_cost", {"X": x, "Y": y}, "X")
    p = _np_softmax(rs(73).randn(2, 4)).astype(np.float32)
    lbl = np.array([[1], [3]], np.int64)
    check_grad("cross_entropy", {"X": p, "Label": lbl}, "X", outs=("Y",))
    check_grad("label_smooth", {"X": p}, "X", attrs={"epsilon": 0.1})
    check_grad("dice_loss", {"X": p, "Label": lbl}, "X")
    lg = rs(74).randn(2, 3).astype(np.float32)
    sl = rs(75).rand(2, 3).astype(np.float32)
    check_grad("sigmoid_cross_entropy_with_logits",
               {"X": lg, "Label": sl}, "X")


def test_grad_normalization():
    check_grad("l2_normalize", {"X": _GX}, "X",
               attrs={"axis": 1, "epsilon": 1e-10})
    check_grad("norm", {"X": _GX}, "X", attrs={"axis": 1})
    x = rs(76).rand(1, 4, 2, 2).astype(np.float32) + 0.5
    check_grad("lrn", {"X": x}, "X", attrs={"n": 3}, rtol=2e-2, atol=2e-3)
    a = np.array([0.3], np.float32)
    xs = away(rs(77).randn(2, 3).astype(np.float32), [0.0])
    check_grad("prelu", {"X": xs, "Alpha": a}, "X", attrs={"mode": "all"})
    check_grad("prelu", {"X": xs, "Alpha": a}, "Alpha",
               attrs={"mode": "all"})


def test_grad_tensor_manip():
    x = rs(78).randn(2, 3).astype(np.float32)
    check_grad("pad", {"X": x}, "X",
               attrs={"paddings": [1, 0, 0, 1], "pad_value": 0.0})
    check_grad("expand", {"X": x}, "X", attrs={"expand_times": [2, 2]})
    check_grad("slice", {"Input": x}, "Input",
               attrs={"axes": [1], "starts": [1], "ends": [3]})
    check_grad("cumsum", {"X": x}, "X", attrs={"axis": 1})
    check_grad("gather", {"X": x, "Index": np.array([1, 0, 1], np.int64)},
               "X")
    w = rs(79).randn(5, 3).astype(np.float32)
    ids = np.array([[1], [4]], np.int64)
    check_grad("lookup_table", {"W": w, "Ids": ids}, "W")
    check_grad("scale", {"X": x}, "X", attrs={"scale": 2.0, "bias": 1.0})
    xc = away(x, [-0.5, 0.5])
    check_grad("clip", {"X": xc}, "X", attrs={"min": -0.5, "max": 0.5})


def test_grad_misc_math():
    x = rs(80).uniform(0.5, 1.5, (2, 3)).astype(np.float32)
    y = rs(81).uniform(0.5, 1.5, (2, 3)).astype(np.float32)
    check_grad("elementwise_pow", {"X": x, "Y": y}, "X")
    check_grad("cos_sim", {"X": x, "Y": y}, "X", rtol=2e-2, atol=2e-3)
    w = (0.3 * rs(82).randn(2, 3, 3)).astype(np.float32)
    b = (0.1 * rs(83).randn(1, 2)).astype(np.float32)
    check_grad("bilinear_tensor_product",
               {"X": x, "Y": y, "Weight": w, "Bias": b}, "X",
               rtol=2e-2, atol=2e-3)
    cs_x = rs(84).randn(1, 4).astype(np.float32)
    cs_y = (0.4 * rs(85).randn(1, 3)).astype(np.float32)
    check_grad("conv_shift", {"X": cs_x, "Y": cs_y}, "X")
    rx = rs(86).randn(1, 4, 2).astype(np.float32)
    rf = (0.4 * rs(87).randn(2, 2)).astype(np.float32)
    check_grad("row_conv", {"X": rx, "Filter": rf}, "X")
    mx = (np.arange(12).reshape(1, 4, 1, 3) * 0.37 + 0.1).astype(np.float32)
    check_grad("maxout", {"X": mx}, "X", attrs={"groups": 2})


def test_grad_conv_variants():
    x = rs(88).randn(1, 2, 3, 3).astype(np.float32)
    w = (0.4 * rs(89).randn(2, 3, 2, 2)).astype(np.float32)  # IOHW
    check_grad("conv2d_transpose", {"Input": x, "Filter": w}, "Input",
               outs=("Output",))
    check_grad("conv2d_transpose", {"Input": x, "Filter": w}, "Filter",
               outs=("Output",))
    x3 = rs(90).randn(1, 1, 3, 3, 3).astype(np.float32)
    w3 = (0.4 * rs(91).randn(2, 1, 2, 2, 2)).astype(np.float32)
    check_grad("conv3d", {"Input": x3, "Filter": w3}, "Input",
               outs=("Output",))
    check_grad("bilinear_interp", {"X": x}, "X",
               attrs={"out_h": 5, "out_w": 5})


def test_grad_sequence_family():
    x = rs(92).randn(2, 4, 2).astype(np.float32)
    lens = np.array([4, 2], np.int32)
    check_grad("sequence_softmax",
               {"X": x[:, :, 0], "Lengths": lens}, "X")
    f = (0.4 * rs(93).randn(6, 3)).astype(np.float32)
    check_grad("sequence_conv", {"X": x, "Lengths": lens, "Filter": f},
               "X", attrs={"contextLength": 3, "contextStart": -1})
    check_grad("sequence_conv", {"X": x, "Lengths": lens, "Filter": f},
               "Filter", attrs={"contextLength": 3, "contextStart": -1})
    # max pool over distinct values (stable argmax)
    xm = (np.arange(16).reshape(2, 4, 2) * 0.31 + 0.05).astype(np.float32)
    check_grad("sequence_pool", {"X": xm, "Lengths": lens}, "X",
               attrs={"pooltype": "MAX"})


# ---------------------------------------------------------------------------
# round-3 closure of the coverage gate: the last two registry ops without a
# dedicated numeric check
# ---------------------------------------------------------------------------


def test_load_file(tmp_path):
    arr = rs(94).randn(3, 4).astype(np.float32)
    path = tmp_path / "var.npy"
    np.save(path, arr)
    out = run_op("load_file", {}, attrs={"file_path": str(path)})["Out"]
    np.testing.assert_allclose(np.asarray(out), arr, rtol=1e-6)
    out16 = run_op("load_file", {}, attrs={"file_path": str(path),
                                           "load_as_fp16": True})["Out"]
    assert np.asarray(out16).dtype == np.float16
    np.testing.assert_allclose(np.asarray(out16), arr.astype(np.float16))


def test_reorder_lod_tensor_by_rank():
    x = rs(95).randn(4, 3).astype(np.float32)
    lens = np.array([2, 5, 1, 3], np.int32)
    got = run_op("reorder_lod_tensor_by_rank",
                 {"X": x, "RankTable": lens},
                 outs=("Out", "OutLengths", "Order"))
    order = np.argsort(-lens, kind="stable")
    np.testing.assert_array_equal(np.asarray(got["Order"]), order)
    np.testing.assert_array_equal(np.asarray(got["OutLengths"]), lens[order])
    np.testing.assert_allclose(np.asarray(got["Out"]), x[order], rtol=1e-6)
