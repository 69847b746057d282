"""Math / tensor op kernels (JAX).

Covers the reference's elementwise_*, activation, reduce, matmul/mul,
softmax/cross-entropy, shape-manipulation and comparison operators
(reference: paddle/fluid/operators/elementwise_op*.h, activation_op.cc,
reduce_op.cc, matmul_op.cc, softmax_op.cc, cross_entropy_op.cc, ...).

All kernels are pure jnp/lax functions: XLA fuses elementwise chains into
matmul epilogues on TPU, so there is no need for the reference's hand-fused
CUDA kernels here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register_op

# ---------------------------------------------------------------------------
# elementwise binary ops with the reference's axis-broadcast rule
# (reference: paddle/fluid/operators/elementwise_op_function.h:46 - Y's shape
# must match a contiguous span of X's dims beginning at `axis`).
# ---------------------------------------------------------------------------


def _broadcast_y(x, y, axis):
    if x.ndim == y.ndim:
        return y
    if axis is None or axis == -1:
        axis = x.ndim - y.ndim
    # squeeze trailing 1s in y (paddle allows (n,1) vs span (n,))
    shape = [1] * x.ndim
    for i, s in enumerate(y.shape):
        shape[axis + i] = s
    return y.reshape(shape)


def _elementwise(fn):
    def kern(ctx):
        x = ctx.input("X")
        y = ctx.input("Y")
        y = _broadcast_y(x, y, ctx.attr("axis", -1))
        return {"Out": fn(x, y)}

    return kern


register_op("elementwise_add")(_elementwise(jnp.add))
register_op("elementwise_sub")(_elementwise(jnp.subtract))
register_op("elementwise_mul")(_elementwise(jnp.multiply))
register_op("elementwise_div")(_elementwise(jnp.divide))
register_op("elementwise_max")(_elementwise(jnp.maximum))
register_op("elementwise_min")(_elementwise(jnp.minimum))
register_op("elementwise_pow")(_elementwise(jnp.power))
register_op("elementwise_mod")(_elementwise(jnp.mod))


# ---------------------------------------------------------------------------
# activations (reference: activation_op.cc — ~30 generated ops)
# ---------------------------------------------------------------------------


def _unary(fn):
    def kern(ctx):
        return {"Out": fn(ctx.input("X"))}

    return kern


register_op("sigmoid")(_unary(jax.nn.sigmoid))
register_op("logsigmoid")(_unary(jax.nn.log_sigmoid))
register_op("exp")(_unary(jnp.exp))
register_op("relu")(_unary(jax.nn.relu))
register_op("tanh")(_unary(jnp.tanh))
register_op("tanh_shrink")(_unary(lambda x: x - jnp.tanh(x)))
register_op("sqrt")(_unary(jnp.sqrt))
register_op("abs")(_unary(jnp.abs))
register_op("ceil")(_unary(jnp.ceil))
register_op("floor")(_unary(jnp.floor))
register_op("cos")(_unary(jnp.cos))
register_op("sin")(_unary(jnp.sin))
register_op("round")(_unary(jnp.round))
register_op("reciprocal")(_unary(lambda x: 1.0 / x))
register_op("square")(_unary(jnp.square))
register_op("softplus")(_unary(jax.nn.softplus))
register_op("softsign")(_unary(lambda x: x / (1 + jnp.abs(x))))
register_op("log")(_unary(jnp.log))
register_op("sign")(_unary(jnp.sign))


@register_op("relu6")
def _relu6(ctx):
    t = ctx.attr("threshold", 6.0)
    return {"Out": jnp.clip(ctx.input("X"), 0.0, t)}


@register_op("leaky_relu")
def _leaky_relu(ctx):
    a = ctx.attr("alpha", 0.02)
    x = ctx.input("X")
    return {"Out": jnp.where(x >= 0, x, a * x)}


@register_op("elu")
def _elu(ctx):
    a = ctx.attr("alpha", 1.0)
    x = ctx.input("X")
    return {"Out": jnp.where(x > 0, x, a * (jnp.exp(x) - 1))}


@register_op("brelu")
def _brelu(ctx):
    lo, hi = ctx.attr("t_min", 0.0), ctx.attr("t_max", 24.0)
    return {"Out": jnp.clip(ctx.input("X"), lo, hi)}


@register_op("soft_relu")
def _soft_relu(ctx):
    t = ctx.attr("threshold", 40.0)
    x = jnp.clip(ctx.input("X"), -t, t)
    return {"Out": jnp.log1p(jnp.exp(x))}


@register_op("pow")
def _pow(ctx):
    return {"Out": jnp.power(ctx.input("X"), ctx.attr("factor", 1.0))}


@register_op("stanh")
def _stanh(ctx):
    a = ctx.attr("scale_a", 2.0 / 3.0)
    b = ctx.attr("scale_b", 1.7159)
    return {"Out": b * jnp.tanh(a * ctx.input("X"))}


@register_op("hard_sigmoid")
def _hard_sigmoid(ctx):
    slope = ctx.attr("slope", 0.2)
    offset = ctx.attr("offset", 0.5)
    return {"Out": jnp.clip(slope * ctx.input("X") + offset, 0.0, 1.0)}


@register_op("swish")
def _swish(ctx):
    beta = ctx.attr("beta", 1.0)
    x = ctx.input("X")
    return {"Out": x * jax.nn.sigmoid(beta * x)}


@register_op("thresholded_relu")
def _thresholded_relu(ctx):
    t = ctx.attr("threshold", 1.0)
    x = ctx.input("X")
    return {"Out": jnp.where(x > t, x, 0.0)}


@register_op("hard_shrink")
def _hard_shrink(ctx):
    t = ctx.attr("threshold", 0.5)
    x = ctx.input("X")
    return {"Out": jnp.where(jnp.abs(x) > t, x, 0.0)}


@register_op("softshrink")
def _softshrink(ctx):
    lam = ctx.attr("lambda", 0.5)
    x = ctx.input("X")
    return {"Out": jnp.where(x > lam, x - lam, jnp.where(x < -lam, x + lam, 0.0))}


@register_op("prelu")
def _prelu(ctx):
    x = ctx.input("X")
    alpha = ctx.input("Alpha")
    mode = ctx.attr("mode", "all")
    if mode == "all":
        a = alpha.reshape(())
    elif mode == "channel":
        a = alpha.reshape((1, -1) + (1,) * (x.ndim - 2))
    else:  # element
        a = alpha.reshape((1,) + x.shape[1:])
    return {"Out": jnp.where(x > 0, x, a * x)}


@register_op("scale")
def _scale(ctx):
    s = ctx.attr("scale", 1.0)
    b = ctx.attr("bias", 0.0)
    after = ctx.attr("bias_after_scale", True)
    x = ctx.input("X")
    out = x * s + b if after else (x + b) * s
    return {"Out": out}


@register_op("clip")
def _clip(ctx):
    return {"Out": jnp.clip(ctx.input("X"), ctx.attr("min"), ctx.attr("max"))}


@register_op("clip_by_norm")
def _clip_by_norm(ctx):
    x = ctx.input("X")
    max_norm = ctx.attr("max_norm")
    norm = jnp.sqrt(jnp.sum(jnp.square(x)))
    scale = jnp.where(norm > max_norm, max_norm / jnp.maximum(norm, 1e-12), 1.0)
    return {"Out": x * scale}


@register_op("cumsum")
def _cumsum(ctx):
    axis = ctx.attr("axis", -1)
    x = ctx.input("X")
    out = jnp.cumsum(x, axis=axis)
    if ctx.attr("exclusive", False):
        out = out - x
    if ctx.attr("reverse", False):
        out = jnp.flip(jnp.cumsum(jnp.flip(x, axis), axis=axis), axis)
        if ctx.attr("exclusive", False):
            out = out - x
    return {"Out": out}


# ---------------------------------------------------------------------------
# matmul family (reference: matmul_op.cc, mul_op.cc) — the MXU path.
# ---------------------------------------------------------------------------


def wmm(x, w, precision=None):
    """``x @ w`` where the matrix ``w`` may be HELD in bfloat16 beside
    float32 activations (``DecodeConfig.matrix_dtype``): the activation
    is rounded to bfloat16, the products summed in float32, the result
    the activation's type. That is what a float32 matmul at the TPU's
    default precision computes (it rounds both operands to bfloat16),
    with the weight rounded once, when it was stored, and read at half
    the bytes; no float32 copy of the matrix is made, so a ``precision``
    is refused there and not dropped. Any other pair of types:
    ``jnp.matmul`` at ``precision``."""
    if w.dtype == jnp.bfloat16 and x.dtype != jnp.bfloat16:
        if precision is not None:
            raise ValueError(
                "precision %r on a matrix held in bfloat16: the product "
                "rounds the activation to bfloat16 whatever is asked"
                % (precision,))
        return jnp.matmul(x.astype(jnp.bfloat16), w,
                          preferred_element_type=jnp.float32).astype(x.dtype)
    return jnp.matmul(x, w, precision=precision)


def _mm2d(x2, y2):
    if y2.dtype == jnp.bfloat16 and x2.dtype != jnp.bfloat16:
        return wmm(x2, y2)  # a matrix held in bfloat16
    out = (jnp.matmul(x2, y2, preferred_element_type=jnp.float32)
           if x2.dtype == jnp.bfloat16 else x2 @ y2)
    return out.astype(x2.dtype)


def _mul_compute(x, y, xnc, ync):
    """The reference's `mul` computation: flatten X to 2-D by
    x_num_col_dims then matmul (reference: paddle/fluid/operators/
    mul_op.cc:36). Shared by the `mul` kernel and the transpiler-emitted
    `fused_fc` op — they MUST stay one code path so fusion is
    bit-exact."""
    import math as _math

    xs, ys = x.shape, y.shape
    x2 = x.reshape((_math.prod(xs[:xnc]) if xnc else 1, -1))
    y2 = y.reshape((_math.prod(ys[:ync]), -1))
    return _mm2d(x2, y2).reshape(xs[:xnc] + ys[ync:])


@register_op("mul")
def _mul(ctx):
    """The reference's `mul` op: flatten X to 2-D by x_num_col_dims then
    matmul (reference: paddle/fluid/operators/mul_op.cc:36)."""
    return {"Out": _mul_compute(ctx.input("X"), ctx.input("Y"),
                                ctx.attr("x_num_col_dims", 1),
                                ctx.attr("y_num_col_dims", 1))}


@register_op("matmul")
def _matmul(ctx):
    x, y = ctx.input("X"), ctx.input("Y")
    if ctx.attr("transpose_X", False):
        x = jnp.swapaxes(x, -1, -2) if x.ndim > 1 else x
    if ctx.attr("transpose_Y", False):
        y = jnp.swapaxes(y, -1, -2) if y.ndim > 1 else y
    # "highest": float32 products on a TPU too (a model's fp32 logits)
    out = wmm(x, y, ctx.attr("precision", None))
    alpha = ctx.attr("alpha", 1.0)
    if alpha != 1.0:
        out = out * alpha
    return {"Out": out}


@register_op("sum")
def _sum(ctx):
    xs = ctx.inputs("X")
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    return {"Out": out}


@register_op("mean")
def _mean(ctx):
    return {"Out": jnp.mean(ctx.input("X"))}


def _reduce(fn):
    def kern(ctx):
        x = ctx.input("X")
        dim = ctx.attr("dim", [0])
        keep = ctx.attr("keep_dim", False)
        if ctx.attr("reduce_all", False):
            return {"Out": fn(x)}
        axes = tuple(d % x.ndim for d in (dim if isinstance(dim, (list, tuple)) else [dim]))
        return {"Out": fn(x, axis=axes, keepdims=keep)}

    return kern


register_op("reduce_sum")(_reduce(jnp.sum))
register_op("reduce_mean")(_reduce(jnp.mean))
register_op("reduce_max")(_reduce(jnp.max))
register_op("reduce_min")(_reduce(jnp.min))
register_op("reduce_prod")(_reduce(jnp.prod))


# ---------------------------------------------------------------------------
# softmax & losses
# ---------------------------------------------------------------------------


@register_op("softmax")
def _softmax(ctx):
    return {"Out": jax.nn.softmax(ctx.input("X"), axis=-1)}


@register_op("log_softmax")
def _log_softmax(ctx):
    return {"Out": jax.nn.log_softmax(ctx.input("X"), axis=-1)}


@register_op("cross_entropy")
def _cross_entropy(ctx):
    """reference: paddle/fluid/operators/cross_entropy_op.cc. X is a
    probability distribution (post-softmax)."""
    x = ctx.input("X")
    label = ctx.input("Label")
    soft = ctx.attr("soft_label", False)
    eps = 1e-8
    if soft:
        loss = -jnp.sum(label * jnp.log(jnp.maximum(x, eps)), axis=-1, keepdims=True)
    else:
        lbl = label.reshape(label.shape[:-1]) if label.shape[-1] == 1 else label
        picked = jnp.take_along_axis(x, lbl[..., None].astype(jnp.int32), axis=-1)
        loss = -jnp.log(jnp.maximum(picked, eps))
    return {"Y": loss}


@register_op("softmax_with_cross_entropy")
def _softmax_with_cross_entropy(ctx):
    """Fused, numerically-stable softmax+xent (reference:
    softmax_with_cross_entropy_op.cc). On TPU this is the natural single
    fused XLA computation — no custom kernel needed."""
    logits = ctx.input("Logits")
    label = ctx.input("Label")
    soft = ctx.attr("soft_label", False)
    logp = jax.nn.log_softmax(logits, axis=-1)
    if soft:
        loss = -jnp.sum(label * logp, axis=-1, keepdims=True)
    else:
        lbl = label.reshape(label.shape[:-1]) if label.shape[-1] == 1 else label
        picked = jnp.take_along_axis(logp, lbl[..., None].astype(jnp.int32), axis=-1)
        loss = -picked
        ignore = ctx.attr("ignore_index", -100)
        loss = jnp.where(lbl[..., None] == ignore, 0.0, loss)
    return {"Loss": loss, "Softmax": jnp.exp(logp)}


@register_op("square_error_cost")
def _square_error_cost(ctx):
    d = ctx.input("X") - ctx.input("Y")
    return {"Out": jnp.square(d)}


@register_op("smooth_l1_loss")
def _smooth_l1(ctx):
    x, y = ctx.input("X"), ctx.input("Y")
    sigma = ctx.attr("sigma", 1.0)
    s2 = sigma * sigma
    d = x - y
    if ctx.has_input("InsideWeight"):
        d = d * ctx.input("InsideWeight")
    ad = jnp.abs(d)
    loss = jnp.where(ad < 1.0 / s2, 0.5 * s2 * d * d, ad - 0.5 / s2)
    if ctx.has_input("OutsideWeight"):
        loss = loss * ctx.input("OutsideWeight")
    return {"Out": jnp.sum(loss, axis=tuple(range(1, loss.ndim)), keepdims=False).reshape(-1, 1), "Diff": d}


@register_op("rank_loss")
def _rank_loss(ctx):
    label, left, right = ctx.input("Label"), ctx.input("Left"), ctx.input("Right")
    d = left - right
    return {"Out": jnp.log1p(jnp.exp(d)) - label * d}


@register_op("label_smooth")
def _label_smooth(ctx):
    x = ctx.input("X")
    eps = ctx.attr("epsilon", 0.0)
    if ctx.has_input("PriorDist"):
        prior = ctx.input("PriorDist")
        return {"Out": (1 - eps) * x + eps * prior}
    return {"Out": (1 - eps) * x + eps / x.shape[-1]}


@register_op("dice_loss")
def _dice_loss(ctx):
    x, label = ctx.input("X"), ctx.input("Label")
    eps = ctx.attr("epsilon", 1e-5)
    label_f = label.astype(x.dtype)
    if label_f.shape != x.shape and label_f.shape[-1] == 1:
        label_f = jax.nn.one_hot(
            label_f[..., 0].astype(jnp.int32), x.shape[-1], dtype=x.dtype)
    reduce_dims = tuple(range(1, x.ndim))
    inter = jnp.sum(x * label_f, axis=reduce_dims)
    union = jnp.sum(x, axis=reduce_dims) + jnp.sum(label_f, axis=reduce_dims)
    dice = (2 * inter + eps) / (union + eps)
    return {"Out": jnp.mean(1 - dice)}


@register_op("sigmoid_cross_entropy_with_logits")
def _sigmoid_cross_entropy_with_logits(ctx):
    """reference: sigmoid_cross_entropy_with_logits_op.cc. Numerically
    stable form: max(x,0) - x*label + log(1+exp(-|x|))."""
    x = ctx.input("X")
    label = ctx.input("Label").astype(x.dtype)
    out = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    return {"Out": out}


@register_op("huber_loss")
def _huber_loss(ctx):
    x, y = ctx.input("X"), ctx.input("Y")
    delta = ctx.attr("delta", 1.0)
    d = y - x
    ad = jnp.abs(d)
    loss = jnp.where(ad <= delta, 0.5 * d * d, delta * (ad - 0.5 * delta))
    return {"Out": loss, "Residual": d}


# ---------------------------------------------------------------------------
# shape manipulation
# ---------------------------------------------------------------------------


@register_op("reshape")
def _reshape(ctx):
    x = ctx.input("X")
    shape = list(ctx.attr("shape"))
    # paddle semantics: 0 means copy dim from input
    for i, s in enumerate(shape):
        if s == 0:
            shape[i] = x.shape[i]
    return {"Out": x.reshape(shape)}


@register_op("squeeze")
def _squeeze(ctx):
    x = ctx.input("X")
    axes = ctx.attr("axes", [])
    if axes:
        out = x
        for ax in sorted([a % x.ndim for a in axes], reverse=True):
            out = jnp.squeeze(out, axis=ax)
    else:
        out = jnp.squeeze(x)
    return {"Out": out}


@register_op("unsqueeze")
def _unsqueeze(ctx):
    x = ctx.input("X")
    for ax in sorted(ctx.attr("axes")):
        x = jnp.expand_dims(x, ax)
    return {"Out": x}


@register_op("transpose")
def _transpose(ctx):
    return {"Out": jnp.transpose(ctx.input("X"), ctx.attr("axis"))}


@register_op("concat")
def _concat(ctx):
    return {"Out": jnp.concatenate(ctx.inputs("X"), axis=ctx.attr("axis", 0))}


@register_op("split")
def _split(ctx):
    x = ctx.input("X")
    axis = ctx.attr("axis", 0)
    sections = ctx.attr("sections", None)
    num = ctx.attr("num", 0)
    if sections:
        # host arithmetic: under a jit trace a jnp cumsum is a tracer
        idx, at = [], 0
        for sec in sections[:-1]:
            at += int(sec)
            idx.append(at)
        outs = jnp.split(x, idx, axis=axis)
    else:
        outs = jnp.split(x, num, axis=axis)
    return {"Out": list(outs)}


@register_op("stack")
def _stack(ctx):
    return {"Y": jnp.stack(ctx.inputs("X"), axis=ctx.attr("axis", 0))}


@register_op("unstack")
def _unstack(ctx):
    x = ctx.input("X")
    axis = ctx.attr("axis", 0)
    n = x.shape[axis]
    return {"Y": [jnp.take(x, i, axis=axis) for i in range(n)]}


@register_op("flatten")
def _flatten(ctx):
    x = ctx.input("X")
    ax = ctx.attr("axis", 1)
    lead = 1
    for s in x.shape[:ax]:
        lead *= s
    return {"Out": x.reshape((lead, -1))}


@register_op("pad")
def _pad(ctx):
    x = ctx.input("X")
    paddings = ctx.attr("paddings")
    val = ctx.attr("pad_value", 0.0)
    pairs = [(paddings[2 * i], paddings[2 * i + 1]) for i in range(x.ndim)]
    return {"Out": jnp.pad(x, pairs, constant_values=val)}


@register_op("pad_constant_like")
def _pad_constant_like(ctx):
    x, y = ctx.input("X"), ctx.input("Y")
    val = ctx.attr("pad_value", 0.0)
    pairs = [(0, xs - ys) for xs, ys in zip(x.shape, y.shape)]
    return {"Out": jnp.pad(y, pairs, constant_values=val)}


@register_op("crop")
def _crop(ctx):
    x = ctx.input("X")
    offsets = ctx.attr("offsets")
    shape = ctx.attr("shape")
    slices = tuple(slice(o, o + s) for o, s in zip(offsets, shape))
    return {"Out": x[slices]}


@register_op("reverse")
def _reverse(ctx):
    x = ctx.input("X")
    axes = ctx.attr("axis")
    if isinstance(axes, int):
        axes = [axes]
    out = x
    for ax in axes:
        out = jnp.flip(out, axis=ax)
    return {"Out": out}


@register_op("expand")
def _expand(ctx):
    x = ctx.input("X")
    times = ctx.attr("expand_times")
    return {"Out": jnp.tile(x, times)}


@register_op("slice")
def _slice(ctx):
    x = ctx.input("Input")
    axes = ctx.attr("axes")
    starts = ctx.attr("starts")
    ends = ctx.attr("ends")
    slices = [slice(None)] * x.ndim
    for ax, st, en in zip(axes, starts, ends):
        slices[ax] = slice(st, en)
    return {"Out": x[tuple(slices)]}


@register_op("shape")
def _shape(ctx):
    x = ctx.input("Input")
    return {"Out": jnp.array(x.shape, dtype=jnp.int32)}


# ---------------------------------------------------------------------------
# indexing / selection
# ---------------------------------------------------------------------------


@register_op("gather")
def _gather(ctx):
    x = ctx.input("X")
    index = ctx.input("Index").astype(jnp.int32).reshape(-1)
    return {"Out": jnp.take(x, index, axis=0)}


@register_op("scatter")
def _scatter(ctx):
    x = ctx.input("X")
    ids = ctx.input("Ids").astype(jnp.int32).reshape(-1)
    updates = ctx.input("Updates")
    if ctx.attr("overwrite", True):
        out = x.at[ids].set(updates)
    else:
        out = x.at[ids].add(updates)
    return {"Out": out}


@register_op("lookup_table")
def _lookup_table(ctx):
    """Embedding lookup (reference: lookup_table_op.cc). The reference has a
    sparse SelectedRows grad path; on TPU the gradient is a dense
    scatter-add which XLA lowers efficiently."""
    w = ctx.input("W")
    ids = ctx.input("Ids").astype(jnp.int32)
    if ids.ndim > 1 and ids.shape[-1] == 1:
        ids = ids[..., 0]
    padding_idx = ctx.attr("padding_idx", -1)
    out = jnp.take(w, ids, axis=0)
    if padding_idx is not None and padding_idx >= 0:
        mask = (ids == padding_idx)[..., None]
        out = jnp.where(mask, 0.0, out)
    return {"Out": out}


@register_op("one_hot")
def _one_hot(ctx):
    x = ctx.input("X").astype(jnp.int32)
    depth = ctx.attr("depth")
    if x.ndim > 1 and x.shape[-1] == 1:
        x = x[..., 0]
    return {"Out": jax.nn.one_hot(x, depth, dtype=jnp.float32)}


@register_op("multiplex")
def _multiplex(ctx):
    ids = ctx.input("Ids").astype(jnp.int32).reshape(-1)
    xs = jnp.stack(ctx.inputs("X"), axis=0)  # (num_candidates, batch, d)
    batch = jnp.arange(xs.shape[1])
    return {"Out": xs[ids, batch]}


@register_op("top_k")
def _top_k(ctx):
    x = ctx.input("X")
    k = ctx.attr("k", 1)
    vals, idx = lax.top_k(x, k)
    return {"Out": vals, "Indices": idx.astype(jnp.int64)}


@register_op("arg_max")
def _arg_max(ctx):
    return {"Out": jnp.argmax(ctx.input("X"), axis=ctx.attr("axis", -1)).astype(jnp.int64)}


@register_op("arg_min")
def _arg_min(ctx):
    return {"Out": jnp.argmin(ctx.input("X"), axis=ctx.attr("axis", -1)).astype(jnp.int64)}


@register_op("argsort")
def _argsort(ctx):
    x = ctx.input("X")
    axis = ctx.attr("axis", -1)
    idx = jnp.argsort(x, axis=axis)
    return {"Out": jnp.sort(x, axis=axis), "Indices": idx.astype(jnp.int64)}


# ---------------------------------------------------------------------------
# comparisons / logical
# ---------------------------------------------------------------------------


def _compare(fn):
    def kern(ctx):
        x, y = ctx.input("X"), ctx.input("Y")
        return {"Out": fn(x, y)}

    return kern


register_op("less_than")(_compare(jnp.less))
register_op("less_equal")(_compare(jnp.less_equal))
register_op("greater_than")(_compare(jnp.greater))
register_op("greater_equal")(_compare(jnp.greater_equal))
register_op("equal")(_compare(jnp.equal))
register_op("not_equal")(_compare(jnp.not_equal))
register_op("logical_and")(_compare(jnp.logical_and))
register_op("logical_or")(_compare(jnp.logical_or))
register_op("logical_xor")(_compare(jnp.logical_xor))
register_op("logical_not")(_unary(jnp.logical_not))
register_op("isfinite")(lambda ctx: {"Out": jnp.all(jnp.isfinite(ctx.input("X")))})


# ---------------------------------------------------------------------------
# misc tensor ops
# ---------------------------------------------------------------------------


@register_op("cast")
def _cast(ctx):
    from ..framework.dtypes import as_numpy_dtype

    return {"Out": ctx.input("X").astype(as_numpy_dtype(ctx.attr("out_dtype")))}


@register_op("assign")
def _assign(ctx):
    return {"Out": ctx.input("X")}


def _attr_tensor(values, shape, dtype):
    """Materialize attr-embedded data (shared by assign_value and fill)."""
    import numpy as np

    from ..framework.dtypes import as_numpy_dtype

    arr = np.asarray(values, dtype=as_numpy_dtype(dtype)).reshape(shape)
    return jnp.asarray(arr)


@register_op("assign_value")
def _assign_value(ctx):
    return {"Out": _attr_tensor(ctx.attr("values"), ctx.attr("shape"),
                                ctx.attr("dtype", "float32"))}


@register_op("fill_constant")
def _fill_constant(ctx):
    from ..framework.dtypes import as_numpy_dtype

    shape = ctx.attr("shape")
    dtype = as_numpy_dtype(ctx.attr("dtype", "float32"))
    return {"Out": jnp.full(shape, ctx.attr("value", 0.0), dtype=dtype)}


@register_op("fill_constant_batch_size_like")
def _fill_constant_batch_size_like(ctx):
    from ..framework.dtypes import as_numpy_dtype

    ref = ctx.input("Input")
    shape = list(ctx.attr("shape"))
    in_idx = ctx.attr("input_dim_idx", 0)
    out_idx = ctx.attr("output_dim_idx", 0)
    shape[out_idx] = ref.shape[in_idx]
    dtype = as_numpy_dtype(ctx.attr("dtype", "float32"))
    return {"Out": jnp.full(shape, ctx.attr("value", 0.0), dtype=dtype)}


@register_op("fill_zeros_like")
def _fill_zeros_like(ctx):
    return {"Out": jnp.zeros_like(ctx.input("X"))}


@register_op("increment")
def _increment(ctx):
    x = ctx.input("X")
    return {"Out": x + jnp.asarray(ctx.attr("step", 1.0), x.dtype)}


@register_op("l2_normalize")
def _l2_normalize(ctx):
    x = ctx.input("X")
    axis = ctx.attr("axis", -1)
    eps = ctx.attr("epsilon", 1e-12)
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True))
    return {"Out": x / jnp.maximum(norm, eps), "Norm": norm}


@register_op("cos_sim")
def _cos_sim(ctx):
    x, y = ctx.input("X"), ctx.input("Y")
    xn = jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True))
    yn = jnp.sqrt(jnp.sum(jnp.square(y), axis=-1, keepdims=True))
    num = jnp.sum(x * y, axis=-1, keepdims=True)
    return {"Out": num / jnp.maximum(xn * yn, 1e-12), "XNorm": xn, "YNorm": yn}


@register_op("bilinear_tensor_product")
def _bilinear_tensor_product(ctx):
    x, y, w = ctx.input("X"), ctx.input("Y"), ctx.input("Weight")
    # w: (out, dx, dy)
    out = jnp.einsum("bd,ode,be->bo", x, w, y)
    if ctx.has_input("Bias"):
        out = out + ctx.input("Bias")
    return {"Out": out}


@register_op("conv_shift")
def _conv_shift(ctx):
    x, y = ctx.input("X"), ctx.input("Y")  # x:(B,M) y:(B,N), N odd, N<=M
    n = y.shape[1]
    half = n // 2
    idx = (jnp.arange(x.shape[1])[:, None] + jnp.arange(-half, half + 1)[None, :]) % x.shape[1]
    gathered = x[:, idx]  # (B, M, N)
    return {"Out": jnp.einsum("bmn,bn->bm", gathered, y)}


@register_op("row_conv")
def _row_conv(ctx):
    """Lookahead row convolution (reference: row_conv_op.cc). Operates on
    (batch, time, d) dense tensors."""
    x = ctx.input("X")
    w = ctx.input("Filter")  # (future_context, d)
    k = w.shape[0]
    outs = jnp.zeros_like(x)
    for i in range(k):
        shifted = jnp.pad(x[:, i:, :], ((0, 0), (0, i), (0, 0)))
        outs = outs + shifted * w[i][None, None, :]
    return {"Out": outs}


@register_op("smooth_l1")
def _smooth_l1_alias(ctx):
    return _smooth_l1(ctx)


@register_op("maxout")
def _maxout(ctx):
    x = ctx.input("X")  # NCHW
    groups = ctx.attr("groups")
    n, c, h, w = x.shape
    return {"Out": x.reshape(n, c // groups, groups, h, w).max(axis=2)}


@register_op("load_file")
def _load_file(ctx):
    """reference: load_op.cc — load a saved tensor into a variable. The
    file (a ``.npy`` written by io.save_vars) is read at trace time and
    enters the computation as a host constant."""
    import numpy as np

    path = ctx.attr("file_path")
    arr = np.load(path)
    if ctx.attr("load_as_fp16", False):
        arr = arr.astype(np.float16)
    return {"Out": jnp.asarray(arr)}


# ---------------------------------------------------------------------------
# small loss / norm ops (reference C++-only operators, reachable through the
# reference's Operator factory and exercised by its unittests)
# ---------------------------------------------------------------------------


@register_op("minus")
def _minus(ctx):
    """reference minus_op.cc: Out = X - Y."""
    return {"Out": ctx.input("X") - ctx.input("Y")}


@register_op("hinge_loss")
def _hinge_loss(ctx):
    """reference hinge_loss_op.cc: labels in {0,1} -> Loss =
    max(0, 1 - (2*label - 1) * logit), elementwise."""
    logits = ctx.input("Logits")
    labels = ctx.input("Labels")
    return {"Loss": jnp.maximum(
        0.0, 1.0 - (2.0 * labels - 1.0) * logits)}


@register_op("log_loss")
def _log_loss(ctx):
    """reference log_loss_op.cc: negative log likelihood of a Bernoulli
    prediction, stabilized with attr epsilon."""
    p = ctx.input("Predicted")
    y = ctx.input("Labels")
    eps = float(ctx.attr("epsilon", 1e-4))
    return {"Loss": -y * jnp.log(p + eps) - (1.0 - y) * jnp.log(1.0 - p + eps)}


@register_op("margin_rank_loss")
def _margin_rank_loss(ctx):
    """reference margin_rank_loss_op.cc: label in {+1,-1} says whether X1
    should rank above X2; Out = max(0, margin - label*(X1 - X2)).
    Activated marks the rows inside the margin (the reference saves it for
    its backward; emitted for parity)."""
    x1, x2 = ctx.input("X1"), ctx.input("X2")
    label = ctx.input("Label")
    margin = float(ctx.attr("margin", 0.0))
    raw = margin - label * (x1 - x2)
    return {"Out": jnp.maximum(0.0, raw),
            "Activated": (raw > 0).astype(x1.dtype)}


@register_op("modified_huber_loss")
def _modified_huber_loss(ctx):
    """reference modified_huber_loss_op.h: with z = (2y-1)*x,
    loss = -4z for z < -1, (1-z)^2 for -1 <= z < 1, else 0."""
    x = ctx.input("X")
    y = ctx.input("Y")
    z = (2.0 * y - 1.0) * x
    loss = jnp.where(z < -1.0, -4.0 * z,
                     jnp.where(z < 1.0, (1.0 - z) ** 2, 0.0))
    return {"Out": loss, "IntermediateVal": z}


@register_op("squared_l2_distance")
def _squared_l2_distance(ctx):
    """reference squared_l2_distance_op.cc: row-wise ||x - y||^2; Y may
    have one row (broadcast). sub_result is saved for the backward in the
    reference; emitted for parity."""
    x = ctx.input("X")
    y = ctx.input("Y")
    sub = x - y  # broadcasts when y has one row
    n = sub.shape[0]
    out = jnp.sum(sub.reshape(n, -1) ** 2, axis=1, keepdims=True)  # (N, 1)
    return {"Out": out, "sub_result": sub}


@register_op("squared_l2_norm")
def _squared_l2_norm(ctx):
    """reference squared_l2_norm_op.cc: scalar sum of squares."""
    x = ctx.input("X")
    return {"Out": jnp.sum(x * x).reshape(1)}


@register_op("l1_norm")
def _l1_norm(ctx):
    """reference l1_norm_op.cc: scalar sum of absolute values."""
    return {"Out": jnp.sum(jnp.abs(ctx.input("X"))).reshape(1)}


# ---------------------------------------------------------------------------
# quantization-aware-training ops (reference fake_quantize_op.h /
# fake_dequantize_op.h)
# ---------------------------------------------------------------------------


@jax.custom_vjp
def _ste_quantize(x, scale, bin_cnt):
    """round(bin_cnt/scale * clip(x, ±scale)) with the straight-through
    estimator: the backward passes dOut through to dX unchanged (the
    reference's fake_quantize grad op), otherwise round()'s zero gradient
    would make QAT learn nothing. Rounds half away from zero like the
    C++ std::round (jnp.round is half-to-even)."""
    clipped = jnp.clip(x, -scale, scale)
    v = bin_cnt / scale * clipped
    return jnp.trunc(v + 0.5 * jnp.sign(v))


def _ste_fwd(x, scale, bin_cnt):
    return _ste_quantize(x, scale, bin_cnt), None


def _ste_bwd(_res, g):
    return g, None, None


_ste_quantize.defvjp(_ste_fwd, _ste_bwd)


@register_op("fake_quantize")
def _fake_quantize(ctx):
    """Simulated int-N quantization for QAT. Out = round(bin_cnt/scale *
    clip(x, ±scale)) with bin_cnt = 2^(bits-1) - 1. The scale comes from
    the chosen quantize_type:

    - "abs_max": current batch's max |x|
    - "range_abs_max": running max over a `window_size` window of batch
      scales (InScales/InCurrentIter thread the window state through the
      step; the reference indexes the window unguarded past its end — UB —
      here the slot is iter % window_size)
    - "moving_average_abs_max": 0.9*cur + 0.1*previous (the reference's
      coefficient order)

    At is_test the stored moving scale is used unchanged. All state is
    functional (OutScales/OutMovingScale/OutCurrentIter), matching the
    one-XLA-computation execution model."""
    x = ctx.input("X")
    qtype = ctx.attr("quantize_type", "abs_max")
    window = int(ctx.attr("window_size", 10000))
    bits = int(ctx.attr("bit_length", 8))
    is_test = ctx.is_test
    bin_cnt = float(2 ** (bits - 1) - 1)

    cur = jnp.maximum(jnp.max(jnp.abs(x)), 1e-8)
    outs = {}
    if qtype == "abs_max":
        scale = cur
        outs["OutMovingScale"] = scale.reshape(1)
        # the reference kernel zero-fills the window state in abs_max mode
        # so QAT graphs that declare the slots find them written
        if ctx.has_input("InScales"):
            outs["OutScales"] = jnp.zeros_like(
                ctx.input("InScales").reshape(-1))
        if ctx.has_input("InCurrentIter"):
            outs["OutCurrentIter"] = jnp.zeros_like(
                ctx.input("InCurrentIter").reshape(-1))
    elif qtype == "range_abs_max":
        moving = ctx.input("InMovingScale")
        if is_test:
            scale = moving.reshape(())
        else:
            scales = ctx.input("InScales").reshape(-1)
            it = ctx.input("InCurrentIter").reshape(()).astype(jnp.int32)
            slot = it % scales.shape[0]
            removed = scales[slot]
            scales = scales.at[slot].set(cur)
            prev_max = moving.reshape(())
            n_valid = jnp.minimum(it + 1, scales.shape[0])
            windowed = jnp.where(jnp.arange(scales.shape[0]) < n_valid,
                                 scales, 0.0)
            # reference FindRangeAbsMax: grow immediately; full rescan
            # only when the evicted slot WAS the max
            scale = jnp.where(
                prev_max < cur, cur,
                jnp.where(jnp.abs(removed - prev_max) < 1e-6,
                          jnp.max(windowed), prev_max))
            outs["OutScales"] = scales
            outs["OutCurrentIter"] = (it + 1).reshape(1)
        outs["OutMovingScale"] = scale.reshape(1)
    elif qtype == "moving_average_abs_max":
        moving = ctx.input("InMovingScale")
        if is_test:
            scale = moving.reshape(())
        else:
            scale = 0.9 * cur + 0.1 * moving.reshape(())
        outs["OutMovingScale"] = scale.reshape(1)
    else:
        raise ValueError("fake_quantize: unknown quantize_type %r" % qtype)

    # floor protects the is_test branches too (an uninitialized stored
    # scale of 0 must not emit inf/nan)
    scale = jnp.maximum(scale, 1e-8)
    outs["Out"] = _ste_quantize(x, scale, bin_cnt)
    return outs


@register_op("fake_dequantize_max_abs")
def _fake_dequantize_max_abs(ctx):
    """reference fake_dequantize_op.h: Out = X * Scale / max_range."""
    x = ctx.input("X")
    scale = ctx.input("Scale").reshape(())
    max_range = float(ctx.attr("max_range"))
    return {"Out": x.astype(jnp.float32) * scale / max_range}


@register_op("fill")
def _fill(ctx):
    """reference fill_op.cc: materialize a tensor from attr-embedded data.
    Same computation as assign_value with the attr spelled `value`
    instead of `values` (force_cpu is meaningless under XLA)."""
    return {"Out": _attr_tensor(ctx.attr("value", []), ctx.attr("shape"),
                                ctx.attr("dtype", "float32"))}


_FEA_UNARY = {
    "scale": lambda v, attr: v * attr,
    "relu": lambda v, attr: jnp.maximum(v, 0.0),
}
_FEA_BINARY = {
    "elementwise_add": jnp.add,
    "elementwise_mul": jnp.multiply,
}


@register_op("fused_elemwise_activation")
def _fused_elemwise_activation(ctx):
    """reference fused_elemwise_activation_op.h: compose one binary and
    one unary functor. functor_list ("binary,unary") computes
    Out = Binary(X, Unary(Y)) with IntermediateOut = Unary(Y);
    ("unary,binary") computes Out = Unary(Binary(X, Y)) with
    IntermediateOut = Binary(X, Y). The unary `scale` reads attr scale.
    XLA fuses the chain either way; the op exists for source parity."""
    x = ctx.input("X")
    y = ctx.input("Y")
    functors = [f.strip() for f in ctx.attr("functor_list")]
    scale = float(ctx.attr("scale", 1.0))
    axis = ctx.attr("axis", -1)
    if len(functors) != 2:
        raise ValueError("functor_list must name exactly two functors")
    f0, f1 = functors
    if f0 in _FEA_BINARY and f1 in _FEA_UNARY:
        # IntermediateOut keeps Y's own shape (reference contract);
        # broadcasting happens only inside the binary step
        intermediate = _FEA_UNARY[f1](y, scale)
        out = _FEA_BINARY[f0](x, _broadcast_y(x, intermediate, axis))
    elif f0 in _FEA_UNARY and f1 in _FEA_BINARY:
        intermediate = _FEA_BINARY[f1](x, _broadcast_y(x, y, axis))
        out = _FEA_UNARY[f0](intermediate, scale)
    else:
        raise ValueError(
            "fused_elemwise_activation: unsupported functor_list %r "
            "(one of %s composed with one of %s)"
            % (functors, sorted(_FEA_BINARY), sorted(_FEA_UNARY)))
    return {"Out": out, "IntermediateOut": intermediate}


# activations the fused_fc op reproduces — each entry is the SAME jnp
# composition the standalone kernel applies at DEFAULT attrs (the fusion
# pass only fuses attr-less activation ops), so fusing is bit-exact
_FC_ACTS = {
    "relu": jax.nn.relu,
    "tanh": jnp.tanh,
    "sigmoid": jax.nn.sigmoid,
    "relu6": lambda x: jnp.clip(x, 0.0, 6.0),
    "softplus": jax.nn.softplus,
    "leaky_relu": lambda x: jnp.where(x >= 0, x, 0.02 * x),
    "swish": lambda x: x * jax.nn.sigmoid(1.0 * x),
    "square": jnp.square,
    "abs": jnp.abs,
    "exp": jnp.exp,
}


@register_op("fused_fc")
def _fused_fc(ctx):
    """Transpiler-emitted fused matmul + bias + activation (the
    reference's `fc` fused op; emitted by transpiler/passes/fusion.py).
    kind="mul" composes the exact `mul` kernel computation; kind="matmul"
    the default-attr `matmul`. The bias add uses the same paddle
    axis-span broadcast as `elementwise_add`, and `act` names one of the
    default-attr activations in _FC_ACTS — every piece is the identical
    jnp call chain the three unfused ops would run, so fusion changes
    nothing numerically."""
    x, y = ctx.input("X"), ctx.input("Y")
    kind = ctx.attr("kind", "mul")
    if kind == "mul":
        out = _mul_compute(x, y, ctx.attr("x_num_col_dims", 1),
                           ctx.attr("y_num_col_dims", 1))
    elif kind == "matmul":
        out = jnp.matmul(x, y)
    else:
        raise ValueError("fused_fc: unknown kind %r" % (kind,))
    b = ctx.input("Bias")
    if b is not None:
        out = jnp.add(out, _broadcast_y(out, b, ctx.attr("axis", -1)))
    act = ctx.attr("act", "")
    if act:
        if act not in _FC_ACTS:
            raise ValueError(
                "fused_fc: unsupported act %r (one of %s)"
                % (act, sorted(_FC_ACTS)))
        out = _FC_ACTS[act](out)
    return {"Out": out}
