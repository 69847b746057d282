"""Shape/dtype inference rules for the high-traffic op set.

One small pure function per op type, registered with
``@register_infer(...)`` — the static twin of the kernel registry in
``ops/``. Each rule mirrors its kernel's output contract exactly
(reference: the per-op ``InferShape`` methods in
paddle/fluid/operators/*_op.cc); ``tests/op_test.py:check_infer``
cross-checks every rule against the shapes JAX actually produces when the
kernel is traced, so rules cannot drift from kernels.

Conventions:
- unknown dims are ``None``; a rule must degrade to unknown rather than
  guess (the zero-false-positive contract),
- a DEFINITE contract violation raises :class:`InferError`, which the
  driver turns into an error diagnostic with op provenance.
"""
from __future__ import annotations

from typing import List, Optional

from ..framework.dtypes import convert_dtype
from .infer import (
    InferContext, InferError, Shape, VarInfo, broadcast_shapes, info,
    prod_dims, promote_dtypes, register_infer, render_shape,
)

# ---------------------------------------------------------------------------
# elementwise binary family (paddle axis-span broadcast, see
# ops/math.py:_broadcast_y)
# ---------------------------------------------------------------------------

_ELEMENTWISE = (
    "elementwise_add", "elementwise_sub", "elementwise_mul",
    "elementwise_div", "elementwise_max", "elementwise_min",
    "elementwise_pow", "elementwise_mod",
)


@register_infer(*_ELEMENTWISE)
def _infer_elementwise(ctx: InferContext):
    x, y = ctx.in_info("X"), ctx.in_info("Y")
    dt = promote_dtypes(x.dtype, y.dtype)
    xs, ys = x.shape, y.shape
    if xs is None:
        return {"Out": VarInfo(None, dt)}
    if ys is None:
        # Y could broadcast any of X's 1-dims UP — degrade those to
        # unknown instead of echoing X's shape verbatim
        return {"Out": VarInfo(
            tuple(None if d == 1 else d for d in xs), dt)}
    if len(ys) > len(xs):
        raise InferError(
            "Y rank %d exceeds X rank %d (Y must match a span of X's "
            "dims)" % (len(ys), len(xs)))
    if len(xs) == len(ys):
        out = broadcast_shapes(xs, ys, "X and Y")
        return {"Out": VarInfo(out, dt)}
    axis = ctx.attr("axis", -1)
    if axis is None or axis == -1:
        axis = len(xs) - len(ys)
    if axis < 0 or axis + len(ys) > len(xs):
        raise InferError(
            "axis=%d places Y%s outside X%s"
            % (axis, render_shape(ys), render_shape(xs)))
    out: List[Optional[int]] = list(xs)
    for i, dy in enumerate(ys):
        dx = xs[axis + i]
        if dx is not None and dy is not None and dx != dy and dy != 1 \
                and dx != 1:
            raise InferError(
                "Y%s does not match X%s's dims starting at axis %d"
                % (render_shape(ys), render_shape(xs), axis))
        if dx == 1:
            # broadcasts up to Y's dim — unknown dy means unknown out,
            # never a guessed 1 (degrade-to-unknown contract)
            out[axis + i] = dy
    return {"Out": VarInfo(tuple(out), dt)}


# ---------------------------------------------------------------------------
# unary, shape- and dtype-preserving ops
# ---------------------------------------------------------------------------

_UNARY = (
    "sigmoid", "logsigmoid", "exp", "relu", "tanh", "tanh_shrink", "sqrt",
    "abs", "ceil", "floor", "cos", "sin", "round", "reciprocal", "square",
    "softplus", "softsign", "log", "sign", "relu6", "leaky_relu", "elu",
    "brelu", "soft_relu", "pow", "stanh", "hard_sigmoid", "swish",
    "thresholded_relu", "hard_shrink", "softshrink", "prelu", "scale",
    "clip", "clip_by_norm", "cumsum", "label_smooth", "assign", "softmax",
    "log_softmax", "sequence_softmax", "increment", "fill_zeros_like",
)


@register_infer(*_UNARY)
def _infer_unary(ctx: InferContext):
    return {"Out": ctx.in_info("X")}


@register_infer("dropout")
def _infer_dropout(ctx: InferContext):
    x = ctx.in_info("X")
    return {"Out": x, "Mask": x}


@register_infer("logical_not")
def _infer_logical_not(ctx: InferContext):
    return {"Out": VarInfo(ctx.in_shape("X"), "bool")}


@register_infer("select")
def _infer_select(ctx: InferContext):
    # Out = Mask ? X : Y — value shape/dtype follow X (the kernel
    # aligns the mask; training.stream's non-finite guard emits these)
    return {"Out": ctx.in_info("X")}


@register_infer("isfinite")
def _infer_isfinite(ctx: InferContext):
    return {"Out": info((), "bool")}


# ---------------------------------------------------------------------------
# comparisons / logical binary (plain numpy broadcast, bool result)
# ---------------------------------------------------------------------------

_COMPARE = (
    "less_than", "less_equal", "greater_than", "greater_equal", "equal",
    "not_equal", "logical_and", "logical_or", "logical_xor",
)


@register_infer(*_COMPARE)
def _infer_compare(ctx: InferContext):
    out = broadcast_shapes(ctx.in_shape("X"), ctx.in_shape("Y"), "X and Y")
    return {"Out": VarInfo(out, "bool")}


# ---------------------------------------------------------------------------
# matmul family — the MXU path, and the highest-value mismatch catcher
# ---------------------------------------------------------------------------


@register_infer("mul")
def _infer_mul(ctx: InferContext):
    xs, ys = ctx.in_shape("X"), ctx.in_shape("Y")
    dt = promote_dtypes(ctx.in_dtype("X"), ctx.in_dtype("Y"))
    xnc = int(ctx.attr("x_num_col_dims", 1))
    ync = int(ctx.attr("y_num_col_dims", 1))
    if xs is None or ys is None:
        return {"Out": VarInfo(None, dt)}
    if xnc > len(xs) or ync >= len(ys) + 1:
        raise InferError(
            "x_num_col_dims=%d / y_num_col_dims=%d out of range for "
            "X%s, Y%s" % (xnc, ync, render_shape(xs), render_shape(ys)))
    k_x = prod_dims(xs[xnc:])
    k_y = prod_dims(ys[:ync])
    if k_x is not None and k_y is not None and k_x != k_y:
        raise InferError(
            "contraction dims disagree: X%s flattens to K=%d but Y%s "
            "flattens to K=%d"
            % (render_shape(xs), k_x, render_shape(ys), k_y),
            hint="the fc/mul weight's first dim must equal the flattened "
                 "input feature count")
    return {"Out": VarInfo(tuple(xs[:xnc]) + tuple(ys[ync:]), dt)}


@register_infer("matmul")
def _infer_matmul(ctx: InferContext):
    xs, ys = ctx.in_shape("X"), ctx.in_shape("Y")
    dt = promote_dtypes(ctx.in_dtype("X"), ctx.in_dtype("Y"))
    if xs is None or ys is None or len(xs) < 2 or len(ys) < 2:
        # 1-D operands follow jnp.matmul's special cases; rare in
        # programs, so degrade instead of modeling them
        return {"Out": VarInfo(None, dt)}
    if ctx.attr("transpose_X", False):
        xs = xs[:-2] + (xs[-1], xs[-2])
    if ctx.attr("transpose_Y", False):
        ys = ys[:-2] + (ys[-1], ys[-2])
    if xs[-1] is not None and ys[-2] is not None and xs[-1] != ys[-2]:
        raise InferError(
            "matmul contraction dims disagree: X%s x Y%s (K %d vs %d)"
            % (render_shape(xs), render_shape(ys), xs[-1], ys[-2]),
            hint="check transpose_X/transpose_Y and the operand layouts")
    batch = broadcast_shapes(xs[:-2], ys[:-2], "matmul batch dims")
    if batch is None:
        return {"Out": VarInfo(None, dt)}
    return {"Out": VarInfo(tuple(batch) + (xs[-2], ys[-1]), dt)}


def _bias_span(out: Shape, bias: Shape, axis, what: str) -> Shape:
    """Paddle axis-span broadcast of a bias onto a larger operand (the
    elementwise Y-convention, see ops/math.py:_broadcast_y): validates
    the span, returns the (possibly widened) output shape."""
    if out is None or bias is None:
        return out
    if len(bias) > len(out):
        raise InferError(
            "%s rank %d exceeds the operand rank %d"
            % (what, len(bias), len(out)))
    if len(bias) == len(out):
        return broadcast_shapes(out, bias, what)
    a = axis if axis is not None and axis != -1 else len(out) - len(bias)
    if a < 0 or a + len(bias) > len(out):
        raise InferError(
            "axis=%d places %s%s outside the operand%s"
            % (a, what, render_shape(bias), render_shape(out)))
    res = list(out)
    for i, db in enumerate(bias):
        do = out[a + i]
        if do is not None and db is not None and do != db and db != 1 \
                and do != 1:
            raise InferError(
                "%s%s does not match the operand%s's dims at axis %d"
                % (what, render_shape(bias), render_shape(out), a))
        if do == 1:
            res[a + i] = db
    return tuple(res)


@register_infer("fused_fc")
def _infer_fused_fc(ctx: InferContext):
    """Transpiler-emitted matmul+bias(+act) fusion: Out has the mul/
    matmul contraction shape (contraction checks included), widened by
    the bias span; the activation is shape-preserving."""
    kind = ctx.attr("kind", "mul")
    if kind == "mul":
        base = _infer_mul(ctx)["Out"]
    else:
        base = _infer_matmul(ctx)["Out"]
    bias = ctx.in_info("Bias")
    if not ctx.has_input("Bias"):
        return {"Out": base}
    out = _bias_span(base.shape, bias.shape, ctx.attr("axis", -1), "Bias")
    return {"Out": VarInfo(out, promote_dtypes(base.dtype, bias.dtype))}


@register_infer("fused_elemwise_activation")
def _infer_fused_elemwise_activation(ctx: InferContext):
    """Binary+unary composition (ops/math.py): Out follows the binary's
    axis-span broadcast; IntermediateOut keeps Y's own shape in the
    ("binary","unary") ordering and the binary's shape otherwise."""
    x, y = ctx.in_info("X"), ctx.in_info("Y")
    dt = promote_dtypes(x.dtype, y.dtype)
    out = _bias_span(x.shape, y.shape, ctx.attr("axis", -1), "Y")
    functors = [str(f).strip() for f in (ctx.attr("functor_list") or ())]
    inter = (y if functors and functors[0] in
             ("elementwise_add", "elementwise_mul")
             else VarInfo(out, dt))
    return {"Out": VarInfo(out, dt), "IntermediateOut": inter}


@register_infer("sum")
def _infer_sum(ctx: InferContext):
    infos = ctx.in_infos("X")
    if not infos:
        return {"Out": VarInfo(None, None)}
    shape = infos[0].shape
    dt = infos[0].dtype
    for other in infos[1:]:
        shape = broadcast_shapes(shape, other.shape, "sum operands")
        dt = promote_dtypes(dt, other.dtype)
    return {"Out": VarInfo(shape, dt)}


@register_infer("minus")
def _infer_minus(ctx: InferContext):
    out = broadcast_shapes(ctx.in_shape("X"), ctx.in_shape("Y"), "X and Y")
    return {"Out": VarInfo(
        out, promote_dtypes(ctx.in_dtype("X"), ctx.in_dtype("Y")))}


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------


@register_infer("mean")
def _infer_mean(ctx: InferContext):
    return {"Out": VarInfo((), ctx.in_dtype("X"))}


def _reduce_axes(dim, rank: int) -> List[int]:
    # fold only genuine negative dims; an out-of-range positive dim must
    # stay out of range so the caller's check fires (the kernel would
    # fail at trace time — wrapping it here would infer a wrong shape)
    dims = dim if isinstance(dim, (list, tuple)) else [dim]
    return sorted({int(d) + rank if int(d) < 0 else int(d) for d in dims})


@register_infer("reduce_sum", "reduce_mean", "reduce_max", "reduce_min",
                "reduce_prod")
def _infer_reduce(ctx: InferContext):
    x = ctx.in_info("X")
    if ctx.attr("reduce_all", False):
        return {"Out": VarInfo((), x.dtype)}
    if x.shape is None:
        return {"Out": VarInfo(None, x.dtype)}
    rank = len(x.shape)
    axes = _reduce_axes(ctx.attr("dim", [0]), rank)
    if any(a >= rank or a < 0 for a in axes):
        raise InferError(
            "reduce dim %s out of range for input %s"
            % (ctx.attr("dim"), render_shape(x.shape)))
    if ctx.attr("keep_dim", False):
        out = [1 if i in axes else d for i, d in enumerate(x.shape)]
    else:
        out = [d for i, d in enumerate(x.shape) if i not in axes]
    return {"Out": VarInfo(tuple(out), x.dtype)}


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------


@register_infer("cross_entropy")
def _infer_cross_entropy(ctx: InferContext):
    x = ctx.in_info("X")
    if x.shape is None:
        return {"Y": VarInfo(None, x.dtype)}
    lbl = ctx.in_shape("Label")
    if not ctx.attr("soft_label", False) and lbl is not None and x.shape \
            and lbl[0] is not None and x.shape[0] is not None \
            and lbl[0] != x.shape[0]:
        raise InferError(
            "Label batch %d does not match X batch %d"
            % (lbl[0], x.shape[0]))
    return {"Y": VarInfo(tuple(x.shape[:-1]) + (1,), x.dtype)}


@register_infer("softmax_with_cross_entropy")
def _infer_softmax_xent(ctx: InferContext):
    logits = ctx.in_info("Logits")
    if logits.shape is None:
        return {"Loss": VarInfo(None, logits.dtype),
                "Softmax": VarInfo(None, logits.dtype)}
    lbl = ctx.in_shape("Label")
    if lbl is not None and logits.shape[0] is not None \
            and lbl[0] is not None and lbl[0] != logits.shape[0]:
        raise InferError(
            "Label batch %d does not match Logits batch %d"
            % (lbl[0], logits.shape[0]))
    loss = tuple(logits.shape[:-1]) + (1,)
    return {"Loss": VarInfo(loss, logits.dtype), "Softmax": logits}


@register_infer("square_error_cost")
def _infer_square_error(ctx: InferContext):
    out = broadcast_shapes(ctx.in_shape("X"), ctx.in_shape("Y"), "X and Y")
    return {"Out": VarInfo(
        out, promote_dtypes(ctx.in_dtype("X"), ctx.in_dtype("Y")))}


@register_infer("sigmoid_cross_entropy_with_logits")
def _infer_sig_xent(ctx: InferContext):
    out = broadcast_shapes(ctx.in_shape("X"), ctx.in_shape("Label"),
                           "X and Label")
    return {"Out": VarInfo(out, ctx.in_dtype("X"))}


@register_infer("huber_loss")
def _infer_huber(ctx: InferContext):
    out = broadcast_shapes(ctx.in_shape("X"), ctx.in_shape("Y"), "X and Y")
    dt = promote_dtypes(ctx.in_dtype("X"), ctx.in_dtype("Y"))
    return {"Out": VarInfo(out, dt), "Residual": VarInfo(out, dt)}


@register_infer("hinge_loss")
def _infer_hinge(ctx: InferContext):
    out = broadcast_shapes(ctx.in_shape("Logits"), ctx.in_shape("Labels"),
                           "Logits and Labels")
    return {"Loss": VarInfo(out, ctx.in_dtype("Logits"))}


@register_infer("log_loss")
def _infer_log_loss(ctx: InferContext):
    out = broadcast_shapes(ctx.in_shape("Predicted"),
                           ctx.in_shape("Labels"), "Predicted and Labels")
    return {"Loss": VarInfo(out, ctx.in_dtype("Predicted"))}


# ---------------------------------------------------------------------------
# shape manipulation
# ---------------------------------------------------------------------------


@register_infer("reshape")
def _infer_reshape(ctx: InferContext):
    x = ctx.in_info("X")
    target = list(ctx.attr("shape") or ())
    if not target:
        return {"Out": VarInfo(None, x.dtype)}
    out: List[Optional[int]] = []
    neg_idx = None
    for i, s in enumerate(target):
        s = int(s)
        if s == 0:  # paddle: copy dim i from input
            if x.shape is None or i >= len(x.shape):
                if x.shape is not None:
                    raise InferError(
                        "reshape shape[%d]=0 copies a dim the input %s "
                        "does not have" % (i, render_shape(x.shape)))
                out.append(None)
            else:
                out.append(x.shape[i])
        elif s == -1:
            if neg_idx is not None:
                raise InferError("reshape shape has more than one -1")
            neg_idx = i
            out.append(None)
        else:
            out.append(s)
    total = prod_dims(x.shape) if x.shape is not None else None
    if neg_idx is not None:
        rest = prod_dims([d for i, d in enumerate(out) if i != neg_idx])
        if total is not None and rest is not None:
            if rest == 0 or total % rest != 0:
                raise InferError(
                    "cannot reshape %s (%d elements) into %s"
                    % (render_shape(x.shape), total, target))
            out[neg_idx] = total // rest
    else:
        want = prod_dims(out)
        if total is not None and want is not None and total != want:
            raise InferError(
                "cannot reshape %s (%d elements) into %s (%d elements)"
                % (render_shape(x.shape), total, target, want))
    return {"Out": VarInfo(tuple(out), x.dtype)}


@register_infer("squeeze")
def _infer_squeeze(ctx: InferContext):
    x = ctx.in_info("X")
    if x.shape is None:
        return {"Out": x}
    axes = ctx.attr("axes", []) or []
    if not axes:
        if not x.known:
            return {"Out": VarInfo(None, x.dtype)}
        return {"Out": VarInfo(
            tuple(d for d in x.shape if d != 1), x.dtype)}
    rank = len(x.shape)
    drop = set()
    for a in axes:
        a = int(a) % rank
        if x.shape[a] is not None and x.shape[a] != 1:
            raise InferError(
                "squeeze axis %d has size %d (must be 1) in %s"
                % (a, x.shape[a], render_shape(x.shape)))
        drop.add(a)
    return {"Out": VarInfo(
        tuple(d for i, d in enumerate(x.shape) if i not in drop),
        x.dtype)}


@register_infer("unsqueeze")
def _infer_unsqueeze(ctx: InferContext):
    x = ctx.in_info("X")
    if x.shape is None:
        return {"Out": x}
    out = list(x.shape)
    for ax in sorted(int(a) for a in ctx.attr("axes")):
        if ax < 0:
            ax += len(out) + 1
        out.insert(ax, 1)
    return {"Out": VarInfo(tuple(out), x.dtype)}


@register_infer("transpose")
def _infer_transpose(ctx: InferContext):
    x = ctx.in_info("X")
    perm = [int(p) for p in ctx.attr("axis")]
    if x.shape is None:
        return {"Out": x}
    if sorted(p % len(x.shape) for p in perm) != list(range(len(x.shape))):
        raise InferError(
            "transpose perm %s is not a permutation of input rank %d"
            % (perm, len(x.shape)))
    return {"Out": VarInfo(
        tuple(x.shape[p % len(x.shape)] for p in perm), x.dtype)}


@register_infer("concat")
def _infer_concat(ctx: InferContext):
    infos = ctx.in_infos("X")
    axis = int(ctx.attr("axis", 0))
    shapes = [i.shape for i in infos]
    dt = infos[0].dtype if infos else None
    for i in infos[1:]:
        dt = promote_dtypes(dt, i.dtype)
    known = [s for s in shapes if s is not None]
    if not known:
        return {"Out": VarInfo(None, dt)}
    rank = len(known[0])
    if any(len(s) != rank for s in known):
        raise InferError(
            "concat inputs have different ranks: %s"
            % ", ".join(render_shape(s) for s in known))
    ax = axis % rank
    out: List[Optional[int]] = list(known[0])
    for s in known[1:]:
        for i in range(rank):
            if i == ax:
                continue
            if out[i] is not None and s[i] is not None and out[i] != s[i]:
                raise InferError(
                    "concat inputs disagree on non-concat dim %d: %s"
                    % (i, ", ".join(render_shape(k) for k in known)))
            if out[i] is None:
                out[i] = s[i]
    if len(known) == len(shapes):
        out[ax] = sum_or_none([s[ax] for s in known])
    else:
        out[ax] = None
    return {"Out": VarInfo(tuple(out), dt)}


def sum_or_none(dims: List[Optional[int]]) -> Optional[int]:
    total = 0
    for d in dims:
        if d is None:
            return None
        total += d
    return total


@register_infer("split")
def _infer_split(ctx: InferContext):
    x = ctx.in_info("X")
    n_out = ctx.n_outputs("Out")
    if x.shape is None:
        return {"Out": [VarInfo(None, x.dtype)] * n_out}
    axis = int(ctx.attr("axis", 0)) % len(x.shape)
    sections = ctx.attr("sections", None)
    outs = []
    if sections:
        if x.shape[axis] is not None and sum(sections) != x.shape[axis]:
            raise InferError(
                "split sections %s sum to %d but dim %d of %s is %d"
                % (sections, sum(sections), axis, render_shape(x.shape),
                   x.shape[axis]))
        for s in sections:
            shp = list(x.shape)
            shp[axis] = int(s)
            outs.append(VarInfo(tuple(shp), x.dtype))
    else:
        num = int(ctx.attr("num", 0)) or n_out
        d = x.shape[axis]
        if d is not None and num and d % num != 0:
            raise InferError(
                "split num=%d does not divide dim %d (size %d) of %s"
                % (num, axis, d, render_shape(x.shape)))
        piece = None if d is None else d // num
        for _ in range(n_out):
            shp = list(x.shape)
            shp[axis] = piece
            outs.append(VarInfo(tuple(shp), x.dtype))
    return {"Out": outs}


@register_infer("stack")
def _infer_stack(ctx: InferContext):
    infos = ctx.in_infos("X")
    shape = infos[0].shape if infos else None
    dt = infos[0].dtype if infos else None
    for i in infos[1:]:
        shape = join_or_raise(shape, i.shape, "stack inputs")
        dt = promote_dtypes(dt, i.dtype)
    if shape is None:
        return {"Y": VarInfo(None, dt)}
    axis = int(ctx.attr("axis", 0))
    if axis < 0:
        axis += len(shape) + 1
    out = list(shape)
    out.insert(axis, len(infos))
    return {"Y": VarInfo(tuple(out), dt)}


def join_or_raise(a: Shape, b: Shape, what: str) -> Shape:
    """Shapes that must be identical (modulo unknowns)."""
    if a is None or b is None:
        return None
    if len(a) != len(b):
        raise InferError("%s have different ranks: %s vs %s"
                         % (what, render_shape(a), render_shape(b)))
    out = []
    for da, db in zip(a, b):
        if da is not None and db is not None and da != db:
            raise InferError("%s disagree: %s vs %s"
                             % (what, render_shape(a), render_shape(b)))
        out.append(da if da is not None else db)
    return tuple(out)


@register_infer("unstack")
def _infer_unstack(ctx: InferContext):
    x = ctx.in_info("X")
    n = ctx.n_outputs("Y")
    if x.shape is None:
        return {"Y": [VarInfo(None, x.dtype)] * n}
    axis = int(ctx.attr("axis", 0)) % len(x.shape)
    if x.shape[axis] is not None and x.shape[axis] != n:
        raise InferError(
            "unstack expects %d outputs but dim %d of %s is %d"
            % (n, axis, render_shape(x.shape), x.shape[axis]))
    shp = tuple(d for i, d in enumerate(x.shape) if i != axis)
    return {"Y": [VarInfo(shp, x.dtype)] * n}


@register_infer("flatten")
def _infer_flatten(ctx: InferContext):
    x = ctx.in_info("X")
    if x.shape is None:
        return {"Out": VarInfo(None, x.dtype)}
    ax = int(ctx.attr("axis", 1))
    lead = prod_dims(x.shape[:ax])
    tail = prod_dims(x.shape[ax:])
    return {"Out": VarInfo((lead, tail), x.dtype)}


@register_infer("expand")
def _infer_expand(ctx: InferContext):
    x = ctx.in_info("X")
    times = [int(t) for t in ctx.attr("expand_times")]
    if x.shape is None:
        return {"Out": VarInfo(None, x.dtype)}
    if len(times) != len(x.shape):
        raise InferError(
            "expand_times %s must have one entry per input dim (%s)"
            % (times, render_shape(x.shape)))
    return {"Out": VarInfo(
        tuple(None if d is None else d * t
              for d, t in zip(x.shape, times)), x.dtype)}


@register_infer("slice")
def _infer_slice(ctx: InferContext):
    x = ctx.in_info("Input")
    if x.shape is None:
        return {"Out": VarInfo(None, x.dtype)}
    out = list(x.shape)
    for ax, st, en in zip(ctx.attr("axes"), ctx.attr("starts"),
                          ctx.attr("ends")):
        ax = int(ax) % len(out)
        d = out[ax]
        if d is None:
            continue
        out[ax] = len(range(*slice(int(st), int(en)).indices(d)))
    return {"Out": VarInfo(tuple(out), x.dtype)}


@register_infer("pad")
def _infer_pad(ctx: InferContext):
    x = ctx.in_info("X")
    pads = [int(p) for p in ctx.attr("paddings")]
    if x.shape is None:
        return {"Out": VarInfo(None, x.dtype)}
    if len(pads) != 2 * len(x.shape):
        raise InferError(
            "paddings has %d entries; input %s needs %d"
            % (len(pads), render_shape(x.shape), 2 * len(x.shape)))
    out = tuple(None if d is None else d + pads[2 * i] + pads[2 * i + 1]
                for i, d in enumerate(x.shape))
    return {"Out": VarInfo(out, x.dtype)}


@register_infer("pad_constant_like")
def _infer_pad_constant_like(ctx: InferContext):
    return {"Out": VarInfo(ctx.in_shape("X"), ctx.in_dtype("Y"))}


@register_infer("crop")
def _infer_crop(ctx: InferContext):
    shape = ctx.attr("shape")
    return {"Out": info(tuple(int(s) for s in shape), ctx.in_dtype("X"))}


@register_infer("reverse")
def _infer_reverse(ctx: InferContext):
    return {"Out": ctx.in_info("X")}


@register_infer("shape")
def _infer_shape_op(ctx: InferContext):
    x = ctx.in_shape("Input")
    return {"Out": VarInfo((len(x),) if x is not None else (None,),
                           "int32")}


# ---------------------------------------------------------------------------
# indexing / selection
# ---------------------------------------------------------------------------


def _require_int(ctx: InferContext, slot: str):
    dt = ctx.in_dtype(slot)
    if dt is not None and not (dt.startswith("int") or dt.startswith("uint")
                               or dt == "bool"):
        raise InferError(
            "input %s of %r must be an integer tensor, got %s"
            % (slot, ctx.op.type, dt), code="dtype-mismatch",
            hint="cast the indices with layers.cast(..., 'int64')")


@register_infer("gather")
def _infer_gather(ctx: InferContext):
    x = ctx.in_info("X")
    _require_int(ctx, "Index")
    idx = ctx.in_shape("Index")
    n = prod_dims(idx) if idx is not None else None
    if x.shape is None:
        return {"Out": VarInfo(None, x.dtype)}
    return {"Out": VarInfo((n,) + tuple(x.shape[1:]), x.dtype)}


@register_infer("lookup_table")
def _infer_lookup_table(ctx: InferContext):
    w = ctx.want_rank("W", 2)
    _require_int(ctx, "Ids")
    ids = ctx.in_shape("Ids")
    emb = w[1] if w is not None else None
    if ids is None or (len(ids) > 1 and ids[-1] is None):
        # the kernel squeezes a trailing 1 at trace time; an UNKNOWN
        # trailing dim means the output rank itself is unknown
        return {"Out": VarInfo(None, ctx.in_dtype("W"))}
    if len(ids) > 1 and ids[-1] == 1:
        ids = ids[:-1]
    return {"Out": VarInfo(tuple(ids) + (emb,), ctx.in_dtype("W"))}


@register_infer("one_hot")
def _infer_one_hot(ctx: InferContext):
    _require_int(ctx, "X")
    ids = ctx.in_shape("X")
    depth = int(ctx.attr("depth"))
    if ids is None or (len(ids) > 1 and ids[-1] is None):
        # same trailing-1 squeeze caveat as lookup_table: unknown
        # trailing dim -> unknown output rank
        return {"Out": VarInfo(None, "float32")}
    if len(ids) > 1 and ids[-1] == 1:
        ids = ids[:-1]
    return {"Out": VarInfo(tuple(ids) + (depth,), "float32")}


@register_infer("top_k")
def _infer_top_k(ctx: InferContext):
    x = ctx.in_info("X")
    k = int(ctx.attr("k", 1))
    if x.shape is None:
        return {"Out": VarInfo(None, x.dtype),
                "Indices": VarInfo(None, "int64")}
    last = x.shape[-1]
    if last is not None and k > last:
        raise InferError(
            "top_k k=%d exceeds the candidate dim %d of %s"
            % (k, last, render_shape(x.shape)))
    out = tuple(x.shape[:-1]) + (k,)
    return {"Out": VarInfo(out, x.dtype), "Indices": VarInfo(out, "int64")}


@register_infer("arg_max", "arg_min")
def _infer_arg_extreme(ctx: InferContext):
    x = ctx.in_shape("X")
    if x is None:
        return {"Out": VarInfo(None, "int64")}
    axis = int(ctx.attr("axis", -1)) % len(x)
    return {"Out": VarInfo(
        tuple(d for i, d in enumerate(x) if i != axis), "int64")}


@register_infer("argsort")
def _infer_argsort(ctx: InferContext):
    x = ctx.in_info("X")
    return {"Out": x, "Indices": VarInfo(x.shape, "int64")}


# ---------------------------------------------------------------------------
# casts / fills / random
# ---------------------------------------------------------------------------


@register_infer("cast")
def _infer_cast(ctx: InferContext):
    return {"Out": VarInfo(ctx.in_shape("X"),
                           convert_dtype(ctx.attr("out_dtype")))}


@register_infer("fill_constant", "gaussian_random", "uniform_random",
                "truncated_gaussian_random")
def _infer_fill_shape_attr(ctx: InferContext):
    return {"Out": info(tuple(int(s) for s in ctx.attr("shape")),
                        ctx.attr("dtype", "float32"))}


@register_infer("fill", "assign_value")
def _infer_fill_values(ctx: InferContext):
    return {"Out": info(tuple(int(s) for s in ctx.attr("shape")),
                        ctx.attr("dtype", "float32"))}


@register_infer("fill_constant_batch_size_like",
                "uniform_random_batch_size_like",
                "gaussian_random_batch_size_like")
def _infer_fill_batch_like(ctx: InferContext):
    ref = ctx.in_shape("Input")
    shape = [int(s) for s in ctx.attr("shape")]
    in_idx = int(ctx.attr("input_dim_idx", 0))
    out_idx = int(ctx.attr("output_dim_idx", 0))
    out: List[Optional[int]] = [None if s < 0 else s for s in shape]
    out[out_idx] = (ref[in_idx]
                    if ref is not None and in_idx < len(ref) else None)
    return {"Out": VarInfo(tuple(out),
                           convert_dtype(ctx.attr("dtype", "float32")))}


# ---------------------------------------------------------------------------
# normalization / conv / pool
# ---------------------------------------------------------------------------


@register_infer("l2_normalize")
def _infer_l2_normalize(ctx: InferContext):
    x = ctx.in_info("X")
    if x.shape is None:
        return {"Out": x, "Norm": VarInfo(None, x.dtype)}
    axis = int(ctx.attr("axis", -1)) % len(x.shape)
    norm = tuple(1 if i == axis else d for i, d in enumerate(x.shape))
    return {"Out": x, "Norm": VarInfo(norm, x.dtype)}


@register_infer("batch_norm")
def _infer_batch_norm(ctx: InferContext):
    x = ctx.in_info("X")
    layout = ctx.attr("data_layout", "NCHW")
    c = None
    if x.shape is not None:
        c_axis = 1 if layout == "NCHW" else len(x.shape) - 1
        c = x.shape[c_axis]
        scale = ctx.in_shape("Scale")
        if scale is not None and scale[0] is not None and c is not None \
                and scale[0] != c:
            raise InferError(
                "Scale has %d channels but X%s has %d"
                % (scale[0], render_shape(x.shape), c))
    stat = VarInfo((c,), ctx.in_dtype("Mean") or "float32")
    return {"Y": x, "MeanOut": stat, "VarianceOut": stat,
            "SavedMean": stat, "SavedVariance": stat}


@register_infer("layer_norm")
def _infer_layer_norm(ctx: InferContext):
    x = ctx.in_info("X")
    begin = int(ctx.attr("begin_norm_axis", 1))
    if x.shape is None:
        return {"Y": x, "Mean": VarInfo(None, None),
                "Variance": VarInfo(None, None)}
    stat_shape = tuple(x.shape[:begin])
    # stats ship in the DECLARED dtype (see ops/nn.py:_layer_norm)
    names = ctx.out_names("Mean")
    st_dt = ctx.declared(names[0]).dtype if names else "float32"
    stat = VarInfo(stat_shape, st_dt or "float32")
    return {"Y": x, "Mean": stat, "Variance": stat}


def _conv_spatial(d, k, p, s, dil):
    if d is None or k is None:
        return None
    return (d + 2 * p - dil * (k - 1) - 1) // s + 1


def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v),) * n


@register_infer("conv2d", "depthwise_conv2d")
def _infer_conv2d(ctx: InferContext):
    x = ctx.in_shape("Input")
    w = ctx.in_shape("Filter")
    dt = ctx.in_dtype("Input")
    if x is None or w is None or len(x) != 4 or len(w) != 4:
        return {"Output": VarInfo(None, dt)}
    strides = _pair(ctx.attr("strides", [1, 1]))
    pads = _pair(ctx.attr("paddings", [0, 0]))
    dil = _pair(ctx.attr("dilations", [1, 1]))
    groups = int(ctx.attr("groups", 1) or 1)
    nhwc = (ctx.attr("data_format", "NCHW") or "NCHW") == "NHWC"
    cin = x[3] if nhwc else x[1]
    if cin is not None and w[1] is not None and cin != w[1] * groups:
        raise InferError(
            "Input has %d channels but Filter %s with groups=%d expects "
            "%d" % (cin, render_shape(w), groups, w[1] * groups),
            hint="num_filters/groups or the input channel count is wrong")
    h_in, w_in = (x[1], x[2]) if nhwc else (x[2], x[3])
    oh = _conv_spatial(h_in, w[2], pads[0], strides[0], dil[0])
    ow = _conv_spatial(w_in, w[3], pads[1], strides[1], dil[1])
    if nhwc:
        out = (x[0], oh, ow, w[0])
    else:
        out = (x[0], w[0], oh, ow)
    return {"Output": VarInfo(out, dt)}


@register_infer("conv3d")
def _infer_conv3d(ctx: InferContext):
    x = ctx.in_shape("Input")
    w = ctx.in_shape("Filter")
    dt = ctx.in_dtype("Input")
    if x is None or w is None or len(x) != 5 or len(w) != 5:
        return {"Output": VarInfo(None, dt)}
    strides = _pair(ctx.attr("strides", [1, 1, 1]), 3)
    pads = _pair(ctx.attr("paddings", [0, 0, 0]), 3)
    dil = _pair(ctx.attr("dilations", [1, 1, 1]), 3)
    groups = int(ctx.attr("groups", 1) or 1)
    if x[1] is not None and w[1] is not None and x[1] != w[1] * groups:
        raise InferError(
            "Input has %d channels but Filter %s with groups=%d expects "
            "%d" % (x[1], render_shape(w), groups, w[1] * groups))
    sp = tuple(
        _conv_spatial(x[2 + i], w[2 + i], pads[i], strides[i], dil[i])
        for i in range(3))
    return {"Output": VarInfo((x[0], w[0]) + sp, dt)}


@register_infer("pool2d", "pool3d")
def _infer_pool(ctx: InferContext):
    x = ctx.in_info("X")
    nd = 2 if ctx.op.type == "pool2d" else 3
    if x.shape is None or len(x.shape) != nd + 2:
        return {"Out": VarInfo(None, x.dtype)}
    nhwc = (ctx.attr("data_format", "NCHW") or "NCHW") in ("NHWC", "NDHWC")
    sp0 = 1 if nhwc else 2
    out = list(x.shape)
    if ctx.attr("global_pooling", False):
        for i in range(nd):
            out[sp0 + i] = 1
        return {"Out": VarInfo(tuple(out), x.dtype)}
    ksize = _pair(ctx.attr("ksize"), nd)
    strides = _pair(ctx.attr("strides", [1] * nd), nd)
    pads = _pair(ctx.attr("paddings", [0] * nd), nd)
    for i in range(nd):
        d = x.shape[sp0 + i]
        out[sp0 + i] = (None if d is None
                        else (d + 2 * pads[i] - ksize[i]) // strides[i] + 1)
    return {"Out": VarInfo(tuple(out), x.dtype)}


# ---------------------------------------------------------------------------
# rnn / sequence
# ---------------------------------------------------------------------------


@register_infer("lstm")
def _infer_lstm(ctx: InferContext):
    x = ctx.want_rank("Input", 3)
    w = ctx.want_rank("Weight", 2)
    dt = ctx.in_dtype("Input")
    hidden = w[0] if w is not None else None
    if x is not None and hidden is not None and x[-1] is not None \
            and x[-1] != 4 * hidden:
        raise InferError(
            "lstm Input%s last dim must be 4*hidden (=%d from Weight%s)"
            % (render_shape(x), 4 * hidden, render_shape(w)),
            hint="project the input with fc(size=4*hidden) first")
    b = x[0] if x is not None else None
    t = x[1] if x is not None else None
    seq = VarInfo((b, t, hidden), dt)
    last = VarInfo((b, hidden), dt)
    return {"Hidden": seq, "Cell": seq, "LastHidden": last,
            "LastCell": last}


@register_infer("gru")
def _infer_gru(ctx: InferContext):
    x = ctx.want_rank("Input", 3)
    w = ctx.want_rank("Weight", 2)
    dt = ctx.in_dtype("Input")
    hidden = w[0] if w is not None else None
    if x is not None and hidden is not None and x[-1] is not None \
            and x[-1] != 3 * hidden:
        raise InferError(
            "gru Input%s last dim must be 3*hidden (=%d from Weight%s)"
            % (render_shape(x), 3 * hidden, render_shape(w)))
    b = x[0] if x is not None else None
    t = x[1] if x is not None else None
    return {"Hidden": VarInfo((b, t, hidden), dt),
            "LastHidden": VarInfo((b, hidden), dt)}


@register_infer("sequence_pool")
def _infer_sequence_pool(ctx: InferContext):
    x = ctx.want_rank("X", 3)
    dt = ctx.in_dtype("X")
    if x is None:
        return {"Out": VarInfo(None, dt)}
    return {"Out": VarInfo((x[0], x[2]), dt)}


@register_infer("sequence_concat")
def _infer_sequence_concat(ctx: InferContext):
    infos = ctx.in_infos("X")
    shapes = [i.shape for i in infos]
    dt = infos[0].dtype if infos else None
    known = [s for s in shapes if s is not None]
    if not known or any(len(s) != len(known[0]) for s in known):
        return {"Out": VarInfo(None, dt)}
    out = list(known[0])
    out[1] = sum_or_none([s[1] for s in known]) \
        if len(known) == len(shapes) else None
    for i in range(len(out)):
        if i == 1:
            continue
        for s in known[1:]:
            out[i] = out[i] if out[i] is not None else s[i]
    return {"Out": VarInfo(tuple(out), dt)}


# ---------------------------------------------------------------------------
# optimizers — elementwise updates: every "<Slot>Out" output mirrors its
# "<Slot>" input (reference: sgd_op.cc etc. InferShape does the same)
# ---------------------------------------------------------------------------

_OPTIMIZERS = (
    "sgd", "momentum", "adam", "adamax", "adagrad", "adadelta",
    "decayed_adagrad", "ftrl", "rmsprop", "proximal_gd",
    "proximal_adagrad",
)


@register_infer(*_OPTIMIZERS)
def _infer_optimizer(ctx: InferContext):
    param = ctx.in_info("Param")
    grad = ctx.in_shape("Grad")
    if param.shape is not None and grad is not None:
        join_or_raise(param.shape, grad, "Param and Grad")
    out = {}
    for slot in ctx.op.outputs:
        if slot.endswith("Out"):
            src = slot[:-3]
            out[slot] = ctx.in_info(src) if ctx.has_input(src) else param
    return out


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


@register_infer("fused_attention", "ring_attention")
def _infer_fused_attention(ctx: InferContext):
    """Out mirrors Q ((B, H, T, Dh) or (B, T, H, Dh) — layout-agnostic:
    attention preserves the query tensor's shape either way)."""
    q = ctx.in_info("Q")
    for slot in ("K", "V"):
        o = ctx.in_shape(slot)
        if (q.shape is not None and o is not None
                and len(o) != len(q.shape)):
            raise InferError(
                "%s rank %d does not match Q rank %d"
                % (slot, len(o), len(q.shape)))
    return {"Out": VarInfo(q.shape, q.dtype)}


@register_infer("decode_attention")
def _infer_decode_attention(ctx: InferContext):
    """Q (B, 1, H, Dh) x KCache/VCache (B, S, Hkv, Dh) -> Out = Q shape.
    The slab's batch and depth dims must match the query's; its heads
    must divide the query's (equal but for grouped queries)."""
    q = ctx.in_info("Q")
    qs = q.shape
    if qs is not None and len(qs) != 4:
        raise InferError("Q must be rank 4 (B, 1, H, Dh), got rank %d"
                         % len(qs))
    if qs is not None and qs[1] not in (None, 1):
        raise InferError(
            "decode_attention takes ONE query per sequence; Q%s has "
            "time dim %s" % (render_shape(qs), qs[1]))
    for slot in ("KCache", "VCache"):
        c = ctx.in_shape(slot)
        if qs is None or c is None:
            continue
        if len(c) != 4:
            raise InferError("%s must be rank 4 (B, S, H, Dh), got rank "
                             "%d" % (slot, len(c)))
        for qi, ci, label in ((0, 0, "batch"), (3, 3, "depth")):
            if qs[qi] is not None and c[ci] is not None \
                    and qs[qi] != c[ci]:
                raise InferError(
                    "%s %s dim %d does not match Q%s"
                    % (slot, label, c[ci], render_shape(qs)))
        # grouped queries: the slab may hold fewer heads, which divide
        if qs[2] is not None and c[2] is not None and qs[2] % c[2]:
            raise InferError(
                "%s head dim %d does not divide Q%s"
                % (slot, c[2], render_shape(qs)))
    return {"Out": VarInfo(qs, q.dtype)}


@register_infer("cache_append")
def _infer_cache_append(ctx: InferContext):
    """Out is the updated slab: Cache's shape and dtype verbatim."""
    c = ctx.in_info("Cache")
    n = ctx.in_shape("New")
    if c.shape is not None and n is not None:
        if len(n) == len(c.shape) and n[1] is not None and n[1] != 1:
            raise InferError(
                "cache_append appends ONE row per sequence; New has "
                "time dim %d" % n[1])
        tail = n[2:] if len(n) == len(c.shape) else n[1:]
        want = tuple(c.shape[2:])
        if (len(tail) != len(want)
            or any(a is not None and b is not None and a != b
                   for a, b in zip(tail, want))):
            raise InferError(
                "New%s row shape does not match Cache%s rows"
                % (render_shape(n), render_shape(c.shape)))
    return {"Out": VarInfo(c.shape, c.dtype)}


@register_infer("rope")
def _infer_rope(ctx: InferContext):
    """Out mirrors X (B, T, H, Dh); rotary_dim is even and fits a
    head."""
    x = ctx.in_info("X")
    r = ctx.attr("rotary_dim", None)
    if x.shape is not None:
        if len(x.shape) != 4:
            raise InferError("X must be rank 4 (B, T, H, Dh), got rank %d"
                             % len(x.shape))
        if r is not None and x.shape[3] is not None and (
                int(r) % 2 or not 0 < int(r) <= x.shape[3]):
            raise InferError("rotary_dim %d is not an even part of X%s's "
                             "heads" % (int(r), render_shape(x.shape)))
    return {"Out": VarInfo(x.shape, x.dtype)}


@register_infer("moe_route")
def _infer_moe_route(ctx: InferContext):
    """Idx and Weights are X's leading axes with top_k last; top_k
    cannot pass the router's width."""
    x, w = ctx.in_shape("X"), ctx.in_shape("W")
    k = int(ctx.attr("top_k"))
    if w is not None and len(w) == 2:
        if (x is not None and x[-1] is not None and w[0] is not None
                and x[-1] != w[0]):
            raise InferError("W%s rows do not match X%s's width"
                             % (render_shape(w), render_shape(x)))
        if w[1] is not None and k > w[1]:
            raise InferError("top_k %d passes the router's %d experts"
                             % (k, w[1]))
    shape = None if x is None else tuple(x[:-1]) + (k,)
    return {"Idx": VarInfo(shape, "int32"),
            "Weights": VarInfo(shape, "float32")}


@register_infer("moe_experts")
def _infer_moe_experts(ctx: InferContext):
    """Out mirrors X (B, T, D); Load is (Eh,) int32 with WGate (Eh, D,
    F), one entry more under ``count_elsewhere``; WDown is (Eh, F,
    D)."""
    x = ctx.in_info("X")
    g, dn = ctx.in_shape("WGate"), ctx.in_shape("WDown")
    eh = None
    if g is not None:
        if len(g) != 3:
            raise InferError("WGate must be rank 3 (Eh, D, F), got rank %d"
                             % len(g))
        eh = g[0]
        if (x.shape is not None and x.shape[-1] is not None
                and g[1] is not None and x.shape[-1] != g[1]):
            raise InferError("WGate%s does not take X%s's width"
                             % (render_shape(g), render_shape(x.shape)))
        if dn is not None and len(dn) == 3 and any(
                a is not None and b is not None and a != b
                for a, b in zip(dn, (g[0], g[2], g[1]))):
            raise InferError("WDown%s is not WGate%s transposed"
                             % (render_shape(dn), render_shape(g)))
    if eh is not None and ctx.attr("count_elsewhere", False):
        eh += 1
    return {"Out": VarInfo(x.shape, x.dtype),
            "Load": VarInfo((eh,), "int32")}


@register_infer("moe_shared")
def _infer_moe_shared(ctx: InferContext):
    """Out mirrors X."""
    x = ctx.in_info("X")
    return {"Out": VarInfo(x.shape, x.dtype)}


def _grouped_heads(ctx: InferContext, slots, axes):
    """Q (B, T, H, Dh) against K/V-like inputs of fewer heads: rank 4,
    batch and depth equal, heads dividing. ``axes``: (batch, head,
    depth) of the other inputs."""
    q = ctx.in_info("Q")
    qs = q.shape
    if qs is not None and len(qs) != 4:
        raise InferError("Q must be rank 4 (B, T, H, Dh), got rank %d"
                         % len(qs))
    for slot in slots:
        c = ctx.in_shape(slot)
        if c is None or qs is None:
            continue
        if len(c) != 4:
            raise InferError("%s must be rank 4, got rank %d"
                             % (slot, len(c)))
        for qi, ci, label in ((0, axes[0], "batch"), (3, axes[2], "depth")):
            if qs[qi] is not None and c[ci] is not None \
                    and qs[qi] != c[ci]:
                raise InferError("%s %s dim %d does not match Q%s"
                                 % (slot, label, c[ci], render_shape(qs)))
        if qs[2] is not None and c[axes[1]] is not None \
                and qs[2] % c[axes[1]]:
            raise InferError("%s head dim %d does not divide Q%s"
                             % (slot, c[axes[1]], render_shape(qs)))
    return VarInfo(qs, q.dtype)


@register_infer("prefill_attention")
def _infer_prefill_attention(ctx: InferContext):
    """Q (B, T, H, dq) x K (B, T, Hkv, dq), V (B, T, Hkv, dv) -> Out =
    Q's shape at V's width."""
    if int(ctx.attr("window", 0) or 0) < 0:
        raise InferError("window must be >= 0, got %r"
                         % ctx.attr("window", None))
    out = _grouped_heads(ctx, ("K",), (0, 2, 3))
    k, v = ctx.in_shape("K"), ctx.in_shape("V")
    if out.shape is None or v is None:
        return {"Out": out}
    if len(v) != 4 or (k is not None and tuple(v[:3]) != tuple(k[:3])):
        raise InferError("V%s does not hold K%s's rows and heads"
                         % (render_shape(v), render_shape(k)))
    return {"Out": VarInfo(tuple(out.shape[:-1]) + (v[-1],), out.dtype)}


@register_infer("decode_attn_ring")
def _infer_decode_attn_ring(ctx: InferContext):
    """Q (B, 1, H, Dh) x rings K (B, W, Hkv, Dh), V (B, W, Hkv, dv) ->
    Out = Q's shape at V's width."""
    out = _grouped_heads(ctx, ("KCache",), (0, 2, 3))
    k, v = ctx.in_shape("KCache"), ctx.in_shape("VCache")
    if out.shape is None or v is None:
        return {"Out": out}
    if len(v) != 4 or (k is not None and tuple(v[:3]) != tuple(k[:3])):
        raise InferError("VCache%s does not hold KCache%s's rows and heads"
                         % (render_shape(v), render_shape(k)))
    return {"Out": VarInfo(tuple(out.shape[:-1]) + (v[-1],), out.dtype)}


@register_infer("decode_attention_uneven")
def _infer_decode_attention_uneven(ctx: InferContext):
    """Q (B, 1, H, dk) x FLAT rows KCache (B, S, Hkv dk), VCache (B, S,
    Hkv dv) -> Out (B, 1, H, dv)."""
    q = ctx.in_info("Q")
    qs, hkv = q.shape, int(ctx.attr("n_kv_head", 0) or 0)
    k, v = ctx.in_shape("KCache"), ctx.in_shape("VCache")
    if qs is None or k is None or v is None:
        return {"Out": VarInfo(None, q.dtype)}
    if len(qs) != 4 or len(k) != 3 or len(v) != 3:
        raise InferError("Q%s must be rank 4 and KCache%s, VCache%s flat "
                         "rows of rank 3" % (render_shape(qs),
                                             render_shape(k),
                                             render_shape(v)))
    if (hkv <= 0 or qs[2] % hkv or k[-1] != hkv * qs[3] or v[-1] % hkv
            or tuple(k[:2]) != tuple(v[:2])):
        raise InferError(
            "KCache%s, VCache%s are not %d key/value heads' rows under Q%s"
            % (render_shape(k), render_shape(v), hkv, render_shape(qs)))
    return {"Out": VarInfo(tuple(qs[:-1]) + (v[-1] // hkv,), q.dtype)}


@register_infer("ring_append")
def _infer_ring_append(ctx: InferContext):
    """Out is the ring: Cache's shape and dtype verbatim."""
    return _infer_cache_append(ctx)


@register_infer("ring_pack")
def _infer_ring_pack(ctx: InferContext):
    """Out is X with its time axis replaced by the window."""
    x = ctx.in_info("X")
    w = int(ctx.attr("window", 0))
    if w < 1:
        raise InferError("window must be >= 1, got %d" % w)
    shape = None if x.shape is None else (x.shape[0], w) + tuple(x.shape[2:])
    return {"Out": VarInfo(shape, x.dtype)}


def _infer_diff(ctx: InferContext, slots):
    """Q (B, T, H, dh) against flat key/value rows (B, ., Hkv dh): Out
    is (B, T, H / 2, 2 dh); Hkv is even and its pairs divide H's."""
    q = ctx.in_info("Q")
    qs = q.shape
    if qs is None:
        return {"Out": VarInfo(None, q.dtype)}
    if len(qs) != 4:
        raise InferError("Q must be rank 4 (B, T, H, dh), got rank %d"
                         % len(qs))
    for slot in slots:
        c = ctx.in_shape(slot)
        if c is None:
            continue
        if len(c) != 3:
            raise InferError("%s must be rank 3 (B, S, Hkv dh: flat rows),"
                             " got rank %d" % (slot, len(c)))
        if qs[3] is not None and c[2] is not None and (
                c[2] % (2 * qs[3]) or not c[2]):
            raise InferError(
                "%s%s does not hold key/value PAIRS of Q%s's heads (a "
                "row of a multiple of 2 x %d)"
                % (slot, render_shape(c), render_shape(qs), qs[3]))
        if None not in (qs[2], qs[3], c[2]) and qs[2] % (c[2] // qs[3]):
            raise InferError("%s's %d key/value heads do not divide Q%s's"
                             % (slot, c[2] // qs[3], render_shape(qs)))
        if qs[0] is not None and c[0] is not None and qs[0] != c[0]:
            raise InferError("%s batch dim %d does not match Q%s"
                             % (slot, c[0], render_shape(qs)))
    h = None if qs[2] is None else qs[2] // 2
    w = None if qs[3] is None else 2 * qs[3]
    return {"Out": VarInfo((qs[0], qs[1], h, w), q.dtype)}


@register_infer("diff_attention")
def _infer_diff_attention(ctx: InferContext):
    if int(ctx.attr("window", 0) or 0) < 0:
        raise InferError("window must be >= 0, got %r"
                         % ctx.attr("window", None))
    return _infer_diff(ctx, ("K", "V"))


@register_infer("diff_decode_attention")
def _infer_diff_decode_attention(ctx: InferContext):
    return _infer_diff(ctx, ("KCache", "VCache"))


@register_infer("attn_cross")
def _infer_attn_cross(ctx: InferContext):
    return _infer_diff(ctx, ("KCache", "VCache"))


@register_infer("gmu")
def _infer_gmu(ctx: InferContext):
    """Out mirrors X; Memory is X's rows at WIn's width."""
    x = ctx.in_info("X")
    m, w = ctx.in_shape("Memory"), ctx.in_shape("WIn")
    if (m is not None and w is not None and len(w) == 2
            and m[-1] is not None and w[1] is not None and m[-1] != w[1]):
        raise InferError("Memory%s is not as wide as WIn%s's columns"
                         % (render_shape(m), render_shape(w)))
    if (x.shape is not None and m is not None and len(m) == len(x.shape)
            and any(a is not None and b is not None and a != b
                    for a, b in zip(x.shape[:-1], m[:-1]))):
        raise InferError("Memory%s does not hold a row for each of X%s's"
                         % (render_shape(m), render_shape(x.shape)))
    return {"Out": VarInfo(x.shape, x.dtype)}


@register_infer("rms_norm")
def _infer_rms_norm(ctx: InferContext):
    """Out mirrors X; Scale is X's last axis."""
    x = ctx.in_info("X")
    sc = ctx.in_shape("Scale")
    if (x.shape is not None and sc is not None and sc[-1] is not None
            and x.shape[-1] is not None and sc[-1] != x.shape[-1]):
        raise InferError("Scale%s does not match X%s's last axis"
                         % (render_shape(sc), render_shape(x.shape)))
    return {"Out": VarInfo(x.shape, x.dtype)}


def _ssm_state_shape(ctx: InferContext):
    """(B, Di, N) from X (B, T, Di) and A (Di, N), with what is known."""
    x, a = ctx.in_shape("X"), ctx.in_shape("A")
    if x is not None and a is not None and len(a) == 2:
        if x[-1] is not None and a[0] is not None and x[-1] != a[0]:
            raise InferError("A%s rows do not match X%s's width"
                             % (render_shape(a), render_shape(x)))
        return (x[0], x[-1], a[1])
    return None


@register_infer("ssm_scan")
def _infer_ssm_scan(ctx: InferContext):
    """Y mirrors X (B, T, Di); State is (B, Di, N) with A (Di, N)."""
    x = ctx.in_info("X")
    if x.shape is not None and len(x.shape) != 3:
        raise InferError("X must be rank 3 (B, T, Di), got rank %d"
                         % len(x.shape))
    return {"Y": VarInfo(x.shape, x.dtype),
            "State": VarInfo(_ssm_state_shape(ctx), x.dtype)}


@register_infer("ssm_step")
def _infer_ssm_step(ctx: InferContext):
    """Y mirrors X; StateOut mirrors State (B, Di, N)."""
    x, st = ctx.in_info("X"), ctx.in_info("State")
    want = _ssm_state_shape(ctx)
    if (want is not None and st.shape is not None
            and any(a is not None and b is not None and a != b
                    for a, b in zip(want, st.shape))):
        raise InferError("State%s is not (B, Di, N) = %s"
                         % (render_shape(st.shape), render_shape(want)))
    return {"Y": VarInfo(x.shape, x.dtype),
            "StateOut": VarInfo(st.shape, st.dtype)}


@register_infer("causal_conv1d")
def _infer_causal_conv1d(ctx: InferContext):
    """Y mirrors X (B, T, C); Window is (B, K - 1, C) with W (C, K)."""
    x = ctx.in_info("X")
    w = ctx.in_shape("W")
    if x.shape is not None and len(x.shape) != 3:
        raise InferError("X must be rank 3 (B, T, C), got rank %d"
                         % len(x.shape))
    win = None
    if x.shape is not None and w is not None and len(w) == 2:
        if (x.shape[2] is not None and w[0] is not None
                and x.shape[2] != w[0]):
            raise InferError("W%s rows do not match X%s channels"
                             % (render_shape(w), render_shape(x.shape)))
        win = (x.shape[0], None if w[1] is None else w[1] - 1, x.shape[2])
    return {"Y": VarInfo(x.shape, x.dtype), "Window": VarInfo(win, x.dtype)}


@register_infer("causal_conv1d_step")
def _infer_causal_conv1d_step(ctx: InferContext):
    """Y mirrors X; WindowOut mirrors Window (B, K - 1, C)."""
    x, win = ctx.in_info("X"), ctx.in_info("Window")
    w = ctx.in_shape("W")
    if (win.shape is not None and w is not None and len(w) == 2
            and win.shape[1] is not None and w[1] is not None
            and win.shape[1] != w[1] - 1):
        raise InferError("Window%s does not hold K - 1 = %d inputs"
                         % (render_shape(win.shape), w[1] - 1))
    return {"Y": VarInfo(x.shape, x.dtype),
            "WindowOut": VarInfo(win.shape, win.dtype)}


@register_infer("cache_gather")
def _infer_cache_gather(ctx: InferContext):
    """Out: Index's element count of slab rows — (N,) + Cache[1:]."""
    c = ctx.in_info("Cache")
    idx = ctx.in_shape("Index")
    n = prod_dims(idx) if idx is not None else None
    if c.shape is None:
        return {"Out": VarInfo(None, c.dtype)}
    return {"Out": VarInfo((n,) + tuple(c.shape[1:]), c.dtype)}


@register_infer("cache_append_window")
def _infer_cache_append_window(ctx: InferContext):
    """Windowed slab append (speculative verify / prefix extension):
    Out is Cache's shape/dtype; New (B, T, ...) rows must match Cache's
    row shape (any T — the window width is the free axis)."""
    c = ctx.in_info("Cache")
    n = ctx.in_shape("New")
    if c.shape is not None and n is not None:
        if len(n) != len(c.shape):
            raise InferError(
                "New%s rank does not match Cache%s (window appends are "
                "(B, T, ...) against (B, S, ...))"
                % (render_shape(n), render_shape(c.shape)))
        tail, want = n[2:], tuple(c.shape[2:])
        if (len(tail) != len(want)
            or any(a is not None and b is not None and a != b
                   for a, b in zip(tail, want))):
            raise InferError(
                "New%s row shape does not match Cache%s rows"
                % (render_shape(n), render_shape(c.shape)))
    return {"Out": VarInfo(c.shape, c.dtype)}


@register_infer("decode_attention_window")
def _infer_decode_attention_window(ctx: InferContext):
    """Q (B, T, H, Dh) x KCache/VCache (B, S, H, Dh) -> Out = Q shape
    (the decode_attention contract with a free window width T)."""
    q = ctx.in_info("Q")
    qs = q.shape
    if qs is not None and len(qs) != 4:
        raise InferError("Q must be rank 4 (B, T, H, Dh), got rank %d"
                         % len(qs))
    for slot in ("KCache", "VCache"):
        c = ctx.in_shape(slot)
        if qs is None or c is None:
            continue
        if len(c) != 4:
            raise InferError("%s must be rank 4 (B, S, H, Dh), got rank "
                             "%d" % (slot, len(c)))
        for qi, ci, label in ((0, 0, "batch"), (3, 3, "depth")):
            if qs[qi] is not None and c[ci] is not None \
                    and qs[qi] != c[ci]:
                raise InferError(
                    "%s %s dim %d does not match Q%s"
                    % (slot, label, c[ci], render_shape(qs)))
        # grouped queries: the slab may hold fewer heads, which divide
        if qs[2] is not None and c[2] is not None and qs[2] % c[2]:
            raise InferError(
                "%s head dim %d does not divide Q%s"
                % (slot, c[2], render_shape(qs)))
    return {"Out": VarInfo(qs, q.dtype)}


@register_infer("spec_accept")
def _infer_spec_accept(ctx: InferContext):
    """Proposed (B, T) window tokens x Logits (B, T, V) -> NextIds
    (B, T) int64 + Accept (B,) int32; the leading (B, T) dims must
    agree."""
    p = ctx.in_shape("Proposed")
    lg = ctx.in_shape("Logits")
    if p is not None and len(p) != 2:
        raise InferError("Proposed must be (B, T), got rank %d" % len(p))
    if lg is not None and len(lg) != 3:
        raise InferError("Logits must be (B, T, V), got rank %d" % len(lg))
    if p is not None and lg is not None:
        for i, label in ((0, "batch"), (1, "window")):
            if p[i] is not None and lg[i] is not None and p[i] != lg[i]:
                raise InferError(
                    "Logits %s dim %d does not match Proposed%s"
                    % (label, lg[i], render_shape(p)))
    b = p[0] if p is not None else (lg[0] if lg is not None else None)
    t = p[1] if p is not None else (lg[1] if lg is not None else None)
    return {"NextIds": VarInfo((b, t), "int64"),
            "Accept": VarInfo((b,), "int32")}


@register_infer("greedy_sample", "top_k_sample", "top_p_sample")
def _infer_sample(ctx: InferContext):
    """(B, V) or (B, 1, V) logits -> (B,) int64 sampled ids."""
    lg = ctx.in_shape("Logits")
    if lg is None:
        return {"Out": VarInfo(None, "int64")}
    if len(lg) not in (2, 3):
        raise InferError(
            "Logits must be (B, V) or (B, 1, V), got rank %d" % len(lg))
    if len(lg) == 3 and lg[1] not in (None, 1):
        raise InferError(
            "3-D Logits need a singleton time dim, got %s"
            % render_shape(lg))
    return {"Out": VarInfo((lg[0],), "int64")}


@register_infer("accuracy")
def _infer_accuracy(ctx: InferContext):
    ind = ctx.in_shape("Indices")
    lbl = ctx.in_shape("Label")
    if ind is not None and lbl is not None and ind[0] is not None \
            and lbl[0] is not None and ind[0] != lbl[0]:
        raise InferError(
            "Indices batch %d does not match Label batch %d"
            % (ind[0], lbl[0]))
    return {"Accuracy": info((), "float32"),
            "Correct": info((), "int32"), "Total": info((), "int32")}


# ---------------------------------------------------------------------------
# int8 quantization ops (ops/quant.py; emitted by transpiler/passes/
# quantize.py and the DecodeServer's int8 KV-slab graphs)
# ---------------------------------------------------------------------------


@register_infer("quantize_linear")
def _infer_quantize_linear(ctx: InferContext):
    """Symmetric int8 quantization: X's shape, int8 out."""
    return {"Out": VarInfo(ctx.in_shape("X"), "int8")}


@register_infer("dequantize_linear")
def _infer_dequantize_linear(ctx: InferContext):
    return {"Out": VarInfo(ctx.in_shape("X"),
                           convert_dtype(ctx.attr("out_dtype", "float32")))}


@register_infer("quantized_matmul")
def _infer_quantized_matmul(ctx: InferContext):
    """Quantized fc: the mul contraction (int8 weight in its original
    layout, flattened by the num_col_dims attrs — contraction checks
    included), widened by the fused_fc bias span; Out keeps the FLOAT
    activation's dtype (the int32 accumulator dequantizes in-op)."""
    base = _infer_mul(ctx)["Out"]
    dt = ctx.in_dtype("X") or "float32"
    if not ctx.has_input("Bias"):
        return {"Out": VarInfo(base.shape, dt)}
    bias = ctx.in_info("Bias")
    out = _bias_span(base.shape, bias.shape, ctx.attr("axis", -1), "Bias")
    return {"Out": VarInfo(out, dt)}


@register_infer("quantized_conv2d")
def _infer_quantized_conv2d(ctx: InferContext):
    """conv2d spatial arithmetic with an int8 filter; Output keeps the
    float Input dtype (per-channel dequant is fused into the op)."""
    base = _infer_conv2d(ctx)["Output"]
    return {"Output": VarInfo(base.shape,
                              ctx.in_dtype("Input") or base.dtype)}


@register_infer("cache_append_quant")
def _infer_cache_append_quant(ctx: InferContext):
    """Quantized slab append: Out echoes the int8 Cache, OutScales the
    (B, S) Scales; New rows must match the slab row shape (the
    cache_append contract)."""
    c = ctx.in_info("Cache")
    s = ctx.in_info("Scales")
    n = ctx.in_shape("New")
    if c.shape is not None and n is not None:
        if len(n) == len(c.shape) and n[1] is not None and n[1] != 1:
            raise InferError(
                "cache_append_quant appends ONE row per sequence; New "
                "has time dim %d" % n[1])
        tail = n[2:] if len(n) == len(c.shape) else n[1:]
        want = tuple(c.shape[2:])
        if (len(tail) != len(want)
            or any(a is not None and b is not None and a != b
                   for a, b in zip(tail, want))):
            raise InferError(
                "New%s row shape does not match Cache%s rows"
                % (render_shape(n), render_shape(c.shape)))
    if c.shape is not None and s.shape is not None:
        if (len(s.shape) != 2
            or any(a is not None and b is not None and a != b
                   for a, b in zip(s.shape, c.shape[:2]))):
            raise InferError(
                "Scales%s must be (B, S) matching Cache%s's slot/seq "
                "dims" % (render_shape(s.shape), render_shape(c.shape)))
    return {"Out": VarInfo(c.shape, c.dtype),
            "OutScales": VarInfo(s.shape, s.dtype)}


@register_infer("decode_attention_quant")
def _infer_decode_attention_quant(ctx: InferContext):
    """Single-query attention over int8 slabs: Out = Q's shape/dtype;
    slab and scale dims must agree with the query (the decode_attention
    contract plus the (B, S) scale layout)."""
    q = ctx.in_info("Q")
    qs = q.shape
    if qs is not None and len(qs) != 4:
        raise InferError("Q must be rank 4 (B, 1, H, Dh), got rank %d"
                         % len(qs))
    if qs is not None and qs[1] not in (None, 1):
        raise InferError(
            "decode_attention_quant takes ONE query per sequence; Q%s "
            "has time dim %s" % (render_shape(qs), qs[1]))
    for slot in ("KCache", "VCache"):
        c = ctx.in_shape(slot)
        if qs is None or c is None:
            continue
        if len(c) != 4:
            raise InferError("%s must be rank 4 (B, S, H, Dh), got rank "
                             "%d" % (slot, len(c)))
        for qi, ci, label in ((0, 0, "batch"), (3, 3, "depth")):
            if qs[qi] is not None and c[ci] is not None \
                    and qs[qi] != c[ci]:
                raise InferError(
                    "%s %s dim %d does not match Q%s"
                    % (slot, label, c[ci], render_shape(qs)))
        # grouped queries: the slab may hold fewer heads, which divide
        if qs[2] is not None and c[2] is not None and qs[2] % c[2]:
            raise InferError(
                "%s head dim %d does not divide Q%s"
                % (slot, c[2], render_shape(qs)))
    for cslot, sslot in (("KCache", "KScales"), ("VCache", "VScales")):
        c = ctx.in_shape(cslot)
        s = ctx.in_shape(sslot)
        if c is None or s is None:
            continue
        if (len(s) != 2
            or any(a is not None and b is not None and a != b
                   for a, b in zip(s, c[:2]))):
            raise InferError(
                "%s%s must be (B, S) matching %s%s"
                % (sslot, render_shape(s), cslot, render_shape(c)))
    return {"Out": VarInfo(qs, q.dtype)}
