"""Build-time feed bucketization: turn feed-signature churn into pow2
buckets.

The recompile-risk lint (PR-6) flags dynamic-batch feeds because every
distinct batch size compiles — and AOT-caches — its own executable; the
PR-2 serving path already answers that at runtime by padding batches to
power-of-two buckets. This pass moves the answer to BUILD time for any
program: it proves, with the inference lattice as the legality oracle,
that every computation downstream of the dynamic feeds is *row-wise*
(output row i depends only on input row i — padding extra rows cannot
perturb real rows), then stamps the program with bucketization metadata
(``program._bucketize``, serialized in the program JSON). The
Executor/Predictor honor the stamp at the feed boundary: feeds pad with
zero rows up to the next power of two before signature derivation, and
batch-carrying fetches slice back to the real row count after execution
— so a workload feeding batches 3,5,6,7 compiles ONE bucket-8
executable instead of four.

Parity: real rows are MATHEMATICALLY unchanged (row-wise is proved, not
assumed), and on small graphs bitwise-identical too — but XLA's CPU
GEMM may pick a different reduction order for a different batch
dimension, so large matmul chains can drift by reduction-order ulps
(measured ≤3e-6 max-abs on the 200-wide mnist MLP, batch 9-in-16;
tests/test_passes.py holds the bound). That is
the same numerical class as running the identical rows at a different
batch size by hand; the parity gates compare padded-path outputs at
ulp tolerance and everything else exactly.

XLA's static-shape contract is why the pad/slice pair lives at the
executor boundary rather than as in-graph ops: an in-graph slice back
to the true row count would need a dynamic output shape, which TPU
compilation rejects. The stamp IS the in-graph artifact — it rides the
serialized program, so an exported model buckets wherever it is served.

Programs that mix rows anywhere on the dynamic-feed cone (batch-mean
losses, training-mode batch_norm, any ``autodiff``) are left unstamped,
with a note saying which op broke legality.
"""
from __future__ import annotations

from typing import Optional, Set

from .manager import register_pass

# elementwise / per-row op families (never mix rows along axis 0)
_ELEMWISE_BINARY = {
    "elementwise_add", "elementwise_sub", "elementwise_mul",
    "elementwise_div", "elementwise_max", "elementwise_min",
    "elementwise_pow", "elementwise_mod", "fused_elemwise_activation",
}
_ELEMWISE_UNARY = {
    "sigmoid", "logsigmoid", "exp", "relu", "tanh", "tanh_shrink",
    "sqrt", "abs", "ceil", "floor", "cos", "sin", "round", "reciprocal",
    "square", "softplus", "softsign", "log", "sign", "relu6",
    "leaky_relu", "elu", "brelu", "soft_relu", "pow", "stanh",
    "hard_sigmoid", "swish", "thresholded_relu", "hard_shrink",
    "softshrink", "scale", "clip", "label_smooth", "assign", "cast",
    "fill_zeros_like", "logical_not", "isfinite",
}
# per-row losses: every output row is a function of the matching input row
_ROW_LOSSES = {
    "cross_entropy", "softmax_with_cross_entropy",
    "sigmoid_cross_entropy_with_logits", "square_error_cost", "log_loss",
    "smooth_l1_loss", "huber_loss", "hinge_loss",
}


def next_pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def _rank(ctx, name: str) -> Optional[int]:
    s = ctx.inference.shape(name)
    return None if s is None else len(s)


def _binary_pad_safe(ctx, op, carrying: Set[str], x_name: str,
                     y_name: str, axis) -> bool:
    """A binary op stays well-formed when the CARRYING operand's axis 0
    grows by padding: either both operands carry (padded together, equal
    known ranks), or the non-carrying one provably never aligns with
    axis 0 — a strict-smaller-rank span placed at axis > 0, or an equal-
    rank operand with dim0 == 1. A static batch-sized operand (N, d)
    against a dynamic feed would shape-error at the padded size."""
    xc, yc = x_name in carrying, y_name in carrying
    xs = ctx.inference.shape(x_name)
    ys = ctx.inference.shape(y_name)
    if xc and yc:
        return (xs is not None and ys is not None
                and len(xs) == len(ys))
    if yc and not xc:
        return False  # Y's axis 0 maps into a span of X, not X's rows
    # X carries, Y is batch-free: Y must never span axis 0
    if xs is None or ys is None:
        return False
    if len(ys) < len(xs):
        a = axis if isinstance(axis, int) and axis != -1 \
            else len(xs) - len(ys)
        return a > 0
    return len(ys) == len(xs) and ys[0] == 1


def _carrying_outputs(ctx, op, carrying: Set[str]) -> Optional[Set[str]]:
    """Which outputs of ``op`` carry the feed batch axis (axis 0), given
    the carrying inputs — or None when the op may MIX rows (illegal to
    pad). Unknown facts degrade to None: the oracle must prove safety,
    never assume it."""
    t = op.type
    ins = set(op.input_arg_names)
    outs = set(op.output_arg_names)
    c_ins = ins & carrying

    if t in _ELEMWISE_BINARY:
        if not _binary_pad_safe(ctx, op, carrying, op.input("X")[0],
                                op.input("Y")[0], op.attr("axis", -1)):
            return None
        return outs
    if t in _ROW_LOSSES:
        # loss inputs are batch-aligned rows: a static-shaped label
        # against a padded prediction would shape-error
        return outs if all(n in carrying for n in ins) else None
    if t in _ELEMWISE_UNARY:
        return outs
    if t in ("softmax", "log_softmax"):
        r = _rank(ctx, op.input("X")[0])
        return outs if r is not None and r >= 2 else None
    if t == "dropout":
        # test mode is a deterministic passthrough; train mode draws a
        # batch-shaped mask whose bits depend on the padded shape
        return outs if op.attr("is_test", False) else None
    if t == "batch_norm":
        if not op.attr("is_test", False):
            return None
        # only Y is batch-shaped; the (C,)-shaped stat outputs must NOT
        # be marked carrying (a stamped stat fetch would get row-sliced)
        return set(op.output("Y"))
    if t == "layer_norm":
        return outs if int(op.attr("begin_norm_axis", 1)) >= 1 else None
    if t in ("mul", "fused_fc", "quantized_matmul"):
        # quantized_matmul is row-wise exactly like fused_fc: the
        # per-tensor activation scale is an attr (pad rows quantize to
        # zero codes, contributing nothing), the int8 weight/bias are
        # batch-free state
        if op.input("Y")[0] in carrying or (
                op.input("Bias") and op.input("Bias")[0] in carrying):
            return None
        if op.input("X")[0] not in carrying:
            return None
        if int(op.attr("x_num_col_dims", 1)) < 1:
            return None
        if t in ("fused_fc", "quantized_matmul") and op.input("Bias"):
            # bias span must not touch the (growing) batch axis
            out_s = ctx.inference.shape(op.output("Out")[0])
            b_s = ctx.inference.shape(op.input("Bias")[0])
            if out_s is None or b_s is None:
                return None
            axis = op.attr("axis", -1)
            if len(b_s) < len(out_s):
                a = axis if isinstance(axis, int) and axis != -1 \
                    else len(out_s) - len(b_s)
                if a <= 0:
                    return None
            elif not (len(b_s) == len(out_s) and b_s[0] == 1):
                return None
        if t in ("fused_fc", "quantized_matmul") \
                and op.attr("kind", "mul") == "matmul":
            # the fusion pass only emits non-transposed matmuls, where
            # axis 0 stays the row axis at any known rank
            if _rank(ctx, op.input("X")[0]) is None:
                return None
        return outs
    if t == "matmul":
        if op.input("Y")[0] in carrying or op.input("X")[0] not in carrying:
            return None
        r = _rank(ctx, op.input("X")[0])
        if r is None:
            return None
        if r == 2 and op.attr("transpose_X", False):
            return None  # transpose would move batch into the contraction
        return outs
    if t in ("lookup_table", "one_hot"):
        first = op.input("Ids" if t == "lookup_table" else "X")
        if t == "lookup_table" and op.input("W")[0] in carrying:
            return None
        return outs if first and first[0] in carrying else None
    if t == "concat":
        axis = op.attr("axis", 0)
        if not isinstance(axis, int) or axis == 0:
            return None
        if axis < 0:
            r = _rank(ctx, op.input("X")[0])
            if r is None or axis % r == 0:
                return None
        return outs if all(n in carrying for n in op.input("X")) else None
    if t in ("reduce_sum", "reduce_mean", "reduce_max", "reduce_min",
             "reduce_prod"):
        if op.attr("reduce_all", False):
            return None
        r = _rank(ctx, op.input("X")[0])
        if r is None:
            return None
        dims = op.attr("dim", [0])
        dims = dims if isinstance(dims, (list, tuple)) else [dims]
        if any((int(d) % r) == 0 for d in dims):
            return None
        return outs
    if t == "reshape":
        shape = op.attr("shape")
        if not shape or shape[0] not in (-1, 0):
            return None
        if any(int(d) <= 0 for d in shape[1:]):
            return None
        s_in = ctx.inference.shape(op.input("X")[0])
        if s_in is None or any(d is None for d in s_in[1:]):
            return None
        import math as _math

        if _math.prod(int(d) for d in shape[1:]) != _math.prod(
                int(d) for d in s_in[1:]):
            return None  # rows would regroup across the batch axis
        return outs
    if t == "transpose":
        perm = op.attr("axis") or op.attr("perm")
        return outs if perm and int(perm[0]) == 0 else None
    if t in ("unsqueeze", "squeeze"):
        axes = op.attr("axes") or []
        r = _rank(ctx, op.input("X")[0])
        if r is None or any((int(a) % (r + (1 if t == "unsqueeze" else 0)))
                            == 0 for a in axes):
            return None
        return outs
    if t == "stack":
        return (outs if int(op.attr("axis", 0)) > 0
                and all(n in carrying for n in op.input("X")) else None)
    if t == "split":
        axis = op.attr("axis", op.attr("dim", 0))
        return outs if isinstance(axis, int) and axis > 0 else None
    if t == "slice":
        axes = op.attr("axes") or []
        return None if any(int(a) == 0 for a in axes) else outs
    if t == "top_k":
        r = _rank(ctx, op.input("X")[0])
        return outs if r is not None and r >= 2 else None
    if t == "gather":
        # out rows follow the Index rows; X must be batch-free state
        if op.input("Index") and op.input("Index")[0] in carrying \
                and op.input("X")[0] not in carrying:
            return outs
        return None
    return None  # unknown op: cannot prove row independence


@register_pass("bucketize", level=2, exact=True)
def bucketize(ctx) -> int:
    """Stamp ``program._bucketize`` when legal (see module docstring).
    Returns 1 the first time the stamp lands, 0 when already stamped or
    illegal — re-running never restamps differently (idempotent)."""
    program = ctx.program
    gb = program.global_block()

    dyn_feeds = sorted(
        name for name, var in gb.vars.items()
        if var.is_data and tuple(var.shape or ())
        and var.shape[0] < 0
        and all(d >= 0 for d in var.shape[1:]))
    if not dyn_feeds:
        return 0
    if any(op.type == "autodiff" for b in program.blocks for op in b.ops):
        ctx.note("bucketize: program trains (autodiff present) — "
                 "gradients mix rows, not stamped")
        return 0
    if len(program.blocks) > 1:
        # control flow could smuggle a carrying var into a sub-block
        # where this straight-line analysis can't follow it
        carried_into_sub = set()
        for block in program.blocks[1:]:
            for op in block.ops:
                carried_into_sub.update(op.input_arg_names)
    else:
        carried_into_sub = set()

    carrying: Set[str] = set(dyn_feeds)
    for op in gb.ops:
        if op.type in ("feed", "fetch", "read"):
            continue
        ins = set(op.input_arg_names)
        if not (ins & carrying):
            continue
        outs = _carrying_outputs(ctx, op, carrying)
        if outs is None:
            ctx.note("bucketize: op %r mixes rows (or cannot be proven "
                     "row-wise) — not stamped" % op.type)
            return 0
        for name in op.output_arg_names:
            var = gb._find_var_recursive(name)
            if var is not None and var.persistable:
                ctx.note("bucketize: %r writes persistable %r from a "
                         "batch-carrying input — not stamped"
                         % (op.type, name))
                return 0
        carrying |= outs
    if carrying & carried_into_sub:
        ctx.note("bucketize: batch-carrying var read by a sub-block — "
                 "not stamped")
        return 0

    stamp = {
        "feeds": dyn_feeds,
        "fetches": sorted(n for n in ctx.fetch_names if n in carrying),
    }
    if getattr(program, "_bucketize", None) == stamp:
        return 0
    program._bucketize = stamp
    program._bump()
    ctx.count("bucketize", "feeds_bucketized", len(dyn_feeds))
    return 1
