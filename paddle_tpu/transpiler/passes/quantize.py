"""Level-3 quantize pass: rewrite fc/conv ops onto the int8 kernels.

Runs on the PR-11 pass manager AFTER the fusion passes (so every
``layers.fc`` chain arrives as one ``fused_fc`` and quantizes with its
bias/activation epilogue intact) and BEFORE bucketize (the stamped
program still proves row-wise through ``quantized_matmul``). The pass
only fires when the PassContext carries a :class:`CalibrationTable`
(``optimize_program(..., calib=table)`` /
``save_inference_model(quantize=table)``) — ``PADDLE_TPU_OPT=3``
without a table runs the level-2 pipeline and leaves precision alone.

Per rewritten op:

- the float weight quantizes symmetrically per OUTPUT channel over its
  flattened contraction layout; the int8 tensor materializes as a fresh
  persistable param (``<w>.int8``) through ``device_owned_tree`` — raw
  numpy in donated state is the PR-10 heap-corruption lesson;
- the per-tensor activation scale (calibrated amax) and the per-channel
  weight scales ride as op ATTRS, so the program JSON is
  self-contained;
- the replacement is 1:1 in place (same Out name, same block position,
  ``__rng_idx__`` preserved), so keep-set and RNG contracts hold
  trivially;
- the float weight's declaration is dropped from the optimized CLONE
  when nothing else reads it — ``save_inference_model`` then exports
  int8 weights only (the original program and Scope keep the float
  values untouched).

Tolerance parity, not bit parity: quantization rounds by design
(``exact=False``); ``quant/parity.py`` gates the drift against float
serving (``tests/test_quant.py``, ``tests/test_quant_decode.py``).
"""
from __future__ import annotations

import numpy as np

from ... import observability as obs
from .manager import RNG_IDX_ATTR, register_pass

# attr marking ops this pass emitted (idempotence: a re-run must not
# try to re-quantize its own output)
_QUANT_ATTR = "__quantized__"


def _fresh(block, name: str) -> str:
    cand = name
    while block._find_var_recursive(cand) is not None:
        cand += "_"
    return cand


def _owned(arrays):
    from ...checkpoint.manager import device_owned_tree

    return device_owned_tree(arrays)


def _materialize_int8(gb, scope, w_name: str, wq: np.ndarray) -> str:
    """Declare + store the int8 twin of ``w_name``; returns its name."""
    new_name = _fresh(gb, w_name + ".int8")
    gb.create_var(name=new_name, shape=tuple(wq.shape), dtype="int8",
                  persistable=True)
    scope.set_var(new_name, _owned({"w": wq})["w"])
    return new_name


def _quantize_fc(ctx, gb, op, idx, calib, scope) -> bool:
    """mul / matmul / fused_fc -> quantized_matmul (False = skipped)."""
    import math as _math

    from ...framework.core import Operator
    from ...ops.quant import quantize_weight_2d

    if op.type == "matmul" and (
            op.attr("transpose_X", False) or op.attr("transpose_Y", False)
            or op.attr("alpha", 1.0) != 1.0):
        return False
    if len(op.input("X")) != 1 or len(op.input("Y")) != 1 \
            or len(op.output("Out")) != 1:
        return False
    x_name, w_name = op.input("X")[0], op.input("Y")[0]
    wvar = gb._find_var_recursive(w_name)
    if wvar is None or not wvar.persistable:
        return False
    wval = scope.find_var(w_name)
    if wval is None:
        return False
    x_scale = calib.scale_for(x_name)
    if x_scale is None:
        return False
    w = np.asarray(wval)
    if w.dtype.kind != "f":
        return False  # already integer (or exotic) — nothing to gain
    matmul_kind = (op.type == "matmul"
                   or (op.type == "fused_fc"
                       and op.attr("kind", "mul") == "matmul"))
    if matmul_kind:
        # the fused flatten below equals jnp.matmul only for plain 2-D
        # operands; batched (rank>2) matmuls — bare OR fused into a
        # fused_fc(kind="matmul") — keep their float kernel
        xs = ctx.inference.shape(x_name)
        if w.ndim != 2 or xs is None or len(xs) != 2:
            return False
        xnc, ync = 1, 1
    else:
        xnc = int(op.attr("x_num_col_dims", 1))
        ync = int(op.attr("y_num_col_dims", 1))
        if xnc < 1 or ync < 1 or ync > w.ndim:
            return False
    # one int8 twin per (weight, flatten) even when several ops share
    # the weight (tied projections): re-materializing per reader would
    # ship N identical int8 copies
    memo_key = (w_name, ync)
    hit = ctx._int8_weights.get(memo_key)
    if hit is not None:
        wq_name, y_scale = hit
    else:
        w2 = w.reshape((_math.prod(w.shape[:ync]), -1))
        wq2, y_scale = quantize_weight_2d(w2)
        # calibrated weight amax (if present) must agree with the
        # stored value's layout; the scope value is authoritative
        wq = wq2.reshape(w.shape)
        wq_name = _materialize_int8(gb, scope, w_name, wq)
        ctx._int8_weights[memo_key] = (wq_name, y_scale)
    attrs = {
        "kind": "matmul" if matmul_kind else "mul",
        "x_num_col_dims": xnc,
        "y_num_col_dims": ync,
        "x_scale": float(x_scale),
        "y_scale": np.asarray(y_scale, np.float32),
        "axis": op.attr("axis", -1),
        "act": op.attr("act", "") if op.type == "fused_fc" else "",
        _QUANT_ATTR: True,
    }
    if RNG_IDX_ATTR in op.attrs:
        attrs[RNG_IDX_ATTR] = op.attrs[RNG_IDX_ATTR]
    inputs = {"X": op.input("X"), "Y": [wq_name]}
    if op.type == "fused_fc" and op.input("Bias"):
        inputs["Bias"] = op.input("Bias")
    new_op = Operator(gb, type="quantized_matmul", inputs=inputs,
                      outputs={"Out": op.output("Out")}, attrs=attrs)
    gb.ops[idx] = new_op
    gb._note_writes(new_op)
    return True


def _quantize_conv(ctx, gb, op, idx, calib, scope) -> bool:
    """conv2d -> quantized_conv2d (False = skipped)."""
    from ...framework.core import Operator
    from ...ops.quant import quantize_conv_filter

    if len(op.input("Input")) != 1 or len(op.input("Filter")) != 1 \
            or len(op.output("Output")) != 1:
        return False
    x_name, w_name = op.input("Input")[0], op.input("Filter")[0]
    wvar = gb._find_var_recursive(w_name)
    if wvar is None or not wvar.persistable:
        return False  # derived in-graph filter (the conv_bn_fold lesson)
    wval = scope.find_var(w_name)
    if wval is None:
        return False
    x_scale = calib.scale_for(x_name)
    if x_scale is None:
        return False
    w = np.asarray(wval)
    if w.dtype.kind != "f" or w.ndim != 4:
        return False
    wq, w_scale = quantize_conv_filter(w)
    wq_name = _materialize_int8(gb, scope, w_name, wq)
    attrs = {
        "strides": op.attr("strides", [1, 1]),
        "paddings": op.attr("paddings", [0, 0]),
        "dilations": op.attr("dilations", [1, 1]),
        "groups": op.attr("groups", 1),
        "data_format": op.attr("data_format", "NCHW"),
        "x_scale": float(x_scale),
        "w_scale": np.asarray(w_scale, np.float32),
        _QUANT_ATTR: True,
    }
    if RNG_IDX_ATTR in op.attrs:
        attrs[RNG_IDX_ATTR] = op.attrs[RNG_IDX_ATTR]
    new_op = Operator(gb, type="quantized_conv2d",
                      inputs={"Input": op.input("Input"),
                              "Filter": [wq_name]},
                      outputs={"Output": op.output("Output")}, attrs=attrs)
    gb.ops[idx] = new_op
    gb._note_writes(new_op)
    return True


@register_pass("quantize", level=3, exact=False, needs_scope=True)
def quantize(ctx) -> int:
    """Rewrite calibrated fc/conv ops in the global block onto the int8
    kernels; stamps ``program._quantized`` so the serving tier is
    visible (Engine.meta / aot_cache_ls) and the stamp rides the
    program JSON."""
    calib = getattr(ctx, "calib", None)
    if calib is None:
        return 0
    program = ctx.program
    if getattr(program, "_amp", False):
        # AMP rewrites precision at trace time; stacking int8 on top
        # would double-round unpredictably
        return 0
    gb = program.global_block()
    scope = ctx.scope
    # (weight name, flatten) -> (int8 name, scales): shared weights
    # materialize once per optimization run
    ctx._int8_weights = getattr(ctx, "_int8_weights", {})
    replaced_weights = []
    n = 0
    for idx, op in enumerate(list(gb.ops)):
        if op.attr(_QUANT_ATTR, False):
            continue
        if op.type in ("mul", "matmul", "fused_fc"):
            w_name = op.input("Y")[0] if op.input("Y") else None
            done = _quantize_fc(ctx, gb, op, idx, calib, scope)
        elif op.type == "conv2d":
            w_name = op.input("Filter")[0] if op.input("Filter") else None
            done = _quantize_conv(ctx, gb, op, idx, calib, scope)
        else:
            continue
        if done:
            n += 1
            replaced_weights.append(w_name)
            obs.QUANT_OPS.inc(op=op.type)
    if not n:
        return 0
    # drop float-weight declarations nothing reads anymore — the export
    # then ships int8 params only (the Scope keeps the float values; the
    # RAW program still uses them)
    still_read = set(ctx.keep_names())
    for block in program.blocks:
        for op in block.ops:
            still_read.update(op.input_arg_names)
    for w_name in replaced_weights:
        if w_name and w_name not in still_read:
            for block in program.blocks:
                if w_name in block.vars:
                    del block.vars[w_name]
    stamp = dict(getattr(program, "_quantized", None) or {})
    stamp["ops"] = int(stamp.get("ops", 0)) + n
    stamp["version"] = 1
    program._quantized = stamp
    program._bump()
    ctx.count("quantize", "ops_quantized", n)
    return n
