"""Lint rules over the Program IR + inference facts.

Each lint is a function ``(LintContext) -> None`` appending Diagnostics to
the shared report, registered with ``@register_lint``. The def-use rules
(``use-before-def`` / ``undeclared`` / ``write-once``) are the former
``framework/verifier.py`` checks folded in — message text is kept
byte-compatible because executor tests and callers match on it.

TPU-specific rules encode what the runtime actually punishes:

- ``tpu-dynamic-shape``: XLA compiles one executable per concrete shape;
  a feed with unknown dims beyond the batch axis means unbounded
  recompilation and defeats the PR-2 bucket pre-warm.
- ``recompile-risk``: feeds whose dynamic batch axis is not covered by
  bucketing / AOT cache keys (PR-2 / PR-5) — each distinct batch size is
  a separate compile + cache entry.
- ``dead-op`` / ``dead-var``: ops/vars that can never influence a fetch
  target or persistable state; dead ops still cost trace time and HLO
  size even when XLA eventually DCEs them — and usually indicate a bug.
- ``op-not-registered``: the op would raise NotImplementedError at trace
  time; caught pre-trace with a did-you-mean hint.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Set

from .diagnostics import Report, did_you_mean
from .infer import PSEUDO_OPS, ProgramInference, render_shape

__all__ = ["register_lint", "run_lints", "LINTS", "LintContext",
           "DEF_USE_LINTS", "backward_liveness"]

# ops that legitimately rewrite an existing var (loop counters, tensor
# arrays, in-place scatter updates, accumulator-style sums). Audited
# against the registered op set (tests/test_analysis.py pins that every
# entry names a real registered op): the stale "sums" entry is gone (the
# `sums` LAYER emits a `sum` op; no "sums" op type ever existed) and
# "assign_value" joined — layers.assign(np.ndarray, output=existing_var)
# emits it into caller-provided outputs exactly like "assign". Optimizer
# ops rewrite only persistable state, which the check already exempts.
REWRITE_OK = {
    "increment", "write_to_array", "assign", "assign_value", "scatter",
    "fill_constant", "sum",
}

# op types the tracer handles itself (never need a kernel) — one shared
# set with the inference driver's coverage accounting
TRACER_OPS = PSEUDO_OPS

# ops kept alive regardless of fetch reachability: side effects, state
# threading, control flow (sub-block ops are handled conservatively)
SIDE_EFFECT_OPS = {"print", "while", "conditional_block", "switch",
                   "static_rnn", "dynamic_rnn", "beam_search",
                   "write_to_array"}

LINTS: Dict[str, Callable] = {}


def register_lint(name: str):
    def deco(fn):
        if name in LINTS:
            raise ValueError("duplicate lint %r" % name)
        LINTS[name] = fn
        fn.lint_name = name
        return fn

    return deco


class LintContext:
    def __init__(self, program, report: Report, feed_names=(),
                 fetch_names=(),
                 inference: Optional[ProgramInference] = None):
        self.program = program
        self.report = report
        self.feed_names = set(feed_names)
        self.fetch_names = list(fetch_names)
        self.inference = inference  # None when running def-use only


def run_lints(ctx: LintContext, only: Optional[List[str]] = None):
    for name, fn in LINTS.items():
        if only is not None and name not in only:
            continue
        fn(ctx)
    return ctx.report


# -- def-use rules (former framework/verifier.py) -------------------------

DEF_USE_LINTS = ["def-use"]


@register_lint("def-use")
def lint_def_use(ctx: LintContext):
    """use-before-def / undeclared inputs / write-once violations.
    Message text matches the legacy verifier exactly (the verify_program
    shim and executor warnings re-render these)."""
    program = ctx.program
    gb = program.global_block()
    defined = {name for name, var in gb.vars.items() if var.persistable}
    _def_use_block(gb, defined, ctx, is_sub=False)


def _def_use_block(block, defined: Set[str], ctx: LintContext,
                   is_sub: bool):
    report = ctx.report
    feed_names = ctx.feed_names
    local_defined = set(defined)
    written_by = {}
    for op_idx, op in enumerate(block.ops):
        if op.type in ("feed", "read"):
            # outputs are bound host-side (executor feeds / reader
            # pipeline injection)
            for name in op.output_arg_names:
                local_defined.add(name)
            continue
        for name in op.input_arg_names:
            if name in local_defined or name in feed_names:
                continue
            var = block._find_var_recursive(name)
            if var is None:
                report.add(
                    "error", "undeclared",
                    "block %d op %d (%s): input %r is not declared "
                    "anywhere" % (block.idx, op_idx, op.type, name),
                    block_idx=block.idx, op_idx=op_idx, op_type=op.type,
                    var=name,
                    hint="declare it with block.create_var / layers.data, "
                         "or fix the op's input name")
            elif not var.persistable and name not in written_by \
                    and not is_sub:
                # sub-blocks get loop carries / step inputs injected by
                # the parent control-flow op at trace time, so
                # use-before-def is only decidable at the top level
                report.add(
                    "error", "use-before-def",
                    "block %d op %d (%s): input %r is read before any op "
                    "defines it (use-before-def)"
                    % (block.idx, op_idx, op.type, name),
                    block_idx=block.idx, op_idx=op_idx, op_type=op.type,
                    var=name,
                    hint="feed it, mark it persistable, or reorder the "
                         "producing op before this one")
        sub_idx = op.attr("sub_block")
        if sub_idx is not None:
            sub = block.program.blocks[int(sub_idx)]
            _def_use_block(sub, local_defined | set(written_by), ctx,
                           is_sub=True)
        for name in op.output_arg_names:
            var = block._find_var_recursive(name)
            persistable = var is not None and var.persistable
            if (name in written_by and not persistable
                    and op.type not in REWRITE_OK
                    and written_by[name][1] not in REWRITE_OK
                    # control-flow ops legitimately rewrite their loop
                    # carries / condition vars
                    and sub_idx is None):
                report.add(
                    "warning", "write-once",
                    "block %d op %d (%s): output %r was already written "
                    "by op %d (%s) — write-once violation (would be a "
                    "race in a parallel executor)"
                    % (block.idx, op_idx, op.type, name,
                       written_by[name][0], written_by[name][1]),
                    block_idx=block.idx, op_idx=op_idx, op_type=op.type,
                    var=name,
                    hint="write to a fresh variable, or use an op in the "
                         "rewrite-ok set (assign/increment/...)")
            written_by[name] = (op_idx, op.type)
            local_defined.add(name)


# -- registry coverage ----------------------------------------------------


@register_lint("op-registered")
def lint_op_registered(ctx: LintContext):
    """Every op must have a TPU kernel, or tracing dies with
    NotImplementedError mid-lower; catch it pre-trace, with suggestions."""
    from ..ops.registry import KERNELS

    for block in ctx.program.blocks:
        for op_idx, op in enumerate(block.ops):
            if op.type in TRACER_OPS or op.type in KERNELS:
                continue
            ctx.report.add(
                "error", "op-not-registered",
                "no TPU kernel registered for op %r%s"
                % (op.type, did_you_mean(op.type, KERNELS)),
                block_idx=block.idx, op_idx=op_idx, op_type=op.type)


# -- TPU static-shape rules -----------------------------------------------


@register_lint("tpu-static-shape")
def lint_tpu_static_shape(ctx: LintContext):
    """Unknown dims OUTSIDE the batch axis are TPU-fatal: XLA requires
    static shapes, so the only tolerable unknown is the leading batch dim
    (handled by PR-2's bucket padding). Checked on data vars — the
    entry points where dynamism comes from."""
    for block in ctx.program.blocks:
        for name, var in block.vars.items():
            if not var.is_data:
                continue
            shape = tuple(var.shape or ())
            bad = [i for i, d in enumerate(shape) if i > 0 and d < 0]
            if bad:
                ctx.report.add(
                    "warning", "tpu-dynamic-shape",
                    "data var %r declares unknown dims at axes %s of %s — "
                    "only the batch axis (0) may be dynamic on TPU; every "
                    "distinct concrete shape compiles a separate "
                    "executable" % (name, bad, list(shape)),
                    block_idx=block.idx, var=name,
                    hint="declare static sizes (pad/bucket the data), or "
                         "move the dynamic dim to axis 0")


@register_lint("recompile-risk")
def lint_recompile_risk(ctx: LintContext):
    """Feed-signature drift: the compile caches (executor memory cache,
    PR-5 AOT disk cache) key on the exact feed signature, and the PR-2
    serving path pre-warms power-of-two batch buckets. A feed var with a
    dynamic batch axis is fine IF batches are bucketed; flag it as info
    so AOT-cache miss hunts (docs/performance.md, "Cold-start &
    compile caching") can start here. More
    than one dynamic axis multiplies signatures and is a warning."""
    gb = ctx.program.global_block()
    for name, var in gb.vars.items():
        if not var.is_data:
            continue
        shape = tuple(var.shape or ())
        dyn = [i for i, d in enumerate(shape) if d < 0]
        if len(dyn) > 1:
            ctx.report.add(
                "warning", "recompile-risk",
                "feed %r has %d dynamic axes %s of %s: every distinct "
                "combination of their sizes is a separate compile-cache /"
                " AOT-cache entry" % (name, len(dyn), dyn, list(shape)),
                block_idx=gb.idx, var=name,
                hint="pin all but the batch axis; bucket the batch axis "
                     "(serving already pads to power-of-two buckets)")
        elif dyn == [0]:
            ctx.report.add(
                "info", "recompile-risk",
                "feed %r has a dynamic batch axis: each distinct batch "
                "size compiles (and caches) its own executable — keep "
                "batch sizes bucketed" % (name,),
                block_idx=gb.idx, var=name,
                hint="fixed batch + partial-batch padding, or rely on "
                     "the serving buckets / run_loop stable windows")


# -- dead-code analysis ---------------------------------------------------


def backward_liveness(program, fetch_names):
    """Backward liveness from fetch targets + persistable state over the
    straight-line global block — the shared core of the ``dead-code``
    lint AND the optimizing transpiler's dead-op elimination pass
    (transpiler/passes/dce.py), so the finding and the transform can
    never disagree about what is dead.

    Returns ``(anchored, dead_ops, live)``: ``anchored`` is False when
    the program has no liveness roots at all (no fetch names, no fetch
    ops, nothing persistable written — nothing can be judged dead);
    ``dead_ops`` is ``[(op_idx, op), ...]`` in reverse block order.

    Correct through ``autodiff`` replay semantics: the autodiff pseudo-op
    is a root whose loss/params (named in attrs, not input slots) are
    live, so everything the vjp replay transitively reads stays; an op
    judged dead is outside every loss's forward cone AND unreachable
    from any fetch/state write, so dropping it from the replay prefix
    cannot change any gradient."""
    gb = program.global_block()
    live: Set[str] = set(fetch_names)
    dead_ops: List[tuple] = []

    def op_is_root(op, block) -> bool:
        if op.type in SIDE_EFFECT_OPS or op.type == "fetch" \
                or op.attr("sub_block") is not None:
            return True
        for name in op.output_arg_names:
            var = block._find_var_recursive(name)
            if var is not None and var.persistable:
                return True
        return False

    anchored = bool(live) or any(
        op_is_root(op, b) for b in program.blocks for op in b.ops)
    if not anchored:
        return False, [], live

    # anything read inside a sub-block (closure over outer vars) or named
    # as a loop carry is live from the parent's perspective
    for block in program.blocks[1:]:
        for op in block.ops:
            live.update(op.input_arg_names)
    for op in gb.ops:
        if op.attr("sub_block") is not None:
            live.update(op.attr("carried_names") or ())

    # reverse pass over the straight-line global block; sub-block ops are
    # roots (conservative), their inputs all live
    for op_idx in range(len(gb.ops) - 1, -1, -1):
        op = gb.ops[op_idx]
        if op.type in ("feed", "read"):
            continue  # executor plumbing: neither root nor reportable
        if op_is_root(op, gb) or any(n in live for n in
                                     op.output_arg_names):
            live.update(op.input_arg_names)
            # autodiff replays the whole forward prefix: everything it
            # reads transitively is live through the vjp, and its attrs
            # name the loss/params rather than input slots
            if op.type == "autodiff":
                live.add(op.attr("loss_name"))
                live.update(op.attr("param_names") or ())
        else:
            dead_ops.append((op_idx, op))
    return True, dead_ops, live


@register_lint("dead-code")
def lint_dead_code(ctx: LintContext):
    """Backward liveness from fetch targets + persistable state. Without
    fetch targets (raw serialized program) every persistable write (and
    every `fetch` op's input) is the root set. A program with NO roots at
    all — no fetch names, no fetch ops, nothing persistable written — has
    nothing to anchor liveness on, so the lint stays silent rather than
    calling a whole valid forward graph dead."""
    program = ctx.program
    gb = program.global_block()  # the dead-VAR sweep below scans it
    anchored, dead_ops, _live = backward_liveness(program,
                                                  ctx.fetch_names)
    if not anchored:
        return

    for op_idx, op in dead_ops:
        outs = op.output_arg_names
        ctx.report.add(
            "warning", "dead-op",
            "computes %s but nothing reads it: not reachable from any "
            "fetch target or persistable state" % (outs,),
            block_idx=0, op_idx=op_idx, op_type=op.type,
            hint="fetch its output, or delete the dead layer call")

    # dead VARS: written by a live op but never consumed anywhere —
    # normal for multi-output ops (e.g. the Softmax side output), so
    # severity is only a note
    consumed: Set[str] = set(ctx.fetch_names)
    for block in program.blocks:
        for op in block.ops:
            consumed.update(op.input_arg_names)
            if op.type == "autodiff":
                consumed.add(op.attr("loss_name"))
                consumed.update(op.attr("param_names") or ())
    dead_op_idx = {id(op) for _i, op in dead_ops}
    for op_idx, op in enumerate(gb.ops):
        if id(op) in dead_op_idx or op.type in TRACER_OPS:
            continue
        for name in op.output_arg_names:
            var = gb._find_var_recursive(name)
            if var is None or var.persistable:
                continue
            if name not in consumed:
                ctx.report.add(
                    "note", "dead-var",
                    "output %r is never consumed" % (name,),
                    block_idx=0, op_idx=op_idx, op_type=op.type, var=name)


# -- analyzer self-check --------------------------------------------------


@register_lint("declared-drift")
def lint_declared_drift(ctx: LintContext):
    """Layer-declared shapes vs analyzer-inferred shapes. A disagreement
    means either the layer's shape math or the infer rule is wrong —
    reported as a note (analyzer self-check), and pinned to zero on the
    bundled example programs by tests."""
    inf = ctx.inference
    if inf is None:
        return
    for block in ctx.program.blocks:
        for name, var in block.vars.items():
            if var.is_data or var.persistable or not var.shape:
                continue
            declared = tuple(var.shape)
            got = inf.shape(name, block.idx)
            if got is None or len(got) != len(declared):
                continue  # unknown rank: nothing to compare
            for d_dim, g_dim in zip(declared, got):
                if d_dim >= 0 and g_dim is not None and d_dim != g_dim:
                    ctx.report.add(
                        "note", "declared-drift",
                        "var %r: declared shape %s but analyzer infers %s"
                        % (name, list(declared), render_shape(got)),
                        block_idx=block.idx, var=name)
                    break
