"""Program-level pipeline parallelism: slice a fluid ``Program`` into
stages and run it under ``pipeline_apply`` — no hand-written stage_fn.

The reference distributes by rewriting the program graph
(reference: python/paddle/fluid/transpiler/distribute_transpiler.py:159
``transpile()`` splits params/ops across workers and wires send/recv).
The TPU-native equivalent keeps the Program UNCHANGED and derives the
partitioning from its structure: models built as ``for i in range(L):
layer(x)`` produce a *periodic* op sequence, and that periodicity IS the
stage cut. ``plan_pipeline`` detects the maximal periodic region by op
fingerprinting (type + attrs + declared shapes), validates the
stage-homogeneity conditions pipelining needs (a single equal-shape
carry between repeats, identical per-repeat parameter structure), and
``build_pipeline_step_fn`` assembles the training step:

    prologue (per microbatch, lax.scan)        e.g. embeddings
      → pipeline_apply over the repeats        L layers / S stages
      → epilogue (per microbatch, lax.scan)    head + loss
    all inside jax.vjp                         reverse pipeline for free
      → optimizer ops traced as usual          reads the vjp's grads

Contract (mirrors the reference's pipeline semantics, where the program
describes ONE microbatch): the Program is built with the MICRO-batch
size; feeds carry ``num_microbatches ×`` that in dim 0. The loss is the
mean of per-microbatch losses == the full-batch loss for mean-reduced
objectives. Activations internal to the pipelined region cannot be
fetched (error at compile); prologue/epilogue vars fetch as
microbatch-concatenated arrays.

Use via ``BuildStrategy``::

    bs = BuildStrategy()
    bs.pipeline_stages = 4
    bs.pipeline_microbatches = 8
    pe = ParallelExecutor(loss_name=..., build_strategy=bs,
                          mesh=make_mesh([2, 4], ("dp", "pp")))

or plan explicitly with ``PipelineTranspiler`` (transpiler package).
"""
from __future__ import annotations

import hashlib
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh

from ..framework.core import Program, grad_var_name
from ..framework.trace import RngStream, TraceError, trace_op
from .pipeline import stack_stage_params

__all__ = ["plan_pipeline", "build_pipeline_step_fn", "PipelinePlan",
           "PipelineError"]


class PipelineError(ValueError):
    """The Program cannot be pipelined; the message says why."""


class PipelinePlan:
    """Where the stage cut sits in the forward op sequence.

    ops are (Operator, original_op_index) pairs (index keys the RNG
    stream exactly like sequential tracing). ``template`` is one repeat's
    op sequence used as the canonical stage body; ``param_map[r]`` maps
    the template's parameter names to repeat ``r``'s actual names.
    """

    def __init__(self, prologue, template, epilogue, repeats, num_stages,
                 param_map, carry_in_names, carry_tpl_in, carry_tpl_out,
                 const_names, region_internal, first_ad, block):
        self.prologue = prologue      # [(op, idx)]
        self.template = template      # [(op, idx)] — canonical repeat
        self.epilogue = epilogue      # [(op, idx)]
        self.repeats = repeats        # R
        self.num_stages = num_stages  # S; K = R // S repeats per stage
        self.param_map = param_map    # [r] -> {template name -> actual}
        self.carry_in_names = carry_in_names  # [r] -> carry-in var name
        self.carry_tpl_in = carry_tpl_in      # template's carry-in name
        self.carry_tpl_out = carry_tpl_out    # template's carry-out name
        self.const_names = const_names        # stage-invariant side inputs
        self.region_internal = region_internal  # names produced in region
        self.first_ad = first_ad
        self.block = block

    @property
    def repeats_per_stage(self) -> int:
        return self.repeats // self.num_stages

    def describe(self) -> str:
        return ("pipeline plan: %d prologue ops | %d repeats x %d ops "
                "(%d stages x %d repeats) | %d epilogue ops; carry %r"
                % (len(self.prologue), self.repeats, len(self.template),
                   self.num_stages, self.repeats_per_stage,
                   len(self.epilogue), self.carry_tpl_in))


# ---------------------------------------------------------------------------
# planning: find the periodic region and validate homogeneity
# ---------------------------------------------------------------------------

def _var_shape(block, name):
    var = block._find_var_recursive(name)
    shape = getattr(var, "shape", None)
    return tuple(shape) if shape else None


def _fingerprint(op, block):
    """Structural identity of an op, blind to variable NAMES: type, attrs
    (arrays by content hash), per-slot arity and declared shapes."""
    attrs = []
    for k in sorted(op.attrs):
        v = op.attrs[k]
        if isinstance(v, np.ndarray):
            attrs.append((k, "ndarray", v.shape, str(v.dtype),
                          hashlib.sha1(v.tobytes()).hexdigest()))
        else:
            attrs.append((k, repr(v)))
    ins = tuple(sorted(
        (slot, tuple(_var_shape(block, n) for n in names))
        for slot, names in op.inputs.items()))
    outs = tuple(sorted(
        (slot, tuple(_var_shape(block, n) for n in names))
        for slot, names in op.outputs.items()))
    return (op.type, tuple(attrs), ins, outs)


def _find_periodic_region(fps) -> Optional[Tuple[int, int, int]]:
    """Longest (start, period, match_run) with fps[i] == fps[i+p] for all
    i in [start, start+match_run), maximizing covered ops (ties: smaller
    period). ``match_run // p + 1`` repeats fit at ``start``; shifted
    starts inside the run trade repeats for alignment (see
    plan_pipeline)."""
    n = len(fps)
    hashes = [hash(f) for f in fps]
    best = None  # (covered, -period, start, period, run)
    for p in range(1, n // 2 + 1):
        i = 0
        while i < n - p:
            if hashes[i] != hashes[i + p] or fps[i] != fps[i + p]:
                i += 1
                continue
            a = i
            while i < n - p and hashes[i] == hashes[i + p] \
                    and fps[i] == fps[i + p]:
                i += 1
            run = i - a                  # matches in [a, a+run)
            reps = run // p + 1
            if reps >= 2:
                cand = (reps * p, -p, a, p, run)
                if best is None or cand > best:
                    best = cand
            i += 1
    if best is None:
        return None
    _, _, start, period, run = best
    return start, period, run


def _external_uses(ops, block):
    """For one repeat's op list: produced names, and the ordered external
    reads as [(position_key, name)] where position_key = (op_offset, slot,
    idx) — the structural location a name is consumed at."""
    produced = set()
    ext = []
    for off, (op, _idx) in enumerate(ops):
        for slot, names in sorted(op.inputs.items()):
            for j, name in enumerate(names):
                if name not in produced:
                    ext.append(((off, slot, j), name))
        for name in op.output_arg_names:
            produced.add(name)
    return produced, ext


def _produced_positions(ops):
    """name -> first (op_offset, slot, idx) where a repeat produces it."""
    pos = {}
    for off, (op, _idx) in enumerate(ops):
        for slot, names in sorted(op.outputs.items()):
            for j, name in enumerate(names):
                pos.setdefault(name, (off, slot, j))
    return pos


def _is_param_like(block, name):
    var = block._find_var_recursive(name)
    return var is not None and getattr(var, "persistable", False)


def plan_pipeline(program: Program, num_stages: int,
                  min_region_ops: int = 2) -> PipelinePlan:
    """Detect the stage cut. Raises PipelineError with a diagnosis when
    the program has no pipelineable structure."""
    if num_stages < 2:
        raise PipelineError("pipeline_stages must be >= 2")
    block = program.global_block()
    from ..framework.trace import _SKIP_OPS

    ad_idxs = [i for i, o in enumerate(block.ops) if o.type == "autodiff"]
    if len(ad_idxs) > 1:
        raise PipelineError(
            "pipeline parallelism supports a single minimize(); the "
            "program has %d autodiff sections" % len(ad_idxs))
    first_ad = ad_idxs[0] if ad_idxs else None

    fwd = [(op, i) for i, op in enumerate(block.ops)
           if op.type not in _SKIP_OPS
           and (first_ad is None or i < first_ad)]
    if not fwd:
        raise PipelineError("program has no forward ops to pipeline")

    fps = [_fingerprint(op, block) for op, _ in fwd]
    region = _find_periodic_region(fps)
    if region is None:
        raise PipelineError(
            "no repeated layer structure found: pipeline parallelism "
            "needs a model built as `for i in range(L): layer(x)` with "
            "structurally identical layers")
    start0, period, run = region

    # The matching run fixes the period but NOT the alignment: a prologue
    # op can fingerprint like an in-layer op (e.g. the embed's tok+pos
    # add vs a residual add at batch 1), extending the run one-or-more
    # ops early and putting the repeat boundary mid-layer. Try every
    # intra-period shift (largest repeat count first) until the boundary
    # analysis validates. When every shift fails, surface the error from
    # the candidate that validated FURTHEST — the correctly-aligned cut
    # fails late with an actionable message (e.g. batch-dependent side
    # input), while misaligned cuts fail early and generically.
    best_err, best_prog = None, -1
    for shift in range(period):
        start = start0 + shift
        reps = (run - shift) // period + 1
        if reps < 2:
            break
        if period * reps < min_region_ops:
            break
        progress = [0]
        try:
            return _analyze_region(block, fwd, start, period, reps,
                                   num_stages, first_ad, progress)
        except PipelineError as e:
            if progress[0] > best_prog:
                best_err, best_prog = e, progress[0]
    if best_err is None:
        raise PipelineError("periodic region too small to pipeline")
    raise best_err


def _analyze_region(block, fwd, start, period, reps, num_stages, first_ad,
                    progress):
    """Validate one candidate (start, period, reps) alignment and build
    the plan; raises PipelineError when the cut is not stage-homogeneous.
    ``progress[0]`` counts the validation phases passed, so the caller
    can pick the most-aligned candidate's diagnostic."""
    # stages must divide the repeats; surplus leading repeats fold into
    # the prologue (they run sequentially there — correct, just unsplit)
    extra = reps % num_stages
    start += extra * period
    reps -= extra
    if reps < num_stages:
        raise PipelineError(
            "found %d repeated layers but %d pipeline stages were "
            "requested; reduce pipeline_stages" % (reps + extra, num_stages))

    progress[0] = 1
    repeat_ops = [fwd[start + r * period: start + (r + 1) * period]
                  for r in range(reps)]
    prologue = fwd[:start]
    epilogue = fwd[start + reps * period:]
    template = repeat_ops[1 if reps > 1 else 0]

    # classify each repeat's external reads by structural position
    pro_produced = set()
    for op, _ in prologue:
        pro_produced.update(op.output_arg_names)
    produced_r, ext_r = zip(*[_external_uses(ops, block)
                              for ops in repeat_ops])
    ext_maps = [dict(e) for e in ext_r]
    positions = [pk for pk, _ in ext_r[0]]
    for r in range(1, reps):
        if [pk for pk, _ in ext_r[r]] != positions:
            raise PipelineError(
                "repeat %d consumes external variables at different "
                "structural positions than repeat 0 — layers are not "
                "homogeneous" % r)

    progress[0] = 2
    carry_pos, param_pos, const_pos = [], [], []
    for pk in positions:
        names = [ext_maps[r][pk] for r in range(reps)]
        if all(_is_param_like(block, n) for n in names):
            param_pos.append(pk)
        elif all(r == 0 or names[r] in produced_r[r - 1]
                 for r in range(reps)):
            carry_pos.append(pk)
        elif len(set(names)) == 1:
            const_pos.append(pk)
        else:
            raise PipelineError(
                "external input at position %s is neither a parameter, "
                "the layer carry, nor a shared constant (names per "
                "repeat: %s) — cannot pipeline" % (pk, sorted(set(names))))

    progress[0] = 3
    if not carry_pos:
        raise PipelineError(
            "repeats do not feed one another (no carry variable found)")
    carry_in_names = []
    for r in range(reps):
        names = {ext_maps[r][pk] for pk in carry_pos}
        if len(names) != 1:
            raise PipelineError(
                "repeat %d reads %d distinct carried variables %s; "
                "pipelining supports exactly one activation crossing "
                "stage boundaries" % (r, len(names), sorted(names)))
        carry_in_names.append(names.pop())

    progress[0] = 4
    # the carry's producing position (consistent across repeats) gives the
    # template's carry-out name
    out_pos_maps = [_produced_positions(ops) for ops in repeat_ops]
    prod_pos = {out_pos_maps[r][carry_in_names[r + 1]]
                for r in range(reps - 1)}
    if len(prod_pos) != 1:
        raise PipelineError(
            "the carried activation is produced at inconsistent "
            "positions across repeats")
    q = prod_pos.pop()
    tpl_r = 1 if reps > 1 else 0
    rev = {v: k for k, v in out_pos_maps[tpl_r].items()}
    carry_tpl_out = rev.get(q)
    if carry_tpl_out is None:
        raise PipelineError("internal: carry-out position missing in "
                            "template repeat")
    carry_tpl_in = carry_in_names[tpl_r]

    # carry shape must be constant (it rides ppermute between stages)
    shapes = {_var_shape(block, n) for n in carry_in_names}
    if len(shapes) != 1 or None in shapes:
        raise PipelineError(
            "carried activation has inconsistent/unknown declared shapes "
            "%s across repeats" % sorted(shapes, key=repr))

    progress[0] = 5
    # per-repeat parameter mapping, keyed by the template's names
    param_map = []
    for r in range(reps):
        m = {}
        for pk in param_pos:
            tpl_name = ext_maps[tpl_r][pk]
            actual = ext_maps[r][pk]
            if tpl_name in m and m[tpl_name] != actual:
                raise PipelineError(
                    "repeat %d ties parameters differently than the "
                    "template (template name %r maps to both %r and %r)"
                    % (r, tpl_name, m[tpl_name], actual))
            m[tpl_name] = actual
        param_map.append(m)

    progress[0] = 6
    # stage-invariant side inputs must not depend on feeds: they are
    # replicated to every stage, but each tick processes a DIFFERENT
    # microbatch, so batch-dependent values cannot be broadcast
    const_names = sorted({ext_maps[0][pk] for pk in const_pos})
    repeat_produced_all = set()
    for prods in produced_r:
        repeat_produced_all |= prods
    producers: Dict[str, List[str]] = {}
    for op, _ in prologue:
        for n in op.output_arg_names:
            producers.setdefault(n, []).extend(op.input_arg_names)

    def _reject_batch_dep(cname, n):
        raise PipelineError(
            "repeated layers read %r, which depends on data variable "
            "%r: batch-dependent side inputs cannot be broadcast to "
            "pipeline stages (restructure the model so per-batch "
            "tensors flow through the carry, e.g. causal fused "
            "attention instead of explicit masks)" % (cname, n))

    for cname in const_names:
        if cname in repeat_produced_all:
            raise PipelineError(
                "repeated layers share %r, produced inside the repeated "
                "region itself — not a broadcastable side input" % cname)
        if _is_param_like(block, cname):
            continue
        if cname not in producers:
            _reject_batch_dep(cname, cname)  # a feed, read by every layer
        frontier, seen = [cname], set()
        while frontier:
            n = frontier.pop()
            if n in seen:
                continue
            seen.add(n)
            if n in producers:
                frontier.extend(producers[n])
            elif not _is_param_like(block, n):
                _reject_batch_dep(cname, n)

    # the LAST repeat's carry-out feeds the epilogue; everything else
    # produced inside the region is unreachable outside it
    last_rev = {v: k for k, v in out_pos_maps[reps - 1].items()}
    carry_last_out = last_rev[q]
    region_internal = repeat_produced_all - {carry_last_out}

    plan = PipelinePlan(
        prologue, template, epilogue, reps, num_stages, param_map,
        carry_in_names, carry_tpl_in, carry_tpl_out, const_names,
        region_internal, first_ad, block)
    plan.carry_last_out = carry_last_out
    return plan


# ---------------------------------------------------------------------------
# step building
# ---------------------------------------------------------------------------

def _consumed_feed_names(ops, feed_names):
    used = set()
    for op, _ in ops:
        used.update(n for n in op.input_arg_names if n in feed_names)
    return sorted(used)


def build_pipeline_step_fn(program: Program, fetch_names, state_in,
                           state_out, mesh: Mesh, plan: PipelinePlan,
                           num_microbatches: int, pp_axis: str = "pp",
                           batch_axis: Optional[str] = None,
                           schedule: str = "gpipe"):
    """The pipelined analog of executor.build_step_fn: same
    ``(feeds, state, rng_key, step) -> (fetches, new_state)`` signature,
    so ParallelExecutor's jit/sharding/donation path is unchanged.

    The whole forward — prologue, pipelined tick loop, epilogue — runs
    inside ONE ``shard_map`` over the (dp?, pp) mesh, so every op sees
    exactly the Program's declared batch: the Program declares the
    PER-DEVICE microbatch, and feeds carry ``num_microbatches × dp ×``
    that in dim 0. Prologue/epilogue compute replicated across the pp
    axis (their cost is amortized by the pipelined middle); ``jax.vjp``
    through the tick loop yields the reverse pipeline, and the optimizer
    ops after ``minimize()`` trace sequentially on the vjp's gradients.
    Mid-region activations cannot be fetched.

    schedule:
      "gpipe"       — fill-drain: device s runs its K repeats back to
                      back each tick; M + S - 1 ticks; bubble fraction
                      (S-1)/(M+S-1).
      "interleaved" — circular: repeat r lives on device r mod S, one
                      repeat per tick, activations ring through all R
                      repeats (wrap-around buffered on device 0);
                      K*M + S - 1 ticks; bubble fraction
                      (S-1)/(K*M+S-1) — K× smaller. Needs M >= S
                      (the wrapped activation must arrive before its
                      next round starts).
    """
    from .pipeline import _pvary

    block = plan.block
    M = int(num_microbatches)
    S = plan.num_stages
    K = plan.repeats_per_stage
    if mesh.shape[pp_axis] != S:
        raise PipelineError(
            "mesh axis %r has %d devices but pipeline_stages=%d"
            % (pp_axis, mesh.shape[pp_axis], S))
    if schedule not in ("gpipe", "interleaved"):
        raise PipelineError(
            "unknown pipeline schedule %r (gpipe | interleaved)" % schedule)
    if schedule == "interleaved" and M < S:
        raise PipelineError(
            "the interleaved schedule needs num_microbatches >= "
            "pipeline_stages (%d < %d): a wrapped activation re-enters "
            "stage 0 only after all microbatches pass it" % (M, S))
    dp_n = mesh.shape[batch_axis] if batch_axis else 1
    carry_shape = _var_shape(block, plan.carry_tpl_in)
    B_decl = carry_shape[0]

    ad_op = block.ops[plan.first_ad] if plan.first_ad is not None else None
    loss_name = ad_op.attr("loss_name") if ad_op is not None else None
    param_names = list(ad_op.attr("param_names")) if ad_op is not None else []

    post_ops = []
    if plan.first_ad is not None:
        from ..framework.trace import _SKIP_OPS
        post_ops = [(op, i) for i, op in
                    enumerate(block.ops[plan.first_ad + 1:],
                              plan.first_ad + 1)
                    if op.type not in _SKIP_OPS and op.type != "autodiff"]

    # fail at compile time on anything that reads unreachable activations
    bad = [n for n in fetch_names if n in plan.region_internal]
    if bad:
        raise PipelineError(
            "fetch targets %s are internal to the pipelined region; only "
            "the loss and prologue/epilogue variables are fetchable under "
            "pipeline parallelism" % bad)
    for op, _i in post_ops:
        bad = [n for n in op.input_arg_names if n in plan.region_internal]
        if bad:
            raise PipelineError(
                "op %r after minimize() reads %s from inside the "
                "pipelined region" % (op.type, bad))
    for op, _i in plan.epilogue:
        bad = [n for n in op.input_arg_names if n in plan.region_internal]
        if bad:
            raise PipelineError(
                "epilogue op %r reads %s from inside the pipelined "
                "region; only the final layer's output reaches the "
                "epilogue" % (op.type, bad))

    tpl_param_names = sorted(plan.param_map[0].keys())
    canon = {r: plan.param_map[r] for r in range(plan.repeats)}

    def subblock_err(*_a, **_k):
        raise TraceError("control-flow sub-blocks inside a pipelined "
                         "region are not supported")

    from jax.sharding import PartitionSpec as P

    from ._compat import shard_map_partial

    # the tick loop is manual over (dp?, pp); any OTHER mesh axis (e.g.
    # a Megatron mp axis) stays automatic — GSPMD partitions the template
    # ops over it inside the manual region, so pp composes with tp
    manual_axes = {pp_axis} | ({batch_axis} if batch_axis else set())

    # vars the outside world needs from prologue/epilogue: fetches and
    # post-op inputs
    wanted = set(fetch_names)
    for _op, _i in post_ops:
        wanted.update(_op.input_arg_names)
    pro_produced = {n for op, _ in plan.prologue
                    for n in op.output_arg_names}
    epi_produced = {n for op, _ in plan.epilogue
                    for n in op.output_arg_names}
    pro_ret = sorted(wanted & pro_produced)
    epi_ret = sorted((wanted - ({loss_name} if loss_name else set()))
                     & epi_produced)

    def _ret_spec(name):
        """Row-major outputs shard over dp; anything else must be
        dp-invariant to leave the shard_map."""
        shape = _var_shape(block, name)
        if shape and shape[0] == B_decl:
            return P(None, batch_axis) if batch_axis else P(None)
        if batch_axis and name not in plan.const_names:
            raise PipelineError(
                "fetching %r under dp x pp is unsupported: it is not "
                "batch-major (declared shape %s), so its per-data-shard "
                "values cannot be concatenated" % (name, shape))
        return P(None)

    pro_specs = {n: _ret_spec(n) for n in pro_ret}
    epi_specs = {n: _ret_spec(n) for n in epi_ret}

    # names the device function needs from the replicated environment:
    # external reads of prologue/epilogue/template that are not feeds and
    # not the per-repeat stage params (those arrive stacked)
    repl_candidates = set()
    for ops_list in (plan.prologue, plan.epilogue, plan.template):
        for op, _i in ops_list:
            repl_candidates.update(op.input_arg_names)
    repl_candidates -= set(tpl_param_names)
    repl_candidates -= {plan.carry_tpl_in, plan.carry_last_out}

    def stepfn(feeds: Dict, state: Dict, rng_key, step=0):
        env: Dict = {}
        env.update(state)
        env.update(feeds)
        env_start = dict(env)
        rng = RngStream(jax.random.fold_in(
            rng_key, jnp.asarray(step, jnp.uint32)))

        feed_names = set(feeds)
        pro_feed = _consumed_feed_names(plan.prologue, feed_names)
        epi_feed = _consumed_feed_names(plan.epilogue, feed_names)
        cin0 = plan.carry_in_names[0]
        used_feeds = set(pro_feed) | set(epi_feed) | ({cin0} & feed_names)

        # only microbatched feeds reshape; feeds consumed solely by
        # post-minimize ops (e.g. a coefficient) stay whole in env
        feeds_mb = {}
        for name in sorted(used_feeds):
            arr = feeds[name]
            if arr.ndim == 0 or arr.shape[0] % (M * dp_n) != 0:
                raise TraceError(
                    "feed %r (shape %s) is not divisible into "
                    "num_microbatches=%d x dp=%d x the declared "
                    "per-device microbatch; under pipeline parallelism "
                    "the Program declares the per-device microbatch and "
                    "feeds carry M x dp x that in dim 0"
                    % (name, getattr(arr, "shape", ()), M, dp_n))
            feeds_mb[name] = arr.reshape(
                (M, arr.shape[0] // M) + arr.shape[1:])

        feed_specs = {n: P(None, batch_axis) if batch_axis else P(None)
                      for n in feeds_mb}
        feeds_used = dict(feeds_mb)

        # consts produced by the prologue (feed-independent, verified at
        # plan time) vs consts read straight from persistable state;
        # epilogue reads of prologue products ride the microbatch stack
        consts_from_pro = sorted(set(plan.const_names) & pro_produced)
        epi_ext = set()
        for op, _i in plan.epilogue:
            epi_ext.update(op.input_arg_names)
        epi_from_pro = sorted((epi_ext - epi_produced) & pro_produced)
        pro_keep = sorted(set(pro_ret) | set(consts_from_pro)
                          | set(epi_from_pro)
                          | ({cin0} & pro_produced))
        epi_keep = sorted(set(epi_ret)
                          | ({loss_name} if loss_name else set()))

        def device_forward(stacked, repl, feeds_loc, key):
            # stacked leaves: (1, ...) — this device's stage slice
            stage_params = jax.tree_util.tree_map(
                lambda p: jnp.squeeze(p, axis=0), stacked)
            stage = lax.axis_index(pp_axis)
            dp_ix = lax.axis_index(batch_axis) if batch_axis else 0

            # -- prologue: one scan step per microbatch ------------------
            def pro_body(mb_idx, mb_feeds):
                penv = dict(repl)
                penv.update(mb_feeds)
                srng = RngStream(key)
                srng.salts = [dp_ix, mb_idx]
                for op, idx in plan.prologue:
                    trace_op(op, block, penv,
                             srng.for_op(block.idx, idx), subblock_err,
                             differentiated=True)
                return mb_idx + 1, {n: penv[n] for n in pro_keep}

            xs_pro = {n: feeds_loc[n] for n in pro_feed}
            if plan.prologue:
                _, pro_stack = lax.scan(
                    pro_body, jnp.uint32(0), xs_pro, length=M)
            else:
                pro_stack = {}

            cin0 = plan.carry_in_names[0]
            if cin0 in pro_stack:
                acts = pro_stack[cin0]
            elif cin0 in feeds_loc:
                acts = feeds_loc[cin0]
            else:
                raise TraceError(
                    "pipeline carry %r was not produced by the prologue"
                    % cin0)

            const_env = dict(repl)
            for n in consts_from_pro:
                const_env[n] = jax.tree_util.tree_map(
                    lambda a: a[0], pro_stack[n])

            # -- pipelined tick loop -------------------------------------
            def run_repeat(x, params_j, mb_ix, rep_ix):
                """Trace ONE template repeat with the given param set."""
                renv = dict(const_env)
                renv.update(params_j)
                renv[plan.carry_tpl_in] = x
                srng = RngStream(key)
                srng.salts = [dp_ix, mb_ix, rep_ix]
                for op, idx in plan.template:
                    trace_op(op, block, renv,
                             srng.for_op(block.idx, idx), subblock_err,
                             differentiated=True)
                return renv[plan.carry_tpl_out]

            perm = [(i, (i + 1) % S) for i in range(S)]
            mb_shape = acts.shape[1:]
            vary = (pp_axis,) + ((batch_axis,) if batch_axis else ())

            def gpipe_tick(carry, t):
                # fill-drain: all K of this device's repeats per tick
                state_c, outs_c = carry
                inj = lax.dynamic_index_in_dim(
                    acts, jnp.minimum(t, M - 1), axis=0, keepdims=False)
                inj = jnp.where(t < M, inj, jnp.zeros_like(inj))
                x = jnp.where(stage == 0, inj, state_c)
                mb_ix = (t - stage).astype(jnp.uint32)
                for j in range(K):
                    x = run_repeat(
                        x,
                        {tn: stage_params["r%d/%s" % (j, tn)]
                         for tn in tpl_param_names},
                        mb_ix, stage * K + j + 7)
                m = t - (S - 1)
                emit = jnp.where((stage == S - 1) & (m >= 0), x,
                                 jnp.zeros_like(x))
                outs_c = lax.dynamic_update_index_in_dim(
                    outs_c, emit, jnp.clip(m, 0, M - 1), axis=0)
                state_c = lax.ppermute(x, pp_axis, perm)
                return (state_c, outs_c), None

            # interleaved: repeat r lives on device r mod S; this
            # device's per-round parameter stacks select by round index
            if schedule == "interleaved":
                jstack = {
                    tn: jnp.stack([stage_params["r%d/%s" % (j, tn)]
                                   for j in range(K)])
                    for tn in tpl_param_names}

            def interleaved_tick(carry, t):
                state_c, buf_c, outs_c = carry
                off = t - stage  # this device's work-stream position
                offc = jnp.clip(off, 0, K * M - 1)
                k = offc // M          # round = which of my K repeats
                m = offc - k * M       # microbatch
                # device 0 banks the wrap-around activation arriving this
                # tick (device S-1's output of round k_in, tick t-1) for
                # round k_in + 1
                off_in = jnp.clip(t - S, 0, K * M - 1)
                k_in = off_in // M
                m_in = off_in - k_in * M
                wrap_ok = ((stage == 0) & (t - S >= 0)
                           & (t - S < K * M) & (k_in < K - 1))
                slot = lax.dynamic_index_in_dim(buf_c, m_in, axis=0,
                                                keepdims=False)
                buf_c = lax.dynamic_update_index_in_dim(
                    buf_c, jnp.where(wrap_ok, state_c, slot), m_in,
                    axis=0)

                inj = lax.dynamic_index_in_dim(acts, m, axis=0,
                                               keepdims=False)
                banked = lax.dynamic_index_in_dim(buf_c, m, axis=0,
                                                  keepdims=False)
                x = jnp.where(stage == 0,
                              jnp.where(k == 0, inj, banked), state_c)
                params_k = {
                    tn: lax.dynamic_index_in_dim(jstack[tn], k, axis=0,
                                                 keepdims=False)
                    for tn in tpl_param_names}
                y = run_repeat(x, params_k, m.astype(jnp.uint32),
                               k * S + stage + 7)
                valid = (off >= 0) & (off < K * M)
                emit = jnp.where((stage == S - 1) & (k == K - 1) & valid,
                                 y, jnp.zeros_like(y))
                outs_c = lax.dynamic_update_index_in_dim(
                    outs_c, emit, m, axis=0)
                state_c = lax.ppermute(y, pp_axis, perm)
                return (state_c, buf_c, outs_c), None

            outs0 = _pvary(jnp.zeros((M,) + mb_shape, acts.dtype), vary)
            state0 = _pvary(jnp.zeros(mb_shape, acts.dtype), vary)
            if schedule == "interleaved":
                buf0 = _pvary(jnp.zeros((M,) + mb_shape, acts.dtype),
                              vary)
                (_, _, outs), _ = lax.scan(
                    interleaved_tick, (state0, buf0, outs0),
                    jnp.arange(K * M + S - 1))
            else:
                (_, outs), _ = lax.scan(gpipe_tick, (state0, outs0),
                                        jnp.arange(M + S - 1))
            # outputs live on the last stage; replicate over pp
            outs = lax.psum(jnp.where(stage == S - 1, outs,
                                      jnp.zeros_like(outs)), pp_axis)

            # -- epilogue: one scan step per microbatch ------------------
            def epi_body(mb_idx, xs):
                act, mb_feeds, mb_pro = xs
                eenv = dict(repl)
                eenv.update(mb_feeds)
                eenv.update(mb_pro)
                eenv[plan.carry_last_out] = act
                srng = RngStream(key)
                srng.salts = [dp_ix, mb_idx + 3]
                for op, idx in plan.epilogue:
                    trace_op(op, block, eenv,
                             srng.for_op(block.idx, idx), subblock_err,
                             differentiated=True)
                return mb_idx + 1, {n: eenv[n] for n in epi_keep}

            xs_epi = (outs, {n: feeds_loc[n] for n in epi_feed},
                      {n: pro_stack[n] for n in epi_from_pro})
            if plan.epilogue:
                _, epi_stack = lax.scan(
                    epi_body, jnp.uint32(0), xs_epi, length=M)
            else:
                epi_stack = {}

            if loss_name is not None:
                if loss_name not in epi_stack:
                    raise TraceError(
                        "loss %r is not computed by the epilogue; losses "
                        "must come after the repeated layers" % loss_name)
                loss = jnp.mean(epi_stack[loss_name])
                if batch_axis:
                    loss = lax.pmean(loss, batch_axis)
            else:
                loss = jnp.zeros(())
            return (loss,
                    {n: pro_stack[n] for n in pro_ret},
                    {n: epi_stack[n] for n in epi_ret})

        def forward(pvals: Dict):
            fenv = dict(env_start)
            fenv.update(pvals)
            stage_trees = []
            for s in range(S):
                tree = {}
                for j in range(K):
                    # gpipe: device s owns the contiguous block of K
                    # repeats; interleaved: it owns every S-th repeat
                    r = (s * K + j if schedule == "gpipe"
                         else j * S + s)
                    for tname in tpl_param_names:
                        tree["r%d/%s" % (j, tname)] = fenv[canon[r][tname]]
                stage_trees.append(tree)
            stacked = stack_stage_params(stage_trees)
            repl_env = {n: fenv[n] for n in repl_candidates
                        if n in fenv and n not in feed_names}
            key = rng.for_op(block.idx, 10 ** 6)()

            stacked_spec = jax.tree_util.tree_map(
                lambda _: P(pp_axis), stacked)
            loss, pro_stack, epi_stack = shard_map_partial(
                device_forward, mesh=mesh,
                in_specs=(stacked_spec,
                          jax.tree_util.tree_map(lambda _: P(), repl_env),
                          feed_specs, P()),
                out_specs=(P(), pro_specs, epi_specs),
                manual_axes=manual_axes,
            )(stacked, repl_env, feeds_used, key)
            return loss, (pro_stack, epi_stack, loss)

        # -- grads (reverse pipeline via vjp) ----------------------------
        if ad_op is not None:
            pvals = {}
            for name in param_names:
                if name not in env_start:
                    raise TraceError(
                        "parameter %r has no value in scope — run the "
                        "startup program first" % name)
                pvals[name] = env_start[name]
            fwd_fn = forward
            policy_name = getattr(block.program, "_remat_policy", None)
            if policy_name:
                fwd_fn = jax.checkpoint(
                    forward,
                    policy=getattr(jax.checkpoint_policies, policy_name))
            loss_val, vjp_fn, (pro_stack, epi_stack, mean_loss) = jax.vjp(
                fwd_fn, pvals, has_aux=True)
            (grads,) = vjp_fn(jnp.ones_like(loss_val))
            for name in param_names:
                env[grad_var_name(name)] = grads[name]
        else:
            _, (pro_stack, epi_stack, mean_loss) = forward({})

        # microbatch-stacked vars flatten back to the global batch view
        for stack in (pro_stack, epi_stack):
            for n, v in stack.items():
                if v.ndim >= 2:
                    env[n] = v.reshape((v.shape[0] * v.shape[1],)
                                       + v.shape[2:])
                else:
                    env[n] = v
        if loss_name is not None:
            env[loss_name] = mean_loss

        # optimizer / lr / clip ops run exactly as in sequential tracing
        for op, idx in post_ops:
            trace_op(op, block, env, rng.for_op(block.idx, idx))

        fetches = []
        for name in fetch_names:
            if name not in env:
                raise KeyError(
                    "fetch target %r was not produced by the program"
                    % name)
            fetches.append(env[name])
        out_names = set(state_in) | set(state_out)
        new_state = {n: env[n] for n in out_names if n in env}
        return tuple(fetches), new_state

    return stepfn
