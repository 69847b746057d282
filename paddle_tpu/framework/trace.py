"""Block tracer: lowers a Program Block to one pure JAX function.

This replaces the reference's per-op dispatch loop (reference:
paddle/fluid/framework/executor.cc:Executor::RunPreparedContext — creates an
OperatorBase per OpDesc and launches a kernel per op). Here the whole block
is traced symbolically once and handed to XLA as a single computation, so
op boundaries vanish: XLA fuses elementwise chains into matmul/conv
epilogues and schedules the entire step.

The ``autodiff`` pseudo-op (inserted by backward.append_backward) is handled
specially: the forward prefix of the block is replayed inside ``jax.vjp`` so
XLA differentiates the whole graph at once — the traced training step
contains forward+backward+optimizer in one XLA program. Several autodiff
ops in one block (e.g. two optimizers on two losses) are supported: each
replays the forward ops before it; identical replayed subcomputations are
CSE'd by XLA, and per-op keyed RNG keeps any dropout masks identical across
replays.
"""
from __future__ import annotations

import contextlib
import re
import threading
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp

from ..ops.registry import NAMES_DEVICE_CALLS, OpContext, get_kernel
from .core import Block, Operator, grad_var_name

# op types the tracer interprets (or skips) itself rather than via a kernel:
# autodiff is expanded into a vjp; feed/fetch (present in reference-style
# serialized programs) are no-ops because the executor feeds/fetches
# directly. `read` ops are resolved by the executor too: it pulls the next
# batch from the reader pipeline and injects the op's outputs as feeds
# before tracing (the jitted step stays pure).
_SKIP_OPS = {"feed", "fetch", "read"}

# Per-op RNG keys derive from the op's block position — which the
# optimizing transpiler perturbs when it deletes or fuses ops. Before its
# first rewrite, the pass manager stamps every op's PRE-optimization
# position as this attr (transpiler/passes/manager.py) and the tracer
# prefers it, so an optimized program draws the exact PRNG stream the
# original would (parity gating requires bit-equal dropout masks).
_RNG_IDX_ATTR = "__rng_idx__"


def _rng_idx(op: Operator, op_idx: int) -> int:
    return op.attrs.get(_RNG_IDX_ATTR, op_idx)

# Mixed precision (program.enable_mixed_precision()): matmul-class ops run
# their float inputs in bf16 — MXU native, half the HBM traffic — while
# numerically sensitive ops are pinned to fp32. Parameters and optimizer
# state stay fp32 (master weights); the casts live inside the traced graph,
# so vjp returns fp32 gradients and XLA dedups repeated casts. bf16 shares
# fp32's exponent range, so no loss scaling is needed (unlike fp16 AMP).
_AMP_BF16_OPS = {
    "mul", "matmul", "conv2d", "conv3d", "conv2d_transpose",
    "conv3d_transpose", "sequence_conv", "fused_attention",
    "fused_lm_head_loss", "fused_fc",
}
_AMP_FP32_OPS = {
    "softmax_with_cross_entropy", "cross_entropy", "layer_norm",
    "softmax", "sequence_softmax", "reduce_mean",
    "reduce_sum", "mean", "exp", "log", "linear_chain_crf", "warpctc",
    "nce", "hierarchical_sigmoid", "l2_normalize",
}
# AMP level O2 (enable_mixed_precision(level="O2")): the elementwise path
# joins the bf16 set, so activations stay bf16 BETWEEN matmuls instead of
# being re-promoted to fp32 by every f32-bias add / residual add (under
# O1 the profile shows f32 (tokens, d_inner) tensors streaming HBM).
# layer_norm moves from the fp32 pin to bf16 in/out — its kernel computes
# statistics in fp32 internally regardless of input dtype.
# Only ACTIVATION-STREAM instances are cast: an op that names a @GRAD
# var or writes a persistable var is gradient/optimizer-state plumbing
# (regularizer decay adds, clip scaling, ModelAverage accumulation) and
# must keep the fp32 master-weight contract — see _o2_eligible().
_AMP_BF16_O2_OPS = {
    "elementwise_add", "elementwise_sub", "elementwise_mul",
    "relu", "tanh", "sigmoid", "swish", "leaky_relu", "relu6",
    "brelu", "dropout", "lookup_table", "layer_norm",
}


def _o2_eligible(op, block) -> bool:
    """True when an _AMP_BF16_O2_OPS instance sits on the activation
    stream: no @GRAD input/output (gradient math stays fp32) and no
    persistable output (optimizer/EMA state stays fp32)."""
    for name in op.input_arg_names:
        if name.endswith("@GRAD") or "@GRAD@" in name:
            return False
    for name in op.output_arg_names:
        if name.endswith("@GRAD") or "@GRAD@" in name:
            return False
        var = block._find_var_recursive(name)
        if var is not None and var.persistable:
            return False
    return True
# batch_norm is deliberately NOT fp32-pinned: the kernel computes its
# statistics in fp32 internally while keeping the (huge) activation tensors
# in the incoming dtype — pinning it would stream fp32 copies of every
# activation through HBM between bf16 convs (profiled on ResNet-50).


# Mesh the step is being traced under (set by ParallelExecutor around the
# first call of its jitted step). Kernels that have a distributed
# implementation (ring_attention) consult this to decide between the
# collective path and the single-device fallback. Thread-local: two
# ParallelExecutors first-running on different threads must not see each
# other's mesh.
_TRACE_MESH = threading.local()


@contextlib.contextmanager
def mesh_context(mesh, plan=None):
    """`plan` (parallel.sharding.ShardingPlan) tells kernels that must
    shard_map themselves which mesh axes split batch and heads."""
    stack = getattr(_TRACE_MESH, "stack", None)
    if stack is None:
        stack = _TRACE_MESH.stack = []
    stack.append((mesh, plan))
    try:
        yield
    finally:
        stack.pop()


def current_trace_mesh():
    stack = getattr(_TRACE_MESH, "stack", None)
    return stack[-1][0] if stack else None


def current_trace_plan():
    stack = getattr(_TRACE_MESH, "stack", None)
    return stack[-1][1] if stack else None


class RngStream:
    """Deterministic PRNG stream keyed on (block idx, op position, draw #):
    replaying an op (e.g. inside an autodiff vjp) yields the same bits, and
    adding ops elsewhere never perturbs other ops' streams.

    ``salts`` holds loop-iteration indices (possibly traced) pushed by
    control-flow kernels while tracing their sub-blocks, so an RNG-drawing
    op inside lax.scan / lax.while_loop gets fresh bits every iteration
    (the key becomes a function of the loop counter instead of a loop
    constant)."""

    def __init__(self, base_key):
        self.base_key = base_key
        self.salts: List = []

    def for_op(self, block_idx: int, op_idx: int) -> Callable:
        draws = [0]
        salts = list(self.salts)

        def next_key():
            k = jax.random.fold_in(self.base_key, block_idx * 1000003 + op_idx)
            for s in salts:
                k = jax.random.fold_in(k, jnp.asarray(s, jnp.uint32).reshape(()))
            k = jax.random.fold_in(k, draws[0])
            draws[0] += 1
            return k

        return next_key


class TraceError(RuntimeError):
    """Carries the failing op's context, mirroring the reference's enforce
    messages that name the op and its inputs."""


def _apply_outputs(op: Operator, block: Block, env: Dict, result: Dict):
    for slot, names in op.outputs.items():
        if slot not in result:
            continue
        vals = result[slot]
        if not isinstance(vals, (list, tuple)):
            vals = [vals]
        for name, val in zip(names, vals):
            var = block._find_var_recursive(name)
            if var is not None and var.stop_gradient and not var.persistable:
                val = jax.lax.stop_gradient(val)
            env[name] = val


_UNSCOPABLE = re.compile(r"[^\w.:@\-]")


def op_scope(op: Operator, block: Block) -> str:
    """``fl.<op.type>:<anchor>``, the ``jax.named_scope`` a Fluid op's
    kernel is traced under: the anchor is the op's first persistable
    input (a weight, a table: ``lm.l3.ffn.w1``), else its first output's
    name. Every HLO instruction the kernel makes carries it in its
    ``op_name``, which is how ``observability.scopes`` lays a device
    operation under the op that made it."""
    anchor = next(
        (n for n in op.input_arg_names
         if getattr(block._find_var_recursive(n), "persistable", False)),
        next(iter(op.output_arg_names), ""))
    return "fl.%s:%s" % (op.type, _UNSCOPABLE.sub("_", anchor))


def trace_op(op: Operator, block: Block, env: Dict, rng_fn, subblock_fn=None,
             differentiated: bool = False):
    """Run ``op``'s kernel on ``env`` under ``op_scope``. Metadata only:
    the lowered text without locations is the same with or without the
    scope. ``differentiated`` (the replay inside an ``autodiff``'s vjp)
    leaves the scope off an op whose kernel names its own device calls
    (``ops.registry.NAMES_DEVICE_CALLS``): jax wraps ``jvp(..)`` around
    the OUTERMOST scope under the transform, and the TPU compiler names
    a Mosaic call after the innermost component, so with a scope between
    the two ``jvp_ptpu.flash_fwd_.N`` would become ``ptpu.flash_fwd.N``
    and the readers that tell a training step's forward kernel from its
    backward by that name would find neither."""
    kernel = get_kernel(op.type)
    view = _EnvView(env, op)
    if getattr(block.program, "_amp", False):
        o2 = getattr(block.program, "_amp_level", "O1") == "O2"
        if op.type in _AMP_BF16_OPS or (
                o2 and op.type in _AMP_BF16_O2_OPS
                and _o2_eligible(op, block)):
            view = _CastEnvView(env, op, jnp.bfloat16)
        elif op.type in _AMP_FP32_OPS:
            view = _CastEnvView(env, op, jnp.float32)
    ctx = OpContext(op, view, rng_fn, subblock_fn, block)
    scope = (contextlib.nullcontext()
             if differentiated and op.type in NAMES_DEVICE_CALLS
             else jax.named_scope(op_scope(op, block)))
    try:
        with scope:
            result = kernel(ctx)
    except (NotImplementedError,):
        raise
    except Exception as e:
        in_shapes = {
            slot: [getattr(env.get(n), "shape", None) for n in names]
            for slot, names in op.inputs.items()
        }
        err = TraceError(
            "error while lowering op %r (inputs %s, attrs %s): %s"
            % (op.type, in_shapes, op.attrs, e)
        )
        # op provenance for the static analyzer's post-mortem: the
        # executor re-renders trace failures with the analyzer's per-op
        # shape/dtype facts (analysis.explain_trace_error) keyed on these
        err.pt_op_type = op.type
        err.pt_block_idx = block.idx
        try:
            err.pt_op_idx = block.ops.index(op)
        except ValueError:  # op replayed from a detached copy
            err.pt_op_idx = None
        raise err from e
    _apply_outputs(op, block, env, result)


class _EnvView(dict):
    """Env lookup that raises with op context for variables that were never
    produced. (Optional inputs never reach here: layers omit the slot
    entirely, so OpContext.input() returns the default before lookup.)"""

    def __init__(self, env, op):
        super().__init__()
        self._env = env
        self._op = op

    def __getitem__(self, name):
        if name in self._env:
            return self._env[name]
        raise KeyError(
            "variable %r (input of op %r) has no value: not a feed, not "
            "persistable state, and not produced by any earlier op"
            % (name, self._op.type)
        )

    def __contains__(self, name):
        return name in self._env

    def snapshot(self):
        return dict(self._env)


class _CastEnvView(_EnvView):
    """Env view that casts float inputs to the op's AMP compute dtype."""

    def __init__(self, env, op, dtype):
        super().__init__(env, op)
        self._amp_dtype = dtype

    def __getitem__(self, name):
        v = super().__getitem__(name)
        dt = getattr(v, "dtype", None)
        if dt in (jnp.float32, jnp.bfloat16) and dt != self._amp_dtype:
            return v.astype(self._amp_dtype)
        return v


def trace_block(block: Block, env: Dict, rng: RngStream,
                differentiated: bool = False) -> Dict:
    """Trace all ops of `block` into `env` (mutated in place and returned).
    ``differentiated``: the block is a sub-block of an op that an
    ``autodiff`` replays (``trace_op``)."""
    program = block.program

    def subblocks(differentiated: bool):
        def subblock_fn(block_idx: int, sub_env: Dict, salt=None) -> Dict:
            sub = program.block(block_idx)
            if salt is None:
                return trace_block(sub, sub_env, rng, differentiated)
            rng.salts.append(salt)
            try:
                return trace_block(sub, sub_env, rng, differentiated)
            finally:
                rng.salts.pop()
        return subblock_fn

    subblock_fn = subblocks(differentiated)
    replayed_subblock_fn = subblocks(True)

    env_start = dict(env)
    # (op, op_idx) pairs replayed inside each vjp. Frozen at the first
    # autodiff: ops after it (optimizer/clip/regularizer updates, metrics)
    # are not part of any loss's forward graph. In fluid programs every
    # forward op precedes the first minimize(), so all losses are covered.
    #
    # Ops BEFORE the first autodiff are not traced eagerly: they are traced
    # exactly once, inside the first autodiff's jax.vjp, and their outputs
    # reach `env` through the vjp's aux (`fenv`). Tracing them both eagerly
    # and in the vjp would double the HLO (and with a remat policy set the
    # two copies are not CSE-able — one is checkpointed).
    forward_ops: List[tuple] = []
    first_ad = next(
        (i for i, o in enumerate(block.ops) if o.type == "autodiff"), None
    )

    for op_idx, op in enumerate(block.ops):
        if op.type in _SKIP_OPS:
            continue
        if op.type != "autodiff":
            if first_ad is not None and op_idx < first_ad:
                # deferred to the vjp (RNG key by pre-optimization stamp)
                forward_ops.append((op, _rng_idx(op, op_idx)))
                continue
            trace_op(op, block, env, rng.for_op(block.idx,
                                                _rng_idx(op, op_idx)),
                     subblock_fn, differentiated)
            continue

        # -- autodiff: differentiate loss wrt params over the full forward
        # prefix (all non-autodiff ops so far), replayed under jax.vjp.
        loss_name = op.attr("loss_name")
        param_names: List[str] = list(op.attr("param_names"))
        replay = list(forward_ops)

        def forward(pvals: Dict[str, jnp.ndarray]):
            fenv = dict(env_start)
            fenv.update(pvals)
            for fop, fidx in replay:
                trace_op(fop, block, fenv, rng.for_op(block.idx, fidx),
                         replayed_subblock_fn, True)
            if loss_name not in fenv:
                raise TraceError(
                    "loss %r is not computed by the forward ops preceding "
                    "the first backward pass; differentiating a loss built "
                    "between two minimize() calls is unsupported" % loss_name
                )
            loss = fenv[loss_name]
            return jnp.sum(loss), fenv

        # gradients are taken at the values the forward pass actually saw
        # (env_start — the block's entry state), matching the reference's
        # sequential semantics: backward ops read the activations stored by
        # the one forward execution, so a second minimize()'s grads are
        # NOT affected by the first optimizer's in-between param updates.
        pvals = {}
        for name in param_names:
            if name in env_start:
                pvals[name] = env_start[name]
            elif name in env:
                pvals[name] = env[name]
            else:
                raise TraceError(
                    "parameter %r has no value in scope — run the startup "
                    "program first" % name
                )

        # memory_optimize() (transpiler/memory_optimizer.py) sets a remat
        # policy: the replayed forward is checkpointed so the backward
        # recomputes activations instead of saving them (HBM for FLOPs).
        policy_name = getattr(block.program, "_remat_policy", None)
        fwd_fn = forward
        if policy_name:
            fwd_fn = jax.checkpoint(
                forward, policy=getattr(jax.checkpoint_policies, policy_name)
            )
        loss_val, vjp_fn, fenv = jax.vjp(fwd_fn, pvals, has_aux=True)
        (grads,) = vjp_fn(jnp.ones_like(loss_val))

        # adopt from fenv only what the replayed forward PRODUCED: copying
        # all of fenv would revert state a previous autodiff section's
        # optimizer ops already updated (fenv's params are env_start
        # values), silently un-training earlier losses in multi-minimize
        # (e.g. GAN-style) programs.
        produced = set()
        for fop, _ in replay:
            produced.update(fop.output_arg_names)
        for name in produced:
            if name in fenv:
                env[name] = fenv[name]
        for name in param_names:
            env[grad_var_name(name)] = grads[name]

    return env
