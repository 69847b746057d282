"""Sharding plans: how a Program's state and feeds map onto a Mesh.

The reference distributes work by rewriting the graph — DistributeTranspiler
splits params into pserver blocks, ParallelExecutor builds per-device SSA
graphs with NCCL ops (reference: python/paddle/fluid/transpiler/
distribute_transpiler.py, paddle/fluid/framework/details/
multi_devices_graph_builder.cc). TPU-native, NOTHING in the program changes:
a ShardingPlan assigns a ``PartitionSpec`` to each variable name and XLA's
SPMD partitioner (GSPMD) materializes the distributed program, inserting
all-reduce/all-gather/reduce-scatter on ICI as the specs require.

Conventions:
- mesh axes: "dp" data, "mp" tensor (model) parallel, "sp" sequence,
  "pp" pipeline stage, "ep" expert.
- optimizer accumulators are named "<param>_<kind>_acc" and have the
  param's shape, so the longest-prefix rule gives them the param's spec.
"""
from __future__ import annotations

import re
from typing import Dict, Optional, Sequence

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ShardingPlan", "PartitionSpec", "megatron_transformer_plan",
           "zero_plan", "seq_parallel_plan", "infer_tp_plan"]

PartitionSpec = P


class ShardingPlan:
    """name/pattern -> PartitionSpec mapping with sensible fallbacks.

    Resolution order for a variable name:
    1. exact entry
    2. regex entries (first match, insertion order)
    3. longest registered prefix (covers "<param>_moment_acc" etc.)
    4. ``default`` (replicated unless overridden)
    """

    def __init__(self, mesh: Mesh, default: P = P(), batch_axes: Sequence[str] = ("dp",)):
        self.mesh = mesh
        self.default = default
        # feed arrays get their leading (batch) dim split over these axes
        self.batch_axes = tuple(a for a in batch_axes if a in mesh.axis_names)
        # sequence-parallel plans shard feed dim 1 (time) over this axis
        self.seq_axis: Optional[str] = None
        # tensor-parallel plans split attention heads over this axis
        # (read by kernels that shard_map themselves, ops/attention.py)
        self.tensor_axis: Optional[str] = None
        self._exact: Dict[str, P] = {}
        self._regex: list = []  # (compiled pattern, spec, strict)

    # -- construction ----------------------------------------------------
    def set(self, name: str, spec: P) -> "ShardingPlan":
        self._exact[name] = spec
        return self

    def set_regex(self, pattern: str, spec: P,
                  strict: bool = False) -> "ShardingPlan":
        """strict: a dim of more than one element that the spec's axes do
        not divide is an error naming the variable and its shape, where
        other rules quietly leave such a dim whole (``spec``). True holds
        every axis of the spec to it, a tuple of axis names those alone."""
        self._regex.append((re.compile(pattern), spec, strict))
        return self

    # -- resolution ------------------------------------------------------
    def spec(self, name: str, ndim: Optional[int] = None,
             shape: Optional[Sequence[int]] = None) -> P:
        s, strict = self._lookup(name)
        if shape is not None:
            ndim = len(shape)
        if ndim is not None and len(s) > ndim:
            # e.g. scalar lr decayed from a matrix param's prefix
            s = P(*s[:ndim]) if ndim else P()
        if shape is not None and len(s):
            # drop axes the actual dims can't be split over (e.g. the (1,)
            # beta-pow accumulators that prefix-inherit a matrix spec)
            import numpy as np

            fixed = []
            for i, ax in enumerate(s):
                if ax is None:
                    fixed.append(None)
                    continue
                axes = ax if isinstance(ax, tuple) else (ax,)
                ways = int(np.prod([self.mesh.shape[a] for a in axes]))
                held = strict is True or (
                    strict and any(a in strict for a in axes))
                if held and shape[i] > 1 and shape[i] % ways:
                    raise ValueError(
                        "%r of shape %s: dim %d does not divide over the %d "
                        "devices of mesh axis %r, and this plan's rule for "
                        "it cannot leave it whole" % (
                            name, tuple(shape), i, ways, ax))
                fixed.append(ax if shape[i] % ways == 0 else None)
            s = P(*fixed)
        return s

    def _lookup(self, name: str):
        """(spec, whether the rule that gave it is strict)."""
        if name in self._exact:
            return self._exact[name], False
        for rx, spec, strict in self._regex:
            if rx.search(name):
                return spec, strict
        best, best_len = None, -1
        for key, spec in self._exact.items():
            if name.startswith(key) and len(key) > best_len:
                best, best_len = spec, len(key)
        if best is not None:
            return best, False
        return self.default, False

    def sharding(self, name: str, ndim: Optional[int] = None,
                 shape: Optional[Sequence[int]] = None) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(name, ndim, shape))

    def feed_sharding(self, ndim: int) -> NamedSharding:
        """Feeds: batch dim split over the data axes, dim 1 split over the
        sequence axis when the plan is sequence-parallel, rest replicated."""
        if ndim == 0 or (not self.batch_axes and not self.seq_axis):
            return NamedSharding(self.mesh, P())
        if not self.batch_axes:
            axes = None
        else:
            axes = (self.batch_axes[0] if len(self.batch_axes) == 1
                    else self.batch_axes)
        dims = [axes] + [None] * (ndim - 1)
        if self.seq_axis and ndim >= 2:
            dims[1] = self.seq_axis
        return NamedSharding(self.mesh, P(*dims))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())


def zero_plan(mesh: Mesh, program, axis: str = "dp") -> ShardingPlan:
    """ZeRO-1-style plan: optimizer accumulators sharded over the data
    axis, params replicated. The TPU-native reading of the reference's
    BuildStrategy.ReduceStrategy.Reduce (each device owns one slice of the
    update) and of DistributeTranspiler's pserver param blocks: GSPMD
    lowers grad-allreduce + sharded update into reduce-scatter/all-gather.
    """
    from ..framework.core import Parameter

    plan = ShardingPlan(mesh, batch_axes=(axis,))
    n = mesh.shape[axis]
    for var in program.global_block().vars.values():
        if not isinstance(var, Parameter) or not var.trainable:
            continue
        if not var.shape or var.shape[0] % n != 0:
            continue
        spec = P(*([axis] + [None] * (len(var.shape) - 1)))
        # "<param>_<kind>_acc" inherits via the prefix rule; the param
        # itself is pinned replicated by the exact entry.
        plan.set(var.name + "_", spec)
        plan.set(var.name, P())
    return plan


def megatron_transformer_plan(
    mesh: Mesh,
    mp_axis: str = "mp",
    batch_axes: Sequence[str] = ("dp",),
    tied: bool = False,
) -> ShardingPlan:
    """Tensor-parallel plan for our transformer naming convention
    (models/transformer.py): q/k/v/fc1 weights column-parallel, out/fc2
    row-parallel, embeddings hidden-sharded. With these param specs GSPMD
    propagates head-sharded activations through attention and inserts one
    all-reduce after each row-parallel matmul — the Megatron-LM comm
    pattern, derived by the compiler instead of hand-written NCCL calls.

    tied=True is for ``transformer_lm(tie_embeddings=True)``: the token
    table (V, D) doubles as the vocab projection, so it is split where the
    head needs it split: by vocabulary ROWS, ``P(mp_axis, None)``, with the
    head bias ``P(mp_axis)`` and the optimizer moments the same. Each mp
    rank then owns V / mp rows of the table: the fused head
    (ops/fused_loss.py) runs its chunk loop over that slice inside a
    shard_map, contracting the whole D locally, and only the (N,) row
    statistics and one dx cross mp; the embedding read of the same table
    is a local masked take and one all-reduce of the activations. The
    default emb rule (hidden-sharding) would split the head matmul's
    CONTRACTED axis, and a table pinned replicated gets it split all the
    same (GSPMD then all-reduces a chunk of partial logits per loop
    iteration, and gathers the updated table every step). A V that mp
    does not divide is an error naming the shape, never a quiet fall back
    to a whole table.

    On a mesh whose batch axes are wider than 1, ONE dp rank owns the
    update of each matrix and table (ZeRO, what ``zero_plan`` does on a
    plain dp mesh): the dimension mp leaves whole is split over the batch
    axes, for the weight at rest and, their names beginning with its, the
    optimizer's accumulators. GSPMD then lowers gradient -> update ->
    weight as reduce-scatter -> Adam on 1/dp of the rows -> all-gather of
    the bfloat16 cast where a matmul reads it, which the TPU compiler
    runs asynchronously under the matmuls; dp twins no longer run the same
    update on the same sums (the chip: 383.8 -> 359.8 ms a step of
    `opt-6.7b-tp2`, 9.8 -> 4.9 GB a device; with the accumulators alone
    split the weights come back by synchronous float32 all-gathers and
    nothing is gained; PERF.md, PR 53). What the plan can see decides, and
    nothing else: no batch axis in the mesh, one of size 1, or a dimension
    it does not divide, gives the specs of a mesh without one (serving
    plans, ``batch_axes=()``, dp=1). Biases and LayerNorm vectors stay
    whole on every dp rank, and so do the untied table's rows and the
    untied head: with the rows over dp GSPMD partitions the embedding's
    gather by them and all-gathers the labels inside the head's loops.
    """
    plan = ShardingPlan(mesh, batch_axes=batch_axes)
    if mp_axis in mesh.axis_names:
        plan.tensor_axis = mp_axis
    # the batch axes wider than 1 (docstring: one dp rank owns the update);
    # None, and so the specs of a mesh without them, where there is none
    own = tuple(a for a in plan.batch_axes
                if a != mp_axis and mesh.shape[a] > 1)
    own = own[0] if len(own) == 1 else (own or None)
    col_w = P(own, mp_axis)  # (in, out) split on out
    row_w = P(mp_axis, own)  # (in, out) split on in
    col_b = P(mp_axis)
    # strict about mp alone: a dim the batch axes do not divide stays whole
    held = (mp_axis,) if tied else False
    for pat, spec, strict in [
        # .qkv: the fused projection's columns are grouped per head
        # [h0:q,k,v | h1:q,k,v | ...], so a contiguous column split over
        # mp keeps whole head groups local — same comm pattern as
        # separate q/k/v columns
        (r"\.(q|k|v|qkv|fc1)\.w", col_w, False),
        (r"\.(q|k|v|qkv|fc1)\.b", col_b, False),
        (r"\.(out|fc2)\.w", row_w, False),
        (r"\.(out|fc2)\.b", P(), False),
        (r"pos_emb", col_w, False),
        # tied: vocabulary rows, as the head reads them (docstring);
        # untied: rows whole, over the batch axes too
        (r"tok_emb", row_w if tied else P(None, mp_axis), held),
        (r"\.head\.w", P(None, mp_axis), False),  # vocab-parallel projection
        (r"\.head\.b", col_b, held),
    ]:
        plan.set_regex(pat, spec, strict=strict)
    return plan


def infer_tp_plan(mesh: Mesh, program, mp_axis: str = "mp") -> ShardingPlan:
    """Tensor-parallel plan for INFERENCE of a loaded program — the
    training-side megatron plan rules reused at serving time
    (ROADMAP item 1: "the megatron plan rules exist for training; reuse
    them at inference").

    Two regimes:

    - The program's parameter names match our transformer convention
      (``.qkv.w`` / ``.fc1.w`` / ``.out.w`` …): return
      ``megatron_transformer_plan`` with batch axes DISABLED — serving
      batches are small and dynamic, so feeds stay replicated and only
      the params shard.
    - Otherwise (exported MLPs and friends): derive the SAME
      column/row alternation structurally. Walk the ops in program
      order; every matmul against a persistable 2-D weight alternates
      column-parallel ``P(None, mp)`` then row-parallel ``P(mp, None)``
      (the Megatron pairing: the all-reduce lands after each
      row-parallel matmul, everything between stays local), and each
      weight's bias follows its matmul (column -> ``P(mp)``, row ->
      replicated). Weights whose shard dim does not divide the mesh
      axis fall back to replicated via ``ShardingPlan.spec``'s shape
      fixing, so an odd layer degrades that layer, not the program.
    """
    matched = False
    probe = megatron_transformer_plan(mesh, mp_axis=mp_axis, batch_axes=())
    try:
        for var in program.global_block().vars.values():
            if getattr(var, "persistable", False) and any(
                    rx.search(var.name) for rx, _, _ in probe._regex):
                matched = True
                break
    except Exception:
        matched = False
    if matched:
        return probe

    plan = ShardingPlan(mesh, batch_axes=())
    col = True  # start column-parallel; its successor goes row-parallel
    pending_bias = None  # spec for the next persistable 1-D add operand
    gb = program.global_block()

    def _pvar(name):
        v = gb._find_var_recursive(name)
        return v if v is not None and getattr(v, "persistable", False) else None

    for block in program.blocks:
        for op in block.ops:
            if op.type in ("mul", "matmul", "matmul_v2"):
                for name in op.input_arg_names:
                    var = _pvar(name)
                    if var is None or len(getattr(var, "shape", ()) or ()) != 2:
                        continue
                    plan.set(name, P(None, mp_axis) if col
                             else P(mp_axis, None))
                    pending_bias = "col" if col else "row"
                    col = not col
            elif op.type == "elementwise_add" and pending_bias is not None:
                for name in op.input_arg_names:
                    var = _pvar(name)
                    shape = tuple(getattr(var, "shape", ()) or ()
                                  ) if var is not None else ()
                    if shape and len(shape) <= 2:
                        # bias follows its matmul: the sharded dim is the
                        # LAST one (fc biases are 1-D [out]; a 2-D bias
                        # replicates its leading dim)
                        if pending_bias == "col":
                            spec = P(*([None] * (len(shape) - 1)
                                       + [mp_axis]))
                        else:
                            spec = P()
                        plan.set(name, spec)
                        pending_bias = None
                        break
    return plan


def seq_parallel_plan(
    mesh: Mesh,
    sp_axis: str = "sp",
    batch_axes: Sequence[str] = ("dp",),
) -> ShardingPlan:
    """Sequence/context-parallel plan for the long-context LM
    (models/transformer.py transformer_lm(use_ring_attention=True)): feeds
    and activations carry the time dim sharded over `sp_axis`, parameters
    stay replicated, and the ring_attention op exchanges K/V blocks over
    the same axis with ppermute. GSPMD keeps every elementwise / matmul op
    local to its sequence shard; only attention communicates.
    """
    plan = ShardingPlan(mesh, batch_axes=batch_axes)
    plan.seq_axis = sp_axis if sp_axis in mesh.axis_names else None
    return plan
