"""Fused LM-head softmax-cross-entropy: projection + loss without ever
materializing the (N, V) logits tensor.

Replaces the reference's `mul` (lm head fc, reference
python/paddle/fluid/layers/nn.py:fc) + `softmax_with_cross_entropy`
(reference paddle/fluid/operators/softmax_with_cross_entropy_op.cc) chain
for large vocabularies. On TPU the unfused chain writes the full (N, V)
logits to HBM in fp32 (batch 8 x seq 1024 x vocab 32768 = 1 GiB), reads it
back for the log-softmax, and materializes a same-sized gradient in the
backward — pure HBM-bandwidth burn on what is otherwise a matmul-bound op.

Here the vocab axis is processed in chunks with an online logsumexp
(flash-attention-style): the forward saves only X, W, b and the per-row
logsumexp; the backward recomputes each chunk's logits, forms
(softmax - onehot) per chunk, and accumulates dX / dW / db — never more
than one (N, block_v) tile live at a time. Chunks are read from W in
place via dynamic slices (no transposed copy of the weight). All matmuls
run on the MXU with fp32 accumulation (`preferred_element_type`), so bf16
inputs under mixed precision keep full-precision loss/grads.

Vocabulary-parallel under a tensor-parallel mesh. Where the step is traced
under a mesh whose plan names a tensor axis of more than one device
(`framework.trace.current_trace_mesh` / `current_trace_plan`, as
`ops/attention._per_shard` reads them), the SAME chunk loop runs inside a
`shard_map`: every rank of the tensor axis owns a contiguous slice of
V / ways vocabulary rows (dim 0 of a (V, D) table, dim 1 of a (D, V)
weight), contracts the whole D locally, and numbers its columns from
`axis_index * V / ways`. No collective runs inside either loop. After the
forward loop the three (N,) row carries cross the tensor axis once (the
max by `pmax`, the sum rescaled to it and the picked logit by `psum`);
after the backward loop `dx` is summed over the tensor axis once, and
`dW` / `db` over the batch axes once (`_grad_vma_like`: a cotangent is
summed over the axes its primal does not vary on). Left to GSPMD the same
loop all-reduces a chunk of partial logits per iteration, forward and
backward (it splits the contracted D), or gathers the table around it.
With no mesh, a tensor axis of one device, or inside an enclosing
`shard_map`, the op lowers to the one-device program unchanged.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..framework.trace import current_trace_mesh, current_trace_plan
from ..observability import FUSED_HEAD_TRACES
from .registry import register_op

_NEG = -1e30


def _vary_like(val, *refs):
    """Inside shard_map, loop carries initialized from literals are
    unvaried over the manual mesh axes while the loop body mixes in
    device-varying operands (x, labels) — the VMA type system rejects
    that. Promote ``val`` to vary over every axis any ref varies over
    (no-op under plain jit)."""
    typeof = getattr(jax, "typeof", None)
    if typeof is None:
        return val
    try:
        vma = set()
        for r in refs:
            vma |= set(getattr(typeof(r), "vma", ()) or ())
        vma -= set(getattr(typeof(val), "vma", ()) or ())
    except Exception:
        return val
    if not vma:
        return val
    from ..parallel._compat import pvary

    return pvary(val, tuple(vma))


def _grad_vma_like(g, primal):
    """The bwd rule's cotangent must carry the primal's varying axes: a
    device-UNvaried primal (e.g. a replicated weight under dp shard_map)
    gets the SUM of per-device contributions — exactly GSPMD's grad
    all-reduce for replicated params."""
    typeof = getattr(jax, "typeof", None)
    if typeof is None:
        return g
    try:
        extra = (set(getattr(typeof(g), "vma", ()) or ())
                 - set(getattr(typeof(primal), "vma", ()) or ()))
    except Exception:
        return g
    return lax.psum(g, tuple(extra)) if extra else g


def _pad_wb(w, b, block_v, transpose_w=False):
    """Pad the vocab axis — dim 1 of a (D, V) weight, dim 0 of a (V, D)
    one (``transpose_w``, the tied-embedding layout) — up to a multiple of
    block_v. Padded bias is -1e30 so padded logits vanish from the
    logsumexp (exp(-1e30 - lse) == 0). No copy when V is already aligned
    (the usual case)."""
    vdim = 0 if transpose_w else 1
    v = w.shape[vdim]
    nblk = -(-v // block_v)
    pv = nblk * block_v
    if pv != v:
        pad = [(0, 0), (0, 0)]
        pad[vdim] = (0, pv - v)
        w = jnp.pad(w, pad)
        b = jnp.pad(b, (0, pv - v), constant_values=_NEG)
    return w, b, nblk


def _w_chunk(wp, j, block_v, transpose_w):
    """Slice chunk j of the vocab axis IN PLACE — (D, BV) from (D, V), or
    (BV, D) from (V, D) — never a transposed copy of the weight."""
    return lax.dynamic_slice_in_dim(wp, j * block_v, block_v,
                                    0 if transpose_w else 1)


def _chunk_logits(x, wb, transpose_w):
    """(N, D) x chunk -> (N, BV) fp32, contracting D in the chunk's native
    orientation (MXU takes either operand layout)."""
    if transpose_w:
        return jnp.einsum("nd,vd->nv", x, wb,
                          preferred_element_type=jnp.float32)
    return jnp.dot(x, wb, preferred_element_type=jnp.float32)


def _chunk_cols(j, block_v, axis, v):
    """Vocabulary ids of chunk j's columns. ``axis`` None: the whole
    vocabulary is here. Else this rank's slice of ``v`` rows starts at
    axis_index * v, and a column of its padded tail gets the id -1, which
    no label has: the tail's ids would be the next rank's first rows."""
    col = j * block_v + jnp.arange(block_v)
    if axis is None:
        return col
    return jnp.where(col < v, col + lax.axis_index(axis) * v, -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _lm_head_loss(block_v, transpose_w, axis, x, w, b, labels):
    loss, _ = _lm_head_fwd(block_v, transpose_w, axis, x, w, b, labels)
    return loss


def _vocab_mesh():
    """(mesh, tensor axis, batch axes) where the step is being traced under
    a mesh whose plan splits tensors over an axis of more than one device;
    (None, None, ()) with no mesh, a tensor axis of one device, or inside
    an enclosing shard_map (its axes are manual already: a pipeline
    stage, a dp-mapped step). `ops/attention._per_shard` reads the same
    two; it is left as it is because a Mosaic kernel's lowered body embeds
    its file's line numbers, and the one-chip step's text with them."""
    mesh, plan = current_trace_mesh(), current_trace_plan()
    axis = getattr(plan, "tensor_axis", None)
    if (mesh is None or axis is None or mesh.shape[axis] == 1
            or jax.sharding.get_abstract_mesh().manual_axes):
        return None, None, ()
    return mesh, axis, tuple(a for a in plan.batch_axes
                             if mesh.shape[a] > 1)


def lm_head_loss(block_v, x, w, b, labels, transpose_w=False):
    """x: (N, D); w: (D, V) — or (V, D) with ``transpose_w=True``, the
    tied-embedding layout where w IS the token-embedding table used in
    place; b: (V,); labels: (N,) int -> loss (N, 1) fp32.

    loss_i = logsumexp_v(x_i @ w + b) - (x_i @ w + b)[labels_i]

    Under a trace mesh with a tensor axis of more than one device the
    vocabulary is split over that axis and the rows over the plan's batch
    axes (module docstring). A V or N the axes do not divide is an error
    naming the shape, never a quiet switch to another path."""
    transpose_w = bool(transpose_w)
    mesh, axis, batch_axes = _vocab_mesh()
    if axis is None:
        FUSED_HEAD_TRACES.inc(path="local", ways=1)
        return _lm_head_loss(block_v, transpose_w, None, x, w, b, labels)
    ways = mesh.shape[axis]
    b_ways = math.prod(mesh.shape[a] for a in batch_axes)
    n = x.shape[0]
    if w.shape[0 if transpose_w else 1] % ways or n % b_ways:
        raise ValueError(
            "fused_lm_head_loss: w %s (transpose_w=%s) and x %s do not "
            "divide over mesh %s with tensor axis %r (vocabulary) and "
            "batch axes %s (rows)" % (w.shape, transpose_w, x.shape,
                                      dict(mesh.shape), axis, batch_axes))
    FUSED_HEAD_TRACES.inc(path="vocab_parallel", ways=ways)
    rows = batch_axes or None
    return jax.shard_map(
        functools.partial(_lm_head_loss, block_v, transpose_w, axis),
        mesh=mesh,
        in_specs=(P(rows, None),
                  P(axis, None) if transpose_w else P(None, axis),
                  P(axis), P(rows)),
        out_specs=P(rows, None))(x, w, b, labels.reshape(n))


def _lm_head_fwd(block_v, transpose_w, axis, x, w, b, labels):
    n = x.shape[0]
    labels = labels.reshape(n).astype(jnp.int32)
    wp, bp, nblk = _pad_wb(w, b, block_v, transpose_w)
    v = b.shape[0]
    xdt = x.dtype

    def body(j, carry):
        m, s, picked = carry
        wb = _w_chunk(wp, j, block_v, transpose_w).astype(xdt)
        bb = lax.dynamic_slice_in_dim(bp, j * block_v, block_v, 0)
        logits = _chunk_logits(x, wb, transpose_w) + bb
        col = _chunk_cols(j, block_v, axis, v)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[:, None]), axis=-1)
        hit = labels[:, None] == col[None, :]
        picked = picked + jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
        return m_new, s, picked

    init = tuple(_vary_like(c, x, labels, wp, bp) for c in
                 (jnp.full((n,), _NEG, jnp.float32),
                  jnp.zeros((n,), jnp.float32),
                  jnp.zeros((n,), jnp.float32)))
    m, s, picked = lax.fori_loop(0, nblk, body, init)
    if axis is not None:
        # the ranks' row statistics meet once, after the loop
        m_all = lax.pmax(m, axis)
        s = lax.psum(s * jnp.exp(m - m_all), axis)
        picked = lax.psum(picked, axis)
        m = m_all
    lse = m + jnp.log(s)
    loss = (lse - picked)[:, None]
    return loss, (x, w, b, labels, lse)


def _lm_head_bwd(block_v, transpose_w, axis, res, g):
    x, w, b, labels, lse = res
    n, d = x.shape
    v = w.shape[0 if transpose_w else 1]
    gl = g.reshape(n, 1).astype(jnp.float32)
    wp, bp, nblk = _pad_wb(w, b, block_v, transpose_w)
    pv = nblk * block_v
    xdt = x.dtype

    def body(j, carry):
        dx, dw, db = carry
        wb = _w_chunk(wp, j, block_v, transpose_w)
        bb = lax.dynamic_slice_in_dim(bp, j * block_v, block_v, 0)
        wbx = wb.astype(xdt)
        logits = _chunk_logits(x, wbx, transpose_w) + bb
        p = jnp.exp(logits - lse[:, None])  # padded cols: exp(-1e30-lse)=0
        col = _chunk_cols(j, block_v, axis, v)
        hit = labels[:, None] == col[None, :]
        gch = (p - hit.astype(jnp.float32)) * gl  # (N, BV) fp32
        gchx = gch.astype(xdt)
        if transpose_w:
            dwb = jnp.einsum("nv,nd->vd", gchx, x,
                             preferred_element_type=jnp.float32)
            dx = dx + jnp.dot(gchx, wbx,
                              preferred_element_type=jnp.float32)
            dw = lax.dynamic_update_slice_in_dim(dw, dwb, j * block_v, 0)
        else:
            dwb = jnp.dot(x.T, gchx, preferred_element_type=jnp.float32)
            dx = dx + jnp.dot(gchx, wbx.T,
                              preferred_element_type=jnp.float32)
            dw = lax.dynamic_update_slice_in_dim(dw, dwb, j * block_v, 1)
        dbb = jnp.sum(gch, axis=0)
        db = lax.dynamic_update_slice_in_dim(db, dbb, j * block_v, 0)
        return dx, dw, db

    dw_shape = (pv, d) if transpose_w else (d, pv)
    init = tuple(_vary_like(c, x, labels, g, wp, bp) for c in
                 (jnp.zeros((n, d), jnp.float32),
                  jnp.zeros(dw_shape, jnp.float32),
                  jnp.zeros((pv,), jnp.float32)))
    dx, dw, db = lax.fori_loop(0, nblk, body, init)
    dw = dw[:v] if transpose_w else dw[:, :v]
    # as written dx is summed over the tensor axis in float32 and rounded
    # after (the TPU compiler moves the rounding first: PERF.md §6, PR 34)
    return (_grad_vma_like(dx, x).astype(x.dtype),
            _grad_vma_like(dw.astype(w.dtype), w),
            _grad_vma_like(db[:v].astype(b.dtype), b), None)


_lm_head_loss.defvjp(_lm_head_fwd, _lm_head_bwd)


@register_op("fused_lm_head_loss")
def _fused_lm_head_loss(ctx):
    """Inputs X: (..., D), W: (D, V), Bias: (V,) optional, Label: (..., 1)
    or (...,) int. Output Loss: (N, 1) fp32 per-token loss, N = prod of
    X's leading dims. Attr block_v: vocab chunk size (a power of two >= 128).
    Attr transpose_w: W is (V, D) — the tied-embedding layout, where W is
    the token-embedding table itself used in place."""
    from .attention import block_attr

    x = ctx.input("X")
    w = ctx.input("W")
    labels = ctx.input("Label")
    transpose_w = bool(ctx.attr("transpose_w", False))
    block_v = block_attr("fused_lm_head_loss", "block_v",
                         ctx.attr("block_v", 4096))
    d = x.shape[-1]
    xf = x.reshape(-1, d)
    b = ctx.input("Bias")
    if b is None:
        b = jnp.zeros((w.shape[0 if transpose_w else 1],), jnp.float32)
    loss = lm_head_loss(block_v, xf, w, b.astype(jnp.float32),
                        labels.reshape(-1), transpose_w=transpose_w)
    return {"Loss": loss}
