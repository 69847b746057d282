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
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register_op

_NEG = -1e30


def _unroll_chunks(nblk: int) -> bool:
    """Sweep lever: PADDLE_TPU_LMHEAD_UNROLL=N
    unrolls the vocab-chunk loop when nblk <= N. Off by default — the
    rolled loop compiles faster and the win is hardware-dependent."""
    import os

    try:
        limit = int(os.environ.get("PADDLE_TPU_LMHEAD_UNROLL", "0"))
    except ValueError:
        limit = 0
    return 0 < nblk <= limit


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


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _lm_head_loss(block_v, transpose_w, x, w, b, labels):
    loss, _ = _lm_head_fwd(block_v, transpose_w, x, w, b, labels)
    return loss


def lm_head_loss(block_v, x, w, b, labels, transpose_w=False):
    """x: (N, D); w: (D, V) — or (V, D) with ``transpose_w=True``, the
    tied-embedding layout where w IS the token-embedding table used in
    place; b: (V,); labels: (N,) int -> loss (N, 1) fp32.

    loss_i = logsumexp_v(x_i @ w + b) - (x_i @ w + b)[labels_i]
    """
    return _lm_head_loss(block_v, bool(transpose_w), x, w, b, labels)


def _lm_head_fwd(block_v, transpose_w, x, w, b, labels):
    n = x.shape[0]
    labels = labels.reshape(n).astype(jnp.int32)
    wp, bp, nblk = _pad_wb(w, b, block_v, transpose_w)
    xdt = x.dtype

    def body(j, carry):
        m, s, picked = carry
        wb = _w_chunk(wp, j, block_v, transpose_w).astype(xdt)
        bb = lax.dynamic_slice_in_dim(bp, j * block_v, block_v, 0)
        logits = _chunk_logits(x, wb, transpose_w) + bb
        col = j * block_v + jnp.arange(block_v)
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
    if _unroll_chunks(nblk):
        # unrolled: XLA overlaps chunk matmuls with the next chunk's
        # weight DMA instead of serializing through a while-loop barrier
        carry = init
        for j in range(nblk):
            carry = body(j, carry)
        m, s, picked = carry
    else:
        m, s, picked = lax.fori_loop(0, nblk, body, init)
    lse = m + jnp.log(s)
    loss = (lse - picked)[:, None]
    return loss, (x, w, b, labels, lse)


def _lm_head_bwd(block_v, transpose_w, res, g):
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
        col = j * block_v + jnp.arange(block_v)
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
    if _unroll_chunks(nblk):
        carry = init
        for j in range(nblk):
            carry = body(j, carry)
        dx, dw, db = carry
    else:
        dx, dw, db = lax.fori_loop(0, nblk, body, init)
    dw = dw[:v] if transpose_w else dw[:, :v]
    return (_grad_vma_like(dx.astype(x.dtype), x),
            _grad_vma_like(dw.astype(w.dtype), w),
            _grad_vma_like(db[:v].astype(b.dtype), b), None)


_lm_head_loss.defvjp(_lm_head_fwd, _lm_head_bwd)


@register_op("fused_lm_head_loss")
def _fused_lm_head_loss(ctx):
    """Inputs X: (..., D), W: (D, V), Bias: (V,) optional, Label: (..., 1)
    or (...,) int. Output Loss: (N, 1) fp32 per-token loss, N = prod of
    X's leading dims. Attr block_v: vocab chunk size (multiple of 128).
    Attr transpose_w: W is (V, D) — the tied-embedding layout, where W is
    the token-embedding table itself used in place."""
    from .attention import _env_block

    x = ctx.input("X")
    w = ctx.input("W")
    labels = ctx.input("Label")
    transpose_w = bool(ctx.attr("transpose_w", False))
    # env override for on-hardware sweeps,
    # validated like the flash-attention block knobs
    block_v = _env_block("PADDLE_TPU_LMHEAD_BLOCK",
                         ctx.attr("block_v", 4096))
    d = x.shape[-1]
    xf = x.reshape(-1, d)
    b = ctx.input("Bias")
    if b is None:
        b = jnp.zeros((w.shape[0 if transpose_w else 1],), jnp.float32)
    loss = lm_head_loss(block_v, xf, w, b.astype(jnp.float32),
                        labels.reshape(-1), transpose_w=transpose_w)
    return {"Loss": loss}
