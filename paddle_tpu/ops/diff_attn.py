"""Differential attention (arXiv:2410.05258) for serving, and the gated
memory unit of a decoder-hybrid-decoder (arXiv:2507.06607).

A differential layer splits its ``H`` query heads of ``dh`` into pairs
``(2j, 2j + 1)`` and its ``Hkv`` key/value heads into pairs ``(2p, 2p +
1)``; query pair ``j`` reads key/value pair ``p = j // g`` (``g = H /
Hkv``). With ``A1 = softmax(q_2j . k_2p / sqrt(dh))`` and ``A2 =
softmax(q_2j+1 . k_2p+1 / sqrt(dh))`` under the layer's mask,

    O_j = (A1 - lam * A2) [v_2p | v_2p+1]                    (2 dh wide)
    O_j <- rms_norm(O_j; gain, eps) * (1 - lam_init)
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init

So query head ``2j`` and ``2j + 1`` do NOT share a key head, and both
read both value heads: no grouped-query kernel computes it as it
stands. THE PAIR LAYOUT makes it one: a key/value row is read as ``P =
Hkv / 2`` pair-heads of ``2 dh`` (the same ``Hkv * dh`` floats in the
same order: a view, never a copy of a slab), and a query head's ``dh``
values are laid into the half of a ``2 dh`` row that its key lies in,
zeros in the other half (``pair_queries``). Then ``q'_h . K_p = q_h .
k_(2p + h % 2)`` exactly (the zeros add nothing), the value of
pair-head ``p`` IS ``[v_2p | v_2p+1]``, and query head ``h`` reads
pair-head ``h // (2 g)``: grouped queries at ``2 dh``. In a prefill
the kernels the repo has compute that (the causal flash kernel and the
window kernel, lane-aligned at ``dh`` = 64). The only cost is the zero
half of the score product, 1.33x an attention's FLOPs and no byte of a
slab. What is left is a subtraction and a norm (``diff_combine``).

Slabs and rings keep a position's row FLAT: ``(B, S, Hkv * dh)``, the
projection's own output, heads in order, so pair-head ``p`` is the
columns ``[2 dh p, 2 dh (p + 1))``. On a TPU a 4-D slab ``(B, S, P, 2
dh)`` whose heads do not fill a sublane tile (``P`` = 10) is laid out
heads-major by the compiler, and the one-row append then costs two
relayout copies of the WHOLE slab a step (compiled for a described
v5e: four 1.25 GiB copies, 2 GiB of temporaries); a flat row is a
multiple of 128 lanes wide, the append is a row scatter in place, and a
pair-head's columns are a lane-aligned slice that the score and value
products read where they lie. Two paths attend such rows, chosen by
shape, dtype and device (``kv_cache.decode_stream_rows`` of
``rows_view``), numerics one: the Pallas kernel ``ptpu.diff_attn_rows``
over a slab (float32 on a TPU), which is the streamed two-pass body of
``ops/decode_stream.py`` under this file's VIEW (``rows_view``: a (1,
rows, P w) block of the slab itself, a pair-head's keys and values its
lane slice); and ``_attend_rows_lax``, exact and pure lax, one product
pair a pair-head, which reads the whole slab whatever the lengths:
every other device, and a ring (one block a slot and nearly all of it
live: the kernel has no dead rows to skip there; PERF.md, PR 32).

Four ops, one scope each:

- ``diff_attention`` (``ptpu.diff_attn``): a prefill's whole sequence,
  causal or causal within a window.
- ``diff_decode_attention`` (``ptpu.diff_attn_slab`` |
  ``ptpu.diff_attn_ring``): one token against the layer's own slab or
  ring of flat rows, after this step's row was written.
- ``attn_cross`` (``ptpu.attn_cross``): one query row against ANOTHER
  layer's keys and values (a slab in a step, a prompt's rows in a
  prefill), to which it appends nothing.
- ``gmu`` (``ptpu.gmu``): ``(M * silu(u W_in)) W_out``, ``M`` the
  memory a state-space layer handed on, row for row.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from . import attention as _A
from . import decode_stream as _DS
from . import kv_cache as _KV
from .registry import register_op
from .ssm import rms_norm

DIFF_ATTN = "ptpu.diff_attn"
DIFF_ATTN_SLAB = "ptpu.diff_attn_slab"
DIFF_ATTN_RING = "ptpu.diff_attn_ring"
ATTN_CROSS = "ptpu.attn_cross"
GMU = "ptpu.gmu"
# the decode kernel over a slab of flat rows: its call's name in lowered
# text and device traces
DIFF_ATTN_ROWS = "ptpu.diff_attn_rows"

_NEG = -1e30


def lambda_init(depth: int) -> float:
    """``lam_init`` of the layer at ``depth`` (0-based)."""
    return 0.8 - 0.6 * math.exp(-0.3 * depth)


def diff_lambda(lq1, lk1, lq2, lk2, lam_init):
    """The layer's one ``lam``, float32."""
    f32 = jnp.float32
    return (jnp.exp(jnp.sum(lq1.astype(f32) * lk1.astype(f32)))
            - jnp.exp(jnp.sum(lq2.astype(f32) * lk2.astype(f32)))
            + lam_init)


def pair_queries(q):
    """q (B, T, H, dh) -> (B, T, H, 2 dh): head ``h``'s values in half
    ``h % 2`` of its row, zeros in the other."""
    zero = jnp.zeros_like(q)
    even = (jnp.arange(q.shape[2]) % 2 == 0)[None, None, :, None]
    return jnp.where(even, jnp.concatenate([q, zero], axis=-1),
                     jnp.concatenate([zero, q], axis=-1))


def diff_combine(ctx, lam, gain, lam_init, eps):
    """ctx (B, T, H, 2 dh), head ``h``'s softmax over its pair's values
    -> (B, T, H / 2, 2 dh): ``ctx[2j] - lam * ctx[2j + 1]``, normalised
    by its own RMS times ``gain``, times ``1 - lam_init``."""
    b, t, h, w = ctx.shape
    both = ctx.reshape(b, t, h // 2, 2, w)
    out = both[:, :, :, 0] - lam.astype(ctx.dtype) * both[:, :, :, 1]
    return rms_norm(out, gain, eps) * jnp.asarray(1.0 - lam_init, ctx.dtype)


def _check(q, k, v):
    """(pair-heads P, 1 / sqrt(dh)) of queries (B, T, H, dh) on flat
    key/value rows (B, S, Hkv dh)."""
    h, dh = q.shape[2], q.shape[3]
    pairs, rem = divmod(k.shape[-1], 2 * dh)
    if (k.ndim != 3 or k.shape != v.shape or rem or not pairs
            or h % (2 * pairs)):
        raise ValueError(
            "differential attention: %d query heads of %d need flat "
            "key/value rows (B, S, Hkv x %d) of an even Hkv whose pairs "
            "divide the query pairs; got K %s V %s"
            % (h, dh, dh, k.shape, v.shape))
    return pairs, 1.0 / math.sqrt(dh)


def rows_view(s, h, row, w, dtype, block_s=512):
    """The view (``ops/decode_stream.py``) of (B, s, row) flat rows of
    ``dtype`` under ``h`` paired query heads of ``w``: a (1, rows, P w)
    block of the slab itself a step, at most ``block_s`` rows of it;
    pair-head p's keys and values are the lanes [p w, (p + 1) w) of a
    block (a lane-aligned slice where the row is a multiple of 128
    lanes), and the g paired query rows that read it meet them in one
    (g, w) x (w, BS) product."""
    f32 = jnp.float32

    def lanes(ref, p):
        return ref[0, :, p * w:(p + 1) * w]

    return _DS.StreamView(
        DIFF_ATTN_ROWS, seq=s, dtype=dtype,
        most=_DS.rows_within(row * 4, block_s), score_rows=h,
        whole_tiles=row % 128 == 0, lanes=row, q_block=(1, 1, h, w),
        k_block=(1, 1, row), v_block=(1, 1, row), o_block=(1, 1, h, w),
        groups=row // w,
        scores=lambda p, hh, q_ref, k_ref: jnp.dot(
            q_ref[0, 0, hh, :], lanes(k_ref, p).T,
            preferred_element_type=f32),
        values=lambda p, wts, v_ref: jnp.dot(
            wts, lanes(v_ref, p), preferred_element_type=f32))


def pallas_attend_rows(qp, k, v, lengths, scale, block_s=512,
                       interpret=False):
    """``_attend_rows_lax``'s contract through the kernel: the slab is
    handed over as it lies, a (1, rows, P w) block a step."""
    _, _, h, w = qp.shape
    view = rows_view(k.shape[1], h, k.shape[2], w, k.dtype, block_s)
    lens = lengths.reshape(-1).astype(jnp.int32)
    return _DS.stream_attend(view, lens, qp * jnp.asarray(scale, qp.dtype),
                             k, v, interpret)


def _attend_rows(qp, k, v, lengths, scale, ring=False):
    """Paired queries qp (B, 1, H, w) on flat rows k, v (B, S, P w), rows
    ``[0, lengths)`` seen -> (B, 1, H, w): the kernel where the rows'
    shape, type and the device allow it and the rows are a slab's, the
    lax path otherwise."""
    _, _, h, w = qp.shape
    if not ring and _KV.decode_stream_rows(rows_view(
            k.shape[1], h, k.shape[2], w, k.dtype)) is not None:
        return pallas_attend_rows(qp, k, v, lengths, scale)
    return _attend_rows_lax(qp, k, v, lengths, scale)


def _attend_rows_lax(qp, k, v, lengths, scale):
    """``_attend_rows``, exact and pure lax, the arithmetic of
    ``kv_cache.decode_attention_reference``: a pair-head at a time over
    its lane-aligned columns. A slot of length 0 gives zeros."""
    b, _, h, w = qp.shape
    s, pairs = k.shape[1], k.shape[2] // w
    f32 = jnp.float32
    qf = (qp[:, 0].astype(f32) * scale).reshape(b, pairs, h // pairs, w)
    valid = (jnp.arange(s)[None, None, :]
             < lengths.reshape(-1).astype(jnp.int32)[:, None, None])
    out = []
    for p in range(pairs):
        kp = lax.slice_in_dim(k, p * w, (p + 1) * w, axis=2).astype(f32)
        vp = lax.slice_in_dim(v, p * w, (p + 1) * w, axis=2).astype(f32)
        sc = jnp.where(valid, jnp.einsum("bgd,bsd->bgs", qf[:, p], kp),
                       _NEG)
        m = jnp.max(sc, axis=-1, keepdims=True)
        pr = jnp.where(valid, jnp.exp(sc - m), 0.0)
        l = jnp.sum(pr, axis=-1, keepdims=True)
        out.append(jnp.einsum("bgs,bsd->bgd", pr / jnp.maximum(l, 1e-30),
                              vp))
    return jnp.stack(out, axis=1).reshape(b, 1, h, w).astype(qp.dtype)


def diff_attention(q, k, v, lam, gain, lam_init, window=0, eps=1e-5,
                   lengths=None):
    """A whole sequence: q (B, T, H, dh), k/v (B, T, Hkv dh) flat rows
    -> (B, T, H / 2, 2 dh). Key j is visible to query t iff j <= t, and
    with ``window`` also j > t - window. ``lengths`` (B,): the rows'
    live tokens, for the kernel to skip what lies past them
    (``attention.prefill_attention``)."""
    pairs, scale = _check(q, k, v)
    with jax.named_scope(DIFF_ATTN):
        qp = pair_queries(q)
        # a prefill's rows are activations: their pair view costs what
        # a reshape of a few megabytes costs
        k = k.reshape(k.shape[:2] + (pairs, -1))
        v = v.reshape(v.shape[:2] + (pairs, -1))
        ctx = _A.prefill_attention(qp, k, v, lengths, window=int(window),
                                   scale=scale)
        return diff_combine(ctx, lam, gain, lam_init, eps)


def diff_decode_attention(q, k_cache, v_cache, lengths, lam, gain, lam_init,
                          ring=False, eps=1e-5, scope=None):
    """One token: q (B, 1, H, dh) against a slab (B, S, Hkv dh), or a
    ring (B, W, Hkv dh) with ``ring``, of flat rows; ``lengths`` (B,)
    the positions held INCLUDING the row this query may see last (a
    ring that has wrapped shows all its rows: a softmax does not care
    for the order of its keys). -> (B, 1, H / 2, 2 dh)."""
    _, scale = _check(q, k_cache, v_cache)
    with jax.named_scope(scope or (DIFF_ATTN_RING if ring
                                   else DIFF_ATTN_SLAB)):
        seen = lengths.reshape(-1).astype(jnp.int32)
        if ring:
            seen = jnp.minimum(seen, k_cache.shape[1])
        ctx = _attend_rows(pair_queries(q), k_cache, v_cache, seen, scale,
                           ring=ring)
        return diff_combine(ctx, lam, gain, lam_init, eps)


def attn_cross(q, k, v, lengths, lam, gain, lam_init, eps=1e-5):
    """``diff_decode_attention`` over keys and values another layer
    owns: a slab (B, S, Hkv dh) in a step, a prompt's rows (B, T, Hkv
    dh) in a prefill; rows ``[0, lengths)`` are seen."""
    return diff_decode_attention(q, k, v, lengths, lam, gain, lam_init,
                                 eps=eps, scope=ATTN_CROSS)


def gmu(u, memory, w_in, w_out):
    """u (B, T, D), memory (B, T, Di), w_in (D, Di), w_out (Di, D) ->
    (B, T, D): ``(memory * silu(u w_in)) w_out``."""
    with jax.named_scope(GMU):
        g = jnp.matmul(u, w_in)
        return jnp.matmul(memory * (g * jax.nn.sigmoid(g)), w_out)


def _lam(ctx):
    return diff_lambda(ctx.input("LQ1"), ctx.input("LK1"), ctx.input("LQ2"),
                       ctx.input("LK2"), float(ctx.attr("lam_init")))


@register_op("diff_attention")
def _diff_attention_op(ctx):
    """Inputs Q (B, T, H, dh), K, V (B, T, Hkv dh), LQ1, LK1, LQ2, LK2
    (dh,), Gain (2 dh,), optional Lengths (B,); attrs lam_init, window
    (0: causal), epsilon -> Out (B, T, H / 2, 2 dh)."""
    return {"Out": diff_attention(
        ctx.input("Q"), ctx.input("K"), ctx.input("V"), _lam(ctx),
        ctx.input("Gain"), float(ctx.attr("lam_init")),
        window=int(ctx.attr("window", 0) or 0),
        eps=float(ctx.attr("epsilon", 1e-5)),
        lengths=ctx.input("Lengths"))}


@register_op("diff_decode_attention")
def _diff_decode_attention_op(ctx):
    """Inputs Q (B, 1, H, dh), KCache, VCache (B, S | W, Hkv dh),
    Lengths (B,), the four lambda vectors, Gain; attrs lam_init, ring,
    epsilon -> Out (B, 1, H / 2, 2 dh)."""
    return {"Out": diff_decode_attention(
        ctx.input("Q"), ctx.input("KCache"), ctx.input("VCache"),
        ctx.input("Lengths"), _lam(ctx), ctx.input("Gain"),
        float(ctx.attr("lam_init")), ring=bool(ctx.attr("ring", False)),
        eps=float(ctx.attr("epsilon", 1e-5)))}


@register_op("attn_cross")
def _attn_cross_op(ctx):
    """As ``diff_decode_attention`` without ``ring``; K and V are
    another layer's."""
    return {"Out": attn_cross(
        ctx.input("Q"), ctx.input("KCache"), ctx.input("VCache"),
        ctx.input("Lengths"), _lam(ctx), ctx.input("Gain"),
        float(ctx.attr("lam_init")), eps=float(ctx.attr("epsilon", 1e-5)))}


@register_op("gmu")
def _gmu_op(ctx):
    """Inputs X (B, T, D), Memory (B, T, Di), WIn (D, Di), WOut (Di, D)
    -> Out = X's shape."""
    return {"Out": gmu(ctx.input("X"), ctx.input("Memory"),
                       ctx.input("WIn"), ctx.input("WOut"))}
