"""EVA as EvaByte serves it (Zheng et al., "Efficient Attention via
Control Variates", ICLR 2023): a query attends the keys of ITS OWN
window of ``W`` positions exactly and every EARLIER window through one
pooled key and value a chunk of ``C`` positions, all under one softmax.

With ``w(t) = t // W`` and ``s`` the scale, a head's two learned vectors
``phi`` and ``mu`` and the ROTATED keys of chunk ``c`` (positions ``[C c,
C c + C)``):

    a_i  = softmax_{i in chunk c}(s * phi . k_i)
    k~_c = sum_i a_i k_i + mu          v~_c = sum_i a_i v_i

and the query at ``t`` sees the keys ``W w(t) <= j <= t`` and the
summaries ``c < (W / C) w(t)``: the chunks of every window that has
CLOSED before its own, never a partial chunk.

What a slot keeps is ONE array for K and one for V a layer, ``(slots,
max_len / C + W, heads, width)`` (``serving.decode.cache_spec``, kind
``eva``), laid out so that its live rows are one range:

- rows ``[0, max_len / C)``: the summaries, LAST FIRST: chunk ``c`` at
  row ``max_len / C - 1 - c`` (a softmax does not care for the order of
  its keys, and a summary keeps no position of its own: the keys were
  rotated before they were pooled);
- rows ``[max_len / C, max_len / C + W)``: the window block, position
  ``p`` at row ``max_len / C + p mod W``. Not a ring: its live rows are
  ``[0, p mod W]``, so it RESTARTS empty each time a window closes.

A step at position ``t`` attends rows ``[max_len / C - (W / C) w(t),
max_len / C + t mod W]``: the visible summaries, then the block's live
rows, with nothing between them. That is ``decode_stream``'s two-pass
body over a live range that does not start at row 0 (``eva_view``), and
an exact lax path beside it.

Five functions, each under its own scope so that a trace tells them:
``eva_summaries`` and ``eva_prefill`` (a prompt: all chunks at once;
each window's queries against [that window's visible summaries | its
own rows, causal], on a TPU two flash forward calls on bfloat16 operands
merged by their log-sum-exp, so no ``(T, T)`` mask exists), ``eva_pack``
(the prompt's entry as a step will find it), ``eva_append`` (a step's
row into the block; where the position closes a chunk, that chunk's two
summary rows, pooled from the block) and ``eva_decode``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from . import decode_stream as _DS
from . import kv_cache as _KV
from .attention import (_FLASH_BLOCK, _fit_block, _mha_fwd_call_bthd,
                        _use_pallas, flash_operand)
from .registry import register_op

_NEG = -1e30

EVA_SUMMARIES = "ptpu.eva_summaries"
EVA_PREFILL = "ptpu.eva_prefill"
EVA_APPEND = "ptpu.eva_append"
EVA_ATTN = "ptpu.eva_attn"


def live_range(pos, window: int, chunk: int, n_sum: int):
    """(start, end) of the rows a query at position ``pos`` (B,)
    attends, ``[start, end)`` of an entry whose block begins at row
    ``n_sum``: the summaries of the ``pos // window`` closed windows,
    then the block's rows up to its own."""
    pos = pos.reshape(-1).astype(jnp.int32)
    start = n_sum - (window // chunk) * (pos // window)
    return jnp.maximum(start, 0), n_sum + pos % window + 1


def _pool(kc, vc, phi, mu, scale):
    """Chunks kc, vc (..., C, H, D) -> their summaries (..., H, D) x 2,
    in float32 multiplies and adds (no contraction: nothing is rounded
    to bfloat16 on the way)."""
    kf, vf = kc.astype(jnp.float32), vc.astype(jnp.float32)
    a = jax.nn.softmax(scale * jnp.sum(kf * phi.astype(jnp.float32), -1),
                       axis=-2)[..., None]                 # (..., C, H, 1)
    return (jnp.sum(a * kf, axis=-3) + mu.astype(jnp.float32),
            jnp.sum(a * vf, axis=-3))


def eva_summaries(k, v, phi, mu, chunk, scale=None):
    """A prompt's rotated keys and its values (B, T, H, D), phi and mu
    (H, D) -> (k~, v~) (B, T / C, H, D): every chunk of the bucket (a
    chunk the prompt did not fill pools padding, and is never live)."""
    b, t, h, d = k.shape
    c = int(chunk)
    if t % c:
        raise ValueError("eva_summaries: %d rows are no whole chunks of %d"
                         % (t, c))
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    with jax.named_scope(EVA_SUMMARIES):
        ks, vs = _pool(k.reshape(b, t // c, c, h, d),
                       v.reshape(b, t // c, c, h, v.shape[-1]), phi, mu,
                       scale)
        return ks.astype(k.dtype), vs.astype(v.dtype)


def _window_attend_lax(q, k, v, ks, vs, scale):
    """One window, exact: q, k, v (B, Wq, H, D) of the window, ks, vs
    (B, n, H, D) the summaries it sees -> (B, Wq, H, D)."""
    wq, n = q.shape[1], ks.shape[1]
    keys = jnp.concatenate([ks, k], axis=1).astype(jnp.float32)
    vals = jnp.concatenate([vs, v], axis=1).astype(jnp.float32)
    s = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32) * scale, keys)
    row = jnp.arange(wq)[:, None]
    col = jnp.arange(n + wq)[None, :]
    seen = (col < n) | (col - n <= row)
    p = jax.nn.softmax(jnp.where(seen, s, _NEG), axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p, vals).astype(q.dtype)


def _flash(q, k, v, lengths, causal, interpret):
    """The flash forward on bfloat16 operands, (B, T, H, D) in and out
    (float32), with its log-sum-exp (B, T, H): q pre-scaled; the
    q-blocks wholly past ``lengths`` give zeros (out and lse)."""
    b, t, h, d = q.shape
    tk = k.shape[1]
    block_q = _fit_block(t, _FLASH_BLOCK)
    block_k = _fit_block(tk, _FLASH_BLOCK)
    out, lse = _mha_fwd_call_bthd(
        flash_operand(q), flash_operand(k), flash_operand(v), h, causal,
        block_q, block_k, interpret, name=EVA_PREFILL, lengths=lengths,
        out_dtype=jnp.float32)
    return (out.reshape(b, t, h, -1)[..., :v.shape[-1]],
            jnp.swapaxes(lse.reshape(b, h, t), 1, 2))


def eva_prefill(q, k, v, ks, vs, lengths, window, chunk, scale=None,
                interpret=False):
    """A prompt's attention: q, k (rotated), v (B, T, H, D), the
    summaries ks, vs (B, T / C, H, D), ``lengths`` (B,) -> (B, T, H, D).
    Window ``i``'s queries (rows ``[i W, (i + 1) W)``) see the summaries
    of chunks ``[0, i W / C)`` and their own window's keys, causal.

    On a TPU at a block-aligned window (``attention._use_pallas``): the
    windows are folded into the batch for ONE causal flash call over
    each window's own rows, window ``i >= 1`` makes one more over its
    ``i W / C`` summaries, and the two merge exactly by their
    log-sum-exp; bfloat16 operands, float32 statistics, the q-blocks
    past a row's length skipped, no (T, T) mask. Elsewhere the exact lax
    form a window at a time (its scores are (W, i W / C + W))."""
    b, t, h, d = q.shape
    w, c = int(window), int(chunk)
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    with jax.named_scope(EVA_PREFILL):
        if t <= w:  # window 0 alone: plain causal attention
            if not (interpret or _use_pallas(t, t, None, 0.0)):
                return _window_attend_lax(q, k, v, ks[:, :0], vs[:, :0],
                                          scale)
            out, _ = _flash(q * jnp.asarray(scale, q.dtype), k, v, lengths,
                            True, interpret)
            return out.astype(q.dtype)
        if t % w:  # a bucket that ends inside a window: pad that window
            rows = [(0, 0), (0, w - t % w), (0, 0), (0, 0)]
            return eva_prefill(jnp.pad(q, rows), jnp.pad(k, rows),
                               jnp.pad(v, rows), ks, vs, lengths, w, c,
                               scale, interpret)[:, :t]
        nw, per = t // w, w // c
        if not (interpret or _use_pallas(w, w, None, 0.0)):
            return jnp.concatenate([
                _window_attend_lax(
                    q[:, i * w:(i + 1) * w], k[:, i * w:(i + 1) * w],
                    v[:, i * w:(i + 1) * w], ks[:, :i * per],
                    vs[:, :i * per], scale) for i in range(nw)], axis=1)
        qs = q * jnp.asarray(scale, q.dtype)
        lens = lengths.reshape(-1).astype(jnp.int32)
        # live rows of (prompt, window): the batch of the folded call
        live = jnp.clip(lens[:, None] - w * jnp.arange(nw)[None, :], 0, w)

        def fold(x):
            return x.reshape((b * nw, w) + x.shape[2:])

        own, lse = _flash(fold(qs), fold(k), fold(v), live.reshape(-1),
                          True, interpret)
        own = own.reshape(b, nw, w, h, -1)
        lse = lse.reshape(b, nw, w, h)
        outs = [own[:, 0]]
        for i in range(1, nw):
            far, lse_far = _flash(qs[:, i * w:(i + 1) * w], ks[:, :i * per],
                                  vs[:, :i * per], live[:, i], False,
                                  interpret)
            both = jnp.logaddexp(lse[:, i], lse_far)
            outs.append(own[:, i] * jnp.exp(lse[:, i] - both)[..., None]
                        + far * jnp.exp(lse_far - both)[..., None])
        return jnp.concatenate(outs, axis=1).astype(q.dtype)


def eva_pack(x, xs, lengths, window, n_sum):
    """A prompt's rows x (B, T, H, D) (rotated keys, or values) and their
    summaries xs (B, T / C, H, D) -> the entry (B, n_sum + W, H, D) an
    admission stores: the summaries last first in the rows before
    ``n_sum`` (zeros before them), then the block: the rows of the
    window the NEXT position lies in, ``[W (len // W), ..)`` (whatever
    a full bucket's last window holds where the next position opens a
    window the bucket does not reach: no row of it is live). One
    contiguous slice a row under ``vmap``, never an elementwise gather
    (``kv_cache.ring_pack`` says why)."""
    w = int(window)
    b, t = x.shape[0], x.shape[1]
    if xs.shape[1] > n_sum:
        raise ValueError("eva_pack: %d chunks do not fit %d summary rows"
                         % (xs.shape[1], n_sum))
    with jax.named_scope(EVA_APPEND):
        if t < w:
            x = jnp.pad(x, [(0, 0), (0, w - t)] + [(0, 0)] * (x.ndim - 2))
            t = w
        at = jnp.minimum(lengths.reshape(-1).astype(jnp.int32) // w,
                         t // w - 1) * w
        block = jax.vmap(lambda row, a: lax.dynamic_slice_in_dim(
            row, a, w, axis=0))(x, at)
        pad = jnp.zeros((b, n_sum - xs.shape[1]) + xs.shape[2:], x.dtype)
        return jnp.concatenate([pad, jnp.flip(xs, axis=1).astype(x.dtype),
                                block], axis=1)


def eva_append(kc, vc, k_new, v_new, pos, phi, mu, window, chunk,
               scale=None):
    """One step's writes: entries kc, vc (B, R, H, D), this position's
    rotated key and value (B, 1, H, D), ``pos`` (B,) the position
    written -> (kc, vc). The row goes into the block at ``pos mod W``;
    where ``pos`` closes a chunk (``pos mod C == C - 1``) that chunk's
    two summaries, pooled from the block's rows (the row just written
    among them), go to the chunk's summary row. A slot whose position
    closes none writes its summaries nowhere (an index past the
    entry, dropped)."""
    w, c = int(window), int(chunk)
    b, r = kc.shape[0], kc.shape[1]
    n_sum = r - w
    scale = 1.0 / math.sqrt(kc.shape[-1]) if scale is None else scale
    with jax.named_scope(EVA_APPEND):
        pos = pos.reshape(-1).astype(jnp.int32)
        at = pos % w
        kc = _KV.cache_append(kc, k_new, n_sum + at)
        vc = _KV.cache_append(vc, v_new, n_sum + at)
        first = n_sum + at // c * c

        def rows(cache):
            # a slice a slot of the entry where it lies: under ``vmap``
            # it is a gather, for which the compiler lays the whole
            # entry out anew (a copy of it a step)
            return jnp.concatenate([lax.dynamic_slice(
                cache, (i, first[i], 0, 0), (1, c) + cache.shape[2:])
                for i in range(b)])

        ks, vs = _pool(rows(kc), rows(vc), phi, mu, scale)
        to = jnp.where(pos % c == c - 1, n_sum - 1 - pos // c, r)
        to = jnp.where(to < 0, r, to)
        slot = jnp.arange(b)
        return (kc.at[slot, to].set(ks.astype(kc.dtype), mode="drop"),
                vc.at[slot, to].set(vs.astype(vc.dtype), mode="drop"))


def eva_decode_reference(q, kc, vc, start, end, scale=None):
    """Exact lax attention of q (B, 1, H, D) over the rows ``[start,
    end)`` (B,) of the entries kc, vc (B, R, H, D): the CPU's path, and
    the kernel's check. An empty range gives zeros."""
    b, _, h, d = q.shape
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    s = jnp.einsum("bhd,bshd->bhs", q[:, 0].astype(jnp.float32) * scale,
                   kc.astype(jnp.float32))
    row = jnp.arange(kc.shape[1])[None, None, :]
    seen = ((row >= start.reshape(-1)[:, None, None])
            & (row < end.reshape(-1)[:, None, None]))
    s = jnp.where(seen, s, _NEG)
    p = jnp.where(seen, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bhs,bshd->bhd", p, vc.astype(jnp.float32))
    return out[:, None].astype(q.dtype)


def eva_view(rows, h, d, dtype, block_s=512):
    """The view (``ops/decode_stream.py``) of a step's attention over
    entries (B, rows, h, d) of ``dtype``: ``kv_cache.decode_view``'s
    blocks of the array where it lies, a head's strided rows against
    its ONE query row (``h`` groups of one), under the two-pass body,
    which is handed the live range's start beside its end."""
    return _DS.StreamView(
        EVA_ATTN, seq=rows, dtype=dtype,
        most=_DS.rows_within(h * d * 4, block_s), whole_tiles=h % 8 == 0,
        lanes=d, score_rows=h, q_block=(1, 1, h, d), k_block=(1, 1, h, d),
        v_block=(1, 1, h, d), o_block=(1, 1, h, d), groups=h,
        scores=_KV._grouped_scores, values=_KV._grouped_values)


def eva_decode(q, kc, vc, pos, window, chunk, scale=None, interpret=False):
    """A step's attention: q (B, 1, H, D) at position ``pos`` (B,), the
    entries after ``eva_append`` -> (B, 1, H, D). The kernel
    ``ptpu.eva_attn`` where the view has one on this device, else the
    lax path under the same scope."""
    b, _, h, d = q.shape
    r = kc.shape[1]
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    start, end = live_range(pos, int(window), int(chunk), r - int(window))
    view = eva_view(r, h, d, kc.dtype)
    if interpret or _KV.decode_stream_rows(view) is not None:
        return _DS.stream_attend(view, end, q * jnp.asarray(scale, q.dtype),
                                 kc, vc, interpret, starts=start)
    with jax.named_scope(EVA_ATTN):
        return eva_decode_reference(q, kc, vc, start, end, scale)


@register_op("eva_summaries")
def _eva_summaries_op(ctx):
    """Inputs K, V (B, T, H, D), Phi, Mu (H, D); attr chunk -> KSum,
    VSum (B, T / chunk, H, D)."""
    ks, vs = eva_summaries(ctx.input("K"), ctx.input("V"), ctx.input("Phi"),
                           ctx.input("Mu"), int(ctx.attr("chunk")))
    return {"KSum": ks, "VSum": vs}


@register_op("eva_prefill")
def _eva_prefill_op(ctx):
    """Inputs Q, K, V (B, T, H, D), KSum, VSum (B, T / chunk, H, D),
    Lengths (B,); attrs window, chunk -> Out (B, T, H, D)."""
    return {"Out": eva_prefill(
        ctx.input("Q"), ctx.input("K"), ctx.input("V"), ctx.input("KSum"),
        ctx.input("VSum"), ctx.input("Lengths"), int(ctx.attr("window")),
        int(ctx.attr("chunk")))}


@register_op("eva_pack")
def _eva_pack_op(ctx):
    """Inputs X (B, T, H, D), XSum (B, T / chunk, H, D), Lengths (B,);
    attrs window, summary_rows -> Out (B, summary_rows + window, H, D)."""
    return {"Out": eva_pack(ctx.input("X"), ctx.input("XSum"),
                            ctx.input("Lengths"), int(ctx.attr("window")),
                            int(ctx.attr("summary_rows")))}


@register_op("eva_append")
def _eva_append_op(ctx):
    """Inputs KCache, VCache (B, R, H, D), K, V (B, 1, H, D), Pos (B,),
    Phi, Mu (H, D); attrs window, chunk -> KOut, VOut."""
    kc, vc = eva_append(
        ctx.input("KCache"), ctx.input("VCache"), ctx.input("K"),
        ctx.input("V"), ctx.input("Pos"), ctx.input("Phi"), ctx.input("Mu"),
        int(ctx.attr("window")), int(ctx.attr("chunk")))
    return {"KOut": kc, "VOut": vc}


@register_op("eva_decode")
def _eva_decode_op(ctx):
    """Inputs Q (B, 1, H, D), KCache, VCache (B, R, H, D), Pos (B,);
    attrs window, chunk -> Out = Q's shape."""
    return {"Out": eva_decode(
        ctx.input("Q"), ctx.input("KCache"), ctx.input("VCache"),
        ctx.input("Pos"), int(ctx.attr("window")), int(ctx.attr("chunk")))}
