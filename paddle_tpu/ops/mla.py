"""Multi-head latent attention (MLA; DeepSeek-V2, arXiv:2405.04434,
section 2.1): what a position KEEPS is one latent row a layer,
``[c_kv ; k_r]`` (``kv_lora_rank + qk_rope_dim`` floats: the normalised
down projection of the layer's input, and ONE rotated key row that all
heads share), where a K/V cache keeps ``2 * n_head * d_head``. Two
attention paths read it, and they compute the same numbers:

- EXPANDED (a prefill; ``mla_expand`` then the flash kernel the repo
  has): ``[k_nope_h ; v_h] = c_kv W_kvb`` for every head, ``k_h =
  [k_nope_h ; k_r]``, causal attention of ``q_h = [q_nope_h ; q_rope_h]``
  at the scale the caller gives. The rows of K and V exist for the one
  program and are never stored.
- ABSORBED (a decode step; ``mla_decode``): with ``W_kvb`` split by
  head into ``W^K_h`` (rank, nope) and ``W^V_h`` (rank, v), ``q~_h =
  q_nope_h W^K_h^T``, ``s_h(j) = a (q~_h . c_kv(j) + q_rope_h . k_r(j))``
  = ``a [q~_h ; q_rope_h] . row(j)``, ``o~_h = sum_j p_h(j) c_kv(j)``,
  ``o_h = o~_h W^V_h``: attention ON the latent rows, no K or V of any
  head is built, and ``W^K_h``, ``W^V_h`` are views of the one stored
  ``W_kvb``. Between ``q~`` and ``o~ W^V`` one of two paths attends
  the rows, numerics one, chosen from the slab's shape and type and
  the device (``kv_cache.decode_stream_rows`` of ``latent_view``):

  - the Pallas kernel ``ptpu.mla_latent_attn`` (a TPU; a float32 slab
    whose row and rank fill whole sublane tiles, whose sequence divides
    into blocks of at least 128 lanes and whose (H, S) scores fit
    beside them): one call a layer of the streamed two-pass body of
    ``ops/decode_stream.py`` under this file's VIEW (``latent_view``),
    over the slab WHERE IT LIES. The TPU compiler lays a (B, S, 320)
    slab out with the sequence minor ({1,2,0}: 320 sublane rows of S
    lanes a slot, no padding to 384 lanes) and a Mosaic call wants
    row-major operands, so the view is of the TRANSPOSED slab (B, 320,
    S), whose row-major form is those very bytes: a bitcast, where a
    call on (B, S, 320) would be handed a padded copy of the whole
    slab. A block is (320, lanes) of positions, and V's the first
    ``rank`` sublane rows of the same array.
  - the exact lax form (``_latent_attend_lax``; every other device,
    type and shape, and the kernel's reference): the slab is read as
    it lies, (B, S, rank + rope), by two products whose contraction is
    the row, every row of every slot whatever its length.

Six ops, one scope each: ``mla_attend`` (``ptpu.mla_attend``: the
expanded path's causal attention, the serving prefills' one entry
``attention.prefill_attention`` at the rows' lengths, whatever the
query/key head's width beside the value head's), ``mla_q``
(``ptpu.mla_q``: down projection, RMS norm, up projection, the
rotation of each head's rope part, the position-dependent query scale), ``mla_kv`` (``ptpu.mla_kv``: down
projection, RMS norm of ``c_kv``, rotation of ``k_r``: the row a
position keeps), ``mla_expand`` (``ptpu.mla_expand``), ``mla_decode``
(``ptpu.mla_decode``) and ``mla_append`` (``ptpu.mla_append``: one row
a slot at its length, in place under donation).
``paddle_tpu_mla_traces_total{path}`` counts which path a program was
traced with: ``expanded``, ``absorbed_kernel_once`` (the kernel, a
slot's live rows fetched once), ``absorbed_kernel`` (the kernel, a
slab too large to keep: fetched a pass) or ``absorbed`` (the lax form).
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability import MLA_TRACES
from . import attention as _A
from . import decode_stream as _DS
from . import kv_cache as _KV
from . import rope as _R
from .dsa import window_positions as _window_positions
from .math import wmm as _wmm
from .ssm import rms_norm as _rms
from .registry import register_op

MLA_Q = "ptpu.mla_q"
MLA_KV = "ptpu.mla_kv"
MLA_EXPAND = "ptpu.mla_expand"
MLA_ATTEND = "ptpu.mla_attend"
MLA_DECODE = "ptpu.mla_decode"
MLA_APPEND = "ptpu.mla_append"
# the absorbed attention's kernel: its call's name in lowered text and
# device traces. It carries ``latent_`` because a trace's reader tells an
# event that reads a latent slab by that piece of its text (the feed's
# name ``latent_i``) or by the slab's shape, and a Mosaic call's text
# holds neither: its operand is the transposed view's bitcast.
MLA_LATENT_ATTN = "ptpu.mla_latent_attn"
# a latent layer over a window: its prefill's flash calls and its step's
# reads of the ring
LATENT_RING_ATTEND = "ptpu.latent_ring_attend"

_NEG = -1e30


def softmax_scale(qk_dim: int, yarn_factor: float = 0.0,
                  mscale_all_dim: float = 0.0) -> float:
    """``qk_dim^-0.5``, times ``(0.1 mscale_all_dim ln(factor) + 1)^2``
    under YaRN with ``mscale_all_dim`` (DeepSeek-V3's rule)."""
    scale = float(qk_dim) ** -0.5
    if yarn_factor and yarn_factor > 1.0 and mscale_all_dim:
        m = 0.1 * float(mscale_all_dim) * math.log(float(yarn_factor)) + 1.0
        scale *= m * m
    return scale


def _rotate(x, positions, rot):
    """x (B, T, H, r) rotated whole by ``rot`` (the op's rope
    attributes: theta, yarn, attention_factor, interleave)."""
    inv = _R.rope_inv_freq(x.shape[-1], rot.get("theta", 10000.0),
                           rot.get("yarn"))
    return _R.rope(x, _window_positions(positions, x.shape[1]), inv,
                   float(rot.get("attention_factor", 1.0) or 1.0),
                   bool(rot.get("interleave", False)))


def mla_q(u, w_qa, g_q, w_qb, positions, n_head, rope_dim, eps, rot):
    """u (B, T, D) -> q (B, T, H, nope + rope): ``c_q = rms(u W_qa)``
    (``c_q = u`` where ``w_qa`` is None: a query with no bottleneck,
    ``q_lora_rank`` null), ``q = c_q W_qb`` by head, each head's LAST
    ``rope_dim`` channels rotated at ``positions`` (None: 0..T-1), the
    whole row times the query scale where ``rot["scale_beta"]`` is
    set."""
    b, t, _ = u.shape
    with jax.named_scope(MLA_Q):
        c_q = u if w_qa is None else _rms(_wmm(u, w_qa), g_q, eps)
        q = _wmm(c_q, w_qb).reshape(b, t, n_head, -1)
        nope = q.shape[-1] - rope_dim
        q = jnp.concatenate(
            [q[..., :nope], _rotate(q[..., nope:], positions, rot)], axis=-1)
        if rot.get("scale_beta"):
            q = q * _R.query_scale(
                positions, t, rot["scale_beta"],
                rot["yarn"]["original_max_position"])[:, :, None, None]
        return q.astype(u.dtype)


def mla_kv(u, w_kva, g_kv, positions, rope_dim, eps, rot, rescale=1.0):
    """u (B, T, D) -> the latent rows (B, T, rank + rope): ``[rms(c_kv) ;
    rope(k_r)]`` of ``[c_kv ; k_r] = u W_kva``, the normalised latent
    times ``rescale`` where a model rescales it (``(d_model /
    kv_lora_rank)^1/2``: ``DecodeConfig.latent_rescale``)."""
    with jax.named_scope(MLA_KV):
        row = _wmm(u, w_kva)
        rank = row.shape[-1] - rope_dim
        k_r = _rotate(row[:, :, None, rank:], positions, rot)[:, :, 0]
        c_kv = _rms(row[..., :rank], g_kv, eps)
        if rescale != 1.0:
            c_kv = c_kv * rescale
        return jnp.concatenate([c_kv, k_r], axis=-1).astype(u.dtype)


def _split_kvb(w_kvb, n_head, nope):
    """W_kvb (rank, H * (nope + v)) -> W^K (rank, H, nope), W^V (rank,
    H, v)."""
    w = w_kvb.reshape(w_kvb.shape[0], n_head, -1)
    return w[..., :nope], w[..., nope:]


def mla_expand(rows, w_kvb, n_head, nope):
    """The EXPANDED path's keys and values: rows (B, T, rank + rope) ->
    (k (B, T, H, nope + rope), v (B, T, H, v)); ``k_r`` is repeated for
    every head."""
    b, t, _ = rows.shape
    rank = w_kvb.shape[0]
    MLA_TRACES.inc(path="expanded")
    with jax.named_scope(MLA_EXPAND):
        kv = jnp.matmul(rows[..., :rank], w_kvb).reshape(b, t, n_head, -1)
        k_r = jnp.broadcast_to(rows[:, :, None, rank:],
                               (b, t, n_head, rows.shape[-1] - rank))
        return (jnp.concatenate([kv[..., :nope], k_r], axis=-1),
                kv[..., nope:])


def mla_attend(q, k, v, scale, lengths=None):
    """The EXPANDED path's causal attention: q, k (B, T, H, dq), v (B,
    T, H, dv) -> (B, T, H, dv), a head's query/key width its value
    width (128 / 128) or not (192 / 128). A view of the serving
    prefills' one entry, ``attention.prefill_attention``: on a TPU at a
    block-aligned bucket the flash forward kernel on bfloat16 operands
    (float32 sums), q and k padded with zero channels to the next
    multiple of the 128 lanes (256) and V AT ITS OWN WIDTH (128: three
    quarters of the MXU passes of a common 256), the q-blocks past a
    row's ``lengths`` skipped; the exact lax form elsewhere."""
    with jax.named_scope(MLA_ATTEND):
        return _A.prefill_attention(q, k, v, lengths, scale=scale)


# heads a pass of ``latent_prefill``: 16 heads of 16,384 rows padded to
# 256 channels are 268 MB each of q, k, v and the output, where 128
# heads' would be 2.1 GB each
_PREFILL_HEADS = 16


def _masked_attend_lax(q, k, v, scale, window, mask):
    """Exact lax attention of a head group: q, k (B, T, g, dq), v (B, T,
    g, dv); causal, the last ``window`` keys where it is set, and under
    ``mask`` (B, T, T) where one is given. Builds the (T, T) scores:
    every device but a TPU, and the kernel's reference."""
    t = q.shape[1]
    s = jnp.einsum("btgd,bsgd->bgts", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    row, col = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = col <= row
    if window:
        seen &= col > row - window
    seen = seen[None, None]
    if mask is not None:
        seen = seen & (mask[:, None] != 0)
    p = jax.nn.softmax(jnp.where(seen, s, _NEG), axis=-1)
    return jnp.einsum("bgts,bsgd->btgd", p, v.astype(jnp.float32))


def latent_prefill(c_q, rows, w_qb, w_kvb, gate, w_o, n_head, nope, scale,
                   rot, window=0, mask=None, lengths=None, name=MLA_ATTEND,
                   heads=_PREFILL_HEADS, interpret=False):
    """A prefill's EXPANDED attention of a latent layer of MANY heads,
    from the query latent to the output projection, ``heads`` heads at a
    time: c_q (B, T, q_rank), the latent rows (B, T, rank + rope), W_qb
    (q_rank, H (nope + rope)), W_kvb (rank, H (nope + v)), gate (B, T,
    H) or None, W_o (H v, D) -> (B, T, D). For each group of heads:
    ``q = c_q W_qb`` (its rope part rotated), ``[k_nope ; v] = c_kv
    W_kvb``, causal attention at ``scale`` (over the last ``window``
    keys where set; under ``mask`` (B, T, T) int8 where given: the keys
    an indexer chose, ``ops/dsa.py``), the gate, and the group's rows
    of ``W_o`` added to the output. The q, k and v of all heads never
    exist at once (128 heads of 16,384 tokens: 1.6, 1.6 and 1.1 GB, 2.1
    GB each padded to the flash kernel's 256 channels), and the same
    numbers as ``mla_expand`` + ``mla_attend`` + the projection give: a
    sum over heads taken in groups. The flash kernel (a TPU, block-
    aligned sequences; its q, k and v in bfloat16, float32 sums; q and k
    padded with zero channels to 256, v at its own 128) under ``name``
    in a device trace; the exact lax form elsewhere. ``lengths`` (B,):
    the rows' live tokens; the kernel then leaves the q-blocks wholly
    past them alone (zeros: rows no one reads)."""
    b, t, _ = c_q.shape
    rank = w_kvb.shape[0]
    rope = rows.shape[-1] - rank
    dq, per = nope + rope, w_kvb.shape[1] // n_head
    dv = per - nope
    g = min(int(heads), n_head)
    while n_head % g:
        g -= 1
    kernel = interpret or _A._use_pallas(t, t, None, 0.0)
    MLA_TRACES.inc(path="expanded")
    c_kv = rows[..., :rank]
    k_r = jnp.broadcast_to(rows[:, :, None, rank:], (b, t, g, rope))

    def attend(q, k, v):
        if not kernel:
            return _masked_attend_lax(q, k, v, scale, window, mask)

        def pad(x):
            # the kernel's operands in bfloat16: what the MXU would round
            # float32 operands to at the default precision anyway (the
            # arithmetic the lax paths compute in; the same bits and the
            # same MXU time on the chip, PERF.md PR 45), at half the
            # bytes of a head's resident K and V; a head's channels a
            # whole number of 128-lane tiles
            width = -(-x.shape[-1] // 128) * 128
            return jnp.pad(x, ((0, 0),) * 3 + ((0, width - x.shape[-1]),)
                           ).reshape(b, t, g * width).astype(jnp.bfloat16)

        block_q = _A._fit_block(t, 256 if mask is not None else 512)
        block_k = _A._fit_block(t, 512)
        out, _ = _A._mha_fwd_call_bthd(
            pad(q * jnp.asarray(scale, q.dtype)), pad(k), pad(v), g, True,
            block_q, block_k, interpret,
            window=0 if window >= t else int(window), name=name, mask=mask,
            lengths=lengths)
        return out.reshape(b, t, g, -1)[..., :dv].astype(jnp.float32)

    def group(i, y):
        q = _wmm(c_q, lax.dynamic_slice_in_dim(
            w_qb, i * g * dq, g * dq, axis=1)).reshape(b, t, g, dq)
        q = jnp.concatenate(
            [q[..., :nope], _rotate(q[..., nope:], None, rot)], axis=-1)
        kv = _wmm(c_kv, lax.dynamic_slice_in_dim(
            w_kvb, i * g * per, g * per, axis=1)).reshape(b, t, g, per)
        k = jnp.concatenate([kv[..., :nope], k_r], axis=-1)
        ctx = attend(q.astype(c_q.dtype), k, kv[..., nope:])
        if gate is not None:
            ctx = ctx * lax.dynamic_slice_in_dim(gate, i * g, g,
                                                 axis=2)[..., None]
        return y + _wmm(
            ctx.reshape(b, t, g * dv).astype(c_q.dtype),
            lax.dynamic_slice_in_dim(w_o, i * g * dv, g * dv, axis=0))

    with jax.named_scope(name):
        return lax.fori_loop(
            0, n_head // g, group,
            jnp.zeros((b, t, w_o.shape[1]), c_q.dtype))


def _latent_attend_lax(q_row, slab, lens, rank, chosen=None):
    """The exact lax form of the absorbed attention: scaled query rows
    q_row (B, H, W) on the slab (B, S, W) as it lies, rows [0, lens)
    of a slot seen (of them those ``chosen`` (B, S) bool, where given)
    -> (B, H, rank). Both products contract the slab's
    row; the second also sums the ``rope`` columns, which are dropped
    (a slice of the slab would be a copy of it). The reference, and the
    path of every shape and device ``latent_view`` has no block for."""
    s = slab.shape[1]
    scores = jnp.einsum("bhw,bsw->bhs", q_row, slab)
    if lens.ndim == 2:
        # a window: lens (B, T), chosen (B, T, S); query row t H + h of
        # q_row (B, T H, W) sees what window row t sees
        h = q_row.shape[1] // lens.shape[1]
        live = jnp.arange(s)[None, None, :] < lens[:, :, None]
        if chosen is not None:
            live = live & chosen
        live = jnp.repeat(live, h, axis=1)
    else:
        live = jnp.arange(s)[None, None, :] < lens[:, None, None]
        if chosen is not None:
            live = live & chosen[:, None, :]
    scores = jnp.where(live, scores, _NEG)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.where(live, jnp.exp(scores - m), 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    return jnp.einsum("bhs,bsw->bhw", p, slab)[..., :rank]


# positions (lanes) a block of the kernel: (320, 1024) float32 is 1.3 MB,
# both passes' blocks double-buffered 4.6 MB beside 2 MB of scores. On
# the chip, a call over 32 slots of 16,384 with ~121,000 rows live that
# fetches them a pass takes 0.60 ms at 1,024 and at 2,048 lanes (which
# streams 15% more rows and runs half the grid cells: a cell costs ~0.16
# us, live or dead), 0.75 at 512 and 1.19 at 256; the lax form takes
# 1.67 whatever is live (PERF.md, PR 39). Since PR 55 the rows are
# fetched once (``decode_stream.kept_vmem_bytes``: K's blocks 2.6 MB, the
# kept ``rank`` rows of a slot 16.8 MB).
_LATENT_BLOCK_LANES = 1024


def latent_view(s, h, row, rank, dtype, block_s=_LATENT_BLOCK_LANES):
    """The view (``ops/decode_stream.py``) of a (B, s, row) latent slab
    of ``dtype`` under ``h`` heads, ``rank`` of the row the summed part:
    blocks of the slab's TRANSPOSED view (B, row, s), at most
    ``block_s`` positions on the lanes (so at least 128 of them). K's
    block (1, row, BS) is every float of BS positions and the scores
    one (h, row) x (row, BS) product of all heads; V's block (1, rank,
    BS) is the ``c_kv`` sublane rows of the same positions of the same
    array (the ``k_r`` rows take no part in the weighted sum), the
    contraction on both operands' lanes: where a slot's (rank, s) fits
    in VMEM the body keeps it from K's pass and fetches nothing twice
    (``decode_stream.kept_vmem_bytes``). Row and rank have to fill
    whole 8-row sublane tiles of that view."""
    f32 = jnp.float32
    return _DS.StreamView(
        MLA_LATENT_ATTN, seq=s, dtype=dtype, most=block_s, score_rows=h,
        least=128, whole_tiles=row % 8 == 0 and rank % 8 == 0,
        q_block=(1, h, row), k_block=(1, row, 1), v_block=(1, rank, 1),
        o_block=(1, h, rank), seq_axis=2,
        scores=lambda i, hh, q_ref, k_ref: jnp.dot(
            q_ref[0], k_ref[0], preferred_element_type=f32),
        values=lambda i, p, v_ref: lax.dot_general(
            p, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=f32))


def pallas_latent_attend(q_row, slab, lens, rank, block_s=_LATENT_BLOCK_LANES,
                         interpret=False):
    """``_latent_attend_lax``'s contract through the kernel, over the
    slab WHERE IT LIES. On the chip the compiler lays a (B, S, W) slab
    whose row is no multiple of 128 lanes out with the sequence minor
    ({1,2,0}: W sublane rows of S lanes a slot), and a Mosaic call wants
    its operands row-major: handed the (B, S, W) array it would be
    handed a padded copy of the whole slab, every layer every step. The
    transposed view (B, W, S), row-major, IS the slab's bytes, so the
    ``swapaxes`` ``stream_attend`` makes of it (the view's sequence is
    index 2) is a bitcast in the compiled step (compiled for a
    described v5e: tests/test_tpu_compile_cells.py)."""
    _, h, w = q_row.shape
    view = latent_view(slab.shape[1], h, w, rank, slab.dtype, block_s)
    return _DS.stream_attend(view, lens, q_row, slab, slab, interpret)


def chosen_view(s, h, row, rank, dtype, block_s=_LATENT_BLOCK_LANES):
    """``latent_view`` for the one-pass kernel under a choice of rows,
    which keeps no slot's scores between passes: any number of heads."""
    return dataclasses.replace(latent_view(s, h, row, rank, dtype, block_s),
                               score_rows=0)


def _chosen_attend_kernel(len_ref, q_ref, k_ref, c_ref, o_ref, m_ref, l_ref,
                          acc_ref, *, block_s, n_blk, rank, n_q=1):
    """One (slot, block) grid cell: q_ref (1, n_q H, row) pre-scaled,
    k_ref (1, row, BS) a block of the slab's transposed view, c_ref (1,
    n_q, BS) 1.0 where the position is chosen. An online softmax over
    the slot's live blocks: ``m_ref``, ``l_ref`` (n_q H, 1) and
    ``acc_ref`` (n_q H, rank) live across them. Both products on
    bfloat16 operands, what the lax form's round to at the TPU's default
    precision; sums in float32. A window's ``n_q`` query rows (each H
    heads under its own choice) attend the block ONE fetch brought in
    one after the other, each by the one-row body: the same products in
    the same order as ``n_q`` steps (a block past a row's own live rows
    is all masked for it and leaves its sums as they were)."""
    j = pl.program_id(1)
    live_blocks = (len_ref[pl.program_id(0)] + block_s - 1) // block_s
    heads = q_ref.shape[1] // n_q

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def attend(k, q, keep, at):
        """One query row's H heads on the block: ``at`` its rows of the
        running sums."""
        sc = jnp.where(keep, jnp.dot(
            q.astype(jnp.bfloat16), k,
            preferred_element_type=jnp.float32), _NEG)         # (H, BS)
        m = jnp.maximum(m_ref[at], jnp.max(sc, axis=1, keepdims=True))
        p = jnp.where(keep, jnp.exp(sc - m), 0.0)
        corr = jnp.exp(m_ref[at] - m)
        l_ref[at] = corr * l_ref[at] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[at] = corr * acc_ref[at] + lax.dot_general(
            p.astype(jnp.bfloat16), k[:rank], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[at] = m

    @pl.when(j < live_blocks)
    def _():
        k = k_ref[0].astype(jnp.bfloat16)                      # (row, BS)
        if n_q == 1:
            keep = c_ref[0] > 0.5                              # (1, BS)
            attend(k, q_ref[0], keep, Ellipsis)
        for t in range(n_q if n_q > 1 else 0):
            at = pl.ds(t * heads, heads)
            attend(k, q_ref[0, at], c_ref[0, pl.ds(t, 1)] > 0.5, at)

    @pl.when(j == n_blk - 1)
    def _():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype)


def pallas_chosen_attend(q_row, slab, lens, chosen, rank, name,
                         block_s=_LATENT_BLOCK_LANES, interpret=False):
    """``_latent_attend_lax``'s contract under ``chosen`` (B, S) bool
    through a kernel, ``name`` in a device trace, over the slab WHERE IT
    LIES (its transposed view, as ``pallas_latent_attend`` reads it): a
    slot's live blocks are streamed ONCE, the rows not chosen masked
    inside, a dead block never fetched; no (B, H, S) array of scores
    goes to memory. One pass and an online softmax where the streamed
    kernels of ``ops/decode_stream.py`` make two: the scores of 128
    heads on 16,384 positions, 8 MiB, do not wait in vector memory, and
    what an online softmax rounds otherwise (unnormalised weights to
    bfloat16: 0.002 of the output's norm, PERF.md, PR 32) the lax form
    under a mask has no claim to either. A WINDOW: q_row (B, T H, W),
    ``lens`` (B, T) and ``chosen`` (B, T, S): the T query rows of a slot
    share ONE stream of its live blocks (as far as the last row's), each
    under its own mask."""
    b, hq, w = q_row.shape
    s = slab.shape[1]
    n_q = 1 if lens.ndim == 1 else lens.shape[1]
    rows = _DS.block_positions(chosen_view(s, hq // n_q, w, rank,
                                            slab.dtype, block_s))
    if rows is None:
        raise ValueError("%s: no kernel for a slab of %d positions of %d %s"
                         % (name, s, w, jnp.dtype(slab.dtype).name))
    n_blk = s // rows
    lens = jnp.clip(lens, 0, s)
    at = jnp.arange(s, dtype=jnp.int32)
    if n_q == 1:
        keep = chosen & (at[None, :] < lens[:, None])
        kernel = functools.partial(_chosen_attend_kernel, block_s=rows,
                                   n_blk=n_blk, rank=rank)
    else:
        keep = chosen & (at[None, None, :] < lens[:, :, None])
        lens = lens[:, -1]  # the blocks streamed: the last row's
        kernel = functools.partial(_chosen_attend_kernel, block_s=rows,
                                   n_blk=n_blk, rank=rank, n_q=n_q)

    def block(bi, j, lens_ref):
        return (bi, 0, _DS.live_block(j, lens_ref, bi, rows))

    return _A.named_pallas_call(
        name, kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n_blk),
            in_specs=[
                pl.BlockSpec((1, hq, w), lambda bi, j, lens_ref: (bi, 0, 0)),
                pl.BlockSpec((1, w, rows), block),
                pl.BlockSpec((1, n_q, rows), block),
            ],
            out_specs=pl.BlockSpec((1, hq, rank),
                                   lambda bi, j, lens_ref: (bi, 0, 0)),
            scratch_shapes=[pltpu.VMEM((hq, 1), jnp.float32),
                            pltpu.VMEM((hq, 1), jnp.float32),
                            pltpu.VMEM((hq, rank), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, hq, rank), q_row.dtype),
        interpret=interpret,
        **_A._tpu_params("parallel", "arbitrary"),
    )(lens, q_row, jnp.swapaxes(slab, 1, 2),
      keep.astype(jnp.float32)[:, None, :] if n_q == 1
      else keep.astype(jnp.float32))


def mla_decode(q, slab, lengths, w_kvb, scale, chosen=None,
               name=MLA_DECODE):
    """The ABSORBED path: q (B, 1, H, nope + rope), the latent slab (B,
    S, rank + rope) with ``lengths`` (B,) live rows a slot -> (B, 1, H,
    v). ``q~ = q_nope W^K`` before and ``o~ W^V`` after are plain
    products; between them the attention on the latent rows runs the
    kernel over a slot's live blocks where the slab's shape, type and
    the device allow it (``kv_cache.decode_stream_rows``) and the exact lax
    form, which reads every row of every slot, elsewhere. A slot of
    length 0 gives zeros. Under ``chosen`` (B, S) bool (an indexer's
    choice, ``ops/dsa.py``) a slot attends its chosen live rows alone:
    the kernel ``<name>_step`` streams a slot's live blocks once and
    masks the rows not chosen (``pallas_chosen_attend``; the lax form
    streams every row of every slot and writes the scores out). A
    RING of latent rows (``latent_ring``) is a slab of ``window`` rows
    whose order the softmax does not see: ``lengths`` is then
    ``min(positions held, window)``."""
    b, t, h, _ = q.shape
    s, rank = slab.shape[1], w_kvb.shape[0]
    nope = q.shape[-1] - (slab.shape[-1] - rank)
    view = (latent_view if chosen is None else chosen_view)(
        s, h, slab.shape[-1], rank, slab.dtype)
    kernel = _KV.decode_stream_rows(view) is not None
    once = kernel and _DS.kept_vmem_bytes(view) is not None
    if t > 1 and chosen is None:
        raise ValueError("mla_decode: a window of %d query rows is built "
                         "under an indexer's choice alone" % t)
    MLA_TRACES.inc(path="absorbed_kernel_once" if once else
                   "absorbed_kernel" if kernel else "absorbed")
    with jax.named_scope(name):
        w_k, w_v = _split_kvb(w_kvb, h, nope)
        if t > 1:
            return _window_decode(
                q, slab, lengths.reshape(-1).astype(jnp.int32), w_k, w_v,
                scale, chosen, kernel, name)
        qf = q[:, 0].astype(jnp.float32)
        q_lat = _absorb(qf[..., :nope], w_k)
        q_row = jnp.concatenate([q_lat, qf[..., nope:]], axis=-1) * scale
        lens = lengths.reshape(-1).astype(jnp.int32)
        if kernel and chosen is not None:
            o_lat = pallas_chosen_attend(q_row, slab, lens, chosen, rank,
                                         name + "_step")
        elif kernel:
            o_lat = pallas_latent_attend(q_row, slab, lens, rank)
        else:
            o_lat = _latent_attend_lax(q_row, slab, lens, rank, chosen)
        out = _expand(o_lat, w_v)
        return out[:, None].astype(q.dtype)


def _absorb(q_nope, w_k):
    """``q~_h = q_nope_h W^K_h^T``: (B, H, nope) -> (B, H, rank); a
    ``W_kvb`` held in bfloat16 meets the query rounded to it
    (``math.wmm``'s rule)."""
    if w_k.dtype == jnp.bfloat16:
        return jnp.einsum("bhd,rhd->bhr", q_nope.astype(jnp.bfloat16), w_k,
                          preferred_element_type=jnp.float32)
    return jnp.einsum("bhd,rhd->bhr", q_nope, w_k)


def _expand(o_lat, w_v):
    """``o_h = o~_h W^V_h``: (B, H, rank) -> (B, H, v)."""
    if w_v.dtype == jnp.bfloat16:
        return jnp.einsum("bhr,rhd->bhd", o_lat.astype(jnp.bfloat16), w_v,
                          preferred_element_type=jnp.float32)
    return jnp.einsum("bhr,rhd->bhd", o_lat, w_v)


def _window_decode(q, slab, lens, w_k, w_v, scale, chosen, kernel, name):
    """``mla_decode`` of a window: q (B, T, H, nope + rope), ``lens``
    (B,) the FIRST row's live rows (row t sees ``lens + t``), ``chosen``
    (B, T, S). Row t's products are those of a step at its position:
    ``_absorb`` and ``_expand`` a row at a time, the attention by the
    step's kernel body a row (``pallas_chosen_attend``)."""
    b, t, h, _ = q.shape
    rank, nope = w_k.shape[0], w_k.shape[-1]
    qf = q.astype(jnp.float32)
    q_row = jnp.concatenate(
        [jnp.concatenate([_absorb(qf[:, i, :, :nope], w_k),
                          qf[:, i, :, nope:]], axis=-1) * scale
         for i in range(t)], axis=1)                       # (B, T H, W)
    row_lens = lens[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    if kernel:
        o_lat = pallas_chosen_attend(q_row, slab, row_lens, chosen, rank,
                                     name + "_step")
    else:
        o_lat = _latent_attend_lax(q_row, slab, row_lens, rank, chosen)
    o_lat = o_lat.reshape(b, t, h, rank)
    return jnp.stack([_expand(o_lat[:, i], w_v) for i in range(t)],
                     axis=1).astype(q.dtype)


def mla_append(slab, row, pos, ring=False):
    """One latent row a slot: row (B, 1, W) at ``pos`` (B,) of slab (B,
    S, W), at ``pos mod S`` where the slab is a ``ring`` of the last S
    positions; a WINDOW's rows (B, T, W) at ``pos..pos + T - 1`` of a
    slab (one update a slot: the form of ``ops/speculative.py:
    cache_append_window`` without its scatter). One
    dynamic-update-slice a slot and not ``kv_cache.
    cache_append``'s scatter: on the chip a row of 320 floats is no
    multiple of the 128 lanes, so the compiler lays the slab out with
    the SEQUENCE minor ({1,2,0}: 320 sublane rows of S lanes, no
    padding), and a scatter there costs two relayout copies of the
    whole slab a step (compiled for a described v5e: PERF.md, PR 38); a
    dynamic-update-slice writes its column where the slab lies."""
    b, s = slab.shape[0], slab.shape[1]
    t = 1
    if row.ndim == slab.ndim:
        t = row.shape[1]
        if t != 1 and ring:
            raise ValueError("mla_append appends ONE row per sequence to "
                             "a ring; New has time dim %d" % t)
        if t == 1:
            row = row[:, 0]
    pos = pos.reshape(-1).astype(jnp.int32)
    # a window of T rows lands at pos..pos+T-1: a slot that has them
    # (the server retires one that has not) is never clipped
    pos = pos % s if ring else jnp.clip(pos, 0, s - t)
    zero = jnp.zeros((), jnp.int32)
    with jax.named_scope(MLA_APPEND):
        for i in range(b):
            new = row[i][None, None, :] if t == 1 else row[i][None]
            slab = lax.dynamic_update_slice(
                slab, new.astype(slab.dtype), (jnp.int32(i), pos[i], zero))
        return slab


def _rot_of(ctx):
    """The rope attributes of ``mla_q`` / ``mla_kv``."""
    return {"theta": float(ctx.attr("theta", 10000.0)),
            "yarn": _R.yarn_of_attrs(ctx.attr),
            "attention_factor": float(ctx.attr("attention_factor", 1.0)
                                      or 1.0),
            "interleave": bool(ctx.attr("interleave", False)),
            "scale_beta": float(ctx.attr("scale_beta", 0.0) or 0.0)}


@register_op("mla_q")
def _mla_q_op(ctx):
    """Inputs X (B, T, D), optional WA (D, q_rank) and Gain (q_rank,)
    (both absent: no bottleneck, WB is (D, ...)), WB (q_rank, H * (nope
    + rope)), optional Positions (B,) at T = 1 (absent: 0..T-1). Attrs n_head, rope_dim, epsilon, and the rotation's: theta,
    interleave, attention_factor, factor / original_max_position /
    beta_fast / beta_slow (YaRN), scale_beta -> Out (B, T, H, nope +
    rope)."""
    return {"Out": mla_q(
        ctx.input("X"), ctx.input("WA"), ctx.input("Gain"), ctx.input("WB"),
        ctx.input("Positions"), int(ctx.attr("n_head")),
        int(ctx.attr("rope_dim")), float(ctx.attr("epsilon", 1e-6)),
        _rot_of(ctx))}


@register_op("mla_kv")
def _mla_kv_op(ctx):
    """Inputs X (B, T, D), WA (D, rank + rope), Gain (rank,), optional
    Positions. Attrs rope_dim, epsilon, rescale and the rotation's
    (``mla_q``) -> Out (B, T, rank + rope)."""
    return {"Out": mla_kv(
        ctx.input("X"), ctx.input("WA"), ctx.input("Gain"),
        ctx.input("Positions"), int(ctx.attr("rope_dim")),
        float(ctx.attr("epsilon", 1e-6)), _rot_of(ctx),
        float(ctx.attr("rescale", 1.0)))}


@register_op("mla_expand")
def _mla_expand_op(ctx):
    """Inputs Rows (B, T, rank + rope), WB (rank, H * (nope + v)).
    Attrs n_head, nope_dim -> K (B, T, H, nope + rope), V (B, T, H,
    v)."""
    k, v = mla_expand(ctx.input("Rows"), ctx.input("WB"),
                      int(ctx.attr("n_head")), int(ctx.attr("nope_dim")))
    return {"K": k, "V": v}


@register_op("mla_attend")
def _mla_attend_op(ctx):
    """Inputs Q, K (B, T, H, dq), V (B, T, H, dv), optional Lengths
    (B,). Attr scale -> Out (B, T, H, dv): causal attention of a
    prefill."""
    return {"Out": mla_attend(ctx.input("Q"), ctx.input("K"),
                              ctx.input("V"), float(ctx.attr("scale")),
                              ctx.input("Lengths"))}


@register_op("mla_decode")
def _mla_decode_op(ctx):
    """Inputs Q (B, 1, H, nope + rope), Cache (B, S, rank + rope),
    Lengths (B,) live rows, WB (rank, H * (nope + v)), optional Chosen
    (B, S) bool. Attrs scale, scope (the named scope: ``ptpu.
    mla_decode`` unless given) -> Out (B, 1, H, v)."""
    return {"Out": mla_decode(ctx.input("Q"), ctx.input("Cache"),
                              ctx.input("Lengths"), ctx.input("WB"),
                              float(ctx.attr("scale")),
                              ctx.input("Chosen"),
                              ctx.attr("scope", None) or MLA_DECODE)}


@register_op("latent_prefill")
def _latent_prefill_op(ctx):
    """Inputs CQ (B, T, q_rank), Rows (B, T, rank + rope), WQB, WKVB, WO
    (H v, D), optional Gate (B, T, H), Mask (B, T, T) int8 and Lengths
    (B,) live tokens a row. Attrs
    n_head, nope_dim, scale, window, scope and the rotation's
    (``mla_q``) -> Out (B, T, D): ``latent_prefill``."""
    return {"Out": latent_prefill(
        ctx.input("CQ"), ctx.input("Rows"), ctx.input("WQB"),
        ctx.input("WKVB"), ctx.input("Gate"), ctx.input("WO"),
        int(ctx.attr("n_head")), int(ctx.attr("nope_dim")),
        float(ctx.attr("scale")), _rot_of(ctx),
        window=int(ctx.attr("window", 0) or 0), mask=ctx.input("Mask"),
        lengths=ctx.input("Lengths"),
        name=ctx.attr("scope", None) or MLA_ATTEND)}


@register_op("mla_append")
def _mla_append_op(ctx):
    """Inputs Cache (B, S, W), New (B, 1, W), Pos (B,). Attr ring (the
    position mod S) -> Out: the slab with each slot's new row at its
    position."""
    return {"Out": mla_append(ctx.input("Cache"), ctx.input("New"),
                              ctx.input("Pos"),
                              bool(ctx.attr("ring", False)))}
