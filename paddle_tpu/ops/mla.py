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
expanded path's causal attention where the query/key head is wider
than the value head, through the flash kernel at a padded common
width), ``mla_q`` (``ptpu.mla_q``: down projection,
RMS norm, up projection, the rotation of each head's rope part, the
position-dependent query scale), ``mla_kv`` (``ptpu.mla_kv``: down
projection, RMS norm of ``c_kv``, rotation of ``k_r``: the row a
position keeps), ``mla_expand`` (``ptpu.mla_expand``), ``mla_decode``
(``ptpu.mla_decode``) and ``mla_append`` (``ptpu.mla_append``: one row
a slot at its length, in place under donation).
``paddle_tpu_mla_traces_total{path}`` counts which path a program was
traced with: ``expanded``, ``absorbed_kernel`` or ``absorbed`` (the
lax form).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from ..observability import MLA_TRACES
from . import attention as _A
from . import decode_stream as _DS
from . import kv_cache as _KV
from . import rope as _R
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
    return _R.rope(x, positions, inv,
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
        c_q = u if w_qa is None else _rms(jnp.matmul(u, w_qa), g_q, eps)
        q = jnp.matmul(c_q, w_qb).reshape(b, t, n_head, -1)
        nope = q.shape[-1] - rope_dim
        q = jnp.concatenate(
            [q[..., :nope], _rotate(q[..., nope:], positions, rot)], axis=-1)
        if rot.get("scale_beta"):
            q = q * _R.query_scale(
                positions, t, rot["scale_beta"],
                rot["yarn"]["original_max_position"])[:, :, None, None]
        return q.astype(u.dtype)


def mla_kv(u, w_kva, g_kv, positions, rope_dim, eps, rot):
    """u (B, T, D) -> the latent rows (B, T, rank + rope): ``[rms(c_kv) ;
    rope(k_r)]`` of ``[c_kv ; k_r] = u W_kva``."""
    with jax.named_scope(MLA_KV):
        row = jnp.matmul(u, w_kva)
        rank = row.shape[-1] - rope_dim
        k_r = _rotate(row[:, :, None, rank:], positions, rot)[:, :, 0]
        return jnp.concatenate([_rms(row[..., :rank], g_kv, eps), k_r],
                               axis=-1).astype(u.dtype)


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


def mla_attend(q, k, v, scale):
    """The EXPANDED path's causal attention where a head's query/key
    width is not its value width (192 / 128): q, k (B, T, H, dq), v (B,
    T, H, dv) -> (B, T, H, dv). The flash kernels take ONE head width,
    a multiple of the 128 lanes, so q, k and v are padded with zero
    channels to the next such width (256) and the output's first ``dv``
    channels are kept: exact (a zero channel adds nothing to a score,
    and a zero value channel is a zero output channel), at 512 / 320 of
    the products' FLOPs. A flash forward with a value width of its own
    would save that; the kernels of the plain models stay as they
    are."""
    dq, dv = q.shape[-1], v.shape[-1]
    with jax.named_scope(MLA_ATTEND):
        width = -(-max(dq, dv) // 128) * 128

        def pad(x):
            return jnp.pad(x, ((0, 0),) * 3 + ((0, width - x.shape[-1]),))

        return _A.causal_attention_bthd(pad(q), pad(k), pad(v),
                                        scale)[..., :dv]


def _latent_attend_lax(q_row, slab, lens, rank):
    """The exact lax form of the absorbed attention: scaled query rows
    q_row (B, H, W) on the slab (B, S, W) as it lies, rows [0, lens)
    of a slot seen -> (B, H, rank). Both products contract the slab's
    row; the second also sums the ``rope`` columns, which are dropped
    (a slice of the slab would be a copy of it). The reference, and the
    path of every shape and device ``latent_view`` has no block for."""
    s = slab.shape[1]
    scores = jnp.einsum("bhw,bsw->bhs", q_row, slab)
    live = jnp.arange(s)[None, None, :] < lens[:, None, None]
    scores = jnp.where(live, scores, _NEG)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.where(live, jnp.exp(scores - m), 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    return jnp.einsum("bhs,bsw->bhw", p, slab)[..., :rank]


# positions (lanes) a block of the kernel: (320, 1024) float32 is 1.3 MB,
# both passes' blocks double-buffered 4.6 MB beside 2 MB of scores. On
# the chip, a call over 32 slots of 16,384 with ~121,000 rows live takes
# 0.60 ms at 1,024 and at 2,048 lanes (which streams 15% more rows and
# runs half the grid cells: a cell costs ~0.16 us, live or dead), 0.75
# at 512 and 1.19 at 256; the lax form takes 1.67 whatever is live
# (PERF.md, PR 39).
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
    contraction on both operands' lanes. Row and rank have to fill
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


def mla_decode(q, slab, lengths, w_kvb, scale):
    """The ABSORBED path: q (B, 1, H, nope + rope), the latent slab (B,
    S, rank + rope) with ``lengths`` (B,) live rows a slot -> (B, 1, H,
    v). ``q~ = q_nope W^K`` before and ``o~ W^V`` after are plain
    products; between them the attention on the latent rows runs the
    kernel over a slot's live blocks where the slab's shape, type and
    the device allow it (``kv_cache.decode_stream_rows``) and the exact lax
    form, which reads every row of every slot, elsewhere. A slot of
    length 0 gives zeros."""
    b, _, h, _ = q.shape
    s, rank = slab.shape[1], w_kvb.shape[0]
    nope = q.shape[-1] - (slab.shape[-1] - rank)
    kernel = _KV.decode_stream_rows(latent_view(
        s, h, slab.shape[-1], rank, slab.dtype)) is not None
    MLA_TRACES.inc(path="absorbed_kernel" if kernel else "absorbed")
    with jax.named_scope(MLA_DECODE):
        w_k, w_v = _split_kvb(w_kvb, h, nope)
        qf = q[:, 0].astype(jnp.float32)
        q_lat = jnp.einsum("bhd,rhd->bhr", qf[..., :nope], w_k)
        q_row = jnp.concatenate([q_lat, qf[..., nope:]], axis=-1) * scale
        lens = lengths.reshape(-1).astype(jnp.int32)
        if kernel:
            o_lat = pallas_latent_attend(q_row, slab, lens, rank)
        else:
            o_lat = _latent_attend_lax(q_row, slab, lens, rank)
        out = jnp.einsum("bhr,rhd->bhd", o_lat, w_v)
        return out[:, None].astype(q.dtype)


def mla_append(slab, row, pos):
    """One latent row a slot: row (B, 1, W) at ``pos`` (B,) of slab (B,
    S, W). One dynamic-update-slice a slot and not ``kv_cache.
    cache_append``'s scatter: on the chip a row of 320 floats is no
    multiple of the 128 lanes, so the compiler lays the slab out with
    the SEQUENCE minor ({1,2,0}: 320 sublane rows of S lanes, no
    padding), and a scatter there costs two relayout copies of the
    whole slab a step (compiled for a described v5e: PERF.md, PR 38); a
    dynamic-update-slice writes its column where the slab lies."""
    b, s = slab.shape[0], slab.shape[1]
    if row.ndim == slab.ndim:
        if row.shape[1] != 1:
            raise ValueError("mla_append appends ONE row per sequence; "
                             "New has time dim %d" % row.shape[1])
        row = row[:, 0]
    pos = jnp.clip(pos.reshape(-1).astype(jnp.int32), 0, s - 1)
    zero = jnp.zeros((), jnp.int32)
    with jax.named_scope(MLA_APPEND):
        for i in range(b):
            slab = lax.dynamic_update_slice(
                slab, row[i][None, None, :].astype(slab.dtype),
                (jnp.int32(i), pos[i], zero))
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
    Positions. Attrs rope_dim, epsilon and the rotation's (``mla_q``)
    -> Out (B, T, rank + rope)."""
    return {"Out": mla_kv(
        ctx.input("X"), ctx.input("WA"), ctx.input("Gain"),
        ctx.input("Positions"), int(ctx.attr("rope_dim")),
        float(ctx.attr("epsilon", 1e-6)), _rot_of(ctx))}


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
    """Inputs Q, K (B, T, H, dq), V (B, T, H, dv), dq != dv. Attr scale
    -> Out (B, T, H, dv): causal attention of a prefill."""
    return {"Out": mla_attend(ctx.input("Q"), ctx.input("K"),
                              ctx.input("V"), float(ctx.attr("scale")))}


@register_op("mla_decode")
def _mla_decode_op(ctx):
    """Inputs Q (B, 1, H, nope + rope), Cache (B, S, rank + rope),
    Lengths (B,) live rows, WB (rank, H * (nope + v)). Attr scale ->
    Out (B, 1, H, v)."""
    return {"Out": mla_decode(ctx.input("Q"), ctx.input("Cache"),
                              ctx.input("Lengths"), ctx.input("WB"),
                              float(ctx.attr("scale")))}


@register_op("mla_append")
def _mla_append_op(ctx):
    """Inputs Cache (B, S, W), New (B, 1, W), Pos (B,) -> Out: the slab
    with each slot's new row at its position."""
    return {"Out": mla_append(ctx.input("Cache"), ctx.input("New"),
                              ctx.input("Pos"))}
