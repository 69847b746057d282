"""KV-cache primitives for autoregressive decode serving.

The training/prefill path runs flash attention over whole sequences
(ops/attention.py). Generation is a different regime: each step carries
exactly ONE new query per sequence and attends against everything
decoded so far. Recomputing the full prefix per token is O(T^2) in
generated length — the algorithmic tax the KV cache removes: K/V live
in a preallocated (B, S, H, Dh) slab (the BTHD layout the head-split
projection produces, same as the prefill kernels consume), each step
appends one row at the sequence's current length and attends the slab
with a single query.

Static-shape discipline (the whole framework's TPU contract): the slab
length S is a compile-time constant — callers bucket it to powers of
two (serving/decode.py) so the executable count stays bounded — and the
per-slot VALID length rides along as an explicit (B,) tensor, exactly
like the `Lengths` input of fused_attention.

Three ops:

- ``decode_attention``: Q (B, 1, H, Dh) x cache K/V (B, S, Hkv, Dh)
  with Lengths (B,) -> (B, 1, H, Dh), H = g * Hkv. A Pallas TPU kernel
  that reads the slab WHERE IT LIES, in blocks of sequence rows with
  every head of a block in one contiguous copy, a head's rows picked by
  a strided load (``_head_rows``: the (B, S*Hkv, Dh) view of the slab
  is a bitcast on the chip when the heads fill whole 8-row sublane
  tiles of a 32-bit type). The lengths are scalar-prefetched, so the
  block index stops at a slot's last live block: dead rows are neither
  fetched nor computed. At g = 1 (a key/value head a query head) the
  body is this file's: an online softmax, K and V blocks side by side,
  one grid cell a (slot, sequence block). At g > 1 (grouped queries)
  this file holds the VIEW (``decode_view``: the (1, rows, Hkv, Dh)
  block of the slab itself, head i's strided rows against the g query
  rows that share it) and ``ops/decode_stream.py`` the body, two passes
  so that the weights are normalised before they are rounded, as the
  lax path rounds them, and the rule for a block's rows. Slabs without
  that free view (heads not a multiple of 8; 16- and 8-bit types,
  whose packed rows Mosaic cannot load strided) run the older kernel
  at g = 1, one grid cell per (batch, head) over the (B, S, H*Dh)
  view, which costs a physical copy of the slab a call, and the exact
  lax path at g > 1 (ONE key/value head under many query heads). Shape
  and dtype alone choose (``decode_stream.block_positions`` of the
  view). The exact pure-``lax`` path serves CPU/GPU and non-aligned
  shapes; the kernels also run under ``interpret=True`` so parity is
  testable off TPU.
- ``cache_append``: scatter one new K or V row per sequence at its
  current length (functional update — callers thread the slab through
  the step function; XLA aliases it in place under donation).
- ``cache_gather``: reorder slab rows along the slot axis (beam-search
  parent reordering, continuous-batching slot compaction).
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..framework.scope import current_device
from . import decode_stream as _DS
from .attention import (_fit_block, _tpu_params, named_pallas_call,
                        sink_share)
from .registry import register_op

_NEG = -1e30

# the decode kernel's stable name in lowered text and device traces
DECODE_ATTN = "ptpu.decode_attn"
# a slab with fewer heads than the query: the kernel's call, or the lax
# path's scope where the slab has no free view
DECODE_ATTN_GROUPED = "ptpu.decode_attn_grouped"
# a slab of FLAT rows whose K and V differ in width and whose few heads
# fill no sublane tile: the kernel's call, or the lax path's scope
DECODE_ATTN_UNEVEN = "ptpu.decode_attn_uneven"
# a sliding-window layer's cache: a ring of `window` rows
DECODE_ATTN_RING = "ptpu.decode_attn_ring"
RING_APPEND = "ptpu.ring_append"
RING_PACK = "ptpu.ring_pack"


# ---------------------------------------------------------------------------
# single-query decode attention
# ---------------------------------------------------------------------------


def decode_attention_reference(q, k_cache, v_cache, lengths, scale=None,
                               sink=None):
    """Pure-lax decode attention: q (B, 1, H, Dh), caches (B, S, Hkv, Dh)
    with H = g * Hkv (g = 1: as many key/value heads as query heads;
    g > 1: grouped queries, query head h reads K/V head h // g),
    lengths (B,) valid rows per slot -> (B, 1, H, Dh); V's heads may be
    of another width than q's and K's, which the output then has;
    ``sink`` (H,): a learned scalar a query head in the softmax's
    denominator (``sink_share``). Exact; the CPU
    serving path, the numeric reference for the Pallas kernel, and on
    every device the path of a ring and of a grouped slab the in-place
    kernel has no free view of (``decode_view``: one key/value
    head, a 16-bit type): the g query rows of a slot against their one K/V head are a
    real matmul, and the slab keeps its Hkv heads (never repeated).

    Rows with length 0 (empty/inactive slots) produce zeros, not the
    mean of garbage V rows — continuous batching runs every slot of the
    slab each step and ignores the inactive ones, so their outputs must
    at least stay finite.
    """
    b, one, h, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    if h % hkv:
        raise ValueError("decode_attention: %d query heads do not divide "
                         "over %d key/value heads" % (h, hkv))
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    qf = (q[:, 0].astype(jnp.float32) * scale).reshape(
        b, hkv, h // hkv, d)                                # (B, Hkv, g, D)
    scores = jnp.einsum("bkgd,bskd->bkgs", qf,
                        k_cache.astype(jnp.float32))        # (B, Hkv, g, S)
    valid = (jnp.arange(s)[None, None, None, :]
             < lengths.reshape(-1)[:, None, None, None])    # (B, 1, 1, S)
    scores = jnp.where(valid, scores, _NEG)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.where(valid, jnp.exp(scores - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bkgs,bskd->bkgd", p / jnp.maximum(l, 1e-30),
                     v_cache.astype(jnp.float32))
    if sink is not None:
        lse = m + jnp.log(jnp.maximum(l, 1e-30))            # (B, Hkv, g, 1)
        out = out * sink_share(lse, sink.reshape(1, hkv, h // hkv, 1))
    return out.reshape(b, 1, h, v_cache.shape[-1]).astype(q.dtype)


def _online_softmax_row(q, kb, vb, col0, length, acc, m, l):
    """One KV block of the single-row online softmax (the body of
    ops/attention.py's ``_mha_fwd_kernel`` at block_q == 1). q (1, D)
    pre-scaled; kb/vb (BS, D), rows col0.. of the slot; acc (1, D), m
    and l (1, 1). Rows at or past ``length`` are masked out."""
    s = jnp.dot(q, kb.T, preferred_element_type=jnp.float32)  # (1, BS)
    live = col0 + lax.broadcasted_iota(jnp.int32, s.shape, 1) < length
    s = jnp.where(live, s, _NEG)
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(live, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m - m_new)
    l = l * corr + jnp.sum(p, axis=1, keepdims=True)
    acc = acc * corr + jnp.dot(p.astype(vb.dtype), vb,
                               preferred_element_type=jnp.float32)
    return acc, m_new, l


def _decode_attn_kernel(q_ref, k_ref, v_ref, len_ref, o_ref, *, block_s,
                        seq_s):
    """One (batch, head) grid cell: the single query row attends its
    slab. q_ref (1, 1, D) pre-scaled; k/v (1, S, D) — the head's column
    slice of the (B, S, H*D) view of the slab; len_ref (1, 1, 1) int32."""
    q = q_ref[0]                       # (1, D), pre-scaled
    length = len_ref[0, 0, 0]
    nblk = seq_s // block_s

    def blk(j, carry):
        kb = k_ref[0, pl.ds(j * block_s, block_s), :]
        vb = v_ref[0, pl.ds(j * block_s, block_s), :]
        return _online_softmax_row(q, kb, vb, j * block_s, length, *carry)

    init = (jnp.zeros((1, q.shape[-1]), jnp.float32),
            jnp.full((1, 1), _NEG, jnp.float32),
            jnp.zeros((1, 1), jnp.float32))
    # KV blocks at or past this slot's length contribute nothing — stop
    # the loop there (the whole column is fetched all the same)
    upper = lax.min((length + block_s - 1) // block_s, nblk)
    acc, m, l = lax.fori_loop(0, upper, blk, init)
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _decode_attn_inplace_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                                acc_ref, m_ref, l_ref, *, block_s, n_head):
    """One (slot, sequence block) grid cell over the slab where it lies.
    len_ref (B,) int32, scalar-prefetched; q_ref/o_ref (1, 1, H, D), q
    pre-scaled; k/v (1, BS * H, D): rows [j*BS, (j+1)*BS) of the slot
    with the heads interleaved, row r of head h at sublane r * H + h, so
    a head's block is a strided load. The accumulators (H, D), (H, 1),
    (H, 1) live in VMEM scratch across the slot's blocks (an "arbitrary"
    axis) and are written out at the last one. Blocks past the slot's
    length are neither computed (the ``pl.when``) nor fetched (the index
    map repeats the last live block, and a block whose index did not
    change is not copied again)."""
    j = pl.program_id(1)
    length = len_ref[pl.program_id(0)]

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)

    @pl.when(j * block_s < length)
    def _():
        for h in range(n_head):
            rows = pl.ds(h, block_s, stride=n_head)
            hh = slice(h, h + 1)
            acc_ref[hh, :], m_ref[hh, :], l_ref[hh, :] = _online_softmax_row(
                q_ref[0, 0, hh, :], k_ref[0, rows, :], v_ref[0, rows, :],
                j * block_s, length, acc_ref[hh, :], m_ref[hh, :],
                l_ref[hh, :])

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)


def _head_rows(ref, i):
    """Key/value head ``i``'s rows of a (1, BS, Hkv, D) block of the
    slab itself, viewed (1, BS * Hkv, D) (one (8, 128) tile a row: the
    same memory): row r of head i lies at sublane r * Hkv + i, a
    strided load."""
    _, rows, hkv, d = ref.shape
    return ref.reshape(1, rows * hkv, d)[0, pl.ds(i, rows, stride=hkv), :]


def _grouped_scores(i, hh, q_ref, k_ref):
    """The g query rows that share key/value head i (rows ``hh``, as
    ``decode_attention_reference`` pairs them) meet its block in one
    (g, D) x (D, BS) product."""
    return jnp.dot(q_ref[0, 0, hh, :], _head_rows(k_ref, i).T,
                   preferred_element_type=jnp.float32)


def _grouped_values(i, p, v_ref):
    return jnp.dot(p, _head_rows(v_ref, i),
                   preferred_element_type=jnp.float32)


def decode_view(s, h, hkv, d, dtype, block_s=512):
    """The view (``ops/decode_stream.py``) of ``decode_attention``'s
    in-place kernel over (B, s, hkv, d) slabs of ``dtype`` under ``h``
    query heads: blocks of at most ``block_s`` rows of the slab where it
    lies, which costs no copy when the heads fill whole sublane tiles (8
    rows of 32 bits: 8 heads of f32; the (B, s*hkv, d) view is then a
    bitcast on the chip). At h == hkv the body is this file's one-pass
    online softmax, which keeps no scores, and the view is the rule's
    numbers alone; at h > hkv it is the two-pass body, handed the slabs
    themselves, a (1, rows, hkv, d) block a step, so that its call's
    text keeps the slab's shape (a trace's reader tells a decode step's
    attention by it)."""
    blocks = dict(seq=s, dtype=dtype, most=_DS.rows_within(hkv * d * 4,
                                                           block_s),
                  whole_tiles=hkv % 8 == 0 and h % hkv == 0, lanes=d)
    if h == hkv:
        return _DS.StreamView(DECODE_ATTN, **blocks)
    return _DS.StreamView(
        DECODE_ATTN_GROUPED, score_rows=h, q_block=(1, 1, h, d),
        k_block=(1, 1, hkv, d), v_block=(1, 1, hkv, d),
        o_block=(1, 1, h, d), groups=hkv, scores=_grouped_scores,
        values=_grouped_values, **blocks)


def pallas_decode_attention(q, k_cache, v_cache, lengths, scale=None,
                            block_s=512, interpret=False):
    """Pallas decode attention over BTHD slabs; same contract as
    ``decode_attention_reference``. A softmax over KV blocks in VMEM, no
    (B, H, S) score tensor in HBM, in one of two shapes chosen from the
    slab's own shape and dtype (``decode_view``):

    - in place: the slab viewed (B, S*H, D), grid (B, S // block_s),
      every head of a sequence block in one contiguous copy, dead
      blocks skipped. No relayout of the slab anywhere in the step. A
      slab of fewer heads than the query (grouped queries) runs the
      two-pass body (``decode_stream.stream_attend``) over the same
      blocks.
    - per head: the slab viewed (B, S, H*D), grid (B, H), each cell its
      head's whole column. On the chip that view is a physical copy of
      the slab: the path of shapes the free view does not exist for.

    Requires S % block_s == 0 (block_s is shrunk to fit)."""
    b, one, h, d = q.shape
    s = k_cache.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    qs = q * jnp.asarray(scale, q.dtype)
    lens = lengths.reshape(-1).astype(jnp.int32)
    view = decode_view(s, h, k_cache.shape[2], d, k_cache.dtype, block_s)
    if k_cache.shape[2] != h:
        return _DS.stream_attend(view, lens, qs, k_cache, v_cache,
                                 interpret)
    rows = _DS.block_positions(view)
    if rows is not None:
        def kv_block(bi, j, lens_ref):
            return bi, _DS.live_block(j, lens_ref, bi, rows), 0

        def qo_block(bi, j, lens_ref):
            return bi, 0, 0, 0

        kernel = functools.partial(_decode_attn_inplace_kernel,
                                   block_s=rows, n_head=h)
        return named_pallas_call(
            DECODE_ATTN, kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(b, s // rows),
                in_specs=[
                    pl.BlockSpec((1, 1, h, d), qo_block),
                    pl.BlockSpec((1, rows * h, d), kv_block),
                    pl.BlockSpec((1, rows * h, d), kv_block),
                ],
                out_specs=pl.BlockSpec((1, 1, h, d), qo_block),
                scratch_shapes=[pltpu.VMEM((h, d), jnp.float32),
                                pltpu.VMEM((h, 1), jnp.float32),
                                pltpu.VMEM((h, 1), jnp.float32)]),
            out_shape=jax.ShapeDtypeStruct((b, 1, h, d), q.dtype),
            interpret=interpret,
            **_tpu_params("parallel", "arbitrary"),
        )(lens, qs, k_cache.reshape(b, s * h, d),
          v_cache.reshape(b, s * h, d))
    block_s = _fit_block(s, block_s)
    if s % block_s:
        raise ValueError("slab length %d must divide block_s %d"
                         % (s, block_s))
    kernel = functools.partial(_decode_attn_kernel, block_s=block_s,
                               seq_s=s)
    out = named_pallas_call(
        DECODE_ATTN, kernel,
        grid=(b, h),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda bi, hi: (bi, 0, hi)),
            pl.BlockSpec((1, s, d), lambda bi, hi: (bi, 0, hi)),
            pl.BlockSpec((1, s, d), lambda bi, hi: (bi, 0, hi)),
            # (B, 1, 1): singleton minor block dims are FULL dims, which
            # Mosaic's block-shape tiling accepts (a (1, 1) block under
            # a B-sized second-minor dim is rejected)
            pl.BlockSpec((1, 1, 1), lambda bi, hi: (bi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda bi, hi: (bi, 0, hi)),
        out_shape=jax.ShapeDtypeStruct((b, 1, h * d), q.dtype),
        interpret=interpret,
        **_tpu_params("parallel", "parallel"),
    )(qs.reshape(b, 1, h * d), k_cache.reshape(b, s, h * d),
      v_cache.reshape(b, s, h * d), lens[:, None, None])
    return out.reshape(b, 1, h, d)


def _uneven_windows(hkv, dk):
    """Where key/value head i's ``dk`` channels lie in a flat row of
    ``hkv * dk`` lanes, as lane-ALIGNED windows: (width, [(start, offset)
    a head]): head i's channels are the lanes ``[start + offset, start +
    offset + dk)`` of the ``width`` lanes from ``start`` on, ``start`` and
    ``width`` whole 128-lane tiles. At dk = 192: 256 wide, heads at (0,
    0), (128, 64), (384, 0), (512, 64); at a dk of whole tiles the
    window is the head itself."""
    at = [(i * dk // 128 * 128, i * dk % 128) for i in range(hkv)]
    width = max(-(-(off + dk) // 128) * 128 for _, off in at)
    # the last window may not pass the row's end
    fit = [max(min(start, hkv * dk - width), 0) for start, _ in at]
    return width, [(to, off + start - to)
                   for to, (start, off) in zip(fit, at)]


def uneven_queries(q, hkv):
    """q (B, 1, H, dk) -> (B, 1, H, width): each query head's channels
    at its key/value head's offset inside that head's lane-aligned
    window of a flat K row (``_uneven_windows``), zeros around them, so
    that ``q' . K[start : start + width] = q . k_i`` exactly (the zeros
    add nothing) and the kernel slices K at whole tiles alone."""
    b, t, h, dk = q.shape
    width, at = _uneven_windows(hkv, dk)
    g = h // hkv
    return jnp.concatenate([
        jnp.pad(q[:, :, i * g:(i + 1) * g],
                ((0, 0),) * 3 + ((off, width - off - dk),))
        for i, (_, off) in enumerate(at)], axis=2)


def uneven_view(s, h, hkv, dk, dv, dtype, block_s=512):
    """The view (``ops/decode_stream.py``) of a slab of FLAT rows, K (B,
    s, hkv * dk) beside V (B, s, hkv * dv), under ``h`` query heads: a
    (1, rows, row) block of each slab itself a step. Key/value head i's
    keys are read as the lane-aligned window that holds them, against
    the g query rows laid out to match (``uneven_queries``: 1.33x the
    score product's FLOPs at dk = 192, no byte of the slab), its values
    the lanes [i dv, (i + 1) dv). No copy where both rows are whole
    128-lane tiles wide (4 x 192 = 768, 4 x 128 = 512)."""
    f32 = jnp.float32
    width, at = _uneven_windows(hkv, dk)

    def scores(i, hh, q_ref, k_ref):
        start = at[i][0]
        return jnp.dot(q_ref[0, 0, hh, :],
                       k_ref[0, :, start:start + width].T,
                       preferred_element_type=f32)

    def values(i, p, v_ref):
        return jnp.dot(p, v_ref[0, :, i * dv:(i + 1) * dv],
                       preferred_element_type=f32)

    return _DS.StreamView(
        DECODE_ATTN_UNEVEN, seq=s, dtype=dtype,
        most=_DS.rows_within(hkv * dk * 4, block_s), score_rows=h,
        whole_tiles=(hkv * dk) % 128 == 0 and dv % 128 == 0
        and h % hkv == 0, lanes=hkv * dk,
        q_block=(1, 1, h, width), k_block=(1, 1, hkv * dk),
        v_block=(1, 1, hkv * dv), o_block=(1, 1, h, dv), groups=hkv,
        scores=scores, values=values)


def pallas_decode_attention_uneven(q, k_rows, v_rows, lengths, hkv,
                                   scale=None, block_s=512,
                                   interpret=False):
    """``decode_attention_uneven`` through the kernel: the slabs are
    handed over as they lie."""
    b, _, h, dk = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(dk)
    view = uneven_view(k_rows.shape[1], h, hkv, dk,
                       v_rows.shape[2] // hkv, k_rows.dtype, block_s)
    return _DS.stream_attend(
        view, lengths.reshape(-1).astype(jnp.int32),
        uneven_queries(q * jnp.asarray(scale, q.dtype), hkv), k_rows,
        v_rows, interpret)


def decode_attention_uneven(q, k_rows, v_rows, lengths, hkv, scale=None,
                            block_s=512):
    """q (B, 1, H, dk) against slabs of FLAT rows, K (B, S, hkv * dk)
    and V (B, S, hkv * dv): key/value heads that fill no sublane tile,
    of a key width that is no whole tile and a value width of its own
    (4 heads of 192 beside 4 of 128) -> (B, 1, H, dv). On a TPU the
    streamed two-pass body over the slot's LIVE blocks (``uneven_view``);
    elsewhere, and where the view does not exist, the exact lax path
    over the rows read as (S, hkv, width), under the same scope."""
    dk = q.shape[-1]
    view = uneven_view(k_rows.shape[1], q.shape[2], hkv, dk,
                       v_rows.shape[2] // hkv, k_rows.dtype, block_s)
    if decode_stream_rows(view) is not None:
        return pallas_decode_attention_uneven(q, k_rows, v_rows, lengths,
                                              hkv, scale, block_s)
    b, s = k_rows.shape[:2]
    with jax.named_scope(DECODE_ATTN_UNEVEN):
        return decode_attention_reference(
            q, k_rows.reshape(b, s, hkv, dk), v_rows.reshape(b, s, hkv, -1),
            lengths, scale=scale)


def _use_pallas_decode(s: int, d: int) -> bool:
    """A step bound for a TPU, lane-aligned head dim, block-aligned slab
    (mirrors ops/attention.py:_use_pallas; PADDLE_TPU_NO_PALLAS opts
    out)."""
    if os.environ.get("PADDLE_TPU_NO_PALLAS", "0") == "1":
        return False
    if current_device().platform != "tpu":
        return False
    return d % 128 == 0 and s % 128 == 0 and s >= 128


def decode_stream_rows(view):
    """Positions a block of ``view``'s kernel brings in on the device a
    step traced now is bound for, or None where the cache is read whole
    (the lax path; the per-head kernel): ``block_positions`` and the
    device gate."""
    rows = _DS.block_positions(view)
    if rows is None or not _use_pallas_decode(view.seq, view.lanes or rows):
        return None
    return rows


def decode_attention(q, k_cache, v_cache, lengths, scale=None,
                     block_s=512):
    """Dispatch: Pallas kernel when eligible, exact lax fallback
    otherwise (numerics identical — same softmax). A slab with fewer
    key/value heads than the query has heads enters the in-place kernel
    where it can (``decode_view``: a 32-bit type, a multiple of 8 heads)
    and stays on the lax path otherwise (ONE key/value head; a 16-bit
    slab): the slab's shape and dtype choose."""
    s, d = k_cache.shape[1], q.shape[-1]
    h, hkv = q.shape[2], k_cache.shape[2]
    if h != hkv:
        if decode_stream_rows(decode_view(s, h, hkv, d, k_cache.dtype,
                                          block_s)) is not None:
            return pallas_decode_attention(q, k_cache, v_cache, lengths,
                                           scale=scale, block_s=block_s)
        with jax.named_scope(DECODE_ATTN_GROUPED):
            return decode_attention_reference(q, k_cache, v_cache, lengths,
                                              scale=scale)
    if _use_pallas_decode(s, d):
        return pallas_decode_attention(q, k_cache, v_cache, lengths,
                                       scale=scale, block_s=block_s)
    return decode_attention_reference(q, k_cache, v_cache, lengths,
                                      scale=scale)


@register_op("decode_attention_uneven")
def _decode_attention_uneven_op(ctx):
    """Inputs Q (B, 1, H, dk), KCache (B, S, Hkv * dk), VCache (B, S,
    Hkv * dv) FLAT rows, Lengths (B,) valid rows per slot (including
    the current token's); attrs n_kv_head, scale -> Out (B, 1, H,
    dv)."""
    return {"Out": decode_attention_uneven(
        ctx.input("Q"), ctx.input("KCache"), ctx.input("VCache"),
        ctx.input("Lengths"), int(ctx.attr("n_kv_head")),
        scale=ctx.attr("scale", None),
        block_s=int(ctx.attr("block_s", 512)))}


@register_op("decode_attention")
def _decode_attention_op(ctx):
    """Single-query attention against a KV slab. Inputs Q (B, 1, H, Dh),
    KCache/VCache (B, S, H, Dh) (or fewer heads that divide H: grouped
    queries), Lengths (B,) valid rows per slot
    (INCLUDING the current token's freshly appended row); attr scale.
    The (B, S) slab shapes are static — serving buckets S to powers of
    two so executable count stays bounded."""
    return {"Out": decode_attention(
        ctx.input("Q"), ctx.input("KCache"), ctx.input("VCache"),
        ctx.input("Lengths"), scale=ctx.attr("scale", None),
        block_s=int(ctx.attr("block_s", 512)))}


# ---------------------------------------------------------------------------
# cache slab updates
# ---------------------------------------------------------------------------


def cache_append(cache, new, pos):
    """cache (B, S, ...) with new (B, 1, ...) or (B, ...) scattered at
    row pos[b] per sequence -> updated cache. Functional; under donation
    XLA performs it in place (one dynamic-update-slice per slot)."""
    b, s = cache.shape[0], cache.shape[1]
    if new.ndim == cache.ndim:
        if new.shape[1] != 1:
            # silently keeping row 0 of a multi-row append would drop
            # K/V rows with no error anywhere downstream
            raise ValueError(
                "cache_append appends ONE row per sequence; New has "
                "time dim %d (append rows one step at a time)"
                % new.shape[1])
        new = new[:, 0]
    pos = jnp.clip(pos.reshape(-1).astype(jnp.int32), 0, s - 1)
    return cache.at[jnp.arange(b), pos].set(new.astype(cache.dtype))


def cache_gather(cache, index):
    """Reorder slab rows along axis 0: out[i] = cache[index[i]] (beam
    parent reordering / slot compaction). Gathering is over SLOTS, not
    sequence positions — the per-slot time axis rides along whole."""
    return jnp.take(cache, index.reshape(-1).astype(jnp.int32), axis=0)


@register_op("cache_append")
def _cache_append_op(ctx):
    """Inputs Cache (B, S, ...), New (B, 1, ...) or (B, ...), Pos (B,)
    int32 write positions (the slot's CURRENT length — append, not
    overwrite) -> Out: the updated slab."""
    return {"Out": cache_append(ctx.input("Cache"), ctx.input("New"),
                                ctx.input("Pos"))}


@register_op("cache_gather")
def _cache_gather_op(ctx):
    """Inputs Cache (B, S, ...), Index (N,) int32 slot indices -> Out
    (N, S, ...): slab rows reordered/duplicated by slot."""
    return {"Out": cache_gather(ctx.input("Cache"), ctx.input("Index"))}


# ---------------------------------------------------------------------------
# the ring of a sliding-window layer
# ---------------------------------------------------------------------------
#
# A layer whose queries see the last `window` keys keeps a RING of
# `window` rows a slot, not a row per position: position p lives at
# row p mod window. The keys are rotated at their absolute positions
# before they are stored and a softmax does not care for the order of
# its keys, so attending the ring is attending its live rows: all of
# them once `window` positions have been written, rows [0, held) before.


def ring_append(ring, new, pos):
    """ring (B, W, ...) with ``new`` (B, 1, ...) written at row
    ``pos[b] mod W``: the position's own row, over the one that left
    the window."""
    with jax.named_scope(RING_APPEND):
        w = ring.shape[1]
        return cache_append(ring, new,
                            pos.reshape(-1).astype(jnp.int32) % w)


def ring_pack(rows, lengths, window):
    """A prefill's rows (B, T, ...) -> the ring (B, W, ...) an
    admission stores: each row's last ``min(len, W)`` positions, at
    ``position mod W``. Rows the prompt did not reach hold whatever the
    padding computed; ``decode_attn_ring`` masks them by length.

    One contiguous slice a row (the positions ``[start, start + W)``,
    ``start = max(len - W, 0)``), then a rotation by ``start mod W``: a
    ``dynamic_slice`` under ``vmap``, never an elementwise gather (a
    ``take_along_axis`` over a wide array hung the chip: PERF.md 7 h)."""
    w = int(window)
    b, t = rows.shape[0], rows.shape[1]
    with jax.named_scope(RING_PACK):
        if t < w:
            rows = jnp.pad(rows, [(0, 0), (0, w - t)]
                           + [(0, 0)] * (rows.ndim - 2))
            t = w
        lens = jnp.clip(lengths.reshape(-1).astype(jnp.int32), 0, t)
        start = jnp.maximum(lens - w, 0)

        def one(row, at):
            seg = lax.dynamic_slice_in_dim(row, at, w, axis=0)
            # seg[j] is position at + j and goes to row (at + j) mod W
            return lax.dynamic_slice_in_dim(
                jnp.concatenate([seg, seg], axis=0), (w - at % w) % w, w,
                axis=0)

        return jax.vmap(one)(rows, start)


def decode_attn_ring(q, k_ring, v_ring, lengths, scale=None, sink=None):
    """q (B, 1, H, Dh) against rings (B, W, Hkv, Dh) (V's heads of their
    own width where they have one); ``lengths`` (B,) the positions held
    INCLUDING this step's freshly written row; ``sink`` (H,): a learned
    scalar a query head in the softmax's denominator. The
    exact grouped lax path over ``min(lengths, W)`` live rows, on every
    device: a ring is one block a slot and nearly all of it live, so
    the kernel has no dead rows to skip (0.42-0.45 ms a call through it
    against 0.39 on the chip; PERF.md, PR 32)."""
    with jax.named_scope(DECODE_ATTN_RING):
        w = k_ring.shape[1]
        live = jnp.minimum(lengths.reshape(-1).astype(jnp.int32), w)
        return decode_attention_reference(q, k_ring, v_ring, live,
                                          scale=scale, sink=sink)


@register_op("ring_append")
def _ring_append_op(ctx):
    """Inputs Cache (B, W, ...), New (B, 1, ...), Pos (B,) the
    position written (the slot's CURRENT length) -> Out: the ring with
    row ``Pos mod W`` replaced."""
    return {"Out": ring_append(ctx.input("Cache"), ctx.input("New"),
                               ctx.input("Pos"))}


@register_op("ring_pack")
def _ring_pack_op(ctx):
    """Inputs X (B, T, ...), Lengths (B,); attr window -> Out (B, W,
    ...): each row's last ``min(len, W)`` positions at ``position mod
    W``."""
    return {"Out": ring_pack(ctx.input("X"), ctx.input("Lengths"),
                             int(ctx.attr("window")))}


@register_op("decode_attn_ring")
def _decode_attn_ring_op(ctx):
    """Inputs Q (B, 1, H, Dh), KCache/VCache (B, W, Hkv, Dh), Lengths
    (B,) positions held including the current token's, optional Sink
    (H,) -> Out (B, 1, H, V's width)."""
    return {"Out": decode_attn_ring(
        ctx.input("Q"), ctx.input("KCache"), ctx.input("VCache"),
        ctx.input("Lengths"), scale=ctx.attr("scale", None),
        sink=ctx.input("Sink"))}
