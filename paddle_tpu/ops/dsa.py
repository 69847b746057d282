"""Learned sparse attention over a latent slab (DeepSeek-V3.2-Exp's
"lightning indexer" over DeepSeek-V2's latent rows, ``ops/mla.py``): a
query attends the ``index_topk`` EARLIER POSITIONS A LEARNED INDEXER
SCORES HIGHEST, not all of them. A position keeps, beside its latent
row, ONE index key ``kI`` (``index_head_dim`` floats: LayerNorm of a
projection of the layer's input, its first ``rotary_dim`` channels
rotated in the half-split layout); a query has ``index_heads`` index
queries ``qI_j`` from its QUERY LATENT ``c_q`` (rotated the same way)
and a weight ``w_j`` a head from the layer's input, and scores

    I(t, s) = sum_j w_j(t) relu(qI_j(t) . kI(s)),   s <= t.

``S_t`` is the ``index_topk`` positions of largest ``I(t, s)``: every
position while ``t < index_topk``, equal scores to the LOWER position
(``lax.top_k``'s rule). Attention then runs over ``S_t`` alone.

Three steps, one scope each, so that a device event can be told:

- ``ptpu.dsa_index`` (``index_queries``, ``index_keys``,
  ``index_scores``): the products. The weighted sum over the index
  heads is float32 multiplies and adds, no contraction (a matmul would
  round ``relu(.)`` and ``w`` to bfloat16 on a TPU); a prefill never
  holds more than a block of query rows times a chunk of index heads of
  per-head products.
- ``ptpu.dsa_select`` (``select``): the EXACT choice, as a mask and
  without a sort. A float's bits, the sign folded, order as unsigned
  integers; the ``k``-th largest of a row is found bit by bit (32
  counts of ``key >= candidate``), and where more rows tie with it than
  ``k`` has room for (rare: a ``lax.cond``), the lowest positions among
  them bit by bit too. Every pass is a compare and a count over the
  row: no sort, no gather, nothing approximate and no block-granular
  stand-in. ``lax.top_k`` over 16,384 positions sorts them.
- ``ptpu.dsa_attend``: attention under that mask. A prefill
  (``prefill_mask`` -> ``mla.latent_prefill``'s flash calls, a mask
  that differs by (query, key)); a step (``step_mask`` ->
  ``mla.mla_decode`` under the mask: on a TPU the kernel
  ``ptpu.dsa_attend_step`` streams a slot's LIVE blocks once and masks
  the rows not chosen inside, and ``ptpu.dsa_index_step`` scores a
  slot's live blocks of index keys; elsewhere the lax forms, which read
  every row of every slot; a gathered step is ROADMAP M3's).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import attention as _A
from . import decode_stream as _DS
from . import kv_cache as _KV
from . import math as _W
from . import rope as _R
from .registry import register_op

DSA_INDEX = "ptpu.dsa_index"
DSA_SELECT = "ptpu.dsa_select"
DSA_ATTEND = "ptpu.dsa_attend"

# query rows whose index scores a prefill holds at once, and index heads
# whose per-head products it holds beside them: (256, 16, 16384) float32
# is 268 MB, where all 64 heads of all 16,384 rows would be 69 GB
_ROWS = 256
_HEADS = 16


def index_weight_scale(n_heads: int, head_dim: int) -> float:
    """``index_heads^-1/2 index_head_dim^-1/2``: what ``w`` carries
    beside the learned projection."""
    return float(n_heads) ** -0.5 * float(head_dim) ** -0.5


def window_positions(positions, t):
    """A cached step's positions: (B,) the position of a slot's FIRST
    query row; a window of ``t`` > 1 rows stands at ``positions[b] +
    0..t-1`` -> (B, t). None (a prefill: 0..T-1) and (B,) at one row
    pass through."""
    if positions is None or t == 1 or positions.ndim != 1:
        return positions
    return (positions.astype(jnp.int32)[:, None]
            + jnp.arange(t, dtype=jnp.int32)[None, :])


def _rotate_first(x, positions, rot):
    """x (B, T, H, d): its FIRST ``rot["rotary_dim"]`` channels rotated
    at ``positions`` (None: 0..T-1), in the half-split layout or, under
    ``rot["interleave"]``, on the pairs (2i, 2i+1)."""
    inv = _R.rope_inv_freq(int(rot["rotary_dim"]),
                           float(rot.get("theta", 10000.0)))
    return _R.rope(x, window_positions(positions, x.shape[1]), inv, 1.0,
                   bool(rot.get("interleave", False)))


def index_queries(c_q, u, w_iq, w_iw, positions, n_heads, rot):
    """The query side: c_q (B, T, q_rank) the query latent, u (B, T, D)
    the layer's input -> (qI (B, T, J, d) rotated, w (B, T, J) with the
    two inverse square roots)."""
    b, t, _ = c_q.shape
    with jax.named_scope(DSA_INDEX):
        q_i = _W.wmm(c_q, w_iq).reshape(b, t, int(n_heads), -1)
        q_i = _rotate_first(q_i, positions, rot)
        w = _W.wmm(u, w_iw) * index_weight_scale(n_heads, q_i.shape[-1])
        return q_i.astype(u.dtype), w.astype(jnp.float32)


def index_keys(u, w_ik, gain, bias, positions, eps, rot):
    """The index key a position keeps: u (B, T, D) -> (B, T, d) =
    LayerNorm(u W_Ik), its first channels rotated."""
    with jax.named_scope(DSA_INDEX):
        k = _W.wmm(u, w_ik).astype(jnp.float32)
        mu = jnp.mean(k, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(k - mu), axis=-1, keepdims=True)
        k = (k - mu) * lax.rsqrt(var + eps) * gain + bias
        return _rotate_first(k[:, :, None, :], positions,
                             rot)[:, :, 0].astype(u.dtype)


def index_scores(q_i, w, k_i, heads=_HEADS):
    """I (B, R, S) of query rows q_i (B, R, J, d), w (B, R, J) on keys
    k_i (B, S, d), ``heads`` index heads' products at a time."""
    b, r, j, d = q_i.shape
    c = heads if j % heads == 0 else j
    with jax.named_scope(DSA_INDEX):
        kf = k_i.astype(jnp.float32)

        def chunk(acc, qw):
            q_c, w_c = qw                      # (B, R, c, d), (B, R, c)
            s = jnp.einsum("brjd,bsd->brjs", q_c.astype(jnp.float32), kf)
            return acc + jnp.sum(jnp.maximum(s, 0.0) * w_c[..., None],
                                 axis=2), None

        q_c = jnp.moveaxis(q_i.reshape(b, r, j // c, c, d), 2, 0)
        w_c = jnp.moveaxis(w.reshape(b, r, j // c, c), 2, 0)
        out, _ = lax.scan(chunk, jnp.zeros((b, r, k_i.shape[1]),
                                           jnp.float32), (q_c, w_c))
        return out


def _ordered_bits(x):
    """float32 -> uint32 that orders as the floats do."""
    bits = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(1 << 31)


def select(scores, live, k):
    """The mask of ``lax.top_k(scores, k)`` among the ``live`` positions
    of the last axis (all of them where fewer than ``k`` are live; equal
    scores to the lower position): scores (..., S) float32, live (...,
    S) bool -> (..., S) bool. No sort: see the module's text."""
    k = int(k)
    s = scores.shape[-1]
    with jax.named_scope(DSA_SELECT):
        key = jnp.where(live, _ordered_bits(scores), jnp.uint32(0))
        want = jnp.minimum(jnp.sum(live, axis=-1, keepdims=True,
                                   dtype=jnp.int32), k)

        def count(mask):
            return jnp.sum(mask, axis=-1, keepdims=True, dtype=jnp.int32)

        def kth(i, thr):
            cand = thr | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
            return jnp.where(count(key >= cand) >= want, cand, thr)

        # the largest threshold that at least ``want`` keys reach: the
        # want-th largest key (a dead position's 0 is never reached)
        thr = lax.fori_loop(0, 32, kth, jnp.zeros(want.shape, jnp.uint32))
        reach = (key >= thr) & live

        def lower_of_ties():
            above = key > thr
            room = want - count(above & live)
            tie = (key == thr) & live
            pos = lax.broadcasted_iota(jnp.int32, key.shape, key.ndim - 1)
            bits = max(int(s).bit_length(), 1)

            def edge(i, p):
                cand = p | (jnp.int32(1) << (bits - 1 - i))
                ok = (cand <= s) & (count(tie & (pos < cand)) <= room)
                return jnp.where(ok, cand, p)

            # the most positions whose ties still fit the room
            p = lax.fori_loop(0, bits, edge, jnp.zeros(want.shape,
                                                       jnp.int32))
            return (above & live) | (tie & (pos < p))

        return lax.cond(jnp.any(count(reach) > want), lower_of_ties,
                        lambda: reach)


def _block_rows(t, want=_ROWS):
    rows = 1
    while rows * 2 <= want and t % (rows * 2) == 0:
        rows *= 2
    return rows if t % rows == 0 else t


def prefill_mask(q_i, w, k_i, topk, lengths=None, rows=_ROWS):
    """A prefill's selection: q_i (B, T, J, d), w (B, T, J), k_i (B, T,
    d) -> (B, T, T) int8, 1 where query t attends key s: ``s <= t`` and
    among the ``topk`` highest ``I(t, .)``. A block of ``rows`` query
    rows at a time: the scores of all (T, T) pairs never exist at
    once. With ``lengths`` (B,), the prompts' live tokens, the blocks
    of query rows past the longest prompt are not scored and stay 0
    (a bucket's padding: rows no one reads)."""
    b, t, j, d = q_i.shape
    r = _block_rows(t, rows)
    nb = t // r
    col = jnp.arange(t, dtype=jnp.int32)[None, None, :]

    def block(i, masks):
        row0 = i * r
        seen = col <= (row0 + jnp.arange(r, dtype=jnp.int32))[None, :, None]
        seen = jnp.broadcast_to(seen, (b, r, t))
        chosen = select(
            index_scores(lax.dynamic_slice_in_dim(q_i, row0, r, axis=1),
                         lax.dynamic_slice_in_dim(w, row0, r, axis=1), k_i),
            seen, topk)
        return lax.dynamic_update_slice_in_dim(
            masks, chosen.astype(jnp.int8), row0, axis=1)

    live = nb if lengths is None else jnp.minimum(
        (jnp.max(lengths).astype(jnp.int32) + r - 1) // r, nb)
    return lax.fori_loop(0, live, block, jnp.zeros((b, t, t), jnp.int8))


# positions a block of the step's kernel: those a block of the attention
# under the choice brings in (``mla._LATENT_BLOCK_LANES``), so that both
# stream the same live blocks of a slot (``rows_scored`` of a step's
# dispatch counts); (1024, 128) float32 is 512 KiB
_STEP_BLOCK = 1024


def _step_scores_kernel(len_ref, q_ref, w_ref, k_ref, o_ref, *, block_s,
                        n_q=1):
    """One (slot, block) grid cell of ``pallas_step_scores``: q_ref (1,
    n_q J, d), w_ref (1, n_q J, 1), k_ref (1, BS, d) -> o_ref (1, n_q,
    BS); zeros for a block past the slot's live rows (never fetched).
    A window's ``n_q`` query rows are scored one after the other on the
    block ONE fetch brought in, each by the one-row body: the same
    products in the same order as ``n_q`` steps."""
    j = pl.program_id(1)
    live_blocks = (len_ref[pl.program_id(0)] + block_s - 1) // block_s
    heads = q_ref.shape[1] // n_q

    def score(at):
        """One query row's index heads on the block: ``at`` its rows of
        q_ref and w_ref (None: all of them, the one-row step)."""
        q = q_ref[0] if at is None else q_ref[0, at]
        s = lax.dot_general(
            q.astype(jnp.bfloat16), k_ref[0].astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # (J, BS)
        s = jnp.maximum(s, 0.0)
        w = w_ref[0] if at is None else w_ref[0, at]
        return jnp.sum(s * w, axis=0, keepdims=True)

    @pl.when(j < live_blocks)
    def _():
        if n_q == 1:
            o_ref[0] = score(None)
        for t in range(n_q if n_q > 1 else 0):
            o_ref[0, pl.ds(t, 1)] = score(pl.ds(t * heads, heads))

    @pl.when(j >= live_blocks)
    def _():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)


def step_block(s, d, dtype):
    """Positions a block of the step's kernel on the device a step
    traced now is bound for, or None where the lax form scores the slab
    (``kv_cache._use_pallas_decode``: a TPU, whole lanes; a float32 slab
    of whole blocks)."""
    rows = _DS.fit_block_rows(s, _STEP_BLOCK)
    if (jnp.dtype(dtype).itemsize != 4 or rows is None or rows < 128
            or not _KV._use_pallas_decode(s, d)):
        return None
    return rows


def pallas_step_scores(q_i, w, keys, lens, block_s=_STEP_BLOCK,
                       interpret=False, n_q=1):
    """``index_scores`` of ONE query row a slot through a kernel
    (``ptpu.dsa_index_step``): q_i (B, J, d), w (B, J), the slab of
    index keys (B, S, d) where it lies, ``lens`` (B,) live rows -> (B,
    S) float32, zeros past a slot's last live block. A window of
    ``n_q`` query rows a slot: q_i (B, n_q J, d), w (B, n_q J), ``lens``
    the LAST row's live rows -> (B, n_q, S): a block is fetched once
    and scored ``n_q`` times. A slot's live
    blocks are read once, as float32, and rounded to bfloat16 in vector
    memory (the lax form converts the whole slab, every slot's every
    row, each step, and reads the copy once a chunk of heads); all
    heads' products of a block at once, summed in float32."""
    b, j, d = q_i.shape
    s = keys.shape[1]
    rows = _DS.fit_block_rows(s, block_s)
    lens = jnp.clip(lens.reshape(-1).astype(jnp.int32), 0, s)
    kernel = (functools.partial(_step_scores_kernel, block_s=rows)
              if n_q == 1 else
              functools.partial(_step_scores_kernel, block_s=rows, n_q=n_q))
    out = _A.named_pallas_call(
        DSA_INDEX + "_step", kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, s // rows),
            in_specs=[
                pl.BlockSpec((1, j, d), lambda bi, i, lens_ref: (bi, 0, 0)),
                pl.BlockSpec((1, j, 1), lambda bi, i, lens_ref: (bi, 0, 0)),
                pl.BlockSpec((1, rows, d), lambda bi, i, lens_ref: (
                    bi, _DS.live_block(i, lens_ref, bi, rows), 0)),
            ],
            out_specs=pl.BlockSpec((1, n_q, rows),
                                   lambda bi, i, lens_ref: (bi, 0, i))),
        out_shape=jax.ShapeDtypeStruct((b, n_q, s), jnp.float32),
        interpret=interpret,
        **_A._tpu_params("parallel", "arbitrary"),
    )(lens, q_i, w.astype(jnp.float32)[..., None], keys)
    return out[:, 0] if n_q == 1 else out


def step_mask(q_i, w, keys, kv_lengths, topk):
    """A step's selection, one set a slot: q_i (B, 1, J, d), w (B, 1,
    J), the slab of index keys (B, S, d) with ``kv_lengths`` (B,) live
    rows (this step's included) -> (B, S) bool. The scores by the
    kernel over a slot's live blocks where the device and the slab's
    shape have one (``step_block``), by the lax form elsewhere. A WINDOW
    of T > 1 query rows a slot (a round of a model with a prediction
    layer): q_i (B, T, J, d), w (B, T, J), ``kv_lengths`` the FIRST
    row's live rows, row t's ``kv_lengths + t`` -> (B, T, S): each row
    its own choice of ``topk`` among its own live rows."""
    b, t, j, d = q_i.shape
    s = keys.shape[1]
    lens = kv_lengths.reshape(-1).astype(jnp.int32)
    kernel = step_block(s, keys.shape[-1], keys.dtype) is not None
    if t == 1:
        live = jnp.arange(s, dtype=jnp.int32)[None, :] < lens[:, None]
        if kernel:
            scores = pallas_step_scores(q_i[:, 0], w[:, 0], keys, lens)
        else:
            scores = index_scores(q_i, w, keys)[:, 0]
        return select(scores, live, topk)
    row_lens = lens[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    live = (jnp.arange(s, dtype=jnp.int32)[None, None, :]
            < row_lens[:, :, None])
    if kernel:
        scores = pallas_step_scores(q_i.reshape(b, t * j, d),
                                    w.reshape(b, t * j), keys,
                                    lens + (t - 1), n_q=t)
    else:
        scores = index_scores(q_i, w, keys)
    return select(scores, live, topk)


def _index_rot(ctx):
    return {"theta": float(ctx.attr("theta", 10000.0)),
            "rotary_dim": int(ctx.attr("rotary_dim")),
            "interleave": bool(ctx.attr("interleave", False))}


@register_op("dsa_index_keys")
def _index_keys_op(ctx):
    """Inputs X (B, T, D), W (D, d), Gain, Bias (d,), optional Positions
    (B,): the first row's position. Attrs epsilon, theta, rotary_dim,
    interleave -> Out (B, T, d)."""
    return {"Out": index_keys(
        ctx.input("X"), ctx.input("W"), ctx.input("Gain"), ctx.input("Bias"),
        ctx.input("Positions"), float(ctx.attr("epsilon", 1e-5)),
        _index_rot(ctx))}


@register_op("dsa_mask")
def _mask_op(ctx):
    """Inputs CQ (B, T, q_rank), X (B, T, D), WQ (q_rank, J * d), WW (D,
    J), Keys (B, T | S, d); a step's Positions (B,) and Lengths (B,) live
    rows; a prefill's optional Lengths (B,), the prompts' live tokens.
    Attrs n_heads, topk, theta, rotary_dim, interleave -> Out: a
    prefill's (B, T, T) int8 selection, a step's (B, S) bool, or a
    window's (B, T, S) bool (T > 1 query rows on the slab)."""
    pos = ctx.input("Positions")
    q_i, w = index_queries(ctx.input("CQ"), ctx.input("X"), ctx.input("WQ"),
                           ctx.input("WW"), pos, int(ctx.attr("n_heads")),
                           _index_rot(ctx))
    if pos is None:
        return {"Out": prefill_mask(q_i, w, ctx.input("Keys"),
                                    int(ctx.attr("topk")),
                                    ctx.input("Lengths"))}
    return {"Out": step_mask(q_i, w, ctx.input("Keys"),
                             ctx.input("Lengths"), int(ctx.attr("topk")))}
