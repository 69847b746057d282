"""One query row a slot attending a cache that lies where it lies,
streamed in live blocks, two passes: the ONE Pallas body behind
``ptpu.decode_attn_grouped`` and ``ptpu.decode_attn_uneven``
(``kv_cache.py``: a slab of fewer heads than the query; one whose keys
are wider than its values), ``ptpu.diff_attn_rows`` (``diff_attn.py``:
a slab of flat rows), ``ptpu.eva_attn`` (``eva.py``: a window's rows
and the chunk summaries before them, a range of a slot), ``ptpu.
mla_latent_attn`` (``mla.py``: a latent slab's transposed view; the
same view under a choice of rows has a one-pass kernel of its own
there), and the one rule for the positions a block of any of the six
brings in.

The body. Grid ``(slot, 2 * n_blk)``, the lengths scalar-prefetched.
Steps ``[0, n_blk)`` stream K: block ``j``'s scores go to a ``(h, S)``
scratch under a running maximum. Step ``n_blk`` sums the weights. Steps
``[n_blk, 2 n_blk)`` stream V: block ``j - n_blk``'s weights,
NORMALISED, are multiplied against it into an ``(h, Dv)`` accumulator,
written out at the last step. Two passes, so that the products round
what the lax paths' round (operands to bfloat16 at the TPU's default
precision: the scaled query, K, the normalised weights, V) and a step's
logits do not move with the path (an online softmax rounds unnormalised
weights: 0.0024 of the output's norm apart; PERF.md, PR 32). K's block
index stops at the slot's last live block and V's waits at block 0
meanwhile (``live_block``, ``second_pass_block``), and a block whose
index did not change is not copied again: each live block is fetched
once a pass, a dead one never, and a dead step computes nothing.

Where K and V are ONE array (the latent view: V's block is the first
``rank`` sublane rows of the block K's pass fetched) and a slot's V
part fits in VMEM (``kept_vmem_bytes``), the second pass reads no
memory: K's pass keeps each block's V part in a scratch of the slot's
positions, and the slot's last K step sums the weights and takes the
weighted sum over the live blocks from there, the same product on the
same weights in the same order. The call then has no V operand and its
grid is ``(slot, n_blk)``: each live row is fetched ONCE (PERF.md, PR
55).

What differs between the six is how a block yields a head's keys and
values, and that comes in as a ``StreamView``, written in the file that
owns the layout: the block shapes, which index of a block is the
sequence, and two functions of refs, traced inside the body.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _tpu_params, named_pallas_call

_NEG = -1e30

# a K or V block: 128 rows of the 32 x 128 float32 slab, a whole MXU
# tile a head (at 64 rows a call takes 0.30 ms where it takes 0.24;
# PERF.md, PR 25), 512 rows of an 8 x 128 one (1.37 ms a call over 64
# slots of ~1,450 live rows, 1.34 at 256 rows and 1.38 at 128: one rule
# serves both; PERF.md, PR 32). Both slabs' blocks, double-buffered, are
# 8 MiB of the 16 MiB of VMEM a v5e kernel may use.
_BLOCK_BYTES = 2 * 2**20
# the two-pass body keeps a slot's scores, (query rows, S) float32, in
# VMEM beside the blocks: 1 MiB for 64 heads on 4096 rows
_SCORE_BYTES = 4 * 2**20


@dataclasses.dataclass(frozen=True)
class StreamView:
    """A cache layout as the streamed decode kernels see it.

    The rule's numbers (``block_positions``): ``seq`` positions a slot
    of ``dtype``, at most ``most`` of them a block and at least
    ``least`` (8 sublane rows; 128 lanes where the positions are a
    block's minor dimension), ``score_rows`` query rows a slot whose
    scores wait in VMEM between the passes (0: the kernel keeps none),
    ``whole_tiles`` whether the layout fills whole tiles, so that the
    kernel's view of the cache costs no copy, and ``lanes``, a block's
    minor dimension where that is not the positions themselves (the
    device gate wants it lane-aligned).

    The body's (``stream_attend``; a kernel with a body of its own,
    ``kv_cache``'s one-pass online softmax, leaves them empty): the
    shapes of ONE position's block of q, K, V and o, ``seq_axis`` the
    index of a K or V block (and of the operand) that is the sequence,
    and for each of the ``groups`` key/value heads of a block, whose
    query rows ``hh`` are rows ``[i g, (i + 1) g)`` of the scratch,
    ``scores(i, hh, q_ref, k_ref) -> (g, BS)`` and ``values(i, p, v_ref)
    -> (g, Dv)`` of the normalised weights ``p (g, BS)``."""
    name: str
    seq: int
    dtype: Any
    most: int
    score_rows: int = 0
    least: int = 8
    whole_tiles: bool = True
    lanes: int = 0
    q_block: Tuple[int, ...] = ()
    k_block: Tuple[int, ...] = ()
    v_block: Tuple[int, ...] = ()
    o_block: Tuple[int, ...] = ()
    seq_axis: int = 1
    groups: int = 1
    scores: Callable = None
    values: Callable = None


def fit_block_rows(s, want):
    """The largest power of two of rows, at least 8 and at most ``want``
    (and ``s``), that divides ``s``; None where none does."""
    want = min(want, s)
    rows = 8
    while rows * 2 <= want:
        rows *= 2
    while rows > 8 and s % rows:
        rows //= 2
    return None if s % rows else rows


def rows_within(position_bytes, block_s=512):
    """``StreamView.most`` of a slab whose position is
    ``position_bytes`` wide: ``block_s`` rows, or as many as
    ``_BLOCK_BYTES`` hold."""
    return min(block_s, _BLOCK_BYTES // position_bytes)


def block_positions(view):
    """Positions a block of ``view``'s kernel brings in, or None where
    the lax path attends the cache: a layout that does not fill whole
    tiles, a type that is not 32 bits wide (narrower types pack two or
    four rows a sublane: Mosaic has no strided load of them, and the
    compiler tiles and lays them out otherwise), a slot's scores that do
    not fit beside the blocks, no block of at least ``view.least``
    positions that divides the slot's. Shape and type alone; the device
    is ``kv_cache.decode_stream_rows``'s to add."""
    if (not view.whole_tiles or jnp.dtype(view.dtype).itemsize != 4
            or view.score_rows * view.seq * 4 > _SCORE_BYTES):
        return None
    n = fit_block_rows(view.seq, view.most)
    return n if n is not None and n >= view.least else None


# what the one-read body may hold in VMEM (a v5e core has 128 MiB): the
# Mistral-Small-4 cell's call holds 21.5 MB (16.8 of kept rows, 2.1 of
# scores, 2.6 of blocks), the Ling-3.0-flash cell's 40.4 (33.6, 2.1, 4.7)
_KEPT_VMEM_CAP = 64 * 2**20


def kept_vmem_bytes(view, rows=None):
    """The VMEM the body holds where it reads ``view``'s cache ONCE (K
    and V one array): the V part of every position of a slot, kept
    between the passes, beside the slot's scores and K's two blocks of
    ``rows`` positions (``block_positions``'s unless given); None where
    that passes ``_KEPT_VMEM_CAP`` and the body reads the live rows
    twice, and where this body does not attend the view at all (no
    block; a kernel of its own, which keeps no scores). Shape and type
    alone."""
    rows = block_positions(view) if rows is None else rows
    if rows is None or not view.score_rows:
        return None
    held = 4 * (math.prod(view.v_block) * view.seq
                + view.score_rows * view.seq
                + 2 * math.prod(view.k_block) * rows)
    return held if held <= _KEPT_VMEM_CAP else None


# The index maps' arithmetic, of the prefetched lengths ``lens`` (a ref
# under a grid, any sequence of integers in a test) and the slot ``bi``.


def last_block(lens, bi, rows):
    """The last live block of slot ``bi`` (block 0 of an empty one:
    something has to be fetched)."""
    return jnp.maximum(lens[bi] + rows - 1, rows) // rows - 1


def live_block(j, lens, bi, rows, starts=None):
    """The block step ``j`` of a pass over K reads: ``j`` (counted from
    the block of the slot's first live row where ``starts`` says the
    live rows do not begin at row 0), and past the slot's last live
    block that block again (not copied again)."""
    if starts is not None:
        j = starts[bi] // rows + j
    return jnp.minimum(j, last_block(lens, bi, rows))


def second_pass_block(j, lens, bi, rows, n_blk, starts=None):
    """The block step ``j`` of ``2 * n_blk`` reads of V: its first live
    block (block 0 without ``starts``) while K streams, then as K's."""
    if starts is None:
        return jnp.clip(j - n_blk, 0, last_block(lens, bi, rows))
    first = starts[bi] // rows
    return jnp.clip(first + j - n_blk, first, last_block(lens, bi, rows))


def _two_pass_kernel(len_ref, *refs, view, block_s, n_blk, ranged=False,
                     once=False):
    """One (slot, step) grid cell of the module's body. len_ref (B,)
    int32, the row a slot's live rows END before; under ``ranged`` a
    second prefetched (B,) int32 comes after it, the row they START at
    (else row 0): a pass's step ``j`` then works on block ``first + j``,
    ``first`` the block of that row, and the blocks before it are
    neither fetched nor computed, as the blocks past the end are. q_ref
    pre-scaled; ``s_ref`` (h, S), ``m_ref`` and ``l_ref`` (h, 1),
    ``acc_ref`` (h, Dv) live across the slot's steps (an "arbitrary"
    axis). Under ``once`` (K and V one array) there is no ``v_ref`` and
    no second half of the grid: a live K step also keeps its block's V
    part in ``kept_ref`` (V's block with all S positions on the
    sequence's index, after ``acc_ref``), and step ``n_blk - 1`` sums
    the weights and makes the second pass over the live blocks from
    there."""
    start_ref = None
    if ranged:
        start_ref, refs = refs[0], refs[1:]
    if once:
        q_ref, k_ref, o_ref, s_ref, m_ref, l_ref, acc_ref, kept_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, s_ref, m_ref, l_ref, acc_ref = refs
    j = pl.program_id(1)
    length = len_ref[pl.program_id(0)]
    first = 0
    if ranged:
        start = start_ref[pl.program_id(0)]
        # an empty range is a slot of length 0: nothing live, zeros out
        length = jnp.where(length > start, length, 0)
        first = start // block_s
    live_blocks = (length + block_s - 1) // block_s

    def block(step):
        """The block a pass's step ``step`` works on."""
        return first + step if ranged else step

    g = view.score_rows // view.groups
    heads = [(i, slice(i * g, (i + 1) * g)) for i in range(view.groups)]

    def col_of(blk):
        return pl.multiple_of(blk * block_s, block_s)

    def kept(col0):
        """``kept_ref``'s block of the positions from ``col0``: what
        ``v_ref`` would hold of them."""
        where = [slice(None)] * len(view.v_block)
        where[view.seq_axis] = pl.ds(col0, block_s)
        return kept_ref.at[tuple(where)]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(block(j) < live_blocks)
    def _():
        col0 = col_of(block(j))
        for i, hh in heads:
            s = view.scores(i, hh, q_ref, k_ref)              # (g, BS)
            col = col0 + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            live = col < length
            if ranged:
                live &= col >= start
            s = jnp.where(live, s, _NEG)
            s_ref[hh, pl.ds(col0, block_s)] = s
            m_ref[hh, :] = jnp.maximum(m_ref[hh, :],
                                       jnp.max(s, axis=1, keepdims=True))
        if once:
            kept(col0)[...] = k_ref[tuple(
                slice(None) if a == view.seq_axis else slice(0, n)
                for a, n in enumerate(view.v_block))]

    def sum_weights():
        def add(i, l):
            s = s_ref[:, pl.ds(col_of(i), block_s)]
            return l + jnp.sum(jnp.exp(s - m_ref[...]), axis=1, keepdims=True)

        l_ref[...] = lax.fori_loop(first, live_blocks, add,
                                   jnp.zeros(l_ref.shape, jnp.float32))

    def weigh(col0, v_ref):
        """The normalised weights of the block from ``col0`` against its
        values, into the accumulator."""
        for i, hh in heads:
            p = (jnp.exp(s_ref[hh, pl.ds(col0, block_s)] - m_ref[hh, :])
                 / jnp.maximum(l_ref[hh, :], 1e-30))
            acc_ref[hh, :] += view.values(i, p, v_ref)

    def write_out():
        o_ref[(0,) * (len(o_ref.shape) - 2)] = acc_ref[...].astype(
            o_ref.dtype)

    if once:
        @pl.when(j == n_blk - 1)
        def _():
            sum_weights()

            def step(blk, carry):
                col0 = col_of(blk)
                weigh(col0, kept(col0))
                return carry

            lax.fori_loop(first, live_blocks, step, 0)
            write_out()
        return

    pl.when(j == n_blk)(sum_weights)

    @pl.when((j >= n_blk) & (block(j - n_blk) < live_blocks))
    def _():
        weigh(col_of(block(j - n_blk)), v_ref)

    pl.when(j == 2 * n_blk - 1)(write_out)


def stream_attend(view, lens, q, k, v, interpret=False, starts=None):
    """The body over ``view``: lens (B,) int32 live positions a slot, q
    (B,) + ``view.q_block[1:]`` pre-scaled, k and v the cache AS IT LIES,
    positions on axis 1 -> (B,) + ``view.o_block[1:]`` of q's type, zeros
    for a slot of length 0. Where the view's blocks hold the positions
    on another axis the call is handed that transposed view: a bitcast
    where the compiler laid the cache out so. Where ``v`` IS ``k`` (one
    array, V's block the leading part of K's) and ``kept_vmem_bytes``
    allows, the call streams that one operand once and keeps V's part
    in VMEM between the passes: half the grid, the output's bits the
    same. A length past the slot's positions reads as "every row", as
    the lax paths read it (unclipped it would index past the score
    scratch). ``starts`` (B,) int32: a slot's live rows are ``[starts,
    lens)`` and not ``[0, lens)`` (any view's: a second prefetched
    scalar a slot; without it the call is the call it was)."""
    rows = block_positions(view)
    if rows is None:
        raise ValueError(
            "%s: no in-place kernel for %d query rows on %d positions a "
            "slot of %s %s; the lax path attends it"
            % (view.name, view.score_rows, view.seq,
               jnp.dtype(view.dtype).name, view.k_block))
    b, s, h, axis = q.shape[0], view.seq, view.score_rows, view.seq_axis
    n_blk = s // rows
    lens = jnp.clip(lens, 0, s)
    ranged = starts is not None
    prefetched = (lens, jnp.clip(starts, 0, s)) if ranged else (lens,)
    shared = v is k
    held = kept_vmem_bytes(view, rows) if shared else None
    once = held is not None
    if axis != 1:
        k = jnp.swapaxes(k, 1, axis)
        v = k if shared else jnp.swapaxes(v, 1, axis)

    def at(block):
        """The index map of K's or V's blocks: the slot, ``block`` of
        (step, lengths, slot) at the sequence's index, 0 elsewhere."""
        def index(bi, j, lens_ref, *starts_ref):
            where = [0] * len(view.k_block)
            where[0] = bi
            where[axis] = (block(j, lens_ref, bi, starts=starts_ref[0])
                           if starts_ref else block(j, lens_ref, bi))
            return tuple(where)
        return index

    def qo_block(bi, j, *prefetched_refs):
        return (bi,) + (0,) * (len(view.q_block) - 1)

    def blocked(shape, positions=rows):
        return shape[:axis] + (positions,) + shape[axis + 1:]

    flags = {name: True for name, on in (("ranged", ranged), ("once", once))
             if on}
    kernel = functools.partial(_two_pass_kernel, view=view, block_s=rows,
                               n_blk=n_blk, **flags)
    streamed = [pl.BlockSpec(blocked(view.k_block), at(functools.partial(
        live_block, rows=rows)))]
    scratch = [pltpu.VMEM((h, s), jnp.float32),
               pltpu.VMEM((h, 1), jnp.float32),
               pltpu.VMEM((h, 1), jnp.float32),
               pltpu.VMEM((h, view.o_block[-1]), jnp.float32)]
    if once:
        scratch.append(pltpu.VMEM(blocked(view.v_block, s), jnp.float32))
        # beside what the body holds, the compiler's default (16 MiB on
        # a v5e) for q, o and its own temporaries
        limit = {"vmem_limit_bytes": held + 16 * 2**20}
    else:
        streamed.append(pl.BlockSpec(blocked(view.v_block), at(
            functools.partial(second_pass_block, rows=rows, n_blk=n_blk))))
        limit = {}
    return named_pallas_call(
        view.name, kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetched),
            grid=(b, n_blk if once else 2 * n_blk),
            in_specs=[pl.BlockSpec(view.q_block, qo_block)] + streamed,
            out_specs=pl.BlockSpec(view.o_block, qo_block),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((b,) + view.o_block[1:], q.dtype),
        interpret=interpret,
        **_tpu_params("parallel", "arbitrary", **limit),
    )(*prefetched, q, k, *(() if once else (v,)))
