"""One query row a slot attending a cache that lies where it lies,
streamed in live blocks, two passes: the ONE Pallas body behind
``ptpu.decode_attn_grouped`` (``kv_cache.py``: a slab of fewer heads
than the query), ``ptpu.diff_attn_rows`` (``diff_attn.py``: a slab of
flat rows) and ``ptpu.mla_latent_attn`` (``mla.py``: a latent slab's
transposed view), and the one rule for the positions a block of any of
them brings in.

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

What differs between the three is how a block yields a head's keys and
values, and that comes in as a ``StreamView``, written in the file that
owns the layout: the block shapes, which index of a block is the
sequence, and two functions of refs, traced inside the body.
"""
from __future__ import annotations

import dataclasses
import functools
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


def _two_pass_kernel(len_ref, *refs, view, block_s, n_blk, ranged=False):
    """One (slot, step) grid cell of the module's body. len_ref (B,)
    int32, the row a slot's live rows END before; under ``ranged`` a
    second prefetched (B,) int32 comes after it, the row they START at
    (else row 0): a pass's step ``j`` then works on block ``first + j``,
    ``first`` the block of that row, and the blocks before it are
    neither fetched nor computed, as the blocks past the end are. q_ref
    pre-scaled; ``s_ref`` (h, S), ``m_ref`` and ``l_ref`` (h, 1),
    ``acc_ref`` (h, Dv) live across the slot's steps (an "arbitrary"
    axis)."""
    start_ref = None
    if ranged:
        start_ref, refs = refs[0], refs[1:]
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

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(block(j) < live_blocks)
    def _():
        col0 = pl.multiple_of(block(j) * block_s, block_s)
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

    @pl.when(j == n_blk)
    def _():
        def add(i, l):
            s = s_ref[:, pl.ds(pl.multiple_of(i * block_s, block_s), block_s)]
            return l + jnp.sum(jnp.exp(s - m_ref[...]), axis=1, keepdims=True)

        l_ref[...] = lax.fori_loop(first, live_blocks, add,
                                   jnp.zeros(l_ref.shape, jnp.float32))

    @pl.when((j >= n_blk) & (block(j - n_blk) < live_blocks))
    def _():
        col0 = pl.multiple_of(block(j - n_blk) * block_s, block_s)
        for i, hh in heads:
            p = (jnp.exp(s_ref[hh, pl.ds(col0, block_s)] - m_ref[hh, :])
                 / jnp.maximum(l_ref[hh, :], 1e-30))
            acc_ref[hh, :] += view.values(i, p, v_ref)

    @pl.when(j == 2 * n_blk - 1)
    def _():
        o_ref[(0,) * (len(o_ref.shape) - 2)] = acc_ref[...].astype(
            o_ref.dtype)


def stream_attend(view, lens, q, k, v, interpret=False, starts=None):
    """The body over ``view``: lens (B,) int32 live positions a slot, q
    (B,) + ``view.q_block[1:]`` pre-scaled, k and v the cache AS IT LIES,
    positions on axis 1 -> (B,) + ``view.o_block[1:]`` of q's type, zeros
    for a slot of length 0. Where the view's blocks hold the positions
    on another axis the call is handed that transposed view (once where
    v is k's array): a bitcast where the compiler laid the cache out
    so. A length past the slot's positions reads as "every row", as the
    lax paths read it (unclipped it would index past the score
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
    if axis != 1:
        shared = v is k
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

    def blocked(shape):
        return shape[:axis] + (rows,) + shape[axis + 1:]

    kernel = functools.partial(_two_pass_kernel, view=view, block_s=rows,
                               n_blk=n_blk, **({"ranged": True} if ranged
                                               else {}))
    return named_pallas_call(
        view.name, kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetched),
            grid=(b, 2 * n_blk),
            in_specs=[
                pl.BlockSpec(view.q_block, qo_block),
                pl.BlockSpec(blocked(view.k_block), at(functools.partial(
                    live_block, rows=rows))),
                pl.BlockSpec(blocked(view.v_block), at(functools.partial(
                    second_pass_block, rows=rows, n_blk=n_blk))),
            ],
            out_specs=pl.BlockSpec(view.o_block, qo_block),
            scratch_shapes=[pltpu.VMEM((h, s), jnp.float32),
                            pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, view.o_block[-1]), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b,) + view.o_block[1:], q.dtype),
        interpret=interpret,
        **_tpu_params("parallel", "arbitrary"),
    )(*prefetched, q, k, v)
