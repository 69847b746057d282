"""Fused (flash) attention: O(T) memory, no (T, T) score materialization.

Replaces the reference's unfused matmul -> softmax -> dropout -> matmul
attention chain (used by benchmark/fluid machine_translation.py and the
fluid transformer nets). On TPU the unfused chain materializes a
(B, H, T, T) score tensor in HBM three+ times per layer (more in the
backward), which both saturates HBM bandwidth and blows past 16 GB at
training batch sizes; seq 1024 x batch 16 already OOMs a v5e.

Two implementations:

- `pallas_flash_attention` (the TPU training+inference fast path): hand-
  tiled Pallas kernels, forward AND backward (via jax.custom_vjp), one
  grid cell per (batch*head, q-or-kv-block), online softmax in VMEM. The
  `fused_attention` op dispatches here on real TPU whenever there is no
  dropout/KV-padding (the LM bench path). Cut the v5e LM bench step from
  204 ms to 125 ms vs the XLA path below.

- `flash_attention` (XLA fallback: CPU tests, dropout, KV padding masks):
  lax.scan over KV blocks with an online softmax. Each scan body is
  `jax.checkpoint`ed, so autodiff recomputes the block's scores instead of
  saving them; exact, but its backward streams per-block probability
  tensors through HBM.
"""
from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..framework.scope import current_device
from ..framework.trace import current_trace_mesh, current_trace_plan
from ..observability import PREFILL_ATTN_FORMS, PREFILL_ATTN_TRACES
from .registry import register_op

_NEG = -1e30

# stable names of the Pallas kernels (`named_pallas_call`): the fused
# backward is one kernel, the split one two
FLASH_FWD = "ptpu.flash_fwd"
FLASH_BWD = "ptpu.flash_bwd"
FLASH_BWD_DQ = "ptpu.flash_bwd_dq"
FLASH_BWD_DKV = "ptpu.flash_bwd_dkv"


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def flash_attention(q, k, v, causal=False, scale=None, lengths=None,
                    dropout_rate=0.0, rng_key=None, block_k=512):
    """q,k,v: (B, H, T, D) -> (B, H, T, D); exact attention, chunked over
    the KV axis. `lengths` (B,) masks padded KV positions."""
    b, h, t, d = q.shape
    tk = k.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    orig_dtype = q.dtype
    q = q * jnp.asarray(scale, q.dtype)

    block_k = min(block_k, _ceil_to(tk, 128))
    pk = _ceil_to(tk, block_k)
    if pk != tk:
        pad = [(0, 0), (0, 0), (0, pk - tk), (0, 0)]
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    nblk = pk // block_k

    k_blocks = k.reshape(b, h, nblk, block_k, d).transpose(2, 0, 1, 3, 4)
    v_blocks = v.reshape(b, h, nblk, block_k, d).transpose(2, 0, 1, 3, 4)

    q_idx = jnp.arange(t)
    kv_valid_len = jnp.full((b,), tk) if lengths is None else lengths.reshape(-1)

    def body(carry, inp):
        acc, m, l = carry
        kb, vb, j = inp  # (B,H,BK,D), (B,H,BK,D), scalar block idx
        # scores for this KV block: (B, H, T, BK)
        s = jnp.einsum("bhtd,bhsd->bhts", q, kb,
                       preferred_element_type=jnp.float32)
        col = j * block_k + jnp.arange(block_k)
        mask = (col[None, :] <= q_idx[:, None]) if causal else jnp.ones(
            (t, block_k), bool)
        mask = mask[None, None] & (col[None, None, None, :]
                                   < kv_valid_len[:, None, None, None])
        s = jnp.where(mask, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # re-mask after the max-subtraction: for a row whose every position
        # so far is masked, s == m_new == _NEG and exp(0) would be 1 —
        # the output must stay 0 (not the mean of V) for fully-padded rows
        p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
        if dropout_rate:
            bits = jax.random.bernoulli(
                jax.random.fold_in(rng_key, j), 1.0 - dropout_rate, p.shape)
            p_drop = p * bits / (1.0 - dropout_rate)
        else:
            p_drop = p
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhts,bhsd->bhtd", p_drop.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return (acc, m_new, l), None

    init = (jnp.zeros((b, h, t, d), jnp.float32),
            jnp.full((b, h, t), _NEG, jnp.float32),
            jnp.zeros((b, h, t), jnp.float32))
    # checkpoint: the backward re-computes each block's scores instead of
    # saving (B,H,T,BK) probabilities per block (which would sum to the
    # full T x T tensor flash attention exists to avoid)
    ckpt_body = jax.checkpoint(body)
    if nblk <= 8:
        # unrolled: lets XLA schedule blocks alongside neighboring layers
        # (a scan is a fusion barrier); same memory story via checkpoint
        carry = init
        for j in range(nblk):
            carry, _ = ckpt_body(
                carry, (k_blocks[j], v_blocks[j], jnp.asarray(j)))
        acc, m, l = carry
    else:
        (acc, m, l), _ = lax.scan(
            ckpt_body, init, (k_blocks, v_blocks, jnp.arange(nblk)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(orig_dtype)


# ---------------------------------------------------------------------------
# pallas flash attention: forward + backward TPU kernels (training fast path)
#
# The XLA scan path above is exact but its backward streams per-block
# (B, H, T, BK) fp32 probability tensors through HBM (the vjp of the two
# einsums materializes them) — profiled at ~100 ms/step on the v5e LM
# bench, dwarfing the matmul stack. These kernels keep every score tile in
# VMEM: the forward saves only (out, logsumexp); the backward recomputes
# score tiles blockwise, flash-attention style.
# ---------------------------------------------------------------------------


def _tpu_params(*dimension_semantics, vmem_limit_bytes=None):
    """compiler_params kwargs marking grid axes "parallel" (Mosaic may
    split them across megacore on v4/v5p) or "arbitrary" (sequential —
    REQUIRED for axes whose output blocks are revisited/accumulated:
    the lse row in the fwd kernel, dk/dv in the fused backward).
    ``vmem_limit_bytes`` raises the kernel's scoped VMEM above the
    compiler's default where a caller knows its blocks need it."""
    more = ({} if vmem_limit_bytes is None
            else {"vmem_limit_bytes": int(vmem_limit_bytes)})
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=tuple(dimension_semantics), **more)}


# what the compiler's default scoped VMEM (16 MiB on a v5e) leaves a
# kernel's resident K and V, double-buffered, beside its other blocks
_KV_VMEM_DEFAULT = 12 * 2**20


def _kv_vmem_limit(tk: int, d: int, itemsize: int):
    """Scoped VMEM for a forward kernel that keeps one head's whole K
    and V resident (``_mha_fwd_call*``: a (1, tk, d) block each, double
    buffered): None while the default holds them (every sequence up to
    4,096 float32 rows of 128: the compiled text is what it was), else
    their bytes and 16 MiB for the other blocks and the score tiles (a
    prompt bucket of 8,192 or 16,384 rows; a v5e core has 128 MiB)."""
    resident = 2 * 2 * tk * d * itemsize
    if resident <= _KV_VMEM_DEFAULT:
        return None
    return resident + 16 * 2**20


def named_pallas_call(name, kernel, **kw):
    """``pl.pallas_call`` under a stable name: a ``jax.named_scope``
    around the call. The TPU compiler names a Mosaic custom-call after
    the innermost component of its ``op_name``, so the forward kernel
    is ``jvp_ptpu.flash_fwd_.N`` in a training step's device trace, the
    backward ``transpose_jvp_ptpu.flash_bwd__.N``, and ``ptpu.<kernel>.N``
    outside autodiff or under ``shard_map``. ``pallas_call``'s own
    ``name=`` is left alone: it pushes a scope of its own, which hides
    the ``jvp`` that the benchmark's accepted readers anchor on.
    Metadata only: the compiled program is the same."""
    call = pl.pallas_call(kernel, **kw)

    def run(*operands):
        with jax.named_scope(name):
            return call(*operands)
    return run

def _causal_mask(s, row0, col0):
    """Mask score tile `s` (BQ, BK) whose top-left element is global
    position (row0, col0): future positions (col > row) get _NEG. Shared
    by the fwd/dq/dkv kernels so the three stay in sync."""
    bq, bk = s.shape
    row = row0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    col = col0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(col <= row, s, _NEG)


def _window_mask(s, row0, col0, window):
    """Keys at or before ``row - window`` get _NEG (with the causal
    mask: key j is visible to query t iff t - window < j <= t)."""
    bq, bk = s.shape
    row = row0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    col = col0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(col > row - window, s, _NEG)


def _mha_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q,
                    block_k, seq_k, causal, pid_axis=1, window=0,
                    mask_ref=None, len_ref=None, sink_ref=None, band=False):
    """``window`` > 0 (causal only): a query at ``t`` sees the keys
    ``(t - window, t]``; KV blocks wholly before a q-block's window are
    SKIPPED (the loop starts at the first block that holds a visible
    key), as blocks above the diagonal are. ``sink_ref`` (H,) float32,
    scalar-prefetched beside the lengths (the BTHD grid, whose second
    index is the head): a learned scalar a head that joins the softmax's
    denominator and takes no value, so the last write is the output
    times ``sigmoid(lse - sink)`` (``sink_share``). ``band`` (a window,
    lengths prefetched): ONE key block a q-block, the ``block_k`` =
    ``block_q`` + window keys it sees, in one pass with no running
    statistics (``_mha_fwd_band_block``; ``mask_ref`` is then its
    additive bias). ``mask_ref`` (1, seq_k /
    block_k, block_q, block_k) int8: a mask that differs by (query,
    key), this q-block's rows against every key block; a key whose
    entry is 0 gets _NEG (``_mha_fwd_masked_kernel``). ``len_ref`` (B,)
    int32, scalar-prefetched (``_mha_fwd_lens_kernel``; the BTHD grid,
    whose first index is the batch row): a q-block wholly past its
    row's length computes nothing and writes zeros: rows of padding,
    which no one reads (the callers are causal and pad a row at its
    END, so no live row sees a padding row's key, and what a prefill
    keeps of a row ends at its length). V's width may be another than
    q's and K's; the output has V's."""
    qi = pl.program_id(pid_axis)
    # the head's sink, read out here: a grid index is read at the
    # kernel's top level, not under a `pl.when`
    sink = None if sink_ref is None else sink_ref[pl.program_id(1)]
    if len_ref is not None:
        live = qi * block_q < len_ref[pl.program_id(0)]

        @pl.when(jnp.logical_not(live))
        def _():
            o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)
            lse_ref[0, 0, pl.ds(qi * block_q, block_q)] = jnp.zeros(
                (block_q,), jnp.float32)

        if band:
            pl.when(live)(functools.partial(
                _mha_fwd_band_block, q_ref, k_ref, v_ref, o_ref, lse_ref,
                mask_ref, qi, block_q, block_k, sink))
            return
        pl.when(live)(functools.partial(
            _mha_fwd_block, q_ref, k_ref, v_ref, o_ref, lse_ref, mask_ref,
            qi, block_q, block_k, seq_k, causal, window, sink))
        return
    _mha_fwd_block(q_ref, k_ref, v_ref, o_ref, lse_ref, mask_ref, qi,
                   block_q, block_k, seq_k, causal, window, sink)


def _mha_fwd_block(q_ref, k_ref, v_ref, o_ref, lse_ref, mask_ref, qi,
                   block_q, block_k, seq_k, causal, window, sink=None):
    """``_mha_fwd_kernel``'s work on q-block ``qi``; ``sink``: the
    head's scalar."""
    # keep matmul operands in the input dtype (bf16 under mixed precision:
    # the MXU runs bf16 x bf16 -> f32 at full rate; converting to f32 first
    # would halve MXU throughput AND double VMEM traffic); only the softmax
    # statistics run in f32.
    q = q_ref[0]  # (BQ, D), pre-scaled
    nkv = seq_k // block_k

    def blk(j, carry):
        acc, m, l = carry
        kb = k_ref[0, pl.ds(j * block_k, block_k), :]
        vb = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jnp.dot(q, kb.T, preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(s, qi * block_q, j * block_k)
        if window:
            s = _window_mask(s, qi * block_q, j * block_k, window)
        if mask_ref is not None:
            s = jnp.where(mask_ref[0, j].astype(jnp.int32) != 0, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=1)
        acc = acc * corr[:, None] + jnp.dot(
            p.astype(vb.dtype), vb, preferred_element_type=jnp.float32)
        return acc, m_new, l

    init = (jnp.zeros((block_q, v_ref.shape[-1]), jnp.float32),
            jnp.full((block_q,), _NEG, jnp.float32),
            jnp.zeros((block_q,), jnp.float32))
    # with causal masking, KV blocks strictly above the diagonal contribute
    # nothing — stop the loop at this q-block's diagonal
    if causal:
        upper = lax.min(((qi + 1) * block_q + block_k - 1) // block_k, nkv)
    else:
        upper = nkv
    # the first key the q-block's first row sees is row0 - window + 1
    lower = (lax.max(qi * block_q - window + 1, 0) // block_k
             if window else 0)
    acc, m, l = lax.fori_loop(lower, upper, blk, init)
    _write_rows(o_ref, lse_ref, qi, block_q, acc, m, jnp.maximum(l, 1e-30),
                sink)


def _write_rows(o_ref, lse_ref, qi, block_q, acc, m, l, sink):
    """A q-block's last write: the weighted sums over the weights' sum,
    times the sink's share where the head has one, and the rows'
    log-sum-exp."""
    out = acc / l[:, None]
    if sink is not None:
        out = out * jax.nn.sigmoid(m + jnp.log(l) - sink)[:, None]
    o_ref[0] = out.astype(o_ref.dtype)
    # lse is blocked as a full (1, T) row (TPU block-shape tiling rejects
    # (1, BQ) blocks); consecutive grid steps over j revisit the same row
    # block, so each writes its own BQ slice
    lse_ref[0, 0, pl.ds(qi * block_q, block_q)] = m + jnp.log(l)


def _mha_fwd_band_block(q_ref, k_ref, v_ref, o_ref, lse_ref, bias_ref, qi,
                        block_q, block_k, sink):
    """``_mha_fwd_kernel``'s work on q-block ``qi`` under a window no
    wider than ``back`` = ``block_k - block_q``: the block's rows see
    only the ``block_k`` keys that end with its last row, so they are
    ONE key block, read where it lies (its first row a multiple of 128;
    the sequence's start clips it for the first ``back / block_q``
    q-blocks), and the softmax is one pass: no running maximum, no
    rescaling, one reduction a row where the walk over key blocks of
    the window's size makes three. Which keys a row sees is a constant
    of the block's place in its key block, so the causal and window
    masks are one addition of ``bias_ref`` (G, block_q, block_k): 0
    where visible, _NEG elsewhere, entry ``min(qi, G - 1)`` (the
    clipped blocks first, ``band_bias``)."""
    q = q_ref[0]  # (BQ, D), pre-scaled
    start = pl.multiple_of(
        lax.max(qi * block_q - (block_k - block_q), 0), 128)
    kb = k_ref[0, pl.ds(start, block_k), :]
    vb = v_ref[0, pl.ds(start, block_k), :]
    s = lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    s = s + bias_ref[lax.min(qi, bias_ref.shape[0] - 1)]
    m = jnp.max(s, axis=1)
    p = jnp.exp(s - m[:, None])
    l = jnp.sum(p, axis=1)
    acc = jnp.dot(p.astype(vb.dtype), vb, preferred_element_type=jnp.float32)
    _write_rows(o_ref, lse_ref, qi, block_q, acc, m, l, sink)


def band_bias(block_q, block_k, window):
    """(G, block_q, block_k) float32 for ``_mha_fwd_band_block``: entry
    ``g`` is of a q-block whose first row lies ``min(g x block_q,
    back)`` rows into its key block (``back`` = ``block_k - block_q``;
    the last entry is every unclipped block's); row ``i`` sees key
    ``j`` iff ``0 <= that + i - j < window``."""
    back = block_k - block_q
    into = jnp.minimum(jnp.arange(-(-back // block_q) + 1) * block_q, back)
    ahead = (into[:, None, None] + jnp.arange(block_q)[None, :, None]
             - jnp.arange(block_k)[None, None, :])
    return jnp.where((ahead >= 0) & (ahead < window), 0.0, _NEG).astype(
        jnp.float32)


def _mha_fwd_masked_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                           **kw):
    """``_mha_fwd_kernel`` with the mask among its operands."""
    _mha_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, mask_ref=mask_ref,
                    **kw)


def _mha_fwd_lens_kernel(len_ref, *refs, sinks=False, **kw):
    """``_mha_fwd_kernel`` with the rows' lengths scalar-prefetched (and,
    with ``sinks``, the heads' sinks beside them), and the mask or the
    band's bias among its operands where there is one."""
    sink_ref, refs = (refs[0], refs[1:]) if sinks else (None, refs)
    q_ref, k_ref, v_ref, *mask, o_ref, lse_ref = refs
    _mha_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                    mask_ref=mask[0] if mask else None,
                    len_ref=len_ref, sink_ref=sink_ref, **kw)


def _mha_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, block_q, block_k, seq_k, causal, pid_axis=1):
    qi = pl.program_id(pid_axis)
    q = q_ref[0]       # (BQ, D), pre-scaled, input dtype (see fwd note)
    do = do_ref[0]     # (BQ, D)
    lse = lse_ref[0, 0, pl.ds(qi * block_q, block_q)]     # (BQ,)
    delta = delta_ref[0, 0, pl.ds(qi * block_q, block_q)]  # (BQ,)
    nkv = seq_k // block_k

    def blk(j, dq):
        kb = k_ref[0, pl.ds(j * block_k, block_k), :]
        vb = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jnp.dot(q, kb.T, preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(s, qi * block_q, j * block_k)
        p = jnp.exp(s - lse[:, None])
        dp = jnp.dot(do, vb.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        return dq + jnp.dot(ds.astype(kb.dtype), kb,
                            preferred_element_type=jnp.float32)

    d = q.shape[-1]
    if causal:
        upper = lax.min(((qi + 1) * block_q + block_k - 1) // block_k, nkv)
    else:
        upper = nkv
    dq = lax.fori_loop(0, upper, blk, jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _mha_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, block_q, block_k, seq_q, causal,
                    pid_axis=1):
    kj = pl.program_id(pid_axis)
    kb = k_ref[0]      # (BK, D), input dtype (see fwd note)
    vb = v_ref[0]
    nq = seq_q // block_q

    def blk(i, carry):
        dk, dv = carry
        qb = q_ref[0, pl.ds(i * block_q, block_q), :]
        dob = do_ref[0, pl.ds(i * block_q, block_q), :]
        lseb = lse_ref[0, 0, pl.ds(i * block_q, block_q)]
        deltab = delta_ref[0, 0, pl.ds(i * block_q, block_q)]
        s = jnp.dot(qb, kb.T, preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(s, i * block_q, kj * block_k)
        p = jnp.exp(s - lseb[:, None])
        dv = dv + jnp.dot(p.T.astype(dob.dtype), dob,
                          preferred_element_type=jnp.float32)
        dp = jnp.dot(dob, vb.T, preferred_element_type=jnp.float32)
        ds = p * (dp - deltab[:, None])
        dk = dk + jnp.dot(ds.T.astype(qb.dtype), qb,
                          preferred_element_type=jnp.float32)
        return dk, dv

    d = kb.shape[-1]
    lower = (kj * block_k) // block_q if causal else 0
    dk, dv = lax.fori_loop(
        lower, nq, blk,
        (jnp.zeros((block_k, d), jnp.float32),
         jnp.zeros((block_k, d), jnp.float32)))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _mha_bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dq_ref, dk_ref, dv_ref, *, block_q, block_k,
                          seq_k, causal, pid_axis=1):
    """Single-pass flash backward: one sweep over (q-block, kv-block)
    pairs computes dq (written per q-block) AND accumulates dk/dv in
    VMEM — the dk/dv output blocks map to the same (batch, head) slice
    for every q-block grid step, so Pallas keeps them resident and only
    flushes when the grid moves to the next head. Versus the split
    dq+dkv kernels this recomputes the probability tile ONCE instead of
    twice (5 matmuls per tile instead of 7) and reads q/k/v/do once
    instead of twice. dk/dv accumulate (and are emitted) in f32; the
    caller casts to the primal dtype."""
    qi = pl.program_id(pid_axis)

    @pl.when(qi == 0)
    def _init():
        dk_ref[0] = jnp.zeros_like(dk_ref[0])
        dv_ref[0] = jnp.zeros_like(dv_ref[0])

    q = q_ref[0]       # (BQ, D), pre-scaled, input dtype (see fwd note)
    do = do_ref[0]     # (BQ, D)
    lse = lse_ref[0, 0, pl.ds(qi * block_q, block_q)]      # (BQ,)
    delta = delta_ref[0, 0, pl.ds(qi * block_q, block_q)]  # (BQ,)
    nkv = seq_k // block_k

    def blk(j, dq):
        kb = k_ref[0, pl.ds(j * block_k, block_k), :]
        vb = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jnp.dot(q, kb.T, preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(s, qi * block_q, j * block_k)
        p = jnp.exp(s - lse[:, None])
        dv_ref[0, pl.ds(j * block_k, block_k), :] += jnp.dot(
            p.T.astype(do.dtype), do, preferred_element_type=jnp.float32
        ).astype(dv_ref.dtype)
        dp = jnp.dot(do, vb.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        dk_ref[0, pl.ds(j * block_k, block_k), :] += jnp.dot(
            ds.T.astype(q.dtype), q, preferred_element_type=jnp.float32
        ).astype(dk_ref.dtype)
        return dq + jnp.dot(ds.astype(kb.dtype), kb,
                            preferred_element_type=jnp.float32)

    d = q.shape[-1]
    if causal:
        upper = lax.min(((qi + 1) * block_q + block_k - 1) // block_k, nkv)
    else:
        upper = nkv
    dq = lax.fori_loop(0, upper, blk, jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


# Scoped-VMEM budget for the fused kernel's per-(batch, head) residents:
# k+v full rows (input dtype, double-buffered by Mosaic) plus the f32
# dk/dv accumulators. 12 MB of the 16 MB scoped limit — the rest is
# q/do/dq blocks, lse/delta rows, and Mosaic's own stack.
_FUSED_BWD_VMEM_BUDGET = 12 * 1024 * 1024


def _fused_bwd_fits(tk: int, d: int, kv_itemsize: int) -> bool:
    """THE selector of the flash backward, from the shape alone: True
    where the single-pass kernel's whole-row VMEM residents fit and it
    runs, False where the split dq + dkv pair does. It rests on two
    points measured on a v5e: the fused kernel compiles at T=4096,
    d=128, bfloat16 (8 MB) and fails at T=8192 (16 MB+: 'Scoped
    allocation with size 24.75M and limit 16.00M'). No option turns
    either way; a test reaches the split pair by a shape over the budget
    or by patching ``_FUSED_BWD_VMEM_BUDGET``."""
    kv_rows = 2 * tk * d * kv_itemsize * 2  # k+v, double-buffered
    acc_rows = 2 * tk * d * 4               # dk+dv f32 accumulators
    # strict <: a footprint exactly AT the budget (f32 rows, T=4096) has
    # never been measured on hardware — stay on the safe side of it
    return kv_rows + acc_rows < _FUSED_BWD_VMEM_BUDGET


def _mha_fwd_call(qs, k, v, causal, block_q, block_k, interpret):
    bh, t, d = qs.shape
    tk = k.shape[1]
    kernel = functools.partial(
        _mha_fwd_kernel, block_q=block_q, block_k=block_k, seq_k=tk,
        causal=causal)
    return named_pallas_call(
        FLASH_FWD, kernel,
        grid=(bh, t // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, tk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, tk, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, t), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), qs.dtype),
            jax.ShapeDtypeStruct((bh, 1, t), jnp.float32),
        ],
        interpret=interpret,
        **_tpu_params("parallel", "arbitrary"),
    )(qs, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _pallas_mha(qs, k, v, causal, block_q, block_k, interpret):
    """(BH, T, D) pre-scaled q; exact attention with Pallas fwd+bwd."""
    out, _ = _mha_fwd_call(qs, k, v, causal, block_q, block_k, interpret)
    return out


def _pallas_mha_fwd(qs, k, v, causal, block_q, block_k, interpret):
    out, lse = _mha_fwd_call(qs, k, v, causal, block_q, block_k, interpret)
    return out, (qs, k, v, out, lse)


def _pallas_mha_bwd(causal, block_q, block_k, interpret, res, do):
    qs, k, v, out, lse = res
    bh, t, d = qs.shape
    tk = k.shape[1]
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, None, :]  # (BH, 1, T) — see lse layout note

    if _fused_bwd_fits(tk, d, k.dtype.itemsize):
        kernel = functools.partial(
            _mha_bwd_fused_kernel, block_q=block_q, block_k=block_k,
            seq_k=tk, causal=causal)
        dq, dk, dv = named_pallas_call(
            FLASH_BWD, kernel,
            grid=(bh, t // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((1, tk, d), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((1, tk, d), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((1, 1, t), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((1, 1, t), lambda i, j: (i, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((1, tk, d), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((1, tk, d), lambda i, j: (i, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, t, d), qs.dtype),
                jax.ShapeDtypeStruct((bh, tk, d), jnp.float32),
                jax.ShapeDtypeStruct((bh, tk, d), jnp.float32),
            ],
            interpret=interpret,
            **_tpu_params("parallel", "arbitrary"),
        )(qs, k, v, do, lse, delta)
        return dq, dk.astype(k.dtype), dv.astype(v.dtype)

    dq_kernel = functools.partial(
        _mha_dq_kernel, block_q=block_q, block_k=block_k, seq_k=tk,
        causal=causal)
    dq = named_pallas_call(
        FLASH_BWD_DQ, dq_kernel,
        grid=(bh, t // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, tk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, tk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, t), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, t), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), qs.dtype),
        interpret=interpret,
        **_tpu_params("parallel", "parallel"),
    )(qs, k, v, do, lse, delta)

    dkv_kernel = functools.partial(
        _mha_dkv_kernel, block_q=block_q, block_k=block_k, seq_q=t,
        causal=causal)
    dk, dv = named_pallas_call(
        FLASH_BWD_DKV, dkv_kernel,
        grid=(bh, tk // block_k),
        in_specs=[
            pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, t), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, t), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, tk, d), v.dtype),
        ],
        interpret=interpret,
        **_tpu_params("parallel", "parallel"),
    )(qs, k, v, do, lse, delta)
    return dq, dk, dv


_pallas_mha.defvjp(_pallas_mha_fwd, _pallas_mha_bwd)


# ---------------------------------------------------------------------------
# BTHD (transpose-free) layout: q/k/v stay exactly as the head-split
# projection produces them — (B, T, H*Dh) with each head's Dh slice
# contiguous — and the grid gains an explicit head axis whose index map
# selects the head's column block. No (B,S,H,D)->(B,H,S,D) transposes
# exist anywhere in fwd or bwd (on the profile those copies were ~14% of
# step time). Requires Dh % 128 == 0 (a partial minor-dim block must be a
# whole number of lane tiles); the dispatch falls back to the BHTD path
# otherwise. Kernel bodies are SHARED with the BHTD path — only grid and
# BlockSpecs differ.
# ---------------------------------------------------------------------------


def _lse_spec_bthd(h, t):
    """BlockSpec for the per-(batch, head) softmax-stat rows (lse, delta)
    in the BTHD kernels. The stats are laid out (B*H, 1, T) — NOT
    (B, H, T): Mosaic requires the last TWO block dims to be 8/128
    multiples or the full dim, and a (1, 1, T) block on a (B, H, T)
    array has a second-minor extent of 1 under a dim of H (rejected on
    real hardware; reproduced offline via jax.export platforms=['tpu']).
    Flattening (B, H) into the major dim makes the singleton blocks
    cover full dims, which is exactly how the proven BHTD path lays out
    its stats."""
    return pl.BlockSpec((1, 1, t),
                        lambda bi, hi, qi, *_: (bi * h + hi, 0, 0))


def _mha_fwd_call_bthd(qs, k, v, h, causal, block_q, block_k, interpret,
                       window=0, name=None, mask=None, lengths=None,
                       out_dtype=None, group=1, sink=None, band=False):
    """``mask`` (B, T, tk) int8, the same for every head: 1 where the
    query attends the key (with ``causal``, which still bounds the key
    blocks a q-block walks: a mask here only ever takes keys away). The
    kernel is handed it as (B, tk / block_k, T, block_k), a key block an
    index of a major axis, so that it reads a (block_q, block_k) tile
    by a plain index. ``lengths`` (B,) int32, scalar-prefetched: the
    q-blocks wholly past a row's length compute nothing, give zeros and
    fetch no tile of the mask (a prefill's bucket is a power of two and
    the prompt in it on average two thirds of that: half the causal
    pairs). V (B, tk, h * dv) may be of another width than q and K; the
    output is (B, T, h * dv), in ``out_dtype`` (q's where not given).
    ``group`` > 1: K and V hold ``h / group`` heads and query head
    ``hi`` reads head ``hi // group``; the grid walks a head's q-blocks
    inside the head, so a group's heads run against ONE resident copy (a
    block whose index did not change is not copied again). ``sink``
    (h,) float32, scalar-prefetched beside the lengths: the last write
    times ``sigmoid(lse - sink)``. ``band`` (causal, a ``window`` no
    wider than ``block_k - block_q``): a q-block's keys are one block
    of ``block_k``, attended in one pass (``_mha_fwd_band_block``).
    A sink or a band is not for a ``mask``, and takes every row as whole
    where no lengths were given."""
    b, t, hd = qs.shape
    tk = k.shape[1]
    hkv = h // group
    d, dv = hd // h, v.shape[2] // hkv
    if (sink is not None or band) and lengths is None:
        lengths = jnp.full((b,), t, jnp.int32)
    if lengths is not None:
        kernel = functools.partial(_mha_fwd_lens_kernel,
                                   sinks=sink is not None, band=band)
    else:
        kernel = _mha_fwd_kernel if mask is None else _mha_fwd_masked_kernel
    kernel = functools.partial(
        kernel, block_q=block_q, block_k=block_k, seq_k=tk,
        causal=causal, pid_axis=2, window=window)
    operands, mask_specs, mask_bytes = (qs, k, v), [], 0

    def live_q(bi, qi, lens):
        """q-block ``qi``, or the row's last live one past it: a block
        whose index did not change is not copied again."""
        if not lens:
            return qi
        return jnp.minimum(qi, jnp.maximum(lens[0][bi] - 1, 0) // block_q)

    def kv_head(bi, hi, qi, *lens):
        return bi, 0, hi if group == 1 else hi // group

    if mask is not None:
        assert sink is None and not band, "no sink or band under a mask"
        nk = tk // block_k
        operands += (jnp.swapaxes(
            mask.astype(jnp.int8).reshape(b, t, nk, block_k), 1, 2),)
        mask_specs = [pl.BlockSpec(
            (1, nk, block_q, block_k),
            lambda bi, hi, qi, *lens: (bi, 0, live_q(bi, qi, lens), 0))]
        mask_bytes = 2 * block_q * tk
    limit = _kv_vmem_limit(tk, d, jnp.dtype(k.dtype).itemsize)
    if mask_bytes and limit is not None:
        limit += mask_bytes
    if band:
        assert causal and 0 < window <= block_k - block_q <= tk - block_q
        bias = band_bias(block_q, block_k, window)
        operands += (bias,)
        # one block for the whole grid: fetched once, and buffered twice
        mask_specs = [pl.BlockSpec(
            bias.shape, lambda bi, hi, qi, *lens: (0, 0, 0))]
        limit = (limit or 16 * 2**20) + 2 * bias.size * 4
    specs = dict(
        grid=(b, h, t // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bi, hi, qi, *lens: (
                bi, live_q(bi, qi, lens), hi)),
            pl.BlockSpec((1, tk, d), kv_head),
            pl.BlockSpec((1, tk, dv), kv_head),
        ] + mask_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, dv),
                         lambda bi, hi, qi, *lens: (bi, qi, hi)),
            _lse_spec_bthd(h, t),
        ])
    if lengths is not None:
        prefetch = (lengths.reshape(-1).astype(jnp.int32),)
        if sink is not None:
            prefetch += (sink.reshape(-1).astype(jnp.float32),)
        operands = prefetch + operands
        specs = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), **specs))
    return named_pallas_call(
        name or FLASH_FWD, kernel,
        out_shape=[
            jax.ShapeDtypeStruct((b, t, h * dv), out_dtype or qs.dtype),
            jax.ShapeDtypeStruct((b * h, 1, t), jnp.float32),
        ],
        interpret=interpret,
        **_tpu_params("parallel", "parallel", "arbitrary",
                      vmem_limit_bytes=limit),
        **specs,
    )(*operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _pallas_mha_bthd(qs, k, v, h, causal, block_q, block_k, interpret):
    """(B, T, H*Dh) pre-scaled q; exact attention, BTHD layout."""
    out, _ = _mha_fwd_call_bthd(qs, k, v, h, causal, block_q, block_k,
                                interpret)
    return out


def _pallas_mha_bthd_fwd(qs, k, v, h, causal, block_q, block_k, interpret):
    out, lse = _mha_fwd_call_bthd(qs, k, v, h, causal, block_q, block_k,
                                  interpret)
    return out, (qs, k, v, out, lse)


def _pallas_mha_bthd_bwd(h, causal, block_q, block_k, interpret, res, do):
    qs, k, v, out, lse = res
    b, t, hd = qs.shape
    tk = k.shape[1]
    d = hd // h
    # per-head delta, laid out (B*H, 1, T) like lse (see _lse_spec_bthd):
    # the only head-axis shuffle in the whole path, on a (B, T, H) f32
    # tensor (~1000x smaller than q/k/v)
    delta = jnp.sum(
        do.astype(jnp.float32).reshape(b, t, h, d)
        * out.astype(jnp.float32).reshape(b, t, h, d),
        axis=-1).transpose(0, 2, 1).reshape(b * h, 1, t)

    if _fused_bwd_fits(tk, d, k.dtype.itemsize):
        kernel = functools.partial(
            _mha_bwd_fused_kernel, block_q=block_q, block_k=block_k,
            seq_k=tk, causal=causal, pid_axis=2)
        dq, dk, dv = named_pallas_call(
            FLASH_BWD, kernel,
            grid=(b, h, t // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda bi, hi, qi: (bi, qi, hi)),
                pl.BlockSpec((1, tk, d), lambda bi, hi, qi: (bi, 0, hi)),
                pl.BlockSpec((1, tk, d), lambda bi, hi, qi: (bi, 0, hi)),
                pl.BlockSpec((1, block_q, d), lambda bi, hi, qi: (bi, qi, hi)),
                _lse_spec_bthd(h, t),
                _lse_spec_bthd(h, t),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, d),
                             lambda bi, hi, qi: (bi, qi, hi)),
                pl.BlockSpec((1, tk, d), lambda bi, hi, qi: (bi, 0, hi)),
                pl.BlockSpec((1, tk, d), lambda bi, hi, qi: (bi, 0, hi)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, t, hd), qs.dtype),
                jax.ShapeDtypeStruct((b, tk, hd), jnp.float32),
                jax.ShapeDtypeStruct((b, tk, hd), jnp.float32),
            ],
            interpret=interpret,
            **_tpu_params("parallel", "parallel", "arbitrary"),
        )(qs, k, v, do, lse, delta)
        return dq, dk.astype(k.dtype), dv.astype(v.dtype)

    dq_kernel = functools.partial(
        _mha_dq_kernel, block_q=block_q, block_k=block_k, seq_k=tk,
        causal=causal, pid_axis=2)
    dq = named_pallas_call(
        FLASH_BWD_DQ, dq_kernel,
        grid=(b, h, t // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bi, hi, qi: (bi, qi, hi)),
            pl.BlockSpec((1, tk, d), lambda bi, hi, qi: (bi, 0, hi)),
            pl.BlockSpec((1, tk, d), lambda bi, hi, qi: (bi, 0, hi)),
            pl.BlockSpec((1, block_q, d), lambda bi, hi, qi: (bi, qi, hi)),
            _lse_spec_bthd(h, t),
            _lse_spec_bthd(h, t),
        ],
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda bi, hi, qi: (bi, qi, hi)),
        out_shape=jax.ShapeDtypeStruct((b, t, hd), qs.dtype),
        interpret=interpret,
        **_tpu_params("parallel", "parallel", "parallel"),
    )(qs, k, v, do, lse, delta)

    dkv_kernel = functools.partial(
        _mha_dkv_kernel, block_q=block_q, block_k=block_k, seq_q=t,
        causal=causal, pid_axis=2)
    dk, dv = named_pallas_call(
        FLASH_BWD_DKV, dkv_kernel,
        grid=(b, h, tk // block_k),
        in_specs=[
            pl.BlockSpec((1, t, d), lambda bi, hi, kj: (bi, 0, hi)),
            pl.BlockSpec((1, block_k, d), lambda bi, hi, kj: (bi, kj, hi)),
            pl.BlockSpec((1, block_k, d), lambda bi, hi, kj: (bi, kj, hi)),
            pl.BlockSpec((1, t, d), lambda bi, hi, kj: (bi, 0, hi)),
            _lse_spec_bthd(h, t),
            _lse_spec_bthd(h, t),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bi, hi, kj: (bi, kj, hi)),
            pl.BlockSpec((1, block_k, d), lambda bi, hi, kj: (bi, kj, hi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, tk, hd), k.dtype),
            jax.ShapeDtypeStruct((b, tk, hd), v.dtype),
        ],
        interpret=interpret,
        **_tpu_params("parallel", "parallel", "parallel"),
    )(qs, k, v, do, lse, delta)
    return dq, dk, dv


_pallas_mha_bthd.defvjp(_pallas_mha_bthd_fwd, _pallas_mha_bthd_bwd)


def pallas_flash_attention_bthd(q, k, v, causal=False, scale=None,
                                block_q=512, block_k=512, interpret=False):
    """Differentiable flash attention over (B, T, H, Dh) tensors with NO
    head transposes: inputs are consumed exactly as the head-split
    projection reshape produces them. Requires Dh % 128 == 0."""
    b, t, h, d = q.shape
    tk = k.shape[1]
    if d % 128:
        raise ValueError(
            "BTHD pallas path needs d_head %% 128 == 0, got %d "
            "(use the BHTD path / pallas_flash_attention instead)" % d)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    block_q = _fit_block(t, block_q)
    block_k = _fit_block(tk, block_k)
    if t % block_q or tk % block_k:
        raise ValueError("seq lens (%d, %d) must divide block sizes (%d, %d)"
                         % (t, tk, block_q, block_k))
    qs = (q * jnp.asarray(scale, q.dtype)).reshape(b, t, h * d)
    kf = k.reshape(b, tk, h * d)
    vf = v.reshape(b, tk, h * d)
    out = _pallas_mha_bthd(qs, kf, vf, h, causal, block_q, block_k,
                           interpret)
    return out.reshape(b, t, h, d)



# The q block of the flash kernels and, forward only, the k block too
# (`_fit_block` halves it to a divisor of the sequence). A constant: a
# test that wants several blocks of a short sequence patches it.
_FLASH_BLOCK = 512


def _fit_block(n: int, want: int) -> int:
    """Largest power-of-two block <= want that divides n (>=128 when
    possible — TPU lane granularity)."""
    b = min(want, n)
    while b > 128 and n % b:
        b //= 2
    return b


def pallas_flash_attention(q, k, v, causal=False, scale=None,
                           block_q=512, block_k=512, interpret=False):
    """Differentiable flash attention as Pallas TPU kernels.
    q,k,v: (B, H, T, D) with T a multiple of 128 (block sizes are shrunk
    to fit non-multiples of the requested block)."""
    b, h, t, d = q.shape
    tk = k.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    block_q = _fit_block(t, block_q)
    block_k = _fit_block(tk, block_k)
    if t % block_q or tk % block_k:
        raise ValueError("seq lens (%d, %d) must divide block sizes (%d, %d)"
                         % (t, tk, block_q, block_k))
    # fold the softmax scale into q: kernels (and their grads) then work in
    # scaled-q space; the chain rule puts the scale back on dq automatically
    # through this multiplication's own vjp.
    qs = (q * jnp.asarray(scale, q.dtype)).reshape(b * h, t, d)
    kf = k.reshape(b * h, tk, d)
    vf = v.reshape(b * h, tk, d)
    out = _pallas_mha(qs, kf, vf, causal, block_q, block_k, interpret)
    return out.reshape(b, h, t, d)


def pallas_flash_fwd(q, k, v, causal=False, scale=None,
                     block_q=256, block_k=256, interpret=False):
    """Forward-only entry kept for compatibility; same kernel as the
    differentiable path."""
    return pallas_flash_attention(q, k, v, causal=causal, scale=scale,
                                  block_q=block_q, block_k=block_k,
                                  interpret=interpret)


@register_op("fused_attention", names_device_calls=True)
def _fused_attention(ctx):
    """Inputs Q,K,V: (B, H, T, Dh) — or (B, T, H, Dh) with attr
    layout="bthd" (+ optional Lengths for KV padding). Attrs: causal,
    scale, dropout_rate, block_k, layout. One op replaces the reference's
    matmul/softmax/dropout/matmul subgraph; see module doc. The bthd
    layout consumes q/k/v exactly as the head-split projection reshape
    produces them, so no head transposes exist in fwd or bwd; it needs
    Dh %% 128 == 0 on the Pallas path and otherwise falls back to the
    transposing path internally (numerics identical either way)."""
    q, k, v = ctx.input("Q"), ctx.input("K"), ctx.input("V")
    lengths = ctx.input("Lengths")
    causal = bool(ctx.attr("causal", False))
    scale = ctx.attr("scale", None)
    dropout_rate = float(ctx.attr("dropout_rate", 0.0) or 0.0)
    if ctx.is_test:
        dropout_rate = 0.0
    block_k = block_attr("fused_attention", "block_k",
                         ctx.attr("block_k", 512))
    layout = str(ctx.attr("layout", "bhtd") or "bhtd").lower()
    rng = ctx.rng() if dropout_rate else None
    if layout == "bthd":
        return {"Out": _attention_bthd(q, k, v, lengths, causal, scale,
                                       dropout_rate, block_k, rng)}
    k, v = _repeat_kv(q, k, v, 1)
    return {"Out": _attention_bhtd(q, k, v, lengths, causal, scale,
                                   dropout_rate, block_k, rng)}


def _repeat_kv(q, k, v, head_axis):
    """Grouped queries: the kernels take q, k, v of one head count, so
    each key/value head is repeated for the query heads that share it
    (a cost in compute, none in mathematics)."""
    if k.shape[head_axis] == q.shape[head_axis]:
        return k, v
    group, rem = divmod(q.shape[head_axis], k.shape[head_axis])
    if rem:
        raise ValueError(
            "fused_attention: %d query heads do not divide over %d "
            "key/value heads" % (q.shape[head_axis], k.shape[head_axis]))
    return (jnp.repeat(k, group, axis=head_axis),
            jnp.repeat(v, group, axis=head_axis))


def _attention_bthd(q, k, v, lengths, causal, scale, dropout_rate, block_k,
                    rng):
    """The (B, T, H, Dh) dispatch: the zero-transpose Pallas kernels at
    a lane-aligned head, ``_attention_bhtd`` around two transposes
    otherwise."""
    k, v = _repeat_kv(q, k, v, 2)
    t, tk, d_head = q.shape[1], k.shape[1], q.shape[-1]
    if d_head % 128 == 0 and _use_pallas(t, tk, lengths, dropout_rate):
        kern = functools.partial(
            pallas_flash_attention_bthd, causal=causal, scale=scale,
            block_q=_FLASH_BLOCK, block_k=block_k)
        return _per_shard(kern, q, k, v, head_dim=2)
    out = _attention_bhtd(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
        jnp.swapaxes(v, 1, 2), lengths, causal, scale, dropout_rate,
        block_k, rng)
    return jnp.swapaxes(out, 1, 2)


def _attention_bhtd(q, k, v, lengths, causal, scale, dropout_rate, block_k,
                    rng):
    """The (B, H, T, Dh) dispatch: Pallas fwd+bwd kernels when eligible,
    XLA flash path (CPU, dropout, KV padding masks) otherwise."""
    if _use_pallas(q.shape[2], k.shape[2], lengths, dropout_rate):
        kern = functools.partial(
            pallas_flash_attention, causal=causal, scale=scale,
            block_q=_FLASH_BLOCK, block_k=block_k)
        return _per_shard(kern, q, k, v, head_dim=1)
    return flash_attention(
        q, k, v, causal=causal, scale=scale, lengths=lengths,
        dropout_rate=dropout_rate, rng_key=rng, block_k=block_k)


def _per_shard(kern, q, k, v, head_dim):
    """Run a Pallas attention kernel where the step is being traced:
    bare on one device; under a multi-device trace mesh
    (framework.trace.mesh_context) inside a shard_map — Mosaic kernels
    cannot be partitioned by GSPMD. Batch splits over the plan's batch
    axes and heads over its tensor axis; attention is independent per
    (batch, head), so no collective runs inside. Mesh axes that split
    neither dim compute replicated. A dim the axes do not divide is an
    error naming the shape, never a quiet switch to another path."""
    mesh = current_trace_mesh()
    if mesh is None or mesh.size == 1:
        return kern(q, k, v)
    plan = current_trace_plan()
    batch_axes = tuple(a for a in getattr(plan, "batch_axes", ())
                       if mesh.shape[a] > 1)
    head_axis = getattr(plan, "tensor_axis", None)
    if head_axis is not None and mesh.shape[head_axis] == 1:
        head_axis = None
    b_ways = math.prod(mesh.shape[a] for a in batch_axes)
    h_ways = mesh.shape[head_axis] if head_axis else 1
    if q.shape[0] % b_ways or q.shape[head_dim] % h_ways:
        raise ValueError(
            "fused_attention: q %s (head dim %d) does not divide over "
            "mesh %s with batch axes %s and tensor axis %r"
            % (q.shape, head_dim, dict(mesh.shape), batch_axes, head_axis))
    dims = [None] * 4
    dims[0] = batch_axes or None
    dims[head_dim] = head_axis
    spec = P(*dims)
    # check_vma off: pallas_call outputs carry no varying-axes type
    return jax.shard_map(kern, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def block_attr(op: str, name: str, val) -> int:
    """An op's block-size attribute as the kernels take it: a power of
    two >= 128 (TPU lane granularity; `_fit_block` halves from here).
    An attribute is input: a bad one fails here, naming the op, and not
    as a cryptic error in the middle of a trace."""
    val = int(val)
    if val < 128 or val & (val - 1):
        raise ValueError(
            "%s: %s=%d must be a power of two >= 128" % (op, name, val))
    return val


def _use_pallas(t, tk, lengths, dropout_rate) -> bool:
    """Pallas fwd+bwd path: a step bound for a TPU, no KV padding mask,
    no dropout, and block-aligned sequence lengths (256 keeps small
    models on XLA). PADDLE_TPU_FORCE_PALLAS=1 skips only the device
    check — for compiling a TPU-bound program on a host without a chip
    (tests/test_tpu_compile.py). Executing such a trace on CPU fails —
    this is a compile/debug lever, not a CPU execution mode."""
    if lengths is not None or dropout_rate:
        return False
    if os.environ.get("PADDLE_TPU_NO_PALLAS", "0") == "1":
        return False
    force = os.environ.get("PADDLE_TPU_FORCE_PALLAS", "0") == "1"
    if not force and current_device().platform != "tpu":
        return False
    # 128 matches _fit_block's floor so the dispatch gate and the kernel
    # entry can never disagree; tiny sequences stay on the XLA path
    return t % 128 == 0 and tk % 128 == 0 and t >= 256 and tk >= 256


ATTN_WINDOW = "ptpu.attn_window"


def prefill_attention_reference(q, k, v, window=0, scale=None, sink=None):
    """Causal attention of a prefill, exact, pure lax: q (B, T, H, dq),
    k (B, T, Hkv, dq), v (B, T, Hkv, dv) with H = g * Hkv -> (B, T, H,
    dv); key j is visible to query t iff j <= t and, with ``window``,
    t - window < j; ``sink`` (H,): a learned scalar a query head in the
    softmax's denominator, which takes no value (``sink_share``).
    Builds the (T, T) scores: the path of every device
    but a TPU and of a bucket under the kernel's threshold, and the
    numeric reference of the kernel."""
    b, t, h, d = q.shape
    hkv = k.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    qf = (q.astype(jnp.float32) * scale).reshape(b, t, hkv, h // hkv, d)
    s = jnp.einsum("btkgd,bskd->bkgts", qf, k.astype(jnp.float32))
    row = jnp.arange(t)[:, None]
    col = jnp.arange(t)[None, :]
    seen = col <= row
    if window:
        seen &= col > row - window
    s = jnp.where(seen, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgts,bskd->btkgd", p, v.astype(jnp.float32))
    out = out.reshape(b, t, h, v.shape[-1])
    if sink is not None:
        lse = jax.nn.logsumexp(s, axis=-1)                  # (B, Hkv, g, T)
        out = out * sink_share(
            jnp.moveaxis(lse.reshape(b, h, t), 1, 2), sink)[..., None]
    return out.astype(q.dtype)


def sink_share(lse, sink):
    """What a softmax keeps of its mass when one learned scalar ``sink``
    joins its denominator and takes no value: with ``lse`` the
    log-sum-exp of a row's scores, ``exp(z_j) / (exp(sink) + sum_j'
    exp(z_j')) = softmax_j z x sigmoid(lse - sink)``, exactly. ``sink``
    broadcasts against ``lse`` (a prefill's (B, T, H) against (H,))."""
    return jax.nn.sigmoid(lse - sink.astype(jnp.float32))


def flash_operand(x):
    """x (B, T, H, D) as the forward-only flash call takes it: float32
    rounded to bfloat16, D padded with zero channels to whole 128-lane
    tiles, (B, T, H x width); K and V at their own head count."""
    if x.dtype == jnp.float32:
        x = x.astype(jnp.bfloat16)
    width = _ceil_to(x.shape[-1], 128)
    x = jnp.pad(x, ((0, 0),) * 3 + ((0, width - x.shape[-1]),))
    return x.reshape(x.shape[0], x.shape[1], x.shape[2] * width)


# the q-block of a band (`prefill_blocks`): what
# `tools/probe_prefill_window.py` read fastest on a v5e among 128 / 256 /
# 512 (PERF.md, PR 57)
_BAND_BLOCK_Q = 256


def band_back(window: int) -> int:
    """The keys a band's q-block reads before its first row: the
    smallest power of two that holds ``window``, 128 at least."""
    return max(128, 1 << (window - 1).bit_length())


def prefill_blocks(window: int, t: int):
    """(block_q, block_k, band) of a prefill's flash forward over a
    bucket of ``t`` rows, from the layer's window and nothing else.

    A window no wider than ``_FLASH_BLOCK`` under the bucket: a BAND.
    A q-block's rows see only the keys from ``band_back(window)``
    before its first row up to its last row, so its keys are ONE block of ``block_q +
    back`` read in one pass (``_mha_fwd_band_block``), not ``2 x
    _FLASH_BLOCK`` keys walked for a window of 128; the q-block is
    ``_BAND_BLOCK_Q`` or ``back``, whichever is larger, halved while
    the bucket does not hold ``block_q + back`` rows. Else (no window,
    one the bucket is inside of, or a wider one) the walk over key
    blocks: both blocks ``_FLASH_BLOCK``, halved to a divisor of ``t``
    (``_fit_block``)."""
    if 0 < window < t and window <= _FLASH_BLOCK:
        back = band_back(window)
        block_q = _fit_block(t, min(max(back, _BAND_BLOCK_Q), _FLASH_BLOCK))
        while block_q > 128 and block_q + back > t:
            block_q //= 2
        if block_q + back <= t and t % block_q == 0:
            return block_q, block_q + back, True
    block = _fit_block(t, _FLASH_BLOCK)
    return block, block, False


def prefill_attention(q, k, v, lengths=None, window=0, scale=None, name=None,
                      interpret=False, sink=None):
    """THE causal attention of a serving prefill, forward only: q (B, T,
    H, dq), k (B, T, Hkv, dq), v (B, T, Hkv, dv) -> (B, T, H, dv) in
    q's type; with ``window`` a query sees its last ``window`` keys.
    ``lengths`` (B,): the rows' live tokens, padding at a row's END.
    ``sink`` (H,): a learned scalar a query head that joins the
    softmax's denominator and takes no value: the output times
    ``sigmoid(lse - sink)``.

    On a TPU at a block-aligned bucket (``_use_pallas``: 256 rows and
    up) ONE call of the flash forward kernel at the layer's own shape,
    with no pass of XLA's over K, V or the output beside it; ``name`` in
    a device trace (``ptpu.attn_window`` where a window was asked for,
    else ``ptpu.flash_fwd``):

    - float32 operands are rounded to bfloat16 in HBM before the call:
      what Mosaic's dot rounds float32 operands to at the default
      precision anyway (on the chip the two give the same bits at the
      same MXU time: PERF.md, PR 45), the arithmetic the lax paths
      compute in and the serving configurations state. What the cast
      buys is bytes: half of what a head's resident K and V take in
      vector memory. The statistics and sums stay float32, and so does
      the output of float32 callers;
    - K and V go in at their own ``Hkv`` heads: query head ``hi`` reads
      head ``hi // group`` by the call's index map, and a group's heads
      run against one resident copy (nothing is repeated in HBM);
    - the blocks follow the window (``prefill_blocks``): under a
      window of 128 a q-block of 256 rows reads the 384 keys it sees as
      one block, in one pass;
    - ``lengths`` is scalar-prefetched: a q-block wholly past its row's
      length computes nothing and gives zeros;
    - ``sink`` is scalar-prefetched beside them, and the kernel's last
      write is the output times ``sigmoid(m + log l - sink[hi])`` in
      float32, from the statistics it holds;
    - q and k are padded with zero channels to a multiple of the 128
      lanes and v to one of its own (192 / 192 / 128 -> 256 / 256 / 128),
      and the output's first ``dv`` channels kept: exact.

    Elsewhere the exact lax form, which computes every row: a padding
    row's output differs between the two paths and no one reads it (the
    attention is causal and padding is at the end, so no live row sees
    a padding row's key). The differentiable ``fused_attention`` of the
    training graphs shares the kernel BODY and nothing of this
    dispatch."""
    b, t, h, dq = q.shape
    dv = v.shape[-1]
    window = int(window)
    name = name or (ATTN_WINDOW if window else FLASH_FWD)
    if window >= t:
        window = 0  # every earlier key is inside it: plain causal
    group = h // k.shape[2]
    kernel = interpret or _use_pallas(t, t, None, 0.0)
    PREFILL_ATTN_TRACES.inc(
        path="kernel" if kernel else "lax",
        operands="bfloat16" if kernel else jnp.dtype(q.dtype).name,
        lengths="none" if lengths is None else "given")
    block_q, block_k, band = prefill_blocks(window, t)
    if sink is not None or dv != dq or window or group > 1:
        PREFILL_ATTN_FORMS.inc(
            sink="none" if sink is None else "learned",
            value_width="query" if dv == dq else "own",
            kv="own" if group > 1 else "query",
            block_k=str(block_k) if kernel else "none")
    if not kernel:
        with jax.named_scope(name):
            return prefill_attention_reference(q, k, v, window, scale, sink)
    if scale is None:
        scale = 1.0 / math.sqrt(dq)
    out, _ = _mha_fwd_call_bthd(
        flash_operand(q * jnp.asarray(scale, q.dtype)), flash_operand(k),
        flash_operand(v), h, True, block_q, block_k, interpret,
        window=window, name=name, lengths=lengths, out_dtype=q.dtype,
        group=group, sink=sink, band=band)
    return out.reshape(b, t, h, -1)[..., :dv]


@register_op("prefill_attention")
def _prefill_attention_op(ctx):
    """Inputs Q (B, T, H, dq), K (B, T, Hkv, dq), V (B, T, Hkv, dv),
    optional Lengths (B,) and Sink (H,); attrs window (0: every earlier
    key), scale
    -> Out (B, T, H, dv): ``prefill_attention``."""
    return {"Out": prefill_attention(
        ctx.input("Q"), ctx.input("K"), ctx.input("V"),
        ctx.input("Lengths"), window=int(ctx.attr("window", 0) or 0),
        scale=ctx.attr("scale", None), sink=ctx.input("Sink"))}


@register_op("ring_attention")
def _ring_attention_op(ctx):
    """Sequence-parallel exact attention (SURVEY §2 long-context
    commitment; no reference twin). Inputs Q,K,V: (B, H, T, Dh), optional
    Lengths (B,) global KV lengths; attrs causal, scale, sp_axis,
    dropout_rate. When the step is traced under a mesh whose `sp_axis`
    exists and is >1 wide (ParallelExecutor sets
    framework.trace.mesh_context), the kernel runs the ppermute ring
    (parallel/ring_attention.py) so each device holds an O(T/N) sequence
    shard; otherwise it falls back to exact full attention. Dropout masks
    are position-stable (keyed on global coordinates), so the two
    dispatches stay numerically identical — the same Program produces
    the same losses on one chip and on an sp mesh."""
    from ..parallel.ring_attention import full_attention, ring_self_attention

    q, k, v = ctx.input("Q"), ctx.input("K"), ctx.input("V")
    lengths = ctx.input("Lengths")
    causal = bool(ctx.attr("causal", False))
    scale = ctx.attr("scale", None)
    sp_axis = ctx.attr("sp_axis", "sp")
    dropout_rate = float(ctx.attr("dropout_rate", 0.0) or 0.0)
    if ctx.is_test:
        dropout_rate = 0.0
    seed = (jax.random.key_data(ctx.rng()).astype(jnp.uint32)
            if dropout_rate else None)
    # per-rotation-step KV sub-chunking (transient-memory bound; see
    # parallel/ring_attention.py); None or 0 means auto/whole-block
    chunk = int(ctx.attr("chunk", None) or 0) or None
    mesh = current_trace_mesh()
    if (mesh is not None and sp_axis in mesh.axis_names
            and mesh.shape[sp_axis] > 1):
        return {"Out": ring_self_attention(
            q, k, v, mesh, sp_axis=sp_axis, causal=causal, scale=scale,
            lengths=lengths, dropout_rate=dropout_rate, dropout_seed=seed,
            chunk=chunk)}
    return {"Out": full_attention(
        q, k, v, causal=causal, scale=scale, lengths=lengths,
        dropout_rate=dropout_rate, dropout_seed=seed)}


@register_op("moe_ffn")
def _moe_ffn_op(ctx):
    """Mixture-of-experts FFN (SURVEY §2 expert-parallel commitment; no
    reference twin). Inputs X (B,T,D), GateW (D,E), W1 (E,D,F), B1 (E,F),
    W2 (E,F,D), B2 (E,D). Under a mesh with the `ep_axis` (ParallelExecutor
    mesh context) experts shard across devices with all_to_all dispatch
    (parallel/moe.py); otherwise the identical-math single-device path
    runs, so one Program serves both worlds."""
    from ..parallel.moe import MoEParams, expert_parallel_ffn, moe_ffn_local

    params = MoEParams(
        gate_w=ctx.input("GateW"), w1=ctx.input("W1"), b1=ctx.input("B1"),
        w2=ctx.input("W2"), b2=ctx.input("B2"))
    x = ctx.input("X")
    cf = float(ctx.attr("capacity_factor", 2.0))
    k = int(ctx.attr("k", 2))
    ep_axis = ctx.attr("ep_axis", "ep")
    mesh = current_trace_mesh()
    if (mesh is not None and ep_axis in mesh.axis_names
            and mesh.shape[ep_axis] > 1):
        if params.gate_w.shape[-1] % mesh.shape[ep_axis] != 0:
            # fail loudly: a silent local fallback would replicate every
            # expert on every device with no parallelism
            raise ValueError(
                "moe_ffn: num_experts %d must divide over the %d-way "
                "'%s' mesh axis" % (params.gate_w.shape[-1],
                                    mesh.shape[ep_axis], ep_axis))
        # tokens replicated over ep (the executor's GSPMD feeds aren't
        # ep-sharded): every device routes the same N tokens, so the
        # capacity factor carries over 1:1 and drops match the
        # single-device path exactly
        out = expert_parallel_ffn(x, params, mesh, axis=ep_axis,
                                  capacity_factor=cf, k=k,
                                  batch_dim_sharded=False)
    else:
        out = moe_ffn_local(x, params, capacity_factor=cf, k=k)
    return {"Out": out}
