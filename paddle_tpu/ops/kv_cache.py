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

- ``decode_attention``: Q (B, 1, H, Dh) x cache K/V (B, S, H, Dh) with
  Lengths (B,) -> (B, 1, H, Dh). A Pallas TPU kernel (one grid cell per
  (batch, head); online softmax over KV blocks in VMEM, the
  single-query sibling of ops/attention.py's ``_mha_fwd_kernel``) with
  the exact pure-``lax`` path on CPU/GPU and non-aligned shapes; the
  kernel also runs under ``interpret=True`` so parity is testable off
  TPU.
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

from ..framework.scope import current_device
from .attention import _fit_block, _tpu_params, named_pallas_call
from .registry import register_op

_NEG = -1e30

# the decode kernel's stable name in lowered text and device traces
DECODE_ATTN = "ptpu.decode_attn"


# ---------------------------------------------------------------------------
# single-query decode attention
# ---------------------------------------------------------------------------


def decode_attention_reference(q, k_cache, v_cache, lengths, scale=None):
    """Pure-lax decode attention: q (B, 1, H, Dh), caches (B, S, H, Dh),
    lengths (B,) valid rows per slot -> (B, 1, H, Dh). Exact; the CPU
    serving path and the numeric reference for the Pallas kernel.

    Rows with length 0 (empty/inactive slots) produce zeros, not the
    mean of garbage V rows — continuous batching runs every slot of the
    slab each step and ignores the inactive ones, so their outputs must
    at least stay finite.
    """
    b, one, h, d = q.shape
    s = k_cache.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    qf = q[:, 0].astype(jnp.float32) * scale                    # (B, H, D)
    scores = jnp.einsum("bhd,bshd->bhs", qf,
                        k_cache.astype(jnp.float32))            # (B, H, S)
    valid = (jnp.arange(s)[None, None, :]
             < lengths.reshape(-1)[:, None, None])              # (B, 1, S)
    scores = jnp.where(valid, scores, _NEG)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.where(valid, jnp.exp(scores - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhs,bshd->bhd", p / jnp.maximum(l, 1e-30),
                     v_cache.astype(jnp.float32))
    return out[:, None].astype(q.dtype)


def _decode_attn_kernel(q_ref, k_ref, v_ref, len_ref, o_ref, *, block_s,
                        seq_s):
    """One (batch, head) grid cell: the single query row attends its
    slab. q_ref (1, 1, D) pre-scaled; k/v (1, S, D) — the head's column
    slice of the BTHD slab; len_ref (1, 1) int32 in SMEM-like lane; the
    online-softmax loop is ops/attention.py's ``_mha_fwd_kernel`` body
    at block_q == 1."""
    q = q_ref[0]                       # (1, D), pre-scaled
    length = len_ref[0, 0, 0]
    nblk = seq_s // block_s

    def blk(j, carry):
        acc, m, l = carry
        kb = k_ref[0, pl.ds(j * block_s, block_s), :]
        vb = v_ref[0, pl.ds(j * block_s, block_s), :]
        s = jnp.dot(q, kb.T, preferred_element_type=jnp.float32)  # (1, BS)
        col = j * block_s + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col < length, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.where(col < length, jnp.exp(s - m_new[:, None]), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=1)
        acc = acc * corr[:, None] + jnp.dot(
            p.astype(vb.dtype), vb, preferred_element_type=jnp.float32)
        return acc, m_new, l

    d = q.shape[-1]
    init = (jnp.zeros((1, d), jnp.float32),
            jnp.full((1,), _NEG, jnp.float32),
            jnp.zeros((1,), jnp.float32))
    # KV blocks at or past this slot's length contribute nothing — stop
    # the loop there (decode cost tracks the LIVE prefix, not the slab)
    upper = lax.min((length + block_s - 1) // block_s, nblk)
    acc, m, l = lax.fori_loop(0, upper, blk, init)
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def pallas_decode_attention(q, k_cache, v_cache, lengths, scale=None,
                            block_s=512, interpret=False):
    """Pallas decode attention over BTHD slabs; same contract as
    ``decode_attention_reference``. Grid (B, H); each cell streams its
    head's KV column blocks through VMEM with an online softmax —
    no (B, H, S) score tensor in HBM. Requires S % block_s == 0 (the
    dispatch shrinks block_s to fit)."""
    b, one, h, d = q.shape
    s = k_cache.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    block_s = _fit_block(s, block_s)
    if s % block_s:
        raise ValueError("slab length %d must divide block_s %d"
                         % (s, block_s))
    qs = (q * jnp.asarray(scale, q.dtype)).reshape(b, 1, h * d)
    # (B, 1, 1): singleton minor block dims are FULL dims, which Mosaic's
    # block-shape tiling accepts (the _lse_spec_bthd layout lesson —
    # a (1, 1) block under a B-sized second-minor dim is rejected)
    lens = lengths.reshape(-1).astype(jnp.int32)[:, None, None]
    kernel = functools.partial(_decode_attn_kernel, block_s=block_s,
                               seq_s=s)
    kf = k_cache.reshape(b, s, h * d)
    vf = v_cache.reshape(b, s, h * d)
    out = named_pallas_call(
        DECODE_ATTN, kernel,
        grid=(b, h),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda bi, hi: (bi, 0, hi)),
            pl.BlockSpec((1, s, d), lambda bi, hi: (bi, 0, hi)),
            pl.BlockSpec((1, s, d), lambda bi, hi: (bi, 0, hi)),
            pl.BlockSpec((1, 1, 1), lambda bi, hi: (bi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda bi, hi: (bi, 0, hi)),
        out_shape=jax.ShapeDtypeStruct((b, 1, h * d), q.dtype),
        interpret=interpret,
        **_tpu_params("parallel", "parallel"),
    )(qs, kf, vf, lens)
    return out.reshape(b, 1, h, d)


def _use_pallas_decode(s: int, d: int) -> bool:
    """A step bound for a TPU, lane-aligned head dim, block-aligned slab
    (mirrors ops/attention.py:_use_pallas; PADDLE_TPU_NO_PALLAS opts
    out)."""
    if os.environ.get("PADDLE_TPU_NO_PALLAS", "0") == "1":
        return False
    if current_device().platform != "tpu":
        return False
    return d % 128 == 0 and s % 128 == 0 and s >= 128


def decode_attention(q, k_cache, v_cache, lengths, scale=None,
                     block_s=512):
    """Dispatch: Pallas kernel when eligible, exact lax fallback
    otherwise (numerics identical — same online softmax)."""
    s, d = k_cache.shape[1], q.shape[-1]
    if _use_pallas_decode(s, d):
        return pallas_decode_attention(q, k_cache, v_cache, lengths,
                                       scale=scale, block_s=block_s)
    return decode_attention_reference(q, k_cache, v_cache, lengths,
                                      scale=scale)


@register_op("decode_attention")
def _decode_attention_op(ctx):
    """Single-query attention against a KV slab. Inputs Q (B, 1, H, Dh),
    KCache/VCache (B, S, H, Dh), Lengths (B,) valid rows per slot
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
