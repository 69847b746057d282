"""Ring attention: exact attention over sequence shards with O(T/N) memory
per chip and compute/communication overlap on the ICI ring.

No reference twin — codeWorm2015/Paddle (2018) predates long-context
attention; this is the TPU-native capability the survey lists as
first-class (SURVEY.md §2 parallel). The design follows the blockwise
online-softmax formulation: K/V blocks rotate around the mesh axis with
``lax.ppermute`` while each device keeps its Q shard resident and folds
each visiting block into (m, num, den) running statistics, so the full
(T, T) score matrix never materializes.

Used three ways:
- `ring_attention(...)` — inside an existing shard_map body (axis in scope)
- `ring_self_attention(...)` — standalone: shard_maps itself over a mesh
- the `ring_attention` IR op (ops/nn.py) — inside a Program; falls back to
  exact full attention when the step is not compiled over a sequence axis.
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ._compat import pvary as _compat_pvary, shard_map

__all__ = ["ring_attention", "ring_self_attention", "full_attention"]

_NEG = -1e30
_U = np.uint32


def _mix32(x):
    """lowbias32 avalanche finalizer on uint32 lattices (public-domain
    integer-hash constants); statistically fine for dropout bits."""
    x = x ^ (x >> _U(16))
    x = x * _U(0x7FEB352D)
    x = x ^ (x >> _U(15))
    x = x * _U(0x846CA68B)
    x = x ^ (x >> _U(16))
    return x


def _dropout_keep_scale(seed, B, H, q_pos, k_pos, rate):
    """(B, H, len(q_pos), len(k_pos)) f32 multiplicative dropout factor
    keep/(1-rate), where `keep` is a pure function of (seed, batch, head,
    GLOBAL query position, GLOBAL key position).

    Position-stable by construction: the mask for any (q, k) score element
    is independent of how the sequence is blocked or sharded, so the ring
    path (any number of sp shards) and the single-device full-attention
    fallback draw bit-identical masks — that is what makes ring-vs-full
    parity hold WITH dropout. `seed` is a uint32 (2,) array
    (jax.random.key_data of a PRNG key)."""
    seed = jnp.asarray(seed, jnp.uint32).reshape(-1)
    b = jnp.arange(B, dtype=jnp.uint32).reshape(B, 1, 1, 1)
    h = jnp.arange(H, dtype=jnp.uint32).reshape(1, H, 1, 1)
    qp = q_pos.astype(jnp.uint32).reshape(1, 1, -1, 1)
    kp = k_pos.astype(jnp.uint32).reshape(1, 1, 1, -1)
    x = _mix32(seed[0] ^ _mix32(seed[1]))
    x = _mix32(x ^ (b * _U(0x9E3779B1)))
    x = _mix32(x ^ (h * _U(0x85EBCA77)))
    x = _mix32(x ^ (qp * _U(0xC2B2AE3D)))
    x = _mix32(x ^ (kp * _U(0x27D4EB2F)))
    # top 24 bits -> uniform [0, 1)
    u = (x >> _U(8)).astype(jnp.float32) * (1.0 / (1 << 24))
    return (u >= rate).astype(jnp.float32) / (1.0 - rate)


def full_attention(q, k, v, causal: bool = False, scale: Optional[float] = None,
                   lengths=None, dropout_rate: float = 0.0, dropout_seed=None):
    """Exact single-device attention, the numeric reference for the ring.
    q,k,v: (B, H, T, Dh). `lengths` (B,) masks padded KV positions;
    `dropout_rate`/`dropout_seed` apply the same position-stable dropout
    as the ring path (see _dropout_keep_scale), so this stays its numeric
    twin under both features."""
    if dropout_rate and dropout_seed is None:
        raise ValueError("dropout_rate > 0 requires dropout_seed "
                         "(uint32 (2,) array, e.g. jax.random.key_data)")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    B, H, Tq, _ = q.shape
    Tk = k.shape[2]
    # match the ring path's score numerics exactly: fold the
    # scale into q in the INPUT dtype (as _ring_fwd_impl does) and
    # accumulate the einsum in f32 — both halves matter for bf16 parity
    qs = (q * jnp.asarray(scale, q.dtype)).astype(q.dtype)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qs, k,
                        preferred_element_type=jnp.float32)
    masked = causal or lengths is not None
    if causal:
        mask = jnp.tril(jnp.ones((Tq, Tk), bool), k=Tk - Tq)
        logits = jnp.where(mask, logits, _NEG)
    if lengths is not None:
        valid = jnp.arange(Tk)[None, :] < lengths.reshape(-1)[:, None]  # (B, Tk)
        logits = jnp.where(valid[:, None, None, :], logits, _NEG)
    weights = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    if masked:
        # a fully-masked row (e.g. lengths[b] == 0) must produce 0, not
        # the softmax of a constant row (the mean of V) — mirrors the
        # ring path's zeroed accumulators
        weights = jnp.where(logits <= _NEG / 2, 0.0, weights)
    if dropout_rate:
        weights = weights * _dropout_keep_scale(
            dropout_seed, B, H, jnp.arange(Tq), jnp.arange(Tk), dropout_rate)
    return jnp.einsum("bhqk,bhkd->bhqd", weights.astype(v.dtype), v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 9))
def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   scale: Optional[float] = None, dropout_rate: float = 0.0,
                   lengths=None, dropout_seed=None,
                   chunk: Optional[int] = None):
    """Blockwise-exact attention inside a shard_map body.

    q, k, v: (B, H, T_local, Dh) — the local sequence shard; the global
    sequence is the concatenation over `axis_name` in axis-index order.
    Accumulates in fp32 regardless of input dtype (bf16-safe).

    `lengths` (B,) are GLOBAL KV lengths: keys at global position >=
    lengths[b] are masked out of batch row b (the reference's sequence
    padding semantics — /root/reference/python/paddle/fluid/nets.py:332's
    attention over padded batches). `dropout_rate`/`dropout_seed` apply
    attention-probability dropout with a position-stable mask
    (_dropout_keep_scale), matching full_attention bit-for-bit. Both are
    replicated inputs — every device sees the full (B,) lengths and the
    same seed.

    `chunk` bounds per-rotation-step TRANSIENT memory: each visiting KV
    block is consumed in sub-blocks of `chunk` keys (a lax.scan with an
    online-softmax carry), so the largest live score tensor is
    (B, H, T_local, chunk) instead of (B, H, T_local, T_local) — the
    difference between seq ~64k and seq 1M+ fitting a chip. None picks
    automatically: whole-block below _CHUNK_AUTO keys (best XLA fusion
    at bench sizes), the largest lane-aligned divisor above it. The
    position-stable masks/dropout make chunking invisible numerically.

    Differentiable with O(T_local) residuals: the custom backward saves
    only (q, k, v, out, lse) and RE-ROTATES K/V around the ring,
    recomputing each block's probabilities from the logsumexp — dK/dV
    accumulators travel with their blocks and arrive home after the
    full cycle. Plain autodiff would instead save every rotation's
    (T_local, T_local) probability tensor (O(size * T_local^2), i.e.
    the full (T, T) ring attention exists to avoid).
    """
    out, _ = _ring_fwd_impl(q, k, v, lengths, dropout_seed, axis_name,
                            causal, scale, dropout_rate, chunk)
    return out


_CHUNK_AUTO = 2048  # auto-chunk threshold AND the auto chunk size


def _pick_chunk(T: int, chunk: Optional[int]):
    """(n_chunks, chunk_size) for a T-key block. Explicit chunk must
    divide T; auto keeps small blocks whole and splits big ones at the
    largest power-of-two divisor <= _CHUNK_AUTO."""
    if chunk is not None:
        chunk = int(chunk)
        if chunk <= 0 or T % chunk:
            raise ValueError(
                "ring attention chunk=%d must positively divide the "
                "local block length %d" % (chunk, T))
        return T // chunk, chunk
    if T <= _CHUNK_AUTO:
        return 1, T
    c = _CHUNK_AUTO
    while c > 128 and T % c:
        c //= 2
    if T % c:
        return 1, T  # odd length: stay whole rather than mis-split
    return T // c, c


def _ring_steps(axis_name):
    size = lax.psum(1, axis_name)
    my_blk = lax.axis_index(axis_name)
    fwd = [(i, (i + 1) % size) for i in range(size)]
    return int(size), my_blk, fwd


def _vary_like(x, axis_name):
    """Mark x varying over the manual mesh axis (shard_map vma typing):
    the chunk scans' initial carries are device-invariant zeros while
    the body outputs mix in the varying q/kv shards."""
    return _compat_pvary(x, axis_name)


def _chunk_scores(qs, kcc, k_pos, q_pos, causal, lengths=None):
    """(B, H, Tq, C) f32 scores of the local q shard against a visiting
    KV sub-chunk at GLOBAL key positions k_pos, causal- and padding-
    masked; bf16 inputs run on the MXU at full rate (f32 accumulation)."""
    scores = jnp.einsum("bhqd,bhkd->bhqk", qs, kcc,
                        preferred_element_type=jnp.float32)
    if causal:
        keep = q_pos[:, None] >= k_pos[None, :]  # (Tq, C)
        scores = jnp.where(keep[None, None], scores, _NEG)
    if lengths is not None:
        valid = k_pos[None, :] < lengths.reshape(-1)[:, None]  # (B, C)
        scores = jnp.where(valid[:, None, None, :], scores, _NEG)
    return scores


def _kv_chunk_axes(x, nc, C):
    """(B, H, T, Dh) -> (nc, B, H, C, Dh) scan-ready sub-chunks."""
    B, H, T, Dh = x.shape
    return x.reshape(B, H, nc, C, Dh).transpose(2, 0, 1, 3, 4)


def _ring_fwd_impl(q, k, v, lengths, dropout_seed, axis_name, causal, scale,
                   dropout_rate, chunk):
    size, my_blk, fwd = _ring_steps(axis_name)
    B, H, T, Dh = q.shape
    if scale is None:
        scale = Dh ** -0.5
    nc, C = _pick_chunk(T, chunk)
    # fold the scale into q and KEEP the input dtype: under bf16 AMP the
    # score einsum then runs bf16 x bf16 -> f32 on the MXU (full rate,
    # f32 accumulation via preferred_element_type) — same recipe as the
    # flash kernels; with f32 inputs this is numerically unchanged.
    qs = (q * jnp.asarray(scale, q.dtype)).astype(q.dtype)
    q_pos = my_blk * T + jnp.arange(T)  # global query positions
    masked = causal or lengths is not None

    def fwd_chunk(carry, kcc, vcc, k_pos):
        """Fold one visiting KV sub-chunk into the (m, num, den) online-
        softmax carry."""
        m, num, den = carry
        scores = _chunk_scores(qs, kcc, k_pos, q_pos, causal, lengths)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        # rows where everything so far is masked keep m=_NEG; exp(score-m)
        # would be exp(0)=1 there, so zero masked terms explicitly.
        p = jnp.exp(scores - m_new[..., None])
        if masked:
            p = jnp.where(scores <= _NEG / 2, 0.0, p)
        if dropout_rate:
            # dropout applies to the normalized softmax weights, which
            # factor as p / den: scale the numerator's p, keep den on the
            # un-dropped p (normalization is over pre-dropout weights)
            p_num = p * _dropout_keep_scale(dropout_seed, B, H, q_pos,
                                            k_pos, dropout_rate)
        else:
            p_num = p
        corr = jnp.exp(m - m_new)
        num = num * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p_num.astype(vcc.dtype), vcc,
            preferred_element_type=jnp.float32)
        den = den * corr + p.sum(axis=-1)
        return m_new, num, den

    # kv rotates "forward" (device i -> i+1), so at step s device i holds
    # the block originally resident on (i - s) mod size.
    def body(s, carry):
        kc, vc, m, num, den = carry
        base = ((my_blk - s) % size) * T
        if nc == 1:
            m, num, den = fwd_chunk((m, num, den), kc, vc,
                                    base + jnp.arange(T))
        else:
            def sub(c2, args):
                kcc, vcc, j = args
                return fwd_chunk(c2, kcc, vcc,
                                 base + j * C + jnp.arange(C)), None

            # the scan body's outputs vary over the manual sp axis (they
            # mix in the varying q/kv shards), so the initial carry must
            # be marked varying too (shard_map scan-vma typing)
            init_c = tuple(_vary_like(x, axis_name) for x in (m, num, den))
            (m, num, den), _ = lax.scan(
                sub, init_c,
                (_kv_chunk_axes(kc, nc, C), _kv_chunk_axes(vc, nc, C),
                 jnp.arange(nc)))
        kc = lax.ppermute(kc, axis_name, perm=fwd)
        vc = lax.ppermute(vc, axis_name, perm=fwd)
        return kc, vc, m, num, den

    init = (
        k, v,
        jnp.full((B, H, T), _NEG, jnp.float32),
        jnp.zeros((B, H, T, Dh), jnp.float32),
        jnp.zeros((B, H, T), jnp.float32),
    )
    # unrolled python loop (size is static): lets XLA overlap each step's
    # einsums with the next ppermute's ICI transfer.
    kc, vc, m, num, den = init
    for s in range(size):
        kc, vc, m, num, den = body(s, (kc, vc, m, num, den))
    den = jnp.maximum(den, 1e-30)
    out = (num / den[..., None]).astype(q.dtype)
    lse = m + jnp.log(den)  # (B, H, T) f32; fully-masked rows: ~_NEG
    return out, lse


def _ring_fwd(q, k, v, axis_name, causal, scale, dropout_rate, lengths,
              dropout_seed, chunk):
    out, lse = _ring_fwd_impl(q, k, v, lengths, dropout_seed, axis_name,
                              causal, scale, dropout_rate, chunk)
    return out, (q, k, v, out, lse, lengths, dropout_seed)


def _ring_bwd(axis_name, causal, scale, dropout_rate, chunk, res, dout):
    q, k, v, out, lse, lengths, dropout_seed = res
    size, my_blk, fwd = _ring_steps(axis_name)
    B, H, T, Dh = q.shape
    if scale is None:
        scale = Dh ** -0.5
    nc, C = _pick_chunk(T, chunk)
    qs = (q * jnp.asarray(scale, q.dtype)).astype(q.dtype)
    q_pos = my_blk * T + jnp.arange(T)
    do = dout
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)  # (B, H, T)

    def bwd_chunk(dq, kcc, vcc, k_pos):
        """One visiting KV sub-chunk's gradient contributions:
        accumulates into dq, returns this chunk's (dk, dv)."""
        scores = _chunk_scores(qs, kcc, k_pos, q_pos, causal, lengths)
        # p = softmax weights reconstructed from the saved logsumexp;
        # masked entries give exp(_NEG - lse) == 0 exactly — EXCEPT on a
        # fully-masked row, where lse itself is ~_NEG and the subtraction
        # would overflow toward +inf: zero those explicitly (the forward
        # already outputs 0 there, so 0 gradient is exact)
        p = jnp.exp(scores - lse[..., None])
        p = jnp.where(scores <= _NEG / 2, 0.0, p)
        if dropout_rate:
            # out = sum_k p_k * ks_k * v_k / den with den over un-dropped
            # p (see forward): d s_i = p_i * (ks_i * (do . v_i) - delta)
            pd = p * _dropout_keep_scale(dropout_seed, B, H, q_pos, k_pos,
                                         dropout_rate)
        else:
            pd = p
        dv_c = jnp.einsum("bhqk,bhqd->bhkd", pd.astype(do.dtype), do,
                          preferred_element_type=jnp.float32)
        dp = jnp.einsum("bhqd,bhkd->bhqk", do, vcc,
                        preferred_element_type=jnp.float32)
        ds = pd * dp - p * delta[..., None]
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds.astype(kcc.dtype), kcc,
                             preferred_element_type=jnp.float32)
        dk_c = jnp.einsum("bhqk,bhqd->bhkd", ds.astype(qs.dtype), qs,
                          preferred_element_type=jnp.float32)
        return dq, dk_c, dv_c

    def body(s, carry):
        kc, vc, dkc, dvc, dq = carry
        base = ((my_blk - s) % size) * T
        if nc == 1:
            dq, dk_step, dv_step = bwd_chunk(dq, kc, vc,
                                             base + jnp.arange(T))
        else:
            def sub(dq2, args):
                kcc, vcc, j = args
                dq2, dk_c, dv_c = bwd_chunk(dq2, kcc, vcc,
                                            base + j * C + jnp.arange(C))
                return dq2, (dk_c, dv_c)

            dq, (dks, dvs) = lax.scan(
                sub, _vary_like(dq, axis_name),
                (_kv_chunk_axes(kc, nc, C), _kv_chunk_axes(vc, nc, C),
                 jnp.arange(nc)))
            # (nc, B, H, C, Dh) stacked chunk grads -> (B, H, T, Dh)
            dk_step = dks.transpose(1, 2, 0, 3, 4).reshape(B, H, T, Dh)
            dv_step = dvs.transpose(1, 2, 0, 3, 4).reshape(B, H, T, Dh)
        # the dK/dV accumulators TRAVEL WITH their blocks: after the full
        # cycle each block is home again carrying every device's
        # contribution
        dkc = lax.ppermute(dkc + dk_step, axis_name, perm=fwd)
        dvc = lax.ppermute(dvc + dv_step, axis_name, perm=fwd)
        kc = lax.ppermute(kc, axis_name, perm=fwd)
        vc = lax.ppermute(vc, axis_name, perm=fwd)
        return kc, vc, dkc, dvc, dq

    zero_kv = jnp.zeros((B, H, T, Dh), jnp.float32)
    carry = (k, v, zero_kv, zero_kv,
             jnp.zeros((B, H, T, Dh), jnp.float32))
    for s in range(size):
        carry = body(s, carry)
    _, _, dkc, dvc, dq = carry
    # d(qs)/dq = scale (the fold at the top)
    dq = dq * jnp.asarray(scale, jnp.float32)
    return (dq.astype(q.dtype), dkc.astype(k.dtype), dvc.astype(v.dtype),
            None, None)


ring_attention.defvjp(_ring_fwd, _ring_bwd)


def ring_self_attention(q, k, v, mesh: Mesh, sp_axis: str = "sp",
                        causal: bool = False, scale: Optional[float] = None,
                        lengths=None, dropout_rate: float = 0.0,
                        dropout_seed=None, chunk: Optional[int] = None):
    """Standalone entry: q,k,v are global (B, H, T, Dh) arrays; the sequence
    dim is sharded over mesh axis `sp_axis` and attention is exact.
    `lengths` (global KV lengths) and the dropout seed are replicated."""
    if dropout_rate and dropout_seed is None:
        raise ValueError("dropout_rate > 0 requires dropout_seed "
                         "(uint32 (2,) array, e.g. jax.random.key_data)")
    spec = P(None, None, sp_axis, None)

    def body(q, k, v, lengths, seed):
        return ring_attention(q, k, v, sp_axis, causal, scale,
                              dropout_rate, lengths, seed, chunk)

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(spec, spec, spec, P(), P()), out_specs=spec,
    )
    return fn(q, k, v,
              None if lengths is None else jnp.asarray(lengths),
              None if dropout_seed is None
              else jnp.asarray(dropout_seed, jnp.uint32))
