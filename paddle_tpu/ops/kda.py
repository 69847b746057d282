"""Kimi Delta Attention (KDA; Kimi Linear, arXiv:2510.26692, section 3):
a linear-attention layer whose memory is ONE matrix a head, written by a
delta rule under a decay a CHANNEL. A head keeps ``S`` (dk, dv); a token
with key ``k`` (a unit vector), value ``v``, write strength ``beta`` in
(0, 1), or in (0, 2) where the transition may have a negative eigenvalue
(``KDA_BETA_MAX``: ``I - beta k k^T`` has the eigenvalue ``1 - beta``),
and log-decay ``g`` <= 0 a key channel (``alpha = exp(g)``) does

    S <- Diag(alpha) S                      (forget, a channel at a time)
    S <- S + beta k (v - S^T k)^T           (the delta rule: replace what
                                             the memory holds under k)
    o  = S^T q

which is ``S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t
k_t v_t^T``. Nothing grows with the context: the state is (H, dk, dv)
floats a sequence whatever its length.

THREE forms that compute the same numbers:

- token by token (a ``lax.scan`` of ``_update`` a token: the tests' and
  the reference's form, which no serving path runs and this file does
  not hold);
- one token (``kda_step``, ``ptpu.kda_step``: a decode step's, all in
  float32 multiplies and adds: a contraction would round the state to
  bfloat16 on a TPU). TWO PATHS of that arithmetic
  (``paddle_tpu_kda_step_traces_total{path}``; ``_use_step_kernel``
  chooses by shape, type and device): ``_update``, five lax lines that
  XLA makes two fusions of (the state read once for the two products
  ``S^T [k, q]`` and once more for its update, written once: the CPU's
  path, a narrower state's, the kernel's reference and its backward),
  and on a TPU one Pallas call a layer (``pallas_kda_step``, since
  PR 48): a block of heads' states comes into vector memory once, the
  whole token is done there, and the block goes out once over its own
  input (the operand aliased to the result);
- CHUNKED (``kda_scan``, ``ptpu.kda_scan``: a prefill's). With ``G_t``
  the log-decay summed from a chunk's start through token t, ``u_t =
  beta_t (v_t - S_{t-1}^T (alpha_t k_t))`` and ``S_t = Diag(alpha_t)
  S_{t-1} + k_t u_t^T``, unrolling from the chunk's first state ``S_0``
  gives ``S_t = Diag(e^{G_t}) S_0 + sum_{i<=t} Diag(e^{G_t - G_i}) k_i
  u_i^T`` and so, for the chunk's C tokens at once (the WY / UT
  transform),

      (I + Diag(beta) A) U = Diag(beta) (V - K~ S_0),
      A[t, i] = sum_c k_t[c] k_i[c] e^{G_t[c] - G_i[c]}  (i < t),
      K~_t = e^{G_t} k_t,

  ``U = T Diag(beta) V - T Diag(beta) K~ S_0`` with ``T = (I + Diag(beta)
  A)^-1`` a unit lower triangle, ``O = Q~ S_0 + A_qk U`` (``A_qk`` as A
  with q for k_t and the diagonal kept) and ``S_C = Diag(e^{G_C}) S_0 +
  K^^T U`` with ``K^_i = e^{G_C - G_i} k_i``. Exact algebra: no term is
  dropped. A, T, ``T Diag(beta) V`` and ``T Diag(beta) K~`` need no
  state; the state is touched once a chunk, by three matrix products.
  The chunked form has TWO PATHS of that one algebra
  (``paddle_tpu_kda_scan_traces_total{path, form}`` says which a program
  was traced with; ``_use_kernel`` chooses by shape and device, the
  gate's bound chooses the FORM, below, on either path): composed lax
  (``_kda_scan_lax``: the state-free parts built for ``_BLOCK_CHUNKS``
  chunks at once in HBM, a ``lax.scan`` a chunk: the CPU's path, the
  kernel's reference and its backward), and on a TPU one Pallas call a
  layer (``pallas_kda_scan``, since PR 42; in the guarded form too since
  PR 52): a head's (dk, dv) state and a block's factors, Grams
  and inverses stay in vector memory, the operands come in once as
  blocks of the (B, T, H * d) arrays where they lie, the L2 norms and
  the masking of dead positions are inside, and a block of positions
  wholly past a row's length is neither fetched nor computed.

The per-channel decay is what makes the chunked form hard: ``e^{G_t -
G_i}`` is a product ``e^{G_t} e^{-G_i}`` only while ``e^{-G_i}`` fits a
float, and 64 tokens at a log-decay of -5 reach e^320. So the
exponentials are taken from reference points: a chunk is ``_CHUNK //
_SUB`` sub-chunks of ``_SUB`` tokens; the rows of sub-chunk a are
factored at ``G_a``, the sum through a's MIDDLE token: ``e^{G_t - G_a}``
times ``e^{G_a - G_i}``. The second is <= 1 for every earlier sub-chunk,
and inside a both stay within ``e^{+-_SUB |bound| / 2}``: with a
log-decay no lower than ``bound`` = -5 a token that is e^+-40, so a
small channel of q or k times its factor is still a normal float, and
the masked products above the diagonal stay under e^80 < 3.4e38 (the
configuration's ``kda_lower_bound`` with ``kda_safe_gate`` exists for
this). Where the gate has NO lower bound (``softplus``: Kimi Linear's
published gate) the sub-chunk's own block is taken the GUARDED way:
``e^{G_t - G_i}`` for t >= i directly (the exponent is never positive)
and the sum over channels as multiplies and adds, 16 exponentials a
(token, channel) where the factored form takes 4 and a matrix product;
the other blocks are factored at ``G`` BEFORE a sub-chunk's first token,
so that both factors are <= 1: safe for any decay (a factor that
underflows stands for a product smaller still), nothing clamped, and
slower. Both paths have both forms: the lax form a chunk at a time, the
kernel with the guarded block in vector memory (``own_blocks``).

``kda_gate`` (``ptpu.kda_gate``) makes ``g`` and ``beta`` from the
layer's projections; the L2 norm of q and k (and q's ``dk^-1/2``) is
part of ``kda_scan`` / ``kda_step``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability import KDA_SCAN_TRACES, KDA_STEP_TRACES
from . import attention as _A
from . import kv_cache as _KV
from .registry import register_op

KDA_GATE = "ptpu.kda_gate"
KDA_SCAN = "ptpu.kda_scan"
KDA_STEP = "ptpu.kda_step"

# the gates ``kda_gate`` builds
KDA_GATES = ("lower_bound_sigmoid", "softplus")
# the write strength's range (0, max): with a unit key ``I - beta k k^T``
# has the eigenvalue ``1 - beta``, in (0, 1) under 1 and in (-1, 1) under 2
# (negative eigenvalues: Grazzi et al., arXiv:2411.12537)
KDA_BETA_MAX = (1.0, 2.0)

_CHUNK = 64         # tokens the state is touched once for
_SUB = 16           # tokens factored at one reference point
_BLOCK_CHUNKS = 16  # chunks whose state-free parts are built at once,
_BLOCK_TOKENS = 2048  # ... as long as the batch's rows hold no more tokens
_SAFE_EXP = 80.0    # e^80 < float32's 3.4e38, with a factor of 6e3 spare
_L2_EPS = 1e-6

# positions a grid cell of the kernel walks (whole pairs of chunks), and
# the pairs that one iteration of its state-free loops holds
_KERNEL_BLOCK_T = 256
_KERNEL_GROUP = 2

# the products inside a chunk (A, its inverse, T Diag(beta) [V, K~]): what
# the solve amplifies. Three bfloat16 passes (float32 to ~2^-17): six
# (``HIGHEST``) read the same in the cell's check and cost a fifth more of
# a prefill program's compile time
_INTRA = lax.Precision.HIGH


def kda_gate(f, b, a_log, dt_bias, kind="lower_bound_sigmoid", bound=-5.0,
             beta_max=1.0):
    """f (B, T, H * dk) = u W_f, b (B, T, H) = u W_beta, a_log (H,),
    dt_bias (H * dk,) -> (g (B, T, H, dk) float32 log-decay <= 0, beta
    (B, T, H) float32). ``kind`` "lower_bound_sigmoid": ``g = bound x
    sigmoid(exp(A_log_h) (f + dt_bias))``, in (bound, 0); "softplus":
    ``g = -exp(A_log_h) softplus(f + dt_bias)``, unbounded below.
    ``beta = beta_max sigmoid(b)``, in (0, ``beta_max``): 1, or 2 where
    the transition may have a negative eigenvalue (``KDA_BETA_MAX``)."""
    if kind not in KDA_GATES:
        raise ValueError("kda_gate: gate %r is not built (%s are)"
                         % (kind, ", ".join(KDA_GATES)))
    if float(beta_max) not in KDA_BETA_MAX:
        raise ValueError("kda_gate: a write strength in (0, %r) is not "
                         "built (0 to 1 or 2 are)" % (beta_max,))
    bsz, t, h = b.shape
    with jax.named_scope(KDA_GATE):
        x = (f.astype(jnp.float32) + dt_bias.astype(jnp.float32)).reshape(
            bsz, t, h, -1)
        a = jnp.exp(a_log.astype(jnp.float32))[None, None, :, None]
        if kind == "softplus":
            g = -a * jax.nn.softplus(x)
        else:
            g = jnp.float32(bound) * jax.nn.sigmoid(a * x)
        beta = jax.nn.sigmoid(b.astype(jnp.float32))
        return g, beta if float(beta_max) == 1.0 else float(beta_max) * beta


def _l2(x, scale=1.0):
    """x / |x|_2 over the last axis, times ``scale``."""
    return x * (lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                          + _L2_EPS) * scale)


def _prepare(q, k, qk_norm):
    """float32 q and k as the recurrence takes them: L2-normalised a
    head where ``qk_norm``, q times ``dk^-1/2``."""
    q, k = q.astype(jnp.float32), k.astype(jnp.float32)
    scale = float(q.shape[-1]) ** -0.5
    if qk_norm:
        return _l2(q, scale), _l2(k)
    return q * scale, k


def _update(state, q, k, v, g, beta):
    """One token, exact float32: state (B, H, dk, dv), q, k, g (B, H,
    dk), v (B, H, dv), beta (B, H) -> (o (B, H, dv), new state)."""
    s = state * jnp.exp(g)[..., None]
    # S^T [k, q] from one pass over the decayed state
    sk = jnp.sum(s * k[..., None], axis=-2)
    sq = jnp.sum(s * q[..., None], axis=-2)
    u = beta[..., None] * (v - sk)
    new = s + k[..., None] * u[..., None, :]
    o = sq + jnp.sum(q * k, axis=-1, keepdims=True) * u
    return o, new


def _inv_unit_lower(m):
    """Inverse of unit lower triangles m (..., n, n), n a power of two.
    The diagonal blocks of ``_SUB`` rows by forward substitution, a row
    at a time in float32 multiplies and adds (``X[i] = e_i - sum_{j<i}
    M[i, j] X[j]``); then by halves, ``[[A, 0], [C, D]]^-1 = [[A^-1, 0],
    [-D^-1 C A^-1, D^-1]]``: forward substitution's numbers (no power of
    the strict part is ever formed) in two rounds of batched products
    for a chunk of 64. (By halves from blocks of ONE the same numbers
    took a third of a prefill program's compile time: twelve batched
    products of 1 to 8 rows a layer.)"""
    n = m.shape[-1]
    lead = m.shape[:-2]
    s = min(_SUB, n)

    def diagonal_blocks(size):
        nb = n // size
        return jnp.moveaxis(jnp.diagonal(
            m.reshape(lead + (nb, size, nb, size)), axis1=-4, axis2=-2),
            -1, -3)                                   # (..., nb, size, size)

    blocks = diagonal_blocks(s)
    eye = jnp.eye(s, dtype=m.dtype)
    rows = [jnp.broadcast_to(eye[0], blocks.shape[:-2] + (s,))]
    for i in range(1, s):
        solved = jnp.stack(rows, axis=-2)             # (..., nb, i, s)
        rows.append(eye[i] - jnp.sum(
            blocks[..., i, :i, None] * solved, axis=-2))
    inv = jnp.stack(rows, axis=-2)
    while s < n:
        c_blocks = diagonal_blocks(2 * s)[..., s:, :s]
        a, d = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        c = -jnp.matmul(jnp.matmul(d, c_blocks, precision=_INTRA), a,
                        precision=_INTRA)
        inv = jnp.concatenate(
            [jnp.concatenate([a, jnp.zeros_like(a)], axis=-1),
             jnp.concatenate([c, d], axis=-1)], axis=-2)
        s *= 2
    return inv[..., 0, :, :]


def _decayed_grams(q, k, g_cum, guarded):
    """The two decay-weighted Gram matrices of chunks: q, k, g_cum (B,
    n, C, H, dk), ``g_cum`` the log-decay summed from the chunk's start
    -> (A_qk, A_kk) (B, n, H, C, C), ``A[t, i] = sum_c x_t[c] k_i[c]
    e^{G_t[c] - G_i[c]}`` for i <= t and 0 above the diagonal. Every
    exponential is taken from sub-chunk a's reference point (``G``
    before a's first token); ``guarded`` takes a sub-chunk's own block
    by ``e^{G_t - G_i}`` itself."""
    b, n, c, h, dk = k.shape
    ns = c // _SUB
    sub = (b, n, ns, _SUB, h, dk)
    gs, ks, qs = g_cum.reshape(sub), k.reshape(sub), q.reshape(sub)
    if guarded:
        # G before each sub-chunk's first token: no exponent is positive
        g0 = jnp.concatenate([jnp.zeros_like(gs[:, :, :1, -1]),
                              gs[:, :, :-1, -1]], axis=2)   # (B, n, ns, H, dk)
    else:
        # G at each sub-chunk's MIDDLE token: both factors of a
        # sub-chunk's own block stay within e^(+-_SUB |bound| / 2), so
        # a small channel of q or k times its factor is still a normal
        # float (factored at the first token the last row's e^-80 flushed
        # channels below 1e-3 of the largest to zero)
        g0 = gs[:, :, :, _SUB // 2 - 1]
    e_row = jnp.exp(gs - g0[:, :, :, None])
    # the key side at every row sub-chunk a's reference: (B, n, a, b, i,
    # H, dk); sub-chunks after a (and a itself when guarded) are masked
    diff = g0[:, :, :, None, None] - gs[:, :, None]
    a_id = jnp.arange(ns)[:, None]
    b_id = jnp.arange(ns)[None, :]
    keep = (b_id < a_id) if guarded else (b_id <= a_id)
    e_col = jnp.where(keep[None, None, :, :, None, None, None],
                      jnp.exp(jnp.where(
                          keep[None, None, :, :, None, None, None], diff,
                          0.0)), 0.0)
    k_col = ks[:, :, None] * e_col
    rows = jnp.stack([qs * e_row, ks * e_row], axis=0)      # (2, B, n, a, r, H, dk)
    gram = jnp.einsum("xbnarhc,bnakihc->xbnharki", rows, k_col,
                      precision=_INTRA)
    if guarded:
        # a sub-chunk's own block: exponents G_t - G_i for t >= i
        d_own = gs[:, :, :, :, None] - gs[:, :, :, None, :]  # (B,n,a,t,i,H,dk)
        low = (jnp.arange(_SUB)[:, None] >= jnp.arange(_SUB)[None, :])[
            None, None, None, :, :, None, None]
        e_own = jnp.where(low, jnp.exp(jnp.where(low, d_own, 0.0)), 0.0)
        own = jnp.stack(
            [jnp.sum(x[:, :, :, :, None] * ks[:, :, :, None, :] * e_own,
                     axis=-1) for x in (qs, ks)], axis=0)    # (2,B,n,a,t,i,H)
        own = jnp.moveaxis(own, -1, 3)                       # (2,B,n,H,a,t,i)
        eye = jnp.eye(ns, dtype=own.dtype)[:, None, :, None]  # (a,1,k,1)
        gram = gram + own[:, :, :, :, :, :, None, :] * eye
    gram = gram.reshape(2, b, n, h, c, c)
    t_id = jnp.arange(c)
    a_qk = jnp.where(t_id[:, None] >= t_id[None, :], gram[0], 0.0)
    a_kk = jnp.where(t_id[:, None] > t_id[None, :], gram[1], 0.0)
    return a_qk, a_kk


def _chunks(state, q, k, v, g, beta, guarded):
    """``n`` chunks from ``state``: q, k, g (B, n, C, H, dk), v (B, n,
    C, H, dv), beta (B, n, C, H) -> (o (B, n, C, H, dv), state after
    them). What needs no state is built for all n at once; the state is
    touched by the three products of the loop below, once a chunk."""
    c = q.shape[2]
    g_cum = jnp.cumsum(g, axis=2)
    g_end = g_cum[:, :, -1]                                  # (B, n, H, dk)
    a_qk, a_kk = _decayed_grams(q, k, g_cum, guarded)
    beta_h = jnp.moveaxis(beta, -1, 2)                       # (B, n, H, C)
    t_inv = _inv_unit_lower(
        jnp.eye(c, dtype=jnp.float32) + beta_h[..., :, None] * a_kk)
    t_beta = t_inv * beta_h[..., None, :]                    # T Diag(beta)
    e_cum = jnp.exp(g_cum)
    w = jnp.einsum("bnhts,bnshc->bnhtc", t_beta, k * e_cum,
                   precision=_INTRA)
    u0 = jnp.einsum("bnhts,bnshv->bnhtv", t_beta, v, precision=_INTRA)
    q_dec = jnp.moveaxis(q * e_cum, 3, 2)                    # (B, n, H, C, dk)
    k_end = jnp.moveaxis(k * jnp.exp(g_end[:, :, None] - g_cum), 3, 2)
    d_end = jnp.exp(g_end)

    def body(s, xs):
        w_n, u0_n, q_n, a_n, k_n, d_n = xs
        u = u0_n - jnp.einsum("bhtc,bhcv->bhtv", w_n, s)
        o = (jnp.einsum("bhtc,bhcv->bhtv", q_n, s)
             + jnp.einsum("bhts,bhsv->bhtv", a_n, u))
        s = d_n[..., None] * s + jnp.einsum("bhtc,bhtv->bhcv", k_n, u)
        return s, o

    state, o = lax.scan(body, state, tuple(
        jnp.swapaxes(a, 0, 1) for a in (w, u0, q_dec, a_qk, k_end, d_end)))
    # (n, B, H, C, dv) -> (B, n, C, H, dv)
    return jnp.transpose(o, (1, 0, 3, 2, 4)), state


def _guarded(lower_bound) -> bool:
    """Whether a gate with this bound takes the guarded form: no bound,
    or one under which ``_SUB`` tokens leave float32."""
    return (lower_bound is None
            or _SUB * abs(float(lower_bound)) > _SAFE_EXP)


def _kda_scan_lax(q, k, v, g, beta, lens, guarded, qk_norm):
    """The chunked form as composed lax: the CPU's path, the guarded
    gate's, the kernel's reference and its backward. Shapes as
    ``kda_scan``; lens (B,) int32 -> (o (B, T, H, dv) float32, state)."""
    bsz, t, h, dk = q.shape
    dv = v.shape[-1]
    q, k = _prepare(q, k, qk_norm)
    live = jnp.arange(t, dtype=jnp.int32)[None, :] < lens[:, None]
    g = jnp.where(live[:, :, None, None], g.astype(jnp.float32), 0.0)
    beta = jnp.where(live[:, :, None], beta.astype(jnp.float32), 0.0)
    # the factored form holds 4 (C, H, dk) factors a chunk (0.5 MB a
    # token at the published widths), the guarded form _SUB
    per = _CHUNK * (1 if guarded else max(1, min(
        _BLOCK_CHUNKS, _BLOCK_TOKENS // (_CHUNK * bsz))))
    per = min(per, -(-t // _CHUNK) * _CHUNK)
    pad = (-t) % per
    nblk, n = (t + pad) // per, per // _CHUNK

    def blocks(a):
        a = jnp.pad(a.astype(jnp.float32),
                    ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape((bsz, nblk, n, _CHUNK) + a.shape[2:])
        return jnp.swapaxes(a, 0, 1)                     # block first

    xs = tuple(blocks(a) for a in (q, k, v, g, beta))
    state = jnp.zeros((bsz, h, dk, dv), jnp.float32)

    def body(s, x):
        o, s = _chunks(s, *x, guarded=guarded)
        return s, o

    if nblk == 1:
        state, o = body(state, tuple(a[0] for a in xs))
        o = o[None]
    else:
        state, o = lax.scan(body, state, xs)
    o = jnp.swapaxes(o, 0, 1).reshape(bsz, t + pad, h, dv)[:, :t]
    return o, state


def _split(x):
    """float32 -> its two leading bfloat16 parts."""
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _mm(a, b, dims, passes):
    """float32 ``a`` times float32 ``b`` contracting ``dims`` (one axis
    of each), accumulated in float32, in ``passes`` bfloat16 passes of
    the matrix unit: 1 (operands rounded to bfloat16: what XLA gives a
    float32 product on a TPU by default), 3 (``Precision.HIGH``'s: both
    operands in two parts, the low x low product dropped; written out,
    since Mosaic lowers only DEFAULT and HIGHEST: the two parts of ``a``
    stacked against the high part of ``b`` in one product, its high
    part against the low part of ``b`` in another) or 6 (``HIGHEST``)."""
    dn = (dims, ((), ()))
    f32 = jnp.float32
    if passes == 6:
        return lax.dot_general(a, b, dn, precision=lax.Precision.HIGHEST,
                               preferred_element_type=f32)
    if passes == 1:
        return lax.dot_general(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                               dn, preferred_element_type=f32)
    (a_hi, a_lo), (b_hi, b_lo) = _split(a), _split(b)
    free = 1 - dims[0][0]               # the axis of ``a`` that stays
    both = lax.dot_general(jnp.concatenate([a_hi, a_lo], axis=free), b_hi,
                           dn, preferred_element_type=f32)
    n = a.shape[free]
    return (both[:n] + both[n:]) + lax.dot_general(
        a_hi, b_lo, dn, preferred_element_type=f32)


def _cumsum_rows(x, period):
    """Running sum down the rows of (n, lanes), started again every
    ``period`` rows (a power of two), in float32 adds: log2(period)
    shifted adds."""
    row = lax.broadcasted_iota(jnp.int32, (x.shape[0], 1), 0) % period
    s = 1
    while s < period:
        x = x + jnp.where(row >= s, pltpu.roll(x, s, 0), 0.0)
        s *= 2
    return x


def _kda_scan_kernel(len_ref, q_ref, k_ref, v_ref, g_ref, b_ref, o_ref,
                     st_ref, s_ref, qd_ref, kd_ref, ke_ref, vb_ref, aqk_ref,
                     am_ref, mb_ref, xb_ref, de_ref, *, block_t, n_t, qk_norm,
                     intra, state, group, guarded=False):
    """One (row, head, block of positions) grid cell, the last axis
    sequential. q_ref, k_ref, g_ref (1, Tb, dk), v_ref, o_ref (1, Tb,
    dv): a head's lanes of the (B, T, H * d) arrays where they lie;
    b_ref (1, Tb, H) every head's beta; st_ref (1, 1, dk, dv) the
    state's block, written at the row's last cell; s_ref (dk, dv) the
    state, which lives in vector memory from the row's first block to
    its last. The other scratches hold a block's state-free parts
    between the passes below; nothing of them goes to HBM.

    The module doc's algebra in four passes over the block's LIVE
    chunks, two chunks (a PAIR) at a time and ``group`` pairs a loop
    iteration. A chunk's 64 x 64 matrices fill half a register's lanes
    and a quarter of the matrix unit, so a pair's lie SIDE BY SIDE,
    ``[X_0 | X_1]`` (64, 128), and a product with both is one product
    with the second operand block-diagonal: ``[X_0 | X_1] diag(Y_0,
    Y_1) = [X_0 Y_0 | X_1 Y_1]``, the same numbers (a zero contributes
    nothing) in half the operations. A chunk of the last pair past the
    row's length, like a position past it inside a live chunk, gets g =
    0 and beta = 0: it decays nothing and writes nothing.

    1, state-free: the L2 norms, the running sum of g, the sub-chunk
       factors, the two Grams (the 2 x 32 rows of q and k of a pair's
       a-th sub-chunks against the keys of the sub-chunks up to their
       own, four products a pair), ``Diag(beta) A`` and its diagonal
       blocks. ``guarded`` (a gate with no lower bound): the factors
       are taken at ``G`` BEFORE a sub-chunk's first token, so that no
       exponent is positive, the products run against the keys of the
       sub-chunks before their own (three a pair), and a sub-chunk's
       own block is ``own_blocks``: ``e^{G_t - G_i}`` itself;
    2, ONCE for the block: forward substitution on all its ``Tb / 16``
       diagonal blocks together, row i of every block a strided read:
       fifteen dependent steps on a few full registers where a block at
       a time would be fifteen steps on a sixteenth of one;
    3, state-free: the inverse by halves as products of the whole 64 x
       64 with the other blocks masked to zero, then ``T Diag(beta) [V,
       K~]``;
    4, the three products that touch the state, a chunk after a
       chunk."""
    f32 = jnp.float32
    c_, s_ = _CHUNK, _SUB
    ns = c_ // s_
    dk, dv = q_ref.shape[-1], v_ref.shape[-1]
    head = pl.program_id(1)
    ti = pl.program_id(2)
    n_live = jnp.clip(len_ref[pl.program_id(0)] - ti * block_t, 0, block_t)
    n_groups = (n_live + 2 * group * c_ - 1) // (2 * group * c_)

    @pl.when(ti == 0)
    def _():
        s_ref[...] = jnp.zeros(s_ref.shape, f32)

    @pl.when(n_live < block_t)
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    # a pair's (64, 128): the row, the column inside its own chunk, and
    # which chunk of the two
    tr = lax.broadcasted_iota(jnp.int32, (c_, 2 * c_), 0)
    lane = lax.broadcasted_iota(jnp.int32, (c_, 2 * c_), 1)
    tc, left = lane % c_, lane < c_
    is_head = lax.broadcasted_iota(
        jnp.int32, (2 * c_, b_ref.shape[-1]), 1) == head

    def norm(x, scale):
        if not qk_norm:
            return x * scale if scale != 1.0 else x
        return x * (lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                              + _L2_EPS) * scale)

    def side_by_side(x_0, x_1):
        # a mask of its own shape: Mosaic does not slice a mask
        shape = (x_0.shape[0], 2 * c_)
        return jnp.where(lax.broadcasted_iota(jnp.int32, shape, 1) < c_,
                         x_0, x_1)

    def block_diagonal(x):
        """[X_0 | X_1] (64, 128) -> diag(X_0, X_1) (128, 128)."""
        return jnp.concatenate([jnp.where(left, x, 0.0),
                                jnp.where(left, 0.0, x)], axis=0)

    def live_rows(base, n):
        return base + lax.broadcasted_iota(jnp.int32, (n, 1), 0) < n_live

    def pair_rows(p):
        """A pair's rows in the (Tb, .) arrays and in the side-by-side
        ones."""
        return (pl.ds(pl.multiple_of(p * 2 * c_, 2 * c_), 2 * c_),
                pl.ds(pl.multiple_of(p * c_, c_), c_))

    def own_blocks(q, k, g_cum):
        """The guarded form's diagonal blocks of a pair, (A_qk, A_kk)
        side by side: ``sum_c x_t[c] k_i[c] e^{G_t[c] - G_i[c]}`` for i
        <= t of one sub-chunk, the exponent taken as it is (never
        positive), the sum over channels in float32 multiplies and adds.
        Diagonal d of every block at once: the rows d tokens back come
        by a roll down the sublanes (a row whose partner lies in the
        sub-chunk before is masked), so a (token, channel) costs ``_SUB
        - 1`` exponentials and no product on the matrix unit."""
        sub_row = lax.broadcasted_iota(jnp.int32, (2 * c_, 1), 0) % s_
        own = [jnp.zeros((c_, 2 * c_), f32)] * 2
        for d in range(s_):
            if d:
                seen = sub_row >= d
                k_back = jnp.where(seen, pltpu.roll(k, d, 0) * jnp.exp(
                    jnp.where(seen, g_cum - pltpu.roll(g_cum, d, 0), 0.0)),
                    0.0)
            else:
                k_back = k
            on_diagonal = tr - tc == d
            for i, x in enumerate((q, k)):
                col = jnp.sum(x * k_back, axis=-1, keepdims=True)  # (2 C, 1)
                own[i] = jnp.where(
                    on_diagonal, side_by_side(col[:c_], col[c_:]), own[i])
        return own

    def state_free(p):
        rows, half = pair_rows(p)
        live = live_rows(p * 2 * c_, 2 * c_)
        q = norm(q_ref[0, rows, :], float(dk) ** -0.5)
        k = norm(k_ref[0, rows, :], 1.0)
        g = jnp.where(live, g_ref[0, rows, :], 0.0)
        beta = jnp.where(live, jnp.sum(
            jnp.where(is_head, b_ref[0, rows, :], 0.0), axis=-1,
            keepdims=True), 0.0)                              # (2 C, 1)
        g_cum = _cumsum_rows(g, c_)
        g_end = jnp.concatenate(
            [jnp.broadcast_to(g_cum[(j + 1) * c_ - 1:(j + 1) * c_], (c_, dk))
             for j in range(2)], axis=0)
        if guarded:
            # G before each sub-chunk's first token: nothing at a chunk's
            mid = [jnp.zeros((1, dk), f32) if a % ns == 0
                   else g_cum[a * s_ - 1:a * s_] for a in range(2 * ns)]
        else:
            # G at each sub-chunk's middle token (module doc)
            mid = [g_cum[a * s_ + s_ // 2 - 1:a * s_ + s_ // 2]
                   for a in range(2 * ns)]
        e_row = jnp.exp(g_cum - jnp.concatenate(
            [jnp.broadcast_to(m, (s_, dk)) for m in mid], axis=0))
        qe, ke = q * e_row, k * e_row
        a_qk, a_kk = [], []
        for a in range(ns):
            # the keys a sub-chunk's rows are multiplied with: up to its
            # own, or (guarded) before its own
            n = a * s_ if guarded else (a + 1) * s_
            if not n:
                a_qk.append(jnp.zeros((s_, 2 * c_), f32))
                a_kk.append(jnp.zeros((s_, 2 * c_), f32))
                continue
            x, k_col = [], []
            for j in range(2):          # the pair's two chunks
                at = j * c_
                x += [qe[at + a * s_:at + (a + 1) * s_],
                      ke[at + a * s_:at + (a + 1) * s_]]
                k_col.append(k[at:at + n] * jnp.exp(
                    mid[j * ns + a] - g_cum[at:at + n]))
                if n < c_:
                    k_col.append(jnp.zeros((c_ - n, dk), f32))
            gram = _mm(jnp.concatenate(x, axis=0),
                       jnp.concatenate(k_col, axis=0), ((1,), (1,)), intra)
            a_qk.append(side_by_side(gram[:s_], gram[2 * s_:3 * s_]))
            a_kk.append(side_by_side(gram[s_:2 * s_], gram[3 * s_:]))
        if guarded:
            own = own_blocks(q, k, g_cum)
            a_qk = [jnp.concatenate(a_qk, axis=0) + own[0]]
            a_kk = [jnp.concatenate(a_kk, axis=0) + own[1]]
        m = (side_by_side(beta[:c_], beta[c_:])
             * jnp.where(tr > tc, jnp.concatenate(a_kk, axis=0), 0.0))
        aqk_ref[half, :] = jnp.where(tr >= tc, jnp.concatenate(a_qk, axis=0),
                                     0.0)
        am_ref[half, :] = m                               # Diag(beta) A
        mb_ref[rows, :] = jnp.concatenate(
            [m[a * s_:(a + 1) * s_, j * c_ + a * s_:j * c_ + (a + 1) * s_]
             for j in range(2) for a in range(ns)], axis=0)
        e_cum = jnp.exp(g_cum)
        qd_ref[rows, :] = q * e_cum
        kd_ref[rows, :] = beta * (k * e_cum)
        ke_ref[rows, :] = k * jnp.exp(g_end - g_cum)
        vb_ref[rows, :] = beta * v_ref[0, rows, :]
        # e^(G_C) a key channel, as the state's rows want it: down the
        # sublanes, the same along the lanes
        for j in range(2):
            at = pl.multiple_of((2 * p + j) * dk, dk)
            de_ref[pl.ds(at, dk), :] = jnp.exp(jnp.broadcast_to(
                g_cum[(j + 1) * c_ - 1:(j + 1) * c_], (dk, dk))).T

    def substitute():
        # X[i] = e_i - sum_{j<i} M[i, j] X[j], every diagonal block of
        # the grid cell at once: a block a sublane, the row's 16
        # numbers along the lanes
        nb = block_t // s_
        col = lax.broadcasted_iota(jnp.int32, (nb, s_), 1)
        solved = []
        for i in range(s_):
            m_row = mb_ref[pl.ds(i, nb, stride=s_), :]
            x = (col == i).astype(f32)
            for j in range(i):
                x = x - m_row[:, j:j + 1] * solved[j]
            solved.append(x)
            xb_ref[pl.ds(i, nb, stride=s_), :] = x

    def invert(p):
        rows, half = pair_rows(p)
        m = am_ref[half, :]
        xb = xb_ref[rows, :]
        t_inv = jnp.where(tr // s_ == tc // s_, jnp.concatenate(
            [xb[:c_]] * ns + [xb[c_:]] * ns, axis=1), 0.0)
        size = s_
        while size < c_:
            # [[A, 0], [C, D]]^-1 = [[A^-1, 0], [-D^-1 C A^-1, D^-1]] for
            # every pair of diagonal blocks of ``size`` at once
            pair_r, pair_c = tr // size, tc // size
            low = jnp.where((pair_r == pair_c + 1) & (pair_r % 2 == 1), m, 0.0)
            t_inv = t_inv - _mm(
                _mm(t_inv, block_diagonal(low), ((1,), (0,)), intra),
                block_diagonal(t_inv), ((1,), (0,)), intra)
            size *= 2
        uw = _mm(block_diagonal(t_inv), jnp.concatenate(
            [vb_ref[rows, :], kd_ref[rows, :]], axis=1), ((1,), (0,)), intra)
        vb_ref[rows, :] = uw[:, :dv]     # T Diag(beta) V
        kd_ref[rows, :] = uw[:, dv:]     # T Diag(beta) K~

    def advance(p):
        half = pair_rows(p)[1]
        for j in range(2):
            rows = pl.ds(pl.multiple_of((2 * p + j) * c_, c_), c_)
            s = s_ref[...]
            wq = _mm(jnp.concatenate([kd_ref[rows, :], qd_ref[rows, :]],
                                     axis=0), s, ((1,), (0,)), state)
            u = vb_ref[rows, :] - wq[:c_]
            o = wq[c_:] + _mm(aqk_ref[half, j * c_:(j + 1) * c_], u,
                              ((1,), (0,)), state)
            o_ref[0, rows, :] = jnp.where(
                live_rows((2 * p + j) * c_, c_), o, 0.0).astype(o_ref.dtype)
            d_end = de_ref[pl.ds(pl.multiple_of((2 * p + j) * dk, dk), dk), :]
            if dv != dk:
                d_end = d_end[:, :1]
            s_ref[...] = d_end * s + _mm(ke_ref[rows, :], u, ((0,), (0,)),
                                         state)

    def grouped(fn):
        def body(i, carry):
            for j in range(group):
                fn(i * group + j)
            return carry
        lax.fori_loop(0, n_groups, body, 0)

    @pl.when(n_live > 0)
    def _():
        grouped(state_free)
        substitute()
        grouped(invert)
        lax.fori_loop(0, (n_live + 2 * c_ - 1) // (2 * c_),
                      lambda p, carry: advance(p) or carry, 0)

    @pl.when(ti == n_t - 1)
    def _():
        st_ref[0, 0] = s_ref[...]


def _kernel_block(t, dk, dv, block_t=_KERNEL_BLOCK_T):
    """Positions a block of the kernel for a (B, t, H, dk) scan into
    (dk, dv) states, or None where the lax form runs: a sequence that is
    not whole blocks, a head that does not fill whole 128-lane vectors."""
    if t < block_t or t % block_t or dk % 128 or dv % 128:
        return None
    return block_t


def _use_kernel(t, dk, dv) -> bool:
    """A step bound for a TPU (PADDLE_TPU_NO_PALLAS opts out, as for
    every kernel: ``kv_cache._use_pallas_decode``) and a shape the
    kernel takes (``_kernel_block``), whatever the gate: the kernel has
    both forms."""
    return (_kernel_block(t, dk, dv) is not None
            and _KV._use_pallas_decode(t, dk))


def pallas_kda_scan(q, k, v, g, beta, lens, qk_norm=True,
                    block_t=_KERNEL_BLOCK_T, intra=3, state=1,
                    group=_KERNEL_GROUP, interpret=False, guarded=False):
    """``_kda_scan_lax``'s contract (the factored form, or the
    ``guarded`` one) through the kernel: ONE call, the operands where
    they lie, a head a block of lanes of the (B, T, H * d) views.
    ``lens`` is a scalar-prefetch
    operand: a block of positions wholly past a row's length is neither
    fetched (its index waits at the row's last live block) nor computed.
    ``intra`` / ``state``: bfloat16 passes of the products inside a
    chunk (``_INTRA``'s three) and of the three that touch the state
    (XLA's default for float32: one); 6 is float32 all through, the
    tests' way to hold the kernel to the recurrence."""
    bsz, t, h, dk = q.shape
    dv = v.shape[-1]
    block_t = _kernel_block(t, dk, dv, block_t)
    if block_t is None:
        raise ValueError(
            "no kernel for a (%d, %d, %d, %d) scan into (%d, %d) states; "
            "the lax form runs it" % (bsz, t, h, dk, dk, dv))
    n_t = t // block_t
    f32 = jnp.float32

    def last(bi, lens_ref):
        return jnp.maximum(lens_ref[bi] + block_t - 1, block_t) // block_t - 1

    def head_block(bi, hi, ti, lens_ref):
        # past the row's last live block: the same block again
        return bi, jnp.minimum(ti, last(bi, lens_ref)), hi

    def beta_block(bi, hi, ti, lens_ref):
        return bi, jnp.minimum(ti, last(bi, lens_ref)), 0

    kernel = functools.partial(
        _kda_scan_kernel, block_t=block_t, n_t=n_t, qk_norm=bool(qk_norm),
        intra=intra, state=state, group=group, guarded=bool(guarded))
    o, st = _A.named_pallas_call(
        KDA_SCAN, kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz, h, n_t),
            in_specs=[
                pl.BlockSpec((1, block_t, dk), head_block),
                pl.BlockSpec((1, block_t, dk), head_block),
                pl.BlockSpec((1, block_t, dv), head_block),
                pl.BlockSpec((1, block_t, dk), head_block),
                pl.BlockSpec((1, block_t, h), beta_block),
            ],
            out_specs=[
                pl.BlockSpec((1, block_t, dv),
                             lambda bi, hi, ti, lens_ref: (bi, ti, hi)),
                pl.BlockSpec((1, 1, dk, dv),
                             lambda bi, hi, ti, lens_ref: (bi, hi, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((dk, dv), f32),           # the state
                pltpu.VMEM((block_t, dk), f32),      # q e^G
                pltpu.VMEM((block_t, dk), f32),      # beta k e^G
                pltpu.VMEM((block_t, dk), f32),      # k e^(G_C - G)
                pltpu.VMEM((block_t, dv), f32),      # beta v
                pltpu.VMEM((block_t // 2, 2 * _CHUNK), f32),  # A_qk, pairs
                pltpu.VMEM((block_t // 2, 2 * _CHUNK), f32),  # Diag(beta) A
                pltpu.VMEM((block_t, _SUB), f32),    # its diagonal blocks
                pltpu.VMEM((block_t, _SUB), f32),    # ... inverted
                pltpu.VMEM((block_t // _CHUNK * dk, dk), f32),  # e^(G_C)
            ]),
        out_shape=[jax.ShapeDtypeStruct((bsz, t, h * dv), f32),
                   jax.ShapeDtypeStruct((bsz, h, dk, dv), f32)],
        interpret=interpret,
        **_A._tpu_params("parallel", "parallel", "arbitrary"),
    )(jnp.clip(lens, 0, t), q.astype(f32).reshape(bsz, t, h * dk),
      k.astype(f32).reshape(bsz, t, h * dk),
      v.astype(f32).reshape(bsz, t, h * dv),
      g.astype(f32).reshape(bsz, t, h * dk), beta.astype(f32))
    return o.reshape(bsz, t, h, dv), st


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _kda_scan_kernel_path(q, k, v, g, beta, lens, qk_norm, interpret,
                          guarded):
    return pallas_kda_scan(q, k, v, g, beta, lens, qk_norm,
                           interpret=interpret, guarded=guarded)


def _kernel_path_fwd(q, k, v, g, beta, lens, qk_norm, interpret, guarded):
    out = pallas_kda_scan(q, k, v, g, beta, lens, qk_norm,
                          interpret=interpret, guarded=guarded)
    return out, (q, k, v, g, beta, lens)


def _kernel_path_bwd(qk_norm, interpret, guarded, res, cts):
    # no cell trains through a scan: the backward is the lax form's
    *operands, lens = res
    _, vjp = jax.vjp(
        lambda *ops: _kda_scan_lax(*ops, lens, guarded, qk_norm), *operands)
    return (*vjp(cts), None)


_kda_scan_kernel_path.defvjp(_kernel_path_fwd, _kernel_path_bwd)


def kda_scan(q, k, v, g, beta, lengths=None, lower_bound=None, qk_norm=True,
             interpret=False):
    """The CHUNKED delta rule from a zero state over padded sequences:
    q, k, g (B, T, H, dk), v (B, T, H, dv), beta (B, T, H), lengths (B,)
    real tokens a row (None: all T) -> (o (B, T, H, dv), state (B, H,
    dk, dv) after each row's LAST REAL token: a position at or past a
    row's length decays nothing and writes nothing; its o is finite and
    meaningless). ``lower_bound``: the least log-decay a token's ``g``
    can hold (the gate's bound), or None where it has none: the
    factored form runs only where ``_SUB`` tokens at the bound stay
    inside float32, the guarded form otherwise (module doc). The kernel
    where ``_use_kernel`` says so (``interpret``: the kernel in
    interpret mode, whatever the device: the tests' way in), else the
    lax form."""
    bsz, t, h, dk = q.shape
    dv = v.shape[-1]
    lens = (jnp.full((bsz,), t, jnp.int32) if lengths is None
            else lengths.reshape(-1).astype(jnp.int32))
    kernel = interpret or _use_kernel(t, dk, dv)
    guarded = _guarded(lower_bound)
    KDA_SCAN_TRACES.inc(path="kernel" if kernel else "lax",
                        form="guarded" if guarded else "factored")
    with jax.named_scope(KDA_SCAN):
        if kernel:
            o, state = _kda_scan_kernel_path(q, k, v, g, beta, lens,
                                             bool(qk_norm), interpret,
                                             guarded)
        else:
            o, state = _kda_scan_lax(q, k, v, g, beta, lens, guarded,
                                     qk_norm)
        return o.astype(v.dtype), state

# bytes of matrix states a grid cell of the step's kernel holds: the block
# comes in and goes out double-buffered, four of it beside the compiler's
# default 16 MiB of scoped vector memory
_STEP_BLOCK_BYTES = 2 * 2**20


def _step_heads(h, dk, dv):
    """Heads a grid cell of the step's kernel takes: the most that
    divide ``h``, fill whole sublane tiles of the (B, H, d) operands'
    blocks (a multiple of 8, or all of them) and whose states are no
    more than ``_STEP_BLOCK_BYTES``; None where no such block is."""
    fits = [n for n in range(1, h + 1)
            if h % n == 0 and (n % 8 == 0 or n == h)
            and n * dk * dv * 4 <= _STEP_BLOCK_BYTES]
    return max(fits) if fits else None


def _use_step_kernel(h, dk, dv, dtype) -> bool:
    """A step bound for a TPU (the rule every kernel shares,
    ``kv_cache._use_pallas_decode``: PADDLE_TPU_NO_PALLAS opts out), a
    float32 state of whole 128 x 128 tiles and a block of heads that
    fits (``_step_heads``)."""
    return (jnp.dtype(dtype) == jnp.float32
            and _step_heads(h, dk, dv) is not None
            and _KV._use_pallas_decode(dk, dv))


def _kda_step_kernel(s_ref, q_ref, k_ref, g_ref, v_ref, b_ref, o_ref,
                     so_ref):
    """One (slot, block of heads) grid cell: s_ref, so_ref (1, hb, dk,
    dv) the states' block, which comes in once and goes out once over
    itself; q_ref, k_ref, g_ref (1, hb, dk), v_ref, o_ref (1, hb, dv),
    b_ref (1, hb, 1). ``_update``'s float32 multiplies and adds a head
    at a time; only the sums over ``dk`` run in another order. A state's
    rows are its key channels (down the sublanes), so q, k and the decay
    of a head are wanted as COLUMNS, each the same along the lanes: the
    block's 3 x hb rows are transposed once, and a head's column is one
    lane of the result, broadcast. (That costs nothing on the chip: the
    kernel runs as fast with no column at all, at what an in-place
    stream of the states reaches: ``tools/kda_step_probe.py``.)"""
    hb, dk, dv = s_ref.shape[1:]
    q, k, decay = q_ref[0], k_ref[0], jnp.exp(g_ref[0])
    qk = jnp.sum(q * k, axis=-1, keepdims=True)               # (hb, 1)
    turned = jnp.concatenate(
        [q, k, decay, jnp.zeros((128 - 3 * hb, dk), jnp.float32)],
        axis=0).T                                             # (dk, 128)

    def column(j):
        return jnp.broadcast_to(turned[:, j:j + 1], (dk, dv))

    for h in range(hb):
        k_c = column(hb + h)
        s = s_ref[0, h] * column(2 * hb + h)
        sk = jnp.sum(s * k_c, axis=0, keepdims=True)          # (1, dv)
        sq = jnp.sum(s * column(h), axis=0, keepdims=True)
        u = b_ref[0, h:h + 1, :] * (v_ref[0, h:h + 1, :] - sk)
        so_ref[0, h] = s + k_c * u
        o_ref[0, h:h + 1, :] = sq + qk[h:h + 1] * u


def pallas_kda_step(state, q, k, v, g, beta, heads=None, interpret=False):
    """``_update``'s contract through the kernel: ONE call, a block of
    ``heads`` heads of one slot a grid cell (``_step_heads``), the state
    operand aliased to the state result: no second copy of the states
    exists, in the call or around it."""
    bsz, h, dk, dv = state.shape
    hb = _step_heads(h, dk, dv) if heads is None else heads
    if hb is None or dk % 128 or dv % 128:
        raise ValueError(
            "no kernel for a step of (%d, %d, %d, %d) states; the lax form "
            "runs it" % (bsz, h, dk, dv))
    f32 = jnp.float32

    def block(*tail):
        return pl.BlockSpec((1, hb) + tail,
                            lambda bi, hi: (bi, hi) + (0,) * len(tail))

    return _A.named_pallas_call(
        KDA_STEP, _kda_step_kernel,
        grid=(bsz, h // hb),
        in_specs=[block(dk, dv), block(dk), block(dk), block(dk), block(dv),
                  block(1)],
        out_specs=[block(dv), block(dk, dv)],
        out_shape=[jax.ShapeDtypeStruct((bsz, h, dv), f32),
                   jax.ShapeDtypeStruct((bsz, h, dk, dv), f32)],
        input_output_aliases={0: 1},
        interpret=interpret,
        **_A._tpu_params("parallel", "parallel"),
    )(state, q, k, g, v, beta[..., None])


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _kda_step_kernel_path(state, q, k, v, g, beta, interpret):
    return pallas_kda_step(state, q, k, v, g, beta, interpret=interpret)


def _step_path_fwd(state, q, k, v, g, beta, interpret):
    operands = (state, q, k, v, g, beta)
    return pallas_kda_step(*operands, interpret=interpret), operands


def _step_path_bwd(interpret, operands, cts):
    # a gradient through a step is the lax form's
    return jax.vjp(_update, *operands)[1](cts)


_kda_step_kernel_path.defvjp(_step_path_fwd, _step_path_bwd)


def kda_step(q, k, v, g, beta, state, qk_norm=True, interpret=False):
    """One token: q, k, g (B, 1, H, dk) or (B, H, dk), v (B, 1, H, dv),
    beta (B, 1, H), state (B, H, dk, dv) -> (o shaped as v, new state):
    ``S <- alpha S; S <- S + beta k (v - S^T k)^T; o = S^T q``. The
    kernel where ``_use_step_kernel`` says so (``interpret``: the kernel
    in interpret mode, whatever the device: the tests' way in), else
    ``_update``."""
    bsz, h, dk, dv = state.shape
    kernel = interpret or _use_step_kernel(h, dk, dv, state.dtype)
    KDA_STEP_TRACES.inc(path="kernel" if kernel else "lax")
    with jax.named_scope(KDA_STEP):
        q, k = _prepare(q.reshape(bsz, h, dk), k.reshape(bsz, h, dk),
                        qk_norm)
        operands = (state.astype(jnp.float32), q, k,
                    v.reshape(bsz, h, dv).astype(jnp.float32),
                    g.reshape(bsz, h, dk).astype(jnp.float32),
                    beta.reshape(bsz, h).astype(jnp.float32))
        if kernel:
            o, new = _kda_step_kernel_path(*operands, interpret)
        else:
            o, new = _update(*operands)
        return o.reshape(v.shape).astype(v.dtype), new.astype(state.dtype)


def _bound_attr(ctx):
    bound = ctx.attr("lower_bound", None)
    return None if bound is None else float(bound)


@register_op("kda_gate")
def _kda_gate_op(ctx):
    """Inputs F (B, T, H * dk), B (B, T, H), ALog (H,), DtBias (H * dk,).
    Attrs kind, bound, beta_max (absent: 1) -> G (B, T, H, dk), Beta
    (B, T, H)."""
    g, beta = kda_gate(ctx.input("F"), ctx.input("B"), ctx.input("ALog"),
                       ctx.input("DtBias"),
                       str(ctx.attr("kind", "lower_bound_sigmoid")),
                       float(ctx.attr("bound", -5.0)),
                       float(ctx.attr("beta_max", 1.0)))
    return {"G": g, "Beta": beta}


@register_op("kda_scan")
def _kda_scan_op(ctx):
    """Inputs Q, K, G (B, T, H, dk), V (B, T, H, dv), Beta (B, T, H),
    optional Lengths (B,). Attrs lower_bound (absent: none), qk_norm ->
    Out (B, T, H, dv), State (B, H, dk, dv) at each row's length."""
    o, state = kda_scan(ctx.input("Q"), ctx.input("K"), ctx.input("V"),
                        ctx.input("G"), ctx.input("Beta"),
                        ctx.input("Lengths"), _bound_attr(ctx),
                        bool(ctx.attr("qk_norm", True)))
    return {"Out": o, "State": state}


@register_op("kda_step")
def _kda_step_op(ctx):
    """Inputs Q, K, G (B, 1, H, dk), V (B, 1, H, dv), Beta (B, 1, H),
    State (B, H, dk, dv). Attr qk_norm -> Out (B, 1, H, dv), StateOut."""
    o, state = kda_step(ctx.input("Q"), ctx.input("K"), ctx.input("V"),
                        ctx.input("G"), ctx.input("Beta"),
                        ctx.input("State"), bool(ctx.attr("qk_norm", True)))
    return {"Out": o, "StateOut": state}
