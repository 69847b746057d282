"""Kimi Delta Attention (KDA; Kimi Linear, arXiv:2510.26692, section 3):
a linear-attention layer whose memory is ONE matrix a head, written by a
delta rule under a decay a CHANNEL. A head keeps ``S`` (dk, dv); a token
with key ``k`` (a unit vector), value ``v``, write strength ``beta`` in
(0, 1) and log-decay ``g`` <= 0 a key channel (``alpha = exp(g)``) does

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
- one token (``kda_step``, ``ptpu.kda_step``: a decode step's; the state
  read once for the two products ``S^T [k, q]`` and once more for its
  update, written once, all in float32 multiplies and adds: a
  contraction would round the state to bfloat16 on a TPU);
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
  state, so they are built for ``_BLOCK_CHUNKS`` chunks at once; the
  state is touched once a chunk, by three matrix products.

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
(token, channel) where the factored form takes 4 and a matrix product,
a chunk at a time: safe for any decay, and slower.

``kda_gate`` (``ptpu.kda_gate``) makes ``g`` and ``beta`` from the
layer's projections; the L2 norm of q and k (and q's ``dk^-1/2``) is
part of ``kda_scan`` / ``kda_step``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register_op

KDA_GATE = "ptpu.kda_gate"
KDA_SCAN = "ptpu.kda_scan"
KDA_STEP = "ptpu.kda_step"

# the gates ``kda_gate`` builds
KDA_GATES = ("lower_bound_sigmoid", "softplus")

_CHUNK = 64         # tokens the state is touched once for
_SUB = 16           # tokens factored at one reference point
_BLOCK_CHUNKS = 16  # chunks whose state-free parts are built at once,
_BLOCK_TOKENS = 2048  # ... as long as the batch's rows hold no more tokens
_SAFE_EXP = 80.0    # e^80 < float32's 3.4e38, with a factor of 6e3 spare
_L2_EPS = 1e-6

# the products inside a chunk (A, its inverse, T Diag(beta) [V, K~]): what
# the solve amplifies. Three bfloat16 passes (float32 to ~2^-17): six
# (``HIGHEST``) read the same in the cell's check and cost a fifth more of
# a prefill program's compile time
_INTRA = lax.Precision.HIGH


def kda_gate(f, b, a_log, dt_bias, kind="lower_bound_sigmoid", bound=-5.0):
    """f (B, T, H * dk) = u W_f, b (B, T, H) = u W_beta, a_log (H,),
    dt_bias (H * dk,) -> (g (B, T, H, dk) float32 log-decay <= 0, beta
    (B, T, H) float32). ``kind`` "lower_bound_sigmoid": ``g = bound x
    sigmoid(exp(A_log_h) (f + dt_bias))``, in (bound, 0); "softplus":
    ``g = -exp(A_log_h) softplus(f + dt_bias)``, unbounded below."""
    if kind not in KDA_GATES:
        raise ValueError("kda_gate: gate %r is not built (%s are)"
                         % (kind, ", ".join(KDA_GATES)))
    bsz, t, h = b.shape
    with jax.named_scope(KDA_GATE):
        x = (f.astype(jnp.float32) + dt_bias.astype(jnp.float32)).reshape(
            bsz, t, h, -1)
        a = jnp.exp(a_log.astype(jnp.float32))[None, None, :, None]
        if kind == "softplus":
            g = -a * jax.nn.softplus(x)
        else:
            g = jnp.float32(bound) * jax.nn.sigmoid(a * x)
        return g, jax.nn.sigmoid(b.astype(jnp.float32))


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


def kda_scan(q, k, v, g, beta, lengths=None, lower_bound=None, qk_norm=True):
    """The CHUNKED delta rule from a zero state over padded sequences:
    q, k, g (B, T, H, dk), v (B, T, H, dv), beta (B, T, H), lengths (B,)
    real tokens a row (None: all T) -> (o (B, T, H, dv), state (B, H,
    dk, dv) after each row's LAST REAL token: a position at or past a
    row's length decays nothing and writes nothing; its o is finite and
    meaningless). ``lower_bound``: the least log-decay a token's ``g``
    can hold (the gate's bound), or None where it has none: the
    factored form runs only where ``_SUB`` tokens at the bound stay
    inside float32, the guarded form otherwise (module doc)."""
    bsz, t, h, dk = q.shape
    dv = v.shape[-1]
    guarded = (lower_bound is None
               or _SUB * abs(float(lower_bound)) > _SAFE_EXP)
    lens = (jnp.full((bsz,), t, jnp.int32) if lengths is None
            else lengths.reshape(-1).astype(jnp.int32))
    with jax.named_scope(KDA_SCAN):
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
        return o.astype(v.dtype), state


def kda_step(q, k, v, g, beta, state, qk_norm=True):
    """One token: q, k, g (B, 1, H, dk) or (B, H, dk), v (B, 1, H, dv),
    beta (B, 1, H), state (B, H, dk, dv) -> (o shaped as v, new state):
    ``S <- alpha S; S <- S + beta k (v - S^T k)^T; o = S^T q``."""
    bsz, h, dk, dv = state.shape
    with jax.named_scope(KDA_STEP):
        q, k = _prepare(q.reshape(bsz, h, dk), k.reshape(bsz, h, dk),
                        qk_norm)
        o, new = _update(
            state.astype(jnp.float32), q, k,
            v.reshape(bsz, h, dv).astype(jnp.float32),
            g.reshape(bsz, h, dk).astype(jnp.float32),
            beta.reshape(bsz, h).astype(jnp.float32))
        return o.reshape(v.shape).astype(v.dtype), new.astype(state.dtype)


def _bound_attr(ctx):
    bound = ctx.attr("lower_bound", None)
    return None if bound is None else float(bound)


@register_op("kda_gate")
def _kda_gate_op(ctx):
    """Inputs F (B, T, H * dk), B (B, T, H), ALog (H,), DtBias (H * dk,).
    Attrs kind, bound -> G (B, T, H, dk), Beta (B, T, H)."""
    g, beta = kda_gate(ctx.input("F"), ctx.input("B"), ctx.input("ALog"),
                       ctx.input("DtBias"),
                       str(ctx.attr("kind", "lower_bound_sigmoid")),
                       float(ctx.attr("bound", -5.0)))
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
