"""Routed-expert feed-forward for serving: NO token is dropped.

``parallel/moe.py`` is the capacity-factor TRAINING layer (a token past
an expert's capacity gets zero weight); a cached decode graph has to
equal the model's forward pass, so this file computes every chosen
(token, expert) pair whatever the load. Three ops, one scope each:

- ``moe_route`` (``ptpu.moe_route``): scores over ALL ``E`` experts in
  float32 (the router's matmul at ``highest`` precision: it is tiny and
  its order decides a discontinuous choice), a sigmoid a logit or a
  softmax over the ``E`` (``ROUTER_SCORES``), the ``k`` largest (ties
  to the lower index), renormalised to sum 1, times ``scale``. With a
  selection ``bias`` the k are chosen by ``s + b`` and weighted by
  ``s``; with ``n_group`` groups the choice is limited to the
  ``topk_group`` whose two best biased scores sum highest
  (DeepSeek-V3's ``noaux_tc``, arXiv:2412.19437 section 2.1.2).
- ``moe_experts`` (``ptpu.moe_experts``): the experts HELD here, ``[lo,
  lo + Eh)`` of the ``E`` the router chose among ("route over all,
  compute your own": a chip of an expert-parallel deployment holds a
  share, and on one chip the layer runs without its exchange). Returns
  the weighted sum over a token's pairs that fall on held experts, and
  the pairs each held expert received.
- ``moe_shared`` (``ptpu.moe_shared``): the shared expert, a gated
  SiLU MLP every token passes through.

One exact form of the routed product, for a prefill and a decode step
alike: the pairs are sorted by expert and a grouped product
(``lax.ragged_dot``; the TPU compiler has kernels of its own for it,
``ragged-dot-none`` in a trace) runs over blocks of ``_BLOCK_PAIRS``
sorted pairs in a loop whose trip count is the HELD pairs', so work and
temporaries follow the pairs routed here and not the worst case, and
only the experts that received a pair are streamed. (A dense product
over every held expert with a mask was measured beside it on the chip at
a decode step's 64 tokens: 2.08 ms a layer against 1.55, PERF.md PR 31;
at a prefill it does 32x the FLOPs. It is not kept.)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .math import wmm as _wmm
from .registry import register_op

MOE_ROUTE = "ptpu.moe_route"
MOE_EXPERTS = "ptpu.moe_experts"
MOE_SHARED = "ptpu.moe_shared"

# the score functions ``moe_route`` builds
ROUTER_SCORES = ("sigmoid", "softmax")

# sorted pairs one iteration gathers and multiplies: a block's rows past
# the held pairs are padding that still costs (0.2 us a row on a v5e), a
# single prompt of 1-2 k tokens routes 2-4 k pairs here
_BLOCK_PAIRS = 4096


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _group_limited(c, n_group, topk_group):
    """Selection scores with every group but the ``topk_group`` best of
    ``n_group`` masked out: c (..., E), the experts in ``n_group`` runs
    of ``E / n_group``; a group's score is the sum of its two largest
    (DeepSeek-V3's ``noaux_tc``); ties to the lower group."""
    e = c.shape[-1]
    grouped = c.reshape(c.shape[:-1] + (n_group, e // n_group))
    best2, _ = lax.top_k(grouped, 2)
    _, keep = lax.top_k(jnp.sum(best2, axis=-1), int(topk_group))
    kept = jnp.any(keep[..., None] == jnp.arange(n_group), axis=-2)
    return jnp.where(jnp.repeat(kept, e // n_group, axis=-1), c, -jnp.inf)


def moe_route(x, w_router, top_k, scale=1.0, score="sigmoid", bias=None,
              n_group=1, topk_group=1):
    """x (..., D), w_router (D, E) -> (idx (..., k) int32, weights
    (..., k) float32). ``score`` "sigmoid": a sigmoid a logit
    (DeepSeek-V3's router); "softmax": a softmax over all E (Mixtral's:
    renormalised over the chosen k it equals a softmax over the chosen
    logits). ``bias`` (E,) or None: a selection bias, the k are CHOSEN
    by ``s + bias`` and weighted by ``s``. ``n_group`` > 1: the choice
    is limited to the ``topk_group`` best groups (``_group_limited``).
    No bias and one group trace to the graph they always have."""
    if score not in ROUTER_SCORES:
        raise ValueError("moe_route: score function %r is not built (%s "
                         "are)" % (score, ", ".join(ROUTER_SCORES)))
    with jax.named_scope(MOE_ROUTE):
        logits = jnp.matmul(x.astype(jnp.float32),
                            w_router.astype(jnp.float32),
                            precision=lax.Precision.HIGHEST)
        s = (jax.nn.sigmoid(logits) if score == "sigmoid"
             else jax.nn.softmax(logits, axis=-1))
        if bias is None and int(n_group) == 1:
            top, idx = lax.top_k(s, int(top_k))
        else:
            c = s if bias is None else s + bias.astype(jnp.float32)
            if int(n_group) > 1:
                c = _group_limited(c, int(n_group), int(topk_group))
            _, idx = lax.top_k(c, int(top_k))
            top = jnp.take_along_axis(s, idx, axis=-1)
        top = top / jnp.sum(top, axis=-1, keepdims=True)
        return idx.astype(jnp.int32), top * jnp.float32(scale)


def _held(idx, weights, valid, lo, n_held):
    """Pairs as flat arrays: local expert id in [0, n_held) or n_held
    for a pair that is not computed here (another chip's expert, or a
    token that is padding), its weight, and its token."""
    n, k = idx.shape
    local = idx - lo
    here = (local >= 0) & (local < n_held)
    if valid is not None:
        here = here & valid[:, None]
    local = jnp.where(here, local, n_held).reshape(-1)
    token = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    return local, jnp.where(here, weights, 0.0).reshape(-1), token


def _add_by_token(out, rows, y, most):
    """``out`` (N, D) plus ``y`` (blk, D) at the tokens ``rows`` (blk,),
    a token's rows (at most ``most`` of them) summed; a row ``N`` is no
    token's and is not read. Without a scatter of rows: the TPU
    compiler's scatter-add costs by the rows of ``out``, 31 ms for 4,096
    rows into (16,384, 5,120) float32 where this is 3.6 (PERF.md, PR
    44). The block is sorted by token, so that a token's rows lie side
    by side; ``most - 1`` shifted adds sum them into the first of them;
    a scatter of ``blk`` INTEGERS finds each token's first row; and ONE
    gather of N rows (a row of zeros for a token with none) is added to
    ``out``."""
    n, blk = out.shape[0], rows.shape[0]
    order = jnp.argsort(rows)
    rs, ys = rows[order], y[order]
    tail = max(int(most) - 1, 0)
    rs_pad = jnp.concatenate([rs, jnp.full((tail,), n + 1, rs.dtype)])
    ys_pad = jnp.concatenate([ys, jnp.zeros((tail,) + ys.shape[1:],
                                            ys.dtype)])
    total = ys
    for j in range(1, tail + 1):
        total = total + jnp.where((rs_pad[j:j + blk] == rs)[:, None],
                                  ys_pad[j:j + blk], 0.0)
    first = jnp.full((n + 1,), blk, jnp.int32).at[rs].min(
        jnp.arange(blk, dtype=jnp.int32))[:n]
    return out + jnp.concatenate(
        [total, jnp.zeros((1,) + ys.shape[1:], ys.dtype)])[first]


def _grouped(x, w, sizes):
    """``lax.ragged_dot`` of float32 rows on the held experts' matrices
    (Eh, K, N); matrices HELD in bfloat16 (``DecodeConfig.
    matrix_dtype``) meet the rows rounded to bfloat16 (the grouped
    product wants operands of one type), sums in float32: what the
    float32 product computes at the TPU's default precision."""
    if w.dtype == jnp.bfloat16 and x.dtype != jnp.bfloat16:
        return lax.ragged_dot(x.astype(jnp.bfloat16), w, sizes,
                              preferred_element_type=jnp.float32)
    return lax.ragged_dot(x, w, sizes)


def _experts_grouped(x, local, w, token, w_gate, w_up, w_down, counts):
    """Pairs sorted by held expert, a grouped product a block of them."""
    n, d = x.shape
    m = local.shape[0]
    blk = min(_BLOCK_PAIRS, m)
    order = jnp.argsort(local)  # stable: held experts first, by id
    pad = (-m) % blk
    tok_s = jnp.pad(token[order], (0, pad))
    w_s = jnp.pad(w[order], (0, pad))
    ends = jnp.cumsum(counts)
    starts = ends - counts
    n_held = ends[-1]

    def body(i, out):
        r0 = i * blk
        rows = lax.dynamic_slice_in_dim(tok_s, r0, blk)
        wr = lax.dynamic_slice_in_dim(w_s, r0, blk)
        # this block's share of each group
        sizes = (jnp.clip(ends, r0, r0 + blk)
                 - jnp.clip(starts, r0, r0 + blk)).astype(jnp.int32)
        xs = x[rows]
        h = _silu(_grouped(xs, w_gate, sizes)) * _grouped(xs, w_up, sizes)
        y = _grouped(h, w_down, sizes)
        # rows past the held pairs belong to no group and to no token:
        # whatever the product left there is not read
        live = (r0 + jnp.arange(blk)) < n_held
        return _add_by_token(out, jnp.where(live, rows, n), y * wr[:, None],
                             m // n)

    trips = (n_held + blk - 1) // blk
    return lax.fori_loop(0, trips, body, jnp.zeros((n, d), jnp.float32))


def moe_experts(x, idx, weights, w_gate, w_up, w_down, lo=0, valid=None,
                count_elsewhere=False):
    """x (N, D); idx, weights (N, k) from ``moe_route``; w_gate, w_up
    (Eh, D, F), w_down (Eh, F, D): the experts ``[lo, lo + Eh)``;
    ``valid`` (N,) bool or None marks real tokens. -> (out (N, D): sum
    over a token's pairs on held experts of weight * expert(x), load
    (Eh,) int32 pairs a held expert received from real tokens; with
    ``count_elsewhere`` (Eh + 1,), the last the real tokens NONE of
    whose pairs fell on a held expert)."""
    eh = w_gate.shape[0]
    with jax.named_scope(MOE_EXPERTS):
        local, w, token = _held(idx, weights, valid, int(lo), eh)
        counts = jnp.zeros((eh + 1,), jnp.int32).at[local].add(1)[:eh]
        out = _experts_grouped(x.astype(jnp.float32), local, w, token,
                               w_gate, w_up, w_down, counts)
        if count_elsewhere:
            none = jnp.all(local.reshape(idx.shape) == eh, axis=-1)
            if valid is not None:
                none = none & valid
            counts = jnp.concatenate(
                [counts, jnp.sum(none, dtype=jnp.int32)[None]])
        return out.astype(x.dtype), counts


def moe_shared(x, w_gate, w_up, w_down):
    """The shared expert: (silu(x W_gate) * (x W_up)) W_down."""
    with jax.named_scope(MOE_SHARED):
        return _wmm(_silu(_wmm(x, w_gate)) * _wmm(x, w_up), w_down)


@register_op("moe_route")
def _moe_route_op(ctx):
    """Inputs X (B, T, D), W (D, E), optional Bias (E,). Attrs top_k,
    scale, score, n_group, topk_group -> Idx (B, T, k) int32, Weights
    (B, T, k) float32."""
    idx, w = moe_route(ctx.input("X"), ctx.input("W"),
                       int(ctx.attr("top_k")),
                       float(ctx.attr("scale", 1.0)),
                       str(ctx.attr("score", "sigmoid")),
                       ctx.input("Bias"), int(ctx.attr("n_group", 1)),
                       int(ctx.attr("topk_group", 1)))
    return {"Idx": idx, "Weights": w}


@register_op("moe_experts")
def _moe_experts_op(ctx):
    """Inputs X (B, T, D), Idx, Weights (B, T, k), WGate, WUp (Eh, D,
    F), WDown (Eh, F, D), optional Lengths (B,). Attrs expert_lo (the
    first expert held), decode (Lengths are tokens held BEFORE this
    step's one: a slot of length 0 is free; otherwise rows at or past a
    row's length are padding), count_elsewhere (Load gets a last entry:
    real tokens that sent no pair here) -> Out (B, T, D), Load (Eh,)
    int32."""
    x = ctx.input("X")
    b, t, d = x.shape
    lengths = ctx.input("Lengths")
    valid = None
    if lengths is not None:
        lens = lengths.reshape(-1).astype(jnp.int32)
        if bool(ctx.attr("decode", False)):
            valid = jnp.repeat(lens > 0, t)
        else:
            valid = (jnp.arange(t, dtype=jnp.int32)[None, :]
                     < lens[:, None]).reshape(-1)
    k = ctx.input("Idx").shape[-1]
    out, load = moe_experts(
        x.reshape(b * t, d), ctx.input("Idx").reshape(b * t, k),
        ctx.input("Weights").reshape(b * t, k), ctx.input("WGate"),
        ctx.input("WUp"), ctx.input("WDown"),
        lo=int(ctx.attr("expert_lo", 0)), valid=valid,
        count_elsewhere=bool(ctx.attr("count_elsewhere", False)))
    return {"Out": out.reshape(b, t, d), "Load": load}


@register_op("moe_shared")
def _moe_shared_op(ctx):
    """Inputs X (B, T, D), WGate, WUp (D, F), WDown (F, D) -> Out."""
    return {"Out": moe_shared(ctx.input("X"), ctx.input("WGate"),
                              ctx.input("WUp"), ctx.input("WDown"))}
