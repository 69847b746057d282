"""Plain reference of Mistral's Mistral-Small-4 decoder LM
(`model_type: mistral4`;
https://huggingface.co/mistralai/Mistral-Small-4-119B-2603/blob/main/config.json),
written from the configuration's keys and DeepSeek-V2's equations
(arXiv:2405.04434, section 2.1). Straightforward `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`: the EXPANDED form only (the
keys and values of every head at every position are built from the
latent rows), no cache, no absorption, no kernel, no batching, a Python
loop over heads and over experts: one whole sequence, every position.
Attention runs a head at a time in blocks of `_ROWS` query rows, so
that 12,000 positions fit beside the weights. Nothing here is imported
from `paddle_tpu`.

With d = hidden_size, eps = rms_norm_eps, rms(x; g) = g * x /
sqrt(mean(x^2) + eps), H = num_attention_heads, dn = qk_nope_head_dim,
dr = qk_rope_head_dim, dv = v_head_dim, r = kv_lora_rank:

  h_0 = E[tokens]
  layer i:  a = h + attn_i(rms(h; g_in));  h' = a + moe_i(rms(a; g_ff))
  logits  = rms(h_L; g_final) W_head        (W_head its own matrix)

Attention, u = rms(h; g_in), position p of each row:
  c_q = rms(u W_qa; g_q)                              (q_lora_rank)
  [q_nope_h ; q_rope_h] = c_q W_qb                    (H x (dn + dr))
  [c_kv ; k_r] = u W_kva;  c_kv <- rms(c_kv; g_kv)    (r + dr)
  q_rope_h, k_r <- RoPE_p(.) on the pairs (2i, 2i+1) (`rope_interleave`),
      frequencies YaRN's (`transformers`' `_compute_yarn_parameters`),
      cos and sin times mscale / mscale_all_dim;  k_r is ONE row for
      all heads
  q_h <- q_h (1 + beta ln(1 + floor(p / original_max)))   (ASSUMED:
      `assumed.query_scale` = "llama4", beta = llama_4_scaling_beta)
  [k_nope_h ; v_h] = c_kv W_kvb                       (H x (dn + dv))
  s_h(t, j) = a ([q_nope_h(t) ; q_rope_h(t)] . [k_nope_h(j) ; k_r(j)]),
      j <= t;  a = qk_head_dim^-0.5 (0.1 mscale_all_dim ln(factor) + 1)^2
      (ASSUMED: `assumed.softmax_scale` = "yarn_mscale_all_dim")
  o_h = softmax_j(s_h) v_h;  y = [o_1 .. o_H] W_o

Experts, x = rms(a; g_ff): g = softmax(x W_r) in float32 over all
`n_routed_experts_scored` (ASSUMED: `assumed.router_score` =
"softmax"); S = the num_experts_per_tok largest (ties to the lower
index); w_e = routed_scaling_factor * g_e / sum_{j in S} g_j
(`norm_topk_prob`); y = sum_{e in S, e held} w_e E_e(x) + E_shared(x),
E(x; W) = (silu(x W_gate) * (x W_up)) W_down.

DEPARTURES from the published model, each also in the configuration's
file: float32 for bfloat16; the language model alone (no vision
tower); `held` = [lo, hi) is the chip's share of the routed experts
(`experts_held`): what the absent experts would add is left out, here
as in the program, and that partial result goes on to the next layer;
the vocabulary is the slice the file states; no capacity: every pair
on a held expert is computed.

`precision` chooses how a matmul is computed and what is stored, for
the control of the correctness check (as `reference/laguna.py`):
  "highest"  float32 operands, `jax.lax.Precision.HIGHEST` (the truth)
  "bf16_ops" matmul operands rounded to bfloat16, float32 accumulation,
             float32 everything else (what an f32 matmul is on a TPU at
             default precision: the arithmetic the configuration states)
  "bf16"     as "bf16_ops", and every stored activation, latent row, key
             and value rounded to bfloat16
A precision may name a variant after a `+` ("bf16_ops+no_shared"): the
forward pass with one part left out, for the runs that show that the
comparison sees each mechanism (`hidden`). The router's scores are
float32 at `highest` in every precision (the program computes them so).
Parameter names are the program's (`lm.l1.attention.kv_b.w`,
`lm.l1.moe.experts.gate.w`: the held experts alone, (hi - lo, d, f)).
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "bf16_ops", "bf16")
VARIANTS = ("", "no_shared", "no_renorm", "no_routed", "kr_not_rotated",
            "no_query_scale", "no_kv_norm", "cache_row_short")
_HI = jax.lax.Precision.HIGHEST
_ROWS = 2048  # query rows of one head attended at a time


def make_ops(precision: str):
    """(matmul, store): `matmul(a, b)` contracts a's last with b's
    first axis; `store(x)` is applied to every activation kept."""
    if precision not in PRECISIONS:
        raise ValueError(precision)

    def mm(a, b):
        if precision == "highest":
            return jnp.matmul(a, b, precision=_HI)
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)

    def store(x):
        if precision == "bf16":
            return x.astype(jnp.bfloat16).astype(jnp.float32)
        return x

    return mm, store


def _rms(x, g, eps):
    return g * x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                                 + eps)


def _silu(x):
    return x * jax.nn.sigmoid(x)


# -- rotary positions, the two scales ----------------------------------------

def inv_freq(rp: dict, r: int) -> np.ndarray:
    """(r / 2,) float64 YaRN inverse frequencies of `rope_parameters`
    over a rotated width of `r`."""
    assert rp["rope_type"] == "yarn", rp["rope_type"]
    base = float(rp["rope_theta"])
    pos_freqs = base ** (np.arange(0, r, 2, dtype=np.float64) / r)
    extra = 1.0 / pos_freqs
    inter = 1.0 / (float(rp["factor"]) * pos_freqs)
    orig = float(rp["original_max_position_embeddings"])

    def c(rotations):
        return (r * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(c(float(rp["beta_fast"]))), 0)
    high = min(math.ceil(c(float(rp["beta_slow"]))), r - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(r // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def _mscale(rp: dict, m) -> float:
    return 0.1 * float(m) * math.log(float(rp["factor"])) + 1.0 if m else 1.0


def rotate(x, rp: dict, interleave: bool):
    """x (T, ..., r) rotated whole at positions 0..T-1, as complex
    numbers: under `interleave` channel 2i is the real and 2i+1 the
    imaginary part of the i-th; otherwise i and i + r/2 are."""
    t, r = x.shape[0], x.shape[-1]
    ang = (jnp.arange(t, dtype=jnp.float32)[:, None]
           * jnp.asarray(inv_freq(rp, r), jnp.float32)[None, :])
    factor = _mscale(rp, rp["mscale"]) / _mscale(rp, rp["mscale_all_dim"])
    shape = (t,) + (1,) * (x.ndim - 2) + (r // 2,)
    cos = (jnp.cos(ang) * factor).reshape(shape)
    sin = (jnp.sin(ang) * factor).reshape(shape)
    if interleave:
        re, im = x[..., 0::2], x[..., 1::2]
        return jnp.stack([re * cos - im * sin, im * cos + re * sin],
                         axis=-1).reshape(x.shape)
    re, im = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([re * cos - im * sin, im * cos + re * sin], -1)


def softmax_scale(cfg: dict) -> float:
    a = float(cfg["qk_head_dim"]) ** -0.5
    rule = cfg["assumed"]["softmax_scale"]
    if rule == "yarn_mscale_all_dim":
        rp = cfg["rope_parameters"]
        a *= _mscale(rp, rp["mscale_all_dim"]) ** 2
    elif rule != "plain":
        raise ValueError(rule)
    return a


def query_scale(cfg: dict, t: int):
    """(T,) the scale of the query row at each position."""
    rule = cfg["assumed"]["query_scale"]
    if rule is None:
        return jnp.ones((t,), jnp.float32)
    if rule != "llama4":
        raise ValueError(rule)
    rp = cfg["rope_parameters"]
    pos = jnp.arange(t, dtype=jnp.float32)
    return 1.0 + float(rp["llama_4_scaling_beta"]) * jnp.log1p(jnp.floor(
        pos / float(rp["original_max_position_embeddings"])))


# -- attention ---------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("precision", "short"))
def _attend_head(q, k, v, row0, precision, short=False):
    """A block of one head's query rows: q (n, dk) pre-scaled, at
    positions row0..row0 + n - 1; k (T, dk), v (T, dv)."""
    mm, store = make_ops(precision)
    n, t = q.shape[0], k.shape[0]
    s = mm(q, k.T)
    row = row0 + jnp.arange(n)[:, None]
    col = jnp.arange(t)[None, :]
    seen = col <= row
    if short:  # a cache that lost every sequence's first row
        seen = seen & ((col > 0) | (row == 0))
    w = store(jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1))
    return mm(w, v)


def attention(p, u, cfg, precision, variant=""):
    """u (T, d) -> (T, d): one layer's latent attention, expanded."""
    mm, store = make_ops(precision)
    t = u.shape[0]
    h = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps, rp = cfg["rms_norm_eps"], cfg["rope_parameters"]
    inter = bool(cfg["rope_interleave"])
    c_q = store(_rms(mm(u, p["q_a.w"]), p["q_norm.w"], eps))
    q = mm(c_q, p["q_b.w"]).reshape(t, h, dn + dr)
    q = jnp.concatenate([q[..., :dn], rotate(q[..., dn:], rp, inter)], -1)
    if variant != "no_query_scale":
        q = q * query_scale(cfg, t)[:, None, None]
    q = store(q)
    row = mm(u, p["kv_a.w"])
    c_kv = row[:, :r]
    if variant != "no_kv_norm":
        c_kv = _rms(c_kv, p["kv_norm.w"], eps)
    k_r = row[:, r:]
    if variant != "kr_not_rotated":
        k_r = rotate(k_r, rp, inter)
    c_kv, k_r = store(c_kv), store(k_r)  # what a position keeps
    kv = mm(c_kv, p["kv_b.w"]).reshape(t, h, dn + dv)
    k_nope, v = store(kv[..., :dn]), store(kv[..., dn:])
    a = softmax_scale(cfg)
    out = []
    for j in range(h):  # a head at a time, a block of rows at a time
        k_j = jnp.concatenate([k_nope[:, j], k_r], axis=-1)
        out.append(jnp.concatenate([
            _attend_head(q[r0:r0 + _ROWS, j] * a, k_j, v[:, j], r0,
                         precision, variant == "cache_row_short")
            for r0 in range(0, t, _ROWS)]))
    ctx = jnp.stack(out, axis=1)  # (T, H, dv)
    return mm(store(ctx.reshape(t, h * dv)), p["o.w"])


# -- experts -----------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("precision",))
def gated_mlp(x, w_gate, w_up, w_down, precision):
    mm, store = make_ops(precision)
    gate = store(_silu(mm(x, w_gate)))
    up = store(mm(x, w_up))
    return mm(store(gate * up), w_down)


def route(x, w_router, cfg, variant=""):
    """(idx (T, k), weights (T, k)) over ALL routed experts, float32 at
    `highest` whatever the precision."""
    if cfg["assumed"]["router_score"] != "softmax":
        raise ValueError(cfg["assumed"]["router_score"])
    g = jax.nn.softmax(jnp.matmul(x, w_router, precision=_HI), axis=-1)
    top, idx = jax.lax.top_k(g, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"] and variant != "no_renorm":
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return idx, top * cfg["routed_scaling_factor"]


def moe(p, x, cfg, precision, held=None, shared=True, variant=""):
    """x (T, d) -> (T, d): the experts `held` = [lo, hi) (default: the
    configuration's `experts_held`) and, with `shared`, the shared
    expert. `p["experts.*.w"]` hold the held experts alone, in order."""
    lo, hi = held if held is not None else cfg["experts_held"]
    idx, w = route(x, p["router.w"], cfg, variant)
    y = jnp.zeros_like(x)
    if variant == "no_routed":
        lo = hi
    for e in range(lo, hi):  # every pair on a held expert, no capacity
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        y = y + w_e[:, None] * gated_mlp(
            x, p["experts.gate.w"][e - lo], p["experts.up.w"][e - lo],
            p["experts.down.w"][e - lo], precision)
    if shared and variant != "no_shared":
        y = y + gated_mlp(x, p["shared.gate.w"], p["shared.up.w"],
                          p["shared.down.w"], precision)
    return y


# -- the model ---------------------------------------------------------------

def _sub(p, prefix):
    return {n[len(prefix):]: v for n, v in p.items() if n.startswith(prefix)}


def hidden(params, tokens, cfg, n_layer, precision="highest", variant=""):
    """Final-norm output (T, d) of one sequence `tokens` (T,), a layer
    at a time. `variant` (one of `VARIANTS`) leaves one thing out, for
    the runs that show the comparison sees it."""
    if variant not in VARIANTS:
        raise ValueError(variant)
    _, store = make_ops(precision)
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        h = store(params["lm.tok_emb"][tokens])
        for i in range(n_layer):
            p = _sub(params, "lm.l%d." % i)
            u = store(_rms(h, p["norm_in.w"], eps))
            h = store(h + attention(_sub(p, "attention."), u, cfg,
                                    precision, variant))
            u = store(_rms(h, p["norm_ff.w"], eps))
            h = store(h + moe(_sub(p, "moe."), u, cfg, precision,
                              variant=variant))
        return store(_rms(h, params["lm.norm_f.w"], eps))


def serve_logits(params, tokens, cfg, n_layer, precision="highest",
                 rows=None, variant=""):
    """(T or len(rows), V) logits of one sequence through the head's
    own matrix: the serving runner's call."""
    if "+" in precision:
        precision, variant = precision.split("+", 1)
    mm, _ = make_ops(precision)
    h = hidden(params, tokens, cfg, n_layer, precision, variant)
    if rows is not None:
        h = h[rows]
    with jax.default_matmul_precision("highest"):
        return mm(h, params["lm.head.w"])
