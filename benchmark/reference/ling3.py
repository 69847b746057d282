"""Plain reference of inclusionAI's Ling-3.0-flash decoder LM
(`model_type: bailing_hybrid`;
https://huggingface.co/inclusionAI/Ling-3.0-flash/blob/main/config.json),
written from the configuration's keys and the three papers its
mechanisms come from: Kimi Delta Attention (Kimi Linear,
arXiv:2510.26692, section 3), multi-head latent attention (DeepSeek-V2,
arXiv:2405.04434, section 2.1) and the `noaux_tc` router (DeepSeek-V3,
arXiv:2412.19437, section 2.1.2). Straightforward `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`: the delta rule as a
`lax.scan` a TOKEN (no chunks), full EXPANDED attention (no cache, no
absorbed form), the router written out, a Python loop over heads and
over experts: one whole sequence, every position. Attention runs a head
at a time in blocks of `_ROWS` query rows, so that 12,000 positions fit
beside the weights. Nothing here is imported from `paddle_tpu`.

With d = hidden_size, eps = rms_norm_eps, rms(x; g) = g * x /
sqrt(mean(x^2) + eps), H = num_attention_heads:

  h_0 = E[tokens]
  layer i:  a = h + mix_i(rms(h; g_in));  h' = a + ffn_i(rms(a; g_ff))
  logits  = rms(h_L; g_final) W_head        (W_head its own matrix)

Layer i is latent attention iff (i + 1) % layer_group_size == 0, else
KDA. Layers below first_k_dense_replace have a dense gated MLP, the
rest routed experts with a shared one.

KDA, u = rms(h; g_in), dk = head_dim (keys, queries and values alike):
  q^ = silu(conv(u W_q)), k^ = silu(conv(u W_k)), v = silu(conv(u W_v)):
      causal depthwise convolutions of short_conv_kernel_size taps, zeros
      before the start (`linear_silu`)
  q = q^ / |q^|_2 dk^-1/2,  k = k^ / |k^|_2, a head at a time
      (`use_qk_norm`; |x|_2 = sqrt(sum x^2 + 1e-6))
  g_t = kda_lower_bound sigmoid(exp(A_log_h) (u W_f + dt_bias)), dk
      channels a head, in (kda_lower_bound, 0) (ASSUMED: `assumed.
      kda_gate` = "lower_bound_sigmoid"; "softplus": g_t = -exp(A_log_h)
      softplus(u W_f + dt_bias), Kimi Linear's)
  beta_t = sigmoid(u W_beta), one a head
  S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T,
      S (dk, dk) a head, S_0 = 0;  o_t = S_t^T q_t
  y = (rms(o_t; g_o) sigmoid(u W_g)_h, a head at a time) W_o
      (`group_norm_size` 1, `gated_attention_proj_granularity_type`
      head_wise)

Latent attention, dn = qk_nope_head_dim, dr = qk_rope_head_dim, dv =
v_head_dim, r = kv_lora_rank:
  [q_nope_h ; q_rope_h] = u W_q                       (H x (dn + dr);
      `q_lora_rank` null: no bottleneck)
  [c_kv ; k_r] = u W_kva;  c_kv <- rms(c_kv; g_kv)    (r + dr)
  q_rope_h, k_r <- RoPE_p(.) on the pairs (2i, 2i+1) (`rope_interleave`),
      theta rope_theta, no scaling;  k_r is ONE row for all heads
  [k_nope_h ; v_h] = c_kv W_kvb                       (H x (dn + dv))
  s_h(t, j) = (dn + dr)^-1/2 [q_nope_h(t) ; q_rope_h(t)] . [k_nope_h(j) ;
      k_r(j)], j <= t;  o_h = softmax_j(s_h) v_h
  y = [o_h sigmoid(u W_g)_h] W_o

Experts, x = rms(a; g_ff): s = sigmoid(x W_r) in float32 over all
`num_experts_scored`; c = s + b (`moe_router_enable_expert_bias`); a
group's score is the sum of its two largest c among its experts
(ASSUMED: `assumed.group_score`); the topk_group best of n_group groups
stay; S = the num_experts_per_tok largest c among theirs (ties to the
lower index); w_e = routed_scaling_factor s_e / sum_{j in S} s_j; y =
sum_{e in S, e held} w_e E_e(x) + E_shared(x), E(x; W) = (silu(x
W_gate) * (x W_up)) W_down.

DEPARTURES from the published model, each also in the configuration's
file: float32 for bfloat16; no multi-token-prediction module; `held` =
[lo, hi) is the chip's share of the routed experts (`experts_held`):
what the absent experts would add is left out, here as in the program,
and that partial result goes on to the next layer; the vocabulary is
the slice the file states; no capacity: every pair on a held expert is
computed.

`precision` chooses how a matmul is computed and what is stored, for
the control of the correctness check (as `reference/mistral4.py`):
  "highest"  float32 operands, `jax.lax.Precision.HIGHEST` (the truth)
  "bf16_ops" matmul operands rounded to bfloat16, float32 accumulation,
             float32 everything else (what an f32 matmul is on a TPU at
             default precision: the arithmetic the configuration states);
             the delta rule's own multiplies and adds are float32
  "bf16"     as "bf16_ops", and every stored activation, delta-rule
             state, latent row, key and value rounded to bfloat16
A precision may name a variant after a `+` ("bf16_ops+no_decay"): the
forward pass with one part left out, for the runs that show that the
comparison sees each mechanism (`hidden`). The router's scores are
float32 at `highest` in every precision (the program computes them so).
Parameter names are the program's (`lm.l0.kda.q.w`, `lm.l5.attention.
kv_b.w`, `lm.l2.moe.experts.gate.w`: the held experts alone).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "bf16_ops", "bf16")
VARIANTS = ("", "no_decay", "beta_one", "no_conv", "no_qk_norm",
            "state_stale", "state_zeroed", "no_group_limit", "no_bias",
            "no_gate", "kr_not_rotated", "no_shared")
_HI = jax.lax.Precision.HIGHEST
_ROWS = 2048  # query rows of one head attended at a time
_CHUNK = 64   # the program's chunk: where `state_zeroed` zeroes


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


def layer_kinds(cfg: dict, n_layer: int):
    """("kda" | "latent", "dense" | "sparse") of layers 0..n_layer-1."""
    return [("latent" if (i + 1) % cfg["layer_group_size"] == 0 else "kda",
             "dense" if i < cfg["first_k_dense_replace"] else "sparse")
            for i in range(n_layer)]


# -- Kimi Delta Attention ----------------------------------------------------

def conv(x, w):
    """x (T, C), w (C, K): y[t] = sum_j w[:, j] x[t - K + 1 + j], zeros
    before the start."""
    t, k = x.shape[0], w.shape[1]
    xp = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return sum(xp[j:j + t] * w[:, j] for j in range(k))


def kda_gate(f, a_log, dt_bias, cfg, h):
    """f (T, H * dk) -> the log-decay (T, H, dk)."""
    x = (f + dt_bias).reshape(f.shape[0], h, -1)
    a = jnp.exp(a_log)[None, :, None]
    kind = cfg["assumed"]["kda_gate"]
    if kind == "softplus":
        return -a * jax.nn.softplus(x)
    if kind != "lower_bound_sigmoid":
        raise ValueError(kind)
    return float(cfg["kda_lower_bound"]) * jax.nn.sigmoid(a * x)


@functools.partial(jax.jit, static_argnames=("precision", "stale", "zero"))
def delta_rule(q, k, v, g, beta, precision, stale=-1, zero=-1):
    """The recurrence a token at a time: q, k, g (T, H, dk), v (T, H,
    dv), beta (T, H) -> o (T, H, dv). `stale`: the token whose update
    the carried state misses (its own o sees it); `zero`: the token
    before which the state is zeroed."""
    _, store = make_ops(precision)
    t, h, dk = q.shape

    def body(s, inp):
        i, q_t, k_t, v_t, g_t, b_t = inp
        s = jnp.where(i == zero, 0.0, s)
        new = s * jnp.exp(g_t)[..., None]
        u = b_t[:, None] * (v_t - jnp.sum(new * k_t[..., None], axis=-2))
        new = store(new + k_t[..., None] * u[:, None, :])
        o = jnp.sum(new * q_t[..., None], axis=-2)
        return jnp.where(i == stale, s, new), o

    _, o = jax.lax.scan(
        body, jnp.zeros((h, dk, v.shape[-1]), jnp.float32),
        (jnp.arange(t), q, k, v, g, beta))
    return o


def kda(p, u, cfg, precision, variant="", handover=-1):
    """u (T, d) -> (T, d): one KDA layer's mixer."""
    mm, store = make_ops(precision)
    t = u.shape[0]
    h, dk, eps = cfg["num_attention_heads"], cfg["head_dim"], \
        cfg["rms_norm_eps"]
    parts = []
    for n in "qkv":
        x = mm(u, p[n + ".w"])
        if variant != "no_conv":
            x = conv(x, p["conv_%s.w" % n])
        parts.append(store(_silu(x)).reshape(t, h, dk))
    q, k, v = parts
    if variant != "no_qk_norm":
        q, k = (x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                                  + 1e-6) for x in (q, k))
    q = q * float(dk) ** -0.5
    g = kda_gate(mm(u, p["f.w"]), p["A_log"], p["dt_bias"], cfg, h)
    if variant == "no_decay":
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(mm(u, p["beta.w"]))
    if variant == "beta_one":
        beta = jnp.ones_like(beta)
    stale = handover if variant == "state_stale" else -1
    zero = (_CHUNK * max(handover // _CHUNK // 2, 1)
            if variant == "state_zeroed" else -1)
    o = delta_rule(store(q), store(k), v, store(g), store(beta), precision,
                   stale, zero)
    o = _rms(o, p["o_norm.w"], eps)
    if variant != "no_gate":
        o = o * jax.nn.sigmoid(mm(u, p["gate.w"]))[:, :, None]
    return mm(store(o.reshape(t, h * dk)), p["o.w"])


# -- latent attention --------------------------------------------------------

def rotate(x, theta: float):
    """x (T, ..., r) rotated whole at positions 0..T-1 on the pairs
    (2i, 2i+1): channel 2i the real and 2i+1 the imaginary part."""
    t, r = x.shape[0], x.shape[-1]
    inv = 1.0 / (float(theta) ** (np.arange(0, r, 2, dtype=np.float64) / r))
    ang = (jnp.arange(t, dtype=jnp.float32)[:, None]
           * jnp.asarray(inv, jnp.float32)[None, :])
    shape = (t,) + (1,) * (x.ndim - 2) + (r // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    re, im = x[..., 0::2], x[..., 1::2]
    return jnp.stack([re * cos - im * sin, im * cos + re * sin],
                     axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("precision",))
def _attend_head(q, k, v, row0, precision):
    """A block of one head's query rows: q (n, dk) pre-scaled, at
    positions row0..row0 + n - 1; k (T, dk), v (T, dv)."""
    mm, store = make_ops(precision)
    n, t = q.shape[0], k.shape[0]
    s = mm(q, k.T)
    seen = jnp.arange(t)[None, :] <= row0 + jnp.arange(n)[:, None]
    w = store(jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1))
    return mm(w, v)


def attention(p, u, cfg, precision, variant=""):
    """u (T, d) -> (T, d): the latent layer's attention, expanded."""
    mm, store = make_ops(precision)
    assert cfg["rope_interleave"] and cfg["rope_scaling"] is None
    t = u.shape[0]
    h = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    q = mm(u, p["q.w"]).reshape(t, h, dn + dr)
    q = store(jnp.concatenate([q[..., :dn], rotate(q[..., dn:], theta)], -1))
    row = mm(u, p["kv_a.w"])
    c_kv = _rms(row[:, :r], p["kv_norm.w"], eps)
    k_r = row[:, r:]
    if variant != "kr_not_rotated":
        k_r = rotate(k_r, theta)
    c_kv, k_r = store(c_kv), store(k_r)  # what a position keeps
    kv = mm(c_kv, p["kv_b.w"]).reshape(t, h, dn + dv)
    k_nope, v = store(kv[..., :dn]), store(kv[..., dn:])
    a = float(dn + dr) ** -0.5
    out = []
    for j in range(h):  # a head at a time, a block of rows at a time
        k_j = jnp.concatenate([k_nope[:, j], k_r], axis=-1)
        out.append(jnp.concatenate([
            _attend_head(q[r0:r0 + _ROWS, j] * a, k_j, v[:, j], r0,
                         precision)
            for r0 in range(0, t, _ROWS)]))
    ctx = jnp.stack(out, axis=1)  # (T, H, dv)
    if variant != "no_gate":
        ctx = ctx * jax.nn.sigmoid(mm(u, p["gate.w"]))[:, :, None]
    return mm(store(ctx.reshape(t, h * dv)), p["o.w"])


# -- feed-forward ------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("precision",))
def gated_mlp(x, w_gate, w_up, w_down, precision):
    mm, store = make_ops(precision)
    gate = store(_silu(mm(x, w_gate)))
    up = store(mm(x, w_up))
    return mm(store(gate * up), w_down)


def route(x, w_router, bias, cfg, variant=""):
    """(idx (T, k), weights (T, k)) over ALL routed experts, float32 at
    `highest` whatever the precision: sigmoid scores, the choice by
    score + bias inside the best groups, the weights by score."""
    assert cfg["score_function"] == "sigmoid" and cfg["norm_topk_prob"]
    assert cfg["assumed"]["group_score"] == "top2_sum"
    s = jax.nn.sigmoid(jnp.matmul(x, w_router, precision=_HI))
    c = s if variant == "no_bias" else s + bias
    n_group, e = cfg["n_group"], s.shape[-1]
    if variant != "no_group_limit":
        per = c.reshape(c.shape[0], n_group, e // n_group)
        score = jnp.sum(jax.lax.top_k(per, 2)[0], axis=-1)       # (T, groups)
        _, kept = jax.lax.top_k(score, cfg["topk_group"])
        stay = jnp.zeros_like(score, bool).at[
            jnp.arange(c.shape[0])[:, None], kept].set(True)
        c = jnp.where(jnp.repeat(stay, e // n_group, axis=-1), c, -jnp.inf)
    _, idx = jax.lax.top_k(c, cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(s, idx, axis=-1)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    return idx, top * cfg["routed_scaling_factor"]


def moe(p, x, cfg, precision, held=None, shared=True, variant=""):
    """x (T, d) -> (T, d): the experts `held` = [lo, hi) (default: the
    configuration's `experts_held`) and, with `shared`, the shared
    expert. `p["experts.*.w"]` hold the held experts alone, in order."""
    lo, hi = held if held is not None else cfg["experts_held"]
    idx, w = route(x, p["router.w"], p["router.bias"], cfg, variant)
    y = jnp.zeros_like(x)
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


def hidden(params, tokens, cfg, n_layer, precision="highest", variant="",
           handover=-1):
    """Final-norm output (T, d) of one sequence `tokens` (T,), a layer
    at a time. `variant` (one of `VARIANTS`) leaves one thing out, for
    the runs that show the comparison sees it; `handover` is the
    prompt's last position, where a served state passes from the
    prefill to the step (`state_stale`, `state_zeroed`)."""
    if variant not in VARIANTS:
        raise ValueError(variant)
    _, store = make_ops(precision)
    eps = cfg["rms_norm_eps"]
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        assert not any(cfg[key][:n_layer]), key
    with jax.default_matmul_precision("highest"):
        h = store(params["lm.tok_emb"][tokens])
        for i, (mixer, ffn) in enumerate(layer_kinds(cfg, n_layer)):
            p = _sub(params, "lm.l%d." % i)
            u = store(_rms(h, p["norm_in.w"], eps))
            if mixer == "kda":
                mixed = kda(_sub(p, "kda."), u, cfg, precision, variant,
                            handover)
            else:
                mixed = attention(_sub(p, "attention."), u, cfg, precision,
                                  variant)
            h = store(h + mixed)
            u = store(_rms(h, p["norm_ff.w"], eps))
            if ffn == "dense":
                q = _sub(p, "mlp.")
                y = gated_mlp(u, q["gate.w"], q["up.w"], q["down.w"],
                              precision)
            else:
                y = moe(_sub(p, "moe."), u, cfg, precision, variant=variant)
            h = store(h + y)
        return store(_rms(h, params["lm.norm_f.w"], eps))


def serve_logits(params, tokens, cfg, n_layer, precision="highest",
                 rows=None, variant=""):
    """(T or len(rows), V) logits of one sequence through the head's
    own matrix: the serving runner's call (`rows[0]` is the prompt's
    last position)."""
    if "+" in precision:
        precision, variant = precision.split("+", 1)
    mm, _ = make_ops(precision)
    handover = -1 if rows is None else int(np.asarray(rows)[0])
    h = hidden(params, tokens, cfg, n_layer, precision, variant, handover)
    if rows is not None:
        h = h[rows]
    with jax.default_matmul_precision("highest"):
        return mm(h, params["lm.head.w"])
