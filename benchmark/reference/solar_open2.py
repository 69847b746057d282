"""Plain reference of Upstage's Solar-Open2-250B decoder LM (`model_type:
solar_open2`;
https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json),
written from the configuration's keys and the papers its mechanisms come
from: Kimi Delta Attention (Kimi Linear, arXiv:2510.26692, section 3;
the public flash-linear-attention `KimiDeltaAttention` for what the
config leaves open), negative eigenvalues of the delta rule's transition
(Grazzi et al., arXiv:2411.12537) and gated attention (arXiv:2505.06708).
Straightforward `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`: the delta rule as a
`lax.scan` a TOKEN (no chunks), full causal attention (no cache), the
router written out, a Python loop over heads and over experts: one whole
sequence, every position. Attention runs a head at a time in blocks of
`_ROWS` query rows. Nothing here is imported from `paddle_tpu`, nor from
another reference: `reference/ling3.py`'s delta rule is copied, not
shared.

With d = hidden_size, eps = rms_norm_eps, rms(x; g) = g * x /
sqrt(mean(x^2) + eps):

  h_0 = E[tokens]
  layer i:  a = h + mix_i(rms(h; g_in));  h' = a + moe_i(rms(a; g_ff))
  logits  = rms(h_L; g_final) W_head        (W_head its own matrix)

Layer i is softmax attention iff i is in `gqa_layers`, else KDA. Every
layer (`first_k_dense_replace` 0) ends in routed experts with a shared
one. There is NO positional term anywhere (`use_rope` false): the
recurrences carry order.

KDA, u = rms(h; g_in), H = linear_attn_config.num_heads heads of dk =
linear_attn_config.head_dim (keys, queries and values alike;
`num_kv_heads` null: as many key/value heads), r = assumed.kda_rank:
  q^ = silu(conv(u W_q)), k^ = silu(conv(u W_k)), v = silu(conv(u W_v)):
      causal depthwise convolutions of short_conv_kernel_size taps, no
      bias, zeros before the start
  q = q^ / |q^|_2 dk^-1/2,  k = k^ / |k^|_2, a head at a time
      (|x|_2 = sqrt(sum x^2 + 1e-6))
  g_t = -exp(A_log_h) softplus((u W_fa) W_fb + dt_bias), dk channels a
      head, UNBOUNDED below (`kda_use_full_proj` false: W_fa (d, r), W_fb
      (r, H dk), Kimi Linear's bottleneck; no `kda_lower_bound`;
      ASSUMED: `assumed.kda_gate` = "softplus")
  beta_t = 2 sigmoid(u W_beta), one a head, in (0, 2)
      (`kda_allow_neg_eigval` true)
  S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T,
      S (dk, dk) a head, S_0 = 0;  o_t = S_t^T q_t
  y = (rms(o_t; g_o) * sigmoid((u W_ga) W_gb), a CHANNEL at a time) W_o
      (W_ga (d, r), W_gb (r, H dk): Kimi Linear's low-rank output gate;
      ASSUMED: `assumed.kda_output_gate`)

Softmax attention, Hq = num_attention_heads query heads on Hkv =
num_key_value_heads key/value heads of dh = head_dim, query head j on
key/value head j // (Hq / Hkv):
  q = u W_q, k = u W_k, v = u W_v, no norm, no rotation
  o_j = softmax_{s <= t}(q_j(t) . k(s) dh^-1/2) v
  y = (o * sigmoid(u W_g), elementwise; W_g (d, Hq dh)) W_o
      (`use_gqa_gate` true; ASSUMED: `assumed.attention_gate`)

Experts, x = rms(a; g_ff): s = softmax(x W_r) in float32 over all
`n_routed_experts_scored` (ASSUMED: `assumed.router_score`); S = the
num_experts_per_tok largest (ties to the lower index); w_e =
routed_scaling_factor s_e / sum_{j in S} s_j (`norm_topk_prob`); y =
sum_{e in S, e held} w_e E_e(x) + E_shared(x), E(x; W) = (silu(x W_gate)
* (x W_up)) W_down.

DEPARTURES from the published model, each also in the configuration's
file: float32 for bfloat16; `held` = [lo, hi) is the chip's share of the
routed experts (`experts_held`): what the absent experts would add is
left out, here as in the program, and that partial result goes on to
the next layer; the vocabulary is the slice the file states; no
capacity: every pair on a held expert is computed.

`precision` chooses how a matmul is computed and what is stored, for
the control of the correctness check (as `reference/mistral4.py`):
  "highest"  float32 operands, `jax.lax.Precision.HIGHEST` (the truth)
  "bf16_ops" matmul operands rounded to bfloat16, float32 accumulation,
             float32 everything else (what an f32 matmul is on a TPU at
             default precision: the arithmetic the configuration states);
             the delta rule's own multiplies and adds are float32
  "bf16"     as "bf16_ops", and every stored activation, delta-rule
             state, key and value rounded to bfloat16
A precision may name a variant after a `+` ("bf16_ops+no_decay"): the
forward pass with one part left out or put wrong, for the runs that show
that the comparison sees each mechanism (`VARIANTS`). The router's
scores are float32 at `highest` in every precision (the program computes
them so). Parameter names are the program's (`lm.l1.kda.f_a.w`,
`lm.l0.attention.gate.w`, `lm.l2.moe.experts.gate.w`: the held experts
alone).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "bf16_ops", "bf16")
# beta_one_max: beta = sigmoid, not doubled; decay_no_fb: the decay
# projected by W_fa alone, its `kda_rank` outputs handed to every head;
# bound_back: the bounded gate -5 sigmoid(exp(A_log) x); gate_per_head:
# a KDA head gated by the mean of its channels' gates; rotated: q and k
# of the attention layer rotated at theta `rope_theta`
VARIANTS = ("", "no_decay", "beta_one_max", "decay_no_fb", "bound_back",
            "gate_per_head", "no_attn_gate", "rotated", "no_shared",
            "state_stale")
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


def layer_kinds(cfg: dict, n_layer: int):
    """"gqa" | "kda" of layers 0..n_layer-1."""
    return ["gqa" if i in cfg["gqa_layers"] else "kda"
            for i in range(n_layer)]


# -- Kimi Delta Attention ----------------------------------------------------

def conv(x, w):
    """x (T, C), w (C, K): y[t] = sum_j w[:, j] x[t - K + 1 + j], zeros
    before the start."""
    t, k = x.shape[0], w.shape[1]
    xp = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return sum(xp[j:j + t] * w[:, j] for j in range(k))


@functools.partial(jax.jit, static_argnames=("precision", "stale"))
def delta_rule(q, k, v, g, beta, precision, stale=-1):
    """The recurrence a token at a time: q, k, g (T, H, dk), v (T, H,
    dv), beta (T, H) -> o (T, H, dv). `stale`: the token whose update
    the carried state misses (its own o sees it)."""
    _, store = make_ops(precision)
    t, h, dk = q.shape

    def body(s, inp):
        i, q_t, k_t, v_t, g_t, b_t = inp
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
    lin = cfg["linear_attn_config"]
    h, dk, eps = lin["num_heads"], lin["head_dim"], cfg["rms_norm_eps"]
    assert lin["num_kv_heads"] is None
    parts = []
    for n in "qkv":
        x = conv(mm(u, p[n + ".w"]), p["conv_%s.w" % n])
        parts.append(store(_silu(x)).reshape(t, h, dk))
    q, k, v = parts
    q, k = (x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                              + 1e-6) for x in (q, k))
    q = q * float(dk) ** -0.5
    f = mm(u, p["f_a.w"])
    if variant == "decay_no_fb":
        f = jnp.tile(f, (1, h * dk // f.shape[-1]))
    else:
        f = mm(f, p["f_b.w"])
    x = (f + p["dt_bias"]).reshape(t, h, dk)
    a = jnp.exp(p["A_log"])[None, :, None]
    assert cfg["assumed"]["kda_gate"] == "softplus"
    g = -a * jax.nn.softplus(x)
    if variant == "bound_back":
        g = -5.0 * jax.nn.sigmoid(a * x)
    if variant == "no_decay":
        g = jnp.zeros_like(g)
    assert cfg["kda_allow_neg_eigval"]
    beta = 2.0 * jax.nn.sigmoid(mm(u, p["beta.w"]))
    if variant == "beta_one_max":
        beta = 0.5 * beta
    stale = handover if variant == "state_stale" else -1
    o = delta_rule(store(q), store(k), v, store(g), store(beta), precision,
                   stale)
    o = _rms(o, p["o_norm.w"], eps)
    gate = jax.nn.sigmoid(mm(mm(u, p["gate_a.w"]), p["gate_b.w"])).reshape(
        t, h, dk)
    if variant == "gate_per_head":
        gate = jnp.mean(gate, axis=-1, keepdims=True)
    return mm(store((o * gate).reshape(t, h * dk)), p["o.w"])


# -- softmax attention -------------------------------------------------------

def rotate(x, theta: float):
    """x (T, H, dh) rotated whole at positions 0..T-1, halves paired
    (channel i with i + dh / 2): the `rotated` variant's, which the
    model does NOT do."""
    t, r = x.shape[0], x.shape[-1]
    inv = 1.0 / (float(theta) ** (np.arange(0, r, 2, dtype=np.float64) / r))
    ang = (jnp.arange(t, dtype=jnp.float32)[:, None]
           * jnp.asarray(inv, jnp.float32)[None, :])[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("precision",))
def _attend_head(q, k, v, row0, precision):
    """A block of one head's query rows: q (n, dh) pre-scaled, at
    positions row0..row0 + n - 1; k, v (T, dh)."""
    mm, store = make_ops(precision)
    n, t = q.shape[0], k.shape[0]
    s = mm(q, k.T)
    seen = jnp.arange(t)[None, :] <= row0 + jnp.arange(n)[:, None]
    w = store(jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1))
    return mm(w, v)


def attention(p, u, cfg, precision, variant=""):
    """u (T, d) -> (T, d): the gated NoPE layer."""
    mm, store = make_ops(precision)
    assert not cfg["use_rope"] and cfg["use_gqa_gate"]
    t = u.shape[0]
    hq, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    q = mm(u, p["q.w"]).reshape(t, hq, dh)
    k = mm(u, p["k.w"]).reshape(t, hkv, dh)
    v = store(mm(u, p["v.w"]).reshape(t, hkv, dh))
    if variant == "rotated":
        q, k = rotate(q, cfg["rope_theta"]), rotate(k, cfg["rope_theta"])
    q, k = store(q), store(k)
    a = float(dh) ** -0.5
    out = []
    for j in range(hq):  # a head at a time, a block of rows at a time
        kv = j // (hq // hkv)
        out.append(jnp.concatenate([
            _attend_head(q[r0:r0 + _ROWS, j] * a, k[:, kv], v[:, kv], r0,
                         precision)
            for r0 in range(0, t, _ROWS)]))
    ctx = jnp.stack(out, axis=1).reshape(t, hq * dh)
    if variant != "no_attn_gate":
        assert cfg["assumed"]["attention_gate"] == "elementwise"
        ctx = ctx * jax.nn.sigmoid(mm(u, p["gate.w"]))
    return mm(store(ctx), p["o.w"])


# -- routed experts ----------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("precision",))
def gated_mlp(x, w_gate, w_up, w_down, precision):
    mm, store = make_ops(precision)
    gate = store(_silu(mm(x, w_gate)))
    up = store(mm(x, w_up))
    return mm(store(gate * up), w_down)


def route(x, w_router, cfg):
    """(idx (T, k), weights (T, k)) over ALL routed experts, float32 at
    `highest` whatever the precision: softmax scores, the largest k,
    renormalised, times `routed_scaling_factor`."""
    assert cfg["assumed"]["router_score"] == "softmax"
    assert cfg["norm_topk_prob"]
    s = jax.nn.softmax(jnp.matmul(x, w_router, precision=_HI), axis=-1)
    top, idx = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    return idx, top * cfg["routed_scaling_factor"]


def moe(p, x, cfg, precision, held=None, shared=True):
    """x (T, d) -> (T, d): the experts `held` = [lo, hi) (default: the
    configuration's `experts_held`) and, with `shared`, the shared
    expert. `p["experts.*.w"]` hold the held experts alone, in order."""
    lo, hi = held if held is not None else cfg["experts_held"]
    idx, w = route(x, p["router.w"], cfg)
    y = jnp.zeros_like(x)
    for e in range(lo, hi):  # every pair on a held expert, no capacity
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        y = y + w_e[:, None] * gated_mlp(
            x, p["experts.gate.w"][e - lo], p["experts.up.w"][e - lo],
            p["experts.down.w"][e - lo], precision)
    if shared:
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
    prefill to the step (`state_stale`)."""
    if variant not in VARIANTS:
        raise ValueError(variant)
    _, store = make_ops(precision)
    eps = cfg["rms_norm_eps"]
    assert cfg["first_k_dense_replace"] == 0 and cfg["n_shared_experts"] == 1
    with jax.default_matmul_precision("highest"):
        h = store(params["lm.tok_emb"][tokens])
        for i, mixer in enumerate(layer_kinds(cfg, n_layer)):
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
            h = store(h + moe(_sub(p, "moe."), u, cfg, precision,
                              shared=variant != "no_shared"))
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
