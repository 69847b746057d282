"""Plain reference of poolside's Laguna decoder LM (`model_type:
laguna`; https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json),
written from the configuration's keys alone. Straightforward
`jax.numpy`, float32, `jax.default_matmul_precision("highest")`, no
kernel, no cache, no batching, a Python loop over experts: one whole
sequence, every position. Nothing here is imported from `paddle_tpu`.

With d = hidden_size, Dh = head_dim, eps = rms_norm_eps and
rms(x; g) = g * x / sqrt(mean(x^2) + eps):

  h_0 = E[tokens]
  layer i:  a = h + attn_i(rms(h; g_in));  h' = a + ffn_i(rms(a; g_ff))
  logits  = rms(h_L; g_final) W_head        (W_head its own matrix)

Attention, layer i: H_i = num_attention_heads_per_layer[i] query heads
on num_key_value_heads key/value heads of Dh (query head h reads K/V
head h // (H_i / Hkv)), no bias. Rotary positions in the half-split
convention, over the first r = Dh * partial_rotary_factor channels of
each head, at the token's absolute position, by
`rope_parameters[layer_types[i]]`: "default" inv_freq_d =
theta^(-2d/r); "yarn" as `transformers`' `_compute_yarn_parameters`
(the blended frequencies, cos and sin times `attention_factor`). Scores
q.k / sqrt(Dh); key j is visible to query t iff j <= t, and on a
`sliding_attention` layer also j > t - sliding_window; softmax in
float32. The output of head h is multiplied by sigmoid(x W_g)[h]
(`model.attention_gate` = "per_head": ASSUMED, see the configuration's
file), then concat(heads) W_o.

FFN: E(x; W) = (silu(x W_gate) * (x W_up)) W_down. A `dense` layer: E
at intermediate_size. A `sparse` layer: s = sigmoid(x W_r) in float32
over all `num_experts_routed` experts (`model.router_score`: ASSUMED);
S = the num_experts_per_tok largest (ties to the lower index); w_e =
moe_routed_scaling_factor * s_e / sum_{j in S} s_j; y = sum_{e in S, e
held} w_e E_e(x) + E_shared(x). `held` = [lo, hi) is the chip's share
of the experts (the configuration's `experts_held`): what the absent
experts would add is left out, here as in the program, and that partial
result goes on to the next layer. No capacity: every pair is computed.

`precision` chooses how a matmul is computed and what is stored, for
the control of the correctness check (as `reference/jamba.py`):
  "highest"  float32 operands, `jax.lax.Precision.HIGHEST` (the truth)
  "bf16_ops" matmul operands rounded to bfloat16, float32 accumulation,
             float32 everything else (what an f32 matmul is on a TPU at
             default precision: the arithmetic the configuration states)
  "bf16"     as "bf16_ops", and every stored activation, key and value
             rounded to bfloat16
A precision may name a variant after a `+` ("bf16_ops+no_shared"): the
forward pass with one part left out, for the runs that show that the
comparison sees each mechanism (`hidden`).
The router's scores are float32 at `highest` in every precision (the
program computes them so: a rounding there moves a discontinuous
choice, not a value). Parameter names are the program's
(`lm.l1.moe.experts.gate.w`, the held experts alone, (hi - lo, d, f)).
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "bf16_ops", "bf16")
_HI = jax.lax.Precision.HIGHEST


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


# -- rotary positions --------------------------------------------------------

def inv_freq(p: dict, head_dim: int) -> np.ndarray:
    """(r / 2,) float64 inverse frequencies of one `rope_parameters`
    entry."""
    r = int(round(head_dim * p["partial_rotary_factor"]))
    base = float(p["rope_theta"])
    pos_freqs = base ** (np.arange(0, r, 2, dtype=np.float64) / r)
    extra = 1.0 / pos_freqs
    if p["rope_type"] == "default":
        return extra
    assert p["rope_type"] == "yarn", p["rope_type"]
    inter = 1.0 / (float(p["factor"]) * pos_freqs)
    orig = float(p["original_max_position_embeddings"])

    def c(rotations):
        return (r * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(c(float(p["beta_fast"]))), 0)
    high = min(math.ceil(c(float(p["beta_slow"]))), r - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(r // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def rotate(x, p: dict, positions=None):
    """x (T, H, Dh) rotated at `positions` (T,) (None: 0..T-1)."""
    t, _, dh = x.shape
    inv = inv_freq(p, dh)
    half = len(inv)
    pos = (jnp.arange(t, dtype=jnp.float32) if positions is None
           else jnp.asarray(positions, jnp.float32))
    ang = pos[:, None] * jnp.asarray(inv, jnp.float32)[None, :]
    factor = float(p.get("attention_factor", 1.0)
                   if p["rope_type"] == "yarn" else 1.0)
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


# -- attention ---------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("window", "precision"))
def _attend_group(q, k, v, window, precision):
    """One key/value head: q (g, T, Dh) pre-scaled, k, v (T, Dh)."""
    mm, store = make_ops(precision)
    t = k.shape[0]
    s = mm(q, k.T)
    row = jnp.arange(t)[:, None]
    col = jnp.arange(t)[None, :]
    seen = col <= row
    if window:
        seen = seen & (col > row - window)
    w = store(jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1))
    return mm(w, v)


def attention(p, u, i, cfg, precision, variant=""):
    """u (T, d) -> (T, d): layer i's attention. `p` holds the mixer's
    parameters by their last name parts (`q.w`, ...)."""
    mm, store = make_ops(precision)
    t = u.shape[0]
    h = cfg["num_attention_heads_per_layer"][i]
    hkv, dh = cfg["num_key_value_heads"], cfg["head_dim"]
    kind = cfg["layer_types"][i]
    rot = cfg["rope_parameters"][kind]
    window = (cfg["sliding_window"] if kind == "sliding_attention" else 0)
    if variant == "ring_row_short" and window:
        window -= 1  # a ring that loses its oldest row
    q = store(rotate(mm(u, p["q.w"]).reshape(t, h, dh), rot))
    k = store(rotate(mm(u, p["k.w"]).reshape(t, hkv, dh), rot))
    v = store(mm(u, p["v.w"]).reshape(t, hkv, dh))
    g = h // hkv
    q = (q * dh ** -0.5).transpose(1, 0, 2).reshape(hkv, g, t, dh)
    # a key/value head at a time, so that (g, T, T) scores fit
    out = [_attend_group(q[j], k[:, j], v[:, j], window, precision)
           for j in range(hkv)]
    ctx = jnp.stack(out).reshape(h, t, dh).transpose(1, 0, 2)  # (T, H, Dh)
    if cfg["model"]["attention_gate"] == "per_head" \
            and variant != "no_gate":
        ctx = ctx * jax.nn.sigmoid(mm(u, p["gate.w"]))[:, :, None]
    elif cfg["model"]["attention_gate"] not in (None, "per_head"):
        raise ValueError(cfg["model"]["attention_gate"])
    return mm(store(ctx.reshape(t, h * dh)), p["o.w"])


# -- feed-forward ------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("precision",))
def gated_mlp(x, w_gate, w_up, w_down, precision):
    mm, store = make_ops(precision)
    gate = store(_silu(mm(x, w_gate)))
    up = store(mm(x, w_up))
    return mm(store(gate * up), w_down)


def route(x, w_router, cfg, variant=""):
    """(idx (T, k), weights (T, k)) over ALL routed experts, float32 at
    `highest` whatever the precision."""
    assert cfg["model"]["router_score"] == "sigmoid"
    s = jax.nn.sigmoid(jnp.matmul(x, w_router, precision=_HI))
    top, idx = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    if variant != "no_renorm":
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return idx, top * cfg["moe_routed_scaling_factor"]


def moe(p, x, cfg, precision, held=None, shared=True, variant=""):
    """x (T, d) -> (T, d): the experts `held` = [lo, hi) of a sparse
    layer (default: the configuration's `experts_held`) and, with
    `shared`, the shared expert. `p["experts.*.w"]` hold the held
    experts alone, in order."""
    lo, hi = held if held is not None else cfg["experts_held"]
    idx, w = route(x, p["router.w"], cfg, variant)
    y = jnp.zeros_like(x)
    if variant == "no_routed":
        lo = hi
    # "capacity_drop": the capacity-factor layer's rule in this layer's
    # place: an expert takes 1.25 x the mean load of the sequence, in
    # token order, and a pair past that gets no weight
    for e in range(lo, hi):  # every pair on a held expert, no capacity
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        if variant == "capacity_drop":
            cap = math.ceil(1.25 * x.shape[0] * cfg["num_experts_per_tok"]
                            / cfg["num_experts_routed"])
            w_e = jnp.where(jnp.cumsum(w_e > 0) <= cap, w_e, 0.0)
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
    at a time. `variant` leaves one thing out, for the runs that show
    the comparison sees it: "no_shared", "no_gate", "no_renorm",
    "no_routed" (the shared expert alone), "capacity_drop" (pairs past
    an expert's capacity dropped), "ring_row_short" (a window one row
    short)."""
    _, store = make_ops(precision)
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        h = store(params["lm.tok_emb"][tokens])
        for i in range(n_layer):
            p = _sub(params, "lm.l%d." % i)
            u = store(_rms(h, p["norm_in.w"], eps))
            h = store(h + attention(_sub(p, "attention."), u, i, cfg,
                                    precision, variant))
            u = store(_rms(h, p["norm_ff.w"], eps))
            if cfg["mlp_layer_types"][i] == "sparse":
                f = moe(_sub(p, "moe."), u, cfg, precision, variant=variant)
            else:
                f = gated_mlp(u, p["mlp.gate.w"], p["mlp.up.w"],
                              p["mlp.down.w"], precision)
            h = store(h + f)
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
