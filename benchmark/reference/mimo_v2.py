"""Plain reference of Xiaomi's MiMo-V2-Flash decoder LM (`model_type:
mimo_v2_flash`;
https://huggingface.co/XiaomiMiMo/MiMo-V2-Flash/blob/main/config.json),
written from the configuration's keys alone. Straightforward
`jax.numpy`, float32, `jax.default_matmul_precision("highest")`, no
kernel, no cache, no batching, a Python loop over experts: one whole
sequence, every position; attention a block of 256 queries at a time,
so that 12,000 tokens fit. Nothing here is imported from `paddle_tpu`.

With d = hidden_size, eps = layernorm_epsilon and rms(x; g) = g * x /
sqrt(mean(x^2) + eps):

  h_0 = E[tokens]
  layer i:  a = h + attn_i(rms(h; g_in));  h' = a + ffn_i(rms(a; g_ff))
  logits  = rms(h_L; g_final) W_head        (W_head its own matrix)

Attention, layer i, `hybrid_layer_pattern[i]` 0 (full) or 1 (sliding):
H = num_attention_heads (64) query heads of head_dim (192) on H_kv key
heads of 192 and value heads of v_head_dim (128), H_kv =
num_key_value_heads (4) on a full layer, swa_num_key_value_heads (8) on
a sliding one (query head h reads K/V head h // (H / H_kv)), no bias.
v = attention_value_scale * (u W_v) (`assumed.value_scale_on` "v": a
scalar, so on v or on the output is one number). Rotary positions in the
half-split convention (`assumed.rope_layout`) over the FIRST r =
int(partial_rotary_factor * head_dim) = 64 channels of every q and k
head, inv_freq_d = theta^(-2d / r), theta = rope_theta on a full layer
and swa_rope_theta on a sliding one; the other 128 channels are not
rotated. Scores z_ij = q_i . k_j / sqrt(head_dim); key j is visible to
query i iff j <= i and, on a sliding layer, i - j < sliding_window
(`assumed.window_counts_self`: the query's own position is one of the
128). A full layer: a_ij = softmax_j z_ij. A sliding layer
(`add_swa_attention_sink_bias`): one learned scalar s_h a query head
joins the denominator and takes no value,

  a_ij = exp(z_ij) / (exp(s_h) + sum_j' exp(z_ij'))

computed as written: the sink's column appended to the scores, a
softmax, the column dropped. Then concat_h(sum_j a_ij v_j) W_o, no gate,
no query/key norm (no key names one).

FFN: E(x; W) = (silu(x W_gate) * (x W_up)) W_down. `moe_layer_freq[i]`
0: E at intermediate_size. 1: s = sigmoid(x W_r) in float32 over all
`n_routed_experts_scored` experts; S = the num_experts_per_tok largest
of s + b (`topk_method` noaux_tc, `n_group` 1: b chooses, s weighs; ties
to the lower index); w_e = s_e / sum_{j in S} s_j (`norm_topk_prob`),
times `routed_scaling_factor` (null: 1); y = sum_{e in S, e held} w_e
E_e(x). No shared expert (`n_shared_experts` null). `held` = [lo, hi) is
the chip's share of the experts (the configuration's `experts_held`):
what the absent experts would add is left out, here as in the program,
and that partial result goes on to the next layer. No capacity.

DEPARTURES from the published description: `described_as` speaks of 3
multi-token-prediction layers and (V2.5) a vision and an audio encoder;
the catalog's `config` has no key for any of them and none is computed.
The vocabulary is the slice the configuration holds.

`precision` chooses how a matmul is computed and what is stored, for
the control of the correctness check (as `reference/laguna.py`):
  "highest"  float32 operands, `jax.lax.Precision.HIGHEST` (the truth)
  "bf16_ops" matmul operands rounded to bfloat16, float32 accumulation,
             float32 everything else (what an f32 matmul is on a TPU at
             default precision: the arithmetic the configuration states)
  "bf16"     as "bf16_ops", and every stored activation, key and value
             rounded to bfloat16
A precision may name a variant after a `+` ("bf16_ops+no_sink"): the
forward pass with one part left out or changed, for the runs that show
that the comparison sees each mechanism (`VARIANTS`). The router's
scores are float32 at `highest` in every precision. Parameter names are
the program's (`lm.l1.moe.experts.gate.w`, the held experts alone, (hi
- lo, d, f); `lm.l1.attention.sink`, (H,)).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "bf16_ops", "bf16")
# what `variant` leaves out or changes, one at a time
VARIANTS = (
    "no_sink",         # the sliding layers' softmax without its sink
    "no_value_scale",  # v = u W_v
    "sliding_kv4",     # sliding layers read 4 of their 8 K/V heads, 16
                       # query heads a head (the full layers' grouping)
    "full_kv8",        # full layers group 8 query heads a K/V head (the
                       # sliding layers' grouping), over their 4 heads
    "thetas_swapped",  # rope_theta on sliding, swa_rope_theta on full
    "rope_all",        # all 192 channels rotated
    "window_short",    # a window one row short
    "no_routed",       # all routed experts out
    "no_renorm",       # the chosen scores not renormalised
    "no_bias",         # the 8 largest of s, not of s + b
)
_HI = jax.lax.Precision.HIGHEST
_BLOCK_Q = 256    # queries a block of attention
_BLOCK_ROWS = 2048  # rows a block of the dense MLP


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


def layer_kinds(cfg: dict):
    """"full" | "sliding" layer by layer."""
    return ["sliding" if p else "full" for p in cfg["hybrid_layer_pattern"]]


# -- rotary positions --------------------------------------------------------

def rotate(x, theta: float, r: int):
    """x (T, H, Dh): the first `r` channels of each head rotated at the
    positions 0..T-1, half-split pairs (d, d + r / 2); the rest as they
    are."""
    t = x.shape[0]
    half = r // 2
    inv = 1.0 / (float(theta) ** (np.arange(0, r, 2, dtype=np.float64) / r))
    ang = (jnp.arange(t, dtype=jnp.float32)[:, None]
           * jnp.asarray(inv, jnp.float32)[None, :])
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:r], x[..., r:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


# -- attention ---------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("window", "precision"))
def _attend_block(q, k, v, row0, sink, window, precision):
    """One key/value head, one block of queries: q (g, BQ, dk)
    pre-scaled, the queries at positions row0..; k (T, dk), v (T, dv)
    every position; sink (g,) or None. -> (g, BQ, dv)."""
    mm, store = make_ops(precision)
    g, bq, _ = q.shape
    t = k.shape[0]
    s = mm(q, k.T)                                          # (g, BQ, T)
    row = row0 + jnp.arange(bq)[:, None]
    col = jnp.arange(t)[None, :]
    seen = col <= row
    if window:
        seen = seen & (row - col < window)
    s = jnp.where(seen, s, -jnp.inf)
    if sink is not None:
        # the sink's column appended, the softmax, the column dropped
        s = jnp.concatenate(
            [s, jnp.broadcast_to(sink[:, None, None], (g, bq, 1))], axis=-1)
    w = jax.nn.softmax(s, axis=-1)[..., :t]
    return mm(store(w), v)


def attention(p, u, i, cfg, precision, variant=""):
    """u (T, d) -> (T, d): layer i's attention. `p` holds the mixer's
    parameters by their last name parts (`q.w`, ..., `sink`)."""
    mm, store = make_ops(precision)
    t = u.shape[0]
    sliding = layer_kinds(cfg)[i] == "sliding"
    h, dk, dv = (cfg["num_attention_heads"], cfg["head_dim"],
                 cfg["v_head_dim"])
    hkv = (cfg["swa_num_key_value_heads"] if sliding
           else cfg["num_key_value_heads"])
    theta = cfg["swa_rope_theta"] if sliding else cfg["rope_theta"]
    if variant == "thetas_swapped":
        theta = cfg["rope_theta"] if sliding else cfg["swa_rope_theta"]
    r = int(cfg["partial_rotary_factor"] * dk)
    if variant == "rope_all":
        r = dk
    window = cfg["sliding_window"] if sliding else 0
    if variant == "window_short" and window:
        window -= 1
    q = store(rotate(mm(u, p["q.w"]).reshape(t, h, dk), theta, r))
    k = store(rotate(mm(u, p["k.w"]).reshape(t, hkv, dk), theta, r))
    v = mm(u, p["v.w"]).reshape(t, hkv, dv)
    if variant != "no_value_scale":
        v = v * cfg["attention_value_scale"]
    v = store(v)
    sink = (p["sink"] if sliding and cfg["add_swa_attention_sink_bias"]
            and variant != "no_sink" else None)
    groups = hkv
    if (variant == "sliding_kv4" and sliding) or (variant == "full_kv8"
                                                  and not sliding):
        groups = (cfg["num_key_value_heads"] if sliding
                  else cfg["swa_num_key_value_heads"])
    g = h // groups
    q = (q * dk ** -0.5).transpose(1, 0, 2)                 # (H, T, dk)
    out = []
    for j in range(groups):     # a key/value head at a time,
        rows = []
        for row0 in range(0, t, _BLOCK_Q):  # a block of queries at a time
            rows.append(_attend_block(
                q[j * g:(j + 1) * g, row0:row0 + _BLOCK_Q], k[:, j % hkv],
                v[:, j % hkv], row0,
                None if sink is None else sink[j * g:(j + 1) * g],
                window, precision))
        out.append(jnp.concatenate(rows, axis=1))
    ctx = jnp.concatenate(out, axis=0).transpose(1, 0, 2)   # (T, H, dv)
    return mm(store(ctx.reshape(t, h * dv)), p["o.w"])


# -- feed-forward ------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("precision",))
def gated_mlp(x, w_gate, w_up, w_down, precision):
    mm, store = make_ops(precision)
    gate = store(_silu(mm(x, w_gate)))
    up = store(mm(x, w_up))
    return mm(store(gate * up), w_down)


def dense_mlp(x, w_gate, w_up, w_down, precision):
    """`gated_mlp` a block of rows at a time (16,384 wide: 12,000 rows
    of it at once are 0.8 GB an activation)."""
    return jnp.concatenate([
        gated_mlp(x[r:r + _BLOCK_ROWS], w_gate, w_up, w_down, precision)
        for r in range(0, x.shape[0], _BLOCK_ROWS)], axis=0)


def route(x, w_router, bias, cfg, variant=""):
    """(idx (T, k), weights (T, k)) over ALL routed experts, float32 at
    `highest` whatever the precision: chosen by s + b, weighed by s."""
    assert cfg["scoring_func"] == "sigmoid" and cfg["n_group"] == 1
    s = jax.nn.sigmoid(jnp.matmul(x, w_router, precision=_HI))
    chosen_by = s if variant == "no_bias" else s + bias
    _, idx = jax.lax.top_k(chosen_by, cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"] and variant != "no_renorm":
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return idx, top * float(cfg["routed_scaling_factor"] or 1.0)


def moe(p, x, cfg, precision, held=None, variant=""):
    """x (T, d) -> (T, d): the experts `held` = [lo, hi) of a sparse
    layer (default: the configuration's `experts_held`).
    `p["experts.*.w"]` hold the held experts alone, in order."""
    lo, hi = held if held is not None else cfg["experts_held"]
    idx, w = route(x, p["router.w"], p["router.bias"], cfg, variant)
    y = jnp.zeros_like(x)
    if variant == "no_routed":
        lo = hi
    for e in range(lo, hi):  # every pair on a held expert, no capacity
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        y = y + w_e[:, None] * gated_mlp(
            x, p["experts.gate.w"][e - lo], p["experts.up.w"][e - lo],
            p["experts.down.w"][e - lo], precision)
    return y


# -- the model ---------------------------------------------------------------

def _sub(p, prefix):
    return {n[len(prefix):]: v for n, v in p.items() if n.startswith(prefix)}


def hidden(params, tokens, cfg, n_layer, precision="highest", variant=""):
    """Final-norm output (T, d) of one sequence `tokens` (T,), a layer
    at a time. `variant`: one of `VARIANTS`, or none."""
    if variant and variant not in VARIANTS:
        raise ValueError("variant %r is none of %s" % (variant, VARIANTS))
    _, store = make_ops(precision)
    eps = cfg["layernorm_epsilon"]
    with jax.default_matmul_precision("highest"):
        h = store(params["lm.tok_emb"][tokens])
        for i in range(n_layer):
            p = _sub(params, "lm.l%d." % i)
            u = store(_rms(h, p["norm_in.w"], eps))
            h = store(h + attention(_sub(p, "attention."), u, i, cfg,
                                    precision, variant))
            u = store(_rms(h, p["norm_ff.w"], eps))
            if cfg["moe_layer_freq"][i]:
                f = moe(_sub(p, "moe."), u, cfg, precision, variant=variant)
            else:
                f = dense_mlp(u, p["mlp.gate.w"], p["mlp.up.w"],
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
