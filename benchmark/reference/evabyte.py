"""Plain reference of EvaByte (`model_type: evabyte`;
https://huggingface.co/EvaByte/EvaByte/blob/main/config.json), written
from the configuration's keys and the published description of EVA
(Zheng et al., "Efficient Attention via Control Variates", ICLR 2023) as
EvaByte uses it. Straightforward `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`, no kernel, no cache, no
batching: one whole sequence, every position, the attention's mask built
from the DEFINITION of what a query sees. Nothing here is imported from
`paddle_tpu`.

With d = hidden_size, H = num_attention_heads (= num_key_value_heads)
heads of Dh = d / H, eps = rms_norm_eps, W = window_size, C = chunk_size,
s = Dh^-1/2 and N(x; g) = x / sqrt(mean(x^2) + eps) * (1 + g)
(`norm_add_unit_offset`: the parameter is the gain's distance from one):

  h_0 = E[tokens]                       (float32 residual, `fp32_skip_add`)
  layer i:  a = h + Attn_i(N(h; g_in));  h' = a + MLP_i(N(a; g_ff))
  MLP(n)  = (silu(n W_gate) * (n W_up)) W_down
  logits  = N(h_L; g_final) W_head      (320 columns, float32 at
                                         `highest`: `fp32_logits`)

Attn(u): q, k, v = u W_q, u W_k, u W_v in heads of Dh; q and k rotated at
their absolute positions (theta = rope_theta over all Dh channels,
channel i paired with i + Dh/2). Position t lies in window w(t) =
floor(t / W); chunk c holds positions [C c, C c + C) and lies in window
floor(C c / W). A head's two learned vectors phi, mu in R^Dh
(`attention.phi`, `attention.mu`: the source's `adaptive_phi`,
`adaptive_mu_k`) pool the ROTATED keys of a chunk:

  a_i  = softmax_{i in chunk c}(s phi . k_i)        (model.eva_pool_logit)
  k~_c = sum_i a_i k_i + mu                         (model.eva_key_offset)
  v~_c = sum_i a_i v_i                              (model.eva_pool_rotated)

and the query at t attends, under ONE softmax with scale s, the exact
keys E(t) = {j : W w(t) <= j <= t} and the summaries R(t) = {c : c <
(W / C) w(t)}: the chunks of every window CLOSED before its own (a chunk
is never seen before its whole window has closed). Then W_o. The four
conventions the source's keys leave open are the configuration's
`model` fields (ASSUMED there, each with its reasoning); `check_assumed`
refuses another value.

`precision` chooses how a matmul is computed and what is stored, for the
control of the correctness check (as `reference/laguna.py`):
  "highest"  float32 operands, `jax.lax.Precision.HIGHEST` (the truth)
  "bf16_ops" matmul operands rounded to bfloat16, float32 accumulation,
             float32 everything else (what an f32 matmul is on a TPU at
             default precision: the arithmetic the configuration states).
             The pooling is float32 multiplies and adds in every
             precision, and the head is at `highest`, as the program's
  "bf16"     as "bf16_ops", and every stored activation, key, value and
             SUMMARY rounded to bfloat16
A precision may name a variant after a `+` ("bf16_ops+no_mu"): the
forward pass with one part changed, for the runs that show which parts
the comparison sees (`VARIANTS`). Parameter names are the program's
(`lm.l1.attention.phi`).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "bf16_ops", "bf16")
_HI = jax.lax.Precision.HIGHEST

# the ONE value of each ASSUMED convention that is computed here
ASSUMED = {"eva_pool_logit": "scaled_phi_dot_key",
           "eva_key_offset": "added_to_pooled_key",
           "eva_pool_rotated": "after_rotation",
           "head": "first_vocab_size_columns"}

# what a variant changes (`hidden`)
VARIANTS = ("no_summaries", "no_mu", "mean_pool", "unrotated_pool",
            "ring_block", "early_summaries", "no_unit_offset")

# query rows a block of the attention holds: (H, rows, T / C + T) scores
_ROWS = 512


def check_assumed(cfg: dict) -> None:
    for key, only in ASSUMED.items():
        if cfg["model"].get(key) != only:
            raise ValueError("model.%s = %r: only %r is computed"
                             % (key, cfg["model"].get(key), only))


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


def _norm(x, g, eps, offset=True):
    gain = 1.0 + g if offset else g
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * gain


def _silu(x):
    return x * jax.nn.sigmoid(x)


def rotate(x, theta: float):
    """x (T, H, Dh) rotated at positions 0..T-1: channel i with i + Dh/2,
    frequency theta^(-2i/Dh)."""
    t, _, dh = x.shape
    half = dh // 2
    inv = 1.0 / (float(theta) ** (np.arange(0, dh, 2, dtype=np.float64)
                                  / dh))
    ang = (jnp.arange(t, dtype=jnp.float32)[:, None]
           * jnp.asarray(inv, jnp.float32)[None, :])
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def summaries(k, v, phi, mu, chunk, scale, variant=""):
    """Rotated keys k and values v (T, H, Dh) -> (k~, v~) (T // C, H,
    Dh) of the WHOLE chunks, in float32 multiplies and adds."""
    t, h, dh = k.shape
    n = t // chunk
    kc = k[:n * chunk].reshape(n, chunk, h, dh)
    vc = v[:n * chunk].reshape(n, chunk, h, dh)
    logit = scale * jnp.sum(kc * phi, axis=-1)               # (n, C, H)
    if variant == "mean_pool":
        logit = jnp.zeros_like(logit)
    a = jax.nn.softmax(logit, axis=1)[..., None]
    ks = jnp.sum(a * kc, axis=1)
    if variant != "no_mu":
        ks = ks + mu
    return ks, jnp.sum(a * vc, axis=1)


def seen(t0, rows, n_keys, n_sum, window, chunk, variant=""):
    """(rows, n_sum + n_keys) bool: what queries t0 .. t0 + rows - 1 see
    of the concatenated [summaries | keys] axis, from the definition of
    E(t) and R(t)."""
    t = (t0 + jnp.arange(rows))[:, None]
    j = jnp.arange(n_keys)[None, :]
    c = jnp.arange(n_sum)[None, :]
    w = t // window
    if variant == "ring_block":  # a block that never restarts
        exact = (j <= t) & (j > t - window)
    else:
        exact = (j >= window * w) & (j <= t)
    if variant == "no_summaries":
        pooled = jnp.zeros((rows, n_sum), bool)
    elif variant == "early_summaries":  # every chunk closed before t
        pooled = (c < (window // chunk) * (w + 1)) & (chunk * (c + 1) <= t)
    else:
        pooled = c < (window // chunk) * w
    return jnp.concatenate([jnp.broadcast_to(pooled, (rows, n_sum)), exact],
                           axis=1)


@functools.partial(jax.jit, static_argnames=(
    "window", "chunk", "n_sum", "precision", "variant"))
def _attend_rows(q, keys, vals, t0, window, chunk, n_sum, precision,
                 variant):
    """Query rows q (rows, H, Dh) pre-scaled, from position t0, on keys
    and vals (n_sum + T, H, Dh) -> (rows, H, Dh)."""
    mm, store = make_ops(precision)
    rows = q.shape[0]
    s = mm(q.transpose(1, 0, 2), keys.transpose(1, 2, 0))    # (H, rows, S)
    mask = seen(t0, rows, keys.shape[0] - n_sum, n_sum, window, chunk,
                variant)
    p = store(jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1))
    return mm(p, vals.transpose(1, 0, 2)).transpose(1, 0, 2)


def attention(p, u, cfg, precision, variant=""):
    """u (T, d) -> (T, d). `p` holds the mixer's parameters by their
    last name parts (`q.w`, `phi`, ...)."""
    mm, store = make_ops(precision)
    t = u.shape[0]
    h = cfg["num_attention_heads"]
    assert cfg["num_key_value_heads"] == h
    dh = cfg["hidden_size"] // h
    w, c, scale = cfg["window_size"], cfg["chunk_size"], dh ** -0.5
    theta = cfg["rope_theta"]
    q = store(rotate(mm(u, p["q.w"]).reshape(t, h, dh), theta))
    k_plain = mm(u, p["k.w"]).reshape(t, h, dh)
    k = store(rotate(k_plain, theta))
    v = store(mm(u, p["v.w"]).reshape(t, h, dh))
    ks, vs = summaries(store(k_plain) if variant == "unrotated_pool" else k,
                       v, p["phi"], p["mu"], c, scale, variant)
    ks, vs = store(ks), store(vs)
    keys = jnp.concatenate([ks, k], axis=0)
    vals = jnp.concatenate([vs, v], axis=0)
    out = [_attend_rows(q[t0:t0 + _ROWS] * scale, keys, vals, t0, w, c,
                        ks.shape[0], precision, variant)
           for t0 in range(0, t, _ROWS)]
    ctx = jnp.concatenate(out, axis=0)
    return mm(store(ctx.reshape(t, h * dh)), p["o.w"])


@functools.partial(jax.jit, static_argnames=("precision",))
def gated_mlp(x, w_gate, w_up, w_down, precision):
    mm, store = make_ops(precision)
    gate = store(_silu(mm(x, w_gate)))
    up = store(mm(x, w_up))
    return mm(store(gate * up), w_down)


def _sub(p, prefix):
    return {n[len(prefix):]: v for n, v in p.items() if n.startswith(prefix)}


def hidden(params, tokens, cfg, n_layer, precision="highest", variant=""):
    """Final-norm output (T, d) of one sequence `tokens` (T,), a layer
    at a time. `variant` changes one thing (`VARIANTS`): the summaries
    left out, `mu` left out, pooling by the mean, unrotated keys pooled,
    a block that does not restart (the last W keys, a ring), a window's
    summaries visible as soon as their chunk has closed, the unit offset
    of the norms left out."""
    check_assumed(cfg)
    assert cfg["norm_add_unit_offset"] and cfg["hidden_act"] == "silu"
    if variant and variant not in VARIANTS:
        raise ValueError(variant)
    _, store = make_ops(precision)
    eps, offset = cfg["rms_norm_eps"], variant != "no_unit_offset"
    with jax.default_matmul_precision("highest"):
        h = store(params["lm.tok_emb"][tokens])
        for i in range(n_layer):
            p = _sub(params, "lm.l%d." % i)
            u = store(_norm(h, p["norm_in.w"], eps, offset))
            h = store(h + attention(_sub(p, "attention."), u, cfg,
                                    precision, variant))
            u = store(_norm(h, p["norm_ff.w"], eps, offset))
            h = store(h + gated_mlp(u, p["mlp.gate.w"], p["mlp.up.w"],
                                    p["mlp.down.w"], precision))
        return store(_norm(h, params["lm.norm_f.w"], eps, offset))


def serve_logits(params, tokens, cfg, n_layer, precision="highest",
                 rows=None, variant=""):
    """(T or len(rows), V) logits of one sequence through head 0 (the
    next byte; `lm.head.w` holds its `vocab_size` columns alone), in
    float32 at `highest` whatever the precision (`fp32_logits`): the
    serving runner's call."""
    if "+" in precision:
        precision, variant = precision.split("+", 1)
    h = hidden(params, tokens, cfg, n_layer, precision, variant)
    if rows is not None:
        h = h[rows]
    return jnp.matmul(h, params["lm.head.w"], precision=_HI)
