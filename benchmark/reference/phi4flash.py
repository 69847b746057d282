"""Plain reference of Microsoft's Phi-4-mini-flash-reasoning decoder LM
(`model_type: phi4flash`;
https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json),
written from the two papers' equations: "Decoder-Hybrid-Decoder
Architecture for Efficient Reasoning with Long Generation"
(arXiv:2507.06607, SambaY) and "Differential Transformer"
(arXiv:2410.05258). Straightforward `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`, no kernel, no cache, no
ring, no one-row shortcut: one whole sequence, EVERY layer on EVERY
row, the (T, T) scores built. Nothing here is imported from
`paddle_tpu`.

With N = the layers run, d = hidden_size, ln(x; g, b) LayerNorm over
the last axis (eps layer_norm_eps), silu(x) = x sigmoid(x):

  h_0 = E[tokens]
  layer i:  a = h + mixer_i(ln(h));  h' = a + W2 (silu(W_g u) * (W_u u)),
            u = ln(a)   (the published [g, h] = W1 u is the two halves)
  logits  = ln(h_N) E^T                 (tied table, no bias)

No positional encoding of any kind. The mixer of layer i (`layer_kinds`):
  i < N/2, even     Mamba-1
  i < N/2, odd      sliding-window differential attention
  i = N/2           Mamba-1, which also hands on its MEMORY M (T, Di):
                    the scan's output with the D skip, BEFORE silu(z)
  i = N/2 + 1       full causal differential attention; K and V (T, ..)
  i > N/2 + 1, even gated memory unit: W_out (M * silu(W_in u))
  i > N/2 + 1, odd  cross differential attention: q = W_q u + b alone,
                    keys and values layer N/2 + 1's, positions <= t

Mamba-1 (the `mamba_*` keys: assumed): [x, z] = u W_in; x = silu(conv1d_causal(x) +
b_conv), kernel 4; [dt, B, C] = x W_x with NO norms on them; delta =
softplus(dt W_dt + b_dt); A = -exp(A_log); s_t = exp(delta_t A) s_(t-1)
+ (delta_t x_t) B_t; y_t = s_t C_t + D x_t; out = (y * silu(z)) W_out.

Differential attention, H query heads of dh = d / H on Hkv key/value
heads, biases on the projections (`assumed_sizes.attention_bias`): q1 = q[:,
0::2], q2 = q[:, 1::2]; k1, k2, v1, v2 alike; query pair j reads
key/value pair j // ((H/2) / (Hkv/2)); A1 = softmax(q1 k1^T / sqrt(dh)),
A2 = softmax(q2 k2^T / sqrt(dh)) over the keys the layer's mask shows
(j <= t; on a sliding layer also j > t - sliding_window); O_j = A1 [v1 |
v2] - lam A2 [v1 | v2]; O_j <- rms(O_j; g_subln, eps) * (1 - lam_init);
concat_j(O_j) W_o + b_o. lam = exp(lq1 . lk1) - exp(lq2 . lk2) +
lam_init, lam_init = 0.8 - 0.6 exp(-0.3 i), i the layer's index AS RUN.

Departures from the papers, all in the arithmetic's order and none in
its meaning: the two softmaxes are multiplied with the values before
they are subtracted (the published code does so too; (A1 - lam A2) V
rounds once less); the MLP's W1 is kept as its two halves.

`precision` chooses how a matmul is computed and what is stored, for
the control of the correctness check (as `reference/laguna.py`):
  "highest"  float32 operands, `jax.lax.Precision.HIGHEST` (the truth)
  "bf16_ops" matmul operands rounded to bfloat16, float32 accumulation,
             float32 everything else (what an f32 matmul is on a TPU at
             default precision: the arithmetic the configuration states)
  "bf16"     as "bf16_ops", and every stored activation, key, value,
             state and memory rounded to bfloat16
A precision may name a variant after a `+` ("bf16_ops+no_lambda"): the
forward pass with one part changed, for the runs that show that the
comparison sees each mechanism (`hidden`). Parameter names are the
program's (`lm.l9.attention.lambda_q1`, `lm.l10.gmu.in_proj.w`).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "bf16_ops", "bf16")
VARIANTS = ("", "no_lambda", "no_subln", "gmu_gated_memory",
            "cross_one_short")
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


def layer_kinds(n_layer: int):
    """The mixer of each of `n_layer` layers, by the rule above."""
    half = n_layer // 2
    out = []
    for i in range(n_layer):
        if i < half:
            out.append("mamba" if i % 2 == 0 else "sliding")
        elif i == half:
            out.append("mamba")
        elif i == half + 1:
            out.append("attention")
        else:
            out.append("gmu" if i % 2 == 0 else "cross")
    return out


def lambda_init(i: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def _ln(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _silu(x):
    return x * jax.nn.sigmoid(x)


# -- Mamba-1 -----------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n", "r", "k", "precision"))
def mamba(p, u, n, r, k, precision):
    """u (T, d) -> (out (T, d), memory (T, Di), gated (T, Di)): the
    memory is the scan's output with the D skip, before the gate."""
    mm, store = make_ops(precision)
    t = u.shape[0]
    xz = store(mm(u, p["in_proj.w"]))
    di = xz.shape[-1] // 2
    x, z = xz[:, :di], xz[:, di:]
    xp = jnp.concatenate([jnp.zeros((k - 1, di), x.dtype), x], axis=0)
    conv = p["conv.b"] + sum(p["conv.w"][:, j] * xp[j:j + t]
                             for j in range(k))
    x = store(_silu(conv))
    dbc = store(mm(x, p["x_proj.w"]))
    dt, b, c = dbc[:, :r], dbc[:, r:r + n], dbc[:, r + n:]
    delta = store(jax.nn.softplus(mm(dt, p["dt_proj.w"]) + p["dt_proj.b"]))
    a = -jnp.exp(p["A_log"])                                  # (Di, N)

    def token(s, inp):
        x_t, d_t, b_t, c_t = inp
        s = store(jnp.exp(d_t[:, None] * a) * s
                  + (d_t * x_t)[:, None] * b_t[None, :])
        return s, jnp.sum(s * c_t[None, :], axis=-1) + p["D"] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((di, n), jnp.float32),
                        (x, delta, b, c))
    y = store(y)
    gated = store(y * _silu(z))
    return mm(gated, p["out_proj.w"]), y, gated


# -- differential attention --------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("window", "short", "precision"))
def _attend(q, k, v, window, short, precision):
    """q (g, T, dh) pre-scaled, k (S, dh), v (S, 2 dh), S = T: the
    softmax of each query row over the keys it may see, times v."""
    mm, store = make_ops(precision)
    t = k.shape[0]
    s = mm(q, k.T)
    row = jnp.arange(t)[:, None]
    col = jnp.arange(t)[None, :]
    seen = col <= (jnp.maximum(row - 1, 0) if short else row)
    if window:
        seen = seen & (col > row - window)
    w = store(jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1))
    return mm(w, v)


def diff_attention(p, q, k, v, i, cfg, precision, window=0, variant="",
                   short=False):
    """q (T, H, dh), k, v (T, Hkv, dh) -> (T, H dh) before W_o: the
    equations of the module's docstring, a key/value pair at a time."""
    _, store = make_ops(precision)
    t, h, dh = q.shape
    hkv = k.shape[1]
    g = (h // 2) // (hkv // 2)  # query pairs a key/value pair
    q1, q2 = q[:, 0::2] * dh ** -0.5, q[:, 1::2] * dh ** -0.5
    k1, k2, v1, v2 = k[:, 0::2], k[:, 1::2], v[:, 0::2], v[:, 1::2]
    lam0 = lambda_init(i)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam0)
    if variant == "no_lambda":
        lam = 0.0
    out = []
    for pair in range(hkv // 2):
        qs = slice(pair * g, (pair + 1) * g)
        vv = jnp.concatenate([v1[:, pair], v2[:, pair]], axis=-1)
        a1 = _attend(q1[:, qs].transpose(1, 0, 2), k1[:, pair], vv, window,
                     short, precision)
        a2 = _attend(q2[:, qs].transpose(1, 0, 2), k2[:, pair], vv, window,
                     short, precision)
        out.append(a1 - lam * a2)                         # (g, T, 2 dh)
    o = jnp.concatenate(out, axis=0)                      # (H / 2, T, 2 dh)
    if variant != "no_subln":
        o = (p["subln.w"] * o * jax.lax.rsqrt(
            jnp.mean(jnp.square(o), -1, keepdims=True)
            + cfg["layer_norm_eps"])) * (1.0 - lam0)
    return store(o.transpose(1, 0, 2).reshape(t, h * dh))


def _heads(cfg):
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return h, hkv, cfg["hidden_size"] // h


def attention(p, u, i, cfg, precision, window=0, variant=""):
    """u (T, d) -> (out (T, d), (k, v)): a layer with its own K and V."""
    mm, store = make_ops(precision)
    t = u.shape[0]
    h, hkv, dh = _heads(cfg)
    q = store(mm(u, p["q.w"]) + p["q.b"]).reshape(t, h, dh)
    k = store(mm(u, p["k.w"]) + p["k.b"]).reshape(t, hkv, dh)
    v = store(mm(u, p["v.w"]) + p["v.b"]).reshape(t, hkv, dh)
    ctx = diff_attention(p, q, k, v, i, cfg, precision, window, variant)
    return mm(ctx, p["o.w"]) + p["o.b"], (k, v)


def cross(p, u, kv, i, cfg, precision, variant=""):
    """u (T, d) -> (T, d): queries of this layer's own on another
    layer's keys and values, positions <= t."""
    mm, store = make_ops(precision)
    t = u.shape[0]
    h, _, dh = _heads(cfg)
    q = store(mm(u, p["q.w"]) + p["q.b"]).reshape(t, h, dh)
    ctx = diff_attention(p, q, kv[0], kv[1], i, cfg, precision,
                         variant=variant,
                         short=variant == "cross_one_short")
    return mm(ctx, p["o.w"]) + p["o.b"]


# -- feed-forward ------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("precision",))
def gated_mlp(x, w_gate, w_up, w_down, precision):
    mm, store = make_ops(precision)
    gate = store(_silu(mm(x, w_gate)))
    up = store(mm(x, w_up))
    return mm(store(gate * up), w_down)


@functools.partial(jax.jit, static_argnames=("precision",))
def gmu(u, memory, w_in, w_out, precision):
    mm, store = make_ops(precision)
    return mm(store(memory * _silu(mm(u, w_in))), w_out)


# -- the model ---------------------------------------------------------------

def _sub(p, prefix):
    return {n[len(prefix):]: v for n, v in p.items() if n.startswith(prefix)}


def hidden(params, tokens, cfg, n_layer, precision="highest", variant=""):
    """Final-norm output (T, d) of one sequence `tokens` (T,), a layer
    at a time, every layer on every row. `variant` changes one thing,
    for the runs that show the comparison sees it: "no_lambda" (lam =
    0: plain attention), "no_subln" (no RMS norm of the heads and no 1
    - lam_init), "gmu_gated_memory" (the memory taken AFTER the silu(z)
    gate), "cross_one_short" (cross layers see the keys one row
    short)."""
    if variant not in VARIANTS:
        raise ValueError(variant)
    _, store = make_ops(precision)
    eps = cfg["layer_norm_eps"]
    n, r, kc = (cfg["mamba_d_state"], cfg["mamba_dt_rank"],
                cfg["mamba_d_conv"])
    memory = kv = None
    with jax.default_matmul_precision("highest"):
        h = store(params["lm.tok_emb"][tokens])
        for i, kind in enumerate(layer_kinds(n_layer)):
            p = _sub(params, "lm.l%d." % i)
            u = store(_ln(h, p["norm_in.w"], p["norm_in.b"], eps))
            if kind == "mamba":
                mixed, y, gated = mamba(_sub(p, "mamba."), u, n, r, kc,
                                        precision)
                memory = gated if variant == "gmu_gated_memory" else y
            elif kind == "gmu":
                mixed = gmu(u, memory, p["gmu.in_proj.w"],
                            p["gmu.out_proj.w"], precision)
            elif kind == "cross":
                mixed = cross(_sub(p, "cross."), u, kv, i, cfg, precision,
                              variant)
            else:
                window = (cfg["sliding_window"] if kind == "sliding" else 0)
                mixed, own = attention(_sub(p, "attention."), u, i, cfg,
                                       precision, window, variant)
                if kind == "attention":
                    kv = own
            h = store(h + mixed)
            u = store(_ln(h, p["norm_ff.w"], p["norm_ff.b"], eps))
            h = store(h + gated_mlp(u, p["mlp.gate.w"], p["mlp.up.w"],
                                    p["mlp.down.w"], precision))
        return store(_ln(h, params["lm.norm_f.w"], params["lm.norm_f.b"],
                         eps))


def serve_logits(params, tokens, cfg, n_layer, precision="highest",
                 rows=None, variant=""):
    """(T or len(rows), V) logits of one sequence through the tied
    table: the serving runner's call."""
    if "+" in precision:
        precision, variant = precision.split("+", 1)
    mm, _ = make_ops(precision)
    h = hidden(params, tokens, cfg, n_layer, precision, variant)
    if rows is not None:
        h = h[rows]
    with jax.default_matmul_precision("highest"):
        return mm(h, params["lm.tok_emb"].T)
