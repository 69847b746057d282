"""Plain reference of AI21's Jamba decoder LM (`model_type: jamba`;
https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json),
written from the configuration's keys alone. Straightforward
`jax.numpy`, float32, `jax.default_matmul_precision("highest")`, no
kernels, no cache, no batching: one whole sequence, every position.
Nothing here is imported from `paddle_tpu`.

With d = hidden_size, Di = mamba_expand * d, N = mamba_d_state,
R = mamba_dt_rank, K = mamba_d_conv, eps = rms_norm_eps and
rms(x; g) = g * x / sqrt(mean(x^2) + eps):

  h_0 = E[tokens]                                  (no position term)
  per layer:  h <- h + mixer(rms(h; g_in));  h <- h + mlp(rms(h; g_ff))
  mlp(x)    = (silu(x W_gate) * (x W_up)) W_down         (no bias)
  logits    = rms(h; g_final) E^T                        (tied, no bias)

Layer i is an attention layer iff i % attn_layer_period ==
attn_layer_offset, and a Mamba-1 layer otherwise (num_experts 1: every
feed-forward is the dense MLP).

  Mamba:  [x, z] = u W_in;  x_t <- silu(b_conv + sum_k w_conv[:, k] *
          x_{t-K+1+k}) (causal, depthwise, zeros before the start);
          [dt, B, C] = x W_x, each RMS-normalized with its own gain
          (Jamba's dt/b/c_layernorm);  delta = softplus(dt W_dt + b_dt);
          A = -exp(A_log);
          s_t = exp(delta_t[:, None] * A) * s_{t-1}
                + (delta_t * x_t)[:, None] * B_t[None, :],  s_0 = 0;
          y_t = s_t C_t + D * x_t;  out = (y * silu(z)) W_out.
  Attention: q = u W_q as num_attention_heads heads, k = u W_k and
          v = u W_v as num_key_value_heads heads (query head h reads
          K/V head h // group), no bias, no rotation; causal
          softmax(q k^T / sqrt(d_head)) v;  out = ctx W_o.

`precision` chooses how a matmul is computed and what is stored, and
exists for the control of the correctness check (as `reference/opt.py`):
  "highest"  float32 operands, `jax.lax.Precision.HIGHEST` (the truth)
  "bf16_ops" matmul operands rounded to bfloat16, float32 accumulation,
             float32 everything else: the recurrence, exp, softplus and
             the norms (what an f32 matmul is on a TPU at default
             precision: the arithmetic the serving config states)
  "bf16"     as "bf16_ops", and every stored activation AND the
             recurrent state after every token rounded to bfloat16
Parameter names are the program's (`lm.l0.mamba.in_proj.w`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "bf16_ops", "bf16")


def make_ops(precision: str):
    """(matmul, store): `matmul(a, b)` contracts a's last with b's
    first axis; `store(x)` is applied to every activation kept."""
    if precision not in PRECISIONS:
        raise ValueError(precision)
    hi = jax.lax.Precision.HIGHEST

    def mm(a, b):
        if precision == "highest":
            return jnp.matmul(a, b, precision=hi)
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)

    def store(x):
        if precision == "bf16":
            return x.astype(jnp.bfloat16).astype(jnp.float32)
        return x

    return mm, store


def layer_kinds(cfg: dict, n_layer: int):
    return ["attention" if i % cfg["attn_layer_period"]
            == cfg["attn_layer_offset"] else "mamba"
            for i in range(n_layer)]


def _rms(x, g, eps):
    return g * x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                                 + eps)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _mamba(p, u, cfg, precision):
    """u (T, d) -> (T, d). `p` holds this mixer's parameters by their
    last name parts (`in_proj.w`, `conv.w`, ...)."""
    mm, store = make_ops(precision)
    eps = cfg["rms_norm_eps"]
    n, r, k = cfg["mamba_d_state"], cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    t = u.shape[0]
    xz = store(mm(u, p["in_proj.w"]))
    di = xz.shape[-1] // 2
    x, z = xz[:, :di], xz[:, di:]
    xp = jnp.concatenate([jnp.zeros((k - 1, di), x.dtype), x], axis=0)
    conv = p["conv.b"] + sum(p["conv.w"][:, j] * xp[j:j + t]
                             for j in range(k))
    x = store(_silu(conv))
    dbc = store(mm(x, p["x_proj.w"]))
    dt = store(_rms(dbc[:, :r], p["dt_norm.w"], eps))
    b = store(_rms(dbc[:, r:r + n], p["b_norm.w"], eps))
    c = store(_rms(dbc[:, r + n:], p["c_norm.w"], eps))
    delta = store(jax.nn.softplus(mm(dt, p["dt_proj.w"]) + p["dt_proj.b"]))
    a = -jnp.exp(p["A_log"])                                  # (Di, N)

    def token(s, inp):
        x_t, d_t, b_t, c_t = inp
        s = store(jnp.exp(d_t[:, None] * a) * s
                  + (d_t * x_t)[:, None] * b_t[None, :])
        return s, jnp.sum(s * c_t[None, :], axis=-1) + p["D"] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((di, n), jnp.float32),
                        (x, delta, b, c))
    y = store(store(y) * _silu(z))
    return mm(y, p["out_proj.w"])


def _attention(p, u, cfg, precision):
    """u (T, d) -> (T, d): grouped-query causal attention, no bias, no
    rotation."""
    mm, store = make_ops(precision)
    t, d = u.shape
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // h
    q = store(mm(u, p["q.w"])).reshape(t, h, dh).transpose(1, 0, 2)
    k = store(mm(u, p["k.w"])).reshape(t, hkv, dh).transpose(1, 0, 2)
    v = store(mm(u, p["v.w"])).reshape(t, hkv, dh).transpose(1, 0, 2)
    k = jnp.repeat(k, h // hkv, axis=0)     # query head h reads h // group
    v = jnp.repeat(v, h // hkv, axis=0)
    s = mm(q * dh ** -0.5, k.transpose(0, 2, 1))
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    w = store(jax.nn.softmax(s, axis=-1))
    ctx = store(mm(w, v).transpose(1, 0, 2).reshape(t, h * dh))
    return mm(ctx, p["o.w"])


def _mlp(p, x, precision):
    mm, store = make_ops(precision)
    gate = store(_silu(mm(x, p["gate.w"])))
    up = store(mm(x, p["up.w"]))
    return mm(store(gate * up), p["down.w"])


@functools.partial(jax.jit, static_argnames=("kind", "cfg_key", "precision"))
def _layer(p, h, kind, cfg_key, precision):
    """One layer on one sequence, jitted once per kind: h (T, d)."""
    cfg = dict(cfg_key)
    _, store = make_ops(precision)
    eps = cfg["rms_norm_eps"]
    mixer = _mamba if kind == "mamba" else _attention
    sub = {n[len(kind) + 1:]: v for n, v in p.items()
           if n.startswith(kind + ".")}
    h = store(h + mixer(sub, store(_rms(h, p["norm_in.w"], eps)), cfg,
                        precision))
    sub = {n[4:]: v for n, v in p.items() if n.startswith("mlp.")}
    return store(h + _mlp(sub, store(_rms(h, p["norm_ff.w"], eps)),
                          precision))


_SHAPE_KEYS = ("attn_layer_offset", "attn_layer_period", "hidden_size",
               "mamba_d_conv", "mamba_d_state", "mamba_dt_rank",
               "num_attention_heads", "num_key_value_heads", "rms_norm_eps")


def hidden(params, tokens, cfg, n_layer, precision="highest"):
    """Final-norm output (T, d) of one sequence `tokens` (T,), a layer
    at a time (a layer's temporaries are freed before the next's)."""
    _, store = make_ops(precision)
    cfg_key = tuple((k, cfg[k]) for k in _SHAPE_KEYS)
    with jax.default_matmul_precision("highest"):
        h = store(params["lm.tok_emb"][tokens])
        for i, kind in enumerate(layer_kinds(cfg, n_layer)):
            pre = "lm.l%d." % i
            p = {n[len(pre):]: v for n, v in params.items()
                 if n.startswith(pre)}
            h = _layer(p, h, kind, cfg_key, precision)
        return store(_rms(h, params["lm.norm_f.w"], cfg["rms_norm_eps"]))


def serve_logits(params, tokens, cfg, n_layer, precision="highest",
                 rows=None):
    """(T or len(rows), V) logits of one sequence through the tied
    table: the serving runner's call."""
    mm, _ = make_ops(precision)
    h = hidden(params, tokens, cfg, n_layer, precision)
    if rows is not None:
        h = h[rows]
    with jax.default_matmul_precision("highest"):
        return mm(h, params["lm.tok_emb"].T)
