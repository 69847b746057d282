"""Plain reference of the OPT decoder LM as this repo builds it
(`models/transformer.py`): learned absolute positions, pre-LayerNorm
blocks of biased multi-head attention and a ReLU FFN, one final
LayerNorm, a head tied to the token table plus a bias. Straightforward
`jax.numpy`, float32, no kernels, no cache, no batching tricks.

Departures from the published OPT (arXiv:2205.01068; HF `modeling_opt`),
which are the repo's and are listed in the configuration file: the head
has a bias (`lm.head.b`); positions start at row 0 of the table (HF
offsets them by 2).

`precision` chooses how a matmul is computed, and exists for the control
of the correctness check:
  "highest"  float32 operands, `jax.lax.Precision.HIGHEST` (the truth)
  "bf16_ops" operands rounded to bfloat16, float32 accumulation and
             float32 everything else (what an f32 matmul is on a TPU at
             default precision: the arithmetic the serving config states)
  "bf16"     operands AND every stored activation in bfloat16
  "int8"     operands fake-quantized to int8, per row of the left and
             per column of the right operand, symmetric, dynamic
  "fp8"      operands fake-quantized to float8 e4m3, per tensor, dynamic
Parameter names are the program's (`lm.l0.self.q.w`, `layer_norm_0.w_0`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-5  # layers.layer_norm's default epsilon


def _fake_int8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)  # straight-through


def _fake_fp8(x):
    """Round to float8 e4m3 after scaling the tensor's largest
    magnitude to the format's 448 (per-tensor, dynamic)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)  # straight-through


def make_ops(precision: str):
    """(matmul, store): `matmul(a, b)` contracts a's last with b's
    first axis; `store(x)` is applied to every activation kept."""
    hi = jax.lax.Precision.HIGHEST

    def mm(a, b):
        if precision == "highest":
            return jnp.matmul(a, b, precision=hi)
        if precision in ("bf16_ops", "bf16"):
            return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32)
        if precision == "int8":
            return jnp.matmul(_fake_int8(a, -1), _fake_int8(b, -2),
                              precision=hi)
        if precision == "fp8":
            return jnp.matmul(_fake_fp8(a), _fake_fp8(b), precision=hi)
        raise ValueError(precision)

    def store(x):
        if precision == "bf16":
            return x.astype(jnp.bfloat16).astype(jnp.float32)
        return x

    return mm, store


def _layer_norm(x, w, b):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + EPS) * w + b


def hidden(params, tokens, n_layer, n_head, precision="highest"):
    """Final-LayerNorm output (T, D) of one sequence `tokens` (T,)."""
    mm, store = make_ops(precision)
    t = tokens.shape[0]
    x = store(params["lm.tok_emb"][tokens] + params["lm.pos_emb"][:t])
    d = x.shape[-1]
    dh = d // n_head
    causal = jnp.tril(jnp.ones((t, t), bool))
    ln = 0
    for i in range(n_layer):
        p = "lm.l%d." % i
        h = store(_layer_norm(x, params["layer_norm_%d.w_0" % ln],
                              params["layer_norm_%d.b_0" % ln]))
        q, k, v = (store(mm(h, params[p + "self.%s.w" % n])
                         + params[p + "self.%s.b" % n])
                   .reshape(t, n_head, dh).transpose(1, 0, 2)
                   for n in "qkv")
        s = mm(q * dh ** -0.5, k.transpose(0, 2, 1))
        s = jnp.where(causal, s, -jnp.inf)
        w = store(jax.nn.softmax(s, axis=-1))
        ctx = mm(w, v)
        ctx = store(ctx.transpose(1, 0, 2).reshape(t, d))
        x = store(x + mm(ctx, params[p + "self.out.w"])
                  + params[p + "self.out.b"])
        h = store(_layer_norm(x, params["layer_norm_%d.w_0" % (ln + 1)],
                              params["layer_norm_%d.b_0" % (ln + 1)]))
        ln += 2
        f = store(jax.nn.relu(mm(h, params[p + "ffn.fc1.w"])
                              + params[p + "ffn.fc1.b"]))
        x = store(x + mm(f, params[p + "ffn.fc2.w"])
                  + params[p + "ffn.fc2.b"])
    return store(_layer_norm(x, params["layer_norm_%d.w_0" % ln],
                             params["layer_norm_%d.b_0" % ln]))


def logits(params, tokens, n_layer, n_head, precision="highest", rows=None):
    """(T or len(rows), V) logits of one sequence."""
    mm, _ = make_ops(precision)
    h = hidden(params, tokens, n_layer, n_head, precision)
    if rows is not None:
        h = h[rows]
    return mm(h, params["lm.tok_emb"].T) + params["lm.head.b"]


def sequence_loss(params, seq, n_layer, n_head, precision="highest"):
    """Mean next-token cross-entropy of one sequence `seq` (T + 1,)."""
    lg = logits(params, seq[:-1], n_layer, n_head, precision)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, seq[1:, None], axis=-1)[:, 0]
    return jnp.mean(lse - picked)


def train_check(params, inputs, cfg, n_layer, grads, precision="highest"):
    """{"loss": mean loss over inputs["tokens"] (N, T + 1), name: dLoss/d
    params[name] for name in `grads`}, one sequence at a time."""
    n_head = cfg["num_attention_heads"]
    toks = jnp.asarray(inputs["tokens"])
    sub = {n: params[n] for n in grads}
    rest = {n: v for n, v in params.items() if n not in sub}

    @jax.jit
    def one(sub, rest, seq):
        return jax.value_and_grad(lambda s: sequence_loss(
            {**rest, **s}, seq, n_layer, n_head, precision))(sub)

    loss, acc = 0.0, None
    for i in range(toks.shape[0]):
        l, g = one(sub, rest, toks[i])
        loss = loss + l
        acc = g if acc is None else jax.tree_util.tree_map(
            jnp.add, acc, g)
    n = toks.shape[0]
    out = {k: v / n for k, v in acc.items()}
    out["loss"] = loss / n
    return out
