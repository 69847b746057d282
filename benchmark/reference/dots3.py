"""Plain reference of dots-studio's dots3-note-prev language model
(`model_type: dots3_note`;
https://huggingface.co/dots-studio/dots3-note-prev/blob/main/config.json),
written from the configuration's keys and the papers its mechanisms
come from: multi-head latent attention (DeepSeek-V2, arXiv:2405.04434,
section 2.1), the learned indexer over it (DeepSeek-V3.2-Exp's
"lightning indexer", which `index_n_heads`, `index_head_dim` and
`index_topk` name), the `noaux_tc` router (DeepSeek-V3, arXiv:2412.19437,
section 2.1.2) and the head-wise output gate (Gated Attention,
arXiv:2505.06708). Straightforward `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`: full EXPANDED attention (no
cache, no absorbed form, no ring), the indexer's scores of every (query,
key) pair and `lax.top_k` over them a block of query rows at a time, the
router written out, a loop over heads and over experts: one whole
sequence, every position. Nothing here is imported from `paddle_tpu`.

With d = hidden_size, eps = rms_norm_eps, rms(x; g) = g * x /
sqrt(mean(x^2) + eps):

  h_0 = E[tokens]
  layer i:  a = h + mix_i(rms(h; g_in));  h' = a + ffn_i(rms(a; g_ff))
  logits  = rms(h_L; g_final) W_head        (W_head its own matrix)

Layer i is `layer_types[i]`: `full_attention` or `sliding_attention`.
Layers below `first_k_dense_replace` have a dense gated MLP, the rest
routed experts with a shared one.

A FULL layer, u = rms(h; g_in), H = num_attention_heads, dn =
qk_nope_head_dim, dr = qk_rope_head_dim, dv = v_head_dim, rq =
q_lora_rank, r = kv_lora_rank:
  c_q = rho_q rms(u W_qa; g_q),  rho_q = (d / rq)^1/2
      (ASSUMED: `assumed.mla_qkv_lora_rescale` = "sqrt_hidden_over_rank")
  [q_nope_h ; q_rope_h] = c_q W_qb                       (H x (dn + dr))
  [c_kv ; k_r] = u W_kva;  c_kv <- rho_kv rms(c_kv; g_kv), rho_kv =
      (d / r)^1/2;  the row a position keeps is [c_kv ; k_r]
  q_rope_h, k_r <- RoPE_p(.) on the pairs (2i, 2i+1), theta rope_theta,
      no scaling;  k_r is ONE row for all heads
  the indexer, J = index_n_heads, di = index_head_dim:
    qI_j = c_q W_Iq,j (di);  kI = LayerNorm(u W_Ik; g_I, b_I) (di, ONE
    key a position);  the FIRST dr channels of both rotated in the
    half-split layout (channel i with i + dr/2) at theta rope_theta
    (ASSUMED: `assumed.index_rope` = "first_half_split");
    w_j = (u W_Iw)_j J^-1/2 di^-1/2
    I(t, s) = sum_j w_j(t) relu(qI_j(t) . kI(s)),  s <= t
    S_t = the index_topk positions s <= t of largest I(t, s): all of
    them while t < index_topk; equal scores to the LOWER position
    (`lax.top_k`'s rule)
  [k_nope_h ; v_h] = c_kv W_kvb                          (H x (dn + dv))
  o_h(t) = sum_{s in S_t} softmax_{s in S_t}((dn + dr)^-1/2 [q_nope_h(t) ;
      q_rope_h(t)] . [k_nope_h(s) ; k_r(s)]) v_h(s)
  y = [o_h sigmoid(u W_g)_h] W_o    (ASSUMED: `assumed.attention_gate`
      = "per_head")

A SLIDING layer: the same without the indexer, at the `swa_*` sizes (H
= swa_num_attention_heads, both ranks, dn = swa_qk_nope_head_dim, ...),
theta swa_rope_theta, and S_t = {s : t - sliding_window_size < s <= t}
(ASSUMED: `assumed.sliding_window` = "includes_query": the window counts
the query's own position).

Experts, x = rms(a; g_ff): s = sigmoid(x W_r) in float32 over all
`n_routed_experts_scored`; c = s + b (`topk_method` noaux_tc); S = the
num_experts_per_tok largest c (no groups: the config names none; ties
to the lower index); w_e = routed_scaling_factor s_e / sum_{j in S} s_j;
y = sum_{e in S, e held} w_e E_e(x) + E_shared(x), E(x; W) = (silu(x
W_gate) * (x W_up)) W_down.

DEPARTURES from the published model, each also in the configuration's
file: float32 for bfloat16; no vision or audio tower and no
multi-token-prediction module; the indexer without its Hadamard
rotation (an orthogonal map of both sides leaves every score as it was)
and without FP8 storage; `held` = [lo, hi) is the chip's share of the
routed experts (`experts_held`): what the absent experts would add is
left out, here as in the program; the vocabulary is the slice the file
states; no capacity: every pair on a held expert is computed.

`precision` chooses how a matmul is computed and what is stored, for
the control of the correctness check (as `reference/ling3.py`):
  "highest"  float32 operands, `jax.lax.Precision.HIGHEST` (the truth)
  "bf16_ops" matmul operands rounded to bfloat16, float32 accumulation,
             float32 everything else (what an f32 matmul is on a TPU at
             default precision: the arithmetic the configuration states);
             the indexer's weighted sum over its heads is float32
             multiplies and adds
  "bf16"     as "bf16_ops", and every stored activation, latent row,
             index key, key and value rounded to bfloat16
A precision may name a variant after a `+` ("bf16_ops+all_rows"): the
forward pass with one part changed, for the runs that show that the
comparison sees each mechanism (`VARIANTS`). The router's scores are
float32 at `highest` in every precision (the program computes them so).
Parameter names are the program's (`lm.l1.attention.index.k.w`,
`lm.l2.attention.kv_b.w`, `lm.l1.moe.experts.gate.w`: the held experts
alone).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "bf16_ops", "bf16")
# all_rows: no selection; topk_half: index_topk / 2; index_key_one_stale:
# at a decode step the newest position's index key is missing (its row
# cannot be chosen); window_one_short: sliding_window_size - 1
VARIANTS = ("", "all_rows", "topk_half", "index_keys_not_rotated",
            "index_key_one_stale", "window_one_short", "no_rescale",
            "no_gate", "kr_not_rotated", "no_shared", "no_bias")
_HI = jax.lax.Precision.HIGHEST
_ROWS = 1024       # query rows of a group of heads attended at a time
_HEADS = 16        # heads whose q, k and v exist at a time
_INDEX_ROWS = 128  # query rows whose 64 index heads are scored at a time
ASSUMED = {"mla_qkv_lora_rescale": "sqrt_hidden_over_rank",
           "attention_gate": "per_head",
           "index_rope": "first_half_split",
           "sliding_window": "includes_query"}


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


def check_assumed(cfg: dict):
    """Each ASSUMED convention is ONE named choice; another is refused,
    never ignored."""
    for key, only in ASSUMED.items():
        if cfg["assumed"].get(key) != only:
            raise ValueError("assumed.%s = %r: only %r is written out"
                             % (key, cfg["assumed"].get(key), only))


def _rms(x, g, eps):
    return g * x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                                 + eps)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def layer_kinds(cfg: dict, n_layer: int):
    """("full" | "sliding", "dense" | "sparse") of layers 0..n_layer-1."""
    return [(cfg["layer_types"][i].split("_")[0],
             "dense" if i < cfg["first_k_dense_replace"] else "sparse")
            for i in range(n_layer)]


def geometry(cfg: dict, kind: str) -> dict:
    """A layer kind's sizes, from the plain keys or the `swa_` ones."""
    p = "swa_" if kind == "sliding" else ""
    return {"h": cfg[p + "num_attention_heads"], "rq": cfg[p + "q_lora_rank"],
            "r": cfg[p + "kv_lora_rank"], "dn": cfg[p + "qk_nope_head_dim"],
            "dr": cfg[p + "qk_rope_head_dim"], "dv": cfg[p + "v_head_dim"],
            "theta": cfg[p + "rope_theta"]}


# -- rotations ---------------------------------------------------------------

def _angles(t, r, theta):
    inv = 1.0 / (float(theta) ** (np.arange(0, r, 2, dtype=np.float64) / r))
    ang = (jnp.arange(t, dtype=jnp.float32)[:, None]
           * jnp.asarray(inv, jnp.float32)[None, :])
    return jnp.cos(ang), jnp.sin(ang)


def rotate_pairs(x, theta: float):
    """x (T, ..., r) rotated whole at positions 0..T-1 on the pairs
    (2i, 2i+1): channel 2i the real and 2i+1 the imaginary part."""
    t, r = x.shape[0], x.shape[-1]
    shape = (t,) + (1,) * (x.ndim - 2) + (r // 2,)
    cos, sin = (a.reshape(shape) for a in _angles(t, r, theta))
    re, im = x[..., 0::2], x[..., 1::2]
    return jnp.stack([re * cos - im * sin, im * cos + re * sin],
                     axis=-1).reshape(x.shape)


def rotate_first_half_split(x, r: int, theta: float):
    """x (T, ..., d): its FIRST r channels rotated at positions 0..T-1,
    channel i paired with channel i + r/2."""
    t = x.shape[0]
    shape = (t,) + (1,) * (x.ndim - 2) + (r // 2,)
    cos, sin = (a.reshape(shape) for a in _angles(t, r, theta))
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., r:]], axis=-1)


# -- the indexer -------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("precision", "k", "stale_from"))
def _choose(q_i, w, k_i, row0, precision, k, stale_from):
    """The choice of a block of query rows at positions row0..: q_i (n,
    J, di), w (n, J), k_i (T, di) -> (n, T) bool, True where the query
    attends the key. `k` 0: every earlier position. `stale_from` >= 0:
    a query at a position from there on does not find its own key."""
    mm, _ = make_ops(precision)
    n, t = q_i.shape[0], k_i.shape[0]
    at = row0 + jnp.arange(n)[:, None]
    seen = jnp.arange(t)[None, :] <= at
    if stale_from >= 0:
        seen &= ~((jnp.arange(t)[None, :] == at) & (at >= stale_from))
    if not k:
        return seen
    s = mm(q_i.reshape(n * q_i.shape[1], -1), k_i.T).reshape(n, -1, t)
    score = jnp.sum(w[:, :, None] * jnp.maximum(s, 0.0), axis=1)
    _, idx = jax.lax.top_k(jnp.where(seen, score, -jnp.inf), min(k, t))
    chosen = jnp.zeros((n, t), bool).at[jnp.arange(n)[:, None], idx].set(True)
    return chosen & seen


def selection(p, u, c_q, cfg, precision, variant="", handover=-1):
    """(T, T) bool: S_t row by row, of one full layer."""
    mm, store = make_ops(precision)
    t = u.shape[0]
    j, di, dr = (cfg["index_n_heads"], cfg["index_head_dim"],
                 cfg["qk_rope_head_dim"])
    theta, eps = cfg["rope_theta"], cfg["rms_norm_eps"]
    k = {"all_rows": 0, "topk_half": cfg["index_topk"] // 2}.get(
        variant, cfg["index_topk"])
    q_i = rotate_first_half_split(
        mm(c_q, p["index.q.w"]).reshape(t, j, di), dr, theta)
    k_i = mm(u, p["index.k.w"])
    mu = jnp.mean(k_i, -1, keepdims=True)
    k_i = ((k_i - mu) * jax.lax.rsqrt(
        jnp.mean(jnp.square(k_i - mu), -1, keepdims=True) + eps)
        * p["index.k_norm.w"] + p["index.k_norm.b"])
    if variant != "index_keys_not_rotated":
        k_i = rotate_first_half_split(k_i, dr, theta)
    k_i = store(k_i)  # what a position keeps
    w = mm(u, p["index.weights.w"]) * (float(j) ** -0.5 * float(di) ** -0.5)
    stale = handover + 1 if variant == "index_key_one_stale" else -1
    return jnp.concatenate([
        _choose(store(q_i[r0:r0 + _INDEX_ROWS]), w[r0:r0 + _INDEX_ROWS], k_i,
                r0, precision, k, stale)
        for r0 in range(0, t, _INDEX_ROWS)])


# -- latent attention --------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("precision",))
def _attend(q, k, v, chosen, precision):
    """A block of query rows, every head: q (H, n, dk) pre-scaled, k (H,
    T, dk), v (H, T, dv), chosen (n, T) bool -> (n, H, dv)."""
    mm, store = make_ops(precision)

    def head(qkv):
        q_h, k_h, v_h = qkv
        s = jnp.where(chosen, mm(q_h, k_h.T), -jnp.inf)
        return mm(store(jax.nn.softmax(s, axis=-1)), v_h)

    return jnp.moveaxis(jax.lax.map(head, (q, k, v)), 0, 1)


def attention(p, u, cfg, kind, precision, variant="", handover=-1,
              probe=None):
    """u (T, d) -> (T, d): one layer's latent attention, expanded.
    `kind` "full" (under the indexer) | "sliding" (over the window)."""
    mm, store = make_ops(precision)
    assert cfg["rope_scaling"] is None and not cfg["attention_bias"]
    g = geometry(cfg, kind)
    t, d = u.shape
    h, dn, dr, dv, r = g["h"], g["dn"], g["dr"], g["dv"], g["r"]
    eps, theta = cfg["rms_norm_eps"], g["theta"]
    rescale = variant != "no_rescale"
    c_q = _rms(mm(u, p["q_a.w"]), p["q_norm.w"], eps)
    if rescale:
        c_q = c_q * (float(d) / g["rq"]) ** 0.5
    c_q = store(c_q)
    row = mm(u, p["kv_a.w"])
    c_kv = _rms(row[:, :r], p["kv_norm.w"], eps)
    if rescale:
        c_kv = c_kv * (float(d) / r) ** 0.5
    k_r = row[:, r:]
    if variant != "kr_not_rotated":
        k_r = rotate_pairs(k_r, theta)
    c_kv, k_r = store(c_kv), store(k_r)  # what a position keeps
    if kind == "full":
        chosen = selection(p, u, c_q, cfg, precision, variant, handover)
        if probe is not None:
            probe.append(np.asarray(chosen))
    else:
        window = cfg["sliding_window_size"] - (variant == "window_one_short")
        at = jnp.arange(t)
        chosen = (at[None, :] <= at[:, None]) & (at[None, :]
                                                 > at[:, None] - window)
    a = float(dn + dr) ** -0.5
    w_q = p["q_b.w"].reshape(-1, h, dn + dr)
    w_kv = p["kv_b.w"].reshape(r, h, dn + dv)
    ctx = []
    for h0 in range(0, h, _HEADS):  # the same sums, a few heads at a time
        n = min(_HEADS, h - h0)
        q = mm(c_q, w_q[:, h0:h0 + n].reshape(-1, n * (dn + dr)))
        q = q.reshape(t, n, dn + dr)
        q = store(jnp.concatenate(
            [q[..., :dn], rotate_pairs(q[..., dn:], theta)], -1))
        kv = mm(c_kv, w_kv[:, h0:h0 + n].reshape(r, n * (dn + dv)))
        kv = kv.reshape(t, n, dn + dv)
        k = jnp.concatenate([store(kv[..., :dn]),
                             jnp.broadcast_to(k_r[:, None], (t, n, dr))], -1)
        q, k, v = (jnp.moveaxis(x, 1, 0)
                   for x in (q * a, k, store(kv[..., dn:])))
        ctx.append(jnp.concatenate([
            _attend(q[:, r0:r0 + _ROWS], k, v, chosen[r0:r0 + _ROWS],
                    precision)
            for r0 in range(0, t, _ROWS)]))
    ctx = jnp.concatenate(ctx, axis=1)  # (T, H, dv)
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
    score + bias, the weights by score."""
    assert cfg["scoring_func"] == "sigmoid" and cfg["norm_topk_prob"]
    assert cfg["topk_method"] == "noaux_tc"
    s = jax.nn.sigmoid(jnp.matmul(x, w_router, precision=_HI))
    c = s if variant == "no_bias" else s + bias
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
           handover=-1, probe=None):
    """Final-norm output (T, d) of one sequence `tokens` (T,), a layer
    at a time. `variant` (one of `VARIANTS`) changes one thing, for the
    runs that show the comparison sees it; `handover` is the prompt's
    last position, after which a served model decodes a token a step
    (`index_key_one_stale`). `probe` (a list) receives each full
    layer's (T, T) choice."""
    if variant not in VARIANTS:
        raise ValueError(variant)
    check_assumed(cfg)
    _, store = make_ops(precision)
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        h = store(params["lm.tok_emb"][tokens])
        for i, (mixer, ffn) in enumerate(layer_kinds(cfg, n_layer)):
            p = _sub(params, "lm.l%d." % i)
            u = store(_rms(h, p["norm_in.w"], eps))
            h = store(h + attention(_sub(p, "attention."), u, cfg, mixer,
                                    precision, variant, handover, probe))
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
