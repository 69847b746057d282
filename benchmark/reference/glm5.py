"""Plain reference of Z.ai's GLM-5 language model (`model_type:
glm_moe_dsa`; https://huggingface.co/zai-org/GLM-5/blob/main/config.json),
written from the configuration's keys and the papers its mechanisms
come from: multi-head latent attention (DeepSeek-V2, arXiv:2405.04434,
section 2.1), the learned indexer over it (DeepSeek-V3.2-Exp's
"lightning indexer", which `index_n_heads`, `index_head_dim` and
`index_topk` name), the `noaux_tc` router (DeepSeek-V3, arXiv:2412.19437,
section 2.1.2) and that paper's multi-token-prediction module (section
2.2), which `num_nextn_predict_layers: 1` names and the config does not
spell. Straightforward `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`: full EXPANDED attention (no
cache, no absorbed form, no round), the indexer's scores of every
(query, key) pair and `lax.top_k` over them a block of query rows at a
time, the router written out, a loop over heads and over experts: one
whole sequence, every position: the loss-free serving form. Nothing here
is imported from `paddle_tpu`.

With d = hidden_size, eps = rms_norm_eps, rms(x; g) = g * x /
sqrt(mean(x^2) + eps):

  h_0 = E[tokens]
  layer i:  a = h + mix_i(rms(h; g_in));  h' = a + ffn_i(rms(a; g_ff))
  h^ = rms(h_L; g_final);  logits = h^ W_head   (W_head its own matrix)

Every layer is latent attention under the indexer. The first
`dense_layers_built` layers (the source's `first_k_dense_replace` 3,
counted once where the depth is cut) have a dense gated MLP of
`intermediate_size`, the rest routed experts with a shared one.

A layer's mixer, u = rms(h; g_in), H = num_attention_heads, dn =
qk_nope_head_dim, dr = qk_rope_head_dim, dv = v_head_dim, rq =
q_lora_rank, r = kv_lora_rank:
  c_q = rms(u W_qa; g_q)                       (no rescale of the latents)
  [q_nope_h ; q_rope_h] = c_q W_qb                       (H x (dn + dr))
  [c_kv ; k_r] = u W_kva;  c_kv <- rms(c_kv; g_kv);  the row a position
      keeps is [c_kv ; k_r]
  q_rope_h, k_r <- RoPE_p(.) on the pairs (2i, 2i+1) (`rope_interleave`),
      theta rope_parameters.rope_theta, plain (`rope_type` default);
      k_r is ONE row for all heads
  the indexer, J = index_n_heads, di = index_head_dim:
    qI_j = c_q W_Iq,j (di);  kI = LayerNorm(u W_Ik; g_I, b_I) (di, ONE
    key a position);  the FIRST dr channels of both (ASSUMED:
    `assumed.index_rope_channels` = "first") rotated on the pairs (2i,
    2i+1) (`indexer_rope_interleave` true) at the same theta;
    w_j = (u W_Iw)_j J^-1/2 di^-1/2
    I(t, s) = sum_j w_j(t) relu(qI_j(t) . kI(s)),  s <= t
    S_t = the index_topk positions s <= t of largest I(t, s): all of
    them while t < index_topk; equal scores to the LOWER position
    (`lax.top_k`'s rule)
  [k_nope_h ; v_h] = c_kv W_kvb                          (H x (dn + dv))
  o_h(t) = sum_{s in S_t} softmax_{s in S_t}((dn + dr)^-1/2 [q_nope_h(t) ;
      q_rope_h(t)] . [k_nope_h(s) ; k_r(s)]) v_h(s)
  y = [o_h] W_o                                 (no gate on the heads)

Experts, x = rms(a; g_ff): s = sigmoid(x W_r) in float32 over all
`n_routed_experts_scored`; c = s + b (`topk_method` noaux_tc); S = the
num_experts_per_tok largest c (`n_group` 1: no groups; ties to the lower
index); w_e = routed_scaling_factor s_e / sum_{j in S} s_j; y = sum_{e in
S, e held} w_e E_e(x) + E_shared(x), E(x; W) = (silu(x W_gate) * (x
W_up)) W_down.

The PREDICTION LAYER (the source's layer 78; DeepSeek-V3 section 2.2 and
its released modelling code). For position i, once t_{i+1} is known:
  h'_i = [rms(E[t_{i+1}]; g_e) ; rms(h^_i; g_h)] W_eh     (2d -> d)
      (ASSUMED: `assumed.mtp_concat` = "embedding_first": the embedding
      is the FIRST half; `assumed.mtp_hidden` = "after_final_norm": h^_i
      is the model's final hidden row AFTER its last norm)
  one decoder layer of the kind above over h'_0 .. h'_i, sparse
      (ASSUMED: `assumed.mtp_layer` = "sparse_with_indexer"), with
      parameters of its own
  draft logits_i = rms(.; g_s) W_head  through the model's OWN table and
      head (ASSUMED: `assumed.mtp_shares` = "table_and_head")
Its argmax at i is the draft for t_{i+2} (ASSUMED: `assumed.draft_tokens`
= 1: one prediction layer, one drafted token).

DEPARTURES from the published model, each also in the configuration's
file: float32 activations, latent rows and index keys for bfloat16 (the
matrices ARE bfloat16 values: the seed's weights rounded once, read here
as float32); the indexer without its Hadamard rotation (an orthogonal
map of both sides leaves every score as it was) and without FP8 storage;
`held` = [lo, hi) is the chip's share of the routed experts
(`experts_held`): what the absent experts would add is left out, here as
in the program; the vocabulary is the slice the file states; no
capacity: every pair on a held expert is computed; the layers are those
`layers_built` names.

`precision` chooses how a matmul is computed and what is stored, for
the control of the correctness check (as `reference/dots3.py`):
  "highest"  float32 operands, `jax.lax.Precision.HIGHEST` (the truth)
  "bf16_ops" matmul operands rounded to bfloat16, float32 accumulation,
             float32 everything else (what an f32 matmul is on a TPU at
             default precision, and what a bfloat16 matrix under a
             rounded activation is: the arithmetic the configuration
             states); the indexer's weighted sum over its heads is
             float32 multiplies and adds
  "bf16"     as "bf16_ops", and every stored activation, latent row,
             index key, key and value rounded to bfloat16
  "fp8_w"    as "bf16_ops", the bfloat16 MATRICES read as stored in
             float8 e4m3 with a scale a matrix (`Fp8Matrices`): the step
             below the type the configuration holds them in
A precision may name a variant after a `+` ("bf16_ops+all_rows"): the
forward pass with one part changed, for the runs that show that the
comparison sees each mechanism (`VARIANTS`). The router's scores are
float32 at `highest` in every precision (the program computes them so).
Parameter names are the program's (`lm.l1.attention.index.k.w`,
`lm.l2.moe.experts.gate.w`: the held experts alone; the prediction
layer's `lm.mtp.enorm.w`, `lm.mtp.eh_proj.w`, `lm.mtp.l<L>.attention...`).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "bf16_ops", "bf16")
# a precision of the MATRICES one step below the bfloat16 the
# configuration holds them in, for the control: `serve_logits(...,
# precision="fp8_w")` is "bf16_ops" on `Fp8Matrices(params)`
MATRIX_CONTROL = "fp8_w"
# all_rows: no selection; topk_half: index_topk / 2; index_key_one_stale:
# at a decode step the newest position's index key is missing (its row
# cannot be chosen); index_half_split: the indexer rotates the half-split
# pairs (i, i + dr/2) in place of (2i, 2i+1); v_half: the LAST dv / 2
# channels of every value head are left out (a value head of 128 where
# the config says 256); mtp_concat_reversed: [hidden ; embedding];
# mtp_hidden_before_norm: the prediction layer is handed h_L, not h^
VARIANTS = ("", "all_rows", "topk_half", "index_keys_not_rotated",
            "index_key_one_stale", "index_half_split", "v_half",
            "kr_not_rotated", "no_shared", "no_bias",
            "mtp_concat_reversed", "mtp_hidden_before_norm")
_HI = jax.lax.Precision.HIGHEST
_ROWS = 1024       # query rows of a group of heads attended at a time
_HEADS = 16        # heads whose q, k and v exist at a time
_INDEX_ROWS = 128  # query rows whose 64 index heads are scored at a time
ASSUMED = {"mtp_concat": "embedding_first",
           "mtp_hidden": "after_final_norm",
           "mtp_shares": "table_and_head",
           "mtp_layer": "sparse_with_indexer",
           "index_rope_channels": "first",
           "draft_tokens": 1}


def make_ops(precision: str):
    """(matmul, store): `matmul(a, b)` contracts a's last with b's
    first axis; `store(x)` is applied to every activation kept."""
    if precision not in PRECISIONS:
        raise ValueError(precision)

    def mm(a, b):
        # a matrix may be HELD in bfloat16 (its values are the model's):
        # read as float32, one matrix at a time
        if precision == "highest":
            return jnp.matmul(a, b.astype(jnp.float32), precision=_HI)
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)

    def store(x):
        if precision == "bf16":
            return x.astype(jnp.bfloat16).astype(jnp.float32)
        return x

    return mm, store


class Fp8Matrices(dict):
    """Parameters whose bfloat16 matrices READ as stored in float8
    (e4m3, one float32 scale a matrix: its largest magnitude at the
    type's 448), one matrix at a time: the step below the type the
    configuration holds them in. float32 parameters (gains, the router)
    read as they are."""

    def __getitem__(self, name):
        v = dict.__getitem__(self, name)
        if v.dtype != jnp.bfloat16:
            return v
        f = v.astype(jnp.float32)
        s = jnp.max(jnp.abs(f)) / 448.0
        return ((f / s).astype(jnp.float8_e4m3fn).astype(jnp.float32)
                * s).astype(jnp.bfloat16)


def _matrices(params, precision):
    """(params as `precision` reads them, the arithmetic it names)."""
    if precision == MATRIX_CONTROL:
        return Fp8Matrices(params), "bf16_ops"
    return params, precision


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


def dense_layers(cfg: dict) -> int:
    """The leading dense layers built (`dense_layers_built` where the
    depth is cut: the source's three count once)."""
    return int(cfg.get("dense_layers_built", cfg["first_k_dense_replace"]))


def layer_kinds(cfg: dict, n_layer: int):
    """"dense" | "sparse" of layers 0..n_layer-1."""
    return ["dense" if i < dense_layers(cfg) else "sparse"
            for i in range(n_layer)]


def theta_of(cfg: dict) -> float:
    assert cfg["rope_parameters"]["rope_type"] == "default"
    return float(cfg["rope_parameters"]["rope_theta"])


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


def rotate_first_pairs(x, r: int, theta: float):
    """x (T, ..., d): its FIRST r channels rotated at positions 0..T-1
    on the pairs (2i, 2i+1); the rest pass through."""
    return jnp.concatenate([rotate_pairs(x[..., :r], theta), x[..., r:]],
                           axis=-1)


def rotate_first_half_split(x, r: int, theta: float):
    """x (T, ..., d): its FIRST r channels rotated at positions 0..T-1,
    channel i paired with channel i + r/2 (the variant
    `index_half_split`: NOT what the config says)."""
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


def index_keys(p, u, cfg, precision, variant=""):
    """(T, di): the index key a position KEEPS, one full layer's."""
    mm, store = make_ops(precision)
    rotate = (rotate_first_half_split if variant == "index_half_split"
              else rotate_first_pairs)
    eps = cfg["rms_norm_eps"]
    k_i = mm(u, p["index.k.w"])
    mu = jnp.mean(k_i, -1, keepdims=True)
    k_i = ((k_i - mu) * jax.lax.rsqrt(
        jnp.mean(jnp.square(k_i - mu), -1, keepdims=True) + eps)
        * p["index.k_norm.w"] + p["index.k_norm.b"])
    if variant != "index_keys_not_rotated":
        k_i = rotate(k_i, cfg["qk_rope_head_dim"], theta_of(cfg))
    return store(k_i)


def selection(p, u, c_q, cfg, precision, variant="", handover=-1):
    """(T, T) bool: S_t row by row, of one full layer."""
    mm, store = make_ops(precision)
    t = u.shape[0]
    j, di, dr = (cfg["index_n_heads"], cfg["index_head_dim"],
                 cfg["qk_rope_head_dim"])
    theta = theta_of(cfg)
    assert cfg["indexer_rope_interleave"]
    rotate = (rotate_first_half_split if variant == "index_half_split"
              else rotate_first_pairs)
    k = {"all_rows": 0, "topk_half": cfg["index_topk"] // 2}.get(
        variant, cfg["index_topk"])
    q_i = rotate(mm(c_q, p["index.q.w"]).reshape(t, j, di), dr, theta)
    k_i = index_keys(p, u, cfg, precision, variant)
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


def latent_rows(p, u, cfg, precision, variant=""):
    """(c_kv (T, rank), k_r (T, dr)): the latent row a position KEEPS."""
    mm, store = make_ops(precision)
    r = cfg["kv_lora_rank"]
    row = mm(u, p["kv_a.w"])
    c_kv = _rms(row[:, :r], p["kv_norm.w"], cfg["rms_norm_eps"])
    k_r = row[:, r:]
    if variant != "kr_not_rotated":
        k_r = rotate_pairs(k_r, theta_of(cfg))
    return store(c_kv), store(k_r)


def attention(p, u, cfg, precision, variant="", handover=-1, probe=None):
    """u (T, d) -> (T, d): one layer's latent attention under the
    indexer, expanded."""
    mm, store = make_ops(precision)
    assert cfg["rope_interleave"] and not cfg["attention_bias"]
    t, d = u.shape
    h, dn, dr, dv, r = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                        cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                        cfg["kv_lora_rank"])
    assert cfg["qk_head_dim"] == dn + dr
    eps, theta = cfg["rms_norm_eps"], theta_of(cfg)
    c_q = store(_rms(mm(u, p["q_a.w"]), p["q_norm.w"], eps))
    c_kv, k_r = latent_rows(p, u, cfg, precision, variant)
    chosen = selection(p, u, c_q, cfg, precision, variant, handover)
    if probe is not None:
        probe.append(np.asarray(chosen))
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
        v = store(kv[..., dn:])
        if variant == "v_half":
            v = v.at[..., dv // 2:].set(0.0)
        q, k, v = (jnp.moveaxis(x, 1, 0) for x in (q * a, k, v))
        ctx.append(jnp.concatenate([
            _attend(q[:, r0:r0 + _ROWS], k, v, chosen[r0:r0 + _ROWS],
                    precision)
            for r0 in range(0, t, _ROWS)]))
    ctx = jnp.concatenate(ctx, axis=1)  # (T, H, dv)
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
    s = jax.nn.sigmoid(jnp.matmul(x, w_router.astype(jnp.float32),
                                  precision=_HI))
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
    # (dict.items: a view of the values as they are held, whatever the
    # mapping's own reading of them)
    return type(p)({n[len(prefix):]: v for n, v in dict.items(p)
                    if n.startswith(prefix)})


def _decoder_layer(p, h, cfg, ffn, precision, variant, handover, probe):
    """One decoder layer: h (T, d) -> (T, d)."""
    _, store = make_ops(precision)
    eps = cfg["rms_norm_eps"]
    u = store(_rms(h, p["norm_in.w"], eps))
    h = store(h + attention(_sub(p, "attention."), u, cfg, precision,
                            variant, handover, probe))
    u = store(_rms(h, p["norm_ff.w"], eps))
    if ffn == "dense":
        q = _sub(p, "mlp.")
        y = gated_mlp(u, q["gate.w"], q["up.w"], q["down.w"], precision)
    else:
        y = moe(_sub(p, "moe."), u, cfg, precision, variant=variant)
    return store(h + y)


def hidden(params, tokens, cfg, n_layer, precision="highest", variant="",
           handover=-1, probe=None, before_norm=False):
    """Final-norm output h^ (T, d) of one sequence `tokens` (T,), a
    layer at a time (`before_norm`: (h_L, h^)). `variant` (one of
    `VARIANTS`) changes one thing, for the runs that show the comparison
    sees it; `handover` is the prompt's last position, after which a
    served model decodes a token a step (`index_key_one_stale`). `probe`
    (a list) receives each layer's (T, T) choice."""
    if variant not in VARIANTS:
        raise ValueError(variant)
    check_assumed(cfg)
    _, store = make_ops(precision)
    with jax.default_matmul_precision("highest"):
        h = store(params["lm.tok_emb"][tokens].astype(jnp.float32))
        for i, ffn in enumerate(layer_kinds(cfg, n_layer)):
            h = _decoder_layer(_sub(params, "lm.l%d." % i), h, cfg, ffn,
                               precision, variant, handover, probe)
        hn = store(_rms(h, params["lm.norm_f.w"], cfg["rms_norm_eps"]))
        return (h, hn) if before_norm else hn


def first_layer_rows(params, tokens, cfg, precision="highest"):
    """What layer 0 KEEPS of each position of one sequence: (index keys
    (T, di), latent rows (T, rank + dr) = [c_kv ; k_r]), as the
    program's `index_0` and `latent_0` entries hold them. Layer 0 reads
    the embedding alone: no choice of rows lies upstream of these, so
    they are compared without the selection's near-ties between
    (`lib/run_serveround.py`), and "bf16" differs from "bf16_ops" here
    by the rounding of what is stored and by nothing else (a variant
    named after a `+` changes nothing here)."""
    params, precision = _matrices(params, precision.split("+", 1)[0])
    check_assumed(cfg)
    _, store = make_ops(precision)
    p = _sub(params, "lm.l0.")
    with jax.default_matmul_precision("highest"):
        h = store(params["lm.tok_emb"][tokens].astype(jnp.float32))
        u = store(_rms(h, p["norm_in.w"], cfg["rms_norm_eps"]))
        a = _sub(p, "attention.")
        c_kv, k_r = latent_rows(a, u, cfg, precision)
        return (index_keys(a, u, cfg, precision),
                jnp.concatenate([c_kv, k_r], axis=-1))


def serve_logits(params, tokens, cfg, n_layer, precision="highest",
                 rows=None, variant=""):
    """(T or len(rows), V) logits of one sequence through the head's
    own matrix: the serving runner's call (`rows[0]` is the prompt's
    last position)."""
    if "+" in precision:
        precision, variant = precision.split("+", 1)
    params, precision = _matrices(params, precision)
    mm, _ = make_ops(precision)
    handover = -1 if rows is None else int(np.asarray(rows)[0])
    h = hidden(params, tokens, cfg, n_layer, precision, variant, handover)
    if rows is not None:
        h = h[rows]
    with jax.default_matmul_precision("highest"):
        return mm(h, params["lm.head.w"])


def draft_logits(params, tokens, next_tokens, cfg, n_layer,
                 precision="highest", rows=None, variant=""):
    """(T or len(rows), V): the PREDICTION LAYER's logits of one
    sequence: row i, from the model's h^_i and `next_tokens[i]` =
    t_{i+1}, scores the token after next, t_{i+2}. `tokens`,
    `next_tokens` (T,): a caller that serves greedily hands the model's
    own choices as `next_tokens` where the prompt ends."""
    if "+" in precision:
        precision, variant = precision.split("+", 1)
    params, precision = _matrices(params, precision)
    mm, store = make_ops(precision)
    handover = -1 if rows is None else int(np.asarray(rows)[0])
    h, hn = hidden(params, tokens, cfg, n_layer, precision, variant,
                   handover, before_norm=True)
    if variant == "mtp_hidden_before_norm":
        hn = h
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        e = store(_rms(store(params["lm.tok_emb"][next_tokens].astype(
            jnp.float32)),
                       params["lm.mtp.enorm.w"], eps))
        g = store(_rms(hn, params["lm.mtp.hnorm.w"], eps))
        both = ([g, e] if variant == "mtp_concat_reversed" else [e, g])
        x = store(mm(jnp.concatenate(both, axis=-1),
                     params["lm.mtp.eh_proj.w"]))
        x = _decoder_layer(_sub(params, "lm.mtp.l%d." % n_layer), x, cfg,
                           "sparse", precision, variant, handover, None)
        x = store(_rms(x, params["lm.mtp.norm.w"], eps))
        if rows is not None:
            x = x[rows]
        return mm(x, params["lm.head.w"])
