"""Decoder LMs of a DESCRIBED block: a mixer kind (``mamba`` |
``attention`` | ``sliding`` | ``gmu`` | ``cross`` | ``latent`` | ``kda`` |
``latent_dsa`` | ``latent_ring`` | ``eva``) times a feed-forward
kind (``dense`` | ``experts``) a layer, one normalization (RMS, or
LayerNorm with bias) before every mixer and feed-forward and after the
last layer, no biases but the convolution's and ``dt_proj``'s and,
where asked, the attention projections'.
``serving.decode.DecodeConfig`` says which:

- AI21's Jamba (`model_type: jamba`): Mamba-1 layers with one attention
  layer every ``attn_layer_period``, each followed by a gated-SiLU MLP,
  NO position term (the state-space layers carry order), logits through
  the tied token table;
- poolside's Laguna (`model_type: laguna`): full and sliding-window
  attention layers mixed, query heads by layer on shared key/value
  heads of their own width, rotary positions by layer kind (plain,
  partial, YaRN), a sigmoid gate a query head on the attention output,
  a leading dense MLP and then routed experts with a shared one
  (``ops/moe.py``: no token dropped, the experts held here), an untied
  output head;
- Microsoft's Phi-4-mini-flash (`model_type: phi4flash`, SambaY): a
  first half of Mamba-1 and sliding-window layers, one Mamba layer
  that also hands on its MEMORY (the scan's output before the gate),
  one full-attention layer whose K and V are the model's only
  full-length cache, then gated memory units (``gmu``: the memory
  times a gate from the layer's input) and cross layers (``cross``:
  queries of their own on that one layer's K and V) that keep NOTHING;
  every attention differential (``ops/diff_attn.py``), LayerNorm, no
  positions;
- Mistral's Mistral-Small-4 (`model_type: mistral4`; DeepSeek-V2's
  layer): every layer multi-head latent attention (``latent``,
  ``ops/mla.py``: a prefill EXPANDS the latent rows to K and V of
  every head and runs the flash kernel, a decode step attends the
  latent slab itself, ABSORBED), rotary pairs (2i, 2i+1) under YaRN
  with a position-dependent query scale, then routed experts under a
  softmax router with a shared one, an untied head;
- inclusionAI's Ling-3.0-flash (`model_type: bailing_hybrid`): five
  Kimi-Delta-Attention layers (``kda``, ``ops/kda.py``: a matrix state
  a head written by a delta rule under a decay a channel; a prefill
  runs the CHUNKED form, a step one update) to one latent layer whose
  query has no bottleneck and whose query/key head (192) is wider than
  its value head (128), a sigmoid gate a head on both mixers' output,
  two leading dense MLPs and then routed experts under a sigmoid
  router with a selection bias and group-limited choice;
- dots-studio's dots3-note-prev (`model_type: dots3_note`): full layers
  of latent attention UNDER A LEARNED INDEXER (``latent_dsa``,
  ``ops/dsa.py``: a query attends the ``index_topk`` earlier positions
  the indexer scores highest), three sliding layers to one of latent
  attention OF ANOTHER GEOMETRY over a window (``latent_ring``), both
  with rescaled latents and a gate a head, of so many heads that a
  prefill expands a group of them at a time (``ops/mla.py:
  latent_prefill``); a leading dense MLP, then routed experts under a
  sigmoid router with a selection bias;
- Xiaomi's MiMo-V2-Flash (`model_type: mimo_v2_flash`): five sliding
  layers of 128 positions to one full layer, 8 key/value heads on a
  sliding layer and 4 on a full one, query and key heads of 192 channels
  (the first 64 rotated, each kind by its own theta) over value heads of
  128 scaled by 0.707, one learned SINK a query head in a sliding
  layer's softmax (it joins the denominator and takes no value), no
  gate; a leading dense MLP, then routed experts under a sigmoid router
  with a selection bias and NO shared expert;
- EvaByte (`model_type: evabyte`): a byte-level model whose every layer
  is EVA (``eva``, ``ops/eva.py``): a query attends its own window of
  2,048 bytes exactly and every earlier window through one pooled key
  and value a chunk of 16 bytes (pooled by a head's learned ``phi``,
  offset by its ``mu``), under one softmax; rotary positions, RMS norms
  whose parameter is the gain's distance from one (``norm_offset``), a
  gated-SiLU MLP, an untied head of 320 ids.

- Z.ai's GLM-5 (`model_type: glm_moe_dsa`): EVERY layer latent
  attention under a learned indexer (``latent_dsa``; dots3's full layer
  without the head gate and the rescale, value heads of 256 under
  query/key heads of 192 + 64, the indexer's rotation INTERLEAVED),
  three leading dense MLPs and then routed experts with a shared one
  under a sigmoid router with a selection bias, matrices HELD in
  bfloat16 (``matrix_dtype``), and ONE multi-token-prediction layer
  (``n_predict_layers``; ``_mtp``: two norms, ``eh_proj``, a decoder
  layer of the same kind with its own cache entries, a norm, the
  SHARED table and head) that drafts the token after next:
  ``hybrid_lm_round`` runs the model on a slot's current token and its
  draft, two positions a slot, and the prediction layer behind it.

The serving graphs only (serving/decode.py): ``hybrid_lm_prefill``
walks padded prompts and returns every layer's cache entries AT EACH
ROW'S LENGTH; ``hybrid_lm_decode`` advances them by one token. Both
are derived from ONE description of a layer, ``_layer``: its kind and
whether it is handed a cache entry decide what it builds, and the
parameter set is written once. A layer may own no cache entry and read
another's: what a layer hands on to the layers after it (the memory,
the keys and values) travels in ``shared``. The layers from
``cfg.tail_start`` on own nothing, so a prefill runs them on each
prompt's LAST row alone: the rows of the memory and of K and V they
need exist already, and nobody reads their other rows. Exact, and the
architecture's prefill saving.

Cache entries, by feed name (``serving.decode.cache_spec``): a Mamba
layer ``i`` keeps ``conv_i`` (B, K - 1, d_inner), the convolution's
window, and ``ssm_i`` (B, d_inner, N), the recurrent state: fixed
size, no row per position. An attention layer keeps ``kcache_i`` /
``vcache_i`` (B, S, n_kv_head, d_head) slabs: the key/value heads as
they are, never repeated for the query heads that share them. A
sliding layer keeps ``kring_i`` / ``vring_i`` (B, window, n_kv_head,
d_head): position p at row p mod window. Keys are stored ROTATED.
Under differential attention a slab or ring row is FLAT, (B, S | window,
n_kv_head * d_head) (``ops/diff_attn.py`` says why); where the two
attention kinds differ in their key/value heads or V's heads are
narrower than K's (``DecodeConfig.uneven_kv``: MiMo-V2), a full layer's
slabs are flat too, ``kcache_i`` (B, S, heads * d_head) beside
``vcache_i`` (B, S, heads * v_head), and a ring keeps (B, window, its
own heads, width); a ``gmu`` or
``cross`` layer keeps nothing. A latent layer keeps ONE ``latent_i`` (B,
S, kv_lora_rank + qk_rope_dim): a position's ``[c_kv ; k_r]``, the
latent normalised and the shared key row rotated, neither K nor V. A
KDA layer keeps ``convq_i``, ``convk_i``, ``convv_i`` (B, K - 1, H *
dk), the three convolutions' windows, and ``kda_i`` (B, H, dk, dv), the
delta rule's state: fixed size, replaced whole by an admission and
rewritten whole by every step. A latent layer under an indexer keeps its
``latent_i`` and ``index_i`` (B, S, index_head_dim), a position's index
key; a latent layer over a window ONE ``lring_i`` (B, window, its own
kv_lora_rank + qk_rope_dim): position p at row p mod window. An EVA
layer keeps ``keva_i`` / ``veva_i`` (B, max_len / eva_chunk + window,
n_head, d_head): the pooled rows, the last chunk first, and after them
the window's block, position p at p mod window, live up to p. A
prediction layer is layer ``n_layer`` for its entries: ``index_<n_layer>``
and ``latent_<n_layer>``, rows per position as any layer's under an
indexer, written by a prefill, a step and a round alike.
"""
from __future__ import annotations

import numpy as np

from .. import layers
from ..framework import default_main_program
from ..initializer import ConstantInitializer, NormalInitializer
from ..ops import diff_attn as _D
from ..ops import eva as _EVA
from ..ops import kv_cache as _KV
from ..ops import mla as _MLA
from ..ops.dsa import DSA_ATTEND
from ..ops.kda import KDA_BETA_MAX, KDA_GATES
from ..ops.moe import ROUTER_SCORES
from ..param_attr import ParamAttr
from .transformer import sample_next


def cache_names(kind: str, i: int):
    """Feed names of layer ``i``'s cache entries, in the order ``_layer``
    takes and returns them."""
    if kind in ("gmu", "cross"):
        return []  # reads what another layer keeps
    if kind == "mamba":
        return ["conv_%d" % i, "ssm_%d" % i]
    if kind == "sliding":
        return ["kring_%d" % i, "vring_%d" % i]
    if kind == "latent":
        return ["latent_%d" % i]
    if kind == "latent_dsa":
        return ["index_%d" % i, "latent_%d" % i]
    if kind == "latent_ring":
        return ["lring_%d" % i]
    if kind == "kda":
        return ["convq_%d" % i, "convk_%d" % i, "convv_%d" % i,
                "kda_%d" % i]
    if kind == "eva":
        return ["keva_%d" % i, "veva_%d" % i]
    return ["kcache_%d" % i, "vcache_%d" % i]


def _proj(x, size, name, bias=False, dtype="float32"):
    """(B, T, in) -> (B, T, size); N(0, 0.02) weight ``name.w``, held
    in ``dtype`` (``DecodeConfig.matrix_dtype``; a matrix of another
    type than its float32 input is its own parameter under a matmul:
    ``ops/math.py: wmm``)."""
    if dtype != "float32":
        return layers.matmul(x, _param(
            [int(x.shape[-1]), size], name + ".w",
            NormalInitializer(0.0, 0.02), dtype=dtype))
    return layers.fc(
        x, size, num_flatten_dims=2,
        param_attr=ParamAttr(name=name + ".w",
                             initializer=NormalInitializer(0.0, 0.02)),
        bias_attr=ParamAttr(name=name + ".b") if bias else False)


def _rms(x, name, eps, unit_offset=False):
    return layers.rms_norm(x, epsilon=eps, unit_offset=unit_offset,
                           param_attr=ParamAttr(name=name + ".w"))


def _norm(x, name, cfg):
    """The block's normalization over the last axis."""
    if cfg.norm == "layer_norm":
        return layers.layer_norm(
            x, begin_norm_axis=len(x.shape) - 1, epsilon=cfg.norm_eps,
            param_attr=ParamAttr(name=name + ".w"),
            bias_attr=ParamAttr(name=name + ".b"))
    return _rms(x, name, cfg.norm_eps, cfg.norm_offset)


def _param(shape, name, init, is_bias=False, dtype="float32"):
    """A parameter of its own: float32, or a MATRIX in the type the
    manifest holds matrices in (gains, biases, the router and its bias
    stay float32 whatever that is)."""
    return layers.create_parameter(
        shape=shape, dtype=dtype, is_bias=is_bias,
        attr=ParamAttr(name=name, initializer=init))


def _mamba_mixer(u, cfg, name, lengths, cache):
    """Mamba-1, with Jamba's RMS norms on delta, B and C where
    ``cfg.mamba_norms``. ``cache`` is None (prefill: scan from zero,
    state and window at ``lengths``) or (window, state) (one token).
    Returns (out, (window, state), memory): the memory is the scan's
    output with the ``D`` skip, BEFORE the ``silu(z)`` gate."""
    di, n, r, k = (cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank,
                   cfg.mamba_d_conv)
    x, z = layers.split(_proj(u, 2 * di, name + ".in_proj"), 2, dim=-1)
    conv_w = _param([di, k], name + ".conv.w", NormalInitializer(0.0, 0.02))
    conv_b = _param([di], name + ".conv.b", ConstantInitializer(0.0),
                    is_bias=True)
    if cache is None:
        x, window = layers.causal_conv1d(x, conv_w, conv_b, lengths)
    else:
        x, window = layers.causal_conv1d_step(x, cache[0], conv_w, conv_b)
    x = layers.swish(x, beta=1.0)  # SiLU
    dt, b, c = layers.split(_proj(x, r + 2 * n, name + ".x_proj"),
                            [r, n, n], dim=-1)
    if cfg.mamba_norms:
        dt = _rms(dt, name + ".dt_norm", cfg.norm_eps)
        b = _rms(b, name + ".b_norm", cfg.norm_eps)
        c = _rms(c, name + ".c_norm", cfg.norm_eps)
    delta = layers.softplus(_proj(dt, di, name + ".dt_proj", bias=True))
    a_log = _param([di, n], name + ".A_log", ConstantInitializer(0.0))
    a = layers.scale(layers.exp(a_log), scale=-1.0)
    d = _param([di], name + ".D", ConstantInitializer(1.0))
    if cache is None:
        y, state = layers.ssm_scan(x, delta, a, b, c, d, lengths)
    else:
        y, state = layers.ssm_step(x, delta, a, b, c, d, cache[1])
    gated = layers.elementwise_mul(y, layers.swish(z, beta=1.0))
    return (_proj(gated, cfg.d_model, name + ".out_proj"), (window, state),
            y)


def _diff_params(cfg, name):
    """A differential layer's four lambda vectors and the gain its
    heads share: ((lq1, lk1, lq2, lk2), gain)."""
    lam = NormalInitializer(0.0, 0.1)
    return (tuple(_param([cfg.d_head], "%s.lambda_%s" % (name, n), lam)
                  for n in ("q1", "k1", "q2", "k2")),
            _param([2 * cfg.d_head], name + ".subln.w",
                   ConstantInitializer(1.0)))


def stream_view(cfg, seq, dtype="float32"):
    """The view (``ops/decode_stream.py``) of the op that reads
    ``cfg``'s full-length cache of ``seq`` positions in a decode step,
    by the layer kind that keeps it, as ``_layer`` picks the op: a
    latent layer's ``mla_decode``; under differential attention the
    full layer's ``diff_decode_attention`` (and the cross layers'
    ``attn_cross``) over flat rows; where the key/value heads differ
    by layer kind or V's width is its own, ``decode_attention_uneven``
    over flat rows of two widths; else ``decode_attention`` over slabs
    of heads (OPT's block too), under the full layers' query heads, not
    the sliding layers'."""
    kinds = cfg.layer_kinds()
    if "eva" in kinds:  # whatever ``seq``: the entry is max_len's
        return _EVA.eva_view(sum(cfg.eva_rows), cfg.n_head, cfg.d_head,
                             dtype)
    if {"latent", "latent_dsa"} & set(kinds):
        # under an indexer the one-pass kernel over the chosen rows
        view = (_MLA.chosen_view if "latent_dsa" in kinds
                else _MLA.latent_view)
        return view(seq, cfg.n_head, cfg.latent_row, cfg.kv_lora_rank,
                    dtype)
    heads = max((cfg.heads(i) for i, k in enumerate(kinds)
                 if k == "attention"), default=cfg.n_head)
    if cfg.diff_attn:
        return _D.rows_view(seq, heads, cfg.kv_row[0], 2 * cfg.d_head,
                            dtype)
    if cfg.uneven_kv:
        return _KV.uneven_view(seq, heads, cfg.kv_heads("attention"),
                               cfg.d_head, cfg.v_head, dtype)
    return _KV.decode_view(seq, heads, cfg.n_kv_head, cfg.d_head, dtype)


def _attention_mixer(u, cfg, name, lengths, cache, i=0, kind="attention"):
    """Layer ``i``'s query heads on ``cfg.kv_heads(kind)`` key/value
    heads (``n_kv_head``, or the layer kind's own), no bias, V's heads of
    ``cfg.v_head`` channels times ``cfg.attn_value_scale``; rotary
    positions where ``cfg.rope`` names this layer kind (a prefill
    rotates row t at t, a decode step its one row at ``lengths``).
    ``cache`` is None (prefill: the forward-only
    ``prefill_attention`` at the rows' ``lengths``, within the window
    on a sliding layer: on a TPU the flash forward on bfloat16 operands,
    ``ptpu.flash_fwd`` or ``ptpu.attn_window`` in a trace; the entries
    are this prompt's k and v, packed into a ring on a sliding layer) or
    the layer's two entries (one token: append at ``lengths``, or at
    ``lengths mod window`` into a ring, and attend). A per-head sigmoid
    gate from the layer's input scales the attention output where
    ``cfg.attn_gate`` asks. Under ``cfg.diff_attn`` the attention is
    differential and k, v and the cache entries keep FLAT rows
    (``cfg.kv_row``: the projection's own output). Under
    ``cfg.uneven_kv`` a full layer's slabs keep flat rows too, K's
    wider than V's, read by ``decode_attention_uneven``, and a sliding
    layer's softmax takes the learned sink ``name.sink`` (H,) into its
    denominator where ``cfg.attn_sink`` names it. Returns (out, (k,
    v))."""
    B, T, _ = u.shape
    h, hkv, dh, dv = cfg.heads(i), cfg.kv_heads(kind), cfg.d_head, cfg.v_head
    bias = cfg.attn_biases
    sliding = kind == "sliding"
    uneven = cfg.uneven_kv
    k_row, v_row = cfg.kv_rows(kind)
    q = layers.reshape(_proj(u, h * dh, name + ".q", bias),
                       shape=[B, T, h, dh])
    # under ``uneven_kv`` k and v are (heads, width) here; a full
    # layer's rows go FLAT into its slabs below
    k = layers.reshape(_proj(u, hkv * dh, name + ".k", bias),
                       shape=[B, T] + list((hkv, dh) if uneven else k_row))
    v = _proj(u, hkv * dv, name + ".v", bias)
    if cfg.attn_value_scale != 1.0:
        v = layers.scale(v, scale=float(cfg.attn_value_scale))
    v = layers.reshape(v, shape=[B, T] + list((hkv, dv) if uneven
                                              else v_row))
    sink = (_param([h], name + ".sink", ConstantInitializer(0.0))
            if cfg.has_sink(kind) else None)
    diff = _diff_params(cfg, name) if cfg.diff_attn else None
    lam0 = _D.lambda_init(i)
    rot = (cfg.rope or {}).get("sliding" if sliding else "full")
    if rot:
        at = None if cache is None else lengths
        q = layers.rope(q, at, **rot)
        k = layers.rope(k, at, **rot)

    def rows(x, row):
        """x (B, T, heads, width) as the layer's cache keeps it."""
        if uneven and not sliding:
            return layers.reshape(x, shape=[B, T] + list(row))
        return x

    if cache is None:
        window = cfg.window if sliding else 0
        if diff:
            ctx = layers.diff_attention(
                q, k, v, *diff, lam_init=lam0, window=window,
                epsilon=cfg.norm_eps, lengths=lengths)
        else:
            # the op repeats k and v for the query heads that share them
            ctx = layers.prefill_attention(q, k, v, lengths, window=window,
                                           sink=sink)
        if sliding:
            k = layers.ring_pack(k, lengths, cfg.window)
            v = layers.ring_pack(v, lengths, cfg.window)
        k, v = rows(k, k_row), rows(v, v_row)
    else:
        append = layers.ring_append if sliding else layers.cache_append
        k = append(cache[0], rows(k, k_row), lengths)
        v = append(cache[1], rows(v, v_row), lengths)
        kv_lengths = layers.elementwise_add(
            layers.cast(lengths, "int32"),
            layers.fill_constant(shape=[B], dtype="int32", value=1))
        if diff:
            ctx = layers.diff_decode_attention(
                q, k, v, kv_lengths, *diff, lam_init=lam0, ring=sliding,
                epsilon=cfg.norm_eps)
        elif sliding:
            ctx = layers.decode_attn_ring(q, k, v, kv_lengths, sink=sink)
        elif uneven:
            ctx = layers.decode_attention_uneven(q, k, v, kv_lengths, hkv)
        else:
            ctx = layers.decode_attention(q, k, v, kv_lengths)
    ctx = _head_gate(ctx, u, cfg, name, h)
    out = _proj(layers.reshape(ctx, shape=[B, T, h * dv]), cfg.d_model,
                name + ".o", bias)
    return out, (k, v)


def _eva_mixer(u, cfg, name, lengths, cache):
    """EVA (``ops/eva.py``): ``n_head`` heads on as many key/value
    heads, q and k rotated by ``cfg.rope["full"]`` at their absolute
    positions, a head's two learned vectors ``phi`` (what pools a
    chunk's keys and values) and ``mu`` (added to the pooled key).
    ``cache`` is None (prefill: every chunk's summaries, each window's
    queries on [the summaries of the windows closed before it | its own
    rows, causal]; the entries are the prompt's summaries and the block
    of the window its next position lies in, packed as a step finds
    them) or the layer's two entries (one token: its row into the block
    and, where it closes a chunk, that chunk's summaries; then the live
    range under one softmax). Returns (out, (k entry, v entry))."""
    B, T, _ = u.shape
    h, dh = cfg.n_head, cfg.d_head
    w, c = int(cfg.window), int(cfg.eva_chunk)
    q, k, v = (layers.reshape(_proj(u, h * dh, "%s.%s" % (name, part)),
                              shape=[B, T, h, dh]) for part in "qkv")
    vec = NormalInitializer(0.0, 0.02)
    phi = _param([h, dh], name + ".phi", vec)
    mu = _param([h, dh], name + ".mu", vec)
    rot = (cfg.rope or {}).get("full")
    if rot:
        at = None if cache is None else lengths
        q = layers.rope(q, at, **rot)
        k = layers.rope(k, at, **rot)
    if cache is None:
        ks, vs = layers.eva_summaries(k, v, phi, mu, c)
        ctx = layers.eva_prefill(q, k, v, ks, vs, lengths, w, c)
        n_sum = cfg.eva_rows[0]
        entries = (layers.eva_pack(k, ks, lengths, w, n_sum),
                   layers.eva_pack(v, vs, lengths, w, n_sum))
    else:
        entries = layers.eva_append(cache[0], cache[1], k, v, lengths, phi,
                                    mu, w, c)
        ctx = layers.eva_decode(q, entries[0], entries[1], lengths, w, c)
    out = _proj(layers.reshape(ctx, shape=[B, T, h * dh]), cfg.d_model,
                name + ".o")
    return out, tuple(entries)


def _latent_mixer(u, cfg, name, lengths, cache):
    """Multi-head latent attention (``ops/mla.py``). ``cache`` is None
    (prefill: the EXPANDED path, K and V of every head from the latent
    rows, then ``mla_attend`` at ``cfg.softmax_scale``: the serving
    prefills' one entry ``prefill_attention`` at the rows' ``lengths``,
    whose flash kernel takes V at its own width, so one call serves a
    query/key head as wide as the value head (128 / 128) and a wider
    one (192 / 128); the entry is the prompt's latent rows) or the
    layer's one entry (one token: append its row at ``lengths``, then
    the ABSORBED path over the slab). ``W_kvb`` is one parameter for
    both. Returns (out, (latent rows or slab,))."""
    B, T, _ = u.shape
    h, d = cfg.n_head, cfg.d_model
    nope, rdim, vdim = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    w = NormalInitializer(0.0, 0.02)
    one = ConstantInitializer(1.0)
    rot = (cfg.rope or {}).get("latent") or {}
    scale = (cfg.softmax_scale if cfg.softmax_scale is not None
             else float(nope + rdim) ** -0.5)
    at = None if cache is None else lengths
    if cfg.q_lora_rank:
        q = layers.mla_q(
            u, _param([d, cfg.q_lora_rank], name + ".q_a.w", w),
            _param([cfg.q_lora_rank], name + ".q_norm.w", one),
            _param([cfg.q_lora_rank, h * (nope + rdim)], name + ".q_b.w",
                   w),
            h, rdim, rot, positions=at, epsilon=cfg.norm_eps)
    else:  # no bottleneck: one projection from the layer's input
        q = layers.mla_q(
            u, None, None, _param([d, h * (nope + rdim)], name + ".q.w", w),
            h, rdim, rot, positions=at, epsilon=cfg.norm_eps)
    rows = layers.mla_kv(
        u, _param([d, cfg.latent_row], name + ".kv_a.w", w),
        _param([cfg.kv_lora_rank], name + ".kv_norm.w", one),
        rdim, rot, positions=at, epsilon=cfg.norm_eps)
    w_kvb = _param([cfg.kv_lora_rank, h * (nope + vdim)], name + ".kv_b.w",
                   w)
    if cache is None:
        k, v = layers.mla_expand(rows, w_kvb, h, nope)
        ctx = layers.mla_attend(q, k, v, scale, lengths)
    else:
        rows = layers.mla_append(cache[0], rows, lengths)
        kv_lengths = layers.elementwise_add(
            layers.cast(lengths, "int32"),
            layers.fill_constant(shape=[B], dtype="int32", value=1))
        ctx = layers.mla_decode(q, rows, kv_lengths, w_kvb, scale)
    ctx = _head_gate(ctx, u, cfg, name, h)
    out = _proj(layers.reshape(ctx, shape=[B, T, h * vdim]), d, name + ".o")
    return out, (rows,)


def _wide_latent_mixer(u, cfg, name, lengths, cache, kind):
    """A latent layer of MANY heads: under a learned indexer
    (``latent_dsa``, ``ops/dsa.py``: a query attends the ``index_topk``
    earlier positions the indexer scores highest) or of a geometry of
    its own over the last ``window`` positions (``latent_ring``). The
    normalised latents are rescaled where ``cfg.latent_rescale``.
    ``cache`` is None (prefill: the expanded attention a group of heads
    at a time, ``ops/mla.py: latent_prefill``, under the indexer's (B,
    T, T) choice or over the window; the entries are the prompt's index
    keys and latent rows, or its last ``window`` rows packed into a
    ring) or the layer's entries (one token: append, then the absorbed
    path over the chosen rows of the slab, or over the ring; under an
    indexer also a WINDOW of T > 1 tokens at positions ``lengths ..
    lengths + T - 1``: T rows appended to both slabs, each row's own
    choice among the rows at or before it, the T x H query rows of a
    slot on one stream of its live blocks). Returns (out, entries in
    ``cache_names`` order)."""
    B, T, _ = u.shape
    geo, d, eps = cfg.latent_geometry(kind), cfg.d_model, cfg.norm_eps
    h, nope, rdim, vdim = geo.n_head, geo.nope, geo.rope, geo.v
    ring = kind == "latent_ring"
    w = NormalInitializer(0.0, 0.02)
    one = ConstantInitializer(1.0)
    md = cfg.matrix_dtype
    rot = (cfg.rope or {}).get("latent_ring" if ring else "latent") or {}
    at = None if cache is None else lengths
    c_q = _rms(_proj(u, geo.q_rank, name + ".q_a", dtype=md),
               name + ".q_norm", eps)
    if geo.rho_q != 1.0:
        c_q = layers.scale(c_q, scale=geo.rho_q)
    w_qb = _param([geo.q_rank, h * (nope + rdim)], name + ".q_b.w", w,
                  dtype=md)
    rows = layers.mla_kv(
        u, _param([d, geo.row], name + ".kv_a.w", w, dtype=md),
        _param([geo.rank], name + ".kv_norm.w", one),
        rdim, rot, positions=at, epsilon=eps, rescale=geo.rho_kv)
    w_kvb = _param([geo.rank, h * (nope + vdim)], name + ".kv_b.w", w,
                   dtype=md)
    w_o = _param([h * vdim, d], name + ".o.w", w, dtype=md)
    gate = (layers.sigmoid(_proj(u, h, name + ".gate", dtype=md))
            if cfg.attn_gate == "per_head" else None)
    if not ring:
        j, di = int(cfg.index_heads), int(cfg.index_head_dim)
        irot = cfg.rope["index"]
        keys = layers.dsa_index_keys(
            u, _param([d, di], name + ".index.k.w", w, dtype=md),
            _param([di], name + ".index.k_norm.w", one),
            _param([di], name + ".index.k_norm.b", ConstantInitializer(0.0),
                   is_bias=True),
            irot, positions=at, epsilon=eps)
        index = (_param([geo.q_rank, j * di], name + ".index.q.w", w,
                        dtype=md),
                 _param([d, j], name + ".index.weights.w", w, dtype=md))
    scope = _MLA.LATENT_RING_ATTEND if ring else DSA_ATTEND
    if cache is None:
        mask = None if ring else layers.dsa_mask(
            c_q, u, index[0], index[1], keys, j, cfg.index_topk, irot,
            lengths=lengths)
        out = layers.latent_prefill(
            c_q, rows, w_qb, w_kvb, w_o, h, nope, geo.scale, rot, gate=gate,
            window=cfg.window if ring else 0, mask=mask, lengths=lengths,
            scope=scope)
        if ring:
            return out, (layers.ring_pack(rows, lengths, cfg.window),)
        return out, (keys, rows)
    q = layers.mla_q(c_q, None, None, w_qb, h, rdim, rot, positions=at,
                     epsilon=eps)
    kv_lengths = layers.elementwise_add(
        layers.cast(lengths, "int32"),
        layers.fill_constant(shape=[B], dtype="int32", value=1))
    if ring:
        # a ring's live rows are min(positions held, window): a length
        # past its rows reads as "every row"
        entries = (layers.mla_append(cache[0], rows, lengths, ring=True),)
        ctx = layers.mla_decode(q, entries[0], kv_lengths, w_kvb, geo.scale,
                                scope=scope)
    else:
        entries = (layers.mla_append(cache[0], keys, lengths),
                   layers.mla_append(cache[1], rows, lengths))
        chosen = layers.dsa_mask(
            c_q, u, index[0], index[1], entries[0], j, cfg.index_topk, irot,
            positions=lengths, lengths=kv_lengths)
        ctx = layers.mla_decode(q, entries[1], kv_lengths, w_kvb, geo.scale,
                                chosen=chosen, scope=scope)
    if gate is not None:
        ctx = layers.elementwise_mul(
            ctx, layers.reshape(gate, shape=[B, T, h, 1]))
    return layers.matmul(layers.reshape(ctx, shape=[B, T, h * vdim]),
                         w_o), entries


def _head_gate(ctx, u, cfg, name, h, rank=0):
    """ctx (B, T, h, dv) times a sigmoid gate from the layer's input
    where ``cfg.attn_gate`` asks: "per_head", one a head (``name.gate.w``
    (D, h)); "per_channel", one a channel of every head, through one
    matrix (``name.gate.w`` (D, h * dv)) or, where ``rank``, through a
    bottleneck (``name.gate_a.w`` (D, rank), ``name.gate_b.w`` (rank, h
    * dv): Kimi Linear's low-rank output gate)."""
    if cfg.attn_gate is None:
        return ctx
    B, T, _, dv = ctx.shape
    if cfg.attn_gate == "per_head":
        gate = layers.sigmoid(_proj(u, h, name + ".gate"))
        return layers.elementwise_mul(
            ctx, layers.reshape(gate, shape=[B, T, h, 1]))
    if rank:
        gate = _proj(_proj(u, rank, name + ".gate_a"), h * dv,
                     name + ".gate_b")
    else:
        gate = _proj(u, h * dv, name + ".gate")
    return layers.elementwise_mul(
        ctx, layers.reshape(layers.sigmoid(gate), shape=[B, T, h, dv]))


def _kda_mixer(u, cfg, name, lengths, cache):
    """Kimi Delta Attention (``ops/kda.py``): q, k and v each through a
    causal depthwise convolution and a SiLU, q and k L2-normalised a
    head, a log-decay a key channel and a write strength a head from
    the layer's input (``kda_gate``), the delta rule, an RMS norm a
    head with one gain of ``kda_head_dim`` and the per-head output
    gate. ``cache`` is None (prefill: the CHUNKED scan from a zero
    state; the entries are the three windows and the state at
    ``lengths``) or (window q, window k, window v, state) (one token).
    No positions: the recurrence carries order. Returns (out,
    entries)."""
    B, T, _ = u.shape
    h, dk, kc = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv
    w = NormalInitializer(0.0, 0.02)
    mixed, windows = [], []
    for j, part in enumerate("qkv"):
        x = _proj(u, h * dk, "%s.%s" % (name, part))
        conv_w = _param([h * dk, kc], "%s.conv_%s.w" % (name, part), w)
        if cache is None:
            x, window = layers.causal_conv1d(x, conv_w, None, lengths)
        else:
            x, window = layers.causal_conv1d_step(x, cache[j], conv_w)
        mixed.append(layers.reshape(layers.swish(x, beta=1.0),
                                    shape=[B, T, h, dk]))
        windows.append(window)
    rank = int(cfg.kda_decay_rank)
    if rank:  # Kimi Linear's bottleneck: (D, rank) and (rank, H * dk)
        f = _proj(_proj(u, rank, name + ".f_a"), h * dk, name + ".f_b")
    else:
        f = _proj(u, h * dk, name + ".f")
    g, beta = layers.kda_gate(
        f, _proj(u, h, name + ".beta"),
        _param([h], name + ".A_log", ConstantInitializer(0.0)),
        _param([h * dk], name + ".dt_bias", ConstantInitializer(0.0)),
        cfg.kda_gate, cfg.kda_gate_bound, cfg.kda_beta_max)
    if cache is None:
        o, state = layers.kda_scan(
            *mixed, g, beta, lengths,
            lower_bound=(cfg.kda_gate_bound
                         if cfg.kda_gate == "lower_bound_sigmoid" else None))
    else:
        o, state = layers.kda_step(*mixed, g, beta, cache[3])
    o = _head_gate(_rms(o, name + ".o_norm", cfg.norm_eps), u, cfg, name, h,
                   rank)
    out = _proj(layers.reshape(o, shape=[B, T, h * dk]), cfg.d_model,
                name + ".o")
    return out, tuple(windows) + (state,)


def _cross_mixer(u, cfg, name, i, kv):
    """Queries of this layer's own on the keys and values ANOTHER layer
    keeps: ``kv`` = (k, v, rows seen (B,)): that layer's slab after this
    step's append, or its prompt rows in a prefill. u is (B, 1, D): one
    query row a sequence, at its last position (a prefill that runs
    every layer on every row hands all T rows in, each of which sees
    the keys up to its own: the causal op, for the test of the
    shortcut)."""
    B, T, _ = u.shape
    h, dh, bias = cfg.heads(i), cfg.d_head, cfg.attn_biases
    q = layers.reshape(_proj(u, h * dh, name + ".q", bias),
                       shape=[B, T, h, dh])
    k, v, seen = kv
    diff = _diff_params(cfg, name)
    if T == 1:
        ctx = layers.attn_cross(q, k, v, seen, *diff,
                                lam_init=_D.lambda_init(i),
                                epsilon=cfg.norm_eps)
    else:
        ctx = layers.diff_attention(q, k, v, *diff,
                                    lam_init=_D.lambda_init(i),
                                    epsilon=cfg.norm_eps, lengths=seen)
    return _proj(layers.reshape(ctx, shape=[B, T, h * dh]), cfg.d_model,
                 name + ".o", bias)


def _gmu_mixer(u, cfg, name, memory):
    """``W_out (memory * silu(W_in u))``: the memory row for row."""
    w = NormalInitializer(0.0, 0.02)
    di = cfg.mamba_d_inner
    return layers.gmu(u, memory,
                      _param([cfg.d_model, di], name + ".in_proj.w", w),
                      _param([di, cfg.d_model], name + ".out_proj.w", w))


def _mlp(x, cfg, name):
    md = cfg.matrix_dtype
    gate = layers.swish(_proj(x, cfg.d_inner, name + ".gate", dtype=md),
                        beta=1.0)
    up = _proj(x, cfg.d_inner, name + ".up", dtype=md)
    return _proj(layers.elementwise_mul(gate, up), cfg.d_model,
                 name + ".down", dtype=md)


def _experts(x, cfg, name, lengths, decode):
    """Routed experts, with a shared one where ``d_shared_expert``: x
    (B, T, D) -> (out, load (experts held,) int32). The router scores
    all ``n_expert``; the experts ``cfg.held`` are the ones computed."""
    d, f = cfg.d_model, cfg.d_expert
    lo, hi = cfg.held
    w = NormalInitializer(0.0, 0.02)
    idx, weights = layers.moe_route(
        x, _param([d, cfg.n_expert], name + ".router.w", w),
        cfg.expert_top_k, scale=cfg.router_scale, score=cfg.router_score,
        bias=(_param([cfg.n_expert], name + ".router.bias",
                     NormalInitializer(0.0, 0.01))
              if cfg.router_bias else None),
        n_group=cfg.router_groups, topk_group=cfg.router_topk_groups)
    md = cfg.matrix_dtype
    routed, load = layers.moe_experts(
        x, idx, weights,
        _param([hi - lo, d, f], name + ".experts.gate.w", w, dtype=md),
        _param([hi - lo, d, f], name + ".experts.up.w", w, dtype=md),
        _param([hi - lo, f, d], name + ".experts.down.w", w, dtype=md),
        expert_lo=lo, lengths=lengths, decode=decode,
        count_elsewhere=cfg.router_groups > 1)
    fs = cfg.d_shared_expert
    if not fs:  # no shared expert: the routed part is the layer's
        return routed, load
    shared = layers.moe_shared(
        x, _param([d, fs], name + ".shared.gate.w", w, dtype=md),
        _param([d, fs], name + ".shared.up.w", w, dtype=md),
        _param([fs, d], name + ".shared.down.w", w, dtype=md))
    return layers.elementwise_add(routed, shared), load


def _layer(x, kind, i, cfg, lengths, cache=None, loads=None, shared=None,
           ffn=None, prefix=None):
    """THE description of layer ``i``: x (B, T, D) -> (x, cache
    entries in ``cache_names(kind, i)`` order). Prefill and decode
    differ only in ``cache``. An expert layer appends its load to
    ``loads``. ``ffn`` names the feed-forward kind of a layer past the
    model's ``n_layer`` and ``prefix`` what its parameters' names begin
    with (a prediction layer's; ``cfg.ffn_kinds()[i]`` and
    ``cfg.prefix`` otherwise). ``shared`` (a dict) carries what a layer
    hands on to the layers after it: a Mamba layer its ``memory`` (B, T, Di), a full
    attention layer its ``kv`` = (k, v, rows seen): a ``gmu`` and a
    ``cross`` layer read the nearest before them and keep nothing."""
    name = "%s.l%d" % (prefix or cfg.prefix, i)
    shared = {} if shared is None else shared
    u = _norm(x, name + ".norm_in", cfg)
    entries = ()
    if kind == "mamba":
        mixed, entries, shared["memory"] = _mamba_mixer(
            u, cfg, name + ".mamba", lengths, cache)
    elif kind == "gmu":
        mixed = _gmu_mixer(u, cfg, name + ".gmu", shared["memory"])
    elif kind == "cross":
        mixed = _cross_mixer(u, cfg, name + ".cross", i, shared["kv"])
    elif kind == "latent":
        mixed, entries = _latent_mixer(u, cfg, name + ".attention",
                                       lengths, cache)
    elif kind in ("latent_dsa", "latent_ring"):
        mixed, entries = _wide_latent_mixer(u, cfg, name + ".attention",
                                            lengths, cache, kind)
    elif kind == "kda":
        mixed, entries = _kda_mixer(u, cfg, name + ".kda", lengths, cache)
    elif kind == "eva":
        mixed, entries = _eva_mixer(u, cfg, name + ".attention", lengths,
                                    cache)
    else:
        mixed, entries = _attention_mixer(u, cfg, name + ".attention",
                                          lengths, cache, i, kind)
        if kind == "attention" and "cross" in cfg.layer_kinds()[i:]:
            # a prefill's rows are seen up to each length; a step's
            # slab with the row it has just appended
            seen = layers.cast(lengths, "int32")
            if cache is not None:
                seen = layers.elementwise_add(seen, layers.fill_constant(
                    shape=[x.shape[0]], dtype="int32", value=1))
            shared["kv"] = entries + (seen,)
    x = layers.elementwise_add(x, mixed)
    u = _norm(x, name + ".norm_ff", cfg)
    if (ffn or cfg.ffn_kinds()[i]) == "experts":
        ffn, load = _experts(u, cfg, name + ".moe", lengths,
                             cache is not None)
        loads.append(load)
    else:
        ffn = _mlp(u, cfg, name + ".mlp")
    return layers.elementwise_add(x, ffn), entries


def _check(cfg):
    """Refuse what no graph here computes."""
    if (cfg.norm not in ("rms_norm", "layer_norm")
            or cfg.ffn != "gated_silu" or cfg.positions or cfg.biases):
        raise ValueError(
            "the described-block builders write RMS norms or LayerNorm, "
            "a gated-SiLU MLP, no learned positions and no biases but "
            "`attn_biases`; got norm=%r ffn=%r positions=%r biases=%r"
            % (cfg.norm, cfg.ffn, cfg.positions, cfg.biases))
    if "cross" in cfg.layer_kinds() and not cfg.diff_attn:
        raise ValueError("a cross layer is built with differential "
                         "attention alone (diff_attn)")
    if cfg.diff_attn and (cfg.rope or cfg.attn_gate):
        raise ValueError("differential attention is built without rotary "
                         "positions and without an output gate")
    if cfg.attn_gate not in (None, "per_head", "per_channel"):
        raise ValueError("attn_gate %r: a sigmoid gate a query head "
                         "('per_head') or a channel ('per_channel') is "
                         "built" % (cfg.attn_gate,))
    if cfg.attn_gate == "per_channel" and cfg.has_latent:
        raise ValueError("attn_gate 'per_channel': beside a latent layer "
                         "only a sigmoid gate a query head ('per_head') is "
                         "built")
    if "experts" in cfg.ffn_kinds() and (
            cfg.router_score not in ROUTER_SCORES
            or int(cfg.d_shared_expert) < 0):
        raise ValueError(
            "an expert layer is built with %s scores and a shared "
            "expert of some width or none (0); got router_score=%r "
            "d_shared_expert=%r"
            % (" or ".join(ROUTER_SCORES), cfg.router_score,
               cfg.d_shared_expert))
    if cfg.uneven_kv and (cfg.diff_attn or cfg.attn_biases or cfg.has_eva
                          or "cross" in cfg.layer_kinds()):
        raise ValueError(
            "key/value heads by layer kind (n_kv_head_by_kind) and a value "
            "width of its own (v_head_dim) are built without differential "
            "attention (its rows are one flat kv_row), biases, cross and "
            "EVA layers")
    if set(cfg.attn_sink or ()) - {"sliding"} or (cfg.attn_sink and (
            cfg.diff_attn or "sliding" not in cfg.layer_kinds())):
        raise ValueError(
            "attn_sink %r: a learned sink is built in the softmax of "
            "sliding layers alone, without differential attention"
            % (cfg.attn_sink,))
    if float(cfg.attn_value_scale) != 1.0 and (
            cfg.diff_attn or not {"attention", "sliding"}
            & set(cfg.layer_kinds())):
        raise ValueError(
            "attn_value_scale %r scales the values of full and sliding "
            "layers, without differential attention"
            % (cfg.attn_value_scale,))
    for kind, rot in (cfg.rope or {}).items():
        if kind == "index":
            # an indexer's queries and keys: their first channels, plain,
            # half-split or on the pairs (2i, 2i+1) (``interleave``)
            if (set(rot) - {"interleave"} != {"theta", "rotary_dim"}
                    or not isinstance(rot.get("interleave", False), bool)
                    or not (0 < int(rot["rotary_dim"])
                            <= int(cfg.index_head_dim))):
                raise ValueError(
                    "rope['index'] = %r: an indexer's rotation is theta, "
                    "a rotary_dim within index_head_dim and interleave"
                    % (rot,))
            continue
        if kind in ("latent", "latent_ring"):
            # the whole rope part of a latent head turns; only YaRN
            # carries the original context the query scale counts in
            unknown = set(rot) - {"theta", "yarn", "attention_factor",
                                  "interleave", "scale_beta"}
            if unknown or (rot.get("scale_beta") and not rot.get("yarn")):
                raise ValueError(
                    "rope[%r] = %r: a latent layer's rotation is "
                    "theta, yarn, attention_factor, interleave and "
                    "scale_beta (with yarn's original_max_position)"
                    % (kind, rot))
            continue
        if kind not in ("full", "sliding") or rot.get(
                "rotary_dim", cfg.d_head) > cfg.d_head:
            raise ValueError("rope[%r] = %r does not describe a rotation "
                             "of a head of %d" % (kind, rot, cfg.d_head))
    if (cfg.has_latent or "kda" in cfg.layer_kinds()) and (
            cfg.diff_attn or cfg.attn_biases):
        raise ValueError("a latent or a KDA layer is built without "
                         "differential attention and without biases")
    if "latent_dsa" in cfg.layer_kinds() and "index" not in (cfg.rope
                                                             or {}):
        raise ValueError("a latent layer under an indexer needs "
                         "rope['index'] = {theta, rotary_dim}")
    if cfg.has_eva and (cfg.diff_attn or cfg.attn_biases or cfg.attn_gate
                        or cfg.n_head_by_layer):
        raise ValueError("an EVA layer is built without differential "
                         "attention, biases, an output gate and head "
                         "counts by layer")
    if cfg.n_predict_layers and (
            cfg.layer_kinds()[-1] != "latent_dsa" or cfg.tie_embeddings
            or cfg.tail_start < cfg.n_layer):
        raise ValueError(
            "a prediction layer (n_predict_layers) is one more layer of "
            "the model's last kind with entries of its own, built over a "
            "last layer under an indexer ('latent_dsa': rows a position, "
            "rolled back by length) and an untied head; got %r, "
            "tie_embeddings=%r" % (cfg.layer_kinds()[-1],
                                   cfg.tie_embeddings))
    if cfg.matrix_dtype != "float32" and set(cfg.layer_kinds()) != {
            "latent_dsa"}:
        raise ValueError(
            "matrix_dtype %r: matrices are held in bfloat16 for layers "
            "under an indexer alone ('latent_dsa': the ops that meet them "
            "round the activation, ops/math.py: wmm); got layers %s"
            % (cfg.matrix_dtype, sorted(set(cfg.layer_kinds()))))
    if cfg.matrix_dtype != "float32" and cfg.head_precision is not None:
        raise ValueError(
            "head_precision %r with matrix_dtype %r: a head held in "
            "bfloat16 meets its rows rounded to bfloat16 (ops/math.py: "
            "wmm); float32 products need a float32 head"
            % (cfg.head_precision, cfg.matrix_dtype))
    if cfg.head_precision not in (None, "highest"):
        raise ValueError("head_precision %r: the head is computed at the "
                         "device's default precision (None) or 'highest'"
                         % (cfg.head_precision,))
    if cfg.norm_offset and cfg.norm != "rms_norm":
        raise ValueError("norm_offset is an RMS norm's (1 + g); got norm=%r"
                         % (cfg.norm,))
    if "kda" in cfg.layer_kinds() and cfg.kda_gate not in KDA_GATES:
        raise ValueError("kda_gate %r: a KDA layer's decay gate is %s"
                         % (cfg.kda_gate, " or ".join(KDA_GATES)))
    if float(cfg.kda_beta_max) not in KDA_BETA_MAX:
        raise ValueError("kda_beta_max %r: a KDA layer's write strength "
                         "lies in (0, 1) or in (0, 2)" % (cfg.kda_beta_max,))
    rank = cfg.kda_decay_rank
    if rank and (int(rank) != rank or not
                 0 < rank < cfg.kda_heads * cfg.kda_head_dim):
        raise ValueError(
            "kda_decay_rank %r: the decay's projection is one matrix (0) "
            "or a bottleneck narrower than its %d outputs"
            % (cfg.kda_decay_rank, cfg.kda_heads * cfg.kda_head_dim))
    if set(cfg.ffn_kinds()) - {"dense", "experts"} or set(
            cfg.attn_types or ()) - {"full", "sliding"}:
        raise ValueError("ffn_types %r / attn_types %r name a kind no "
                         "graph computes" % (cfg.ffn_types, cfg.attn_types))


def _embed(tokens, cfg):
    """The table's rows of ``tokens``: float32 activations, whatever
    type the table is held in."""
    md = cfg.matrix_dtype
    x = layers.embedding(
        input=tokens, size=[cfg.vocab_size, cfg.d_model], dtype=md,
        param_attr=ParamAttr(name=cfg.prefix + ".tok_emb",
                             initializer=NormalInitializer(0.0, 0.02)))
    return x if md == "float32" else layers.cast(x, "float32")


def _head(last, cfg):
    """(B, D) -> (B, V), no bias: through the tied table, or through
    the head's own matrix ``head.w`` (D, V); in float32 products where
    ``cfg.head_precision`` is "highest"."""
    if not cfg.tie_embeddings:
        # a second call (a prediction layer's logits) is handed the
        # same parameter: ``create_parameter`` finds it by name
        return layers.matmul(last, _param(
            [cfg.d_model, cfg.vocab_size], cfg.prefix + ".head.w",
            NormalInitializer(0.0, 0.02), dtype=cfg.matrix_dtype),
            precision=cfg.head_precision)
    emb = default_main_program().global_block().var(cfg.prefix + ".tok_emb")
    return layers.matmul(last, emb, transpose_y=True,
                         precision=cfg.head_precision)


def _mtp(hidden, next_tokens, cfg, lengths, cache=None, loads=None):
    """The multi-token-prediction layer (DeepSeek-V3, arXiv:2412.19437,
    section 2.2; ``cfg.n_predict_layers`` 1): for position i, once
    ``t_{i+1}`` is known, ``h'_i = W_eh [rms_e(Emb(t_{i+1})) ;
    rms_h(h^_i)]`` (``hidden`` (B, T, D): the model's final hidden rows
    AFTER its last norm; ``next_tokens`` (B, T) int), ONE decoder layer
    of the model's own kind (its last layer's mixer and feed-forward)
    with ITS OWN cache entries (layer index ``n_layer``), a norm, and
    the model's table and head, SHARED. Every parameter of its own is
    named ``<prefix>.mtp.*`` (its decoder layer ``<prefix>.mtp.l<n_layer>.
    *``), so a device trace tells its operations by their scopes'
    anchors. ``cache`` as ``_layer``'s.
    Returns (x (B, T, D) normalised: ``_head`` of a row is the logits of
    the token after next, ``t_{i+2}``; the layer's cache entries)."""
    B, T, _ = hidden.shape
    name = "%s.mtp" % cfg.prefix
    i = cfg.n_layer
    e = layers.reshape(_embed(next_tokens, cfg), shape=[B, T, cfg.d_model])
    both = layers.concat([_norm(e, name + ".enorm", cfg),
                          _norm(hidden, name + ".hnorm", cfg)], axis=-1)
    x = _proj(both, cfg.d_model, name + ".eh_proj", dtype=cfg.matrix_dtype)
    x, entries = _layer(x, cfg.layer_kinds()[-1], i, cfg, lengths,
                        cache=cache, loads=loads, ffn=cfg.ffn_kinds()[-1],
                        prefix=name)
    return _norm(x, name + ".norm", cfg), entries


def _predict(hidden, next_tokens, cfg, lengths, caches, new, loads, extras):
    """Run the prediction layer inside a serving graph: on ``hidden``
    and ``next_tokens`` (``_mtp``), its cache entries read from
    ``caches`` (None: a prefill) and written into ``new``; ``extras``
    then takes ``moe_load`` with the layer's row last. Returns its
    normalised output (B, T, D)."""
    names = cache_names(cfg.layer_kinds()[-1], cfg.n_layer)
    m, entries = _mtp(
        hidden, next_tokens, cfg, lengths, loads=loads,
        cache=None if caches is None else tuple(caches[n] for n in names))
    new.update(zip(names, entries))
    if extras is not None and loads:
        extras["moe_load"] = layers.stack(loads, axis=0)
    return m


def hybrid_lm_prefill(tokens, lengths, cfg, extras=None,
                      one_row_tail=True):
    """Padded prompts ``tokens`` (B, S), ``lengths`` (B,) -> (logits
    (B, V) of each row's last real position, {feed name: cache entry}):
    slab entries hold the prompt's k and v rows (garbage past a row's
    length, masked by length later), state entries the state and the
    window after each row's LAST REAL token, ring entries each row's
    last ``window`` positions as a decode step will find them.
    ``extras`` (a dict) receives ``moe_load`` where layers route over
    experts: the pairs of REAL tokens each held expert received. The
    layers from ``cfg.tail_start`` on run on each row's last real
    position alone; ``one_row_tail=False`` runs them on every row, for
    the test that shows the two equal."""
    _check(cfg)
    B, S = tokens.shape
    x = _embed(tokens, cfg)
    caches, loads, shared, at = {}, [], {}, []

    def last_rows(a):
        """(B, S, W) -> (B, W): each row's last real position."""
        flat = layers.reshape(a, shape=[B * S, a.shape[-1]])
        if not at:
            base = layers.assign(
                (np.arange(B, dtype=np.int32) * S - 1).reshape(B))
            at.append(layers.elementwise_add(layers.cast(lengths, "int32"),
                                             base))
        return layers.gather(flat, at[0])

    tail = cfg.tail_start if one_row_tail else cfg.n_layer
    for i, kind in enumerate(cfg.layer_kinds()):
        if i == tail:
            # no layer from here on owns a cache entry: one row a prompt
            x = layers.reshape(last_rows(x), shape=[B, 1, cfg.d_model])
            if "memory" in shared:
                shared["memory"] = layers.reshape(
                    last_rows(shared["memory"]),
                    shape=[B, 1, cfg.mamba_d_inner])
        x, entries = _layer(x, kind, i, cfg, lengths, loads=loads,
                            shared=shared)
        caches.update(zip(cache_names(kind, i), entries))
    if extras is not None and loads and not cfg.n_predict_layers:
        # (sparse layers, experts held) int32
        extras["moe_load"] = layers.stack(loads, axis=0)
    x = _norm(x, cfg.prefix + ".norm_f", cfg)
    if tail < cfg.n_layer:
        return _head(layers.reshape(x, shape=[B, cfg.d_model]), cfg), caches
    logits = _head(last_rows(x), cfg)
    if cfg.n_predict_layers:
        # the prediction layer walks the prompt too: position i with the
        # token after it, the last real position with the token the
        # model has just chosen (greedy); its logits there are the first
        # DRAFT's, for the token after that one
        chosen = layers.argmax(logits, axis=-1)
        m = _predict(x, layers.mtp_next_tokens(tokens, lengths, chosen),
                     cfg, lengths, None, caches, loads, extras)
        if extras is not None:
            extras["draft"] = layers.argmax(_head(last_rows(m), cfg),
                                            axis=-1)
    return logits, caches


def hybrid_lm_decode(tokens, lengths, caches, cfg, strategy="greedy",
                     seed=None, sample_k=40, sample_p=0.9, temperature=1.0,
                     extras=None):
    """One token per slot: ``tokens`` (B, 1), ``lengths`` (B,) tokens
    each slot holds BEFORE this one, ``caches`` {feed name: entry} ->
    (next_ids (B,) or None, logits (B, V), {feed name: updated
    entry}). A model with a prediction layer has ONE step graph,
    ``hybrid_lm_round``: its plain step is a round (``serving/decode.py:
    _StepOfRound``), and it is refused here."""
    _check(cfg)
    if cfg.n_predict_layers:
        raise ValueError(
            "a model with a prediction layer steps by rounds "
            "(hybrid_lm_round); its plain greedy step is the round with "
            "the current token standing in for the draft, and no "
            "sampling step is built over it")
    B = tokens.shape[0]
    # embedding squeezes the trailing ids dim of 1: restore the time axis
    x = layers.reshape(_embed(tokens, cfg), shape=[B, 1, cfg.d_model])
    new, loads, shared = {}, [], {}
    for i, kind in enumerate(cfg.layer_kinds()):
        names = cache_names(kind, i)
        x, entries = _layer(x, kind, i, cfg, lengths,
                            cache=tuple(caches[n] for n in names),
                            loads=loads, shared=shared)
        new.update(zip(names, entries))
    if extras is not None and loads:
        # (sparse layers, experts held) int32
        extras["moe_load"] = layers.stack(loads, axis=0)
    x = _norm(x, cfg.prefix + ".norm_f", cfg)
    logits = _head(layers.reshape(x, shape=[B, cfg.d_model]), cfg)
    next_ids = sample_next(logits, strategy, seed, sample_k, sample_p,
                           temperature)
    return next_ids, logits, new


def hybrid_lm_round(tokens, lengths, caches, cfg, extras=None):
    """One ROUND of a model with a prediction layer: ``tokens`` (B, T),
    a slot's current token and the T - 1 tokens drafted after it (T =
    ``1 + cfg.n_predict_layers`` = 2), at positions ``lengths ..
    lengths + T - 1``; ``caches`` {feed name: entry}, the prediction
    layer's among them. The model runs on all T (T rows appended to each
    layer's entries, each row's own choice among the rows at or before
    it), ``spec_accept`` takes the drafted tokens the model itself would
    have chosen, and the prediction layer runs on each position's hidden
    row and the token the model chose after it. Greedy: the ids are the
    model's argmaxes. Returns (next_ids (B, T): the model's choice after
    each position, accept (B,) int32: drafted tokens taken, logits (B,
    T, V), draft_logits (B, T, V): the prediction layer's, {feed name:
    updated entry}); ``extras["draft"]`` (B,) is the draft for the NEXT
    round, read at position ``accept``: the caller commits ``next_ids[:,
    :accept + 1]`` and advances each slot's length by ``accept + 1``;
    rows past that are hypotheses the next round overwrites."""
    _check(cfg)
    B, T = tokens.shape
    D, V = cfg.d_model, cfg.vocab_size
    x = layers.reshape(_embed(tokens, cfg), shape=[B, T, D])
    new, loads, shared = {}, [], {}
    for i, kind in enumerate(cfg.layer_kinds()):
        names = cache_names(kind, i)
        x, entries = _layer(x, kind, i, cfg, lengths,
                            cache=tuple(caches[n] for n in names),
                            loads=loads, shared=shared)
        new.update(zip(names, entries))
    x = _norm(x, cfg.prefix + ".norm_f", cfg)
    logits = layers.reshape(
        _head(layers.reshape(x, shape=[B * T, D]), cfg), shape=[B, T, V])
    next_ids, accept = layers.spec_accept(tokens, logits)
    m = _predict(x, next_ids, cfg, lengths, caches, new, loads, extras)
    draft_logits = layers.reshape(
        _head(layers.reshape(m, shape=[B * T, D]), cfg), shape=[B, T, V])
    if extras is not None:
        extras["draft"] = layers.spec_pick(
            layers.argmax(draft_logits, axis=-1), accept)
    return next_ids, accept, logits, draft_logits, new
