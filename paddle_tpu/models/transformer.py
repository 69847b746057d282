"""Transformer (reference capability: Transformer NMT training à la
benchmark/fluid/machine_translation.py + the fluid transformer test nets).

TPU-first design notes:
- all attention heads in one batched matmul pair ((B*H, T, Dh) shapes keep
  the MXU saturated); softmax/dropout/residual fuse into epilogues.
- causal + padding masks are additive -inf masks built once per step from
  the lengths tensor (no ragged ops).
- `transformer_lm` is the decoder-only variant used as the flagship model
  (see __graft_entry__.py); pre-norm residuals for stable bf16 training.
"""
from __future__ import annotations

import numpy as np

from .. import layers
from ..framework import default_main_program
from ..initializer import NormalInitializer
from ..param_attr import ParamAttr


def _linear(x, size, name=None, num_flatten_dims=2, act=None):
    return layers.fc(
        input=x,
        size=size,
        num_flatten_dims=num_flatten_dims,
        act=act,
        param_attr=ParamAttr(name=name + ".w" if name else None,
                             initializer=NormalInitializer(0.0, 0.02)),
        bias_attr=ParamAttr(name=name + ".b" if name else None),
    )


def multi_head_attention(
    q_in, kv_in, n_head, d_model, dropout_rate=0.0, causal=False,
    kv_lengths=None, name=None, use_fused=True, use_ring=False,
    sp_axis="sp", fused_qkv=False,
):
    """(B, Tq, D) x (B, Tk, D) -> (B, Tq, D).

    use_fused=True routes through the flash-attention op (ops/attention.py):
    no (Tq, Tk) score tensor ever hits HBM, which is what lets seq-1024
    training batches fit a single v5e. use_ring=True routes through the
    ring_attention op instead — sequence-parallel over the mesh's
    `sp_axis` (long-context path). The unfused path is kept for numerics
    debugging.

    fused_qkv=True (self-attention only) computes q/k/v in ONE
    (D, 3D) matmul whose output columns are grouped per head
    [h0:q,k,v | h1:q,k,v | ...], so the Megatron column-parallel split
    over `mp` keeps whole (q,k,v) head groups on each device — tp-safe.
    Opt-in pending on-hardware measurement."""
    B, Tq, _ = q_in.shape
    Tk = kv_in.shape[1]
    d_head = d_model // n_head
    # BTHD: hand the fused-attention op (B, T, H, Dh) — the projection's
    # natural shape — so NO head transposes are built in fwd or bwd (they
    # were ~14%% of profiled step time). The op itself falls back to an
    # internal transpose off-TPU or when d_head isn't lane-aligned, so
    # this is always numerically safe. Ring attention keeps BHTD (its
    # sequence axis must be the ppermute'd one).
    bthd = use_fused and not use_ring

    def split_heads(x, T):
        x = layers.reshape(x, shape=[B, T, n_head, d_head])
        if bthd:
            return x  # (B, T, H, Dh) — consumed as-is
        return layers.transpose(x, perm=[0, 2, 1, 3])  # (B, H, T, Dh)

    if fused_qkv and q_in is not kv_in:
        raise ValueError(
            "fused_qkv packs q/k/v of SELF-attention into one matmul; "
            "pass the same Variable as q_in and kv_in (cross-attention "
            "must use separate projections)")
    if fused_qkv:
        qkv = _linear(q_in, 3 * d_model, name and name + ".qkv")
        # (B, T, H, 3, Dh): dim 3 separates q/k/v within each head group
        qkv = layers.reshape(qkv, shape=[B, Tq, n_head, 3, d_head])
        if bthd:
            qkv = layers.transpose(qkv, perm=[3, 0, 1, 2, 4])  # (3,B,T,H,Dh)
        else:
            qkv = layers.transpose(qkv, perm=[3, 0, 2, 1, 4])  # (3,B,H,T,Dh)
        q, k, v = layers.unstack(qkv, axis=0)
    else:
        q = _linear(q_in, d_model, name and name + ".q")
        k = _linear(kv_in, d_model, name and name + ".k")
        v = _linear(kv_in, d_model, name and name + ".v")
        q = split_heads(q, Tq)
        k = split_heads(k, Tk)
        v = split_heads(v, Tk)

    if use_ring:
        ctx = layers.ring_attention(q, k, v, causal=causal, sp_axis=sp_axis,
                                    lengths=kv_lengths,
                                    dropout_rate=dropout_rate)
    elif use_fused:
        ctx = layers.fused_attention(
            q, k, v, causal=causal, sequence_length=kv_lengths,
            dropout_rate=dropout_rate,
            layout="bthd" if bthd else "bhtd")
        if bthd:
            # already (B, Tq, H, Dh): fold heads without a transpose
            return _linear(layers.reshape(ctx, shape=[B, Tq, d_model]),
                           d_model, name and name + ".out")
    else:
        q = layers.scale(q, scale=float(d_head) ** -0.5)
        logits = layers.matmul(q, k, transpose_y=True)  # (B, H, Tq, Tk)
        mask = _attn_mask(B, Tq, Tk, causal=causal, kv_lengths=kv_lengths)
        if mask is not None:
            logits = layers.elementwise_add(logits, mask)
        weights = layers.softmax(logits)
        if dropout_rate:
            weights = layers.dropout(weights, dropout_prob=dropout_rate)
        ctx = layers.matmul(weights, v)  # (B, H, Tq, Dh)
    ctx = layers.transpose(ctx, perm=[0, 2, 1, 3])
    ctx = layers.reshape(ctx, shape=[B, Tq, d_model])
    return _linear(ctx, d_model, name and name + ".out")


def _attn_mask(B, Tq, Tk, causal=False, kv_lengths=None):
    """Additive mask (B or 1, 1, Tq, Tk): 0 keep, -1e9 drop."""
    parts = []
    if causal:
        causal_np = np.triu(np.full((Tq, Tk), -1e9, np.float32), k=1)
        causal_var = layers.assign(causal_np.reshape(1, 1, Tq, Tk))
        parts.append(causal_var)
    if kv_lengths is not None:
        # (B, Tk) padding mask from lengths
        mask = layers.sequence_mask(kv_lengths, maxlen=Tk, dtype="float32")
        neg = layers.scale(mask, scale=1e9, bias=-1e9)  # 0 where valid, -1e9 where pad
        neg = layers.reshape(neg, shape=[B, 1, 1, Tk])
        parts.append(neg)
    if not parts:
        return None
    out = parts[0]
    for p in parts[1:]:
        out = layers.elementwise_add(out, p)
    return out


def positionwise_ffn(x, d_inner, d_model, dropout_rate=0.0, name=None):
    h = _linear(x, d_inner, name and name + ".fc1", act="relu")
    if dropout_rate:
        h = layers.dropout(h, dropout_prob=dropout_rate)
    return _linear(h, d_model, name and name + ".fc2")


def _pre_norm(x, name=None):
    return layers.layer_norm(x, begin_norm_axis=len(x.shape) - 1)


def encoder_layer(x, n_head, d_model, d_inner, dropout_rate, lengths, name):
    h = _pre_norm(x)
    attn = multi_head_attention(
        h, h, n_head, d_model, dropout_rate,
        kv_lengths=lengths, name=name + ".attn",
    )
    x = layers.elementwise_add(x, attn)
    ffn = positionwise_ffn(_pre_norm(x), d_inner, d_model, dropout_rate,
                           name=name + ".ffn")
    return layers.elementwise_add(x, ffn)


def decoder_layer(x, enc, n_head, d_model, d_inner, dropout_rate,
                  src_lengths, tgt_lengths, name, use_ring=False,
                  sp_axis="sp", moe_experts=0, fused_qkv=False):
    """`enc` must already be normalized (transformer_encoder output).
    moe_experts>0 swaps the dense FFN for a mixture-of-experts block
    (layers.moe_ffn) — expert-parallel under an ep mesh."""
    h = _pre_norm(x)
    self_attn = multi_head_attention(
        h, h, n_head, d_model, dropout_rate,
        causal=True, kv_lengths=tgt_lengths, name=name + ".self",
        use_ring=use_ring, sp_axis=sp_axis, fused_qkv=fused_qkv,
    )
    x = layers.elementwise_add(x, self_attn)
    if enc is not None:
        cross = multi_head_attention(
            _pre_norm(x), enc, n_head, d_model, dropout_rate,
            kv_lengths=src_lengths, name=name + ".cross",
        )
        x = layers.elementwise_add(x, cross)
    if moe_experts:
        ffn = layers.moe_ffn(_pre_norm(x), num_experts=moe_experts,
                             d_ff=d_inner, name=name + ".moe")
        if dropout_rate:
            # the dense path drops inside positionwise_ffn; keep the MoE
            # branch equivalently regularized
            ffn = layers.dropout(ffn, dropout_prob=dropout_rate)
    else:
        ffn = positionwise_ffn(_pre_norm(x), d_inner, d_model, dropout_rate,
                               name=name + ".ffn")
    return layers.elementwise_add(x, ffn)


def _embed(ids, vocab_size, d_model, max_len, name):
    B, T = ids.shape
    tok = layers.embedding(
        input=ids, size=[vocab_size, d_model],
        param_attr=ParamAttr(name=name + ".tok_emb",
                             initializer=NormalInitializer(0.0, 0.02)),
    )
    pos_ids = layers.assign(np.arange(max_len, dtype=np.int64)[:T].reshape(1, T))
    pos = layers.embedding(
        input=pos_ids, size=[max_len, d_model],
        param_attr=ParamAttr(name=name + ".pos_emb",
                             initializer=NormalInitializer(0.0, 0.02)),
    )
    return layers.elementwise_add(tok, pos)


def transformer_encoder(src_ids, src_lengths, vocab_size, n_layer, n_head,
                        d_model, d_inner, dropout_rate=0.1, max_len=512):
    x = _embed(src_ids, vocab_size, d_model, max_len, "enc")
    for i in range(n_layer):
        x = encoder_layer(x, n_head, d_model, d_inner, dropout_rate,
                          src_lengths, "enc.l%d" % i)
    return _pre_norm(x)


def transformer_nmt(
    src_ids, src_lengths, tgt_ids, tgt_lengths, label_ids,
    src_vocab_size, tgt_vocab_size,
    n_layer=2, n_head=8, d_model=512, d_inner=2048,
    dropout_rate=0.1, max_len=512,
):
    """Encoder-decoder training graph; returns (avg_cost, logits)."""
    enc = transformer_encoder(src_ids, src_lengths, src_vocab_size, n_layer,
                              n_head, d_model, d_inner, dropout_rate, max_len)
    x = _embed(tgt_ids, tgt_vocab_size, d_model, max_len, "dec")
    for i in range(n_layer):
        x = decoder_layer(x, enc, n_head, d_model, d_inner, dropout_rate,
                          src_lengths, tgt_lengths, "dec.l%d" % i)
    x = _pre_norm(x)
    logits = _linear(x, tgt_vocab_size, "dec.head")
    B, T = tgt_ids.shape
    loss = layers.softmax_with_cross_entropy(
        layers.reshape(logits, shape=[B * T, tgt_vocab_size]),
        layers.reshape(label_ids, shape=[B * T, 1]),
    )
    # mask padding positions out of the loss
    mask = layers.sequence_mask(tgt_lengths, maxlen=T, dtype="float32")
    mask = layers.reshape(mask, shape=[B * T, 1])
    loss = layers.elementwise_mul(loss, mask)
    avg_cost = layers.elementwise_div(
        layers.reduce_sum(loss), layers.reduce_sum(mask)
    )
    return avg_cost, logits


def transformer_lm(
    ids, labels, vocab_size, n_layer=4, n_head=8, d_model=512, d_inner=2048,
    dropout_rate=0.0, max_len=2048, fused_head=True,
    use_ring_attention=False, sp_axis="sp", moe_experts=0,
    fused_qkv=False, tie_embeddings=False,
):
    """Decoder-only causal LM (flagship). Returns (avg_cost, logits).

    fused_head=True (default) computes the vocab projection + loss through
    `layers.fused_lm_head_loss` — the (B*T, vocab) logits never hit HBM —
    and returns logits=None. Pass fused_head=False when the logits tensor
    itself is needed (e.g. decoding/inspection).

    use_ring_attention=True is the LONG-CONTEXT path: every self-attention
    runs the sequence-parallel ring (layers.ring_attention), so compiling
    under a ParallelExecutor whose mesh has `sp_axis` shards the sequence
    dim across chips — seq lengths far beyond one chip's HBM. The same
    Program still runs on one device (exact-attention fallback).

    fused_qkv=True packs each layer's self-attention q/k/v into one
    (D, 3D) matmul (see multi_head_attention).

    tie_embeddings=True shares the token-embedding table with the vocab
    projection (head logits = x @ emb^T): one less (V, D) parameter, so
    the Adam f32 moment traffic and gradient convert chains on the two
    largest tensors halve — the profiled ~1.5%-of-step lever.
    Off by default: the reference benchmark model keeps
    the matrices separate (reference
    benchmark/fluid/models/machine_translation.py:1). Under a
    tensor-parallel mesh pass megatron_transformer_plan(tied=True): the
    table is then split by vocabulary rows over mp, the fused head runs
    per rank over its own rows (ops/fused_loss.py) and vocab_size must
    divide by mp. The default plan's hidden-sharded emb rule would split
    the head matmul's contracted axis (see that plan's docstring)."""
    x = _embed(ids, vocab_size, d_model, max_len, "lm")
    for i in range(n_layer):
        x = decoder_layer(x, None, n_head, d_model, d_inner, dropout_rate,
                          None, None, "lm.l%d" % i,
                          use_ring=use_ring_attention, sp_axis=sp_axis,
                          moe_experts=moe_experts, fused_qkv=fused_qkv)
    x = _pre_norm(x)
    B, T = ids.shape
    if fused_head:
        if tie_embeddings:
            # create_parameter returns the EXISTING "lm.tok_emb" (V, D)
            # table; transpose_w makes the kernel read it in place. The
            # table MUST already exist (built by _embed above) — a fresh
            # creation here would silently train untied.
            default_main_program().global_block().var("lm.tok_emb")
            head_attr = ParamAttr(name="lm.tok_emb")
        else:
            head_attr = ParamAttr(name="lm.head.w",
                                  initializer=NormalInitializer(0.0, 0.02))
        loss = layers.fused_lm_head_loss(
            x, labels, vocab_size,
            param_attr=head_attr,
            bias_attr=ParamAttr(name="lm.head.b"),
            transpose_w=tie_embeddings,
        )
        return layers.mean(loss), None
    if tie_embeddings:
        emb = default_main_program().global_block().var("lm.tok_emb")
        logits = layers.matmul(x, emb, transpose_y=True)
        bias = layers.create_parameter(
            shape=[vocab_size], dtype=logits.dtype, name="lm.head.b",
            is_bias=True)
        logits = layers.elementwise_add(logits, bias)
    else:
        logits = _linear(x, vocab_size, "lm.head")
    loss = layers.softmax_with_cross_entropy(
        layers.reshape(logits, shape=[B * T, vocab_size]),
        layers.reshape(labels, shape=[B * T, 1]),
    )
    return layers.mean(loss), logits


# ---------------------------------------------------------------------------
# incremental decode graphs (KV-cache serving path, serving/decode.py)
# ---------------------------------------------------------------------------
#
# Both builders re-create transformer_lm's parameter set NAME-FOR-NAME
# (explicitly named projections AND the auto-named layer_norm_N scale/
# bias pairs), so a scope trained through transformer_lm loads into them
# directly. That only holds when the layer-creation ORDER matches
# transformer_lm exactly — build under unique_name.guard() and keep the
# layer_norm call sequence identical (2 per layer + 1 final). A drifted
# name fails loudly at export/load time (missing persistable), and the
# prefill-vs-training logits parity test pins it.


def _cached_self_attention(h, n_head, d_model, name, k_cache=None,
                           v_cache=None, lengths=None, kv_lengths=None,
                           k_scale=None, v_scale=None, use_ring=False,
                           sp_axis="sp", window=False):
    """transformer_lm's self-attention with its K/V exposed.

    Prefill mode (no caches): full causal flash attention over (B, S);
    returns (out, k, v) with k/v in the (B, S, H, Dh) slab layout —
    exactly what decode steps attend against. Decode mode (caches
    given): h is (B, 1, D); the step's k/v rows append into the slabs
    at ``lengths`` and a single-query decode_attention runs against the
    updated slabs up to ``kv_lengths`` valid rows; returns
    (out, new_k_cache, new_v_cache). With ``k_scale``/``v_scale``
    (B, S) tensors the slabs are INT8 (the quantized-KV serving
    opt-in): appends quantize each fresh row against its own scale and
    attention dequantizes on read; returns (out, new_k, new_v,
    new_k_scale, new_v_scale). Parameter names and creation order
    match multi_head_attention(fused_qkv=False) verbatim.

    ``use_ring=True`` (prefill mode only) routes the causal attention
    through the sequence-parallel ring op instead of fused flash
    attention — the long-context prefill path: under a ParallelExecutor
    whose mesh has ``sp_axis`` the sequence dim shards across chips; on
    a single device the ring op falls back to exact attention, so the
    Program stays portable. The returned K/V slabs are the SAME
    (B, S, H, Dh) BTHD tensors either way — decode always runs dense.

    ``window=True`` (decode mode, T > 1): the speculative verify /
    prefix-extension step — T fresh rows append per slot
    (cache_append_window) and T queries attend with the staircase mask
    (decode_attention_window), so verifying k draft tokens is ONE call
    instead of k sequential steps."""
    B, T, _ = h.shape
    d_head = d_model // n_head
    q = _linear(h, d_model, name + ".q")
    k = _linear(h, d_model, name + ".k")
    v = _linear(h, d_model, name + ".v")
    q = layers.reshape(q, shape=[B, T, n_head, d_head])
    k = layers.reshape(k, shape=[B, T, n_head, d_head])
    v = layers.reshape(v, shape=[B, T, n_head, d_head])
    if k_cache is None:
        if use_ring:
            # ring attention keeps BHTD (its sequence axis is the
            # ppermute'd one); the slabs stay the BTHD projections
            qr = layers.transpose(q, perm=[0, 2, 1, 3])
            kr = layers.transpose(k, perm=[0, 2, 1, 3])
            vr = layers.transpose(v, perm=[0, 2, 1, 3])
            ctx = layers.ring_attention(qr, kr, vr, causal=True,
                                        sp_axis=sp_axis)
            ctx = layers.transpose(ctx, perm=[0, 2, 1, 3])
        else:
            ctx = layers.fused_attention(q, k, v, causal=True,
                                         layout="bthd")
        out = _linear(layers.reshape(ctx, shape=[B, T, d_model]),
                      d_model, name + ".out")
        return out, k, v
    if window:
        new_k = layers.cache_append_window(k_cache, k, lengths)
        new_v = layers.cache_append_window(v_cache, v, lengths)
        ctx = layers.decode_attention_window(q, new_k, new_v, lengths)
        out = _linear(layers.reshape(ctx, shape=[B, T, d_model]),
                      d_model, name + ".out")
        return out, new_k, new_v
    if k_scale is not None:
        new_k, new_ks = layers.cache_append_quant(k_cache, k_scale, k,
                                                  lengths)
        new_v, new_vs = layers.cache_append_quant(v_cache, v_scale, v,
                                                  lengths)
        ctx = layers.decode_attention_quant(q, new_k, new_ks, new_v,
                                            new_vs, kv_lengths)
        out = _linear(layers.reshape(ctx, shape=[B, T, d_model]),
                      d_model, name + ".out")
        return out, new_k, new_v, new_ks, new_vs
    new_k = layers.cache_append(k_cache, k, lengths)
    new_v = layers.cache_append(v_cache, v, lengths)
    ctx = layers.decode_attention(q, new_k, new_v, kv_lengths)
    out = _linear(layers.reshape(ctx, shape=[B, T, d_model]),
                  d_model, name + ".out")
    return out, new_k, new_v


def _lm_head_logits(x, vocab_size, tie_embeddings, prefix):
    """Vocab projection on a (B, D) last-hidden row; same parameters as
    transformer_lm(fused_head=False)."""
    if tie_embeddings:
        emb = default_main_program().global_block().var(prefix + ".tok_emb")
        logits = layers.matmul(x, emb, transpose_y=True)
        bias = layers.create_parameter(
            shape=[vocab_size], dtype=logits.dtype, name=prefix + ".head.b",
            is_bias=True)
        return layers.elementwise_add(logits, bias)
    return layers.fc(
        x, vocab_size, num_flatten_dims=1,
        param_attr=ParamAttr(name=prefix + ".head.w",
                             initializer=NormalInitializer(0.0, 0.02)),
        bias_attr=ParamAttr(name=prefix + ".head.b"))


def transformer_lm_prefill(
    tokens, lengths, vocab_size, n_layer=4, n_head=8, d_model=512,
    d_inner=2048, max_len=2048, tie_embeddings=False, prefix="lm",
    use_ring_attention=False, sp_axis="sp",
):
    """Prefill graph: run the full causal forward over padded prompts
    ``tokens`` (B, S) with ``lengths`` (B,) valid tokens, POPULATING the
    KV slabs as a side product of the flash-attention forward.

    Returns (last_logits, caches): last_logits (B, V) is the vocab
    projection of each row's final valid position (the hidden state is
    gathered BEFORE the head, so the (B, S, V) logits tensor never
    materializes), caches is [(k_0, v_0), ...] per layer in the
    (B, S, H, Dh) slab layout. Positions past a row's length hold
    garbage K/V — decode_attention masks them by length, so they are
    never read.

    ``use_ring_attention=True`` is the LONG-CONTEXT prefill: every
    self-attention runs the sequence-parallel ring (layers.
    ring_attention), so compiling under a mesh with ``sp_axis`` shards
    the prompt's sequence dim across chips — prompts far beyond one
    chip's dense-bucket range prefill sharded, then decode continues
    from the same dense (B, S, H, Dh) slabs. On a single device the
    ring op falls back to exact attention, so the graph is portable
    (and CPU-testable)."""
    x = _embed(tokens, vocab_size, d_model, max_len, prefix)
    B, S = tokens.shape
    caches = []
    for i in range(n_layer):
        h = _pre_norm(x)
        attn, k, v = _cached_self_attention(
            h, n_head, d_model, "%s.l%d.self" % (prefix, i),
            use_ring=use_ring_attention, sp_axis=sp_axis)
        caches.append((k, v))
        x = layers.elementwise_add(x, attn)
        ffn = positionwise_ffn(_pre_norm(x), d_inner, d_model, 0.0,
                               name="%s.l%d.ffn" % (prefix, i))
        x = layers.elementwise_add(x, ffn)
    x = _pre_norm(x)
    # gather each row's LAST VALID hidden state: flat row index
    # b*S + (lengths[b] - 1)
    flat = layers.reshape(x, shape=[B * S, d_model])
    base = layers.assign(
        (np.arange(B, dtype=np.int32) * S - 1).reshape(B))
    idx = layers.elementwise_add(layers.cast(lengths, "int32"), base)
    last = layers.gather(flat, idx)  # (B, D)
    return _lm_head_logits(last, vocab_size, tie_embeddings, prefix), caches


def sample_next(logits, strategy="greedy", seed=None, sample_k=40,
                sample_p=0.9, temperature=1.0):
    """The in-graph sampler of a decode step: next ids (B,) int64 from
    logits (B, V) per ``strategy``, or None for "logits" (host-side
    beam search samples for itself)."""
    if strategy == "greedy":
        return layers.greedy_sample(logits)
    if strategy == "topk":
        return layers.top_k_sample(logits, seed=seed, k=sample_k,
                                   temperature=temperature)
    if strategy == "topp":
        return layers.top_p_sample(logits, seed=seed, p=sample_p,
                                   temperature=temperature)
    if strategy == "logits":
        return None
    raise ValueError("unknown decode strategy %r (greedy | topk | "
                     "topp | logits)" % (strategy,))


def transformer_lm_decode(
    tokens, positions, lengths, k_caches, v_caches, vocab_size,
    n_layer=4, n_head=8, d_model=512, d_inner=2048, max_len=2048,
    tie_embeddings=False, prefix="lm", strategy="greedy", seed=None,
    sample_k=40, sample_p=0.9, temperature=1.0,
    k_scales=None, v_scales=None,
):
    """One incremental decode step: ``tokens`` (B, 1) int64 (the
    previously sampled token per slot), ``positions`` (B, 1) int64 (its
    sequence position = the slot's pre-append length), ``lengths`` (B,)
    int32 valid cache rows BEFORE this step, and per-layer K/V slabs
    (B, S, H, Dh).

    Each layer appends its fresh K/V row at ``lengths`` and runs
    single-query decode_attention over lengths+1 valid rows. Returns
    (next_ids, logits, new_caches): next_ids (B,) int64 per
    ``strategy`` ("greedy" | "topk" | "topp" | "logits" — the last
    skips sampling for host-side beam search), logits (B, V), and the
    updated slabs to thread into the next step (donated in place on
    TPU).

    With ``k_scales``/``v_scales`` (per-layer (B, S) tensors) the slabs
    are INT8 and each ``new_caches`` entry is the 4-tuple (k, v,
    k_scales, v_scales) — the quantized-KV serving graph (ops/quant.py;
    2x sequences per slab byte budget)."""
    B = tokens.shape[0]
    # embedding squeezes the trailing ids dim of 1 (LoD convention):
    # (B, 1) ids -> (B, D); restore the singleton time axis explicitly
    tok = layers.embedding(
        input=tokens, size=[vocab_size, d_model],
        param_attr=ParamAttr(name=prefix + ".tok_emb",
                             initializer=NormalInitializer(0.0, 0.02)))
    pos = layers.embedding(
        input=positions, size=[max_len, d_model],
        param_attr=ParamAttr(name=prefix + ".pos_emb",
                             initializer=NormalInitializer(0.0, 0.02)))
    x = layers.reshape(layers.elementwise_add(tok, pos),
                       shape=[B, 1, d_model])
    kv_lengths = layers.elementwise_add(
        layers.cast(lengths, "int32"),
        layers.fill_constant(shape=[B], dtype="int32", value=1))
    new_caches = []
    for i in range(n_layer):
        h = _pre_norm(x)
        if k_scales is not None:
            attn, nk, nv, nks, nvs = _cached_self_attention(
                h, n_head, d_model, "%s.l%d.self" % (prefix, i),
                k_cache=k_caches[i], v_cache=v_caches[i], lengths=lengths,
                kv_lengths=kv_lengths, k_scale=k_scales[i],
                v_scale=v_scales[i])
            new_caches.append((nk, nv, nks, nvs))
        else:
            attn, nk, nv = _cached_self_attention(
                h, n_head, d_model, "%s.l%d.self" % (prefix, i),
                k_cache=k_caches[i], v_cache=v_caches[i], lengths=lengths,
                kv_lengths=kv_lengths)
            new_caches.append((nk, nv))
        x = layers.elementwise_add(x, attn)
        ffn = positionwise_ffn(_pre_norm(x), d_inner, d_model, 0.0,
                               name="%s.l%d.ffn" % (prefix, i))
        x = layers.elementwise_add(x, ffn)
    x = _pre_norm(x)
    last = layers.reshape(x, shape=[B, d_model])
    logits = _lm_head_logits(last, vocab_size, tie_embeddings, prefix)
    next_ids = sample_next(logits, strategy, seed, sample_k, sample_p,
                           temperature)
    return next_ids, logits, new_caches


def transformer_lm_verify(
    tokens, positions, lengths, last_idx, k_caches, v_caches, vocab_size,
    n_layer=4, n_head=8, d_model=512, d_inner=2048, max_len=2048,
    tie_embeddings=False, prefix="lm",
):
    """One speculative VERIFY window (also the shared-prefix suffix
    extension step): ``tokens`` (B, T) int64 — window slot 0 is each
    sequence's committed current token, slots 1..T-1 the draft's
    proposals — at ``positions`` (B, T), with ``lengths`` (B,) valid
    cache rows BEFORE the window and per-layer K/V slabs (B, S, H, Dh).

    Every layer appends its T fresh K/V rows at lengths..lengths+T-1
    (cache_append_window) and runs T-query staircase attention
    (decode_attention_window) — the whole window is ONE executable, not
    T sequential decode steps. Returns (next_ids, accept, last_logits,
    new_caches):

    - next_ids (B, T) int64: the target's next token after each window
      position (greedy argmax — the accept test AND the emitted
      tokens);
    - accept (B,) int32: accepted-proposal count per slot (longest
      matching prefix; the caller emits next_ids[b, :accept[b]+1] and
      advances the slot length by accept[b]+1 — rejected slab rows roll
      back by length truncation, never by scatter-undo);
    - last_logits (B, V): the logits row at window position
      ``last_idx[b]`` per slot — the suffix-extension path samples its
      first token from this exactly as a private prefill would from its
      last-position logits.

    Parameter names match transformer_lm / the other decode builders,
    so the same loaded state drives all graph kinds."""
    B, T = tokens.shape
    if T < 2:
        raise ValueError(
            "verify windows need T >= 2 (one committed token + at least "
            "one proposal); got T=%d" % T)
    tok = layers.embedding(
        input=tokens, size=[vocab_size, d_model],
        param_attr=ParamAttr(name=prefix + ".tok_emb",
                             initializer=NormalInitializer(0.0, 0.02)))
    pos = layers.embedding(
        input=positions, size=[max_len, d_model],
        param_attr=ParamAttr(name=prefix + ".pos_emb",
                             initializer=NormalInitializer(0.0, 0.02)))
    x = layers.elementwise_add(tok, pos)                   # (B, T, D)
    new_caches = []
    for i in range(n_layer):
        h = _pre_norm(x)
        attn, nk, nv = _cached_self_attention(
            h, n_head, d_model, "%s.l%d.self" % (prefix, i),
            k_cache=k_caches[i], v_cache=v_caches[i], lengths=lengths,
            window=True)
        new_caches.append((nk, nv))
        x = layers.elementwise_add(x, attn)
        ffn = positionwise_ffn(_pre_norm(x), d_inner, d_model, 0.0,
                               name="%s.l%d.ffn" % (prefix, i))
        x = layers.elementwise_add(x, ffn)
    x = _pre_norm(x)
    flat = layers.reshape(x, shape=[B * T, d_model])
    logits = _lm_head_logits(flat, vocab_size, tie_embeddings, prefix)
    logits3 = layers.reshape(logits, shape=[B, T, vocab_size])
    next_ids, accept = layers.spec_accept(tokens, logits3)
    base = layers.assign((np.arange(B, dtype=np.int32) * T).reshape(B))
    idx = layers.elementwise_add(layers.cast(last_idx, "int32"), base)
    last_logits = layers.gather(logits, idx)               # (B, V)
    return next_ids, accept, last_logits, new_caches


def get_model(
    batch_size=16, seq_len=64, src_vocab_size=10000, tgt_vocab_size=10000,
    n_layer=2, n_head=8, d_model=512, d_inner=2048, dropout_rate=0.1,
):
    src = layers.data(name="src_ids", shape=[batch_size, seq_len],
                      dtype="int64", append_batch_size=False)
    src_len = layers.data(name="src_len", shape=[batch_size], dtype="int32",
                          append_batch_size=False)
    tgt = layers.data(name="tgt_ids", shape=[batch_size, seq_len],
                      dtype="int64", append_batch_size=False)
    tgt_len = layers.data(name="tgt_len", shape=[batch_size], dtype="int32",
                          append_batch_size=False)
    lbl = layers.data(name="lbl_ids", shape=[batch_size, seq_len],
                      dtype="int64", append_batch_size=False)
    avg_cost, _logits = transformer_nmt(
        src, src_len, tgt, tgt_len, lbl, src_vocab_size, tgt_vocab_size,
        n_layer, n_head, d_model, d_inner, dropout_rate,
    )
    return avg_cost, None, [src, src_len, tgt, tgt_len, lbl]
