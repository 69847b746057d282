"""Speculative-decoding window kernels (the draft-verify serving path).

Plain KV-cache decode (ops/kv_cache.py) advances one token per target
step — the per-token cost IS one full forward of the target model.
Speculative decoding breaks that bound: a cheap draft proposes k tokens,
and the target model checks ALL of them in ONE window step. These are
the window-shaped primitives that make the verify step a single compiled
call rather than k sequential decode steps:

- ``cache_append_window``: scatter T fresh K/V rows per sequence at its
  current length (``cache_append`` widened along the time axis; rows
  land at pos[b]..pos[b]+T-1).
- ``decode_attention_window``: T queries per sequence attend the slab
  with a STAIRCASE mask — window query i sees ``lengths[b] + i + 1``
  valid rows (everything committed plus the window rows up to and
  including its own). With T == 1 this is exactly ``decode_attention``.
- ``spec_accept``: the in-graph accept/reject. Given the window's
  proposed tokens and the target logits the window produced, emit the
  target's next-token ids per position plus the per-slot count of
  accepted proposals (longest matching prefix). Greedy semantics: with
  a greedy target the emitted tokens next_ids[b, :accept[b]+1] are
  token-for-token what non-speculative greedy decode would produce —
  the lossless property serving/decode.py's parity tests pin.

Rollback contract: the verify step APPENDS all T window rows, then the
caller advances each slot's length by only ``accept + 1`` — rejected
rows stay in the slab as garbage beyond the valid length, masked by
every later attention read and overwritten by later appends (the same
discipline as prefill's past-length garbage rows). No slab copy, no
scatter-undo: rollback is per-slot length truncation.

A model that publishes a multi-token-prediction layer drafts for
itself (``models/jamba.py: hybrid_lm_round``): ``spec_accept`` as it
is, ``mtp_next_tokens`` (what that layer is fed beside a prompt) and
``spec_pick`` (the next draft, read where the round stopped). Its rows
per position are latent rows and index keys (``ops/mla.py: mla_append``
of a window); the rollback contract is the one above.

The same window graph doubles as the shared-prefix SUFFIX EXTENSION
path (serving/prefix.py): a prompt whose header is prefix-cached feeds
its remaining suffix through the verify executable chunk by chunk —
multi-token cached prefill — instead of paying a full private prefill.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from .registry import register_op

_NEG = -1e30


def cache_append_window(cache, new, pos):
    """cache (B, S, ...) with new (B, T, ...) scattered at rows
    pos[b]..pos[b]+T-1 per sequence -> updated cache. Functional; under
    donation XLA updates the slab in place. Rows whose target index
    lands past S-1 are DROPPED (mode="drop"), never clipped: clipping
    would alias several window rows onto row S-1 and XLA scatter with
    duplicate indices is order-unspecified — a real row near the slab
    end could be corrupted by a dropped one."""
    b, t = cache.shape[0], new.shape[1]
    pos = pos.reshape(-1).astype(jnp.int32)
    idx = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]  # (B, T)
    rows = jnp.repeat(jnp.arange(b, dtype=jnp.int32), t)          # (B*T,)
    return cache.at[rows, idx.reshape(-1)].set(
        new.astype(cache.dtype).reshape((b * t,) + cache.shape[2:]),
        mode="drop")


def decode_attention_window(q, k_cache, v_cache, lengths, scale=None):
    """Window decode attention: q (B, T, H, Dh) x caches (B, S, H, Dh)
    with lengths (B,) valid rows BEFORE the window -> (B, T, H, Dh).
    Query i's staircase mask keeps rows < lengths[b] + i + 1: the
    committed prefix plus window rows 0..i (its own fresh row included),
    exactly what T sequential decode_attention steps would see. Pure
    lax — T is small (spec window / extension chunk), so the (B, H, T,
    S) score tensor is fine; the Pallas single-query kernel stays the
    steady-state path."""
    b, t, h, d = q.shape
    s = k_cache.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    qf = q.astype(jnp.float32) * scale
    scores = jnp.einsum("bthd,bshd->bhts", qf,
                        k_cache.astype(jnp.float32))            # (B, H, T, S)
    limit = (lengths.reshape(-1).astype(jnp.int32)[:, None]
             + jnp.arange(1, t + 1, dtype=jnp.int32)[None, :])  # (B, T)
    valid = (jnp.arange(s, dtype=jnp.int32)[None, None, :]
             < limit[:, :, None])                               # (B, T, S)
    valid = valid[:, None]                                      # (B, 1, T, S)
    scores = jnp.where(valid, scores, _NEG)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.where(valid, jnp.exp(scores - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhts,bshd->bthd", p / jnp.maximum(l, 1e-30),
                     v_cache.astype(jnp.float32))
    return out.astype(q.dtype)


def spec_accept(proposed, logits):
    """In-graph accept/reject for one verify window.

    proposed (B, T) int: the tokens FED to the window — slot 0 is the
    already-committed current token, slots 1..T-1 are the draft's
    proposals. logits (B, T, V): the target logits at each window
    position. Returns (next_ids (B, T) int64, accept (B,) int32):

    - next_ids[b, i] = argmax(logits[b, i]) — the target's next token
      after window position i;
    - accept[b] = length of the longest prefix of proposals matching
      the target: proposals proposed[b, 1..j] accepted while
      proposed[b, i+1] == next_ids[b, i] for every i < j.

    The caller emits next_ids[b, :accept[b]+1] (the accepted proposals
    ARE the target argmaxes there, plus one bonus token from the first
    disagreement position) and advances the slot length by accept+1.
    """
    next_ids = jnp.argmax(logits, axis=-1).astype(jnp.int64)    # (B, T)
    t = proposed.shape[1]
    if t <= 1:
        accept = jnp.zeros((proposed.shape[0],), jnp.int32)
        return next_ids, accept
    matches = (proposed[:, 1:].astype(jnp.int64)
               == next_ids[:, :-1]).astype(jnp.int32)           # (B, T-1)
    accept = jnp.sum(jnp.cumprod(matches, axis=1), axis=1).astype(jnp.int32)
    return next_ids, accept


def mtp_next_tokens(tokens, lengths, first):
    """What a prediction layer is fed beside a prompt's hidden rows:
    tokens (B, S) shifted left by one (position i beside the token after
    it), with ``first`` (B,), the token the model has just chosen, at
    each row's last real position ``lengths - 1`` -> (B, S). Positions
    past a row's length hold what the shift left there: rows no one
    reads."""
    b = tokens.shape[0]
    nxt = jnp.concatenate([tokens[:, 1:], jnp.zeros_like(tokens[:, :1])],
                          axis=1)
    at = jnp.clip(lengths.reshape(-1).astype(jnp.int32) - 1, 0,
                  tokens.shape[1] - 1)
    return nxt.at[jnp.arange(b), at].set(first.reshape(-1).astype(nxt.dtype))


def spec_pick(ids, accept):
    """ids (B, T) read at column ``accept`` (B,) a row -> (B,): a
    round's next draft is the prediction layer's choice at the LAST
    position the round committed."""
    at = jnp.clip(accept.reshape(-1, 1).astype(jnp.int32), 0,
                  ids.shape[1] - 1)
    return jnp.take_along_axis(ids, at, axis=1)[:, 0]


@register_op("mtp_next_tokens")
def _mtp_next_tokens_op(ctx):
    """Inputs Tokens (B, S) int, Lengths (B,), First (B,) int -> Out (B,
    S): ``mtp_next_tokens``."""
    return {"Out": mtp_next_tokens(ctx.input("Tokens"), ctx.input("Lengths"),
                                   ctx.input("First"))}


@register_op("spec_pick")
def _spec_pick_op(ctx):
    """Inputs Ids (B, T), Accept (B,) int32 -> Out (B,): column
    ``Accept`` of each row."""
    return {"Out": spec_pick(ctx.input("Ids"), ctx.input("Accept"))}


@register_op("cache_append_window")
def _cache_append_window_op(ctx):
    """Inputs Cache (B, S, ...), New (B, T, ...), Pos (B,) int32 write
    bases (each slot's CURRENT length) -> Out: the slab with T rows
    appended per slot at pos..pos+T-1."""
    return {"Out": cache_append_window(ctx.input("Cache"),
                                       ctx.input("New"),
                                       ctx.input("Pos"))}


@register_op("decode_attention_window")
def _decode_attention_window_op(ctx):
    """T-query decode attention with the staircase window mask. Inputs
    Q (B, T, H, Dh), KCache/VCache (B, S, H, Dh), Lengths (B,) valid
    rows BEFORE the window; attr scale."""
    return {"Out": decode_attention_window(
        ctx.input("Q"), ctx.input("KCache"), ctx.input("VCache"),
        ctx.input("Lengths"), scale=ctx.attr("scale", None))}


@register_op("spec_accept")
def _spec_accept_op(ctx):
    """Inputs Proposed (B, T) int window tokens, Logits (B, T, V) ->
    NextIds (B, T) int64 per-position target argmax, Accept (B,) int32
    accepted-proposal count (longest matching prefix)."""
    next_ids, accept = spec_accept(ctx.input("Proposed"),
                                   ctx.input("Logits"))
    return {"NextIds": next_ids, "Accept": accept}
