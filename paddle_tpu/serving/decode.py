"""KV-cache autoregressive decode serving: DecodePredictor + DecodeServer.

Serving an LM before this module meant full forward passes: generating N
tokens re-ran the whole prefix N times — O(T^2) work the training-side
flash attention cannot hide. This module is the incremental path:

- ``save_decode_model`` exports a trained ``models.transformer.
  transformer_lm`` scope as a decode-servable directory: the canonical
  prefill graph goes through ``save_inference_model`` (so the plain
  ``Predictor`` can still serve it), plus a ``__decode__.json`` manifest
  with the architecture config the decode-side builders need.

- ``DecodePredictor`` loads that directory and compiles TWO kinds of
  executables through the shared PR-8 ``Engine`` (both land in the PR-5
  AOT disk cache next to the model): a PREFILL step (the existing
  flash-attention forward over the padded prompt, emitting last-position
  logits plus per-layer K/V slabs) and a per-token DECODE step
  (single-query ``decode_attention`` against the slabs, ``cache_append``
  of the fresh K/V row, and in-graph greedy/top-k/top-p sampling so only
  token ids cross the host boundary). Shapes are static: batch and slab
  length bucket to powers of two (the PR-2 batch-bucket trick applied to
  the sequence axis), so the executable count stays bounded at
  O(log B x log S) per strategy.

- ``DecodeServer`` is the continuous-batching serving loop (Orca-style
  iteration-level scheduling): requests enter the same C++ bounded
  channel as every other server, but instead of padding whole batches,
  new requests are admitted into FREE CACHE SLOTS between decode steps
  (prefilled as a power-of-two sub-batch, scattered into the resident
  slab) and finished sequences retire eagerly, freeing their slot
  mid-flight. One compiled decode signature — (slots, S) — serves the
  whole lifetime of the server. ``continuous=False`` degrades to static
  batching (admit a batch, run it to completion) for A/B measurement.

The fleet path reuses all of it: ``serving.worker`` builds a
DecodeServer when the Router is constructed with ``decode=True``, and
the zero-drop drain/restart contract extends to in-flight decode
sequences (``stop()`` finishes every admitted generation and admits
everything still queued before exiting).
"""
from __future__ import annotations

import collections
import functools
import json
import os
import re
import threading
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from .. import observability as obs
from ..observability import tracing as _tracing
from ..runtime import aot_cache as _aot
from ..framework.scope import current_device
from ..ops import kv_cache as _KV
from ..runtime import recordio as _rio

__all__ = ["DecodeConfig", "save_decode_model", "DecodePredictor",
           "DecodeServer", "kv_slab_slots", "cache_spec", "CacheEntry"]

_DECODE_MANIFEST = "__decode__.json"
_AOT_DIR = "__aot_cache__"


def _pow2_bucket(n: int, floor: int = 1) -> int:
    b = max(int(floor), 1)
    while b < n:
        b *= 2
    return b


# bytes per slab element by kv dtype (int8 additionally pays a float32
# scale PER (slot, position) — 4 bytes per seq position per K/V slab)
_KV_ITEMSIZE = {"float32": 4, "bfloat16": 2, "int8": 1}


def kv_slab_slots(budget_bytes: int, config: "DecodeConfig", seq: int,
                  kv_dtype: str = "float32") -> int:
    """How many cache slots one cache byte budget holds at ``seq``
    positions: the continuous-batching capacity arithmetic. A slot
    costs what ``cache_spec`` says it keeps: per latent layer one slab of
    seq * (kv_lora_rank + qk_rope_dim) elements, per attention layer two
    slabs of seq * n_kv_head * d_head elements (plus the per-position
    scales when int8: int8 rows cost 1 byte + 4 / (n_head * d_head) of
    scale vs bf16's 2, so one budget holds ~2x the sequences), per
    state-space layer its window and its state, whatever ``seq``."""
    per_slot = sum(e.nbytes for e in cache_spec(config, 1, seq, kv_dtype))
    return max(int(budget_bytes) // per_slot, 0)


def _executable_name(kind, batch, seq, strategy="", kv_dtype="float32",
                     window=0, draft_layers=0, ring=False) -> str:
    """Stable module name of one decode-path executable, from what
    ``DecodePredictor._acquire`` keys it by: ``ptpu_prefill_b1_s512``,
    ``ptpu_decode_b8_s2048``, ``ptpu_verify_b8_s2048_w5``,
    ``ptpu_draft_b8_s2048_l2``; a sampling strategy, int8 slabs and
    ring prefill add their own suffix."""
    parts = ["ptpu", kind, "b%d" % batch, "s%d" % seq]
    if window:
        parts.append("w%d" % window)
    if draft_layers:
        parts.append("l%d" % draft_layers)
    if strategy and strategy != "greedy":
        parts.append(strategy)
    if kv_dtype == "int8":
        parts.append("kv8")
    if ring:
        parts.append("ring")
    return "_".join(parts)


def _kv_dtype_from_env() -> str:
    """PADDLE_TPU_QUANT=kv8|int8 opts DecodeServer slabs into int8."""
    raw = (os.environ.get("PADDLE_TPU_QUANT") or "").strip().lower()
    return "int8" if raw in ("kv8", "int8") else "float32"


def _attn_key(kind: str) -> str:
    """The name a manifest's by-kind fields (``rope``,
    ``n_kv_head_by_kind``, ``attn_sink``) give an ``attention`` |
    ``sliding`` layer: "full" | "sliding"."""
    return "sliding" if kind == "sliding" else "full"


class DecodeConfig:
    """Architecture manifest for the decode-side graph builders.
    Everything else (batch, slab length, strategy) is a serving-time
    choice and deliberately NOT part of the manifest.

    The first nine fields are the arguments ``models.transformer.
    transformer_lm`` was trained with; the rest describe what a block
    is made of, with OPT's block as the default (a manifest written
    before they existed loads as what it was): layer ``i`` is an
    attention layer iff ``i % attn_layer_period == attn_layer_offset``
    and a Mamba layer otherwise; ``n_kv_head`` key/value heads serve
    the ``n_head`` query heads; ``norm`` ("layer_norm" | "rms_norm"),
    ``ffn`` ("relu" | "gated_silu"), ``positions`` (a learned position
    table is added to the token embedding) and ``biases`` say the rest.
    ``d_inner`` is the feed-forward width; a Mamba layer's inner width
    is ``mamba_expand * d_model``.

    ``MORE_FIELDS`` describe what OPT's and Jamba's blocks lack, each
    written to a manifest only where it is set: ``head_dim`` (a head
    width of its own: ``d_head`` is ``d_model // n_head`` only where
    none is given), ``n_head_by_layer`` (query heads layer by layer),
    ``attn_types`` ("full" | "sliding" for each attention layer: a
    sliding layer's queries see the last ``window`` keys and its cache
    is a ring of ``window`` rows), ``ffn_types`` ("dense" | "experts"
    layer by layer: an expert layer routes over ``n_expert`` experts of
    width ``d_expert``, ``expert_top_k`` a token, scores by
    ``router_score``, renormalised, times ``router_scale``, computes
    those of ``experts_held`` = [lo, hi) and adds a shared expert of
    width ``d_shared_expert``), ``attn_gate`` ("per_head": a sigmoid
    gate a query head on the attention output, from the layer's
    normalised input) and ``rope`` ({"full" | "sliding": {rotary_dim,
    theta, attention_factor, yarn}}: rotary positions by layer kind).
    The output head is its own matrix where ``tie_embeddings`` is
    false. ``layer_types`` names every layer's mixer outright where the
    kinds are no period and offset ("mamba" | "attention" | "sliding" |
    "gmu" | "cross"): a ``gmu`` layer gates the MEMORY (the scan's
    output before its gate) of the nearest state-space layer before it,
    a ``cross`` layer attends the keys and values of the nearest
    full-attention layer before it, and neither owns a cache entry;
    ``diff_attn`` makes every attention differential (``ops/
    diff_attn.py``: slabs and rings then keep a position's row FLAT,
    ``n_kv_head * d_head`` floats); ``attn_biases`` puts a bias
    on the attention projections alone; ``mamba_norms`` (Jamba's RMS
    norms on delta, B and C) is true unless a manifest says otherwise.
    A ``latent`` layer is multi-head latent attention (``ops/mla.py``):
    queries through a bottleneck of ``q_lora_rank``, heads of
    ``qk_nope_dim + qk_rope_dim`` query/key channels (the last
    ``qk_rope_dim`` rotated by ``rope["latent"]``: theta, yarn,
    attention_factor, ``interleave``, and ``scale_beta``, the
    position-dependent query scale) and ``v_head_dim`` value channels,
    scores times ``softmax_scale`` (None: ``(qk_nope_dim +
    qk_rope_dim)^-0.5``); its cache entry keeps, for each position, ONE
    latent row of ``kv_lora_rank + qk_rope_dim`` floats that is neither
    K nor V (``latent_row``). ``router_score`` names the router's score
    function ("sigmoid" | "softmax"); ``router_bias`` adds a selection
    bias an expert (chosen by score + bias, weighted by score) and
    ``router_groups`` / ``router_topk_groups`` limit the choice to the
    best groups (``ops/moe.py``). ``q_lora_rank`` 0 is a latent query
    with no bottleneck; ``attn_gate`` reaches a latent and a KDA layer
    too. A ``kda`` layer is Kimi Delta Attention (``ops/kda.py``):
    ``kda_heads`` heads of ``kda_head_dim`` key and value channels,
    convolutions of ``kda_conv`` taps on q, k and v, a decay gate
    ``kda_gate`` ("lower_bound_sigmoid", log-decay in (``kda_gate_bound``,
    0): the chunked scan's FACTORED form | "softplus", unbounded: its
    GUARDED form; on a TPU either is one Pallas call a layer), a write
    strength in (0, ``kda_beta_max``) (1.0 | 2.0: negative eigenvalues),
    the decay projected by one matrix (``kda_decay_rank`` 0) or through
    a bottleneck of that rank (Kimi Linear's); it keeps three windows
    and ONE matrix state a head, all fixed-size. ``attn_gate``
    "per_channel" is a gate finer than a head: on an attention layer one
    sigmoid a channel of every query head (a (D, h x d_head) matrix), on
    a KDA layer the same through the bottleneck of ``kda_decay_rank``
    (low-rank; one matrix where that is 0); no latent layer builds it. A ``latent_dsa`` layer is a latent
    layer UNDER A LEARNED INDEXER (``ops/dsa.py``): ``index_heads`` index
    queries of ``index_head_dim`` from the query latent (so
    ``q_lora_rank`` > 0), one index key a position, and a query attends
    the ``index_topk`` earlier positions the indexer scores highest; it
    keeps its latent slab and, beside it, a slab of index keys. A
    ``latent_ring`` layer is latent attention OF A GEOMETRY OF ITS OWN
    over the last ``window`` positions: ``latent_ring`` = {n_head,
    q_lora_rank, kv_lora_rank, qk_nope_dim, qk_rope_dim, v_head_dim},
    rotated by ``rope["latent_ring"]``; it keeps a RING of ``window``
    latent rows. ``latent_rescale`` multiplies the normalised latents by
    ``(d_model / rank)^1/2``, each kind by its own ranks.
    ``latent_geometry(kind)`` is a latent kind's sizes. An ``eva`` layer
    is EvaByte's EVA attention (``ops/eva.py``): ``n_head`` heads on as
    many key/value heads, rotated by ``rope["full"]``; a query attends
    the keys of its own window of ``window`` positions exactly and each
    earlier window through one pooled key and value a chunk of
    ``eva_chunk`` positions (``window`` a multiple of it, ``max_len`` of
    both), all under one softmax; it keeps ONE array for K and one for
    V of ``max_len / eva_chunk + window`` rows, the summaries and the
    window's block (``eva_rows``). ``norm_offset``: an RMS norm's
    parameter is its gain's distance from one, ``(1 + g) x / rms(x)``;
    ``head_precision`` "highest": the logits in float32 products.
    Four fields describe attention layers whose two kinds differ in
    more than their window (MiMo-V2): ``n_kv_head_by_kind`` ({"full":,
    "sliding":}: the key/value heads of a full and of a sliding layer;
    a kind it does not name keeps ``n_kv_head``), ``v_head_dim`` on a
    model WITHOUT latent layers (the value head's own width under query
    and key heads of ``head_dim``), ``attn_sink`` (the layer kinds,
    "sliding" alone is built, whose softmax has one learned scalar a
    query head in its denominator: ``a_ij = exp(z_ij) / (exp(s_h) +
    sum_j' exp(z_ij'))``) and ``attn_value_scale`` (v is multiplied by
    it). Where the first or the second is set (``uneven_kv``) a full
    layer's slabs keep a position's row FLAT, K ``(n_kv x head_dim,)``
    beside V ``(n_kv x v_head_dim,)``, read where they lie by
    ``ptpu.decode_attn_uneven`` (``ops/kv_cache.py``); a ring keeps
    ``(n_kv, width)`` rows of its own kind's head count.
    ``n_predict_layers`` (0 | 1): the model publishes a
    multi-token-prediction layer (DeepSeek-V3's: one more decoder layer
    of the last layer's kind behind two norms and a projection of
    ``[embedding ; hidden]``, through the model's own table and head),
    which keeps cache entries of its own as layer ``n_layer`` and drafts
    the token after next: a server runs ROUNDS of two positions over it
    (``DecodeServer``), greedy only, and the round is the model's one
    step program (its plain greedy step is the round executable,
    ``_StepOfRound``). ``matrix_dtype`` ("float32" | "bfloat16"): the
    type the matrices (projections, experts, table, head) are HELD in;
    gains, biases, the router and every cache entry stay float32.
    ``rope["index"]`` may carry ``interleave``: the indexer rotates the
    pairs (2i, 2i+1) where it is set."""

    FIELDS = ("vocab_size", "n_layer", "n_head", "d_model", "d_inner",
              "max_len", "tie_embeddings", "prefix", "eos_id")
    # (field, OPT's value): written to a manifest only where they differ
    BLOCK_FIELDS = (("n_kv_head", None), ("attn_layer_period", 1),
                    ("attn_layer_offset", 0), ("mamba_d_state", 16),
                    ("mamba_d_conv", 4), ("mamba_dt_rank", None),
                    ("mamba_expand", 2), ("norm", "layer_norm"),
                    ("norm_eps", 1e-5), ("ffn", "relu"),
                    ("positions", True), ("biases", True))
    # (field, value where the block has none): written only where set
    MORE_FIELDS = (("head_dim", None), ("n_head_by_layer", None),
                   ("attn_types", None), ("window", None),
                   ("ffn_types", None), ("n_expert", 0),
                   ("expert_top_k", 0), ("d_expert", 0),
                   ("d_shared_expert", 0), ("experts_held", None),
                   ("router_score", "sigmoid"), ("router_scale", 1.0),
                   ("attn_gate", None), ("rope", None),
                   ("layer_types", None), ("diff_attn", False),
                   ("attn_biases", False), ("mamba_norms", True),
                   ("q_lora_rank", 0), ("kv_lora_rank", 0),
                   ("qk_nope_dim", 0), ("qk_rope_dim", 0),
                   ("v_head_dim", 0), ("softmax_scale", None),
                   ("kda_heads", 0), ("kda_head_dim", 0), ("kda_conv", 4),
                   ("kda_gate", "lower_bound_sigmoid"),
                   ("kda_gate_bound", -5.0), ("kda_beta_max", 1.0),
                   ("kda_decay_rank", 0), ("router_groups", 1),
                   ("router_topk_groups", 1), ("router_bias", False),
                   ("latent_ring", None), ("latent_rescale", False),
                   ("index_heads", 0), ("index_head_dim", 0),
                   ("index_topk", 0), ("eva_chunk", 0),
                   ("norm_offset", False), ("head_precision", None),
                   ("n_kv_head_by_kind", None), ("attn_sink", None),
                   ("attn_value_scale", 1.0), ("n_predict_layers", 0),
                   ("matrix_dtype", "float32"))
    MIXERS = ("mamba", "attention", "sliding", "gmu", "cross", "latent",
              "kda", "latent_dsa", "latent_ring", "eva")
    # the kinds that keep latent rows
    LATENT_KINDS = ("latent", "latent_dsa", "latent_ring")
    RING_WIDTHS = ("n_head", "q_lora_rank", "kv_lora_rank", "qk_nope_dim",
                   "qk_rope_dim", "v_head_dim")
    # ``q_lora_rank`` 0 is a query with no bottleneck
    LATENT_WIDTHS = ("kv_lora_rank", "qk_nope_dim", "qk_rope_dim",
                     "v_head_dim")

    def __init__(self, vocab_size, n_layer=4, n_head=8, d_model=512,
                 d_inner=2048, max_len=2048, tie_embeddings=False,
                 prefix="lm", eos_id=None, **block):
        self.vocab_size = int(vocab_size)
        self.n_layer = int(n_layer)
        self.n_head = int(n_head)
        self.d_model = int(d_model)
        self.d_inner = int(d_inner)
        self.max_len = int(max_len)
        self.tie_embeddings = bool(tie_embeddings)
        self.prefix = str(prefix)
        self.eos_id = None if eos_id is None else int(eos_id)
        for f, default in self.BLOCK_FIELDS + self.MORE_FIELDS:
            setattr(self, f, block.pop(f, default))
        if block:
            raise TypeError("DecodeConfig got unknown fields %s"
                            % sorted(block))
        if self.n_kv_head is None:
            self.n_kv_head = self.n_head
        if self.mamba_dt_rank is None:
            self.mamba_dt_rank = -(-self.d_model // 16)
        if not 0 <= self.attn_layer_offset < self.attn_layer_period:
            raise ValueError(
                "attn_layer_offset %r is not a layer of a period of %r"
                % (self.attn_layer_offset, self.attn_layer_period))
        for f in ("n_head_by_layer", "attn_types", "ffn_types",
                  "layer_types"):
            per_layer = getattr(self, f)
            if per_layer is not None:
                per_layer = list(per_layer)[:self.n_layer]
                setattr(self, f, per_layer)
                if len(per_layer) != self.n_layer:
                    raise ValueError("%s names %d layers of %d"
                                     % (f, len(per_layer), self.n_layer))
        if self.n_kv_head_by_kind is not None:
            by_kind = dict(self.n_kv_head_by_kind)
            if not by_kind or set(by_kind) - {"full", "sliding"}:
                raise ValueError(
                    "n_kv_head_by_kind %r names key/value heads of 'full' "
                    "and 'sliding' layers" % (self.n_kv_head_by_kind,))
            self.n_kv_head_by_kind = {k: int(by_kind[k])
                                      for k in sorted(by_kind)}
        if self.attn_sink is not None:
            self.attn_sink = sorted(self.attn_sink)
        for h in set(self.n_head_by_layer or ()) | {self.n_head}:
            for hkv in {self.n_kv_head} | set(
                    (self.n_kv_head_by_kind or {}).values()):
                if hkv <= 0 or h % hkv:
                    raise ValueError(
                        "%d query heads do not divide over %d key/value "
                        "heads" % (h, hkv))
        if self.experts_held is not None:
            self.experts_held = [int(e) for e in self.experts_held]
        if "experts" in (self.ffn_types or ()):
            lo, hi = self.held
            if not (0 <= lo < hi <= self.n_expert
                    and 0 < self.expert_top_k <= self.n_expert
                    and self.d_expert > 0):
                raise ValueError(
                    "an expert layer needs n_expert, expert_top_k <= "
                    "n_expert, d_expert and experts_held within them; got "
                    "%r, %r, %r, %r" % (self.n_expert, self.expert_top_k,
                                        self.d_expert, self.experts_held))
        kinds = self.layer_kinds()
        if {"sliding", "latent_ring"} & set(kinds) and not self.window:
            raise ValueError("a sliding attention layer needs a window")
        if "eva" in kinds:
            w, c = int(self.window or 0), int(self.eva_chunk or 0)
            if not (c > 0 and w > 0 and w % c == 0
                    and self.max_len % c == 0
                    and self.n_kv_head == self.n_head):
                raise ValueError(
                    "an eva layer needs eva_chunk, a window of whole "
                    "chunks, a max_len of whole chunks and a key/value "
                    "head a query head; got eva_chunk=%r window=%r "
                    "max_len=%r n_kv_head=%r of %r"
                    % (self.eva_chunk, self.window, self.max_len,
                       self.n_kv_head, self.n_head))
        for i, kind in enumerate(kinds):
            if kind not in self.MIXERS:
                raise ValueError("layer_types[%d] = %r is none of %s"
                                 % (i, kind, ", ".join(self.MIXERS)))
            need = {"gmu": "mamba", "cross": "attention"}.get(kind)
            if need and need not in kinds[:i]:
                raise ValueError(
                    "layer %d (%s) reads what a %s layer before it hands "
                    "on, and none is" % (i, kind, need))
        if {"latent", "latent_dsa"} & set(kinds) and not all(
                int(getattr(self, f) or 0) > 0 for f in self.LATENT_WIDTHS):
            raise ValueError(
                "a latent layer needs %s; got %s" % (
                    ", ".join(self.LATENT_WIDTHS),
                    [getattr(self, f) for f in self.LATENT_WIDTHS]))
        if "latent_dsa" in kinds and not all(
                int(getattr(self, f) or 0) > 0 for f in (
                    "q_lora_rank", "index_heads", "index_head_dim",
                    "index_topk")):
            raise ValueError(
                "a latent layer under an indexer needs q_lora_rank (the "
                "index queries come from the query latent), index_heads, "
                "index_head_dim and index_topk; got %r, %r, %r, %r"
                % (self.q_lora_rank, self.index_heads, self.index_head_dim,
                   self.index_topk))
        if "latent_ring" in kinds:
            ring = dict(self.latent_ring or {})
            if (set(ring) != set(self.RING_WIDTHS)
                    or not all(int(v or 0) > 0 for v in ring.values())):
                raise ValueError(
                    "a latent layer over a window needs latent_ring = {%s}, "
                    "all positive; got %r"
                    % (", ".join(self.RING_WIDTHS), self.latent_ring))
            self.latent_ring = {f: int(ring[f]) for f in self.RING_WIDTHS}
        if "kda" in kinds and not (int(self.kda_heads or 0) > 0
                                   and int(self.kda_head_dim or 0) > 0
                                   and int(self.kda_conv or 0) > 1):
            raise ValueError(
                "a kda layer needs kda_heads, kda_head_dim and a kda_conv "
                "of at least 2; got %r, %r, %r"
                % (self.kda_heads, self.kda_head_dim, self.kda_conv))
        if "experts" in (self.ffn_types or ()) and (
                self.n_expert % int(self.router_groups)
                or not 0 < int(self.router_topk_groups)
                <= int(self.router_groups)
                or self.expert_top_k > int(self.router_topk_groups)
                * (self.n_expert // int(self.router_groups))):
            raise ValueError(
                "%d experts in %r groups of which %r are kept do not hold "
                "a top-%d" % (self.n_expert, self.router_groups,
                              self.router_topk_groups, self.expert_top_k))
        if self.n_predict_layers not in (0, 1):
            raise ValueError(
                "n_predict_layers %r: one prediction layer (one draft a "
                "round) is built, or none" % (self.n_predict_layers,))
        if self.matrix_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                "matrix_dtype %r: matrices are held in float32 or bfloat16"
                % (self.matrix_dtype,))
        if self.diff_attn and self.n_kv_head % 2:
            raise ValueError(
                "differential attention pairs its heads: %d key/value "
                "heads do not pair" % self.n_kv_head)

    @property
    def d_head(self) -> int:
        return int(self.head_dim or self.d_model // self.n_head)

    def heads(self, i: int) -> int:
        """Query heads of layer ``i``."""
        return int(self.n_head_by_layer[i] if self.n_head_by_layer
                   else self.n_head)

    def kv_heads(self, kind: str = "attention") -> int:
        """Key/value heads of an ``attention`` (full) or a ``sliding``
        layer: ``n_kv_head_by_kind``'s where it names the kind."""
        return int((self.n_kv_head_by_kind or {}).get(
            _attn_key(kind), self.n_kv_head))

    @property
    def v_head(self) -> int:
        """Width of an attention layer's value head: ``v_head_dim``
        where a model without latent layers sets it (a latent layer
        reads that field for its own heads), else ``d_head``."""
        if self.v_head_dim and not self.has_latent:
            return int(self.v_head_dim)
        return self.d_head

    @property
    def uneven_kv(self) -> bool:
        """The attention layers' K and V rows differ by layer kind or
        from each other: a full layer's slabs keep FLAT rows."""
        return bool(self.n_kv_head_by_kind) or self.v_head != self.d_head

    def kv_rows(self, kind: str = "attention"):
        """(K row, V row): the shapes of one position's row of an
        ``attention`` layer's slabs or a ``sliding`` layer's rings.
        ``kv_row`` twice, but under ``uneven_kv``: a slab row FLAT, K
        ``(heads x d_head,)`` beside V ``(heads x v_head,)``, a ring row
        ``(heads, width)``, by the kind's own head count."""
        if not self.uneven_kv:
            return self.kv_row, self.kv_row
        hkv = self.kv_heads(kind)
        if kind == "sliding":
            return (hkv, self.d_head), (hkv, self.v_head)
        return (hkv * self.d_head,), (hkv * self.v_head,)

    def has_sink(self, kind: str) -> bool:
        """An ``attention`` | ``sliding`` layer's softmax has a learned
        sink a query head."""
        return _attn_key(kind) in (self.attn_sink or ())

    @property
    def held(self):
        """[lo, hi) of the routed experts this program computes."""
        return tuple(self.experts_held or (0, self.n_expert))

    def ffn_kinds(self) -> List[str]:
        """"dense" | "experts" for each layer."""
        return list(self.ffn_types or ["dense"] * self.n_layer)

    @property
    def mamba_d_inner(self) -> int:
        return int(self.mamba_expand) * self.d_model

    @property
    def kv_row(self):
        """Shape of one position's row of a slab or a ring: (heads,
        width), or FLAT under differential attention (the same floats
        in the same order; ``ops/diff_attn.py`` says why)."""
        if self.diff_attn:
            return (self.n_kv_head * self.d_head,)
        return self.n_kv_head, self.d_head

    @property
    def latent_row(self) -> int:
        """Floats of the one row a latent layer keeps a position:
        ``[c_kv ; k_r]``."""
        return int(self.kv_lora_rank) + int(self.qk_rope_dim)

    def latent_geometry(self, kind: str = "latent") -> "LatentGeometry":
        """The sizes of latent kind ``kind``: a ``latent_ring`` layer's
        own (``latent_ring``), else the model's one set."""
        g = (self.latent_ring if kind == "latent_ring" else
             {f: int(getattr(self, f) or 0) for f in self.RING_WIDTHS})
        scale = (self.softmax_scale
                 if kind != "latent_ring" and self.softmax_scale is not None
                 else float(g["qk_nope_dim"] + g["qk_rope_dim"]) ** -0.5)

        def rho(rank):
            if not (self.latent_rescale and rank):
                return 1.0
            return (float(self.d_model) / rank) ** 0.5

        return LatentGeometry(
            g["n_head"], g["q_lora_rank"], g["kv_lora_rank"],
            g["qk_nope_dim"], g["qk_rope_dim"], g["v_head_dim"],
            g["kv_lora_rank"] + g["qk_rope_dim"], float(scale),
            rho(g["q_lora_rank"]), rho(g["kv_lora_rank"]))

    @property
    def eva_rows(self):
        """(summary rows, block rows) of an ``eva`` layer's entry: a row
        a chunk of ``max_len`` positions, then the window's."""
        return self.max_len // int(self.eva_chunk), int(self.window)

    @property
    def tail_start(self) -> int:
        """The first layer from which on no layer owns a cache entry
        (``n_layer`` where the last layer owns one): a prefill runs the
        layers from here on each prompt's LAST row alone."""
        kinds = self.layer_kinds()
        i = len(kinds)
        while i and kinds[i - 1] in ("gmu", "cross"):
            i -= 1
        return i

    def layer_kinds(self) -> List[str]:
        """The mixer of each layer: "attention" | "sliding" | "mamba",
        and where ``layer_types`` names them "gmu" | "cross" | "latent"
        too."""
        if self.layer_types:
            return list(self.layer_types)
        kinds = ["attention" if i % self.attn_layer_period
                 == self.attn_layer_offset else "mamba"
                 for i in range(self.n_layer)]
        if self.attn_types:
            kinds = ["sliding" if k == "attention" and t == "sliding"
                     else k for k, t in zip(kinds, self.attn_types)]
        return kinds

    @property
    def has_state(self) -> bool:
        """Some layer keeps a recurrent state: a cache entry that is
        not a row per position (no snapshot, no rollback)."""
        return bool({"mamba", "kda"} & set(self.layer_kinds()))

    @property
    def has_ring(self) -> bool:
        """Some layer keeps a ring of ``window`` rows: positions that
        left the window are overwritten (no rows to roll back to)."""
        return bool({"sliding", "latent_ring"} & set(self.layer_kinds()))

    @property
    def has_latent(self) -> bool:
        """Some layer keeps latent rows: a row per position that is
        neither K nor V (no head axis, one array a layer)."""
        return bool(set(self.LATENT_KINDS) & set(self.layer_kinds()))

    @property
    def has_eva(self) -> bool:
        """Some layer keeps pooled rows and a window's block (kind
        ``eva``): positions that left the window survive only pooled."""
        return "eva" in self.layer_kinds()

    @property
    def extra_fetches(self) -> List[str]:
        """Names of what a prefill, a decode step or a round returns
        AFTER its cache entries: ``draft`` (B,) int64 where the model
        has a prediction layer (its guess at the token after the one the
        program chose), then ``moe_load`` (sparse layers, experts held)
        int32 where a layer routes over experts."""
        return ((["draft"] if self.n_predict_layers else [])
                + (["moe_load"] if "experts" in self.ffn_kinds() else []))

    def sparse_layers(self) -> List[int]:
        """The layers that route over experts, in ``moe_load``'s row
        order: the model's own, then a prediction layer (index
        ``n_layer``) of that kind."""
        kinds = self.ffn_kinds()
        return ([i for i, k in enumerate(kinds) if k == "experts"]
                + [self.n_layer + j for j in range(self.n_predict_layers)
                   if kinds[-1] == "experts"])

    def cache_layers(self):
        """(index, mixer kind) of every layer that may own cache
        entries: the model's, then a prediction layer as layer
        ``n_layer`` of the last layer's kind."""
        kinds = self.layer_kinds()
        return list(enumerate(kinds)) + [
            (self.n_layer + j, kinds[-1])
            for j in range(self.n_predict_layers)]

    @property
    def is_opt_block(self) -> bool:
        """Every block field at OPT's value: the graphs are
        ``models.transformer.transformer_lm_*``'s."""
        return (all(getattr(self, f) == d
                    for f, d in self.BLOCK_FIELDS + self.MORE_FIELDS
                    if f not in ("n_kv_head", "mamba_dt_rank"))
                and self.n_kv_head == self.n_head)

    def to_dict(self) -> Dict:
        d = {f: getattr(self, f) for f in self.FIELDS}
        if not self.is_opt_block:
            d.update({f: getattr(self, f) for f, _ in self.BLOCK_FIELDS})
            d.update({f: getattr(self, f) for f, dflt in self.MORE_FIELDS
                      if getattr(self, f) != dflt})
        return d

    @classmethod
    def from_dict(cls, d: Dict) -> "DecodeConfig":
        known = cls.FIELDS + tuple(
            f for f, _ in cls.BLOCK_FIELDS + cls.MORE_FIELDS)
        return cls(**{f: d[f] for f in known if f in d})


# a latent kind's sizes (``DecodeConfig.latent_geometry``): heads, the
# two ranks (``q_rank`` 0: no bottleneck), the three head widths, the
# floats of the row a position keeps, the softmax scale, and what the
# normalised query and key/value latents are multiplied by
LatentGeometry = collections.namedtuple(
    "LatentGeometry", "n_head q_rank rank nope rope v row scale rho_q rho_kv")


class CacheEntry(collections.namedtuple(
        "CacheEntry", "name shape dtype per_position")):
    """One array of a model's decode cache: its feed name, its shape
    at (slots, seq), its dtype, whether an admission writes it by rows
    (``per_position``); beside the four, ``stride``, the positions ONE
    of its rows may stand for (1, but for an entry made by
    ``CacheEntry.strided``: an ``eva`` entry, whose summary rows each
    stand for a chunk); and, read from those, its ``kind``:

    - ``"rows"``: a row per position (a K/V slab, or an int8 slab's
      scales): axis 1 is the sequence, an admission writes ``[:sp]``
      and a length masks the rest (``per_position`` true);
    - ``"latent"``: a row per position too, written and masked the same
      way, but ONE array a layer of ``(slots, seq, kv_lora_rank +
      qk_rope_dim)``: the normalised latent ``c_kv`` and the rotated key
      row ``k_r`` all heads share, neither K nor V and with no head
      axis (``ops/mla.py`` reads it by two paths);
    - ``"state"``: a fixed-size recurrent state, replaced whole;
    - ``"ring"``: the last ``window`` rows of a sliding-window layer at
      ``position mod window``: a prefill hands the ring over as it is
      stored, so an admission replaces it whole, as a state
      (``per_position`` false); a decode step writes one row of it;
    - ``"latent_ring"``: such a ring of LATENT rows, ``(slots, window,
      kv_lora_rank + qk_rope_dim)`` of the layer kind's own widths;
    - ``"index"``: an indexer's keys, ``(slots, seq, index_head_dim)``:
      a row per position beside the latent slab of the same layer,
      written and masked as that is (``ops/dsa.py``);
    - ``"eva"``: an EVA layer's K or V, ``(slots, max_len / stride +
      window, heads, width)`` whatever ``seq``: a row per CHUNK of
      ``stride`` positions (pooled; the last chunk first) and after them
      the window's block, position p at ``p mod window``, which restarts
      empty when a window closes where a ring stays full
      (``ops/eva.py``). A prefill hands it over as a step will find it
      and an admission replaces it whole (``per_position`` false: a
      caller that writes ``[:sp]`` rows or the whole entry needs to know
      no more), a step writes one row of the block and, where its
      position closes a chunk, one summary row."""

    __slots__ = ()
    stride = 1

    @classmethod
    def strided(cls, stride: int, *fields) -> "CacheEntry":
        """The entry ``fields`` whose rows may each stand for ``stride``
        positions: the same four fields (it compares and sorts as
        they do), the stride on its type."""
        return _strided_entry(int(stride))(*fields)

    @property
    def kind(self) -> str:
        if self.stride > 1:
            return "eva"
        if self.per_position:
            # ``cache_names`` calls a latent layer's entry latent_i and
            # an indexer's keys index_i
            return {"latent": "latent", "index": "index"}.get(
                self.name.split("_")[0], "rows")
        if self.name.startswith("lring_"):
            return "latent_ring"
        # ``cache_names`` calls a sliding layer's entries kring_i, vring_i
        return "ring" if self.name[1:].startswith("ring") else "state"

    @property
    def nbytes(self) -> int:
        itemsize = _KV_ITEMSIZE.get(self.dtype) or np.dtype(
            self.dtype).itemsize  # numpy has no bfloat16
        return int(np.prod(self.shape)) * itemsize


@functools.lru_cache(maxsize=None)
def _strided_entry(stride: int):
    return type("CacheEntry", (CacheEntry,),
                {"__slots__": (), "stride": stride})


def cache_spec(config: DecodeConfig, slots: int, seq: int,
               kv_dtype: str = "float32") -> List[CacheEntry]:
    """The ordered cache entries of ``config`` at (slots, seq): the ONE
    description the server's feed names, fresh arrays, admission
    scatter and capacity arithmetic, and the decode graphs' feeds and
    fetches, all go by.

    OPT's block: ``kcache_i``, ``vcache_i`` (slots, seq, n_head,
    d_head) layer by layer (with ``kscale_i``, ``vscale_i`` (slots,
    seq) after them when int8), the order its decode graph has always
    fetched them in. Any other block: an attention layer's two slabs
    (slots, seq, n_kv_head, d_head), a sliding-window layer's two
    rings ``kring_i``, ``vring_i`` (slots, window, n_kv_head, d_head)
    whatever ``seq``, a latent layer's ONE ``latent_i`` (slots, seq,
    ``config.latent_row``): a row per position that is neither K nor V
    (``[c_kv ; k_r]``, normalised and rotated: what both of MLA's
    attention paths read), a latent layer under an indexer that and
    ``index_i`` (slots, seq, ``index_head_dim``), its index keys, a
    latent layer over a window ONE ring ``lring_i`` (slots, window,
    its own row) whatever ``seq``, a Mamba layer's ``conv_i`` (slots, K - 1,
    d_inner) window and ``ssm_i`` (slots, d_inner, N) state, NOTHING for
    a ``gmu`` or a ``cross`` layer (it reads what another layer keeps;
    a slab may so have several readers a step), a prediction layer's
    (``n_predict_layers``) two as layer ``n_layer``'s under an indexer,
    a KDA layer's three
    windows ``convq_i``, ``convk_i``, ``convv_i`` (slots, K - 1, H *
    dk) and its ``kda_i`` (slots, H, dk, dv) matrix state; a slab's or a ring's
    row is ``config.kv_row``, flat under differential attention, or, where
    the layer kinds differ in their key/value heads or V's width is its
    own (``config.uneven_kv``), ``config.kv_rows(kind)``: K's beside
    V's, a slab's flat, a ring's (heads, width); SORTED BY
    NAME: the order a dict of feeds flattens in, so that a
    donated feed pairs with its own updated output and a step compiles
    with no pairing copy (PERF.md 7a is what happens otherwise)."""
    if kv_dtype not in _KV_ITEMSIZE:
        raise ValueError("kv_dtype must be one of %s, got %r"
                         % (sorted(_KV_ITEMSIZE), kv_dtype))
    if kv_dtype != "float32" and not config.is_opt_block:
        raise ValueError(
            "%s slabs are built for OPT's block only (rows per position "
            "of one head count, quantized a row); this model's caches "
            "(%s) are float32"
            % (kv_dtype, ", ".join(sorted(set(
                {"attention": "rows", "sliding": "ring", "mamba": "state",
                 "kda": "state", "latent": "latent",
                 "latent_dsa": "latent", "latent_ring": "ring",
                 "eva": "eva"}.get(
                     k, "none")
                for k in config.layer_kinds())))))
    from ..models.jamba import cache_names

    out = []
    for i, kind in config.cache_layers():
        names = cache_names(kind, i)
        if kind == "mamba":
            out.append(CacheEntry(
                names[0], (slots, config.mamba_d_conv - 1,
                           config.mamba_d_inner), "float32", False))
            out.append(CacheEntry(
                names[1], (slots, config.mamba_d_inner,
                           config.mamba_d_state), "float32", False))
            continue
        if kind == "kda":
            width = config.kda_heads * config.kda_head_dim
            out += [CacheEntry(n, (slots, config.kda_conv - 1, width),
                               "float32", False) for n in names[:3]]
            out.append(CacheEntry(
                names[3], (slots, config.kda_heads, config.kda_head_dim,
                           config.kda_head_dim), "float32", False))
            continue
        if kind == "sliding":
            out += [CacheEntry(n, (slots, int(config.window)) + row,
                               "float32", False)
                    for n, row in zip(names, config.kv_rows(kind))]
            continue
        if kind == "eva":
            rows = (slots, sum(config.eva_rows), config.n_head,
                    config.d_head)
            out += [CacheEntry.strided(config.eva_chunk, n, rows, "float32",
                                       False) for n in names]
            continue
        if not names:  # reads another layer's entries, owns none
            continue
        if kind == "latent":
            out.append(CacheEntry(
                names[0], (slots, seq, config.latent_row), "float32", True))
            continue
        if kind == "latent_dsa":
            out.append(CacheEntry(
                names[0], (slots, seq, int(config.index_head_dim)),
                "float32", True))
            out.append(CacheEntry(
                names[1], (slots, seq, config.latent_row), "float32", True))
            continue
        if kind == "latent_ring":
            out.append(CacheEntry(
                names[0], (slots, int(config.window),
                           config.latent_geometry(kind).row),
                "float32", False))
            continue
        out += [CacheEntry(n, (slots, seq) + row, kv_dtype, True)
                for n, row in zip(names, config.kv_rows(kind))]
        if kv_dtype == "int8":
            # the (slot, position) float32 scale of each int8 row
            out += [CacheEntry("%sscale_%d" % (kv, i), (slots, seq),
                               "float32", True) for kv in "kv"]
    return out if config.is_opt_block else sorted(out)


def _kept_pairs(prompts, k: int) -> int:
    """(query, key) pairs of ``prompts`` where a query at position t
    keeps ``min(t + 1, k)`` keys: a window of ``k``, or an indexer's
    choice of ``k``."""
    return sum(n * (n + 1) // 2 if n <= k else k * (k + 1) // 2 + (n - k) * k
               for n in map(len, prompts))


def _eva_pairs(n: int, window: int, per: int) -> int:
    """(query, key or summary) pairs of a prompt of ``n`` positions
    under EVA: a query at t sees ``t mod window + 1`` keys of its own
    window and ``per`` summaries a window closed before it."""
    a, r = divmod(int(n), window)
    return (a * window * (window + 1) // 2 + r * (r + 1) // 2
            + per * (window * a * (a - 1) // 2 + a * r))


@functools.lru_cache(maxsize=None)
def _scatter_fn(per_position: tuple, donate: bool):
    """The jitted admission scatter of a cache whose entries are rows
    per position (True) or fixed-size states (False): (caches, sub,
    slot_idx, sp) -> caches with ``sub``'s rows at ``slot_idx`` (an
    index past the last slot drops its row). One call an admission
    where there was an eager ``.at[].set`` an entry, each some
    milliseconds of Python for a fraction of that on the device
    (PERF.md, PR 24 and PR 26). Shared by every server of the process,
    so a fresh server compiles nothing a warm-up server has. The
    resident arrays are donated where the backend can reuse them."""
    def scatter(caches, sub, slot_idx, sp):
        return [c.at[slot_idx, :sp].set(s, mode="drop") if rows
                else c.at[slot_idx].set(s, mode="drop")
                for rows, c, s in zip(per_position, caches, sub)]

    scatter.__name__ = scatter.__qualname__ = "ptpu_admit_scatter"
    return jax.jit(scatter, static_argnums=(3,),
                   donate_argnums=(0,) if donate else ())


@functools.lru_cache(maxsize=None)
def _chain_fn(slots: int, device):
    """The compiled select that builds a decode step's ``tokens`` feed
    on the device while the step before it is still in flight: (fresh,
    first, prev) -> (slots, 1) ids, ``first[i]`` where ``fresh[i]`` (the
    host's token: a slot admitted since, or a free one's 0), else the
    id that step sampled for the slot, ``prev[i]``: the host never reads
    it on the way. One shape a server, compiled ahead (a call can
    neither trace nor compile) and shared by every server of the
    process with as many slots."""
    ids = jax.dtypes.canonicalize_dtype(np.int64)

    def chain(fresh, first, prev):
        return jnp.where(fresh, first, prev).reshape(slots, 1)

    chain.__name__ = chain.__qualname__ = "ptpu_chain_tokens"
    row = jax.ShapeDtypeStruct((slots,), ids)
    with jax.default_device(device):
        return jax.jit(chain).lower(
            jax.ShapeDtypeStruct((slots,), np.bool_), row, row).compile()


@functools.lru_cache(maxsize=None)
def _round_chain_fn(slots: int, seq: int, eos, device):
    """The compiled select that builds a ROUND's ``tokens`` and
    ``lengths`` feeds on the device while the round before it is still
    in flight: (fresh, tokens, lengths, remaining, ids, prev) -> (tokens
    (slots, 2), lengths (slots,), state (slots, 2)). A slot where
    ``fresh`` (admitted since, or free) takes the host's three: its
    current token and draft, its length, the tokens it may still take
    (``max_new - count``; 0 for a free slot). Every other slot is
    advanced from that round's ``ids`` ([a_1, a_2, accept, next draft])
    and from what that round was fed (``prev``: this function's own
    ``state`` a round ago, [length, remaining] a slot), by
    ``_accepted_tokens``' arithmetic: it took ``min(1 + accept,
    remaining, seq - length)`` tokens, cut after an ``eos``; the last of
    them is its current token, ``ids[:, 3]`` its draft. A slot that
    ended there (its budget, the slab's end, eos) is fed as a FREE slot
    from then on (length 0, tokens 0, remaining 0), so no round reads or
    writes a row at or past ``seq``; the host learns of it when it reads
    the ids, a round late. ``state`` repeats the lengths in an array of
    its own: the round DONATES its feeds, ``lengths`` among them. One
    shape a server, compiled ahead like ``_chain_fn``."""
    ids_t = jax.dtypes.canonicalize_dtype(np.int64)

    def chain(fresh, tokens, lengths, remaining, ids, prev):
        was, had = prev[:, 0], prev[:, 1]
        take = jnp.clip(jnp.minimum(1 + ids[:, 2],
                                    jnp.minimum(had, seq - was)), 0, 2)
        stopped = jnp.zeros((slots,), jnp.bool_)
        if eos is not None:
            hit = ids[:, :2] == eos
            take = jnp.where(hit[:, 0], jnp.minimum(take, 1), take)
            stopped = (hit[:, 0] & (take >= 1)) | (hit[:, 1] & (take == 2))
        length, left = was + take, had - take
        live = (had > 0) & ~stopped & (left > 0) & (length + 1 < seq)
        cur = jnp.where(take == 2, ids[:, 1], ids[:, 0])
        chained = jnp.where(live[:, None],
                            jnp.stack([cur, ids[:, 3]], axis=1), 0)
        lengths = jnp.where(fresh, lengths, jnp.where(live, length, 0))
        remaining = jnp.where(fresh, remaining, jnp.where(live, left, 0))
        return (jnp.where(fresh[:, None], tokens, chained), lengths,
                jnp.stack([lengths, remaining], axis=1))

    chain.__name__ = chain.__qualname__ = "ptpu_chain_round"
    row = jax.ShapeDtypeStruct((slots,), np.int32)
    with jax.default_device(device):
        return jax.jit(chain).lower(
            jax.ShapeDtypeStruct((slots,), np.bool_),
            jax.ShapeDtypeStruct((slots, 2), ids_t), row, row,
            jax.ShapeDtypeStruct((slots, 4), ids_t),
            jax.ShapeDtypeStruct((slots, 2), np.int32)).compile()


def _pairing_order(feed_names, fetch_names, spec_names):
    """(traced, take, n): the order a step's outputs are TRACED in, the
    index into them of each of ``fetch_names`` (None where the two
    orders are one), and how many cache entries the step is fed: its
    last ``n`` outputs are their updates (what a model returns AFTER
    its cache entries, ``DecodeConfig.extra_fetches``, is not handed in
    here: ``DecodePredictor._step`` keeps it in its place).

    jax pairs a donated input with the FIRST output of its shape and
    dtype, inputs taken in the order the feed dict flattens: sorted by
    name. A cache entry comes back in its feed's own buffer only if,
    within every such class, the i-th output is the update of the i-th
    feed; otherwise XLA honours the crossed aliases and copies whole
    slabs around them (PERF.md, PR 27: eight 268 MB copies a step). So
    the cache updates (the tail of ``fetch_names``, which ``cache_spec``
    ties one to one to the cache feeds) are traced sorted by their
    FEED's name, which holds in every class at once, and the caller is
    handed them back in ``fetch_names``' order: an indexing of a tuple
    of array handles. The outputs before them (ids, logits) keep their
    places. A spec that sorts already (every block but OPT's) traces as
    it fetches."""
    fed = set(feed_names)
    fed = [n for n in spec_names if n in fed]
    head = len(fetch_names) - len(fed)
    order = sorted(range(len(fed)), key=fed.__getitem__)
    traced = list(fetch_names[:head]) + [fetch_names[head + i]
                                         for i in order]
    if traced == list(fetch_names):
        return traced, None, len(fed)
    take = list(range(head)) + [head + order.index(i)
                                for i in range(len(fed))]
    return traced, take, len(fed)


def _accepted_tokens(next_row, accept: int, budget: int, eos):
    """What one sequence takes of a speculative round (the OPT
    self-draft's verify window, or a round over a prediction layer):
    ``next_row[:accept + 1]``, the model's own choices, capped by
    ``budget`` (tokens it may still take: its max_new and its slab's
    room) and cut after an ``eos``. Returns (tokens, stopped at eos).
    The acceptance counters are booked here, once a sequence a round."""
    obs.DECODE_SPEC_ACCEPTED.inc(int(accept))
    out = []
    for j in range(max(min(int(accept) + 1, int(budget)), 0)):
        out.append(int(next_row[j]))
        if eos is not None and out[-1] == eos:
            return out, True
    return out, False


_Step = collections.namedtuple(
    "_Step", "fn program feed_names fetch_names traced take n_cache n_tail")

# a signature made ready for the disk tier (``DecodePredictor._keyed``):
# the executable's name, its step, the step program's Engine, its feed
# structs and their signature, the key, and when the building began
_Keyed = collections.namedtuple(
    "_Keyed", "name step engine feed_structs feed_sig key t_build")


# a decode step (or a round) the serving loop has dispatched and not
# read: its outputs (device values), the (slot, sequence, last) it ran
# for, where ``last`` says the host knew at dispatch that this token
# ends the sequence, when its dispatch began, and of a round the
# [length, remaining] a slot it was fed, on the device
# (``_round_chain_fn`` advances the next round's from them)
_Flight = collections.namedtuple("_Flight", "outs rows t0 state",
                                 defaults=(None,))


class _InFetchOrder:
    """A loaded step whose outputs were traced in pairing order
    (``_pairing_order``), called as the executable is and answering in
    ``fetch_names``' order. No device work: the outputs are handles.
    Whatever else is asked of it is the executable's own."""

    def __init__(self, loaded, take):
        self._loaded, self._take = loaded, take

    def __call__(self, feeds, state):
        outs = self._loaded(feeds, state)
        return tuple(outs[i] for i in self._take)

    def __getattr__(self, name):
        return getattr(self._loaded, name)


class _StepOfRound:
    """The plain one-token GREEDY step of a model with a prediction
    layer: its ROUND executable, the current token standing in for the
    draft as well. Position 0 of a round IS the plain step (position 1's
    results are not read and its rows lie past the slot's length, where
    the next step overwrites them), and being the SAME executable it is
    so bit for bit: two programs of one mathematics (the step's dense
    products at 16 rows, the round's at 32) differ in the order of their
    float32 sums, a choice of 2,048 among thousands of scores turns a
    difference in the last bit into another row now and then, and a
    server's rounds would then leave the plain step's greedy tokens
    after some tens of them (on the chip: 12 of 16 sequences within 140
    tokens, PERF.md, PR 58). Called as a decode step is (``tokens`` (B,
    1), a ``seed`` it ignores) and answering in a decode step's fetch
    order: ids (B,), logits (B, V), the entries, the prediction layer's
    choice at position 0, then the round's last fetches (``moe_load``
    counts the stand-in position too). The round is the ONE step
    program of such a model: a plain step pays for the second position,
    and no sampling step is built (``hybrid_lm_decode`` refuses)."""

    def __init__(self, round_exe, n_cache):
        self._round, self._n_cache = round_exe, n_cache

    def __call__(self, feeds, state):
        feeds = {n: v for n, v in feeds.items() if n != "seed"}
        tok = jnp.asarray(feeds["tokens"])
        feeds["tokens"] = jnp.concatenate([tok, tok], axis=1)
        outs = self._round(feeds, state)
        ids, logits, draft_logits = outs[:3]
        at = 3 + self._n_cache
        draft = jnp.argmax(draft_logits[:, 0], axis=-1).astype(ids.dtype)
        return ((ids[:, 0], logits[:, 0]) + tuple(outs[3:at]) + (draft,)
                + tuple(outs[at + 1:]))

    def __getattr__(self, name):
        return getattr(self._round, name)


_ALIAS_MAP = re.compile(r"input_output_alias=\{(.*?)\}, entry_computation")


def _aliased_outputs(loaded) -> set:
    """Indices of the outputs a compiled step writes into a donated
    input's buffer, from the ``input_output_alias`` of its module line:
    ``{ {2}: (0, {}, may-alias), .. }``. Empty where nothing was donated
    (the CPU) or the text cannot be had."""
    try:
        found = _ALIAS_MAP.search(loaded.as_text())
    except Exception:
        return set()
    return ({int(i or 0) for i in re.findall(r"\{(\d*)\}:", found.group(1))}
            if found else set())


def _prefill_graph(config: DecodeConfig, tokens, lengths, use_ring=False):
    """The prefill graph ``config`` describes: (last-position logits,
    the cache entries in ``cache_spec`` order, then what
    ``config.extra_fetches`` names)."""
    if config.is_opt_block:
        from ..models import transformer as _T

        logits, caches = _T.transformer_lm_prefill(
            tokens, lengths, config.vocab_size,
            n_layer=config.n_layer, n_head=config.n_head,
            d_model=config.d_model, d_inner=config.d_inner,
            max_len=config.max_len,
            tie_embeddings=config.tie_embeddings,
            prefix=config.prefix, use_ring_attention=use_ring)
        return logits, [c for pair in caches for c in pair]
    if use_ring:
        raise ValueError("ring prefill is built for OPT's block only")
    from ..models import jamba as _J

    extras = {}
    logits, caches = _J.hybrid_lm_prefill(tokens, lengths, config,
                                          extras=extras)
    return logits, ([caches[n] for n in sorted(caches)]
                    + [extras[n] for n in config.extra_fetches])


def save_decode_model(dirname: str, config: DecodeConfig, executor,
                      scope=None, export_batch: int = 1,
                      export_seq: Optional[int] = None) -> None:
    """Export a trained LM scope (transformer_lm, or whatever block
    ``config`` describes) for decode serving.

    Builds the canonical prefill graph (full flash-attention forward,
    last-position logits as the fetch target) and writes it through
    ``save_inference_model`` — the directory stays loadable by the plain
    ``Predictor`` — plus the ``__decode__.json`` manifest. Parameters
    come from ``scope`` (or the current global scope), exactly as
    ``save_inference_model`` resolves them; a parameter the decode
    builders expect but the scope lacks fails HERE, not at first
    request."""
    from .. import Program, io as fluid_io, program_guard, unique_name

    export_seq = int(export_seq or min(config.max_len, 128))
    prog, startup = Program(), Program()
    with program_guard(prog, startup):
        with unique_name.guard():
            from .. import layers

            tokens = layers.data(name="tokens",
                                 shape=[export_batch, export_seq],
                                 dtype="int64", append_batch_size=False)
            lengths = layers.data(name="lengths", shape=[export_batch],
                                  dtype="int32", append_batch_size=False)
            last_logits, rest = _prefill_graph(config, tokens, lengths)
    targets = [last_logits]
    if config.n_predict_layers:
        # the first draft too: the export keeps what its targets need,
        # and the prediction layer's parameters are needed by it alone
        targets.append(rest[len(cache_spec(config, export_batch,
                                           export_seq))])
    fluid_io.save_inference_model(
        dirname, ["tokens", "lengths"], targets, executor,
        main_program=prog, scope=scope)
    with open(os.path.join(dirname, _DECODE_MANIFEST), "w") as f:
        json.dump(config.to_dict(), f, indent=2, sort_keys=True)


class DecodePredictor:
    """Incremental-decode predictor over an exported decode model.

    pred = DecodePredictor(model_dir)
    outs = pred.generate([np.array([5, 3, 9])], max_new_tokens=16)

    Compiled executables are acquired through the shared ``Engine``
    (kind="prefill" | "decode") and persist in the model directory's AOT
    disk cache — a fresh process warm-starts every bucket it has served
    before. ``generate`` is the static-batch surface (one call, one
    bucketed batch, run to completion); ``DecodeServer`` drives the same
    executables with continuous batching.
    """

    def __init__(self, model_dir: str, place=None, aot_cache: bool = True,
                 cache_dir: Optional[str] = None, strategy: str = "greedy",
                 sample_k: int = 40, sample_p: float = 0.9,
                 temperature: float = 1.0, eos_id: Optional[int] = None,
                 draft_n_layer: Optional[int] = None,
                 ring_prefill_min_seq: Optional[int] = None):
        from .. import io as fluid_io
        from ..executor import Executor, analyze_state
        from ..framework.scope import Scope

        with open(os.path.join(model_dir, _DECODE_MANIFEST)) as f:
            self.config = DecodeConfig.from_dict(json.load(f))
        self.model_dir = model_dir
        self.strategy = strategy
        self.sample_k = int(sample_k)
        self.sample_p = float(sample_p)
        self.temperature = float(temperature)
        self.eos_id = eos_id if eos_id is not None else self.config.eos_id
        # speculative decoding: the draft is the target's FIRST
        # draft_n_layer layers driven through the same loaded state
        # (self-drafting — no second parameter set to ship); default
        # half depth, floor 1
        self.draft_n_layer = (int(draft_n_layer)
                              if draft_n_layer is not None
                              else max(1, self.config.n_layer // 2))
        if not 1 <= self.draft_n_layer <= self.config.n_layer:
            raise ValueError(
                "draft_n_layer must be in [1, %d], got %d"
                % (self.config.n_layer, self.draft_n_layer))
        # long-context prefill: prompt buckets at or past this length
        # build their prefill graph with ring attention (sequence-
        # parallel under an sp mesh; exact-attention fallback on one
        # device, so the knob is portable). None = always dense.
        self.ring_prefill_min_seq = (None if not ring_prefill_min_seq
                                     else int(ring_prefill_min_seq))
        self._scope = Scope()
        exe = Executor(place)
        if not aot_cache:
            exe._disk.enabled = False
        # the canonical prefill program: parameter loading + the stable
        # model fingerprint the fleet's sticky version routing keys on
        self._program, self._feed_names, _fetch = (
            fluid_io.load_inference_model(model_dir, exe,
                                          scope=self._scope))
        self._disk = _aot.AotDiskCache(
            cache_dir=cache_dir or os.path.join(model_dir, _AOT_DIR),
            enabled=aot_cache)
        _aot.enable_compile_cache()
        state_in, _ = analyze_state(self._program, set(self._feed_names))
        dev = self._device = exe._device
        # ONE copy of the weights on the device: `_state` holds the
        # very arrays the load put into the scope (`device_put` of an
        # array that already lies on `dev` is that array), and the scope
        # is handed them back where they had to move
        self._state = {}
        for n in state_in:
            val = self._scope.find_var(n)
            if val is None:
                raise RuntimeError(
                    "decode model is missing persistable %r" % n)
            self._state[n] = jax.device_put(val, dev)
            self._scope.set_var(n, self._state[n])
        self._compiled: Dict = {}
        self._lock = threading.Lock()
        # `preload`'s counts once it has run (None: not yet), and the
        # lock a second server's `start` waits on while the first loads
        self._preloaded: Optional[Dict[str, int]] = None
        self._preload_lock = threading.Lock()
        self.traces = 0

    def fingerprint(self) -> str:
        """Stable model identity (program content fingerprint of the
        canonical prefill graph) — the fleet's program version."""
        return obs.program_fp(self._program)

    def cache_spec(self, slots: int, seq: int,
                   kv_dtype: str = "float32") -> List[CacheEntry]:
        """This model's cache entries at (slots, seq), in the order the
        decode executables take and return them (``cache_spec``)."""
        return cache_spec(self.config, slots, seq, kv_dtype)

    def _rows_only(self, what: str, latent_rows: bool = False):
        """Levers that snapshot, roll back or reorder a cache work on
        rows per position. A state, a ring and an ``eva`` entry have
        none to roll back to, whatever the graph. Latent rows and index
        keys ARE rows per position: a window step built over them (a
        ROUND of a model with a prediction layer, ``latent_rows``) rolls
        back by length as one over K and V rows does; the levers written
        for OPT's block (its verify window, row copies, int8 slabs)
        read K and V and still refuse them."""
        if self.config.has_state:
            kinds = self.config.layer_kinds()
            raise ValueError(
                "%s needs a cache of rows per position (it rolls back by "
                "length, or copies rows); this model's %s layers "
                "keep a recurrent state (cache entries of kind 'state'), "
                "which has no snapshot and no rollback yet"
                % (what, " and ".join(
                    n for k, n in (("mamba", "state-space"),
                                   ("kda", "delta-rule (KDA)"))
                    if k in kinds)))
        if self.config.has_ring:
            raise ValueError(
                "%s needs a cache of rows per position (it rolls back by "
                "length, or copies rows); this model's sliding-window "
                "layers keep a ring of %d rows (cache entries of kind "
                "'ring'): a position that left the window is overwritten, "
                "so there are no rows to roll back to or to share"
                % (what, self.config.window))
        if self.config.has_eva:
            raise ValueError(
                "%s needs a cache of rows per position (it rolls back by "
                "length, or copies rows); this model's EVA layers keep a "
                "window's block of %d rows that restarts when the window "
                "closes and one pooled row a chunk of %d positions (cache "
                "entries of kind 'eva'): a position that left its window "
                "survives only pooled, so there are no rows to roll back "
                "to or to share"
                % (what, self.config.window, self.config.eva_chunk))
        if latent_rows:
            if not self.config.n_predict_layers:
                raise ValueError(
                    "%s needs a prediction layer to draft with "
                    "(DecodeConfig.n_predict_layers); this model has none"
                    % what)
            return
        if self.config.has_latent:
            raise ValueError(
                "%s is built for OPT's block only, through graphs that "
                "read K and V rows; this model's latent layers keep one "
                "row of %d floats a position (cache entries of kind "
                "'latent'), which IS a row per position (a round of a "
                "model with a prediction layer rolls it back by length) "
                "but is neither K nor V: no OPT verify window, row copy "
                "or int8 quantisation is built over it"
                % (what, self.config.latent_row))
        if not self.config.is_opt_block:
            raise ValueError("%s is built for OPT's block only" % what)

    # -- graph building ---------------------------------------------------
    def _build(self, kind: str, batch: int, seq: int, strategy: str,
               kv_dtype: str = "float32", window: int = 0,
               use_ring: bool = False):
        """Build the (batch, seq) Program for one executable kind;
        returns (program, feed_names, fetch_names). Deterministic for
        given arguments, so the program content fingerprint (and with
        it the AOT key) is stable across processes.

        Kinds: "prefill" (full causal forward; ``use_ring=True`` swaps
        flash attention for the sequence-parallel ring — the
        long-context path), "decode" (one token per step;
        ``kv_dtype="int8"`` builds the quantized-slab variant with
        per-layer scale feeds), "draft" (the decode step at
        ``draft_n_layer`` depth — the speculative proposer, driven by
        the same loaded state), and "verify" (the ``window``-token
        speculative verify / prefix suffix-extension step: window
        appends + staircase attention + in-graph accept), and "round" (a
        model with a prediction layer: its current token and its draft,
        two positions a slot, the accept, and the prediction layer
        behind them: ``models/jamba.py: hybrid_lm_round``)."""
        from .. import Program, layers, program_guard, unique_name
        from ..models import transformer as _T

        cfg = self.config
        if kind in ("draft", "verify"):
            self._rows_only("a %s step" % kind)
        if kind == "round":
            self._rows_only("a round of two positions", latent_rows=True)
        prog, startup = Program(), Program()
        with program_guard(prog, startup):
            with unique_name.guard():
                if kind in ("decode", "round") and not cfg.is_opt_block:
                    return (prog,) + self._build_described_decode(
                        batch, seq, strategy, kv_dtype,
                        round_=kind == "round")
                if kind == "prefill":
                    tokens = layers.data(name="tokens", shape=[batch, seq],
                                         dtype="int64",
                                         append_batch_size=False)
                    lengths = layers.data(name="lengths", shape=[batch],
                                          dtype="int32",
                                          append_batch_size=False)
                    logits, caches = _prefill_graph(cfg, tokens, lengths,
                                                    use_ring=use_ring)
                    feeds = ["tokens", "lengths"]
                    fetches = [logits.name] + [c.name for c in caches]
                elif kind == "verify":
                    tokens = layers.data(name="tokens",
                                         shape=[batch, window],
                                         dtype="int64",
                                         append_batch_size=False)
                    positions = layers.data(name="positions",
                                            shape=[batch, window],
                                            dtype="int64",
                                            append_batch_size=False)
                    lengths = layers.data(name="lengths", shape=[batch],
                                          dtype="int32",
                                          append_batch_size=False)
                    last_idx = layers.data(name="last_idx", shape=[batch],
                                           dtype="int32",
                                           append_batch_size=False)
                    kc, vc = [], []
                    for i in range(cfg.n_layer):
                        kc.append(layers.data(
                            name="kcache_%d" % i,
                            shape=[batch, seq, cfg.n_head, cfg.d_head],
                            dtype="float32", append_batch_size=False))
                        vc.append(layers.data(
                            name="vcache_%d" % i,
                            shape=[batch, seq, cfg.n_head, cfg.d_head],
                            dtype="float32", append_batch_size=False))
                    next_ids, accept, last_logits, ncaches = (
                        _T.transformer_lm_verify(
                            tokens, positions, lengths, last_idx, kc, vc,
                            cfg.vocab_size, n_layer=cfg.n_layer,
                            n_head=cfg.n_head, d_model=cfg.d_model,
                            d_inner=cfg.d_inner, max_len=cfg.max_len,
                            tie_embeddings=cfg.tie_embeddings,
                            prefix=cfg.prefix))
                    feeds = (["tokens", "positions", "lengths",
                              "last_idx"]
                             + [v.name for v in kc]
                             + [v.name for v in vc])
                    fetches = ([next_ids.name, accept.name,
                                last_logits.name]
                               + [c.name for pair in ncaches
                                  for c in pair])
                else:
                    tokens = layers.data(name="tokens", shape=[batch, 1],
                                         dtype="int64",
                                         append_batch_size=False)
                    positions = layers.data(name="positions",
                                            shape=[batch, 1], dtype="int64",
                                            append_batch_size=False)
                    lengths = layers.data(name="lengths", shape=[batch],
                                          dtype="int32",
                                          append_batch_size=False)
                    seed = layers.data(name="seed", shape=[1],
                                       dtype="int64",
                                       append_batch_size=False)
                    cache_dt = ("int8" if kv_dtype == "int8"
                                else "float32")
                    n_layer = (self.draft_n_layer if kind == "draft"
                               else cfg.n_layer)
                    kc, vc, ks, vs = [], [], [], []
                    for i in range(n_layer):
                        kc.append(layers.data(
                            name="kcache_%d" % i,
                            shape=[batch, seq, cfg.n_head, cfg.d_head],
                            dtype=cache_dt, append_batch_size=False))
                        vc.append(layers.data(
                            name="vcache_%d" % i,
                            shape=[batch, seq, cfg.n_head, cfg.d_head],
                            dtype=cache_dt, append_batch_size=False))
                        if kv_dtype == "int8":
                            ks.append(layers.data(
                                name="kscale_%d" % i, shape=[batch, seq],
                                dtype="float32",
                                append_batch_size=False))
                            vs.append(layers.data(
                                name="vscale_%d" % i, shape=[batch, seq],
                                dtype="float32",
                                append_batch_size=False))
                    next_ids, logits, ncaches = _T.transformer_lm_decode(
                        tokens, positions, lengths, kc, vc, cfg.vocab_size,
                        n_layer=n_layer, n_head=cfg.n_head,
                        d_model=cfg.d_model, d_inner=cfg.d_inner,
                        max_len=cfg.max_len,
                        tie_embeddings=cfg.tie_embeddings,
                        prefix=cfg.prefix, strategy=strategy, seed=seed,
                        sample_k=self.sample_k, sample_p=self.sample_p,
                        temperature=self.temperature,
                        k_scales=ks or None, v_scales=vs or None)
                    feeds = (["tokens", "positions", "lengths", "seed"]
                             + [v.name for v in kc]
                             + [v.name for v in vc]
                             + [v.name for v in ks]
                             + [v.name for v in vs])
                    fetches = [logits.name] + [
                        c.name for tup in ncaches for c in tup]
                    if next_ids is not None:
                        fetches = [next_ids.name] + fetches
        return prog, feeds, fetches

    def _build_described_decode(self, batch, seq, strategy, kv_dtype,
                                round_=False):
        """The decode step of a block that is not OPT's, inside the
        caller's program guard: (feed_names, fetch_names). The cache
        feeds and the fetches of their updates both go in
        ``cache_spec`` order (sorted names), no ``positions`` feed.
        ``round_``: the ROUND of a model with a prediction layer in its
        place: ``tokens`` (batch, 2), a slot's current token and its
        draft, no ``seed`` (greedy); fetches ``ids`` (batch, 4) int64
        = [the model's choice after each position | the drafted tokens
        accepted (0 | 1) | the next round's draft], the logits and the
        prediction layer's (batch, 2, V) each, the entries, then
        ``config.extra_fetches``."""
        from .. import layers
        from ..models import jamba as _J

        width = 1 + self.config.n_predict_layers if round_ else 1
        tokens = layers.data(name="tokens", shape=[batch, width],
                             dtype="int64", append_batch_size=False)
        lengths = layers.data(name="lengths", shape=[batch], dtype="int32",
                              append_batch_size=False)
        if not round_:
            seed = layers.data(name="seed", shape=[1], dtype="int64",
                               append_batch_size=False)
        spec = self.cache_spec(batch, seq, kv_dtype)
        caches = {e.name: layers.data(name=e.name, shape=list(e.shape),
                                      dtype=e.dtype,
                                      append_batch_size=False)
                  for e in spec}
        extras = {}
        if round_:
            next_ids, accept, logits, draft_logits, new = _J.hybrid_lm_round(
                tokens, lengths, caches, self.config, extras=extras)
            # all a round's commit needs of it, in ONE array the host
            # fetches: [next_ids | accept | the next draft]
            ids = layers.concat(
                [next_ids,
                 layers.reshape(layers.cast(accept, "int64"),
                                shape=[batch, 1]),
                 layers.reshape(layers.cast(extras["draft"], "int64"),
                                shape=[batch, 1])], axis=1)
            head = [ids.name, logits.name, draft_logits.name]
            feeds = ["tokens", "lengths"]
        else:
            next_ids, logits, new = _J.hybrid_lm_decode(
                tokens, lengths, caches, self.config, strategy=strategy,
                seed=seed, sample_k=self.sample_k, sample_p=self.sample_p,
                temperature=self.temperature, extras=extras)
            head = ([next_ids.name] if next_ids is not None else []) + [
                logits.name]
            feeds = ["tokens", "lengths", "seed"]
        feeds += [e.name for e in spec]
        fetches = (head + [new[e.name].name for e in spec]
                   + [extras[n].name for n in self.config.extra_fetches])
        return feeds, fetches

    # -- compilation ------------------------------------------------------
    def _feed_structs(self, program, feed_names):
        from ..framework.dtypes import as_numpy_dtype

        structs = {}
        for name in feed_names:
            var = program.global_block().var(name)
            structs[name] = jax.ShapeDtypeStruct(
                tuple(var.shape), np.dtype(as_numpy_dtype(var.dtype)))
        return structs

    def acquire(self, kind: str, batch: int, seq: int,
                strategy: Optional[str] = None,
                kv_dtype: str = "float32", window: int = 0):
        # keyed, traced and compiled for the predictor's own device
        with jax.default_device(self._device):
            return self._acquire(kind, batch, seq, strategy, kv_dtype,
                                 window)

    def _acquire(self, kind, batch, seq, strategy, kv_dtype, window):
        """Executable for one (kind, batch, seq, strategy, kv_dtype,
        window) signature: memory hit, else the shared Engine's
        disk-load-or-compile path. Returns (executable, fetch_names).
        ``kv_dtype`` only shapes decode steps (int8 slabs + scale
        feeds); prefill always emits float slabs the caller quantizes
        at scatter time. ``window`` is the verify kind's token width
        (spec_k proposals + the committed token); "draft" builds the
        decode step at ``draft_n_layer`` depth. Prefill buckets at or
        past ``ring_prefill_min_seq`` build with ring attention —
        their programs fingerprint differently, so dense and ring
        prefills coexist in the AOT cache.

        Where a shape is acquired: a prefill that the disk tier held
        when the predictor's first server started is in memory since
        then (``preload``: loaded on that caller's thread before the
        loop opened), so an admission's call here is a memory hit. What
        still comes here lazily, on whatever thread asks first (a
        server's loop thread at a bucket's first admission), is a shape
        the disk did not hold: it is compiled, and stored for the next
        process."""
        ck = self._signature(kind, batch, seq, strategy, kv_dtype, window)
        with self._lock:
            hit = self._compiled.get(ck)
        if hit is not None:
            obs.CACHE_HITS.inc(kind=kind, tier="memory",
                               program=self.fingerprint())
            return hit
        if (kind == "decode" and self.config.n_predict_layers
                and ck[3] == "greedy" and ck[4] == "float32"):
            # ONE step program for a model with a prediction layer: its
            # greedy step is its round (``_StepOfRound`` says why)
            rexe, _ = self._acquire("round", batch, seq, None, "float32", 0)
            spec = [e.name for e in self.cache_spec(batch, seq)]
            hit = (_StepOfRound(rexe, len(spec)),
                   ["next_ids", "logits"] + spec + self.config.extra_fetches)
            with self._lock:
                self._compiled[ck] = hit
            return hit
        return self._acquire_keyed(ck, self._keyed(ck))

    def _signature(self, kind, batch, seq, strategy=None,
                   kv_dtype="float32", window=0) -> tuple:
        """What names one executable of this predictor (the memory
        cache's key, and ``_executable_name``'s arguments): the caller's
        signature with what its kind ignores blanked."""
        strategy = strategy or self.strategy
        if kind not in ("decode", "draft"):
            kv_dtype = "float32"
        if kind in ("draft", "round"):
            strategy = "greedy"  # proposals are always argmax
        use_ring = bool(kind == "prefill"
                        and self.ring_prefill_min_seq is not None
                        and seq >= self.ring_prefill_min_seq)
        return (kind, batch, seq,
                strategy if kind in ("decode", "draft") else "",
                kv_dtype, int(window),
                self.draft_n_layer if kind == "draft" else 0, use_ring)

    def _keyed(self, ck) -> "_Keyed":
        """A signature's step program, feed structs and the key its
        executable has in the disk tier: all an acquisition needs before
        it touches the tier, and all a preload needs to tell whether a
        sidecar is this predictor's."""
        from .engine import Engine

        t_build = time.perf_counter()
        kind, batch, seq, strategy, kv_dtype, window, _, use_ring = ck
        name = _executable_name(*ck)
        step = self._step(kind, batch, seq, strategy or self.strategy,
                          kv_dtype, window, use_ring, name=name)
        engine = Engine(step.program, disk=self._disk,
                        feed_names=step.feed_names,
                        fetch_names=step.fetch_names)
        feed_structs = self._feed_structs(step.program, step.feed_names)
        feed_sig = tuple((n, tuple(s.shape), str(np.dtype(s.dtype)))
                         for n, s in sorted(feed_structs.items()))
        # keyed by the order that was TRACED: an executable of another
        # order under this key would hand back permuted slabs
        key = engine.key(kind, feed_sig, tuple(step.traced))
        return _Keyed(name, step, engine, feed_structs, feed_sig, key,
                      t_build)

    def _acquire_keyed(self, ck, keyed: "_Keyed", compile: bool = True):
        """The Engine's acquisition of one keyed signature, into the
        memory cache: (executable, fetch_names). ``compile=False`` (the
        preload) takes what the disk tier holds or nothing: None where
        the blob cannot be read."""
        kind = ck[0]
        name, step, engine, feed_structs, feed_sig, key, t_build = keyed
        traced = tuple(step.traced)

        def lower():
            # donate the feeds (the KV slabs dominate them) so XLA
            # appends cache rows IN PLACE on device backends; CPU
            # ignores donation with a warning, so keep it off there.
            # NEVER donate the draft step's feeds: the speculative
            # round re-feeds the SAME committed target slabs to the
            # verify executable after drafting — donation would consume
            # them (the draft's appended rows are hypotheses; its
            # returned slabs are discarded each round)
            donate = ((0,) if kind != "draft"
                      and current_device().platform != "cpu" else ())
            fn = jax.jit(step.fn, donate_argnums=donate)
            state_structs = {n: jax.ShapeDtypeStruct(a.shape, a.dtype)
                             for n, a in self._state.items()}
            return fn.lower(feed_structs, state_structs)

        def pairing(loaded):
            # decided when the program was compiled, so counted from
            # it: the cache entries fed, and those of them that come
            # back in a donated feed's buffer (all of them on a chip,
            # or the spec and the graph disagree on a shape or dtype;
            # none on the CPU, where nothing is donated)
            tail = range(len(traced) - step.n_tail - step.n_cache,
                         len(traced) - step.n_tail)
            aliased = len(_aliased_outputs(loaded).intersection(tail))
            obs.CACHE_ENTRIES_FED.inc(step.n_cache, kind=kind)
            obs.CACHE_ENTRIES_ALIASED.inc(aliased, kind=kind)
            return {"cache_fed": step.n_cache, "cache_aliased": aliased}

        loaded, _path, _timings = engine.acquire(
            kind, key, lower if compile else None,
            meta=engine.meta(kind, feed_sig, traced),
            describe=pairing if kind != "draft" else None, name=name,
            build_ms=(time.perf_counter() - t_build) * 1e3)
        if loaded is None:
            return None
        exe = (loaded if step.take is None
               else _InFetchOrder(loaded, step.take))
        with self._lock:
            self._compiled[ck] = (exe, step.fetch_names)
        return exe, step.fetch_names

    # -- preload ------------------------------------------------------------
    def _prefill_signature_of(self, meta, env) -> Optional[tuple]:
        """The signature a sidecar of the disk tier names, where it
        names a prefill of this environment (``env``:
        ``aot_cache.env_fingerprint()``): ``tokens``' shape in its
        ``feed_sig`` is the prefill's (batch, bucket). None for any
        other kind (a server's step, draft and verify window follow from
        ITS slots and strategy, and ``DecodeServer.start`` acquires them
        on the same thread), for a sidecar written under another
        environment or jax (it could only re-hash to another key) and
        for one that does not read as ``Engine.meta`` wrote it."""
        try:
            if (meta.get("kind") != "prefill"
                    or meta.get("env") != env):
                return None
            (shape,) = [tuple(int(d) for d in shp)
                        for n, shp, _ in meta["feed_sig"] if n == "tokens"]
            batch, seq = shape
        except (AttributeError, KeyError, TypeError, ValueError):
            return None  # a sidecar is a pickle of anything
        return self._signature("prefill", batch, seq)

    def preload(self) -> Dict[str, int]:
        """Load every prefill executable this predictor's disk directory
        holds, once a predictor: ``DecodeServer.start`` calls it on its
        caller's thread before the loop thread exists, which is where a
        load from the disk tier is cheap: on a v5e the same blob
        deserializes in 0.16 s on the main thread and in 2.2-2.5 s on a
        thread started for the purpose, a server's loop under
        ``decode.loop.admit`` among them, whatever else is resident
        (PERF.md 7 i). An admission then finds its shape in memory.

        The directory is walked by its sidecars, newest first. One that
        names a prefill (``_prefill_signature_of``) has that signature's
        key rebuilt as ``_acquire`` builds it; where the key IS the
        sidecar's, the executable is acquired through ``Engine.acquire``
        like any other: one ``path="warm"`` record with ``load_ms`` and
        ``blob_bytes``, begun under the phase ``decode.preload``. A
        sidecar of another program re-hashes to another key and costs
        its step program, no more (``stale``); a blob that will not load
        is ``AotDiskCache``'s never-a-crash path (quarantined,
        ``unreadable``) and is compiled again at its first admission, as
        any shape the disk does not hold. NOTHING IS COMPILED HERE, and
        there is no cap: what a directory holds is what its servers
        admitted, and the loop would load each at its first use anyway.
        Unlike ``Predictor._preload_executables`` (eight signatures at
        most, loaded past ``Engine.acquire``: no record, no counter).

        Returns the counts (``found``, ``loaded``, ``resident``: in
        memory already, ``stale``, ``unreadable``); a second call, from a
        second server of the predictor, returns them again and does
        nothing: twenty warm-up servers pay one walk."""
        with self._preload_lock:
            if self._preloaded is None:
                with jax.default_device(self._device):  # as acquire()
                    self._preloaded = self._preload()
            return dict(self._preloaded)

    def _preload(self) -> Dict[str, int]:
        n = dict.fromkeys(
            ("found", "loaded", "resident", "stale", "unreadable"), 0)
        if not self._disk.enabled:
            return n
        held = {}  # signature -> the sidecars' keys, newest first
        env = _aot.env_fingerprint()
        for key, meta in self._disk.sidecars_by_recency():
            ck = self._prefill_signature_of(meta, env)
            if ck is not None:
                held.setdefault(ck, []).append(key)
        n["found"] = len(held)
        if not held:
            return n
        with _tracing.phase("decode.preload", found=n["found"]) as ph:
            for ck, keys in held.items():
                with self._lock:
                    resident = ck in self._compiled
                if resident:
                    n["resident"] += 1
                    continue
                keyed = self._keyed(ck)
                if keyed.key not in keys:
                    result = "stale"
                elif self._acquire_keyed(ck, keyed, compile=False) is None:
                    result = "unreadable"
                else:
                    result = "loaded"
                n[result] += 1
                obs.DECODE_PRELOAD.inc(result=result)
            ph.note(loaded=n["loaded"], skipped=n["found"] - n["loaded"])
        return n

    def _step(self, kind, batch, seq, strategy, kv_dtype="float32",
              window=0, use_ring=False, name="ptpu_step") -> _Step:
        """The function ``_acquire`` jits for one signature (``fn``) and
        what is known of it before a trace. ``fn(feeds, state)`` returns
        the fetches in the order ``traced`` (``_pairing_order``);
        ``take`` re-indexes them to ``fetch_names``."""
        from ..framework.trace import RngStream, trace_block

        program, feed_names, fetch_names = self._build(
            kind, batch, seq, strategy, kv_dtype=kv_dtype,
            window=window, use_ring=use_ring)
        # what follows the cache entries keeps its place at the end
        n_tail = len(self.config.extra_fetches)
        body = len(fetch_names) - n_tail
        traced, take, n_cache = _pairing_order(
            feed_names, fetch_names[:body],
            [e.name for e in self.cache_spec(batch, seq, kv_dtype)])
        traced = list(traced) + list(fetch_names[body:])
        if take is not None:
            take = list(take) + list(range(body, len(fetch_names)))

        def step_fn(feeds, state):
            self.traces += 1
            env = dict(state)
            env.update(feeds)
            rng = RngStream(jax.random.PRNGKey(0))
            trace_block(program.global_block(), env, rng)
            return tuple(env[n] for n in traced)

        # jit names the module after the function: a device trace's
        # module line then tells a prefill from a decode step
        step_fn.__name__ = step_fn.__qualname__ = name
        return _Step(step_fn, program, feed_names, fetch_names, traced,
                     take, n_cache, n_tail)

    # -- host-side sampling (first token, from prefill logits) ------------
    def _sample_host(self, logits, strategy: str, seed: int):
        from ..ops import sampling as _S

        if strategy in ("greedy", "logits", "beam"):
            return np.asarray(_S.greedy_sample(logits))
        seed_arr = jnp.asarray([seed], jnp.int32)
        if strategy == "topk":
            return np.asarray(_S.top_k_sample(
                logits, seed_arr, self.sample_k, self.temperature))
        if strategy == "topp":
            return np.asarray(_S.top_p_sample(
                logits, seed_arr, self.sample_p, self.temperature))
        raise ValueError("unknown decode strategy %r" % strategy)

    def _bucketed(self, prompts: Sequence[np.ndarray], max_new: int,
                  batch_floor: int = 1, seq: Optional[int] = None):
        """Pad a prompt list into bucketed (tokens, lengths) arrays.
        Pad rows (beyond the real batch) carry one dummy token so the
        prefill's last-position gather stays in range."""
        b = len(prompts)
        plens = [int(len(p)) for p in prompts]
        if min(plens) < 1:
            raise ValueError("empty prompt (decode needs >= 1 token)")
        need = max(plens) + max_new
        if need > self.config.max_len:
            raise ValueError(
                "prompt %d + max_new_tokens %d exceeds the model's "
                "max_len %d" % (max(plens), max_new, self.config.max_len))
        s = seq if seq is not None else _pow2_bucket(need, floor=16)
        s = min(s, _pow2_bucket(self.config.max_len))
        if s > self.config.max_len:
            s = self.config.max_len  # max_len itself may not be pow2
        bb = _pow2_bucket(b, floor=batch_floor)
        tokens = np.zeros((bb, s), np.int64)
        lens = np.ones((bb,), np.int32)
        for i, p in enumerate(prompts):
            tokens[i, :plens[i]] = np.asarray(p, np.int64).reshape(-1)
            lens[i] = plens[i]
        return tokens, lens, b, s

    def _prefill(self, tokens, lens, slab_seq):
        """Run prefill at the PROMPTS' own pow2 sequence bucket, then
        zero-pad the returned K/V rows out to the slab length — prompt
        cost scales with the prompt, not with the decode budget.
        Returns (outs, caches in ``cache_spec`` order)."""
        bb = tokens.shape[0]
        sp = min(_pow2_bucket(int(lens.max()), floor=16), slab_seq)
        pexe, _ = self.acquire("prefill", bb, sp)
        t0 = time.perf_counter()
        outs = pexe({"tokens": tokens[:, :sp], "lengths": lens},
                    self._state)
        obs.DECODE_STEP_MS.observe((time.perf_counter() - t0) * 1e3,
                                   stage="prefill")
        caches = list(outs[1:1 + len(self.cache_spec(bb, slab_seq))])
        if sp < slab_seq:
            # rows per position pad out to the slab; a state or a ring
            # is whole
            caches = [
                jnp.pad(jnp.asarray(c), [(0, 0), (0, slab_seq - sp)]
                        + [(0, 0)] * (len(e.shape) - 2))
                if e.per_position else c
                for e, c in zip(self.cache_spec(bb, slab_seq), caches)]
        return outs, caches

    # -- generation (static batch, run to completion) ----------------------
    def generate(self, prompts: Sequence[np.ndarray],
                 max_new_tokens: int = 32, strategy: Optional[str] = None,
                 seed: int = 0, eos_id: Optional[int] = None,
                 beam_size: int = 4, speculative: bool = False,
                 spec_k: int = 4) -> List[np.ndarray]:
        """Generate up to ``max_new_tokens`` per prompt (stopping a row
        early at ``eos_id``). Returns one int64 array of generated ids
        per prompt. ``strategy`` overrides the constructor's
        ("greedy" | "topk" | "topp" | "beam").

        ``speculative=True`` (greedy only) runs draft-verify rounds:
        the ``draft_n_layer``-deep draft proposes ``spec_k`` tokens,
        the target checks all of them in ONE verify call — output is
        token-for-token identical to plain greedy (the lossless
        property), up to spec_k+1 tokens per target-model call."""
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1, got %d"
                             % max_new_tokens)
        strategy = strategy or self.strategy
        eos = eos_id if eos_id is not None else self.eos_id
        if speculative:
            if strategy != "greedy":
                raise ValueError(
                    "speculative decoding is lossless for greedy only; "
                    "got strategy %r" % (strategy,))
            return self.generate_speculative(
                prompts, max_new_tokens, spec_k=spec_k, eos_id=eos)
        if strategy == "beam":
            return self.generate_beam(prompts, max_new_tokens,
                                      beam_size=beam_size, eos_id=eos)
        if strategy not in ("greedy", "topk", "topp"):
            # "logits" builds a sampler-less step whose fetch layout
            # (no next_ids) this loop cannot drive — it is the
            # generate_beam/acquire surface, not a generate strategy
            raise ValueError(
                "unknown decode strategy %r (greedy | topk | topp | "
                "beam)" % (strategy,))
        tokens, lens, b, s = self._bucketed(prompts, max_new_tokens)
        bb = tokens.shape[0]
        outs, caches = self._prefill(tokens, lens, s)
        obs.DECODE_TOKENS.inc(int(lens[:b].sum()), kind="prefill")
        cur = np.array(self._sample_host(outs[0], strategy, seed))
        generated = [[int(cur[i])] for i in range(b)]
        finished = np.array([eos is not None and int(cur[i]) == eos
                             for i in range(b)])
        obs.DECODE_TOKENS.inc(b, kind="decode")
        if max_new_tokens > 1 and not finished.all():
            dexe, _fetch_names = self.acquire("decode", bb, s, strategy)
            self._plain_decode_steps(dexe, caches, cur, lens.copy(),
                                     generated, finished, b, s, eos,
                                     max_new_tokens, seed)
        return [np.asarray(g, np.int64) for g in generated]

    def _plain_decode_steps(self, dexe, caches, cur, lens, generated,
                            finished, b, s, eos, max_new_tokens,
                            seed) -> list:
        """THE one-token-per-iteration step loop, shared by
        ``generate()`` (the whole decode after the first sample) and
        ``generate_speculative()`` (the slab-headroom tail once a
        verify window no longer fits). Mutates ``cur`` / ``lens`` /
        ``generated`` / ``finished`` per ROW — a finished row's slot
        state freezes (its re-fed token and parked length only touch
        its own independent slab row, masked from every live row) —
        and returns the final caches. A row stops at eos, at its token
        budget, or when its slab row is full."""
        bb = cur.shape[0]
        names = [e.name for e in self.cache_spec(bb, s)]
        step = 0
        while not finished.all():
            step += 1
            feeds = {"tokens": cur.reshape(bb, 1).astype(np.int64),
                     "lengths": lens,
                     "seed": np.array([seed + step], np.int64)}
            if self.config.positions:
                feeds["positions"] = lens.reshape(bb, 1).astype(np.int64)
            feeds.update(zip(names, caches))
            t0 = time.perf_counter()
            outs = dexe(feeds, self._state)
            obs.DECODE_STEP_MS.observe(
                (time.perf_counter() - t0) * 1e3, stage="step")
            nxt = np.asarray(outs[0]).astype(np.int64)
            caches = list(outs[2:2 + len(names)])
            emitted = 0
            for i in range(b):
                if finished[i]:
                    continue
                tok = int(nxt[i])
                generated[i].append(tok)
                cur[i] = tok
                lens[i] += 1
                emitted += 1
                if (eos is not None and tok == eos) \
                        or len(generated[i]) >= max_new_tokens \
                        or lens[i] + 1 >= s:
                    finished[i] = True
            obs.DECODE_TOKENS.inc(emitted, kind="decode")
        return caches

    # -- speculative decoding (draft-verify rounds, greedy/lossless) -------
    def draft_window(self, drexe, caches, cur, lens, spec_k):
        """One speculative round's DRAFT half, shared by
        ``generate_speculative`` and ``DecodeServer._spec_round``:
        run ``spec_k`` reduced-depth steps over the committed slabs'
        first ``draft_n_layer`` layers (the draft executable never
        donates, so the committed arrays stay valid for the verify
        feed; its returned slabs are hypotheses, dropped here) and
        build the verify window. Returns (window_tokens (B, spec_k+1),
        positions (B, spec_k+1)) — positions clipped to max_len-1 so
        window slots past a row's reach still embed in range."""
        bb = cur.shape[0]
        dn = self.draft_n_layer
        max_len = self.config.max_len
        dcaches = caches[:2 * dn]
        dcur, dlens = cur.copy(), lens.copy()
        zeros_seed = np.zeros((1,), np.int64)
        proposals = []
        t0 = time.perf_counter()
        for _ in range(spec_k):
            feeds = {"tokens": dcur.reshape(bb, 1).astype(np.int64),
                     "positions": np.minimum(
                         dlens, max_len - 1).reshape(bb, 1).astype(
                             np.int64),
                     "lengths": dlens, "seed": zeros_seed}
            for i in range(dn):
                feeds["kcache_%d" % i] = dcaches[2 * i]
                feeds["vcache_%d" % i] = dcaches[2 * i + 1]
            douts = drexe(feeds, self._state)
            dcur = np.asarray(douts[0]).astype(np.int64)
            dcaches = list(douts[2:])
            proposals.append(dcur)
            dlens = dlens + 1
        obs.DECODE_STEP_MS.observe((time.perf_counter() - t0) * 1e3,
                                   stage="draft")
        window = np.stack([cur] + proposals, axis=1)
        positions = np.minimum(
            lens[:, None].astype(np.int64)
            + np.arange(spec_k + 1, dtype=np.int64)[None, :],
            max_len - 1)
        return window, positions

    def generate_speculative(self, prompts: Sequence[np.ndarray],
                             max_new_tokens: int = 32, spec_k: int = 4,
                             eos_id: Optional[int] = None
                             ) -> List[np.ndarray]:
        """Greedy speculative decode: per round, ``spec_k`` draft steps
        (the target's first ``draft_n_layer`` layers — self-drafting,
        same loaded state) propose tokens, then ONE verify window call
        checks them all against the full target and emits
        accept+1 tokens per row. Token-for-token identical to
        ``generate(strategy="greedy")``; when the window would overrun
        the slab, the tail finishes on plain decode steps."""
        self._rows_only("speculative decoding")
        if spec_k < 1:
            raise ValueError("spec_k must be >= 1, got %d" % spec_k)
        eos = eos_id if eos_id is not None else self.eos_id
        tokens, lens, b, s = self._bucketed(prompts, max_new_tokens)
        bb = tokens.shape[0]
        outs, caches = self._prefill(tokens, lens, s)
        obs.DECODE_TOKENS.inc(int(lens[:b].sum()), kind="prefill")
        cur = np.array(self._sample_host(outs[0], "greedy", 0))  # writable
        generated = [[int(cur[i])] for i in range(b)]
        finished = np.array([(eos is not None and int(cur[i]) == eos)
                             or max_new_tokens <= 1 for i in range(b)])
        obs.DECODE_TOKENS.inc(b, kind="decode")
        lens = lens.copy().astype(np.int32)
        T = spec_k + 1
        if not finished.all():
            dexe, _ = self.acquire("draft", bb, s)
            vexe, _ = self.acquire("verify", bb, s, window=T)
        zeros_idx = np.zeros((bb,), np.int32)
        while not finished.all() and int(lens.max()) + T <= s:
            window, positions = self.draft_window(dexe, caches, cur,
                                                  lens, spec_k)
            feeds = {"tokens": window, "positions": positions,
                     "lengths": lens, "last_idx": zeros_idx}
            for i in range(self.config.n_layer):
                feeds["kcache_%d" % i] = caches[2 * i]
                feeds["vcache_%d" % i] = caches[2 * i + 1]
            t0 = time.perf_counter()
            vouts = vexe(feeds, self._state)
            obs.DECODE_STEP_MS.observe(
                (time.perf_counter() - t0) * 1e3, stage="verify")
            next_ids = np.asarray(vouts[0]).astype(np.int64)
            accept = np.asarray(vouts[1]).astype(np.int64)
            caches = list(vouts[3:])
            live = int((~finished[:b]).sum()) if b else 0
            obs.DECODE_SPEC_PROPOSED.inc(spec_k * live)
            emitted = 0
            for i in range(b):
                if finished[i]:
                    continue
                a = int(accept[i])
                toks, stopped = _accepted_tokens(
                    next_ids[i], a, max_new_tokens - len(generated[i]), eos)
                generated[i].extend(toks)
                emitted += len(toks)
                if stopped or len(generated[i]) >= max_new_tokens:
                    finished[i] = True
                if not finished[i]:
                    # rollback by truncation: rows past lens+a are
                    # rejected-window garbage, masked by length and
                    # overwritten by later appends
                    lens[i] += a + 1
                    cur[i] = next_ids[i, a]
            obs.DECODE_TOKENS.inc(emitted, kind="decode")
        if not finished.all():
            # slab headroom exhausted: finish the tail on the SAME
            # plain step loop generate() runs (greedy ignores the seed
            # feed, so the shared loop's seed+step stream is
            # token-for-token the old constant-zero feed)
            dexe2, _ = self.acquire("decode", bb, s, "greedy")
            self._plain_decode_steps(dexe2, caches, cur, lens,
                                     generated, finished, b, s, eos,
                                     max_new_tokens, seed=0)
        return [np.asarray(g, np.int64) for g in generated]

    # -- beam-search strategy (ops-layer beam step between decode execs) ---
    def generate_beam(self, prompts: Sequence[np.ndarray],
                      max_new_tokens: int = 32, beam_size: int = 4,
                      eos_id: Optional[int] = None,
                      return_all: bool = False):
        """Beam-search decode: the compiled decode step runs with
        strategy="logits" (no sampler) and the ops-layer
        ``beam_search_step`` / ``beam_search_backtrack`` kernels
        (ops/decode.py — the same math contrib's BeamSearchDecoder scans
        with) pick continuations and reorder the KV slabs by parent via
        ``cache_gather`` between steps. Returns the best beam's ids per
        prompt (or, with return_all, (ids (B, K, T), lengths, scores))."""
        from ..ops.decode import beam_search_backtrack, beam_search_step
        from ..ops.kv_cache import cache_gather

        self._rows_only("beam search")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1, got %d"
                             % max_new_tokens)
        k = int(beam_size)
        eos = eos_id if eos_id is not None else self.eos_id
        end_id = -1 if eos is None else int(eos)
        tokens, lens, b, s = self._bucketed(prompts, max_new_tokens)
        outs, pcaches = self._prefill(tokens, lens, s)
        obs.DECODE_TOKENS.inc(int(lens[:b].sum()), kind="prefill")
        lp = jax.nn.log_softmax(
            jnp.asarray(outs[0][:b]).astype(jnp.float32), axis=-1)
        pre_scores, pre_ids = jax.lax.top_k(lp, k)     # (B, K) each
        pre_ids = pre_ids.astype(jnp.int32)

        bk = _pow2_bucket(b * k)
        # beam-expand the caches: slab row b*K+j starts as prompt b's
        pad = np.zeros(bk - b * k, np.int32)
        expand = np.concatenate(
            [np.repeat(np.arange(b, dtype=np.int32), k), pad])
        caches = [cache_gather(c, jnp.asarray(expand)) for c in pcaches]
        lens_k = np.concatenate(
            [np.repeat(lens[:b], k), np.ones(bk - b * k, np.int32)]
        ).astype(np.int32)
        step_ids = [pre_ids]
        step_parents = [jnp.broadcast_to(
            jnp.arange(k, dtype=jnp.int32)[None, :], (b, k))]
        dexe, _ = self.acquire("decode", bk, s, "logits")
        for _step in range(1, max_new_tokens):
            cur = np.zeros((bk,), np.int64)
            cur[:b * k] = np.asarray(pre_ids).reshape(-1)
            feeds = {"tokens": cur.reshape(bk, 1),
                     "positions": lens_k.reshape(bk, 1).astype(np.int64),
                     "lengths": lens_k,
                     "seed": np.zeros((1,), np.int64)}
            for i in range(self.config.n_layer):
                feeds["kcache_%d" % i] = caches[2 * i]
                feeds["vcache_%d" % i] = caches[2 * i + 1]
            t0 = time.perf_counter()
            outs = dexe(feeds, self._state)
            obs.DECODE_STEP_MS.observe((time.perf_counter() - t0) * 1e3,
                                       stage="step")
            logits = jnp.asarray(outs[0][:b * k]).astype(jnp.float32)
            lp = jax.nn.log_softmax(logits, axis=-1).reshape(
                b, k, self.config.vocab_size)
            total = pre_scores[:, :, None] + lp
            sel_ids, sel_scores, parents = beam_search_step(
                pre_ids, pre_scores, total, None, k, end_id)
            # reorder the APPENDED slabs by parent so each surviving
            # beam carries its parent's full lineage
            flat_parent = np.concatenate([
                (np.arange(b, dtype=np.int32)[:, None] * k
                 + np.asarray(parents)).reshape(-1), pad])
            caches = [cache_gather(c, jnp.asarray(flat_parent))
                      for c in outs[1:]]
            pre_ids, pre_scores = sel_ids, sel_scores
            step_ids.append(sel_ids)
            step_parents.append(parents)
            lens_k = lens_k + 1
            obs.DECODE_TOKENS.inc(b * k, kind="decode")
            if eos is not None and bool(
                    (np.asarray(sel_ids) == end_id).all()):
                break
        sent, slens = beam_search_backtrack(
            jnp.stack(step_ids), jnp.stack(step_parents), end_id)
        sent = np.asarray(sent)
        slens = np.asarray(slens)
        if return_all:
            return sent, slens, np.asarray(pre_scores)
        return [np.asarray(sent[i, 0, :slens[i, 0]], np.int64)
                for i in range(b)]


class DecodeServer:
    """Continuous-batching decode serving loop.

    server = DecodeServer(DecodePredictor(model_dir), slots=8)
    server.start()
    fut = server.submit((prompt_ids,))            # or (ids, [max_new])
    (generated,) = fut.result()
    server.stop()

    One resident KV slab of ``slots`` rows serves every request: the
    loop admits queued prompts into free rows BETWEEN decode steps (a
    bucketed prefill sub-batch, scattered into the slab), steps every
    active row one token per iteration through ONE compiled (slots, S)
    executable, and retires finished rows eagerly — a long sequence
    never holds short ones hostage, and a fresh request starts decoding
    mid-flight instead of waiting for the batch to drain
    (``continuous=False`` restores gang scheduling for A/B runs).

    The loop keeps ONE decode step in flight: step n+1 is dispatched on
    the device's own next-token ids before the host reads step n's, so
    the host's share of an iteration runs beside the device's. Lengths
    and budgets are the host's to know, so a sequence leaves its slot
    at the dispatch of its last step; only an ``eos_id`` hit is learnt
    a step late, and that slot's one extra step is delivered to nobody
    (slots are independent rows, and an admission overwrites what it
    scatters). A server over a model with a prediction layer keeps one
    ROUND in flight the same way: how far a round takes a slot is data,
    so lengths, tokens and budgets are advanced on the device
    (``_round_chain_fn``) and the host reads a round's ids one round
    late; a slot that ended there runs as a free slot meanwhile. The
    OPT self-draft's rounds (``spec_k`` draft dispatches, then a verify
    call) keep the host in the loop.

    Requests ride the same zero-copy channel frames as PredictorServer
    (slot 0: int prompt ids; optional slot 1: [max_new_tokens] or
    [max_new_tokens, seed] int64), and the response is one int64 array
    of generated ids — so the PR-8 Router forwards decode traffic
    verbatim and ``stop()`` keeps the zero-drop contract: everything
    admitted OR still queued finishes before the loop exits. A
    per-request ``seed`` seeds that request's FIRST sampled token;
    later steps draw from the server's stream (steps are shared across
    slots), so fully seeded reproducible sampling is
    ``DecodePredictor.generate``'s surface — greedy traffic is
    deterministic either way.
    """

    def __init__(self, predictor: DecodePredictor, slots: int = 4,
                 max_seq: Optional[int] = None, max_new_tokens: int = 32,
                 strategy: Optional[str] = None, capacity: int = 256,
                 eos_id: Optional[int] = None, continuous: bool = True,
                 prewarm: bool = True, kv_dtype: Optional[str] = None,
                 speculative: bool = False, spec_k: int = 4,
                 prefix_cache: bool = False, prefix_block: int = 16,
                 prefix_max_bytes: Optional[int] = None,
                 prefix_store=None):
        from ..runtime.recordio import Channel

        if slots < 1:
            raise ValueError("slots must be >= 1, got %d" % slots)
        self.predictor = predictor
        self.slots = int(slots)
        # int8 KV slabs (opt-in; PADDLE_TPU_QUANT=kv8 is the env knob):
        # rows quantize at append against per-(slot, position) scales
        # and dequantize on attention read — slab bytes drop 2x vs bf16
        # (4x vs these float32 slabs), so one slab budget holds 2x the
        # sequences (kv_slab_slots has the arithmetic)
        self.kv_dtype = kv_dtype if kv_dtype is not None \
            else _kv_dtype_from_env()
        if self.kv_dtype not in ("float32", "int8"):
            raise ValueError(
                "kv_dtype must be 'float32' or 'int8', got %r"
                % (self.kv_dtype,))
        cfg = predictor.config
        if speculative:
            predictor._rows_only("speculative decoding")
        if prefix_cache or prefix_store is not None:
            predictor._rows_only("a prefix store")
        if self.kv_dtype == "int8":
            predictor._rows_only("an int8 KV slab")
        want = max_seq or cfg.max_len
        self.seq = min(_pow2_bucket(want, floor=16),
                       _pow2_bucket(cfg.max_len))
        if self.seq > cfg.max_len:
            self.seq = cfg.max_len
        self.max_new_tokens = int(max_new_tokens)
        self.strategy = strategy or predictor.strategy
        if self.strategy in ("beam", "logits"):
            raise ValueError(
                "DecodeServer streams one token per step; strategy %r "
                "is a DecodePredictor.generate-only mode" % self.strategy)
        self.eos_id = eos_id if eos_id is not None else predictor.eos_id
        self.continuous = bool(continuous)
        self._prewarm = prewarm
        # speculative decoding: per loop iteration, spec_k draft steps
        # (the target's first draft_n_layer layers) propose tokens and
        # ONE verify window call checks them — each active slot
        # advances by accept+1 tokens per round, token-for-token
        # identical to the plain greedy loop (lossless)
        self.speculative = bool(speculative)
        self.spec_k = int(spec_k)
        if self.speculative and self.strategy != "greedy":
            raise ValueError(
                "speculative decoding is lossless for greedy only; the "
                "server strategy is %r" % (self.strategy,))
        if (self.speculative or prefix_cache or prefix_store is not None) \
                and self.spec_k < 1:
            # prefix-only servers still size their suffix-extension
            # window off spec_k (_win below) — fail HERE, not as a
            # cryptic "verify windows need T >= 2" mid-admission
            raise ValueError("spec_k must be >= 1, got %d" % self.spec_k)
        # shared-prefix KV: admission hashes prompts against a
        # refcounted store of prefilled rows — N users of one prompt
        # pay ONE prefill; prompts sharing an aligned header seed from
        # the cached rows and extend only their suffix
        if prefix_store is not None:
            self._prefix = prefix_store
        elif prefix_cache:
            from .prefix import PrefixStore

            self._prefix = PrefixStore(max_bytes=prefix_max_bytes,
                                       block=prefix_block)
        else:
            self._prefix = None
        # a model that publishes a prediction layer drafts for itself:
        # its server runs ROUNDS of two positions a slot (the current
        # token and the draft), ONE dispatch and ONE fetch each, one in
        # flight behind another, without being asked. The round is the
        # model's one step program (the OPT self-draft and an int8 slab
        # were refused above)
        self.rounds = bool(cfg.n_predict_layers)
        if self.rounds and self.strategy != "greedy":
            raise ValueError(
                "a model with a prediction layer is served by rounds, "
                "which are lossless for greedy only; the server strategy "
                "is %r" % (self.strategy,))
        if (self.speculative or self._prefix is not None) \
                and self.kv_dtype == "int8":
            raise ValueError(
                "speculative decoding / prefix sharing run float32 "
                "slabs (int8 scatter-quantized windows are a device-"
                "window follow-up); drop kv_dtype='int8' or the lever")
        # the shared verify-window width: spec rounds AND prefix suffix
        # extension ride one compiled (slots, S, T) signature
        self._win = self.spec_k + 1
        # prefill-execution count — the test-pinned "N users of one
        # prompt pay ONE prefill" observable
        self.prefill_executions = 0
        # the rows those prefills ran on (power-of-two batch x sequence
        # bucket, padding included) and the prompts' own among them:
        # 1 - prompt / bucket is the padding share of a server's prefills
        self.prefill_bucket_rows = 0
        self.prefill_prompt_rows = 0
        self._chan = Channel(capacity)
        self._results: Dict[int, "_DecodeFuture"] = {}
        self._next_id = 0
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._http = None
        self._http_thread = None
        self._seed_ctr = 0
        # diagnostic: per-iteration active-slot counts (the continuous-
        # vs-static fill story; benchmark/lib/run_serve.py reads it).
        # BOUNDED: a long-lived server must not grow an entry per decode
        # step forever — 100k covers any benchmark window
        import collections

        self.step_active_counts: "collections.deque" = collections.deque(
            maxlen=100_000)
        # the model's cache entries, in the SAME order the decode
        # graph's fetch list flattens its updated tensors — so
        # zip(self._cache_feed_names, outs[2:]) rethreads each step.
        # The one source of the feed names, the fresh arrays, the
        # admission scatter and the step's counts
        self._spec = predictor.cache_spec(self.slots, self.seq,
                                          self.kv_dtype)
        self._cache_feed_names = [e.name for e in self._spec]
        self._cache_per_layer = 4 if self.kv_dtype == "int8" else 2
        # bytes of fixed-size recurrent state a slot keeps (a step reads
        # and writes all of it; rows and rings are counted by the row)
        self._state_bytes_per_slot = sum(
            e.nbytes for e in self._spec
            if e.kind == "state") // self.slots
        # of those, a slot's delta-rule (KDA) matrix states, all layers
        self._kda_state_bytes_per_slot = sum(
            e.nbytes for e in self._spec
            if e.name.startswith("kda_")) // self.slots
        # layers whose prefill is a selective scan (``ptpu.ssm_scan``)
        self._ssm_layers = cfg.layer_kinds().count("mamba")
        # a sliding-window layer's ring holds this many rows (0: none)
        self._ring_window = int(cfg.window) if cfg.has_ring else 0
        self._uneven_kv = cfg.uneven_kv
        # layers that route over experts: a step and a prefill return
        # the pairs each held expert received (``moe_load``, last)
        self._moe_layers = cfg.sparse_layers()
        lo, hi = cfg.held
        self.moe_load_total = np.zeros((len(self._moe_layers), hi - lo),
                                       np.int64)
        self._moe_last = {"expert_pairs": 0, "experts_active": 0}
        # under group-limited routing a token may send this chip nothing:
        # ``moe_load`` then carries that count in a last column
        self._moe_elsewhere = int(cfg.router_groups) > 1
        # rows a block of the float32 decode kernel brings in, or None
        # where a step reads whole slabs (int8 slabs and the speculative
        # verify window are lax paths of their own; so is a slab of
        # fewer heads than the query that the kernel has no view of)
        self._stream_rows = None
        if self.kv_dtype == "float32" and not self.speculative:
            from ..models import jamba as _J

            with jax.default_device(predictor._device):  # as acquire()
                self._stream_rows = _KV.decode_stream_rows(
                    _J.stream_view(cfg, self.seq))
        # layers that attend ONE shared slab in a step: its owner and
        # the cross layers after it (0: every slab has one reader)
        kinds = cfg.layer_kinds()
        self._slab_readers = (1 + kinds.count("cross")
                              if "cross" in kinds else 0)
        # a prefill runs the layers from here on one row a prompt
        self._has_tail = cfg.tail_start < cfg.n_layer
        # bytes of one latent row (0: no latent layer); a step's
        # absorbed attention reads every latent layer's live rows
        self._latent_row_bytes = (4 * cfg.latent_row if cfg.has_latent
                                  else 0)
        # a query of a layer under an indexer attends this many rows at
        # most (0: no such layer); its step scores and streams a slot's
        # live blocks and masks the rows not chosen (``ops/dsa.py``; the
        # lax forms, where ``_stream_rows`` is None, every row of every
        # slot)
        self._index_topk = (int(cfg.index_topk)
                            if "latent_dsa" in kinds else 0)
        # an EVA layer's (summary rows, window, chunk), or None: its
        # step reads a slot's visible summaries and its window's rows
        self._eva = (cfg.eva_rows[0], int(cfg.window), int(cfg.eva_chunk)
                     ) if cfg.has_eva else None

    # -- submission (PredictorServer-compatible surface) -------------------
    def submit(self, sample: Sequence[np.ndarray]):
        from ..inference import _Future, _encode_sample

        fut = _Future()
        fut._t0 = time.perf_counter()
        with self._lock:
            rid = self._next_id
            self._next_id += 1
            self._results[rid] = fut
        fut._bind(self, rid)
        tid = _tracing.maybe_start()
        if tid is not None:
            # standalone-server client edge (the PredictorServer.submit
            # pattern): no wire hop, bind straight into the stage table
            _tracing.bind_rid(rid, tid)
            _tracing.record_span(tid, "client.submit", rid=rid)
        try:
            sent = self._chan.send(_encode_sample(rid, sample))
        except BaseException:
            with self._lock:
                self._results.pop(rid, None)
            _tracing.pop_rid(rid)
            raise
        if not sent:
            with self._lock:
                self._results.pop(rid, None)
            _tracing.pop_rid(rid)
            raise RuntimeError("decode server is stopped")
        return fut

    def submit_frame(self, msg):
        """Router fan-in: an already-encoded frame, tag = request id."""
        from ..inference import _Future

        rid = _rio.frame_tag(msg)
        fut = _Future()
        fut._t0 = time.perf_counter()
        with self._lock:
            if rid in self._results:
                raise ValueError("request tag %d is already in flight"
                                 % rid)
            self._results[rid] = fut
        fut._bind(self, rid)
        if not self._chan.send(msg):
            with self._lock:
                self._results.pop(rid, None)
            raise RuntimeError("decode server is stopped")
        return fut

    def _pop(self, rid):
        # every future exit path funnels here: the trace binding a
        # traced request carried can never leak
        _tracing.pop_rid(rid)
        with self._lock:
            return self._results.pop(rid, None)

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        """Open the serving loop. With ``prewarm`` (the default)
        everything this server can have ready is made ready first, HERE,
        on the caller's thread, before the loop thread exists (a load
        made on the main thread costs a tenth of one made on the loop's:
        ``DecodePredictor.preload``): every prefill executable the
        predictor's disk directory holds is loaded (once a predictor,
        so a second server of it pays nothing; nothing is compiled),
        then the server's own step, the floor-bucket prefills and, where
        it has them, the draft and the verify window are loaded or
        compiled. What is left to the loop thread is a prompt bucket the
        disk has never held: compiled at its first admission, stored,
        and preloaded by the next process. ``prewarm=False`` leaves
        everything to the first use: no preload either."""
        if self._thread is not None and self._thread.is_alive():
            return
        if self._prewarm:
            # the steady-state signatures compile/AOT-load BEFORE the
            # first request: the ONE (slots, S) decode step, plus the
            # single-request and full-burst admission prefills at the
            # floor PROMPT bucket (_admit prefills at the prompts' own
            # pow2 bucket, so the floor is what typical short-prompt
            # traffic actually hits — longer prompts lazily warm their
            # own bucket on first arrival, unless the disk tier held
            # it: then the preload has it in memory already)
            t0 = time.perf_counter()
            self.predictor.preload()
            if self.rounds:
                self.predictor.acquire("round", self.slots, self.seq)
            else:
                self.predictor.acquire("decode", self.slots, self.seq,
                                       self.strategy,
                                       kv_dtype=self.kv_dtype)
            if self.rounds:
                _round_chain_fn(self.slots, self.seq, self.eos_id,
                                self.predictor._device)
            elif not self.speculative:
                _chain_fn(self.slots, self.predictor._device)
            sp = min(16, self.seq)
            self.predictor.acquire("prefill", 1, sp)
            if self.slots > 1:
                self.predictor.acquire(
                    "prefill",
                    _pow2_bucket(self._admit_room(self.slots)), sp)
            if self.speculative:
                self.predictor.acquire("draft", self.slots, self.seq)
            if self.speculative or self._prefix is not None:
                self.predictor.acquire("verify", self.slots, self.seq,
                                       window=self._win)
            obs.SERVER_STAGE_MS.observe(
                (time.perf_counter() - t0) * 1e3, stage="prewarm")
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="ptpu-decode-loop")
        self._thread.start()

    def stop(self):
        """Zero-drop stop: close the intake, then the loop admits
        everything still queued (as slots free) and finishes every
        in-flight generation before exiting."""
        self.stop_http()
        self._chan.close()
        if self._thread is not None:
            self._thread.join(timeout=300)
            self._thread = None

    # metrics endpoint: same handler as the PR-2 server (self._http/
    # self._http_thread are the only state it touches)
    from ..inference import PredictorServer as _PS

    start_http = _PS.start_http
    stop_http = _PS.stop_http
    del _PS

    # -- serving loop ------------------------------------------------------
    def _decode_request(self, msg):
        from ..inference import _decode_request

        rid, rows = _decode_request(msg)
        prompt = np.asarray(rows[0]).reshape(-1).astype(np.int64)
        max_new = self.max_new_tokens
        seed = None
        if len(rows) > 1:
            opts = np.asarray(rows[1]).reshape(-1)
            if opts.size >= 1:
                if int(opts[0]) < 1:
                    raise ValueError(
                        "max_new_tokens must be >= 1, got %d"
                        % int(opts[0]))
                max_new = min(int(opts[0]), self.max_new_tokens)
            if opts.size >= 2:
                seed = int(opts[1])
        return rid, prompt, max_new, seed

    def _set_slot_gauges(self, n_active: int):
        obs.DECODE_SLOTS.set(n_active, state="active")
        obs.DECODE_SLOTS.set(self.slots - n_active, state="free")

    def _fail(self, rid, exc):
        fut = self._pop(rid)
        if fut is not None:
            obs.PREDICT_FAILURES.inc(path="decode")
            fut.set_exception(exc)

    def _retire(self, slot_state):
        rid = slot_state["rid"]
        # span BEFORE _pop — _pop drops the trace binding
        _tracing.rid_span(rid, "decode.retire",
                          tokens=len(slot_state["generated"]))
        fut = self._pop(rid)
        obs.DECODE_REQUESTS.inc(kind="retired")
        if self._prefix is not None:
            # refcount release: the retired sequence no longer pins its
            # prefix entry against eviction
            self._prefix.release(slot_state.get("prefix_entry"))
        if fut is not None:  # abandoned via cancel/timeout otherwise
            fut.set_result([np.asarray(slot_state["generated"], np.int64)])
            obs.PREDICT_LATENCY_MS.observe(
                (time.perf_counter() - fut._t0) * 1e3, path="decode")
            obs.PREDICT_REQUESTS.inc(path="decode")

    def _prefill_prompts(self, prompts):
        """The ONE admission-prefill recipe (shared by ``_admit`` and
        ``_admit_prefix``), run ONCE an admission (``_admit_group``
        says why): bucket the prompts it is given to a pow2 batch and
        the pow2 sequence length of the longest (``_admit_group`` hands
        it prompts of one bucket, or of any under ``_ADMIT_FLOOR``) —
        not the slab length: admitting a 16-token prompt into a
        1024-token slab must cost a 16-token forward (this is what lets
        continuous admission beat gang scheduling — a slab-sized
        prefill per admission would eat the win) — DISPATCH the prefill
        executable, and account its tokens and its rows
        (``prefill_bucket_rows``, what the program ran on, and
        ``prefill_prompt_rows``, the prompts' own). Returns ``(outs,
        sp, t0)``: the raw executable outputs (logits + per-layer float
        K/V sub-slabs, still on their way),
        the sequence bucket they are shaped at, and the clock at the
        dispatch: the caller waits for the logits under
        ``decode.loop.first_token`` and hands ``t0`` to
        ``_observe_prefill`` there. Raises what the acquire/execute
        raises — the caller owns the admission-failure contract."""
        bb = _pow2_bucket(len(prompts))
        sp = min(_pow2_bucket(max(len(p) for p in prompts), floor=16),
                 self.seq)
        tokens = np.zeros((bb, sp), np.int64)
        plens = np.ones((bb,), np.int32)
        for i, p in enumerate(prompts):
            tokens[i, :len(p)] = p
            plens[i] = len(p)
        pexe, _ = self.predictor.acquire("prefill", bb, sp)
        with _tracing.phase("decode.loop.prefill") as ph:
            t0 = ph.t0 or time.perf_counter()
            outs = pexe({"tokens": tokens, "lengths": plens},
                        self.predictor._state)
        rows = int(plens[:len(prompts)].sum())
        self.prefill_executions += 1
        self.prefill_bucket_rows += bb * sp
        self.prefill_prompt_rows += rows
        obs.DECODE_TOKENS.inc(rows, kind="prefill")
        return outs, sp, t0

    @staticmethod
    def _observe_prefill(t0, waited) -> float:
        """An admission's prefill, in ms, from its dispatch (``t0`` of
        ``_prefill_prompts``) to its logits on the host (the end of the
        ``first_token`` phase ``waited``): the sample of
        ``paddle_tpu_decode_step_ms{stage="prefill"}`` and the
        ``prefill_ms`` of the ``decode.admit`` span. ``pexe(...)`` alone
        returns at once; the device's time shows where the host waits.
        At rate 0 the phases read no clock and this reads the one that
        closes the pair."""
        ms = ((waited.t1 or time.perf_counter()) - t0) * 1e3
        obs.DECODE_STEP_MS.observe(ms, stage="prefill")
        return ms

    # a model without sliding-window or expert layers has neither, and
    # one whose every layer owns its cache entries no shared slab's
    # readers and no one-row tail of a prefill
    _ring_window = 0
    _moe_layers = ()
    _slab_readers = 0
    _has_tail = False
    _latent_row_bytes = 0
    _index_topk = 0
    _eva = None
    _kda_state_bytes_per_slot = 0
    _ssm_layers = 0
    _uneven_kv = False

    # prompts one admission prefills at most, while sequences are live,
    # and the bucketed tokens (power-of-two batch x the prompts' bucket)
    _ADMIT_MOST = 8
    _ADMIT_TOKENS = 16384
    # the rows under which prompts of different buckets still share an
    # admission. Under the ridge of the weight stream a prefill program
    # costs its weights' read whatever its rows (the hybrid cell on a
    # v5e, PERF.md, PR 49: one prompt in the 64 bucket 10.2 ms, 128 9.6,
    # 256 11.0, 512 15.3, then ~25 us a row), so two short prompts in
    # one program of the longer's bucket cost less than two programs;
    # from 1024 rows on a row is paid for in full, and a shorter
    # neighbour's padding with it
    _ADMIT_FLOOR = 512

    def _admit_room(self, free: int) -> int:
        """The most requests one admission takes: between two decode
        steps at most ``_ADMIT_MOST``, since an admission stalls every
        live sequence for its prefill. A gang-scheduled server
        (``continuous=False``) fills its slots at once, as it always
        has."""
        return min(free, self._ADMIT_MOST) if self.continuous else free

    def _admit_group(self, free: int, pending) -> List[int]:
        """Which of ``pending`` (the queue, oldest first) the next
        admission takes, as indices into it: the oldest request, and of
        the ``_admit_room(free) - 1`` behind it those that run in ITS
        program without paying for rows they do not have.

        The group's bucket is the oldest prompt's own power-of-two
        bucket, never under ``_ADMIT_FLOOR``; a waiting request joins
        when its bucket, floored the same way, is the group's. A 300-
        and an 1,100-token prompt that free slots together are two
        admissions one decode step apart (512 + 2,048 rows), not one
        ``b2_s2048`` program of 4,096 rows for 1,400; prompts under the
        floor share as they always have. What is passed over KEEPS ITS
        PLACE in ``pending`` and is the head, or joins one, at the next
        iteration. The oldest always goes, so a request is passed over
        at most as many times as requests stood before it.

        Of the group no more are taken than keep the prefill's bucketed
        tokens (power-of-two batch x bucket) within ``_ADMIT_TOKENS``,
        never fewer than one: a prefill's temporaries grow with the
        tokens it holds (8 prompts of 2048 tokens: 1.9 GB at the widths
        of a 3 B hybrid model; 8 of 4096 with their repeated K/V and
        expert-sorted copies would not fit beside a chip's weights and
        slabs), and the executables a server has to have compiled stay
        the power-of-two batches up to 8 x 2048, 4 x 4096, 2 x 8192 and
        1 x 16384 (the last two where a slab is that long). And a group
        of 3 (5, 6, 7) whose program would pass ``_ADMIT_FLOOR`` rows
        runs as 2 (4): ``_prefill_prompts`` pads a batch to a power of
        two, and past the floor the dead prompts' rows are paid for like
        live ones. So a batch of ``b`` holds ``b`` prompts (or lies
        under the floor): a backlog of passed-over requests never makes
        a wider program than as many arrivals at once would have, and a
        server asks for no shape that arrival order could not.

        The rest waits ONE decode step; it is not prefilled by a second
        program at once. One admission is one prefill program, one
        ``first_token`` wait and one ``scatter`` phase, in that order:
        a trace's readers give a ``jit_ptpu_prefill_*`` event the
        counts of the first ``scatter`` that opens after it started,
        and a decode step between two prefills is what the live
        sequences' token gap wants. Gang scheduling takes the queue's
        head as it stands."""
        n = min(self._admit_room(free), len(pending))
        if not self.continuous:
            return list(range(n))

        def bucket(i):
            return min(_pow2_bucket(len(pending[i][1]), floor=16), self.seq)

        def group(i):
            return max(bucket(i), self._ADMIT_FLOOR)

        take = [i for i in range(n) if group(i) == group(0)]

        def rows(k):  # of the program the first k of the group run in
            return _pow2_bucket(k) * max(bucket(i) for i in take[:k])

        k = len(take)
        while k > 1 and rows(k) > self._ADMIT_TOKENS:
            k -= 1
        if rows(k) > self._ADMIT_FLOOR:
            # past the floor a dead row of the power-of-two batch is
            # paid for like a live one: 3 (5, 6, 7) run as 2 (4)
            k = _pow2_bucket(k + 1) // 2
        return take[:k]

    def _admit(self, batch, caches, lens, active):
        """One admission: prefill ``batch``, the requests
        ``_admit_group`` picked of the queue, into free slots, in ONE
        program. Entries are (rid, prompt, max_new, seed); returns the
        updated caches (slab rows replaced via one scatter per tensor).
        With a prefix store attached, admission first hashes each prompt
        against it — hits seed from cached rows (full hit: no model
        call at all; partial hit: suffix-only extension through the
        verify window) and identical prompts inside one sub-batch
        dedupe to a single prefill row."""
        free = [i for i in range(self.slots) if active[i] is None]
        if self._prefix is not None:
            return self._admit_prefix(batch, free, caches, lens, active)
        n = len(batch)
        try:
            outs, sp, t_pf = self._prefill_prompts([b[1] for b in batch])
        except Exception as e:
            # an admission that cannot prefill (compile error, device
            # OOM) fails ITS requests and leaves the server serving —
            # the already-admitted slots and the queue are untouched
            for rid, _p, _mn, _seed in batch:
                self._fail(rid, e)
            return caches
        with _tracing.phase("decode.loop.first_token") as waited:
            # the host waits here for the prefill's logits
            first = np.array(self.predictor._sample_host(
                outs[0], self.strategy, self._seed_ctr))  # writable copy
            self._seed_ctr += 1
            # a request that carried its own seed gets ITS first token
            # from that seed (matching DecodePredictor.generate(...,
            # seed=s) for the first sample); later steps draw from the
            # server's stream — full per-request reproducibility under
            # continuous batching is a greedy/direct-predictor
            # property, not a server one
            for i, (_rid, _p, _mn, seed) in enumerate(batch):
                if seed is not None and self.strategy not in ("greedy",):
                    first[i] = self.predictor._sample_host(
                        outs[0][i:i + 1], self.strategy, seed)[0]
            # a model with a prediction layer hands back the first DRAFT
            # beside the first token: the entry after the cache entries
            drafts = (np.asarray(outs[1 + len(self._spec)])
                      if self.rounds else None)
        pf_ms = self._observe_prefill(t_pf, waited)
        if self._moe_layers:
            # the prefill has ended (the host has its logits): no wait
            self._note_load(outs[-1])
        with _tracing.phase("decode.loop.scatter",
                            **self._scatter_counts(
                                n, [b[1] for b in batch],
                                int(outs[0].shape[0]) * sp)):
            caches = self._scatter_prefill(
                caches, list(outs[1:1 + len(self._spec)]), free[:n], sp)
        for i, (rid, prompt, max_new, seed) in enumerate(batch):
            slot = free[i]
            tok = int(first[i])
            st = {"rid": rid, "generated": [tok], "max_new": max_new,
                  "cur": tok, "count": 1}
            if drafts is not None:
                st["draft"] = int(drafts[i])
            lens[slot] = len(prompt)
            active[slot] = st
            _tracing.rid_span(rid, "decode.admit", kind="fresh",
                              prompt_len=len(prompt),
                              prefill_ms=round(pf_ms, 3))
            obs.DECODE_REQUESTS.inc(kind="admitted")
            obs.DECODE_TOKENS.inc(kind="decode")
            if (self.eos_id is not None and tok == self.eos_id) \
                    or max_new <= 1:
                self._retire(st)
                active[slot] = None
                lens[slot] = 0
        return caches

    def _scatter_counts(self, n: int, prompts=(), bucket_rows=0) -> dict:
        """What an admission's ``decode.loop.scatter`` phase carries:
        ``entries``, the arrays it scatters into, and ``state_slots``,
        the slots whose fixed-size state it replaces whole (0 for a
        model of K/V rows alone). Of the admission's prefill, known
        once it has run (a phase's counts are fixed when it opens, so
        they ride here and not on ``admit``): ``ring_rows``, the rows
        it leaves in a sliding-window layer's rings (each prompt's last
        ``min(len, window)``), and ``expert_pairs``, the token-expert
        pairs it routed to held experts, all sparse layers. Of a model
        whose last layers own no cache entry, ``prompt_rows``, the real
        prompt rows the prefill walked, and ``tail_rows``, the rows
        those last layers ran on: one a prompt
        (``DecodeConfig.tail_start``). Of a model with latent layers,
        ``prompt_rows`` too and ``bucket_rows``, the rows of the
        prefill's bucket (power-of-two batch x sequence bucket): what
        its expanded attention and projections ran on, padding
        included; ``prompts``, how many it held, and ``attn_pairs``, the
        (query, key) pairs under the causal mask of their live rows
        (each prompt's ``len (len + 1) / 2``): what model FLOPs of a
        prefill are counted from. Of a model with KDA layers,
        ``kda_tokens`` and ``kda_pad_tokens``: the real rows each such
        layer's chunked scan walked, and the rows of the bucket beyond
        them (scanned too, and leaving every state alone), and the
        latent layers' four whatever full layer stands beside them. Of a model
        with state-space layers, ``ssm_tokens`` and ``ssm_pad_tokens``:
        the same two of each selective scan (the kernel skips the
        blocks of positions wholly past a row's length, the lax form
        walks them all). Of a model with layers under an indexer,
        ``index_pairs`` and ``chosen_pairs`` beside ``attn_pairs``: the
        (query, key) pairs one such layer's indexer scored under the
        causal mask, and those its attention kept (a query at position
        t keeps ``min(t + 1, index_topk)``). Of a model with latent
        layers over a window, ``window_pairs``: the pairs one such
        layer attends (``min(t + 1, window)`` a query). Of a model
        whose sliding and full layers differ in their key/value heads
        or value width (``DecodeConfig.uneven_kv``), ``prompt_rows``,
        ``bucket_rows``, ``prompts``, ``attn_pairs`` (one full layer's)
        and ``window_pairs`` (one sliding layer's). Of a model with
        EVA layers, ``eva_window_rows`` and ``eva_summary_rows``: the
        rows the admission leaves live in one such layer's entries (each
        prompt's ``len mod window`` rows of its block and ``window /
        eva_chunk`` summaries a window it closed), ``prompt_rows``,
        ``bucket_rows``, ``prompts`` and ``attn_pairs``, here the
        (query, key OR SUMMARY) pairs one such layer's prefill attends
        (``_eva_pairs``)."""
        counts = {"entries": len(self._spec),
                  "state_slots": n if self._state_bytes_per_slot else 0}

        def prefill_counts():
            """What a prefill's model FLOPs are counted from."""
            return dict(
                prompt_rows=sum(len(p) for p in prompts),
                bucket_rows=int(bucket_rows), prompts=len(prompts),
                attn_pairs=sum(len(p) * (len(p) + 1) // 2 for p in prompts))

        if self._eva:
            _, w, c = self._eva
            counts.update(
                eva_window_rows=sum(len(p) % w for p in prompts),
                eva_summary_rows=sum(w // c * (len(p) // w)
                                     for p in prompts),
                prompt_rows=sum(len(p) for p in prompts),
                bucket_rows=int(bucket_rows), prompts=len(prompts),
                attn_pairs=sum(_eva_pairs(len(p), w, w // c)
                               for p in prompts))
        if self._ring_window:
            counts["ring_rows"] = sum(
                min(len(p), self._ring_window) for p in prompts)
        if self._moe_layers:
            counts["expert_pairs"] = self._moe_last["expert_pairs"]
        if self._has_tail:
            counts["prompt_rows"] = sum(len(p) for p in prompts)
            counts["tail_rows"] = len(prompts)
        if self._latent_row_bytes:
            counts.update(prefill_counts())
        if self._index_topk:
            counts["index_pairs"] = counts["attn_pairs"]
            counts["chosen_pairs"] = _kept_pairs(prompts, self._index_topk)
        if self._ring_window and self._latent_row_bytes:
            counts["window_pairs"] = _kept_pairs(prompts, self._ring_window)
        if self._ring_window and self._uneven_kv:
            # sliding layers beside full ones, each kind its own heads
            counts.update(prefill_counts(), window_pairs=_kept_pairs(
                prompts, self._ring_window))
        if self._kda_state_bytes_per_slot:
            counts["kda_tokens"] = sum(len(p) for p in prompts)
            counts["kda_pad_tokens"] = (int(bucket_rows)
                                        - counts["kda_tokens"])
            # beside an attention layer too (no latent layer counts them)
            counts.update(prefill_counts())
        if self._ssm_layers:
            counts["ssm_tokens"] = sum(len(p) for p in prompts)
            counts["ssm_pad_tokens"] = (int(bucket_rows)
                                        - counts["ssm_tokens"])
        return counts

    def _note_load(self, load):
        """Book one program's ``moe_load`` (sparse layers, experts
        held): the counter and the gauge a layer, the server's running
        total, the flight recorder's ``moe.load`` record when it
        samples, and what the next ``dispatch`` / ``scatter`` phase
        reports."""
        load = np.asarray(load, np.int64).reshape(
            len(self._moe_layers), -1)
        if self._moe_elsewhere:
            for j, layer in enumerate(self._moe_layers):
                obs.MOE_TOKENS_ELSEWHERE.inc(int(load[j, -1]),
                                             layer=str(layer))
            load = load[:, :-1]
        self.moe_load_total += load
        for j, layer in enumerate(self._moe_layers):
            obs.MOE_EXPERT_PAIRS.inc(int(load[j].sum()), layer=str(layer))
            tot = self.moe_load_total[j]
            obs.MOE_LOAD_MAX_OVER_MEAN.set(
                float(tot.max()) / max(float(tot.mean()), 1e-9),
                layer=str(layer))
        self._moe_last = {"expert_pairs": int(load.sum()),
                          "experts_active": int((load > 0).sum())}
        if _tracing.sampled():
            _tracing.record_process_span(
                "moe.load", pairs=[int(v) for v in load.sum(axis=1)],
                busiest=[int(v) for v in load.max(axis=1)],
                **self._moe_last)

    def _scatter_prefill(self, caches, sub, slots, sp):
        """The cache rebuild of a plain admission: scatter the prefill's
        entries ``sub`` (``cache_spec`` order, ``n`` rows each) into
        ``slots``. An entry of rows per position takes its first ``sp``
        positions; rows past sp keep old garbage, masked by length. A
        fixed-size state is REPLACED WHOLE: no length hides a slot's
        last occupant. ONE jitted call for all entries, which donates
        the resident arrays and updates them in place (the int8 path
        below still rebuilds each array out of place, eagerly)."""
        n = len(slots)
        if self.kv_dtype != "int8":
            # all rows of the prefill's power-of-two batch are scattered,
            # its pad rows at an index past the last slot, where "drop"
            # leaves them out: the call's shapes follow the prefill
            # bucket alone, and an admission of 3 compiles nothing that
            # an admission of 4 has not
            idx = np.full((int(sub[0].shape[0]),), self.slots, np.int32)
            idx[:n] = slots
            scatter = _scatter_fn(
                tuple(e.per_position for e in self._spec),
                self.predictor._device.platform != "cpu")
            return list(scatter(list(caches), list(sub), idx, sp))
        slot_idx = jnp.asarray(np.array(slots, np.int32))
        # prefill emits float rows; quantize per (slot, position) at
        # scatter time — the same row-scale scheme the in-graph
        # cache_append_quant applies to decoded rows
        from ..ops.quant import quantize_kv_rows

        per = self._cache_per_layer
        caches = list(caches)
        for li in range(len(sub) // 2):
            for j in (0, 1):  # K then V
                rows = jnp.asarray(sub[2 * li + j])[:n]
                q, sc = quantize_kv_rows(rows)
                caches[per * li + j] = (
                    caches[per * li + j].at[slot_idx, :sp].set(q))
                caches[per * li + 2 + j] = (
                    caches[per * li + 2 + j].at[slot_idx, :sp].set(sc))
        return caches

    def _first_token(self, logits_row, seed):
        """First sampled token for one admitted sequence, honoring the
        per-request seed contract the plain admission path applies."""
        with _tracing.phase("decode.loop.first_token"):
            first = int(self.predictor._sample_host(
                logits_row.reshape(1, -1), self.strategy,
                self._seed_ctr)[0])
            self._seed_ctr += 1
            if seed is not None and self.strategy not in ("greedy",):
                first = int(self.predictor._sample_host(
                    logits_row.reshape(1, -1), self.strategy, seed)[0])
        return first

    def _activate(self, slot, rid, prompt, max_new, first, lens, active,
                  entry_id):
        """Mark one slot live after its rows are resident (common tail
        of every admission flavor)."""
        st = {"rid": rid, "generated": [first], "max_new": max_new,
              "cur": first, "count": 1, "prefix_entry": entry_id}
        if entry_id is not None:
            self._prefix.acquire(entry_id)
        lens[slot] = len(prompt)
        active[slot] = st
        obs.DECODE_REQUESTS.inc(kind="admitted")
        obs.DECODE_TOKENS.inc(kind="decode")
        if (self.eos_id is not None and first == self.eos_id) \
                or max_new <= 1:
            self._retire(st)
            active[slot] = None
            lens[slot] = 0

    def _admit_prefix(self, batch, free, caches, lens, active):
        """Prefix-aware admission: hash each prompt against the store;
        full hits admit with ZERO model calls, partial hits seed the
        cached header rows and extend only their suffix through the
        verify window, misses (deduped within the sub-batch) prefill
        once and populate the store. Any failure fails THIS batch and
        leaves the server serving."""
        from .prefix import prefix_hash

        n = len(batch)
        if n == 0:
            return caches
        plan: List[dict] = []
        uniq_prompts: List[np.ndarray] = []
        uniq_map: Dict[str, int] = {}
        for rid, prompt, _mn, _seed in batch:
            eid, L, rows, logits = self._prefix.lookup(prompt)
            if eid is not None and L == len(prompt):
                plan.append({"kind": "full", "eid": eid, "L": L,
                             "rows": rows, "logits": logits})
            elif eid is not None:
                plan.append({"kind": "partial", "eid": eid, "L": L,
                             "rows": rows})
            else:
                h = prefix_hash(prompt)
                if h in uniq_map:
                    obs.DECODE_PREFIX_HITS.inc(kind="batch")
                    plan.append({"kind": "dup", "uniq": uniq_map[h]})
                else:
                    uniq_map[h] = len(uniq_prompts)
                    uniq_prompts.append(prompt)
                    plan.append({"kind": "miss", "uniq": uniq_map[h]})
        try:
            # ONE prefill over the deduped misses
            uniq_rows: List[List[np.ndarray]] = []
            uniq_logits: List[np.ndarray] = []
            uniq_eids: List[Optional[int]] = []
            pf_ms = 0.0
            if uniq_prompts:
                outs, _sp, t_pf = self._prefill_prompts(uniq_prompts)
                with _tracing.phase("decode.loop.first_token") as waited:
                    # the host waits for the prefill: logits AND rows
                    sub = [np.asarray(c) for c in outs[1:]]
                    logits_all = np.asarray(outs[0])
                pf_ms = self._observe_prefill(t_pf, waited)
                for i, p in enumerate(uniq_prompts):
                    rows = [s[i, :len(p)] for s in sub]
                    uniq_rows.append(rows)
                    uniq_logits.append(logits_all[i])
                    uniq_eids.append(self._prefix.insert(
                        p, rows, logits_all[i]))
            # scatter every request's resident prefix rows in ONE pass
            # per cache tensor (a per-request scatter would copy the
            # whole slab once per request — the plain path pays one
            # copy per admission WAVE, and so must this one). Rows
            # shorter than the wave's max length zero-pad: the padded
            # positions sit beyond each slot's valid length, masked by
            # every read and overwritten by later appends.
            ext_jobs = []   # (idx-in-batch, slot, suffix, eid)
            seeds_rows = []  # (slot, rows, L) for the batched scatter
            for i, ((rid, prompt, max_new, seed), p) in enumerate(
                    zip(batch, plan)):
                slot = free[i]
                # prefix-aware admission span: the kind says whether
                # this sequence paid a prefill (miss/dup share the
                # deduped one) or rode cached rows (full/partial)
                _tracing.rid_span(
                    rid, "decode.admit", kind="prefix_" + p["kind"],
                    prompt_len=len(prompt),
                    prefill_ms=(round(pf_ms, 3)
                                if p["kind"] in ("miss", "dup") else 0.0))
                if p["kind"] in ("miss", "dup"):
                    rows = uniq_rows[p["uniq"]]
                    logits = uniq_logits[p["uniq"]]
                    eid = uniq_eids[p["uniq"]]
                    L = len(prompt)
                elif p["kind"] == "full":
                    rows, logits, eid, L = (p["rows"], p["logits"],
                                            p["eid"], p["L"])
                else:
                    rows, logits, eid, L = p["rows"], None, p["eid"], \
                        p["L"]
                seeds_rows.append((slot, rows, L))
                if p["kind"] == "partial":
                    lens[slot] = L  # extension advances it to len(prompt)
                    ext_jobs.append((i, slot, np.asarray(
                        prompt[L:], np.int64), eid))
                else:
                    first = self._first_token(logits, seed)
                    self._activate(slot, rid, prompt, max_new, first,
                                   lens, active, eid)
            caches = list(caches)
            lmax = max(L for _s, _r, L in seeds_rows)
            slot_idx = jnp.asarray(np.array(
                [s for s, _r, _l in seeds_rows], np.int32))
            with _tracing.phase("decode.loop.scatter"):
                for j in range(len(caches)):
                    stacked = np.zeros(
                        (len(seeds_rows), lmax)
                        + tuple(caches[j].shape[2:]), np.float32)
                    for i, (_s, rows, L) in enumerate(seeds_rows):
                        stacked[i, :L] = rows[j]
                    caches[j] = caches[j].at[slot_idx, :lmax].set(
                        jnp.asarray(stacked))
        except Exception as e:
            # pre-extension admission failed (prefill compile/run,
            # store insert, host scatter): fail THIS batch, free its
            # slots, release any refs it took; already-active slots
            # keep serving — everything up to here is host-side or a
            # non-donating scatter, so their resident rows are intact
            for (rid, _p, _mn, _seed), slot in zip(
                    batch, free[:len(batch)]):
                st = active[slot]
                if st is not None and st["rid"] == rid:
                    if self._prefix is not None:
                        self._prefix.release(st.get("prefix_entry"))
                    active[slot] = None
                self._fail(rid, e)
                lens[slot] = 0
            return caches
        if ext_jobs:
            try:
                caches = self._extend_suffixes(ext_jobs, batch, caches,
                                               lens, active)
            except Exception as e:
                # a failed verify call may have CONSUMED the fed slabs
                # under donation (device backends) — the pre-extension
                # cache list is not reusable, so this is the
                # step-failure contract, not the admission one: fail
                # the extension jobs AND every active sequence, hand
                # back fresh slabs. No ref release for the ext jobs
                # here: acquire happens only in _activate (after a
                # SUCCESSFUL extension) — releasing un-acquired refs
                # would steal another live holder's pin; jobs that DID
                # activate are in `active`, released by the line below
                for i, slot, _suf, _eid in ext_jobs:
                    self._fail(batch[i][0], e)
                    lens[slot] = 0
                caches = self._fail_all_active(active, lens, e)
        return caches

    def _extend_suffixes(self, ext_jobs, batch, caches, lens, active):
        """Drive partial-hit suffixes through the shared verify-window
        executable, chunk by chunk — multi-token cached prefill on the
        RESIDENT slab. Non-extending slots ride along untouched: their
        window rows land past their valid lengths (masked, then
        overwritten by their own later appends)."""
        cfg = self.predictor.config
        T = self._win
        vexe, _ = self.predictor.acquire("verify", self.slots, self.seq,
                                         window=T)
        remaining = {slot: suf for _i, slot, suf, _e in ext_jobs}
        offset = {slot: 0 for _i, slot, _s, _e in ext_jobs}
        final_logits: Dict[int, np.ndarray] = {}
        while remaining:
            tokens = np.zeros((self.slots, T), np.int64)
            positions = np.zeros((self.slots, T), np.int64)
            last_idx = np.zeros((self.slots,), np.int32)
            chunk_lens = {}
            for slot, suf in remaining.items():
                off = offset[slot]
                chunk = suf[off:off + T]
                cl = len(chunk)
                tokens[slot, :cl] = chunk
                positions[slot] = np.minimum(
                    lens[slot] + np.arange(T), cfg.max_len - 1)
                last_idx[slot] = cl - 1
                chunk_lens[slot] = cl
            feeds = {"tokens": tokens, "positions": positions,
                     "lengths": lens.copy(), "last_idx": last_idx}
            feeds.update(zip(self._cache_feed_names, caches))
            with _tracing.phase("decode.loop.dispatch") as ph:
                t0 = ph.t0 or time.perf_counter()
                vouts = vexe(feeds, self.predictor._state)
            obs.DECODE_STEP_MS.observe(
                ((ph.t1 or time.perf_counter()) - t0) * 1e3,
                stage="extend")
            with _tracing.phase("decode.loop.fetch"):
                last_logits = np.asarray(vouts[2])
            caches = list(vouts[3:])
            done = []
            for slot, cl in chunk_lens.items():
                lens[slot] += cl
                offset[slot] += cl
                obs.DECODE_TOKENS.inc(cl, kind="prefill")
                if offset[slot] >= len(remaining[slot]):
                    final_logits[slot] = last_logits[slot]
                    done.append(slot)
            for slot in done:
                del remaining[slot]
        for i, slot, _suf, eid in ext_jobs:
            rid, prompt, max_new, seed = batch[i]
            first = self._first_token(final_logits[slot], seed)
            self._activate(slot, rid, prompt, max_new, first, lens,
                           active, eid)
        return caches

    def _fresh_slabs(self):
        """Zeroed cache arrays in ``self._cache_feed_names`` order."""
        return [jnp.zeros(e.shape, e.dtype) for e in self._spec]

    def _fail_all_active(self, active, lens, exc, flights=()):
        """Shared step-failure recovery: a decode/draft/verify call
        that dies (device OOM, donated-buffer misuse, backend loss)
        must not kill the serving loop and strand every future — fail
        the ACTIVE sequences (their cache state is no longer
        trustworthy), release their prefix refs, free the slots, and
        hand back FRESH slabs (the failed call may have CONSUMED the
        fed ones under donation; lengths are all 0 now, so zeros are
        correct). ``flights``: the plain branch's steps lost with the
        call (None entries skipped); a sequence that had left its slot
        and waited only for its last token from one of them fails too.
        A failed sequence is marked ``done``: a step in flight that
        still lands delivers it nothing."""
        waiting = [st for f in flights if f is not None
                   for _i, st, last in f.rows if last]
        for st in [a for a in active if a is not None] + waiting:
            if st.get("done"):
                continue
            st["done"] = True
            if self._prefix is not None:
                self._prefix.release(st.get("prefix_entry"))
            self._fail(st["rid"], exc)
            obs.DECODE_REQUESTS.inc(kind="retired")
        active[:] = [None] * self.slots
        lens[:] = 0
        return self._fresh_slabs()

    def _step_counts(self, lens, n_active):
        """What a step's ``decode.loop.dispatch`` phase carries:
        ``active`` live slots; ``attended``, the K/V rows its attention
        reads: each live slot's length with the row this step appends
        (free slots hold length 0); and ``streamed``, the rows a layer's
        attention brings in for them: every slot's length with that row,
        rounded up to the decode kernel's block (a free slot still costs
        one block), or the whole slab where the step does not run the
        in-place kernel. ``attended / streamed`` is how much of what is
        fetched is live. ``state_bytes``: the bytes of fixed-size state
        (a state-space layer's window and recurrent state) the step
        reads and writes, every slot's, live or not. Of a model with
        sliding-window layers, ``ring_rows``: the rows one such layer's
        attention reads, each live slot's ``min(length + 1, window)``.
        Of a model with expert layers, ``expert_pairs`` and
        ``experts_active``: the token-expert pairs routed to held
        experts and the (layer, expert) that received any, all sparse
        layers, of the LAST step whose ``moe_load`` the host has read
        (with a step in flight, the one dispatched two before this:
        the loads ride back with the ids and are never waited for).
        Of a model with cross layers, ``slab_readers``: the layers that
        attend the ONE shared slab in this step (``attended`` and
        ``streamed`` count its rows once; its bytes are rows x
        readers). Of a model with latent layers, ``latent_rows``: the
        live latent rows the step's absorbed attention reads, one
        latent layer's (= ``attended``; every latent layer reads as
        many), and ``latent_row_bytes``, the bytes of one such row. Of a
        model with KDA layers, ``kda_state_bytes``: the bytes of the
        LIVE slots' matrix states, all such layers (a step reads and
        writes each once: ``2 x`` this is its state traffic). Of a
        model with layers under an indexer, one such layer's
        ``rows_live`` (= ``attended``), ``rows_scored``, the rows its
        indexer's product ran over and its attention streamed (=
        ``streamed``: the kernels read a slot's live blocks, the lax
        forms every row of every slot), and ``rows_chosen``, the rows its
        attention kept: each live slot's ``min(length + 1,
        index_topk)``. Of a model with EVA layers, ``eva_window_rows``
        and ``eva_summary_rows``: the rows one such layer's attention
        reads, each live slot's ``length mod window + 1`` of its block
        and ``window / eva_chunk`` summaries a closed window
        (``attended`` is their sum, and ``streamed`` the rows of the
        blocks that hold a slot's live range, every slot's, or every row
        of every entry on the lax path), and ``eva_chunks_closed``: the
        live slots whose position closes a chunk, which write its two
        summary rows."""
        rows = self._stream_rows
        if self._eva:
            return self._eva_step_counts(lens, n_active)
        streamed = (self.slots * self.seq if rows is None
                    else int((lens // rows + 1).sum()) * rows)
        counts = {"active": n_active,
                  "attended": int(lens.sum()) + n_active,
                  "streamed": streamed,
                  "state_bytes": 2 * self.slots * self._state_bytes_per_slot}
        if self._ring_window:
            counts["ring_rows"] = int(np.minimum(
                lens[lens > 0] + 1, self._ring_window).sum())
        if self._moe_layers:
            counts.update(self._moe_last)
        if self._slab_readers:
            counts["slab_readers"] = self._slab_readers
        if self._latent_row_bytes:
            counts["latent_rows"] = counts["attended"]
            counts["latent_row_bytes"] = self._latent_row_bytes
        if self._index_topk:
            counts["rows_live"] = counts["attended"]
            counts["rows_scored"] = streamed
            counts["rows_chosen"] = int(np.minimum(
                lens[lens > 0] + 1, self._index_topk).sum())
        if self._kda_state_bytes_per_slot:
            counts["kda_state_bytes"] = (
                n_active * self._kda_state_bytes_per_slot)
        return counts

    def _eva_step_counts(self, lens, n_active):
        """``_step_counts`` of a model of EVA layers: a slot at position
        p reads rows ``[n_sum - per (p // window), n_sum + p mod window]``
        of each layer's two entries."""
        n_sum, w, c = self._eva
        per, rows = w // c, self._stream_rows
        live = lens[lens > 0]
        window_rows = int((live % w + 1).sum())
        summary_rows = int((live // w).sum()) * per
        if rows is None:
            streamed = self.slots * (n_sum + w)
        else:
            first = np.maximum(n_sum - per * (lens // w), 0) // rows
            streamed = int(((n_sum + lens % w) // rows + 1 - first).sum()
                           ) * rows
        obs.EVA_ROWS.inc(window_rows, kind="window")
        obs.EVA_ROWS.inc(summary_rows, kind="summary")
        return {"active": n_active, "attended": window_rows + summary_rows,
                "streamed": streamed, "state_bytes": 0,
                "eva_window_rows": window_rows,
                "eva_summary_rows": summary_rows,
                "eva_chunks_closed": int((live % c == c - 1).sum())}

    def _spec_round(self, drexe, vexe, caches, lens, active, n_active):
        """One speculative round across every active slot: spec_k draft
        steps propose, ONE verify window call checks, each slot
        advances by its accept+1 tokens (capped by budget and slab
        room). Greedy-lossless: the emitted tokens are the target's own
        argmaxes, token-for-token what the plain loop would emit."""
        k = self.spec_k
        with _tracing.phase("decode.loop.feeds"):
            cur = np.zeros((self.slots,), np.int64)
            for i, st in enumerate(active):
                if st is not None:
                    cur[i] = st["cur"]
        try:
            # the spec_k draft steps, each a dispatch and a fetch
            with _tracing.phase("decode.loop.draft"):
                window, positions = self.predictor.draft_window(
                    drexe, caches, cur, lens, k)
            with _tracing.phase("decode.loop.feeds"):
                feeds = {"tokens": window, "positions": positions,
                         "lengths": lens.copy(),
                         "last_idx": np.zeros((self.slots,), np.int32)}
                feeds.update(zip(self._cache_feed_names, caches))
            with _tracing.phase("decode.loop.dispatch",
                                **self._step_counts(lens, n_active)) as ph:
                t0 = ph.t0 or time.perf_counter()
                vouts = vexe(feeds, self.predictor._state)
            with _tracing.phase("decode.loop.fetch") as ph:
                next_ids = np.asarray(vouts[0]).astype(np.int64)
                accept = np.asarray(vouts[1]).astype(np.int64)
            t1 = ph.t1 or time.perf_counter()
        except Exception as e:
            return self._fail_all_active(active, lens, e)
        obs.DECODE_STEP_MS.observe((t1 - t0) * 1e3, stage="verify")
        with _tracing.phase("decode.loop.retire"):
            return self._spec_commit(list(vouts[3:]), next_ids, accept,
                                     lens, active, self.spec_k)

    def _spec_commit(self, caches, next_ids, accept, lens, active,
                     proposed):
        """The bookkeeping half of the OPT self-draft's round: each
        slot takes its accepted tokens (``next_ids[i, :accept[i] + 1]``,
        of ``proposed`` drafted ones), finished ones retire.
        ``step_active_counts`` takes the tokens the round delivered, as
        ``_deliver`` gives it a step's. Returns ``caches``."""
        n_active = sum(1 for a in active if a is not None)
        obs.DECODE_SPEC_PROPOSED.inc(proposed * n_active)
        emitted = 0
        traced = _tracing.bound()
        for i, st in enumerate(active):
            if st is None:
                continue
            n, stopped = self._take_accepted(
                st, next_ids[i], int(accept[i]), proposed,
                self.seq - int(lens[i]), traced)
            emitted += n
            lens[i] += n
            if stopped or st["count"] >= st["max_new"] \
                    or lens[i] + 1 >= self.seq:
                self._retire(st)
                active[i] = None
                lens[i] = 0
        self.step_active_counts.append(emitted)
        obs.DECODE_TOKENS.inc(emitted, kind="decode")
        return caches

    def _take_accepted(self, st, next_row, accept, proposed, room, traced):
        """One sequence's share of a speculative round, the OPT
        self-draft's and a prediction layer's alike: its accepted tokens
        (``_accepted_tokens``: capped by its budget and by ``room``, the
        slab's: window position j needs rows length..length + j
        resident, so at most ``seq - length`` tokens) join what it has
        generated. Returns (how many, stopped at eos)."""
        if traced:
            _tracing.rid_span(st["rid"], "decode.spec_round",
                              accepted=accept, proposed=proposed)
        toks, stopped = _accepted_tokens(
            next_row, accept, min(st["max_new"] - st["count"], room),
            self.eos_id)
        st["generated"].extend(toks)
        st["count"] += len(toks)
        if toks:
            st["cur"] = toks[-1]
        return len(toks), stopped

    def _dispatch(self, dexe, chain, caches, lens, active, n_active,
                  flight) -> _Flight:
        """Dispatch one decode step for every live slot and book what
        the host knows of it without its ids: lengths advance by one,
        and a sequence that reaches its budget or the slab's end with
        this token leaves its slot NOW (free for the next admission),
        to be resolved when the ids land. ``flight`` is the step before
        it, still unread: a slot that continues from it takes its token
        from that step's ids on the device (``_chain_fn``), any other
        the host's (an admission's first token; 0 for a free slot)."""
        with _tracing.phase("decode.loop.feeds"):
            chained = self._chained(flight, active)
            first = np.zeros((self.slots,), np.int64)
            for i, st in enumerate(active):
                if st is not None and i not in chained:
                    first[i] = st["cur"]
            if chained:
                fresh = np.ones((self.slots,), np.bool_)
                fresh[list(chained)] = False
                tokens = chain(fresh, first, flight.outs[0])
            else:
                tokens = first.reshape(self.slots, 1)
            feeds = {"tokens": tokens, "lengths": lens.copy(),
                     "seed": np.array([self._seed_ctr], np.int64)}
            if self.predictor.config.positions:
                feeds["positions"] = lens.reshape(
                    self.slots, 1).astype(np.int64)
            self._seed_ctr += 1
            feeds.update(zip(self._cache_feed_names, caches))
        outs, t0 = self._launch(dexe, feeds, flight,
                                self._step_counts(lens, n_active))
        return _Flight(outs, self._flight_rows(lens, active, chained), t0)

    @staticmethod
    def _chained(flight, active):
        """The slots that continue from ``flight``, the step or round
        still unread: live in it, and still the same sequence's."""
        if flight is None:
            return ()
        return {i for i, st, _last in flight.rows if active[i] is st}

    def _launch(self, exe, feeds, flight, counts):
        """The ``dispatch`` phase of a step or a round: the call, and
        its ids on their way to the host as soon as it ends, not when
        the host comes to ask (an iteration later); the experts' loads
        ride with them. Returns (outputs, when the dispatch began)."""
        in_flight = int(flight is not None)
        with _tracing.phase("decode.loop.dispatch", in_flight=in_flight,
                            **counts) as ph:
            t0 = ph.t0 or time.perf_counter()
            outs = exe(feeds, self.predictor._state)
            outs[0].copy_to_host_async()
            if self._moe_layers:
                outs[-1].copy_to_host_async()
        obs.DECODE_STEPS.inc(in_flight=str(in_flight))
        return outs, t0

    def _flight_rows(self, lens, active, chained):
        """What the host books of a step or a round at its dispatch,
        without its ids: every live slot's length advances by one (a
        round's LEAST advance: what it took beyond that is added when
        its ids are read), and a sequence that reaches its budget or
        the slab's end with one more token (beside the one of the
        unread flight it is ``chained`` to) leaves its slot NOW.
        Returns the flight's (slot, sequence, last)."""
        rows = []
        for i, st in enumerate(active):
            if st is None:
                continue
            lens[i] += 1
            last = (st["count"] + (i in chained) + 1 >= st["max_new"]
                    or lens[i] + 1 >= self.seq)
            rows.append((i, st, last))
            if last:
                active[i] = None
                lens[i] = 0
        return rows

    def _dispatch_round(self, rexe, chain, caches, lens, active, n_active,
                        flight) -> _Flight:
        """Dispatch one ROUND of a model with a prediction layer, every
        live slot at once: the model on a slot's current token and its
        draft (two positions, its length and the next), the accept, and
        the prediction layer behind them, in ONE executable whose
        ``ids`` are [a_1, a_2, accept, next draft]. ``flight`` is the
        round before it, still unread: how far that round took a slot
        the host does not know yet, so a slot that continues from it is
        advanced on the device (``_round_chain_fn``: its token, draft,
        length and budget from that round's ids), any other takes the
        host's (an admission's first token and draft; zeros for a free
        slot). The host books the round at its least advance, one token
        a slot (``_flight_rows``): the counts on ``dispatch`` are at
        most one row a slot short of an accepted draft, and a slot ends
        here only where one token more ends it; what a round really
        took, an ``eos`` and a budget met by an accepted draft are
        learnt when it is read, a round late (``_deliver_round``), and
        the device has by then run that slot as a free one.
        Greedy-lossless: what a slot commits are the model's own
        argmaxes."""
        with _tracing.phase("decode.loop.feeds"):
            chained = self._chained(flight, active)
            fresh = np.ones((self.slots,), np.bool_)
            tokens = np.zeros((self.slots, 2), np.int64)
            remaining = np.zeros((self.slots,), np.int32)
            for i, st in enumerate(active):
                if st is None:
                    continue
                if i in chained:
                    fresh[i] = False
                else:
                    tokens[i] = st["cur"], st["draft"]
                    remaining[i] = st["max_new"] - st["count"]
                    st["len"] = int(lens[i])  # the host's exact one
            if flight is not None:
                prev = flight.outs[0], flight.state
            else:  # every slot is fresh: nothing is advanced from these
                prev = (np.zeros((self.slots, 4), np.int64),
                        np.zeros((self.slots, 2), np.int32))
            tokens, lengths, state = chain(fresh, tokens, lens.copy(),
                                           remaining, *prev)
            feeds = {"tokens": tokens, "lengths": lengths}
            feeds.update(zip(self._cache_feed_names, caches))
        outs, t0 = self._launch(
            rexe, feeds, flight, dict(self._step_counts(lens, n_active),
                                      round_positions=2 * n_active))
        return _Flight(outs, self._flight_rows(lens, active, chained), t0,
                       state)

    def _fetch(self, flight: _Flight, t_token: float):
        """(ids, now): read a dispatched step's ids; the host waits here
        for the step, beside whatever was dispatched behind it. The
        histogram's ``stage="step"`` takes the time this token took
        once the one before it had arrived (``t_token``), or since the
        step's own dispatch where nothing was in flight: one
        observation a step, and their sum the time the loop had a step
        outstanding (a traced iteration hands over its own clock
        readings)."""
        with _tracing.phase("decode.loop.fetch") as ph:
            ids = np.asarray(flight.outs[0])
            if self._moe_layers:
                self._note_load(flight.outs[-1])
        now = ph.t1 or time.perf_counter()
        obs.DECODE_STEP_MS.observe(
            (now - max(flight.t0, t_token)) * 1e3,
            stage="round" if self.rounds else "step")
        return ids, now

    def _deliver(self, flight: _Flight, ids, lens, active):
        """Hand a landed step's tokens to their sequences and retire
        the finished. An ``eos_id`` hit is learnt here, one step late:
        the slot has run (or is running) one more step, whose token is
        delivered to nobody and counted nowhere."""
        with _tracing.phase("decode.loop.retire"):
            delivered = 0
            for i, st, last in flight.rows:
                if st.get("done"):
                    continue
                tok = int(ids[i])
                st["generated"].append(tok)
                st["count"] += 1
                delivered += 1
                if last or (self.eos_id is not None
                            and tok == self.eos_id):
                    st["done"] = True
                    self._retire(st)
                    if active[i] is st:
                        active[i] = None
                        lens[i] = 0
            self.step_active_counts.append(delivered)
            obs.DECODE_TOKENS.inc(delivered, kind="decode")
            # refresh occupancy AFTER retirements: an idle server must
            # scrape as 0 active, not as its pre-retirement count (the
            # next iteration may park on the channel before updating)
            self._set_slot_gauges(
                sum(1 for a in active if a is not None))

    def _deliver_round(self, flight: _Flight, ids, lens, active):
        """Hand a landed ROUND's tokens to their sequences, one round
        late, and retire the finished: each takes what
        ``_accepted_tokens`` gives it of [a_1, a_2] (``_round_chain_fn``
        has advanced the slot by as much on the device), and the host's
        length gains what the round took beyond the one token its
        dispatch booked. A sequence that ended in a round read before
        this one (its budget or the slab's end met by an accepted
        draft, an eos) ran here as a free slot: nothing is delivered
        and nothing counted. The round's ``round_committed`` and its
        ``decode.spec_round`` spans (one a live slot) are booked HERE,
        once."""
        with _tracing.phase("decode.loop.retire") as ph:
            delivered = proposed = 0
            traced = _tracing.bound()
            for i, st, last in flight.rows:
                if st.get("done"):
                    continue
                proposed += 1
                n, stopped = self._take_accepted(
                    st, ids[i], int(ids[i, 2]), 1, self.seq - st["len"],
                    traced)
                st["len"] += n
                delivered += n
                held = active[i] is st
                if held:
                    lens[i] += n - 1
                if last or stopped or st["count"] >= st["max_new"] \
                        or st["len"] + 1 >= self.seq:
                    st["done"] = True
                    self._retire(st)
                    if held:
                        active[i] = None
                        lens[i] = 0
            obs.DECODE_SPEC_PROPOSED.inc(proposed)
            self.step_active_counts.append(delivered)
            obs.DECODE_TOKENS.inc(delivered, kind="decode")
            ph.note(round_committed=delivered)
            self._set_slot_gauges(
                sum(1 for a in active if a is not None))

    def _loop(self):
        caches = self._fresh_slabs()
        lens = np.zeros((self.slots,), np.int32)
        active: List[Optional[dict]] = [None] * self.slots
        pending: List[tuple] = []
        # a server over a prediction layer runs its loop on ROUNDS as
        # any other runs it on steps: a round is a step whose advance is
        # data. Its entries follow ids and two logits where a step's
        # follow ids and one
        if self.rounds:
            dexe, _ = self.predictor.acquire("round", self.slots, self.seq)
            chain = _round_chain_fn(self.slots, self.seq, self.eos_id,
                                    self.predictor._device)
            dispatch, deliver, at = (self._dispatch_round,
                                     self._deliver_round, 3)
        else:
            dexe, _ = self.predictor.acquire("decode", self.slots,
                                             self.seq, self.strategy,
                                             kv_dtype=self.kv_dtype)
            dispatch, deliver, at = self._dispatch, self._deliver, 2
            if not self.speculative:
                chain = _chain_fn(self.slots, self.predictor._device)
        if self.speculative:
            drexe, _ = self.predictor.acquire("draft", self.slots,
                                              self.seq)
            vexe, _ = self.predictor.acquire("verify", self.slots,
                                             self.seq, window=self._win)
        # the step (or round) in flight (dispatched, its ids not yet
        # read), and when the last token reached the host
        flight: Optional[_Flight] = None
        t_token = 0.0
        closed = False
        while True:
            # one iteration = one record of the flight recorder's
            # process ring when the sample rate asks for it; every
            # boundary below is a phase of it (docs/performance.md),
            # and at rate 0 each is the shared no-op
            with _tracing.phase("decode.loop.iter"):
                n_active = sum(1 for a in active if a is not None)
                free = self.slots - n_active
                batch = []
                # what an admission passed over waits in ``pending``
                # and counts against the free slots: queue and channel
                # together never hold more than the slots can take
                room = free - len(pending)
                drain = not closed and room > 0 and (
                    self.continuous or n_active == 0)
                if not closed and n_active == 0 and not pending \
                        and flight is None:
                    # idle: park on the channel until work (or close)
                    with _tracing.phase("decode.loop.park"):
                        batch = self._chan.recv_batch(self.slots, None)
                    drain = False
                with _tracing.phase("decode.loop.recv"):
                    if drain:
                        # mid-flight admission: non-blocking drain,
                        # bounded by the free slots (leaving the rest
                        # in the channel keeps submit()'s backpressure
                        # intact)
                        batch = self._chan.recv_batch(room, 0)
                    if batch is None:
                        closed = True
                        batch = []
                    for msg in batch:
                        try:
                            rid, prompt, max_new, seed = \
                                self._decode_request(msg)
                            if len(prompt) + max_new > self.seq:
                                raise ValueError(
                                    "prompt %d + max_new %d exceeds the "
                                    "server's %d-token slab"
                                    % (len(prompt), max_new, self.seq))
                            if len(prompt) < 1:
                                raise ValueError("empty prompt")
                            pending.append((rid, prompt, max_new, seed))
                        except Exception as e:
                            try:
                                self._fail(_rio.frame_tag(bytes(msg)), e)
                            except Exception:
                                pass
                admit_ok = (free > 0 and pending
                            and (self.continuous or n_active == 0))
                if admit_ok:
                    take = self._admit_group(free, pending)
                    group = [pending[i] for i in take]
                    # had a free slot in this admission's room, and wait
                    deferred = min(self._admit_room(free),
                                   len(pending)) - len(take)
                    for i in reversed(take):
                        del pending[i]
                    if deferred:
                        obs.DECODE_ADMIT_DEFERRED.inc(deferred)
                    with _tracing.phase("decode.loop.admit",
                                        admitted=len(group),
                                        deferred=deferred):
                        caches = self._admit(group, caches, lens, active)
                    n_active = sum(1 for a in active if a is not None)
                self._set_slot_gauges(n_active)
                if self.speculative and n_active:
                    caches = self._spec_round(drexe, vexe, caches, lens,
                                              active, n_active)
                    self._set_slot_gauges(
                        sum(1 for a in active if a is not None))
                    continue
                # one token (one round) across every active slot,
                # dispatched BEFORE the host reads the step in flight:
                # all it needs of that step (the ids, the cache entries;
                # of a round the lengths and budgets too) is on the
                # device, and the host's part of an iteration runs
                # beside the device's. With nothing live there is no
                # step to put behind the one in flight, and it is read
                # before the loop parks or returns
                step = ids = None
                try:
                    if n_active:
                        step = dispatch(dexe, chain, caches, lens, active,
                                        n_active, flight)
                        caches = list(step.outs[at:at + len(self._spec)])
                    if flight is not None:
                        ids, t_token = self._fetch(flight, t_token)
                except Exception as e:
                    # a decode step that dies (device OOM, donated-
                    # buffer misuse, backend loss), at its dispatch or
                    # when its ids are read, must not kill the serving
                    # loop and strand every future: the steps in flight
                    # are lost together. Fail the ACTIVE sequences and
                    # those waiting for their last token (their cache
                    # state is no longer trustworthy), free the slots,
                    # keep serving the queue
                    caches = self._fail_all_active(active, lens, e,
                                                   (flight, step))
                    self._set_slot_gauges(0)
                    flight = None
                    continue
                if flight is not None:
                    deliver(flight, ids, lens, active)
                flight = step
                if n_active == 0 and closed and not pending:
                    return
