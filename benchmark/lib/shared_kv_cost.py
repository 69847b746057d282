"""Bytes a decode step of a Phi-4-mini-flash-family configuration has to
move (a decoder that keeps ONE layer's K/V and lets every later cross
layer read it), from the configuration's keys alone: what
`decode_step_roofline_shared.serve` divides by the HBM peak, and the
pieces of HLO text `shared_kv_time_pct.serve` tells the shared slab's
events by. Kept with the benchmark, apart from the program
(`paddle_tpu` computes none of this)."""
from __future__ import annotations

ITEM = 4  # float32 weights, slab, rings and states


def depth(cfg: dict) -> int:
    return int(cfg["num_hidden_layers"])


def layer_kinds(cfg: dict):
    """The mixer of each layer run, by the rule the configuration
    assumes (`assumed_sizes.layer_rule`; the builder's own statement of
    it, plain Python)."""
    from benchmark.models import phi4flash_lm

    return phi4flash_lm.layer_kinds(depth(cfg))


def full_layer(cfg: dict) -> int:
    """Index of the one layer that owns the shared slab."""
    return layer_kinds(cfg).index("attention")


def slab_readers(cfg: dict) -> int:
    """Layers that attend the shared slab in a step."""
    return 1 + layer_kinds(cfg).count("cross")


def kv_row_bytes(cfg: dict) -> int:
    """Bytes of K and V of ONE position of ONE layer: every key/value
    head (10,240 B at 20 heads of 64 in float32)."""
    dh = cfg["hidden_size"] // cfg["num_attention_heads"]
    return 2 * cfg["num_key_value_heads"] * dh * ITEM


def ring_row_bytes(cfg: dict) -> int:
    """The same for one row of EVERY sliding layer's ring (the step's
    `ring_rows` counts one layer's rows)."""
    return layer_kinds(cfg).count("sliding") * kv_row_bytes(cfg)


def layer_params(cfg: dict) -> dict:
    """Parameters of one layer of each kind, with its two LayerNorms
    and its MLP."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // h
    di = cfg["mamba_expand"] * d
    n, r, k = cfg["mamba_d_state"], cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    bias = 1 if cfg["assumed_sizes"]["attention_bias"] else 0
    mlp = 3 * d * f + 4 * d
    diff = 4 * dh + 2 * dh  # four lambda vectors and the heads' gain
    q_o = 2 * d * h * dh + bias * (h * dh + d)
    k_v = 2 * (d * hkv * dh + bias * hkv * dh)
    mamba = (d * 2 * di + di * k + di + di * (r + 2 * n) + r * di + di
             + di * n + di + di * d)
    return {"mamba": mamba + mlp, "sliding": q_o + k_v + diff + mlp,
            "attention": q_o + k_v + diff + mlp,
            "gmu": 2 * d * di + mlp, "cross": q_o + diff + mlp}


def decode_weight_params(cfg: dict) -> int:
    """Parameters one decode step HAS to read: every layer's, the final
    norm's and the tied table's (once: the head multiplies by all of
    it; the embedding's gathered rows are part of it)."""
    per = layer_params(cfg)
    d = cfg["hidden_size"]
    return (sum(per[k] for k in layer_kinds(cfg)) + 2 * d
            + cfg["vocab_size"] * d)


def step_bytes(cfg: dict, step: dict) -> float:
    """Bytes the step whose `decode.loop.dispatch` phase carries `step`
    HAS to move: the weights once, the fixed-size states in and out
    (`state_bytes`), the LIVE rows of the shared slab once a reader
    (`attended` x `slab_readers`), the live rows of every ring. Not what
    a path happens to read (a kernel's last block of a slot is part
    dead, `streamed`; a lax path reads whole slabs): that is what the
    share falls short by."""
    return (ITEM * decode_weight_params(cfg) + float(step["state_bytes"])
            + float(step["attended"]) * kv_row_bytes(cfg)
            * float(step["slab_readers"])
            + float(step["ring_rows"]) * ring_row_bytes(cfg))


def patterns(cfg: dict):
    """Pieces of HLO text by which a device event of a DECODE STEP is
    told to read or write the shared slab (an XLA fusion carries no
    scope in its name on the chip, only its operands' shapes and the
    names of the feeds it reads): the slab's two feeds by name, and its
    shape (a position's row is kept flat: 20 heads of 64 = 1,280)."""
    i = full_layer(cfg)
    slots, seq = cfg["serve"]["slots"], cfg["serve"]["max_seq"]
    dh = cfg["hidden_size"] // cfg["num_attention_heads"]
    return ["kcache_%d" % i, "vcache_%d" % i,
            "f32[%d,%d,%d]" % (slots, seq, cfg["num_key_value_heads"] * dh)]
