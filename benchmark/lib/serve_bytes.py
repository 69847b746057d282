"""Bytes a decode step of a Jamba-family configuration has to move, from
the configuration's keys alone: what `decode_step_roofline.serve`
divides by the HBM peak. Kept with the benchmark, apart from the
program (`paddle_tpu` computes none of this)."""
from __future__ import annotations


def layer_kinds(cfg: dict, n_layer: int):
    return ["attention" if i % cfg["attn_layer_period"]
            == cfg["attn_layer_offset"] else "mamba"
            for i in range(n_layer)]


def decode_weight_params(cfg: dict, n_layer: int) -> int:
    """Parameters one decode step reads: every layer's, the final norm's
    and the tied table's (once: the head multiplies by all of it; the
    embedding's gathered rows are part of it)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    di = cfg["mamba_expand"] * d
    n, r, k = cfg["mamba_d_state"], cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // h
    mlp = 3 * d * f + 2 * d  # gate, up, down and the layer's two gains
    mamba = (d * 2 * di + di * k + di + di * (r + 2 * n) + r + 2 * n
             + r * di + di + di * n + di + di * d)
    attn = 2 * d * h * dh + 2 * d * hkv * dh
    kinds = layer_kinds(cfg, n_layer)
    return (kinds.count("mamba") * (mamba + mlp)
            + kinds.count("attention") * (attn + mlp)
            + d + cfg["vocab_size"] * d)


def kv_row_bytes(cfg: dict, n_layer: int, itemsize: int = 4) -> int:
    """Bytes of K and V one attended position costs a step, over the
    attention layers: the slab keeps the key/value heads alone."""
    dh = cfg["hidden_size"] // cfg["num_attention_heads"]
    return (layer_kinds(cfg, n_layer).count("attention") * 2
            * cfg["num_key_value_heads"] * dh * itemsize)
