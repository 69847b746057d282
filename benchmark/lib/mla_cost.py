"""Operations and bytes of what a latent-attention (MLA) configuration
adds, from the configuration's keys alone: the latent row a position
keeps, the absorbed attention a decode step must do over the live rows,
the weights a step has to stream, and the model FLOPs of a prefill's
live rows. What `mla_decode_roofline.serve`,
`decode_step_roofline_mla.serve` and `prefill_mfu_pct_mla.serve` divide
by the peaks. Kept with the benchmark, apart from the program
(`paddle_tpu` computes none of this)."""
from __future__ import annotations

ITEM = 4  # float32 weights and slabs


def depth(cfg: dict) -> int:
    return int(cfg["num_hidden_layers"])


def latent_row(cfg: dict) -> int:
    """Floats one position keeps a layer: [c_kv ; k_r]."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def row_bytes(cfg: dict) -> int:
    """Bytes of one position's latent rows, every layer's (5,120 at a
    row of 320 floats and 4 layers)."""
    return depth(cfg) * latent_row(cfg) * ITEM


def absorbed_flops_per_row(cfg: dict) -> int:
    """FLOPs the ABSORBED form must do for one attended row, every
    layer and head: the score over the row's rank + rope floats and the
    weighted sum of its rank floats, a multiply and an add each (147,456
    at 4 layers x 32 heads x (320 + 256))."""
    return (depth(cfg) * cfg["num_attention_heads"]
            * (latent_row(cfg) + cfg["kv_lora_rank"]) * 2)


def attention_params(cfg: dict) -> int:
    """One layer's attention: W_qa, W_qb, W_kva, W_kvb, W_o and the two
    gains inside it."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return (d * rq + rq * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv)
            + h * dv * d + rq + r)


def expert_params(cfg: dict) -> int:
    """ONE expert (routed or shared): gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_params(cfg: dict) -> int:
    """What a decode step reads whatever it routes: every layer's
    attention, router, shared expert(s) and two gains, the final gain
    and the head's own matrix (the table's gathered rows are not
    counted): 282.1 M = 1.13 GB at 4 layers and 16,384 ids."""
    d = cfg["hidden_size"]
    layer = (attention_params(cfg) + d * cfg["n_routed_experts_scored"]
             + cfg["n_shared_experts"] * expert_params(cfg) + 2 * d)
    return depth(cfg) * layer + d + d * cfg["vocab_size"]


def step_bytes(cfg: dict, experts_active: float, latent_rows: float):
    """Bytes one decode step HAS to read: the dense weights, the held
    (layer, expert) that received a pair, once each, and the live
    latent rows of every layer."""
    return (ITEM * (dense_params(cfg)
                    + experts_active * expert_params(cfg))
            + latent_rows * row_bytes(cfg))


def prefill_flops(cfg: dict, prompt_rows: float, expert_pairs: float,
                  attn_pairs: float, prompts: float) -> float:
    """Model FLOPs of a prefill's LIVE rows, a multiply and an add each:
    the projections, the router and the shared expert(s) of every live
    row, every layer (2 x 53.7 M a row a layer); the held (token,
    expert) pairs the program counted; causal attention over the
    (query, key) pairs of the live rows, counted once (not the whole
    square), score and weighted sum, every head and layer; the head on
    one row a prompt. Not the bucket's padding."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    per_row = (attention_params(cfg) + d * cfg["n_routed_experts_scored"]
               + cfg["n_shared_experts"] * expert_params(cfg))
    qk, v = cfg["qk_head_dim"], cfg["v_head_dim"]
    return (2.0 * depth(cfg) * per_row * prompt_rows
            + 2.0 * expert_params(cfg) * expert_pairs
            + 2.0 * depth(cfg) * h * (qk + v) * attn_pairs
            + 2.0 * d * cfg["vocab_size"] * prompts)


def patterns(cfg: dict) -> dict:
    """Pieces of HLO text by which a device event is told to touch a
    latent slab (an XLA fusion carries no scope in its name on the chip,
    only its operands' shapes and the names of the parameters it reads):
    "slab": the slab by its feed's name or its shape at the cell's
    (slots, seq); "rows": a prefill's latent rows by their width
    (`f32[B,T,320]`, any B and T: matched by the trailing `,320]`);
    "expand": W_kvb by its parameter's name."""
    slots, seq = cfg["serve"]["slots"], cfg["serve"]["max_seq"]
    return {"slab": ["latent_", "f32[%d,%d,%d]" % (slots, seq,
                                                    latent_row(cfg))],
            "rows": [",%d]" % latent_row(cfg)],
            "expand": ["_attention_kv_b_w"]}
