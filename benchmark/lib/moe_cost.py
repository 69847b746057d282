"""Operations and bytes of what a Laguna-family configuration adds, from
the configuration's keys alone: the routed experts' product, a ring
read, a full layer's K/V read, and the weights one decode step has to
stream. What `moe_experts_roofline.serve` and
`decode_step_roofline_moe.serve` divide by the peaks. Kept with the
benchmark, apart from the program (`paddle_tpu` computes none of
this)."""
from __future__ import annotations

ITEM = 4  # float32 weights, slabs and rings


def depth(cfg: dict) -> int:
    layers = cfg["num_hidden_layers"]
    return int(layers["serve"] if isinstance(layers, dict) else layers)


def sparse_layers(cfg: dict):
    return [i for i in range(depth(cfg))
            if cfg["mlp_layer_types"][i] == "sparse"]


def layer_count(cfg: dict, kind: str) -> int:
    """Layers of `kind` ("full_attention" | "sliding_attention")."""
    return cfg["layer_types"][:depth(cfg)].count(kind)


def expert_params(cfg: dict) -> int:
    """Parameters of ONE routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def pair_flops(cfg: dict) -> int:
    """FLOPs of one (token, expert) pair: three products of
    hidden_size x moe_intermediate_size, a multiply and an add each
    (6.29 MFLOP at 2048 x 512)."""
    return 2 * expert_params(cfg)


def routed_product(cfg: dict, pairs: float, experts_active: float,
                   tokens: float):
    """(flops, bytes) the routed product of the sparse layers of one
    program HAS to do: `pairs` token-expert pairs on held experts and
    the weights of the `experts_active` (layer, expert) that received
    any, once each; the tokens' activations in and out a sparse layer.
    Not what a form of the product happens to read (the dense form
    reads every held expert, the 64-fold FLOPs of its mask are not
    counted)."""
    d = cfg["hidden_size"]
    acts = 2 * tokens * d * ITEM * len(sparse_layers(cfg))
    return (pairs * pair_flops(cfg),
            experts_active * expert_params(cfg) * ITEM + acts)


def kv_row_bytes(cfg: dict) -> int:
    """Bytes of K and V one attended position costs a step, over the
    full-attention layers (the slab keeps the key/value heads alone)."""
    return (layer_count(cfg, "full_attention") * 2
            * cfg["num_key_value_heads"] * cfg["head_dim"] * ITEM)


def ring_row_bytes(cfg: dict) -> int:
    """The same for one row of every sliding layer's ring."""
    return (layer_count(cfg, "sliding_attention") * 2
            * cfg["num_key_value_heads"] * cfg["head_dim"] * ITEM)


def decode_weight_params(cfg: dict, experts_active=None) -> float:
    """Parameters one decode step HAS to read: every layer's attention
    (q, k, v, o, the per-head gate), its two gains, the dense layers'
    MLP, the sparse layers' router and shared expert, the final gain
    and the head's own matrix (the embedding's 64 gathered rows are not
    counted), and of the held experts the `experts_active` (layer,
    expert) that received a pair, all sparse layers together: the count
    `routed_product` takes, so the step's and the kernel's rooflines
    agree on it (in the cell a step routes ~128 held pairs a layer to
    ~27 of 64 held experts: the router's columns are uneven). None:
    every held expert, the most a step can have to read."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    hkv = cfg["num_key_value_heads"]
    gate = 1 if cfg["model"]["attention_gate"] == "per_head" else 0
    total = d + cfg["vocab_size"] * d
    for i in range(depth(cfg)):
        h = cfg["num_attention_heads_per_layer"][i]
        total += 2 * d * h * dh + 2 * d * hkv * dh + gate * d * h + 2 * d
        if cfg["mlp_layer_types"][i] == "sparse":
            total += (d * cfg["num_experts_routed"]
                      + 3 * d * cfg["shared_expert_intermediate_size"])
        else:
            total += 3 * d * cfg["intermediate_size"]
    if experts_active is None:
        experts_active = cfg["num_experts"] * len(sparse_layers(cfg))
    return total + experts_active * expert_params(cfg)


def patterns(cfg: dict) -> dict:
    """Pieces of HLO text by which a device event is told to belong to
    a mechanism (an XLA fusion carries no scope in its name on the chip,
    only its operands' shapes and the names of the parameters it reads:
    `f32[64,2048,512]{...} %state__lm_l1_moe_experts_gate_w__`):
    "experts": the held experts' weights by shape; "moe": those, and
    anything read from a `.moe.` parameter (router, shared expert);
    "cache": a slab or a ring by its feed's name or its shape."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    eh = cfg["num_experts"]
    slots, seq = cfg["serve"]["slots"], cfg["serve"]["max_seq"]
    hkv, dh = cfg["num_key_value_heads"], cfg["head_dim"]
    experts = ["f32[%d,%d,%d]" % (eh, d, f), "f32[%d,%d,%d]" % (eh, f, d)]
    return {
        "experts": experts,
        "moe": experts + ["_moe_"],
        "cache": ["kcache_", "vcache_", "kring_", "vring_",
                  "f32[%d,%d,%d,%d]" % (slots, seq, hkv, dh),
                  "f32[%d,%d,%d,%d]" % (slots, cfg["sliding_window"], hkv,
                                        dh)],
    }
