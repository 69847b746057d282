"""Builder for Xiaomi's MiMo-V2-Flash decoder LM (`model_type:
mimo_v2_flash`: five sliding layers of 128 positions to one full layer,
8 key/value heads on a sliding layer and 4 on a full one, query and key
heads of 192 channels over value heads of 128, a learned sink a head in
the sliding layers' softmax, a value scale, no gate; a leading dense
MLP, then routed experts under a sigmoid router with a selection bias
and NO shared expert; an untied head) through the public `models` /
`serving` API: the `DecodeConfig` that describes its layers, the
parameter set `save_decode_model` exports, and the rule the seeded
weights follow. Serving only. Found by the name in a configuration file
(`"builder"`).

The configuration file keeps the source's keys; `n_routed_experts` there
is the count of routed experts HELD by this chip (`experts_held` = [lo,
hi) of the `n_routed_experts_scored` the router scores), as the
`model-configs` guide has a chip's share written."""
from __future__ import annotations

import math
import re

import numpy as np

from .laguna_lm import _ByColumn, router_spread

# the readings the source's keys leave open, each one field of the
# configuration's `assumed`: any other value is refused here, since no
# graph of the program computes it
ASSUMED = {"rope_layout": "half_split",
           "window_counts_self": True,
           "value_scale_on": "v"}


def depth(cfg: dict, kind: str) -> int:
    return int(cfg["num_hidden_layers"])


def layer_kinds(cfg: dict):
    """"full" | "sliding" layer by layer (`hybrid_layer_pattern`: 0 is
    full, 1 sliding)."""
    return ["sliding" if p else "full"
            for p in cfg["hybrid_layer_pattern"][:depth(cfg, "serve")]]


def rotary_dim(cfg: dict) -> int:
    """`int(partial_rotary_factor x head_dim)`: 64 of 192."""
    return int(cfg["partial_rotary_factor"] * cfg["head_dim"])


def decode_config(cfg: dict, kind: str):
    from paddle_tpu.serving import DecodeConfig

    assert cfg["model_type"] == "mimo_v2_flash" and not cfg["attention_bias"]
    assert not cfg["tie_word_embeddings"] and cfg["hidden_act"] == "silu"
    assert cfg["scoring_func"] == "sigmoid" and cfg["norm_topk_prob"]
    assert cfg["topk_method"] == "noaux_tc"
    assert cfg["n_group"] == 1 and cfg["topk_group"] == 1
    assert not cfg["n_shared_experts"]
    assert not cfg["add_full_attention_sink_bias"]
    assert cfg["sliding_window"] == cfg["sliding_window_size"]
    # one head geometry but for the key/value head count
    assert cfg["swa_num_attention_heads"] == cfg["num_attention_heads"]
    assert cfg["swa_head_dim"] == cfg["head_dim"]
    assert cfg["swa_v_head_dim"] == cfg["v_head_dim"]
    for field, built in ASSUMED.items():
        if cfg["assumed"][field] != built:
            raise ValueError(
                "assumed.%s = %r: the program builds %r alone"
                % (field, cfg["assumed"][field], built))
    n = depth(cfg, kind)
    lo, hi = cfg["experts_held"]
    assert hi - lo == cfg["n_routed_experts"] <= cfg["n_routed_experts_scored"]
    r = rotary_dim(cfg)
    return DecodeConfig(
        cfg["vocab_size"], n_layer=n, n_head=cfg["num_attention_heads"],
        d_model=cfg["hidden_size"], d_inner=cfg["intermediate_size"],
        max_len=int(cfg["serve"]["max_seq"]), tie_embeddings=False,
        n_kv_head=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        n_kv_head_by_kind={"full": cfg["num_key_value_heads"],
                           "sliding": cfg["swa_num_key_value_heads"]},
        v_head_dim=cfg["v_head_dim"],
        attn_value_scale=float(cfg["attention_value_scale"]),
        attn_sink=(["sliding"] if cfg["add_swa_attention_sink_bias"]
                   else None),
        attn_types=layer_kinds(cfg), window=cfg["sliding_window"],
        ffn_types=["experts" if f else "dense"
                   for f in cfg["moe_layer_freq"][:n]],
        n_expert=cfg["n_routed_experts_scored"],
        expert_top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"], d_shared_expert=0,
        experts_held=[lo, hi], router_score="sigmoid", router_bias=True,
        router_scale=float(cfg["routed_scaling_factor"] or 1.0),
        rope={"full": {"rotary_dim": r, "theta": float(cfg["rope_theta"])},
              "sliding": {"rotary_dim": r,
                          "theta": float(cfg["swa_rope_theta"])}},
        norm="rms_norm", norm_eps=cfg["layernorm_epsilon"],
        ffn="gated_silu", positions=False, biases=False)


def parameter_specs(cfg: dict, kind: str):
    """[(name, shape, dtype)] of the model's parameters, from a prefill
    Program that is built and never run."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.models import jamba

    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        with fluid.unique_name.guard():
            tokens = layers.data(name="tokens", shape=[1, 16], dtype="int64",
                                 append_batch_size=False)
            lengths = layers.data(name="lengths", shape=[1], dtype="int32",
                                  append_batch_size=False)
            jamba.hybrid_lm_prefill(tokens, lengths,
                                    decode_config(cfg, kind))
    return [(p.name, tuple(p.shape), np.float32)
            for p in main_p.all_parameters()]


def init_rule(name: str, shape):
    """(mean, std) of a parameter's seeded values. Laguna's rule, for
    Laguna's reason (`models/laguna_lm.py: init_rule`): matrices, the
    table and the head N(0, 0.02); norm gains N(1, 0.1); the router's
    columns N(0, 0.02 u_e) with u_e log-normal(0, 0.5), so that loads
    are uneven; the routed experts' down projections N(0, 0.002), so
    that one flipped pair at a near-tie of the top-8 moves the logits by
    less than the base reading fluctuates; the selection bias N(0, 0.01)
    (Ling's and dots3's). This model's own: **the sinks N(log 128, 1)**.
    With matrices at 0.02 a row's scores lie near 0, a full window's
    denominator is ~128, and a sink near 0 would take 1 / 129 of the
    mass and be invisible to any limit; at log 128 it takes about half,
    so a sink left out doubles a sliding layer's output."""
    if re.search(r"norm\w*\.w$", name):
        return 1.0, 0.1
    if name.endswith(".sink"):
        return math.log(128.0), 1.0
    if name.endswith(".experts.down.w"):
        return 0.0, 0.002
    if name.endswith(".router.w"):
        return 0.0, (0.02 * router_spread(shape[-1])).view(_ByColumn)
    if name.endswith(".router.bias"):
        return 0.0, 0.01
    return 0.0, 0.02
