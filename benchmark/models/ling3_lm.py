"""Builder for inclusionAI's Ling-3.0-flash decoder LM (`model_type:
bailing_hybrid`: five Kimi-Delta-Attention layers to one multi-head
latent attention layer, two leading dense MLPs and then routed experts
with a shared one under a sigmoid router with a selection bias and
group-limited choice; an untied head) through the public `models` /
`serving` API: the `DecodeConfig` that describes its layers, the
parameter set `save_decode_model` exports, and the rule the seeded
weights follow. Serving only (the repo builds no training graph for the
delta rule, rotary positions or routed experts). Found by the name in a
configuration file (`"builder"`).

The configuration file keeps the source's keys; `num_experts` there is
the count of routed experts HELD by this chip (`experts_held` = [lo, hi)
of the `num_experts_scored` the router scores), as the `model-configs`
guide has a chip's share written. What the source's keys leave open is
read from the file's `assumed`, one field each, and a value no graph
builds is refused here."""
from __future__ import annotations

import re

import numpy as np

from .laguna_lm import _ByColumn, router_spread


def depth(cfg: dict, kind: str) -> int:
    return int(cfg["num_hidden_layers"])


def layer_types(cfg: dict, n: int):
    """Layer i is latent attention iff (i + 1) % layer_group_size == 0,
    else KDA."""
    return ["latent" if (i + 1) % int(cfg["layer_group_size"]) == 0
            else "kda" for i in range(n)]


def decode_config(cfg: dict, kind: str):
    from paddle_tpu.serving import DecodeConfig

    n = depth(cfg, kind)
    assumed = cfg["assumed"]
    assert cfg["model_type"] == "bailing_hybrid"
    assert not (cfg["use_bias"] or cfg["use_qkv_bias"]
                or cfg["tie_word_embeddings"])
    assert cfg["hidden_act"] == "silu" and cfg["norm_topk_prob"]
    assert cfg["score_function"] == cfg["scoring_func"] == "sigmoid"
    assert cfg["topk_method"] == "noaux_tc"
    assert cfg["moe_router_enable_expert_bias"]
    assert not cfg["scale_router_input"] and cfg["rope_scaling"] is None
    assert cfg["q_lora_rank"] is None and cfg["rope_interleave"]
    assert cfg["rotary_dim"] == cfg["qk_rope_head_dim"]
    assert (cfg["qk_head_dim"]
            == cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])
    assert cfg["use_qk_norm"] and not cfg["value_norm"]
    assert cfg["linear_silu"] and cfg["group_norm_size"] == 1
    assert cfg["no_kda_lora"] and not cfg["use_kda_lora"]
    assert cfg["kda_safe_gate"] and not (cfg["use_nGPT"]
                                         or cfg["up_proj_norm"]
                                         or cfg["use_mla_nope"])
    assert (cfg["gated_attention_proj_granularity_type"] == "head_wise"
            and assumed["output_gate"] == "per_head")
    assert assumed["kda_decay_rank"] == "full"
    assert assumed["qk_norm_scope"] == "kda_l2"
    assert assumed["group_score"] == "top2_sum"
    # a clamp on the gated product is not built: refuse one, never
    # ignore it (the published limits are 0 for layers 0-33)
    for key in ("expert_swiglu_limit_list",
                "share_expert_swiglu_limit_list"):
        if any(cfg[key][:n]):
            raise ValueError(
                "%s names a non-zero limit among layers 0-%d (%r): the "
                "clamped SwiGLU is not built"
                % (key, n - 1, cfg[key][:n]))
    lo, hi = cfg["experts_held"]
    assert hi - lo == cfg["num_experts"] <= cfg["num_experts_scored"]
    dense = int(cfg["first_k_dense_replace"])
    return DecodeConfig(
        cfg["vocab_size"], n_layer=n, n_head=cfg["num_attention_heads"],
        d_model=cfg["hidden_size"], d_inner=cfg["intermediate_size"],
        max_len=int(cfg["serve"]["max_seq"]), tie_embeddings=False,
        layer_types=layer_types(cfg, n),
        ffn_types=["dense" if i < dense else "experts" for i in range(n)],
        kda_heads=cfg["num_attention_heads"], kda_head_dim=cfg["head_dim"],
        kda_conv=cfg["short_conv_kernel_size"],
        kda_gate=assumed["kda_gate"],
        kda_gate_bound=float(cfg["kda_lower_bound"]),
        attn_gate="per_head",
        q_lora_rank=0, kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        rope={"latent": {"theta": float(cfg["rope_theta"]),
                         "interleave": True}},
        n_expert=cfg["num_experts_scored"],
        expert_top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"],
        d_shared_expert=cfg["num_shared_experts"]
        * cfg["moe_shared_expert_intermediate_size"],
        experts_held=[lo, hi], router_score="sigmoid",
        router_scale=cfg["routed_scaling_factor"], router_bias=True,
        router_groups=cfg["n_group"], router_topk_groups=cfg["topk_group"],
        norm="rms_norm", norm_eps=cfg["rms_norm_eps"], ffn="gated_silu",
        positions=False, biases=False)


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


# dt_bias ~ N(-4.6, 1.3), A_log ~ N(0, 0.3): with u W_f ~ N(0, 1) (a
# normalised input through a matrix of N(0, 0.02) over 2560) a token's
# log-decay -5 sigmoid(exp(A_log)(u W_f + dt_bias)) gives decays exp(g)
# of 0.32 (2.5th percentile), 0.82 (25th), 0.946 (median), 0.989 (75th)
# and 0.9998 (97.5th; 1e6 draws from the rule): a state that forgets in
# a few tokens on some channels and remembers some thousands on others
_DT_BIAS = (-4.6, 1.3)
_A_LOG = (0.0, 0.3)


def init_rule(name: str, shape):
    """(mean, std) of a parameter's seeded values. Laguna's rule, for
    Laguna's reason (`models/laguna_lm.py`, `init_rule`): matrices, the
    table and the head N(0, 0.02); norm gains (the one on `c_kv` and a
    KDA head's output norm too) N(1, 0.1); the router's columns N(0,
    0.02 u_e) with u_e log-normal(0, 0.5), so that loads are uneven; the
    routed experts' down projections N(0, 0.002), so that one flipped
    pair (or one flipped GROUP, up to eight pairs) at a near-tie moves
    the logits by less than the base reading fluctuates. This model's
    own: the router's selection bias N(0, 0.01), enough to change
    choices (the scores' gaps at the 8th of 256 are of that size), and
    the decay gate's `dt_bias` and `A_log` spread as above."""
    if re.search(r"norm\w*\.w$", name):
        return 1.0, 0.1
    if name.endswith(".experts.down.w"):
        return 0.0, 0.002
    if name.endswith(".router.w"):
        return 0.0, (0.02 * router_spread(shape[-1])).view(_ByColumn)
    if name.endswith(".router.bias"):
        return 0.0, 0.01
    if name.endswith(".dt_bias"):
        return _DT_BIAS
    if name.endswith(".A_log"):
        return _A_LOG
    return 0.0, 0.02
