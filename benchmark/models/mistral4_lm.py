"""Builder for Mistral's Mistral-Small-4 decoder LM (`model_type:
mistral4`: every layer multi-head latent attention and then routed
experts with a shared one, under a softmax router; an untied head)
through the public `models` / `serving` API: the `DecodeConfig` that
describes its layers, the parameter set `save_decode_model` exports,
and the rule the seeded weights follow. Serving only (the repo builds
no training graph with rotary positions or routed experts). Found by
the name in a configuration file (`"builder"`).

The configuration file keeps the source's keys; `n_routed_experts`
there is the count of routed experts HELD by this chip (`experts_held`
= [lo, hi) of the `n_routed_experts_scored` the router scores), as the
`model-configs` guide has a chip's share written. What the source's
keys leave open is read from the file's `assumed`, one field each, and
a value no graph builds is refused here."""
from __future__ import annotations

import math
import re

import numpy as np

from .laguna_lm import _ByColumn, router_spread


def depth(cfg: dict, kind: str) -> int:
    return int(cfg["num_hidden_layers"])


def softmax_scale(cfg: dict) -> float:
    """The scale `a` of the attention scores, by `assumed.softmax_scale`:
    "yarn_mscale_all_dim": qk_head_dim^-0.5 x (0.1 mscale_all_dim
    ln(factor) + 1)^2 (DeepSeek-V3's rule for these keys,
    `ops/mla.py: softmax_scale`); "plain": qk_head_dim^-0.5."""
    from paddle_tpu.ops.mla import softmax_scale as rule

    kind = cfg["assumed"]["softmax_scale"]
    if kind == "plain":
        return rule(cfg["qk_head_dim"])
    if kind != "yarn_mscale_all_dim":
        raise ValueError("assumed.softmax_scale %r is not built" % (kind,))
    rp = cfg["rope_parameters"]
    return rule(cfg["qk_head_dim"], rp["factor"], rp["mscale_all_dim"])


def rope_of(cfg: dict) -> dict:
    """`DecodeConfig.rope` from the source's `rope_parameters`: YaRN
    over the rope part of a head, cos and sin times mscale /
    mscale_all_dim, pairs (2i, 2i+1) under `rope_interleave`, and the
    query scale by `assumed.query_scale`."""
    rp = cfg["rope_parameters"]
    assert rp["rope_type"] == "yarn", rp["rope_type"]

    def mscale(m):
        return 0.1 * float(m) * math.log(float(rp["factor"])) + 1.0 if m \
            else 1.0

    rot = {"theta": float(rp["rope_theta"]),
           "attention_factor": mscale(rp["mscale"]) / mscale(
               rp["mscale_all_dim"]),
           "interleave": bool(cfg["rope_interleave"]),
           "yarn": {"factor": float(rp["factor"]),
                    "original_max_position": int(
                        rp["original_max_position_embeddings"]),
                    "beta_fast": float(rp["beta_fast"]),
                    "beta_slow": float(rp["beta_slow"])}}
    rule = cfg["assumed"]["query_scale"]
    if rule == "llama4":
        rot["scale_beta"] = float(rp["llama_4_scaling_beta"])
    elif rule is not None:
        raise ValueError("assumed.query_scale %r is not built" % (rule,))
    return {"latent": rot}


def decode_config(cfg: dict, kind: str):
    from paddle_tpu.serving import DecodeConfig

    assert cfg["model_type"] == "mistral4" and not cfg["attention_bias"]
    assert not cfg["mlp_bias"] and not cfg["tie_word_embeddings"]
    assert cfg["hidden_act"] == "silu" and cfg["norm_topk_prob"]
    assert cfg["n_group"] == cfg["topk_group"] == 1
    assert cfg["first_k_dense_replace"] == 0
    assert (cfg["qk_head_dim"]
            == cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])
    n = depth(cfg, kind)
    lo, hi = cfg["experts_held"]
    assert hi - lo == cfg["n_routed_experts"] <= cfg["n_routed_experts_scored"]
    return DecodeConfig(
        cfg["vocab_size"], n_layer=n, n_head=cfg["num_attention_heads"],
        d_model=cfg["hidden_size"], d_inner=cfg["intermediate_size"],
        max_len=int(cfg["serve"]["max_seq"]), tie_embeddings=False,
        layer_types=["latent"] * n, ffn_types=["experts"] * n,
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        softmax_scale=softmax_scale(cfg), rope=rope_of(cfg),
        n_expert=cfg["n_routed_experts_scored"],
        expert_top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"],
        d_shared_expert=cfg["n_shared_experts"]
        * cfg["moe_intermediate_size"],
        experts_held=[lo, hi], router_score=cfg["assumed"]["router_score"],
        router_scale=cfg["routed_scaling_factor"],
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


def init_rule(name: str, shape):
    """(mean, std) of a parameter's seeded values. Laguna's rule, for
    Laguna's reason (`models/laguna_lm.py`, `init_rule`): matrices, the
    table and the head N(0, 0.02); norm gains (the two inside a latent
    layer too) N(1, 0.1); the router's columns N(0, 0.02 u_e) with u_e
    log-normal(0, 0.5), so that loads are uneven; the routed experts'
    down projections N(0, 0.002), so that one flipped pair at a near-tie
    of the 4th and 5th of 128 scores, which no two correct computations
    agree on, moves the logits by less than the base reading fluctuates
    (`benchmark/configs/mistral-small-4.json`, check.serve.why has the
    first reading)."""
    if re.search(r"norm\w*\.w$", name):
        return 1.0, 0.1
    if name.endswith(".experts.down.w"):
        return 0.0, 0.002
    if name.endswith(".router.w"):
        return 0.0, (0.02 * router_spread(shape[-1])).view(_ByColumn)
    return 0.0, 0.02
