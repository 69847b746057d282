"""Builder for dots-studio's dots3-note-prev language model
(`model_type: dots3_note`: multi-head latent attention under a learned
indexer in the full layers, latent attention of another geometry over a
window of 513 in three layers of four, a head-wise output gate on both,
a leading dense MLP and then routed experts with a shared one under a
sigmoid router with a selection bias; an untied head) through the public
`models` / `serving` API: the `DecodeConfig` that describes its layers,
the parameter set `save_decode_model` exports, and the rule the seeded
weights follow. Serving only. Found by the name in a configuration file
(`"builder"`).

The configuration file keeps the source's keys; `n_routed_experts` there
is the count of routed experts HELD by this chip (`experts_held` = [lo,
hi) of the `n_routed_experts_scored` the router scores), as the
`model-configs` guide has a chip's share written. What the source's keys
leave open is read from the file's `assumed`, one field each, and a
value no graph builds is refused here."""
from __future__ import annotations

import re

import numpy as np

from .laguna_lm import _ByColumn, router_spread

# the ONE value of each ASSUMED convention that the graphs build
ASSUMED = {"mla_qkv_lora_rescale": "sqrt_hidden_over_rank",
           "attention_gate": "per_head",
           "index_rope": "first_half_split",
           "sliding_window": "includes_query"}
_KINDS = {"full_attention": "latent_dsa", "sliding_attention": "latent_ring"}


def depth(cfg: dict, kind: str) -> int:
    return int(cfg["num_hidden_layers"])


def layer_types(cfg: dict, n: int):
    return [_KINDS[t] for t in cfg["layer_types"][:n]]


def decode_config(cfg: dict, kind: str):
    from paddle_tpu.serving import DecodeConfig

    n = depth(cfg, kind)
    assert cfg["model_type"] == "dots3_note"
    for key, only in ASSUMED.items():
        if cfg["assumed"].get(key) != only:
            raise ValueError("assumed.%s = %r: only %r is built"
                             % (key, cfg["assumed"].get(key), only))
    assert cfg["apply_mla_qkv_lora_rescale"]
    assert (cfg["attention_gate_type"] == cfg["swa_attention_gate_type"]
            == "headwise")
    assert not (cfg["attention_bias"] or cfg["tie_word_embeddings"])
    assert cfg["hidden_act"] == "silu" and cfg["norm_topk_prob"]
    assert cfg["scoring_func"] == "sigmoid"
    assert cfg["topk_method"] == "noaux_tc" and cfg["rope_scaling"] is None
    assert cfg["moe_layer_freq"] == 1
    assert cfg["num_key_value_heads"] == cfg["num_attention_heads"]
    assert cfg["swa_num_key_value_heads"] == cfg["swa_num_attention_heads"]
    lo, hi = cfg["experts_held"]
    assert hi - lo == cfg["n_routed_experts"] <= cfg["n_routed_experts_scored"]
    dense = int(cfg["first_k_dense_replace"])
    return DecodeConfig(
        cfg["vocab_size"], n_layer=n, n_head=cfg["num_attention_heads"],
        d_model=cfg["hidden_size"], d_inner=cfg["intermediate_size"],
        max_len=int(cfg["serve"]["max_seq"]), tie_embeddings=False,
        layer_types=layer_types(cfg, n),
        ffn_types=["dense" if i < dense else "experts" for i in range(n)],
        attn_gate="per_head", latent_rescale=True,
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        latent_ring={"n_head": cfg["swa_num_attention_heads"],
                     "q_lora_rank": cfg["swa_q_lora_rank"],
                     "kv_lora_rank": cfg["swa_kv_lora_rank"],
                     "qk_nope_dim": cfg["swa_qk_nope_head_dim"],
                     "qk_rope_dim": cfg["swa_qk_rope_head_dim"],
                     "v_head_dim": cfg["swa_v_head_dim"]},
        window=cfg["sliding_window_size"],
        index_heads=cfg["index_n_heads"],
        index_head_dim=cfg["index_head_dim"], index_topk=cfg["index_topk"],
        rope={"latent": {"theta": float(cfg["rope_theta"]),
                         "interleave": True},
              "latent_ring": {"theta": float(cfg["swa_rope_theta"]),
                              "interleave": True},
              "index": {"theta": float(cfg["rope_theta"]),
                        "rotary_dim": cfg["qk_rope_head_dim"]}},
        n_expert=cfg["n_routed_experts_scored"],
        expert_top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"],
        d_shared_expert=cfg["n_shared_experts"]
        * cfg["moe_intermediate_size"],
        experts_held=[lo, hi], router_score="sigmoid",
        router_scale=cfg["routed_scaling_factor"], router_bias=True,
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


# std of the query up-projections W_qb and of the indexer's W_Iq and
# W_Ik: the other matrices' 0.02 times this. The rescale of the latents
# multiplies a full layer's scores by (5120 / 1024)^1/2 (5120 / 512)^1/2
# = 7: at 1 their standard deviation is near 2 over thousands of keys,
# a query's attention rests on a handful of rows whose order bfloat16
# operands can swap, and the base reading swings with the seed (0.019-
# 0.070 on the chip; at 2.5 0.11-0.17, the reference itself 0.38 from
# its own float32 run). At 0.5 the reading is 0.010 on every seed and
# prompt, and WHICH 2,048 rows a query attends still moves the logits
# by their own norm (`all_rows` 0.64 / 1.04; PERF.md, PR 44)
SHARP = 0.5


def init_rule(name: str, shape):
    """(mean, std) of a parameter's seeded values. Laguna's rule, for
    Laguna's reason (`models/laguna_lm.py`, `init_rule`): matrices, the
    table and the head N(0, 0.02); norm gains N(1, 0.1) (a LayerNorm's
    bias N(0, 0.02) as any matrix); the router's columns N(0, 0.02 u_e)
    with u_e log-normal(0, 0.5), so that loads are uneven; the routed
    experts' down projections N(0, 0.002), so that one flipped pair at a
    near-tie moves the logits by less than the base reading fluctuates;
    the router's selection bias N(0, 0.01) (Ling's). This model's own:
    the query up-projections and the indexer's query and key projections
    N(0, 0.02 SHARP), so that the rescaled latents leave the attention
    neither flat nor resting on near-ties (`SHARP`; `check.serve.why`)."""
    if re.search(r"norm\w*\.w$", name):
        return 1.0, 0.1
    if name.endswith(".experts.down.w"):
        return 0.0, 0.002
    if name.endswith(".router.w"):
        return 0.0, (0.02 * router_spread(shape[-1])).view(_ByColumn)
    if name.endswith(".router.bias"):
        return 0.0, 0.01
    if name.endswith((".q_b.w", ".index.q.w", ".index.k.w")):
        return 0.0, 0.02 * SHARP
    return 0.0, 0.02
