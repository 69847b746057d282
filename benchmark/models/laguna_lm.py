"""Builder for poolside's Laguna decoder LM (`model_type: laguna`: full
and sliding-window attention layers mixed, query heads by layer, rotary
positions by layer kind, a per-head output gate, a leading dense MLP
and then routed experts with a shared one, an untied head) through the
public `models` / `serving` API: the `DecodeConfig` that describes its
layers, the parameter set `save_decode_model` exports, and the rule the
seeded weights follow. Serving only (the repo builds no training graph
with rotary positions or routed experts). Found by the name in a
configuration file (`"builder"`).

The configuration file keeps the source's keys; `num_experts` there is
the count of routed experts HELD by this chip (`experts_held` = [lo,
hi) of the `num_experts_routed` the router scores), as the
`model-configs` guide has a chip's share written."""
from __future__ import annotations

import re

import numpy as np


def depth(cfg: dict, kind: str) -> int:
    return int(cfg["num_hidden_layers"])


def rope_of(cfg: dict) -> dict:
    """`DecodeConfig.rope` from the source's `rope_parameters`."""
    out = {}
    for kind, key in (("full", "full_attention"),
                      ("sliding", "sliding_attention")):
        p = cfg["rope_parameters"][key]
        rot = {"rotary_dim": int(round(cfg["head_dim"]
                                       * p["partial_rotary_factor"])),
               "theta": float(p["rope_theta"])}
        if p["rope_type"] == "yarn":
            rot["attention_factor"] = float(p["attention_factor"])
            rot["yarn"] = {
                "factor": float(p["factor"]),
                "original_max_position": int(
                    p["original_max_position_embeddings"]),
                "beta_fast": float(p["beta_fast"]),
                "beta_slow": float(p["beta_slow"])}
        elif p["rope_type"] != "default":
            raise ValueError("rope_type %r" % p["rope_type"])
        out[kind] = rot
    return out


def decode_config(cfg: dict, kind: str):
    from paddle_tpu.serving import DecodeConfig

    assert cfg["model_type"] == "laguna" and not cfg["attention_bias"]
    assert not cfg["tie_word_embeddings"]
    assert not cfg["moe_apply_router_weight_on_input"]
    n = depth(cfg, kind)
    lo, hi = cfg["experts_held"]
    assert hi - lo == cfg["num_experts"] <= cfg["num_experts_routed"]
    model = cfg["model"]  # the two readings the source leaves open
    return DecodeConfig(
        cfg["vocab_size"], n_layer=n, n_head=cfg["num_attention_heads"],
        d_model=cfg["hidden_size"], d_inner=cfg["intermediate_size"],
        max_len=int(cfg["serve"]["max_seq"]), tie_embeddings=False,
        n_kv_head=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        n_head_by_layer=cfg["num_attention_heads_per_layer"][:n],
        attn_types=[{"full_attention": "full",
                     "sliding_attention": "sliding"}[t]
                    for t in cfg["layer_types"][:n]],
        window=cfg["sliding_window"],
        ffn_types=[{"dense": "dense", "sparse": "experts"}[t]
                   for t in cfg["mlp_layer_types"][:n]],
        n_expert=cfg["num_experts_routed"],
        expert_top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"],
        d_shared_expert=cfg["shared_expert_intermediate_size"],
        experts_held=[lo, hi], router_score=model["router_score"],
        router_scale=cfg["moe_routed_scaling_factor"],
        attn_gate=model["attention_gate"], rope=rope_of(cfg),
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


class _ByColumn(np.ndarray):
    """A std that differs by column. `lib/weights.seeded_weights` asks
    `std == 0.0` of what it takes for a scalar: the answer is no."""

    def __eq__(self, other):
        return False

    __hash__ = None


def router_spread(n_expert: int) -> np.ndarray:
    """u_e of the router's columns: log-normal(0, 0.5), the same for
    every seed and layer (the SEED draws the columns; their spread is
    part of the rule), so that some experts are chosen several times as
    often as others."""
    return np.random.default_rng(20260928).lognormal(
        0.0, 0.5, n_expert).astype(np.float32)


def init_rule(name: str, shape):
    """(mean, std) of a parameter's seeded values; either may be an
    array that broadcasts. Matrices, the table, the head and the gate
    N(0, 0.02); norm gains N(1, 0.1); the router's columns N(0, 0.02
    u_e) with u_e log-normal(0, 0.5): uneven loads, so that dropped or
    mis-weighted pairs show in the logits; the routed experts' down
    projections N(0, 0.002).

    Why the routed experts speak a tenth as loud (my chip runs, PR 31,
    10 seeds): the top-8 of 256 scores is a discontinuous choice, and
    the 8th and 9th scores of some token lie closer than the rounding
    that separates any two correct float32-on-TPU computations of the
    router's input (a flip in 1.7% of (token, layer): 9 of 20 probe
    prompts had one among their 9 compared rows). With every matrix at
    0.02 one flipped pair moved a prompt's logits_rel_l2 from 0.005 to
    0.017-0.040, above what the bf16-storage control reads (0.022), so
    no limit separated the two. At 0.002 a flip moves it by what the
    base reading fluctuates: the price is that ONE dropped or
    mis-weighted pair does not show either. The program against a
    reference without all routed experts (0.016-0.018), without the
    pairs past a capacity (0.015), the renormalisation or the shared
    expert still fails the limit of 0.0095, by the runner's own
    comparison (tools/variants_serveany.py;
    benchmark/configs/laguna-xs.2.json, check.serve.why)."""
    if re.search(r"norm\w*\.w$", name):
        return 1.0, 0.1
    if name.endswith(".experts.down.w"):
        return 0.0, 0.002
    if name.endswith(".router.w"):
        return 0.0, (0.02 * router_spread(shape[-1])).view(_ByColumn)
    return 0.0, 0.02
