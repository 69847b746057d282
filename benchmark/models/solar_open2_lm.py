"""Builder for Upstage's Solar-Open2-250B decoder LM (`model_type:
solar_open2`: three Kimi-Delta-Attention layers under Kimi Linear's
UNBOUNDED decay gate and a write strength in (0, 2) to one gated
softmax layer of 64 query heads on 8 key/value heads, no positions
anywhere; every layer ends in routed experts under a softmax router
with a shared one; an untied head) through the public `models` /
`serving` API: the `DecodeConfig` that describes its layers, the
parameter set `save_decode_model` exports, and the rule the seeded
weights follow. Serving only (the repo builds no training graph for the
delta rule or routed experts). Found by the name in a configuration
file (`"builder"`).

The configuration file keeps the source's keys; `n_routed_experts`
there is the count of routed experts HELD by this chip (`experts_held`
= [lo, hi) of the `n_routed_experts_scored` the router scores), as the
`model-configs` guide has a chip's share written. What the source's
keys leave open is read from the file's `assumed`, one field each, and
a value no graph builds is refused here."""
from __future__ import annotations

import re
import zlib

import numpy as np

from .laguna_lm import _ByColumn, router_spread


def depth(cfg: dict, kind: str) -> int:
    return int(cfg["num_hidden_layers"])


def layer_types(cfg: dict, n: int):
    """Layer i is softmax attention iff i is in `gqa_layers`, else
    KDA."""
    return ["attention" if i in cfg["gqa_layers"] else "kda"
            for i in range(n)]


def decode_config(cfg: dict, kind: str):
    from paddle_tpu.serving import DecodeConfig

    n = depth(cfg, kind)
    assumed, lin = cfg["assumed"], cfg["linear_attn_config"]
    assert cfg["model_type"] == "solar_open2"
    assert not (cfg["use_rope"] or cfg["tie_word_embeddings"]
                or cfg["first_k_dense_replace"])
    assert cfg["use_gqa_gate"] and cfg["norm_topk_prob"]
    assert cfg["kda_allow_neg_eigval"] and not cfg["kda_use_full_proj"]
    assert cfg["n_shared_experts"] == 1 and lin["num_kv_heads"] is None
    assert cfg["gqa_layers"][:2] == [0, cfg["gqa_interval"] + 1]
    assert assumed["kda_output_gate"] == "low_rank_per_channel"
    assert assumed["attention_gate"] == "elementwise"
    assert assumed["beta_max"] == 2 and assumed["qk_norm"] == "kda_l2_only"
    lo, hi = cfg["experts_held"]
    assert hi - lo == cfg["n_routed_experts"] <= cfg[
        "n_routed_experts_scored"]
    return DecodeConfig(
        cfg["vocab_size"], n_layer=n, n_head=cfg["num_attention_heads"],
        d_model=cfg["hidden_size"], d_inner=cfg["intermediate_size"],
        max_len=int(cfg["serve"]["max_seq"]), tie_embeddings=False,
        n_kv_head=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        layer_types=layer_types(cfg, n), ffn_types=["experts"] * n,
        kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        kda_conv=lin["short_conv_kernel_size"],
        kda_gate=assumed["kda_gate"], kda_beta_max=2.0,
        kda_decay_rank=int(assumed["kda_rank"]), attn_gate="per_channel",
        n_expert=cfg["n_routed_experts_scored"],
        expert_top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"],
        d_shared_expert=cfg["n_shared_experts"]
        * cfg["moe_intermediate_size"],
        experts_held=[lo, hi], router_score=assumed["router_score"],
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


# Kimi Linear's published initialisation of the decay, a head's rate
# exp(A_log) uniform in [1, 16] and a channel's step softplus(dt_bias)
# log-uniform in [1e-3, 0.1], drawn ONCE a parameter (by its name, the
# same for every seed: the spread is part of the rule, as the router's),
# and around it the seed's own N(0, .): see `decay_percentiles`
_A_RANGE = (1.0, 16.0)
_DT_RANGE = (1e-3, 0.1)
_A_LOG_STD = 0.3
_DT_BIAS_STD = 1.0


def _drawn(name: str, n: int) -> np.random.Generator:
    return np.random.default_rng([zlib.crc32(name.encode()), n])


def decay_means(name: str, n: int) -> np.ndarray:
    """The rule's centre of `A_log` (H,) or `dt_bias` (H * dk,)."""
    r = _drawn(name, n)
    if name.endswith(".A_log"):
        return np.log(r.uniform(*_A_RANGE, n)).astype(np.float32)
    dt = np.exp(r.uniform(*np.log(_DT_RANGE), n))
    return np.log(np.expm1(dt)).astype(np.float32)  # softplus^-1


def init_rule(name: str, shape):
    """(mean, std) of a parameter's seeded values. Laguna's rule, for
    Laguna's reason (`models/laguna_lm.py`, `init_rule`): matrices, the
    table and the head N(0, 0.02); norm gains (a KDA head's output norm
    too) N(1, 0.1); the router's columns N(0, 0.02 u_e) with u_e
    log-normal(0, 0.5), so that loads are uneven; the routed experts'
    down projections N(0, 0.002), so that one flipped pair at a near-tie
    moves the logits by less than the base reading fluctuates. This
    model's own: the decay gate's `A_log` and `dt_bias` around Kimi
    Linear's published initialisation (`decay_means`)."""
    if re.search(r"norm\w*\.w$", name):
        return 1.0, 0.1
    if name.endswith(".experts.down.w"):
        return 0.0, 0.002
    if name.endswith(".router.w"):
        return 0.0, (0.02 * router_spread(shape[-1])).view(_ByColumn)
    if name.endswith(".A_log"):
        return decay_means(name, shape[0]), _A_LOG_STD
    if name.endswith(".dt_bias"):
        return decay_means(name, shape[0]), _DT_BIAS_STD
    return 0.0, 0.02


def decay_percentiles(cfg: dict, draws: int = 1_000_000, seed: int = 0):
    """What the rule makes of a token's decay `exp(g)`: percentiles over
    `draws` (token, channel) pairs of one KDA layer, and the share of
    them under e^-5 (a log-decay the BOUNDED gate cannot produce and
    the factored kernel could not have taken), with `x = (u W_fa) W_fb`
    drawn as the rule makes it: a normalised input through N(0, 0.02)
    over hidden_size, then N(0, 0.02) over `kda_rank`."""
    lin = cfg["linear_attn_config"]
    h, dk = lin["num_heads"], lin["head_dim"]
    r = np.random.default_rng(seed)
    name = "lm.l1.kda."
    a_log = decay_means(name + "A_log", h) + _A_LOG_STD * r.normal(size=h)
    dt_bias = (decay_means(name + "dt_bias", h * dk)
               + _DT_BIAS_STD * r.normal(size=h * dk))
    x_std = 0.02 * cfg["hidden_size"] ** 0.5 * 0.02 * float(
        cfg["assumed"]["kda_rank"]) ** 0.5
    at = r.integers(0, h * dk, draws)
    x = x_std * r.normal(size=draws) + dt_bias[at]
    g = -np.exp(a_log[at // dk]) * np.logaddexp(0.0, x)
    qs = (0.1, 1, 2.5, 25, 50, 75, 97.5)
    return ({q: float(v) for q, v in zip(qs, np.percentile(np.exp(g), qs))},
            float(np.mean(g < -5.0)))
