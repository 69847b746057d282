"""Builder for the Jamba-family hybrid decoder LM (state-space layers
with one attention layer a period) through the public `models` /
`serving` API: the parameter set `save_decode_model` exports, the
`DecodeConfig` that describes its layers, and the rule the seeded
weights follow. Serving only: this builder has no `build_train` (the
repo builds no training graph for state-space layers). Found by the
name in a configuration file (`"builder"`)."""
from __future__ import annotations

import math
import re

import numpy as np


def depth(cfg: dict, kind: str) -> int:
    """Layers run for a mix kind: "serve*_*" -> "serve"."""
    layers = cfg["num_hidden_layers"]
    if isinstance(layers, dict):
        return int(layers["serve"])
    return int(layers)


def decode_config(cfg: dict, kind: str):
    from paddle_tpu.serving import DecodeConfig

    assert cfg["model_type"] == "jamba" and cfg["hidden_act"] == "silu"
    assert cfg["num_experts"] == 1 and cfg["tie_word_embeddings"]
    assert cfg["mamba_conv_bias"] and not cfg["mamba_proj_bias"]
    return DecodeConfig(
        cfg["vocab_size"], n_layer=depth(cfg, kind),
        n_head=cfg["num_attention_heads"], d_model=cfg["hidden_size"],
        d_inner=cfg["intermediate_size"],
        max_len=int(cfg["serve"]["max_seq"]), tie_embeddings=True,
        n_kv_head=cfg["num_key_value_heads"],
        attn_layer_period=cfg["attn_layer_period"],
        attn_layer_offset=cfg["attn_layer_offset"],
        mamba_d_state=cfg["mamba_d_state"], mamba_d_conv=cfg["mamba_d_conv"],
        mamba_dt_rank=cfg["mamba_dt_rank"], mamba_expand=cfg["mamba_expand"],
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
    """(mean, std) of a parameter's seeded values; a mean may be an
    array that broadcasts. Matrices, the table and the conv bias
    N(0, 0.02). Mamba's published initialisation with spread, so that a
    wrong recurrence or a state that leaks between slots shows: `A_log`
    N(log(1..N) along the state axis, 0.1), `dt_proj`'s bias
    N(log(expm1(0.01)), 0.5) (softplus of it lies near 0.003-0.03: the
    state remembers hundreds of tokens), `D` and every norm gain
    N(1, 0.1)."""
    if name.endswith(".A_log"):
        return np.log(np.arange(1, shape[-1] + 1, dtype=np.float32)), 0.1
    if name.endswith(".dt_proj.b"):
        return math.log(math.expm1(0.01)), 0.5
    if name.endswith(".D") or re.search(r"norm\w*\.w$", name):
        return 1.0, 0.1
    return 0.0, 0.02
