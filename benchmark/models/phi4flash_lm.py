"""Builder for Microsoft's Phi-4-mini-flash decoder LM (`model_type:
phi4flash`, the SambaY decoder-hybrid-decoder: Mamba-1 and
sliding-window layers, one Mamba layer that hands on its memory, one
full-attention layer whose K and V every later cross layer reads, gated
memory units; differential attention, LayerNorm, no positions, a tied
table) through the public `models` / `serving` API: the `DecodeConfig`
that describes its layers, the parameter set `save_decode_model`
exports, and the rule the seeded weights follow. Serving only (the repo
builds no training graph with a state-space layer). Found by the name
in a configuration file (`"builder"`)."""
from __future__ import annotations

import math
import re

import numpy as np


def depth(cfg: dict, kind: str) -> int:
    return int(cfg["num_hidden_layers"])


def layer_kinds(n_layer: int):
    """The mixer of each of `n_layer` layers (`assumed.layer_rule`):
    the first half alternates Mamba (even) and sliding-window attention
    (odd); layer N/2 is the Mamba layer that hands on its memory; layer
    N/2 + 1 the one full-attention layer; after it gated memory units
    (even) and cross attention (odd)."""
    half = n_layer // 2
    out = []
    for i in range(n_layer):
        if i < half:
            out.append("mamba" if i % 2 == 0 else "sliding")
        elif i == half:
            out.append("mamba")
        elif i == half + 1:
            out.append("attention")
        else:
            out.append("gmu" if i % 2 == 0 else "cross")
    return out


def decode_config(cfg: dict, kind: str):
    from paddle_tpu.serving import DecodeConfig

    assert cfg["model_type"] == "phi4flash" and cfg["hidden_act"] == "silu"
    assert cfg["tie_word_embeddings"] and cfg["mb_per_layer"] == 2
    assert not cfg["mlp_bias"] and not cfg["lm_head_bias"]
    a = cfg["assumed_sizes"]
    assert a["differential_attention"] and a["mamba_conv_bias"]
    assert a["head_dim"] * cfg["num_attention_heads"] == cfg["hidden_size"]
    n = depth(cfg, kind)
    return DecodeConfig(
        cfg["vocab_size"], n_layer=n, n_head=cfg["num_attention_heads"],
        d_model=cfg["hidden_size"], d_inner=cfg["intermediate_size"],
        max_len=int(cfg["serve"]["max_seq"]), tie_embeddings=True,
        n_kv_head=cfg["num_key_value_heads"],
        layer_types=layer_kinds(n), window=cfg["sliding_window"],
        diff_attn=True, attn_biases=bool(a["attention_bias"]),
        mamba_norms=False, mamba_d_state=cfg["mamba_d_state"],
        mamba_d_conv=cfg["mamba_d_conv"],
        mamba_dt_rank=cfg["mamba_dt_rank"],
        mamba_expand=cfg["mamba_expand"], norm="layer_norm",
        norm_eps=cfg["layer_norm_eps"], ffn="gated_silu", positions=False,
        biases=False)


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
    array that broadcasts. Matrices, the table and every bias N(0,
    0.02); Mamba's published initialisation with spread, as `jamba_lm`
    has it (`A_log` N(log(1..N), 0.1), `dt_proj`'s bias
    N(log(expm1(0.01)), 0.5), `D` N(1, 0.1)); the four lambda vectors
    of a differential layer N(0, 0.1) (lam then differs from lam_init
    by about +-0.1 a layer); every norm's gain, the heads' shared one
    too, N(1, 0.1), so that a gain left out shows."""
    if name.endswith(".A_log"):
        return np.log(np.arange(1, shape[-1] + 1, dtype=np.float32)), 0.1
    if name.endswith(".dt_proj.b"):
        return math.log(math.expm1(0.01)), 0.5
    if re.search(r"\.lambda_[qk][12]$", name):
        return 0.0, 0.1
    if name.endswith(".D") or re.search(r"(norm\w*|subln)\.w$", name):
        return 1.0, 0.1
    return 0.0, 0.02
