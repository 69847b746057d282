"""Builder for EvaByte (`model_type: evabyte`: a byte-level decoder whose
every layer is EVA attention, a window of 2,048 bytes attended exactly
and every earlier window through one pooled key and value a chunk of 16
bytes; RMS norms with a unit offset, rotary positions, a gated-SiLU MLP,
an untied head of 320 ids) through the public `models` / `serving` API:
the `DecodeConfig` that describes its layers, the parameter set
`save_decode_model` exports, and the rule the seeded weights follow.
Serving only (the repo's training graphs know OPT's block alone). Found
by the name in a configuration file (`"builder"`).

The configuration file keeps the source's keys. What they leave open is
read from the file's `model`, one field each, and a value no graph builds
is refused here. `num_pred_heads` stays at its published 8; head 0 alone
is built (`pred_heads_built`), the next byte's."""
from __future__ import annotations

import re

import numpy as np

# the ONE value of each ASSUMED convention that the graphs build: what
# the plain reference computes
from ..reference.evabyte import ASSUMED


# std of a head's two learned vectors (`init_rule`)
PHI_STD = 1.0
MU_STD = 0.5


def depth(cfg: dict, kind: str) -> int:
    return int(cfg["num_hidden_layers"])


def decode_config(cfg: dict, kind: str):
    from paddle_tpu.serving import DecodeConfig

    n = depth(cfg, kind)
    assert cfg["model_type"] == "evabyte" and cfg["attention_class"] == "eva"
    for key, only in ASSUMED.items():
        if cfg["model"].get(key) != only:
            raise ValueError("model.%s = %r: only %r is built"
                             % (key, cfg["model"].get(key), only))
    assert not (cfg["attention_bias"] or cfg["tie_word_embeddings"])
    assert cfg["hidden_act"] == "silu" and cfg["rope_scaling"] is None
    assert cfg["norm_add_unit_offset"] and cfg["fp32_logits"]
    assert cfg["num_key_value_heads"] == cfg["num_attention_heads"]
    assert cfg["num_chunks"] is None and cfg["pred_heads_built"] == 1
    head_dim = cfg["hidden_size"] // cfg["num_attention_heads"]
    return DecodeConfig(
        cfg["vocab_size"], n_layer=n, n_head=cfg["num_attention_heads"],
        d_model=cfg["hidden_size"], d_inner=cfg["intermediate_size"],
        max_len=int(cfg["serve"]["max_seq"]), tie_embeddings=False,
        layer_types=["eva"] * n, window=cfg["window_size"],
        eva_chunk=cfg["chunk_size"],
        rope={"full": {"rotary_dim": head_dim,
                       "theta": float(cfg["rope_theta"])}},
        norm="rms_norm", norm_eps=cfg["rms_norm_eps"], norm_offset=True,
        head_precision="highest", ffn="gated_silu", positions=False,
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
    """(mean, std) of a parameter's seeded values: matrices, the table
    and the head N(0, 0.02); a norm's parameter N(0, 0.1) ABOUT ITS UNIT
    OFFSET (the gain is 1 + g); a head's `phi` N(0, PHI_STD) and `mu`
    N(0, MU_STD).

    Why phi and mu are not N(0, 0.02): with N(0, 0.02) matrices on
    unit-rms inputs a key's channel has spread ~1.3 and the scale is
    128^-1/2 = 0.088, so a chunk's pooling logits s phi . k_i have spread
    0.088 x (128)^1/2 x 1.3 x PHI_STD ~ 1.3 at PHI_STD 1: the weights
    inside a chunk are uneven (a mean would be PHI_STD -> 0, and pooling
    by the mean could not be told from pooling by phi); and a summary's
    score moves by s q . mu, of spread 0.088 x 11.3 x 1.3 x MU_STD ~ 0.65
    at MU_STD 0.5: a fraction of the scores' own spread (~1.5), so the
    offset shows in the logits without taking the softmax over. At 0.02
    the check would see neither (`check.serve.why` has the variants'
    readings)."""
    if re.search(r"norm\w*\.w$", name):
        return 0.0, 0.1
    if name.endswith(".phi"):
        return 0.0, PHI_STD
    if name.endswith(".mu"):
        return 0.0, MU_STD
    return 0.0, 0.02
