"""Builder for Z.ai's GLM-5 language model (`model_type: glm_moe_dsa`:
multi-head latent attention under a learned indexer in EVERY layer,
value heads of 256 under query/key heads of 192 + 64, the indexer's
rotation interleaved; three leading dense MLPs and then routed experts
with a shared one under a sigmoid router with a selection bias; an
untied head; ONE multi-token-prediction layer behind
`num_nextn_predict_layers`) through the public `models` / `serving` API:
the `DecodeConfig` that describes its layers, the parameter set
`save_decode_model` exports (matrices in the type `precision.matrices`
names), and the rule the seeded weights follow. Serving only. Found by
the name in a configuration file (`"builder"`).

The configuration file keeps the source's keys; `n_routed_experts` there
is the count of routed experts HELD by this chip (`experts_held` = [lo,
hi) of the `n_routed_experts_scored` the router scores), and
`num_hidden_layers` the layers BUILT: `layers_built` names which of the
published ones they are (the three leading dense layers count once, so
`first_k_dense_replace` dense layers of the source are `dense_layers_built`
here). What the source's keys leave open is read from the file's
`assumed`, one field each, and a value no graph builds is refused
here."""
from __future__ import annotations

import re

import numpy as np

from .laguna_lm import _ByColumn, router_spread

# the ONE value of each ASSUMED convention that the graphs build
ASSUMED = {"mtp_concat": "embedding_first",
           "mtp_hidden": "after_final_norm",
           "mtp_shares": "table_and_head",
           "mtp_layer": "sparse_with_indexer",
           "index_rope_channels": "first",
           "draft_tokens": 1}
MATRIX_TYPES = ("float32", "bfloat16")


def depth(cfg: dict, kind: str) -> int:
    return int(cfg["num_hidden_layers"])


def check_config(cfg: dict):
    """Refuse a configuration no graph here computes."""
    assert cfg["model_type"] == "glm_moe_dsa"
    for key, only in ASSUMED.items():
        if cfg["assumed"].get(key) != only:
            raise ValueError("assumed.%s = %r: only %r is built"
                             % (key, cfg["assumed"].get(key), only))
    assert not (cfg["attention_bias"] or cfg["tie_word_embeddings"])
    assert cfg["hidden_act"] == "silu" and cfg["norm_topk_prob"]
    assert cfg["scoring_func"] == "sigmoid"
    assert cfg["topk_method"] == "noaux_tc"
    assert cfg["n_group"] == 1 and cfg["topk_group"] == 1
    assert cfg["moe_layer_freq"] == 1
    assert cfg["rope_parameters"]["rope_type"] == "default"
    assert cfg["num_key_value_heads"] == cfg["num_attention_heads"]
    assert (cfg["qk_head_dim"]
            == cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])
    assert cfg["num_nextn_predict_layers"] == 1
    lo, hi = cfg["experts_held"]
    assert hi - lo == cfg["n_routed_experts"] <= cfg["n_routed_experts_scored"]
    assert 0 < dense_layers(cfg) < depth(cfg, "serve")
    assert matrix_dtype(cfg) in MATRIX_TYPES


def dense_layers(cfg: dict) -> int:
    """The leading dense layers BUILT: the source's
    `first_k_dense_replace` of them counted once where the depth is cut
    (`dense_layers_built`), else all of them."""
    return int(cfg.get("dense_layers_built", cfg["first_k_dense_replace"]))


def matrix_dtype(cfg: dict) -> str:
    return str(cfg["precision"].get("matrices", "float32"))


def decode_config(cfg: dict, kind: str):
    from paddle_tpu.serving import DecodeConfig

    check_config(cfg)
    n = depth(cfg, kind)
    lo, hi = cfg["experts_held"]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    return DecodeConfig(
        cfg["vocab_size"], n_layer=n, n_head=cfg["num_attention_heads"],
        d_model=cfg["hidden_size"], d_inner=cfg["intermediate_size"],
        max_len=int(cfg["serve"]["max_seq"]), tie_embeddings=False,
        layer_types=["latent_dsa"] * n,
        ffn_types=["dense" if i < dense_layers(cfg) else "experts"
                   for i in range(n)],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        index_heads=cfg["index_n_heads"],
        index_head_dim=cfg["index_head_dim"], index_topk=cfg["index_topk"],
        rope={"latent": {"theta": theta,
                         "interleave": bool(cfg["rope_interleave"])},
              "index": {"theta": theta,
                        "rotary_dim": cfg["qk_rope_head_dim"],
                        "interleave": bool(cfg["indexer_rope_interleave"])}},
        n_expert=cfg["n_routed_experts_scored"],
        expert_top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"],
        d_shared_expert=cfg["n_shared_experts"]
        * cfg["moe_intermediate_size"],
        experts_held=[lo, hi], router_score="sigmoid",
        router_scale=cfg["routed_scaling_factor"], router_bias=True,
        norm="rms_norm", norm_eps=cfg["rms_norm_eps"], ffn="gated_silu",
        positions=False, biases=False,
        n_predict_layers=cfg["num_nextn_predict_layers"],
        matrix_dtype=matrix_dtype(cfg))


def parameter_specs(cfg: dict, kind: str):
    """[(name, shape, dtype)] of the model's parameters, the prediction
    layer's among them, each in the type it is HELD in, from a prefill
    Program that is built and never run."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.framework.dtypes import as_numpy_dtype
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
    return [(p.name, tuple(p.shape), np.dtype(as_numpy_dtype(p.dtype)))
            for p in main_p.all_parameters()]


def init_rule(name: str, shape):
    """(mean, std) of a parameter's seeded values. dots3's rule
    (`models/dots3_lm.py`, `init_rule`) WITHOUT its `SHARP`: GLM-5 does
    not rescale its latents, so a layer's scores at the plain N(0, 0.02)
    are as flat as Mistral's and Ling's and the base reading does not
    swing with the seed (`check.serve.why` has the readings; a larger
    `W_kvb` or `W_qb`, tried on the chip to make the slabs' precision
    visible, raised the program's reading as fast as the control's and
    is not kept). Matrices,
    the table and the head N(0, 0.02); norm gains N(1, 0.1) (a
    LayerNorm's bias N(0, 0.02) as any matrix); the router's columns
    N(0, 0.02 u_e) with u_e log-normal(0, 0.5), so that loads are
    uneven; the routed experts' down projections N(0, 0.002), so that
    one flipped pair at a near-tie moves the logits by less than the
    base reading fluctuates; the router's selection bias N(0, 0.01). The
    prediction layer's parameters follow the same rule by their names'
    endings: nothing makes its choice agree with the model's."""
    if re.search(r"norm\w*\.w$", name):
        return 1.0, 0.1
    if name.endswith(".experts.down.w"):
        return 0.0, 0.002
    if name.endswith(".router.w"):
        return 0.0, (0.02 * router_spread(shape[-1])).view(_ByColumn)
    if name.endswith(".router.bias"):
        return 0.0, 0.01
    return 0.0, 0.02
