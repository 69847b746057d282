"""Builder for the OPT-family decoder LM through the public
`layers`/`models` API: the training Program, the forward Program whose
parameters `save_decode_model` exports, and the rule the seeded weights
follow. Found by the name in a configuration file (`"builder"`)."""
from __future__ import annotations

import os
import re

import numpy as np


def depth(cfg: dict, kind: str) -> int:
    """Layers run for a mix kind: "train" or "serve_*" -> "serve"."""
    return int(cfg["num_hidden_layers"][kind.split("_")[0]])


def _lm_kwargs(cfg: dict, n_layer: int) -> dict:
    assert cfg["do_layer_norm_before"] and cfg["activation_function"] == "relu"
    assert cfg["word_embed_proj_dim"] == cfg["hidden_size"]
    return dict(vocab_size=cfg["vocab_size"], n_layer=n_layer,
                n_head=cfg["num_attention_heads"],
                d_model=cfg["hidden_size"], d_inner=cfg["ffn_dim"],
                max_len=cfg["max_position_embeddings"],
                tie_embeddings=bool(cfg["tie_word_embeddings"]))


def _program(cfg, batch, seq, n_layer, train):
    import paddle_tpu as fluid
    from paddle_tpu import layers, models, optimizer

    for k, v in cfg.get("env", {}).items():
        os.environ[k] = str(v)  # trace-time switches the config states
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = 1
    with fluid.program_guard(main_p, startup):
        with fluid.unique_name.guard():
            ids = layers.data(name="ids", shape=[batch, seq], dtype="int64",
                              append_batch_size=False)
            labels = layers.data(name="labels", shape=[batch, seq],
                                 dtype="int64", append_batch_size=False)
            loss, _ = models.transformer.transformer_lm(
                ids, labels, dropout_rate=float(cfg["dropout"]),
                fused_head=train, **_lm_kwargs(cfg, n_layer))
            if train:
                t = cfg["train"]
                assert t["optimizer"] == "adam", t
                optimizer.Adam(learning_rate=t["learning_rate"]).minimize(
                    loss)
        if train:
            main_p.enable_mixed_precision(level=cfg["train"]["amp"])
    return main_p, startup, loss


def build_train(cfg: dict, mix: dict) -> dict:
    main_p, startup, loss = _program(
        cfg, mix["batch"], mix["seq"], depth(cfg, "train"), train=True)
    return {"main": main_p, "startup": startup, "loss": loss,
            "units_per_step": mix["batch"] * mix["seq"]}


def parameter_specs(cfg: dict, kind: str):
    """[(name, shape, dtype)] of the model's parameters, from a forward
    Program that is built and never run."""
    main_p, _, _ = _program(cfg, 1, 16, depth(cfg, kind), train=False)
    return [(p.name, tuple(p.shape), np.float32)
            for p in main_p.all_parameters()]


def init_rule(name: str, shape):
    """(mean, std) of a parameter's seeded values. Matrices and tables
    N(0, 0.02) as the repo's initializers have them; biases and the
    LayerNorm affine are given spread too, so that a dropped bias or
    scale shows in the comparison."""
    if re.match(r"layer_norm_\d+\.w_0$", name):
        return 1.0, 0.1
    return 0.0, 0.02


def plan(cfg: dict, mesh):
    from paddle_tpu.parallel import megatron_transformer_plan

    return megatron_transformer_plan(
        mesh, tied=bool(cfg["tie_word_embeddings"]))


def train_pool(cfg: dict, mix: dict, seed: int):
    """(pool of feeds as numpy dicts, check feed, reference inputs).

    The check batch tiles `check_sequences` distinct sequences over the
    batch: its mean loss and gradients are those of the distinct
    sequences alone, which is what the reference computes."""
    from benchmark.lib import traffic

    b, t = mix["batch"], mix["seq"]
    arr = traffic.token_batches(seed, mix["pool_batches"] + 1, b, t,
                                cfg["vocab_size"])
    n = int(mix["check_sequences"])
    assert b % n == 0, (b, n)
    sample = arr[0, :n]
    tiled = np.tile(sample, (b // n, 1))
    pool = [{"ids": a[:, :-1], "labels": a[:, 1:]} for a in arr[1:]]
    check = {"ids": tiled[:, :-1], "labels": tiled[:, 1:]}
    return pool, check, {"tokens": sample}


def check_grads(cfg: dict):
    return list(cfg["check"]["train"]["grads"])


def decode_config(cfg: dict, kind: str):
    from paddle_tpu.serving import DecodeConfig

    kw = _lm_kwargs(cfg, depth(cfg, kind))
    return DecodeConfig(kw.pop("vocab_size"), **kw)
