"""Builder for ResNet-50 on ImageNet-shaped input through the public
API: `models.resnet.get_model(dataset="imagenet", depth=50)`, Momentum,
AMP as the configuration states. Found by the name in a configuration
file (`"builder"`)."""
from __future__ import annotations

import math
import re

import numpy as np


def depth(cfg: dict, kind: str) -> int:
    return int(cfg["depth"])


def build_train(cfg: dict, mix: dict) -> dict:
    import paddle_tpu as fluid
    from paddle_tpu import models, optimizer

    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = 1
    with fluid.program_guard(main_p, startup):
        with fluid.unique_name.guard():
            avg_cost, _acc, _feeds = models.resnet.get_model(
                dataset="imagenet", depth=int(cfg["depth"]),
                class_dim=int(cfg["num_classes"]),
                image_shape=tuple(cfg["image_shape"]),
                layout=cfg["layout"])
            t = cfg["train"]
            assert t["optimizer"] == "momentum", t
            optimizer.Momentum(learning_rate=t["learning_rate"],
                               momentum=t["momentum"]).minimize(avg_cost)
        main_p.enable_mixed_precision(level=cfg["train"]["amp"])
    return {"main": main_p, "startup": startup, "loss": avg_cost,
            "units_per_step": mix["batch"], "feed_names": ("data", "label")}


def _branch_last_bn(stages=(3, 4, 6, 3)):
    """Indices of the BatchNorm that closes each bottleneck's residual
    branch, in the order the program creates them: the stem is 0, a
    stage's first block has a projection shortcut before its three."""
    out, i = set(), 1
    for count in stages:
        for j in range(count):
            i += 4 if j == 0 else 3
            out.add(i - 1)
    return frozenset(out)


_BRANCH_LAST_BN = _branch_last_bn()


def init_rule(name: str, shape):
    """(mean, std): He-normal filters, BatchNorm scale N(1, 0.1) and bias
    N(0, 0.1), running mean 0 and variance 1, a classifier whose logits
    have a spread of about 1. The BatchNorm that closes a residual
    branch has a scale of N(0.1, 0.01) (a tenth; Goyal et al.,
    arXiv:1706.02677, start it at 0): with all scales near 1 the
    gradient of every filter in bfloat16 is already unrelated to the
    float32 one (relative error 0.7 to 1.2), and no comparison of the
    backward pass can tell a precision from another."""
    if re.match(r"conv2d_\d+\.w_0$", name):
        return 0.0, math.sqrt(2.0 / (shape[1] * shape[2] * shape[3]))
    m = re.match(r"batch_norm_(\d+)\.w_0$", name)
    if m:
        return (0.1, 0.01) if int(m.group(1)) in _BRANCH_LAST_BN else (1.0, 0.1)
    if re.match(r"batch_norm_\d+\.b_0$", name):
        return 0.0, 0.1
    if re.match(r"batch_norm_\d+\.w_1$", name):
        return 0.0, 0.0
    if re.match(r"batch_norm_\d+\.w_2$", name):
        return 1.0, 0.0
    if name == "fc_0.w_0":
        return 0.0, 1.0 / math.sqrt(shape[0])
    if name == "fc_0.b_0":
        return 0.0, 0.01
    raise KeyError("no init rule for parameter %r" % name)


def train_pool(cfg: dict, mix: dict, seed: int):
    """`pool_batches` + 1 batches made on the device in one jitted call
    (a batch of 128 is 77 MB; made on the host it would be seconds of
    numpy and an upload). The extra one is the check batch."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import weights

    n = int(mix["pool_batches"]) + 1
    b = int(mix["batch"])
    shape = (n, b) + tuple(cfg["image_shape"])

    @jax.jit
    def make(key):
        k1, k2 = jax.random.split(key)
        return (jax.random.normal(k1, shape, jnp.float32),
                jax.random.randint(k2, (n, b, 1), 0,
                                   int(cfg["num_classes"]), jnp.int32))

    data, label = make(jax.random.fold_in(weights.raw_key(seed), 77))
    pool = [{"data": data[i], "label": label[i]} for i in range(1, n)]
    check = {"data": data[0], "label": label[0]}
    return pool, check, dict(check)


def check_grads(cfg: dict):
    return list(cfg["check"]["train"]["grads"])
