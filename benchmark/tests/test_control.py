"""The control of `correct`, at a size a test run can hold: the
reference put in the program's place and computed one precision below
the stated one must come out as NOT correct, by the same comparison and
at a limit the sound computation passes."""
import json
import os

import numpy as np
import pytest

from benchmark.lib import compare, weights
from benchmark.models import opt_lm, resnet50 as resnet_model
from benchmark.reference import opt as opt_ref
from benchmark.reference import resnet50 as resnet_ref

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")


def _tiny(name):
    with open(os.path.join(TINY, name)) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 4, 99991])
def test_lm_training_control_fails(seed):
    """AMP O2 states bfloat16; the control is fp8 matmul operands. The
    'program' here is the reference with bfloat16 operands."""
    cfg, mix = _tiny("opt-tiny.json"), _tiny("lm-tiny.json")
    grads = ["lm.l1.ffn.fc2.w"]
    w = weights.seeded_weights(opt_lm.parameter_specs(cfg, "train"), seed,
                               opt_lm.init_rule)
    _, _, ref_in = opt_lm.train_pool(cfg, mix, seed)
    out = {p: {k: np.asarray(v) for k, v in opt_ref.train_check(
        w, ref_in, cfg, 2, grads, precision=p).items()}
        for p in ("highest", "bf16", "fp8")}
    limit = cfg["check"]["train"]["grads"][grads[0]]
    sound = compare.rel_l2(out["bf16"][grads[0]], out["highest"][grads[0]])
    control = compare.rel_l2(out["fp8"][grads[0]], out["highest"][grads[0]])
    assert sound < limit < control and control > 3 * sound


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 4, 99991])
def test_serving_control_fails(seed):
    """Serving states float32 storage with bfloat16-rounded matmul
    operands; the control stores every activation in bfloat16."""
    cfg = _tiny("opt-tiny.json")
    w = weights.seeded_weights(opt_lm.parameter_specs(cfg, "serve"), seed,
                               opt_lm.init_rule)
    toks = np.random.default_rng(seed).integers(1, 1000, 48)
    lg = {p: np.asarray(opt_ref.logits(w, toks, 2, 2, precision=p))
          for p in ("bf16_ops", "bf16")}
    again = np.asarray(opt_ref.logits(w, toks, 2, 2, precision="bf16_ops"))
    assert compare.rel_l2(again, lg["bf16_ops"]) == 0.0
    assert compare.rel_l2(lg["bf16"], lg["bf16_ops"]) > 1e-3


RESNET_GRADS = ["fc_0.w_0", "conv2d_52.w_0", "conv2d_0.w_0"]


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 4, 99991])
def test_resnet_control_fails(seed):
    """AMP O1 states bfloat16 convolutions; the control is float8 e4m3
    operands, forward and backward. The gradient of the classifier needs
    the forward pass alone, that of the last 1x1 filter one block of the
    backward pass, that of the stem all of it."""
    cfg, mix = _tiny("resnet-tiny.json"), _tiny("images-tiny.json")
    # 32 x 32 with a batch of 8 leaves BatchNorm 8 samples in the last
    # stage and rounding noise swamps everything; this size separates
    cfg["image_shape"], mix["batch"] = [3, 64, 64], 32
    built = resnet_model.build_train(cfg, mix)
    specs = [(p.name, tuple(p.shape), np.float32)
             for p in built["main"].all_parameters()]
    w = weights.seeded_weights(specs, seed, resnet_model.init_rule)
    _, _, ref_in = resnet_model.train_pool(cfg, mix, seed)
    out = {p: {k: np.asarray(v) for k, v in resnet_ref.train_check(
        w, ref_in, cfg, 50, RESNET_GRADS, precision=p).items()}
        for p in ("highest", "bf16", "fp8")}
    for g in RESNET_GRADS:
        sound = compare.rel_l2(out["bf16"][g], out["highest"][g])
        control = compare.rel_l2(out["fp8"][g], out["highest"][g])
        assert control > 3 * sound, (g, sound, control)


def test_resnet_lower_precision_reaches_the_backward_pass():
    """With the forward pass left in float32 the control still moves the
    stem's gradient: the rounding is in the backward products too."""
    import jax
    import jax.numpy as jnp

    f = lambda a, b: jnp.matmul(a, b)  # noqa: E731
    a = jnp.linspace(-1.0, 1.0, 48).reshape(6, 8)
    b = jnp.cos(jnp.arange(40.0)).reshape(8, 5)
    g = jnp.sin(jnp.arange(30.0)).reshape(6, 5) * 0.37
    exact = jax.vjp(f, a, b)[1](g)
    _, vjp = jax.vjp(lambda a, b: resnet_ref._product(f, a, b, "fp8"), a, b)
    got = vjp(g)
    want = jax.vjp(f, resnet_ref._quant(a, "fp8"),
                   resnet_ref._quant(b, "fp8"))[1](
        resnet_ref._quant(g, "fp8"))
    for x, y, z in zip(got, want, exact):
        assert np.allclose(x, y) and not np.allclose(x, z, rtol=1e-4)
