"""Plain reference of ResNet-50 (He et al., arXiv:1512.03385, Table 1)
as `models/resnet.py` builds it: bottleneck blocks [3, 4, 6, 3] with the
stride on the first 1x1 convolution (the original v1 placement),
projection shortcuts where the width changes, BatchNorm in training mode
(batch statistics, biased variance, eps 1e-5), NCHW, softmax
cross-entropy. Straightforward `jax.numpy`/`lax.conv`, float32.

The stem here is the plain 7x7 stride-2 pad-3 convolution; the program
computes the same map through a space-to-depth rearrangement, so the
comparison covers that identity too.

`precision`: "highest" (float32, the truth), "bf16" (convolution and
matmul operands rounded to bfloat16), "int8" / "fp8" (operands
fake-quantized per tensor; the control of the correctness check). A
lower precision holds in the backward pass too: the incoming gradient of
every convolution and of the classifier matmul is rounded the same way
before the two gradient products, which use the rounded operands
(straight-through: the rounding itself has the derivative 1).

The gradient of an early filter needs the backward pass through every
block; each bottleneck is a `jax.checkpoint`, so that the float32
activations of a whole batch fit beside the weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

EPS = 1e-5
STAGES = {50: [3, 4, 6, 3], 101: [3, 4, 23, 3], 152: [3, 8, 36, 3]}


def _round_e4m3(x):
    """`x` (|x| <= 448) rounded to float8 e4m3: 3 mantissa bits, the
    smallest exponent -6, ties to even. The same values as
    `x.astype(jnp.float8_e4m3fn)`, in arithmetic: the 265 conversions of
    a backward pass take the TPU compiler minutes."""
    exp = (lax.bitcast_convert_type(x, jnp.int32) >> 23) & 0xFF
    step = lax.bitcast_convert_type(
        (jnp.maximum(exp, 127 - 6) - 3) << 23, jnp.float32)
    return jnp.round(x / step) * step


def _quant(x, precision):
    if precision == "highest":
        return x
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    if precision == "int8":
        s = amax / 127.0
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    if precision == "fp8":
        return _round_e4m3(x * (448.0 / amax)) * (amax / 448.0)
    raise ValueError(precision)


def _product(f, a, b, precision):
    """f(a, b), a bilinear product, with both operands, and in the
    backward pass the incoming gradient, rounded to `precision`."""
    if precision == "highest":
        return f(a, b)

    @jax.custom_vjp
    def prod(a, b):
        return f(_quant(a, precision), _quant(b, precision))

    def fwd(a, b):
        return prod(a, b), (a, b)

    def bwd(res, g):
        _, vjp = jax.vjp(f, _quant(res[0], precision),
                         _quant(res[1], precision))
        return vjp(_quant(g, precision))

    prod.defvjp(fwd, bwd)
    return prod(a, b)


class _Net:
    """Walks the parameters in the order the program created them."""

    def __init__(self, params, precision):
        self.p, self.prec = params, precision
        self.conv_i = self.bn_i = 0

    def conv(self, x, stride, pad):
        w = self.p["conv2d_%d.w_0" % self.conv_i]
        self.conv_i += 1
        return _product(lambda a, b: lax.conv_general_dilated(
            a, b, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            precision=lax.Precision.HIGHEST), x, w, self.prec)

    def bn(self, x, relu):
        i = self.bn_i
        self.bn_i += 1
        mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=(0, 2, 3), keepdims=True)
        y = (x - mean) * lax.rsqrt(var + EPS)
        y = (y * self.p["batch_norm_%d.w_0" % i].reshape(1, -1, 1, 1)
             + self.p["batch_norm_%d.b_0" % i].reshape(1, -1, 1, 1))
        return jax.nn.relu(y) if relu else y

    def conv_bn(self, x, stride, pad, relu=True):
        return self.bn(self.conv(x, stride, pad), relu)

    def bottleneck(self, x, ch_out, stride):
        short = x
        if x.shape[1] != ch_out * 4:
            short = self.conv_bn(x, stride, 0, relu=False)
        y = self.conv_bn(x, stride, 0)
        y = self.conv_bn(y, 1, 1)
        y = self.conv_bn(y, 1, 0, relu=False)
        return jax.nn.relu(short + y)

    def block(self, x, ch_out, stride):
        """`bottleneck`, its activations recomputed in the backward pass."""
        at = (self.conv_i, self.bn_i)

        def run(p, x):
            sub = _Net(p, self.prec)
            sub.conv_i, sub.bn_i = at
            y = sub.bottleneck(x, ch_out, stride)
            self.conv_i, self.bn_i = sub.conv_i, sub.bn_i  # plain ints
            return y

        return jax.checkpoint(run)(self.p, x)


def probabilities(params, data, depth=50, precision="highest"):
    net = _Net(params, precision)
    x = net.conv_bn(data, 2, 3)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          [(0, 0), (0, 0), (1, 1), (1, 1)])
    for ch, count, stride in zip((64, 128, 256, 512), STAGES[depth],
                                 (1, 2, 2, 2)):
        for j in range(count):
            x = net.block(x, ch, stride if j == 0 else 1)
    pooled = jnp.mean(x, axis=(2, 3))
    logits = _product(lambda a, b: jnp.matmul(
        a, b, precision=lax.Precision.HIGHEST), pooled, params["fc_0.w_0"],
        precision) + params["fc_0.b_0"]
    return jax.nn.softmax(logits, axis=-1)


def loss(params, data, label, depth=50, precision="highest"):
    p = probabilities(params, data, depth, precision)
    picked = jnp.take_along_axis(p, label.reshape(-1, 1), axis=1)[:, 0]
    return jnp.mean(-jnp.log(picked))


def train_check(params, inputs, cfg, depth, grads, precision="highest"):
    """{"loss": ..., name: dLoss/dparams[name] for name in `grads`}."""
    sub = {n: params[n] for n in grads}
    rest = {n: v for n, v in params.items() if n not in sub}

    @jax.jit
    def run(sub, rest, data, label):
        return jax.value_and_grad(lambda s: loss(
            {**rest, **s}, data, label, depth, precision))(sub)

    val, g = run(sub, rest, inputs["data"], inputs["label"])
    out = dict(g)
    out["loss"] = val
    return out
