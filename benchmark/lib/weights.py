"""Weights from `--seed`, made on the device in one jitted call, in the
type the program holds them in. The rule for each parameter (mean, std)
belongs to the model's builder; the values belong to the seed alone, so
the reference and the program are given the same tensors and the
reference takes nothing the program has made."""
from __future__ import annotations

import contextlib

import numpy as np


def raw_key(seed: int):
    """A threefry key for any non-negative Python int (seeds above 2**31
    do not fit jax's int32 seed argument with x64 off)."""
    import jax.numpy as jnp

    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jnp.asarray(words, jnp.uint32)


def seeded_weights(specs, seed: int, rule, shardings=None, device=None):
    """{name: array} for `specs` = [(name, shape, dtype)], value
    mean + std * normal with (mean, std) = rule(name, shape). One jit,
    one dispatch; `shardings` ({name: Sharding}) places each output."""
    import jax
    import jax.numpy as jnp

    specs = sorted(specs)
    rules = [rule(n, tuple(s)) for n, s, _ in specs]

    def make(key):
        out = {}
        for i, ((name, shape, dtype), (mean, std)) in enumerate(
                zip(specs, rules)):
            if std == 0.0:
                out[name] = jnp.full(shape, mean, dtype)
            else:
                k = jax.random.fold_in(key, i)
                out[name] = (mean + std * jax.random.normal(
                    k, shape, jnp.float32)).astype(dtype)
        return out

    kw = {}
    if shardings is not None:
        kw["out_shardings"] = {n: shardings[n] for n, _, _ in specs}
    place = (jax.default_device(device) if device is not None
             else contextlib.nullcontext())
    with place:
        return jax.jit(make, **kw)(raw_key(seed))
