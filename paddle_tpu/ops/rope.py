"""Rotary position embedding: the one op that rotates a query or key.

The half-split convention of the published checkpoints
(``transformers``' ``rotate_half``): of a head's first ``r`` channels
(``r = rotary_dim``; the rest pass through untouched)

    x1, x2 = x[..., :r/2], x[..., r/2:r]
    out    = (x1 cos - x2 sin, x2 cos + x1 sin),   angle = p * inv_freq

at the token's ABSOLUTE position ``p``: a prefill rotates row ``t`` of
every sequence at ``t``, a decode step rotates its one row at the
slot's length. Three kinds, by attribute:

- plain: ``inv_freq_d = theta^(-2d/r)``, the whole head (``r = Dh``);
- partial: the same over the first ``r < Dh`` channels;
- YaRN (``transformers``' ``_compute_yarn_parameters``): the plain
  frequencies blended with the same frequencies divided by ``factor``
  along a linear ramp between the channels that turn ``beta_fast`` and
  ``beta_slow`` times within the original context, and ``cos``/``sin``
  multiplied by ``attention_factor``.

``interleave`` rotates the pairs ``(2i, 2i+1)`` of those channels in
place of the half-split pairs ``(i, i + r/2)`` (the published MLA
checkpoints' ``rope_interleave``): channel ``2i`` is the real and
``2i+1`` the imaginary part of a complex number turned by ``p *
inv_freq_i``, and the row keeps its layout.

``query_scale`` is the position-dependent scale of a query row that
the Llama-4 / Ministral-3 rule adds for long contexts
(``llama_4_scaling_beta``): ``1 + beta * ln(1 + floor(p / original))``,
1 for every position below the original context.

The frequencies are host arithmetic in float64 from the attributes (a
constant of the program); the angles, ``cos`` and ``sin`` are float32.
"""
from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

from .registry import register_op

ROPE = "ptpu.rope"


def rope_inv_freq(rotary_dim: int, theta: float, yarn=None) -> np.ndarray:
    """(rotary_dim / 2,) float64 inverse frequencies. ``yarn`` is None
    (plain) or a dict with ``factor``, ``original_max_position``,
    ``beta_fast``, ``beta_slow`` (``truncate`` defaults true, as the
    published rule)."""
    half = rotary_dim // 2
    pos_freqs = float(theta) ** (np.arange(half, dtype=np.float64) * 2.0
                                 / rotary_dim)
    extra = 1.0 / pos_freqs
    if not yarn:
        return extra
    inter = 1.0 / (float(yarn["factor"]) * pos_freqs)
    orig = float(yarn["original_max_position"])

    def correction(rotations):
        return (rotary_dim * math.log(orig / (rotations * 2.0 * math.pi))
                / (2.0 * math.log(float(theta))))

    low, high = correction(float(yarn["beta_fast"])), correction(
        float(yarn["beta_slow"]))
    if yarn.get("truncate", True):
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, rotary_dim - 1)
    if low == high:
        high += 0.001  # the published rule's guard against 0 / 0
    ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    # ramp 0: a fast channel, kept (extrapolated); 1: interpolated
    return inter * ramp + extra * (1.0 - ramp)


def _positions(positions, t):
    """(B | 1, T) float32 from (B, T), (T,), (B,) at T = 1, or None."""
    if positions is None:
        return jnp.arange(t, dtype=jnp.float32)[None, :]
    return positions.reshape(-1, t).astype(jnp.float32)


def query_scale(positions, t, beta, original_max_position):
    """(B | 1, T) float32: ``1 + beta ln(1 + floor(p / original))``."""
    pos = _positions(positions, t)
    return 1.0 + float(beta) * jnp.log1p(
        jnp.floor(pos / float(original_max_position)))


def yarn_of_attrs(attr):
    """The ``yarn`` dict of ``rope_inv_freq`` from an op's attributes
    (``attr(name, default)``); None where ``factor`` is 0 or absent."""
    factor = float(attr("factor", 0.0) or 0.0)
    if not factor:
        return None
    return {"factor": factor,
            "original_max_position": attr("original_max_position", None),
            "beta_fast": attr("beta_fast", 32.0),
            "beta_slow": attr("beta_slow", 1.0)}


def rope(x, positions, inv_freq, attention_factor=1.0, interleave=False):
    """x (B, T, H, Dh) rotated over its first ``2 * len(inv_freq)``
    channels at ``positions`` (B, T) (or (T,), or None: 0..T-1): the
    half-split pairs, or the pairs (2i, 2i+1) under ``interleave``."""
    b, t, _, dh = x.shape
    half = len(inv_freq)
    r = 2 * half
    with jax.named_scope(ROPE):
        pos = _positions(positions, t)
        ang = pos[:, :, None] * jnp.asarray(inv_freq, jnp.float32)
        cos = (jnp.cos(ang) * attention_factor)[:, :, None, :]
        sin = (jnp.sin(ang) * attention_factor)[:, :, None, :]
        xf = x.astype(jnp.float32)
        if interleave:
            pairs = xf[..., :r].reshape(xf.shape[:-1] + (half, 2))
            x1, x2 = pairs[..., 0], pairs[..., 1]
            parts = [jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1).reshape(xf.shape[:-1] + (r,))]
        else:
            x1, x2 = xf[..., :half], xf[..., half:r]
            parts = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
        if r < dh:
            parts.append(xf[..., r:])
        return jnp.concatenate(parts, axis=-1).astype(x.dtype)


@register_op("rope")
def _rope_op(ctx):
    """Inputs X (B, T, H, Dh), optional Positions (B, T) or (B,) for
    T = 1 (absent: 0..T-1). Attrs: rotary_dim, theta, attention_factor,
    interleave, and for YaRN factor, original_max_position, beta_fast,
    beta_slow (factor 0 or absent: plain). -> Out = X's shape."""
    x = ctx.input("X")
    inv = rope_inv_freq(int(ctx.attr("rotary_dim", x.shape[-1])),
                        float(ctx.attr("theta", 10000.0)),
                        yarn_of_attrs(ctx.attr))
    return {"Out": rope(x, ctx.input("Positions"), inv,
                        float(ctx.attr("attention_factor", 1.0) or 1.0),
                        bool(ctx.attr("interleave", False)))}
