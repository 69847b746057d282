"""State-space (Mamba-1) layer primitives, and RMS normalization.

A state-space layer keeps a FIXED-SIZE recurrent state per sequence
where attention keeps a row per position: the selective scan

    s_t = exp(delta_t[:, None] * A) * s_{t-1}
          + (delta_t * x_t)[:, None] * B_t[None, :]
    y_t = s_t @ C_t + D * x_t

over a (d_inner, d_state) state, fed by a causal depthwise convolution
that looks back ``K - 1`` inputs. Prefill walks a whole padded prompt
and hands back the state and the convolution window AT EACH ROW'S
LENGTH (padding must not advance a state: there is no length mask to
hide it afterwards, as there is for K/V rows); a decode step advances
both by one token.

Pure ``lax`` under ``jax.named_scope`` (the names a device trace
shows): ``ptpu.ssm_scan`` is a plain ``lax.scan`` over the sequence
that carries only the (batch, d_state, d_inner) state, never a
(seq, d_inner, d_state) tensor. No Pallas kernel yet: the serving
cell's trace says what one is worth (PERF.md).

The state's interface shape is (batch, d_inner, d_state). Inside, the
wide axis is kept minor, (batch, d_state, d_inner): on a TPU the
compiler lays a small trailing axis out that way in any case (the
transposes at both ends are bitcasts there), and the loop body then
works on full 128-lane vectors.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register_op

SSM_SCAN = "ptpu.ssm_scan"
SSM_STEP = "ptpu.ssm_step"
CAUSAL_CONV = "ptpu.causal_conv1d"
RMS_NORM = "ptpu.rms_norm"

# steps of the scan that one loop iteration holds: the loop's own
# overhead is paid once for them
_SCAN_UNROLL = 8


def rms_norm(x, scale, epsilon=1e-6):
    """scale * x / sqrt(mean(x^2) + eps) over the last axis, in
    float32."""
    with jax.named_scope(RMS_NORM):
        xf = x.astype(jnp.float32)
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        return (xf * lax.rsqrt(ms + epsilon) * scale).astype(x.dtype)


@register_op("rms_norm")
def _rms_norm_op(ctx):
    """Inputs X (..., D), Scale (D,); attr epsilon -> Out = X's shape."""
    return {"Out": rms_norm(ctx.input("X"), ctx.input("Scale"),
                            float(ctx.attr("epsilon", 1e-6)))}


def _ssm_update(state, x, delta, a_t, b, c, d):
    """One token: state (B, N, Di), x/delta (B, Di), a_t (N, Di),
    b/c (B, N), d (Di,) -> (y (B, Di), new state)."""
    decay = jnp.exp(delta[:, None, :] * a_t[None])
    new = decay * state + (delta * x)[:, None, :] * b[:, :, None]
    y = jnp.sum(new * c[:, :, None], axis=1) + d * x
    return y, new


def ssm_scan(x, delta, a, b, c, d, lengths=None):
    """Selective scan from a zero state over padded sequences.

    x, delta (B, T, Di); a (Di, N); b, c (B, T, N); d (Di,); lengths
    (B,) real tokens per row (None: all T). Returns (y (B, T, Di),
    state (B, Di, N)): the state after each row's LAST REAL token:
    positions at or past a row's length leave its state untouched
    (their y is finite and meaningless)."""
    bsz, t, di = x.shape
    n = a.shape[1]
    f32 = jnp.float32
    lens = (jnp.full((bsz,), t, jnp.int32) if lengths is None
            else lengths.reshape(-1).astype(jnp.int32))
    with jax.named_scope(SSM_SCAN):
        a_t = a.astype(f32).T
        dd = d.astype(f32)

        def body(state, inp):
            i, x_t, dt_t, b_t, c_t = inp
            y, new = _ssm_update(state, x_t, dt_t, a_t, b_t, c_t, dd)
            live = (i < lens)[:, None, None]
            return jnp.where(live, new, state), y

        xs = (jnp.arange(t, dtype=jnp.int32),
              jnp.swapaxes(x.astype(f32), 0, 1),
              jnp.swapaxes(delta.astype(f32), 0, 1),
              jnp.swapaxes(b.astype(f32), 0, 1),
              jnp.swapaxes(c.astype(f32), 0, 1))
        state, ys = lax.scan(body, jnp.zeros((bsz, n, di), f32), xs,
                             unroll=min(_SCAN_UNROLL, t))
        return (jnp.swapaxes(ys, 0, 1).astype(x.dtype),
                jnp.swapaxes(state, 1, 2))


def ssm_step(x, delta, a, b, c, d, state):
    """One token of the scan: x, delta (B, Di) or (B, 1, Di); b, c
    (B, N) or (B, 1, N); state (B, Di, N) -> (y shaped as x, new
    state (B, Di, N))."""
    f32 = jnp.float32
    bsz = x.shape[0]
    with jax.named_scope(SSM_STEP):
        y, new = _ssm_update(
            jnp.swapaxes(state.astype(f32), 1, 2),
            x.reshape(bsz, -1).astype(f32),
            delta.reshape(bsz, -1).astype(f32), a.astype(f32).T,
            b.reshape(bsz, -1).astype(f32), c.reshape(bsz, -1).astype(f32),
            d.astype(f32))
        return (y.reshape(x.shape).astype(x.dtype),
                jnp.swapaxes(new, 1, 2).astype(state.dtype))


@register_op("ssm_scan")
def _ssm_scan_op(ctx):
    """Inputs X, Delta (B, T, Di), A (Di, N), B, C (B, T, N), D (Di,),
    optional Lengths (B,) -> Y (B, T, Di), State (B, Di, N) at each
    row's length."""
    y, state = ssm_scan(ctx.input("X"), ctx.input("Delta"), ctx.input("A"),
                        ctx.input("B"), ctx.input("C"), ctx.input("D"),
                        ctx.input("Lengths"))
    return {"Y": y, "State": state}


@register_op("ssm_step")
def _ssm_step_op(ctx):
    """Inputs X, Delta (B, 1, Di), A (Di, N), B, C (B, 1, N), D (Di,),
    State (B, Di, N) -> Y (B, 1, Di), StateOut (B, Di, N)."""
    y, state = ssm_step(ctx.input("X"), ctx.input("Delta"), ctx.input("A"),
                        ctx.input("B"), ctx.input("C"), ctx.input("D"),
                        ctx.input("State"))
    return {"Y": y, "StateOut": state}


def causal_conv1d(x, w, bias=None, lengths=None):
    """Causal depthwise convolution over time, zeros before the start:
    y[:, t] = bias + sum_k w[:, k] * x[:, t - K + 1 + k].

    x (B, T, C); w (C, K); bias (C,) or None; lengths (B,) or None.
    Returns (y (B, T, C), window (B, K - 1, C)): the last K - 1 inputs
    BEFORE each row's length (zeros where the prompt is shorter), which
    is what the one-token step carries on from."""
    bsz, t, ch = x.shape
    k = w.shape[1]
    lens = (jnp.full((bsz,), t, jnp.int32) if lengths is None
            else lengths.reshape(-1).astype(jnp.int32))
    with jax.named_scope(CAUSAL_CONV):
        xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
        y = sum(xp[:, j:j + t] * w[:, j] for j in range(k))
        if bias is not None:
            y = y + bias
        # input position len - K + 1 is row len of the padded x: one
        # (K - 1, C) slice a row, always in bounds (len <= T). Not an
        # elementwise `take_along_axis`: its gather of a (B, K - 1, C)
        # index tensor hung the chip within six runs of a 4 x 512
        # prefill, with no error (PERF.md, PR 26), and cost 0.6 GB of
        # temporaries there
        window = jax.vmap(
            lambda row, at: lax.dynamic_slice_in_dim(row, at, k - 1, axis=0)
        )(xp, jnp.clip(lens, 0, t))
        return y.astype(x.dtype), window


def causal_conv1d_step(x, window, w, bias=None):
    """One token: x (B, 1, C) or (B, C), window (B, K - 1, C) the
    inputs before it -> (y shaped as x, window moved on by one)."""
    bsz = x.shape[0]
    with jax.named_scope(CAUSAL_CONV):
        full = jnp.concatenate(
            [window, x.reshape(bsz, 1, -1).astype(window.dtype)], axis=1)
        # multiply and add, as the prefill's convolution does: a
        # contraction would round its operands to bfloat16 on a TPU
        y = jnp.sum(full * w.astype(full.dtype).T[None], axis=1)
        if bias is not None:
            y = y + bias
        return y.reshape(x.shape).astype(x.dtype), full[:, 1:]


@register_op("causal_conv1d")
def _causal_conv1d_op(ctx):
    """Inputs X (B, T, C), W (C, K), optional Bias (C,), Lengths (B,)
    -> Y (B, T, C), Window (B, K - 1, C) at each row's length."""
    y, window = causal_conv1d(ctx.input("X"), ctx.input("W"),
                              ctx.input("Bias"), ctx.input("Lengths"))
    return {"Y": y, "Window": window}


@register_op("causal_conv1d_step")
def _causal_conv1d_step_op(ctx):
    """Inputs X (B, 1, C), Window (B, K - 1, C), W (C, K), optional
    Bias -> Y (B, 1, C), WindowOut (B, K - 1, C)."""
    y, window = causal_conv1d_step(ctx.input("X"), ctx.input("Window"),
                                   ctx.input("W"), ctx.input("Bias"))
    return {"Y": y, "WindowOut": window}
