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

Under ``jax.named_scope`` (the names a device trace shows).
``ptpu.ssm_scan`` carries only the (batch, d_state, d_inner) state,
never a (seq, d_inner, d_state) tensor, and has two forms of ONE
recurrence, a position after a position in float32: a plain
``lax.scan`` over the sequence (the CPU's path, a bucket under one
block of positions, and the numeric reference), and on a TPU one
Pallas call a layer (``_ssm_scan_kernel``, since PR 41) whose state
tile stays in vector memory across a row's blocks of positions and
whose grid does no work past a row's length. ``_use_kernel`` chooses
by shape and device; ``paddle_tpu_ssm_scan_traces_total{path}`` says
which a program was traced with. The one-token step is lax.

The state's interface shape is (batch, d_inner, d_state). Inside, the
wide axis is kept minor, (batch, d_state, d_inner): on a TPU the
compiler lays a small trailing axis out that way in any case (the
transposes at both ends are bitcasts there), and the loop body then
works on full 128-lane vectors.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability import SSM_SCAN_TRACES
from . import attention as _A
from . import kv_cache as _KV
from .registry import register_op

SSM_SCAN = "ptpu.ssm_scan"
SSM_STEP = "ptpu.ssm_step"
CAUSAL_CONV = "ptpu.causal_conv1d"
RMS_NORM = "ptpu.rms_norm"

# steps of the scan that one loop iteration holds: the loop's own
# overhead is paid once for them
_SCAN_UNROLL = 8

# the kernel's blocks: positions a grid cell walks, and the lanes of
# d_inner whose (d_state, lanes) state tile it keeps. On the chip a
# live position of a row costs 0.34-0.47 us at 1,024 lanes, 0.46-0.61
# at 512 and 0.8-0.9 at 256 (a position's transposed B and C columns
# are broadcast once a tile, whatever its width), and 64, 128 or 256
# positions a block read the same within 5% (PERF.md, PR 41: the
# probe's table; the lax form walks a BUCKET position in 0.93-1.70)
_KERNEL_BLOCK_T = 128
_KERNEL_BLOCK_D = 1024


def rms_norm(x, scale, epsilon=1e-6, unit_offset=False):
    """scale * x / sqrt(mean(x^2) + eps) over the last axis, in
    float32; ``(1 + scale)`` for ``scale`` under ``unit_offset``."""
    with jax.named_scope(RMS_NORM):
        xf = x.astype(jnp.float32)
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        if unit_offset:
            scale = 1.0 + scale.astype(jnp.float32)
        return (xf * lax.rsqrt(ms + epsilon) * scale).astype(x.dtype)


@register_op("rms_norm")
def _rms_norm_op(ctx):
    """Inputs X (..., D), Scale (D,); attrs epsilon, unit_offset -> Out
    = X's shape."""
    return {"Out": rms_norm(ctx.input("X"), ctx.input("Scale"),
                            float(ctx.attr("epsilon", 1e-6)),
                            bool(ctx.attr("unit_offset", False)))}


def _ssm_update(state, x, delta, a_t, b, c, d):
    """One token: state (B, N, Di), x/delta (B, Di), a_t (N, Di),
    b/c (B, N), d (Di,) -> (y (B, Di), new state)."""
    decay = jnp.exp(delta[:, None, :] * a_t[None])
    new = decay * state + (delta * x)[:, None, :] * b[:, :, None]
    y = jnp.sum(new * c[:, :, None], axis=1) + d * x
    return y, new


def _ssm_scan_lax(x, delta, a, b, c, d, lens):
    """The scan as a ``lax.scan`` over time-major copies: x, delta (B,
    T, Di), a (Di, N), b, c (B, T, N), d (Di,), lens (B,) int32 -> (y
    (B, T, Di) float32, state (B, N, Di))."""
    bsz, t, di = x.shape
    f32 = jnp.float32
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
    state, ys = lax.scan(body, jnp.zeros((bsz, a.shape[1], di), f32), xs,
                         unroll=min(_SCAN_UNROLL, t))
    return jnp.swapaxes(ys, 0, 1), state


def _ssm_scan_kernel(len_ref, x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref,
                     y_ref, st_ref, s_ref, *, block_t, n_t):
    """One (row, d_inner block, block of positions) grid cell, the last
    axis sequential. x_ref, dt_ref, y_ref (1, Tb, Dl): the positions'
    rows where they lie in (B, T, Di); a_ref (N, Dl) is ``A^T``; b_ref,
    c_ref (1, Tb, N); d_ref (1, Dl); st_ref (1, N, Dl) the row's state,
    written at the last cell; s_ref (N, Dl) the state tile, which lives
    in vector memory from the row's first block to its last.

    Eight positions a loop iteration, unrolled: their (8, N) rows of
    B and C are transposed once, so that a position's N numbers lie
    along the sublanes as the state's do, and each position is
    ``_ssm_update`` on the tile. The loop ends with the group of eight
    that holds the row's last live position of the block. A dead
    position of that group gets delta, x, B and C of zero, and so
    leaves the state as it was to the last bit (``exp(0 * A) = 1``, for
    a finite A, and ``1 * s + 0 * 0 = s``): four selects on eight rows
    where a select on the state tile would be paid by every position,
    and ONE body, so that Mosaic compiles half the code a call. The y
    of a block's dead groups is zeros, that of a dead position beside a
    live one ``0`` too."""
    length = len_ref[pl.program_id(0)]
    ti = pl.program_id(2)
    n_live = jnp.clip(length - ti * block_t, 0, block_t)

    @pl.when(ti == 0)
    def _():
        s_ref[...] = jnp.zeros(s_ref.shape, jnp.float32)

    @pl.when(n_live < block_t)
    def _():
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    def group(g, state):
        base = pl.multiple_of(g * 8, 8)
        live = base + lax.broadcasted_iota(jnp.int32, (8, 1), 0) < n_live
        a_t = a_ref[...]
        dd = d_ref[...]
        x8 = jnp.where(live, x_ref[0, pl.ds(base, 8), :], 0.0)
        dt8 = jnp.where(live, dt_ref[0, pl.ds(base, 8), :], 0.0)
        b8 = jnp.where(live, b_ref[0, pl.ds(base, 8), :], 0.0).T
        c8 = jnp.where(live, c_ref[0, pl.ds(base, 8), :], 0.0).T
        ys = []
        for k in range(8):
            x, dt = x8[k:k + 1], dt8[k:k + 1]
            decay = jnp.exp(dt * a_t)
            state = decay * state + (dt * x) * b8[:, k:k + 1]
            ys.append(jnp.sum(state * c8[:, k:k + 1], axis=0, keepdims=True)
                      + dd * x)
        y_ref[0, pl.ds(base, 8), :] = jnp.concatenate(ys, axis=0)
        return state

    @pl.when(n_live > 0)
    def _():
        s_ref[...] = lax.fori_loop(0, (n_live + 7) // 8, group, s_ref[...])

    @pl.when(ti == n_t - 1)
    def _():
        st_ref[0] = s_ref[...]


def _kernel_blocks(t, di, n, block_t=_KERNEL_BLOCK_T,
                   block_d=_KERNEL_BLOCK_D):
    """(positions, lanes) a block of the kernel for a (B, t, di) scan
    over a state of ``n``, or None where the lax form runs: a sequence
    that is not whole blocks of positions (a bucket under one block
    among them), a ``d_inner`` that does not fill whole 128-lane
    vectors, a ``d_state`` that does not fill whole 8-row sublane
    tiles."""
    if t < block_t or t % block_t or di % 128 or n % 8:
        return None
    return block_t, _A._fit_block(di, block_d)


def _use_kernel(t, di, n) -> bool:
    """A step bound for a TPU and a shape the kernel takes
    (``_kernel_blocks``); PADDLE_TPU_NO_PALLAS opts out, as it does for
    every kernel (``kv_cache._use_pallas_decode``)."""
    return (_kernel_blocks(t, di, n) is not None
            and _KV._use_pallas_decode(t, di))


def pallas_ssm_scan(x, delta, a, b, c, d, lens, block_t=_KERNEL_BLOCK_T,
                    block_d=_KERNEL_BLOCK_D, interpret=False):
    """``_ssm_scan_lax``'s contract through the kernel: ONE call, the
    operands where they lie. ``lens`` is a scalar-prefetch operand: a
    block of positions wholly past a row's length is neither fetched
    (its index waits at the row's last live block) nor computed."""
    bsz, t, di = x.shape
    n = a.shape[1]
    blocks = _kernel_blocks(t, di, n, block_t, block_d)
    if blocks is None:
        raise ValueError(
            "no kernel for a (%d, %d, %d) scan over a state of %d in "
            "blocks of %d positions; the lax form runs it"
            % (bsz, t, di, n, block_t))
    block_t, block_d = blocks
    n_t = t // block_t
    f32 = jnp.float32

    def last(bi, lens_ref):
        return jnp.maximum(lens_ref[bi] + block_t - 1, block_t) // block_t - 1

    def xd_block(bi, dj, ti, lens_ref):
        # past the row's last live block: the same block again
        return bi, jnp.minimum(ti, last(bi, lens_ref)), dj

    def bc_block(bi, dj, ti, lens_ref):
        return bi, jnp.minimum(ti, last(bi, lens_ref)), 0

    def lanes(bi, dj, ti, lens_ref):
        return 0, dj

    kernel = functools.partial(_ssm_scan_kernel, block_t=block_t, n_t=n_t)
    y, state = _A.named_pallas_call(
        SSM_SCAN, kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz, di // block_d, n_t),
            in_specs=[
                pl.BlockSpec((1, block_t, block_d), xd_block),
                pl.BlockSpec((1, block_t, block_d), xd_block),
                pl.BlockSpec((n, block_d), lanes),
                pl.BlockSpec((1, block_t, n), bc_block),
                pl.BlockSpec((1, block_t, n), bc_block),
                pl.BlockSpec((1, block_d), lanes),
            ],
            out_specs=[
                pl.BlockSpec((1, block_t, block_d),
                             lambda bi, dj, ti, lens_ref: (bi, ti, dj)),
                pl.BlockSpec((1, n, block_d),
                             lambda bi, dj, ti, lens_ref: (bi, 0, dj)),
            ],
            scratch_shapes=[pltpu.VMEM((n, block_d), f32)]),
        out_shape=[jax.ShapeDtypeStruct((bsz, t, di), f32),
                   jax.ShapeDtypeStruct((bsz, n, di), f32)],
        interpret=interpret,
        **_A._tpu_params("parallel", "parallel", "arbitrary"),
    )(jnp.clip(lens, 0, t), x.astype(f32), delta.astype(f32),
      a.astype(f32).T, b.astype(f32), c.astype(f32),
      d.astype(f32).reshape(1, di))
    return y, state


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _ssm_scan_kernel_path(x, delta, a, b, c, d, lens, interpret):
    return pallas_ssm_scan(x, delta, a, b, c, d, lens, interpret=interpret)


def _kernel_path_fwd(x, delta, a, b, c, d, lens, interpret):
    out = pallas_ssm_scan(x, delta, a, b, c, d, lens, interpret=interpret)
    return out, (x, delta, a, b, c, d, lens)


def _kernel_path_bwd(interpret, res, cts):
    # no cell trains through a scan: the backward is the lax form's
    *operands, lens = res
    _, vjp = jax.vjp(
        lambda *ops: _ssm_scan_lax(*ops, lens), *operands)
    return (*vjp(cts), None)


_ssm_scan_kernel_path.defvjp(_kernel_path_fwd, _kernel_path_bwd)


def ssm_scan(x, delta, a, b, c, d, lengths=None, interpret=False):
    """Selective scan from a zero state over padded sequences.

    x, delta (B, T, Di); a (Di, N); b, c (B, T, N); d (Di,); lengths
    (B,) real tokens per row (None: all T). Returns (y (B, T, Di),
    state (B, Di, N)): the state after each row's LAST REAL token:
    positions at or past a row's length leave its state untouched
    (their y is finite and meaningless). The kernel where
    ``_use_kernel`` says so (``interpret``: the kernel in interpret
    mode, whatever the device: the tests' way in), else the lax
    form."""
    bsz, t, di = x.shape
    n = a.shape[1]
    lens = (jnp.full((bsz,), t, jnp.int32) if lengths is None
            else lengths.reshape(-1).astype(jnp.int32))
    kernel = interpret or _use_kernel(t, di, n)
    SSM_SCAN_TRACES.inc(path="kernel" if kernel else "lax")
    with jax.named_scope(SSM_SCAN):
        if kernel:
            y, state = _ssm_scan_kernel_path(x, delta, a, b, c, d, lens,
                                             interpret)
        else:
            y, state = _ssm_scan_lax(x, delta, a, b, c, d, lens)
        return y.astype(x.dtype), jnp.swapaxes(state, 1, 2)


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
