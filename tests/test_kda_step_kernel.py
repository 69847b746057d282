"""The one-token step's Pallas kernel (`paddle_tpu/ops/kda.py`,
`pallas_kda_step`) in interpret mode against `_update`: the decay at the
gate's bound and at none, a write strength of 0 and of 1, a freed slot's
zero state and a prefill's, twenty updates one after the other; its
gate (`_use_step_kernel`) by shape, type and device; the counter's
`path`; the `custom_vjp`'s backward. (The chunked scan's kernel:
`test_kda_kernel.py`; a file of its own so that neither passes the
gate's ceiling of seconds a file.)"""
import functools
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import observability as obs
from paddle_tpu.ops import kda
from paddle_tpu.ops import kv_cache as KV

from test_kda_ops import _inputs


def _step_traces():
    got = {k["path"]: v for k, v in obs.KDA_STEP_TRACES.samples()}
    return got.get("kernel", 0), got.get("lax", 0)


@functools.lru_cache(maxsize=None)
def _after_a_prefill(shape, seed):
    """The state `kda_scan` leaves after the first 64 of
    `_inputs(..., seed)`'s 65 tokens, once a shape: the cases share
    it."""
    bsz, h, dk, dv = shape
    ops = _inputs(bsz, 65, seed=seed, h=h, dk=dk, dv=dv)
    return kda.kda_scan(*(jnp.asarray(a[:, :64]) for a in ops),
                        lower_bound=-5.0)[1]


def _step_operands(shape, seed, g_at=None, beta_at=None, state="scan"):
    """(state, q, k, v, g, beta) as `_update` takes them. ``state``:
    "zero" (a freed slot), "scan" (what a prefill of 64 tokens leaves)
    or "normal"."""
    bsz, h, dk, dv = shape
    q, k, v, g, beta = _inputs(bsz, 65, seed=seed, h=h, dk=dk, dv=dv)
    if state == "scan":
        st = _after_a_prefill(tuple(shape), seed)
    elif state == "zero":
        st = jnp.zeros(shape, jnp.float32)
    else:
        st = jnp.asarray(np.random.default_rng(seed + 1).normal(
            size=shape), jnp.float32)
    q, k = kda._prepare(jnp.asarray(q[:, 64]), jnp.asarray(k[:, 64]), True)
    g, beta = g[:, 64], beta[:, 64]
    if g_at is not None:
        g = np.full_like(g, g_at)
    if beta_at is not None:
        beta = np.full_like(beta, beta_at)
    return (st, q, k, jnp.asarray(v[:, 64]), jnp.asarray(g),
            jnp.asarray(beta))


def _assert_relative(got, want, tol=1e-6):
    """The largest difference within ``tol`` of the largest number."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape and np.isfinite(got).all()
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1e-30)


_STEP_SHAPES = [
    # id, (B, H, dk, dv)
    ("few-heads", (3, 4, 128, 128)),
    ("the-cells-heads", (4, 32, 128, 128)),   # the cell's (64, 32, 128, 128)
    ("wider-values", (2, 8, 128, 256)),
]
_STEP_CASES = [
    # id, g everywhere (None: the gate's mix), beta everywhere, state
    ("mixed-after-a-prefill", None, None, "scan"),
    ("decay-at-the-bound", -5.0, None, "scan"),
    ("no-decay", 0.0, None, "scan"),
    ("writes-nothing", None, 0.0, "scan"),
    ("replaces-what-k-holds", None, 1.0, "scan"),
    ("a-freed-slot", None, None, "zero"),
]


@pytest.mark.parametrize("case", [c[1:] for c in _STEP_CASES],
                         ids=[c[0] for c in _STEP_CASES])
@pytest.mark.parametrize("shape", [c[1] for c in _STEP_SHAPES],
                         ids=[c[0] for c in _STEP_SHAPES])
def test_step_kernel_equals_update(shape, case):
    """The step's Pallas kernel (interpret mode) against `_update`: the
    same float32 multiplies and adds, the sums over `dk` in another
    order at most: o and the new state within 1e-6 of the largest
    number."""
    g_at, beta_at, state = case
    ops = _step_operands(shape, seed=sum(shape), g_at=g_at, beta_at=beta_at,
                         state=state)
    want_o, want_s = kda._update(*ops)
    got_o, got_s = jax.jit(lambda *a: kda.pallas_kda_step(
        *a, interpret=True))(*ops)
    _assert_relative(got_o, want_o)
    _assert_relative(got_s, want_s)
    if beta_at == 0.0:      # nothing written: the state decays and no more
        np.testing.assert_array_equal(
            got_s, ops[0] * jnp.exp(ops[4])[..., None])


@pytest.mark.parametrize("heads", [8, 16, 32])
def test_step_kernel_by_heads_a_grid_cell(heads):
    """8, 16 or all 32 heads a grid cell: the same numbers."""
    ops = _step_operands((2, 32, 128, 128), seed=heads, state="normal")
    want_o, want_s = kda._update(*ops)
    got_o, got_s = kda.pallas_kda_step(*ops, heads=heads, interpret=True)
    _assert_relative(got_o, want_o)
    _assert_relative(got_s, want_s)


def test_twenty_chained_steps_do_not_drift():
    """Twenty updates of one state, the kernel's against `_update`'s:
    the difference after the twentieth is no more than after the first
    would allow (the decay forgets an error as it forgets the state)."""
    shape = (2, 8, 128, 128)
    q, k, v, g, beta = (jnp.asarray(a) for a in _inputs(
        2, 20, seed=11, h=8, dk=128, dv=128))
    q, k = kda._prepare(q, k, True)

    def run(update):
        def body(s, x):
            o, s = update(s, *x)
            return s, o
        return jax.jit(lambda s: jax.lax.scan(body, s, tuple(
            jnp.swapaxes(a, 0, 1) for a in (q, k, v, g, beta))))

    start = _step_operands(shape, seed=12, state="normal")[0]
    want_s, want_o = run(kda._update)(start)
    got_s, got_o = run(lambda *a: kda.pallas_kda_step(
        *a, interpret=True))(start)
    _assert_relative(got_o, want_o, 2e-6)
    _assert_relative(got_s, want_s, 2e-6)


_STEP_GATE_CASES = [
    # id, (H, dk, dv, dtype), whether the kernel takes it on a TPU
    ("the-cells", (32, 128, 128, jnp.float32), True),
    ("few-heads", (4, 128, 128, jnp.float32), True),
    ("wider-values", (8, 128, 256, jnp.float32), True),
    ("dk-of-64", (32, 64, 128, jnp.float32), False),
    ("dv-of-no-lane-tiles", (32, 128, 96, jnp.float32), False),
    ("a-bfloat16-state", (32, 128, 128, jnp.bfloat16), False),
    ("heads-no-block-divides", (36, 128, 128, jnp.float32), False),
]


@pytest.mark.parametrize("shape,takes", [c[1:] for c in _STEP_GATE_CASES],
                         ids=[c[0] for c in _STEP_GATE_CASES])
def test_step_gate_answers_from_shape_type_and_device(shape, takes,
                                                      monkeypatch):
    """`_use_step_kernel` answers from the state it is handed (float32,
    whole 128 x 128 tiles, a block of heads that fits) and the device a
    step is bound for: never the CPU, never under
    PADDLE_TPU_NO_PALLAS."""
    assert not kda._use_step_kernel(*shape)
    monkeypatch.setattr(KV, "current_device",
                        lambda: types.SimpleNamespace(platform="tpu"))
    assert kda._use_step_kernel(*shape) == takes
    monkeypatch.setenv("PADDLE_TPU_NO_PALLAS", "1")
    assert not kda._use_step_kernel(*shape)


@pytest.mark.parametrize(
    "shape,dtype,tpu",
    [((2, 4, 128, 128), jnp.float32, False),
     ((2, 4, 64, 128), jnp.float32, True),
     ((2, 4, 128, 128), jnp.bfloat16, True)],
    ids=["on-the-cpu", "dk-of-64", "a-bfloat16-state"])
def test_a_refused_step_is_updates_bits(shape, dtype, tpu, monkeypatch):
    """Where the gate says no, `kda_step` returns `_update`'s bits and
    the counter reads `lax`."""
    if tpu:
        monkeypatch.setattr(KV, "current_device",
                            lambda: types.SimpleNamespace(platform="tpu"))
    bsz, h, dk, dv = shape
    q, k, v, g, beta = (jnp.asarray(a) for a in _inputs(
        bsz, 1, seed=2, h=h, dk=dk, dv=dv))
    state = jnp.asarray(np.random.default_rng(3).normal(size=shape), dtype)
    k0, l0 = _step_traces()
    got_o, got_s = kda.kda_step(q, k, v, g, beta, state)
    assert _step_traces() == (k0, l0 + 1)
    qp, kp = kda._prepare(q[:, 0], k[:, 0], True)
    want_o, want_s = kda._update(state.astype(jnp.float32), qp, kp, v[:, 0],
                                 g[:, 0], beta[:, 0])
    assert got_s.dtype == dtype and got_o.shape == v.shape
    np.testing.assert_array_equal(got_o[:, 0], want_o)
    np.testing.assert_array_equal(got_s, want_s.astype(dtype))


def test_step_by_the_kernel_counts_and_differentiates():
    """`kda_step(..., interpret=True)`: the op through the kernel, the
    counter's `kernel`, and a gradient that is `_update`'s."""
    q, k, v, g, beta = (jnp.asarray(a) for a in _inputs(
        2, 1, seed=7, h=4, dk=128, dv=128))
    state = _step_operands((2, 4, 128, 128), seed=8, state="normal")[0]
    k0, l0 = _step_traces()
    got_o, got_s = kda.kda_step(q, k, v, g, beta, state, interpret=True)
    assert _step_traces() == (k0 + 1, l0)
    want_o, want_s = kda.kda_step(q, k, v, g, beta, state)
    _assert_relative(got_o, want_o)
    _assert_relative(got_s, want_s)
    w = np.random.default_rng(9).normal(size=want_o.shape).astype(np.float32)
    ws = np.random.default_rng(10).normal(size=state.shape).astype(np.float32)

    def loss(interpret, *a):
        o, s = kda.kda_step(*a, interpret=interpret)
        return jnp.sum(o * w) + jnp.sum(s * ws)

    grad = jax.jit(jax.grad(loss, argnums=tuple(range(1, 7))),
                   static_argnums=0)
    for got, want in zip(grad(True, q, k, v, g, beta, state),
                         grad(False, q, k, v, g, beta, state)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
