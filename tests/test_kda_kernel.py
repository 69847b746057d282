"""The chunked delta rule's Pallas kernel (`paddle_tpu/ops/kda.py`,
`pallas_kda_scan`) in interpret mode against its lax form: lengths
inside a chunk, inside a sub-chunk, at a block's edge, a row far shorter
than the bucket, whole chunks at the gate's bound; the gate
(`_use_kernel`) by shape and device; the GUARDED form (a gate with no
bound) against the guarded lax form; the counter's `path` and `form`;
the `custom_vjp`'s backward."""
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import observability as obs
from paddle_tpu.ops import kda
from paddle_tpu.ops import kv_cache as KV

from test_kda_ops import _inputs

_BT = kda._KERNEL_BLOCK_T
_KH, _KD = 2, 128   # heads of one 128-lane column, as the kernel takes them


def _kernel_inputs(bsz, t, seed=0, decay="mixed"):
    q, k, v, g, beta = _inputs(bsz, t, seed=seed, decay=decay, h=_KH,
                               dk=_KD, dv=_KD)
    if decay == "bound_channels":   # whole chunks at the bound, some channels
        g[..., ::4] = -4.999
    if decay == "deep":             # and channels no float could factor
        g[..., ::7] = -1e4
    return tuple(jnp.asarray(a) for a in (q, k, v, g, beta))


def _traces(form=None):
    """(kernel, lax) traces, of one ``form`` ("factored" | "guarded") or
    of both."""
    got = {"kernel": 0, "lax": 0}
    for k, v in obs.KDA_SCAN_TRACES.samples():
        if form in (None, k["form"]):
            got[k["path"]] += v
    return got["kernel"], got["lax"]


_KERNEL_CASES = [
    # id, bucket, lengths, decay
    ("inside-a-chunk", _BT, [200, _BT], "mixed"),
    ("inside-a-sub-chunk", _BT, [70, 9], "mixed"),
    ("at-a-blocks-edge", 2 * _BT, [_BT, 2 * _BT], "mixed"),
    ("far-shorter-than-the-bucket", 3 * _BT, [3 * _BT, 30, 0], "mixed"),
    ("chunks-at-the-bound", 2 * _BT, [2 * _BT - 1, _BT + 65],
     "bound_channels"),
    ("every-channel-at-the-bound", _BT, [_BT, 129], "bound"),
]


# every case with all products in float32; three of them as the chip runs
_AS_RUN = ("inside-a-chunk", "far-shorter-than-the-bucket",
           "chunks-at-the-bound")


@pytest.mark.parametrize(
    "t,lens,decay,passes",
    [c[1:] + (6,) for c in _KERNEL_CASES]
    + [c[1:] + (None,) for c in _KERNEL_CASES if c[0] in _AS_RUN],
    ids=[c[0] + "-float32" for c in _KERNEL_CASES]
    + [c + "-as-run" for c in _AS_RUN])
def test_kernel_equals_the_lax_form(t, lens, decay, passes):
    """The Pallas kernel (interpret mode) against the lax form: o over
    the live positions and the state at each row's length, o zeros past
    a row's length (the lax form's is finite and meaningless). With
    every product in float32 (``passes`` 6) at the tolerance the chunked
    form holds against token by token; as the chip runs it (three
    bfloat16 passes inside a chunk, one on the state, which the CPU's
    lax form does not round) at a bfloat16's."""
    ops = _kernel_inputs(len(lens), t, seed=t, decay=decay)
    ln = jnp.asarray(lens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        want_o, want_s = jax.jit(
            lambda *a: kda.kda_scan(*a, lower_bound=-5.0))(*ops, ln)
    if passes is None:
        k0, l0 = _traces()
        got_o, got_s = kda.kda_scan(*ops, ln, lower_bound=-5.0,
                                    interpret=True)
        assert _traces() == (k0 + 1, l0)
        tol = dict(rtol=2e-2, atol=2e-2)
    else:
        got_o, got_s = jax.jit(lambda *a: kda.pallas_kda_scan(
            *a, intra=passes, state=passes, interpret=True))(*ops, ln)
        tol = dict(rtol=1e-5, atol=1e-5)
    assert got_o.shape == want_o.shape == (len(lens), t, _KH, _KD)
    assert got_s.shape == want_s.shape == (len(lens), _KH, _KD, _KD)
    np.testing.assert_allclose(got_s, want_s, **tol)
    got_o = np.asarray(got_o)
    assert np.isfinite(got_o).all()
    for bi, n in enumerate(lens):
        np.testing.assert_allclose(got_o[bi, :n], np.asarray(want_o)[bi, :n],
                                   **tol)
        np.testing.assert_array_equal(got_o[bi, n:], 0.0)


_GUARDED_CASES = [
    # id, bucket, lengths, decay: the gate with no bound
    ("softplus-inside-a-chunk", _BT, [200, _BT], "softplus"),
    ("softplus-inside-a-sub-chunk", 2 * _BT, [_BT + 70, 9], "softplus"),
    ("deep-far-shorter", 3 * _BT, [3 * _BT, 30, 0], "deep"),
    ("every-channel-at-minus-5", _BT, [_BT, 129], "bound"),
]


@pytest.mark.parametrize(
    "t,lens,decay,passes",
    [c[1:] + (6,) for c in _GUARDED_CASES]
    + [c[1:] + (None,) for c in _GUARDED_CASES[:2]],
    ids=[c[0] + "-float32" for c in _GUARDED_CASES]
    + [c[0] + "-as-run" for c in _GUARDED_CASES[:2]])
def test_guarded_kernel_equals_the_guarded_lax_form(t, lens, decay, passes):
    """The kernel's GUARDED form (interpret mode) against
    ``_kda_scan_lax(guarded=True)``, the same algebra: a sub-chunk's own
    block by ``e^{G_t - G_i}`` itself, the reference points before a
    sub-chunk's first token. Kimi Linear's gate with beta in (0, 2) and
    a row of decays below e^-40 a token; log-decays of -30 and channels
    at -1e4, which no reference point could factor; lengths inside a
    chunk, inside a sub-chunk, a row far shorter than the bucket and an
    empty one. Nothing is clamped: the numbers are the lax form's, and
    finite. Tolerances as ``test_kernel_equals_the_lax_form``; as run,
    through ``kda_scan(lower_bound=None)``, whose counter says
    ``form=guarded``."""
    ops = _kernel_inputs(len(lens), t, seed=t, decay=decay)
    ln = jnp.asarray(lens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        want_o, want_s = jax.jit(
            lambda *a: kda._kda_scan_lax(*a, True, True))(*ops, ln)
    if passes is None:
        before = _traces("guarded"), _traces("factored")
        got_o, got_s = kda.kda_scan(*ops, ln, lower_bound=None,
                                    interpret=True)
        assert _traces("guarded") == (before[0][0] + 1, before[0][1])
        assert _traces("factored") == before[1]
        tol = dict(rtol=2e-2, atol=2e-2)
    else:
        got_o, got_s = jax.jit(lambda *a: kda.pallas_kda_scan(
            *a, intra=passes, state=passes, interpret=True,
            guarded=True))(*ops, ln)
        tol = dict(rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got_s, want_s, **tol)
    got_o = np.asarray(got_o)
    assert np.isfinite(got_o).all() and np.isfinite(np.asarray(got_s)).all()
    for bi, n in enumerate(lens):
        np.testing.assert_allclose(got_o[bi, :n], np.asarray(want_o)[bi, :n],
                                   **tol)
        np.testing.assert_array_equal(got_o[bi, n:], 0.0)


@pytest.mark.parametrize("bound", [None, -8.0],
                         ids=["a-gate-with-no-bound",
                              "a-bound-too-low-to-factor"])
def test_a_gate_the_factored_form_cannot_take_has_a_kernel(bound):
    """What `_use_kernel` refused before the kernel had a guarded form:
    a gate with no bound, and one whose bound lets 16 tokens leave
    float32. Both now reach the kernel, in its guarded form."""
    assert kda._guarded(bound) and not kda._guarded(-5.0)
    ops = _kernel_inputs(1, _BT, seed=11)
    ln = jnp.asarray([_BT - 3], jnp.int32)
    before = _traces("guarded")
    got_o, got_s = kda.kda_scan(*ops, ln, lower_bound=bound, interpret=True)
    assert _traces("guarded") == (before[0] + 1, before[1])
    with jax.default_matmul_precision("highest"):
        want_o, want_s = kda._kda_scan_lax(*ops, ln, True, True)
    np.testing.assert_allclose(got_s, want_s, rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(got_o[0, :_BT - 3], want_o[0, :_BT - 3],
                               rtol=2e-2, atol=2e-2)


def test_kernel_without_the_norm_and_with_no_lengths():
    """``qk_norm`` off (q times ``dk^-1/2`` only) and ``lengths`` None
    (every position live) reach the kernel as they reach the lax form."""
    ops = _kernel_inputs(2, _BT, seed=5)
    small = (ops[0] * 0.1, ops[1] * 0.1) + ops[2:]
    with jax.default_matmul_precision("highest"):
        want_o, want_s = kda.kda_scan(*small, lower_bound=-5.0,
                                      qk_norm=False)
    lens = jnp.full((2,), _BT, jnp.int32)
    got_o, got_s = kda.pallas_kda_scan(*small, lens, qk_norm=False, intra=6,
                                       state=6, interpret=True)
    np.testing.assert_allclose(got_o, want_o, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got_s, want_s, rtol=1e-5, atol=1e-5)


def test_kernel_path_differentiates_as_the_lax_form():
    """No cell trains through a scan, and the op must not start to
    raise: the kernel path's backward is the lax form's."""
    ops = _kernel_inputs(2, _BT, seed=3)
    ln = jnp.asarray([_BT, 67], jnp.int32)
    w = np.random.default_rng(4).normal(
        size=(2, _BT, _KH, _KD)).astype(np.float32)
    w[1, 67:] = 0.0   # a padded position's o means nothing
    ws = np.random.default_rng(5).normal(
        size=(2, _KH, _KD, _KD)).astype(np.float32)

    def loss(interpret, *a):
        # linear in both outputs: the cotangents do not depend on which
        # form ran forward (the kernel rounds the state's products)
        o, state = kda.kda_scan(*a, ln, lower_bound=-5.0,
                                interpret=interpret)
        return jnp.sum(o * w) + jnp.sum(state * ws)

    grad = jax.jit(jax.grad(loss, argnums=tuple(range(1, 6))),
                   static_argnums=0)
    with jax.default_matmul_precision("highest"):
        want, got = grad(False, *ops), grad(True, *ops)
    for g, wnt in zip(got, want):
        np.testing.assert_allclose(g, wnt, rtol=1e-4, atol=1e-4)


_GATE_CASES = [
    # id, (t, dk, dv), whether the kernel takes it on a TPU
    ("the-cells-16384", (16384, 128, 128), True),
    ("one-block", (_BT, 128, 128), True),
    ("wider-heads", (2 * _BT, 256, 128), True),
    ("under-one-block", (_BT // 2, 128, 128), False),
    ("not-whole-blocks", (_BT + 64, 128, 128), False),
    ("a-head-of-no-lane-tiles", (_BT, 64, 64), False),
    ("a-value-head-of-no-lane-tiles", (_BT, 128, 96), False),
]


@pytest.mark.parametrize("shape,takes", [c[1:] for c in _GATE_CASES],
                         ids=[c[0] for c in _GATE_CASES])
def test_kernel_gate_answers_from_shape_gate_and_device(shape, takes,
                                                        monkeypatch):
    """``_use_kernel`` answers from what the op is handed (whole blocks
    of positions, heads of whole lane tiles; whatever the gate: the
    kernel has the factored form and the guarded one) and the device a
    step is bound for: never the CPU, never under
    PADDLE_TPU_NO_PALLAS."""
    assert not kda._use_kernel(*shape)
    monkeypatch.setattr(KV, "current_device",
                        lambda: types.SimpleNamespace(platform="tpu"))
    assert kda._use_kernel(*shape) == takes
    monkeypatch.setenv("PADDLE_TPU_NO_PALLAS", "1")
    assert not kda._use_kernel(*shape)


def test_a_refused_shape_takes_the_lax_path(monkeypatch):
    """A bucket that is not whole blocks, under either gate, on a
    device the kernel runs on: the lax form, and the counter says so,
    form by form (a program traced onto the composed GUARDED path on a
    TPU is what `tests/test_tpu_compile_serving.py` refuses of the
    Solar-Open2 cell's prefills); the kernel's own entry refuses the
    shape by name."""
    monkeypatch.setattr(KV, "current_device",
                        lambda: types.SimpleNamespace(platform="tpu"))
    ops = _kernel_inputs(1, 64, seed=1)
    ln = jnp.asarray([50], jnp.int32)
    k0, l0 = _traces()
    o, state = kda.kda_scan(*ops, ln, lower_bound=-5.0)
    assert _traces() == (k0, l0 + 1)
    assert o.shape == (1, 64, _KH, _KD) and state.shape == (1, _KH, _KD, _KD)
    guarded = _traces("guarded")
    kda.kda_scan(*ops, ln, lower_bound=None)
    assert _traces() == (k0, l0 + 2)
    assert _traces("guarded") == (guarded[0], guarded[1] + 1)
    with pytest.raises(ValueError, match="the lax form runs it"):
        kda.pallas_kda_scan(*ops, ln, interpret=True)
