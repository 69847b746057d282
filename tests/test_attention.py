"""Fused (flash) attention parity vs naive attention — values and grads."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.ops.attention import flash_attention, pallas_flash_fwd


def _naive(q, k, v, causal=False, lengths=None):
    d = q.shape[-1]
    s = jnp.einsum("bhtd,bhsd->bhts", q, k) / np.sqrt(d)
    t, tk = q.shape[2], k.shape[2]
    mask = jnp.ones((t, tk), bool)
    if causal:
        mask = jnp.tril(mask)
    mask = mask[None, None]
    if lengths is not None:
        mask = mask & (jnp.arange(tk)[None, None, None, :]
                       < lengths[:, None, None, None])
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhts,bhsd->bhtd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_naive(causal):
    r = np.random.RandomState(0)
    q, k, v = (jnp.asarray(r.randn(2, 3, 64, 16), jnp.float32)
               for _ in range(3))
    out = flash_attention(q, k, v, causal=causal, block_k=32)
    ref = _naive(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_with_lengths():
    r = np.random.RandomState(1)
    q, k, v = (jnp.asarray(r.randn(3, 2, 40, 8), jnp.float32)
               for _ in range(3))
    lengths = jnp.asarray([40, 17, 3], jnp.int32)
    out = flash_attention(q, k, v, lengths=lengths, block_k=16)
    ref = _naive(q, k, v, lengths=lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_gradients_match_naive():
    r = np.random.RandomState(2)
    q, k, v = (jnp.asarray(r.randn(2, 2, 32, 8), jnp.float32)
               for _ in range(3))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_k=16) ** 2)

    def loss_naive(q, k, v):
        return jnp.sum(_naive(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_pallas_fwd_interpret_matches_naive():
    r = np.random.RandomState(3)
    q, k, v = (jnp.asarray(r.randn(1, 2, 128, 16), jnp.float32)
               for _ in range(3))
    for causal in (False, True):
        out = pallas_flash_fwd(q, k, v, causal=causal, block_q=64,
                               block_k=64, interpret=True)
        ref = _naive(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_fused_attention_layer_in_program():
    r = np.random.RandomState(4)
    qv = r.randn(2, 2, 16, 8).astype(np.float32)
    q = layers.data(name="q", shape=[2, 2, 16, 8], append_batch_size=False)
    out = layers.fused_attention(q, q, q, causal=True)
    loss = layers.reduce_mean(out)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    o, = exe.run(feed={"q": qv}, fetch_list=[out])
    ref = _naive(jnp.asarray(qv), jnp.asarray(qv), jnp.asarray(qv),
                 causal=True)
    np.testing.assert_allclose(o, np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_transformer_lm_fused_matches_unfused():
    """Same params/seed: fused and unfused attention give the same loss."""
    from paddle_tpu import models

    r = np.random.RandomState(5)
    feed = {
        "ids": r.randint(0, 100, (2, 32)).astype(np.int64),
        "labels": r.randint(0, 100, (2, 32)).astype(np.int64),
    }
    losses = {}
    for fused in (True, False):
        main, start = fluid.Program(), fluid.Program()
        main.random_seed = start.random_seed = 11
        scope = fluid.Scope()
        with fluid.scope_guard(scope), fluid.program_guard(main, start):
            with fluid.unique_name.guard():
                ids = layers.data(name="ids", shape=[2, 32], dtype="int64",
                                  append_batch_size=False)
                labels = layers.data(name="labels", shape=[2, 32],
                                     dtype="int64", append_batch_size=False)
                import paddle_tpu.models.transformer as tfm
                x = tfm._embed(ids, 100, 32, 32, "lm")
                for i in range(2):
                    h = tfm._pre_norm(x)
                    attn = tfm.multi_head_attention(
                        h, h, 4, 32, causal=True, name="l%d" % i,
                        use_fused=fused)
                    x = layers.elementwise_add(x, attn)
                x = tfm._pre_norm(x)
                logits = layers.fc(x, 100, num_flatten_dims=2)
                loss = layers.mean(layers.softmax_with_cross_entropy(
                    layers.reshape(logits, shape=[64, 100]),
                    layers.reshape(labels, shape=[64, 1])))
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(start)
            losses[fused], = exe.run(main, feed=feed, fetch_list=[loss])
    np.testing.assert_allclose(losses[True], losses[False], rtol=1e-4)


def test_fused_attention_dropout_off_in_test_clone():
    """clone(for_test=True) must disable fused-attention dropout."""
    r = np.random.RandomState(6)
    qv = r.randn(1, 2, 16, 8).astype(np.float32)
    q = layers.data(name="q", shape=[1, 2, 16, 8], append_batch_size=False)
    out = layers.fused_attention(q, q, q, causal=True, dropout_rate=0.5)
    test_prog = fluid.default_main_program().clone(for_test=True)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    t1, = exe.run(test_prog, feed={"q": qv}, fetch_list=[out.name])
    t2, = exe.run(test_prog, feed={"q": qv}, fetch_list=[out.name])
    np.testing.assert_array_equal(t1, t2)
    # train program: dropout active -> differs across steps
    a1, = exe.run(feed={"q": qv}, fetch_list=[out])
    a2, = exe.run(feed={"q": qv}, fetch_list=[out])
    assert not np.array_equal(a1, a2)


def test_pallas_bwd_interpret_matches_naive():
    """Pallas dq/dk/dv kernels (custom_vjp backward) vs naive attention
    gradients, causal and not, with block_q != block_k."""
    from paddle_tpu.ops.attention import pallas_flash_attention

    r = np.random.RandomState(7)
    q, k, v = (jnp.asarray(r.randn(1, 2, 256, 16), jnp.float32)
               for _ in range(3))
    for causal in (False, True):
        def loss_p(q, k, v):
            out = pallas_flash_attention(q, k, v, causal=causal,
                                         block_q=128, block_k=64,
                                         interpret=True)
            return jnp.sum(jnp.sin(out))

        def loss_n(q, k, v):
            return jnp.sum(jnp.sin(_naive(q, k, v, causal=causal)))

        gp = jax.grad(loss_p, argnums=(0, 1, 2))(q, k, v)
        gn = jax.grad(loss_n, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gp, gn):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)


def test_pallas_bthd_interpret_matches_naive():
    """BTHD (transpose-free) pallas kernels vs naive attention — values
    AND grads, causal and not, d_head=128 (the lane-aligned case the
    layout requires)."""
    from paddle_tpu.ops.attention import pallas_flash_attention_bthd

    r = np.random.RandomState(9)
    # (B, T, H, Dh) with Dh = 128
    q, k, v = (jnp.asarray(r.randn(2, 256, 2, 128), jnp.float32) * 0.1
               for _ in range(3))
    for causal in (False, True):
        out = pallas_flash_attention_bthd(q, k, v, causal=causal,
                                          block_q=128, block_k=128,
                                          interpret=True)
        ref = _naive(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                     jnp.swapaxes(v, 1, 2), causal=causal)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(jnp.swapaxes(ref, 1, 2)),
                                   rtol=2e-4, atol=2e-4)

        def loss_p(q, k, v):
            o = pallas_flash_attention_bthd(q, k, v, causal=causal,
                                            block_q=128, block_k=128,
                                            interpret=True)
            return jnp.sum(jnp.sin(o))

        def loss_n(q, k, v):
            o = _naive(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                       jnp.swapaxes(v, 1, 2), causal=causal)
            return jnp.sum(jnp.sin(jnp.swapaxes(o, 1, 2)))

        gp = jax.grad(loss_p, argnums=(0, 1, 2))(q, k, v)
        gn = jax.grad(loss_n, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gp, gn):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)


def test_pallas_bthd_rejects_unaligned_head_dim():
    from paddle_tpu.ops.attention import pallas_flash_attention_bthd

    r = np.random.RandomState(0)
    q = jnp.asarray(r.randn(1, 256, 4, 64), jnp.float32)
    with pytest.raises(ValueError, match="128"):
        pallas_flash_attention_bthd(q, q, q, interpret=True)


def test_fused_attention_bthd_layout_op_parity():
    """layout="bthd" through the op (CPU: exercises the internal
    transpose fallback) must equal layout="bhtd" on the same tensors."""
    r = np.random.RandomState(3)
    qh = r.randn(2, 4, 64, 16).astype(np.float32)  # (B, H, T, Dh)
    kh = r.randn(2, 4, 64, 16).astype(np.float32)
    vh = r.randn(2, 4, 64, 16).astype(np.float32)

    def run(layout):
        mp, sp = fluid.Program(), fluid.Program()
        scope = fluid.Scope()
        with fluid.scope_guard(scope), fluid.program_guard(mp, sp):
            q = layers.data(name="q", shape=list(qh.shape), dtype="float32",
                            append_batch_size=False)
            k = layers.data(name="k", shape=list(kh.shape), dtype="float32",
                            append_batch_size=False)
            v = layers.data(name="v", shape=list(vh.shape), dtype="float32",
                            append_batch_size=False)
            if layout == "bthd":
                q, k, v = (layers.transpose(x, perm=[0, 2, 1, 3])
                           for x in (q, k, v))
            out = layers.fused_attention(q, k, v, causal=True, layout=layout)
            if layout == "bthd":
                out = layers.transpose(out, perm=[0, 2, 1, 3])
            exe = fluid.Executor(fluid.CPUPlace())
            (res,) = exe.run(mp, feed={"q": qh, "k": kh, "v": vh},
                             fetch_list=[out])
        return res

    np.testing.assert_allclose(run("bhtd"), run("bthd"), rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("causal", [False, True])
def test_fused_bwd_matches_split_bwd_bhtd(causal, monkeypatch):
    """Single-pass fused backward == split dq/dkv backward (BHTD)."""
    from paddle_tpu.ops import attention as A
    from paddle_tpu.ops.attention import pallas_flash_attention

    r = np.random.RandomState(11)
    q, k, v = (jnp.asarray(r.randn(1, 2, 256, 16), jnp.float32) * 0.2
               for _ in range(3))

    def grads():
        def loss(q, k, v):
            o = pallas_flash_attention(q, k, v, causal=causal,
                                       block_q=128, block_k=64,
                                       interpret=True)
            return jnp.sum(jnp.sin(o))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    g_fused = grads()
    # a budget nothing fits: the split pair
    monkeypatch.setattr(A, "_FUSED_BWD_VMEM_BUDGET", 1)
    g_split = grads()
    for a, b in zip(g_fused, g_split):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("causal", [False, True])
def test_fused_bwd_matches_split_bwd_bthd(causal, monkeypatch):
    """Single-pass fused backward == split backward (BTHD layout)."""
    from paddle_tpu.ops import attention as A
    from paddle_tpu.ops.attention import pallas_flash_attention_bthd

    r = np.random.RandomState(12)
    q, k, v = (jnp.asarray(r.randn(2, 256, 2, 128), jnp.float32) * 0.1
               for _ in range(3))

    def grads():
        def loss(q, k, v):
            o = pallas_flash_attention_bthd(q, k, v, causal=causal,
                                            block_q=128, block_k=128,
                                            interpret=True)
            return jnp.sum(jnp.sin(o))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    g_fused = grads()
    # a budget nothing fits: the split pair
    monkeypatch.setattr(A, "_FUSED_BWD_VMEM_BUDGET", 1)
    g_split = grads()
    for a, b in zip(g_fused, g_split):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)


def test_fused_bwd_vmem_gate_boundary():
    """The fused single-pass backward keeps whole-row k/v + f32 dk/dv
    accumulators in scoped VMEM, so it must not be dispatched when that
    footprint exceeds the budget: measured on v5e, T=4096/d=128/bf16
    compiles (8 MB) and T=8192 OOMs ('Scoped allocation with size
    24.75M and limit 16.00M'). The gate's boundary pins exactly that."""
    from paddle_tpu.ops.attention import _fused_bwd_fits

    assert _fused_bwd_fits(4096, 128, 2)       # bf16, the measured pass
    assert not _fused_bwd_fits(8192, 128, 2)   # bf16, the measured OOM
    assert not _fused_bwd_fits(4096, 128, 4)   # f32 rows: 12 MB+4 MB acc


@pytest.mark.parametrize("layout", ["bthd", "bhtd"])
@pytest.mark.parametrize("t,d,dtype,fused", [
    (2048, 128, "bfloat16", True),    # both training cells' shape: 4 MB
    (4096, 128, "bfloat16", True),    # the measured pass: 8 MB
    (4096, 128, "float32", False),    # AT the 12 MB budget: split
    (8192, 128, "bfloat16", False),   # the measured failure: 16 MB
])
def test_backward_chosen_by_fit(layout, t, d, dtype, fused, monkeypatch):
    """What `fused_attention` dispatches to for a TPU, with no option
    set: the backward's kernels are chosen by `_fused_bwd_fits` alone,
    the fused one where its residents fit, the split pair where they do
    not, and nothing raises. Traced on shapes (no compile, nothing
    runs); the kernels by the names a device trace shows."""
    from paddle_tpu.ops import attention as A

    for name in [n for n in os.environ if n.startswith("PADDLE_TPU_FLASH_")]:
        monkeypatch.delenv(name)
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    shape = (1, t, 2, d) if layout == "bthd" else (1, 2, t, d)
    x = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
    attend = A._attention_bthd if layout == "bthd" else A._attention_bhtd

    def grads(q, k, v):
        out, vjp = jax.vjp(
            lambda q, k, v: attend(q, k, v, None, True, None, 0.0, 512,
                                   None), q, k, v)
        return vjp(out)

    kernels = [str(e.source_info.name_stack)
               for e in jax.make_jaxpr(grads)(x, x, x).jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    bwd = [A.FLASH_BWD] if fused else [A.FLASH_BWD_DQ, A.FLASH_BWD_DKV]
    assert kernels == ["jvp(%s)" % A.FLASH_FWD] + [
        "transpose(jvp(%s))" % n for n in bwd]


# -- the serving prefills' forward-only entry (`prefill_attention`) ------------

_PREFILL_CASES = [
    # id, T, (H, Hkv), (dq, dv), window, lengths of the two rows, dtype
    ("full-whole-bucket", 512, (4, 4), (128, 128), 0, [512, 512], "float32"),
    ("full-no-lengths", 256, (2, 2), (128, 128), 0, None, "float32"),
    ("window", 512, (4, 4), (128, 128), 200, [512, 300], "float32"),
    ("window-wider-than-bucket", 256, (2, 2), (128, 128), 4096, [256, 9],
     "float32"),
    ("fewer-kv-heads", 512, (6, 2), (128, 128), 0, [384, 512], "float32"),
    ("v-narrower-192-128", 512, (4, 4), (192, 128), 0, [512, 257],
     "float32"),
    ("head-of-64-window-grouped", 512, (4, 2), (64, 64), 130, [500, 128],
     "float32"),
    ("length-0", 512, (4, 2), (128, 128), 0, [0, 130], "float32"),
    ("length-inside-a-block", 512, (4, 4), (128, 128), 0, [129, 383],
     "float32"),
    ("bfloat16-inputs", 512, (4, 2), (128, 128), 0, [512, 200], "bfloat16"),
    ("bfloat16-window-192-128", 512, (4, 4), (192, 128), 150, [300, 512],
     "bfloat16"),
]


@pytest.mark.parametrize("t,heads,widths,window,lens,dtype",
                         [c[1:] for c in _PREFILL_CASES],
                         ids=[c[0] for c in _PREFILL_CASES])
def test_prefill_attention_kernel_gives_the_live_rows_of_the_lax_form(
        monkeypatch, t, heads, widths, window, lens, dtype):
    """The flash forward under `prefill_attention` (interpret mode, blocks
    of 128: bfloat16 operands, the rows' lengths scalar-prefetched, q
    and k padded to whole lane tiles and V to its own) against the exact
    lax form on each row's LIVE positions, at what bfloat16 operands
    give; a padding row's output is whatever the kernel left, finite."""
    from paddle_tpu.ops import attention as A

    (h, hkv), (dq, dv) = heads, widths
    r = np.random.default_rng(t + h + dq + window)
    q, k, v = (jnp.asarray(r.normal(size=s), dtype) for s in
               ((2, t, h, dq), (2, t, hkv, dq), (2, t, hkv, dv)))
    lengths = None if lens is None else jnp.asarray(lens, jnp.int32)
    monkeypatch.setattr(A, "_FLASH_BLOCK", 128)
    got = A.prefill_attention(q, k, v, lengths, window=window,
                              interpret=True)
    assert got.shape == (2, t, h, dv) and got.dtype == q.dtype
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    want = np.asarray(A.prefill_attention_reference(
        *(a.astype(jnp.float32) for a in (q, k, v)), window))
    for b in range(2):
        n = t if lens is None else lens[b]
        if not n:
            continue
        np.testing.assert_allclose(got[b, :n], want[b, :n], rtol=2e-2,
                                   atol=2e-2)
        err = np.linalg.norm(got[b, :n] - want[b, :n]) / np.linalg.norm(
            want[b, :n])
        assert err < 8e-3, err
    if lens is not None:
        # the q-blocks wholly past a row's length are skipped: zeros
        for b, n in enumerate(lens):
            first_dead = -(-max(n, 1) // 128) * 128 if n else 0
            assert not got[b, first_dead:].any()


def _rounded_reference(q, k, v, window, sink=None):
    """The exact lax form on operands rounded as the kernel rounds them
    (bfloat16, q after its scale)."""
    from paddle_tpu.ops import attention as A

    bf = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    scale = 1.0 / np.sqrt(q.shape[-1])
    return np.asarray(A.prefill_attention_reference(
        bf(q * scale) / scale, bf(k), bf(v), window, sink=sink))


@pytest.mark.parametrize("group", [1, 2, 8, 16])
def test_prefill_attention_kernel_reads_k_and_v_at_their_own_heads(
        monkeypatch, group):
    """K and V go into the kernel at their own head count (nothing is
    repeated: the index map `hi // group`), V narrower than q and K,
    the rows' lengths given: against the lax form on the live rows, and
    bit for bit against the same call on K and V repeated by hand."""
    from paddle_tpu.ops import attention as A

    hkv, t, (dq, dv) = (2 if group < 8 else 1), 256, (192, 128)
    r = np.random.default_rng(group)
    q, k, v = (jnp.asarray(r.normal(size=s), jnp.float32) for s in
               ((2, t, group * hkv, dq), (2, t, hkv, dq), (2, t, hkv, dv)))
    lens = [256, 130]
    lengths = jnp.asarray(lens, jnp.int32)
    monkeypatch.setattr(A, "_FLASH_BLOCK", 128)
    got = np.asarray(A.prefill_attention(q, k, v, lengths, interpret=True))
    assert got.shape == (2, t, group * hkv, dv)
    want = _rounded_reference(q, k, v, 0)
    for b, n in enumerate(lens):
        np.testing.assert_allclose(got[b, :n], want[b, :n], rtol=2e-2,
                                   atol=5e-3)
    by_hand = np.asarray(A.prefill_attention(
        q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2),
        lengths, interpret=True))
    np.testing.assert_array_equal(got, by_hand)


_BAND_CASES = [
    # id, T, (H, Hkv), (dq, dv), window, lengths, (block_q, block_k, band)
    ("w128-t1024", 1024, (4, 2), (192, 128), 128, [1024, 700],
     (256, 384, True)),
    ("w128-t256-one-clipped-block-of-two", 256, (2, 2), (128, 128), 128,
     [256, 100], (128, 256, True)),
    ("w512-t1024", 1024, (2, 1), (128, 128), 512, [1024, 513],
     (512, 1024, True)),
    ("w200-t512", 512, (2, 2), (128, 128), 200, [512, 300],
     (256, 512, True)),
    ("w1-t512", 512, (2, 1), (128, 128), 1, [512, 257], (256, 384, True)),
    ("w600-t1024-walks", 1024, (2, 1), (128, 128), 600, [1024, 601],
     (512, 512, False)),
]


@pytest.mark.parametrize("t,heads,widths,window,lens,blocks",
                         [c[1:] for c in _BAND_CASES],
                         ids=[c[0] for c in _BAND_CASES])
def test_prefill_attention_kernel_under_a_window(t, heads, widths, window,
                                                 lens, blocks):
    """The kernel at the blocks the window gives it, nothing patched: a
    window up to 512 is a BAND (a q-block against the one key block it
    sees, in one pass; the first q-blocks' key block clipped by the
    sequence's start), a wider one the walk over key blocks of 512 with
    the first q-block's clipped `lower`; against the lax form on the
    live rows."""
    from paddle_tpu.ops import attention as A

    (h, hkv), (dq, dv) = heads, widths
    assert A.prefill_blocks(window, t) == blocks
    r = np.random.default_rng(t + window)
    q, k, v = (jnp.asarray(r.normal(size=s), jnp.float32) for s in
               ((2, t, h, dq), (2, t, hkv, dq), (2, t, hkv, dv)))
    got = np.asarray(A.prefill_attention(
        q, k, v, jnp.asarray(lens, jnp.int32), window=window,
        interpret=True))
    assert np.isfinite(got).all()
    want = _rounded_reference(q, k, v, window)
    for b, n in enumerate(lens):
        np.testing.assert_allclose(got[b, :n], want[b, :n], rtol=2e-2,
                                   atol=5e-3)
        first_dead = -(-n // blocks[0]) * blocks[0]
        assert not got[b, first_dead:].any()


_BLOCK_RULE = [
    # (window, T) -> (block_q, block_k, band)
    ((0, 16384), (512, 512, False)),
    ((0, 256), (256, 256, False)),
    ((128, 16384), (256, 384, True)),      # MiMo-V2-Flash's sliding layers
    ((128, 512), (256, 384, True)),
    ((128, 256), (128, 256, True)),
    ((128, 128), (128, 128, False)),       # the bucket is inside the window
    ((100, 1024), (256, 384, True)),
    ((130, 384), (128, 384, True)),
    ((200, 512), (256, 512, True)),
    ((512, 4096), (512, 1024, True)),      # Laguna's and Phi's
    ((512, 1024), (512, 1024, True)),
    ((512, 512), (512, 512, False)),
    ((513, 4096), (512, 512, False)),      # wider than a block: the walk
    ((4096, 16384), (512, 512, False)),
]


@pytest.mark.parametrize("shape,blocks", _BLOCK_RULE,
                         ids=["w%d-t%d" % s for s, _ in _BLOCK_RULE])
def test_prefill_blocks_follow_the_window_and_the_bucket(shape, blocks):
    """`prefill_blocks`: a rule of (window, T) and of nothing else."""
    from paddle_tpu.ops import attention as A

    got = A.prefill_blocks(*shape)
    assert got == blocks
    block_q, block_k, band = got
    assert shape[1] % block_q == 0 and block_k <= shape[1]
    if band:
        assert shape[0] <= block_k - block_q <= A._FLASH_BLOCK


def test_prefill_attention_counts_the_forms_it_was_traced_in():
    """`paddle_tpu_prefill_attn_forms_total{sink, value_width, kv,
    block_k}`: one a traced call that is not the plain form; `kv=own`
    where K and V have fewer heads than q, `block_k` the kernel's key
    block (`none` on the lax path)."""
    from paddle_tpu import observability as obs
    from paddle_tpu.ops import attention as A

    def count(**labels):
        return obs.PREFILL_ATTN_FORMS.value(**labels)

    q = jnp.ones((1, 512, 4, 192), jnp.float32)
    k = jnp.ones((1, 512, 2, 192), jnp.float32)
    v = jnp.ones((1, 512, 2, 128), jnp.float32)
    sink = jnp.zeros((4,), jnp.float32)
    band = dict(sink="learned", value_width="own", kv="own", block_k="384")
    walk = dict(sink="none", value_width="query", kv="own", block_k="512")
    lax_ = dict(sink="none", value_width="own", kv="own", block_k="none")
    plain = dict(sink="none", value_width="query", kv="query", block_k="512")
    before = [count(**c) for c in (band, walk, lax_, plain)]
    A.prefill_attention(q, k, v, window=128, sink=sink, interpret=True)
    A.prefill_attention(q, k, k, interpret=True)
    A.prefill_attention(q, k, v)
    A.prefill_attention(q, q, q, interpret=True)   # plain: not counted
    after = [count(**c) for c in (band, walk, lax_, plain)]
    assert [a - b for a, b in zip(after, before)] == [1, 1, 1, 0]


def test_prefill_attention_counts_its_traces_and_names_its_kernel():
    """`paddle_tpu_prefill_attn_traces_total{path, operands, lengths}`:
    one a traced call; the lax form under the name a device trace would
    show, `ptpu.attn_window` where a window was asked for."""
    from paddle_tpu import observability as obs
    from paddle_tpu.ops import attention as A

    def count(**labels):
        return obs.PREFILL_ATTN_TRACES.value(**labels)

    q = jnp.ones((1, 256, 2, 128), jnp.float32)
    lens = jnp.asarray([100], jnp.int32)
    lax_none = dict(path="lax", operands="float32", lengths="none")
    lax_given = dict(path="lax", operands="float32", lengths="given")
    kernel = dict(path="kernel", operands="bfloat16", lengths="given")
    before = [count(**c) for c in (lax_none, lax_given, kernel)]
    text = jax.jit(lambda q: (A.prefill_attention(q, q, q),
                              A.prefill_attention(q, q, q, lens, window=8))
                   ).lower(q).as_text(debug_info=True)
    assert "ptpu.flash_fwd" in text and "ptpu.attn_window" in text
    A.prefill_attention(q, q, q, lens, interpret=True)
    after = [count(**c) for c in (lax_none, lax_given, kernel)]
    assert [a - b for a, b in zip(after, before)] == [1, 1, 1]


def test_prefill_attention_through_the_layers_api():
    """The op in a Program (V narrower than q and k, lengths fed): the
    exact lax form on the CPU, the output at V's width, and its infer
    rule."""
    from paddle_tpu.analysis import infer_program
    from paddle_tpu.ops import attention as A

    r = np.random.default_rng(5)
    feed = {"q": r.normal(size=(2, 12, 4, 24)).astype(np.float32),
            "k": r.normal(size=(2, 12, 2, 24)).astype(np.float32),
            "v": r.normal(size=(2, 12, 2, 16)).astype(np.float32),
            "lens": np.array([12, 5], np.int32)}
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        d = {n: layers.data(name=n, shape=list(a.shape), dtype=str(a.dtype),
                            append_batch_size=False)
             for n, a in feed.items()}
        full = layers.prefill_attention(d["q"], d["k"], d["v"], d["lens"])
        win = layers.prefill_attention(d["q"], d["k"], d["v"], window=3,
                                       scale=0.5)
    assert tuple(full.shape) == tuple(win.shape) == (2, 12, 4, 16)
    result = infer_program(main)
    assert result.report.errors == [], result.report.errors
    assert result.info(win.name).shape == (2, 12, 4, 16)
    got = fluid.Executor(fluid.CPUPlace()).run(main, feed=feed,
                                               fetch_list=[full, win])
    np.testing.assert_allclose(got[0], A.prefill_attention_reference(
        feed["q"], feed["k"], feed["v"]), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got[1], A.prefill_attention_reference(
        feed["q"], feed["k"], feed["v"], 3, 0.5), rtol=1e-5, atol=1e-6)
