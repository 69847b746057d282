"""Ring attention on the 8-device virtual CPU mesh (conftest): global
KV-length masks, attention dropout and KV sub-chunking.

Beside `tests/test_parallel.py` and not in it: run op by op, a ring of
eight shards forward and backward is thousands of small compiles, minutes
a case on the CPU, and `--dist loadfile` gives a whole file to one worker.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.parallel import (
    default_mesh,
    full_attention,
    ring_self_attention,
)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_kv_lengths_matches_full(causal):
    """Global KV-length masking (the reference's padded-batch attention
    semantics) must agree between the ring and the full fallback — the
    lengths tensor is global, each rotation step masks by global key
    position. Includes a zero-length batch row (fully-masked: output 0,
    finite grads — the backward's lse guard)."""
    mesh = default_mesh("sp")
    r = np.random.RandomState(11)
    q, k, v = (jnp.asarray(r.randn(3, 2, 64, 16), jnp.float32) * 0.5
               for _ in range(3))
    lengths = jnp.asarray([40, 64, 0], jnp.int32)

    ref = full_attention(q, k, v, causal=causal, lengths=lengths)
    out = ring_self_attention(q, k, v, mesh, "sp", causal=causal,
                              lengths=lengths)
    assert np.isfinite(np.asarray(ref)).all()
    # fully-masked batch row -> exactly zero, not mean-of-V
    np.testing.assert_array_equal(np.asarray(out[2]), 0.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    def loss_ring(q, k, v):
        return jnp.sum(jnp.sin(ring_self_attention(
            q, k, v, mesh, "sp", causal=causal, lengths=lengths)))

    def loss_full(q, k, v):
        return jnp.sum(jnp.sin(full_attention(
            q, k, v, causal=causal, lengths=lengths)))

    gr = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gr, gf):
        assert np.isfinite(np.asarray(a)).all(), "d%s not finite" % name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5,
                                   err_msg="d%s diverged" % name)


def test_ring_attention_dropout_matches_full():
    """Attention-probability dropout (reference:
    python/paddle/fluid/nets.py scaled_dot_product_attention dropout_rate)
    on the ring path: the mask is a pure function of (seed, b, h, global
    q, global k) — independent of shard count — so ring == full EXACTLY
    for the same seed, values and gradients."""
    mesh = default_mesh("sp")
    r = np.random.RandomState(13)
    q, k, v = (jnp.asarray(r.randn(2, 2, 64, 16), jnp.float32) * 0.5
               for _ in range(3))
    lengths = jnp.asarray([64, 40], jnp.int32)
    seed = jax.random.key_data(jax.random.PRNGKey(21)).astype(jnp.uint32)
    rate = 0.3

    ref = full_attention(q, k, v, causal=True, lengths=lengths,
                         dropout_rate=rate, dropout_seed=seed)
    out = ring_self_attention(q, k, v, mesh, "sp", causal=True,
                              lengths=lengths, dropout_rate=rate,
                              dropout_seed=seed)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # dropout actually dropped something
    ref_nodrop = full_attention(q, k, v, causal=True, lengths=lengths)
    assert float(jnp.abs(ref - ref_nodrop).max()) > 1e-3

    def loss_ring(q, k, v):
        return jnp.sum(jnp.sin(ring_self_attention(
            q, k, v, mesh, "sp", causal=True, lengths=lengths,
            dropout_rate=rate, dropout_seed=seed)))

    def loss_full(q, k, v):
        return jnp.sum(jnp.sin(full_attention(
            q, k, v, causal=True, lengths=lengths, dropout_rate=rate,
            dropout_seed=seed)))

    gr = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gr, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5,
                                   err_msg="d%s diverged" % name)


def test_ring_attention_dropout_mask_statistics():
    """The lowbias32 position-hash must behave like Bernoulli(1-rate):
    empirical drop fraction within 3 sigma on a 64k-element mask."""
    from paddle_tpu.parallel.ring_attention import _dropout_keep_scale

    seed = jax.random.key_data(jax.random.PRNGKey(3)).astype(jnp.uint32)
    rate = 0.25
    ks = np.asarray(_dropout_keep_scale(
        seed, 4, 4, jnp.arange(64), jnp.arange(64), rate))
    dropped = float((ks == 0.0).mean())
    n = ks.size
    sigma = (rate * (1 - rate) / n) ** 0.5
    assert abs(dropped - rate) < 3 * sigma, (dropped, rate)
    # kept entries carry the 1/(1-rate) inverted-dropout scale
    kept = ks[ks != 0.0]
    np.testing.assert_allclose(kept, 1.0 / (1 - rate), rtol=1e-6)


@pytest.mark.parametrize("chunk", [8, 16, 32])
def test_ring_attention_chunked_matches_unchunked(chunk):
    """KV sub-chunking (the transient-memory bound for 100k+ sequences)
    is numerically invisible: same values and grads as the whole-block
    path, with causal + ragged lengths + dropout all on — the masks and
    dropout are keyed on GLOBAL positions, so blocking can't shift them.
    T_local = 32, so chunk=8/16 split each visiting block and chunk=32
    degenerates to whole-block."""
    mesh = default_mesh("sp")  # 8 shards
    r = np.random.RandomState(29)
    T = 256  # T_local = 32
    q, k, v = (jnp.asarray(r.randn(2, 2, T, 16), jnp.float32) * 0.5
               for _ in range(3))
    lengths = jnp.asarray([T, 200], jnp.int32)
    seed = jax.random.key_data(jax.random.PRNGKey(31)).astype(jnp.uint32)

    def run(chunk_):
        def loss(q, k, v):
            o = ring_self_attention(
                q, k, v, mesh, "sp", causal=True, lengths=lengths,
                dropout_rate=0.25, dropout_seed=seed, chunk=chunk_)
            return jnp.sum(jnp.sin(o)), o

        # one compiled program a run, as a training step has it: op by
        # op, the two runs of a case cost 200 s of small compiles
        (lv, o), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return np.asarray(o), [np.asarray(g) for g in grads]

    o_ref, g_ref = run(None)  # T_local=32 < auto threshold: whole-block
    o_c, g_c = run(chunk)
    np.testing.assert_allclose(o_c, o_ref, rtol=2e-6, atol=2e-6)
    for name, a, b in zip("qkv", g_c, g_ref):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6,
                                   err_msg="d%s diverged (chunk=%d)"
                                   % (name, chunk))


def test_ring_attention_chunk_validation():
    from paddle_tpu.parallel.ring_attention import _pick_chunk

    assert _pick_chunk(32, None) == (1, 32)          # small: whole block
    assert _pick_chunk(4096, None) == (2, 2048)      # auto split
    assert _pick_chunk(8192, None) == (4, 2048)
    assert _pick_chunk(96, 32) == (3, 32)            # explicit divisor
    with pytest.raises(ValueError, match="divide"):
        _pick_chunk(100, 32)
    # odd big block with no pow2 divisor >=128: stays whole
    assert _pick_chunk(2049 * 3, None) == (1, 2049 * 3)
