"""KV-cache op battery (ops/kv_cache.py): decode_attention numerics vs
the full-attention kernels, Pallas-interpret parity, cache append/gather
semantics, and the infer-rule cross-checks."""
import math

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.ops import decode_stream as DS
from paddle_tpu.ops import kv_cache as kc
from tests.op_test import check_infer, run_op

B, S, H, D = 3, 32, 2, 8


def _rand(shape, seed=0):
    return np.random.RandomState(seed).randn(*shape).astype(np.float32)


def _ref_decode(q, k, v, lens, scale=None):
    """Plain numpy single-query attention over the first lens[b] rows."""
    scale = scale or 1.0 / math.sqrt(q.shape[-1])
    out = np.zeros_like(q)
    for b in range(q.shape[0]):
        for h in range(q.shape[2]):
            if lens[b] == 0:
                continue
            s = (q[b, 0, h] @ k[b, :lens[b], h].T) * scale
            p = np.exp(s - s.max())
            p /= p.sum()
            out[b, 0, h] = p @ v[b, :lens[b], h]
    return out


@pytest.fixture
def qkv():
    return (_rand((B, 1, H, D), 0), _rand((B, S, H, D), 1),
            _rand((B, S, H, D), 2))


def test_decode_attention_matches_numpy(qkv):
    q, k, v = qkv
    lens = np.array([5, S, 1], np.int32)
    out = np.asarray(run_op("decode_attention",
                            {"Q": q, "KCache": k, "VCache": v,
                             "Lengths": lens})["Out"])
    np.testing.assert_allclose(out, _ref_decode(q, k, v, lens),
                               rtol=1e-5, atol=1e-5)


def test_decode_attention_zero_length_row_is_finite(qkv):
    """Length-0 slots (free continuous-batching slots) must produce
    zeros, not NaN/garbage — the server steps every slot of the slab."""
    q, k, v = qkv
    lens = np.array([0, 4, 0], np.int32)
    out = np.asarray(run_op("decode_attention",
                            {"Q": q, "KCache": k, "VCache": v,
                             "Lengths": lens})["Out"])
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out[0], 0.0, atol=1e-7)
    np.testing.assert_allclose(out[2], 0.0, atol=1e-7)


def test_decode_attention_matches_causal_prefix_of_flash_attention(qkv):
    """The incremental contract itself: attending a cache of the first
    t tokens must equal row t-1 of full causal flash attention."""
    from paddle_tpu.ops.attention import flash_attention

    _, k, v = qkv
    q_full = _rand((B, S, H, D), 3)
    # full causal attention, BHTD layout
    full = np.asarray(flash_attention(
        jnp.asarray(q_full.transpose(0, 2, 1, 3)),
        jnp.asarray(k.transpose(0, 2, 1, 3)),
        jnp.asarray(v.transpose(0, 2, 1, 3)), causal=True))
    for t in (1, 7, S):
        lens = np.full((B,), t, np.int32)
        out = np.asarray(run_op(
            "decode_attention",
            {"Q": q_full[:, t - 1:t], "KCache": k, "VCache": v,
             "Lengths": lens})["Out"])
        np.testing.assert_allclose(out[:, 0], full[:, :, t - 1],
                                   rtol=1e-4, atol=1e-5)


def test_pallas_decode_kernel_interpret_parity(qkv):
    """The TPU kernel, run under interpret=True, must match the lax
    fallback bit-for-tolerance — the off-hardware guard for the
    on-hardware path."""
    q, k, v = qkv
    lens = np.array([5, S, 1], np.int32)
    got = np.asarray(kc.pallas_decode_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(lens), interpret=True, block_s=8))
    np.testing.assert_allclose(got, _ref_decode(q, k, v, lens),
                               rtol=1e-5, atol=1e-5)


def test_pallas_decode_kernel_partial_block(qkv):
    """Lengths that end mid-KV-block exercise the kernel's masked tail."""
    q, k, v = qkv
    lens = np.array([3, 13, 27], np.int32)
    got = np.asarray(kc.pallas_decode_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(lens), interpret=True, block_s=8))
    np.testing.assert_allclose(got, _ref_decode(q, k, v, lens),
                               rtol=1e-5, atol=1e-5)


# the in-place kernel: slabs with whole sublane tiles of heads (8 of f32)
_IP_S, _IP_D, _IP_BLOCK = 128, 128, 32
_IP_LENGTHS = {
    # 0, 1, one less than / exactly / one more than a block boundary, full
    "edges": [0, 1, _IP_BLOCK - 1, _IP_BLOCK, _IP_BLOCK + 1, _IP_S],
    "second-boundary": [2 * _IP_BLOCK - 1, 2 * _IP_BLOCK, 2 * _IP_BLOCK + 1,
                        _IP_S - 1, 7, 0],
    "full": [_IP_S] * 6,
    "empty": [0] * 6,
}


@pytest.mark.parametrize("lengths", list(_IP_LENGTHS.values()),
                         ids=list(_IP_LENGTHS))
@pytest.mark.parametrize("heads,kv_heads", [(8, 8), (32, 32)],
                         ids=["8", "32"])
def test_pallas_decode_kernel_in_place_parity(heads, kv_heads, lengths):
    """The kernel that reads the (B, S, H, D) slab where it lies (grid
    over sequence blocks, every head of a block in one copy, dead blocks
    skipped), interpret mode against the lax reference. A slab of fewer
    heads than the query runs the streamed two-pass body: its cases
    (48, 64 and 16 heads on 8, and a ring) are
    `test_decode_stream.py::test_view_kernel_matches_its_lax_path`'s."""
    b = len(lengths)
    assert DS.block_positions(kc.decode_view(
        _IP_S, heads, kv_heads, _IP_D, np.float32, _IP_BLOCK)) == _IP_BLOCK
    q = jnp.asarray(_rand((b, 1, heads, _IP_D), 0))
    k = jnp.asarray(_rand((b, _IP_S, kv_heads, _IP_D), 1))
    v = jnp.asarray(_rand((b, _IP_S, kv_heads, _IP_D), 2))
    lens = jnp.asarray(lengths, jnp.int32)
    got = np.asarray(kc.pallas_decode_attention(
        q, k, v, lens, interpret=True, block_s=_IP_BLOCK))
    want = np.asarray(kc.decode_attention_reference(q, k, v, lens))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-6)
    for i, n in enumerate(lengths):
        if n == 0:
            np.testing.assert_array_equal(got[i], 0.0)


@pytest.mark.parametrize("shape,dtype,block_s,want", [
    # the serving cell's slab: 2 MiB blocks
    ((2048, 32, 128), "float32", 512, 128),
    ((1024, 8, 128), "float32", 512, 512),
    ((1024, 8, 128), "float32", 64, 64),
    ((2048, 24, 128), "float32", 512, 128),
    ((96, 8, 128), "float32", 512, 32),
    # heads that do not fill a sublane tile, packed rows: per-head kernel
    ((32, 2, 8), "float32", 8, None),
    ((1024, 12, 128), "float32", 512, None),
    ((2048, 32, 128), "bfloat16", 512, None),
    ((2048, 32, 128), "int8", 512, None),
], ids=["cell", "h8", "h8-small-block", "h24", "s96", "h2", "h12", "bf16",
        "int8"])
def test_decode_block_rows_follows_shape_and_dtype(shape, dtype, block_s,
                                                   want):
    s, h, d = shape
    assert DS.block_positions(
        kc.decode_view(s, h, h, d, dtype, block_s)) == want


# what the dispatch sees decides: (query heads, slab (s, hkv, d), dtype)
# -> the kernel, or the exact lax path
_DISPATCH = {
    "opt-32on32": (32, (2048, 32, 128), "float32", True),
    "laguna-48on8": (48, (4096, 8, 128), "float32", True),
    "laguna-64on8": (64, (4096, 8, 128), "float32", True),
    "32on16": (32, (1024, 16, 128), "float32", True),
    # ONE key/value head (the hybrid cell's slab): no free view
    "jamba-20on1": (20, (2048, 1, 128), "float32", False),
    "12on4": (12, (1024, 4, 128), "float32", False),
    # packed rows: Mosaic has no strided load of them
    "48on8-bf16": (48, (4096, 8, 128), "bfloat16", False),
    # a slab the kernels' blocks do not divide
    "48on8-s100": (48, (100, 8, 128), "float32", False),
    "48on8-d64": (48, (4096, 8, 64), "float32", False),
    # a slot's scores (heads x S float32) would not fit beside the blocks
    "64on8-s32768": (64, (32768, 8, 128), "float32", False),
}


@pytest.mark.parametrize("heads,slab,dtype,kernel",
                         list(_DISPATCH.values()), ids=list(_DISPATCH))
def test_decode_attention_dispatch_follows_shape_and_dtype(
        monkeypatch, heads, slab, dtype, kernel):
    """On a TPU the slab's shape and dtype alone choose between the
    in-place kernel and the lax path: no knob, no model name. A ring
    stays on the lax path whatever its shape."""
    import types

    import jax

    monkeypatch.setattr(kc, "current_device",
                        lambda: types.SimpleNamespace(platform="tpu"))
    s, hkv, d = slab
    sds = jax.ShapeDtypeStruct
    avals = (sds((4, 1, heads, d), dtype), sds((4, s, hkv, d), dtype),
             sds((4, s, hkv, d), dtype), sds((4,), "int32"))
    text = str(jax.make_jaxpr(kc.decode_attention)(*avals))
    assert ("pallas_call" in text) == kernel
    if kernel:
        # the grouped call is handed the slab itself, the standing one
        # its (B, S*H, D) view
        assert ("f32[4,%d,%d]" % (s * hkv, d) in text) == (heads == hkv)
    rows = kc.decode_stream_rows(kc.decode_view(s, heads, hkv, d, dtype))
    assert (rows is not None) == kernel
    assert "pallas_call" not in str(jax.make_jaxpr(kc.decode_attn_ring)(
        *avals))


def test_grouped_slab_without_the_free_view_is_refused_by_the_kernel():
    q = jnp.zeros((2, 1, 20, 128))
    kv = jnp.zeros((2, 128, 1, 128))
    with pytest.raises(ValueError, match="no in-place kernel"):
        kc.pallas_decode_attention(q, kv, kv, jnp.ones((2,), jnp.int32),
                                   interpret=True)


def test_cache_append():
    cache = _rand((B, S, H, D), 4)
    new = _rand((B, 1, H, D), 5)
    pos = np.array([0, 7, S - 1], np.int32)
    out = np.asarray(run_op("cache_append",
                            {"Cache": cache, "New": new, "Pos": pos})
                     ["Out"])
    for b in range(B):
        np.testing.assert_array_equal(out[b, pos[b]], new[b, 0])
        untouched = [i for i in range(S) if i != pos[b]]
        np.testing.assert_array_equal(out[b, untouched],
                                      cache[b, untouched])


def test_cache_append_squeezed_new():
    """New accepted as (B, ...) without the singleton time axis."""
    cache = _rand((B, S, H, D), 4)
    new = _rand((B, H, D), 5)
    pos = np.array([2, 2, 2], np.int32)
    out = np.asarray(run_op("cache_append",
                            {"Cache": cache, "New": new, "Pos": pos})
                     ["Out"])
    np.testing.assert_array_equal(out[:, 2], new)


def test_cache_append_out_of_range_pos_clips():
    """A full slab clips the append instead of crashing (the serving
    loop also length-caps retirement before this can trigger)."""
    cache = _rand((B, S, H, D), 4)
    new = _rand((B, 1, H, D), 5)
    pos = np.array([S, S + 5, 0], np.int32)
    out = np.asarray(run_op("cache_append",
                            {"Cache": cache, "New": new, "Pos": pos})
                     ["Out"])
    np.testing.assert_array_equal(out[0, S - 1], new[0, 0])


def test_cache_gather():
    cache = _rand((4, S, H, D), 6)
    idx = np.array([3, 3, 0, 1, 2], np.int32)
    out = np.asarray(run_op("cache_gather",
                            {"Cache": cache, "Index": idx})["Out"])
    assert out.shape == (5, S, H, D)
    for i, j in enumerate(idx):
        np.testing.assert_array_equal(out[i], cache[j])


def test_kv_cache_infer_rules():
    q, k, v = (_rand((B, 1, H, D)), _rand((B, S, H, D)),
               _rand((B, S, H, D)))
    lens = np.array([1] * B, np.int32)
    check_infer("decode_attention",
                {"Q": q, "KCache": k, "VCache": v, "Lengths": lens})
    check_infer("cache_append",
                {"Cache": k, "New": q, "Pos": lens})
    check_infer("cache_gather",
                {"Cache": k, "Index": np.array([0, 2, 1], np.int32)})


# -- speculative window ops (ops/speculative.py) ---------------------------


def test_window_ops_match_sequential_decode_steps():
    """THE window contract: cache_append_window + decode_attention_window
    over a T-token window produce exactly what T sequential
    cache_append + decode_attention steps produce — the property that
    makes the speculative verify step ONE call."""
    from paddle_tpu.ops import speculative as sp

    T = 4
    k_slab = _rand((B, S, H, D), 7)
    v_slab = _rand((B, S, H, D), 8)
    q_win = _rand((B, T, H, D), 9)
    k_win = _rand((B, T, H, D), 10)
    v_win = _rand((B, T, H, D), 11)
    lens = np.array([5, 0, 12], np.int32)

    # sequential reference: T single-row appends + single-query reads
    ks, vs = jnp.asarray(k_slab), jnp.asarray(v_slab)
    seq_out = []
    for i in range(T):
        pos = jnp.asarray(lens + i)
        ks = kc.cache_append(ks, jnp.asarray(k_win[:, i:i + 1]), pos)
        vs = kc.cache_append(vs, jnp.asarray(v_win[:, i:i + 1]), pos)
        seq_out.append(np.asarray(kc.decode_attention_reference(
            jnp.asarray(q_win[:, i:i + 1]), ks, vs,
            jnp.asarray(lens + i + 1))))
    seq_out = np.concatenate(seq_out, axis=1)

    new_k = sp.cache_append_window(jnp.asarray(k_slab),
                                   jnp.asarray(k_win), jnp.asarray(lens))
    new_v = sp.cache_append_window(jnp.asarray(v_slab),
                                   jnp.asarray(v_win), jnp.asarray(lens))
    np.testing.assert_array_equal(np.asarray(new_k), np.asarray(ks))
    np.testing.assert_array_equal(np.asarray(new_v), np.asarray(vs))
    win_out = np.asarray(sp.decode_attention_window(
        jnp.asarray(q_win), new_k, new_v, jnp.asarray(lens)))
    np.testing.assert_allclose(win_out, seq_out, rtol=1e-5, atol=1e-6)


def test_cache_append_window_drops_rows_past_slab_end():
    """Out-of-range window rows are DROPPED, not clipped: a clipped
    write would alias onto row S-1 with unspecified scatter order and
    could corrupt the real row there."""
    cache = _rand((B, S, H, D), 12)
    new = _rand((B, 3, H, D), 13)
    pos = np.array([S - 1, 0, S - 2], np.int32)
    out = np.asarray(run_op("cache_append_window",
                            {"Cache": cache, "New": new, "Pos": pos})
                     ["Out"])
    np.testing.assert_array_equal(out[0, S - 1], new[0, 0])  # in range
    np.testing.assert_array_equal(out[0, :S - 1], cache[0, :S - 1])
    np.testing.assert_array_equal(out[1, 0:3], new[1])
    np.testing.assert_array_equal(out[2, S - 2], new[2, 0])
    np.testing.assert_array_equal(out[2, S - 1], new[2, 1])


def test_spec_accept_counts_longest_matching_prefix():
    from paddle_tpu.ops.speculative import spec_accept

    V, T = 7, 4
    logits = np.full((3, T, V), -1.0, np.float32)
    # row 0: target argmaxes [2, 3, 4, 5]; proposals [2, 3, 9] -> accept 2
    # row 1: proposals all match -> accept 3;  row 2: first differs -> 0
    targets = np.array([[2, 3, 4, 5], [1, 2, 3, 4], [6, 0, 1, 2]])
    for b in range(3):
        for i in range(T):
            logits[b, i, targets[b, i]] = 1.0
    proposed = np.array([[0, 2, 3, 9], [0, 1, 2, 3], [0, 5, 0, 1]],
                        np.int64)
    next_ids, accept = spec_accept(jnp.asarray(proposed),
                                   jnp.asarray(logits))
    np.testing.assert_array_equal(np.asarray(next_ids), targets)
    np.testing.assert_array_equal(np.asarray(accept), [2, 3, 0])
    # the emitted tokens next_ids[:accept+1] are the accepted proposals
    # plus the bonus token at the first disagreement
    assert list(np.asarray(next_ids)[0][:3]) == [2, 3, 4]


def test_speculative_infer_rules():
    T = 3
    q = _rand((B, T, H, D))
    k = _rand((B, S, H, D))
    lens = np.array([1] * B, np.int32)
    check_infer("decode_attention_window",
                {"Q": q, "KCache": k, "VCache": k, "Lengths": lens})
    check_infer("cache_append_window",
                {"Cache": k, "New": q, "Pos": lens})
    check_infer("spec_accept",
                {"Proposed": np.zeros((B, T), np.int64),
                 "Logits": _rand((B, T, 11))},
                outs=("NextIds", "Accept"))


def test_decode_attention_infer_rejects_bad_slab():
    from paddle_tpu.analysis import get_infer_rule
    from paddle_tpu.analysis.infer import (
        InferContext, InferError, VarInfo, _Env, normalize_shape)
    from tests.op_test import build_one_op_program

    q = _rand((B, 1, H, D))
    bad_k = _rand((B, S, H + 1, D))  # head-count mismatch
    v = _rand((B, S, H, D))
    lens = np.array([1] * B, np.int32)
    block, op, trace_env, _i, _o = build_one_op_program(
        "decode_attention",
        {"Q": q, "KCache": bad_k, "VCache": v, "Lengths": lens})
    env = _Env()
    for name, val in trace_env.items():
        arr = np.asarray(val)
        env.set(name, VarInfo(normalize_shape(arr.shape),
                              str(arr.dtype)))
    with pytest.raises(InferError):
        get_infer_rule("decode_attention")(InferContext(op, block, env))
