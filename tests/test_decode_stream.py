"""The streamed two-pass decode kernel (`ops/decode_stream.py`): ONE
body behind `ptpu.decode_attn_grouped`, `ptpu.diff_attn_rows` and
`ptpu.mla_latent_attn`, so what is a property of that body is tested
here once: its index maps as plain functions, and every view in
interpret mode against that view's own exact lax path (the parity cases
the three op files used to hold, case for case, and each view at the
lengths around a block's edges and past the slab).
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import observability as obs
from paddle_tpu.ops import decode_stream as DS
from paddle_tpu.ops import diff_attn as D
from paddle_tpu.ops import kv_cache as KV
from paddle_tpu.ops import mla


# -- the index maps -------------------------------------------------------------

ROWS, N_BLK = 32, 4   # a slot of 128 positions in blocks of 32
_LENGTHS = [0, 1, ROWS - 1, ROWS, ROWS + 1, 2 * ROWS, N_BLK * ROWS - 1,
            N_BLK * ROWS]


def _live_blocks(length):
    return -(-length // ROWS)


@pytest.mark.parametrize("length", _LENGTHS)
def test_k_block_never_passes_the_slots_last_live_block(length):
    """Pass one reads blocks 0 .. last live, each once, then that block
    again (an index that did not change is not copied again)."""
    lens = np.array([7, length], np.int32)
    got = [int(DS.live_block(j, lens, 1, ROWS)) for j in range(2 * N_BLK)]
    last = max(_live_blocks(length), 1) - 1
    assert got == [min(j, last) for j in range(2 * N_BLK)]
    assert int(DS.last_block(lens, 1, ROWS)) == last
    assert max(got) * ROWS < max(length, 1)  # no block wholly dead is read


@pytest.mark.parametrize("length", _LENGTHS)
def test_v_block_waits_at_block_0_until_the_second_pass(length):
    lens = np.array([length, 99], np.int32)
    got = [int(DS.second_pass_block(j, lens, 0, ROWS, N_BLK))
           for j in range(2 * N_BLK)]
    last = max(_live_blocks(length), 1) - 1
    assert got[:N_BLK] == [0] * N_BLK
    assert got[N_BLK:] == [min(j, last) for j in range(N_BLK)]


def test_an_empty_slot_fetches_block_0_once_a_pass():
    """Length 0: both maps stay at block 0 through all 2 n_blk steps, so
    each operand's one copy is its first; nothing is computed on it."""
    lens = np.array([0], np.int32)
    steps = range(2 * N_BLK)
    assert {int(DS.live_block(j, lens, 0, ROWS)) for j in steps} == {0}
    assert {int(DS.second_pass_block(j, lens, 0, ROWS, N_BLK))
            for j in steps} == {0}


def test_the_rule_is_the_views_numbers_and_the_type():
    """`block_positions`: the largest power of two of positions within
    `most` that divides the slot's, at least `least`; None for a layout
    that fills no whole tiles, a type not 32 bits wide, scores past the
    budget."""
    def view(**kw):
        return DS.StreamView("v", **{"seq": 4096, "dtype": "float32",
                                     "most": 512, **kw})

    assert DS.block_positions(view()) == 512
    assert DS.block_positions(view(most=DS.rows_within(32 * 128 * 4))) == 128
    assert DS.block_positions(view(seq=96)) == 32
    assert DS.block_positions(view(seq=192, least=128)) is None
    assert DS.block_positions(view(seq=100)) is None
    assert DS.block_positions(view(dtype="bfloat16")) is None
    assert DS.block_positions(view(whole_tiles=False)) is None
    assert DS.block_positions(view(score_rows=256)) == 512
    assert DS.block_positions(view(score_rows=257)) is None


# -- every view against its lax path ------------------------------------------------

def _rand(shape, seed=0):
    return np.random.RandomState(seed).randn(*shape).astype(np.float32)


def _grouped(lengths, heads, s=128, block=32, ring=False):
    """`heads` query heads on slabs (or, with `ring`, one-block rings)
    of 8 key/value heads of 128: the kernel against
    `decode_attention_reference` (`decode_attn_ring` for a ring, which
    sees `min(lengths, W)` rows)."""
    b = len(lengths)
    q = jnp.asarray(_rand((b, 1, heads, 128), 3 if ring else 0))
    k = jnp.asarray(_rand((b, s, 8, 128), 4 if ring else 1))
    v = jnp.asarray(_rand((b, s, 8, 128), 5 if ring else 2))
    lens = jnp.asarray(lengths, jnp.int32)
    assert DS.block_positions(KV.decode_view(
        s, heads, 8, 128, np.float32, block)) == min(block, s)
    if ring:
        want = KV.decode_attn_ring(q, k, v, lens)
        got = KV.pallas_decode_attention(q, k, v, jnp.minimum(lens, s),
                                         interpret=True)
    else:
        want = KV.decode_attention_reference(q, k, v, lens)
        got = KV.pallas_decode_attention(q, k, v, lens, interpret=True,
                                         block_s=block)
    return got, want, dict(rtol=1e-5, atol=2e-6)


def _rows(lengths, block, s=256, h=16, pairs=4, w=128):
    """`h` paired query heads of `w` on slabs of `s` flat rows of
    `pairs` pair-heads: the kernel against `_attend_rows_lax`."""
    r = np.random.default_rng(7)
    b = len(lengths)
    qp = D.pair_queries(jnp.asarray(
        r.normal(size=(b, 1, h, w // 2)).astype(np.float32)))
    k = jnp.asarray(r.normal(size=(b, s, pairs * w)).astype(np.float32))
    v = jnp.asarray(r.normal(size=(b, s, pairs * w)).astype(np.float32))
    lens = jnp.asarray(lengths, jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = D._attend_rows_lax(qp, k, v, lens, 0.125)
        got = D.pallas_attend_rows(qp, k, v, lens, 0.125, block_s=block,
                                   interpret=True)
    return got, want, dict(rtol=1e-5, atol=1e-6)


def _latent(lengths, block, s=512, h=8, row=40, rank=32, seed=5):
    """`h` heads on a latent slab of `row`-float rows, `rank` of them
    summed: the kernel against `_latent_attend_lax`. Rows past a slot's
    length hold garbage (1e6) and the `k_r` columns are large: neither
    may reach the output."""
    r = np.random.default_rng(seed)
    lens = np.asarray(lengths, np.int32)
    b = len(lens)
    slab = r.normal(size=(b, s, row)).astype(np.float32)
    slab[..., rank:] *= 10.0
    for i, n in enumerate(lens):
        slab[i, n:] = 1e6
    q_row = jnp.asarray(r.normal(size=(b, h, row)) * 0.05, jnp.float32)
    slab, lens = jnp.asarray(slab), jnp.asarray(lens)
    want = mla._latent_attend_lax(q_row, slab, lens, rank)
    got = mla.pallas_latent_attend(q_row, slab, lens, rank, block_s=block,
                                   interpret=True)
    assert got.shape == (b, h, rank)
    return got, want, None


KS = 512
_UNEQUAL = [int(n) for n in np.random.default_rng(11).integers(0, KS + 1, 32)]


def _mla_decode(lengths, block, monkeypatch):
    """`mla_decode` through the kernel (interpret mode, steered past the
    device rule) against `mla_decode` by the lax path, which the CPU
    takes; the counter names each path."""
    kh, krank, krope, dn, dv = 8, 32, 8, 4, 8
    lens = np.asarray(lengths, np.int32)
    b = len(lens)
    r = np.random.default_rng(5)
    slab = r.normal(size=(b, KS, krank + krope)).astype(np.float32)
    slab[..., krank:] *= 10.0
    for i, n in enumerate(lens):
        slab[i, n:] = 1e6
    q = jnp.asarray(r.normal(size=(b, 1, kh, dn + krope)) * 0.3, jnp.float32)
    w_kvb = jnp.asarray(r.normal(size=(krank, kh * (dn + dv))) * 0.3,
                        jnp.float32)
    slab, lens = jnp.asarray(slab), jnp.asarray(lens)

    def counts():
        return {k["path"]: v for k, v in obs.MLA_TRACES.samples()}

    c0 = counts()
    want = mla.mla_decode(q, slab, lens, w_kvb, 0.37)
    c1 = counts()
    assert c1["absorbed"] - c0.get("absorbed", 0) == 1
    assert c1.get("absorbed_kernel", 0) == c0.get("absorbed_kernel", 0)
    monkeypatch.setattr(KV, "decode_stream_rows", lambda view: block)
    kernel = mla.pallas_latent_attend
    monkeypatch.setattr(
        mla, "pallas_latent_attend",
        lambda *a: kernel(*a, block_s=block, interpret=True))
    got = mla.mla_decode(q, slab, lens, w_kvb, 0.37)
    c2 = counts()
    assert c2["absorbed_kernel"] - c1.get("absorbed_kernel", 0) == 1
    assert c2["absorbed"] == c1["absorbed"]
    assert got.shape == want.shape == (b, 1, kh, dv)
    np.testing.assert_array_equal(np.asarray(want)[np.asarray(lens) == 0],
                                  0.0)
    return got, want, None


_IP_S, _IP_BLOCK, _RING_W = 128, 32, 32
_IP_LENGTHS = {
    # 0, 1, one less than / exactly / one more than a block boundary, full
    "edges": [0, 1, _IP_BLOCK - 1, _IP_BLOCK, _IP_BLOCK + 1, _IP_S],
    "second-boundary": [2 * _IP_BLOCK - 1, 2 * _IP_BLOCK, 2 * _IP_BLOCK + 1,
                        _IP_S - 1, 7, 0],
    "full": [_IP_S] * 6,
    "empty": [0] * 6,
}
_RING_LENGTHS = {
    "below": [0, 1, _RING_W - 1, 5],
    "at": [_RING_W] * 4,
    "past": [_RING_W + 1, 2 * _RING_W, 1000, _RING_W + 7],
    "mixed": [7, _RING_W, _RING_W + 1, 0],
}
_MLA_LENGTHS = {
    # live rows a slot (`b` stands for the block)
    "empty": lambda b: [0, 0],
    "one-row": lambda b: [1, 0],
    "edge-1": lambda b: [b - 1, 1],
    "edge": lambda b: [b, 2 * b],
    "edge+1": lambda b: [b + 1, KS - 1],
    "full-slab": lambda b: [KS, KS],
    "past-the-slab": lambda b: [KS + 5, KS],  # read as every row
    "unequal-32": lambda b: _UNEQUAL,
}


def _edges(block, s):
    return [0, 1, block - 1, block, block + 1, s]


# id -> (the slots' lengths, run(lengths, monkeypatch) -> (got, want, tol))
_CASES = {}
for _h in (48, 64, 16):  # 6, 8 and 2 query rows share a key/value head
    for _n, _l in _IP_LENGTHS.items():
        _CASES["grouped-%don8-%s" % (_h, _n)] = (
            _l, lambda l, mp, h=_h: _grouped(l, h))
for _h in (16, 64):  # a ring is a one-block slab of min(lengths, W) rows
    for _n, _l in _RING_LENGTHS.items():
        _CASES["grouped-ring-%don8-%s" % (_h, _n)] = (
            _l, lambda l, mp, h=_h: _grouped(l, h, s=_RING_W, block=512,
                                             ring=True))
for _b in (64, 128, 256):
    # a free slot, one that ends inside a block, at its edge, one past it
    for _l in ((0, 100, 256), (1, 64, 65)):
        _CASES["rows-block%d-%s" % (_b, "-".join(map(str, _l)))] = (
            list(_l), lambda l, mp, b=_b: _rows(l, b))
for _b in (128, 256):
    for _n, _f in _MLA_LENGTHS.items():
        _CASES["latent-mla_decode-block%d-%s" % (_b, _n)] = (
            _f(_b), lambda l, mp, b=_b: _mla_decode(l, b, mp))
# the Ling cell's row (512 + 64), 32 heads
_CASES["latent-row576-rank512"] = (
    [0, 1, 129, 256],
    lambda l, mp: _latent(l, 128, s=256, h=32, row=576, rank=512, seed=6))
# each view around a block's edges, and at a length past the slab
_CASES["grouped-edges"] = (
    _edges(32, 128), lambda l, mp: _grouped(l, 48))
_CASES["rows-edges"] = (_edges(64, 256), lambda l, mp: _rows(l, 64))
_CASES["latent-edges"] = (_edges(128, 512), lambda l, mp: _latent(l, 128))
_CASES["grouped-past-the-slab"] = (
    [128 + 5, 128, 7], lambda l, mp: _grouped(l, 16))
_CASES["rows-past-the-slab"] = (
    [256 + 5, 256, 7], lambda l, mp: _rows(l, 64))
_CASES["latent-past-the-slab"] = (
    [512 + 5, 512, 7], lambda l, mp: _latent(l, 128))


@pytest.mark.parametrize("lengths,run", list(_CASES.values()),
                         ids=list(_CASES))
def test_view_kernel_matches_its_lax_path(monkeypatch, lengths, run):
    """A view's kernel in interpret mode against that view's exact lax
    path: elementwise where the op's own test asked that, and to 1e-5
    of the output's norm (what two passes give; PERF.md, PR 32); zeros
    and finite at length 0; a length past the slab reads as "every
    row"."""
    got, want, tol = run(lengths, monkeypatch)
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape and np.isfinite(got).all()
    np.testing.assert_array_equal(got[np.asarray(lengths) == 0], 0.0)
    if tol:
        np.testing.assert_allclose(got, want, **tol)
    err = float(np.linalg.norm(got - want))
    assert err <= 1e-5 * max(float(np.linalg.norm(want)), 1e-30), err

